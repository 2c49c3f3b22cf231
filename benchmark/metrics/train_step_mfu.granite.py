from benchmark import readers_granite


def read(run):
    """The step's operations by counts_granite.py (the scan in its chunked form at the published chunk, no recomputation) over window x peak."""
    return readers_granite.train_step_mfu(run)
