from benchmark import readers_lfm2


def read(run):
    """The step's operations by counts_lfm2.py, live pairs from the program's counter, over window x peak."""
    return readers_lfm2.train_step_mfu(run)
