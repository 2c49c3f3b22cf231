from benchmark import readers_laguna


def read(run):
    """The step's operations by counts_laguna.py, live pairs from the program's counter, over window x peak."""
    return readers_laguna.train_step_mfu(run)
