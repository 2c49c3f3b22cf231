from benchmark import readers


def read(run):
    return readers.train_step_mfu(run)
