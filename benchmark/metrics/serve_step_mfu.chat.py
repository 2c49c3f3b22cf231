from benchmark import readers


def read(run):
    return readers.serve_step_mfu(run)
