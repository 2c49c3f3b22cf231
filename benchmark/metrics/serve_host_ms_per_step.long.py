from benchmark import readers


def read(run):
    return readers.serve_host_ms_per_step(run)
