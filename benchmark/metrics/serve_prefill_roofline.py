from benchmark import readers


def read(run):
    return readers.serve_roofline(run, "prefill")
