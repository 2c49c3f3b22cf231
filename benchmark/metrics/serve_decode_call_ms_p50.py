from benchmark import readers


def read(run):
    return readers.serve_call_ms_p50(run, "decode")
