from benchmark import phases


def read(run):
    return phases.span_ms_p50(run, "call_fetch")
