from benchmark import phases


def read(run):
    return phases.step_bookkeeping_ms_p50(run)
