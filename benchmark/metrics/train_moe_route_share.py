from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase moe_route: scores, top-k, sort, the two moves of rows."""
    return phases.phase_share(run, "train_step", ("moe_route",))
