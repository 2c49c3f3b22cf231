from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase moe_shared: the shared expert's three products."""
    return phases.phase_share(run, "train_step", ("moe_shared",))
