from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in operations the phase map gives no phase."""
    return phases.phase_share(run, "train_step", ("unattributed",))
