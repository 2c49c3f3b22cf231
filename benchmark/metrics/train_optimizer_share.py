from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phases ``grad_clip`` and ``optimizer``."""
    return phases.phase_share(run, "train_step", ("grad_clip", "optimizer"))
