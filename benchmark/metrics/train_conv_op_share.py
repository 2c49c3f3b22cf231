from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase conv_op."""
    return phases.phase_share(run, "train_step", ("conv_op",))
