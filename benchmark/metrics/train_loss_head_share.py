from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in ``loss_head``: the head's product and the loss, forward and backward."""
    return phases.phase_share(run, "train_step", ("loss_head",))
