from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase ssm_conv: the depthwise conv, its bias, silu and the split."""
    return phases.phase_share(run, "train_step", ("ssm_conv",))
