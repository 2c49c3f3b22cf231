from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase ssm_proj: the mixer's in- and out-projection."""
    return phases.phase_share(run, "train_step", ("ssm_proj",))
