from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase ssm_gate_norm: y * silu(z) and the norm over the held channels."""
    return phases.phase_share(run, "train_step", ("ssm_gate_norm",))
