from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase attn_gate: the gate's product, its sigmoid and the multiply."""
    return phases.phase_share(run, "train_step", ("attn_gate",))
