from benchmark import phases


def read(run):
    """Per cent of the traced train steps' device time in the phase ssm_scan: softplus, running sums, exponentials, chunk products, carry, D x."""
    return phases.phase_share(run, "train_step", ("ssm_scan",))
