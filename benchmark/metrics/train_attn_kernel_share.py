from benchmark import phases

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run):
    """Per cent of the traced train steps' device time in the three flash kernels, found by name."""
    table = phases.kernels(run, "train_step")
    if table is None or not table["busy_ns"] or not any(k in table["kernels"] for k in KERNELS):
        return None
    return 100.0 * sum(table["kernels"].get(k, 0) for k in KERNELS) / table["busy_ns"]
