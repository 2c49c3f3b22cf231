from benchmark import phases


def read(run):
    return phases.kernel_ms_per_step(run, "train_step", "flash_bwd_dkv")
