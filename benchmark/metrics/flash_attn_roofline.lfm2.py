from benchmark import readers_lfm2


def read(run):
    """The three flash kernels, found by name, against counts_lfm2.py at 32/8 heads of 64, no window."""
    return readers_lfm2.flash_attn_roofline(run)
