from benchmark import readers_granite


def read(run):
    """The three flash kernels, found by name, against counts_granite.py: 16 query and 4 KV heads of 64, no window."""
    return readers_granite.flash_attn_roofline(run)
