from benchmark import readers_laguna


def read(run):
    """The three flash kernels, found by name, against counts_laguna.py: window and full layers, each with its own heads and kept elements."""
    return readers_laguna.flash_attn_roofline(run)
