from benchmark import readers_laguna


def read(run):
    """The flash kernels' calls under the phase attn_window_kernel against the elements a 512 window keeps."""
    return readers_laguna.flash_window_roofline(run)
