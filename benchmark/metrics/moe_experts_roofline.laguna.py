from benchmark import readers_laguna


def read(run):
    """The grouped products' roofline time for the live pairs over the device time of the grouped-product kernels, found by name."""
    return readers_laguna.moe_experts_roofline(run)
