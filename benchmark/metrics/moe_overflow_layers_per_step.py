from benchmark import readers_laguna


def read(run):
    """Mean over the window's steps of the tracker's moe/overflow_layers."""
    return readers_laguna.overflow_layers_per_step(run)
