from benchmark import readers_lfm2


def read(run):
    """Median over the window's steps of the tracker's moe/load_max_over_mean."""
    return readers_lfm2.held_load_max_over_mean(run)
