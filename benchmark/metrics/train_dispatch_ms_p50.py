from benchmark import readers


def read(run):
    """Median of the tracker's misc/step_dispatch_ms over the window's steps."""
    return readers.percentile(run["dispatch_ms"], 50)
