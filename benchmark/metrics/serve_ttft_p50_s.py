from benchmark import readers


def read(run):
    """Median over every request due in the window of first token minus due time."""
    return readers.percentile(readers.ttfts(run), 50)
