from benchmark import readers


def read(run):
    """90th percentile of (last token - first token) / (tokens - 1)."""
    return readers.percentile(readers.tpots_ms(run), 90)
