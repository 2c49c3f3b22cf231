from benchmark import readers


def read(run):
    return readers.percentile(readers.ttfts(run), 95)
