from benchmark import readers


def read(run):
    """All tokens of all steps of the window over the window, the last step ended by block_until_ready."""
    return readers.train_tokens_per_s(run)
