from benchmark import readers


def read(run):
    """Compile requests and new engine signatures after the window opened: work that belongs in set-up."""
    return readers.compiles_in_window(run)
