from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the `epoch` spans that end before the window opens: the checked steps and the lead-in."""
    return readers_setup.first_epochs(run)
