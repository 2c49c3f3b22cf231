from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the `state` spans: `make_state()`, the `TrainState` on the device and the optimizer's init."""
    return readers_setup.part(run, "state")
