from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the `.lower()` of the `compile` spans (their `lower_s`), summed."""
    return readers_setup.compile_parts(run, lowering=True)
