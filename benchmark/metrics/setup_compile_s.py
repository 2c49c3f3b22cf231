from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the `compile` spans less their `lower_s`: XLA's compile, or the cache's load when warm."""
    return readers_setup.compile_parts(run, lowering=False)
