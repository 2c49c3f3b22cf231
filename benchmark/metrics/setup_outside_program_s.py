from benchmark import readers_setup


def read(run):
    """Seconds of set-up under no program span: import of `jax`, the backend's start, the harness's own imports and work."""
    return readers_setup.outside_program(run)
