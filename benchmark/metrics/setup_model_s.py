from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the `pre_stage` spans: the user's hook (in the cells the model's abstract init, weights, registrations)."""
    return readers_setup.part(run, "pre_stage")
