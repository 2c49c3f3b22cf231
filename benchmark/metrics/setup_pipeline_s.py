from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the `pipeline` span: `TrainingPipeline._pre_run` through the user's `pre_run()`."""
    return readers_setup.part(run, "pipeline")
