from benchmark import readers_setup


def read(run):
    """Seconds of set-up in the program's `import` span: `import dmlcloud_tpu`."""
    return readers_setup.part(run, "import")
