def read(run):
    """Process start to the window's opening."""
    return run["setup_s"]
