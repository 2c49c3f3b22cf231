def read(run):
    """Programs jax had to compile, not load, before the window opened."""
    return run["cache_events_at_open"]["misses"]
