from benchmark import readers


def read(run):
    """How late the generator submitted: submit time minus due time."""
    late = [(r["submitted"] - r["due"]) * 1e3 for r in readers.measured(run) if r["submitted"] is not None]
    return readers.percentile(late, 99)
