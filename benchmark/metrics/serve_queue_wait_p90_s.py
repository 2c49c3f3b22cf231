from benchmark import readers


def read(run):
    """Admission minus arrival, as the program's ledger recorded both."""
    waits = [r["admitted"] - r["arrival"] for r in readers.measured(run) if r["admitted"] is not None]
    return readers.percentile(waits, 90)
