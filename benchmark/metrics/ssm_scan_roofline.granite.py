from benchmark import readers_granite


def read(run):
    """The scans' least time by counts_granite.py over the device time of the phase ssm_scan in the traced steps, recomputed time included."""
    return readers_granite.ssm_scan_roofline(run)
