from benchmark import readers


def read(run):
    return readers.flash_attn_roofline(run)
