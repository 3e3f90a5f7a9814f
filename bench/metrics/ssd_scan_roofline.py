"""``ssd_scan``'s share of its roofline (see ``bench.roofline``)."""
from bench.roofline import share


def read(ctx):
    return share(ctx, "ssd_scan")
