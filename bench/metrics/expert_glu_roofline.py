"""``expert_glu``'s share of its roofline (see ``bench.roofline``)."""
from bench.roofline import share


def read(ctx):
    return share(ctx, "expert_glu")
