"""The whole request's share of the chip's peak: the operations each
request needs (``flops_per_request`` of the driver, each class at its
own peak) times the requests completed per second."""
from bench.peaks import least_time


def read(ctx):
    if ctx.peaks is None:
        return None
    need = least_time(ctx.driver.flops_per_request(), 0.0, ctx.peaks)
    return need * ctx.completed / ctx.window_s * 100.0
