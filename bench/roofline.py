"""A kernel's share of its roofline: the least time its calls need on
the chip (operations and bytes from ``bench/counts``, peaks from
``peaks.json``) over the device time of its events in the trace."""
from bench.peaks import least_time


def share(ctx, kernel: str):
    calls = ctx.driver.kernel_calls()
    if ctx.trace is None or ctx.peaks is None or kernel not in calls:
        return None
    module, call = calls[kernel]
    events = ctx.trace.kernel_events(module.EVENT)
    if not events:
        return None
    flops, nbytes = module.count(**call)
    spent = sum(e.end - e.start for e in events) * 1e-9
    return len(events) * least_time(flops, nbytes, ctx.peaks) / spent * 100.0
