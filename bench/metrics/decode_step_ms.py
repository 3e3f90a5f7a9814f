"""Device time of one jitted decode step: the mean duration of the decode
program's events in the trace."""


def read(ctx):
    pattern = ctx.driver.decode_module
    if ctx.trace is None or pattern is None:
        return None
    steps = ctx.trace.module_events(pattern)
    if not steps:
        return None
    return sum(e.end - e.start for e in steps) / len(steps) * 1e-6
