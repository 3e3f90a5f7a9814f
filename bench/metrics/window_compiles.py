"""Programs compiled, or loaded from the persistent compilation cache,
inside the window (JAX's monitoring events), plus the lane programs the
orchestrator built there (its ``program_misses``)."""


def read(ctx):
    return ctx.compiles + ctx.counters.get("program_misses", 0)
