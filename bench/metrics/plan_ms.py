"""Host time of ``Orchestrator.plan`` per round, from the harness's
``plan`` spans."""


def read(ctx):
    plans = [t1 - t0 for name, t0, t1 in ctx.spans if name == "plan"]
    return sum(plans) / len(plans) * 1e3 if plans else None
