"""``expert_mlp``'s share of its roofline.  The kernel runs at two shapes
a round, the prefill's and the decode steps', with rows that vary call
by call, so the least time is summed over the traced calls: each
phase's calls times the least time of its mean call (rows and experts
read from the window's ``moe.*`` counters, ``driver.expert_mlp_calls``),
over the kernel's device time in the trace."""
from bench.counts import expert_mlp
from bench.peaks import least_time


def read(ctx):
    phases = expert_mlp.phases(ctx)
    if phases is None or ctx.peaks is None:
        return None
    need = sum(len(events) * least_time(*expert_mlp.count(**call), ctx.peaks)
               for events, _, call in phases)
    spent = sum(e.end - e.start for events, _, _ in phases for e in events)
    return need / (spent * 1e-9) * 100.0
