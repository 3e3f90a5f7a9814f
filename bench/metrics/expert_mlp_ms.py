"""Device time of the ``expert_mlp`` kernel a round, in ms: for each phase
(prefill, decode) the mean duration of its calls in the trace times its
calls a round (``bench.counts.expert_mlp.phases``)."""
from bench.counts import expert_mlp


def read(ctx):
    phases = expert_mlp.phases(ctx)
    if phases is None:
        return None
    return sum(sum(e.end - e.start for e in events) / len(events) * per_round
               for events, per_round, _ in phases) * 1e-6
