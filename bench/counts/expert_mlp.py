"""Relu^2 experts over sorted, grouped rows, ``repro.kernels.expert_mlp``:
y = relu(x W_up^T)^2 W_down for the rows routed to each held expert."""
from __future__ import annotations

import re

EVENT = "expert_mlp"
_ROWS = re.compile(r"=\s*\w+\[(\d+),")


def count(*, rows: float, experts: float, d: int, F: int, dtype: str):
    """One call: ``rows`` (token, expert) pairs routed to held experts,
    not the padded blocks, two d x F contractions each; ``experts`` the
    held experts with any rows, whose two (F, d) weights are read once
    (an expert with no rows is not read); the rows read and written
    once."""
    item = 4 if dtype == "float32" else 2
    flops = 4 * rows * d * F
    nbytes = item * (2 * rows * d + 2 * experts * F * d)
    cls = "f32_highest" if dtype == "float32" else "bf16"
    return {cls: float(flops)}, float(nbytes)


def phases(ctx):
    """``[(events, calls a round, count arguments of the mean call)]``, one
    entry a phase of ``ctx.driver.expert_mlp_calls``: the kernel runs at
    one padded shape a phase, so a trace event belongs to the phase whose
    rank by rows a call matches the rank of its output rows.  Matching
    the events, and not counting the calls a round should hold, keeps a
    reading sound where the profiler dropped events.  ``None`` where
    there is no trace, no such driver, or the shapes do not pair up."""
    calls_of = getattr(ctx.driver, "expert_mlp_calls", None)
    if ctx.trace is None or calls_of is None:
        return None
    events = ctx.trace.kernel_events(EVENT)
    calls = calls_of(ctx.counters, ctx.rounds)
    if not events or not calls:
        return None
    by_rows: dict = {}
    for e in events:
        m = _ROWS.search(e.detail)
        by_rows.setdefault(m and int(m.group(1)), []).append(e)
    if None in by_rows or len(by_rows) != len(calls):
        return None
    ranked = sorted(calls, key=lambda c: c[1]["rows"])
    return [(by_rows[r], n / ctx.rounds, call)
            for r, (n, call) in zip(sorted(by_rows), ranked)]
