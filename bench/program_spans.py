"""The program's own spans (``repro.telemetry``) on the device trace's
clock, and the chip's idle time inside them.

The program records its spans on ``time.perf_counter_ns()`` while the
profiler runs; the trace keeps its events on a clock of its own.  The
harness's ``generate`` spans sit on both: in ``ctx.spans`` (perf_counter
seconds) and, as annotations, in ``ctx.trace.spans`` (trace ns).  Paired
in order, each gives the offset between the clocks at its start and its
end; the median of those maps the program's spans onto the trace.  Where
the pairs disagree (a different count, or offsets that spread by more
than ``SPREAD_NS``) nothing is mapped and every metric read from here is
left out rather than wrong.

A gap metric is the first chip's idle time inside the named spans: their
union, clipped to the window, less the union of the chip's operations,
per round.  ``engine.prefill``, ``engine.decode`` and the rest of the
window partition the idle time, so the three gap metrics sum to
``idle_share`` times the window, per round.

A program without ``repro.telemetry`` records no spans, and a window
without the named spans holds nothing to read: the readers then return
``None``.
"""
from __future__ import annotations

import statistics

ANCHOR = "generate"
SPREAD_NS = 100_000
PREFILL = "engine.prefill"
DECODE = "engine.decode"


def clock_offset(host: list, traced: list, anchor: str = ANCHOR):
    """``(offset, spread)`` in ns, trace clock less perf_counter, from the
    ``anchor`` spans of ``host`` (``(name, t0_s, t1_s)``) and ``traced``
    (``bench.trace.Event``); ``None`` where they cannot be paired."""
    h = sorted((t0, t1) for name, t0, t1 in host if name == anchor)
    t = sorted((e.start, e.end) for e in traced if e.name == anchor)
    if not h or len(h) != len(t):
        return None
    offsets = [off for (h0, h1), (s, e) in zip(h, t)
               for off in (s - h0 * 1e9, e - h1 * 1e9)]
    return statistics.median(offsets), max(offsets) - min(offsets)


def mapped(ctx):
    """The program's spans that overlap the window, as ``(span, start,
    end)`` on the trace's clock; ``None`` where they cannot be mapped."""
    if ctx.trace is None:
        return None
    try:
        from repro import telemetry
    except ImportError:
        return None
    found = clock_offset(ctx.spans, ctx.trace.spans)
    if found is None or found[1] > SPREAD_NS:
        return None
    offset = found[0]
    lo, hi = ctx.trace.window
    out = []
    for s in telemetry.spans():
        start, end = s.t0_ns + offset, s.t1_ns + offset
        if end > lo and start < hi:
            out.append((s, start, end))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged intervals."""
    out: list[list[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def overlap(a, b) -> float:
    """Length of the intersection of two sorted, merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, t = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if t > s:
            total += t - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _idle_ns(ctx, names):
    """``(idle inside the spans named in names, idle in the window)`` of
    the first chip, in ns; ``None`` without a chip, a mapping or such a
    span in the window."""
    if ctx.trace is None or not ctx.trace.chips:
        return None
    spans = mapped(ctx)
    if spans is None:
        return None
    lo, hi = ctx.trace.window
    inside = union((max(a, lo), min(b, hi)) for s, a, b in spans
                   if s.name in names)
    if not inside:
        return None
    busy = ctx.trace.busy_intervals(ctx.trace.chips[0])
    window_idle = (hi - lo) - sum(t - s for s, t in busy)
    return (sum(t - s for s, t in inside) - overlap(inside, busy),
            window_idle)


def gap_ms(ctx, names) -> float | None:
    """The chip's idle time inside the spans named in ``names``, in ms a
    round."""
    idle = _idle_ns(ctx, names)
    return None if idle is None else idle[0] * 1e-6 / ctx.rounds


def other_gap_ms(ctx) -> float | None:
    """The chip's idle time in the window outside ``engine.prefill`` and
    ``engine.decode``, in ms a round."""
    idle = _idle_ns(ctx, (PREFILL, DECODE))
    return None if idle is None else (idle[1] - idle[0]) * 1e-6 / ctx.rounds


def compiles(ctx, name: str) -> float | None:
    """Compilations and cache loads in the ``name`` spans of the window
    and their descendants, a round."""
    spans = mapped(ctx)
    if spans is None:
        return None
    children: dict = {}
    for s, _, _ in spans:
        children.setdefault(s.parent, []).append(s)
    total, todo = 0, [s for s, _, _ in spans if s.name == name]
    if not todo:
        return None
    while todo:
        s = todo.pop()
        total += s.compiles
        todo += children.get(s.id, [])
    return total / ctx.rounds
