"""The program's spans on the trace's clock (``bench/program_spans.py``)
and the four metrics read from them: exact values on a synthetic trace,
the partition of the chip's idle time, the clock mapping against a real
CPU trace, and a traced tiny run that reports all four."""
import sys
import time
import types

import jax
import pytest

from bench import program_spans, run, trace
from bench.metrics import (decode_gap_ms, idle_share, other_gap_ms,
                           prefill_compiles, prefill_gap_ms)
from bench.trace import Event, Reduction
from repro import telemetry

GAPS = (prefill_gap_ms, decode_gap_ms, other_gap_ms)
SHIFT = 5_000_000_000          # perf_counter ns less trace ns


def _span(name, sid, parent, start, end, compiles=0):
    """A recorded span whose interval is ``start``..``end`` in trace ns."""
    return types.SimpleNamespace(name=name, id=sid, parent=parent,
                                 t0_ns=start + SHIFT, t1_ns=end + SHIFT,
                                 compiles=compiles)


def _ctx(monkeypatch, skew=0):
    """Two rounds in a window of 10 ms.  The chip runs 1.5-3.5 ms and
    6-7.9 ms; prefill is 1-2 and 5-6.5 ms, decode 2-4 and 6.5-8 ms.
    ``skew`` moves the second anchor on the trace's clock."""
    gens = [(1e6, 4e6), (5e6 + skew, 8e6 + skew)]
    red = Reduction(
        window=(0.0, 10e6),
        ops={"/device:TPU:0": [Event("a", 1.5e6, 3.5e6, "a"),
                               Event("b", 6e6, 7.9e6, "b")]},
        modules=[],
        spans=[Event("bench.window", 0.0, 10e6, "bench.window")]
        + [Event("generate", s, e, "generate") for s, e in gens])
    host = [("generate", (s + SHIFT) * 1e-9, (e + SHIFT) * 1e-9)
            for s, e in [(1e6, 4e6), (5e6, 8e6)]]
    host.insert(1, ("sync", 4.1e-3 + SHIFT * 1e-9, 4.2e-3 + SHIFT * 1e-9))
    spans = [
        _span("engine.prefill", 1, None, -3e6, -2e6, compiles=100),
        _span("engine.generate", 10, None, 1e6, 4e6),
        _span("engine.prefill", 11, 10, 1e6, 2e6, compiles=2),
        _span("model.prefill.setup", 12, 11, 1e6, 1.1e6, compiles=1),
        _span("engine.decode", 13, 10, 2e6, 4e6),
        _span("engine.decode_step", 14, 13, 2e6, 2.5e6, compiles=5),
        _span("engine.generate", 20, None, 5e6, 8e6),
        _span("engine.prefill", 21, 20, 5e6, 6.5e6),
        _span("model.prefill.logits", 22, 21, 6.4e6, 6.5e6, compiles=1),
        _span("engine.decode", 23, 20, 6.5e6, 8e6),
    ]
    monkeypatch.setattr(telemetry, "spans", lambda: spans)
    return types.SimpleNamespace(trace=red, spans=host, rounds=2)


def test_clock_offset_is_the_median_over_the_anchors():
    host = [("generate", 1.0, 2.0), ("prep", 2.1, 2.2), ("generate", 3.0, 4.0)]
    traced = [Event("generate", s, e, "generate") for s, e in
              [(1e9 - 40, 2e9 - 30), (3e9 - 20, 4e9 - 10)]]
    assert program_spans.clock_offset(host, traced) == (-25.0, 30.0)
    assert program_spans.clock_offset(host, traced[:1]) is None
    assert program_spans.clock_offset([], []) is None


def test_the_four_metrics_on_a_synthetic_trace(monkeypatch):
    ctx = _ctx(monkeypatch)
    # prefill idle 0.5 + 1.0 ms, decode 0.5 + 0.1 ms, over two rounds
    assert prefill_gap_ms.read(ctx) == pytest.approx(0.75)
    assert decode_gap_ms.read(ctx) == pytest.approx(0.3)
    # the window's 6.1 ms idle less the 2.1 ms above
    assert other_gap_ms.read(ctx) == pytest.approx(2.0)
    # engine.prefill and its children, in the window: 2 + 1 + 1
    assert prefill_compiles.read(ctx) == pytest.approx(2.0)


def test_the_gaps_partition_the_idle_time(monkeypatch):
    ctx = _ctx(monkeypatch)
    whole = idle_share.read(ctx) / 100 * ctx.trace.window_s / ctx.rounds
    assert sum(m.read(ctx) for m in GAPS) == pytest.approx(whole * 1e3)


def test_unpaired_clocks_leave_every_metric_out(monkeypatch):
    ctx = _ctx(monkeypatch, skew=150_000)       # 150 us apart
    assert program_spans.mapped(ctx) is None
    assert [m.read(ctx) for m in GAPS + (prefill_compiles,)] == [None] * 4
    ctx = _ctx(monkeypatch, skew=50_000)
    assert program_spans.mapped(ctx) is not None


def test_a_program_without_the_engine_spans_reads_nothing(monkeypatch):
    ctx = _ctx(monkeypatch)
    monkeypatch.setattr(telemetry, "spans", lambda: [
        _span("lane.segment", 1, None, 1e6, 2e6, compiles=3)])
    assert [m.read(ctx) for m in GAPS + (prefill_compiles,)] == [None] * 4


def test_a_program_without_the_recorder_reads_nothing(monkeypatch):
    ctx = _ctx(monkeypatch)
    monkeypatch.delattr(sys.modules["repro"], "telemetry")
    monkeypatch.setitem(sys.modules, "repro.telemetry", None)
    assert [m.read(ctx) for m in GAPS + (prefill_compiles,)] == [None] * 4


def test_no_chip_no_gaps(monkeypatch):
    ctx = _ctx(monkeypatch)
    ctx.trace.ops = {}
    assert [m.read(ctx) for m in GAPS] == [None] * 3
    assert prefill_compiles.read(ctx) == pytest.approx(2.0)


def test_mapped_spans_meet_their_annotations_in_a_cpu_trace(tmp_path):
    """The anchors carry a name of their own, so that no other
    ``generate`` annotation the process made can pair with them."""
    anchor = "clock.anchor"
    harness = run.Spans()
    with jax.profiler.trace(str(tmp_path)):
        with harness("bench.window"):
            for _ in range(5):
                with harness(anchor):
                    with telemetry.span("clock.probe"):
                        time.sleep(0.002)
    red = trace.reduce(str(tmp_path), [anchor, "clock.probe"])
    offset, spread = program_spans.clock_offset(harness.items, red.spans,
                                                anchor)
    assert spread < program_spans.SPREAD_NS
    lo, hi = red.window
    got = sorted((s.t0_ns + offset, s.t1_ns + offset)
                 for s in telemetry.spans() if s.name == "clock.probe"
                 and lo < s.t0_ns + offset < hi)
    want = sorted((e.start, e.end) for e in red.spans
                  if e.name == "clock.probe")
    assert len(got) == len(want) == 5
    for (a, b), (s, e) in zip(got, want):
        assert abs(a - s) < 50_000 and abs(b - e) < 50_000, (got, want)


def test_a_traced_tiny_run_reports_the_four(run_tiny, monkeypatch):
    """Off the chip the trace holds no device plane: a synthetic chip,
    busy through the first half of the window, stands in."""
    reduce = trace.reduce

    def with_a_chip(path, span_names=()):
        red = reduce(path, span_names)
        lo, hi = red.window
        red.ops = {"/device:TPU:0": [Event("op", lo, (lo + hi) / 2, "op")]}
        return red

    monkeypatch.setattr(trace, "reduce", with_a_chip)
    result, _ = run_tiny("zamba2.prefill", trace=True)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    names = ("prefill_gap_ms", "decode_gap_ms", "other_gap_ms",
             "prefill_compiles")
    assert set(names) <= set(got)
    rounds = result["attempted"] / 2             # two tiny clients a round
    whole = got["idle_share"] / 100 * result["device"]["window_s"] / rounds
    assert sum(got[n] for n in names[:3]) == pytest.approx(whole * 1e3,
                                                           rel=1e-6)
    assert all(got[n] >= 0 for n in names)
