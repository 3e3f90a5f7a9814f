"""The trace reducer: busy time as a union, clipping to the window, idle
gaps labelled by the harness's spans, and the reduction of a small trace
recorded on a v5e chip."""
import os

import pytest

from bench import trace
from bench.trace import Event, Reduction

DATA = os.path.join(os.path.dirname(__file__), "data")


def _red(ops, spans=(), window=(0.0, 100.0)):
    return Reduction(
        window=window,
        ops={"/device:TPU:0": [Event(n, s, e, n) for n, s, e in ops]},
        modules=[], spans=[Event(n, s, e, n) for n, s, e in spans])


def test_busy_is_the_union_clipped_to_the_window():
    red = _red([("a", -10, 10), ("b", 5, 20), ("c", 30, 40), ("d", 90, 120)])
    assert red.busy_intervals("/device:TPU:0") == [(0, 20), (30, 40),
                                                   (90, 100)]
    assert red.busy_s == pytest.approx(40e-9)
    assert red.window_s == pytest.approx(100e-9)


def test_idle_gaps_longest_first_labelled_by_innermost_span():
    red = _red([("a", 0, 10), ("b", 30, 40), ("c", 45, 100)],
               spans=[("bench.window", 0, 100), ("execute", 5, 35),
                      ("plan", 12, 28)])
    # gaps: 10..30 (middle 20: inside plan, inside execute) and 40..45
    assert red.idle_gaps() == [["plan", pytest.approx(20e-9)],
                               [trace.NO_SPAN, pytest.approx(5e-9)]]


def test_kernel_events_and_top_ops_by_self_time():
    red = _red([("while.3", 0, 60), ("ssd_scan.1", 0, 10),
                ("fusion", 10, 40), ("ssd_scan.1", 50, 60),
                ("ssd_scan_copy", 60, 70)])
    for e in red.ops["/device:TPU:0"]:
        e.detail = f"%{e.name} = f32[8] " + (
            "custom-call(f32[8] %a)" if e.name == "ssd_scan.1"
            else "copy(f32[8] %ssd_scan.1)")
    assert len(red.kernel_events("ssd_scan")) == 2
    # the loop's self time is what its body leaves: 60 - 10 - 30 - 10
    assert red.top_ops(2) == [["fusion", pytest.approx(30e-9)],
                              ["ssd_scan.1", pytest.approx(20e-9)]]
    assert dict(red.top_ops())["while.3"] == pytest.approx(10e-9)


def test_no_device_plane_reads_nothing():
    red = Reduction(window=(0.0, 1.0), ops={}, modules=[], spans=[])
    assert red.chips == [] and red.busy_s == 0.0 and red.idle_gaps() == []


def test_reduce_a_trace_recorded_on_the_chip():
    """One second of ``chain.solo`` on a TPU v5e (``--trace 1``): 59
    rounds, each one jitted program with the three kernels and the sort."""
    red = trace.reduce(os.path.join(DATA, "chain_solo_v5e.xplane.pb"),
                       ("prep", "plan", "execute", "sync"))
    assert red.chips == ["/device:TPU:0"]
    assert red.window_s == pytest.approx(1.001537244)
    assert red.busy_s == pytest.approx(0.89336411)
    assert 0 < red.busy_s < red.window_s
    assert len(red.modules) == 59
    for kernel in ("flash_attention", "ssd_scan", "expert_glu"):
        assert len(red.kernel_events(kernel)) == 59
    assert [name for name, _ in red.top_ops(4)] == [
        "sort.7", "flash_attention.1", "expert_glu.1", "ssd_scan.1"]
    gaps = red.idle_gaps(10)
    assert len(gaps) == 10 and gaps[0][1] == pytest.approx(0.002330312)
    assert {label for label, _ in gaps} <= {"sync", trace.NO_SPAN}
    assert sorted({s.name for s in red.spans}) == [
        "bench.window", "execute", "plan", "prep", "sync"]
