"""The program's span recorder (``repro.telemetry``): nesting and
parents, recording only under an active profiler trace, the bounded
buffer, compile attribution, and the spans of ``Engine.generate``, the
lane programs and the serving loop."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config
from repro.core import FusedOp, OpGraph, ScheduleExecutor
from repro.core.costmodel import EDGE_PUS
from repro.models import model as M
from repro.serving.engine import Engine
from repro.sharding import Policy

from test_chaos_serving import _trace, fresh_engine
from test_laneprogram import _jax_chain, _x
from test_serve import make_engine


def _since(mark: int, names=None) -> list:
    """Spans kept after span id ``mark``, oldest first."""
    return [s for s in telemetry.spans() if s.id > mark
            and (names is None or s.name in names)]


def _mark() -> int:
    with telemetry.span("test.mark") as s:
        pass
    return s.id


@pytest.fixture
def traced(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        yield


def test_nesting_parents_and_attrs(traced):
    mark = _mark()
    with telemetry.span("outer", call=7) as outer:
        with telemetry.span("inner", i=1) as inner:
            time.sleep(0.001)
        with telemetry.span("inner", i=2) as second:
            pass
    kept = _since(mark)
    assert [s.name for s in kept] == ["inner", "inner", "outer"]
    assert outer.parent is None
    assert inner.parent == second.parent == outer.id
    assert outer.attrs == {"call": 7} and inner.attrs == {"i": 1}
    assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= second.t0_ns \
        <= second.t1_ns <= outer.t1_ns
    assert inner.seconds >= 0.001
    assert len({outer.id, inner.id, second.id}) == 3


def test_nothing_kept_without_a_trace():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = telemetry.spans()
    with telemetry.span("off") as s:
        time.sleep(0.001)
    assert telemetry.spans() == before
    assert s.seconds >= 0.001          # the duration is there all the same


def test_spans_kept_inside_a_trace(tmp_path):
    mark = _mark()
    with jax.profiler.trace(str(tmp_path)):
        with telemetry.span("on"):
            pass
    with telemetry.span("after"):
        pass
    assert [s.name for s in _since(mark)] == ["on"]


def test_dropped_counts_spans_past_the_bound(traced):
    assert telemetry.CAPACITY == 65536
    rec = telemetry.Recorder(capacity=4)
    ids = []
    for i in range(6):
        with rec.span("x", i=i) as s:
            pass
        ids.append(s.id)
    assert [s.id for s in rec.spans()] == ids[2:]
    assert rec.counters() == {"dropped": 2, "compiles/none": 0}


def test_a_fresh_jit_lands_in_its_span(traced):
    x = jnp.arange(5.0)
    with telemetry.span("outer") as outer:
        with telemetry.span("compiling") as inner:
            jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    assert inner.compiles >= 1 and outer.compiles == 0
    none0 = telemetry.counters()["compiles/none"]
    jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
    assert telemetry.counters()["compiles/none"] > none0


@pytest.fixture(scope="module")
def engine():
    cfg = get_config("llama3.2-1b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg=cfg, params=params, policy=Policy())


def test_generate_span_tree(engine, traced):
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, engine.cfg.vocab, (2, 8), dtype=np.int32))
    cold = _mark()
    engine.generate(toks, max_new=3)             # traces prefill and step
    mark = _mark()
    # the jitted prefill's model spans fire while it traces, and only then
    (cold_pre,) = _since(cold, {"engine.prefill"})
    assert [s.parent for s in _since(
        cold, {"model.prefill.setup", "model.prefill.logits"})] \
        == [cold_pre.id, cold_pre.id]
    assert cold_pre.compiles >= 1
    engine.generate(toks, max_new=3)
    kept = _since(mark)
    by = {}
    for s in kept:
        by.setdefault(s.name, []).append(s)
    (gen,) = by["engine.generate"]
    assert gen.attrs["batch"] == 2 and gen.attrs["prompt"] == 8
    assert gen.attrs["max_new"] == 3 and gen.attrs["call"] == engine._calls
    (pre,), (dec,) = by["engine.prefill"], by["engine.decode"]
    assert pre.parent == dec.parent == gen.id
    assert "model.prefill.setup" not in by and "model.prefill.logits" not in by
    assert pre.compiles == 0
    steps = by["engine.decode_step"]
    assert [s.attrs["i"] for s in steps] == [0, 1, 2]
    assert all(s.parent == dec.id for s in steps)
    assert gen.t0_ns <= pre.t0_ns <= pre.t1_ns <= dec.t0_ns \
        <= dec.t1_ns <= gen.t1_ns
    # the calls' counter is the engine's own
    engine.generate(toks, max_new=1)
    assert _since(mark, {"engine.generate"})[-1].attrs["call"] \
        == gen.attrs["call"] + 1


def test_segment_timings_are_the_segment_spans(traced):
    ex = ScheduleExecutor(list(EDGE_PUS))
    prog = ex.compile_scheduled(_jax_chain(6), {0: "CPU", 1: "CPU", 2: "CPU",
                                                3: "GPU", 4: "GPU", 5: "CPU"})
    mark = _mark()
    timings: list = []
    prog.run({0: (_x(),)}, segment_timings=timings)
    segs = _since(mark, {"lane.segment"})
    assert len(timings) == len(segs) == len(prog.segments)
    assert sorted(dt for _, _, dt in timings) == sorted(
        s.seconds for s in segs)
    assert {(s.attrs["lane"], s.attrs["segment"]) for s in segs} == {
        (seg.lane, seg.index) for seg in prog.segments}


def test_plan_ms_is_read_from_the_plan_spans(traced):
    from repro.core import ArrivalTrace
    orch, eng = make_engine(np.random.default_rng(0), max_concurrent=3)
    mark = _mark()
    rep = eng.serve(ArrivalTrace.poisson(list(eng._graphs), rate=50.0,
                                         n=8, seed=1))
    plans = [s for s in _since(mark, {"orchestrator.plan"})
             if "via" in s.attrs]
    assert rep.plan_events == len(plans) > 0
    assert {s.attrs["via"] for s in plans} <= {"admit", "retire",
                                                "replan_active"}
    ms = [s.seconds * 1e3 for s in plans]
    assert rep.plan_ms_p50 == pytest.approx(float(np.percentile(ms, 50)))


def test_cross_lane_waits_have_spans(traced):
    def slow(a):
        time.sleep(0.02)
        return np.tanh(a)
    ops = [FusedOp("src", "act", ((4, 4),), (4, 4),
                   fn=lambda: np.ones((4, 4))),
           FusedOp("a1", "act", ((4, 4),), (4, 4), fn=slow),
           FusedOp("a2", "act", ((4, 4),), (4, 4), fn=np.sin),
           FusedOp("join", "add", ((4, 4), (4, 4)), (4, 4),
                   fn=lambda x, y: x + y)]
    graph = OpGraph(ops, edges=[(0, 1), (0, 2), (1, 3), (2, 3)])
    prog = ScheduleExecutor(list(EDGE_PUS)).compile_scheduled(
        graph, {0: "CPU", 1: "GPU", 2: "NPU", 3: "CPU"})
    assert prog.serial_order is None       # the branches may overlap
    mark = _mark()
    prog.run()
    (slow_seg,) = [seg for seg in prog.segments if seg.lane == "GPU"]
    waits = [s for s in _since(mark, {"lane.wait"})
             if s.attrs["on"] == slow_seg.index]
    assert waits and all(s.attrs["lane"] == "CPU" for s in waits)
    assert max(s.seconds for s in waits) > 0.01
    assert len(_since(mark, {"lane.segment"})) == len(prog.segments)


def test_exec_wall_and_recovery_are_read_from_spans(traced):
    from repro.core import ChaosEvent, ChaosTrace
    orch, eng = fresh_engine()
    trace = _trace(n=8, seed=3)
    chaos = ChaosTrace([ChaosEvent(time=trace.arrivals[3].time,
                                   kind="pu_lost", lane="CPU")],
                       kind="pu_lost", seed=3)
    mark = _mark()
    rep = eng.serve(trace, chaos=chaos)
    windows = [s for s in _since(mark, {"orchestrator.execute"})
               if s.attrs.get("kind") == "window"]
    assert windows and rep.exec_wall_s == pytest.approx(
        sum(s.seconds for s in windows))
    faults = _since(mark, {"serve.fault"})
    assert rep.recoveries >= 1 and faults
    assert rep.recovery_ms_p50 == pytest.approx(float(np.percentile(
        [s.seconds * 1e3 for s in faults], 50)))
