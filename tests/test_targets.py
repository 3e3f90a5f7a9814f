"""Target registry + backend binding: registry semantics, per-PU variant
selection/verification on the compiled path, per-target measured
profiling, fenced timing, and stale-variant program invalidation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (FusedOp, Orchestrator, ScheduleExecutor, chain_graph,
                        results_bitwise_equal)
from repro.core.backends import (default_registry, device_target,
                                 discover_devices, numpy_eager,
                                 pallas_interpret, xla_cpu)
from repro.core.laneprogram import JIT, PYTHON
from repro.core.profiler import (Measurement, MeasuredProfiler,
                                 measure_callable, measure_callable_stats)
from repro.core.schedule import ConcurrentSchedule, ConcurrentStep
from repro.core.targets import (Target, TargetRegistry, pu_specs_for_targets,
                                resolve_targets, variant_tolerance)
from repro.core.workload import Workload


def _x(dim=8):
    return jnp.linspace(0.0, 1.0, dim * dim,
                        dtype=jnp.float32).reshape(dim, dim)


def _variant_chain(n=4, dim=8, variants=None):
    """Chain of tanh payloads; ``variants`` maps op index -> extra
    payload table entries installed as ``op.variants``."""
    ops = []
    for i in range(n):
        c = jnp.float32(1.0 + 0.01 * i)
        fn = (lambda c: lambda v: jnp.tanh(v * c))(c)
        op = FusedOp(f"o{i}", "act", ((dim, dim),), (dim, dim), fn=fn)
        op.meta["example_inputs"] = (_x(dim),)
        if variants and i in variants:
            op.variants = dict(variants[i])
        ops.append(op)
    return chain_graph(ops)


def _three_targets():
    return {
        "host": numpy_eager(name="host"),
        "fast": xla_cpu(name="fast"),
        "alt": Target(name="alt", dialect="alt", jit=False,
                      dispatch_s=1e-6, handoff_s=0.0),
    }


# ---------------------------------------------------------------------------
# registry + resolution
# ---------------------------------------------------------------------------


def test_registry_register_get_names():
    reg = TargetRegistry([numpy_eager(), xla_cpu()])
    assert reg.names() == ["numpy-eager", "xla-cpu"]
    assert "xla-cpu" in reg and len(reg) == 2
    assert reg.get("numpy-eager").dialect == "numpy"
    with pytest.raises(KeyError, match="registered"):
        reg.get("nope")
    with pytest.raises(ValueError, match="already registered"):
        reg.register(xla_cpu())
    faster = reg.register(xla_cpu(dispatch_s=1e-6), replace=True)
    assert reg.get("xla-cpu") is faster
    with pytest.raises(TypeError):
        reg.register("xla-cpu")


def test_default_registry_contains_builtins_and_devices():
    reg = default_registry()
    for name in ("numpy-eager", "xla-cpu", "pallas-interpret"):
        assert name in reg
    devs = discover_devices()
    assert devs and all(t.device is not None for t in devs)
    for t in devs:
        assert t.name in reg
    assert len(default_registry(devices=False)) == 3


def test_discover_devices_raises_when_the_backend_fails(monkeypatch):
    """A backend that cannot start must not silently drop its lanes."""
    def broken():
        raise RuntimeError("backend failed to initialise")
    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="initialise"):
        discover_devices()


def test_resolve_targets_forms():
    assert resolve_targets(None) is None
    reg = TargetRegistry([numpy_eager(), xla_cpu()])
    by_reg = resolve_targets(reg)
    assert set(by_reg) == {"numpy-eager", "xla-cpu"}
    t = xla_cpu()
    assert resolve_targets({"A": t, "B": t}) == {"A": t, "B": t}
    assert set(resolve_targets([numpy_eager(), xla_cpu()])) \
        == {"numpy-eager", "xla-cpu"}
    with pytest.raises(ValueError, match="empty"):
        resolve_targets({})
    with pytest.raises(TypeError, match="expected a Target"):
        resolve_targets({"A": "xla-cpu"})


def test_target_pu_spec_and_tolerance():
    t = xla_cpu(handoff_s=3e-3, power_compute=9.0)
    spec = t.pu_spec()
    assert spec.name == "xla-cpu" and spec.is_accelerator
    assert spec.h2d_base == 3e-3 and spec.power_compute == 9.0
    assert spec.kind_eff.get("other") == 1.0
    # declared atol/rtol override the per-dtype variant buckets
    assert t.tolerance(np.float32) == (t.atol, t.rtol)
    assert numpy_eager().tolerance(np.float32) \
        == variant_tolerance(np.float32)
    assert variant_tolerance(np.int32) == (0.0, 0.0)
    specs = pu_specs_for_targets({"L0": t})
    assert specs["L0"].name == "xla-cpu"   # keyed by lane, named by target


def test_workload_accepts_target_values_as_pus():
    g = _variant_chain(3)
    binding = _three_targets()
    table = MeasuredProfiler(warmup=0, iters=1, targets=binding).profile(g)
    wl = Workload.build(list(range(3)), table, binding, ops=g.ops)
    assert all(hasattr(p, "is_accelerator") for p in wl.pus.values())


# ---------------------------------------------------------------------------
# orchestrator / executor binding
# ---------------------------------------------------------------------------


def test_orchestrator_derives_lanes_from_targets():
    binding = _three_targets()
    g = _variant_chain(3)
    table = MeasuredProfiler(warmup=1, iters=2, targets=binding).profile(g)
    orch = Orchestrator(table, targets=binding)
    assert set(orch.pus) == set(binding)
    plan = orch.plan(orch.register(g))
    lanes = {lane for _, lane in plan.route[0]}
    assert lanes <= set(binding)


def test_unknown_target_lane_rejected():
    with pytest.raises(ValueError, match="nope"):
        ScheduleExecutor({"A": numpy_eager().pu_spec()},
                         targets={"nope": numpy_eager()})


# ---------------------------------------------------------------------------
# variant selection + probe verification on the compiled path
# ---------------------------------------------------------------------------


def _compiled_on(binding, graph, lane):
    ex = ScheduleExecutor(pu_specs_for_targets(binding), targets=binding)
    prog = ex.compile_scheduled(graph, {i: lane
                                        for i in range(len(graph))})
    return ex, prog


def test_variant_bitwise_accept_and_serve():
    binding = _three_targets()
    # the alt variant is a different callable computing the same value
    variants = {1: {"alt": lambda v: jnp.tanh(v * jnp.float32(1.01))}}
    g = _variant_chain(3, variants=variants)
    ex, prog = _compiled_on(binding, g, "alt")
    got = prog.run({0: (_x(),)})
    st = prog.stats
    assert st["n_variant"] == 1
    assert set(st["variant_verified"].values()) == {"bitwise"}
    mono = ex.run_monolithic(g, {0: (_x(),)})
    assert results_bitwise_equal(mono, got)


def test_variant_tolerance_accept():
    binding = _three_targets()
    eps = jnp.float32(1e-6)      # inside the f32 bucket (3e-4)
    variants = {1: {"alt": lambda v: jnp.tanh(v * jnp.float32(1.01)) + eps}}
    g = _variant_chain(3, variants=variants)
    ex, prog = _compiled_on(binding, g, "alt")
    prog.run({0: (_x(),)})               # cold run: probe, serves reference
    got = prog.run({0: (_x(),)})         # warm run: serves accepted variant
    assert set(prog.stats["variant_verified"].values()) == {"tolerance"}
    mono = ex.run_monolithic(g, {0: (_x(),)})
    assert not results_bitwise_equal(mono, got)
    assert ex.outputs_close(mono, got, atol=3e-4, rtol=3e-4)


def test_variant_rejected_falls_back_to_reference():
    binding = _three_targets()
    variants = {1: {"alt": lambda v: jnp.tanh(v) + jnp.float32(1.0)}}
    g = _variant_chain(3, variants=variants)
    ex, prog = _compiled_on(binding, g, "alt")
    got = prog.run({0: (_x(),)})
    (verdict,) = set(prog.stats["variant_verified"].values())
    assert verdict.startswith("rejected: output ")   # says which and why
    assert "max_abs_err=1.000e+00" in verdict
    assert prog.stats["n_variant"] == 0
    assert results_bitwise_equal(ex.run_monolithic(g, {0: (_x(),)}), got)


def test_variant_error_falls_back_to_reference():
    binding = _three_targets()

    def boom(v):
        raise RuntimeError("kernel exploded")

    g = _variant_chain(3, variants={1: {"alt": boom}})
    ex, prog = _compiled_on(binding, g, "alt")
    got = prog.run({0: (_x(),)})
    (verdict,) = set(prog.stats["variant_verified"].values())
    assert verdict.startswith("error")
    assert results_bitwise_equal(ex.run_monolithic(g, {0: (_x(),)}), got)


def test_ref_dialect_never_reads_variant_tables():
    binding = _three_targets()
    poison = {i: {"fast": lambda v: v * 0.0, "ref": lambda v: v * 0.0}
              for i in range(3)}
    g = _variant_chain(3, variants=poison)
    ex, prog = _compiled_on(binding, g, "fast")   # dialect "ref"
    got = prog.run({0: (_x(),)})
    assert prog.stats["n_variant"] == 0
    assert results_bitwise_equal(ex.run_monolithic(g, {0: (_x(),)}), got)


def test_interpreter_path_stays_single_variant_oracle():
    binding = _three_targets()
    variants = {0: {"alt": lambda v: v * jnp.float32(100.0)}}
    g = _variant_chain(2, variants=variants)
    ex = ScheduleExecutor(pu_specs_for_targets(binding), targets=binding)
    got = ex.run_scheduled(g, {0: "alt", 1: "alt"}, {0: (_x(),)})
    assert results_bitwise_equal(ex.run_monolithic(g, {0: (_x(),)}), got)


def test_target_jit_policy_and_tolerance_gated_jit():
    binding = _three_targets()
    g = _variant_chain(4)
    # jit=False target: composed-Python, never jitted
    _, prog = _compiled_on(binding, g, "host")
    prog.run({0: (_x(),)})
    assert [s.mode for s in prog.segments] == [PYTHON]
    # jit=True target with declared tolerance: jit admitted and recorded
    _, prog = _compiled_on(binding, g, "fast")
    prog.run({0: (_x(),)})
    (seg,) = prog.segments
    assert seg.mode == JIT
    assert prog.stats["jit_verified"][seg.index] in ("bitwise", "tolerance")


_HOST = jax.devices("cpu")[0]


@pytest.mark.parametrize("make", [
    numpy_eager, xla_cpu, pallas_interpret,
    lambda: device_target(_HOST),
    lambda: device_target(_HOST, dialect="pallas", interpret=True),
], ids=["numpy-eager", "xla-cpu", "pallas-interpret", "device", "device-pallas"])
def test_builtin_target_outputs_land_on_declared_device(make):
    """Every output of a lane lives where its target says: on
    ``target.device``, or on the host for a target that declares none."""
    tgt = make()
    variants = {i: {"pallas": lambda v: jnp.tanh(v * jnp.float32(1.0))}
                for i in range(3)}
    g = _variant_chain(3, variants=variants)
    _, prog = _compiled_on({"L": tgt}, g, "L")
    prog.run({0: (_x(),)})                 # cold: probe + settle
    got = prog.run({0: (_x(),)})           # warm: the served path
    want = tgt.device if tgt.device is not None else _HOST
    for out in got.values():
        assert isinstance(out, (np.ndarray, jax.Array))
        if isinstance(out, jax.Array):
            assert out.devices() == {want}
        else:
            assert tgt.device is None      # host arrays only on host lanes
    if tgt.jit:
        assert [s.mode for s in prog.segments] == [JIT]


def test_jit_hoisting_constants_keeps_weights_out_of_the_program():
    """Closed-over weights are arguments of the jitted program, placed on
    the given device, not constants baked into it; values are those of
    ``jax.jit``."""
    import warnings
    from repro.core.hoist import jit_hoisting_constants
    w = jnp.arange(64 * 64, dtype=jnp.float32).reshape(64, 64) / 4096.0

    def f(x, scale):
        return {"y": jnp.tanh(x @ w) * scale}

    x = jnp.linspace(-1.0, 1.0, 8 * 64, dtype=jnp.float32).reshape(8, 64)
    prev = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1024)
    try:
        with warnings.catch_warnings(record=True) as baked:
            warnings.simplefilter("always")
            want = jax.jit(f)(x, 2.0)
        with warnings.catch_warnings(record=True) as hoisted:
            warnings.simplefilter("always")
            run = jit_hoisting_constants(f, _HOST)
            got = run(x, 2.0)
            again = run(x + 1.0, 3.0)        # same signature: no retrace
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", prev)
    captured = "constants were captured"
    assert any(captured in str(m.message) for m in baked)
    assert not any(captured in str(m.message) for m in hoisted)
    assert np.array_equal(np.asarray(got["y"]), np.asarray(want["y"]))
    assert got["y"].devices() == {_HOST}
    assert np.array_equal(np.asarray(again["y"]),
                          np.asarray(jax.jit(f)(x + 1.0, 3.0)["y"]))


@pytest.mark.parametrize("flag", [True, False])
def test_target_interpret_setting_reaches_the_kernels(flag):
    """``Target.interpret`` governs how the served Pallas payloads run,
    on the compiled path and in the profiler alike."""
    from repro.kernels.ops import default_interpret
    seen = []

    def spy(v):
        seen.append(default_interpret())   # read at trace time, as kernels do
        return jnp.tanh(v * jnp.float32(1.0))

    g = _variant_chain(1, variants={0: {"pallas": spy}})
    tgt = device_target(_HOST, dialect="pallas", interpret=flag)
    _, prog = _compiled_on({"L": tgt}, g, "L")
    prog.run({0: (_x(),)})
    MeasuredProfiler(warmup=0, iters=1, targets={"L": tgt}).profile(g)
    assert seen and set(seen) == {flag}
    assert default_interpret() is True     # restored outside the lane


def test_targetless_segments_remain_strictly_bitwise():
    """The PR 5 analytic path must not inherit tolerance-gated jit."""
    from repro.core.laneprogram import Segment
    seg = Segment(index=0, lane="CPU")
    seg.fns = [lambda e, v: v + jnp.float32(1e-7)]
    seg.argspecs = [[("f", 0)]]
    seg.flat_refs = [(0, 0)]
    assert seg.target is None and seg.jit_verified is None


# ---------------------------------------------------------------------------
# stale-variant invalidation (PR 5 op.fn rule extended to variant tables)
# ---------------------------------------------------------------------------


def test_variant_rebind_invalidates_scheduled_program():
    binding = _three_targets()
    variants = {1: {"alt": lambda v: jnp.tanh(v * jnp.float32(1.01))}}
    g = _variant_chain(3, variants=variants)
    ex, prog = _compiled_on(binding, g, "alt")
    prog.run({0: (_x(),)})
    assert prog.payloads_current()
    g.ops[1].variants["alt"] = lambda v: jnp.tanh(v * jnp.float32(1.02))
    assert not prog.payloads_current()
    # adding a brand-new dialect entry also invalidates
    g2 = _variant_chain(3, variants=variants)
    _, prog2 = _compiled_on(binding, g2, "alt")
    prog2.run({0: (_x(),)})
    g2.ops[0].variants["numpy"] = lambda v: np.tanh(v)
    assert not prog2.payloads_current()


def test_variant_rebind_invalidates_concurrent_program():
    binding = _three_targets()
    variants = {0: {"alt": lambda v: jnp.tanh(v * jnp.float32(1.0))}}
    g0 = _variant_chain(2, variants=variants)
    g1 = _variant_chain(2)
    ex = ScheduleExecutor(pu_specs_for_targets(binding), targets=binding)
    sched = ConcurrentSchedule(
        steps=[ConcurrentStep(ops=(0, 0), pus=("alt", "fast"), cost=1.0),
               ConcurrentStep(ops=(1, 1), pus=("alt", "fast"), cost=1.0)],
        latency=2.0, energy=2.0, objective="latency", mode="aligned")
    prog = ex.compile_concurrent([g0, g1], sched)
    prog.run([{0: (_x(),)}, {0: (_x(),)}])
    assert prog.payloads_current()
    g0.ops[0].variants["alt"] = lambda v: jnp.tanh(v)
    assert not prog.payloads_current()


def test_orchestrator_recompiles_after_variant_rebind():
    binding = _three_targets()
    variants = {1: {"alt": lambda v: jnp.tanh(v * jnp.float32(1.01))}}
    g = _variant_chain(3, variants=variants)
    table = MeasuredProfiler(warmup=1, iters=2, targets=binding).profile(g)
    orch = Orchestrator(table, targets=binding)
    plan = orch.plan(orch.register(g))
    inputs = {0: (_x(),)}
    orch.execute(plan, inputs)
    assert orch.stats["program_misses"] == 1
    orch.execute(plan, inputs)
    assert orch.stats["program_hits"] == 1
    g.ops[1].variants["alt"] = lambda v: jnp.tanh(v * jnp.float32(1.02))
    orch.execute(plan, inputs)           # stale: must recompile, not serve
    assert orch.stats["program_misses"] == 2


# ---------------------------------------------------------------------------
# fenced timing (satellite: async-skew regression)
# ---------------------------------------------------------------------------


def test_measure_callable_fences_async_dispatch():
    """A jitted payload must be timed to completion, not to dispatch:
    unfenced timing of a chained 512x512 matmul reports ~dispatch cost
    (tens of us); fenced timing cannot."""
    a = jnp.ones((512, 512), jnp.float32) * 0.01

    def payload(x):
        for _ in range(8):
            x = x @ x + x
        return x

    m = measure_callable_stats(payload, (a,), warmup=1, iters=3, jit=True)
    assert m.median >= 1e-4          # dispatch alone is ~1e-5
    assert m.best <= m.median <= max(m.times)
    assert len(m.times) == 3
    assert float(m) == m.median and m.spread >= 0.0
    assert measure_callable(payload, (a,), warmup=1, iters=2) > 0.0


def test_measurement_reports_median_and_best():
    m = Measurement(median=2.0, best=1.0, times=(1.0, 2.0, 3.0))
    assert m.spread == 2.0 and float(m) == 2.0


def test_measure_callable_forces_warmup_before_timing():
    """warmup=0 still compiles before the timed loop: compilation time
    must never land in the measured median."""
    calls = []

    def payload(x):
        calls.append(1)      # traced once per compilation
        return x * 2.0

    measure_callable_stats(payload, (jnp.ones((4,)),), warmup=0, iters=2)
    assert len(calls) == 1   # compiled during (forced) warmup, then cached


# ---------------------------------------------------------------------------
# per-target measured profiling
# ---------------------------------------------------------------------------


@pytest.mark.backend
def test_profiler_measures_every_op_on_every_target():
    binding = _three_targets()
    g = _variant_chain(3)
    table = MeasuredProfiler(warmup=1, iters=2, targets=binding).profile(g)
    assert list(table.pus) == list(binding)
    for i in range(3):
        for lane, tgt in binding.items():
            e = table.get(i, lane)
            assert e is not None and e.kernel > 0
            assert e.dispatch == tgt.dispatch_s
            assert e.h2d == tgt.handoff_s
    meta = table.meta
    assert set(meta["measurements"]) == {(i, lane) for i in range(3)
                                         for lane in binding}
    assert meta["profile_failures"] == {}
    assert meta["targets"] == {lane: t.name for lane, t in binding.items()}
    m = meta["measurements"][(0, "host")]
    assert m["best"] <= m["median"] and m["spread"] >= 0.0


@pytest.mark.backend
def test_profiler_omits_cell_on_target_failure():
    binding = _three_targets()
    g = _variant_chain(3)

    def only_eager(v):
        if isinstance(jnp.asarray(v), jax.core.Tracer):
            raise RuntimeError("no tracing here")
        return np.tanh(np.asarray(v))

    g.ops[1].fn = only_eager     # fails under jit targets only
    table = MeasuredProfiler(warmup=1, iters=1, targets=binding).profile(g)
    assert table.get(1, "fast") is None          # jit target: cell omitted
    assert table.get(1, "host") is not None      # eager target: fine
    failures = table.meta["profile_failures"]
    assert (1, "fast") in failures
    with pytest.raises(RuntimeError, match="o1.*fast"):
        MeasuredProfiler(warmup=1, iters=1, targets=binding,
                         strict=True).profile(g)


@pytest.mark.backend
def test_profiler_respects_unsupported_on_and_anchors_payload_less_ops():
    binding = _three_targets()
    g = _variant_chain(3)
    g.ops[0].meta["unsupported_on"] = ("host",)
    del g.ops[2].meta["example_inputs"]          # no example: analytic
    table = MeasuredProfiler(warmup=1, iters=1, targets=binding).profile(g)
    assert table.get(0, "host") is None
    assert table.get(0, "fast") is not None
    fallback = set(table.meta["analytic_fallback"])
    assert fallback == {(2, lane) for lane in binding}
    for lane in binding:
        assert table.get(2, lane) is not None


@pytest.mark.backend
def test_per_target_cells_differ_between_eager_and_jit():
    """The whole point: one op, different measured numbers per backend."""
    binding = _three_targets()
    g = _variant_chain(2)
    table = MeasuredProfiler(warmup=1, iters=3, targets=binding).profile(g)
    kernels = {lane: table.get(0, lane).kernel for lane in binding}
    assert len({round(v, 9) for v in kernels.values()}) > 1
