"""Profiler (Algorithm 1, Stage 1).

Two complementary paths fill the same ``CostTable``:

* ``AnalyticProfiler`` — per-PU analytic cost models (``EdgeSoCCostModel``),
  used when the target PUs don't physically exist in this container.
* ``MeasuredProfiler`` — wall-clock measurement of each fused operator as a
  standalone jitted sub-model on the host backend (the paper's
  extract-and-measure flow: 20 warm-up + 200 measurement iterations,
  here reduced for CI budgets).  Host measurements anchor the CPU column;
  accelerator columns are derived by the analytic PU ratios, mirroring how
  the paper's offline profiling would populate the table on real silicon.

``trace_fused_ops`` extracts a fused-operator graph from an arbitrary JAX
callable via its jaxpr, applying a backend-compiler-like fusion rule
(elementwise/reduction ops fuse into the preceding anchor op, the paper's
"Conv-BN-ReLU" granularity).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np

_log = logging.getLogger(__name__)

from .costmodel import CostEntry, CostTable, EdgeSoCCostModel, PUSpec
from .hoist import jit_hoisting_constants
from .op import FusedOp, OpGraph

# jaxpr primitive -> op kind classification
_ANCHOR_KINDS: dict[str, str] = {
    "dot_general": "matmul",
    "conv_general_dilated": "conv2d",
    "cumsum": "cumsum",
    "cumlogsumexp": "cumsum",
    "scan": "scan",
    "while": "scan",
    "gather": "gather",
    "scatter": "scatter",
    "scatter-add": "scatter",
    "scatter_add": "scatter",
    "fft": "rdft",
    "sort": "gather",
    "argmax": "gather",
    "top_k": "gather",
    "dynamic_slice": "gather",
    "dynamic_update_slice": "scatter",
}
_ELTWISE = {
    "add", "sub", "mul", "div", "max", "min", "exp", "log", "tanh",
    "logistic", "rsqrt", "sqrt", "pow", "integer_pow", "neg", "sign",
    "abs", "erf", "select_n", "clamp", "convert_element_type", "and",
    "or", "xor", "not", "lt", "le", "gt", "ge", "eq", "ne", "squeeze",
    "expand_dims", "cos", "sin", "floor", "ceil", "round", "stop_gradient",
    "copy", "real", "imag", "complex", "conj",
}
_REDUCE = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
           "argmin", "reduce_and", "reduce_or", "softmax"}
_LAYOUT = {"reshape", "transpose", "broadcast_in_dim", "concatenate",
           "slice", "rev", "pad", "iota", "split"}


def _classify(prim_name: str) -> str | None:
    if prim_name in _ANCHOR_KINDS:
        return _ANCHOR_KINDS[prim_name]
    if prim_name in _ELTWISE:
        return "eltwise"
    if prim_name in _REDUCE:
        return "reduce"
    if prim_name in _LAYOUT:
        return "layout"
    return None


def trace_fused_ops(fn: Callable, *example_args, name: str = "model") -> OpGraph:
    """Extract a fused-operator chain from a JAX callable.

    Fusion rule: anchor ops (GEMM/conv/scan/gather/fft/...) start a new
    fused operator; elementwise / reduction / layout ops fuse into the
    current one.  The result is a sequential chain in program order — the
    granularity the paper's NPU PERF_COUNT decomposition yields.
    """
    jaxpr = jax.make_jaxpr(fn)(*example_args)
    fused: list[FusedOp] = []
    cur_extra_flops = 0.0
    cur_extra_bytes = 0.0

    def shape_of(v) -> tuple[int, ...]:
        aval = v.aval
        return tuple(int(d) for d in getattr(aval, "shape", ()) or ())

    def dtype_bytes_of(v) -> int:
        aval = v.aval
        dt = getattr(aval, "dtype", None)
        return int(np.dtype(dt).itemsize) if dt is not None else 2

    def walk(jp) -> None:
        nonlocal cur_extra_flops, cur_extra_bytes
        for eqn in jp.eqns:
            pname = eqn.primitive.name
            # recurse into pjit/closed calls (control flow like scan/while
            # stays a single anchor op — it IS the fused recurrence kernel)
            if pname in ("pjit", "closed_call", "custom_jvp_call",
                         "custom_vjp_call", "custom_vjp_call_jaxpr",
                         "remat", "checkpoint"):
                inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                if inner is not None:
                    walk(inner.jaxpr if hasattr(inner, "jaxpr") else inner)
                    continue
            kind = _classify(pname)
            outv = eqn.outvars[0] if eqn.outvars else None
            out_shape = shape_of(outv) if outv is not None else ()
            dtb = dtype_bytes_of(outv) if outv is not None else 2
            in_shapes = tuple(shape_of(v) for v in eqn.invars
                              if hasattr(v, "aval"))
            if kind in ("eltwise", "reduce", "layout", None):
                # fuse into current op
                n_out = float(np.prod(out_shape)) if out_shape else 0.0
                cur_extra_flops += n_out
                cur_extra_bytes += n_out * dtb
                continue
            op = FusedOp(
                name=f"{name}.{len(fused)}.{pname}", kind=kind,
                in_shapes=in_shapes, out_shape=out_shape, dtype_bytes=dtb,
            )
            if fused and (cur_extra_flops or cur_extra_bytes):
                fused[-1].flops += cur_extra_flops
                fused[-1].bytes_moved += cur_extra_bytes
            cur_extra_flops = cur_extra_bytes = 0.0
            fused.append(op)
    walk(jaxpr.jaxpr)
    if fused and (cur_extra_flops or cur_extra_bytes):
        fused[-1].flops += cur_extra_flops
        fused[-1].bytes_moved += cur_extra_bytes
    if not fused:
        fused = [FusedOp(name=f"{name}.all", kind="other", out_shape=(1,))]
    return OpGraph(fused, edges=None)


# ---------------------------------------------------------------------------
# Wall-clock measurement
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Measurement:
    """One payload's timing distribution: ``median`` (the robust number
    the cost table consumes), ``best`` (the min — what a noiseless
    machine would report), and the raw ``times`` so jitter is never
    hidden by a single scalar."""

    median: float
    best: float
    times: tuple[float, ...]

    @property
    def spread(self) -> float:
        """max/best - 1: the visible jitter of this measurement."""
        return (max(self.times) / self.best - 1.0) if self.best > 0 else 0.0

    def __float__(self) -> float:
        return self.median


def measure_callable_stats(fn: Callable, args: Sequence[Any], *,
                           warmup: int = 3, iters: int = 10,
                           jit: bool = True,
                           device: Any = None) -> Measurement:
    """Wall-clock :class:`Measurement` of ``fn(*args)``.

    JAX dispatch is **asynchronous**: a call returns future-backed arrays
    long before the computation finishes, so every timed iteration (and
    every warmup) is fenced with ``jax.block_until_ready`` on the actual
    output pytree — without the fence a jitted payload times as ~0 (the
    dispatch cost alone).  ``jit=False`` measures the payload eagerly
    (still fenced — eager JAX is async too), which is what non-jitting
    targets (NumPy/eager backends) execute; ``device`` pins the inputs
    (and, jitted, the arrays ``fn`` closes over) with ``jax.device_put``
    first so transfers are not billed to the kernel.
    """
    if device is not None:
        args = tuple(jax.device_put(a, device) for a in args)
    run = jit_hoisting_constants(fn, device) if jit else fn
    for _ in range(max(warmup, 1)):   # at least once: trigger compilation
        jax.block_until_ready(run(*args))
    ts = []
    for _ in range(max(iters, 1)):
        t0 = time.perf_counter()
        out = run(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return Measurement(median=float(np.median(ts)), best=float(min(ts)),
                       times=tuple(ts))


def measure_callable(fn: Callable, args: Sequence[Any], *, warmup: int = 3,
                     iters: int = 10, jit: bool = True,
                     device: Any = None) -> float:
    """Median wall-clock seconds of ``fn(*args)`` (blocked until ready).
    Scalar form of :func:`measure_callable_stats`."""
    return measure_callable_stats(fn, args, warmup=warmup, iters=iters,
                                  jit=jit, device=device).median


class AnalyticProfiler:
    """Fill a CostTable from analytic PU models (no hardware needed)."""

    def __init__(self, model: EdgeSoCCostModel | None = None):
        self.model = model or EdgeSoCCostModel()

    def profile(self, graph: OpGraph) -> CostTable:
        return self.model.build_table(graph)


class MeasuredProfiler:
    """Fill the cost table from real wall-clock measurements.

    Two modes share the constructor:

    * **CPU-anchored (default, ``targets=None``).**  The paper's
      offline-profiling stand-in when the PUs don't physically exist:
      measure each payload once on the host, anchor the CPU column, and
      derive the accelerator columns via the analytic per-PU ratios.
    * **Per-target (``targets={lane: Target}``).**  The real loop: each
      op's resolved payload variant (``op.payload_for(target.dialect)``)
      is measured *on every bound backend* under that target's jit
      policy and device placement, and each measurement lands directly
      in that lane's column (``kernel`` = median; ``dispatch``/
      ``h2d``/``d2h``/``power`` from the target's declared pricing).
      Full distributions go to ``table.meta["measurements"]``
      (``{(op, lane): {"median", "best", "spread"}}``).  Payload-less
      ops fall back to the analytic CPU estimate on every lane (noted
      in ``table.meta["analytic_fallback"]``); an op a target declares
      in ``meta["unsupported_on"]`` gets no cell on that lane.

    For ops that carry an ``fn`` payload and example inputs in
    ``op.meta['example_inputs']`` we measure; otherwise we fall back to the
    analytic CPU estimate.  A measurement that *fails* (payload raises,
    un-jittable closure, ...) is never silently swallowed: each failure is
    logged, collected into the returned table's
    ``meta["profile_failures"]`` (``{op index: "ExcType: message"}`` in
    CPU-anchored mode, ``{(op index, lane): ...}`` per-target — where a
    failed cell is *omitted*, i.e. the op is unsupported on that
    backend), and under ``strict=True`` re-raised with the op named
    instead of falling back.
    """

    def __init__(self, model: EdgeSoCCostModel | None = None,
                 warmup: int = 2, iters: int = 5, strict: bool = False,
                 targets=None):
        from .targets import resolve_targets
        self.model = model or EdgeSoCCostModel()
        self.warmup = warmup
        self.iters = iters
        self.strict = strict
        self.targets = resolve_targets(targets)

    def profile(self, graph: OpGraph,
                strict: bool | None = None) -> CostTable:
        strict = self.strict if strict is None else strict
        if self.targets is not None:
            return self._profile_targets(graph, strict)
        failures: dict[int, str] = {}
        table = CostTable(list(self.model.pus))
        table.meta["profile_failures"] = failures
        for i, op in enumerate(graph.ops):
            analytic = {name: self.model.entry(op, pu)
                        for name, pu in self.model.pus.items()}
            cpu_est = analytic.get("CPU")
            measured = None
            if op.fn is not None and "example_inputs" in op.meta:
                try:
                    measured = measure_callable(
                        op.fn, op.meta["example_inputs"],
                        warmup=self.warmup, iters=self.iters)
                except Exception as e:
                    if strict:
                        raise RuntimeError(
                            f"MeasuredProfiler: measuring op {i} "
                            f"({op.name!r}, kind {op.kind!r}) failed"
                        ) from e
                    failures[i] = f"{type(e).__name__}: {e}"
                    _log.warning(
                        "MeasuredProfiler: op %d (%s) measurement failed "
                        "(%s); falling back to the analytic CPU estimate",
                        i, op.name, failures[i])
                    measured = None
            scale = (measured / cpu_est.kernel
                     if (measured and cpu_est and cpu_est.kernel > 0) else 1.0)
            for name, e in analytic.items():
                if e is None:
                    continue
                table.set(i, name, CostEntry(
                    kernel=e.kernel * scale, dispatch=e.dispatch,
                    h2d=e.h2d, d2h=e.d2h, power=e.power))
        return table

    # -- per-target mode ----------------------------------------------------
    def _analytic_anchor(self, op: FusedOp) -> CostEntry | None:
        """Analytic estimate for payload-less ops: the model's CPU spec
        (any host spec if "CPU" is absent)."""
        pu = self.model.pus.get("CPU")
        if pu is None:
            pu = next(iter(self.model.pus.values()))
        return self.model.entry(op, pu)

    def _profile_targets(self, graph: OpGraph, strict: bool) -> CostTable:
        """Measure every op on every bound backend; see the class docs."""
        targets = self.targets
        failures: dict[tuple[int, str], str] = {}
        stats: dict[tuple[int, str], dict] = {}
        fallback: list[tuple[int, str]] = []
        table = CostTable(list(targets))
        table.meta["profile_failures"] = failures
        table.meta["measurements"] = stats
        table.meta["analytic_fallback"] = fallback
        table.meta["targets"] = {lane: t.name for lane, t in targets.items()}
        for i, op in enumerate(graph.ops):
            unsupported = op.meta.get("unsupported_on", ())
            for lane, tgt in targets.items():
                if lane in unsupported or tgt.name in unsupported:
                    continue
                fn = tgt.bind(op.payload_for(tgt.dialect))
                if fn is None or "example_inputs" not in op.meta:
                    est = self._analytic_anchor(op)
                    if est is None:
                        continue
                    fallback.append((i, lane))
                    table.set(i, lane, CostEntry(
                        kernel=est.kernel, dispatch=tgt.dispatch_s,
                        h2d=tgt.handoff_s, d2h=tgt.handoff_s,
                        power=tgt.power_compute))
                    continue
                try:
                    m = measure_callable_stats(
                        fn, op.meta["example_inputs"],
                        warmup=self.warmup, iters=self.iters,
                        jit=tgt.jit, device=tgt.device)
                except Exception as e:
                    if strict:
                        raise RuntimeError(
                            f"MeasuredProfiler: measuring op {i} "
                            f"({op.name!r}, kind {op.kind!r}) on target "
                            f"{tgt.name!r} (lane {lane!r}) failed") from e
                    failures[(i, lane)] = f"{type(e).__name__}: {e}"
                    _log.warning(
                        "MeasuredProfiler: op %d (%s) failed on target %s "
                        "(%s); cell omitted — op unsupported on this lane",
                        i, op.name, tgt.name, failures[(i, lane)])
                    continue
                stats[(i, lane)] = {"median": m.median, "best": m.best,
                                    "spread": m.spread}
                table.set(i, lane, CostEntry(
                    kernel=m.median, dispatch=tgt.dispatch_s,
                    h2d=tgt.handoff_s, d2h=tgt.handoff_s,
                    power=tgt.power_compute))
        return table
