"""Compiled lane programs: the segment-fused execution path.

The interpreter in :mod:`repro.core.executor` dispatches every op as a
Python closure call plus a ``threading.Event`` wait/set — faithful to the
command-queue model, but after the planning side went ms-scale that per-op
overhead *is* the runtime cost the paper says the orchestrator avoids
("the output schedule ... is applied directly by the execution
orchestrator").  A :class:`LaneProgram` removes it in two moves:

* **Segment partitioning.**  Each PU lane's FIFO queue is cut into
  *maximal contiguous same-lane segments*: a new segment starts only at a
  cross-lane boundary (an op whose predecessor ran on another lane — the
  D2H/H2D handoff points), at a request switch on a shared lane, or at a
  co-scheduled concurrent step (co-scheduled ops stay individually
  dispatched so the granularity the contention laws priced is preserved —
  they become single-op *barrier* segments).  The boundary test reads the
  op graph's true predecessor sets, so for DAG schedules (lane queues
  from ``ScheduleExecutor.compile_dag``) cuts land exactly at cross-lane
  dependency *edges*: two independent subgraphs mapped to different
  lanes fuse into segments that overlap with no synchronisation at all.  Synchronisation collapses
  from one event per op to one event per segment, waited on only across
  the boundary cuts.

* **Segment fusion.**  Each segment's op payloads compose into one
  callable.  On the first run the segment executes composed-but-eager
  (the *probe*), then attempts ``jax.jit`` of the composition and keeps
  the jitted version **only if its outputs are bitwise identical** to
  eager execution — checked on the probe inputs and on a perturbed
  same-shape input set, so a value coincidence cannot certify it —
  payloads that are not JAX-traceable (NumPy closures, ``None``
  payloads) or whose dtypes a jit round-trip would alter fall back to the
  composed-Python form automatically.  Either way the per-op event churn
  is gone; the jitted form additionally collapses a whole segment into a
  single XLA dispatch.

Programs are built once per (plan, input-signature) by
``ScheduleExecutor.compile_scheduled`` / ``compile_dag`` /
``compile_concurrent`` and cached
by ``Orchestrator.execute`` (see the ``program_for`` hook), mirroring the
plan cache: a repeat ``execute`` call skips partitioning and compilation
entirely.  The per-op interpreter remains the bitwise-equivalence oracle
(``Orchestrator.execute(..., compile=False)``).

A program's first ``run`` mutates segment state (probe → jit/python mode
settling), so a single program must not be run from two threads
concurrently until warm; the orchestrator's cache serialises this in
practice (one program per plan/input key).

Op payloads must be **pure** on this path: compile verification executes
each payload a few extra times (the jit probe, plus an eager + jitted
pass over perturbed same-shape inputs), and warm runs replay the fused
callable — a payload with internal state (counters, cache mutation,
appended buffers) would advance differently than under the per-op
interpreter.  Stateful or side-effecting payloads belong on the
interpreter oracle (``Orchestrator.execute(..., compile=False)``).
Purity is also what makes the fault runtime's *segment-granularity
retry* safe (see :mod:`repro.core.faults`): a transiently-failed
segment writes no results and simply re-executes; every cross-lane wait
in ``run`` is bounded by the watchdog budget; and a permanent PU loss
surfaces as :class:`~repro.core.errors.PULostError` carrying the
frontier of completed segments for orchestrator-level re-plan + resume.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np

from repro import telemetry
from repro.fault.manager import RecoverableError

from .errors import ExecutionError, PULostError
from .faults import (_JOIN_GRACE, ExecutionPolicy, FaultPlan, RunContext,
                     _Aborted, run_with_retries)
from .hoist import jit_hoisting_constants
from .op import OpGraph
from .targets import variant_tolerance

# segment execution modes
COLD = "cold"        # not yet run: next run probes eagerly, then compiles
JIT = "jit"          # fused callable is jitted (bitwise-verified vs probe)
PYTHON = "python"    # composed-Python fallback (non-traceable payloads)


def _bitwise_equal(a, b) -> bool:
    """True iff two payload outputs are bitwise identical (dtype, shape,
    and raw bytes — ``allclose`` is deliberately not used here)."""
    if a is None or b is None:
        return a is None and b is None
    xa, xb = np.asarray(a), np.asarray(b)
    return (xa.dtype == xb.dtype and xa.shape == xb.shape
            and xa.tobytes() == xb.tobytes())


def _perturb(x):
    """A same-shape/dtype input with different float values, for the
    second leg of compile verification (non-floats pass through)."""
    a = np.asarray(x)
    if np.issubdtype(a.dtype, np.floating):
        return ((a * np.asarray(0.7371, a.dtype)
                 + np.asarray(0.1113, a.dtype)).astype(a.dtype, copy=False))
    return x


def results_bitwise_equal(a: Mapping[int, Any], b: Mapping[int, Any]) -> bool:
    """Bitwise comparison of two executor results dicts (the strict form
    of ``ScheduleExecutor.outputs_close``: dtypes and bytes must match)."""
    if set(a) != set(b):
        return False
    return all(_bitwise_equal(a[k], b[k]) for k in a)


def _within_tolerance(ref, got, target) -> bool:
    """Variant-vs-reference closeness at the target's per-dtype tolerance
    bucket (non-float outputs must be bitwise; shape/dtype must match)."""
    if ref is None or got is None:
        return ref is None and got is None
    a, b = np.asarray(ref), np.asarray(got)
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    if a.dtype.kind not in "fc":
        return a.tobytes() == b.tobytes()
    atol, rtol = (target.tolerance(a.dtype) if target is not None
                  else variant_tolerance(a.dtype))
    if atol == 0.0 and rtol == 0.0:
        return a.tobytes() == b.tobytes()
    return bool(np.allclose(a.astype(np.float64), b.astype(np.float64),
                            atol=atol, rtol=rtol))


def _tolerance_miss(ref_outs, got, target) -> str:
    """Why ``got`` fails :func:`_within_tolerance` against ``ref_outs``:
    the first output outside the bucket, with its largest error."""
    if len(got) != len(ref_outs):
        return f"{len(got)} outputs, expected {len(ref_outs)}"
    for t, (ref, out) in enumerate(zip(ref_outs, got)):
        if _within_tolerance(ref, out, target):
            continue
        a, b = np.asarray(ref), np.asarray(out)
        if a.shape != b.shape or a.dtype != b.dtype:
            return (f"output {t} is {b.dtype}{list(b.shape)}, expected "
                    f"{a.dtype}{list(a.shape)}")
        err = np.abs(a.astype(np.float64) - b.astype(np.float64))
        atol, rtol = (target.tolerance(a.dtype) if target is not None
                      else variant_tolerance(a.dtype))
        return (f"output {t} max_abs_err={err.max():.3e} beyond "
                f"(atol={atol:g}, rtol={rtol:g})")
    return "outputs differ"


@dataclasses.dataclass
class Segment:
    """A maximal run of same-lane ops fused into one callable.

    ``items`` are ``(request, op)`` pairs in lane-queue order; ``deps``
    are indices of segments on *other* lanes whose outputs this segment
    reads (same-lane predecessors are implicit in FIFO order).  A
    ``barrier`` segment holds exactly one co-scheduled concurrent-step op
    and is never fused with its neighbours.

    When the lane is bound to a :class:`~repro.core.targets.Target`,
    ``fns`` still holds the reference payloads (the probe oracle) and
    ``var_fns`` the target-dialect variants; the cold run verifies the
    variant composition against the reference outputs (bitwise, else the
    target's per-dtype tolerance) before it is ever served, and the
    target's ``jit``/``device``/``interpret`` policy governs compilation,
    input placement and how its Pallas kernels run.  ``verified`` records
    the outcome (``"bitwise"`` / ``"tolerance"`` / ``"rejected"`` /
    ``"rejected: <first output outside the bucket>"`` / ``"error: <type>:
    <message>"``); ``jit_verified`` records which rule admitted the jit,
    or why it was refused (``"rejected: ..."`` / ``"error: ..."``, the
    segment then runs composed-Python).
    """

    index: int
    lane: str
    barrier: bool = False
    target: Any = None
    items: list[tuple[int, int]] = dataclasses.field(default_factory=list)
    fns: list[Callable | None] = dataclasses.field(default_factory=list)
    var_fns: list[Callable | None] | None = None
    use_variant: bool = False
    verified: str | None = None
    jit_verified: str | None = None
    deps: list[int] = dataclasses.field(default_factory=list)
    # results of other segments this segment reads, in flat order
    flat_refs: list[tuple[int, int]] = dataclasses.field(default_factory=list)
    # per item: arg sources after the op's external inputs — ("f", j) is
    # flat input j (another segment's output), ("o", t) is item t's output
    argspecs: list[list[tuple[str, int]]] = dataclasses.field(
        default_factory=list)
    # one descriptive wait label per entry of ``deps``, precomputed at
    # compile time so the watchdog can name both sides of a hung handoff
    # without per-run string formatting
    dep_whats: list[str] = dataclasses.field(default_factory=list)
    mode: str = COLD
    _jfn: Any = dataclasses.field(default=None, repr=False)

    # -- composition --------------------------------------------------------
    def _compose(self, fns: Sequence[Callable | None], flat: tuple,
                 ext_lists: tuple) -> tuple:
        """Run every op of the segment over a payload list.

        ``flat`` holds the cross-segment input values (in ``flat_refs``
        order), ``ext_lists`` the per-item external-input tuples.  Arg
        order per op matches the interpreter exactly: external inputs
        first, then predecessor outputs in ``graph.pred`` order.
        """
        outs: list[Any] = []
        for t, spec in enumerate(self.argspecs):
            fn = fns[t]
            if fn is None:
                outs.append(None)
                continue
            deps = tuple(flat[j] if kind == "f" else outs[j]
                         for kind, j in spec)
            outs.append(fn(*(tuple(ext_lists[t]) + deps)))
        return tuple(outs)

    def _composed(self, flat: tuple, ext_lists: tuple) -> tuple:
        """The reference composition (``op.fn`` payloads)."""
        return self._compose(self.fns, flat, ext_lists)

    def _composed_var(self, flat: tuple, ext_lists: tuple) -> tuple:
        """The target-dialect variant composition."""
        return self._compose(self.var_fns, flat, ext_lists)

    def _place(self, flat: tuple, ext_lists: tuple) -> tuple[tuple, tuple]:
        """Pin segment inputs to the bound target's device (identity when
        no target/device is bound)."""
        tgt = self.target
        if tgt is None or tgt.device is None:
            return flat, ext_lists
        def put(v):
            return jax.device_put(v, tgt.device)
        return (tuple(put(v) for v in flat),
                tuple(tuple(put(v) for v in e) for e in ext_lists))

    def _gather(self, results: Sequence[dict], ext: Sequence[dict]):
        flat = tuple(results[r][p] for r, p in self.flat_refs)
        ext_lists = tuple(tuple(ext[r].get(i, ())) for r, i in self.items)
        return flat, ext_lists

    def execute(self, results: Sequence[dict], ext: Sequence[dict]) -> None:
        flat, ext_lists = self._gather(results, ext)
        if self.mode == JIT:
            outs = self._jfn(flat, ext_lists)
        elif self.mode == PYTHON and self.use_variant:
            outs = self._composed_var(*self._place(flat, ext_lists))
        elif self.mode == COLD:
            # the reference probe runs where the lane runs
            flat, ext_lists = self._place(flat, ext_lists)
            outs = self._composed(flat, ext_lists)
            self._settle(flat, ext_lists, outs)
        else:
            outs = self._composed(flat, ext_lists)
        for (r, i), o in zip(self.items, outs):
            results[r][i] = o

    def _settle(self, flat, ext_lists, outs) -> None:
        """Cold-run settling.  ``outs`` are the eager *reference* outputs
        (they are what this cold run serves — a variant is never served
        unverified).  Order of business: probe-verify the target variant
        against them, then attempt jit compilation of whichever
        composition survived, honouring the target's jit policy."""
        self.mode = PYTHON
        tgt = self.target
        if self.var_fns is not None:
            probe = self._verify_variant(flat, ext_lists, outs)
            if probe is not None:          # variant accepted: serve it
                if tgt is None or tgt.jit:
                    self._jit_verify(self._composed_var, *probe)
                return
        if tgt is not None and not tgt.jit:
            return                          # eager-by-policy target
        self._maybe_compile(flat, ext_lists, outs)

    def _verify_variant(self, flat, ext_lists, ref_outs):
        """Probe the variant composition against the reference outputs.
        Accepts on bitwise equality, else on the target's per-dtype
        tolerance; rejection (or any execution error) drops ``var_fns``
        so the segment permanently serves the reference payloads.
        Returns ``(placed_flat, placed_ext, variant_outs)`` when the
        variant is accepted, else ``None``."""
        try:
            pflat, pext = self._place(flat, ext_lists)
            got = self._composed_var(pflat, pext)
        except Exception as e:
            self.verified = f"error: {type(e).__name__}: {e}"
            self.var_fns = None
            return None
        if len(got) == len(ref_outs) and all(
                _bitwise_equal(a, b) for a, b in zip(ref_outs, got)):
            self.verified = "bitwise"
        elif len(got) == len(ref_outs) and all(
                _within_tolerance(a, b, self.target)
                for a, b in zip(ref_outs, got)):
            self.verified = "tolerance"
        else:
            self.verified = ("rejected: "
                             + _tolerance_miss(ref_outs, got, self.target))
            self.var_fns = None
            return None
        self.use_variant = True
        return pflat, pext, got

    def _maybe_compile(self, flat, ext_lists, outs) -> None:
        """Probe-and-verify compilation of the *reference* composition:
        jit it and keep the jitted form only if its outputs match the
        eager probe bitwise — on the probe inputs AND on an independently
        perturbed same-shape input set, so a value coincidence on the
        probe (e.g. an FMA contraction that happens to round identically
        there) cannot certify a jit that diverges on later inputs.
        Anything else (trace failures on NumPy payloads, f64→f32 dtype
        drift under a jit round-trip, non-array outputs) keeps the
        Python form."""
        self.mode = PYTHON
        if any(fn is None for fn in self.fns):
            return
        self._jit_verify(self._composed, flat, ext_lists, outs)

    def _jit_verify(self, composed, flat, ext_lists, outs) -> None:
        """Shared jit probe for the reference and variant compositions:
        bitwise on the probe inputs and on a perturbed second leg, exactly
        the PR 5 rule.  A target that *declares* a tolerance
        (``Target.atol``/``rtol``) additionally accepts a jit whose
        outputs stay within that tolerance on both legs — XLA fusion
        reorders float accumulation, so an eager-vs-jit probe of e.g. a
        softmax composition is rarely bitwise; a declared-tolerance
        target says so in data rather than silently eating the ~100x
        eager fallback.  Targetless segments (the PR 5 analytic path)
        remain strictly bitwise.  The closed-over weights are arguments of the jitted program, not constants in it
        (:func:`~repro.core.hoist.jit_hoisting_constants`), placed on the
        target's device.  On success ``_jfn`` wraps the jitted callable
        with the target's device placement and ``mode`` flips to JIT;
        ``jit_verified`` records which rule admitted it."""
        if not all(isinstance(o, jax.Array) for o in outs):
            return
        tgt = self.target
        declared = tgt is not None and (tgt.atol or tgt.rtol)
        miss = []

        def admit(ref_o, got_o):
            if len(got_o) != len(ref_o):
                return None
            if all(_bitwise_equal(a, b) for a, b in zip(ref_o, got_o)):
                return "bitwise"
            if declared and all(_within_tolerance(a, b, tgt)
                                for a, b in zip(ref_o, got_o)):
                return "tolerance"
            miss.append(_tolerance_miss(ref_o, got_o, tgt) if declared
                        else "not bitwise, and no tolerance is declared")
            return None

        try:
            jfn = jit_hoisting_constants(
                composed, tgt.device if tgt is not None else None)
            how = admit(outs, tuple(jfn(flat, ext_lists)))
            if how is not None:
                flat2 = tuple(_perturb(v) for v in flat)
                ext2 = tuple(tuple(_perturb(v) for v in e)
                             for e in ext_lists)
                ref2 = tuple(composed(flat2, ext2))
                how2 = admit(ref2, tuple(jfn(flat2, ext2)))
                how = (None if how2 is None
                       else ("bitwise" if how == how2 == "bitwise"
                             else "tolerance"))
        except Exception as e:
            self.jit_verified = f"error: {type(e).__name__}: {e}"
            return
        if how is None:
            self.jit_verified = "rejected: " + (miss[-1] if miss else "")
        else:
            if self.target is not None and self.target.device is not None:
                self._jfn = lambda f, e: tuple(jfn(*self._place(f, e)))
            else:
                self._jfn = jfn
            self.jit_verified = how
            self.mode = JIT


class LanePool:
    """Persistent lane workers: one daemon thread + FIFO task queue per
    lane (the command-queue model, kept warm across runs so thread spawn
    cost never lands on the dispatch path).

    Threads are **daemon** deliberately: a payload that hangs in native
    code past the watchdog budget wedges its worker, and a non-daemon
    thread would then block interpreter exit forever (the
    ``ThreadPoolExecutor`` atexit-join behaviour this replaces).  The
    watchdog backstop drops the whole pool (``shutdown``) and the next
    run builds a fresh one; wedged daemon workers leak harmlessly.
    """

    def __init__(self, lanes: Sequence[str]):
        self._queues: dict[str, queue.SimpleQueue] = {}
        for pu in lanes:
            q: queue.SimpleQueue = queue.SimpleQueue()
            self._queues[pu] = q
            threading.Thread(target=self._worker, args=(q,),
                             name=f"lane-{pu}", daemon=True).start()

    @staticmethod
    def _worker(q: "queue.SimpleQueue") -> None:
        while True:
            task = q.get()
            if task is None:
                return
            fn, done = task
            try:
                fn()
            except BaseException:   # submitted fns do their own reporting
                pass
            finally:
                done.set()

    def submit(self, lane: str, fn: Callable[[], None]) -> threading.Event:
        """Enqueue ``fn`` on ``lane``; the returned event is set when it
        finishes (success or not — errors are the fn's job to record)."""
        done = threading.Event()
        self._queues[lane].put((fn, done))
        return done

    def shutdown(self, wait: bool = False) -> None:
        for q in self._queues.values():
            q.put(None)


class LaneProgram:
    """A compiled plan: per-lane segment lists + cross-lane handoff deps.

    Build with :func:`compile_lane_program` (or the ``ScheduleExecutor``
    ``compile_*`` wrappers); ``run(external_inputs)`` executes with one
    worker thread per lane and returns the same results shape as the
    interpreter (``run_scheduled`` for single-graph programs,
    ``run_concurrent`` for M-request programs).
    """

    def __init__(self, graphs: Sequence[OpGraph],
                 segments: list[Segment],
                 lane_segments: dict[str, list[Segment]],
                 single: bool):
        self.graphs = list(graphs)
        self.segments = segments
        self.lane_segments = lane_segments
        self.lanes = [pu for pu, segs in lane_segments.items() if segs]
        self.single = single
        self.n_requests = len(self.graphs)
        self.runs = 0
        # a program whose segment DAG (handoff deps + per-lane FIFO
        # order) admits exactly ONE topological order is inherently
        # serial: no two segments can ever overlap, so run() executes it
        # inline — no worker threads, no events at all.  Sequential
        # chains always qualify; programs with real co-execution
        # (parallel branches, concurrent requests) never do and keep the
        # lane workers (pooled persistently: thread spawn per run would
        # dwarf the dispatch overhead this path removes).
        self.serial_order = self._serial_order()
        self._pool: LanePool | None = None
        # identity snapshot of every covered op's fn + variant table,
        # taken at compile time (see payloads_current)
        self._payload_tokens: dict[tuple[int, int], tuple] = {
            (r, i): self.graphs[r].ops[i].payload_token()
            for seg in segments for (r, i) in seg.items}

    def payloads_current(self) -> bool:
        """True while every op's payload *and variant table* are still
        the ones baked in at compile time.  A caller that rebinds
        ``graph.ops[i].fn`` — or any entry of ``graph.ops[i].variants``
        — after compilation invalidates the program: the orchestrator
        checks this on every program-cache hit and recompiles on
        mismatch, so a stale fused callable (or a stale variant
        selection) is never served."""
        for (r, i), (fn0, var0) in self._payload_tokens.items():
            op = self.graphs[r].ops[i]
            if op.fn is not fn0:
                return False
            variants = op.variants
            if len(variants) != len(var0):
                return False
            for key, f in var0:
                if variants.get(key) is not f:
                    return False
        return True

    def close(self) -> None:
        """Release the persistent lane-worker pool (idempotent; a later
        ``run`` lazily recreates it).  Called on cache eviction so idle
        worker threads don't outlive the program's cache entry."""
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None

    def _serial_order(self) -> list[Segment] | None:
        n = len(self.segments)
        indeg = [0] * n
        succ: list[list[int]] = [[] for _ in range(n)]
        for s in self.segments:
            for d in s.deps:
                succ[d].append(s.index)
                indeg[s.index] += 1
        for segs in self.lane_segments.values():
            for a, b in zip(segs, segs[1:]):
                succ[a.index].append(b.index)
                indeg[b.index] += 1
        ready = [i for i in range(n) if indeg[i] == 0]
        order: list[int] = []
        while ready:
            if len(ready) > 1:
                return None            # two segments could co-execute
            u = ready.pop()
            order.append(u)
            for v in succ[u]:
                indeg[v] -= 1
                if indeg[v] == 0:
                    ready.append(v)
        return [self.segments[i] for i in order] if len(order) == n else None

    @property
    def stats(self) -> dict:
        """Structure + compilation summary (jit counts settle after the
        first ``run``; before it every segment reports ``cold``)."""
        modes = [s.mode for s in self.segments]
        return {
            "n_ops": sum(len(s.items) for s in self.segments),
            "n_segments": len(self.segments),
            "n_jitted": modes.count(JIT),
            "n_python": modes.count(PYTHON),
            "n_cold": modes.count(COLD),
            "n_barrier": sum(1 for s in self.segments if s.barrier),
            "n_variant": sum(1 for s in self.segments if s.use_variant),
            "variant_verified": {s.index: s.verified for s in self.segments
                                 if s.verified is not None},
            "jit_verified": {s.index: s.jit_verified for s in self.segments
                             if s.jit_verified is not None},
            "lane_targets": {s.lane: s.target.name for s in self.segments
                             if s.target is not None},
            "max_segment_ops": max((len(s.items) for s in self.segments),
                                   default=0),
            "serial": self.serial_order is not None,
            "runs": self.runs,
        }

    def _exec_segment(self, seg: Segment, results, ext,
                      run: RunContext | None) -> None:
        """Execute one segment under the fault runtime: injected faults
        fire per (request, op) item, transient failures retry the whole
        segment with backoff (payloads are pure on this path, and a
        failed ``execute`` writes no results, so re-execution is clean),
        and a jitted segment failing with a non-transient error falls
        back to its composed-eager form once — mirroring the
        compile-time probe fallback — before giving up.  ``run=None`` is
        the fault-free serial fast path (no injection, default retry
        policy)."""
        what = (f"segment {seg.index} on lane {seg.lane!r} "
                f"(ops {seg.items[0]}..{seg.items[-1]})")

        def attempt():
            if run is not None and run.faults is not None:
                for (r, i) in seg.items:
                    run.faults.fire(seg.lane, r, i, run)
            seg.execute(results, ext)

        r0, i0 = seg.items[0]
        if run is not None:
            run.current[seg.lane] = what
        try:
            run_with_retries(run, attempt, what,
                             lane=seg.lane, request=r0, op=i0)
        except (ExecutionError, RecoverableError):
            raise
        except Exception:
            if seg.mode != JIT:
                raise
            # jitted form failed eagerly-unseen (e.g. a donated-buffer or
            # tracing edge on later inputs): demote to composed-Python
            # and retry once, mirroring the probe's fallback rule
            seg.mode = PYTHON
            seg._jfn = None
            run_with_retries(run, attempt, what,
                             lane=seg.lane, request=r0, op=i0)
        finally:
            if run is not None:
                run.current.pop(seg.lane, None)

    def run(self, external_inputs=None, *,
            policy: ExecutionPolicy | None = None,
            faults: FaultPlan | None = None,
            estimate: float | None = None,
            completed=None,
            segment_timings: list | None = None):
        """Execute the program; same results shape as the interpreter.

        ``policy`` tunes the watchdog/retry runtime (``estimate`` — e.g.
        the plan's cost-model latency — scales the watchdog budget) and
        ``faults`` injects a scripted
        :class:`~repro.core.faults.FaultPlan`.  Every cross-lane wait is
        deadline-bounded; on a permanent PU loss the raised
        :class:`~repro.core.errors.PULostError` carries the execution
        frontier (results of every segment completed before the loss).

        ``completed`` seeds the results with an execution frontier (one
        ``{op: value}`` dict for single-graph programs, a sequence of
        them for M-request programs): a program compiled over a *window*
        of remaining ops (``compile_concurrent(..., completed=...)``)
        reads its cross-window inputs from the frontier instead of
        recomputing them.  ``segment_timings``, when a list, receives one
        ``(lane, items, wall_seconds)`` tuple per completed segment — the
        compiled path's advance-event / drift-measurement feed, mirroring
        the interpreter's ``op_timings``.
        """
        if self.single:
            ext = [dict(external_inputs or {})]
            seeds = [dict(completed or {})]
        else:
            ext_seq = list(external_inputs or [None] * self.n_requests)
            if len(ext_seq) != self.n_requests:
                raise ValueError(
                    f"program covers {self.n_requests} requests, got "
                    f"{len(ext_seq)} input mapping(s)")
            ext = [dict(e or {}) for e in ext_seq]
            seeds = [dict(c or {}) for c in
                     (completed or [None] * self.n_requests)]
        results: list[dict[int, Any]] = seeds

        def exec_seg(seg: Segment, run: RunContext | None) -> None:
            with telemetry.span("lane.segment", lane=seg.lane,
                                segment=seg.index) as sp:
                self._exec_segment(seg, results, ext, run)
            if segment_timings is not None:
                segment_timings.append(
                    (seg.lane, tuple(seg.items), sp.seconds))

        if self.serial_order is not None:
            # inherently serial: no cross-lane waits exist, so the
            # watchdog has nothing to bound — fault-free runs skip the
            # RunContext entirely (this is the warm fast path)
            run = (RunContext(policy, faults, estimate)
                   if faults is not None else None)
            try:
                for seg in self.serial_order:
                    exec_seg(seg, run)
            except PULostError as e:
                if e.partial is None:
                    e.partial = [dict(res) for res in results]
                raise
            self.runs += 1
            return results[0] if self.single else results

        run = RunContext(policy, faults, estimate)
        done = [threading.Event() for _ in self.segments]

        def release_all() -> None:
            for ev in done:
                ev.set()

        run.release = release_all

        def lane_worker(pu: str) -> None:
            try:
                for seg in self.lane_segments[pu]:
                    for d, dwhat in zip(seg.deps, seg.dep_whats):
                        if not done[d].is_set():
                            with telemetry.span("lane.wait", lane=pu, on=d):
                                run.wait(done[d], dwhat)
                    run.check_abort()
                    exec_seg(seg, run)
                    done[seg.index].set()
            except _Aborted:
                pass  # a peer already failed; unwind silently
            except BaseException as e:
                run.fail(e)

        if self._pool is None:
            self._pool = LanePool(self.lanes)
        tasks = [(pu, self._pool.submit(pu, lambda pu=pu: lane_worker(pu)))
                 for pu in self.lanes]
        for pu, task_done in tasks:
            if run.deadline is None:
                task_done.wait()
            elif not task_done.wait(
                    max(run.deadline - time.monotonic(), 0.0) + _JOIN_GRACE):
                # backstop: a payload the watchdog cannot interrupt wedged
                # this worker — drop the whole pool (daemon threads; the
                # next run builds a fresh one) and surface a typed timeout
                run.abort.set()
                release_all()
                self.close()
                raise run._timeout(f"lane worker {pu!r}")
        if run.errors:
            err = run.first_error()
            if isinstance(err, PULostError) and err.partial is None:
                err.partial = [dict(res) for res in results]
            raise err
        self.runs += 1
        return results[0] if self.single else results


def compile_lane_program(graphs: Sequence[OpGraph],
                         lane_items: Mapping[str, Sequence[tuple[int, int]]],
                         barriers: frozenset[tuple[int, int]] | set = frozenset(),
                         single: bool = False,
                         targets: Mapping[str, Any] | None = None
                         ) -> LaneProgram:
    """Partition per-lane op queues into segments and build the program.

    ``lane_items`` maps each PU lane to its FIFO queue of ``(request,
    op)`` pairs (already validated/ordered by the executor); ``barriers``
    are co-scheduled concurrent-step ops that must stay single-op
    segments.  Cut rules, applied walking each queue in order — a new
    segment starts when:

    * the op (or the previous op) is a barrier op,
    * the request changes (segments never span requests), or
    * any predecessor ran on a *different* lane (the handoff cut: waits
      happen only at segment starts, so a cross-lane input is only legal
      for a segment's first op).

    Same-lane predecessors never cut (earlier queue position ⇒ an earlier
    segment on the same FIFO lane ⇒ already complete).

    A predecessor absent from every lane queue is a *frontier* op (window
    programs over a partially-executed plan): it cuts like a cross-lane
    handoff and resolves as a flat input read from the ``completed``
    seeds at run time, with no segment dependency.

    ``targets`` optionally binds lane names to
    :class:`~repro.core.targets.Target`\\ s: a bound segment keeps the
    reference payloads as its probe oracle and additionally resolves the
    target dialect's variant payloads at compile time (served only after
    the cold-run verification — see :class:`Segment`).
    """
    lane_of: dict[tuple[int, int], str] = {}
    for pu, items in lane_items.items():
        for it in items:
            lane_of[it] = pu

    tmap = dict(targets or {})
    segments: list[Segment] = []
    lane_segments: dict[str, list[Segment]] = {pu: [] for pu in lane_items}
    seg_of: dict[tuple[int, int], Segment] = {}
    for pu, items in lane_items.items():
        cur: Segment | None = None
        for (r, i) in items:
            barrier = (r, i) in barriers
            cross = any(lane_of.get((r, p)) != pu
                        for p in graphs[r].pred[i])
            if (cur is None or barrier or cur.barrier
                    or cur.items[-1][0] != r or cross):
                cur = Segment(index=len(segments), lane=pu, barrier=barrier,
                              target=tmap.get(pu))
                segments.append(cur)
                lane_segments[pu].append(cur)
            cur.items.append((r, i))
            cur.fns.append(graphs[r].ops[i].fn)
            seg_of[(r, i)] = cur

    # compile-time variant selection: a segment on a non-"ref"-dialect
    # target gets the resolved variant payload list iff any op actually
    # carries a variant for that dialect (otherwise the reference path
    # is the variant path and nothing needs verifying)
    for seg in segments:
        tgt = seg.target
        if tgt is None or tgt.dialect in (None, "ref"):
            continue
        vf = [graphs[r].ops[i].payload_for(tgt.dialect)
              for (r, i) in seg.items]
        if any(v is not f for v, f in zip(vf, seg.fns)):
            seg.var_fns = [tgt.bind(v) for v in vf]

    for seg in segments:
        internal = {it: t for t, it in enumerate(seg.items)}
        flat_index: dict[tuple[int, int], int] = {}
        deps: set[int] = set()
        for (r, i) in seg.items:
            spec: list[tuple[str, int]] = []
            for p in graphs[r].pred[i]:
                src = (r, p)
                t2 = internal.get(src)
                if t2 is not None:
                    spec.append(("o", t2))
                    continue
                j = flat_index.setdefault(src, len(flat_index))
                spec.append(("f", j))
                producer = seg_of.get(src)
                if producer is not None and producer.lane != seg.lane:
                    deps.add(producer.index)
            seg.argspecs.append(spec)
        seg.flat_refs = sorted(flat_index, key=flat_index.get)
        seg.deps = sorted(deps)
        seg.dep_whats = [
            f"segment {seg.index} on lane {seg.lane!r} (first op "
            f"{seg.items[0]}) waiting for segment {d} on lane "
            f"{segments[d].lane!r} (ops {segments[d].items[0]}.."
            f"{segments[d].items[-1]})"
            for d in seg.deps]
    return LaneProgram(graphs, segments, lane_segments, single=single)
