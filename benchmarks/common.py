"""Shared helpers for the per-table benchmark modules."""
from __future__ import annotations

import math
import platform
import time
from typing import Mapping, Sequence

from repro.core import (CostTable, EdgeSoCCostModel, EDGE_PUS, Orchestrator,
                        Workload, single_pu_cost, solve_sequential)
from repro.core.costmodel import CostEntry
from repro.core.op import FusedOp, OpGraph

PUS = ("CPU", "GPU", "NPU")


def geomean(xs: Sequence[float]) -> float:
    if not xs:
        raise ValueError("geomean of an empty sequence")
    bad = [x for x in xs if x <= 0]
    if bad:
        raise ValueError(
            f"geomean requires positive values; got {len(bad)} non-positive "
            f"entries (e.g. {bad[0]!r})")
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def best_single(chain, ops, table, pus=EDGE_PUS, objective: str = "latency",
                workload: Workload | None = None):
    """(best_pu, value, per_pu dict) of monolithic execution — a thin
    wrapper over ``Workload.best_solo`` that adds per-PU blocker detail
    to the infeasibility error."""
    wl = workload if workload is not None else Workload.build(
        chain, table, pus, ops=ops)
    try:
        return wl.best_solo(objective)
    except ValueError:
        blockers = {
            pu: [f"op {oi} ({ops[oi].name})" for oi in chain
                 if not table.supported(oi, pu)][:3]
            for pu in table.pus}
        raise ValueError(
            "no single PU supports every op of the chain "
            f"(len={len(chain)}); first unsupported ops per PU: {blockers}")


def sequential_report(graph: OpGraph, model: EdgeSoCCostModel | None = None):
    """One Table-2 row: single-PU latencies + BIDENT-lat + BIDENT-energy.

    Runs through the ``Orchestrator`` front door: one ``register`` (the
    single dense ingestion, shared by the baselines and both solves),
    then a latency and an energy ``plan`` — bitwise what the direct
    ``solve_sequential`` calls returned."""
    orch = Orchestrator(model or EdgeSoCCostModel(), EDGE_PUS)
    h = orch.register(graph)
    wl = orch.workload(h)
    table, chain = wl.table, wl.chain
    b, bl, lat = best_single(chain, graph.ops, table, workload=wl)
    sched_l = orch.plan(h, mode="sequential").schedule
    sched_e = orch.plan(h, objective="energy", mode="sequential").schedule
    _, be, _ = best_single(chain, graph.ops, table, objective="energy",
                           workload=wl)
    return {
        "table": table, "chain": chain, "best": b,
        "single_lat": lat, "best_lat": bl, "best_energy": be,
        "bident_lat": sched_l.latency, "bident_lat_energy": sched_l.energy,
        "bident_energy": sched_e.energy, "bident_energy_lat": sched_e.latency,
        "speedup": bl / sched_l.latency,
        "energy_red_latopt": 1.0 - sched_l.energy / be,
        "energy_red_engopt": 1.0 - sched_e.energy / be,
        "sched_l": sched_l, "sched_e": sched_e,
    }


# ---------------------------------------------------------------------------
# segment coarsening for the 190-pair concurrent sweep
# ---------------------------------------------------------------------------


def segment_table(graph: OpGraph, table: CostTable,
                  max_segments: int = 48) -> tuple[list[int], CostTable]:
    """Collapse a long op chain into <= max_segments super-ops.

    Consecutive ops merge into one segment whose per-PU cost is the sum of
    member costs (intra-segment transitions are zero: one PU per segment).
    A segment supports a PU iff every member does — so e.g. KAN segments
    stay NPU-less.

    Historical note: this coarsening was *required* by the seed's pure-
    Python joint (i, j) Dijkstra to keep the 190-pair sweep tractable.
    Since the dense-table A* joint solver landed, ``fig8_concurrent`` runs
    at full operator resolution by default and this helper is an opt-in
    fallback (``--max-segments``) kept for comparison runs and for
    scheduler micro-benchmarks at fixed granularity.
    """
    chain = graph.topo_order()
    n = len(chain)
    seg_len = max(1, -(-n // max_segments))
    segments: list[list[int]] = [chain[i:i + seg_len]
                                 for i in range(0, n, seg_len)]
    out = CostTable(list(table.pus))
    for si, seg in enumerate(segments):
        sup = set(table.pus)
        for oi in seg:
            sup &= set(table.supported_pus(oi))
        for pu in sup:
            w = sum(table.require(oi, pu).w for oi in seg)
            e = sum(table.require(oi, pu).energy for oi in seg)
            first = table.require(seg[0], pu)
            last = table.require(seg[-1], pu)
            out.set(si, pu, CostEntry(
                kernel=w, dispatch=0.0, h2d=first.h2d, d2h=last.d2h,
                power=(e / w if w > 0 else first.power)))
    return list(range(len(segments))), out


def env_meta() -> dict:
    """Environment provenance for every ``BENCH_*.json``: numbers are
    meaningless without knowing what produced them.  Records python /
    jax / jaxlib versions, the backend platform and device kinds, and
    the registered target names."""
    import jax
    import jaxlib

    from repro.core.backends import default_registry
    return {
        "python": platform.python_version(),
        "platform": platform.platform(),
        "machine": platform.machine(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "devices": [{"id": d.id, "platform": d.platform,
                     "kind": d.device_kind} for d in jax.devices()],
        "targets": default_registry().names(),
    }


class Timer:
    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.dt = time.time() - self.t0
