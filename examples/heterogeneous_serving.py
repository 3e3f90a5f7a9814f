"""Heterogeneous serving: BIDENT's Fig. 5 on a real model, through the
register → plan → execute front door.

The ``Orchestrator`` session owns the cost provider and the plan cache —
the serving posture: ``register`` the decode-step operator graph once
(profiled + densified behind a handle), then ``plan`` it under latency
AND energy objectives (the second objective reuses the same memoized
``Workload``; a repeated ``plan`` call is a cache hit).  The per-operator
PU path (the paper's Fig. 5 "highlighted path") is read off
``plan.route``, and batched requests are then actually served with the
engine.

The second half swaps the analytic EdgeSoC cost model for **two real
registered targets** (``numpy-eager`` and ``xla-cpu`` from the builtin
registry): the same plan loop, but the per-op costs are measured on the
bound backends and the compiled lane program actually executes on them,
probe-verified against the reference composition.

Run:  PYTHONPATH=src python examples/heterogeneous_serving.py [--arch ...]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_ARCHS, get_config
from repro.core import EdgeSoCCostModel, MeasuredProfiler, Orchestrator
from repro.core.backends import default_registry
from repro.core.modelgraph import kernel_chain, model_op_graph
from repro.core.targets import variant_tolerance
from repro.models import model as M
from repro.serving.engine import Engine
from repro.sharding import Policy

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="zamba2-2.7b", choices=ALL_ARCHS)
ap.add_argument("--batch", type=int, default=2)
args = ap.parse_args()

# -- register the decode-step operator graph ------------------------------
cfg_full = get_config(args.arch)
g = model_op_graph(cfg_full, kind="decode", batch=1, seq=2048)
orch = Orchestrator(EdgeSoCCostModel())
h = orch.register(g)

for objective in ("latency", "energy"):
    plan = orch.plan(h, objective=objective)
    counts: dict[str, int] = {}
    for _, pu in plan.route[0]:
        counts[pu] = counts.get(pu, 0) + 1
    print(f"{args.arch} decode, {objective}-optimal: "
          f"{plan.latency*1e3:.2f} ms / {plan.energy*1e3:.1f} mJ, "
          f"assignment {counts}")

# Fig. 5-style path for the first layer's operators (cache hit: the
# latency plan above is served back from the plan cache)
plan = orch.plan(h)
table = orch.workload(h).table
print("\nper-operator path (first 12 ops):")
for oi, pu in plan.route[0][:12]:
    op = g.ops[oi]
    best1 = min(table.supported_pus(oi),
                key=lambda p: table.require(oi, p).w)
    print(f"  {op.name:24s} kind={op.kind:9s} -> {pu}"
          + ("   (solo-best: %s)" % best1 if best1 != pu else ""))

_, base, _ = orch.workload(h).best_solo()
print(f"\nbest single PU {base*1e3:.2f} ms -> BIDENT {plan.latency*1e3:.2f} ms "
      f"({base/plan.latency:.2f}x)   [plan cache: {orch.stats}]")

# the compiled execution path: the plan's lane queues partition into
# maximal same-PU segments with handoff events only at the cross-lane
# cuts — the dispatch shape a real command-queue runtime would see
prog = orch.program_for(plan)
s = prog.stats
print(f"compiled lane program: {s['n_ops']} ops -> {s['n_segments']} "
      f"segments ({s['n_ops'] / max(s['n_segments'], 1):.1f} ops/segment; "
      f"{'serial' if s['serial'] else 'multi-lane'} dispatch)")

# -- the same loop on two REAL registered targets -------------------------
# The registry carries the builtin backends as data; binding a subset of
# them as PU lanes makes the orchestrator profile, plan, and execute on
# the actual backends instead of the analytic EdgeSoC model.
reg = default_registry()
binding = {name: reg.get(name) for name in ("numpy-eager", "xla-cpu")}
kg, kext = kernel_chain(blocks=1, seq=64, heads=2, head_dim=16,
                        state=8, moe_ff=16, chunk=32,
                        block_q=32, block_k=32)
ktable = MeasuredProfiler(warmup=1, iters=3, targets=binding).profile(kg)
korch = Orchestrator(ktable, targets=binding)
kplan = korch.plan(korch.register(kg))
kprog = korch.program_for(kplan)
kprog.run(kext)              # cold run: probe-verify, settle jit
kout = kprog.run(kext)       # warm run: serves the accepted variants
kref = korch.executor.run_monolithic(kg, kext)
atol, rtol = variant_tolerance(np.float32)
match = korch.executor.outputs_close(kout, kref, atol=atol, rtol=rtol)
route = [pu for _, pu in kplan.route[0]]
ks = kprog.stats
print(f"\nreal targets {list(binding)}: measured plan "
      f"{kplan.latency*1e6:.0f} us predicted, route "
      f"{dict((p, route.count(p)) for p in dict.fromkeys(route))}, "
      f"{ks['n_segments']} segments on bound backends "
      f"(verified: {ks['variant_verified'] or 'bitwise'}), outputs "
      f"{'match' if match else 'MISMATCH'} oracle")
if not match:
    raise SystemExit("compiled outputs differ from the oracle beyond the "
                     f"f32 variant tolerance ({atol:g})")

# -- actually serve requests (reduced config on this CPU container) -------
cfg = cfg_full.reduced()
params = M.init_params(cfg, jax.random.PRNGKey(0))
engine = Engine(cfg=cfg, params=params, policy=Policy())
prompts = jnp.asarray(np.random.default_rng(0).integers(
    0, cfg.vocab, (args.batch, 16), dtype=np.int32))
out = engine.generate(prompts, max_new=8)
out = engine.generate(prompts, max_new=8)   # prefill, decode step: no re-trace
print(f"\nserved batch: prompts {prompts.shape} -> generated {out.shape} "
      f"(prefill traces: {sum(engine.prefill_trace_counts.values())}, "
      f"decode-step traces: {sum(engine.decode_trace_counts.values())} "
      f"across 2 generate calls)")
