"""Run BIDENT's main path once on a TPU chip, in one process.

    python chip_smoke.py             # one chip: the four phases below
    python chip_smoke.py --chips 4   # only the four-chip lane path

With one chip the phases run in this order:

1. ``kernels``: the three Pallas kernels compiled (``interpret=False``)
   at zamba2-2.7b widths, each against its ``kernels/ref.py`` oracle in
   f32 at full matmul precision, within the ``VARIANT_TOL`` bucket of
   the kernel's dtype.
2. ``orchestrate``: the kernel-backed chain profiled on every lane
   (``MeasuredProfiler(strict=True)``), registered, planned and run as a
   compiled lane program on two lanes: ``tpu:0`` serving the compiled
   Pallas kernels and ``numpy-eager`` on the host.
3. ``serve``: ``ServingEngine`` in real execution over that binding,
   serving 8 Poisson arrivals.
4. ``model``: ``Engine.generate`` for zamba2-2.7b at its published
   widths, cut to 12 layers, in bf16 with the kernels; the prefill and
   first decode-step logits and the prefill cache against an f32 forward
   of the same weights.

``--chips 4`` runs four small one-block chain requests (seeds 0..3) as
one concurrent set on lanes ``tpu:0`` .. ``tpu:3`` plus ``numpy-eager``,
then the same requests one after another on the single lane ``tpu:0``;
the four-chip plan must use at least two chips, every lane's outputs
must live on its own chip, and the results must agree with the one-chip
run.  The ops are profiled once, on ``tpu:0`` and the host; ``tpu:1`` ..
``tpu:3`` are priced with ``tpu:0``'s cells.

Every check raises on failure, so the exit code is non-zero and the last
line is a traceback.  Only when every phase passed does the script print
its last line, a JSON object naming the device.  Where JAX's default
backend is not a TPU it stops before any phase.  JAX's compilation cache
lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``.jax_cache`` beside this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
# zamba2-2.7b widths: shared attention (32 heads of 80) and Mamba2 layer
# (80 heads, state 64, head dim 64); the expert GLU at granite-class MoE
# widths (8 experts, d 2048, ff 1024)
ATTN = dict(B=1, T=2048, H=32, D=80, block=128)
SSD = dict(B=1, T=2048, H=80, N=64, P=64, chunk=256)
GLU = dict(E=8, cap=256, d=2048, F=1024, block_m=128, block_f=256)
CHAIN = dict(blocks=2, batch=1, seq=2048, heads=32, head_dim=64, state=64,
             experts=8, moe_ff=1024, top_k=2, chunk=256, block_q=128,
             block_k=128, block_m=128, block_f=256)
# the four-chip path checks placement and agreement across chips, and
# every segment compiles once per chip it runs on: one block, with an
# activation of 8192 elements.  The chain's full 1-D sort compiles for
# the v5e in about a second at that size and in 23 to 55 s from 32768
# elements up (compile only, on a described chip), once per program
# that holds it on each chip.
CHAIN4 = dict(CHAIN, blocks=1, seq=128, heads=1, chunk=128, moe_ff=256)
MODEL = dict(arch="zamba2-2.7b", n_layers=12, batch=4, prompt=512, new=16)
N_ARRIVALS = 8
# bf16 model vs f32 reference: the bf16 bucket of VARIANT_TOL, taken
# relative to the largest value of each compared tensor: bf16 rounding
# through 12 layers grows with the scale of a layer's activations as a
# whole, not with each element.  A causal mask shifted by one position in
# the shared attention puts the prefill cache's keys and values at 0.4 to
# 0.7 of that scale (interpret mode on the CPU, 4 layers), against ~0.02
# for the correct kernel
MODEL_TOL = 5e-2
# the first run of every lane program compiles its segments, so the
# watchdog floor must cover a compile (the default floor is 10 s)
COMPILE_FLOOR_S = 600.0


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def require(ok, phase: str, what: str) -> None:
    if not ok:
        raise RuntimeError(f"[{phase}] check failed: {what}")


def enable_compilation_cache(jax) -> None:
    """JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; without it the
    cache goes to a fixed path, so that the next run finds it."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def compare(got, want, dtype):
    """(largest |got - want|, worst error over the bucket's bound, the
    (atol, rtol) bucket of ``dtype``); the pair passes iff worst <= 1."""
    import numpy as np
    from repro.core.targets import variant_tolerance
    atol, rtol = variant_tolerance(dtype)
    a = np.asarray(got, np.float64)
    b = np.asarray(want, np.float64)
    require(a.shape == b.shape, "compare", f"shape {a.shape} != {b.shape}")
    require(np.isfinite(a).all(), "compare", "non-finite output")
    err = np.abs(a - b)
    return (float(err.max()), float((err / (atol + rtol * np.abs(b))).max()),
            (atol, rtol))


def on_device(x, dev) -> bool:
    import jax
    return isinstance(x, jax.Array) and x.devices() == {dev}


# ---------------------------------------------------------------------------
# phase 1: the kernels, compiled
# ---------------------------------------------------------------------------

def phase_kernels(dev, attn=ATTN, ssd=SSD, glu=GLU) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    compiled_kernels = dev.platform == "tpu"
    f32, bf16 = jnp.float32, jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def rnd(shape, dtype, scale=1.0):
        x = scale * jax.random.normal(next(keys), shape, f32)
        return jax.device_put(x.astype(dtype), dev)

    def up(*xs):
        return tuple(x.astype(f32) for x in xs)

    B, T, H, D = attn["B"], attn["T"], attn["H"], attn["D"]
    q, k, v = (rnd((B, T, H, D), bf16) for _ in range(3))
    cases = [(
        f"flash_attention B={B} T={T} H={H} D={D} bf16",
        lambda q, k, v: ops.flash_attention(
            q, k, v, causal=True, block_q=attn["block"],
            block_k=attn["block"], interpret=not compiled_kernels),
        (q, k, v),
        lambda: (ref.attention_ref(*up(q, k, v), causal=True),))]

    B, T, H, N, P = (ssd[n] for n in ("B", "T", "H", "N", "P"))
    c, b, x = rnd((B, T, H, N), f32), rnd((B, T, H, N), f32), \
        rnd((B, T, H, P), f32)
    la = -jax.nn.softplus(rnd((B, T, H), f32))
    cases.append((
        f"ssd_scan B={B} T={T} H={H} N={N} P={P} chunk={ssd['chunk']} f32",
        lambda c, b, x, la: ops.ssd_scan(
            c, b, x, la, chunk=ssd["chunk"], interpret=not compiled_kernels),
        (c, b, x, la),
        lambda: ref.ssd_scan_ref(c, b, x, la)))

    E, cap, d, F = glu["E"], glu["cap"], glu["d"], glu["F"]
    xe = rnd((E, cap, d), bf16)
    w_up = rnd((E, d, 2 * F), bf16, d ** -0.5)
    w_down = rnd((E, F, d), bf16, F ** -0.5)
    cases.append((
        f"expert_glu E={E} cap={cap} d={d} F={F} bf16",
        lambda xe, w_up, w_down: ops.expert_glu(
            xe, w_up, w_down, block_m=glu["block_m"], block_f=glu["block_f"],
            interpret=not compiled_kernels),
        (xe, w_up, w_down),
        lambda: (ref.expert_glu_ref(*up(xe, w_up, w_down)),)))

    for name, kernel, args, oracle in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        if compiled_kernels:
            require("tpu_custom_call" in compiled.as_text(), "kernels",
                    f"{name}: no Mosaic kernel in the compiled program")
        outs = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        outs = jax.block_until_ready(compiled(*args))
        t_run = time.perf_counter() - t0
        outs = outs if isinstance(outs, tuple) else (outs,)
        with jax.default_matmul_precision("highest"):
            wants = jax.block_until_ready(oracle())
        for j, (o, w) in enumerate(zip(outs, wants)):
            require(on_device(o, dev), "kernels",
                    f"{name} output {j} on {o.devices()}, not {dev}")
            err, worst, (atol, rtol) = compare(o, w, o.dtype)
            log("kernels", f"{name} out{j}: compiled={compiled_kernels} "
                f"compile_s={t_compile:.3f} run_s={t_run:.6f} "
                f"max_abs_err={err:.3e} worst_err/bound={worst:.3f} "
                f"bucket={o.dtype}(atol={atol:g},rtol={rtol:g})")
            require(worst <= 1.0, "kernels",
                    f"{name} output {j} outside its {o.dtype} bucket")


# ---------------------------------------------------------------------------
# phase 2 (and the four-chip path): profile -> plan -> compiled lanes
# ---------------------------------------------------------------------------

def lanes(devs, host: bool = True) -> dict:
    """One lane per device serving the compiled Pallas kernels (or, off a
    TPU, the interpreted ones), plus the NumPy host lane if ``host``."""
    from repro.core.backends import device_target, numpy_eager
    binding = {}
    for d in devs:
        t = device_target(d, dialect="pallas", interpret=d.platform != "tpu")
        binding[t.name] = t
    if host:
        binding["numpy-eager"] = numpy_eager()
    return binding


def profile(phase: str, graph, binding):
    """Measure every op of ``graph`` on every lane (strict: a failing
    cell raises)."""
    from repro.core import MeasuredProfiler
    t0 = time.perf_counter()
    table = MeasuredProfiler(warmup=1, iters=3, strict=True,
                             targets=binding).profile(graph)
    fails = table.meta["profile_failures"]
    log(phase, f"profiled {len(graph)} ops x {len(binding)} lanes "
        f"{sorted(binding)} in {time.perf_counter() - t0:.2f}s; "
        f"failures={len(fails)}")
    require(not fails, phase, f"profile failures {fails}")
    return table


def run_chain(phase: str, graphs, exts, binding, table=None):
    """Plan the requests as one set (profiling the first graph when no
    ``table`` is given: the graphs share their shapes), run the compiled
    program cold then warm, and check the warm run: device lanes jitted
    with accepted variants, every device-lane output on its lane's
    device, values within the f32 bucket of the reference oracle.
    Returns (orchestrator, plan, warm results)."""
    import jax
    import numpy as np
    from repro.core import ExecutionPolicy, Orchestrator
    from repro.core.laneprogram import JIT
    from repro.core.targets import variant_tolerance

    single = len(graphs) == 1
    if table is None:
        table = profile(phase, graphs[0], binding)
    orch = Orchestrator(table, targets=binding)
    hs = [orch.register(g) for g in graphs]

    plan = orch.plan(hs[0] if single else hs)
    log(phase, f"plan {plan.kind}: predicted latency {plan.latency:.6e}s")
    for r, route in enumerate(plan.route):
        g = graphs[r]
        log(phase, f"request {r} route: " + ", ".join(
            f"{g.ops[i].name}->{lane}" for i, lane in route))

    inputs = exts[0] if single else list(exts)
    prog = orch.program_for(plan, inputs)
    policy = ExecutionPolicy(min_timeout=COMPILE_FLOOR_S)
    t0 = time.perf_counter()
    jax.block_until_ready(prog.run(inputs, policy=policy))
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = prog.run(inputs, policy=policy)
    jax.block_until_ready(got)
    t_warm = time.perf_counter() - t0
    got_list = [got] if single else got
    st = prog.stats
    log(phase, f"program: {st['n_segments']} segments, {st['n_jitted']} "
        f"jitted, {st['n_variant']} serving variants; cold_s={t_cold:.3f} "
        f"warm_s={t_warm:.6f}")

    device_lanes = {lane for lane, t in binding.items() if t.device is not None}
    for seg in prog.segments:
        names = [graphs[r].ops[i].name for r, i in seg.items]
        log(phase, f"segment {seg.index} lane={seg.lane} mode={seg.mode} "
            f"variant={seg.verified} jit={seg.jit_verified} ops={names}")
        if seg.lane not in device_lanes:
            continue
        require(seg.mode == JIT, phase,
                f"segment {seg.index} on {seg.lane} is {seg.mode}, not jit "
                f"({seg.jit_verified})")
        require(seg.verified in (None, "bitwise", "tolerance"), phase,
                f"segment {seg.index} on {seg.lane}: variant {seg.verified}")
        dev = binding[seg.lane].device
        for r, i in seg.items:
            out = got_list[r][i]
            require(on_device(out, dev), phase,
                    f"op {graphs[r].ops[i].name} of segment {seg.index} "
                    f"is on {getattr(out, 'devices', lambda: type(out))()}, "
                    f"not {dev}")

    worst_all, err_all = 0.0, 0.0
    for r, (g, ext) in enumerate(zip(graphs, exts)):
        want = orch.executor.run_monolithic(g, ext)
        for i in sorted(want):
            err, worst, _ = compare(got_list[r][i], want[i], np.float32)
            worst_all, err_all = max(worst_all, worst), max(err_all, err)
    atol, rtol = variant_tolerance(np.float32)
    log(phase, f"outputs vs f32 oracle: max_abs_err={err_all:.3e} "
        f"worst_err/bound={worst_all:.3f} bucket=float32(atol={atol:g},"
        f"rtol={rtol:g})")
    require(worst_all <= 1.0, phase, "outputs outside the f32 bucket")
    return orch, plan, got_list


def phase_orchestrate(dev, chain=CHAIN):
    from repro.core.modelgraph import kernel_chain
    g, ext = kernel_chain(**chain, seed=SEED)
    orch, plan, _ = run_chain("orchestrate", [g], [ext], lanes([dev]))
    return orch, plan, g, ext


# ---------------------------------------------------------------------------
# phase 3: the serving loop over that binding
# ---------------------------------------------------------------------------

def phase_serve(orch, plan, g, ext, n=N_ARRIVALS) -> None:
    from repro.core import ArrivalTrace, ExecutionPolicy, ServingEngine
    eng = ServingEngine(
        orch, {"chain": g}, execution="real", compile_exec=True,
        inputs={"chain": ext},
        # every window is a fresh program, so each window compiles
        exec_policy=ExecutionPolicy(min_timeout=COMPILE_FLOOR_S))
    # arrivals twice as fast as one request's predicted latency, so that
    # requests overlap and share windows
    trace = ArrivalTrace.poisson(["chain"], rate=2.0 / plan.latency, n=n,
                                 seed=SEED)
    t0 = time.perf_counter()
    rep = eng.serve(trace)
    wall = time.perf_counter() - t0
    # latencies of this engine are on its virtual clock: counts only
    log("serve", f"requests={rep.n_requests} completed={rep.completed} "
        f"shed={rep.shed} bitwise_checked={rep.bitwise_checked} "
        f"bitwise_failures={rep.bitwise_failures} retried={rep.retried} "
        f"recoveries={rep.recoveries} plan_events={rep.plan_events} "
        f"wall_s={wall:.2f}")
    require(rep.completed == n and rep.shed == 0, "serve",
            f"{rep.completed}/{n} completed, {rep.shed} shed "
            f"{rep.shed_reasons}")
    require(rep.bitwise_checked == n and rep.bitwise_failures == 0, "serve",
            f"{rep.bitwise_failures} of {rep.bitwise_checked} requests "
            "differ from the solo reference")


# ---------------------------------------------------------------------------
# phase 4: a served model
# ---------------------------------------------------------------------------

def phase_model(dev, arch=MODEL["arch"], n_layers=MODEL["n_layers"],
                batch=MODEL["batch"], prompt=MODEL["prompt"],
                new=MODEL["new"], overrides=None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serving.engine import Engine

    cfg = dataclasses.replace(get_config(arch), n_layers=n_layers,
                              dtype="bfloat16", use_kernels=True,
                              **(overrides or {}))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        M.init_params(cfg, jax.random.PRNGKey(SEED)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (batch, prompt), 0, cfg.vocab, jnp.int32)
    log("model", f"{cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"params={n_params} bf16, prompts {batch}x{prompt}, +{new} tokens; "
        f"init_s={time.perf_counter() - t0:.2f}")

    eng = Engine(cfg=cfg, params=params)
    t0 = time.perf_counter()
    gen = jax.block_until_ready(eng.generate(tokens, max_new=new))
    t_gen = time.perf_counter() - t0
    gen_np = np.asarray(gen)
    require(gen_np.shape == (batch, new), "model", f"generated {gen.shape}")
    require(((gen_np >= 0) & (gen_np < cfg.vocab)).all(), "model",
            "token ids out of range")
    require(on_device(gen, dev), "model", f"tokens on {gen.devices()}")

    # the logits of the two calls generate makes first: the prefill and
    # the first decode step
    max_len = prompt + new
    lp, cache = M.prefill(cfg, params, {"tokens": tokens}, max_len=max_len,
                          shd=eng.policy)
    tok0 = jnp.argmax(lp[:, -1], axis=-1)[:, None].astype(jnp.int32)
    require((np.asarray(tok0) == gen_np[:, :1]).all(), "model",
            "first generated token is not the prefill argmax")
    # the step donates its cache, and the prefill's is compared below
    ld, _ = eng.decode_step_fn()(params, jax.tree.map(jnp.copy, cache),
                                 {"tokens": tok0})

    cfg32 = dataclasses.replace(cfg, dtype="float32", use_kernels=False)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        rp, rcache = M.prefill(cfg32, p32, {"tokens": tokens},
                               max_len=max_len)
        rd, _ = M.decode_step(cfg32, p32, rcache, {"tokens": tok0})
    log("model", f"generate_s={t_gen:.2f} (prefill + {new} decode steps, "
        "compile included)")
    # the logits are of the last position only; the prefill cache holds
    # every layer's keys, values and states at every prompt position
    pairs = [("prefill logits", lp, rp), ("decode0 logits", ld, rd)]
    pairs += [(f"prefill cache{jax.tree_util.keystr(path)}", got, want)
              for (path, got), want in zip(
                  jax.tree_util.tree_leaves_with_path(cache),
                  jax.tree.leaves(rcache))]
    for name, got, want in pairs:
        g64 = np.asarray(got, np.float64)
        w64 = np.asarray(want, np.float64)
        require(np.isfinite(g64).all(), "model", f"{name}: non-finite")
        err = float(np.abs(g64 - w64).max())
        scale = float(np.abs(w64).max())
        rel_l2 = float(np.linalg.norm(g64 - w64) / np.linalg.norm(w64))
        top1 = ("" if "logits" not in name else
                f" top1_agree={(g64.argmax(-1) == w64.argmax(-1)).mean():.3f}")
        log("model", f"{name} {tuple(got.shape)} {got.dtype}: "
            f"max_abs_err={err:.4e} tolerance={MODEL_TOL * scale:.4e} "
            f"(={MODEL_TOL:g} x max|ref|={scale:.4e}) rel_l2={rel_l2:.3e}"
            + top1)
        require(err <= MODEL_TOL * scale, "model",
                f"{name} beyond the stated tolerance")


# ---------------------------------------------------------------------------
# four chips: lanes bound to distinct chips
# ---------------------------------------------------------------------------

def same_chip_costs(table, binding, chip: str):
    """``table`` over the lanes of ``binding``, pricing every device lane
    with ``chip``'s measured cells: the chips of one host are one kind,
    so measuring each again would only compile every op once more."""
    from repro.core.costmodel import CostTable
    devices = [d for d, t in binding.items() if t.device is not None]
    out = CostTable(list(binding))
    out.meta = dict(table.meta)
    for (i, lane), e in table.items():
        if lane == chip:
            dsts = devices
        elif lane in binding:
            dsts = [lane]
        else:
            continue
        for dst in dsts:
            out.set(i, dst, e)
    return out


def phase_four_chips(devs, chain=CHAIN4, n=4) -> None:
    import numpy as np
    from repro.core.modelgraph import kernel_chain

    made = [kernel_chain(**chain, seed=SEED + s) for s in range(n)]
    graphs = [g for g, _ in made]
    exts = [e for _, e in made]
    measured = lanes(devs[:1])
    chip0 = next(name for name, t in measured.items() if t.device is not None)
    table = profile("chips4", graphs[0], measured)
    four, one = lanes(devs), lanes(devs[:1], host=False)
    _, plan4, got4 = run_chain("chips4", graphs, exts, four,
                               same_chip_costs(table, four, chip0))
    used = sorted({lane for route in plan4.route for _, lane in route})
    chips = [lane for lane in used if four[lane].device is not None]
    log("chips4", f"lanes used: {used}")
    require(len(chips) >= 2, "chips4",
            f"the concurrent plan used {chips} only: no cross-chip path ran")
    # one lane runs one op at a time, so on it the set runs request after
    # request: a plan per request keeps each request one segment, where
    # the interleaved set plan would cut and compile every op on its own
    one_table = same_chip_costs(table, one, chip0)
    got1 = [run_chain("chips1", [g], [e], one, one_table)[2][0]
            for g, e in made]
    worst_all, err_all = 0.0, 0.0
    for a, b in zip(got4, got1):
        for i in sorted(b):
            err, worst, _ = compare(a[i], b[i], np.float32)
            worst_all, err_all = max(worst_all, worst), max(err_all, err)
    log("chips4", f"four-chip vs one-chip results: max_abs_err={err_all:.3e} "
        f"worst_err/bound={worst_all:.3f}")
    require(worst_all <= 1.0, "chips4",
            "four-chip results disagree with the one-chip run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lanes-on-four-chips path")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX's default backend is {devs[0].platform!r}, "
              "not a TPU; nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2
    enable_compilation_cache(jax)
    dev = devs[0]
    log("device", f"{dev.platform} {dev.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}")

    phases = []
    if args.chips == 4:
        phases.append(("chips4", lambda: phase_four_chips(devs[:4])))
    else:
        state = {}

        def orchestrate():
            state["o"] = phase_orchestrate(dev)

        phases += [("kernels", lambda: phase_kernels(dev)),
                   ("orchestrate", orchestrate),
                   ("serve", lambda: phase_serve(*state["o"])),
                   ("model", lambda: phase_model(dev))]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        log(name, f"passed in {time.perf_counter() - t0:.2f}s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
