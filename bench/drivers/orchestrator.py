"""Driver for a graph served by BIDENT's orchestrator (``Orchestrator``).

Set-up builds one ``kernel_chain`` graph from a graph seed drawn from
the run's seed, measures every op on every lane with
``MeasuredProfiler``, registers the graph, plans it (the sequential
plan), and runs the plan once through ``Orchestrator.execute``, which
compiles and probe-verifies the lane program.  The lanes are the chip,
serving the ``pallas`` dialect, and ``numpy-eager`` on the host.  One
client: several tenants' graphs planned as one concurrent set have no
cell yet.

A round is one request: ``Orchestrator.plan`` (a plan-cache hit) and
``Orchestrator.execute`` on an input drawn from the pool, then a wait
for every output.

The check runs each sampled request's ops through the reference, each op
from the program's own output of the op before it (from the request's
input for the first), and compares every op's output:
``op_err`` is the largest difference over the reference output's
largest magnitude, over all ops of all sampled requests.
"""
from __future__ import annotations

import time

import numpy as np

from bench.common import block, max_rel, sub_seed
from bench.reference import kernel_chain as ref

# the first run of the lane program compiles and probes its segments: the
# watchdog's floor must cover that (its default floor is 10 s)
COMPILE_FLOOR_S = 900.0


class Driver:
    SPANS = ("prep", "plan", "execute", "sync")
    decode_module = None

    def __init__(self, cell: dict, config: dict, seed: int, device):
        self.cell, self.config, self.seed, self.device = cell, config, seed, device
        self.widths = config["widths"]
        self.blocks = config["blocks"]
        if cell["clients"] != 1:
            raise ValueError("the orchestrator driver serves one client")
        self.clients = 1
        self.graph_seed = sub_seed(seed, "graph", 0)
        self.log: list[str] = []

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        from repro.core import ExecutionPolicy, MeasuredProfiler, Orchestrator
        from repro.core.backends import device_target, numpy_eager
        from repro.core.modelgraph import kernel_chain

        w = self.widths
        t0 = time.perf_counter()
        self.graph = kernel_chain(**w, blocks=self.blocks,
                                  seed=self.graph_seed)[0]
        chip = device_target(self.device, dialect="pallas",
                             interpret=self.device.platform != "tpu")
        binding = {chip.name: chip, "numpy-eager": numpy_eager()}
        table = MeasuredProfiler(warmup=1, iters=3, strict=True,
                                 targets=binding).profile(self.graph)
        t1 = time.perf_counter()
        self.orch = Orchestrator(table, targets=binding)
        self.handle = self.orch.register(self.graph)

        self.make_pool()
        plan = self.orch.plan(self.handle)
        for route in plan.route:
            self.log.append("route: " + ", ".join(
                f"{self.graph.ops[i].name}->{lane}" for i, lane in route))
        # the cold run compiles and probes; the second is the window's path
        t2 = time.perf_counter()
        block(self.orch.execute(plan, self._inputs(0)[1],
                                policy=ExecutionPolicy(min_timeout=COMPILE_FLOOR_S)))
        self.log.append(f"build and profile {t1 - t0:.2f}s, cold run "
                        f"{time.perf_counter() - t2:.2f}s")
        block(self.orch.execute(self.orch.plan(self.handle),
                                self._inputs(1)[1]))
        prog = self.orch.program_for(plan, self._inputs(0)[1])
        st = prog.stats
        self.log.append(f"program: {st['n_segments']} segments, "
                        f"{st['n_jitted']} jitted, {st['n_variant']} serving "
                        "variants; " + "; ".join(
                            f"seg {s.index} {s.lane} {s.mode} "
                            f"variant={s.verified} jit={s.jit_verified}"
                            for s in prog.segments))

    def make_pool(self) -> None:
        """The requests' inputs, drawn from the seed on the chip."""
        import jax
        import jax.numpy as jnp
        w, n = self.widths, self.cell["pool"]
        shape = (n, w["batch"], w["seq"], w["heads"], w["head_dim"])
        pool = jax.jit(lambda k: jax.random.normal(k, shape, jnp.float32))(
            jax.random.PRNGKey(sub_seed(self.seed, "inputs")))
        self.pool = [jax.device_put(pool[i], self.device) for i in range(n)]
        block(self.pool)

    def control_sample(self) -> list:
        """A sample the control can be read on without the program: the
        control computes its own outputs from the inputs."""
        self.make_pool()
        return [(self._inputs(0)[0], None)]

    def _inputs(self, k: int):
        i = k % len(self.pool)
        return i, {0: (self.pool[i],)}

    # -- the window --------------------------------------------------------
    def round(self, k: int, span):
        with span("prep"):
            i, inputs = self._inputs(k)
        with span("plan"):
            plan = self.orch.plan(self.handle)
        with span("execute"):
            out = self.orch.execute(plan, inputs)
        with span("sync"):
            block(out)
        return i, out

    def counters(self) -> dict:
        return dict(self.orch.stats)

    def kernel_calls(self) -> dict:
        return ref.kernel_calls(self.widths)

    def flops_per_request(self) -> dict:
        return ref.flops_per_request(self.widths, self.blocks)

    def release(self) -> None:
        """Drop the program's state: the orchestrator, its programs and the
        graph's weights."""
        del self.orch, self.graph

    # -- the check ---------------------------------------------------------
    def check(self, sample: list, control: bool = False) -> dict:
        """``{name: value}`` of the compared numbers over ``sample``, a
        list of round results.  With ``control`` the reference in three
        bf16 passes takes the program's place: its outputs from the same
        inputs are compared in the same way."""
        worst = 0.0
        blocks = ref.weights(self.graph_seed, self.widths, self.blocks)
        fns = ref.op_list(blocks, self.widths)
        low = ref.op_list(blocks, self.widths, passes=3) if control else None
        for i, got in sample:
            x = np.asarray(self.pool[i])
            if control:
                got = ref.forward(x, low)
            errs = []
            for j, fn in enumerate(fns):
                errs.append(max_rel(got[j], fn(x)))
                x = got[j]
            worst = max([worst] + errs)
            self.log.append(f"{'control' if control else 'program'} "
                            f"input {i} op errors: " + ", ".join(
                                f"{n}={e:.3e}" for n, e in zip(
                                    ref.OPS_PER_BLOCK * self.blocks, errs)))
        return {"op_err": worst}
