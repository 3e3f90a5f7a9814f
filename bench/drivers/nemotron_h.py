"""Driver for a Nemotron-H model served by ``repro.serving.engine.Engine``.

As ``bench/drivers/engine.py`` (rounds, spans and the decode module are
its), with the configuration file's published
keys mapped onto the program's ``ModelConfig``, the reference of
``bench/reference/nemotron_h.py``, the kernel calls of the SSD scan, the
flash attention and the expert kernel, and the MoE layers' counters
(``repro.telemetry``'s ``moe.*``, read once per generate).  The check
reads the served tokens' logit gap against the reference as
``engine.Driver.check`` does, averaged over the sampled positions.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.common import block, sub_seed
from bench.drivers import engine
from bench.reference import nemotron_h as ref

# ModelConfig field <- configuration-file key
PROGRAM_NAMES = {
    "n_layers": "num_hidden_layers", "layer_pattern": "hybrid_override_pattern",
    "d_model": "hidden_size", "vocab": "vocab_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "d_head": "head_dim", "n_experts": "router_experts",
    "moe_held": "n_routed_experts", "moe_expert_offset": "expert_offset",
    "moe_top_k": "num_experts_per_tok", "moe_d_ff": "moe_intermediate_size",
    "moe_shared_d_ff": "moe_shared_expert_intermediate_size",
    "moe_routed_scale": "routed_scaling_factor",
    "ssm_n_heads": "mamba_num_heads", "ssm_headdim": "mamba_head_dim",
    "ssm_state": "ssm_state_size", "ssm_groups": "n_groups",
    "ssm_conv": "conv_kernel", "ssm_chunk": "chunk_size",
    "norm_eps": "norm_eps", "ssm_norm_eps": "layer_norm_epsilon",
}
PHASES = ("prefill", "decode")


class Driver(engine.Driver):

    def __init__(self, cell: dict, config: dict, seed: int, device):
        self.cell, self.config, self.seed, self.device = cell, config, seed, device
        self.m = ref.dims(config)
        self.clients, self.prompt, self.new = (
            cell["clients"], cell["prompt"], cell["new"])
        self.log: list[str] = []
        self.snapshots: list[tuple[int, dict]] = []

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import model as M
        from repro.serving.engine import Engine

        fields = {k: self.config[v] for k, v in PROGRAM_NAMES.items()}
        self.cfg = dataclasses.replace(
            get_config(self.config["arch"]), **fields,
            dtype=self.config["dtype"], use_kernels=self.config["use_kernels"])
        dtype = jnp.dtype(self.config["dtype"])
        key = jax.random.PRNGKey(sub_seed(self.seed, "weights"))
        with jax.default_device(self.device):
            self.params = jax.jit(lambda k: ref.init(k, self.m, dtype))(key)
        want = jax.eval_shape(lambda: M.init_params(self.cfg, key))
        got = jax.eval_shape(lambda: self.params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")
        n = self.cell["pool"]
        toks = jax.jit(lambda k: jax.random.randint(
            k, (n, self.clients, self.prompt), 0, self.m["vocab"], jnp.int32))(
            jax.random.PRNGKey(sub_seed(self.seed, "prompts")))
        self.pool = [jax.device_put(toks[i], self.device) for i in range(n)]
        block((self.params, self.pool))
        self.engine = Engine(cfg=self.cfg, params=self.params)
        for k in range(2):
            t0 = time.perf_counter()
            block(self.engine.generate(self.pool[k % n], max_new=self.new))
            self.log.append(f"warm-up generate {k}: "
                            f"{time.perf_counter() - t0:.2f}s")

    def counters(self) -> dict:
        """The MoE layers' counters so far (``moe.rows/<phase>``,
        ``moe.experts/<phase>``, ``moe.rows_max/<phase>``)."""
        from repro import telemetry
        now = {k: v for k, v in telemetry.counters().items()
               if k.startswith("moe.")}
        self.snapshots.append((self.engine._calls, now))
        return now

    def kernel_calls(self) -> dict:
        from bench.counts import flash_attention, ssd_scan
        m, B, T = self.m, self.clients, self.prompt
        return {
            "flash_attention": (flash_attention, dict(
                B=B, Tq=T, Tk=T, H=m["n_heads"], D=m["d_head"],
                Hk=m["n_kv_heads"], dtype=self.config["dtype"], causal=True)),
            "ssd_scan": (ssd_scan, dict(
                B=B, T=T, H=m["ssm_heads"], N=m["ssm_state"],
                P=m["ssm_headdim"], chunk=m["ssm_chunk"],
                dtype=self.config["dtype"])),
        }

    def expert_mlp_calls(self, counters: dict, rounds: int) -> list | None:
        """``[(calls, expert_mlp.count arguments of the mean call)]``, one
        entry a phase: a round runs the kernel once a MoE layer in the
        prefill and in each of ``new`` decode steps; its rows and the
        experts it reads are the window's counters over those calls.
        ``None`` where the counters are missing."""
        layers = self.m["kinds"].count("E")
        out = []
        for phase, steps in zip(PHASES, (1, self.new)):
            rows = counters.get(f"moe.rows/{phase}")
            experts = counters.get(f"moe.experts/{phase}")
            if rows is None or experts is None or not layers:
                return None
            calls = rounds * steps * layers
            out.append((calls, dict(rows=rows / calls, experts=experts / calls,
                                    d=self.m["d_model"], F=self.m["moe_ff"],
                                    dtype=self.config["dtype"])))
        return out

    def flops_per_request(self) -> dict:
        return ref.flops_per_request(self.m, self.prompt, self.new)

    def moe_line(self) -> str:
        """The MoE counters of the window (the last two snapshots), a
        round, beside uniform routing's expectation, and the high-water
        rows of one held expert in one layer call."""
        (c0, before), (c1, after) = self.snapshots[-2:]
        rounds = max(c1 - c0, 1)
        share = self.m["top_k"] * self.m["held"] / self.m["experts"]
        layers = self.m["kinds"].count("E")
        parts = []
        for phase, tokens in zip(PHASES, (self.prompt, self.new)):
            rows = (after.get(f"moe.rows/{phase}", 0)
                    - before.get(f"moe.rows/{phase}", 0)) / rounds
            uniform = share * tokens * self.clients * layers
            parts.append(f"{phase} rows {rows:.1f} a round (uniform "
                         f"{uniform:.1f}), rows_max "
                         f"{after.get(f'moe.rows_max/{phase}', 0)}")
        return "moe: " + "; ".join(parts)

    def check(self, sample: list, control: bool = False) -> dict:
        """As ``engine.Driver.check``, against this reference, but the
        compared number is ``logit_gap_mean``: the gap of the served
        token's logit below the reference's best, averaged over every
        sampled position.  Routing is discrete: a bf16 rounding that
        moves a token's score across the top-6 boundary swaps one
        expert's whole output at that position, so the widest gap of
        the sample reads as high for the bf16 program as for the int8
        control, while the mean tells them apart.  The widest gap and
        the share of positions off the reference's best are logged."""
        import jax.numpy as jnp
        if len(self.snapshots) >= 2 and not control:
            self.log.append(self.moe_line())
        rows = []
        for i, out in sample:
            prompts = np.asarray(self.pool[i])
            served = np.asarray(out)
            rows += [(prompts[c], served[c]) for c in range(len(served))]
        gaps = []
        step = self.cell["check_rows"]
        P = self.prompt
        for s in range(0, len(rows), step):
            part = rows[s:s + step]
            seq = np.stack([np.concatenate([p, t[:-1]]) for p, t in part])
            served = np.stack([t for _, t in part])
            want = ref.logits(self.params, jnp.asarray(seq), self.m, P - 1)
            if control:
                pick = ref.logits(self.params, jnp.asarray(seq), self.m,
                                  P - 1, int8=True).argmax(-1)
            else:
                pick = jnp.asarray(served)
            best = want.max(-1)
            chosen = jnp.take_along_axis(want, pick[..., None], -1)[..., 0]
            gaps.append(np.asarray(best - chosen, np.float64).ravel())
        gaps = np.concatenate(gaps)
        self.log.append(
            f"{'control' if control else 'program'} logit gap: widest "
            f"{gaps.max()!r}, off the best at {(gaps > 0).mean()!r} of "
            f"{gaps.size} positions")
        return {"logit_gap_mean": float(gaps.mean())}
