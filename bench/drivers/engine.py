"""Driver for a model served by ``repro.serving.engine.Engine``.

Set-up builds the model configuration from the configuration file's
``model`` group, makes the weights on the chip in one jitted call from
the seed (in the served dtype and the program's layout), draws a pool of
prompts, and runs ``Engine.generate`` on the cell's shapes until no call
compiles any more.

A round is one request per client, sent together as one batch:
``Engine.generate`` on a ``(clients, prompt)`` batch of pooled prompts for
``new`` greedy tokens, then a wait for the tokens.

The check runs the reference over each sampled request's prompt and
served tokens and reads, at each served position, how far the served
token's logit lies below the reference's best: ``logit_gap`` is the
widest such gap over the sample.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.common import block, sub_seed
from bench.reference import zamba2 as ref

# configuration-file names that the program's ModelConfig names otherwise
PROGRAM_NAMES = {"attn_every": "zamba_attn_every"}


class Driver:
    SPANS = ("prep", "generate", "sync")
    decode_module = "jit_step"

    def __init__(self, cell: dict, config: dict, seed: int, device):
        self.cell, self.config, self.seed, self.device = cell, config, seed, device
        self.m = ref.dims(config["model"])
        self.clients, self.prompt, self.new = (
            cell["clients"], cell["prompt"], cell["new"])
        self.log: list[str] = []

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import model as M
        from repro.serving.engine import Engine

        fields = {PROGRAM_NAMES.get(k, k): v
                  for k, v in self.config["model"].items()}
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

    def round(self, k: int, span):
        with span("prep"):
            i = k % len(self.pool)
        with span("generate"):
            out = self.engine.generate(self.pool[i], max_new=self.new)
        with span("sync"):
            block(out)
        return i, out

    def counters(self) -> dict:
        return {}

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

    def flops_per_request(self) -> dict:
        return ref.flops_per_request(self.m, self.prompt, self.new)

    def release(self) -> None:
        """Drop the engine and its compiled steps; the weights stay for the
        reference."""
        del self.engine

    def check(self, sample: list, control: bool = False) -> dict:
        """``{name: value}`` of the compared numbers over ``sample``, a
        list of round results.  With ``control`` the reference in int8
        takes the program's place: the gap is of the token that int8
        puts first at each of the same positions."""
        import jax.numpy as jnp
        rows = []
        for i, out in sample:
            prompts = np.asarray(self.pool[i])
            served = np.asarray(out)
            rows += [(prompts[c], served[c]) for c in range(len(served))]
        worst = 0.0
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
            worst = max(worst, float((best - chosen).max()))
        return {"logit_gap": worst}
