"""Batched serving engine: prefill + decode with sharded KV/state caches.

``jit_decode_step`` / ``jit_prefill`` are what the dry-run lowers for the
``decode_*`` / ``prefill_*`` shape cells.  The engine's ``generate`` drives
real batched requests: one jitted prefill program and one jitted decode
step per engine, each traced once per argument shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..models import model as M
from ..sharding import Policy
from ..train.trainer import batch_pspecs, param_shardings

# cache leaf name -> logical axes for its *last* dims (leading stack dims
# padded with None).  kv-head and state-head dims shard over the model
# axis (guarded by divisibility), batch over data(+pod).
_CACHE_AXES: dict[str, tuple] = {
    "k": ("batch", "kv_len", "heads", None),
    "v": ("batch", "kv_len", "heads", None),
    "xk": ("batch", "kv_len", "heads", None),
    "xv": ("batch", "kv_len", "heads", None),
    "c_kv": ("batch", "kv_len", None),
    "k_pe": ("batch", "kv_len", None),
    "ssm": ("batch", "heads", None, None),
    "conv": ("batch", None, "ff"),
    "mlstm": ("batch", "heads", None, None),
    "slstm": ("batch", "heads", None),
    "len": (),
}


def cache_pspecs(policy: Policy, cache_tree) -> Any:
    def spec(path, leaf):
        name = None
        for p in reversed(path):
            if hasattr(p, "key"):
                name = str(p.key)
                break
        axes = _CACHE_AXES.get(name, ())
        ndim = len(leaf.shape)
        ax = axes[-ndim:] if len(axes) > ndim else axes
        ax = (None,) * (ndim - len(ax)) + tuple(ax)
        return policy.param_spec(leaf.shape, ax)
    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def cache_shardings(policy: Policy, cache_tree) -> Any:
    mesh = policy.mesh
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_pspecs(policy, cache_tree))


def jit_decode_step(cfg, policy: Policy, params_shapes, cache_shapes,
                    batch_shapes):
    """serve_step: one new token against an existing cache."""
    mesh = policy.mesh
    pshard = param_shardings(policy, params_shapes)
    cshard = cache_shardings(policy, cache_shapes)
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          batch_pspecs(policy, batch_shapes))
    B = _batch_of(batch_shapes)
    lshard = NamedSharding(
        mesh, policy.guarded_spec((B, 1, cfg.vocab), "batch", None, "vocab"))

    def step(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch, policy)

    return jax.jit(step, in_shardings=(pshard, cshard, bshard),
                   out_shardings=(lshard, cshard), donate_argnums=(1,))


def jit_prefill(cfg, policy: Policy, params_shapes, batch_shapes,
                max_len: int):
    mesh = policy.mesh
    pshard = param_shardings(policy, params_shapes)
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          batch_pspecs(policy, batch_shapes))
    cache_shapes = jax.eval_shape(
        lambda: M.init_cache(cfg, _batch_of(batch_shapes), max_len))
    cshard = cache_shardings(policy, cache_shapes)
    B = _batch_of(batch_shapes)
    lshard = NamedSharding(
        mesh, policy.guarded_spec((B, 1, cfg.vocab), "batch", None, "vocab"))

    def pre(params, batch):
        return M.prefill(cfg, params, batch, max_len=max_len, shd=policy)

    return jax.jit(pre, in_shardings=(pshard, bshard),
                   out_shardings=(lshard, cshard))


def _batch_of(batch_shapes) -> int:
    leaf = jax.tree.leaves(batch_shapes)[0]
    return leaf.shape[0]


# ---------------------------------------------------------------------------
# simple engine for the examples (greedy decode, CPU-friendly)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Engine:
    cfg: Any
    params: Any
    policy: Policy = dataclasses.field(default_factory=Policy)
    # one trace per distinct (batch, cache) shape signature — the decode
    # step used to be re-wrapped in a fresh ``jax.jit`` on every
    # ``generate`` call, which re-traced and re-compiled the whole step
    # each time; ``decode_trace_counts`` makes the reuse observable
    # (regression-tested: two same-shape generates == one trace)
    decode_trace_counts: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _jit_decode: Any = dataclasses.field(
        default=None, repr=False, compare=False)
    # the same for the prompt's forward pass, keyed (tokens shape,
    # max_len): an eager ``M.prefill`` re-traced its layer scan and
    # reloaded it from the compilation cache on every call
    prefill_trace_counts: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _jit_prefill: Any = dataclasses.field(
        default=None, repr=False, compare=False)
    # generate calls so far: the ``call`` attribute of their spans
    _calls: int = dataclasses.field(default=0, repr=False, compare=False)

    def decode_step_fn(self):
        """The engine's single jitted decode step.

        ``jax.jit``'s own cache keys on argument shapes/dtypes, so one
        jitted callable per engine covers every (batch, cache-length)
        combination — new shapes trace once, repeats hit the compile
        cache.  The cache argument is donated, as in ``jit_decode_step``:
        the step writes the new cache into its buffers, and the caller's
        cache is deleted.
        """
        if self._jit_decode is None:
            def step(params, cache, batch):
                key = (tuple(batch["tokens"].shape),
                       tuple(tuple(getattr(l, "shape", ()))
                             for l in jax.tree.leaves(cache)))
                self.decode_trace_counts[key] = \
                    self.decode_trace_counts.get(key, 0) + 1
                return M.decode_step(self.cfg, params, cache, batch,
                                     self.policy)
            self._jit_decode = jax.jit(step, donate_argnums=1)
        return self._jit_decode

    def prefill_fn(self):
        """The engine's single jitted prefill: ``(params, batch, max_len)``
        -> (last-position logits, filled cache), ``max_len`` static.

        The weights are an argument, never closed over, so they are not
        baked into the program as constants.
        """
        if self._jit_prefill is None:
            def prefill(params, batch, max_len):
                key = (tuple(batch["tokens"].shape), max_len)
                self.prefill_trace_counts[key] = \
                    self.prefill_trace_counts.get(key, 0) + 1
                return M.prefill(self.cfg, params, batch, max_len=max_len,
                                 shd=self.policy)
            self._jit_prefill = jax.jit(prefill, static_argnames="max_len")
        return self._jit_prefill

    def generate(self, prompt_tokens, max_new: int = 16,
                 max_len: int | None = None):
        """Greedy batched generation.  prompt_tokens: (B, T) int32.

        Runs ``prefill_fn()`` over the prompt, then ``decode_step_fn()``
        once per new token.

        Spans (``repro.telemetry``): ``engine.generate`` around the call,
        ``engine.prefill`` around the prompt's forward pass (with
        ``model.prefill.*`` under it only on a call that traces), and
        ``engine.decode`` around the token loop, one ``engine.decode_step``
        per step's dispatch and pick.  A model with MoE layers carries
        their stats in the cache's ``moe_stats``; they are read once, at
        the end, into ``repro.telemetry``'s ``moe.*`` counters."""
        B, T = prompt_tokens.shape
        max_len = max_len or (T + max_new)
        self._calls += 1
        with telemetry.span("engine.generate", batch=B, prompt=T,
                            max_new=max_new, call=self._calls):
            with telemetry.span("engine.prefill"):
                logits, cache = self.prefill_fn()(
                    self.params, {"tokens": prompt_tokens}, max_len=max_len)
            with telemetry.span("engine.decode"):
                outs = []
                tok = _greedy(logits)
                step = self.decode_step_fn()
                for i in range(max_new):
                    outs.append(tok)
                    with telemetry.span("engine.decode_step", i=i):
                        logits, cache = step(self.params, cache,
                                             {"tokens": tok})
                        tok = _greedy(logits)
                if "moe_stats" in cache:
                    _count_moe(cache["moe_stats"])
                return jnp.concatenate(outs, axis=1)


def _count_moe(stats) -> None:
    """The MoE stats of one generate, (prefill, decode) x (rows,
    rows_max, experts), into the telemetry counters."""
    stats = np.asarray(stats)
    for phase, (rows, rows_max, experts) in zip(("prefill", "decode"), stats):
        telemetry.add(f"moe.rows/{phase}", rows)
        telemetry.add(f"moe.experts/{phase}", experts)
        telemetry.peak(f"moe.rows_max/{phase}", rows_max)


def _greedy(logits):
    """The next token of each row: the argmax of its last logits."""
    return jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
