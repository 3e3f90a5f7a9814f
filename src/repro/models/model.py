"""Composable LM: block-spec patterns -> init / forward / prefill / decode.

Every assigned architecture maps to one of five block patterns:

* ``dense``    — GQA attention + SwiGLU (llama3.2 / mistral-large /
                 qwen3 (qk-norm) / stablelm / qwen2-vl (M-RoPE, stub
                 patch embeddings))
* ``moe``      — GQA attention + top-k MoE (granite)
* ``mla_moe``  — MLA attention, first-k dense then MoE + shared expert,
                 optional MTP head (deepseek-v3)
* ``encdec``   — encoder + decoder with cross-attention (seamless, stub
                 frame embeddings)
* ``xlstm``    — alternating mLSTM / sLSTM pairs
* ``zamba2``   — Mamba2 backbone + one *shared* GQA attention block applied
                 every ``zamba_attn_every`` layers
* ``nemotron_h`` — one mixer a layer, Mamba2, held-expert MoE or GQA
                 without positions, as ``cfg.layer_pattern`` says
                 (nemotron-3-nano)

All patterns but ``nemotron_h`` loop over stacked layer parameters so HLO
size (and CPU compile time for the 512-device dry-run) is
depth-independent.  ``nemotron_h`` stacks each mixer kind's parameters
and unrolls its layers: its pattern is not periodic, and its runs of one
kind are one layer long, so a scan per run would be as long as the
unrolled program.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from .. import telemetry
from ..sharding import Policy, NO_POLICY
from . import layers as L


def _stack_init(init_fn, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


# nemotron_h: each mixer kind's stacked parameters
_KIND_BLOCKS = {"M": "mamba_blocks", "E": "moe_blocks", "*": "attn_blocks"}


def _kinds(cfg) -> list[tuple[str, int]]:
    """(mixer kind, index among the layers of its kind) of each layer."""
    seen = dict.fromkeys(_KIND_BLOCKS, 0)
    out = []
    for kind in cfg.layer_kinds:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def _layer(params, kind: str, i: int):
    """Layer ``i`` of ``kind``'s stacked parameters; an MoE layer keeps
    the experts' weights stacked, for the kernel to read in place."""
    lp = jax.tree.map(lambda a: a[i], params[_KIND_BLOCKS[kind]])
    if kind == "E":
        stack = params["moe_blocks"]["moe"]
        lp["moe"] = dict(lp["moe"], w_up=stack["w_up"], w_down=stack["w_down"])
    return lp


def _moe_fold(stats: list, prev=None):
    """Per-layer MoE stats (rows, rows_max, experts) summed, maxed,
    summed; onto ``prev`` where given."""
    acc = jnp.zeros((3,), jnp.int32) if prev is None else prev
    for st in stats:
        acc = jnp.stack([acc[0] + st[0], jnp.maximum(acc[1], st[1]),
                         acc[2] + st[2]])
    return acc


def _nemotron_h_layers(cfg, params, h, cache, shd, pos, *, decode: bool):
    """``nemotron_h``'s layers over ``h`` and their cache: a prompt's
    prefill into an empty cache, or with ``decode`` one step.  Returns
    (h, the new cache), the MoE layers' stats folded onto the phase's row
    of ``moe_stats``."""
    idx, phase = cache["len"], int(decode)
    # each layer's state is written into the cache's own stacks, so a
    # donated cache is updated in place and not copied
    ssm, conv = cache["ssm"], cache["conv"]
    ks, vs = cache["attn"]["k"], cache["attn"]["v"]
    stats = []
    for kind, i in _kinds(cfg):
        lp = _layer(params, kind, i)
        x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
        if kind == "M":
            state = {"ssm": ssm[i], "conv": conv[i]} if decode else None
            m, ns = L.mamba2_block(lp["mamba"], x, cfg, shd, state=state,
                                   use_kernel=cfg.use_kernels)
            ssm = ssm.at[i].set(ns["ssm"])
            conv = conv.at[i].set(ns["conv"])
        elif kind == "E":
            m, st = L.held_moe_block(lp["moe"], x, cfg, shd,
                                     use_kernel=cfg.use_kernels, layer=i)
            stats.append(st)
        else:
            ck, cv = ks[i], vs[i]
            if decode:
                m, kv = L.gqa_attention(
                    lp["attn"], x, cfg, shd, positions=pos,
                    cache={"k": ck, "v": cv, "len": idx})
            else:
                m, kv = L.gqa_attention(
                    lp["attn"], x, cfg, shd, positions=pos,
                    use_flash="pallas" if cfg.use_kernels else None)
                kv = {n: jax.lax.dynamic_update_slice_in_dim(c, kv[n], 0, 1)
                      for n, c in (("k", ck), ("v", cv))}
            ks = ks.at[i].set(kv["k"])
            vs = vs.at[i].set(kv["v"])
        h = h + m
    moe = cache["moe_stats"]
    return h, {"ssm": ssm, "conv": conv, "attn": {"k": ks, "v": vs},
               "len": idx + h.shape[1],
               "moe_stats": moe.at[phase].set(_moe_fold(stats, moe[phase]))}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(cfg, key) -> dict:
    dt = cfg.jdtype
    k_embed, k_blocks, k_head, k_extra = jax.random.split(key, 4)
    params: dict[str, Any] = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab, cfg.d_model),
                                    jnp.float32) * 0.02).astype(dt),
        "final_norm": jnp.ones((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab, dt)

    def dense_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "attn": L.gqa_init(k1, cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dt)}

    def moe_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "attn": L.gqa_init(k1, cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "moe": L.moe_init(k2, cfg, dt)}

    def mla_dense_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "attn": L.mla_init(k1, cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dt)}

    def mla_moe_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "attn": L.mla_init(k1, cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "moe": L.moe_init(k2, cfg, dt)}

    def enc_block(k):
        k1, k2 = jax.random.split(k)
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "attn": L.gqa_init(k1, cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "mlp": L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dt)}

    def dec_block(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "attn": L.gqa_init(k1, cfg, dt),
                "lnx": jnp.ones((cfg.d_model,), dt),
                "xattn": L.cross_attn_init(k2, cfg, dt),
                "ln2": jnp.ones((cfg.d_model,), dt),
                "mlp": L.swiglu_init(k3, cfg.d_model, cfg.d_ff, dt)}

    def mamba_block(k):
        return {"ln1": jnp.ones((cfg.d_model,), dt),
                "mamba": L.mamba2_init(k, cfg, dt)}

    def xlstm_pair(k):
        k1, k2 = jax.random.split(k)
        return {"ln_m": jnp.ones((cfg.d_model,), dt),
                "mlstm": L.mlstm_init(k1, cfg, dt),
                "ln_s": jnp.ones((cfg.d_model,), dt),
                "slstm": L.slstm_init(k2, cfg, dt)}

    bp = cfg.block_pattern
    if bp == "dense":
        params["blocks"] = _stack_init(dense_block, k_blocks, cfg.n_layers)
    elif bp == "moe":
        params["blocks"] = _stack_init(moe_block, k_blocks, cfg.n_layers)
    elif bp == "mla_moe":
        kd, km, kt = jax.random.split(k_blocks, 3)
        params["dense_blocks"] = _stack_init(mla_dense_block, kd, cfg.first_k_dense)
        params["moe_blocks"] = _stack_init(
            mla_moe_block, km, cfg.n_layers - cfg.first_k_dense)
        if cfg.mtp:
            k1, k2 = jax.random.split(kt)
            params["mtp"] = {
                "proj": L.dense_init(k1, 2 * cfg.d_model, cfg.d_model, dt),
                "block": mla_dense_block(k2),
                "norm": jnp.ones((cfg.d_model,), dt),
            }
    elif bp == "encdec":
        ke, kd = jax.random.split(k_blocks)
        params["enc_blocks"] = _stack_init(enc_block, ke, cfg.n_enc_layers)
        params["dec_blocks"] = _stack_init(dec_block, kd, cfg.n_dec_layers)
        params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
    elif bp == "xlstm":
        params["blocks"] = _stack_init(xlstm_pair, k_blocks, cfg.n_layers // 2)
    elif bp == "zamba2":
        params["blocks"] = _stack_init(mamba_block, k_blocks, cfg.n_layers)
        params["shared_attn"] = {"ln": jnp.ones((cfg.d_model,), dt),
                                 "attn": L.gqa_init(k_extra, cfg, dt)}
    elif bp == "nemotron_h":
        inits = {
            "M": mamba_block,
            "E": lambda k: {"ln1": jnp.ones((cfg.d_model,), dt),
                            "moe": L.held_moe_init(k, cfg, dt)},
            "*": lambda k: {"ln1": jnp.ones((cfg.d_model,), dt),
                            "attn": L.gqa_init(k, cfg, dt)}}
        for kind, k in zip(_KIND_BLOCKS, jax.random.split(k_blocks, 3)):
            params[_KIND_BLOCKS[kind]] = _stack_init(
                inits[kind], k, cfg.layer_kinds.count(kind))
    else:
        raise ValueError(f"unknown block pattern {bp!r}")
    return params


def param_shapes(cfg) -> Any:
    """ShapeDtypeStruct pytree (no allocation) — dry-run input."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# forward (training / full-sequence)
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _embed_in(cfg, params, batch, shd: Policy):
    """tokens (B,T) int32 -> embeddings, or pass through stub embeddings."""
    if "embeds" in batch:
        h = batch["embeds"].astype(cfg.jdtype)
    else:
        h = jnp.take(params["embed"], batch["tokens"], axis=0)
    return shd.constrain(h, "batch", "seq_act", "embed", name="embed_out")


def _positions(cfg, batch, T: int):
    B = (batch["tokens"].shape[0] if "tokens" in batch
         else batch["embeds"].shape[0])
    if cfg.mrope:
        if "positions" in batch:
            return batch["positions"]
        p = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return jnp.stack([p, p, p])           # text-only: t=h=w stream
    return jnp.broadcast_to(jnp.arange(T)[None], (B, T))


def _logits(cfg, params, h, shd: Policy):
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = h @ w
    return shd.constrain(logits, "batch", "seq", "vocab", name="logits")


def forward(cfg, params, batch, shd: Policy = NO_POLICY,
            return_hidden: bool = False):
    """Full-sequence forward -> (logits, aux_loss[, hidden])."""
    h = _embed_in(cfg, params, batch, shd)
    T = h.shape[1]
    pos = _positions(cfg, batch, T)
    bp = cfg.block_pattern
    aux = jnp.zeros((), jnp.float32)

    if bp in ("dense", "moe"):
        def body(carry, lp):
            h, aux = carry
            a, _ = L.gqa_attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   cfg, shd, positions=pos)
            h = h + a
            if bp == "moe":
                m, a_l = L.moe_block(lp["moe"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                     cfg, shd)
                aux = aux + a_l
            else:
                m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return (h + m, aux), None
        (h, aux), _ = jax.lax.scan(_maybe_remat(body, cfg), (h, aux),
                                   params["blocks"])

    elif bp == "mla_moe":
        def dense_body(carry, lp):
            h, aux = carry
            a, _ = L.mla_attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   cfg, shd, positions=pos)
            h = h + a
            m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return (h + m, aux), None

        def moe_body(carry, lp):
            h, aux = carry
            a, _ = L.mla_attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   cfg, shd, positions=pos)
            h = h + a
            m, a_l = L.moe_block(lp["moe"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                 cfg, shd)
            return (h + m, aux + a_l), None

        (h, aux), _ = jax.lax.scan(_maybe_remat(dense_body, cfg), (h, aux),
                                   params["dense_blocks"])
        (h, aux), _ = jax.lax.scan(_maybe_remat(moe_body, cfg), (h, aux),
                                   params["moe_blocks"])

    elif bp == "encdec":
        # batch: embeds (encoder input, stub frontend) + tokens (decoder)
        enc_cfg = dataclasses.replace(cfg, causal=False)
        e = batch["embeds"].astype(cfg.jdtype)
        e = shd.constrain(e, "batch", "seq_act", "embed", name="enc_in")
        epos = jnp.broadcast_to(jnp.arange(e.shape[1])[None], e.shape[:2])

        def enc_body(h, lp):
            a, _ = L.gqa_attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   enc_cfg, shd, positions=epos)
            h = h + a
            m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return h + m, None
        e, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), e, params["enc_blocks"])
        memory = L.rms_norm(e, params["enc_norm"], cfg.norm_eps)

        h = jnp.take(params["embed"], batch["tokens"], axis=0)
        h = shd.constrain(h, "batch", "seq_act", "embed", name="dec_in")
        T = h.shape[1]
        dpos = jnp.broadcast_to(jnp.arange(T)[None], (h.shape[0], T))

        def dec_body(h, lp):
            a, _ = L.gqa_attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   cfg, shd, positions=dpos)
            h = h + a
            x = L.cross_attention(lp["xattn"], L.rms_norm(h, lp["lnx"], cfg.norm_eps),
                                  memory, cfg, shd)
            h = h + x
            m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return h + m, None
        h, _ = jax.lax.scan(_maybe_remat(dec_body, cfg), h, params["dec_blocks"])

    elif bp == "xlstm":
        def body(h, lp):
            a, _ = L.mlstm_block(lp["mlstm"], L.rms_norm(h, lp["ln_m"], cfg.norm_eps),
                                 cfg, shd)
            h = h + a
            s, _ = L.slstm_block(lp["slstm"], L.rms_norm(h, lp["ln_s"], cfg.norm_eps),
                                 cfg, shd)
            return h + s, None
        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["blocks"])

    elif bp == "zamba2":
        every = cfg.zamba_attn_every
        G = cfg.n_layers // every
        grouped = jax.tree.map(
            lambda x: x.reshape(G, every, *x.shape[1:]), params["blocks"])
        sa = params["shared_attn"]

        def group_body(h, glp):
            def inner(h, lp):
                m, _ = L.mamba2_block(lp["mamba"],
                                      L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                      cfg, shd)
                return h + m, None
            h, _ = jax.lax.scan(inner, h, glp)
            a, _ = L.gqa_attention(sa["attn"], L.rms_norm(h, sa["ln"], cfg.norm_eps),
                                   cfg, shd, positions=pos)
            return h + a, None
        h, _ = jax.lax.scan(_maybe_remat(group_body, cfg), h, grouped)

    elif bp == "nemotron_h":
        def layer(kind, i, lp, h):
            x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
            if kind == "M":
                m, _ = L.mamba2_block(lp["mamba"], x, cfg, shd)
            elif kind == "E":
                m, _ = L.held_moe_block(lp["moe"], x, cfg, shd, layer=i)
            else:
                m, _ = L.gqa_attention(lp["attn"], x, cfg, shd, positions=pos)
            return h + m
        for kind, i in _kinds(cfg):
            h = _maybe_remat(partial(layer, kind, i), cfg)(
                _layer(params, kind, i), h)
    else:
        raise ValueError(bp)

    logits = _logits(cfg, params, h, shd)
    if return_hidden:
        return logits, aux, h
    return logits, aux


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------

def _ce(logits, labels):
    lg = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = ((lse - gold) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    zloss = ((lse ** 2) * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return nll, zloss, mask.sum()


def loss_fn(cfg, params, batch, shd: Policy = NO_POLICY):
    """Next-token cross-entropy (+ MoE aux + z-loss + MTP for deepseek)."""
    use_mtp = cfg.mtp and "mtp" in params and "tokens" in batch
    if use_mtp:
        logits, aux, h = forward(cfg, params, batch, shd, return_hidden=True)
    else:
        logits, aux = forward(cfg, params, batch, shd)
    labels = batch["labels"]
    nll, zloss, ntok = _ce(logits, labels)
    total = nll + 1e-4 * zloss + cfg.aux_loss_coef * aux
    metrics = {"nll": nll, "zloss": zloss, "aux": aux, "tokens": ntok}

    if use_mtp:
        # DeepSeek-V3 multi-token prediction (depth 1): predict token t+2
        # from h_t combined with the embedding of token t+1.
        mtp = params["mtp"]
        tok_next = batch["tokens"][:, 1:]
        e_next = jnp.take(params["embed"], tok_next, axis=0)
        hin = jnp.concatenate([h[:, :-1], e_next], axis=-1) @ mtp["proj"]
        T1 = hin.shape[1]
        pos = jnp.broadcast_to(jnp.arange(T1)[None], hin.shape[:2])
        lp = mtp["block"]
        a, _ = L.mla_attention(lp["attn"], L.rms_norm(hin, lp["ln1"], cfg.norm_eps),
                               cfg, shd, positions=pos)
        hin = hin + a
        hin = hin + L.swiglu_mlp(lp["mlp"], L.rms_norm(hin, lp["ln2"], cfg.norm_eps),
                                 shd)
        hin = L.rms_norm(hin, mtp["norm"], cfg.norm_eps)
        w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        mtp_logits = hin @ w
        mtp_labels = jnp.concatenate(
            [labels[:, 2:], jnp.full_like(labels[:, :1], -1)], axis=1)
        mtp_nll, _, _ = _ce(mtp_logits, mtp_labels)
        total = total + 0.3 * mtp_nll
        metrics["mtp_nll"] = mtp_nll
    return total, metrics


# ---------------------------------------------------------------------------
# KV / state caches
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int) -> dict:
    dt = cfg.jdtype
    bp = cfg.block_pattern
    Lc = cfg.n_layers

    def attn_cache(n, length):
        return {"k": jnp.zeros((n, batch, length, cfg.n_kv_heads, cfg.d_head), dt),
                "v": jnp.zeros((n, batch, length, cfg.n_kv_heads, cfg.d_head), dt)}

    if bp == "dense" or bp == "moe":
        return {"attn": attn_cache(Lc, max_len),
                "len": jnp.zeros((), jnp.int32)}
    if bp == "mla_moe":
        def mla_cache(n):
            return {"c_kv": jnp.zeros((n, batch, max_len, cfg.kv_lora_rank), dt),
                    "k_pe": jnp.zeros((n, batch, max_len, cfg.qk_rope_head_dim), dt)}
        return {"dense": mla_cache(cfg.first_k_dense),
                "moe": mla_cache(Lc - cfg.first_k_dense),
                "len": jnp.zeros((), jnp.int32)}
    if bp == "encdec":
        n = cfg.n_dec_layers
        return {"attn": attn_cache(n, max_len),
                # cross-attention K/V computed once from encoder memory
                "xk": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, cfg.d_head), dt),
                "xv": jnp.zeros((n, batch, max_len, cfg.n_kv_heads, cfg.d_head), dt),
                "len": jnp.zeros((), jnp.int32)}
    if bp == "xlstm":
        P2 = Lc // 2
        H = cfg.n_heads
        dh = cfg.xlstm_d_inner // H
        dhs = cfg.d_model // H
        return {
            "mlstm": jnp.zeros((P2, batch, H, dh, dh + 1), jnp.float32),
            "slstm": tuple(jnp.zeros((P2, batch, H, dhs), jnp.float32)
                           for _ in range(4)),
            "len": jnp.zeros((), jnp.int32)}
    if bp == "zamba2":
        G = cfg.n_layers // cfg.zamba_attn_every
        conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_state * cfg.ssm_groups
        P = cfg.ssm_d_inner // cfg.ssm_heads
        return {
            "ssm": jnp.zeros((Lc, batch, cfg.ssm_heads, cfg.ssm_state, P),
                             jnp.float32),
            "conv": jnp.zeros((Lc, batch, cfg.ssm_conv - 1, conv_dim), dt),
            "attn": attn_cache(G, max_len),
            "len": jnp.zeros((), jnp.int32)}
    if bp == "nemotron_h":
        # SSM and convolution state of the Mamba2 layers beside the K/V
        # of the attention layers; moe_stats: the MoE layers' (rows,
        # rows_max, experts) of the prefill [0] and of the decode steps [1]
        kinds = cfg.layer_kinds
        n_m = kinds.count("M")
        conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_state * cfg.ssm_groups
        return {
            "ssm": jnp.zeros((n_m, batch, cfg.ssm_heads, cfg.ssm_state,
                              cfg.ssm_headdim), jnp.float32),
            "conv": jnp.zeros((n_m, batch, cfg.ssm_conv - 1, conv_dim), dt),
            "attn": attn_cache(kinds.count("*"), max_len),
            "len": jnp.zeros((), jnp.int32),
            "moe_stats": jnp.zeros((2, 3), jnp.int32)}
    raise ValueError(bp)


# ---------------------------------------------------------------------------
# decode step (one token; the ``serve_step`` the dry-run lowers)
# ---------------------------------------------------------------------------

_at = partial(jax.lax.dynamic_index_in_dim, keepdims=False)
_put = partial(jax.lax.dynamic_update_index_in_dim, axis=0)


def _scan_layers(body, h, xs, cache):
    """``body(h, x, entry) -> (h, new entry)`` over stacked layers: ``xs``
    (the layers' weights) are scanned, and ``cache`` (stacks of the
    layers' entries) is carried whole, each layer's new entry written
    back into its stack by index.  A donated cache is so updated where
    it lies: new stacks built as the scan's outputs would be copied into
    the donated buffers after the loop."""
    def step(carry, x_i):
        h, c = carry
        x, i = x_i
        h, new = body(h, x, jax.tree.map(lambda a: _at(a, i), c))
        return (h, jax.tree.map(lambda a, e: _put(a, e, i), c, new)), None

    n = jax.tree.leaves(xs)[0].shape[0]
    (h, cache), _ = jax.lax.scan(step, (h, cache), (xs, jnp.arange(n)))
    return h, cache


def _zamba2_decode_layers(cfg, params, h, cache, shd, pos):
    """zamba2's layers for one token: each Mamba2 layer reads its weights
    and state from the full stacks by global layer index, and the shared
    attention runs after every ``zamba_attn_every``-th layer on its
    group's K/V.  Nothing is sliced as a sub-stack: a scan over groups
    whose ``xs`` are stacks of layers makes XLA copy each group's weights
    before the layers read them."""
    every = cfg.zamba_attn_every
    blocks = params["blocks"]
    sa = params["shared_attn"]
    # ``in_proj`` read through a transposed view, whose per-layer ``.T``
    # below folds into the dot: zamba2-2.7b's is 10448 wide, off the
    # 128-lane tiling, so a TPU keeps its rows minor, and a row-major read
    # copies the whole stack each step
    blocks = {**blocks, "mamba": {
        **blocks["mamba"],
        "in_proj": jnp.swapaxes(blocks["mamba"]["in_proj"], -1, -2)}}

    def group(g, carry):
        def layer(j, carry):
            h, ssm, conv = carry
            i = g * every + j
            lp = jax.tree.map(lambda a: _at(a, i), blocks)
            lp["mamba"]["in_proj"] = lp["mamba"]["in_proj"].T
            state = {"ssm": _at(ssm, i), "conv": _at(conv, i)}
            m, ns = L.mamba2_block(lp["mamba"],
                                   L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   cfg, shd, state=state)
            return h + m, _put(ssm, ns["ssm"], i), _put(conv, ns["conv"], i)

        h, ssm, conv, ck, cv = carry
        h, ssm, conv = jax.lax.fori_loop(0, every, layer, (h, ssm, conv))
        a, nc = L.gqa_attention(sa["attn"],
                                L.rms_norm(h, sa["ln"], cfg.norm_eps), cfg,
                                shd, positions=pos,
                                cache={"k": _at(ck, g), "v": _at(cv, g),
                                       "len": cache["len"]})
        return h + a, ssm, conv, _put(ck, nc["k"], g), _put(cv, nc["v"], g)

    h, ssm, conv, nk, nv = jax.lax.fori_loop(
        0, cfg.n_layers // every, group,
        (h, cache["ssm"], cache["conv"], cache["attn"]["k"],
         cache["attn"]["v"]))
    return h, {"ssm": ssm, "conv": conv, "attn": {"k": nk, "v": nv}}


def decode_step(cfg, params, cache, batch, shd: Policy = NO_POLICY):
    """One decode step.  batch: tokens (B, 1) (+ embeds for stubs).
    Returns (logits (B, 1, V), new_cache)."""
    h = _embed_in(cfg, params, batch, shd)
    B, T = h.shape[:2]
    idx = cache["len"]
    if cfg.mrope:
        p = jnp.broadcast_to(idx[None, None], (B, T))
        pos = jnp.stack([p, p, p])
    else:
        pos = jnp.broadcast_to(idx[None, None], (B, T))
    bp = cfg.block_pattern

    if bp in ("dense", "moe"):
        def body(h, lp, kv):
            a, nc = L.gqa_attention(
                lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps), cfg, shd,
                positions=pos, cache={**kv, "len": idx})
            h = h + a
            if bp == "moe":
                m, _ = L.moe_block(lp["moe"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                   cfg, shd)
            else:
                m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                 shd)
            return h + m, {"k": nc["k"], "v": nc["v"]}
        h, kv = _scan_layers(body, h, params["blocks"], cache["attn"])
        new_cache = {"attn": kv, "len": idx + T}

    elif bp == "mla_moe":
        def mk_body(is_moe):
            def body(h, lp, c):
                a, nc = L.mla_attention(
                    lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps), cfg, shd,
                    positions=pos, cache={**c, "len": idx})
                h = h + a
                if is_moe:
                    m, _ = L.moe_block(lp["moe"],
                                       L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                       cfg, shd)
                else:
                    m = L.swiglu_mlp(lp["mlp"],
                                     L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
                return h + m, {"c_kv": nc["c_kv"], "k_pe": nc["k_pe"]}
            return body
        h, dense = _scan_layers(mk_body(False), h, params["dense_blocks"],
                                cache["dense"])
        h, moe = _scan_layers(mk_body(True), h, params["moe_blocks"],
                              cache["moe"])
        new_cache = {"dense": dense, "moe": moe, "len": idx + T}

    elif bp == "encdec":
        def body(h, xs, kv):
            lp, xk, xv = xs
            a, nc = L.gqa_attention(
                lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps), cfg, shd,
                positions=pos, cache={**kv, "len": idx})
            h = h + a
            # cross-attention against cached encoder K/V
            xq = (L.rms_norm(h, lp["lnx"], cfg.norm_eps) @ lp["xattn"]["wq"])
            xq = xq.reshape(B, T, cfg.n_heads, cfg.d_head)
            valid = jnp.ones((xk.shape[1],), bool)
            xo = L._decode_attention(xq, xk, xv, valid, q_offset=xk.shape[1])
            h = h + xo.reshape(B, T, -1) @ lp["xattn"]["wo"]
            m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return h + m, {"k": nc["k"], "v": nc["v"]}
        h, kv = _scan_layers(body, h, (params["dec_blocks"], cache["xk"],
                                       cache["xv"]), cache["attn"])
        new_cache = {"attn": kv, "xk": cache["xk"], "xv": cache["xv"],
                     "len": idx + T}

    elif bp == "xlstm":
        def body(h, lp, st):
            a, nm = L.mlstm_block(lp["mlstm"],
                                  L.rms_norm(h, lp["ln_m"], cfg.norm_eps),
                                  cfg, shd, state={"ssm": st["mlstm"]})
            h = h + a
            s, ns = L.slstm_block(lp["slstm"],
                                  L.rms_norm(h, lp["ln_s"], cfg.norm_eps),
                                  cfg, shd, state={"slstm": st["slstm"]})
            return h + s, {"mlstm": nm["ssm"], "slstm": ns["slstm"]}
        h, st = _scan_layers(body, h, params["blocks"],
                             {"mlstm": cache["mlstm"], "slstm": cache["slstm"]})
        new_cache = {**st, "len": idx + T}

    elif bp == "zamba2":
        h, new_cache = _zamba2_decode_layers(cfg, params, h, cache, shd, pos)
        new_cache["len"] = idx + T

    elif bp == "nemotron_h":
        h, new_cache = _nemotron_h_layers(cfg, params, h, cache, shd, pos,
                                          decode=True)
    else:
        raise ValueError(bp)

    return _logits(cfg, params, h, shd), new_cache


# ---------------------------------------------------------------------------
# prefill (the ``prefill_step`` the dry-run lowers for prefill shapes)
# ---------------------------------------------------------------------------

def prefill(cfg, params, batch, max_len: int, shd: Policy = NO_POLICY):
    """Run the full prompt, returning (last-position logits, filled cache).

    For recurrent patterns the cache is the final recurrent state; for
    attention patterns the K/V cache is written back chunk-free via a
    second pass of the per-layer K/V projections (cheap relative to
    attention itself) — a deliberate simplification that keeps prefill a
    single scan-over-layers program.

    Spans (``repro.telemetry``): ``model.prefill.setup`` (embedding,
    positions, empty cache) and ``model.prefill.logits``; the layer scan
    between them is the rest of the caller's span.  Under ``jax.jit``
    they fire at trace time.
    """
    with telemetry.span("model.prefill.setup"):
        h = _embed_in(cfg, params, batch, shd)
        B, T = h.shape[:2]
        pos = _positions(cfg, batch, T)
        bp = cfg.block_pattern
        cache = init_cache(cfg, B, max_len)

    if bp in ("dense", "moe"):
        def body(h, xs):
            lp, ck, cv = xs
            x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
            # write K/V into the cache at [0, T)
            k = (x @ lp["attn"]["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
            v = (x @ lp["attn"]["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
            if cfg.qk_norm:
                k = L.rms_norm(k, lp["attn"]["k_norm"])
            cs, sn = L.rope_cos_sin(pos[0] if pos.ndim == 3 else pos,
                                    cfg.d_head, cfg.rope_theta)
            if cfg.mrope:
                cs, sn = L.mrope_cos_sin(pos, cfg.d_head, cfg.rope_theta,
                                         cfg.mrope_sections)
            k = L.apply_rope(k, cs, sn)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1)
            a, _ = L.gqa_attention(
                lp["attn"], x, cfg, shd, positions=pos,
                use_flash="pallas" if cfg.use_kernels else None)
            h = h + a
            if bp == "moe":
                m, _ = L.moe_block(lp["moe"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                   cfg, shd)
            else:
                m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                 shd)
            return h + m, (ck, cv)
        h, (nk, nv) = jax.lax.scan(
            _maybe_remat(body, cfg), h,
            (params["blocks"], cache["attn"]["k"], cache["attn"]["v"]))
        cache = {"attn": {"k": nk, "v": nv},
                 "len": jnp.asarray(T, jnp.int32)}

    elif bp == "mla_moe":
        def mk_body(is_moe):
            def body(h, xs):
                lp, cc, cp = xs
                x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
                kv_a = x @ lp["attn"]["wkv_a"]
                c_kv = L.rms_norm(kv_a[..., :cfg.kv_lora_rank],
                                  lp["attn"]["kv_a_norm"])
                k_pe = kv_a[..., cfg.kv_lora_rank:]
                cs, sn = L.rope_cos_sin(pos, cfg.qk_rope_head_dim, cfg.rope_theta)
                k_pe = L.apply_rope(k_pe[:, :, None, :], cs, sn)[:, :, 0]
                cc = jax.lax.dynamic_update_slice_in_dim(cc, c_kv, 0, axis=1)
                cp = jax.lax.dynamic_update_slice_in_dim(cp, k_pe, 0, axis=1)
                a, _ = L.mla_attention(lp["attn"], x, cfg, shd, positions=pos)
                h = h + a
                if is_moe:
                    m, _ = L.moe_block(lp["moe"],
                                       L.rms_norm(h, lp["ln2"], cfg.norm_eps),
                                       cfg, shd)
                else:
                    m = L.swiglu_mlp(lp["mlp"],
                                     L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
                return h + m, (cc, cp)
            return body
        h, (dc, dp) = jax.lax.scan(
            _maybe_remat(mk_body(False), cfg), h,
            (params["dense_blocks"], cache["dense"]["c_kv"], cache["dense"]["k_pe"]))
        h, (mc, mp) = jax.lax.scan(
            _maybe_remat(mk_body(True), cfg), h,
            (params["moe_blocks"], cache["moe"]["c_kv"], cache["moe"]["k_pe"]))
        cache = {"dense": {"c_kv": dc, "k_pe": dp},
                 "moe": {"c_kv": mc, "k_pe": mp},
                 "len": jnp.asarray(T, jnp.int32)}

    elif bp == "encdec":
        # encode, then prefill the decoder prompt + cross K/V
        enc_cfg = dataclasses.replace(cfg, causal=False)
        e = batch["embeds"].astype(cfg.jdtype)
        epos = jnp.broadcast_to(jnp.arange(e.shape[1])[None], e.shape[:2])

        def enc_body(h, lp):
            a, _ = L.gqa_attention(lp["attn"], L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   enc_cfg, shd, positions=epos)
            h = h + a
            m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return h + m, None
        e, _ = jax.lax.scan(_maybe_remat(enc_body, cfg), e, params["enc_blocks"])
        memory = L.rms_norm(e, params["enc_norm"], cfg.norm_eps)
        S = memory.shape[1]

        h = jnp.take(params["embed"], batch["tokens"], axis=0)
        T2 = h.shape[1]
        dpos = jnp.broadcast_to(jnp.arange(T2)[None], (B, T2))

        def dec_body(h, xs):
            lp, ck, cv = xs
            x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
            k = (x @ lp["attn"]["wk"]).reshape(B, T2, cfg.n_kv_heads, cfg.d_head)
            v = (x @ lp["attn"]["wv"]).reshape(B, T2, cfg.n_kv_heads, cfg.d_head)
            cs, sn = L.rope_cos_sin(dpos, cfg.d_head, cfg.rope_theta)
            k = L.apply_rope(k, cs, sn)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1)
            a, _ = L.gqa_attention(lp["attn"], x, cfg, shd, positions=dpos)
            h = h + a
            xh = L.rms_norm(h, lp["lnx"], cfg.norm_eps)
            xo = L.cross_attention(lp["xattn"], xh, memory, cfg, shd)
            h = h + xo
            xk = (memory @ lp["xattn"]["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
            xv = (memory @ lp["xattn"]["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.d_head)
            m = L.swiglu_mlp(lp["mlp"], L.rms_norm(h, lp["ln2"], cfg.norm_eps), shd)
            return h + m, (ck, cv, xk, xv)
        h, (nk, nv, xk, xv) = jax.lax.scan(
            _maybe_remat(dec_body, cfg), h,
            (params["dec_blocks"], cache["attn"]["k"], cache["attn"]["v"]))
        cache = {"attn": {"k": nk, "v": nv}, "xk": xk, "xv": xv,
                 "len": jnp.asarray(T2, jnp.int32)}

    elif bp == "xlstm":
        def body(h, lp):
            a, nm = L.mlstm_block(lp["mlstm"], L.rms_norm(h, lp["ln_m"], cfg.norm_eps),
                                  cfg, shd, use_kernel=cfg.use_kernels)
            h = h + a
            s, ns = L.slstm_block(lp["slstm"], L.rms_norm(h, lp["ln_s"], cfg.norm_eps),
                                  cfg, shd)
            return h + s, (nm["ssm"], ns["slstm"])
        h, (nms, nss) = jax.lax.scan(_maybe_remat(body, cfg), h, params["blocks"])
        cache = {"mlstm": nms, "slstm": nss, "len": jnp.asarray(T, jnp.int32)}

    elif bp == "zamba2":
        every = cfg.zamba_attn_every
        G = cfg.n_layers // every
        grouped = jax.tree.map(
            lambda x: x.reshape(G, every, *x.shape[1:]), params["blocks"])
        sa = params["shared_attn"]

        def group_body(h, xs):
            glp, ck, cv = xs
            def inner(h, lp):
                x = L.rms_norm(h, lp["ln1"], cfg.norm_eps)
                m, ns = L.mamba2_block(lp["mamba"], x, cfg, shd,
                                       use_kernel=cfg.use_kernels)
                return h + m, (ns["ssm"], ns["conv"])
            h, (ssm_g, conv_g) = jax.lax.scan(inner, h, glp)
            x = L.rms_norm(h, sa["ln"], cfg.norm_eps)
            k = (x @ sa["attn"]["wk"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
            v = (x @ sa["attn"]["wv"]).reshape(B, T, cfg.n_kv_heads, cfg.d_head)
            cs, sn = L.rope_cos_sin(pos, cfg.d_head, cfg.rope_theta)
            k = L.apply_rope(k, cs, sn)
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1)
            a, _ = L.gqa_attention(
                sa["attn"], x, cfg, shd, positions=pos,
                use_flash="pallas" if cfg.use_kernels else None)
            return h + a, (ssm_g, conv_g, ck, cv)
        h, (nssm, nconv, nk, nv) = jax.lax.scan(
            _maybe_remat(group_body, cfg), h,
            (grouped, cache["attn"]["k"], cache["attn"]["v"]))
        cache = {
            "ssm": nssm.reshape(cfg.n_layers, *nssm.shape[2:]),
            "conv": nconv.reshape(cfg.n_layers, *nconv.shape[2:]),
            "attn": {"k": nk, "v": nv}, "len": jnp.asarray(T, jnp.int32)}

    elif bp == "nemotron_h":
        h, cache = _nemotron_h_layers(cfg, params, h, cache, shd, pos,
                                      decode=False)
    else:
        raise ValueError(bp)

    with telemetry.span("model.prefill.logits"):
        logits = _logits(cfg, params, h[:, -1:], shd)
    return logits, cache
