"""Model-zoo primitive layers (pure JAX, functional, shard-annotated).

Conventions:
* activations are (batch, seq, ...) laid out as ``B T H D`` for attention;
* every layer is ``fn(params, x, cfg, shd, ...)`` with ``shd`` a
  ``repro.sharding.Policy`` (no-op without a mesh);
* params are plain dict pytrees; init functions live next to apply
  functions; stacked-layer variants are produced by ``jax.vmap`` of init.

Numerics note: layers compute in ``cfg.dtype`` (bf16 for the big configs)
with fp32 softmax/normalizer accumulations — matching what the Pallas
kernels do on TPU.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding import Policy


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def split(key, n: int):
    return jax.random.split(key, n)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dt)


def layer_norm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings (+ M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------

def rope_cos_sin(positions, d_head: int, theta: float):
    """positions (..., T) -> cos/sin (..., T, d_head//2), fp32."""
    inv = 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (B, T, H, D); cos/sin (B, T, D/2) or (B, T, H, D/2)."""
    if cos.ndim == x.ndim - 1:
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def mrope_cos_sin(positions3, d_head: int, theta: float, sections=(16, 24, 24)):
    """M-RoPE (Qwen2-VL): three position streams (t, h, w) each driving a
    section of the rotary dims.  positions3: (3, B, T)."""
    assert sum(sections) == d_head // 2
    cos_p, sin_p = [], []
    inv = 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))
    start = 0
    for s, sec in enumerate(sections):
        ang = positions3[s][..., None].astype(jnp.float32) * inv[start:start + sec]
        cos_p.append(jnp.cos(ang))
        sin_p.append(jnp.sin(ang))
        start += sec
    return jnp.concatenate(cos_p, -1), jnp.concatenate(sin_p, -1)


# ---------------------------------------------------------------------------
# chunked (flash) attention — the jnp oracle shared with the Pallas kernel
# ---------------------------------------------------------------------------

def flash_attention_ref(q, k, v, *, causal: bool = True,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        q_offset: int = 0):
    """Online-softmax blockwise attention (pure jnp + lax.scan).

    q: (B, Tq, Hq, D), k/v: (B, Tk, Hk, D) with Hq % Hk == 0.  Never
    materialises the (Tq, Tk) score matrix; memory is O(q_chunk x kv_chunk).
    ``q_offset`` positions q tokens at kv index ``q_offset + i`` for causal
    masking (prefill continuation / decode).
    """
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hk
    scale = 1.0 / math.sqrt(D)
    q_chunk = min(q_chunk, Tq)
    kv_chunk = min(kv_chunk, Tk)
    nq = -(-Tq // q_chunk)
    nk = -(-Tk // kv_chunk)
    # pad to multiples
    qp = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Tq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - Tk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - Tk), (0, 0), (0, 0)))
    # (nq, B, C, Hk, G, D)
    qs = qp.reshape(B, nq, q_chunk, Hk, G, D).transpose(1, 0, 2, 3, 4, 5)
    ks = kp.reshape(B, nk, kv_chunk, Hk, D).transpose(1, 0, 2, 3, 4)
    vs = vp.reshape(B, nk, kv_chunk, Hk, Dv).transpose(1, 0, 2, 3, 4)
    kv_valid = (jnp.arange(nk * kv_chunk) < Tk).reshape(nk, kv_chunk)

    def q_block(qi, q_blk):
        q_blk = q_blk.astype(jnp.float32) * scale
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inp):
            acc, m, l = carry
            ki, k_blk, v_blk, valid = inp
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            # scores: (B, C, Hk, G, Ck)
            s = jnp.einsum("bchgd,bkhd->bchgk", q_blk,
                           k_blk.astype(jnp.float32))
            mask = valid[None, None, None, None, :]
            if causal:
                cm = q_pos[:, None] >= k_pos[None, :]
                mask = jnp.logical_and(mask, cm[None, :, None, None, :])
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bchgk,bkhd->bchgd", p, v_blk.astype(jnp.float32))
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((B, q_chunk, Hk, G, Dv), jnp.float32)
        m0 = jnp.full((B, q_chunk, Hk, G), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, Hk, G), jnp.float32)
        # remat the kv step: backward recomputes each score block instead of
        # saving nk of them (flash-attention backward's memory contract)
        (acc, m, l), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (acc0, m0, l0),
            (jnp.arange(nk), ks, vs, kv_valid))
        return acc / jnp.maximum(l[..., None], 1e-30)

    out = jax.lax.map(lambda args: q_block(*args), (jnp.arange(nq), qs))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, Hq, Dv)
    return out[:, :Tq].astype(q.dtype)


def plain_attention(q, k, v, *, causal: bool, q_offset: int = 0):
    """Reference dense attention (small shapes / decode).  v's head dim may
    differ from q/k's (MLA)."""
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hk
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Tq, Hk, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    if causal:
        q_pos = q_offset + jnp.arange(Tq)
        mask = q_pos[:, None] >= jnp.arange(Tk)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Tq, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block (llama/qwen/stablelm/mistral/qwen2-vl/zamba2-shared)
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype) -> dict:
    d, dh = cfg.d_model, cfg.d_head
    ks = split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * dh, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * dh, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * dh, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    return p


def gqa_attention(p, x, cfg, shd: Policy, *, positions, cache=None,
                  use_flash: bool | None = None):
    """Returns (out, new_cache).  cache = dict(k, v, len) for decode;
    without one, new_cache is this call's dict(k, v) after positions."""
    B, T, d = x.shape
    dh = cfg.d_head
    q = (x @ p["wq"]).reshape(B, T, cfg.n_heads, dh)
    k = (x @ p["wk"]).reshape(B, T, cfg.n_kv_heads, dh)
    v = (x @ p["wv"]).reshape(B, T, cfg.n_kv_heads, dh)
    q = shd.constrain(q, "batch", "seq", "heads", None, name="attn_q")
    k = shd.constrain(k, "batch", "seq", "kv_heads", None, name="attn_k")
    v = shd.constrain(v, "batch", "seq", "kv_heads", None, name="attn_v")
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.rope:
        if cfg.mrope:
            cos, sin = mrope_cos_sin(positions, dh, cfg.rope_theta,
                                     cfg.mrope_sections)
        else:
            cos, sin = rope_cos_sin(positions[0] if positions.ndim == 3
                                    else positions, dh, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    # without a cache: this call's (k, v), for a prefill to keep
    new_cache = {"k": k, "v": v}
    if cache is not None:
        # decode: insert k/v at cache['len'], attend over the full cache
        idx = cache["len"]
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, idx, axis=1)
        new_cache = {"k": ck, "v": cv, "len": idx + T}
        mask_len = ck.shape[1]
        kv_pos = jnp.arange(mask_len)
        valid = kv_pos < (idx + T)
        # under seq-sharded serving layouts q must stay cheap to move:
        # scores/output then contract against the sharded cache locally
        q = shd.constrain(q, "batch", None, "decode_q_heads", None,
                          name="decode_q")
        o = _decode_attention(q, ck, cv, valid, q_offset=idx)
    else:
        q_off = 0
        if use_flash is None:
            use_flash = T > 1024
        if use_flash == "pallas":
            from ..kernels import ops as K
            o = K.flash_attention(q, k, v, causal=cfg.causal,
                                  block_q=min(cfg.q_chunk, 128),
                                  block_k=min(cfg.kv_chunk, 128))
        elif use_flash:
            o = flash_attention_ref(q, k, v, causal=cfg.causal,
                                    q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                                    q_offset=q_off)
        else:
            o = plain_attention(q, k, v, causal=cfg.causal, q_offset=q_off)
    o = shd.constrain(o, "batch", "seq", "heads", None, name="attn_o")
    of = o.reshape(B, T, cfg.n_heads * dh)
    of = shd.constrain(of, "batch", "seq", "attn_o_feat", name="attn_o_flat")
    out = of @ p["wo"]
    return shd.constrain(out, "batch", "seq_act", "embed", name="attn_out"), new_cache


def _decode_attention(q, k, v, valid, q_offset):
    """Attention of T=1..few query tokens over a padded cache."""
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    Dv = v.shape[-1]
    G = Hq // Hk
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Tq, Hk, G, D).astype(jnp.float32) * scale
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(Tq)
    causal = q_pos[:, None] >= jnp.arange(Tk)[None, :]
    mask = jnp.logical_and(valid[None, :], causal)
    s = jnp.where(mask[None, None, None], s, -1e30)
    # fp32 softmax, then probs cast to the cache dtype before the PV
    # contraction: halves the partial-sum bytes the seq-sharded serving
    # layouts all-reduce (standard practice; f32 path kept for f32 caches)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return o.reshape(B, Tq, Hq, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v3): latent-compressed KV + decoupled RoPE
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    H = cfg.n_heads
    ks = split(key, 8)
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": dense_init(ks[0], d, qr, dtype),
        "q_a_norm": jnp.ones((qr,), dtype),
        "wq_b": dense_init(ks[1], qr, H * (dn + dr), dtype),
        "wkv_a": dense_init(ks[2], d, kvr + dr, dtype),
        "kv_a_norm": jnp.ones((kvr,), dtype),
        "wkv_b": dense_init(ks[3], kvr, H * (dn + dv), dtype),
        "wo": dense_init(ks[4], H * dv, d, dtype),
    }


def mla_attention(p, x, cfg, shd: Policy, *, positions, cache=None):
    """DeepSeek-V3 Multi-head Latent Attention.

    Prefill/train: expanded form.  Decode: *weight-absorbed* form scoring
    directly against the latent cache (the MLA serving optimisation) —
    cache holds only (c_kv[kvr], k_pe[dr]) per position.
    """
    B, T, d = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    q = rms_norm(x @ p["wq_a"], p["q_a_norm"]) @ p["wq_b"]
    q = q.reshape(B, T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    kv_a = x @ p["wkv_a"]
    c_kv, k_pe = kv_a[..., :kvr], kv_a[..., kvr:]
    c_kv = rms_norm(c_kv, p["kv_a_norm"])
    pos = positions[0] if positions.ndim == 3 else positions
    cos, sin = rope_cos_sin(pos, dr, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe[:, :, None, :], cos, sin)[:, :, 0]  # shared across heads
    scale = 1.0 / math.sqrt(dn + dr)

    w_kv_b = p["wkv_b"].reshape(kvr, H, dn + dv)
    w_uk, w_uv = w_kv_b[..., :dn], w_kv_b[..., dn:]

    if cache is not None:
        idx = cache["len"]
        cc = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, idx, axis=1)
        cp = jax.lax.dynamic_update_slice_in_dim(cache["k_pe"], k_pe, idx, axis=1)
        new_cache = {"c_kv": cc, "k_pe": cp, "len": idx + T}
        # absorbed scoring: q_abs (B,T,H,kvr) = q_nope . W_uk
        q_nope = shd.constrain(q_nope, "batch", None, "decode_q_heads", None,
                               name="mla_decode_q")
        q_pe = shd.constrain(q_pe, "batch", None, "decode_q_heads", None,
                             name="mla_decode_qpe")
        q_abs = jnp.einsum("bthn,rhn->bthr", q_nope.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        s = jnp.einsum("bthr,bsr->bhts", q_abs, cc.astype(jnp.float32))
        s = s + jnp.einsum("bthr,bsr->bhts", q_pe.astype(jnp.float32),
                           cp.astype(jnp.float32))
        s = s * scale
        kv_pos = jnp.arange(cc.shape[1])
        q_pos = idx + jnp.arange(T)
        mask = jnp.logical_and(kv_pos[None, :] < idx + T,
                               q_pos[:, None] >= kv_pos[None, :])
        s = jnp.where(mask[None, None], s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhts,bsr->bthr", pr, cc.astype(jnp.float32))
        o = jnp.einsum("bthr,rhv->bthv", o_lat, w_uv.astype(jnp.float32))
        o = o.astype(x.dtype)
    else:
        new_cache = None
        kv = jnp.einsum("btr,rhe->bthe", c_kv, w_kv_b.astype(c_kv.dtype))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, T, H, dr))], -1)
        qf = jnp.concatenate([q_nope, q_pe], -1)
        qf = shd.constrain(qf, "batch", "seq", "heads", None, name="mla_q")
        k = shd.constrain(k, "batch", "seq", "heads", None, name="mla_k")
        if T > 1024:
            o = flash_attention_ref(qf, k, v, causal=True,
                                    q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        else:
            o = plain_attention(qf, k, v, causal=True)
    of = o.reshape(B, T, H * dv)
    of = shd.constrain(of, "batch", "seq", "attn_o_feat", name="mla_o_flat")
    out = of @ p["wo"]
    return shd.constrain(out, "batch", "seq_act", "embed", name="mla_out"), new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def swiglu_init(key, d: int, d_ff: int, dtype) -> dict:
    ks = split(key, 2)
    return {"wi": dense_init(ks[0], d, 2 * d_ff, dtype),
            "wo": dense_init(ks[1], d_ff, d, dtype)}


def swiglu_mlp(p, x, shd: Policy):
    h = x @ p["wi"]
    h = shd.constrain(h, "batch", "seq", "ff", name="mlp_h")
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate) * up
    out = h @ p["wo"]
    return shd.constrain(out, "batch", "seq_act", "embed", name="mlp_out")


# ---------------------------------------------------------------------------
# MoE (granite / deepseek-v3): top-k routing, capacity, shared expert
# ---------------------------------------------------------------------------

def moe_init(key, cfg, dtype) -> dict:
    d, e, ff = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = split(key, 4)
    scale_i = 1.0 / math.sqrt(d)
    scale_o = 1.0 / math.sqrt(ff)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_up": (jax.random.normal(ks[1], (e, d, 2 * ff), jnp.float32)
                 * scale_i).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (e, ff, d), jnp.float32)
                   * scale_o).astype(dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = swiglu_init(ks[3], d, cfg.moe_d_ff * cfg.n_shared_experts, dtype)
    return p


def moe_block(p, x, cfg, shd: Policy):
    """Grouped dispatch-einsum MoE (Switch/MaxText style), static capacity.

    Tokens are partitioned into contiguous *groups* (the group dim shards
    over the data axis), routing capacity is per (group, expert), and the
    dispatch one-hot is (G, Ng, E, cap) — per-device memory is
    tokens_per_device x E x cap, independent of global batch.  Tokens
    beyond capacity are dropped (residual path carries them).  The expert
    dim of the weights shards over the model axis (EP).
    """
    B, T, d = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    N = B * T
    gs = min(getattr(cfg, "moe_group_size", 512), N)
    if N % gs:
        gs = N
    G = N // gs
    xg = x.reshape(G, gs, d)
    xg = shd.constrain(xg, "batch", None, None, name="moe_groups")
    logits = (xg.astype(jnp.float32) @ p["router"])          # (G, Ng, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)            # (G, Ng, K)
    if cfg.moe_renorm:
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    cap = max(int(cfg.moe_capacity_factor * gs * K / E), 1)
    # position of each (token, k) within its (group, expert) queue
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)    # (G, Ng, K, E)
    flat = onehot.reshape(G, gs * K, E)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat               # (G, Ng*K, E)
    pos = (pos_in_e * flat).sum(-1).reshape(G, gs, K)
    keep = pos < cap
    # dispatch (G, Ng, E, cap) one-hot
    disp = (jax.nn.one_hot(gate_idx, E, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=x.dtype)[..., :cap][:, :, :, None, :])
    disp = disp.sum(2)                                       # (G, Ng, E, cap)
    disp = shd.constrain(disp, "batch", None, "experts", None, name="moe_disp")
    xe = jnp.einsum("gnec,gnd->gecd", disp, xg)              # (G, E, cap, d)
    xe = shd.constrain(xe, "batch", "experts", None, None, name="moe_xe")
    h = jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    g, u = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(g) * u
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ye = shd.constrain(ye, "batch", "experts", None, None, name="moe_ye")
    # combine: weight each token's expert outputs by its gate value
    gate_full = (jax.nn.one_hot(gate_idx, E, dtype=x.dtype)
                 * gate_vals.astype(x.dtype)[..., None]).sum(2)  # (G, Ng, E)
    y = jnp.einsum("gnec,gecd,gne->gnd", disp, ye, gate_full)
    out = y.reshape(B, T, d)
    if "shared" in p:
        out = out + swiglu_mlp(p["shared"], x, shd)
    # aux losses for training: load-balance (Switch) in fp32
    me = probs.mean((0, 1))                                  # mean router prob
    ce = (disp.sum((0, 1, 3)) / jnp.maximum(disp.sum(), 1.0))  # fraction routed
    aux = E * jnp.sum(me * ce)
    return shd.constrain(out, "batch", "seq_act", "embed", name="moe_out"), aux


def held_moe_init(key, cfg, dtype) -> dict:
    """Router over all ``n_experts``, its choice-only bias, the held
    experts' relu^2 weights as (held, F, d) each, and the shared expert."""
    d, ff, n = cfg.d_model, cfg.moe_d_ff, cfg.experts_held
    ks = split(key, 5)
    return {
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32),
        "router_bias": jnp.zeros((cfg.n_experts,), jnp.float32),
        "w_up": (jax.random.normal(ks[1], (n, ff, d), jnp.float32)
                 / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (n, ff, d), jnp.float32)
                   / math.sqrt(ff)).astype(dtype),
        "shared": {"wi": dense_init(ks[3], d, cfg.moe_shared_d_ff, dtype),
                   "wo": dense_init(ks[4], cfg.moe_shared_d_ff, d, dtype)},
    }


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def sigmoid_route(p, x, cfg):
    """x (N, d) -> (experts (N, K), weights (N, K) f32): ``s = sigmoid(x
    W_r)`` in f32 over all experts, the top ``moe_top_k`` by ``s +
    router_bias`` (the bias chooses, it does not weigh), weighted by
    ``s`` normalised to sum 1 and scaled by ``moe_routed_scale``."""
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"])
    _, idx = jax.lax.top_k(s + p["router_bias"], cfg.moe_top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * cfg.moe_routed_scale


def held_moe_block(p, x, cfg, shd: Policy, *, use_kernel: bool = False,
                   layer=None):
    """Dropless MoE layer of one chip that holds experts
    ``[moe_expert_offset, + experts_held)`` of ``n_experts`` (Nemotron-H).

    The router (``sigmoid_route``) runs in f32 over all experts.  The
    (token, expert) pairs are sorted by held expert; pairs routed to
    experts held elsewhere add nothing here.  Every pair that lands on a
    held expert is computed, ``relu(x W_up^T)^2 W_down``: with
    ``use_kernel`` by the Pallas ``expert_mlp`` over row blocks padded per
    expert, else by ``jax.lax.ragged_dot``.  The shared relu^2 expert is
    added for every token.  With ``layer``, ``w_up`` and ``w_down`` are
    the stacks of every MoE layer and ``layer`` picks this one's (the
    kernel reads the stack in place).

    Returns (out, stats): stats int32 (3,) = the pairs on held experts,
    the most on one held expert, and the held experts with any.
    """
    B, T, d = x.shape
    N, K, n = B * T, cfg.moe_top_k, cfg.experts_held
    rows = N * min(K, n)              # at most this many pairs are held
    xf = x.reshape(N, d)
    with jax.named_scope("moe.route"):
        idx, w = sigmoid_route(p, xf, cfg)                       # (N, K)
        local = idx.reshape(-1) - cfg.moe_expert_offset
        group = jnp.where((local >= 0) & (local < n), local, n)  # n: elsewhere
        order = jnp.argsort(group, stable=True)[:rows].astype(jnp.int32)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        # each pair's place among the sorted ones
        rank = jnp.zeros((N * K,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32))
        xz = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)])  # N: zeros
    with jax.named_scope("moe.experts"):
        if use_kernel:
            from ..kernels import ops as Kn
            from ..kernels.expert_mlp import block_rows
            per_expert = N * K // cfg.n_experts
            bm = min(128, max(block_rows(x.dtype),
                              1 << max(per_expert - 1, 0).bit_length()))
            place, block_group, n_blocks, padded = Kn.block_layout(
                sizes, rows, bm)
            src = jnp.full((padded,), N, jnp.int32).at[place].set(
                order // K, mode="drop")
            y = Kn.expert_mlp(xz[src], p["w_up"], p["w_down"], block_group,
                              n_blocks, 0 if layer is None else layer,
                              block_m=bm)
        else:
            w_up, w_down = p["w_up"], p["w_down"]
            if layer is not None:
                w_up, w_down = w_up[layer], w_down[layer]
            xs = xz[order // K]
            h = jax.lax.ragged_dot(xs, jnp.swapaxes(w_up, 1, 2), sizes)
            y = jax.lax.ragged_dot(_relu2(h).astype(x.dtype), w_down, sizes)
            place = jnp.arange(rows, dtype=jnp.int32)
        # the pairs held elsewhere add nothing (a select: the rows of
        # blocks the kernel skipped are undefined)
        held = (group < n).reshape(N, K)
        slot = jnp.where(held, place[rank].reshape(N, K), 0)
        routed = jnp.zeros((N, d), jnp.float32)
        for k in range(K):
            routed += jnp.where(held[:, k, None], y[slot[:, k]].astype(
                jnp.float32) * w[:, k, None], 0.0)
    with jax.named_scope("moe.shared"):
        shared = _relu2(xf @ p["shared"]["wi"]) @ p["shared"]["wo"]
    out = (routed.astype(x.dtype) + shared).reshape(B, T, d)
    stats = jnp.stack([sizes.sum(), sizes.max(), (sizes > 0).sum()])
    return (shd.constrain(out, "batch", "seq_act", "embed", name="moe_out"),
            stats.astype(jnp.int32))


# ---------------------------------------------------------------------------
# chunked gated linear recurrence — shared by Mamba2 (SSD) and mLSTM
# ---------------------------------------------------------------------------

def chunked_linear_recurrence(c, b, v, log_a, *, chunk: int,
                              initial_state=None):
    """y_t = c_t^T S_t,  S_t = exp(log_a_t) * S_{t-1} + b_t v_t^T.

    c, b: (B, T, H, N); v: (B, T, H, P); log_a: (B, T, H) (<= 0).
    Returns (y: (B, T, H, P), final_state: (B, H, N, P)).

    This is the Mamba-2 SSD chunked algorithm: intra-chunk work is dense
    matmuls (MXU-friendly), inter-chunk state is a short scan — the
    TPU-native restructuring of the paper's "CumSum favours CPU"
    sequential-recurrence operator.
    """
    B, T, H, N = b.shape
    P = v.shape[-1]
    nc = -(-T // chunk)
    pad = nc * chunk - T
    if pad:
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0)))
    f32 = jnp.float32
    cc = c.reshape(B, nc, chunk, H, N).astype(f32)
    bb = b.reshape(B, nc, chunk, H, N).astype(f32)
    vv = v.reshape(B, nc, chunk, H, P).astype(f32)
    la = log_a.reshape(B, nc, chunk, H).astype(f32)
    cum = jnp.cumsum(la, axis=2)                    # (B, nc, C, H)
    tot = cum[:, :, -1]                             # (B, nc, H)

    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j.  Mask *before*
    # exp: masked entries have diff > 0 and exp would overflow to inf,
    # poisoning gradients through the where (0 * inf = NaN in the vjp).
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,nc,C,C,H)
    ii = jnp.arange(chunk)
    lmask = ii[:, None] >= ii[None, :]
    diff = jnp.where(lmask[None, None, :, :, None], diff, -1e9)
    L = jnp.exp(diff)
    s_intra = jnp.einsum("bgihn,bgjhn->bgijh", cc, bb) * L
    y_intra = jnp.einsum("bgijh,bgjhp->bgihp", s_intra, vv)

    # per-chunk state contribution: sum_j exp(tot - cum_j) b_j v_j^T
    w = jnp.exp(tot[:, :, None, :] - cum)                   # (B,nc,C,H)
    chunk_state = jnp.einsum("bgjh,bgjhn,bgjhp->bghnp", w, bb, vv)

    # inter-chunk scan over nc
    def step(S, inp):
        cs, dec = inp                                       # (B,H,N,P), (B,H)
        S_new = S * jnp.exp(dec)[..., None, None] + cs
        return S_new, S                                     # emit state *before* chunk

    S0 = (jnp.zeros((B, H, N, P), f32) if initial_state is None
          else initial_state.astype(f32))
    S_final, states_in = jax.lax.scan(
        step, S0, (chunk_state.transpose(1, 0, 2, 3, 4),
                   tot.transpose(1, 0, 2)))
    states_in = states_in.transpose(1, 0, 2, 3, 4)          # (B,nc,H,N,P)
    y_inter = jnp.einsum("bgihn,bghnp,bgih->bgihp", cc, states_in,
                         jnp.exp(cum))
    y = (y_intra + y_inter).reshape(B, nc * chunk, H, P)[:, :T]
    return y.astype(v.dtype), S_final


def linear_recurrence_step(S, c_t, b_t, v_t, log_a_t):
    """Single decode step: S' = a*S + b v^T; y = c^T S'."""
    f32 = jnp.float32
    S = S.astype(f32)
    a = jnp.exp(log_a_t.astype(f32))[..., None, None]
    S_new = S * a + jnp.einsum("bhn,bhp->bhnp", b_t.astype(f32), v_t.astype(f32))
    y = jnp.einsum("bhn,bhnp->bhp", c_t.astype(f32), S_new)
    return y.astype(v_t.dtype), S_new


# ---------------------------------------------------------------------------
# Mamba2 block (zamba2 backbone)
# ---------------------------------------------------------------------------

def mamba2_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    di = cfg.ssm_d_inner
    H = cfg.ssm_heads
    N = cfg.ssm_state
    ks = split(key, 6)
    conv_dim = di + 2 * N * cfg.ssm_groups
    return {
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * N * cfg.ssm_groups + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim), jnp.float32)
                   * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_w": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[2], di, d, dtype),
    }


def mamba2_block(p, x, cfg, shd: Policy, *, state=None,
                 use_kernel: bool = False):
    """Mamba-2 (SSD).  state = dict(ssm (B,H,N,P), conv (B, k-1, convdim))
    for single-step decode; None for a full sequence, whose new state is
    the final SSM state and the convolution's last k-1 inputs.  Heads
    share B and C in ``ssm_groups`` groups, and the gated RMS norm is
    taken per group."""
    B, T, d = x.shape
    di, H, N, G = cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    P = di // H
    conv_dim = di + 2 * N * G
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim], axis=-1)
    z = shd.constrain(z, "batch", "seq", "ff", name="ssm_z")
    # depthwise causal conv over (x, B, C)
    if state is not None:
        conv_in = jnp.concatenate([state["conv"], xbc], axis=1)
        new_conv = conv_in[:, -(cfg.ssm_conv - 1):]
        xbc = jnp.einsum("bkc,kc->bc", conv_in[:, -cfg.ssm_conv:],
                         p["conv_w"])[:, None, :] + p["conv_b"]
    else:
        pad = jnp.zeros((B, cfg.ssm_conv - 1, conv_dim), xbc.dtype)
        xin = jnp.concatenate([pad, xbc], axis=1)
        new_conv = xin[:, T:]                  # the last k-1 inputs
        xbc = sum(xin[:, i:i + T] * p["conv_w"][i] for i in range(cfg.ssm_conv))
        xbc = xbc + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, Bc, Cc = jnp.split(xbc, [di, di + N * G], axis=-1)
    Tx = xs.shape[1]
    xs = xs.reshape(B, Tx, H, P)
    Bc = Bc.reshape(B, Tx, G, N)
    Cc = Cc.reshape(B, Tx, G, N)
    rep = H // G
    Bh = jnp.repeat(Bc, rep, axis=2)
    Ch = jnp.repeat(Cc, rep, axis=2)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,T,H)
    A = -jnp.exp(p["A_log"])
    log_a = dt * A                                                 # (B,T,H)
    xdt = xs * dt[..., None].astype(xs.dtype)
    if state is not None:
        y, S = linear_recurrence_step(state["ssm"], Ch[:, 0], Bh[:, 0],
                                      xdt[:, 0], log_a[:, 0])
        y = y[:, None]
        new_state = {"ssm": S, "conv": new_conv}
    elif use_kernel:
        from ..kernels import ops as K
        y, S = K.ssd_scan(Ch, Bh, xdt, log_a, chunk=cfg.ssm_chunk)
        new_state = {"ssm": S, "conv": new_conv}
    else:
        y, S = chunked_linear_recurrence(Ch, Bh, xdt, log_a,
                                         chunk=cfg.ssm_chunk)
        new_state = {"ssm": S, "conv": new_conv}
    y = y + xs * p["D"][None, None, :, None].astype(xs.dtype)
    y = y.reshape(B, y.shape[1], di)
    y = y * jax.nn.silu(z[:, :y.shape[1]])
    if G == 1:
        y = rms_norm(y, p["norm_w"], cfg.ssm_norm_eps)
    else:
        # the gated norm is taken over each group's di / G channels
        w = p["norm_w"].reshape(G, di // G)
        y = rms_norm(y.reshape(B, Tx, G, di // G), w,
                     cfg.ssm_norm_eps).reshape(B, Tx, di)
    out = y @ p["out_proj"]
    return shd.constrain(out, "batch", "seq_act", "embed", name="ssm_out"), new_state


# ---------------------------------------------------------------------------
# xLSTM blocks (mLSTM: matrix memory; sLSTM: scalar memory + state mixing)
# ---------------------------------------------------------------------------

def mlstm_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    H = cfg.n_heads
    di = cfg.xlstm_d_inner
    dh = di // H
    ks = split(key, 8)
    return {
        "up": dense_init(ks[0], d, 2 * di, dtype),
        "wq": dense_init(ks[1], di, di, dtype),
        "wk": dense_init(ks[2], di, di, dtype),
        "wv": dense_init(ks[3], di, di, dtype),
        "wif": dense_init(ks[4], di, 2 * H, dtype),  # input+forget gates
        "norm_w": jnp.ones((di,), dtype),
        "down": dense_init(ks[5], di, d, dtype),
    }


def mlstm_block(p, x, cfg, shd: Policy, *, state=None,
                use_kernel: bool = False):
    """mLSTM: exponentially-gated matrix memory == gated linear attention.
    Uses the same chunked recurrence as Mamba2 (TPU adaptation)."""
    B, T, d = x.shape
    H = cfg.n_heads
    di = cfg.xlstm_d_inner
    dh = di // H
    h = x @ p["up"]
    hx, hg = jnp.split(h, 2, axis=-1)
    q = (hx @ p["wq"]).reshape(B, T, H, dh)
    k = (hx @ p["wk"]).reshape(B, T, H, dh) / math.sqrt(dh)
    v = (hx @ p["wv"]).reshape(B, T, H, dh)
    gates = (hx @ p["wif"]).astype(jnp.float32)
    i_g, f_g = jnp.split(gates, 2, axis=-1)                   # (B,T,H)
    log_f = -jax.nn.softplus(-f_g)                            # log sigmoid
    # stabilised exponential input gate: fold exp(i) into k
    k = k * jnp.exp(jnp.minimum(i_g, 8.0))[..., None].astype(k.dtype)
    # normaliser: append ones column to v
    v_aug = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
    if state is not None:
        y_aug, S = linear_recurrence_step(state["ssm"], q[:, 0], k[:, 0],
                                          v_aug[:, 0], log_f[:, 0])
        y_aug = y_aug[:, None]
        new_state = {"ssm": S}
    elif use_kernel:
        from ..kernels import ops as K
        y_aug, S = K.ssd_scan(q, k, v_aug, log_f, chunk=cfg.ssm_chunk)
        new_state = {"ssm": S}
    else:
        y_aug, S = chunked_linear_recurrence(q, k, v_aug, log_f,
                                             chunk=cfg.ssm_chunk)
        new_state = {"ssm": S}
    y, nrm = y_aug[..., :dh], y_aug[..., dh:]
    y = y / jnp.maximum(jnp.abs(nrm), 1.0).astype(y.dtype)
    y = y.reshape(B, y.shape[1], di)
    y = rms_norm(y, p["norm_w"]) * jax.nn.silu(hg[:, :y.shape[1]])
    out = y @ p["down"]
    return shd.constrain(out, "batch", "seq_act", "embed", name="mlstm_out"), new_state


def slstm_init(key, cfg, dtype) -> dict:
    d = cfg.d_model
    H = cfg.n_heads
    dh = d // H
    ks = split(key, 4)
    return {
        "w_in": dense_init(ks[0], d, 4 * d, dtype),     # z i f o pre-acts
        "r": (jax.random.normal(ks[1], (H, dh, 4 * dh), jnp.float32)
              / math.sqrt(dh)).astype(dtype),           # block-diag recurrent
        "bias": jnp.zeros((4 * d,), dtype),
        "norm_w": jnp.ones((d,), dtype),
        "ff": swiglu_init(ks[2], d, cfg.slstm_ff, dtype),
    }


def slstm_block(p, x, cfg, shd: Policy, *, state=None):
    """sLSTM: scalar memories, exponential gating, per-head state mixing.
    Truly sequential -> lax.scan over time (the CPU-affine recurrence of
    the paper, kept as a scan on TPU)."""
    B, T, d = x.shape
    H = cfg.n_heads
    dh = d // H
    pre_all = x @ p["w_in"] + p["bias"]                      # (B,T,4d)

    def cell(carry, pre_t):
        c, n, hprev, m = carry                               # (B,H,dh) each, m (B,H,dh)
        rec = jnp.einsum("bhe,hef->bhf", hprev, p["r"].astype(jnp.float32))
        pre = pre_t.reshape(B, H, 4 * dh).astype(jnp.float32) + rec
        z, i, f, o = jnp.split(pre, 4, axis=-1)
        z = jnp.tanh(z)
        o = jax.nn.sigmoid(o)
        log_f = -jax.nn.softplus(-f)
        m_new = jnp.maximum(log_f + m, i)
        i_p = jnp.exp(i - m_new)
        f_p = jnp.exp(log_f + m - m_new)
        c_new = f_p * c + i_p * z
        n_new = f_p * n + i_p
        h_new = o * c_new / jnp.maximum(jnp.abs(n_new), 1.0)
        return (c_new, n_new, h_new, m_new), h_new

    if state is None:
        zeros = jnp.zeros((B, H, dh), jnp.float32)
        carry0 = (zeros, zeros, zeros, zeros)
    else:
        carry0 = state["slstm"]
    carry, hs = jax.lax.scan(cell, carry0,
                             pre_all.transpose(1, 0, 2))     # scan over T
    h = hs.transpose(1, 0, 2, 3).reshape(B, T, d).astype(x.dtype)
    h = rms_norm(h, p["norm_w"])
    out = h + swiglu_mlp(p["ff"], h, shd)
    return shd.constrain(out, "batch", "seq_act", "embed", name="slstm_out"), \
        {"slstm": carry}


# ---------------------------------------------------------------------------
# cross-attention (seamless enc-dec)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg, dtype) -> dict:
    d, dh = cfg.d_model, cfg.d_head
    ks = split(key, 4)
    return {
        "wq": dense_init(ks[0], d, cfg.n_heads * dh, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * dh, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * dh, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * dh, d, dtype),
    }


def cross_attention(p, x, memory, cfg, shd: Policy):
    B, T, d = x.shape
    S = memory.shape[1]
    dh = cfg.d_head
    q = (x @ p["wq"]).reshape(B, T, cfg.n_heads, dh)
    k = (memory @ p["wk"]).reshape(B, S, cfg.n_kv_heads, dh)
    v = (memory @ p["wv"]).reshape(B, S, cfg.n_kv_heads, dh)
    q = shd.constrain(q, "batch", "seq", "heads", None, name="xattn_q")
    if S > 2048:
        o = flash_attention_ref(q, k, v, causal=False,
                                q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    else:
        o = plain_attention(q, k, v, causal=False)
    of = o.reshape(B, T, cfg.n_heads * dh)
    of = shd.constrain(of, "batch", "seq", "attn_o_feat", name="xattn_o_flat")
    out = of @ p["wo"]
    return shd.constrain(out, "batch", "seq_act", "embed", name="xattn_out")
