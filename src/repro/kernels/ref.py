"""Pure-jnp oracles for every Pallas kernel in this package.

These are the single source of truth for kernel semantics: the interpret-
mode sweep tests assert each ``pallas_call`` against the matching function
here.  The model zoo (``repro.models.layers``) calls the same math, so a
kernel validated against ref.py is validated against the models too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# flash attention (causal/full GQA) — mirrors layers.flash_attention_ref
# but in the simplest dense form (the oracle must be obviously correct).
# ---------------------------------------------------------------------------


def attention_ref(q, k, v, *, causal: bool = True, q_offset: int = 0):
    """Dense softmax attention.  q (B,Tq,Hq,D); k/v (B,Tk,Hk,D), Hq%Hk==0.

    fp32 scores/normalizer, output cast back to q.dtype — the numerics
    contract the Pallas kernel implements on the MXU.
    """
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
# SSD / chunked gated linear recurrence — sequential-scan oracle
# ---------------------------------------------------------------------------


def ssd_scan_ref(c, b, v, log_a, *, initial_state=None):
    """Sequential oracle: S_t = exp(log_a_t)*S_{t-1} + b_t v_t^T; y_t = c_t^T S_t.

    c, b: (B,T,H,N); v: (B,T,H,P); log_a: (B,T,H).
    Returns (y (B,T,H,P), S_final (B,H,N,P)).  O(T) steps — slow but
    unambiguous; the kernel's chunked algebra must reproduce it.
    """
    B, T, H, N = b.shape
    P = v.shape[-1]
    f32 = jnp.float32

    def step(S, inp):
        c_t, b_t, v_t, la_t = inp
        S = S * jnp.exp(la_t.astype(f32))[..., None, None]
        S = S + jnp.einsum("bhn,bhp->bhnp", b_t.astype(f32), v_t.astype(f32))
        y = jnp.einsum("bhn,bhnp->bhp", c_t.astype(f32), S)
        return S, y

    S0 = (jnp.zeros((B, H, N, P), f32) if initial_state is None
          else initial_state.astype(f32))
    S_final, ys = jax.lax.scan(
        step, S0,
        (c.transpose(1, 0, 2, 3), b.transpose(1, 0, 2, 3),
         v.transpose(1, 0, 2, 3), log_a.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2, 3).astype(v.dtype), S_final


# ---------------------------------------------------------------------------
# MoE top-k dispatch/combine — dense-loop oracle
# ---------------------------------------------------------------------------


def expert_glu_ref(x, w_up, w_down):
    """Oracle for the fused expert GLU on capacity-padded tokens.

    x: (E, cap, d); w_up: (E, d, 2F) ([..., :F] gate, [..., F:] up);
    w_down: (E, F, d).  fp32 math, output in x.dtype.
    """
    h = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                   w_up.astype(jnp.float32))
    g, u = jnp.split(h, 2, axis=-1)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                   w_down.astype(jnp.float32))
    return y.astype(x.dtype)


def moe_dispatch_combine_ref(x, gate_idx, gate_vals, w_up, w_down, *,
                             capacity: int):
    """Oracle for the fused MoE expert-apply with capacity dropping.

    x: (T, d) tokens; gate_idx/gate_vals: (T, K); w_up: (E, d, 2F);
    w_down: (E, F, d).  A (token, k) assignment beyond the expert's
    ``capacity`` (in first-come order over the flattened (t, k) stream)
    is dropped.  Returns (T, d) combined expert outputs.
    """
    T, d = x.shape
    K = gate_idx.shape[1]
    E = w_up.shape[0]
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)       # (T,K,E)
    flat = onehot.reshape(T * K, E)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
    pos_tk = (pos * onehot).sum(-1)                              # (T,K)
    keep = pos_tk < capacity

    xf = x.astype(jnp.float32)
    out = jnp.zeros((T, d), jnp.float32)
    for e in range(E):
        h = xf @ w_up[e].astype(jnp.float32)                     # (T, 2F)
        g, u = jnp.split(h, 2, axis=-1)
        y_e = (jax.nn.silu(g) * u) @ w_down[e].astype(jnp.float32)
        w_e = ((gate_idx == e) * keep * gate_vals).sum(-1)       # (T,)
        out = out + y_e * w_e[:, None]
    return out.astype(x.dtype)
