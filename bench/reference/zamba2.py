"""Plain reference of zamba2 as this repository serves it, in
``jax.numpy`` and float32, one layer at a time.

The model (``repro.configs`` ``zamba2-2.7b``, block pattern ``zamba2``):
token embedding; ``layers`` Mamba2 layers, each ``h += mamba(rms(h))``,
and after every ``attn_every`` of them the one shared attention block,
``h += attn(rms(h))`` (causal, rotary positions, its weights shared by
every use); a final RMS norm and an untied head.  The Mamba2 layer is:
``z, xBC, dt = x W_in``; a causal depthwise convolution of width
``conv`` over ``xBC``, then SiLU; ``dt = softplus(dt + dt_bias)``,
``a = exp(-exp(A_log) dt)``; the scan ``S_t = a_t S_{t-1} + B_t (x_t
dt_t)^T``, ``y_t = C_t . S_t + D x_t``; ``rms(y * silu(z)) W_out``.
Nothing of the program is imported.

The weights are the benchmark's own (``init``), laid out as the program
takes them.  The control runs the same forward with every dense matmul
in int8 (``int8=True``): weights rounded per output channel and
activations per token to the int8 grid of their largest magnitude.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

f32 = jnp.float32


def dims(model: dict) -> dict:
    """Derived sizes of the ``model`` group of a configuration file."""
    d = model["d_model"]
    di = model["ssm_expand"] * d
    return dict(model, d_inner=di, ssm_heads=di // model["ssm_headdim"],
                conv_dim=di + 2 * model["ssm_state"])


def init(key, m: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights in the program's layout, at the scales its own
    initialiser uses: normal / sqrt(fan-in) for projections, 0.02 for the
    embedding, 0.2 for the convolution; unit norms, ``A_log`` spread over
    log 1 .. log 16, ``D`` one and ``dt_bias`` zero."""
    d, di, H, L = m["d_model"], m["d_inner"], m["ssm_heads"], m["n_layers"]
    dh, nh, V = m["d_head"], m["n_heads"], m["vocab"]
    ks = iter(jax.random.split(key, 10))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape, f32)
                / math.sqrt(fan_in)).astype(dtype)

    proj = 2 * di + 2 * m["ssm_state"] + H
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, d), f32)).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense((d, V), d),
        "blocks": {
            "ln1": jnp.ones((L, d), dtype),
            "mamba": {
                "in_proj": dense((L, d, proj), d),
                "conv_w": (0.2 * jax.random.normal(
                    next(ks), (L, m["ssm_conv"], m["conv_dim"]), f32)
                           ).astype(dtype),
                "conv_b": jnp.zeros((L, m["conv_dim"]), dtype),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.linspace(1.0, 16.0, H)), (L, H)).astype(f32),
                "D": jnp.ones((L, H), f32),
                "dt_bias": jnp.zeros((L, H), f32),
                "norm_w": jnp.ones((L, di), dtype),
                "out_proj": dense((L, di, d), di),
            },
        },
        "shared_attn": {
            "ln": jnp.ones((d,), dtype),
            "attn": {"wq": dense((d, nh * dh), d),
                     "wk": dense((d, m["n_kv_heads"] * dh), d),
                     "wv": dense((d, m["n_kv_heads"] * dh), d),
                     "wo": dense((nh * dh, d), nh * dh)},
        },
    }


def _int8(x, axis):
    """``x`` rounded to the int8 grid of its largest magnitude along
    ``axis``, kept in f32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, int8):
    x, w = x.astype(f32), w.astype(f32)
    if int8:
        x, w = _int8(x, -1), _int8(w, 0)
    return x @ w


def _rms(x, w, eps):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(f32)


@functools.partial(jax.jit, static_argnames=("sizes", "int8"))
def _mamba(blocks, i, h, *, sizes, int8):
    m = dict(sizes)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a[i], blocks)
        B, T, _ = h.shape
        di, N, H = m["d_inner"], m["ssm_state"], m["ssm_heads"]
        P, K = m["ssm_headdim"], m["ssm_conv"]
        x = _rms(h, p["ln1"], m["norm_eps"])
        mp = p["mamba"]
        zxbcdt = _mm(x, mp["in_proj"], int8)
        z, xbc, dt = jnp.split(zxbcdt, [di, di + m["conv_dim"]], axis=-1)
        xpad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        w = mp["conv_w"].astype(f32)
        xbc = sum(xpad[:, k:k + T] * w[k] for k in range(K)) \
            + mp["conv_b"].astype(f32)
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :di].reshape(B, T, H, P)
        Bm = xbc[..., di:di + N]
        Cm = xbc[..., di + N:]
        dt = jax.nn.softplus(dt + mp["dt_bias"])                 # (B, T, H)
        log_a = -jnp.exp(mp["A_log"]) * dt
        xdt = xs * dt[..., None]

        def step(S, inp):
            b_t, c_t, x_t, la_t = inp
            S = S * jnp.exp(la_t)[..., None, None] \
                + b_t[:, None, :, None] * x_t[:, :, None, :]
            return S, jnp.einsum("bn,bhnp->bhp", c_t, S)

        tm = lambda a: jnp.moveaxis(a, 1, 0)
        _, y = jax.lax.scan(step, jnp.zeros((B, H, N, P), f32),
                            (tm(Bm), tm(Cm), tm(xdt), tm(log_a)))
        y = jnp.moveaxis(y, 0, 1) + xs * mp["D"][:, None]
        y = _rms(y.reshape(B, T, di) * jax.nn.silu(z), mp["norm_w"],
                 m["norm_eps"])
        return h + _mm(y, mp["out_proj"], int8)


@functools.partial(jax.jit, static_argnames=("sizes", "int8"))
def _attention(p, h, *, sizes, int8):
    m = dict(sizes)
    with jax.default_matmul_precision("highest"):
        B, T, _ = h.shape
        nh, nk, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
        x = _rms(h, p["ln"], m["norm_eps"])
        a = p["attn"]
        q = _mm(x, a["wq"], int8).reshape(B, T, nh, dh)
        k = _mm(x, a["wk"], int8).reshape(B, T, nk, dh)
        v = _mm(x, a["wv"], int8).reshape(B, T, nk, dh)
        inv = 1.0 / (m["rope_theta"] ** (jnp.arange(0, dh, 2, dtype=f32) / dh))
        ang = jnp.arange(T, dtype=f32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

        def rope(t):
            t1, t2 = jnp.split(t, 2, axis=-1)
            return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], -1)

        q, k = rope(q), rope(k)
        k = jnp.repeat(k, nh // nk, axis=2)
        v = jnp.repeat(v, nh // nk, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q / math.sqrt(dh), k)
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        pr = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v).reshape(B, T, nh * dh)
        return h + _mm(o, a["wo"], int8)


@functools.partial(jax.jit, static_argnames=("sizes", "int8"))
def _head(params, h, *, sizes, int8):
    m = dict(sizes)
    with jax.default_matmul_precision("highest"):
        x = _rms(h, params["final_norm"], m["norm_eps"])
        return _mm(x, params["lm_head"], int8)


def logits(params, tokens, m: dict, first: int, *, int8: bool = False):
    """Logits ``(B, T - first, vocab)`` at positions ``first`` .. ``T-1``
    of ``tokens`` ``(B, T)``, run layer by layer at ``highest`` matmul
    precision."""
    sizes = tuple(sorted(m.items()))
    h = jnp.take(params["embed"], tokens, axis=0).astype(f32)
    for i in range(m["n_layers"]):
        h = _mamba(params["blocks"], i, h, sizes=sizes, int8=int8)
        if (i + 1) % m["attn_every"] == 0:
            h = _attention(params["shared_attn"], h, sizes=sizes, int8=int8)
    return _head(params, h[:, first:], sizes=sizes, int8=int8)


def flops_per_request(m: dict, prompt: int, new: int) -> dict:
    """Operations one request needs, by precision class: the prefill of
    ``prompt`` tokens, then ``new - 1`` decode steps (the last of the
    ``new`` steps the program runs yields a token nobody reads, and the
    prefill's second ``x W_in`` is a recomputation: neither counts).
    Dense matmuls and attention in bf16; the prefill's SSD scan on the
    kernel's f32 HIGHEST count; the decode scan step (4 N P per head) in
    bf16."""
    from bench.counts import ssd_scan
    d, di, H, N, P = (m["d_model"], m["d_inner"], m["ssm_heads"],
                      m["ssm_state"], m["ssm_headdim"])
    L, G = m["n_layers"], m["n_layers"] // m["attn_every"]
    nh, nk, dh, V = m["n_heads"], m["n_kv_heads"], m["d_head"], m["vocab"]
    mamba = d * (2 * di + 2 * N + H) + di * d
    attn = d * (nh + 2 * nk) * dh + nh * dh * d
    per_token = 2.0 * (L * mamba + G * attn)
    bf16 = prompt * per_token + 2.0 * d * V
    bf16 += G * 4.0 * nh * dh * prompt * (prompt + 1) / 2
    ssd, _ = ssd_scan.count(B=1, T=prompt, H=H, N=N, P=P,
                            chunk=m["ssm_chunk"], dtype="bfloat16")
    for j in range(new - 1):
        ctx = prompt + j + 1
        bf16 += per_token + 2.0 * d * V + G * 4.0 * nh * dh * ctx \
            + L * 4.0 * H * N * P
    return {"bf16": bf16, "f32_highest": L * ssd["f32_highest"]}
