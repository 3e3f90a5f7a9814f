"""Plain reference of Nemotron-H (``nemotron_h``) as this chip holds it, in
``jax.numpy`` and float32, one layer at a time.

The model (NVIDIA-Nemotron-3-Nano-30B-A3B): token embedding; the first
``num_hidden_layers`` layers of ``hybrid_override_pattern``, each
``h += mixer(rms(h))`` with one mixer a layer; a final RMS norm and an
untied head.  The mixers:

* ``M``, Mamba2: ``z, xBC, dt = x W_in``; a causal depthwise convolution
  of width ``conv_kernel`` with bias over ``xBC``, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``a = exp(-exp(A_log) dt)``; heads
  share ``B`` and ``C`` in ``n_groups`` groups (head h reads group
  ``h // (heads / groups)``); the scan ``S_t = a_t S_{t-1} + B_t (x_t
  dt_t)^T``, ``y_t = C_t . S_t + D x_t``; ``rms_g(y * silu(z)) W_out``
  with the RMS norm taken over each group's channels.
* ``E``, experts: ``s = sigmoid(x W_r)`` over all ``router_experts``;
  the top ``num_experts_per_tok`` by ``s + e_score_correction_bias``,
  weighted by ``s`` normalised to sum 1 and times
  ``routed_scaling_factor``; of those, only the experts this chip holds
  (``expert_offset`` .. ``+ n_routed_experts``) add
  ``w relu(x W_up^T)^2 W_down``; the shared expert
  ``relu(x W_si)^2 W_so`` is added for every token.
* ``*``, attention: causal GQA without positions (NoPE).

Nothing of the program is imported.  The weights are the benchmark's own
(``init``), laid out as the program takes them.  The control runs the
same forward with every dense matmul in int8 (``int8=True``): weights
rounded per output channel and activations per token to the int8 grid
of their largest magnitude.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

f32 = jnp.float32
KINDS = {"M": "mamba_blocks", "E": "moe_blocks", "*": "attn_blocks"}


def dims(config: dict) -> dict:
    """The sizes a run needs, from a configuration file's published keys."""
    di = config["mamba_num_heads"] * config["mamba_head_dim"]
    G, N = config["n_groups"], config["ssm_state_size"]
    kinds = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return dict(
        d_model=config["hidden_size"], kinds=kinds, vocab=config["vocab_size"],
        ssm_heads=config["mamba_num_heads"], ssm_headdim=config["mamba_head_dim"],
        ssm_state=N, ssm_groups=G, ssm_conv=config["conv_kernel"],
        ssm_chunk=config["chunk_size"], d_inner=di, conv_dim=di + 2 * G * N,
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], d_head=config["head_dim"],
        experts=config["router_experts"], held=config["n_routed_experts"],
        offset=config["expert_offset"], top_k=config["num_experts_per_tok"],
        moe_ff=config["moe_intermediate_size"],
        shared_ff=config["moe_shared_expert_intermediate_size"],
        scale=config["routed_scaling_factor"],
        norm_eps=config["norm_eps"], mixer_eps=config["layer_norm_epsilon"])


def init(key, m: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights in the program's layout: projections normal /
    sqrt(fan-in) (experts' up as (F, d), down as (F, d)), the embedding
    normal 0.02, the router in f32 with a correction bias normal 0.05;
    Mamba2's convolution uniform +-1/sqrt(width) with its bias, ``A_log``
    log(1 .. heads), ``dt_bias`` the inverse softplus of dt log-uniform
    in [0.001, 0.1] (floored at 1e-4), ``D`` and the norms one."""
    d, V, H = m["d_model"], m["vocab"], m["ssm_heads"]
    di, K, cd = m["d_inner"], m["ssm_conv"], m["conv_dim"]
    n_m, n_e, n_a = (m["kinds"].count(k) for k in "ME*")
    n, F, SF, E = m["held"], m["moe_ff"], m["shared_ff"], m["experts"]
    nh, nk, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    ks = iter(jax.random.split(key, 24))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.normal(next(ks), shape, f32)
                / math.sqrt(fan_in)).astype(dt)

    def uniform(shape, bound):
        return jax.random.uniform(next(ks), shape, f32, -bound, bound
                                  ).astype(dtype)

    dt = jnp.exp(jax.random.uniform(next(ks), (n_m, H), f32, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "embed": (0.02 * jax.random.normal(next(ks), (V, d), f32)).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense((d, V), d),
        "mamba_blocks": {
            "ln1": jnp.ones((n_m, d), dtype),
            "mamba": {
                "in_proj": dense((n_m, d, 2 * di + 2 * m["ssm_groups"]
                                  * m["ssm_state"] + H), d),
                "conv_w": uniform((n_m, K, cd), 1 / math.sqrt(K)),
                "conv_b": uniform((n_m, cd), 1 / math.sqrt(K)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, H + 1, dtype=f32)), (n_m, H)),
                "D": jnp.ones((n_m, H), f32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm_w": jnp.ones((n_m, di), dtype),
                "out_proj": dense((n_m, di, d), di),
            },
        },
        "moe_blocks": {
            "ln1": jnp.ones((n_e, d), dtype),
            "moe": {
                "router": dense((n_e, d, E), d, f32),
                "router_bias": 0.05 * jax.random.normal(next(ks), (n_e, E), f32),
                "w_up": dense((n_e, n, F, d), d),
                "w_down": dense((n_e, n, F, d), F),
                "shared": {"wi": dense((n_e, d, SF), d),
                           "wo": dense((n_e, SF, d), SF)},
            },
        },
        "attn_blocks": {
            "ln1": jnp.ones((n_a, d), dtype),
            "attn": {"wq": dense((n_a, d, nh * dh), d),
                     "wk": dense((n_a, d, nk * dh), d),
                     "wv": dense((n_a, d, nk * dh), d),
                     "wo": dense((n_a, nh * dh, d), nh * dh)},
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


@functools.partial(jax.jit, static_argnames=("sizes", "int8"))
def _mamba(blocks, i, h, *, sizes, int8):
    m = dict(sizes)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a[i], blocks)
        B, T, _ = h.shape
        di, N, H, G = m["d_inner"], m["ssm_state"], m["ssm_heads"], m["ssm_groups"]
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
        # head h reads group h // (H / G)
        Bm = jnp.repeat(xbc[..., di:di + G * N].reshape(B, T, G, N), H // G, 2)
        Cm = jnp.repeat(xbc[..., di + G * N:].reshape(B, T, G, N), H // G, 2)
        dt = jax.nn.softplus(dt + mp["dt_bias"])                 # (B, T, H)
        log_a = -jnp.exp(mp["A_log"]) * dt
        xdt = xs * dt[..., None]

        def step(S, inp):
            b_t, c_t, x_t, la_t = inp
            S = S * jnp.exp(la_t)[..., None, None] \
                + b_t[..., :, None] * x_t[..., None, :]
            return S, jnp.einsum("bhn,bhnp->bhp", c_t, S)

        tm = lambda a: jnp.moveaxis(a, 1, 0)
        _, y = jax.lax.scan(step, jnp.zeros((B, H, N, P), f32),
                            (tm(Bm), tm(Cm), tm(xdt), tm(log_a)))
        y = jnp.moveaxis(y, 0, 1) + xs * mp["D"][:, None]
        g = (y.reshape(B, T, di) * jax.nn.silu(z)).reshape(B, T, G, di // G)
        y = _rms(g, mp["norm_w"].reshape(G, di // G), m["mixer_eps"])
        return h + _mm(y.reshape(B, T, di), mp["out_proj"], int8)


@functools.partial(jax.jit, static_argnames=("sizes", "int8"))
def _moe(blocks, i, h, *, sizes, int8):
    m = dict(sizes)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a[i], blocks)
        x = _rms(h, p["ln1"], m["norm_eps"])
        mp = p["moe"]
        s = jax.nn.sigmoid(_mm(x, mp["router"], int8))         # (B, T, E)
        _, top = jax.lax.top_k(s + mp["router_bias"], m["top_k"])
        chosen = jax.nn.one_hot(top, m["experts"], dtype=f32).sum(-2)
        w = s * chosen
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * m["scale"]
        out = _mm(_relu2(_mm(x, mp["shared"]["wi"], int8)),
                  mp["shared"]["wo"], int8)
        for e in range(m["held"]):
            y = _mm(_relu2(_mm(x, mp["w_up"][e].T, int8)), mp["w_down"][e],
                    int8)
            out = out + w[..., m["offset"] + e, None] * y
        return h + out


@functools.partial(jax.jit, static_argnames=("sizes", "int8"))
def _attention(blocks, i, h, *, sizes, int8):
    m = dict(sizes)
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a[i], blocks)
        B, T, _ = h.shape
        nh, nk, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
        x = _rms(h, p["ln1"], m["norm_eps"])
        a = p["attn"]
        q = _mm(x, a["wq"], int8).reshape(B, T, nh, dh)
        k = jnp.repeat(_mm(x, a["wk"], int8).reshape(B, T, nk, dh), nh // nk, 2)
        v = jnp.repeat(_mm(x, a["wv"], int8).reshape(B, T, nk, dh), nh // nk, 2)
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


MIXERS = {"M": _mamba, "E": _moe, "*": _attention}


def logits(params, tokens, m: dict, first: int, *, int8: bool = False):
    """Logits ``(B, T - first, vocab)`` at positions ``first`` .. ``T-1``
    of ``tokens`` ``(B, T)``, run layer by layer at ``highest`` matmul
    precision."""
    sizes = tuple(sorted(m.items()))
    h = jnp.take(params["embed"], tokens, axis=0).astype(f32)
    seen = dict.fromkeys(KINDS, 0)
    for kind in m["kinds"]:
        h = MIXERS[kind](params[KINDS[kind]], seen[kind], h, sizes=sizes,
                         int8=int8)
        seen[kind] += 1
    return _head(params, h[:, first:], sizes=sizes, int8=int8)


def flops_per_request(m: dict, prompt: int, new: int) -> dict:
    """Operations one request needs, by precision class: the prefill of
    ``prompt`` tokens, then ``new - 1`` decode steps (the last of the
    ``new`` steps the program runs yields a token nobody reads).  Dense
    matmuls, the router and attention in bf16, the held experts at their
    expected rows under uniform routing (``top_k x held / experts`` a
    token); the prefill's SSD scan on the kernel's f32 HIGHEST count; the
    decode scan step (4 N P per head) in bf16."""
    from bench.counts import ssd_scan
    d, di, H, N, P = (m["d_model"], m["d_inner"], m["ssm_heads"],
                      m["ssm_state"], m["ssm_headdim"])
    G, V = m["ssm_groups"], m["vocab"]
    n_m, n_e, n_a = (m["kinds"].count(k) for k in "ME*")
    nh, nk, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    mamba = d * (2 * di + 2 * G * N + H) + di * d
    attn = d * (nh + 2 * nk) * dh + nh * dh * d
    rows = m["top_k"] * m["held"] / m["experts"]
    moe = d * m["experts"] + 2 * d * m["shared_ff"] + rows * 2 * d * m["moe_ff"]
    per_token = 2.0 * (n_m * mamba + n_a * attn + n_e * moe)
    bf16 = prompt * per_token + 2.0 * d * V
    bf16 += n_a * 4.0 * nh * dh * prompt * (prompt + 1) / 2
    ssd, _ = ssd_scan.count(B=1, T=prompt, H=H, N=N, P=P,
                            chunk=m["ssm_chunk"], dtype="bfloat16")
    for j in range(new - 1):
        ctx = prompt + j + 1
        bf16 += per_token + 2.0 * d * V + n_a * 4.0 * nh * dh * ctx \
            + n_m * 4.0 * H * N * P
    return {"bf16": bf16, "f32_highest": n_m * ssd["f32_highest"]}
