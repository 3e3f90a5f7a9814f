"""Plain reference of the kernel chain, in NumPy and float64 on the host.

The chain is ``repro.core.modelgraph.kernel_chain``: per block,
attention -> gate -> SSD scan -> sort -> MoE -> out on a
``(batch, seq, heads, head_dim)`` f32 activation.  Its weights are remade
here from the graph seed by the chain's own recipe (one normal draw per
tensor from ``split(PRNGKey(seed), 8 * blocks + 1)``, in this order), and
each op's mathematics is written out.  Nothing of the program is
imported.

The reference computes in float64, so that its own rounding lies far
below the f32 program's: on the chip, an f32 reference's sequential scan
multiplies thousands of per-step decays and reads the program's SSD
within 1.7e-5 of the output's scale, as close as a control does.  The
control computes in float32 and contracts with three bf16 passes (what
``high`` does on the MXU), written out so that it computes the same on
any backend: each operand splits into a bf16 head and a bf16 tail, and
the product is head*head + head*tail + tail*head.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

f32 = jnp.float32
OPS_PER_BLOCK = ("attn", "gate", "ssd", "sort", "moe", "out")


def capacity(w: dict) -> int:
    """Tokens each expert takes: the even share of the routed
    assignments, rounded up to 8 and to at least one ``block_m``."""
    tokens = w["batch"] * w["seq"]
    cap = -((-tokens * w["top_k"]) // w["experts"])
    return max(w["block_m"], -(-cap // 8) * 8)


def weights(seed: int, w: dict, blocks: int) -> list[dict]:
    """Each block's tensors, drawn as the chain draws them."""
    B, T, H, D = w["batch"], w["seq"], w["heads"], w["head_dim"]
    d, E, F, N = H * D, w["experts"], w["moe_ff"], w["state"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8 * blocks + 1))

    def rnd(shape, scale=1.0):
        return (scale * jax.random.normal(next(keys), shape)).astype(f32)

    next(keys)   # the chain's own example input, which the traffic replaces
    out = []
    for _ in range(blocks):
        blk = {"k": rnd((B, T, H, D), 0.5), "v": rnd((B, T, H, D), 0.5),
               "c": rnd((B, T, H, N), 0.5), "b": rnd((B, T, H, N), 0.5)}
        blk["log_a"] = -0.05 * jnp.abs(rnd((B, T, H)))
        blk["w_gate"] = rnd((d, E), d ** -0.5)
        blk["w_up"] = rnd((E, d, 2 * F), d ** -0.5)
        blk["w_down"] = rnd((E, F, d), F ** -0.5)
        out.append(blk)
    return out


def _split(a):
    """An f32 array as a bf16 head and a bf16 tail, each held in f32."""
    a = np.asarray(a, np.float32)
    head = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    return head, (a - head).astype(ml_dtypes.bfloat16).astype(np.float32)


def contract(a, b, passes: int | None = None, spec: str | None = None):
    """``a @ b`` (or ``einsum(spec, a, b)``): in float64, or with
    ``passes=3`` as three bf16 passes accumulated in float32."""
    mul = (lambda x, y: np.einsum(spec, x, y)) if spec else np.matmul
    if passes is None:
        return mul(a, b)
    (ah, at), (bh, bt) = _split(a), _split(b)
    return mul(ah, bh) + mul(ah, bt) + mul(at, bh)


def _softmax(s):
    e = np.exp(s - s.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def attention(q, k, v, passes=None):
    B, T, H, D = q.shape
    heads = lambda a: a.transpose(0, 2, 1, 3)                  # (B, H, T, D)
    s = contract(heads(q) * (1.0 / math.sqrt(D)),
                 heads(k).transpose(0, 1, 3, 2), passes)
    mask = np.arange(T)[:, None] >= np.arange(T)[None, :]
    p = _softmax(np.where(mask, s, -np.inf)).astype(s.dtype)
    return heads(contract(p, heads(v), passes))


def ssd(c, b, x, log_a, passes=None):
    """S_t = exp(log_a_t) S_{t-1} + b_t x_t^T; y_t = c_t . S_t, one step
    at a time."""
    B, T, H, N = b.shape
    S = np.zeros((B, H, N, x.shape[-1]), x.dtype)
    y = np.empty_like(x)
    decay = np.exp(log_a)
    for t in range(T):
        S = S * decay[:, t, :, None, None] \
            + b[:, t, :, :, None] * x[:, t, :, None, :]
        y[:, t] = contract(c[:, t], S, passes, "bhn,bhnp->bhp")
    return y


def moe(x, w_gate, w_up, w_down, *, top_k: int, cap: int, passes=None):
    """Softmax top-k routing, renormalised; each expert takes its first
    ``cap`` assignments in (token, k) order and drops the rest."""
    shape = x.shape
    t = x.reshape(-1, w_gate.shape[0])
    E = w_gate.shape[1]
    mm = lambda a, b: contract(a, b, passes)
    probs = _softmax(mm(t, w_gate))
    gi = np.argsort(-probs, axis=-1, kind="stable")[:, :top_k]
    gv = np.take_along_axis(probs, gi, -1)
    gv = gv / gv.sum(-1, keepdims=True)
    onehot = (gi[..., None] == np.arange(E)).reshape(-1, E).astype(np.int64)
    slot = ((np.cumsum(onehot, axis=0) - onehot) * onehot).sum(-1)
    keep = (slot < cap).reshape(gi.shape)
    out = np.zeros_like(t)
    for e in range(E):
        rows = ((gi == e) & keep).any(-1)
        if not rows.any():
            continue
        g, u = np.split(mm(t[rows], w_up[e]), 2, axis=-1)
        y = mm(g / (1.0 + np.exp(-g)) * u, w_down[e])
        out[rows] += y * ((gi[rows] == e) * keep[rows] * gv[rows]).sum(-1)[:, None]
    return out.reshape(shape)


def ops(blk: dict, j: int, w: dict, passes=None):
    """The six ops of block ``j`` as functions of the activation."""
    cap = capacity(w)
    return [
        lambda x: attention(x, blk["k"], blk["v"], passes),
        lambda x: np.tanh(x * (1.0 + 0.25 * j)),
        lambda x: ssd(blk["c"], blk["b"], x, blk["log_a"], passes),
        lambda x: np.sort(x.reshape(-1)).reshape(x.shape),
        lambda x: moe(x, blk["w_gate"], blk["w_up"], blk["w_down"],
                      top_k=w["top_k"], cap=cap, passes=passes),
        lambda x: np.tanh(x * 0.5),
    ]


def op_list(blocks: list[dict], w: dict, passes: int | None = None):
    """Every op of the chain, in order, as a function of the activation:
    float64 on the host, or with ``passes=3`` the control, float32 with
    three-pass contractions."""
    dtype = np.float64 if passes is None else np.float32
    fns = []
    for j, blk in enumerate(blocks):
        host = {k: np.asarray(v, dtype) for k, v in blk.items()}
        fns += [(lambda f: lambda x: f(np.asarray(x, dtype)))(f)
                for f in ops(host, j, w, passes)]
    return fns


def forward(x, fns) -> list:
    """Each op's output when the chain runs from ``x``."""
    outs = []
    for fn in fns:
        x = fn(x)
        outs.append(x)
    return outs


def flops_per_request(w: dict, blocks: int) -> dict:
    """Operations of one request by precision class (all f32 at
    HIGHEST): the kernels' counts plus the router's matmul."""
    total = sum(sum(mod.count(**call)[0].values())
                for mod, call in kernel_calls(w).values())
    total += 2.0 * w["batch"] * w["seq"] * w["heads"] * w["head_dim"] \
        * w["experts"]
    return {"f32_highest": blocks * total}


def kernel_calls(w: dict) -> dict:
    """One call of each kernel in a block: its count module and shape."""
    from bench.counts import expert_glu, flash_attention, ssd_scan
    B, T, H, D = w["batch"], w["seq"], w["heads"], w["head_dim"]
    tokens = B * T
    return {
        "flash_attention": (flash_attention, dict(
            B=B, Tq=T, Tk=T, H=H, D=D, dtype="float32", causal=True)),
        "ssd_scan": (ssd_scan, dict(
            B=B, T=T, H=H, N=w["state"], P=D, chunk=w["chunk"],
            dtype="float32")),
        "expert_glu": (expert_glu, dict(
            E=w["experts"],
            rows=min(tokens * w["top_k"], w["experts"] * capacity(w)),
            d=H * D, F=w["moe_ff"], dtype="float32")),
    }
