"""Fused MoE expert GLU apply — Pallas TPU kernel.

TPU adaptation of the paper's Gather-affinity operator class (KAN spline
eval / MoE dispatch): on the edge SoC the gather favours the CPU because
it falls outside the NPU MAC datapath; on TPU the fix is to restructure
dispatch into *dense, capacity-padded* form (XLA one-hot dispatch is
MXU-friendly) and fuse the expert FFN so the (E, cap, 2F) GLU hidden
tensor never round-trips HBM.

The kernel computes, per expert e and token tile m:

    y[e, m] = (silu(x[e,m] @ Wg[e]) * (x[e,m] @ Wu[e])) @ Wd[e]

with the ff dimension tiled sequentially and a fp32 (bm x d) accumulator
in VMEM scratch.  Eliminated HBM traffic vs the unfused path: the
2 x (E x cap x F) hidden write+read (the dominant activation traffic of
the MoE block at decode batch sizes).

Grid: (E, cap/bm, F/bf); the f axis is innermost/sequential.
VMEM per step (bm=128, bf=256, d=4096, bf16): x 1MB + wg,wu 2x2MB +
wd 2MB + acc(f32) 2MB ~= 9MB, double-buffered by the pipeline; the
kernel raises the scoped-VMEM limit to its working set where that passes
the 16 MiB default (f32 at d=2048), and bf shrinks for d=7168
(deepseek).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _expert_glu_kernel(x_ref, wg_ref, wu_ref, wd_ref, y_ref, acc_ref, *,
                       num_f: int):
    fi = pl.program_id(2)

    @pl.when(fi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                    # (bm, d)
    wg = wg_ref[0]                                  # (d, bf)
    wu = wu_ref[0]                                  # (d, bf)
    wd = wd_ref[0]                                  # (bf, d)
    # f32 operands contract at full f32 precision, bf16 natively
    prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def mm(p, q):
        return jax.lax.dot_general(p, q, (((1,), (0,)), ((), ())),
                                   precision=prec,
                                   preferred_element_type=jnp.float32)
    a = (jax.nn.silu(mm(x, wg)) * mm(x, wu)).astype(x.dtype)
    acc_ref[...] += mm(a, wd)

    @pl.when(fi == num_f - 1)
    def _finish():
        y_ref[0] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f", "interpret"))
def expert_glu(x, w_up, w_down, *, block_m: int = 128, block_f: int = 256,
               interpret: bool = False):
    """x: (E, cap, d) capacity-padded per-expert tokens; w_up: (E, d, 2F)
    ([..., :F] gate, [..., F:] up); w_down: (E, F, d).
    Returns (E, cap, d) expert outputs in x.dtype.
    """
    E, cap, d = x.shape
    F = w_down.shape[1]
    assert w_up.shape == (E, d, 2 * F), (w_up.shape, (E, d, 2 * F))
    block_m = min(block_m, max(cap, 1))
    block_f = min(block_f, F)
    nm = -(-cap // block_m)
    nf = -(-F // block_f)
    assert F % block_f == 0, "pick block_f dividing d_ff"
    pm = nm * block_m - cap
    if pm:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, 0)))

    kernel = functools.partial(_expert_glu_kernel, num_f=nf)
    # double-buffered x/wg/wu/wd/y blocks + the f32 accumulator; at f32 and
    # d=2048 this passes the 16 MiB default scoped-VMEM limit, so ask for
    # the working set plus a quarter of headroom
    item = jnp.dtype(x.dtype).itemsize
    working = (2 * item * (2 * block_m * d + 3 * d * block_f)
               + 4 * block_m * d)
    y = pl.pallas_call(
        kernel,
        grid=(E, nm, nf),
        in_specs=[
            pl.BlockSpec((1, block_m, d), lambda e, mi, fi: (e, mi, 0)),
            pl.BlockSpec((1, d, block_f), lambda e, mi, fi: (e, 0, fi)),
            pl.BlockSpec((1, d, block_f),
                         lambda e, mi, fi, nf=nf: (e, 0, nf + fi)),
            pl.BlockSpec((1, block_f, d), lambda e, mi, fi: (e, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, d), lambda e, mi, fi: (e, mi, 0)),
        out_shape=jax.ShapeDtypeStruct((E, nm * block_m, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 << 20, working + working // 4)),
        interpret=interpret,
    )(x, w_up, w_up, w_down)
    return y[:, :cap]


def dispatch_indices(gate_idx, capacity: int, n_experts: int):
    """Capacity-padded dispatch bookkeeping (XLA side; cheap vs matmuls).

    gate_idx: (T, K) int32.  Returns (token_of (E, cap) int32 with -1 pads,
    keep (T, K) bool, pos (T, K) int32) where pos is each (t, k) slot's
    queue position within its expert.
    """
    T, K = gate_idx.shape
    onehot = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.int32)  # (T,K,E)
    flat = onehot.reshape(T * K, n_experts)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = (pos_flat.reshape(T, K, n_experts) * onehot).sum(-1)      # (T,K)
    keep = pos < capacity
    # scatter token ids into the (E, cap) table
    tok_ids = jnp.broadcast_to(jnp.arange(T)[:, None], (T, K))
    e_flat = gate_idx.reshape(-1)
    p_flat = jnp.where(keep, pos, capacity).reshape(-1)
    token_of = jnp.full((n_experts, capacity + 1), -1, jnp.int32)
    token_of = token_of.at[e_flat, p_flat].set(tok_ids.reshape(-1),
                                               mode="drop")
    return token_of[:, :capacity], keep, pos


def moe_dispatch_combine(x, gate_idx, gate_vals, w_up, w_down, *,
                         capacity: int, block_m: int = 128,
                         block_f: int = 256, interpret: bool = False):
    """End-to-end fused MoE: dispatch (XLA gather) -> expert_glu (Pallas)
    -> combine (XLA weighted scatter-add).  Matches
    ``ref.moe_dispatch_combine_ref``.
    """
    T, d = x.shape
    E = w_up.shape[0]
    K = gate_idx.shape[1]
    token_of, keep, pos = dispatch_indices(gate_idx, capacity, E)
    valid = token_of >= 0
    xe = jnp.where(valid[..., None],
                   x[jnp.where(valid, token_of, 0)], 0.0)           # (E,cap,d)
    ye = expert_glu(xe, w_up, w_down, block_m=block_m, block_f=block_f,
                    interpret=interpret)                            # (E,cap,d)
    # combine: each kept (t, k) adds gate_vals[t,k] * ye[e, pos]
    ye_flat = ye.reshape(E * capacity, d)
    slot = gate_idx * capacity + jnp.minimum(pos, capacity - 1)     # (T,K)
    contrib = ye_flat[slot] * (gate_vals * keep)[..., None].astype(x.dtype)
    return contrib.sum(axis=1).astype(x.dtype)
