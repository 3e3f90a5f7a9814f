"""Mamba-2 chunked SSD scan — Pallas TPU kernel.

This is the TPU adaptation of the paper's CumSum/selective-scan operator
class (Fig. 2: sequential recurrences favour the CPU on the edge SoC
because GPU/NPU MAC datapaths can't express them).  On TPU the same
insight becomes: restructure the recurrence into *chunked* form so the
intra-chunk work is dense (chunk x chunk) / (chunk x N) matmuls on the
MXU and only the inter-chunk state carry is sequential.

Recurrence: S_t = exp(log_a_t) * S_{t-1} + b_t v_t^T;  y_t = c_t^T S_t.

Grid: (B, H, num_chunks); the chunk axis is sequential — the (N x P) state
lives in fp32 VMEM scratch across chunk iterations.  All contractions run
at full f32 precision.  Per chunk:

  intra:  y_intra = ((c b^T) .* L) v     with L[i,j] = exp(cum_i - cum_j), i>=j
  inter:  y_inter = (c .* exp(cum)) S_prev
  carry:  S = exp(tot) * S_prev + (b .* exp(tot - cum))^T v

VMEM working set (chunk=256, N=P=64, f32): c,b,v 3x64KB + L 256KB +
state 16KB — far under budget; chunk up to 512 remains safe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(c_ref, b_ref, v_ref, la_ref, s0_ref, y_ref, sfin_ref,
                state_ref, *, chunk: int, num_chunks: int, seq_len: int):
    ci = pl.program_id(2)
    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST

    def dot(x, y, contract):
        return jax.lax.dot_general(x, y, (contract, ((), ())), precision=hi,
                                   preferred_element_type=f32)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(f32)

    c = c_ref[0, 0].astype(f32)                           # (C, N)
    b = b_ref[0, 0].astype(f32)                           # (C, N)
    v = v_ref[0, 0].astype(f32)                           # (C, P)
    la = la_ref[0, 0].astype(f32)                         # (1, C)

    # padded tail positions (t >= seq_len) must not touch the state: force
    # their decay to 0 (identity carry) and their b/v contribution to zero.
    t0 = ci * chunk
    la = jnp.where(t0 + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
                   < seq_len, la, 0.0)
    b = jnp.where(t0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
                  < seq_len, b, 0.0)

    # in-chunk decay sums as triangular-mask matmuls (no scan primitive).
    # Every sum is taken over exactly the steps it spans.  A difference of
    # two prefix sums keeps the rounding of every step of both, taken at
    # the running sums' scale (|cum| ~ 1e2 within a chunk): ~1e-4 relative
    # in L, which put zamba2-width outputs past the f32 tolerance even
    # with exact f32 matmuls (interpret mode: 1.06 of the bucket, against
    # 0.05 for exact spans).
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    incl = (ii >= jj).astype(f32)                         # [k <= i] at (i, k)
    cum = dot(incl, la, ((1,), (1,)))                     # (C,1) sum_{k<=i}
    rest = dot((ii < jj).astype(f32), la, ((1,), (1,)))   # (C,1) sum_{k>i}
    tot = jnp.sum(la, axis=1, keepdims=True)              # (1, 1)
    # seg[i, j] = sum_{j<k<=i} la_k = (([k<=i] la_k) @ [k>j])[i, j]
    seg = dot(incl * la, (ii > jj).astype(f32), ((1,), (0,)))

    # intra-chunk: decay matrix L (C, C), lower-triangular in exp space
    L = jnp.exp(jnp.where(ii >= jj, seg, -1e30))
    y = dot(dot(c, b, ((1,), (1,))) * L, v, ((1,), (0,)))

    # inter-chunk: contribution of the carried state
    S_prev = state_ref[...]                               # (N, P) f32
    y += dot(c * jnp.exp(cum), S_prev, ((1,), (0,)))

    # state update
    chunk_state = dot(b * jnp.exp(rest), v, ((0,), (0,)))
    state_ref[...] = S_prev * jnp.exp(tot) + chunk_state

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _finish():
        sfin_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(c, b, v, log_a, *, initial_state=None, chunk: int = 256,
             interpret: bool = False):
    """c, b: (B,T,H,N); v: (B,T,H,P); log_a: (B,T,H) (<= 0).

    Returns (y (B,T,H,P) in v.dtype, S_final (B,H,N,P) f32).
    T is padded to a chunk multiple internally (pad positions carry the
    state through unchanged).  The kernel runs head-major: c/b/v as
    ``(B, H, T, ·)`` and the decay gates as ``(B, H, 1, T)`` rows, so each
    block's trailing pair is ``(chunk, width)`` or ``(1, chunk)``.
    """
    B, T, H, N = b.shape
    P = v.shape[-1]
    chunk = min(chunk, max(T, 1))
    nc = -(-T // chunk)
    pad = nc * chunk - T
    zc = ((0, 0), (0, pad), (0, 0), (0, 0))
    c = jnp.pad(c, zc).transpose(0, 2, 1, 3)
    b = jnp.pad(b, zc).transpose(0, 2, 1, 3)
    v = jnp.pad(v, zc).transpose(0, 2, 1, 3)
    log_a = jnp.pad(log_a, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)
    log_a = log_a[:, :, None, :]
    if initial_state is None:
        s0 = jnp.zeros((B, H, N, P), jnp.float32)
    else:
        s0 = initial_state.astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc,
                               seq_len=T)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, N), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, P), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bb, h, ci: (bb, h, 0, ci)),
            pl.BlockSpec((1, 1, N, P), lambda bb, h, ci: (bb, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda bb, h, ci: (bb, h, ci, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bb, h, ci: (bb, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc * chunk, P), v.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(c, b, v, log_a, s0)
    return y[:, :, :T].transpose(0, 2, 1, 3), s_final
