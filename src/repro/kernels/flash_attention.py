"""Blockwise causal GQA flash attention — Pallas TPU kernel.

TPU mapping of the attention hot-spot (the paper's GEMM-affinity operator
class): online-softmax over MXU-aligned (block_q x block_k) score tiles,
fp32 accumulators in VMEM scratch, q/k/v streamed HBM->VMEM by BlockSpec.

Grid: (B, Hq, num_q_blocks, num_kv_blocks) over head-major (B, H, T, D)
arrays.  The kv axis is the innermost, sequential ("arbitrary")
dimension; acc/m/l scratch carries across it.  GQA is handled in the k/v
index maps (query head h reads kv head h // group).
Causal skipping: kv blocks strictly above the diagonal are not processed
(@pl.when), which halves compute for causal masks.

The VMEM working set per grid step is
  q (bq x D) + k,v (bk x D each) + acc (bq x Dv, f32) + 2 x (bq x 1)
= 128x128 tiles at bf16 -> well under the ~16 MiB VMEM budget.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, q_offset: int,
                 block_q: int, block_k: int, kv_len: int, num_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def _process():
        # operands stay in the input dtype (bf16 feeds the MXU natively);
        # f32 inputs contract at full f32 precision.  Accumulation is f32.
        q = q_ref[0, 0]                                          # (bq, D)
        k = k_ref[0, 0]                                          # (bk, D)
        v = v_ref[0, 0]                                          # (bk, Dv)
        prec = _precision(q.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32) * scale
        mask = k_pos < kv_len                                    # kv padding
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                      # (bq, 1)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)

    if causal:
        # skip kv blocks strictly above the causal diagonal
        first_q = q_offset + qi * block_q
        needed = ki * block_k <= first_q + block_q - 1

        @pl.when(needed)
        def _():
            _process()
    else:
        _process()

    @pl.when(ki == num_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _precision(dtype):
    """Full f32 contraction for f32 operands (the MXU's default pass
    count would round them to bf16); the native pass otherwise."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q (B,Tq,Hq,D); k/v (B,Tk,Hk,Dk/Dv) with Hq % Hk == 0.

    Returns (B,Tq,Hq,Dv) in q.dtype.  Tq/Tk are padded to the block sizes
    internally; padded kv positions are masked, padded q rows dropped.
    The kernel runs head-major, ``(B, H, T, D)``: a ``(block, D)`` tile is
    then the whole trailing pair of a block, the layout the TPU's
    (8, 128) tiling accepts at any head width.
    """
    B, Tq, Hq, D = q.shape
    _, Tk, Hk, _ = k.shape
    Dv = v.shape[-1]
    assert Hq % Hk == 0, (Hq, Hk)
    group = Hq // Hk
    scale = 1.0 / math.sqrt(D)

    block_q = min(block_q, max(Tq, 1))
    block_k = min(block_k, max(Tk, 1))
    nq = -(-Tq // block_q)
    nk = -(-Tk // block_k)
    pq, pk = nq * block_q - Tq, nk * block_k - Tk
    q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, q_offset=q_offset,
        block_q=block_q, block_k=block_k, kv_len=Tk, num_kv=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, nq * block_q, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom l
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Tq].transpose(0, 2, 1, 3)
