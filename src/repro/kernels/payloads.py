"""Kernel payload variant tables: the Pallas kernels as first-class
per-target op payloads.

Each factory returns a ``{dialect: callable}`` table for one fused-op
payload, with weights/side operands closed over so a single activation
flows through a chain graph (the layer convention: weights are module
state, activations are the dataflow).  Dialects:

* ``"ref"``    — the pure-jnp oracle from :mod:`repro.kernels.ref`
  (bind it as ``op.fn``: the interpreter path and every probe verify
  against it), run at full f32 matmul precision so that the oracle is
  f32 on every backend (a TPU's default pass rounds f32 operands to
  bf16);
* ``"pallas"`` — the Pallas kernel via :mod:`repro.kernels.ops`
  (``interpret=None`` → interpret-mode off-TPU, compiled on TPU);
* ``"numpy"``  — host NumPy, for the host-affine ops the paper maps to
  CPU (eltwise glue, sort) — eager, never jitted.

``bind_variants(op, table)`` installs a table on a
:class:`~repro.core.op.FusedOp` (``fn`` ← ``"ref"``, the rest into
``op.variants``) and records example inputs for measured profiling.
The compiled executor serves ``op.payload_for(target.dialect)`` only
after the cold-run probe against the reference composition — see
:mod:`repro.core.laneprogram`.
"""
from __future__ import annotations

from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from . import ops, ref

PayloadTable = Mapping[str, Callable[..., Any]]


def _highest(fn):
    """``fn`` with its matmuls at full f32 precision."""
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return run


def bind_variants(op, table: PayloadTable,
                  example_inputs: tuple | None = None):
    """Install a payload table on a ``FusedOp``: ``table["ref"]`` becomes
    the reference ``op.fn``, every other dialect goes into
    ``op.variants``; ``example_inputs`` (if given) lands in
    ``op.meta["example_inputs"]`` for the measured profiler."""
    if "ref" not in table:
        raise ValueError("payload table needs a 'ref' entry (the oracle)")
    op.fn = table["ref"]
    op.variants = {k: fn for k, fn in table.items() if k != "ref"}
    if example_inputs is not None:
        op.meta["example_inputs"] = example_inputs
    return op


# ---------------------------------------------------------------------------
# kernel payloads (activation in, activation out; weights closed over)
# ---------------------------------------------------------------------------


def attention_payloads(k, v, *, causal: bool = True, q_offset: int = 0,
                       block_q: int = 64, block_k: int = 64,
                       interpret: bool | None = None) -> dict:
    """Fused attention: activation is the query ``(B, Tq, Hq, D)``; the
    key/value streams (e.g. a decode KV cache) are closed over."""
    @_highest
    def ref_fn(q):
        return ref.attention_ref(q, k, v, causal=causal, q_offset=q_offset)

    def pallas_fn(q):
        return ops.flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)
    return {"ref": ref_fn, "pallas": pallas_fn}


def ssd_payloads(c, b, log_a, *, initial_state=None, chunk: int = 32,
                 interpret: bool | None = None) -> dict:
    """SSD recurrence: activation is the value stream ``(B, T, H, P)``;
    the state/input projections and decay gates are closed over.  Only
    the sequence output flows (the carried state is layer-internal)."""
    @_highest
    def ref_fn(x):
        y, _ = ref.ssd_scan_ref(c, b, x, log_a, initial_state=initial_state)
        return y

    def pallas_fn(x):
        y, _ = ops.ssd_scan(c, b, x, log_a, initial_state=initial_state,
                            chunk=chunk, interpret=interpret)
        return y
    return {"ref": ref_fn, "pallas": pallas_fn}


def moe_payloads(w_gate, w_up, w_down, *, capacity: int, top_k: int = 2,
                 block_m: int = 16, block_f: int = 16,
                 interpret: bool | None = None) -> dict:
    """Routed MoE layer: activation ``(T, d)`` tokens; router + expert
    weights closed over.  Gating (softmax top-k, renormalized) is shared
    jnp code (at full precision, so both dialects route alike) and the
    dialects differ only in dispatch/combine."""
    @_highest
    def gates(x):
        logits = x.astype(jnp.float32) @ w_gate.astype(jnp.float32)
        gv, gi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        gv = (gv / gv.sum(-1, keepdims=True)).astype(x.dtype)
        return gi, gv

    @_highest
    def ref_fn(x):
        gi, gv = gates(x)
        return ref.moe_dispatch_combine_ref(x, gi, gv, w_up, w_down,
                                            capacity=capacity)

    def pallas_fn(x):
        gi, gv = gates(x)
        return ops.moe_dispatch_combine(x, gi, gv, w_up, w_down,
                                        capacity=capacity, block_m=block_m,
                                        block_f=block_f, interpret=interpret)
    return {"ref": ref_fn, "pallas": pallas_fn}


# ---------------------------------------------------------------------------
# host-affine payloads (the CPU-mapped glue the paper's Fig. 2 CPU class)
# ---------------------------------------------------------------------------


def eltwise_payloads(scale: float = 1.0) -> dict:
    """Elementwise gate/activation with a NumPy host variant."""
    s32 = np.float32(scale)

    def ref_fn(x):
        return jnp.tanh(x * jnp.asarray(s32))

    def numpy_fn(x):
        return np.tanh(np.asarray(x) * s32)
    return {"ref": ref_fn, "numpy": numpy_fn}


def sort_payloads() -> dict:
    """Shape-preserving full sort of the flattened activation — the
    classic host-affine op (XLA:CPU's variadic sort trails ``np.sort``
    by a wide, stable margin at large N)."""
    def ref_fn(x):
        return jnp.sort(x.reshape(-1)).reshape(x.shape)

    def numpy_fn(x):
        a = np.asarray(x)
        return np.sort(a.reshape(-1)).reshape(a.shape)
    return {"ref": ref_fn, "numpy": numpy_fn}
