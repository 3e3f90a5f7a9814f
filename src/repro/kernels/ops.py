"""Public jit'd wrappers for the Pallas kernels.

On a TPU backend these dispatch to the compiled kernels; on a CPU
backend they run in ``interpret=True`` mode (the kernel body executed in
Python), which is how the sweep tests validate them against ``ref.py``.
``default_interpret()`` picks automatically from the backend unless an
enclosing :func:`interpret_mode` says otherwise (a target pinned to the
host CPU of a TPU machine interprets; one pinned to the chip compiles).
"""
from __future__ import annotations

import contextlib
import contextvars

import jax

from .expert_mlp import block_layout, expert_mlp as _expert_mlp
from .flash_attention import flash_attention as _flash_attention
from .moe_gather import (dispatch_indices, expert_glu as _expert_glu,
                         moe_dispatch_combine as _moe_dispatch_combine)
from .ssd_scan import ssd_scan as _ssd_scan


_INTERPRET: contextvars.ContextVar[bool | None] = contextvars.ContextVar(
    "pallas_interpret", default=None)


@contextlib.contextmanager
def interpret_mode(flag: bool | None):
    """Within the block, kernels called with ``interpret=None`` run
    interpreted (``True``) or compiled (``False``); ``None`` restores the
    backend default.  Read at trace time, so it also governs kernels
    traced inside an enclosing ``jax.jit``."""
    token = _INTERPRET.set(flag)
    try:
        yield
    finally:
        _INTERPRET.reset(token)


def default_interpret() -> bool:
    forced = _INTERPRET.get()
    if forced is not None:
        return forced
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


def ssd_scan(c, b, v, log_a, *, initial_state=None, chunk: int = 256,
             interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _ssd_scan(c, b, v, log_a, initial_state=initial_state,
                     chunk=chunk, interpret=interpret)


def expert_glu(x, w_up, w_down, *, block_m: int = 128, block_f: int = 256,
               interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _expert_glu(x, w_up, w_down, block_m=block_m, block_f=block_f,
                       interpret=interpret)


def expert_mlp(x, w_up, w_down, block_group, n_blocks, layer=0, *,
               block_m: int, block_f: int = 512,
               interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _expert_mlp(x, w_up, w_down, block_group, n_blocks, layer,
                       block_m=block_m, block_f=block_f, interpret=interpret)


def moe_dispatch_combine(x, gate_idx, gate_vals, w_up, w_down, *,
                         capacity: int, block_m: int = 128,
                         block_f: int = 256, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    return _moe_dispatch_combine(x, gate_idx, gate_vals, w_up, w_down,
                                 capacity=capacity, block_m=block_m,
                                 block_f=block_f, interpret=interpret)


__all__ = ["flash_attention", "ssd_scan", "expert_glu", "expert_mlp",
           "block_layout", "moe_dispatch_combine", "dispatch_indices",
           "default_interpret", "interpret_mode"]
