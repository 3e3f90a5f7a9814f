"""``jax.jit`` for payloads that close over their weights.

Op payloads close over their weights and side inputs (a layer's expert
matrices, a decode step's KV cache).  Plain ``jax.jit`` bakes every
closed-over array into the compiled program as a constant: at real
widths that is hundreds of MiB per program, copied into each compile,
which makes compiles slow and the programs too large for JAX's
persistent compilation cache.  :func:`jit_hoisting_constants` passes
those arrays as arguments instead, so the program holds only the
computation and identical compositions share one cached executable.
"""
from __future__ import annotations

from typing import Any, Callable

import jax


def jit_hoisting_constants(fn: Callable, device: Any = None) -> Callable:
    """``jax.jit(fn)`` with ``fn``'s closed-over arrays passed as arguments.

    ``fn`` is traced once per input signature; the arrays it closes over
    are taken from that trace and, when ``device`` is given, placed there
    once, so that a program pinned to one device never reads its weights
    from another.  ``fn`` must be pure, as for ``jax.jit``.
    """
    traced: dict = {}

    def run(*args):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple(jax.typeof(x) for x in leaves))
        hit = traced.get(key)
        if hit is None:
            closed, shapes = jax.make_jaxpr(fn, return_shape=True)(*args)
            consts = list(closed.consts)
            if device is not None:
                consts = [jax.device_put(c, device) for c in consts]
            jaxpr, out_tree = closed.jaxpr, jax.tree.structure(shapes)

            def body(consts, *xs):
                return jax.tree.unflatten(
                    out_tree, jax.core.eval_jaxpr(jaxpr, consts, *xs))
            hit = traced[key] = (jax.jit(body), consts)
        jitted, consts = hit
        return jitted(consts, *leaves)

    return run
