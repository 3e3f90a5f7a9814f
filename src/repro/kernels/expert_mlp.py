"""Non-gated relu^2 experts over sorted, grouped rows — Pallas TPU kernel.

The dropless counterpart of ``moe_gather.expert_glu``: rows arrive sorted
by expert, each expert's group padded to a multiple of the row block, so
every row block belongs to one expert.  Per block b of expert e:

    y[b] = relu(x[b] @ W_up[e]^T)^2 @ W_down[e]

Both weights are laid out ``(experts, F, d)`` (``W_up`` as out x in,
``W_down`` as in x out), so the F axis tiles as the second-minor dim of
both and the innermost, sequential grid axis carries an f32 (bm x d)
accumulator in VMEM.  The weights may come stacked over layers,
``(layers, experts, F, d)``, with the layer's index: the index maps pick
the layer, so no layer's weights are copied out of the stack (a custom
call's operand that is a slice would be).  ``layer``, ``block_group``
and ``n_blocks`` are scalar-prefetched: a block's weights come from its
own expert, and blocks past
``n_blocks`` neither compute nor fetch (their index maps repeat the last
active block), so the work is the routed rows plus at most one row block
of padding per expert, and an expert with no rows is never read.  Rows
of the blocks past ``n_blocks`` are left undefined in the output.

Grid: (row blocks, F / bf).  VMEM per step (bm=128, bf=464, d=2688,
bf16): x 0.7 MB + two weight tiles of 2.5 MB + y 0.7 MB, double-buffered,
plus the 1.4 MB accumulator: about 14 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def block_rows(dtype) -> int:
    """The least row block of ``dtype`` on the TPU's (8, 128) tiling."""
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


def pick_block_f(F: int, target: int, align: int) -> int:
    """The largest divisor of ``F`` that is a multiple of ``align`` and at
    most ``target``; ``F`` itself where none is."""
    fits = [bf for bf in range(align, min(F, target) + 1, align) if F % bf == 0]
    return fits[-1] if fits else F


def block_layout(group_sizes, rows: int, block_m: int):
    """Where sorted rows go when each group is padded to whole blocks.

    ``group_sizes`` (G,) int32 counts the rows of each group; the rows are
    sorted by group, and ``rows`` (static) bounds their total.  Returns
    ``(place, block_group, n_blocks, padded)``: ``place`` (rows,) int32,
    each sorted row's row in the padded layout (``padded`` for the rows
    past the groups); ``block_group`` (padded / block_m,) int32, the
    group of each block (the last group past ``n_blocks``); ``n_blocks``
    (1,) int32, the blocks that hold rows; ``padded`` the static number
    of padded rows, enough for any sizes that sum to at most ``rows``.
    """
    G = group_sizes.shape[0]
    padded = -(-(rows + G * (block_m - 1)) // block_m) * block_m
    sizes = group_sizes.astype(jnp.int32)
    blocks = (sizes + block_m - 1) // block_m
    block_end = jnp.cumsum(blocks)
    pstart = (block_end - blocks) * block_m
    ustart = jnp.cumsum(sizes) - sizes
    j = jnp.arange(rows, dtype=jnp.int32)
    # compare_all: one fused comparison over the G groups, where the
    # default scan is a loop of small steps on the device
    group = jnp.searchsorted(jnp.cumsum(sizes), j, side="right",
                             method="compare_all")
    g = jnp.minimum(group, G - 1)
    place = jnp.where(group < G, pstart[g] + j - ustart[g], padded)
    b = jnp.arange(padded // block_m, dtype=jnp.int32)
    block_group = jnp.minimum(
        jnp.searchsorted(block_end, b, side="right", method="compare_all"),
        G - 1).astype(jnp.int32)
    n_blocks = block_end[-1:].astype(jnp.int32)
    return place.astype(jnp.int32), block_group, n_blocks, padded


def _expert_mlp_kernel(layer_ref, group_ref, nb_ref, x_ref, wu_ref, wd_ref,
                       y_ref, acc_ref, *, num_f: int):
    i, fi = pl.program_id(0), pl.program_id(1)

    @pl.when(i < nb_ref[0])
    def _block():
        @pl.when(fi == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]                                  # (bm, d)
        # f32 operands contract at full f32 precision, bf16 natively
        prec = (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        h = jax.lax.dot_general(x, wu_ref[0, 0], (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32)
        h = jnp.square(jnp.maximum(h, 0.0)).astype(x.dtype)   # (bm, bf)
        acc_ref[...] += jax.lax.dot_general(
            h, wd_ref[0, 0], (((1,), (0,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32)

        @pl.when(fi == num_f - 1)
        def _finish():
            y_ref[...] = acc_ref[...].astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_f",
                                             "interpret"))
def expert_mlp(x, w_up, w_down, block_group, n_blocks, layer=0, *,
               block_m: int, block_f: int = 512, interpret: bool = False):
    """x: (R, d) rows in ``block_layout``'s padded layout, R a multiple of
    ``block_m``; w_up, w_down: (G, F, d), or (layers, G, F, d) with
    ``layer`` the one to use; block_group (R / block_m,) and n_blocks (1,)
    int32 from ``block_layout``.  Returns (R, d) in x.dtype:
    ``relu(x W_up[g]^T)^2 W_down[g]`` on the rows of the first
    ``n_blocks`` blocks."""
    if w_up.ndim == 3:
        w_up, w_down = w_up[None], w_down[None]
    R, d = x.shape
    _, G, F, _ = w_up.shape
    assert w_down.shape == w_up.shape, (w_down.shape, w_up.shape)
    assert R % block_m == 0, (R, block_m)
    bf = pick_block_f(F, block_f, block_rows(w_up.dtype))
    nf = F // bf

    def row_map(i, fi, layer, group, nb):
        return (jnp.minimum(i, jnp.maximum(nb[0] - 1, 0)), 0)

    def weight_map(i, fi, layer, group, nb):
        last = jnp.maximum(nb[0] - 1, 0)
        # past the last block: stay on its final tile, so nothing is fetched
        return (layer[0], group[jnp.minimum(i, last)],
                jnp.where(i < nb[0], fi, nf - 1), 0)

    item = jnp.dtype(x.dtype).itemsize
    working = 2 * item * (2 * block_m * d + 2 * bf * d) + 4 * block_m * d
    return pl.pallas_call(
        functools.partial(_expert_mlp_kernel, num_f=nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(R // block_m, nf),
            in_specs=[
                pl.BlockSpec((block_m, d), row_map),
                pl.BlockSpec((1, 1, bf, d), weight_map),
                pl.BlockSpec((1, 1, bf, d), weight_map),
            ],
            out_specs=pl.BlockSpec((block_m, d), row_map),
            scratch_shapes=[pltpu.VMEM((block_m, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((R, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, working + working // 4)),
        interpret=interpret,
        name="expert_mlp",
    )(jnp.asarray(layer, jnp.int32).reshape(1), block_group, n_blocks, x,
      w_up, w_down)
