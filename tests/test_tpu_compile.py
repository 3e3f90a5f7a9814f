"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The TPU compiler compiles for a chip that is described, not attached, so
these run on a CPU-only machine: each kernel is lowered with
``interpret=False`` at the widths ``chip_smoke.py`` runs on the chip and
must come out as a Mosaic kernel (``tpu_custom_call``).  They catch what
interpret mode cannot: block shapes off the (8, 128) tiling, primitives
without a TPU lowering, and kernels that overrun scoped VMEM.  The rest
compile served models' decode steps, to catch the copies of stacked
weights that the chip's layouts would add to zamba2-2.7b's, and copies
of a whole cache that a donated cache can add to any pattern's.

The topology is described inside a module fixture: only one process at
a time may load the TPU library, so nothing here touches it while the
module is imported.
"""
import dataclasses
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as M

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _attention(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128, interpret=False)


def _ssd(c, b, v, log_a):
    return ops.ssd_scan(c, b, v, log_a, chunk=256, interpret=False)


def _expert_mlp(bm):
    def run(x, w_up, w_down, block_group, n_blocks):
        return ops.expert_mlp(x, w_up, w_down, block_group, n_blocks,
                              block_m=bm, interpret=False)
    return run


def _shaped(tree, sharding):
    """``tree``'s arrays as shapes placed on ``sharding``."""
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _step_inputs(cfg, batch, max_len, sharding):
    """Shapes of a decode step's weights, cache and one token a sequence."""
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, batch, max_len))
    tokens = {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
    return tuple(_shaped(t, sharding) for t in (params, cache, tokens))


def _expert_glu(x, w_up, w_down):
    return ops.expert_glu(x, w_up, w_down, block_m=128, block_f=256,
                          interpret=False)


# (kernel, argument shapes and dtypes): zamba2-2.7b's shared attention and
# Mamba2 layer, the expert GLU at bf16, nemotron-3-nano's experts, and the
# three kernels at the f32 widths of the smoke's kernel chains (32 heads x
# 64, state 64, d 2048, cap 512; and the four-chip path's)
CASES = {
    "attention-zamba2-bf16": (_attention, [((1, 2048, 32, 80), BF16)] * 3),
    "attention-chain-f32": (_attention, [((1, 2048, 32, 64), F32)] * 3),
    "ssd-zamba2-f32": (_ssd, [((1, 2048, 80, 64), F32)] * 3
                       + [((1, 2048, 80), F32)]),
    "ssd-chain-f32": (_ssd, [((1, 2048, 32, 64), F32)] * 3
                      + [((1, 2048, 32), F32)]),
    "expert_glu-bf16": (_expert_glu, [((8, 256, 2048), BF16),
                                      ((8, 2048, 2048), BF16),
                                      ((8, 1024, 2048), BF16)]),
    "expert_glu-chain-f32": (_expert_glu, [((8, 512, 2048), F32),
                                           ((8, 2048, 2048), F32),
                                           ((8, 1024, 2048), F32)]),
    # the smaller chain of the four-chip path (seq 128, one head of 64)
    "attention-chain4-f32": (_attention, [((1, 128, 1, 64), F32)] * 3),
    "ssd-chain4-f32": (_ssd, [((1, 128, 1, 64), F32)] * 3
                       + [((1, 128, 1), F32)]),
    "expert_glu-chain4-f32": (_expert_glu, [((8, 128, 64), F32),
                                            ((8, 64, 512), F32),
                                            ((8, 256, 64), F32)]),
    # nemotron-3-nano's 16 held experts of 1856 at hidden 2688: a decode
    # step's 32 tokens x top 6 and a prefill's 32 x 256 tokens
    "expert_mlp-nemotron-decode": (_expert_mlp(16), [
        ((432, 2688), BF16), ((16, 1856, 2688), BF16),
        ((16, 1856, 2688), BF16), ((27,), jnp.int32), ((1,), jnp.int32)]),
    "expert_mlp-nemotron-prefill": (_expert_mlp(128), [
        ((51200, 2688), BF16), ((16, 1856, 2688), BF16),
        ((16, 1856, 2688), BF16), ((400,), jnp.int32), ((1,), jnp.int32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    kernel, shapes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the stacked Mamba2 projections of zamba2-2.7b, (rows, width) either way
_ZAMBA2_SLABS = re.compile(
    r"= bf16\[(\d+),(?:2560,10448|10448,2560|5120,2560|2560,5120)\]\S* "
    r"(copy|fusion|dynamic-slice)\(")


def test_zamba2_decode_step_reads_weights_in_place(one_chip):
    """zamba2-2.7b's decode step at the published widths (12 layers: the
    loop body does not depend on depth), 8 sequences of a 160-token
    cache: no copy or slice of more than one layer of ``in_proj`` or
    ``out_proj``, and temporaries well below the weights, which the step
    has only to stream."""
    cfg = dataclasses.replace(get_config("zamba2-2.7b"), n_layers=12)
    params, cache, batch = _step_inputs(cfg, 8, 160, one_chip)
    compiled = jax.jit(partial(M.decode_step, cfg), donate_argnums=1).lower(
        params, cache, batch).compile()
    slabs = [m.group(0) for m in _ZAMBA2_SLABS.finditer(compiled.as_text())
             if int(m.group(1)) >= 2]
    assert not slabs
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert compiled.memory_analysis().temp_size_in_bytes < weights / 3


def test_nemotron_decode_step_updates_donated_cache_in_place(one_chip):
    """nemotron-3-nano's decode step at the published widths (its first
    13 layers, two periods of the pattern: at 6 the compiler finds the
    in-place update itself), 32 sequences of a 288-token cache, the
    cache donated: no copy of a whole cache stack, which a step that
    builds its new cache apart from the donated one needs."""
    cfg = dataclasses.replace(get_config("nemotron-3-nano-30b-a3b"),
                              n_layers=13, moe_held=16)
    params, cache, batch = _step_inputs(cfg, 32, 288, one_chip)
    text = jax.jit(partial(M.decode_step, cfg), donate_argnums=1).lower(
        params, cache, batch).compile().as_text()
    stacks = {",".join(map(str, a.shape))
              for a in jax.tree.leaves(cache) if a.ndim >= 4}
    copies = set(re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text))
    assert not stacks & copies


# one config of each block pattern the decode step serves: the published
# widths at 4 layers where a layer is small, else the reduced config
PATTERNS = {
    "dense": ("llama3.2-1b", {"n_layers": 4}),
    "moe": ("granite-moe-1b-a400m", {"n_layers": 4}),
    "mla_moe": ("deepseek-v3-671b", None),
    "encdec": ("seamless-m4t-medium", None),
    "xlstm": ("xlstm-125m", {}),
    "zamba2": ("zamba2-2.7b", None),
    "nemotron_h": ("nemotron-3-nano-30b-a3b", None),
}


@pytest.mark.parametrize("pattern", list(PATTERNS))
def test_donating_the_cache_adds_no_copy_of_it(pattern, one_chip):
    """The Engine and the dry-run donate the decode step's cache: compiled
    that way, the step holds no more copies of a whole cache stack than
    it does with the cache not donated.  A step that builds its new cache
    apart from the donated one, as a scan's outputs or a ``jnp.stack``,
    has to copy it into the donated buffers after the loop."""
    name, widths = PATTERNS[pattern]
    cfg = get_config(name)
    cfg = cfg.reduced() if widths is None else dataclasses.replace(cfg,
                                                                   **widths)
    assert cfg.block_pattern == pattern
    params, cache, batch = _step_inputs(cfg, 8, 256, one_chip)
    stacks = {",".join(map(str, a.shape))
              for a in jax.tree.leaves(cache) if a.ndim >= 3}

    def whole_copies(donate):
        text = jax.jit(partial(M.decode_step, cfg),
                       donate_argnums=donate).lower(
            params, cache, batch).compile().as_text()
        return sum(s in stacks for s in re.findall(
            r"= \w+\[([\d,]+)\]\S* copy(?:-done)?\(", text))

    assert whole_copies(1) <= whole_copies(())
