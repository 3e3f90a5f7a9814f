"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The TPU compiler compiles for a chip that is described, not attached, so
these run on a CPU-only machine: each kernel is lowered with
``interpret=False`` at the widths ``chip_smoke.py`` runs on the chip and
must come out as a Mosaic kernel (``tpu_custom_call``).  They catch what
interpret mode cannot: block shapes off the (8, 128) tiling, primitives
without a TPU lowering, and kernels that overrun scoped VMEM.

The topology is described inside a module fixture: only one process at
a time may load the TPU library, so nothing here touches it while the
module is imported.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

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
