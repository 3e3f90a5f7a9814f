"""Nemotron-H (``nemotron_h``, nemotron-3-nano-30b-a3b) at tiny sizes on the
CPU: the served path against the plain reference of the benchmark, the
held-expert layer's share and droplessness, the router's bias, Mamba2 at
one group unchanged for zamba2, and the parameter counts."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.nemotron_h import PROGRAM_NAMES
from bench.reference import nemotron_h as ref
from repro.configs import get_config
from repro.models import layers as L
from repro.models import model as M
from repro.sharding import NO_POLICY

ARCH = "nemotron-3-nano-30b-a3b"
# the reference's configuration-file keys at a tiny size: all three mixer
# kinds, 8 routed experts of which 4 are held
TINY = dict(hidden_size=64, num_hidden_layers=6, vocab_size=256,
            hybrid_override_pattern="MEMEM*EMEMEM*", num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, router_experts=8,
            n_routed_experts=4, expert_offset=2, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
            routed_scaling_factor=2.5, mamba_num_heads=8, mamba_head_dim=16,
            ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
            norm_eps=1e-5, layer_norm_epsilon=1e-5)


def program_config(file: dict, **kw):
    """The program's config of reference-file keys ``file``, mapped as the
    benchmark's driver maps them."""
    return dataclasses.replace(
        get_config(ARCH), **{k: file[v] for k, v in PROGRAM_NAMES.items()},
        dtype="float32", remat=False, **kw)


def _layer_params(file, key):
    """One MoE layer's weights of the reference (``held`` experts)."""
    m = ref.dims(file)
    params = ref.init(key, m, jnp.float32)
    return m, params["moe_blocks"]


@pytest.mark.parametrize("use_kernels", [False, True])
def test_prefill_then_decode_match_the_reference(use_kernels):
    """Prefill of 12 tokens, then 6 decode steps through the cache, against
    the reference's forward over all 18, logit by logit.  Both sides in
    f32 (the kernels interpreted at HIGHEST): what is left is the order of
    summation, chunked SSD against the sequential scan and blockwise
    against whole matmuls, ~1e-6 of logits of order 1; 1e-4 leaves room
    for that and for nothing a wrong layer would give."""
    m = ref.dims(TINY)
    cfg = program_config(TINY, use_kernels=use_kernels)
    params = ref.init(jax.random.PRNGKey(3), m, jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 18), 0, 256)
    want = np.asarray(ref.logits(params, tokens, m, 11))       # 11 .. 17
    got, cache = M.prefill(cfg, params, {"tokens": tokens[:, :12]}, max_len=18)
    outs = [got[:, -1]]
    for t in range(12, 17):
        logits, cache = M.decode_step(cfg, params, cache,
                                      {"tokens": tokens[:, t:t + 1]})
        outs.append(logits[:, 0])
    got = np.stack([np.asarray(o) for o in outs], axis=1)
    np.testing.assert_allclose(got, want[:, :6], atol=1e-4, rtol=1e-4)
    # every MoE layer call was counted: prefill 24 tokens x 2 over 3 layers
    stats = np.asarray(cache["moe_stats"])
    assert 0 < stats[0, 0] <= 3 * 24 * 2 and 0 < stats[1, 0] <= 5 * 3 * 2 * 2


@pytest.mark.parametrize("use_kernel", [False, True])
def test_held_shares_sum_to_the_whole_layer(use_kernel):
    """Two chips holding experts 0-3 and 4-7 of 8: their outputs, with the
    shared expert that each computes counted once, add up to the uncut
    reference's layer."""
    whole = dict(TINY, n_routed_experts=8, expert_offset=0)
    m, blocks = _layer_params(whole, jax.random.PRNGKey(5))
    h = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 64), jnp.float32)
    want = ref._moe(blocks, 0, h, sizes=tuple(sorted(m.items())),
                    int8=False) - h
    p = jax.tree.map(lambda a: a[0], blocks)
    x = L.rms_norm(h, p["ln1"], 1e-5)
    total = 0.0
    for offset in (0, 4):
        cfg = program_config(dict(TINY, n_routed_experts=4,
                                  expert_offset=offset))
        share = dict(p["moe"], w_up=p["moe"]["w_up"][offset:offset + 4],
                     w_down=p["moe"]["w_down"][offset:offset + 4])
        out, _ = L.held_moe_block(share, x, cfg, NO_POLICY,
                                  use_kernel=use_kernel)
        total = total + out
    xf = x.reshape(-1, 64)
    shared = (jnp.square(jax.nn.relu(xf @ p["moe"]["shared"]["wi"]))
              @ p["moe"]["shared"]["wo"]).reshape(h.shape)
    np.testing.assert_allclose(np.asarray(total - shared), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_dropless_when_every_token_picks_one_held_expert(use_kernel):
    """A bias that makes all 64 tokens choose held expert 3 puts 64 rows on
    it, where a capacity of 1.25 x the mean would keep 20: none is
    dropped, and the layer equals the reference."""
    m, blocks = _layer_params(TINY, jax.random.PRNGKey(7))
    blocks["moe"]["router_bias"] = blocks["moe"]["router_bias"].at[0, 3].set(50.)
    h = jax.random.normal(jax.random.PRNGKey(8), (4, 16, 64), jnp.float32)
    want = ref._moe(blocks, 0, h, sizes=tuple(sorted(m.items())),
                    int8=False) - h
    p = jax.tree.map(lambda a: a[0], blocks)
    cfg = program_config(TINY)
    out, stats = L.held_moe_block(p["moe"], L.rms_norm(h, p["ln1"], 1e-5),
                                  cfg, NO_POLICY, use_kernel=use_kernel)
    assert int(stats[1]) == 64                    # rows on the one expert
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_correction_bias_chooses_and_does_not_weigh():
    cfg = program_config(TINY)
    p = {"router": jax.random.normal(jax.random.PRNGKey(9), (64, 8)) / 8,
         "router_bias": jnp.zeros((8,))}
    x = jax.random.normal(jax.random.PRNGKey(10), (32, 64))
    idx0, w0 = L.sigmoid_route(p, x, cfg)
    biased = dict(p, router_bias=jnp.linspace(-0.3, 0.3, 8))
    idx1, w1 = L.sigmoid_route(biased, x, cfg)
    assert (np.asarray(idx0) != np.asarray(idx1)).any()
    s = jax.nn.sigmoid(x @ p["router"])
    for idx, w in ((idx0, w0), (idx1, w1)):
        picked = jnp.take_along_axis(s, idx, axis=-1)
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(picked / picked.sum(-1, keepdims=True)
                                      * 2.5), rtol=1e-6)


def _mamba2_one_group(p, x, cfg, *, state=None):
    """``layers.mamba2_block`` as it stood before groups and explicit heads
    (zamba2's path): the oracle for one group."""
    B, T, d = x.shape
    di, H, N = cfg.ssm_d_inner, cfg.ssm_heads, cfg.ssm_state
    P = di // H
    conv_dim = di + 2 * N
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim], axis=-1)
    if state is not None:
        conv_in = jnp.concatenate([state["conv"], xbc], axis=1)
        xbc = jnp.einsum("bkc,kc->bc", conv_in[:, -cfg.ssm_conv:],
                         p["conv_w"])[:, None, :] + p["conv_b"]
    else:
        pad = jnp.zeros((B, cfg.ssm_conv - 1, conv_dim), xbc.dtype)
        xin = jnp.concatenate([pad, xbc], axis=1)
        xbc = sum(xin[:, i:i + T] * p["conv_w"][i] for i in range(cfg.ssm_conv))
        xbc = xbc + p["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs, Bc, Cc = jnp.split(xbc, [di, di + N], axis=-1)
    Tx = xs.shape[1]
    xs = xs.reshape(B, Tx, H, P)
    Bh = jnp.repeat(Bc.reshape(B, Tx, 1, N), H, axis=2)
    Ch = jnp.repeat(Cc.reshape(B, Tx, 1, N), H, axis=2)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    log_a = dt * -jnp.exp(p["A_log"])
    xdt = xs * dt[..., None].astype(xs.dtype)
    if state is not None:
        y, _ = L.linear_recurrence_step(state["ssm"], Ch[:, 0], Bh[:, 0],
                                        xdt[:, 0], log_a[:, 0])
        y = y[:, None]
    else:
        y, _ = L.chunked_linear_recurrence(Ch, Bh, xdt, log_a,
                                           chunk=cfg.ssm_chunk)
    y = y + xs * p["D"][None, None, :, None].astype(xs.dtype)
    y = y.reshape(B, y.shape[1], di)
    y = L.rms_norm(y * jax.nn.silu(z[:, :y.shape[1]]), p["norm_w"])
    return y @ p["out_proj"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mamba2_one_group_is_bitwise_unchanged(dtype):
    cfg = dataclasses.replace(get_config("zamba2-2.7b").reduced(), dtype=dtype)
    p = L.mamba2_init(jax.random.PRNGKey(11), cfg, cfg.jdtype)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 20, cfg.d_model)
                          ).astype(cfg.jdtype)
    got, st = L.mamba2_block(p, x, cfg, NO_POLICY)
    assert np.array_equal(np.asarray(got), np.asarray(_mamba2_one_group(p, x, cfg)))
    state = {"ssm": st["ssm"], "conv": st["conv"]}
    got, _ = L.mamba2_block(p, x[:, :1], cfg, NO_POLICY, state=state)
    assert np.array_equal(np.asarray(got), np.asarray(
        _mamba2_one_group(p, x[:, :1], cfg, state=state)))


def test_param_counts():
    cfg = get_config(ARCH)
    assert cfg.param_count() == pytest.approx(31.58e9, rel=5e-3)
    cut = dataclasses.replace(cfg, n_layers=27, moe_held=16)
    assert cut.layer_kinds == "MEMEM*" + "EMEMEM*" * 3
    assert cut.param_count() == pytest.approx(3.24e9, rel=5e-3)
