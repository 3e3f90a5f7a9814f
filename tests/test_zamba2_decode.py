"""zamba2's decode step, which reads each layer's weights and state from
the full stacks in place, against the nested scan over groups of layers
that it replaced, kept here as the oracle: same tokens, and logits and
caches within bf16 rounding, step after step (bitwise where the oracle
reads ``in_proj`` as the new step does)."""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import layers as L
from repro.models import model as M
from repro.sharding import NO_POLICY


def nested_scan_decode_step(cfg, params, cache, batch, shd=NO_POLICY, *,
                            transposed_read=False):
    """The zamba2 decode step as a scan over groups of ``zamba_attn_every``
    layers, each a scan over its layers' sub-stack of weights; with
    ``transposed_read``, each layer's ``in_proj`` is read as the new step
    reads it, through a transposed view of the stack."""
    h = M._embed_in(cfg, params, batch, shd)
    B, T = h.shape[:2]
    idx = cache["len"]
    pos = jnp.broadcast_to(idx[None, None], (B, T))
    every = cfg.zamba_attn_every
    G = cfg.n_layers // every
    blocks = params["blocks"]
    if transposed_read:
        blocks = {**blocks, "mamba": {
            **blocks["mamba"],
            "in_proj": jnp.swapaxes(blocks["mamba"]["in_proj"], -1, -2)}}
    grouped = jax.tree.map(
        lambda x: x.reshape(G, every, *x.shape[1:]), blocks)
    gssm = cache["ssm"].reshape(G, every, *cache["ssm"].shape[1:])
    gconv = cache["conv"].reshape(G, every, *cache["conv"].shape[1:])
    sa = params["shared_attn"]

    def group_body(h, xs):
        glp, ssm_g, conv_g, ck, cv = xs

        def inner(h, ixs):
            lp, s, c = ixs
            if transposed_read:
                lp["mamba"]["in_proj"] = lp["mamba"]["in_proj"].T
            m, ns = L.mamba2_block(lp["mamba"],
                                   L.rms_norm(h, lp["ln1"], cfg.norm_eps),
                                   cfg, shd, state={"ssm": s, "conv": c})
            return h + m, (ns["ssm"], ns["conv"])
        h, (nssm, nconv) = jax.lax.scan(inner, h, (glp, ssm_g, conv_g))
        a, nc = L.gqa_attention(sa["attn"],
                                L.rms_norm(h, sa["ln"], cfg.norm_eps), cfg,
                                shd, positions=pos,
                                cache={"k": ck, "v": cv, "len": idx})
        return h + a, (nssm, nconv, nc["k"], nc["v"])
    h, (nssm, nconv, nk, nv) = jax.lax.scan(
        group_body, h, (grouped, gssm, gconv,
                        cache["attn"]["k"], cache["attn"]["v"]))
    new_cache = {
        "ssm": nssm.reshape(cfg.n_layers, *nssm.shape[2:]),
        "conv": nconv.reshape(cfg.n_layers, *nconv.shape[2:]),
        "attn": {"k": nk, "v": nv}, "len": idx + T}
    return M._logits(cfg, params, h, shd), new_cache


# (layers, zamba_attn_every): two and three groups sharing the attention.
# The reduced config's in_proj is 296 wide, off the 128-lane tiling like
# zamba2-2.7b's, and the new step reads it through its transposed view.
DEPTHS = {"2_groups": (6, 3), "3_groups": (6, 2)}


@pytest.mark.parametrize("use_kernels", [False, True],
                         ids=["plain", "kernels"])
@pytest.mark.parametrize("depth,oracle", [
    ("2_groups", "as_replaced"), ("2_groups", "same_read"),
    ("3_groups", "same_read")])
def test_decode_step_matches_nested_group_scan(depth, oracle, use_kernels):
    """Against the nested scan reading ``in_proj`` as the new step does,
    bitwise: the loops over the full stacks change nothing else.  Against
    the step as it was, within bf16 rounding: on the CPU the transposed
    read sums the ``in_proj`` dot in another order, which moves a rare
    element of the conv state by one bf16 step, and that difference
    grows with the layers and steps it passes through, so this one is
    held at the smaller depth."""
    n_layers, every = DEPTHS[depth]
    cfg = dataclasses.replace(get_config("zamba2-2.7b").reduced(),
                              n_layers=n_layers, zamba_attn_every=every,
                              dtype="bfloat16", use_kernels=use_kernels)
    same = _equal if oracle == "same_read" else _close
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (3, 10), 0, cfg.vocab)
    logits, cache = M.prefill(cfg, params, {"tokens": prompt}, max_len=16)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    new = jax.jit(partial(M.decode_step, cfg))
    old = jax.jit(partial(nested_scan_decode_step, cfg,
                          transposed_read=oracle == "same_read"))
    c_new = c_old = cache
    t_new = t_old = tok
    for step in range(5):
        l_new, c_new = new(params, c_new, {"tokens": t_new})
        l_old, c_old = old(params, c_old, {"tokens": t_old})
        t_new = jnp.argmax(l_new[:, -1], -1)[:, None].astype(jnp.int32)
        t_old = jnp.argmax(l_old[:, -1], -1)[:, None].astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(t_new), np.asarray(t_old),
                                      err_msg=f"step {step}")
        same(l_new, l_old, f"logits, step {step}")
        assert jax.tree.structure(c_new) == jax.tree.structure(c_old)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(c_new),
                                jax.tree.leaves(c_old)):
            same(a, b, f"cache{jax.tree_util.keystr(path)}, step {step}")
    assert int(c_new["len"]) == 15


def _equal(got, want, what):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=what)


def _close(got, want, what):
    """Agreement within bf16 rounding: bf16's epsilon (2**-7) relative,
    and the same absolute for values near zero."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -7,
                               err_msg=what)
