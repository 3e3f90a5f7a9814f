"""Pallas kernel sweeps: interpret-mode kernel == ref.py oracle.

Shapes/dtypes sweep per kernel + hypothesis property tests on the
invariants (GQA group equivalence, scan associativity via chunk-size
independence, MoE capacity monotonicity).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(atol=3e-5, rtol=3e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Tq,Tk,Hq,Hk,D,causal,off,bq,bk",
    [
        (2, 256, 256, 4, 2, 64, True, 0, 128, 128),
        (1, 128, 384, 8, 8, 64, True, 0, 64, 128),
        (2, 200, 200, 4, 1, 32, True, 0, 64, 64),     # padded seqs
        (1, 64, 512, 4, 2, 128, False, 0, 64, 128),
        (1, 1, 300, 4, 2, 64, True, 299, 64, 64),     # decode-style
        (1, 96, 96, 2, 2, 16, True, 0, 32, 32),
    ])
def test_flash_attention_sweep(B, Tq, Tk, Hq, Hk, D, causal, off, bq, bk,
                               dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, Tq, Hq, D), dtype)
    k = rand(ks[1], (B, Tk, Hk, D), dtype)
    v = rand(ks[2], (B, Tk, Hk, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, q_offset=off,
                              block_q=bq, block_k=bk, interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal, q_offset=off)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **TOL[dtype])


def test_flash_attention_block_size_independent():
    """The online softmax must not depend on the tiling."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 160, 4, 32), jnp.float32)
    k = rand(ks[1], (1, 160, 2, 32), jnp.float32)
    v = rand(ks[2], (1, 160, 2, 32), jnp.float32)
    outs = [ops.flash_attention(q, k, v, block_q=bq, block_k=bk,
                                interpret=True)
            for bq, bk in [(32, 32), (64, 32), (32, 64), (160, 160)]]
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, atol=2e-6, rtol=2e-6)


@settings(max_examples=20, deadline=None)
@given(
    B=st.integers(1, 2),
    tq_blocks=st.integers(1, 3),
    hk=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([16, 32]),
    causal=st.booleans(),
)
def test_flash_attention_property(B, tq_blocks, hk, g, d, causal):
    Tq = 32 * tq_blocks + 7    # deliberately non-multiple
    ks = jax.random.split(jax.random.PRNGKey(B * 1000 + Tq), 3)
    q = rand(ks[0], (B, Tq, hk * g, d), jnp.float32)
    k = rand(ks[1], (B, Tq, hk, d), jnp.float32)
    v = rand(ks[2], (B, Tq, hk, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, expected, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,T,H,N,P,chunk,with_s0",
    [
        (2, 128, 2, 16, 32, 32, False),
        (1, 100, 3, 8, 16, 32, False),     # padded T
        (2, 64, 2, 16, 16, 16, True),
        (1, 256, 1, 32, 64, 64, False),
        (1, 17, 2, 8, 8, 32, True),        # T < chunk
    ])
def test_ssd_scan_sweep(B, T, H, N, P, chunk, with_s0, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    c = rand(ks[0], (B, T, H, N), dtype)
    b = rand(ks[1], (B, T, H, N), dtype)
    v = rand(ks[2], (B, T, H, P), dtype)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (B, T, H), jnp.float32))
    s0 = (jax.random.normal(ks[4], (B, H, N, P), jnp.float32)
          if with_s0 else None)
    y, S = ops.ssd_scan(c, b, v, la, initial_state=s0, chunk=chunk,
                        interpret=True)
    yr, Sr = ref.ssd_scan_ref(c, b, v, la, initial_state=s0)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               **TOL[dtype])
    np.testing.assert_allclose(S, Sr, atol=5e-4, rtol=5e-4)


@settings(max_examples=15, deadline=None)
@given(
    T=st.integers(4, 80),
    chunk=st.sampled_from([8, 16, 32]),
    H=st.integers(1, 3),
    N=st.sampled_from([8, 16]),
)
def test_ssd_scan_chunk_independence(T, chunk, H, N):
    """Chunked recomposition must equal the sequential recurrence for any
    chunk size (the associativity invariant of the SSD algebra)."""
    ks = jax.random.split(jax.random.PRNGKey(T * 97 + chunk), 4)
    c = rand(ks[0], (1, T, H, N), jnp.float32)
    b = rand(ks[1], (1, T, H, N), jnp.float32)
    v = rand(ks[2], (1, T, H, N), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (1, T, H), jnp.float32))
    y, S = ops.ssd_scan(c, b, v, la, chunk=chunk, interpret=True)
    yr, Sr = ref.ssd_scan_ref(c, b, v, la)
    np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(S, Sr, atol=1e-4, rtol=1e-4)


def test_ssd_scan_state_chaining():
    """scan(T) == scan(T/2) chained through the carried state."""
    T = 64
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    c = rand(ks[0], (1, T, 2, 8), jnp.float32)
    b = rand(ks[1], (1, T, 2, 8), jnp.float32)
    v = rand(ks[2], (1, T, 2, 8), jnp.float32)
    la = -jax.nn.softplus(jax.random.normal(ks[3], (1, T, 2), jnp.float32))
    y_full, S_full = ops.ssd_scan(c, b, v, la, chunk=16, interpret=True)
    h = T // 2
    y1, S1 = ops.ssd_scan(c[:, :h], b[:, :h], v[:, :h], la[:, :h],
                          chunk=16, interpret=True)
    y2, S2 = ops.ssd_scan(c[:, h:], b[:, h:], v[:, h:], la[:, h:],
                          initial_state=S1, chunk=16, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(S2, S_full, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# moe dispatch/combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "T,d,E,K,F,cap,bm,bf",
    [
        (64, 32, 4, 2, 16, 32, 16, 16),
        (128, 64, 8, 2, 32, 24, 32, 32),    # drops happen
        (100, 32, 4, 4, 16, 128, 64, 16),   # no drops
        (32, 16, 2, 1, 8, 16, 8, 8),
    ])
def test_moe_sweep(T, d, E, K, F, cap, bm, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = rand(ks[0], (T, d), dtype)
    logits = jax.random.normal(ks[1], (T, E), jnp.float32)
    gv, gi = jax.lax.top_k(jax.nn.softmax(logits), K)
    gv = (gv / gv.sum(-1, keepdims=True)).astype(dtype)
    w_up = rand(ks[2], (E, d, 2 * F), dtype) * 0.1
    w_down = rand(ks[3], (E, F, d), dtype) * 0.1
    out = ops.moe_dispatch_combine(x, gi, gv, w_up, w_down, capacity=cap,
                                   block_m=bm, block_f=bf, interpret=True)
    expected = ref.moe_dispatch_combine_ref(x, gi, gv, w_up, w_down,
                                            capacity=cap)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 2e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,cap,d,F,bm,bf", [
    (2, 32, 64, 32, 16, 16),
    (3, 40, 32, 48, 16, 16),     # cap padded to the row block
])
def test_expert_glu_sweep(E, cap, d, F, bm, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = rand(ks[0], (E, cap, d), dtype)
    w_up = rand(ks[1], (E, d, 2 * F), dtype) * d ** -0.5
    w_down = rand(ks[2], (E, F, d), dtype) * F ** -0.5
    out = ops.expert_glu(x, w_up, w_down, block_m=bm, block_f=bf,
                         interpret=True)
    expected = ref.expert_glu_ref(x, w_up, w_down)
    assert out.shape == expected.shape and out.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 2e-4,
                               rtol=5e-2 if dtype == jnp.bfloat16 else 2e-4)


@settings(max_examples=15, deadline=None)
@given(
    T=st.integers(8, 64),
    E=st.sampled_from([2, 4, 8]),
    K=st.integers(1, 3),
    cap_frac=st.floats(0.2, 2.0),
)
def test_moe_property(T, E, K, cap_frac):
    K = min(K, E)
    cap = max(int(cap_frac * T * K / E), 1)
    d, F = 16, 8
    ks = jax.random.split(jax.random.PRNGKey(T * 31 + E), 4)
    x = rand(ks[0], (T, d), jnp.float32)
    logits = jax.random.normal(ks[1], (T, E), jnp.float32)
    gv, gi = jax.lax.top_k(jax.nn.softmax(logits), K)
    w_up = rand(ks[2], (E, d, 2 * F), jnp.float32) * 0.1
    w_down = rand(ks[3], (E, F, d), jnp.float32) * 0.1
    out = ops.moe_dispatch_combine(x, gi, gv, w_up, w_down, capacity=cap,
                                   block_m=16, block_f=8, interpret=True)
    expected = ref.moe_dispatch_combine_ref(x, gi, gv, w_up, w_down,
                                            capacity=cap)
    np.testing.assert_allclose(out, expected, atol=2e-4, rtol=2e-4)


def test_moe_dispatch_capacity_invariants():
    """Queue positions are dense per expert and respect arrival order."""
    T, K, E, cap = 40, 2, 4, 8
    gi = jax.random.randint(jax.random.PRNGKey(7), (T, K), 0, E)
    token_of, keep, pos = ops.dispatch_indices(gi, cap, E)
    token_of = np.asarray(token_of)
    # every non-pad slot holds a valid token id, strictly increasing per
    # expert queue (first-come order)
    for e in range(E):
        ids = [t for t in token_of[e] if t >= 0]
        assert ids == sorted(ids)
    # kept count per expert <= capacity
    kept_per_e = np.zeros(E, int)
    gi_n, keep_n = np.asarray(gi), np.asarray(keep)
    for t in range(T):
        for k in range(K):
            if keep_n[t, k]:
                kept_per_e[gi_n[t, k]] += 1
    assert (kept_per_e <= cap).all()


# ---------------------------------------------------------------------------
# relu^2 experts over sorted, grouped rows (expert_mlp)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("sizes,rows,d,F,bm,bf", [
    ((5, 0, 17, 3), 30, 64, 48, 16, 16),     # uneven, one empty, rows past
    ((0, 40, 0), 40, 32, 64, 16, 32),        # one group holds every row
    ((1, 1, 1, 1, 1, 1), 8, 128, 32, 16, 32),  # decode-like: a row each
])
def test_expert_mlp_matches_ragged_dot(sizes, rows, d, F, bm, bf, dtype):
    """The kernel over ``block_layout``'s padded blocks against the layer's
    ``jax.numpy`` path (``ragged_dot`` over the sorted rows); f32 at
    HIGHEST, bf16 at the MXU's default with an f32 accumulator."""
    G = len(sizes)
    sizes = jnp.asarray(sizes, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    x = rand(ks[0], (rows, d), dtype)
    w_up = (rand(ks[1], (G, F, d), jnp.float32) * d ** -0.5).astype(dtype)
    w_down = (rand(ks[2], (G, F, d), jnp.float32) * F ** -0.5).astype(dtype)
    place, block_group, n_blocks, padded = ops.block_layout(sizes, rows, bm)
    xp = jnp.zeros((padded + 1, d), dtype).at[place].set(x)[:padded]
    y = ops.expert_mlp(xp, w_up, w_down, block_group, n_blocks, block_m=bm,
                       block_f=bf, interpret=True)
    assert y.shape == (padded, d) and y.dtype == x.dtype
    got = jnp.concatenate([y, jnp.zeros((1, d), dtype)])[place]
    with jax.default_matmul_precision("highest"):
        h = jax.lax.ragged_dot(x, jnp.swapaxes(w_up, 1, 2), sizes)
        want = jax.lax.ragged_dot(jnp.square(jax.nn.relu(h)).astype(dtype),
                                  w_down, sizes)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    # rows past the groups are placed nowhere
    assert (np.asarray(place)[int(sizes.sum()):] == padded).all()
    assert int(n_blocks[0]) == int(sum(-(-int(s) // bm) for s in sizes))
