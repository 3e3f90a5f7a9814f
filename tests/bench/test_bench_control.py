"""Each cell's control comes out not correct: the reference in the next
lower precision than its configuration states, put in the program's
place, fails the cell's comparison at its limit.  On the chip the
control is read at the cells' sizes by ``bench/control.py``; here at
sizes a CPU test run holds."""
import jax
import numpy as np

from bench import run
from bench.common import max_rel
from bench.drivers.orchestrator import Driver
from bench.reference import kernel_chain, zamba2


def test_chain_control_fails_its_limit(tiny_cell):
    cell, config = tiny_cell("chain.solo")
    driver = Driver(cell, config, 2**31 + 11, jax.devices()[0])
    got = driver.check(driver.control_sample(), control=True)
    limit = run.load_cell("chain.solo")[0]["limits"]["op_err"]
    assert got["op_err"] > limit


def test_control_is_judged_like_the_program(run_tiny):
    """On the window's own sample the program comes out correct and the
    control, through the same comparison with the cell's limits, not."""
    result, _ = run_tiny("chain.solo", control=True)
    assert result["correct"] is True
    assert result["control_correct"] is False
    limit = run.load_cell("chain.solo")[0]["limits"]["op_err"]
    assert result["control"]["op_err"]["limit"] == limit
    assert result["control"]["op_err"]["value"] > limit


def test_compare_holds_each_number_to_its_limit():
    compared, ok = run.compare({"a": 0.5, "b": 2.0}, {"a": 1.0, "b": 2.0})
    assert ok and compared["a"] == {"value": 0.5, "limit": 1.0}
    assert run.compare({"a": 1.5}, {"a": 1.0})[1] is False


def test_chain_control_is_three_bf16_passes():
    a = np.float32(1.0) + np.float32(2.0 ** -12)      # not a bf16 value
    head, tail = kernel_chain._split(np.array([a]))
    assert head[0] == 1.0 and head[0] + tail[0] == a
    x = np.random.default_rng(0).standard_normal((64, 64)).astype(np.float32)
    exact = x.astype(np.float64) @ x.astype(np.float64)
    three = kernel_chain.contract(x, x, passes=3)
    assert 1e-7 < max_rel(three, exact) < 1e-4


def test_zamba2_control_fails_its_limit():
    """The int8 reference's greedy tokens, read against the f32
    reference, at the published depth of 54 layers and width 1024, with
    attention twice as wide as the hidden state as in the cells."""
    m = zamba2.dims(dict(n_layers=54, d_model=1024, n_heads=16,
                         n_kv_heads=16, d_head=128, vocab=8192, ssm_state=64,
                         ssm_headdim=64, ssm_expand=2, ssm_conv=4,
                         ssm_chunk=64, attn_every=6, rope_theta=10000.0,
                         norm_eps=1e-5))
    params = jax.jit(lambda k: zamba2.init(k, m))(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 96), 0, 8192)
    want = zamba2.logits(params, tokens, m, 32)
    pick = zamba2.logits(params, tokens, m, 32, int8=True).argmax(-1)
    gap = want.max(-1) - np.take_along_axis(
        np.asarray(want), np.asarray(pick)[..., None], -1)[..., 0]
    for name in ("zamba2.prefill", "zamba2.decode"):
        assert float(gap.max()) > run.load_cell(name)[0]["limits"]["logit_gap"]
