"""``nemotron3.decode`` at tiny sizes on the CPU: a sound run is correct and
the int8 control is not; the traced run's readers return numbers; the
expert kernel's counts by hand; the roofline reader over the kernel's two
call shapes."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import peaks, run, trace
from bench.counts import expert_mlp
from bench.metrics import expert_mlp_ms, expert_mlp_roofline
from bench.reference import nemotron_h
from bench.trace import Event, Reduction

CELL = "nemotron3.decode"
V5E = "TPU v5 lite"
# the configuration file's published keys at a CPU test's size: every
# mixer kind, 8 routed experts of which 4 are held from the third on
TINY = dict(hidden_size=64, num_hidden_layers=6, vocab_size=256,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            router_experts=8, n_routed_experts=4, expert_offset=2,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=64, mamba_num_heads=8,
            mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=16)


def _run(seed=2**31 + 7, trace_on=False, **kw):
    cell, config = run.load_cell(CELL)
    cell = dict(cell, prompt=32, new=4, clients=2, check_rows=2)
    return run.run_cell(CELL, seed, 0.5, trace_on, jax.devices()[0],
                        cell=cell, config=dict(config, **TINY), **kw)


def test_sound_run_is_correct():
    result, lines = _run()
    assert result["correct"], result["checked"]
    assert set(result["metrics"]) == {
        m["name"] for m in run.metrics_of(CELL, trace=False)}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    # the window's MoE counters, a round, beside uniform routing's
    moe = [ln for ln in lines if ln.startswith("moe: ")]
    assert len(moe) == 1 and "prefill rows" in moe[0] and "decode rows" in moe[0]


def test_int8_control_fails_the_limit():
    """The int8 reference's greedy tokens, read against the f32 reference,
    at the cell's depth and pattern (27 layers) and width 512, with 8 held
    of 64 experts: their mean logit gap over 4 x 32 positions is past the
    cell's limit."""
    config = dict(run.load_cell(CELL)[1], hidden_size=512,
                  num_attention_heads=8, head_dim=64, vocab_size=4096,
                  router_experts=64, n_routed_experts=8,
                  moe_intermediate_size=256,
                  moe_shared_expert_intermediate_size=512, mamba_num_heads=16,
                  mamba_head_dim=32, ssm_state_size=32)
    m = nemotron_h.dims(config)
    params = jax.jit(lambda k: nemotron_h.init(k, m))(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 64), 0, 4096)
    want = nemotron_h.logits(params, tokens, m, 32)
    pick = nemotron_h.logits(params, tokens, m, 32, int8=True).argmax(-1)
    gap = want.max(-1) - np.take_along_axis(
        np.asarray(want), np.asarray(pick)[..., None], -1)[..., 0]
    limit = run.load_cell(CELL)[0]["limits"]["logit_gap_mean"]
    assert float(gap.mean()) > limit


def test_traced_run_reads_its_metrics(monkeypatch):
    """Off the chip the trace holds no device plane: a synthetic chip that
    runs one expert kernel call of 1 ms per window stands in, and the
    roofline is read from the run's own counters at v5e's peaks."""
    reduce = trace.reduce

    def with_a_chip(path, span_names=()):
        red = reduce(path, span_names)
        lo, hi = red.window
        red.ops = {"/device:TPU:0": [
            Event("op", lo, (lo + hi) / 2, "op"),
            Event("expert_mlp.3", lo, lo + 1e6,
                  "%expert_mlp.3 = bf16[64,64] custom-call(bf16[64,64] %p)"),
            Event("expert_mlp.4", lo + 1e6, lo + 1.5e6,
                  "%expert_mlp.4 = bf16[16,64] custom-call(bf16[16,64] %p)")]}
        return red

    seen = []

    class Context(run.Context):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen.append(self)

    monkeypatch.setattr(trace, "reduce", with_a_chip)
    monkeypatch.setattr(run, "Context", Context)
    result, _ = _run(trace_on=True)
    assert result["correct"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("expert_mlp_ms", "idle_share", "prefill_gap_ms",
                 "decode_gap_ms", "other_gap_ms", "prefill_compiles",
                 "window_compiles"):
        assert got[name] is not None and got[name] >= 0, name
    # a round: 2 MoE layers, in the prefill (1 ms a call, the larger
    # shape) and in each of 4 decode steps (0.5 ms)
    assert got["expert_mlp_ms"] == pytest.approx(2 * 1.0 + 8 * 0.5)
    ctx = seen[0]
    assert ctx.counters["moe.rows/prefill"] > 0
    assert ctx.counters["moe.rows/decode"] > 0
    ctx.peaks = peaks.for_kind(V5E)
    assert expert_mlp_roofline.read(ctx) > 0


def test_expert_mlp_count_by_hand():
    # 10 rows over d 4 and F 3: two contractions of 4 x 3 each, 2 flops a
    # multiply-add; 2 experts' (3, 4) weights twice, rows in and out, bf16
    flops, nbytes = expert_mlp.count(rows=10, experts=2, d=4, F=3,
                                     dtype="bfloat16")
    assert flops == {"bf16": 10 * 2 * 2 * 4 * 3}
    assert nbytes == 2 * (2 * 10 * 4 + 2 * 2 * 3 * 4)
    flops, nbytes = expert_mlp.count(rows=1, experts=1, d=4, F=3,
                                     dtype="float32")
    assert flops == {"f32_highest": 48.0} and nbytes == 4 * (8 + 24)


def test_roofline_sums_the_two_call_shapes():
    """A prefill call bound by its operations and 8 decode calls bound by
    their weights, told apart by their output rows: each phase's calls at
    their own least time, over the kernel's time, and not one shape's
    least time times all calls.  Where the profiler dropped events, the
    share is of the calls traced and the time a round is each phase's
    mean call times its calls a round."""
    p = peaks.for_kind(V5E)
    pre = dict(rows=6144.0, experts=16.0, d=2688, F=1856, dtype="bfloat16")
    dec = dict(rows=24.0, experts=13.0, d=2688, F=1856, dtype="bfloat16")
    driver = types.SimpleNamespace(
        expert_mlp_calls=lambda counters, rounds: [(1, pre), (8, dec)])

    def call(name, start, end, rows):
        return Event(name, start, end,
                     f"%{name} = bf16[{rows},2688] custom-call(...)")

    decode = [call("expert_mlp.2", 3e6 + i * 1e6, 3.5e6 + i * 1e6, 432)
              for i in range(8)]
    events = [call("expert_mlp.1", 0, 2e6, 51200),
              Event("fusion.2", 2e6, 3e6, "fusion")] + decode
    red = Reduction(window=(0.0, 20e6), ops={"/device:TPU:0": events},
                    modules=[], spans=[])
    ctx = types.SimpleNamespace(trace=red, peaks=p, driver=driver,
                                counters={}, rounds=1)

    def least(call):
        flops, nbytes = expert_mlp.count(**call)
        return max(flops["bf16"] / p["bf16"], nbytes / p["hbm_bytes_per_s"])

    assert least(pre) > expert_mlp.count(**pre)[1] / p["hbm_bytes_per_s"]
    assert expert_mlp_roofline.read(ctx) == pytest.approx(
        (least(pre) + 8 * least(dec)) / (2e-3 + 8 * 0.5e-3) * 100)
    assert expert_mlp_ms.read(ctx) == pytest.approx(6.0)
    # half the decode calls dropped from the trace
    red.ops["/device:TPU:0"] = events[:2] + decode[::2]
    assert expert_mlp_roofline.read(ctx) == pytest.approx(
        (least(pre) + 4 * least(dec)) / (2e-3 + 4 * 0.5e-3) * 100)
    assert expert_mlp_ms.read(ctx) == pytest.approx(6.0)
    # one shape only, no kernel events, or no trace: nothing to read
    red.ops["/device:TPU:0"] = decode
    assert expert_mlp_roofline.read(ctx) is None
    ctx.trace = Reduction(window=(0.0, 1e6), ops={}, modules=[], spans=[])
    assert expert_mlp_roofline.read(ctx) is None
    ctx.trace = None
    assert expert_mlp_ms.read(ctx) is None


def test_flops_per_request_by_hand():
    m = nemotron_h.dims(dict(
        TINY, hidden_size=4, num_hidden_layers=3,
        hybrid_override_pattern="ME*", vocab_size=10, num_attention_heads=2,
        num_key_value_heads=1, head_dim=2, router_experts=4,
        n_routed_experts=2, num_experts_per_tok=2, moe_intermediate_size=3,
        moe_shared_expert_intermediate_size=5, mamba_num_heads=2,
        mamba_head_dim=2, ssm_state_size=2, n_groups=1, chunk_size=2,
        conv_kernel=4, routed_scaling_factor=2.5, expert_offset=0,
        norm_eps=1e-5, layer_norm_epsilon=1e-5))
    # Mamba2: in_proj 4 x (8 + 4 + 2), out_proj 4 x 4; attention 4 x 4 x 2
    # + 4 x 4; MoE: router 4 x 4, shared 2 x 4 x 5, 1 expected row of 2 x 4 x 3
    per_token = 2 * ((4 * 14 + 16) + (32 + 16) + (16 + 40 + 24))
    got = nemotron_h.flops_per_request(m, prompt=2, new=1)
    assert got["bf16"] == 2 * per_token + 2 * 4 * 10 + 4 * 2 * 2 * 3
    got2 = nemotron_h.flops_per_request(m, prompt=2, new=2)
    assert got2["bf16"] - got["bf16"] == (
        per_token + 2 * 4 * 10 + 4 * 2 * 2 * 3 + 4 * 2 * 2 * 2)
