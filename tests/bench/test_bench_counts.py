"""The benchmark's yardstick on the CPU: kernel counts at tiny shapes
against hand arithmetic, the peaks table, and a roofline share and a
model-FLOP share computed from a synthetic trace."""
import types

import pytest

from bench import peaks, roofline
from bench.counts import expert_glu, flash_attention, ssd_scan
from bench.metrics import idle_share, mfu
from bench.reference import kernel_chain, zamba2
from bench.trace import Event, Reduction

V5E = "TPU v5 lite"


@pytest.mark.parametrize("Tq,Tk,causal,want", [
    (4, 4, True, 10),           # 1 + 2 + 3 + 4
    (4, 5, False, 20),
    (1, 8, True, 8),            # one decode query sees the whole cache
    (2, 8, True, 15),           # 7 + 8
])
def test_attention_pairs(Tq, Tk, causal, want):
    assert flash_attention.pairs(Tq, Tk, causal) == want


def test_flash_attention_count():
    flops, nbytes = flash_attention.count(B=1, Tq=4, Tk=4, H=2, D=8,
                                          dtype="float32")
    assert flops == {"f32_highest": 4 * 2 * 8 * 10}
    assert nbytes == 4 * (2 * 4 * 2 * 8 + 2 * 4 * 2 * 8)
    flops, nbytes = flash_attention.count(B=2, Tq=4, Tk=4, H=4, D=8, Hk=2,
                                          dtype="bfloat16", causal=False)
    assert flops == {"bf16": 4 * 2 * 4 * 8 * 16}
    assert nbytes == 2 * (2 * 2 * 4 * 4 * 8 + 2 * 2 * 4 * 2 * 8)


def test_ssd_scan_count():
    # two chunks of 4; per chunk 2(N+P) C(C+1)/2 + 4 C N P + 2 N P
    flops, nbytes = ssd_scan.count(B=1, T=8, H=1, N=2, P=3, chunk=4,
                                   dtype="float32")
    assert flops == {"f32_highest": 2 * (100 + 96 + 12)}
    assert nbytes == 4 * 8 * (4 + 6) + 4 * 8 + 4 * 6
    # bf16 inputs are still contracted in f32 at HIGHEST inside the kernel
    flops, nbytes = ssd_scan.count(B=1, T=8, H=1, N=2, P=3, chunk=4,
                                   dtype="bfloat16")
    assert list(flops) == ["f32_highest"]
    assert nbytes == 2 * 8 * (4 + 6) + 4 * 8 + 4 * 6


def test_expert_glu_count():
    flops, nbytes = expert_glu.count(E=2, rows=3, d=4, F=5, dtype="bfloat16")
    assert flops == {"bf16": 6 * 3 * 4 * 5}
    assert nbytes == 2 * (2 * 3 * 4 + 3 * 2 * 4 * 5)


def test_peaks_by_device_kind():
    p = peaks.for_kind(V5E)
    assert p["bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["f32_highest"] == pytest.approx(p["bf16"] / 6)     # six passes
    with pytest.raises(KeyError, match="no peaks"):
        peaks.for_kind("TPU v9 imaginary")


def test_least_time_takes_the_larger_bound():
    p = peaks.for_kind(V5E)
    compute = {"bf16": 197e12, "f32_highest": p["f32_highest"]}   # 2 s
    assert peaks.least_time(compute, 0.0, p) == pytest.approx(2.0)
    assert peaks.least_time(compute, 819e9 * 3, p) == pytest.approx(3.0)


def _trace(events, window=(0.0, 10e6)):
    ops = {"/device:TPU:0": [Event(n, s, e, f"%{n} = f32[8] {d}(f32[8] %p)")
                             for n, s, e, d in events]}
    return Reduction(window=window, ops=ops, modules=[], spans=[])


def test_roofline_share_from_synthetic_events():
    call = dict(B=1, Tq=128, Tk=128, H=2, D=64, dtype="bfloat16")
    driver = types.SimpleNamespace(
        kernel_calls=lambda: {"flash_attention": (flash_attention, call)})
    # two kernel events of 1 ms each, one other op that must not count
    red = _trace([("flash_attention.1", 0, 1e6, "custom-call"),
                  ("fusion.3", 1e6, 3e6, "fusion"),
                  ("flash_attention.1", 4e6, 5e6, "custom-call"),
                  ("copy.2", 5e6, 6e6, "copy")])
    p = peaks.for_kind(V5E)
    ctx = types.SimpleNamespace(trace=red, peaks=p, driver=driver)
    flops, nbytes = flash_attention.count(**call)
    least = max(flops["bf16"] / p["bf16"], nbytes / p["hbm_bytes_per_s"])
    assert roofline.share(ctx, "flash_attention") == pytest.approx(
        2 * least / 2e-3 * 100)
    # a kernel with no events, or no trace, reads nothing
    assert roofline.share(ctx, "ssd_scan") is None
    red2 = _trace([("fusion.3", 1e6, 3e6, "fusion")])
    assert roofline.share(types.SimpleNamespace(
        trace=red2, peaks=p, driver=driver), "flash_attention") is None


def test_mfu_and_idle_share():
    p = peaks.for_kind(V5E)
    driver = types.SimpleNamespace(
        flops_per_request=lambda: {"bf16": 197e12 * 0.01})   # 10 ms at peak
    ctx = types.SimpleNamespace(driver=driver, peaks=p, completed=40,
                                window_s=2.0,
                                trace=_trace([("a", 0, 2e6, ""),
                                              ("b", 1e6, 5e6, "")]))
    assert mfu.read(ctx) == pytest.approx(20.0)              # 0.4 s of 2 s
    assert idle_share.read(ctx) == pytest.approx(50.0)       # 5 ms of 10 ms


def test_chain_flops_per_request_is_the_sum_of_its_counts():
    w = dict(batch=1, seq=64, heads=2, head_dim=16, state=8, experts=4,
             moe_ff=16, top_k=2, chunk=32, block_m=16)
    want = (sum(flash_attention.count(B=1, Tq=64, Tk=64, H=2, D=16,
                                      dtype="float32")[0].values())
            + sum(ssd_scan.count(B=1, T=64, H=2, N=8, P=16, chunk=32,
                                 dtype="float32")[0].values())
            + 6 * 128 * 32 * 16            # 64 tokens x top 2 over d 32
            + 2 * 64 * 32 * 4)             # the router
    assert kernel_chain.capacity(w) == 32
    assert kernel_chain.flops_per_request(w, 2) == {"f32_highest": 2 * want}


def test_zamba2_flops_per_request_by_hand():
    m = zamba2.dims(dict(n_layers=2, d_model=4, n_heads=2, n_kv_heads=2,
                         d_head=2, vocab=10, ssm_state=2, ssm_headdim=4,
                         ssm_expand=2, ssm_conv=4, ssm_chunk=2, attn_every=2))
    # per layer: in_proj 4 x (16 + 4 + 2), out_proj 8 x 4; attention 4 x 6 x 2
    # + 4 x 4; two prompt tokens, no decode step that counts
    got = zamba2.flops_per_request(m, prompt=2, new=1)
    per_token = 2 * (2 * 120 + 64)
    assert got["bf16"] == 2 * per_token + 2 * 4 * 10 + 4 * 2 * 2 * 3
    ssd, _ = ssd_scan.count(B=1, T=2, H=2, N=2, P=4, chunk=2, dtype="bf16")
    assert got["f32_highest"] == 2 * ssd["f32_highest"]
    # one more new token adds one decode step at context 3
    got2 = zamba2.flops_per_request(m, prompt=2, new=2)
    assert got2["bf16"] - got["bf16"] == (
        per_token + 2 * 4 * 10 + 4 * 2 * 2 * 3 + 2 * 4 * 2 * 2 * 4)
