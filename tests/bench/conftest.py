"""Tiny sizes at which the benchmark's cells run on the CPU."""
import jax
import pytest

from bench import run

CHAIN = dict(batch=1, seq=64, heads=2, head_dim=16, state=8, experts=4,
             moe_ff=16, top_k=2, chunk=32, block_q=32, block_k=32,
             block_m=16, block_f=16)
ZAMBA2 = dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, d_head=16,
              vocab=256, ssm_state=16, ssm_headdim=16, ssm_expand=2,
              ssm_conv=4, ssm_chunk=16, attn_every=2, rope_theta=10000.0,
              norm_eps=1e-5)


def tiny(name: str) -> tuple[dict, dict]:
    """Cell ``name``'s workload and configuration at a CPU test's size."""
    cell, config = run.load_cell(name)
    if config["driver"] == "orchestrator":
        return cell, dict(config, widths=CHAIN)
    return (dict(cell, prompt=32, new=4, clients=2, check_rows=2),
            dict(config, model=ZAMBA2))


@pytest.fixture
def tiny_cell():
    return tiny


@pytest.fixture
def run_tiny():
    """``run_tiny(name, seed, **kw)``: one run of cell ``name`` at its tiny
    size on the CPU, past the harness's look for a chip."""
    def go(name, seed=2**31 + 7, seconds=0.5, trace=False, **kw):
        cell, config = tiny(name)
        return run.run_cell(name, seed, seconds, trace, jax.devices()[0],
                            cell=cell, config=config, **kw)
    return go
