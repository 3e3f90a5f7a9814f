"""Both drivers, driven through the harness at tiny sizes on the CPU: a
sound run is correct and reports its metrics; the same seed gives the
same inputs; the command itself refuses to run off a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import run

CELLS = ["chain.solo", "zamba2.prefill", "zamba2.decode"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(run_tiny, name):
    result, lines = run_tiny(name)
    assert result["correct"], result["checked"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in run.metrics_of(name, trace=False)}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checked"
    assert lines[-1].startswith("check ")
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", ["chain.solo", "zamba2.prefill"])
def test_traced_run_reads_host_metrics(run_tiny, name):
    result, _ = run_tiny(name, trace=True)
    assert result["correct"]
    assert "busy_s" in result["device"] and "breakdown" in result
    want = {m["name"] for m in run.metrics_of(name, trace=True)}
    # off the chip the trace holds no device plane: only host metrics
    assert set(result["metrics"]) <= want
    assert ("window_compiles" in result["metrics"]) == (
        "window_compiles" in want)


def test_same_seed_same_inputs(tiny_cell):
    import jax
    from bench.drivers import engine
    cell, config = tiny_cell("zamba2.prefill")
    got = []
    for _ in range(2):
        d = engine.Driver(cell, config, 2**33 + 5, jax.devices()[0])
        d.setup()
        got.append([np.asarray(p) for p in d.pool])
    assert all((a == b).all() for a, b in zip(*got))


def test_command_refuses_the_cpu():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chain.solo",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr
