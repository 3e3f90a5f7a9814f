"""A run whose timed path is broken underneath comes out not correct:
each fault a cell can have, planted in what the window's rounds return,
with the rest of the run (set-up, window, check) as the benchmark runs
it, at tiny sizes on the CPU."""
import jax.numpy as jnp
import numpy as np
import pytest


class Broken:
    """The driver, with ``fault`` applied to every round's result."""

    def __init__(self, driver, fault):
        self._driver, self._fault = driver, fault

    def __getattr__(self, name):
        return getattr(self._driver, name)

    def round(self, k, span):
        return self._fault(self._driver, *self._driver.round(k, span))


def altered_answer(driver, i, out):
    """One element of the SSD op's output changed by 1."""
    out[2] = jnp.asarray(out[2]).at[0, 0, 0, 0].add(1.0)
    return i, out


def skipped_op(driver, i, out):
    """The sort returns its input unchanged."""
    out[3] = out[2]
    return i, out


def altered_token(driver, i, out):
    """The last served token of the first request changed to the next
    token id."""
    return i, out.at[0, -1].set((out[0, -1] + 1) % driver.m["vocab"])


@pytest.mark.parametrize("name,fault", [
    ("chain.solo", altered_answer),
    ("chain.solo", skipped_op),
    ("zamba2.prefill", altered_token),
    ("zamba2.decode", altered_token),
])
def test_fault_comes_out_not_correct(run_tiny, name, fault):
    result, lines = run_tiny(name, window=lambda d: Broken(d, fault))
    assert result["correct"] is False, result["checked"]
    assert lines[-1].startswith("check ")


def test_unbroken_wrapper_stays_correct(run_tiny):
    result, _ = run_tiny("chain.solo",
                         window=lambda d: Broken(d, lambda d, *r: r))
    assert result["correct"] is True
