"""Engine.generate compilation reuse, for the decode step and the prefill.

Regression for the re-jitting bug: ``generate`` used to build
``jax.jit(lambda ...)`` *inside* the method, so every call owned a fresh
jit cache and re-traced + re-compiled the decode step.  The step is now
cached on the engine; the traced-call counter (incremented only when jax
actually traces) proves two same-shape ``generate`` calls share one
compilation.  The prompt's forward pass, once run eagerly (its layer scan
traced again on every call), is jitted the same way.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving.engine import Engine, _greedy
from repro.sharding import Policy


def _fresh(name="llama3.2-1b", **overrides):
    cfg = dataclasses.replace(get_config(name).reduced(), **overrides)
    return Engine(cfg=cfg, params=M.init_params(cfg, jax.random.PRNGKey(0)),
                  policy=Policy())


@pytest.fixture(scope="module")
def engine():
    return _fresh()


def _prompts(engine, batch=2, seq=8, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, engine.cfg.vocab, (batch, seq),
                                    dtype=np.int32))


def test_two_generates_reuse_one_decode_compilation(engine):
    toks = _prompts(engine)
    out1 = engine.generate(toks, max_new=3)
    assert sum(engine.decode_trace_counts.values()) == 1
    out2 = engine.generate(toks, max_new=3)
    # same shapes -> still exactly one trace, and greedy decode is
    # deterministic, so the outputs must agree
    assert sum(engine.decode_trace_counts.values()) == 1
    assert len(engine.decode_trace_counts) == 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape == (2, 3)


def test_new_shapes_trace_once_each(engine):
    engine.generate(_prompts(engine), max_new=3)
    base = sum(engine.decode_trace_counts.values())
    # a different max_len changes the cache shapes -> exactly one new
    # trace, reused by the repeat call
    engine.generate(_prompts(engine), max_new=3, max_len=24)
    assert sum(engine.decode_trace_counts.values()) == base + 1
    engine.generate(_prompts(engine), max_new=3, max_len=24)
    assert sum(engine.decode_trace_counts.values()) == base + 1


def test_two_generates_reuse_one_prefill_compilation():
    eng = _fresh()
    toks = _prompts(eng)
    out1 = eng.generate(toks, max_new=3)
    assert eng.prefill_trace_counts == {((2, 8), 11): 1}
    out2 = eng.generate(toks, max_new=3)
    assert eng.prefill_trace_counts == {((2, 8), 11): 1}
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_new_max_len_traces_prefill_once_more():
    eng = _fresh()
    eng.generate(_prompts(eng), max_new=3)
    # max_len is static: a new value is one new trace, reused by the repeat
    eng.generate(_prompts(eng), max_new=3, max_len=24)
    eng.generate(_prompts(eng, seed=1), max_new=3, max_len=24)
    assert eng.prefill_trace_counts == {((2, 8), 11): 1, ((2, 8), 24): 1}


@pytest.mark.parametrize("name,overrides", [
    ("llama3.2-1b", {}),
    ("zamba2-2.7b", {"use_kernels": True}),
], ids=["dense", "zamba2"])
def test_jitted_prefill_tokens_match_eager_prefill(name, overrides):
    eng = _fresh(name, **overrides)
    toks = _prompts(eng, seq=16)
    new = 4
    served = eng.generate(toks, max_new=new)
    assert sum(eng.prefill_trace_counts.values()) == 1

    # the eager forward pass, then the engine's own decode step
    logits, cache = M.prefill(eng.cfg, eng.params, {"tokens": toks},
                              max_len=16 + new, shd=eng.policy)
    step = eng.decode_step_fn()
    tok, outs = _greedy(logits), []
    for _ in range(new):
        outs.append(tok)
        logits, cache = step(eng.params, cache, {"tokens": tok})
        tok = _greedy(logits)
    np.testing.assert_array_equal(np.asarray(served),
                                  np.asarray(jnp.concatenate(outs, axis=1)))


@pytest.mark.parametrize("name", [
    "llama3.2-1b", "zamba2-2.7b", "nemotron-3-nano-30b-a3b",
    "granite-moe-1b-a400m", "deepseek-v3-671b", "xlstm-125m",
], ids=["dense", "zamba2", "nemotron_h", "moe", "mla_moe", "xlstm"])
def test_decode_step_donates_its_cache(name):
    """The engine's step writes the new cache into the old one's buffers,
    so the cache it is given is deleted; ``generate`` still serves the
    tokens of a loop whose step donates nothing."""
    eng = _fresh(name)
    toks = _prompts(eng, seq=8)
    new = 4
    logits, cache = M.prefill(eng.cfg, eng.params, {"tokens": toks},
                              max_len=8 + new, shd=eng.policy)
    kept = jax.tree.map(jnp.copy, cache)
    eng.decode_step_fn()(eng.params, cache, {"tokens": _greedy(logits)})
    assert all(a.is_deleted() for a in jax.tree.leaves(cache))

    served = eng.generate(toks, max_new=new)
    step = jax.jit(lambda p, c, b: M.decode_step(eng.cfg, p, c, b,
                                                 eng.policy))
    tok, outs, cache = _greedy(logits), [], kept
    for _ in range(new):
        outs.append(tok)
        logits, cache = step(eng.params, cache, {"tokens": tok})
        tok = _greedy(logits)
    assert not any(a.is_deleted() for a in jax.tree.leaves(cache))
    np.testing.assert_array_equal(np.asarray(served),
                                  np.asarray(jnp.concatenate(outs, axis=1)))
