"""Helpers shared by the harness and the drivers."""
from __future__ import annotations

import numpy as np


def sub_seed(seed: int, *path) -> int:
    """A 31-bit seed derived from the run's ``--seed`` (any whole number)
    and a path of names or indices, the same for the same arguments."""
    words = [seed % 2**32, seed // 2**32 % 2**32]
    for p in path:
        if isinstance(p, str):
            words += [ord(ch) for ch in p]
        else:
            words.append(int(p) % 2**32)
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


def block(tree) -> None:
    """Wait for every JAX array in ``tree``."""
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.block_until_ready()


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown
    length, drawn from ``seed``: the sample of finished requests that the
    correctness check compares."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.rng = np.random.default_rng(seed)
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self.rng.integers(self.seen))
        if j < self.size:
            self.items[j] = item


def max_rel(got, want) -> float:
    """Largest absolute difference over the largest magnitude of
    ``want``, in float64 on the host."""
    g = np.asarray(got, np.float64)
    w = np.asarray(want, np.float64)
    if g.shape != w.shape:
        raise ValueError(f"shape {g.shape} != {w.shape}")
    if not np.isfinite(g).all():
        return float("inf")
    return float(np.abs(g - w).max() / max(np.abs(w).max(), 1e-30))
