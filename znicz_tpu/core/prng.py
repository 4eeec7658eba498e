"""Seeded PRNG service (rebuild of the reference's ``veles/prng/``).

The reference kept one globally-seeded xorshift stream consumed in
unit-creation order, plus device-side xorshift kernels for dropout /
stochastic pooling.  That design is hostile to SPMD reproducibility, so the
TPU rebuild replaces it (documented RNG divergence, SURVEY.md §7 hard part 2)
with:

  - named host streams: ``get(name)`` returns a ``RandomGenerator`` with a
    numpy Generator seeded by hash(global_seed, name) — used for weight init,
    loader shuffling, GA mutation.  Deterministic and order-independent.
  - device keys: ``RandomGenerator.jax_key(step)`` folds the stream's seed and
    a step counter into a ``jax.random`` threefry key — used inside jitted
    train steps for dropout / stochastic pooling masks.  Per-step folding
    keeps the train step pure (no RNG state threading through the loop).

Parity with the reference is *distributional* (same loss curves within
tolerance), not bitwise.
"""

from __future__ import annotations

import hashlib
from typing import Dict

import numpy as np


def _derive_seed(global_seed: int, name: str) -> int:
    digest = hashlib.sha256(f"{global_seed}:{name}".encode()).digest()
    return int.from_bytes(digest[:8], "little") & 0x7FFFFFFFFFFFFFFF


class RandomGenerator:
    """One named random stream: numpy host RNG + jax device-key derivation."""

    def __init__(self, name: str, seed: int) -> None:
        self.name = name
        self.seed = _derive_seed(seed, name)
        self.state = np.random.default_rng(self.seed)
        self._jax_base = None       # cached PRNGKey(seed), built lazily

    # -- host-side (numpy) ---------------------------------------------------

    def fill_uniform(self, arr: np.ndarray, low: float, high: float) -> None:
        arr[...] = self.state.uniform(low, high, size=arr.shape).astype(
            arr.dtype, copy=False)

    def fill_normal(self, arr: np.ndarray, stddev: float) -> None:
        arr[...] = self.state.normal(0.0, stddev, size=arr.shape).astype(
            arr.dtype, copy=False)

    def uniform(self, low: float, high: float, shape, dtype=np.float32):
        return self.state.uniform(low, high, size=shape).astype(dtype)

    def normal(self, stddev: float, shape, dtype=np.float32):
        return self.state.normal(0.0, stddev, size=shape).astype(dtype)

    def permutation(self, n: int) -> np.ndarray:
        return self.state.permutation(n)

    def randint(self, low: int, high: int) -> int:
        return int(self.state.integers(low, high))

    # -- device-side (jax) ---------------------------------------------------

    def jax_base_key(self):
        """The stream's base PRNGKey(seed), built once and cached — per-step
        keys are ``fold_in(base, step)``; consumers inside jit should take
        the base as an argument and fold_in IN-GRAPH (each eager
        PRNGKey+fold_in pair costs several host->device dispatches)."""
        if self._jax_base is None:
            import jax

            self._jax_base = jax.random.PRNGKey(self.seed)
        return self._jax_base

    def jax_key(self, step: int = 0):
        """A threefry key derived from (stream seed, step) — identical to
        ``fold_in(jax_base_key(), step)``.  Import of jax is deferred so
        pure-host users (loaders, GA) never touch the device."""
        import jax

        return jax.random.fold_in(self.jax_base_key(), step)

    def reseed(self, seed: int) -> None:
        self.seed = _derive_seed(seed, self.name)
        self.state = np.random.default_rng(self.seed)
        self._jax_base = None


_streams: Dict[str, RandomGenerator] = {}
_global_seed: int | None = None


def _seed() -> int:
    global _global_seed
    if _global_seed is None:
        from znicz_tpu.core.config import root

        _global_seed = int(root.common.engine.get("seed", 1013))
    return _global_seed


def get(name: str = "default") -> RandomGenerator:
    """Return (creating on first use) the named stream."""
    stream = _streams.get(name)
    if stream is None:
        stream = RandomGenerator(name, _seed())
        _streams[name] = stream
    return stream


def seed_all(seed: int) -> None:
    """Reset the global seed and reseed every existing stream (tests use this
    to make module-order irrelevant)."""
    global _global_seed
    _global_seed = int(seed)
    from znicz_tpu.core.config import root

    root.common.engine.seed = int(seed)
    for stream in _streams.values():
        stream.reseed(_global_seed)


def reset(seed: int) -> None:
    """Drop every named stream and reseed: the state is indistinguishable
    from a fresh process started with this global seed.  The public home of
    the ``_streams.clear(); seed_all(seed)`` idiom."""
    _streams.clear()
    seed_all(seed)
