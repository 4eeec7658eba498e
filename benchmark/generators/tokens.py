"""Generator ``tokens``: rows of token ids made on the device, in one
jitted call, from the seed.  A traffic mix names its generator; the driver
finds ``benchmark/generators/<name>.py`` and calls ``make``.

Every row is one document of ``row_tokens + 1`` ids drawn independently
Zipf(``s``) over the vocabulary slice (id ``k`` with probability
proportional to ``(k + 1)^-s``): a few hot ids and a long tail, as
natural text has, so that an expert layer's routing is uneven — hot ids
always meet the same experts.  The inputs are the row without its last
id, the targets the row shifted by one.  The same seed gives the same
rows.
"""

from __future__ import annotations


def make(seed: int, rows: int, row_tokens: int, vocab: int, s: float = 1.1):
    """``(data, targets)``, each ``(rows, row_tokens)`` int32 in ``[0,
    vocab)``, on the device."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        weight = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -s
        cdf = jnp.cumsum(weight) / jnp.sum(weight)
        u = jax.random.uniform(key, (rows, row_tokens + 1), jnp.float32)
        ids = jnp.clip(jnp.searchsorted(cdf, u), 0, vocab - 1).astype(
            jnp.int32)
        return ids[:, :-1], ids[:, 1:]

    return jax.jit(draw)(jax.random.key(seed, impl="rbg"))
