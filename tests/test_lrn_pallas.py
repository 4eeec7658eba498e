"""The LRN arithmetic of ops/lrn_pallas.py (the windowed channel sum and
``s ** -beta`` shared with znicz_tpu/lrn.py) inside the fused conv-block
kernel vs the jnp oracle: forward and gradient agreement (interpreter mode
on the CPU test platform)."""

import numpy as np


def _jnp_lrn(x, n=5, alpha=1e-4, beta=0.75, k=2.0):
    import jax.numpy as jnp

    half = n // 2
    sq = jnp.square(x)
    padded = jnp.pad(sq, [(0, 0)] * (x.ndim - 1) + [(half, half)])
    acc = jnp.zeros_like(x)
    for j in range(n):
        acc = acc + padded[..., j:j + x.shape[-1]]
    return x / jnp.power(k + alpha * acc, beta)


def test_fused_block_lrn_stage_matches_oracle():
    """The single-pass conv-block kernel (pallas_fused_block) degenerates
    to relu -> LRN under a 1x1/s1 identity pool — its LRN stage must match
    the shifted-slices oracle, forward AND gradient (the fused bwd's
    closed-form LRN term)."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_block

    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 6, 6, 96)).astype(np.float32) * 2)
    b = jnp.zeros((96,), jnp.float32)

    def oracle(t):
        return _jnp_lrn(jnp.maximum(t, 0.0))

    y = fused_block(x, b, 5, 1e-4, 0.75, 2.0, (1, 1, 1, 1))
    np.testing.assert_allclose(np.asarray(y), np.asarray(oracle(x)),
                               rtol=1e-5, atol=1e-6)

    cot = jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
    g = jax.grad(lambda t: jnp.sum(
        fused_block(t, b, 5, 1e-4, 0.75, 2.0, (1, 1, 1, 1)) * cot))(x)
    g_ref = jax.grad(lambda t: jnp.sum(oracle(t) * cot))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-6)
