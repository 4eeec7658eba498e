"""The two pieces of across-channel LRN arithmetic that the composed unit
(``znicz_tpu/lrn.py``) and the fused conv-block kernel
(``znicz_tpu/pallas_fused_block.py``) share, so that both compute

    y = x * (k + alpha * sum_{j in win(c)} x_j^2) ** (-beta)

with one summation order and one ``s ** -beta``.
"""

from __future__ import annotations


def windowed_channel_sum(t, n):
    """Sum over the n-channel window centered on the LAST axis (zero-padded
    ends), unrolled with static shifts — identical summation order to the
    jnp oracle in znicz_tpu/lrn.py.  Rank-general; the ONE home of the
    shift-unrolled window sum: the fused conv-block kernel's parity
    guarantees depend on this exact order."""
    import jax.numpy as jnp

    half = n // 2
    acc = None
    for j in range(n):
        o = j - half                    # offset: acc_c += t_{c+o}
        if o == 0:
            part = t
        elif o > 0:
            part = jnp.concatenate(
                [t[..., o:], jnp.zeros(t.shape[:-1] + (o,), t.dtype)],
                axis=-1)
        else:
            part = jnp.concatenate(
                [jnp.zeros(t.shape[:-1] + (-o,), t.dtype), t[..., :o]],
                axis=-1)
        acc = part if acc is None else acc + part
    return acc


def inv_pow_rsqrt(s, beta: float):
    """``s ** -beta`` via ``rsqrt(s)*sqrt(rsqrt(s))`` for the reference
    default beta=0.75 (two pipelined VPU ops instead of the exp/log
    ``pow`` expansion); plain ``pow`` otherwise.  Shared by lrn.py's jnp
    path and the fused conv-block kernel."""
    import jax
    import jax.numpy as jnp

    if beta == 0.75:
        r = jax.lax.rsqrt(s)
        return r * jnp.sqrt(r)
    return jnp.power(s, -beta)
