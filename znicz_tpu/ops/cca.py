"""Attention in a compressed latent with convolutional mixing: what stands
between the down-projections and the attention core (``ops/attention.py``
``blocked_attention`` runs the core itself, as for every decoder).

The block projects the residual stream DOWN to a few heads (queries
``heads x dim``, keys ``kv_heads x dim``), mixes queries and keys along
the sequence with two short causal convolutions (one pair of taps a
channel, then one small matrix a tap and HEAD), adds the mean of a query
and its key before the mixing back in, shifts half the value heads by one
token, and normalises q and k to a fixed length with a learned
temperature on the keys (Zyphra, CCA, arXiv:2510.04476).  Every function
here is pure and causal: position ``t`` of a result reads positions
``<= t`` of its operands (``tests/test_zaya.py`` holds each to an explicit
loop).

Shapes: ``(batch, seq, heads, dim)`` throughout; the sequence is axis 1.
"""

from __future__ import annotations


def shift(x, n: int = 1):
    """``y[:, t] = x[:, t - n]``, zeros for ``t < n``."""
    import jax.numpy as jnp

    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (n, 0)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def mix_channels(u, w, b):
    """A causal convolution with one tap a channel and step:
    ``c[t] = sum_j w[j] * u[t - (taps - 1 - j)] + b``; ``w`` is ``(taps,
    heads, dim)``, the last tap the current position's."""
    taps = w.shape[0]
    out = b
    for j in range(taps):
        out = out + w[j] * shift(u, taps - 1 - j)
    return out


def mix_heads(c, a, b):
    """A causal convolution with one ``dim x dim`` matrix a tap and HEAD
    (one group a head): ``d[t, h] = sum_j c[t - (taps - 1 - j), h] @
    a[j, h] + b[h]``; ``a`` is ``(taps, heads, dim, dim)``."""
    import jax.numpy as jnp

    taps = a.shape[0]
    out = b
    for j in range(taps):
        out = out + jnp.einsum("bthc,hcd->bthd", shift(c, taps - 1 - j),
                               a[j])
    return out.astype(c.dtype)


def qk_mean(q, k):
    """``(m, n)``: for query head ``i`` of KV head ``g(i) = i // (heads /
    kv_heads)``, ``m_i = (q_i + k_g(i)) / 2``; for KV head ``j``, ``n_j``
    the mean of its group's ``m_i``."""
    b, t, heads, dim = q.shape
    kv = k.shape[2]
    grouped = q.reshape(b, t, kv, heads // kv, dim)
    m = (grouped + k[:, :, :, None, :]) * 0.5
    return m.reshape(q.shape), m.mean(axis=3).astype(k.dtype)


def shift_values(v):
    """The second half of the value heads holds the PREVIOUS token's
    value: ``v[:, t, kv/2:] <- v[:, t - 1, kv/2:]`` (zeros at ``t = 0``);
    the first half stays the current token's."""
    import jax.numpy as jnp

    half = v.shape[2] // 2
    return jnp.concatenate([v[:, :, :half], shift(v[:, :, half:])], axis=2)


def unit_length(x, log_scale=None):
    """``sqrt(dim) * x / |x|`` over the last axis, in float32, times
    ``exp(log_scale)`` (one number a head) where given; the result in
    ``x``'s dtype."""
    import jax
    import jax.numpy as jnp

    x32 = x.astype(jnp.float32)
    # sqrt(dim) / |x| == rsqrt(mean(x^2))
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
                          + 1e-12)
    if log_scale is not None:
        scale = scale * jnp.exp(log_scale.astype(jnp.float32))[:, None]
    return (x32 * scale).astype(x.dtype)


def mix(q0, k0, v, p, cos, sin):
    """``(q, k, v)`` for the core from the down-projections ``q0``
    ``(batch, seq, heads, dim)``, ``k0``, ``v`` ``(batch, seq, kv_heads,
    dim)``: both convolutions over ``[q0, k0]``, the query-key mean, the
    value shift, the normalisation (temperature ``p["temp"]`` on the
    keys), then the rotation ``(cos, sin)`` (``apply_rope``).  ``p`` holds
    ``mix_w``, ``mix_b``, ``mix_heads``, ``mix_heads_b``, ``temp``."""
    import jax.numpy as jnp

    from znicz_tpu.ops.attention import apply_rope

    heads = q0.shape[2]
    u = jnp.concatenate([q0, k0], axis=2)
    d = mix_heads(mix_channels(u, p["mix_w"], p["mix_b"]).astype(u.dtype),
                  p["mix_heads"], p["mix_heads_b"])
    m, n = qk_mean(q0, k0)
    q = unit_length(d[:, :, :heads] + m)
    k = unit_length(d[:, :, heads:] + n, p["temp"])
    return (apply_rope(q, cos, sin), apply_rope(k, cos, sin),
            shift_values(v))
