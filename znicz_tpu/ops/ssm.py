"""A state-space mixer's ops (Mamba-2): the causal depthwise convolution
in front of the scan, the scan itself in CHUNKS with its backward, and the
gated grouped norm behind it.

The recurrence, one head ``h`` of size ``P`` reading group ``g(h) = h //
(heads / groups)`` of ``B`` and ``C`` (state size ``N``)::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T      (P x N, zero before
    y_t = S_t C_t + D_h x_t                            the row)

``chunked_scan`` never walks the positions.  With ``a_t = dt_t A_h`` and
``cum`` its running sum inside a chunk of ``Q`` positions, position ``i``
of a chunk reads the chunk's own positions ``j <= i`` through ``(C_i .
B_j) exp(cum_i - cum_j)`` — one masked ``Q x Q`` product a chunk and
head, on the MXU — and everything before the chunk through the state at
the chunk's entry, ``exp(cum_i) C_i . S_entry``; the entry states follow
from the chunks' own sums by a recurrence over CHUNKS (``seq / Q``
steps of elementwise work).  ``dt``, the decays and the states are
float32; the products take their operands in ``x``'s dtype and
accumulate in float32.

The backward pass is written out (``jax.custom_vjp``), chunk by chunk like
the forward: it keeps the inputs and the chunks' entry states and nothing
of size ``Q x Q``; decays, masked products and what the state recurrence
needs are made again from them (two exponentials and one ``C B^T``
product a chunk, against keeping 2 x 0.5 GB of float32 a layer at 2 x
8,192 tokens, 64 heads and ``Q`` 128).

``STATS`` notes on the host, while a scan is traced, which way it ran
(composed of XLA operations here; a kernel would count under
``kernel``) and in how many chunks a row.
"""

from __future__ import annotations

import functools

#: noted while ``chunked_scan`` is traced (no device work): how the last
#: traced scan ran and its chunks a row
STATS = {"way": None, "chunks": 0}


def causal_conv(x, w, b):
    """Depthwise causal convolution along the sequence: ``y_t = sum_k w[k]
    x_{t - (K - 1) + k} + b`` with zeros before the row — the LAST tap is
    the current position's.  ``x`` ``(batch, seq, channels)``, ``w``
    ``(K, channels)``, ``b`` ``(channels,)``."""
    import jax.numpy as jnp

    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = b.astype(x.dtype)
    for k in range(taps):
        y = y + padded[:, k:k + t] * w[k].astype(x.dtype)
    return y


def gated_norm(y, z, gain, groups: int, eps: float):
    """``RMSNorm over groups of channels (y * silu(z)) * gain``: ``y`` and
    ``z`` ``(..., channels)``, the mean square taken over each of
    ``groups`` equal runs of channels, in float32; the result in ``y``'s
    dtype."""
    import jax
    import jax.numpy as jnp

    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    split = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    split = split * jax.lax.rsqrt(
        jnp.mean(jnp.square(split), axis=-1, keepdims=True) + eps)
    return (split.reshape(g.shape) * gain.astype(jnp.float32)).astype(
        y.dtype)


def _split(x, dt, b, c, chunk: int):
    """The operands by chunk and by group: ``x`` ``(B, n, Q, G, R, P)``,
    ``dt`` ``(B, n, Q, G, R)``, ``b`` / ``c`` ``(B, n, Q, G, N)``."""
    bsz, t, heads, p = x.shape
    groups, n = b.shape[2], t // chunk
    r = heads // groups
    return (x.reshape(bsz, n, chunk, groups, r, p),
            dt.reshape(bsz, n, chunk, groups, r),
            b.reshape(bsz, n, chunk, groups, b.shape[3]),
            c.reshape(bsz, n, chunk, groups, c.shape[3]))


def _decays(dt, a):
    """``(cum, last)``: the running sum of ``dt A`` inside each chunk
    ``(B, n, Q, G, R)`` and its value at the chunk's end ``(B, n, G,
    R)``, float32."""
    import jax.numpy as jnp

    cum = jnp.cumsum(dt * a, axis=2)
    return cum, cum[:, :, -1]


def _within(cum):
    """``L[i, j] = exp(cum_i - cum_j)`` for ``j <= i``, else 0: ``(B, n,
    G, R, Q, Q)`` float32."""
    import jax.numpy as jnp

    q = cum.shape[2]
    c = jnp.moveaxis(cum, 2, -1)                        # (B, n, G, R, Q)
    seg = c[..., :, None] - c[..., None, :]
    return jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg,
                             -jnp.inf))


def _entry_states(sums, last):
    """The state at each chunk's ENTRY from the chunks' own sums ``(B, n,
    G, R, P, N)`` and decays over a whole chunk ``exp(last)``: ``H_0 = 0,
    H_{c+1} = exp(last_c) H_c + sums_c``.  Float32."""
    import jax
    import jax.numpy as jnp

    def step(h, xs):
        s, decay = xs
        return decay[..., None, None] * h + s, h

    _, entry = jax.lax.scan(step, jnp.zeros_like(sums[:, 0]), (
        jnp.moveaxis(sums, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)))
    return jnp.moveaxis(entry, 0, 1)


def _forward(x, dt, a_log, b, c, d, chunk: int):
    """``(y, entry states)``; see the module's text."""
    import jax.numpy as jnp

    f32, dtype = jnp.float32, x.dtype
    xs, dts, bs, cs = _split(x, dt.astype(f32), b, c, chunk)
    groups, r = xs.shape[3], xs.shape[4]
    a = -jnp.exp(a_log.astype(f32)).reshape(groups, r)
    cum, last = _decays(dts, a)
    xdt32 = xs.astype(f32) * dts[..., None]
    xdt = xdt32.astype(dtype)
    scores = jnp.einsum("bzign,bzjgn->bzgij", cs, bs,
                        preferred_element_type=f32)
    masked = (scores[:, :, :, None] * _within(cum)).astype(dtype)
    y = jnp.einsum("bzgrij,bzjgrp->bzigrp", masked, xdt,
                   preferred_element_type=f32)
    to_end = jnp.exp(last[:, :, None] - cum)            # (B, n, Q, G, R)
    sums = jnp.einsum("bzjgrp,bzjgn->bzgrpn",
                      (xdt32 * to_end[..., None]).astype(dtype), bs,
                      preferred_element_type=f32)
    entry = _entry_states(sums, last)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "bzign,bzgrpn->bzigrp", cs, entry.astype(dtype),
        preferred_element_type=f32)
    y = y + d.astype(f32).reshape(groups, r)[..., None] * xs.astype(f32)
    return y.reshape(x.shape).astype(dtype), entry


def _backward(chunk: int, kept, dy):
    """The cotangents of ``(x, dt, a_log, b, c, d)`` from ``dy``: every
    term of the forward pass transposed chunk by chunk, decays and masked
    products made again from the inputs."""
    import jax
    import jax.numpy as jnp

    x, dt, a_log, b, c, d, entry = kept
    f32, dtype = jnp.float32, x.dtype
    xs, dts, bs, cs = _split(x, dt.astype(f32), b, c, chunk)
    dys = dy.reshape(xs.shape)
    groups, r = xs.shape[3], xs.shape[4]
    a = -jnp.exp(a_log.astype(f32)).reshape(groups, r)
    cum, last = _decays(dts, a)
    x32, dy32 = xs.astype(f32), dys.astype(f32)
    xdt32 = x32 * dts[..., None]
    xdt = xdt32.astype(dtype)

    def product(spec, left, right):
        return jnp.einsum(spec, left.astype(dtype), right.astype(dtype),
                          preferred_element_type=f32)

    # y = ... + D x
    d_d = jnp.sum(dy32 * x32, axis=(0, 1, 2, 5)).reshape(d.shape)
    dx = d.astype(f32).reshape(groups, r)[..., None] * dy32
    # y_off = exp(cum_i) C_i . H: towards C, the entry states and cum
    grown = jnp.exp(cum)[..., None] * dy32              # (B, n, Q, G, R, P)
    dc = product("bzigrp,bzgrpn->bzign", grown, entry)
    d_entry = product("bzigrp,bzign->bzgrpn", grown, cs)
    dcum = jnp.sum(grown * product("bzign,bzgrpn->bzigrp", cs, entry),
                   axis=-1)
    # H_{c+1} = exp(last_c) H_c + sums_c, from the last chunk back
    def step(carry, xs_):
        local, decay = xs_
        return local + decay[..., None, None] * carry, carry

    decay = jnp.exp(last)
    _, d_sums = jax.lax.scan(step, jnp.zeros_like(d_entry[:, 0]), (
        jnp.moveaxis(d_entry, 1, 0), jnp.moveaxis(decay, 1, 0)),
        reverse=True)
    d_sums = jnp.moveaxis(d_sums, 0, 1)                 # dH_{c+1} = dsums_c
    dlast = decay * jnp.sum(d_sums * entry, axis=(-1, -2))
    # sums_c = sum_j exp(last - cum_j) xdt_j B_j^T
    to_end = jnp.exp(last[:, :, None] - cum)
    back = product("bzgrpn,bzjgn->bzjgrp", d_sums, bs)
    dxdt = to_end[..., None] * back
    db = product("bzjgrp,bzgrpn->bzjgn", xdt32 * to_end[..., None], d_sums)
    dw = to_end * jnp.sum(back * xdt32, axis=-1)        # d(to_end) to_end
    dcum = dcum - dw
    dlast = dlast + jnp.sum(dw, axis=2)
    # y_diag = (C B^T . L) xdt
    within = _within(cum)
    scores = jnp.einsum("bzign,bzjgn->bzgij", cs, bs,
                        preferred_element_type=f32)
    masked = scores[:, :, :, None] * within             # (B, n, G, R, Q, Q)
    dmasked = product("bzigrp,bzjgrp->bzgrij", dys, xdt)
    dxdt = dxdt + product("bzgrij,bzigrp->bzjgrp", masked, dys)
    dscores = jnp.sum(dmasked * within, axis=3)
    dc = dc + product("bzgij,bzjgn->bzign", dscores, bs)
    db = db + product("bzgij,bzign->bzjgn", dscores, cs)
    through = dmasked * masked                          # d(log L) L
    dcum = dcum + jnp.moveaxis(
        jnp.sum(through, axis=-1) - jnp.sum(through, axis=-2), -1, 2)
    # cum is a running sum of dt A; ``last`` is its final entry
    dcum = dcum.at[:, :, -1].add(dlast)
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2)
    ddt = da * a + jnp.sum(dxdt * x32, axis=-1)
    d_a = jnp.sum(da * dts, axis=(0, 1, 2))             # dA, (G, R)
    dx = dx + dxdt * dts[..., None]
    return (dx.reshape(x.shape).astype(dtype),
            ddt.reshape(dt.shape).astype(dt.dtype),
            (d_a * a).reshape(a_log.shape).astype(a_log.dtype),
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype), d_d.astype(d.dtype))


@functools.lru_cache(maxsize=None)
def _scan():
    """The scan with its written-out backward pass (built once: jax is
    imported where it is used)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
    def scan(x, dt, a_log, b, c, d, chunk):
        return _forward(x, dt, a_log, b, c, d, chunk)[0]

    def fwd(x, dt, a_log, b, c, d, chunk):
        y, entry = _forward(x, dt, a_log, b, c, d, chunk)
        return y, (x, dt, a_log, b, c, d, entry)

    scan.defvjp(fwd, _backward)
    return scan


def chunked_scan(x, dt, a_log, b, c, d, chunk: int):
    """``y`` ``(batch, seq, heads, P)`` of the recurrence in the module's
    text, in chunks of ``chunk`` positions.  ``x`` ``(batch, seq, heads,
    P)``; ``dt`` ``(batch, seq, heads)`` float32, already positive;
    ``a_log`` and ``d`` ``(heads,)`` (``A = -exp(a_log)``); ``b``, ``c``
    ``(batch, seq, groups, N)``, head ``h`` reading group ``h // (heads
    / groups)``.  A row that the chunk does not divide is padded with
    positions of ``dt = 0``, which neither decay nor feed the state.
    Differentiable in all six."""
    import jax.numpy as jnp

    t = x.shape[1]
    if x.shape[2] % b.shape[2]:
        raise ValueError(f"{x.shape[2]} heads over {b.shape[2]} groups")
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (
            v.ndim - 2)) for v in (x, dt, b, c))
    STATS.update(way="composed", chunks=(t + pad) // chunk)
    return _scan()(x, dt, a_log, b, c, d, int(chunk))[:, :t]
