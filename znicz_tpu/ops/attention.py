"""Attention ops, single-device and sequence-parallel (ring attention).

The reference has no attention anywhere (SURVEY.md §5 "long-context:
absent") — this is a beyond-reference, TPU-first capability so the
framework handles long sequences at the scale the task demands:

  - ``attention(q, k, v, causal)`` — standard scaled-dot-product MHA core,
    one fused jit (XLA flash-fuses the softmax chain on TPU);
  - ``cache_append(cache, row, t)`` / ``decode_attention(q1, k, v, t)`` —
    the KV-cache decode step (ISSUE 16): append this step's key/value row
    at per-row position ``t``, then attend a length-1 query over the
    prefix ``[0..t]`` of a preallocated cache, the unwritten tail masked
    by ``k_valid``.  O(cache) per emitted token instead of O(seq^2) for a
    re-prefill;
  - ``paged_gather`` / ``paged_append`` / ``paged_decode_attention`` —
    the BLOCK-PAGED pool forms of the above (ISSUE 19): one
    ``(num_pages + 1, page_size, heads, dim)`` pool per layer holds every
    request's cache as page-table-indexed blocks (the last page is pad
    scratch), so requests share read-only prefix pages by table entry
    instead of by copy.  Positions stay GLOBAL (``t`` -> page
    ``t // page_size``, offset ``t % page_size``), which keeps the
    contiguous path's masking — and its bit-exactness contract — intact;
  - ``ring_attention(q, k, v, axis_name, causal)`` — blockwise attention
    for SEQUENCE-PARALLEL inputs: every device of the mesh axis holds a
    sequence shard of q/k/v; k/v blocks rotate around the ring via
    ``lax.ppermute`` (ICI neighbor hops, bandwidth-optimal) while a running
    flash-style online softmax (max/denominator carried per query) keeps
    memory at one block — exact attention over sequences n_devices x
    longer than a chip could hold.  Call inside ``shard_map`` over the
    sequence axis.

Shapes: (batch, seq, heads, head_dim) throughout.
"""

from __future__ import annotations

import math


def attention(q, k, v, causal: bool = False, q_offset=0, k_offset=0,
              k_valid=None):
    """Exact attention; offsets give global positions for causal masking of
    sharded blocks.

    ``k_valid`` is an optional (batch, k) bool mask of which keys exist —
    the variable-length serving plane's padding mask (ISSUE 15): padded
    key positions carry exactly zero probability mass, making each row's
    output a pure function of its OWN unpadded length.

    ``q_offset``/``k_offset`` may be scalars (a sharded block's global
    start) or per-row (batch,) arrays (ISSUE 19's chunked prefill: each
    co-batched row's chunk sits at its own depth).  The scalar path's
    mask is unchanged bit for bit — the row axis merely broadcasts.

    A query row whose keys are ALL masked (the empty-cache decode edge)
    returns zeros rather than NaN: masked scores get a finite fill (not
    ``-inf``, whose ``exp(-inf - -inf)`` poisons the softmax), masked
    probabilities are zeroed explicitly, and the denominator is clamped.
    Rows with at least one valid key are bit-identical to the unguarded
    softmax — the row max is unchanged and the clamped denominator is
    already >= 1."""
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    dead = None                                        # (b, h, q, k) bcast
    if causal:
        qpos = jnp.asarray(q_offset)[..., None] + jnp.arange(q.shape[1])
        kpos = jnp.asarray(k_offset)[..., None] + jnp.arange(k.shape[1])
        if qpos.ndim == 1:                             # scalar offset
            qpos = qpos[None]
        if kpos.ndim == 1:
            kpos = kpos[None]
        dead = kpos[:, None, None, :] > qpos[:, None, :, None]
    if k_valid is not None:
        miss = ~k_valid[:, None, None, :]
        dead = miss if dead is None else (dead | miss)
    if dead is not None:
        s = jnp.where(dead, jnp.finfo(s.dtype).min, s)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    if dead is not None:
        p = jnp.where(dead, 0.0, p)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    if dead is not None:
        denom = jnp.maximum(denom, jnp.finfo(p.dtype).tiny)
    p = p / denom
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def cache_append(cache, row, t):
    """Scatter one step's (batch, heads, dim) row into a preallocated
    (batch, cache_len, heads, dim) cache at per-row position ``t``
    ((batch,) int32).  Pure — returns the updated cache."""
    import jax.numpy as jnp

    b = cache.shape[0]
    return cache.at[jnp.arange(b), t].set(row)


def decode_attention(q1, k_cache, v_cache, t):
    """One autoregressive decode step: a length-1 query at per-row global
    position ``t`` attends over cache positions ``[0..t]``; the unwritten
    tail ``(t, cache_len)`` is excluded via ``k_valid``.  ``q1`` is
    (batch, 1, heads, dim), caches (batch, cache_len, heads, dim), ``t``
    (batch,) int32.  Callers append this step's k/v row first (so position
    ``t`` is valid and every row keeps >= 1 valid key).  Equivalent to the
    causal mask at row ``t`` of a full forward, without the O(seq^2)
    score matrix."""
    import jax.numpy as jnp

    cache_len = k_cache.shape[1]
    k_valid = jnp.arange(cache_len)[None, :] <= t[:, None]
    return attention(q1, k_cache, v_cache, k_valid=k_valid)


def paged_gather(pool, table):
    """Gather a per-request contiguous K/V view out of a block-paged
    pool (ISSUE 19).  ``pool`` is (num_pages + 1, page_size, heads, dim)
    — the LAST page is pad scratch — and ``table`` is (batch, P) int32
    page ids listing each row's pages in position order (slots past a
    row's allocation point at scratch).  Returns
    (batch, P * page_size, heads, dim): position ``t`` of row ``i``
    lives at page ``table[i, t // page_size]`` offset ``t % page_size``,
    so downstream masking keeps using GLOBAL positions unchanged."""
    b, npages = table.shape
    page_size = pool.shape[1]
    return pool[table].reshape(b, npages * page_size,
                               pool.shape[2], pool.shape[3])


def paged_append(pool, table, row, t):
    """Scatter one step's (batch, heads, dim) row into the paged pool at
    per-row GLOBAL position ``t``: page ``table[i, t // page_size]``,
    offset ``t % page_size``.  Pure — returns the updated pool.  Rows
    whose table entry is the scratch page (pad rows) scatter there and
    never touch a real page."""
    import jax.numpy as jnp

    b, npages = table.shape
    page_size = pool.shape[1]
    page = table[jnp.arange(b), jnp.clip(t // page_size, 0, npages - 1)]
    return pool.at[page, t % page_size].set(row)


def paged_decode_attention(q1, k_pool, v_pool, table, t):
    """:func:`decode_attention` over the block-paged pool: gather each
    row's pages into its contiguous view, then run the SAME masked
    softmax over ``[0..t]`` — the unwritten/stale page tail past ``t``
    (including scratch table slots) is excluded by ``k_valid`` exactly
    as the contiguous path excludes its unwritten tail, so paging
    preserves the per-decoded-token bit-exactness contract."""
    return decode_attention(q1, paged_gather(k_pool, table),
                            paged_gather(v_pool, table), t)


def ring_attention(q, k, v, axis_name: str, causal: bool = False):
    """Exact attention over a sequence sharded on ``axis_name``.

    Each step attends the local q block to the current k/v block, folds the
    result into flash-style accumulators, then passes the k/v block to the
    next device on the ring.  After n steps every q saw every k/v.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    neg = jnp.finfo(jnp.float32.dtype).min

    qpos = my * t + jnp.arange(t)                      # global q positions

    def step(i, carry):
        m, l, acc, k_blk, v_blk = carry
        src = (my - i) % n                             # who produced k_blk
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
        kmask = None
        if causal:
            kpos = src * t + jnp.arange(t)
            kmask = (kpos[None, None, None, :]
                     > qpos[None, None, :, None])
            s = jnp.where(kmask, neg, s)
        blk_max = jnp.max(s, axis=-1)                  # (b, h, q)
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(s - m_new[..., None])
        if kmask is not None:
            # fully-masked blocks leave m_new at neg; exp(neg-neg)=1 would
            # leak mass — zero masked entries explicitly
            p = jnp.where(kmask, 0.0, p)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk))
        perm = [(j, (j + 1) % n) for j in range(n)]    # ring hop
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return m_new, l_new, acc_new, k_next, v_next

    def vary(x):
        """Mark a fresh array as varying over every mesh axis q varies
        over — the ring axis, and the batch axis too on a training mesh
        (shard_map tracks varying-axis types; loop carries must match)."""
        return lax.pcast(x, tuple(jax.typeof(q).vma), to="varying")

    m0 = vary(jnp.full((b, h, t), neg, jnp.float32))
    l0 = vary(jnp.zeros((b, h, t), jnp.float32))
    acc0 = vary(jnp.zeros((b, h, t, d), jnp.float32))
    m, l, acc, _, _ = lax.fori_loop(0, n, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)[..., None]       # (b, h, q, d)
    return out.transpose(0, 2, 1, 3)                   # (b, q, h, d)
