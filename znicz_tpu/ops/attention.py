"""Attention ops, single-device and sequence-parallel (ring attention).

The reference has no attention anywhere (SURVEY.md §5 "long-context:
absent") — this is a beyond-reference, TPU-first capability so the
framework handles long sequences at the scale the task demands:

  - ``attention(q, k, v, causal)`` — standard scaled-dot-product MHA core
    that materialises the ``(batch, heads, q, k)`` scores: serving's
    prefill chunks and decode, where they are small.  XLA does NOT keep a
    softmax chain on the chip by itself: composed of XLA operations, even
    the blocked core below wrote every block of scores to HBM and read it
    back several times (13.6 % of its roofline; ledger, PR 27);
  - ``blocked_attention(q, k, v, window)`` — the decoder's training core:
    causal or windowed, grouped-query, block by block with a running
    softmax and its own backward pass.  On a TPU, where the shapes tile,
    a block pair runs in the Pallas kernels of ``attention_pallas.py``
    (the block of scores stays in VMEM); elsewhere composed of XLA
    operations — ``core_tiles`` decides, nothing is set;
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


# -- the decoder's attention core -------------------------------------------------


def rope_tables(length: int, rotary_dim: int, theta: float, yarn=None):
    """``(cos, sin)``, each ``(length, rotary_dim)`` float32, of rotary
    positions ``0 .. length - 1`` in the rotate-half pairing (dimension
    ``i`` pairs with ``i + rotary_dim / 2``; both take frequency ``i``).

    ``yarn`` (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``, ``attention_factor``) blends each
    frequency between the trained one (dimensions that turn more than
    ``beta_fast`` times over the original length) and the same divided by
    ``factor`` (fewer than ``beta_slow`` turns), linearly in between, and
    multiplies cos and sin by ``attention_factor`` (Peng et al., YaRN,
    2023, section 3.2-3.4).  Host numpy: the tables are constants of the
    traced program."""
    import numpy as np

    half = rotary_dim // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / rotary_dim)
    scale = 1.0
    if yarn:
        span = float(yarn["original_max_position_embeddings"])

        def turns_dim(turns):       # the dimension that makes ``turns``
            return (rotary_dim * math.log(span / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(turns_dim(yarn["beta_fast"])), 0)
        high = min(math.ceil(turns_dim(yarn["beta_slow"])), rotary_dim - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
        inv = inv / yarn["factor"] * ramp + inv * (1 - ramp)
        scale = float(yarn["attention_factor"])
    angle = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    return ((np.cos(angle) * scale).astype(np.float32),
            (np.sin(angle) * scale).astype(np.float32))


def apply_rope(x, cos, sin):
    """Rotate the first ``cos.shape[-1]`` dimensions of ``x`` ``(batch,
    seq, heads, dim)``; the rest pass through.  Arithmetic in float32, the
    result in ``x``'s dtype."""
    import jax.numpy as jnp

    r = cos.shape[-1]
    half = r // 2
    a = x[..., :half].astype(jnp.float32)
    b = x[..., half:r].astype(jnp.float32)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    # the halves are rotated apart and joined once, in ``x``'s dtype: a
    # float32 ``[-b, a]`` joined first is a tensor of its own on the chip
    # wherever the consumer cannot take it into its fusion (PERF.md
    # section 6, PR 28)
    parts = [(a * cos[..., :half] - b * sin[..., :half]).astype(x.dtype),
             (b * cos[..., half:] + a * sin[..., half:]).astype(x.dtype)]
    if r < x.shape[-1]:
        parts.append(x[..., r:])
    return jnp.concatenate(parts, axis=-1)


def _key_blocks(i, block: int, window):
    """Key blocks ``[lo, hi)`` that hold a key query block ``i`` admits."""
    import jax.numpy as jnp

    if window is None:
        return 0, i + 1
    return jnp.maximum((i * block - (window - 1)) // block, 0), i + 1


def _dead(i, j, block: int, window):
    """``(query, key)`` pairs of blocks ``(i, j)`` the mask excludes."""
    import jax.numpy as jnp

    qpos = i * block + jnp.arange(block)[:, None]
    kpos = j * block + jnp.arange(block)[None, :]
    dead = kpos > qpos
    if window is not None:
        dead |= qpos - kpos >= window
    return dead


def _blocks(x, block: int):
    """``(batch, seq, kv, ..., dim)`` -> ``(seq blocks, batch, kv, ...,
    block, dim)``."""
    b, t = x.shape[:2]
    x = x.reshape((b, t // block, block) + x.shape[2:])
    nd = x.ndim
    return x.transpose((1, 0) + tuple(range(3, nd - 1)) + (2, nd - 1))


def _unblocks(x):
    """Inverse of ``_blocks``."""
    nd = x.ndim
    x = x.transpose((1, 0, nd - 2) + tuple(range(2, nd - 2)) + (nd - 1,))
    return x.reshape((x.shape[0], x.shape[1] * x.shape[2]) + x.shape[3:])


def _add_at(acc, j, part):
    """``acc[j] += part`` for a traced ``j``, as a slice update."""
    from jax import lax

    return lax.dynamic_update_index_in_dim(
        acc, lax.dynamic_index_in_dim(acc, j, 0, keepdims=False) + part,
        j, 0)


def _blocked_forward(q, k, v, window, block):
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t, heads, d = q.shape
    kv = k.shape[2]
    qb = _blocks(q.reshape(b, t, kv, heads // kv, d), block)
    kb, vb = _blocks(k, block), _blocks(v, block)
    scale = 1.0 / math.sqrt(d)
    neg = jnp.finfo(jnp.float32).min

    def query_block(args):
        i, qi = args                            # qi: (b, kv, g, block, d)

        def key_block(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_index_in_dim(kb, j, 0, keepdims=False)
            vj = lax.dynamic_index_in_dim(vb, j, 0, keepdims=False)
            s = jnp.einsum("bkgqd,bkjd->bkgqj", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            dead = _dead(i, j, block, window)
            s = jnp.where(dead, neg, s)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(dead, 0.0, jnp.exp(s - m_new[..., None]))
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqj,bkjd->bkgqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        shape = qi.shape[:-1]
        lo, hi = _key_blocks(i, block, window)
        m, l, acc = lax.fori_loop(lo, hi, key_block, (
            jnp.full(shape, neg, jnp.float32), jnp.zeros(shape, jnp.float32),
            jnp.zeros(shape + (d,), jnp.float32)))
        # every query admits itself, so l > 0
        return (acc / l[..., None]).astype(q.dtype), m + jnp.log(l)

    out, lse = lax.map(query_block, (jnp.arange(t // block), qb))
    return _unblocks(out).reshape(b, t, heads, d), lse


def _blocked_backward(window, block, res, dout):
    import jax.numpy as jnp
    from jax import lax

    q, k, v, out, lse = res
    b, t, heads, d = q.shape
    kv = k.shape[2]
    grouped = (b, t, kv, heads // kv, d)
    qb = _blocks(q.reshape(grouped), block)
    dob = _blocks(dout.reshape(grouped), block)
    kb, vb = _blocks(k, block), _blocks(v, block)
    delta = jnp.sum(dob.astype(jnp.float32)
                    * _blocks(out.reshape(grouped), block), axis=-1)
    scale = 1.0 / math.sqrt(d)

    def query_block(carry, args):
        i, qi, doi, lsei, di = args

        def key_block(j, carry):
            dqi, dk, dv = carry
            kj = lax.dynamic_index_in_dim(kb, j, 0, keepdims=False)
            vj = lax.dynamic_index_in_dim(vb, j, 0, keepdims=False)
            s = jnp.einsum("bkgqd,bkjd->bkgqj", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            p = jnp.where(_dead(i, j, block, window), 0.0,
                          jnp.exp(s - lsei[..., None]))
            dp = jnp.einsum("bkgqd,bkjd->bkgqj", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - di[..., None]) * scale).astype(q.dtype)
            dqi = dqi + jnp.einsum("bkgqj,bkjd->bkgqd", ds, kj,
                                   preferred_element_type=jnp.float32)
            dkj = jnp.einsum("bkgqj,bkgqd->bkjd", ds, qi,
                             preferred_element_type=jnp.float32)
            dvj = jnp.einsum("bkgqj,bkgqd->bkjd", p.astype(q.dtype), doi,
                             preferred_element_type=jnp.float32)
            return (dqi, _add_at(dk, j, dkj), _add_at(dv, j, dvj))

        lo, hi = _key_blocks(i, block, window)
        dqi, dk, dv = lax.fori_loop(
            lo, hi, key_block,
            (jnp.zeros(qi.shape, jnp.float32),) + carry)
        return (dk, dv), dqi.astype(q.dtype)

    zeros = jnp.zeros(kb.shape, jnp.float32)
    (dk, dv), dq = lax.scan(query_block, (zeros, zeros), (
        jnp.arange(t // block), qb, dob, lse, delta))
    return (_unblocks(dq).reshape(q.shape), _unblocks(dk).astype(k.dtype),
            _unblocks(dv).astype(v.dtype))


def core_tiles(backend: str, q_shape, kv_heads: int, dtype, window=None):
    """The one decision of which way ``blocked_attention`` runs a block
    pair, from what it observes: the kernel's ``(query tile, key tile)``
    where the backend is a TPU and the shapes tile (head size a multiple
    of the 128 lanes, rows a multiple of a tile, bfloat16 operands: the
    dtype the kernel was measured and compared in on the chip), else
    ``None``: the composed path."""
    import numpy as np

    _, seq, heads, dim = q_shape
    if (backend != "tpu" or dim % 128 or heads % kv_heads
            or np.dtype(dtype).name != "bfloat16"):
        return None
    from znicz_tpu.ops import attention_pallas

    return attention_pallas.pick_tiles(seq, window)


def kernel_counts() -> dict:
    """How often this process traced one of the core's kernels and
    lowered one to a Mosaic body (``attention_pallas.COUNTS``; zeros where
    no core ever ran in the kernels): kinds of core x 4 traces a process
    at most (forward, forward again under ``jax.checkpoint``, dq, dk/dv)
    however many layers, passes and programs there are, and kinds x 3
    lowerings a training program, kinds x 1 an evaluation, whatever the
    layers keep across rematerialisation.  A regression of set-up shows
    here as a number."""
    import sys

    kernels = sys.modules.get("znicz_tpu.ops.attention_pallas")
    counts = kernels.COUNTS if kernels else {"traces": 0, "lowerings": 0}
    return {"attn_kernel_traces": counts["traces"],
            "attn_kernel_lowerings": counts["lowerings"]}


#: the names (``jax.ad_checkpoint.checkpoint_name``) of what the core's
#: forward pass hands its backward pass beside q, k and v: the output and
#: the log-sum-exp.  A rematerialised unit that keeps them
#: (``jax.checkpoint_policies.save_only_these_names``; ``DecoderLayer.
#: remat_keeps``) recomputes q, k and v on the way back and not the core.
#: Outside ``jax.checkpoint`` a name is an identity.
CORE_KEEPS = ("attn_core_out", "attn_core_lse")


def _core(q, k, v, window, block: int, tiles):
    """The attention core behind one ``custom_vjp`` whose residuals are
    q, k, v, the output and one float32 log-sum-exp a query and head
    (the last two under the names ``CORE_KEEPS``, whichever way it runs):
    a block pair runs in the Pallas kernels with ``tiles``, composed of
    XLA operations in blocks of ``block`` without."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    if tiles is None:
        def forward(q, k, v):
            return _blocked_forward(q, k, v, window, block)

        def backward(res, dout):
            return _blocked_backward(window, block, res, dout)
    else:
        from znicz_tpu.backends import pallas_interpret
        from znicz_tpu.ops import attention_pallas

        static = dict(window=window, tiles=tiles,
                      interpret=pallas_interpret())

        def forward(q, k, v):
            return attention_pallas.forward(q, k, v, **static)

        def backward(res, dout):
            return attention_pallas.backward(*res, dout, **static)

    @jax.custom_vjp
    def core(q, k, v):
        return forward(q, k, v)[0]

    def fwd(q, k, v):
        out, lse = forward(q, k, v)
        out = checkpoint_name(out, CORE_KEEPS[0])
        lse = checkpoint_name(lse, CORE_KEEPS[1])
        return out, (q, k, v, out, lse)

    core.defvjp(fwd, backward)
    return core(q, k, v)


def blocked_attention(q, k, v, window=None, block: int = 512):
    """Causal grouped-query attention that never holds more than one
    block of scores: ``q`` is ``(batch, seq, heads, dim)``, ``k`` and
    ``v`` ``(batch, seq, kv_heads, dim)``; query head ``h`` reads KV head
    ``h // (heads // kv_heads)``.  Key ``j`` is admitted for query ``i``
    iff ``j <= i`` and, with ``window``, ``i - j < window``.

    One function for full and window layers: queries go block by block,
    each over the key blocks that hold an admitted key only (a window of
    512 reads what overlaps it, whatever the sequence), with the running
    max and sum ``ring_attention`` carries around its ring, here on one
    device; scores, mask and softmax in float32, both products in the
    operands' dtype with float32 accumulation, the normalisation by the
    exact sum.  The backward pass recomputes each block's probabilities
    from the saved log-sum-exp (Dao et al., FlashAttention, 2022,
    algorithm 4), so what a layer keeps is its output and one float a
    query and head.

    Two ways to run a block pair, chosen by ``core_tiles`` from the
    backend and the shapes, with nothing to set: on a TPU, with a head
    size that is a multiple of 128, rows a tile divides and bfloat16
    operands, the Pallas kernels of ``ops/attention_pallas.py``, where the
    block of scores stays in VMEM; everywhere else (the CPU backend, the
    ``tiny`` preset's head size 16, float32 operands) the same algorithm
    composed of XLA operations in blocks of ``block``, which falls back
    to the whole sequence where it does not divide it."""
    import jax

    if q.shape[1] % block:
        block = q.shape[1]
    tiles = core_tiles(jax.default_backend(), q.shape, k.shape[2], q.dtype,
                       window)
    return _core(q, k, v, window, block, tiles)
