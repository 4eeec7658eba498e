"""Pallas TPU kernels for the decoder's attention core
(``ops/attention.py`` ``blocked_attention``): the score block of a (query
tile, key tile) pair lives in VMEM from the first product to the second
and never crosses HBM (Dao et al., FlashAttention, 2022, algorithms 1 and
4; /opt/skills/guides/boom_attention_tricks.md sections 3-7).

One kernel family for full and window layers, forward and backward, as
the chip measured each best (a layer at the cell's shapes, 2 rows of
8,192, bf16; PERF.md section 6 has every variant tried):

  - ``forward``: queries tile by tile, each over the key tiles that hold
    an admitted key, running max and sum in float32, the output divided
    by the exact sum; keeps one float32 log-sum-exp a query and head.
    The scores of a pair are held TRANSPOSED, keys on sublanes and queries
    on lanes: max and sum over the keys are then elementwise across
    vector registers instead of lane reductions, and what a query carries
    is a lane-dense row: 24.7 -> 16.2 ms a full layer (PR 28);
  - ``dk``/``dv`` walk the pairs key tile by key tile in the same
    orientation (log-sum-exp and ``delta`` are the forward pass's
    ``(group, queries)`` rows, one sublane a head) and sum over a group's
    heads in values, one store a pair;
  - ``dq`` walks the forward pass's pairs with the scores as written,
    queries on sublanes, so that ``ds @ k`` lands as ``dq`` is stored and
    nothing is transposed when a query tile is done; it takes log-sum-exp
    and ``delta`` down columns.  Transposed like the others it was 0.8 %
    slower in a full layer's backward pass and 7.5 % in a window layer's,
    whose query tiles finish after three pairs (37.9 against 37.5 ms,
    15.1 against 14.0; PR 29).
    Both recompute a tile's probabilities from the log-sum-exp.

Which pairs run is a host-side schedule (``_schedule``: numpy, from the
tiles and the window) that the kernels read from SMEM as scalar
prefetch, so a causal row stops at its diagonal and a window reads what
overlaps it without one empty grid step; the element mask is made once a
pair, and only in pairs the mask cuts (one body for both cost a full
layer 2 % forward and 0.9 % backward on the chip, PR 29: two bodies).

Layout: q, k and the output stay where the projections want them.
``(batch, seq, heads, dim)`` is viewed as ``(batch, seq, heads * dim)``
and one grid step takes the ``group * dim`` lanes of ONE KV head's query
heads — grouped-query attention reads a key tile once for its whole group
(6 or 8 heads) and no query-head tensor is transposed.  The forward
pass's values, whose product lands transposed, are handed over as
``(batch, kv_heads, dim, seq)``: a transpose of the 8 KV heads only.

Cheap to set up (PR 29; PR 28's kernels were refused for 17 s of it,
every layer, pass and program tracing and lowering 12-16 Python-unrolled
copies of a pair's arithmetic anew):

  - each kernel is a module-level jitted function, static in what it is
    specialised on, so jax's trace cache hands every layer of a kind and
    every later program the first trace (the forward pass is traced twice
    a kind: ``jax.checkpoint``'s trace context is another cache key), and
    one lowered program holds one Mosaic body a kind and kernel, inlined
    at every layer's call (each call keeps its own scope: ``op_name`` is
    the call site's).  ``COUNTS`` says how often either happened in this
    process.  Since a decoder layer keeps its core's output and
    log-sum-exp across rematerialisation (PR 33;
    ``ops.attention.CORE_KEEPS``) the recomputed forward pass calls no
    kernel: a training program holds layers x 3 calls where it held
    layers x 4, and the counts read what they read before — the
    recomputed call had shared the first pass's trace and body.  One
    thing moved in the lowered TEXT: under a policy ``jax.checkpoint``'s
    partial evaluation gives each layer's jitted ``forward`` a jaxpr of
    its own, so the text holds one ``forward`` function a layer (each
    inlining the one cached body) where it held two a kind; ``dq`` and
    ``dkv`` stay one a kind;
  - the heads of a group go through ONE traced body, a ``lax.fori_loop``
    over lane-aligned dynamic slices (``pl.ds(h * dim, dim)``: a head is
    whole lane tiles), unrolled when the kernel is lowered.  Left rolled
    the loop is cheaper still to lower (0.16 against 0.33 s a training
    program, host) and lost 11 % of a full layer's kernel time and 50-70 %
    of a window layer's on the chip: the heads no longer overlap (PR 29).

Precision, as ``blocked_attention`` states it: scores, mask and softmax
in float32; both products in the operands' dtype with float32
accumulation; the scale applied to the float32 scores; exact division.
Bit-equal to PR 28's kernels on the chip, forward and all three
gradients, both kinds of layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Primitive
from jax.interpreters import mlir

#: masked scores: finite, so that ``exp(s - max)`` of a row whose keys so
#: far are all masked is 1, not NaN; the first admitted key's correction
#: ``exp(MASKED - max)`` is exactly 0 and wipes what such a row summed
MASKED = -0.7 * float(np.finfo(np.float32).max)
VMEM_LIMIT = 64 * 1024 * 1024

#: how often this process traced a kernel's entry and lowered one to a
#: Mosaic body (``FusedTrainer.stats`` ``attn_kernel_traces`` /
#: ``attn_kernel_lowerings``): kinds of core x 3 kernels however many
#: layers, passes and programs there are (the forward one once more a
#: kind, under ``jax.checkpoint``), and kinds x 3 lowerings a training
#: program, kinds x 1 an evaluation — whatever the layers keep across
#: rematerialisation
COUNTS = {"traces": 0, "lowerings": 0}

# An identity whose only work is done while it is lowered: it counts.  jax
# lowers an equation once a module for each distinct (primitive,
# parameters, operand types), a ``pallas_call`` (whose parameters are its
# trace's) like this one (whose parameter is its trace's number): this
# runs once where the kernel beside it becomes one Mosaic body.
_lowered_p = Primitive("attn_kernel_lowered")
_lowered_p.def_abstract_eval(lambda x, trace: x)


def _count_lowering(ctx, x, trace):
    COUNTS["lowerings"] += 1
    return [x]


mlir.register_lowering(_lowered_p, _count_lowering)


def pick_tiles(seq: int, window=None):
    """``(query tile, key tile)`` for rows of ``seq``, or ``None`` where
    no tile divides them.  Full layers take the largest tile (the fewest
    steps; only the diagonal pairs are cut by the mask).  A window layer
    visits about ``window + query tile + key tile`` keys a query tile
    where it needs ``window``: tiles of half the window visit 1.5 times
    the admitted pairs where tiles of the window visit twice.  Smaller
    tiles visit fewer still and lost on the chip: at a window of 512,
    tiles of 128 (1.25 times) took 24.8 ms forward and backward, 256
    19.0, 512 20.7; tiles of 1,024 lost in full layers (PERF.md section
    6, PR 28)."""
    limit = 512 if window is None or window >= seq else max(window // 2, 128)
    for tile in (512, 256, 128):
        if tile <= limit and seq % tile == 0:
            return tile, tile
    return None


def _schedule(seq: int, bq: int, bk: int, window, by_key: bool):
    """The (query tile, key tile) pairs that hold an admitted pair, as
    three int32 arrays ``(query tile, key tile, edge)``: in query-tile
    order (``by_key`` false: a query tile's pairs are consecutive) or
    key-tile order; ``edge`` has bit 0 on the first pair of a run and bit
    1 on its last."""
    pairs = []
    for i in range(seq // bq):
        first, last = i * bq, i * bq + bq - 1           # queries of tile i
        lo = 0 if window is None else max(first - (window - 1), 0) // bk
        pairs += [(i, j) for j in range(lo, last // bk + 1)]
    if by_key:
        pairs.sort(key=lambda ij: (ij[1], ij[0]))
    run = [ij[1 if by_key else 0] for ij in pairs]
    edge = [(a != b) + 2 * (a != c) for a, b, c in zip(
        run, [None] + run[:-1], run[1:] + [None])]
    qi, kj = zip(*pairs)
    return tuple(np.asarray(x, np.int32) for x in (qi, kj, edge))


_NT = (((1,), (1,)), ((), ()))      # a @ b.T


class _Pair:
    """What the three kernels share of one grid step: its tile pair from
    the schedule, the element mask where the mask cuts the pair, a head's
    scores (keys first, or queries first for ``dq``) and the loop over the
    group's heads."""

    def __init__(self, qi_ref, kj_ref, edge_ref, *, group, dim, bq, bk,
                 window, keys_first=True):
        n = pl.program_id(2)
        self.keys_first = keys_first    # scores as (keys, queries)
        self.i, self.j, edge = qi_ref[n], kj_ref[n], edge_ref[n]
        self.first, self.last = edge & 1 != 0, edge & 2 != 0
        self.group, self.dim, self.bq, self.bk = group, dim, bq, bk
        self.window, self.scale = window, 1.0 / math.sqrt(dim)

    def lanes(self, h):
        """Head ``h`` of the group in a ``group * dim``-lane block."""
        return pl.ds(pl.multiple_of(h * self.dim, self.dim), self.dim)

    def either(self, pair) -> None:
        """``pair(dead)`` once: with the bools of the excluded pairs,
        laid out as the scores are, where the mask cuts this pair of
        tiles; with ``None`` where it admits them all."""
        i, j, bq, bk = self.i, self.j, self.bq, self.bk

        def dead():
            shape = (bk, bq) if self.keys_first else (bq, bk)
            rel = (i * bq - j * bk) + (
                lax.broadcasted_iota(jnp.int32, shape, self.keys_first)
                - lax.broadcasted_iota(jnp.int32, shape,
                                       not self.keys_first))
            out = rel < 0                               # query before key
            if self.window is not None:
                out = jnp.logical_or(out, rel >= self.window)
            return out

        cut = j * bk + (bk - 1) > i * bq                # above the diagonal
        if self.window is not None:
            cut = jnp.logical_or(cut, i * bq + (bq - 1) - j * bk
                                 >= self.window)
        pl.when(cut)(lambda: pair(dead()))
        pl.when(jnp.logical_not(cut))(lambda: pair(None))

    def scores(self, q, k, dead):
        """Float32 scores, scaled, ``MASKED`` where ``dead``: ``(keys,
        queries)``, or ``(queries, keys)`` without ``keys_first``."""
        a, b = (k, q) if self.keys_first else (q, k)
        s = lax.dot_general(a, b, _NT,
                            preferred_element_type=jnp.float32) * self.scale
        return s if dead is None else jnp.where(dead, MASKED, s)

    def every_head(self, head, carry=None):
        """``carry = head(h, carry)`` over the group's heads: ONE traced
        body, a head's slices lane-aligned and dynamic, unrolled when the
        kernel is lowered (rolled, the heads cannot overlap: 11 % slower in
        full layers and 50-70 % in window layers on the chip, PR 29)."""
        return lax.fori_loop(0, self.group, head, carry, unroll=True)


def _forward_kernel(qi_ref, kj_ref, edge_ref, q_ref, k_ref, vt_ref, o_ref,
                    lse_ref, m_scr, l_scr, acc_scr, **static):
    pair = _Pair(qi_ref, kj_ref, edge_ref, **static)

    @pl.when(pair.first)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, MASKED, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def tile_pair(dead):
        k, vt = k_ref[0], vt_ref[0, 0]

        def head(h, _):
            s = pair.scores(q_ref[0, :, pair.lanes(h)], k, dead)
            m_prev = m_scr[h]                           # (1, bq)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * corr + jnp.sum(p, axis=0, keepdims=True)
            m_scr[h] = m_new
            acc_scr[h] = acc_scr[h] * corr + jnp.dot(
                vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)

        pair.every_head(head)

    pair.either(tile_pair)

    def write(h, _):
        l = l_scr[h]
        o_ref[0, :, pair.lanes(h)] = (acc_scr[h] / l).T.astype(o_ref.dtype)
        lse_ref[0, 0, pl.ds(h, 1), :] = m_scr[h] + jnp.log(l)

    pl.when(pair.last)(lambda: pair.every_head(write))


def _dq_kernel(qi_ref, kj_ref, edge_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_scr, **static):
    pair = _Pair(qi_ref, kj_ref, edge_ref, keys_first=False, **static)

    @pl.when(pair.first)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def tile_pair(dead):
        k, v = k_ref[0], v_ref[0]

        def head(h, _):
            q, do = q_ref[0, :, pair.lanes(h)], do_ref[0, :, pair.lanes(h)]
            p = jnp.exp(pair.scores(q, k, dead)
                        - lse_ref[0, 0, :, pl.ds(h, 1)])
            dp = lax.dot_general(do, v, _NT,
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, 0, :, pl.ds(h, 1)])
                  * pair.scale).astype(k.dtype)
            dq_scr[h] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

        pair.every_head(head)

    pair.either(tile_pair)

    def write(h, _):
        dq_ref[0, :, pair.lanes(h)] = dq_scr[h].astype(dq_ref.dtype)

    pl.when(pair.last)(lambda: pair.every_head(write))


def _dkv_kernel(qi_ref, kj_ref, edge_ref, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, **static):
    pair = _Pair(qi_ref, kj_ref, edge_ref, **static)

    @pl.when(pair.first)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def tile_pair(dead):
        k, v = k_ref[0], v_ref[0]

        def head(h, sums):
            dk, dv = sums
            q, do = q_ref[0, :, pair.lanes(h)], do_ref[0, :, pair.lanes(h)]
            p = jnp.exp(pair.scores(q, k, dead)
                        - lse_ref[0, 0, pl.ds(h, 1), :])
            dv += jnp.dot(p.astype(do.dtype), do,
                          preferred_element_type=jnp.float32)
            dp = lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, 0, pl.ds(h, 1), :])
                  * pair.scale).astype(q.dtype)
            return dk + jnp.dot(ds, q,
                                preferred_element_type=jnp.float32), dv

        dk_scr[...], dv_scr[...] = pair.every_head(
            head, (dk_scr[...], dv_scr[...]))

    pair.either(tile_pair)

    @pl.when(pair.last)
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _call(kernel, name, static, schedule, grid_bkv, in_specs, out_specs,
          out_shape, scratch, operands, interpret):
    COUNTS["traces"] += 1               # the entry's body runs when traced
    first, *rest = operands
    return pl.pallas_call(
        functools.partial(kernel, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid_bkv + (len(schedule[0]),),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        name=name, interpret=interpret,
    )(*schedule, _lowered_p.bind(first, trace=COUNTS["traces"]), *rest)


def _specs(group, dim, bq, bk):
    """Block specs over the ``(batch, seq, heads * dim)`` views: a query
    tile of one KV head's group, a key tile of that KV head; the per-query
    floats as ``(group, queries)`` rows or ``(queries, group)`` columns;
    a key tile of a transposed ``(batch, kv_heads, dim, seq)`` operand."""
    return {
        "q": pl.BlockSpec((1, bq, group * dim),
                          lambda b, h, n, qi, kj, e: (b, qi[n], h)),
        "k": pl.BlockSpec((1, bk, dim),
                          lambda b, h, n, qi, kj, e: (b, kj[n], h)),
        "row": pl.BlockSpec((1, 1, group, bq),
                            lambda b, h, n, qi, kj, e: (b, h, 0, qi[n])),
        "col": pl.BlockSpec((1, 1, bq, group),
                            lambda b, h, n, qi, kj, e: (b, h, qi[n], 0)),
        "k_t": pl.BlockSpec((1, 1, dim, bk),
                            lambda b, h, n, qi, kj, e: (b, h, 0, kj[n])),
    }


def _static(q, k, window, tiles):
    """What every kernel of one core is specialised on."""
    bq, bk = tiles
    _, seq, heads, dim = q.shape
    return dict(group=heads // k.shape[2], dim=dim, bq=bq, bk=bk,
                window=None if window is None or window >= seq else window)


_ENTRY = functools.partial(jax.jit,
                           static_argnames=("window", "tiles", "interpret"))


@_ENTRY
def forward(q, k, v, *, window, tiles, interpret):
    """``(out, lse)``: ``out`` like ``q``; ``lse`` ``(batch, kv_heads,
    group, seq)`` float32."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    static = _static(q, k, window, tiles)
    g, bq, bk = (static[x] for x in ("group", "bq", "bk"))
    spec = _specs(g, d, bq, bk)
    out, lse = _call(
        _forward_kernel, "attn_core_forward", static,
        _schedule(t, bq, bk, static["window"], False), (b, kv),
        [spec["q"], spec["k"], spec["k_t"]], [spec["q"], spec["row"]],
        [jax.ShapeDtypeStruct((b, t, heads * d), q.dtype),
         jax.ShapeDtypeStruct((b, kv, g, t), jnp.float32)],
        [pltpu.VMEM((g, 1, bq), jnp.float32),
         pltpu.VMEM((g, 1, bq), jnp.float32),
         pltpu.VMEM((g, d, bq), jnp.float32)],
        (q.reshape(b, t, heads * d), k.reshape(b, t, kv * d),
         v.transpose(0, 2, 3, 1)), interpret)
    return out.reshape(q.shape), lse


@_ENTRY
def dq(q, k, v, dout, lse, delta, *, window, tiles, interpret):
    """The queries' gradient; ``lse`` and ``delta`` ``(batch, kv_heads,
    seq, group)`` float32: a query's floats down a column, as this
    kernel's scores lie."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    static = _static(q, k, window, tiles)
    g, bq, bk = (static[x] for x in ("group", "bq", "bk"))
    spec = _specs(g, d, bq, bk)
    out = _call(
        _dq_kernel, "attn_core_dq", static,
        _schedule(t, bq, bk, static["window"], False), (b, kv),
        [spec["q"], spec["k"], spec["k"], spec["q"], spec["col"],
         spec["col"]], spec["q"],
        jax.ShapeDtypeStruct((b, t, heads * d), q.dtype),
        [pltpu.VMEM((g, bq, d), jnp.float32)],
        (q.reshape(b, t, heads * d), k.reshape(b, t, kv * d),
         v.reshape(b, t, kv * d), dout.reshape(b, t, heads * d), lse, delta),
        interpret)
    return out.reshape(q.shape)


@_ENTRY
def dkv(q, k, v, dout, lse, delta, *, window, tiles, interpret):
    """``(dk, dv)``; ``lse`` and ``delta`` ``(batch, kv_heads, group,
    seq)`` float32, as ``forward`` returns the log-sum-exp."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    static = _static(q, k, window, tiles)
    g, bq, bk = (static[x] for x in ("group", "bq", "bk"))
    spec = _specs(g, d, bq, bk)
    dk, dv = _call(
        _dkv_kernel, "attn_core_dkv", static,
        _schedule(t, bq, bk, static["window"], True), (b, kv),
        [spec["q"], spec["k"], spec["k"], spec["q"], spec["row"],
         spec["row"]], [spec["k"], spec["k"]],
        [jax.ShapeDtypeStruct((b, t, kv * d), k.dtype),
         jax.ShapeDtypeStruct((b, t, kv * d), v.dtype)],
        [pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, d), jnp.float32)],
        (q.reshape(b, t, heads * d), k.reshape(b, t, kv * d),
         v.reshape(b, t, kv * d), dout.reshape(b, t, heads * d), lse, delta),
        interpret)
    return dk.reshape(k.shape), dv.reshape(v.shape)


def backward(q, k, v, out, lse, dout, **static):
    """``(dq, dk, dv)`` from the forward pass's output and log-sum-exp."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    delta = jnp.sum(dout.astype(jnp.float32) * out, axis=-1).reshape(
        b, t, kv, heads // kv)
    return (dq(q, k, v, dout, lse.transpose(0, 1, 3, 2),
               delta.transpose(0, 2, 1, 3), **static),
            *dkv(q, k, v, dout, lse, delta.transpose(0, 2, 3, 1), **static))
