"""Single-pass fused Pallas kernel for the conv1/conv2 elementwise block:
bias-add -> StrictRELU -> cross-channel LRN -> overlapping maxpool, forward
AND backward, each as ONE VMEM-resident pass over the activation planes.

Why (a profile of the old installation's chip): the composed ops lower to
several XLA fusions that each stream the 55x55x96-class conv1/conv2 tensors
through HBM — 4.39 ms of the 10.75 ms AlexNet step at a measured
320–490 GB/s against the chip's 819, and the one lever behind three rounds
of flat ~39.5% MFU.  The r5 masked-pool-backward experiment proved that
MULTI-pass reformulations lose (more passes, more HBM traffic); this kernel
is the single-pass counterpart: the forward reads x once and writes the
pooled output once; the backward reads (x, bias, d_pool) once and writes
(dx, dbias) once, with every intermediate (ReLU mask, LRN window sums, pool
argmax masks) living only in VMEM.

Grid: one image per grid step — a (1, H, W, C) block is VMEM-resident
(conv1: 55*55*96*4 B = 1.2 MB f32).  The channel-window sum is unrolled
static lane shifts (identical summation order to ops/lrn_pallas.py); the
pool is unrolled ky*kx strided max/compare over windows of a VMEM scratch
plane; the pool backward re-dilates window contributions by strided
accumulation into a second scratch plane — the same formulation
``pooling._masked_maxpool`` uses, but fused in VMEM where its ~18
intermediate tensors are free instead of 18 HBM round trips.

Semantics vs the composed ops:
  - forward is bit-for-tolerance identical (same rsqrt-based ``s^-0.75``,
    same shift summation order as the LRN oracle);
  - pool-backward TIES split d_y equally among a window's tied maxima
    (mass-conserving) where select_and_scatter routes to the first.  After
    StrictRELU the only systematic ties are all-zero windows, whose
    gradient the ReLU mask zeroes either way, so the two subgradients agree
    everywhere it matters (tests assert parity on random data);
  - internal arithmetic is f32 even for bf16 operands (outputs cast back),
    at least as accurate as the composed bf16 chain.

Engagement (``plan_fused_blocks``): opt-in via
``root.common.engine.fused_elementwise`` (default OFF: an undecided lever,
ROADMAP.md "Undecided levers"), and only where the graph shape matches
exactly:
Conv(+bias)+StrictRELU (fused or as a standalone activation unit) ->
LRNormalizerForward (odd window) -> MaxPooling whose windows tile the plane
exactly (AlexNet's 55/27/13 planes all do; partial edge windows fall back
to the composed ops), with a channel count that splits into whole
128-lane tiles (``lanes_tile``; 96 and 256 do).

Backward wiring: ``fused_block`` carries a ``jax.custom_vjp``, so wherever
the fused trainer's forward_pass routes through it, ``jax.grad`` of the
train step executes the fused backward kernel in place of the
``GradientDescent*`` chain (GDStrictRELUConv's activation term,
LRNormalizerBackward, GDMaxPooling's offset scatter).  The unit-at-a-time
engine keeps the composed units — it cannot fuse across unit boundaries by
construction.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from znicz_tpu.backends import pallas_interpret


class FusedBlockSpec(NamedTuple):
    """One matched conv-block occurrence in a forwards list."""

    span: int                      # units consumed (3, or 4 with a
    #                                standalone StrictRELU unit)
    n: int                         # LRN channel window
    alpha: float
    beta: float
    k: float
    pool: Tuple[int, int, int, int]   # (ky, kx, sy, sx)


def _relu_lrn(x, b, n, alpha, beta, k):
    """The pre-pool part shared by both kernels: f32 a/mask/r/s/y.  The
    window sum and ``s^-beta`` come from ops/lrn_pallas — the ONE home of
    that order-sensitive math (the parity guarantees depend on the exact
    summation order and rsqrt formulation)."""
    import jax.numpy as jnp

    from znicz_tpu.ops.lrn_pallas import (inv_pow_rsqrt,
                                          windowed_channel_sum)

    a = x + b
    r = jnp.maximum(a, 0.0)
    s = k + alpha * windowed_channel_sum(r * r, n)
    return a, r, s, r * inv_pow_rsqrt(s, beta)


#: Mosaic's strided VMEM access addresses ONE 128-lane tile: planes wider
#: than that live as ``C // _LANES`` lane chunks (``_plane_scratch``)
_LANES = 128


def _store_plane(ref, v):
    """(H, W, C) value -> the (chunks, H, W, lanes) VMEM plane."""
    lanes = ref.shape[-1]
    for c in range(ref.shape[0]):
        ref[c] = v[..., c * lanes:(c + 1) * lanes]


def _load_plane(ref, win=(slice(None),) * 3):
    """The (chunks, H, W, lanes) VMEM plane (or one window of it, see
    ``_pool_windows``) as ONE (.., .., C) value."""
    import jax.numpy as jnp

    parts = [ref[(c,) + win] for c in range(ref.shape[0])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def _pool_windows(ky, kx, sy, sx, oh, ow):
    """Index tuples of the ky*kx strided (OH, OW, C) windows of an
    exactly-tiling (H, W, C) VMEM plane.  Windows are strided REF
    accesses: Mosaic lowers those (``tpu.strided_load``/``strided_store``)
    but refuses a strided ``lax.slice`` of a value, and interior
    ``lax.pad``."""
    from jax.experimental import pallas as pl

    return [(pl.ds(i, oh, stride=sy), pl.ds(j, ow, stride=sx), slice(None))
            for i in range(ky) for j in range(kx)]


def _fwd_kernel(n, alpha, beta, k, ky, kx, sy, sx,
                x_ref, b_ref, out_ref, y_ref):
    import jax.numpy as jnp

    x = x_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    _store_plane(y_ref, _relu_lrn(x, b, n, alpha, beta, k)[3])
    oh, ow = out_ref.shape[1], out_ref.shape[2]
    p = functools.reduce(
        jnp.maximum, (_load_plane(y_ref, win)
                      for win in _pool_windows(ky, kx, sy, sx, oh, ow)))
    out_ref[0] = p.astype(out_ref.dtype)


def _bwd_kernel(n, alpha, beta, k, ky, kx, sy, sx,
                x_ref, b_ref, dp_ref, dx_ref, db_ref, y_ref, dy_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from znicz_tpu.ops.lrn_pallas import (inv_pow_rsqrt,
                                          windowed_channel_sum)

    x = x_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    dp = dp_ref[0].astype(jnp.float32)
    a, r, s, y = _relu_lrn(x, b, n, alpha, beta, k)
    _store_plane(y_ref, y)
    sb = inv_pow_rsqrt(s, beta)
    oh, ow = dp.shape[0], dp.shape[1]
    # pool backward: recompute window maxima, split dp among ties
    # (mass-conserving; see module docstring for the tie semantics)
    wins = _pool_windows(ky, kx, sy, sx, oh, ow)
    vals = [_load_plane(y_ref, win) for win in wins]
    p = functools.reduce(jnp.maximum, vals)
    masks = [(v == p).astype(jnp.float32) for v in vals]
    nt = functools.reduce(jnp.add, masks)
    g = dp / nt
    # strided accumulation re-dilates each window's share back to plane
    # coordinates — no scatter, all in VMEM
    dy_ref[...] = jnp.zeros(dy_ref.shape, jnp.float32)
    lanes = dy_ref.shape[-1]
    for win, mk in zip(wins, masks):
        contrib = g * mk
        for c in range(dy_ref.shape[0]):
            dy_ref[(c,) + win] = (dy_ref[(c,) + win]
                                  + contrib[..., c * lanes:(c + 1) * lanes])
    dy = _load_plane(dy_ref)
    # LRN backward — the closed form from znicz_tpu/lrn.py:
    #   dr = dy*s^-beta - 2*alpha*beta * r * W(dy * r * s^(-beta-1))
    t = dy * r * (sb / s)
    dr = dy * sb - (2.0 * alpha * beta) * r * windowed_channel_sum(t, n)
    # StrictRELU mask + bias reduction
    da = dr * (a > 0.0).astype(jnp.float32)
    dx_ref[0] = da.astype(dx_ref.dtype)
    partial = jnp.sum(da, axis=(0, 1))
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _():
        db_ref[0] = partial

    @pl.when(bi > 0)
    def _():
        db_ref[0] = db_ref[0] + partial


#: generous VMEM cap: the backward holds ~20 plane-sized intermediates
#: live before Mosaic's buffer reuse (conv1 plane ~1.2 MB f32)
_VMEM_LIMIT = 100 * 1024 * 1024


def _pool_out_hw(h, w, ky, kx, sy, sx):
    return (h - ky) // sy + 1, (w - kx) // sx + 1


def _img_spec(shape):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((1,) + tuple(shape[1:]),
                        lambda bi: (bi, 0, 0, 0), memory_space=pltpu.VMEM)


def _bias_spec(c):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((1, c), lambda bi: (0, 0),
                        memory_space=pltpu.VMEM)


def lanes_tile(c: int) -> bool:
    """Whether a C-channel plane splits into whole strided-access lane
    tiles (see ``_LANES``): AlexNet's 96 and 256 both do."""
    return c <= _LANES or c % _LANES == 0


def _plane_scratch(h, w, c):
    """One f32 (H, W, C) plane in VMEM as (chunks, H, W, lanes) — the home
    of the LRN output (and, in the backward, of d_y) that the pool's
    strided windows address."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    lanes = min(c, _LANES)
    return pltpu.VMEM((c // lanes, h, w, lanes), jnp.float32)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT)


def _call_fwd(x, bias, n, alpha, beta, k, pool):
    import jax
    from jax.experimental import pallas as pl

    ky, kx, sy, sx = pool
    B, H, W, C = x.shape
    oh, ow = _pool_out_hw(H, W, ky, kx, sy, sx)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n, alpha, beta, k, ky, kx, sy, sx),
        grid=(B,),
        in_specs=[_img_spec(x.shape), _bias_spec(C)],
        out_specs=_img_spec((B, oh, ow, C)),
        out_shape=jax.ShapeDtypeStruct((B, oh, ow, C), x.dtype),
        scratch_shapes=[_plane_scratch(H, W, C)],
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(x, bias.reshape(1, C))


def _call_bwd(x, bias, dp, n, alpha, beta, k, pool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    ky, kx, sy, sx = pool
    B, H, W, C = x.shape
    dx, db = pl.pallas_call(
        functools.partial(_bwd_kernel, n, alpha, beta, k, ky, kx, sy, sx),
        grid=(B,),
        in_specs=[_img_spec(x.shape), _bias_spec(C),
                  _img_spec(dp.shape)],
        out_specs=(_img_spec(x.shape), _bias_spec(C)),
        out_shape=(jax.ShapeDtypeStruct((B, H, W, C), x.dtype),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)),
        scratch_shapes=[_plane_scratch(H, W, C)] * 2,
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(x, bias.reshape(1, C), dp)
    return dx, db.reshape(bias.shape).astype(bias.dtype)


def _make():
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
    def fused_block(x, bias, n, alpha, beta, k, pool):
        return _call_fwd(x, bias, n, alpha, beta, k, pool)

    def fwd(x, bias, n, alpha, beta, k, pool):
        # residual is (x, bias) only — everything else is recomputed in
        # VMEM by the backward kernel (same policy as lrn.py's closed vjp)
        return fused_block(x, bias, n, alpha, beta, k, pool), (x, bias)

    def bwd(n, alpha, beta, k, pool, res, dp):
        x, bias = res
        return _call_bwd(x, bias, dp, n, alpha, beta, k, pool)

    fused_block.defvjp(fwd, bwd)
    return fused_block


_fused = None


def fused_block(x, bias, n=5, alpha=1e-4, beta=0.75, k=2.0,
                pool=(3, 3, 2, 2)):
    """Fused bias+StrictRELU+LRN+maxpool with the fused backward as its
    custom vjp.  ``x`` is the RAW conv output (``Conv.apply_linear``) of
    shape (B, H, W, C); ``pool`` = (ky, kx, sy, sx) and must tile (H, W)
    exactly — ``plan_fused_blocks`` guarantees this."""
    global _fused
    if _fused is None:
        _fused = _make()
    ky, kx, sy, sx = (int(v) for v in pool)
    _, H, W, C = x.shape
    assert (H - ky) % sy == 0 and (W - kx) % sx == 0, \
        f"pool {pool} does not tile ({H}, {W}) exactly"
    assert lanes_tile(C), \
        f"{C} channels do not split into whole {_LANES}-lane tiles"
    return _fused(x, bias, int(n), float(alpha), float(beta), float(k),
                  (ky, kx, sy, sx))


def match_fused_block(forwards: Sequence, i: int) -> Optional[FusedBlockSpec]:
    """The FusedBlockSpec for a conv-block starting at ``forwards[i]``, or
    None.  Patterns: ConvStrictRELU -> norm -> max_pooling (span 3), or
    plain Conv -> StrictRELU activation unit -> norm -> max_pooling
    (span 4).  Units must be initialized (geometry comes from live
    shapes)."""
    from znicz_tpu.activation import is_strict_relu_unit
    from znicz_tpu.conv import Conv
    from znicz_tpu.lrn import LRNormalizerForward
    from znicz_tpu.ops import activations
    from znicz_tpu.pooling import MaxPooling

    conv = forwards[i]
    if not isinstance(conv, Conv) or not conv.include_bias \
            or not lanes_tile(conv.n_kernels):
        return None
    j = i + 1
    if conv.ACTIVATION is activations.strict_relu:
        pass
    elif conv.ACTIVATION is activations.identity and j < len(forwards) \
            and is_strict_relu_unit(forwards[j]):
        j += 1
    else:
        return None
    if j + 1 >= len(forwards):
        return None
    lrn_u, pool_u = forwards[j], forwards[j + 1]
    if not isinstance(lrn_u, LRNormalizerForward):
        return None
    hypers = lrn_u.fused_block_hypers
    if hypers is None:
        return None
    # exact class: MaxAbs/stochastic/avg pooling have different math
    if type(pool_u) is not MaxPooling or not pool_u.exact_tiling():
        return None
    n, alpha, beta, k = hypers
    sy, sx = pool_u.sliding
    return FusedBlockSpec(span=j + 2 - i, n=n, alpha=alpha, beta=beta,
                          k=k, pool=(pool_u.ky, pool_u.kx, sy, sx))


def plan_fused_blocks(forwards: Sequence) -> Dict[int, FusedBlockSpec]:
    """start-index -> FusedBlockSpec for every fusable conv block, or {}
    when the ``fused_elementwise`` flag is off."""
    from znicz_tpu.core.config import root

    if not bool(root.common.engine.get("fused_elementwise", False)):
        return {}
    plan: Dict[int, FusedBlockSpec] = {}
    i = 0
    while i < len(forwards):
        spec = match_fused_block(forwards, i)
        if spec is not None:
            plan[i] = spec
            i += spec.span
        else:
            i += 1
    return plan


# -- the AlexNet tail (ISSUE 7) ------------------------------------------------
#
# The conv1/conv2 block kernel above left the TAIL of the network on the
# composed path: conv3-5's bias+StrictRELU, the fc6/fc7
# bias+StrictRELU+dropout epilogues, and the softmax-CE loss head.  Each
# of those is elementwise work whose AUTODIFF residuals (ReLU gates,
# dropout masks, softmax probabilities) round-trip HBM between the
# forward and backward passes — for AlexNet at batch 128 that is
# ~27 MB/step of pure mask traffic on top of the activations.  The three
# tail stages below each carry a ``jax.custom_vjp`` whose residual is
# ONLY what already exists (the stage's raw linear input + params): the
# backward recomputes every mask in-register instead of loading it.
#
# Engagement: ``root.common.engine.fused_tail`` (default OFF — an
# undecided lever like ``fused_elementwise``).  Where BOTH
# knobs are on, the conv1/conv2 BLOCK matcher wins its span and the tail
# matcher takes everything else.


class FusedTailSpec(NamedTuple):
    """One matched tail-stage occurrence in a forwards list."""

    kind: str                  # "conv_bias_relu" | "fc_epilogue"
    span: int                  # units consumed
    ratio: float = 0.0         # dropout ratio (fc_epilogue only)
    dropout_index: int = -1    # forwards index of the absorbed dropout
    #                            unit (-1 = no dropout); the fused mask
    #                            key is fold_in(key, dropout_index) —
    #                            bit-identical to the unit path's draw


def _bias_relu_fwd_kernel(x_ref, b_ref, out_ref):
    import jax.numpy as jnp

    x = x_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    out_ref[0] = jnp.maximum(x + b, 0.0).astype(out_ref.dtype)


def _bias_relu_bwd_kernel(x_ref, b_ref, dp_ref, dx_ref, db_ref):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x = x_ref[0].astype(jnp.float32)
    b = b_ref[0].astype(jnp.float32)
    dp = dp_ref[0].astype(jnp.float32)
    da = dp * ((x + b) > 0.0).astype(jnp.float32)
    dx_ref[0] = da.astype(dx_ref.dtype)
    partial = jnp.sum(da, axis=(0, 1))
    bi = pl.program_id(0)

    @pl.when(bi == 0)
    def _():
        db_ref[0] = partial

    @pl.when(bi > 0)
    def _():
        db_ref[0] = db_ref[0] + partial


def _call_bias_relu_fwd(x, bias):
    import jax
    from jax.experimental import pallas as pl

    B, H, W, C = x.shape
    return pl.pallas_call(
        _bias_relu_fwd_kernel,
        grid=(B,),
        in_specs=[_img_spec(x.shape), _bias_spec(C)],
        out_specs=_img_spec(x.shape),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(x, bias.reshape(1, C))


def _call_bias_relu_bwd(x, bias, dp):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, H, W, C = x.shape
    dx, db = pl.pallas_call(
        _bias_relu_bwd_kernel,
        grid=(B,),
        in_specs=[_img_spec(x.shape), _bias_spec(C), _img_spec(x.shape)],
        out_specs=(_img_spec(x.shape), _bias_spec(C)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((1, C), jnp.float32)),
        compiler_params=_compiler_params(),
        interpret=pallas_interpret(),
    )(x, bias.reshape(1, C), dp)
    return dx, db.reshape(bias.shape).astype(bias.dtype)


def _make_bias_relu():
    import jax

    @jax.custom_vjp
    def bias_relu(x, bias):
        return _call_bias_relu_fwd(x, bias)

    def fwd(x, bias):
        # residual is (x, bias) only — the ReLU gate is recomputed by
        # the backward kernel in VMEM, never written to HBM
        return bias_relu(x, bias), (x, bias)

    def bwd(res, dp):
        x, bias = res
        return _call_bias_relu_bwd(x, bias, dp)

    bias_relu.defvjp(fwd, bwd)
    return bias_relu


_bias_relu = None


def fused_bias_relu(x, bias):
    """Fused bias+StrictRELU over a (B, H, W, C) conv output — the
    conv3-5 tail stage (no LRN, no pool there) as ONE Pallas pass each
    way: forward reads x once and writes relu(x+b) once; backward reads
    (x, bias, d_out) once and writes (dx, dbias) once, the ReLU gate
    living only in VMEM.  Internal arithmetic is f32 even for bf16
    operands (outputs cast back), matching the block kernel's policy."""
    global _bias_relu
    if _bias_relu is None:
        _bias_relu = _make_bias_relu()
    assert x.ndim == 4, f"fused_bias_relu expects NHWC, got {x.shape}"
    return _bias_relu(x, bias)


def fused_fc_epilogue(y, bias, key, ratio, train):
    """Fused FC-layer epilogue — bias+StrictRELU(+inverted-scale dropout)
    over the raw GEMM output ``y`` as ONE custom-vjp stage.  The forward
    is a single elementwise fusion; the backward recomputes the ReLU gate
    from (y, bias) and the dropout mask FROM THE KEY instead of loading
    either from HBM (the 4096-wide fc6/fc7 masks are the dominant
    non-GEMM autodiff residual).  The mask is ``DropoutForward.
    make_mask``'s own bernoulli draw with the caller's key, so fused and
    unfused paths apply BIT-IDENTICAL masks — e2e trainer parity is
    exact, not distributional.  ``key`` may be None when no mask applies
    (eval, or ratio 0)."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dropout import DropoutForward

    use_mask = bool(train) and float(ratio) > 0.0 and key is not None
    shape, ratio = y.shape, float(ratio)

    def mask_of():
        return DropoutForward.make_mask(key, shape, ratio)

    @jax.custom_vjp
    def epilogue(y, b):
        r = jnp.maximum(y + b, 0.0)
        return r * mask_of() if use_mask else r

    def fwd(y, b):
        return epilogue(y, b), (y, b)

    def bwd(res, g):
        y, b = res
        da = g * ((y + b) > 0.0).astype(g.dtype)
        if use_mask:
            da = da * mask_of().astype(g.dtype)
        # bias grad sums every leading axis (a seq epilogue's y is
        # (B, T, F); for the classic (B, F) this is the same axis-0 sum)
        return (da.astype(y.dtype),
                jnp.sum(da, axis=tuple(range(da.ndim - 1))).astype(b.dtype))

    epilogue.defvjp(fwd, bwd)
    return epilogue(y, bias)


def fused_softmax_xent(logits, labels, valid, denom):
    """Softmax-CE loss + gradient as ONE custom-vjp epilogue.  Forward is
    the max-subtracted logsumexp CE — the IDENTICAL formula the composed
    trainer loss uses (``logsumexp(logits) - logits[label]``, masked and
    batch-mean scaled).  Backward writes ``(softmax(logits) - onehot) *
    valid / denom`` in a single fusion that re-reads the logits (which
    must exist anyway — they are the FC head's output) instead of
    consuming saved logsumexp/softmax residuals; for the 1000-class
    AlexNet head that is the difference between one HBM read and three.
    ``labels``/``valid``/``denom`` are closed over (non-differentiable
    operands)."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def loss_of(lg):
        logz = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(valid, logz - ll, 0.0)) / denom

    def fwd(lg):
        return loss_of(lg), (lg,)

    def bwd(res, g):
        lg, = res
        p = jax.nn.softmax(lg, axis=-1)
        onehot = jax.nn.one_hot(labels, lg.shape[-1], dtype=lg.dtype)
        d = (p - onehot) * valid[:, None].astype(lg.dtype) / denom * g
        return (d,)

    loss_of.defvjp(fwd, bwd)
    return loss_of(logits)


def match_conv_bias_relu(forwards: Sequence, i: int) \
        -> Optional[FusedTailSpec]:
    """Conv(+bias) with a StrictRELU — fused into the class (span 1) or a
    standalone activation unit (span 2) — with NO LRN/pool requirement:
    the conv3-5 shape.  (Where the full conv-block matcher also fires,
    ``plan_fused_tail`` lets the block win its span.)"""
    from znicz_tpu.activation import is_strict_relu_unit
    from znicz_tpu.conv import Conv
    from znicz_tpu.ops import activations

    conv = forwards[i]
    if not isinstance(conv, Conv) or not conv.include_bias:
        return None
    if conv.ACTIVATION is activations.strict_relu:
        return FusedTailSpec("conv_bias_relu", 1)
    if conv.ACTIVATION is activations.identity and i + 1 < len(forwards) \
            and is_strict_relu_unit(forwards[i + 1]):
        return FusedTailSpec("conv_bias_relu", 2)
    return None


def match_fc_epilogue(forwards: Sequence, i: int) -> Optional[FusedTailSpec]:
    """All2AllStrictRELU(+bias), optionally followed by a DropoutForward
    it absorbs (span 2) — the fc6/fc7 shape.  The softmax head is NOT
    matched here (its epilogue is the loss head, ``fused_softmax_xent``,
    routed by the trainer's loss function)."""
    from znicz_tpu.all2all import All2All, All2AllSoftmax
    from znicz_tpu.dropout import DropoutForward
    from znicz_tpu.ops import activations

    f = forwards[i]
    if not isinstance(f, All2All) or isinstance(f, All2AllSoftmax):
        return None
    if type(f).ACTIVATION is not activations.strict_relu \
            or not f.include_bias:
        return None
    if i + 1 < len(forwards) and isinstance(forwards[i + 1],
                                            DropoutForward):
        return FusedTailSpec("fc_epilogue", 2,
                             float(forwards[i + 1].dropout_ratio), i + 1)
    return FusedTailSpec("fc_epilogue", 1)


def match_seq_epilogue(forwards: Sequence, i: int) -> Optional[FusedTailSpec]:
    """SeqAll2AllStrictRELU(+bias) — the position-wise transformer-FFN
    shape (ISSUE 15; span 1).  The softmax head is NOT matched here
    (its epilogue is the loss head, like the All2All case), and no
    dropout is absorbed (the charlm FFN carries none)."""
    from znicz_tpu.attention import SeqAll2All, SeqAll2AllSoftmax
    from znicz_tpu.ops import activations

    f = forwards[i]
    if not isinstance(f, SeqAll2All) or isinstance(f, SeqAll2AllSoftmax):
        return None
    if type(f).ACTIVATION is not activations.strict_relu \
            or not f.include_bias:
        return None
    return FusedTailSpec("seq_epilogue", 1)


def fused_tail_enabled() -> bool:
    """The ``root.common.engine.fused_tail`` gate (default OFF)."""
    from znicz_tpu.core.config import root

    return bool(root.common.engine.get("fused_tail", False))


def plan_fused_tail(forwards: Sequence,
                    block_plan: Optional[Dict[int, FusedBlockSpec]] = None
                    ) -> Dict[int, FusedTailSpec]:
    """start-index -> FusedTailSpec for every fusable tail stage, or {}
    when ``fused_tail`` is off.  Indices covered by a conv-block span
    (``block_plan``) are skipped — the single-pass block kernel already
    owns their bias+ReLU."""
    if not fused_tail_enabled():
        return {}
    covered = set()
    for i, spec in (block_plan or {}).items():
        covered.update(range(i, i + spec.span))
    plan: Dict[int, FusedTailSpec] = {}
    i = 0
    while i < len(forwards):
        if i in covered:
            i += 1
            continue
        spec = match_conv_bias_relu(forwards, i)
        if spec is None:
            spec = match_fc_epilogue(forwards, i)
        if spec is None:
            spec = match_seq_epilogue(forwards, i)
        if spec is not None:
            plan[i] = spec
            i += spec.span
        else:
            i += 1
    return plan
