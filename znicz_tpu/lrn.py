"""Local response normalization fwd+bwd (rebuild of ``znicz/normalization.py``
— the AlexNet-style across-channel LRN; the input-data normalizers live in
``znicz_tpu/normalization.py`` matching the reference's core-vs-znicz split).

Forward: ``y = x / (k + alpha * sum_{j in window(c)} x_j^2) ^ beta`` with the
window of ``n`` adjacent channels centered on c.  Backward is a CLOSED-FORM
custom vjp (below) — autodiff through pow+window-sum materializes several
extra activation-sized tensors per step, and on AlexNet's conv1/conv2
activations that HBM traffic was ~20% of the whole train step (r4 profile).
Defaults follow the reference kernels: alpha=1e-4, beta=0.75, n=5, k=2.

The closed form: with ``s = k + alpha*winsum(x^2)`` and ``y = x*s^-beta``,

    dx = dy*s^-beta - 2*alpha*beta * x * winsum(dy * x * s^(-beta-1))

i.e. backward = 2 elementwise passes + 2 channel-window sums, with only
``(x,)`` saved from the forward (``s`` is recomputed — bitwise identical,
measured neutral, smaller residual).  ``s^-beta`` for the default beta=0.75
is computed as ``rsqrt(s)*sqrt(rsqrt(s))`` — two pipelined VPU ops instead
of the exp/log ``pow`` expansion.
"""

from __future__ import annotations

from functools import partial

from znicz_tpu.nn_units import ForwardBase, GradientDescentBase
from znicz_tpu.ops.lrn_pallas import inv_pow_rsqrt


def _winsum(t, n: int):
    """Sum over a window of n adjacent channels (zero-padded ends), via
    reduce_window: the pad+shifted-slices formulation materializes a
    channel-padded copy whose slices fall off the sublane tiling (96 -> 100
    channels), and the resulting relayout traffic capped the big LRN
    fusions at ~320 GB/s of the chip's 819 (r4 profile).  ODD n only —
    the closed-form vjp relies on the window being self-adjoint."""
    import jax

    assert n % 2 == 1, n
    half = n // 2
    return jax.lax.reduce_window(
        t, jax.numpy.zeros((), t.dtype), jax.lax.add,
        window_dimensions=(1,) * (t.ndim - 1) + (n,),
        window_strides=(1,) * t.ndim,
        padding=[(0, 0)] * (t.ndim - 1) + [(half, half)])


@partial(__import__("jax").custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn_ref(x, n: int, alpha: float, beta: float, k: float):
    s = k + alpha * _winsum(x * x, n)
    return x * inv_pow_rsqrt(s, beta)


def _lrn_ref_fwd(x, n, alpha, beta, k):
    s = k + alpha * _winsum(x * x, n)
    return x * inv_pow_rsqrt(s, beta), (x,)


def _lrn_ref_bwd(n, alpha, beta, k, res, dy):
    # recompute s from x instead of saving it (same expression, same
    # reduction order -> bitwise-identical): forward and backward live in
    # ONE jitted step, so XLA schedules the residual either way, and the
    # smaller residual is never worse.
    (x,) = res
    s = k + alpha * _winsum(x * x, n)
    r = inv_pow_rsqrt(s, beta)
    t = dy * x * (r / s)
    dx = dy * r - (2.0 * alpha * beta) * x * _winsum(t, n)
    return (dx,)


lrn_ref.defvjp(_lrn_ref_fwd, _lrn_ref_bwd)


class LRNormalizerForward(ForwardBase):
    has_weights = False

    def __init__(self, workflow=None, name=None, alpha=1e-4, beta=0.75,
                 n=5, k=2.0, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.alpha = float(alpha)
        self.beta = float(beta)
        self.n = int(n)
        self.k = float(k)

    def output_shape_for(self, in_shape):
        return tuple(in_shape)

    @property
    def fused_block_hypers(self):
        """(n, alpha, beta, k) when this unit's config is expressible by
        the single-pass conv-block kernel (odd windows only — the kernel
        shares the closed-form vjp's self-adjoint-window assumption), else
        None.  Consumed by pallas_fused_block.match_fused_block."""
        if self.n % 2 == 1:
            return (self.n, self.alpha, self.beta, self.k)
        return None

    def apply(self, params, x):
        if self.n % 2 == 1:
            return lrn_ref(x, self.n, self.alpha, self.beta, self.k)
        # even windows are asymmetric (not self-adjoint): plain autodiff
        # through the shifted-slices formulation instead of the
        # closed-form vjp
        import jax.numpy as jnp

        half = self.n // 2
        padded = jnp.pad(jnp.square(x),
                         [(0, 0)] * (x.ndim - 1) + [(half, half)])
        acc = jnp.zeros_like(x)
        for j in range(self.n):
            acc = acc + padded[..., j:j + x.shape[-1]]
        return x / jnp.power(self.k + self.alpha * acc, self.beta)

    def initialize(self, device=None, **kwargs):
        self.create_output()
        super().initialize(device=device, **kwargs)


class LRNormalizerBackward(GradientDescentBase):
    def __init__(self, workflow=None, name=None, forward=None, **kwargs):
        kwargs.setdefault("apply_gradient", False)
        super().__init__(workflow=workflow, name=name, forward=forward,
                         **kwargs)
