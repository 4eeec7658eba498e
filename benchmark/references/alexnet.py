"""Plain reference: one-tower AlexNet, forward, softmax cross-entropy and
the paper's update rule.

Krizhevsky, Sutskever, Hinton, "ImageNet Classification with Deep
Convolutional Neural Networks", NIPS 2012, sections 3 and 3.5, in the
one-tower form of Caffe's ``bvlc_alexnet`` without groups.  Straight
``jax.numpy``/``lax`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no mixed
precision, no fusion plan.  Dropout is the identity unless the caller
hands in the masks (the random draw is an input, not part of the network).

Departures from the paper, shared with the system under test and noted
here: no groups in conv2/4/5 (one tower); LRN with k=2, n=5, alpha=1e-4,
beta=0.75 where alpha multiplies the window *sum* (the paper's form; Caffe
divides alpha by n); 227x227 input so that conv1 (11x11, stride 4, no
padding) gives 55x55.

Weight layouts are this file's own statement: a convolution's weights are
``(K, ky, kx, C)`` and a dense layer's ``(out, in)`` over the NHWC
activation flattened in that order; ``layers`` is the list of
``(weights, bias)`` pairs of the eight weighted layers in order.
"""

from __future__ import annotations

#: (kind, kernel, stride, padding) of the eight weighted layers, and
#: whether LRN and the 3x3/2 max pool follow
CONVS = ((11, 4, 0, True, True), (5, 1, 2, True, True),
         (3, 1, 1, False, False), (3, 1, 1, False, False),
         (3, 1, 1, False, True))
LRN_N, LRN_K, LRN_ALPHA, LRN_BETA = 5, 2.0, 1e-4, 0.75


def lrn(x):
    import jax.numpy as jnp

    half = LRN_N // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, half)))
    c = x.shape[-1]
    window = sum(sq[..., i:i + c] for i in range(LRN_N))
    return x / (LRN_K + LRN_ALPHA * window) ** LRN_BETA


def max_pool_3x3_s2(x):
    import jax.numpy as jnp
    from jax import lax

    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "VALID")


def forward(layers, x, masks=()):
    """Logits ``(batch, classes)`` of ``x`` ``(batch, H, W, 3)``.
    ``masks``: the dropout masks of fc6 and fc7, each ``(batch, width)``
    and already scaled by 1/keep (section 4.2 of the paper scales at test
    time instead; the expectation is the same); none in evaluation mode."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    with jax.default_matmul_precision("highest"):
        h = x.astype(jnp.float32)
        for (k, stride, pad, has_lrn, has_pool), (w, b) in zip(CONVS,
                                                               layers):
            w = jnp.transpose(w.astype(jnp.float32), (1, 2, 3, 0))  # HWIO
            h = lax.conv_general_dilated(
                h, w, (stride, stride), ((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=lax.Precision.HIGHEST)
            h = jnp.maximum(h + b.astype(jnp.float32), 0.0)
            if has_lrn:
                h = lrn(h)
            if has_pool:
                h = max_pool_3x3_s2(h)
        h = h.reshape(h.shape[0], -1)
        for i, (w, b) in enumerate(layers[len(CONVS):]):
            h = jnp.dot(h, w.astype(jnp.float32).T,
                        precision=lax.Precision.HIGHEST) \
                + b.astype(jnp.float32)
            if i < 2:                   # fc6, fc7: ReLU; fc8: logits
                h = jnp.maximum(h, 0.0)
                if masks:
                    h = h * masks[i].astype(jnp.float32)
        return h


def loss(layers, x, labels, masks=()):
    """Mean softmax cross-entropy of ``x`` against integer ``labels``."""
    import jax
    import jax.numpy as jnp

    logits = forward(layers, x, masks)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)


def sgd_momentum(w, v, g, learning_rate, momentum, weight_decay):
    """Section 5 of the paper: ``v <- momentum v - decay lr w - lr g``,
    ``w <- w + v``.  Returns ``(w, v)`` in float32."""
    import jax.numpy as jnp

    w, v, g = (t.astype(jnp.float32) for t in (w, v, g))
    v = momentum * v - learning_rate * (g + weight_decay * w)
    return w + v, v
