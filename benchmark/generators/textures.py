"""Generator ``textures``: procedural images made on the device, in one
jitted call, from the seed.  A configuration names its generator; the
harness finds ``benchmark/generators/<name>.py`` and calls ``make``.

The sample workflows make their stand-in data set on the host
(``znicz_tpu.datasets.tinyimages``: 6.6 ms an AlexNet-size image in this
sandbox, so 8,448 images would add ~56 s to every run's set-up).  The
benchmark makes the same kind of image — ten classes of parametric
textures, one reliable cue each (grating angle or blob position), colour,
frequency and width as nuisances, a faint distractor grating and pixel
noise — vectorised on the chip, where the whole set takes a fraction of a
second and nothing crosses the host link.  Every image is distinct; the
same seed gives the same set.
"""

from __future__ import annotations

import math


def _chunk(n: int, cap: int = 256) -> int:
    """Largest divisor of ``n`` not above ``cap``: the set is made chunk by
    chunk so the temporaries stay small beside the result."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def make(seed: int, n: int, size: int, n_classes: int, sharding=None,
         noise: float = 0.25):
    """``(data, labels)``: ``(n, size, size, 3)`` float32 in [0, 1] and
    ``(n,)`` int32 labels in ``[0, min(10, n_classes))``, on the device
    (or replicated over ``sharding``'s mesh)."""
    import jax
    import jax.numpy as jnp

    chunk = _chunk(n)
    kinds = min(10, n_classes)

    def make_chunk(key):
        ks = jax.random.split(key, 12)

        def u(k, lo, hi, shape=(chunk,)):
            return jax.random.uniform(k, shape, jnp.float32, lo, hi)

        labels = jax.random.randint(ks[0], (chunk,), 0, kinds)
        k5 = labels.astype(jnp.float32)
        grid = jnp.arange(size, dtype=jnp.float32) / size
        yy, xx = grid[None, :, None], grid[None, None, :]

        def grating(angle, freq, phase):
            a, f, p = (t[:, None, None] for t in (angle, freq, phase))
            return 0.5 + 0.5 * jnp.sin(
                2 * math.pi * f * (xx * jnp.cos(a) + yy * jnp.sin(a)) + p)

        # classes 0-4: orientation is the cue (36 degrees apart)
        angle = k5 * (math.pi / 5) + 0.10 * jax.random.normal(ks[1], (chunk,))
        wave = grating(angle, u(ks[2], 3.0, 6.0), u(ks[3], 0, 2 * math.pi))
        color = u(ks[4], 0.5, 1.0, (chunk, 3))
        img_a = wave[..., None] * color[:, None, None, :]
        # classes 5-9: blob position is the cue
        j = k5 - 5.0
        cx = (0.25 + 0.125 * j + 0.04 * jax.random.normal(ks[5], (chunk,)))
        cy = (0.35 + 0.08 * j + 0.04 * jax.random.normal(ks[6], (chunk,)))
        sigma = u(ks[7], 0.08, 0.16)
        blob = jnp.exp(-((xx - cx[:, None, None]) ** 2
                         + (yy - cy[:, None, None]) ** 2)
                       / (2 * sigma[:, None, None] ** 2))
        chan = jax.random.randint(ks[8], (chunk,), 0, 3)
        weights = (jax.nn.one_hot(chan, 3)
                   + 0.3 * jax.nn.one_hot((chan + 1) % 3, 3))
        img_b = blob[..., None] * weights[:, None, None, :]
        img = jnp.where((labels < 5)[:, None, None, None], img_a, img_b)
        # a faint distractor grating and pixel noise over every image
        dk = jax.random.split(ks[9], 4)
        dist = grating(u(dk[0], 0, math.pi), u(dk[1], 3.0, 6.0),
                       u(dk[2], 0, 2 * math.pi))
        img = img + 0.10 * dist[..., None] * u(dk[3], 0.3, 1.0,
                                              (chunk, 3))[:, None, None, :]
        img = img + noise * (jax.random.uniform(
            ks[10], img.shape, jnp.float32, -1.7320508, 1.7320508))
        return jnp.clip(img, 0.0, 1.0), labels.astype(jnp.int32)

    def make(key):
        data, labels = jax.lax.map(make_chunk,
                                   jax.random.split(key, n // chunk))
        return (data.reshape((n, size, size, 3)), labels.reshape((n,)))

    fn = jax.jit(make) if sharding is None else jax.jit(
        make, out_shardings=(sharding, sharding))
    return fn(jax.random.key(seed, impl="rbg"))

