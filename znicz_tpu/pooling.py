"""Pooling forward units (rebuild of ``znicz/pooling.py``).

``MaxPooling`` / ``MaxAbsPooling`` / ``AvgPooling`` / ``StochasticPooling`` /
``StochasticAbsPooling`` over NHWC, with the reference's geometry: ``sliding``
defaults to the kernel size (non-overlapping), partial windows at the
right/bottom edges are processed (output = ceil-style
``(H - ky) // sy + 1`` after implicit edge padding), and the max/stochastic
variants record per-output *offsets* (flat window-relative argmax / sampled
position) that their GD twins use to scatter err_output back — exactly the
reference's forward/backward contract (SURVEY.md §2.2 "Pooling").

Implementation: windows are materialized by strided advanced indexing
(an XLA gather with static index grids — shapes are all static, jit-safe).
Stochastic pooling samples position ∝ activation (∝|activation| for the Abs
variant) from the device PRNG (SURVEY.md hard part 4: the sampled offsets are
unit state reused by the backward, not resampled).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.loader.base import TRAIN
from znicz_tpu.memory import Array
from znicz_tpu.nn_units import ForwardBase


def pool_output_hw(h: int, w: int, ky: int, kx: int,
                   sliding: Tuple[int, int]) -> Tuple[int, int]:
    sy, sx = sliding
    return (max(1, -(-max(h - ky, 0) // sy) + 1),
            max(1, -(-max(w - kx, 0) // sx) + 1))


class PoolingBase(ForwardBase):
    has_weights = False
    #: value used to pad partial edge windows (max: -inf, avg: 0)
    PAD_VALUE = 0.0

    def __init__(self, workflow=None, name=None, kx=2, ky=2, sliding=None,
                 **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.kx = int(kx)
        self.ky = int(ky)
        self.sliding = tuple(sliding) if sliding else (self.ky, self.kx)
        #: flat window-relative position chosen per output element
        #: (max/stochastic variants; avg leaves it empty)
        self.input_offset = Array()

    def output_shape_for(self, in_shape):
        b, h, w, c = in_shape
        oh, ow = pool_output_hw(h, w, self.ky, self.kx, self.sliding)
        return (b, oh, ow, c)

    # -- window extraction (shared by subclasses & GD twins) ------------------

    def _window_geometry(self):
        b, h, w, c = self.input.shape
        oh, ow = pool_output_hw(h, w, self.ky, self.kx, self.sliding)
        sy, sx = self.sliding
        ph = (oh - 1) * sy + self.ky       # padded extent covering all windows
        pw = (ow - 1) * sx + self.kx
        return (int(b), int(h), int(w), int(c), oh, ow, sy, sx, ph, pw)

    def exact_tiling(self) -> bool:
        """True when every pooling window is full — the padded extent the
        windows cover equals the input plane, so no partial edge windows
        exist.  Geometry precondition of the single-pass fused conv-block
        kernel (pallas_fused_block): AlexNet's 55/27/13 planes with 3x3/s2
        overlapping pools all tile exactly; anything else falls back to
        the composed ops."""
        _, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
        return ph == h and pw == w

    def windows(self, x):
        """(B, OH, OW, C, ky*kx) view of all pooling windows.  Spatial
        geometry is the unit's static config; the batch dim follows ``x``
        so eval-time batches of any size reuse the same unit."""
        import jax.numpy as jnp

        _, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
        b = x.shape[0]
        xp = jnp.pad(x, ((0, 0), (0, ph - h), (0, pw - w), (0, 0)),
                     constant_values=type(self).PAD_VALUE)
        ys = (np.arange(oh) * sy)[:, None] + np.arange(self.ky)[None, :]
        xs = (np.arange(ow) * sx)[:, None] + np.arange(self.kx)[None, :]
        # advanced indexing broadcast -> (B, OH, OW, ky, kx, C)
        win = xp[:, ys[:, None, :, None], xs[None, :, None, :], :]
        win = win.transpose(0, 1, 2, 5, 3, 4)       # (B, OH, OW, C, ky, kx)
        return win.reshape(b, oh, ow, c, self.ky * self.kx)

    def _offset_grids(self, offsets):
        """(bidx, ay, ax, cidx) absolute padded-input coordinates for
        window-relative ``offsets`` — the single home of the offset
        convention shared by the GD scatter and Depooling (adjointness
        depends on all users agreeing on this math)."""
        import jax.numpy as jnp

        b, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
        oy = np.arange(oh)[None, :, None, None]
        ox = np.arange(ow)[None, None, :, None]
        ay = oy * sy + offsets // self.kx
        ax = ox * sx + offsets % self.kx
        bidx = jnp.arange(b)[:, None, None, None]
        cidx = jnp.arange(c)[None, None, None, :]
        return bidx, ay, ax, cidx

    def scatter_at_offsets(self, values, offsets):
        """Input-shaped array with ``values`` scatter-added at the recorded
        positions (the max/stochastic backward and Depooling forward)."""
        import jax.numpy as jnp

        b, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
        bidx, ay, ax, cidx = self._offset_grids(offsets)
        padded = jnp.zeros((b, ph, pw, c), values.dtype)
        padded = padded.at[bidx, ay, ax, cidx].add(values)
        return padded[:, :h, :w, :]

    def gather_at_offsets(self, full, offsets):
        """Output-shaped gather of an input-shaped array at the recorded
        positions (the Depooling backward — exact adjoint of the scatter)."""
        import jax.numpy as jnp

        b, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
        bidx, ay, ax, cidx = self._offset_grids(offsets)
        padded = jnp.pad(full, ((0, 0), (0, ph - h), (0, pw - w), (0, 0)))
        return padded[bidx, ay, ax, cidx]

    def initialize(self, device=None, **kwargs):
        self.create_output()
        self.input_offset.initialize(device)
        super().initialize(device=device, **kwargs)

    def _select(self, win):
        """(output, offsets|None) from windows; subclasses implement."""
        raise NotImplementedError

    def _reduce_window(self, x, init, op):
        """TPU-native pooling: one ``lax.reduce_window`` (XLA lowers its
        gradient to select_and_scatter) — the ``windows()`` gather is kept
        only where offsets must be RECORDED (unit path / stochastic /
        Depooling); as a forward op inside the fused step the gather was
        ~50x slower than reduce_window on real v5e hardware (bench r3)."""
        from jax import lax

        _, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
        return lax.reduce_window(
            x, x.dtype.type(init), op,
            window_dimensions=(1, self.ky, self.kx, 1),
            window_strides=(1, sy, sx, 1),
            padding=((0, 0), (0, ph - h), (0, pw - w), (0, 0)))

    def apply(self, params, x):
        y, _ = self._select(self.windows(x))
        return y

    def run(self):
        if self._compiled is None:
            import jax
            self._compiled = jax.jit(
                lambda x: self._select(self.windows(x)))
        y, off = self._compiled(self.input.devmem)
        self.output.devmem = y
        if off is not None:
            self.input_offset.devmem = off


class MaxPooling(PoolingBase):
    PAD_VALUE = -np.inf

    def _select(self, win):
        import jax.numpy as jnp

        off = jnp.argmax(win, axis=-1)
        y = jnp.take_along_axis(win, off[..., None], axis=-1)[..., 0]
        return y, off

    def apply(self, params, x):
        from jax import lax

        return self._reduce_window(x, -np.inf, lax.max)


class MaxAbsPooling(PoolingBase):
    """Selects the element with the largest |value| but outputs its signed
    value (reference semantics)."""

    PAD_VALUE = 0.0

    def _select(self, win):
        import jax.numpy as jnp

        off = jnp.argmax(jnp.abs(win), axis=-1)
        y = jnp.take_along_axis(win, off[..., None], axis=-1)[..., 0]
        return y, off

    def apply(self, params, x):
        import jax.numpy as jnp
        from jax import lax

        mx = self._reduce_window(x, -np.inf, lax.max)
        mn = self._reduce_window(x, np.inf, lax.min)
        # signed value with the larger magnitude; on an exact tie the
        # positive branch wins (the gather path's argmax(|.|) picks the
        # first window position instead — indistinguishable on real data)
        return jnp.where(-mn > mx, mn, mx)


class AvgPooling(PoolingBase):
    PAD_VALUE = 0.0

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self._counts: Optional[np.ndarray] = None   # real elems per window

    def window_counts(self):
        """(OH, OW) count of real (non-pad) elements in each window — edge
        windows are partial; the reference averaged over real elements."""
        if self._counts is None:
            b, h, w, c, oh, ow, sy, sx, ph, pw = self._window_geometry()
            ones = np.zeros((ph, pw), np.float32)
            ones[:h, :w] = 1.0
            counts = np.zeros((oh, ow), np.float32)
            for oy in range(oh):
                for ox in range(ow):
                    counts[oy, ox] = ones[oy * sy:oy * sy + self.ky,
                                          ox * sx:ox * sx + self.kx].sum()
            self._counts = counts
        return self._counts

    def _select(self, win):
        import jax.numpy as jnp

        counts = jnp.asarray(self.window_counts())
        y = jnp.sum(win, axis=-1) / counts[None, :, :, None]
        return y, None

    def apply(self, params, x):
        import jax.numpy as jnp
        from jax import lax

        s = self._reduce_window(x, 0.0, lax.add)
        counts = jnp.asarray(self.window_counts(), x.dtype)
        return s / counts[None, :, :, None]


class StochasticPoolingBase(PoolingBase):
    PAD_VALUE = 0.0

    def __init__(self, workflow=None, name=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self._step_counter = 0
        #: eval-time behavior: deterministic expectation (weighted mean)
        self.minibatch_class = TRAIN               # link from loader

    def _weights_from(self, win):
        raise NotImplementedError

    def _select_stochastic(self, win, key):
        import jax
        import jax.numpy as jnp

        p = self._weights_from(win)
        total = jnp.sum(p, axis=-1, keepdims=True)
        # all-zero window -> pick position 0 (matches reference kernels)
        safe = jnp.where(total > 0, p / jnp.maximum(total, 1e-30),
                         jnp.zeros_like(p).at[..., 0].set(1.0))
        off = jax.random.categorical(key, jnp.log(jnp.maximum(safe, 1e-30)),
                                     axis=-1)
        y = jnp.take_along_axis(win, off[..., None], axis=-1)[..., 0]
        return y, off

    def _select_expected(self, win):
        """Deterministic eval-time output: probability-weighted mean
        (the reference's testing-mode behavior)."""
        import jax.numpy as jnp

        p = self._weights_from(win)
        total = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        y = jnp.sum(win * (p / total), axis=-1)
        off = jnp.argmax(p, axis=-1)
        return y, off

    def run(self):
        import jax

        if self._compiled is None:
            self._compiled = (
                jax.jit(lambda x, k: self._select_stochastic(
                    self.windows(x), k)),
                jax.jit(lambda x: self._select_expected(self.windows(x))))
        train = (int(self.minibatch_class) == TRAIN)
        if train:
            key = prng.get(self.name).jax_key(self._step_counter)
            self._step_counter += 1
            y, off = self._compiled[0](self.input.devmem, key)
        else:
            y, off = self._compiled[1](self.input.devmem)
        self.output.devmem = y
        self.input_offset.devmem = off


class StochasticPooling(StochasticPoolingBase):
    """Position sampled ∝ max(value, 0) (reference samples over positive
    activations)."""

    def _weights_from(self, win):
        import jax.numpy as jnp

        return jnp.maximum(win, 0.0)


class StochasticAbsPooling(StochasticPoolingBase):
    def _weights_from(self, win):
        import jax.numpy as jnp

        return jnp.abs(win)
