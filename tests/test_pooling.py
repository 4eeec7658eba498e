"""Pooling forward/backward numerics incl. partial edge windows, offset
recording, and the stochastic variants' mask-reuse contract."""

import numpy as np

from znicz_tpu.gd_pooling import (
    GDAvgPooling,
    GDMaxPooling,
    GDStochasticPooling,
)
from znicz_tpu.memory import Array
from znicz_tpu.pooling import (
    AvgPooling,
    MaxAbsPooling,
    MaxPooling,
    StochasticPooling,
)


def test_max_pooling_matches_numpy():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(2, 6, 6, 3)).astype(np.float32)
    p = MaxPooling(name="mp", kx=2, ky=2)
    p.input = Array(x)
    p.initialize(device=None)
    p.run()
    got = np.array(p.output.map_read())
    want = x.reshape(2, 3, 2, 3, 2, 3).max(axis=(2, 4))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_max_pooling_partial_edge_windows():
    """5x5 input, 2x2 stride-2 pool -> 3x3 output with partial edges."""
    x = np.arange(25, dtype=np.float32).reshape(1, 5, 5, 1)
    p = MaxPooling(name="mpe", kx=2, ky=2)
    p.input = Array(x)
    p.initialize(device=None)
    assert p.output_shape_for((1, 5, 5, 1)) == (1, 3, 3, 1)
    p.run()
    got = np.array(p.output.map_read())[0, :, :, 0]
    want = np.array([[6, 8, 9], [16, 18, 19], [21, 23, 24]], np.float32)
    np.testing.assert_allclose(got, want)


def test_maxabs_pooling_keeps_sign():
    x = np.array([[[[1.0], [-5.0]], [[2.0], [3.0]]]], np.float32)
    p = MaxAbsPooling(name="map", kx=2, ky=2)
    p.input = Array(x)
    p.initialize(device=None)
    p.run()
    assert float(np.array(p.output.map_read()).reshape(())) == -5.0


def test_avg_pooling_partial_window_counts():
    x = np.ones((1, 3, 3, 1), np.float32)
    p = AvgPooling(name="ap", kx=2, ky=2)
    p.input = Array(x)
    p.initialize(device=None)
    p.run()
    got = np.array(p.output.map_read())[0, :, :, 0]
    # full windows avg 1; partial edge windows must also avg 1 (divide by
    # real count, not kx*ky)
    np.testing.assert_allclose(got, np.ones((2, 2)), rtol=1e-6)


def test_gd_max_pooling_routes_err_to_argmax():
    rng = np.random.default_rng(6)
    x = rng.normal(size=(2, 4, 4, 2)).astype(np.float32)
    p = MaxPooling(name="gmp", kx=2, ky=2)
    p.input = Array(x)
    p.initialize(device=None)
    p.run()
    err = rng.normal(size=(2, 2, 2, 2)).astype(np.float32)
    gd = GDMaxPooling(name="gmpgd", forward=p)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    gd.run()
    got = np.array(gd.err_input.map_read())
    # oracle: scatter err to argmax positions
    want = np.zeros_like(x)
    for b in range(2):
        for oy in range(2):
            for ox in range(2):
                for c in range(2):
                    win = x[b, oy*2:oy*2+2, ox*2:ox*2+2, c]
                    dy, dx = np.unravel_index(np.argmax(win), (2, 2))
                    want[b, oy*2+dy, ox*2+dx, c] += err[b, oy, ox, c]
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_gd_avg_pooling_is_vjp_of_forward():
    rng = np.random.default_rng(8)
    x = rng.normal(size=(1, 4, 4, 1)).astype(np.float32)
    p = AvgPooling(name="gap", kx=2, ky=2)
    p.input = Array(x)
    p.initialize(device=None)
    p.run()
    err = rng.normal(size=(1, 2, 2, 1)).astype(np.float32)
    gd = GDAvgPooling(name="gapgd", forward=p)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    gd.run()
    got = np.array(gd.err_input.map_read())
    want = np.repeat(np.repeat(err, 2, axis=1), 2, axis=2) / 4.0
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_stochastic_pooling_mask_reuse_and_eval_mode():
    rng = np.random.default_rng(9)
    x = np.abs(rng.normal(size=(2, 4, 4, 2))).astype(np.float32)
    p = StochasticPooling(name="sp", kx=2, ky=2)
    p.input = Array(x)
    p.minibatch_class = 2                 # TRAIN
    p.initialize(device=None)
    p.run()
    off = np.array(p.input_offset.map_read())
    out = np.array(p.output.map_read())
    # sampled offsets select actual window values
    for b in range(2):
        for oy in range(2):
            for ox in range(2):
                for c in range(2):
                    win = x[b, oy*2:oy*2+2, ox*2:ox*2+2, c].reshape(-1)
                    assert out[b, oy, ox, c] == win[off[b, oy, ox, c]]
    # backward scatters via the SAME offsets (mask reuse, not resampled)
    err = rng.normal(size=out.shape).astype(np.float32)
    gd = GDStochasticPooling(name="spgd", forward=p)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    gd.run()
    got = np.array(gd.err_input.map_read())
    want = np.zeros_like(x)
    for b in range(2):
        for oy in range(2):
            for ox in range(2):
                for c in range(2):
                    dy, dx = divmod(int(off[b, oy, ox, c]), 2)
                    want[b, oy*2+dy, ox*2+dx, c] += err[b, oy, ox, c]
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # eval mode: deterministic expectation, two runs agree
    p.minibatch_class = 1
    p.run()
    a = np.array(p.output.map_read()).copy()
    p.run()
    b2 = np.array(p.output.map_read())
    np.testing.assert_allclose(a, b2)
    # expectation oracle for one window
    win = x[0, 0:2, 0:2, 0].reshape(-1)
    wsum = win.sum()
    np.testing.assert_allclose(a[0, 0, 0, 0], float((win * win).sum() / wsum),
                               rtol=1e-5)
