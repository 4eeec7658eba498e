"""The state-space mixer's ops (ISSUE 34), ``znicz_tpu/ops/ssm.py``, at a
small size on the CPU: the chunked scan and its written-out backward pass
against the plain reference's RECURRENCE
(``benchmark/references/nemotron.py``: a ``lax.scan`` over the positions)
and autodiff through it — forward and the gradient of every input, at
rows of 1, 2 and 5 chunks of 16 and two rows a batch —, the causal
depthwise convolution against a shifted sum, the gated grouped norm
against numpy, and what the scan notes while it is traced."""

import functools
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from benchmark import spec                                  # noqa: E402
from znicz_tpu.ops import ssm                               # noqa: E402

ref = spec.load_module("references", "nemotron")
CHUNK, HEADS, DIM, GROUPS, STATE = 16, 4, 8, 2, 16
INPUTS = ("x", "dt", "a_log", "b", "c", "d")


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def operands(seq: int, seed: int = 0, batch: int = 2):
    rng = np.random.default_rng(seed)

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    return {"x": f32(rng.normal(size=(batch, seq, HEADS, DIM))),
            "dt": f32(rng.uniform(0.001, 0.5, size=(batch, seq, HEADS))),
            "a_log": f32(np.log(rng.uniform(1, 16, size=(HEADS,)))),
            "b": f32(rng.normal(size=(batch, seq, GROUPS, STATE))),
            "c": f32(rng.normal(size=(batch, seq, GROUPS, STATE))),
            "d": f32(rng.normal(size=(HEADS,)))}


def recurrence(x, dt, a_log, b, c, d):
    return ref.recurrence(x, dt, -jnp.exp(a_log), b, c, d)


@functools.lru_cache(maxsize=None)
def both(chunks: int):
    """``(outputs, gradients)`` of the chunked scan and of the
    recurrence at ``chunks`` chunks a row, float32 products."""
    ops = operands(chunks * CHUNK, seed=chunks)
    weight = jnp.asarray(np.random.default_rng(9).normal(
        size=ops["x"].shape), jnp.float32)
    out, grads = [], []
    with jax.default_matmul_precision("highest"):
        for fn in (lambda *a: ssm.chunked_scan(*a, CHUNK), recurrence):
            args = [ops[k] for k in INPUTS]
            out.append(fn(*args))
            grads.append(dict(zip(INPUTS, jax.grad(
                lambda *a: jnp.sum(fn(*a) * weight),
                argnums=range(6))(*args))))
    return out, grads


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_chunked_scan_is_the_recurrence(chunks):
    (got, want), _ = both(chunks)
    assert got.shape == (2, chunks * CHUNK, HEADS, DIM)
    assert rel(got, want) < 1e-5
    assert ssm.STATS == {"way": "composed", "chunks": chunks}


@pytest.mark.parametrize("name", INPUTS)
@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_the_written_backward_is_the_recurrences_gradient(chunks, name):
    _, (got, want) = both(chunks)
    assert got[name].shape == want[name].shape
    assert float(jnp.abs(want[name]).max()) > 0
    assert rel(got[name], want[name]) < 1e-4, name


def test_a_row_the_chunk_does_not_divide_is_padded_with_still_positions():
    ops = operands(70, seed=3)
    args = [ops[k] for k in INPUTS]
    with jax.default_matmul_precision("highest"):
        got = ssm.chunked_scan(*args, CHUNK)
        assert rel(got, recurrence(*args)) < 1e-5
        grad = jax.grad(lambda x: jnp.sum(ssm.chunked_scan(
            x, *args[1:], CHUNK)))(args[0])
        want = jax.grad(lambda x: jnp.sum(recurrence(x, *args[1:])))(args[0])
    assert got.shape == ops["x"].shape and rel(grad, want) < 2e-5
    assert ssm.STATS["chunks"] == 5


def test_the_scan_is_causal_and_its_state_crosses_chunks():
    ops = operands(3 * CHUNK, seed=4)
    # slow decays, so that what a chunk hands on is still there two on
    ops = dict(ops, dt=ops["dt"] * 0.02, a_log=jnp.zeros_like(ops["a_log"]))
    args = [ops[k] for k in INPUTS]
    got = ssm.chunked_scan(*args, CHUNK)
    t = CHUNK + 3
    later = dict(ops, x=ops["x"].at[:, t + 1:].add(1.0))
    moved = ssm.chunked_scan(*[later[k] for k in INPUTS], CHUNK)
    np.testing.assert_allclose(np.asarray(moved)[:, :t + 1],
                               np.asarray(got)[:, :t + 1], atol=1e-6)
    # the first chunk reaches the last one through the entry states
    early = dict(ops, x=ops["x"].at[:, :CHUNK].add(1.0))
    reached = ssm.chunked_scan(*[early[k] for k in INPUTS], CHUNK)
    assert float(jnp.abs(reached - got)[:, 2 * CHUNK:].max()) > 1e-4


def test_bfloat16_operands_keep_float32_decays_and_states():
    ops = operands(2 * CHUNK, seed=5)
    low = {k: (v.astype(jnp.bfloat16) if k in ("x", "b", "c") else v)
           for k, v in ops.items()}
    got = ssm.chunked_scan(*[low[k] for k in INPUTS], CHUNK)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*[low[k].astype(jnp.float32) for k in INPUTS])
    assert rel(got.astype(jnp.float32), want) < 2e-2
    grads = jax.grad(lambda *a: jnp.sum(ssm.chunked_scan(
        *a, CHUNK).astype(jnp.float32)), argnums=range(6))(
            *[low[k] for k in INPUTS])
    assert [g.dtype for g in grads] == [low[k].dtype for k in INPUTS]


def test_heads_have_to_divide_over_the_groups():
    ops = operands(CHUNK)
    with pytest.raises(ValueError, match="groups"):
        ssm.chunked_scan(ops["x"][:, :, :3], ops["dt"][:, :, :3],
                         ops["a_log"][:3], ops["b"], ops["c"],
                         ops["d"][:3], CHUNK)


@pytest.mark.parametrize("taps", [2, 4])
def test_the_causal_convolution_is_a_shifted_sum(taps):
    rng = np.random.default_rng(taps)
    x = rng.normal(size=(2, 12, 6))
    w, b = rng.normal(size=(taps, 6)), rng.normal(size=(6,))
    want = np.zeros_like(x)
    for t in range(12):
        want[:, t] = b + sum(w[k] * x[:, t - (taps - 1 - k)]
                             for k in range(taps) if t - (taps - 1 - k) >= 0)
    got = ssm.causal_conv(*(jnp.asarray(v, jnp.float32) for v in (x, w, b)))
    assert rel(got, want) < 1e-6
    assert rel(ref.convolved(*(jnp.asarray(v, jnp.float32)
                               for v in (x, w, b))), want) < 1e-6
    later = x.copy()
    later[:, 8:] += 1.0
    moved = ssm.causal_conv(*(jnp.asarray(v, jnp.float32)
                              for v in (later, w, b)))
    np.testing.assert_array_equal(np.asarray(moved)[:, :8],
                                  np.asarray(got)[:, :8])


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_gated_norm_is_an_rms_norm_over_each_group(groups):
    rng = np.random.default_rng(groups)
    y, z = rng.normal(size=(2, 5, 16)), rng.normal(size=(2, 5, 16))
    gain = 1 + 0.1 * rng.normal(size=(16,))
    g = (y * z / (1 + np.exp(-z))).reshape(2, 5, groups, 16 // groups)
    want = (g / np.sqrt((g * g).mean(-1, keepdims=True) + 1e-5)).reshape(
        2, 5, 16) * gain
    got = ssm.gated_norm(*(jnp.asarray(v, jnp.float32)
                           for v in (y, z, gain)), groups, 1e-5)
    assert rel(got, want) < 1e-6
    low = ssm.gated_norm(jnp.asarray(y, jnp.bfloat16),
                         jnp.asarray(z, jnp.bfloat16),
                         jnp.asarray(gain, jnp.float32), groups, 1e-5)
    assert low.dtype == jnp.bfloat16 and rel(
        low.astype(jnp.float32), want) < 2e-2
