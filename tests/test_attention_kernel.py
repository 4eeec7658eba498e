"""The attention core's Pallas kernels (ISSUEs 28, 29), interpreted on the
CPU: output and gradients against materialised scores and against the
composed path, which pairs a schedule visits, which keys a window reads,
the path under ``jax.checkpoint`` in a ``lax.scan``, the predicate that
chooses the path, the rotary form that joins its halves once, the
counters it leaves in ``FusedTrainer.stats``, the scope its calls carry,
and what a process pays to set the kernels up: one trace a kernel and
context, one Mosaic body a kernel and program, however many layers.
"""

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
from znicz_tpu import decoder                               # noqa: E402
from znicz_tpu.core.config import root                      # noqa: E402
from znicz_tpu.ops import attention, attention_pallas       # noqa: E402

ref = spec.load_module("references", "laguna")
driver = spec.load_module("drivers", "train_tokens")

TILES = (128, 128)
WINDOWS = pytest.mark.parametrize(
    "window", [None, 48, 128, 256],
    ids=["full", "under-a-tile", "one-tile", "whole-sequence"])
GROUPS = pytest.mark.parametrize("heads,kv", [(4, 2), (6, 2), (8, 8)],
                                 ids=["pairs", "threes", "mha"])


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def qkv(heads, kv, t=256, d=128, b=1, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, t, heads, d)),
            jax.random.normal(keys[1], (b, t, kv, d)),
            jax.random.normal(keys[2], (b, t, kv, d)),
            jax.random.normal(keys[3], (b, t, heads, d)))


def core(q, k, v, window, tiles=TILES):
    """The core as ``blocked_attention`` runs it; ``tiles=None`` is the
    composed path."""
    return attention._core(q, k, v, window, 128, tiles)


def out_and_grads(fn, q, k, v, ct):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(ct)


# -- the kernels against two second opinions ---------------------------------------


@WINDOWS
@GROUPS
def test_kernel_matches_materialised_scores(window, heads, kv):
    q, k, v, ct = qkv(heads, kv)
    got = out_and_grads(lambda *a: core(*a, window), q, k, v, ct)
    want = out_and_grads(lambda *a: ref.attention(*a, window, 256),
                         q, k, v, ct)
    for g, w, limit in zip(got, want, (2e-6, 5e-6, 5e-6, 5e-6)):
        assert rel(g, w) < limit


@WINDOWS
@GROUPS
def test_kernel_matches_the_composed_path(window, heads, kv):
    q, k, v, ct = qkv(heads, kv, seed=1)
    got = out_and_grads(lambda *a: core(*a, window), q, k, v, ct)
    want = out_and_grads(lambda *a: core(*a, window, None), q, k, v, ct)
    for g, w in zip(got, want):
        assert rel(g, w) < 5e-6


@pytest.mark.parametrize("tiles", [(256, 128), (128, 256)],
                         ids=["wide-queries", "wide-keys"])
@pytest.mark.parametrize("window", [None, 200], ids=["full", "window200"])
def test_query_and_key_tiles_may_differ(window, tiles):
    q, k, v, ct = qkv(6, 2, t=512, seed=2)
    got = out_and_grads(lambda *a: core(*a, window, tiles), q, k, v, ct)
    want = out_and_grads(lambda *a: ref.attention(*a, window, 512),
                         q, k, v, ct)
    for g, w in zip(got, want):
        assert rel(g, w) < 5e-6


@pytest.mark.parametrize("group,tile,window", [
    (6, 512, None), (6, 256, None), (8, 256, 512), (8, 512, 512),
    (8, 256, 300), (6, 128, 200), (8, 128, 1024)],
    ids=["6-full-512", "6-full-256", "8-window-two-tiles",
         "8-window-one-tile", "8-window-no-tile-divides",
         "6-window-under-two-tiles", "8-window-of-it-all"])
def test_the_cells_groups_at_the_cells_tiles(group, tile, window):
    """The cell's groups (6 query heads a KV head in full layers, 8 in
    window layers) through the loop over the group, at the tiles the
    predicate picks on the chip and the ones it measured against, windows
    a tile does and does not divide."""
    q, k, v, ct = qkv(group, 1, t=1024, seed=7)
    got = out_and_grads(lambda *a: core(*a, window, (tile, tile)),
                        q, k, v, ct)
    want = out_and_grads(lambda *a: ref.attention(*a, window, 512),
                         q, k, v, ct)
    for g, w in zip(got, want):
        assert rel(g, w) < 5e-6


def test_kernel_in_bfloat16_is_as_close_as_the_composed_path():
    """The chip's dtype: both paths round the same operands; neither is
    further from float32 than bf16 allows."""
    q, k, v, ct = qkv(6, 2, seed=3)
    want = out_and_grads(lambda *a: ref.attention(*a, 100, 256), q, k, v, ct)
    low = [t.astype(jnp.bfloat16) for t in (q, k, v, ct)]
    for tiles in (TILES, None):
        got = out_and_grads(lambda *a: core(*a, 100, tiles), *low)
        assert all(g.dtype == jnp.bfloat16 for g in got)
        for g, w in zip(got, want):
            assert rel(g.astype(jnp.float32), w) < 2e-2


# -- what a schedule visits -----------------------------------------------------------


@pytest.mark.parametrize("seq,bq,bk,window", [
    (1024, 128, 128, None), (1024, 256, 128, None), (1024, 128, 256, 300),
    (1024, 128, 128, 512), (1024, 256, 256, 512), (2048, 512, 512, 512),
    (512, 128, 128, 1), (512, 128, 128, 129)])
@pytest.mark.parametrize("by_key", [False, True], ids=["by-query", "by-key"])
def test_a_schedule_visits_the_tiles_that_hold_an_admitted_pair(
        seq, bq, bk, window, by_key):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    admitted = (j <= i) & (True if window is None else i - j < window)
    holds = admitted.reshape(seq // bq, bq, seq // bk, bk).any(axis=(1, 3))
    qi, kj, edge = attention_pallas._schedule(seq, bq, bk, window, by_key)
    pairs = list(zip(qi.tolist(), kj.tolist()))
    assert len(set(pairs)) == len(pairs)                # each once
    assert set(pairs) == set(zip(*np.nonzero(holds)))   # those and no other
    run = kj if by_key else qi
    # a run's pairs are consecutive, its first and last marked
    assert (np.diff(run) >= 0).all()
    first = np.r_[True, run[1:] != run[:-1]]
    last = np.r_[run[1:] != run[:-1], True]
    np.testing.assert_array_equal(edge & 1, first)
    np.testing.assert_array_equal(edge >> 1, last)


def test_small_tiles_bring_a_window_layer_near_its_admitted_pairs():
    """The tile choice is part of the window layers' gain: at the cell's
    shapes tiles of the window visit twice the admitted pairs, the chosen
    ones 1.5 times; a full layer keeps the largest tile."""
    seq, window = 8192, 512
    admitted = sum(min(i + 1, window) for i in range(seq))

    def visited(tile):
        return len(attention_pallas._schedule(
            seq, tile, tile, window, False)[0]) * tile * tile

    assert visited(512) / admitted > 1.9
    chosen = attention_pallas.pick_tiles(seq, window)
    assert chosen[0] == chosen[1] and visited(chosen[0]) / admitted < 1.55
    assert attention_pallas.pick_tiles(seq, None) == (512, 512)


def test_kernel_reads_only_admitted_key_tiles():
    """The composed path's poisoned-key test, for the kernels: keys
    before every window of the queries compared may hold NaN, forward
    and backward."""
    q, k, v, ct = qkv(8, 2, t=512, seed=4)
    poisoned = k.at[:, :128].set(jnp.nan)
    got = out_and_grads(lambda *a: core(*a, 128), q, poisoned, v, ct)
    want = out_and_grads(lambda *a: core(*a, 128), q, k, v, ct)
    for g, w in zip(got, want):                 # out, dq, dk, dv
        g, w = np.asarray(g)[:, 256:], np.asarray(w)[:, 256:]
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)


def test_kernel_under_checkpoint_in_a_scan_as_the_trainer_runs_it():
    q, k, v, ct = qkv(4, 2, seed=5)

    def loss(tiles, q, k, v):
        def layer(h, _):
            h = h + jax.checkpoint(
                lambda h: core(h, k, v, 100, tiles))(h)
            return h, None

        return jnp.sum(jax.lax.scan(layer, q, None, length=2)[0] * ct)

    got = jax.jit(jax.grad(lambda *a: loss(TILES, *a), (0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: loss(None, *a), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert rel(g, w) < 5e-6


# -- the rotary form that feeds it ---------------------------------------------------


def rope_joined_in_float32(x, cos, sin):
    """``apply_rope`` as it was before PR 28: ``[-b, a]`` joined in
    float32, then the multiply-add, then the cast."""
    r = cos.shape[-1]
    rot = x[..., :r].astype(jnp.float32)
    a, b = rot[..., :r // 2], rot[..., r // 2:]
    turned = jnp.concatenate([-b, a], axis=-1)
    out = rot * cos[None, :, None, :] + turned * sin[None, :, None, :]
    out = out.astype(x.dtype)
    return out if r == x.shape[-1] else jnp.concatenate(
        [out, x[..., r:]], axis=-1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("dim,rotary", [(128, 128), (128, 64), (16, 8)],
                         ids=["whole-head", "half-head", "tiny"])
def test_rope_joins_its_halves_once_and_gives_the_same_bits(dim, rotary,
                                                            dtype):
    cos, sin = attention.rope_tables(96, rotary, 10000.0)
    x = jax.random.normal(jax.random.PRNGKey(8),
                          (2, 96, 3, dim)).astype(dtype)
    got = attention.apply_rope(x, cos, sin)
    assert got.dtype == x.dtype and got.shape == x.shape
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(rope_joined_in_float32(x, cos, sin).astype(jnp.float32)))
    # no float32 tensor of the rotated width is joined
    joins = [eqn for eqn in jax.make_jaxpr(attention.apply_rope)(
        x, cos, sin).eqns if eqn.primitive.name == "concatenate"]
    assert len(joins) == 1 and joins[0].outvars[0].aval.dtype == x.dtype


# -- which path runs ------------------------------------------------------------------


@pytest.mark.parametrize("backend,dim,seq,dtype,window,want", [
    ("tpu", 128, 8192, "bfloat16", None, (512, 512)),
    ("tpu", 128, 8192, "bfloat16", 512, (256, 256)),
    ("tpu", 128, 8192, "bfloat16", 128, (128, 128)),
    ("tpu", 128, 8192, "bfloat16", 8192, (512, 512)),   # a window of it all
    ("tpu", 256, 1024, "bfloat16", None, (512, 512)),
    ("tpu", 128, 384, "bfloat16", None, (128, 128)),
    ("tpu", 128, 8200, "bfloat16", None, None),         # no tile divides
    ("tpu", 16, 64, "bfloat16", None, None),            # the tiny preset
    ("tpu", 64, 8192, "bfloat16", None, None),
    ("tpu", 128, 8192, "float32", None, None),
    ("cpu", 128, 8192, "bfloat16", None, None),
    ("gpu", 128, 8192, "bfloat16", 512, None),
])
def test_the_predicate_chooses_kernel_or_composed(backend, dim, seq, dtype,
                                                  window, want):
    got = attention.core_tiles(backend, (2, seq, 48, dim), 8,
                               jnp.dtype(dtype), window)
    assert got == want


def pallas_calls(jaxpr, outer=""):
    """The name stacks of every ``pallas_call`` equation under ``jaxpr``,
    each behind the stacks of the equations that hold it."""
    from jax._src.core import jaxprs_in_params

    found = []
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            found.append(stack)
        for sub in jaxprs_in_params(eqn.params):
            found += pallas_calls(sub, stack)
    return found


def decoder_layer(dim, dtype=jnp.float32, **attrs):
    """A decoder layer (``attrs`` laid over it: what it asks of
    rematerialisation) and the jaxpr of its value-and-gradient under
    ``jax.checkpoint`` as ``forward_pass`` wraps a unit that asks."""
    from znicz_tpu.parallel.fused import rematerialised

    layer = decoder.DecoderLayer(name="layer1", heads=4, kv_heads=2,
                                 head_dim=dim, window=100, dense_width=64)
    layer.hidden = 64
    for key, value in attrs.items():
        setattr(layer, key, value)
    keys = jax.random.split(jax.random.PRNGKey(6), 16)
    params = {name: (jnp.ones(shape, dtype) if std is None else
                     (jax.random.normal(key, shape) * std).astype(dtype))
              for key, (name, (shape, std)) in zip(
                  keys, layer.param_shapes().items())}
    x = jax.random.normal(keys[-1], (1, 256, 64)).astype(dtype)

    def value(params, x):
        with jax.named_scope(layer.name):       # as ``forward_pass`` does
            return jnp.sum(rematerialised(layer, layer.apply)(params, x))

    return layer, jax.make_jaxpr(jax.value_and_grad(value))(params, x)


def test_the_cpu_backend_runs_the_composed_path():
    layer, jaxpr = decoder_layer(128, jnp.bfloat16)
    assert pallas_calls(jaxpr.jaxpr) == []
    assert layer.core_in_kernels is False
    stats = decoder.DecoderLayer.run_stats([layer])
    assert (stats["attn_cores_kernel"], stats["attn_cores_composed"]) == (0, 1)


def test_the_kernels_calls_carry_the_scope(monkeypatch):
    """The forward kernel ONCE and both backward kernels sit under
    ``attn_core`` in the layer's value-and-gradient, each behind its
    jitted entry, and none under ``rematted_computation``: the layer keeps
    its core's output and log-sum-exp across rematerialisation (ISSUE
    33), so the recomputed forward pass holds no kernel.  The stacks are
    what ``benchmark/reduce/inner.py`` ``tag_of`` reads from ``op_name``,
    and so what ``attention_ms_per_step`` and ``attention_roofline``
    time."""
    for module in (attention, decoder):
        monkeypatch.setattr(module, "core_tiles", lambda *a: TILES)
    layer, jaxpr = decoder_layer(128)
    calls = pallas_calls(jaxpr.jaxpr)
    assert layer.core_in_kernels is True
    assert layer.remat_kept == attention.CORE_KEEPS
    stats = decoder.DecoderLayer.run_stats([layer])
    assert (stats["attn_cores_kernel"], stats["attn_cores_composed"]) == (1, 0)
    assert stats["attn_cores_kept"] == 1
    for kernel in ("attn_core_forward", "attn_core_dq", "attn_core_dkv"):
        stacks = [s for s in calls if s.endswith(kernel)]
        assert len(stacks) == 1, (kernel, calls)
        assert all("/attn_core/" in s for s in stacks)
    assert not any("rematted_computation" in s for s in calls)
    inner = spec.load_module("reduce", "inner")
    for name, want in (
            ("jit(train)/jvp(layer1)/attn_core/jit(forward)/"
             "attn_core_forward/pallas_call", "forward"),
            ("jit(train)/transpose(jvp(layer1))/jvp(layer1)/checkpoint/"
             "rematted_computation/attn_core/jit(forward)/attn_core_forward/"
             "pallas_call", "recompute"),
            ("jit(train)/transpose(jvp(layer1))/jvp(layer1)/checkpoint/"
             "attn_core/jit(dkv)/attn_core_dkv/pallas_call", "backward")):
        assert inner.tag_of(name) == ("layer1", "attn_core", want)


def test_a_unit_that_names_nothing_keeps_its_input_alone(monkeypatch):
    """``remat = True`` with no ``remat_keeps`` is the bare
    ``jax.checkpoint``: the whole forward pass runs again on the way back,
    the core's forward kernel under ``rematted_computation`` with it, and
    no core is counted as kept."""
    for module in (attention, decoder):
        monkeypatch.setattr(module, "core_tiles", lambda *a: TILES)
    layer, jaxpr = decoder_layer(128, remat_keeps=())
    calls = pallas_calls(jaxpr.jaxpr)
    forward = [s for s in calls if s.endswith("attn_core_forward")]
    assert len(forward) == 2 and len(calls) == 4
    assert sum("rematted_computation" in s for s in forward) == 1
    assert layer.remat_kept == ()
    assert decoder.DecoderLayer.run_stats([layer])["attn_cores_kept"] == 0


def test_the_trainer_counts_the_cores_by_path(tmp_path, restore_root):
    """``FusedTrainer.stats`` after a run of the tiny preset: head size 16
    on the CPU, so no core in the kernels and as many composed as the
    model has decoder layers, no kernel traced or lowered — noted while
    the programs were traced, booked when the run ends."""
    before = attention.kernel_counts()
    cell = spec.Cell(spec.load(), "laguna-train-8k")
    root.common.dirs.snapshots = str(tmp_path)
    built = driver.build(cell, 11, True)
    trainer = built.trainer
    layers = [f for f in built.wf.forwards
              if isinstance(f, decoder.DecoderLayer)]
    assert "attn_cores_composed" not in trainer.stats
    built.wf.decision.max_epochs = 1
    trainer.run()
    assert len(layers) == 5
    assert trainer.stats["attn_cores_kernel"] == 0
    assert trainer.stats["attn_cores_composed"] == len(layers)
    assert {k: trainer.stats[k] for k in before} == before


# -- what a process pays to set the kernels up --------------------------------------

#: the cell's layers in small: two kinds of core (full; window), five
#: layers, each a ``custom_vjp`` under ``jax.checkpoint`` in its own
#: scope, its output and log-sum-exp kept as a decoder layer keeps them
STACK = (None, 100, 100, 100, None)


def stack_of_cores(q, k, v, grad: bool):
    """A program over ``STACK`` as the trainer builds a step (``grad``)
    or an evaluation: a fresh function object each call, as each of the
    trainer's programs is."""
    def value(q, k, v):
        h = q
        for n, window in enumerate(STACK):
            with jax.named_scope(f"layer{n}"):
                h = h + jax.checkpoint(
                    lambda h, window=window: core(h, k, v, window),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        *attention.CORE_KEEPS))(h)
        return jnp.sum(h)

    return jax.jit(jax.grad(value, (0, 1, 2)) if grad else value)


def mosaic_bodies(lowered):
    """``(distinct, written)`` kernel bodies in a lowered module's text
    (interpret mode off: each ``pallas_call`` is one ``tpu_custom_call``
    whose ``backend_config`` holds its Mosaic module)."""
    import re

    text = lowered.as_text()
    return (len(set(re.findall(r'backend_config = "([^"]*)"', text))),
            text.count("@tpu_custom_call"))


@pytest.mark.parametrize("programs", [
    ("train",), ("train", "train"), ("train", "eval", "train")],
    ids=["one-step", "the-step-twice", "step-evaluation-step"])
def test_a_process_traces_a_kernel_once_and_a_program_lowers_it_once(
        programs, monkeypatch):
    """Kinds of core x (forward, dq, dk/dv) is what a process traces and
    what one program lowers to Mosaic, however many layers, passes and
    programs there are: five layers x (forward, dq, dk/dv) calls a step
    go through 2 x 3 bodies (the recomputed forward pass calls none: the
    output and the log-sum-exp are kept).  The forward kernel is traced
    once more a kind where jax's trace context differs (under
    ``jax.checkpoint`` and outside it): 2 x 4 traces a process at most.
    The lowered text WRITES the forward body once a layer, not once a
    kind — ``jax.checkpoint``'s partial evaluation under a policy gives
    each layer's jitted ``forward`` a jaxpr of its own, and each inlines
    the one cached lowering — and the backward bodies once a kind."""
    from znicz_tpu import backends

    monkeypatch.setattr(backends, "pallas_interpret", lambda: False)
    jax.clear_caches()                  # the process's caches, as at start
    kinds = len(set(STACK))
    q, k, v, _ = qkv(4, 2)
    start = attention.kernel_counts()

    def since():
        now = attention.kernel_counts()
        return (now["attn_kernel_traces"] - start["attn_kernel_traces"],
                now["attn_kernel_lowerings"]
                - start["attn_kernel_lowerings"])

    lowered_before = 0
    for n, program in enumerate(programs):
        traced = stack_of_cores(q, k, v, program == "train").trace(q, k, v)
        traces, _ = since()
        assert kinds * 3 <= traces <= kinds * 4, (program, traces)
        if n:                           # a later program traces no kernel
            assert traces == traces_before, (program, traces)
        traces_before = traces
        lowered = traced.lower(lowering_platforms=("tpu",))
        lowerings = since()[1] - lowered_before
        lowered_before += lowerings
        distinct, bodies = mosaic_bodies(lowered)
        if program == "train":
            assert lowerings == distinct == kinds * 3
            assert bodies <= len(STACK) + kinds * 2 < len(STACK) * 3
        else:
            assert lowerings == distinct == bodies == kinds
