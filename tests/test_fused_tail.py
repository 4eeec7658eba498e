"""Fused AlexNet tail + bf16 compute path (ISSUE 7): every new fused
stage (conv3-5 bias+StrictRELU, FC bias+ReLU+dropout epilogue,
softmax-xent loss+grad epilogue) has interpret-mode fwd/bwd parity vs the
composed ops and finite-difference checks on this CPU-only box; the
matcher/plan respects the ``fused_tail`` flag and yields to the
conv-block kernel's span; e2e FusedTrainer parity fused-tail on/off (f32
and bf16); the ``compute_dtype`` knob (canonical spelling of the legacy
``precision``); the bf16 non-finite-delta / quarantine interaction; the
staging+bf16 zero-recompile proof; and the XLA latency-hiding flag
wiring."""

import numpy as np
import pytest

from znicz_tpu.core.config import root

from tests.test_fused import fresh_mnist


def _rand(shape, seed, scale=1.0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


# -- stage 1: conv3-5 bias+StrictRELU (Pallas, interpret mode here) ------------


def test_bias_relu_forward_and_grad_match_composed():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_bias_relu

    x = _rand((2, 5, 5, 8), 3, 2.0)
    b = _rand((8,), 4, 0.1)
    np.testing.assert_allclose(
        np.asarray(fused_bias_relu(x, b)),
        np.asarray(jnp.maximum(x + b, 0.0)), rtol=1e-6, atol=1e-7)
    cot = _rand((2, 5, 5, 8), 5)
    gx, gb = jax.grad(
        lambda xx, bb: jnp.sum(fused_bias_relu(xx, bb) * cot),
        argnums=(0, 1))(x, b)
    rx, rb = jax.grad(
        lambda xx, bb: jnp.sum(jnp.maximum(xx + bb, 0.0) * cot),
        argnums=(0, 1))(x, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), rtol=1e-5,
                               atol=1e-5)
    # bf16 operands: bf16 out, f32 internal math (block-kernel policy)
    xb = x.astype(jnp.bfloat16)
    bb16 = b.astype(jnp.bfloat16)
    out = fused_bias_relu(xb, bb16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(jnp.maximum(xb.astype(jnp.float32)
                               + bb16.astype(jnp.float32), 0.0)),
        rtol=2e-2, atol=2e-2)


def test_bias_relu_finite_differences():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_bias_relu

    # keep pre-activations off the ReLU kink (measure-zero; the composed
    # parity above covers tie behavior)
    x = _rand((1, 4, 4, 4), 21)
    x = jnp.sign(x) * (jnp.abs(x) + 0.3)
    b = _rand((4,), 22, 0.05)
    cot = _rand((1, 4, 4, 4), 23)

    def loss(xx, bb):
        return jnp.sum(fused_bias_relu(xx, bb) * cot)

    gx, gb = jax.grad(loss, argnums=(0, 1))(x, b)
    eps = 1e-3
    # probe count is budget-bound (each interpret-mode eval is ~0.3s);
    # the composed-parity test above is the dense check
    for idx in [(0, 0, 0, 0), (0, 2, 3, 1)]:
        e = jnp.zeros_like(x).at[idx].set(eps)
        fd = (float(loss(x + e, b)) - float(loss(x - e, b))) / (2 * eps)
        assert abs(fd - float(gx[idx])) <= 5e-2 * max(1.0, abs(fd))
    e = jnp.zeros_like(b).at[3].set(eps)
    fd = (float(loss(x, b + e)) - float(loss(x, b - e))) / (2 * eps)
    assert abs(fd - float(gb[3])) <= 5e-2 * max(1.0, abs(fd))


# -- stage 2: FC bias+ReLU+dropout epilogue ------------------------------------


def test_fc_epilogue_matches_composed_and_grads():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.dropout import DropoutForward
    from znicz_tpu.pallas_fused_block import fused_fc_epilogue

    y = _rand((4, 16), 31)
    b = _rand((16,), 32, 0.1)
    key = jax.random.PRNGKey(7)
    ratio = 0.5

    def composed(yy, bb):
        r = jnp.maximum(yy + bb, 0.0)
        # the SAME bernoulli draw the unit path's DropoutForward makes —
        # mask parity is bit-exact, not distributional
        return r * DropoutForward.make_mask(key, y.shape, ratio)

    out = fused_fc_epilogue(y, b, key, ratio, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(composed(y, b)),
                               rtol=1e-6)
    cot = _rand((4, 16), 33)
    g = jax.grad(lambda a, c: jnp.sum(
        fused_fc_epilogue(a, c, key, ratio, True) * cot),
        argnums=(0, 1))(y, b)
    r = jax.grad(lambda a, c: jnp.sum(composed(a, c) * cot),
                 argnums=(0, 1))(y, b)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(r[0]),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(r[1]),
                               rtol=1e-5, atol=1e-5)
    # eval / no-dropout: plain bias+relu, key unused (and allowed None)
    np.testing.assert_allclose(
        np.asarray(fused_fc_epilogue(y, b, None, ratio, False)),
        np.asarray(jnp.maximum(y + b, 0.0)), rtol=1e-6)


def test_fc_epilogue_finite_differences():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_fc_epilogue

    y = _rand((2, 8), 41)
    y = jnp.sign(y) * (jnp.abs(y) + 0.3)       # off the kink
    b = _rand((8,), 42, 0.05)
    key = jax.random.PRNGKey(11)
    cot = _rand((2, 8), 43)

    def loss(yy, bb):
        return jnp.sum(fused_fc_epilogue(yy, bb, key, 0.5, True) * cot)

    gy, gb = jax.grad(loss, argnums=(0, 1))(y, b)
    eps = 1e-3
    for idx in [(0, 0), (1, 5)]:
        e = jnp.zeros_like(y).at[idx].set(eps)
        fd = (float(loss(y + e, b)) - float(loss(y - e, b))) / (2 * eps)
        assert abs(fd - float(gy[idx])) <= 5e-2 * max(1.0, abs(fd))
    e = jnp.zeros_like(b).at[5].set(eps)
    fd = (float(loss(y, b + e)) - float(loss(y, b - e))) / (2 * eps)
    assert abs(fd - float(gb[5])) <= 5e-2 * max(1.0, abs(fd))


# -- stage 3: softmax-xent loss+grad epilogue ----------------------------------


def _composed_xent(logits, labels, valid, denom):
    import jax
    import jax.numpy as jnp

    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - ll, 0.0)) / denom


def test_softmax_xent_matches_composed_and_grad():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_softmax_xent

    rng = np.random.default_rng(51)
    logits = _rand((6, 10), 51)
    labels = jnp.asarray(rng.integers(0, 10, 6).astype(np.int32))
    valid = jnp.arange(6) < 5                   # padded tail row masked
    denom = jnp.maximum(jnp.int32(5), 1)
    l_f = fused_softmax_xent(logits, labels, valid, denom)
    l_c = _composed_xent(logits, labels, valid, denom)
    np.testing.assert_allclose(float(l_f), float(l_c), rtol=1e-6)
    g = jax.grad(lambda lg: fused_softmax_xent(lg, labels, valid,
                                               denom))(logits)
    r = jax.grad(lambda lg: _composed_xent(lg, labels, valid,
                                           denom))(logits)
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-5,
                               atol=1e-7)
    # the masked row's gradient is exactly zero both ways
    assert float(np.abs(np.asarray(g)[5]).max()) == 0.0


def test_softmax_xent_finite_differences():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_softmax_xent

    rng = np.random.default_rng(61)
    logits = _rand((3, 6), 61)
    labels = jnp.asarray(rng.integers(0, 6, 3).astype(np.int32))
    valid = jnp.arange(3) < 3
    denom = jnp.int32(3)

    def loss(lg):
        return fused_softmax_xent(lg, labels, valid, denom)

    g = jax.grad(loss)(logits)
    eps = 1e-3
    for idx in [(0, 0), (1, 3), (2, 5)]:
        e = jnp.zeros_like(logits).at[idx].set(eps)
        fd = (float(loss(logits + e)) - float(loss(logits - e))) / (2 * eps)
        assert abs(fd - float(g[idx])) <= 5e-2 * max(1e-3, abs(fd)), \
            (idx, fd, float(g[idx]))


# -- matcher / plan ------------------------------------------------------------


def _tail_workflow(max_epochs=2, minibatch_size=25):
    """conv_strict_relu -> max_pooling -> all2all_strict_relu -> dropout
    -> softmax: the AlexNet tail shape in miniature (15x15 textures; no
    LRN, so the conv matches the TAIL stage, not the block kernel).
    Sized for the tier-1 time budget — four e2e runs ride this shape."""
    from znicz_tpu import datasets
    from znicz_tpu.core import prng
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    prng.reset(1013)

    class _Loader(FullBatchLoader):
        def load_data(self):
            data, labels = datasets.tinyimages(130, size=15)
            self.original_data.mem = data
            self.original_labels.mem = labels
            self.class_lengths = [0, 30, 100]
            super().load_data()

    gd = {"learning_rate": 0.02, "gradient_moment": 0.9}
    layers = [
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 8, "kx": 5, "ky": 5, "padding": (2, 2, 2, 2)},
         "<-": dict(gd)},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "all2all_strict_relu", "->": {"output_sample_shape": 32},
         "<-": dict(gd)},
        {"type": "dropout", "->": {"dropout_ratio": 0.4}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": dict(gd)},
    ]
    wf = StandardWorkflow(
        name="TailWF",
        loader=_Loader(name="loader", minibatch_size=minibatch_size),
        layers=layers, loss_function="softmax",
        decision_config={"max_epochs": max_epochs, "fail_iterations": 0})
    wf.initialize(device=None)
    return wf


def test_plan_fused_tail_matches_and_respects_flag():
    from znicz_tpu.pallas_fused_block import (plan_fused_blocks,
                                              plan_fused_tail)

    wf = _tail_workflow()
    assert plan_fused_tail(wf.forwards) == {}        # flag off -> no plan
    root.common.engine.fused_tail = True
    try:
        plan = plan_fused_tail(wf.forwards,
                               plan_fused_blocks(wf.forwards))
        assert sorted(plan) == [0, 2]
        assert plan[0].kind == "conv_bias_relu" and plan[0].span == 1
        fc = plan[2]
        assert (fc.kind, fc.span, fc.dropout_index) == ("fc_epilogue", 2, 3)
        assert fc.ratio == pytest.approx(0.4)
        # the softmax head is never an fc_epilogue (it is the loss head)
        assert 4 not in plan
    finally:
        root.common.engine.fused_tail = False


def test_plan_fused_tail_yields_to_conv_block_span():
    """With BOTH knobs on, an LRN'd conv block belongs to the single-pass
    block kernel; the tail matcher must not shadow its span."""
    from tests.test_fused_block_pallas import _tiny_alexstyle_workflow
    from znicz_tpu.pallas_fused_block import (plan_fused_blocks,
                                              plan_fused_tail)

    wf = _tiny_alexstyle_workflow()
    root.common.engine.fused_elementwise = True
    root.common.engine.fused_tail = True
    try:
        blocks = plan_fused_blocks(wf.forwards)
        assert list(blocks) == [0]
        tail = plan_fused_tail(wf.forwards, blocks)
        assert 0 not in tail                 # block kernel owns indices 0-2
        # but with the BLOCK knob off, the tail stage picks up the conv's
        # bias+relu (LRN/pool stay composed — same math either way)
        root.common.engine.fused_elementwise = False
        tail2 = plan_fused_tail(wf.forwards, plan_fused_blocks(wf.forwards))
        assert tail2[0].kind == "conv_bias_relu"
    finally:
        root.common.engine.fused_elementwise = False
        root.common.engine.fused_tail = False


# -- e2e trainer parity --------------------------------------------------------


def _run_fused(wf):
    from znicz_tpu.parallel.fused import FusedTrainer

    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    FusedTrainer(wf).run()
    return losses, {f.name: np.array(f.weights.map_read())
                    for f in wf.forwards if f.has_weights}


def test_trainer_fused_tail_matches_composed_path(tmp_path):
    """E2e FusedTrainer parity fused_tail on/off over 2 epochs: identical
    dropout masks (same fold_in key) and identical loss formula make the
    trajectories match to float-accumulation tolerance."""
    root.common.dirs.snapshots = str(tmp_path)
    l_off, w_off = _run_fused(_tail_workflow())
    root.common.engine.fused_tail = True
    try:
        l_on, w_on = _run_fused(_tail_workflow())
    finally:
        root.common.engine.fused_tail = False
    np.testing.assert_allclose(l_off, l_on, rtol=1e-4)
    assert l_on[-1] < l_on[0], l_on              # it actually trains
    for name in w_off:
        np.testing.assert_allclose(w_off[name], w_on[name], rtol=5e-3,
                                   atol=5e-5, err_msg=name)


def test_trainer_fused_tail_bf16_compute_dtype(tmp_path):
    """The new canonical ``compute_dtype`` knob drives the bf16 path
    through the fused tail: trajectory stays in band with the composed
    bf16 run, and the knob validates its spelling."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.compute_dtype = "bf16"    # the short alias
    try:
        wf = _tail_workflow()
        assert FusedTrainer(wf).compute_dtype == "bfloat16"
        l_off, _ = _run_fused(wf)                # same wf: build once
        root.common.engine.fused_tail = True
        try:
            l_on, _ = _run_fused(_tail_workflow())
        finally:
            root.common.engine.fused_tail = False
        np.testing.assert_allclose(l_off, l_on, rtol=5e-2)
        assert l_on[-1] < l_on[0], l_on
        # a bad spelling is refused at construction, not silently f32
        root.common.engine.compute_dtype = "float16"
        with pytest.raises(ValueError, match="compute_dtype"):
            FusedTrainer(wf)
    finally:
        root.common.engine.compute_dtype = "float32"


def test_compute_dtype_bf16_mnist_convergence_band(tmp_path):
    """ISSUE 7 satellite: e2e f32 vs bf16-activations/f32-master parity
    band on the MNIST MLP (CPU, lean)."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    l_f32, _ = _run_fused(fresh_mnist(max_epochs=2))
    root.common.engine.compute_dtype = "bfloat16"
    try:
        wf = fresh_mnist(max_epochs=2)
        assert FusedTrainer(wf).compute_dtype == "bfloat16"
        l_bf16, _ = _run_fused(wf)               # same wf: build once
    finally:
        root.common.engine.compute_dtype = "float32"
    np.testing.assert_allclose(l_f32, l_bf16, rtol=5e-2)
    assert l_bf16[-1] < l_bf16[0], l_bf16


# -- bf16 wire deltas vs the quarantine guard ----------------------------------


def test_bf16_nonfinite_delta_ships_raw_and_quarantines(tmp_path):
    """A non-finite gradient under the bf16 compute path must still be
    SEEN by the master's delta quarantine: the bf16 wire encoder ships
    non-finite deltas raw (nothing masked by quantization), and the
    server's quarantine flags them."""
    from znicz_tpu.core import prng
    from znicz_tpu.parallel import wire
    from znicz_tpu.server import Server

    enc = wire.DeltaEncoder("bfloat16")
    good = {"layer": {"weights": np.ones((4, 4), np.float32)}}
    bad = {"layer": {"weights": np.array([[np.inf, 1.0], [0.0, np.nan]],
                                         np.float32)}}
    qt_good = enc.encode(good)["layer"]["weights"]
    qt_bad = enc.encode(bad)["layer"]["weights"]
    assert isinstance(qt_good, wire.QuantizedTensor)
    assert qt_good.wire == "bfloat16"
    # non-finite: raw fallback (plain f32 array, no QuantizedTensor) —
    # the delta reaches the server's quarantine undisguised
    assert not isinstance(qt_bad, wire.QuantizedTensor)
    dec = np.asarray(qt_bad)
    assert not np.all(np.isfinite(dec))

    root.common.dirs.snapshots = str(tmp_path)
    prng.reset(1013)
    srv = Server(fresh_mnist(), segment_steps=2)
    assert srv._quarantine_reason({"layer": {"weights": dec}}) is not None
    assert srv._quarantine_reason(
        {"layer": {"weights": wire.dequantize(qt_good)}}) is None


# -- zero-recompile proof (staging + bf16) -------------------------------------


def test_staging_bf16_zero_recompiles(tmp_path):
    """Acceptance (ISSUE 7): the bf16 and async-staging paths add no jit
    cache entries after warmup — trace-counter + ``_cache_size()``
    cross-check, the serving layer's method on the training path."""
    from znicz_tpu.loader.streaming import HostArraySource
    from znicz_tpu.parallel.fused import FusedTrainer

    from tests.test_ingest import _build_stream_wf

    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.compute_dtype = "bf16"
    try:
        from znicz_tpu.core import prng

        prng.reset(1013)
        rng = np.random.default_rng(3)
        data = (rng.random((16, 6, 6)) * 255).astype(np.uint8)
        labels = (np.arange(16) % 2).astype(np.int32)
        wf = _build_stream_wf(HostArraySource(data, labels), max_epochs=2)
        trainer = FusedTrainer(wf)
        assert trainer.staging and trainer.compute_dtype == "bfloat16"
        trainer.run()
        assert trainer._stager is not None       # async staging engaged
        compiles0 = int(trainer._m_compiles.value)
        sizes0 = trainer.jit_cache_sizes()
        assert compiles0 > 0
        if sizes0:                               # jax exposes _cache_size
            assert sum(sizes0.values()) == compiles0, (sizes0, compiles0)
        # continue the SAME trainer for two more epochs: every dispatch
        # kind re-runs; nothing may re-trace
        wf.decision.complete.set(False)
        wf.decision.max_epochs = int(wf.decision.epoch_number) + 1 + 2
        trainer.run()
        assert int(trainer._m_compiles.value) == compiles0
        assert trainer.jit_cache_sizes() == sizes0
    finally:
        root.common.engine.compute_dtype = "float32"


# -- XLA latency-hiding flags --------------------------------------------------


def test_xla_latency_hiding_flag_wiring():
    """``configure_xla_flags``: off by default, appends the published
    scheduler flags exactly once when the knob is on (scratch env — the
    launcher applies it to os.environ before the backend exists)."""
    from znicz_tpu.backends import (LATENCY_HIDING_XLA_FLAGS,
                                    configure_xla_flags)

    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    assert configure_xla_flags(env) == ()        # knob off -> no-op
    root.common.engine.xla_latency_hiding = True
    try:
        added = configure_xla_flags(env)
        assert added == LATENCY_HIDING_XLA_FLAGS
        for f in LATENCY_HIDING_XLA_FLAGS:
            assert f in env["XLA_FLAGS"]
        # pre-existing flags survive; re-run is idempotent
        assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
        assert configure_xla_flags(env) == ()
        # an operator-set flag of the same NAME (different value) is
        # respected — no conflicting duplicate appended (last-wins parse
        # would silently override the operator)
        env2 = {"XLA_FLAGS": "--xla_tpu_host_transfer_overlap_limit=4"}
        added2 = configure_xla_flags(env2)
        assert all("host_transfer_overlap" not in f for f in added2)
        assert env2["XLA_FLAGS"].count(
            "--xla_tpu_host_transfer_overlap_limit") == 1
        assert "--xla_tpu_host_transfer_overlap_limit=4" in env2["XLA_FLAGS"]
    finally:
        root.common.engine.xla_latency_hiding = False
