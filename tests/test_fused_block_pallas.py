"""Single-pass fused conv-block Pallas kernel
(znicz_tpu/pallas_fused_block.py): forward bit-parity vs the composed
bias+StrictRELU+LRN+maxpool ops, backward vs the composed VJP and vs
finite differences (interpreter mode on the CPU test platform), matcher /
geometry-fallback behavior, and end-to-end FusedTrainer parity with the
``fused_elementwise`` flag on vs off.  Also covers this round's satellite
hardening: the dedicated fused-slave staging refusal type, the server's
segment-metrics length validation, and Array.host_dirty."""

import numpy as np
import pytest

from znicz_tpu.core.config import root

N, ALPHA, BETA, K = 5, 1e-4, 0.75, 2.0
POOL = (3, 3, 2, 2)


def _composed(x, b, n=N, alpha=ALPHA, beta=BETA, k=K, pool=POOL):
    """The composed oracle: relu(x+b) -> LRN (shifted-slices oracle, same
    as tests/test_lrn_pallas.py) -> exactly-tiling overlapping maxpool."""
    import jax.numpy as jnp
    from jax import lax

    ky, kx, sy, sx = pool
    r = jnp.maximum(x + b, 0.0)
    half = n // 2
    padded = jnp.pad(jnp.square(r), [(0, 0)] * (r.ndim - 1) + [(half, half)])
    acc = jnp.zeros_like(r)
    for j in range(n):
        acc = acc + padded[..., j:j + r.shape[-1]]
    y = r / jnp.power(k + alpha * acc, beta)
    return lax.reduce_window(y, x.dtype.type(-np.inf), lax.max,
                             (1, ky, kx, 1), (1, sy, sx, 1), "VALID")


def _rand(shape, seed, scale=1.0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)


def test_fused_block_forward_matches_composed():
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_block

    x = _rand((2, 9, 9, 32), 3, 2.0)
    b = _rand((32,), 4, 0.1)
    out = fused_block(x, b, N, ALPHA, BETA, K, POOL)
    ref = _composed(x, b)
    assert out.shape == ref.shape == (2, 4, 4, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    # a second geometry (non-overlapping 2x2, 96 channels like conv1)
    x2 = _rand((1, 8, 8, 96), 5)
    b2 = _rand((96,), 6, 0.1)
    out2 = fused_block(x2, b2, N, ALPHA, BETA, K, (2, 2, 2, 2))
    ref2 = _composed(x2, b2, pool=(2, 2, 2, 2))
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               rtol=1e-5, atol=1e-6)


def test_fused_block_forward_bf16_within_tolerance():
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_block

    x = _rand((2, 9, 9, 32), 7).astype(jnp.bfloat16)
    b = _rand((32,), 8, 0.1).astype(jnp.bfloat16)
    out = fused_block(x, b, N, ALPHA, BETA, K, POOL)
    assert out.dtype == jnp.bfloat16
    ref = _composed(x.astype(jnp.float32), b.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("channels", [32, 256])   # 256: two lane chunks
def test_fused_block_grad_matches_composed_vjp(channels):
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_block

    x = _rand((2, 9, 9, channels), 11, 2.0)
    b = _rand((channels,), 12, 0.1)
    cot = _rand((2, 4, 4, channels), 13)

    gx, gb = jax.grad(
        lambda xx, bb: jnp.sum(
            fused_block(xx, bb, N, ALPHA, BETA, K, POOL) * cot),
        argnums=(0, 1))(x, b)
    rx, rb = jax.grad(
        lambda xx, bb: jnp.sum(_composed(xx, bb) * cot),
        argnums=(0, 1))(x, b)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=2e-4, atol=1e-5)


def test_fused_block_grad_finite_differences():
    import jax
    import jax.numpy as jnp

    from znicz_tpu.pallas_fused_block import fused_block

    # keep pre-activations away from the ReLU kink so the FD probe is on
    # a smooth branch (the kink itself is measure-zero and covered by the
    # composed-vjp parity above)
    x = _rand((1, 5, 5, 8), 21)
    x = jnp.sign(x) * (jnp.abs(x) + 0.3)
    b = _rand((8,), 22, 0.05)
    cot = _rand((1, 2, 2, 8), 23)

    def loss(xx, bb):
        return jnp.sum(fused_block(xx, bb, N, ALPHA, BETA, K, POOL) * cot)

    gx, gb = jax.grad(loss, argnums=(0, 1))(x, b)
    eps = 1e-3
    for idx in [(0, 0, 0, 0), (0, 2, 3, 5), (0, 4, 4, 7), (0, 1, 2, 2)]:
        e = jnp.zeros_like(x).at[idx].set(eps)
        fd = (float(loss(x + e, b)) - float(loss(x - e, b))) / (2 * eps)
        assert abs(fd - float(gx[idx])) <= 5e-2 * max(1.0, abs(fd)), \
            (idx, fd, float(gx[idx]))
    for ci in (0, 3, 7):
        e = jnp.zeros_like(b).at[ci].set(eps)
        fd = (float(loss(x, b + e)) - float(loss(x, b - e))) / (2 * eps)
        assert abs(fd - float(gb[ci])) <= 5e-2 * max(1.0, abs(fd)), \
            (ci, fd, float(gb[ci]))


def test_fused_block_rejects_non_tiling_pool():
    from znicz_tpu.pallas_fused_block import fused_block

    x = _rand((1, 6, 6, 8), 31)        # (6-3) % 2 != 0: partial windows
    b = _rand((8,), 32)
    with pytest.raises(AssertionError, match="tile"):
        fused_block(x, b, N, ALPHA, BETA, K, POOL)


# -- matcher / trainer routing ------------------------------------------------


def _tiny_alexstyle_workflow(minibatch_size=50, max_epochs=2,
                             pool_kwargs=None):
    """conv_strict_relu -> norm -> max_pooling -> softmax on a 19x19
    procedural texture set: 19 = 2*8 + 3, so the 3x3/s2 overlapping pool
    tiles the plane exactly (the conv1/conv2 condition)."""
    from znicz_tpu import datasets
    from znicz_tpu.core import prng
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    prng.reset(1013)

    class _Loader(FullBatchLoader):
        def load_data(self):
            data, labels = datasets.tinyimages(260, size=19)
            self.original_data.mem = data
            self.original_labels.mem = labels
            self.class_lengths = [0, 60, 200]
            super().load_data()

    gd = {"learning_rate": 0.02, "gradient_moment": 0.9}
    layers = [
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 16, "kx": 5, "ky": 5, "padding": (2, 2, 2, 2)},
         "<-": dict(gd)},
        {"type": "norm"},
        {"type": "max_pooling",
         "->": pool_kwargs or {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "softmax", "->": {"output_sample_shape": 10}, "<-": dict(gd)},
    ]
    wf = StandardWorkflow(
        name="TinyAlexStyle",
        loader=_Loader(name="loader", minibatch_size=minibatch_size),
        layers=layers, loss_function="softmax",
        decision_config={"max_epochs": max_epochs, "fail_iterations": 0})
    wf.initialize(device=None)
    return wf


def test_plan_matches_conv_block_and_respects_flag():
    from znicz_tpu.pallas_fused_block import lanes_tile, plan_fused_blocks

    # strided VMEM windows address whole 128-lane tiles (Mosaic)
    assert lanes_tile(96) and lanes_tile(256) and not lanes_tile(192)
    wf = _tiny_alexstyle_workflow()
    assert plan_fused_blocks(wf.forwards) == {}      # flag off -> no plan
    root.common.engine.fused_elementwise = True
    try:
        plan = plan_fused_blocks(wf.forwards)
        assert list(plan) == [0]
        spec = plan[0]
        assert (spec.span, spec.n, spec.pool) == (3, 5, (3, 3, 2, 2))
    finally:
        root.common.engine.fused_elementwise = False


def test_plan_falls_back_on_partial_edge_windows():
    """A pool whose windows do NOT tile the plane (non-overlapping 2x2 on
    19x19 -> partial edge column/row) must not match; the composed ops
    keep running and the workflow still trains."""
    from znicz_tpu.pallas_fused_block import plan_fused_blocks

    wf = _tiny_alexstyle_workflow(
        pool_kwargs={"kx": 2, "ky": 2})     # sliding=(2,2); 19 % 2 != 0
    assert not wf.forwards[2].exact_tiling()
    root.common.engine.fused_elementwise = True
    try:
        assert plan_fused_blocks(wf.forwards) == {}
    finally:
        root.common.engine.fused_elementwise = False


def _run_fused(wf):
    from znicz_tpu.parallel.fused import FusedTrainer

    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    FusedTrainer(wf).run()
    return losses, {f.name: np.array(f.weights.map_read())
                    for f in wf.forwards if f.has_weights}


def test_trainer_fused_block_matches_composed_path(tmp_path):
    """End-to-end FusedTrainer parity: fused_elementwise on vs off over 2
    epochs — same losses and final weights within float-accumulation
    tolerance (the kernel's tie semantics differ only where the ReLU mask
    zeroes the gradient anyway; see pallas_fused_block docstring)."""
    root.common.dirs.snapshots = str(tmp_path)
    l_off, w_off = _run_fused(_tiny_alexstyle_workflow())
    root.common.engine.fused_elementwise = True
    try:
        l_on, w_on = _run_fused(_tiny_alexstyle_workflow())
    finally:
        root.common.engine.fused_elementwise = False
    np.testing.assert_allclose(l_off, l_on, rtol=1e-3)
    assert l_on[-1] < l_on[0], l_on                  # it actually trains
    for name in w_off:
        np.testing.assert_allclose(w_off[name], w_on[name], rtol=5e-3,
                                   atol=5e-5, err_msg=name)


def test_trainer_fused_block_bf16_trains(tmp_path):
    """Mixed precision through the kernel: bf16 activations in, bf16 out,
    f32 internal math — the loss trajectory stays in band with the
    composed bf16 path."""
    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.compute_dtype = "bfloat16"
    try:
        l_off, _ = _run_fused(_tiny_alexstyle_workflow())
        root.common.engine.fused_elementwise = True
        try:
            l_on, _ = _run_fused(_tiny_alexstyle_workflow())
        finally:
            root.common.engine.fused_elementwise = False
    finally:
        root.common.engine.compute_dtype = "float32"
    np.testing.assert_allclose(l_off, l_on, rtol=5e-2)
    assert l_on[-1] < l_on[0], l_on


# -- satellite hardening ------------------------------------------------------


def test_staging_refusal_is_dedicated_exception_type():
    """The fused-slave host-staged-loader refusal is a dedicated
    FusedUnsupportedError subclass, so engine.train's slave fallback
    catches exactly the known refusals and real ValueErrors propagate."""
    from znicz_tpu.parallel.fused import (FusedStagingUnsupportedError,
                                          FusedUnsupportedError)

    assert issubclass(FusedStagingUnsupportedError, FusedUnsupportedError)
    assert issubclass(FusedStagingUnsupportedError, ValueError)


def test_server_refuses_short_segment_metrics(tmp_path):
    """A segment update whose metrics list is shorter than the job's
    minibatch list is refused (no decision feed, no deltas) and the job is
    re-queued — zip() must not silently truncate (server.py satellite)."""
    from znicz_tpu.core import prng
    from znicz_tpu.samples import mnist
    from znicz_tpu.server import Server

    prng.reset(1013)
    root.common.dirs.snapshots = str(tmp_path)
    root.mnist.loader.n_train = 300
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = 3
    wf = mnist.MnistWorkflow()
    wf.initialize(device=None)
    srv = Server(wf, segment_steps=3)
    srv.registered.add("s1")

    def next_job():
        while True:
            r = srv._handle({"cmd": "job", "id": "s1"})
            if not r.get("wait"):
                return r

    def next_segment_job():
        """Drain eval singletons / flat train tails (well-formed replies)
        until the server issues a segment job."""
        for _ in range(64):
            r = next_job()
            if "minibatches" in r["job"]:
                return r
            srv._handle({"cmd": "update", "id": "s1",
                         "job_id": r["job_id"], "deltas": None,
                         "metrics": {"loss": 1.0, "n_err": 0}})
        raise AssertionError("no segment job issued")

    rep = next_segment_job()
    job = rep["job"]
    srv.jobs_done = 0                    # count only the segment exchange
    assert len(job["minibatches"]) > 1
    n_mb = len(job["minibatches"])
    before = np.array(wf.forwards[0].weights.map_read()).copy()
    bad = srv._handle({"cmd": "update", "id": "s1", "job_id": rep["job_id"],
                       "deltas": {wf.forwards[0].name: {
                           "weights": np.ones_like(before)}},
                       "metrics": [{"loss": 1.0}] * (n_mb - 1)})
    assert bad["ok"] is False and "metrics length" in bad["error"]
    assert srv.bad_updates == 1
    assert srv.jobs_done == 0
    # the refused update applied nothing and the job went back to pending
    np.testing.assert_array_equal(
        before, np.array(wf.forwards[0].weights.map_read()))
    assert any(j.get("kind") == "segment" for j in srv._pending)
    # a well-formed reply for the re-queued job is accepted
    rep2 = srv._handle({"cmd": "job", "id": "s1"})
    ok = srv._handle({"cmd": "update", "id": "s1", "job_id": rep2["job_id"],
                      "deltas": None,
                      "metrics": [{"loss": 1.0}] * n_mb})
    assert ok["ok"] is True and srv.jobs_done == 1
    # a deterministically-broken slave must NOT livelock: after
    # MAX_BAD_REPLIES refusals of the SAME job it is dropped, not requeued
    rep3 = next_segment_job()
    job3 = rep3["job"]
    for attempt in range(srv.MAX_BAD_REPLIES):
        bad = srv._handle({"cmd": "update", "id": "s1",
                           "job_id": rep3["job_id"], "deltas": None,
                           "metrics": []})
        assert bad["ok"] is False
        if attempt < srv.MAX_BAD_REPLIES - 1:
            rep3 = next_job()
            assert rep3["job"] is job3       # same requeued job
    assert not srv._pending                  # dropped, not requeued


def test_array_host_dirty_tracks_map_state():
    from znicz_tpu.memory import Array

    a = Array(np.arange(6, dtype=np.float32).reshape(2, 3))
    assert a.host_dirty                      # fresh host data, no device
    _ = a.devmem
    assert not a.host_dirty                  # synced
    a.map_write()[0, 0] = 7.0
    assert a.host_dirty                      # host newer than device
    _ = a.devmem
    assert not a.host_dirty


def test_op_value_refuses_stale_cross_host_shard():
    """_op_value must raise, not silently hand out a stale sharded device
    buffer, when the host copy is newer (fused.py satellite).  The
    cross-host condition is simulated via the same attributes
    Array.cross_host_sharded reads."""
    from znicz_tpu.memory import Array
    from znicz_tpu.parallel.fused import FusedTrainer

    class _FakeGlobal:
        is_fully_addressable = False
        is_fully_replicated = False

        def is_deleted(self):
            return False

    arr = Array(np.zeros((2, 2), np.float32))
    arr._devmem = _FakeGlobal()              # pretend: sharded global array
    arr._state = 0                           # synced -> passes through
    trainer = FusedTrainer.__new__(FusedTrainer)
    trainer.mesh = object()                  # non-None mesh

    import jax

    if jax.process_count() > 1:              # single-process test only
        pytest.skip("single-controller test")
    orig = jax.process_count
    jax.process_count = lambda: 2
    try:
        assert trainer._op_value(arr) is arr._devmem
        arr._state = 1                       # _HOST_DIRTY
        with pytest.raises(RuntimeError, match="NEWER host copy"):
            trainer._op_value(arr)
    finally:
        jax.process_count = orig
