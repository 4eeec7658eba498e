"""Regression guards of the training hot path that run on the CPU
backend.  What a CPU can show is counted, not timed: the staging
machinery ships each dispatch's samples exactly once, and always-on
confusion crosses to the host once a class and epoch (what either costs
on the chip: PERF.md)."""

import time

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.config import root


def _u8_mnist(budget):
    """A u8 MNIST workflow whose set is resident (``budget`` large) or
    host-staged a segment at a time (``budget`` 0)."""
    from tests.test_streaming import _StreamingMnistLoader
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 1024
    root.mnist.loader.n_valid = 256
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 128
    root.mnist.decision.max_epochs = 3
    root.mnist.layers = [64, 10]
    _StreamingMnistLoader.u8 = True
    _StreamingMnistLoader.budget = budget
    orig = mnist.MnistLoader
    mnist.MnistLoader = _StreamingMnistLoader
    try:
        wf = mnist.MnistWorkflow()
    finally:
        mnist.MnistLoader = orig
        root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    return wf


def test_staged_run_ships_each_segment_once_and_matches_resident():
    """The staging machinery (host row gather, per-segment device_put,
    the staged-direct scan) moves where the samples live and nothing
    else: the staged run ends on the resident run's weights bit for bit,
    every dispatch's minibatches are staged exactly once, and a segment
    crosses to the device in one ``device_put`` a tensor (samples,
    labels)."""
    from znicz_tpu.parallel.fused import FusedTrainer

    def weights(wf):
        return {f.name: np.array(f.weights.map_read()) for f in wf.forwards}

    wf_r = _u8_mnist(budget=1 << 30)
    FusedTrainer(wf_r).run()
    assert wf_r.loader.device_resident

    wf_s = _u8_mnist(budget=0)
    trainer = FusedTrainer(wf_s)
    assert trainer.staging and not wf_s.loader.device_resident
    segments, puts = [], []
    stage = trainer._stage_direct

    def counting_stage(idx_rows, put):
        segments.append(len(idx_rows))

        def counting_put(x):
            puts.append(np.shape(x))
            return put(x)

        return stage(idx_rows, counting_put)

    trainer._stage_direct = counting_stage
    trainer.run()
    assert bool(wf_s.decision.complete)
    want, got = weights(wf_r), weights(wf_s)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    # an epoch is a 2-step validation scan and an 8-step train scan that
    # ends in the tail; the last epoch's tail is ruled on alone (7 + 1):
    # each staged exactly once
    assert sorted(segments) == [1] + [2] * 3 + [7] + [8] * 2, segments
    assert len(puts) == 2 * len(segments), (len(puts), len(segments))


def test_confusion_is_summed_on_device_and_fed_once_an_epoch():
    """The fused path's always-on confusion is a device-side scan-carry
    accumulator.  The regression class this guards against is a per-step
    or per-segment host transfer of the (C, C) matrix (28 MB a segment at
    1,000 classes).  Counted: nothing the steps pull has a C-sized axis,
    and the Decision is handed ONE (C, C) device array a class and epoch,
    which holds every sample of that epoch — the segments' sums were
    added on the device."""
    import jax

    from znicz_tpu.loader.base import TRAIN, VALID
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist

    n_classes, epochs, n_train, n_valid = 1000, 3, 1024, 128
    prng.reset(1013)
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = n_valid
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 128
    root.mnist.decision.max_epochs = epochs
    root.mnist.layers = [64, n_classes]     # 10-class labels, WIDER head
    try:
        wf = mnist.MnistWorkflow()
    finally:
        root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    trainer = FusedTrainer(wf)
    assert trainer.compute_confusion and trainer._n_confusion() == n_classes

    pulled, fed = [], []
    sync, feed = trainer._sync, trainer._feed_decision

    def recording_sync(*values):
        out = sync(*values)
        pulled.extend(np.shape(v) for v in jax.tree_util.tree_leaves(out))
        return out

    def recording_feed(mb, metrics):
        conf = metrics[2]
        if conf is not None:
            assert isinstance(conf, jax.Array), type(conf)
            fed.append((mb["class"], mb["epoch_number"], conf.shape,
                        int(conf.sum())))
        return feed(mb, metrics)

    trainer._sync, trainer._feed_decision = recording_sync, recording_feed
    trainer.run()
    assert pulled and not [s for s in pulled if n_classes in s], pulled
    cc = (n_classes, n_classes)
    assert [f for f in fed if f[0] == TRAIN] == [
        (TRAIN, e, cc, n_train) for e in range(epochs)], fed
    assert [f for f in fed if f[0] == VALID] == [
        (VALID, e, cc, n_valid) for e in range(epochs)], fed


def test_anchor_bands_enforced():
    """The seeded sample anchors are tolerance BANDS a math change cannot
    silently cross.  Unit half: check_anchor flags out-of-band finals
    (the CIFAR error an older LRN formulation ended at, 41.25%, is
    outside the band 44.0 +/- 1.5).  E2e half: the cheapest real anchor
    (config 0, MNIST) still lands in band."""
    from znicz_tpu.samples import anchors

    # the unit half
    assert anchors.check_anchor(1, {"final_train_loss": 0.9501,
                                    "valid_err_pct": 44.0}) == []
    bad = anchors.check_anchor(1, {"final_train_loss": 0.9499,
                                   "valid_err_pct": 41.25})
    assert [f["metric"] for f in bad] == ["valid_err_pct"]

    # the e2e half: BASELINE config 0 at the sample's defaults (restore
    # them first — sibling tests shrink them)
    root.mnist.loader.n_train = 4000
    root.mnist.loader.n_valid = 800
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = 5
    root.mnist.layers = [100, 10]
    vals, bad = anchors.measure(0)
    assert bad == [], vals


def test_async_snapshot_does_not_stall_training_cpu():
    """Every-epoch snapshots (interval=1) must bill their cost to the
    background writer, not the training thread.

    The property is WHERE the save cost lands, so it is tested
    structurally (two wall-clock throughputs, gated against active,
    flaked with the host's load): inject a deliberate DELAY into the
    disk-write path and assert each ``save_async`` call made by the
    training loop returns in a small fraction of it.  A regression of
    the guarded class — the per-epoch writeback+pickle made synchronous
    again — bills >= DELAY to every call and fails by multiples, while
    host load cannot fake a 0.6 s stall inside a lock-append-notify.
    The writes still really happen (async_saves_written through the
    slowed writer), so the worker handoff is exercised end to end."""
    import tempfile

    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 512
    root.mnist.loader.n_valid = 128
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 128
    root.mnist.decision.max_epochs = 4
    root.mnist.snapshotter.interval = 1
    try:
        wf = mnist.MnistWorkflow()
    finally:
        root.mnist.snapshotter.interval = 0
    wf.initialize(device=None)
    snap = wf.snapshotter
    snap.directory = tempfile.mkdtemp(prefix="snapstall_")

    DELAY = 0.6
    real_write = snap._write_host_format

    def slow_write(path, s):
        time.sleep(DELAY)               # stands in for the TPU host's
        real_write(path, s)             # link-bound pull+write

    snap._write_host_format = slow_write

    calls = []
    real_save_async = snap.save_async

    def timed_save_async(s, tags):
        t0 = time.perf_counter()
        real_save_async(s, tags)
        calls.append(time.perf_counter() - t0)

    snap.save_async = timed_save_async

    trainer = FusedTrainer(wf)
    trainer.run()
    # the async path was really taken, and every queued save was
    # durably written THROUGH the slowed writer (run() drains the queue)
    assert calls, "async snapshot path not taken"
    assert snap.async_saves_written >= 3, snap.async_saves_written
    # the structural gate: handing a snapshot to the writer is a
    # lock-append-notify, orders of magnitude under DELAY; synchronous
    # saving would bill >= DELAY per call
    assert max(calls) < 0.4 * DELAY, (calls, DELAY)


def test_bf16_master_weights_variant_trains():
    """The opt-in bf16-MASTER-weights traffic lever
    (root.common.engine.master_dtype — an undecided lever, never a
    cell's default or the anchors'): params are stored bf16, update math stays
    f32, and training still converges to the f32 run's neighborhood."""
    from znicz_tpu.parallel.fused import FusedTrainer

    from tests.test_fused import fresh_mnist, run_fused

    l32, _ = run_fused(fresh_mnist(max_epochs=3))
    root.common.engine.master_dtype = "bfloat16"
    try:
        wf = fresh_mnist(max_epochs=3)
        losses = []
        wf.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        tr = FusedTrainer(wf)
        assert tr._master_dtype == "bfloat16"
        tr.run()
        w = wf.forwards[0].weights.map_read()
        assert str(w.dtype) == "bfloat16"       # stored dtype really bf16
    finally:
        root.common.engine.master_dtype = "float32"
    # loose band: bf16 weight rounding shifts the trajectory, it must
    # not break it
    assert losses[-1] < 1.5 * l32[-1] + 0.05, (losses, l32)

    # and the config validates
    root.common.engine.master_dtype = "float16"
    try:
        import pytest as _pytest

        with _pytest.raises(ValueError, match="master_dtype"):
            FusedTrainer(fresh_mnist(max_epochs=1))
    finally:
        root.common.engine.master_dtype = "float32"
