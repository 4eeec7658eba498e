"""FusedTrainer: parity with the unit-at-a-time engine, and 8-virtual-device
data parallelism (SURVEY.md §4: multi-device tests on CPU)."""

import os

import numpy as np
import pytest

from znicz_tpu.core.config import root


def fresh_mnist(max_epochs=2, n_valid=60):
    from znicz_tpu.core import prng
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 300
    root.mnist.loader.n_valid = n_valid
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = max_epochs
    try:
        wf = mnist.MnistWorkflow()
        wf.initialize(device=None)
    finally:
        root.mnist.loader.n_valid = 60
    return wf


def streaming_mnist(max_epochs, budget):
    """``tests/test_streaming.py``'s mnist over a streaming loader (its
    epochs end in a short minibatch): resident (``budget`` large) or
    host-staged a segment at a time (0)."""
    from tests import test_streaming as ts

    ts._StreamingMnistLoader.u8 = False
    ts._StreamingMnistLoader.budget = budget
    try:
        return ts._fresh(ts._StreamingMnistLoader, max_epochs)
    finally:
        ts._StreamingMnistLoader.budget = 0


def run_unit(wf):
    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    wf.run()
    return losses, {f.name: np.array(f.weights.map_read())
                    for f in wf.forwards}


def run_fused(wf, mesh=None, tp_threshold=None):
    from znicz_tpu.parallel.fused import FusedTrainer

    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    trainer = FusedTrainer(wf, mesh=mesh)
    if tp_threshold is not None:
        trainer.tp_threshold = tp_threshold
    trainer.run()
    return losses, {f.name: np.array(f.weights.map_read())
                    for f in wf.forwards}


def test_fused_matches_unit_path(tmp_path):
    root.common.dirs.snapshots = str(tmp_path)
    wfu = fresh_mnist()
    lu, wu = run_unit(wfu)
    wff = fresh_mnist()
    lf, wf_ = run_fused(wff)
    np.testing.assert_allclose(lu, lf, rtol=1e-4)
    for name in wu:
        np.testing.assert_allclose(wu[name], wf_[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)
    # confusion totals match exactly — the fused path accumulates the
    # confusion on DEVICE across each epoch and transfers once at the
    # tail, which must be invisible to the Decision's epoch metrics
    for klass in (1, 2):
        cu = wfu.decision.epoch_metrics[klass]["confusion"]
        cf = wff.decision.epoch_metrics[klass]["confusion"]
        np.testing.assert_array_equal(np.asarray(cu), np.asarray(cf),
                                      err_msg=f"class {klass}")
        assert np.asarray(cf).sum() > 0


def test_fused_data_parallel_8dev_matches_single(tmp_path):
    import jax

    root.common.dirs.snapshots = str(tmp_path)
    assert len(jax.devices()) >= 8, "conftest must force 8 virtual devices"
    from znicz_tpu.parallel.mesh import make_mesh

    l1, w1 = run_fused(fresh_mnist())
    mesh = make_mesh(axes=("data",))
    l8, w8 = run_fused(fresh_mnist(), mesh=mesh)
    np.testing.assert_allclose(l1, l8, rtol=1e-4)
    for name in w1:
        np.testing.assert_allclose(w1[name], w8[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)


def hybrid_mesh():
    """A (data=4, model=2) mesh: batch sharded over ``data``, the 100-wide
    hidden FC row-sharded over ``model`` (tp_threshold lowered to 64)."""
    from znicz_tpu.parallel.mesh import make_mesh

    return make_mesh((4, 2), ("data", "model"))


def test_fused_tp_hybrid_mesh_matches_single(tmp_path):
    """Tensor parallelism correctness: a hybrid data x model mesh must
    reproduce the single-device losses AND weights (GSPMD inserts the
    collectives; the math may not change)."""
    root.common.dirs.snapshots = str(tmp_path)
    l1, w1 = run_fused(fresh_mnist())
    lt, wt = run_fused(fresh_mnist(), mesh=hybrid_mesh(), tp_threshold=64)
    np.testing.assert_allclose(l1, lt, rtol=1e-4)
    for name in w1:
        np.testing.assert_allclose(w1[name], wt[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)


def test_fused_tp_hybrid_mesh_matches_single_bf16(tmp_path):
    """Same TP-parity property under mixed precision: bf16 on the hybrid
    mesh vs bf16 single-device (looser tolerances — bf16 collective
    reduction order differs)."""
    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.compute_dtype = "bfloat16"
    try:
        l1, w1 = run_fused(fresh_mnist())
        lt, wt = run_fused(fresh_mnist(), mesh=hybrid_mesh(),
                           tp_threshold=64)
    finally:
        root.common.engine.compute_dtype = "float32"
    np.testing.assert_allclose(l1, lt, rtol=5e-2)
    assert lt[-1] < lt[0] * 0.9, lt             # and it actually trains
    for name in w1:
        np.testing.assert_allclose(w1[name], wt[name], rtol=5e-2,
                                   atol=5e-3, err_msg=name)


def test_fused_snapshot_restore_continue(tmp_path):
    """Restore-then-continue UNDER FusedTrainer: velocities + prng streams
    must round-trip, and the continued trajectory must match the unit
    engine continuing from the very same snapshot."""
    from znicz_tpu import snapshotter as snap_mod
    from znicz_tpu.core import prng
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist
    from znicz_tpu.snapshotter import Snapshotter

    root.common.dirs.snapshots = str(tmp_path)
    wf = fresh_mnist(max_epochs=2)
    FusedTrainer(wf).run()
    path = wf.snapshotter.destination
    assert path is not None
    snap = Snapshotter.load(path)

    def resume(engine):
        prng.reset(1013)
        root.mnist.decision.max_epochs = 4           # 2 more epochs
        losses = []
        wf2 = mnist.MnistWorkflow()
        wf2.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        wf2.initialize(device=None)
        snap_mod.restore(wf2, snap)
        if engine == "fused":
            trainer = FusedTrainer(wf2)
            # restored velocities must be what the trainer picks up
            for name, layer in trainer.extract_velocities().items():
                gd_name = trainer.gd_of[name].name
                for k, v in layer.items():
                    np.testing.assert_allclose(
                        np.asarray(v), snap["velocities"][gd_name][k],
                        err_msg=f"{gd_name}.{k}")
            trainer.run()
        else:
            wf2.run()
        assert bool(wf2.decision.complete)
        return losses, {f.name: np.array(f.weights.map_read())
                        for f in wf2.forwards}

    lf, wf_f = resume("fused")
    lu, wf_u = resume("unit")
    assert len(lf) >= 2 and len(lf) == len(lu)       # continuation ran
    np.testing.assert_allclose(lf, lu, rtol=1e-4)
    for name in wf_u:
        np.testing.assert_allclose(wf_u[name], wf_f[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)


def test_bf16_state_dtype_parity_mnist(tmp_path):
    """root.common.engine.state_dtype="bfloat16" stores optimizer
    velocities in bf16 (HBM-traffic lever, VERDICT r3 item 3a); update
    math stays f32.  Documented semantics: the velocity is quantized once
    per step — loss curves must track f32 within tolerance and training
    must clearly progress."""
    root.common.dirs.snapshots = str(tmp_path)
    l32, w32 = run_fused(fresh_mnist(max_epochs=3))
    root.common.engine.state_dtype = "bfloat16"
    try:
        wf = fresh_mnist(max_epochs=3)
        from znicz_tpu.parallel.fused import FusedTrainer

        losses = []
        wf.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        trainer = FusedTrainer(wf)
        for gd in wf.gds:
            for k, a in gd._velocities.items():
                assert str(a.dtype) == "bfloat16", (gd.name, k, a.dtype)
        trainer.run()
    finally:
        root.common.engine.state_dtype = "float32"
    np.testing.assert_allclose(l32, losses, rtol=2e-2)
    assert losses[-1] < losses[0] * 0.7


@pytest.mark.slow
def test_bf16_state_dtype_parity_cifar(tmp_path):
    """Same property on the CIFAR anchor (conv net, the BASELINE
    config[1] gate): bf16 velocities track the f32 trajectory and the
    anchor's beats-chance bar still holds.

    Slow-marked (ISSUE 7 budget discipline): the property itself stays
    tier-1 via the mnist twin above; this conv-anchor re-run cost ~70s
    of a budget the suite had outgrown."""
    from znicz_tpu.core import prng
    from znicz_tpu.samples import cifar

    root.cifar.loader.n_train = 300
    root.cifar.loader.n_valid = 100
    root.cifar.loader.n_test = 0
    root.cifar.loader.minibatch_size = 50
    root.cifar.decision.max_epochs = 4
    root.common.dirs.snapshots = str(tmp_path)

    def run_once():
        prng.reset(1013)
        wf = cifar.CifarWorkflow()
        losses = []
        wf.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        wf.initialize(device=None)
        from znicz_tpu.parallel.fused import FusedTrainer

        FusedTrainer(wf).run()
        return losses

    l32 = run_once()
    root.common.engine.state_dtype = "bfloat16"
    try:
        lb = run_once()
    finally:
        root.common.engine.state_dtype = "float32"
    np.testing.assert_allclose(l32, lb, rtol=5e-2)
    # 4 shrunk epochs move the conv net ~9% down the curve; the parity
    # assert above is the real gate, this is just "it trains at all"
    assert lb[-1] < lb[0] * 0.95


def test_cross_topology_checkpoint_resume(tmp_path):
    """SHARDED orbax save under a {data:4, model:2} mesh, restored onto a
    {data:8} mesh AND onto a single device (VERDICT r3 item 5): orbax
    delivers every leaf already placed in the restoring trainer's
    shardings, and both continued trajectories match uninterrupted
    training."""
    from znicz_tpu.core import prng
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.samples import mnist

    root.common.dirs.snapshots = str(tmp_path)
    lo, wo = run_fused(fresh_mnist(max_epochs=4))    # uninterrupted oracle

    # phase 1: train on the hybrid mesh; the snapshotter writes a SHARDED
    # orbax checkpoint MID-RUN at the end of epoch 1 (interval=2) — the
    # preemption-resume scenario.  (An end-of-run checkpoint could never
    # match uninterrupted training: the stop semantics deliberately skip
    # the final tail update.)
    root.mnist.snapshotter.interval = 2
    try:
        wf = fresh_mnist(max_epochs=4)
    finally:
        root.mnist.snapshotter.interval = 0
    wf.snapshotter.format = "orbax"
    wf.snapshotter.sharded = True
    trainer = FusedTrainer(wf, mesh=hybrid_mesh())
    trainer.tp_threshold = 64
    trainer.run()
    path = str(tmp_path / "mnist_epoch_1.orbax")
    assert os.path.isdir(path), os.listdir(tmp_path)
    # the saved leaves really were the live sharded device arrays
    w = wf.forwards[0].weights.devmem
    assert len(w.sharding.device_set) == 8, w.sharding

    def resume(mesh, tp_threshold=None):
        prng.reset(1013)
        root.mnist.decision.max_epochs = 4
        losses = []
        wf2 = mnist.MnistWorkflow()
        wf2.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        wf2.initialize(device=None)
        tr = FusedTrainer(wf2, mesh=mesh)
        if tp_threshold is not None:
            tr.tp_threshold = tp_threshold
        tr.restore_sharded(path)
        # leaves arrive placed per the RESTORING topology
        w2 = wf2.forwards[0].weights.devmem
        n_dev = len(w2.sharding.device_set)
        assert n_dev == (1 if mesh is None else mesh.devices.size), \
            w2.sharding
        tr.run()
        assert bool(wf2.decision.complete)
        return losses, {f.name: np.array(f.weights.map_read())
                        for f in wf2.forwards}

    l8, w8 = resume(make_mesh(axes=("data",)))       # reshard 4x2 -> 8
    l1, w1 = resume(None)                            # reshard -> one device
    assert len(l8) == 2 and len(l1) == 2             # epochs 2..3 ran
    np.testing.assert_allclose(l8, l1, rtol=1e-4)    # topology-invariant
    np.testing.assert_allclose(l1, lo[2:], rtol=1e-3)  # matches oracle
    for name in w1:
        np.testing.assert_allclose(w1[name], wo[name], rtol=5e-3,
                                   atol=5e-5, err_msg=name)
        np.testing.assert_allclose(w8[name], w1[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)


def test_fused_snapshotter_fires(tmp_path):
    root.common.dirs.snapshots = str(tmp_path)
    wf = fresh_mnist()
    from znicz_tpu.parallel.fused import FusedTrainer

    FusedTrainer(wf).run()
    assert wf.snapshotter.destination is not None
    import os
    assert os.path.exists(wf.snapshotter.destination)


def test_fused_rejects_tied_weights(tmp_path):
    root.common.dirs.snapshots = str(tmp_path)
    root.mnist_ae.loader.n_train = 100
    root.mnist_ae.loader.n_valid = 50
    root.mnist_ae.loader.minibatch_size = 50
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist_ae

    wf = mnist_ae.MnistAEWorkflow()
    wf.initialize(device=None)
    wf.forwards = [wf.conv, wf.pool, wf.depool, wf.deconv]
    wf.gds = [wf.gd_deconv, wf.gd_depool, wf.gd_pool, wf.gd_conv]
    with pytest.raises(ValueError, match="tied"):
        FusedTrainer(wf)

def test_fused_stats_observability(tmp_path):
    """The fast path reports per-step timing (VERDICT r2 item 3): stats
    accumulate in FusedTrainer.run, appear in Workflow.print_stats and in
    the web_status snapshot."""
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.web_status import WebStatus

    root.common.dirs.snapshots = str(tmp_path)
    # three epochs: the second repeats the first's programs (its tail
    # rides the scan), the last ends in a shorter scan and a tail alone
    wf = fresh_mnist(max_epochs=3)
    trainer = FusedTrainer(wf)
    trainer.run()
    s = trainer.stats
    assert s["train_steps"] > 0 and s["eval_steps"] > 0
    assert s["images"] >= s["train_steps"]       # >= 1 image per step
    assert s["wall_s"] > 0 and s["steps_per_sec"] > 0
    assert s["img_per_sec"] > 0 and s["last_step_ms"] > 0
    # warm numbers exclude each variant's first (compiling) dispatch
    assert s["warm_steps"] > 0
    assert s["warm_steps"] < s["train_steps"] + s["eval_steps"]
    assert s["warm_img_per_sec"] > s["img_per_sec"]
    assert wf.fused_stats is s
    table = wf.print_stats()
    assert "steps/s" in table and "img/s" in table
    assert "warm (excl. compiles)" in table

    status = WebStatus(port=0).start()
    try:
        status.register(wf)
        snap = status.snapshot()
        info = next(w for w in snap["workflows"] if w["name"] == wf.name)
        assert info["fused"]["train_steps"] == s["train_steps"]
        # how each epoch's tail ran: /status.json and /metrics
        assert (info["fused"]["tails_in_scan"],
                info["fused"]["tails_alone"]) == (2, 1)
        from znicz_tpu import telemetry

        scraped = telemetry.render_prometheus()
        assert 'znicz_tails_in_scan_total{component="trainer"}' in scraped
        assert 'znicz_tails_alone_total{component="trainer"}' in scraped
    finally:
        status.stop()


def test_fused_remat_matches(tmp_path):
    """Units that ask for rematerialisation (``remat = True``, one
    ``jax.checkpoint`` a unit) change memory, not math: loss curves and
    final weights match the run that keeps its activations."""
    root.common.dirs.snapshots = str(tmp_path)
    lf, wf_ = run_fused(fresh_mnist())

    from znicz_tpu.parallel.fused import FusedTrainer

    wf2 = fresh_mnist()
    losses2 = []
    wf2.decision.on_epoch_end.append(
        lambda d: losses2.append(d.epoch_metrics[2]["loss"]))
    for f in wf2.forwards:
        f.remat = True
    FusedTrainer(wf2).run()
    np.testing.assert_allclose(lf, losses2, rtol=1e-5)
    for f in wf2.forwards:
        np.testing.assert_allclose(np.array(f.weights.map_read()),
                                   wf_[f.name], rtol=1e-4, atol=1e-6,
                                   err_msg=f.name)


def test_fused_eval_segments_respect_class_boundary(tmp_path):
    """With both TEST and VALID sets, per-class confusion must match the
    unit path exactly — eval scan segments may not span the class
    boundary (their summed confusion is booked to the first class)."""
    from znicz_tpu.core import prng
    from znicz_tpu.samples import mnist

    def build():
        prng.reset(1013)
        root.mnist.loader.n_train = 300
        root.mnist.loader.n_valid = 120
        root.mnist.loader.n_test = 120
        root.mnist.loader.minibatch_size = 60
        root.mnist.decision.max_epochs = 2
        root.common.dirs.snapshots = str(tmp_path)
        wf = mnist.MnistWorkflow()
        wf.initialize(device=None)
        return wf

    try:
        wfu = build()
        wfu.run()
        wff = build()
        from znicz_tpu.parallel.fused import FusedTrainer

        FusedTrainer(wff).run()
        for klass in (0, 1, 2):
            cu = np.asarray(wfu.decision.epoch_metrics[klass]["confusion"])
            cf = np.asarray(wff.decision.epoch_metrics[klass]["confusion"])
            np.testing.assert_array_equal(cu, cf, err_msg=f"class {klass}")
            assert cf.sum() > 0
    finally:
        root.mnist.loader.n_test = 0


def test_fused_train_only_epoch_hook_once_per_epoch(tmp_path):
    """Train-only workflows (no TEST/VALID): the epoch-end hook must fire
    exactly once per epoch — a stale epoch_ended flag used to re-run it
    after the next epoch's first pipelined segment."""
    from znicz_tpu.core import prng
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 300
    root.mnist.loader.n_valid = 0
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = 3
    root.common.dirs.snapshots = str(tmp_path)
    try:
        wf = mnist.MnistWorkflow()
        wf.initialize(device=None)
        calls = []
        # a due epoch goes through run() (sync) or tags_for()+save_async
        # (r5 async default) — count the hook either way
        wf.snapshotter.run = lambda: calls.append("sync")
        orig_tags = wf.snapshotter.tags_for
        wf.snapshotter.tags_for = \
            lambda e, i: (calls.append("async"), orig_tags(e, i))[1]
        wf.snapshotter.gate_skip.set(False)
        FusedTrainer(wf).run()
        assert bool(wf.decision.complete)
        assert len(calls) == 3, calls       # once per epoch, not more
    finally:
        root.mnist.loader.n_valid = 60


def test_fused_wall_time_not_double_counted(tmp_path):
    """Pipelined accounting must charge non-overlapping intervals:
    stats wall_s may not exceed true elapsed time."""
    import time as _t

    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    wf = fresh_mnist()
    trainer = FusedTrainer(wf)
    t0 = _t.perf_counter()
    trainer.run()
    elapsed = _t.perf_counter() - t0
    assert trainer.stats["wall_s"] <= elapsed * 1.02 + 0.01, \
        (trainer.stats["wall_s"], elapsed)


def test_fused_writeback_need_driven(tmp_path):
    """Epoch-end device->host writeback is paid only when a consumer will
    use it that epoch (a due snapshot or a wired plotter) — never as an
    unconditional per-epoch tax (VERDICT r3 weak #3).  One final
    writeback always lands the trained weights in the unit Arrays."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)

    def counting(trainer):
        calls = []
        orig = trainer.writeback
        trainer.writeback = lambda p, v: (calls.append(1), orig(p, v))[1]
        return calls

    # no consumers: snapshotter gated off, no plotters -> exactly one
    # (final) writeback over the whole run
    wf = fresh_mnist(max_epochs=3)
    wf.snapshotter.gate_skip.set(True)
    tr = FusedTrainer(wf)
    calls = counting(tr)
    tr.run()
    assert len(calls) == 1, calls
    final_loss = wf.decision.epoch_metrics[2]["loss"]

    # snapshotter active, r5 ASYNC default: snapshots go through
    # snapshot_from_trees + the background writer — NO writeback at all
    # beyond the final one, and the snapshots still land
    wf2 = fresh_mnist(max_epochs=3)
    tr2 = FusedTrainer(wf2)
    calls2 = counting(tr2)
    tr2.run()
    assert wf2.snapshotter.async_saves_written > 0
    assert len(calls2) == 1, calls2
    np.testing.assert_allclose(final_loss,
                               wf2.decision.epoch_metrics[2]["loss"],
                               rtol=1e-6)

    # async off (sync fallback): one writeback per epoch that actually
    # saves, plus the final one; and the snapshotter changed no math
    root.common.engine.async_snapshot = False
    try:
        wf3 = fresh_mnist(max_epochs=3)
        tr3 = FusedTrainer(wf3)
        calls3 = counting(tr3)
        saves = []
        orig_save = wf3.snapshotter.save
        wf3.snapshotter.save = lambda tag: (saves.append(tag),
                                            orig_save(tag))[1]
        tr3.run()
    finally:
        root.common.engine.async_snapshot = True
    assert saves, "best-only snapshotter never fired"
    assert len(calls3) == len(saves) + 1, (calls3, saves)
    np.testing.assert_allclose(final_loss,
                               wf3.decision.epoch_metrics[2]["loss"],
                               rtol=1e-6)


def test_fused_confusion_wide_head_always_on(tmp_path):
    """Heads wider than the unit path's 128-class auto-off still get an
    exact per-epoch confusion matrix on the fused path: the sum lives on
    device and is transferred only when the metric is read (VERDICT r3
    missing #4)."""
    from znicz_tpu.core import prng
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import kanji

    n_classes = 160
    prng.reset(1013)
    root.kanji.loader.n_train = 320
    root.kanji.loader.n_valid = 160
    root.kanji.loader.n_classes = n_classes
    root.kanji.loader.minibatch_size = 80
    root.kanji.decision.max_epochs = 2
    root.common.dirs.snapshots = str(tmp_path)
    wf = kanji.KanjiWorkflow()
    wf.initialize(device=None)
    # the unit path's auto-off resolved OFF for this width...
    assert wf.evaluator.compute_confusion is False
    trainer = FusedTrainer(wf)
    # ...but the fused path collects anyway (device-side accumulation)
    assert trainer.compute_confusion is True
    trainer.run()
    for klass, total in ((1, 160), (2, 320)):
        conf = np.asarray(wf.decision.epoch_metrics[klass]["confusion"])
        assert conf.shape == (n_classes, n_classes)
        assert conf.sum() == total, (klass, conf.sum())
        # column sums = per-class sample counts of that split
        labels = np.asarray(wf.loader.original_labels.mem)
        lo, hi = wf.loader.class_end_offsets[klass - 1], \
            wf.loader.class_end_offsets[klass]
        hist = np.bincount(labels[lo:hi], minlength=n_classes)
        np.testing.assert_array_equal(conf.sum(axis=0), hist,
                                      err_msg=f"class {klass}")


def test_engine_fused_fallback_specific_and_logged(tmp_path):
    """--fused falls back to the unit engine ONLY for the dedicated
    FusedUnsupportedError (tied weights), with a warning; unrelated
    ValueErrors propagate (ADVICE r3)."""
    import logging

    from znicz_tpu import engine
    from znicz_tpu.parallel import fused as fused_mod

    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.fused = True
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r)
    logging.getLogger("znicz").addHandler(handler)
    try:
        wf = fresh_mnist(max_epochs=1)
        orig_init = fused_mod.FusedTrainer.__init__

        def boom(self, *a, **kw):
            raise fused_mod.FusedUnsupportedError("tied weights (test)")

        fused_mod.FusedTrainer.__init__ = boom
        try:
            engine.train(wf)                     # falls back, trains
            assert bool(wf.decision.complete)
            assert any("falling back" in r.getMessage()
                       for r in records), records
        finally:
            fused_mod.FusedTrainer.__init__ = orig_init

        def boom2(self, *a, **kw):
            raise ValueError("unrelated misconfiguration")

        fused_mod.FusedTrainer.__init__ = boom2
        try:
            with pytest.raises(ValueError, match="unrelated"):
                engine.train(fresh_mnist(max_epochs=1))
        finally:
            fused_mod.FusedTrainer.__init__ = orig_init
    finally:
        root.common.engine.fused = False
        logging.getLogger("znicz").removeHandler(handler)


def run_fused_depth(wf, depth, mesh=None):
    from znicz_tpu.parallel.fused import FusedTrainer

    wf.snapshotter.gate_skip.set(True)     # deep needs no epoch consumers
    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    trainer = FusedTrainer(wf, mesh=mesh)
    trainer.pipeline_depth = depth
    trainer.run()
    return losses, {f.name: np.array(f.weights.map_read())
                    for f in wf.forwards}, trainer


def test_fused_deep_pipeline_matches_legacy(tmp_path):
    """pipeline_depth > 1 (whole-epoch dispatches, metrics deferred up to
    depth epochs) is a host-sync optimization, not a semantics change:
    losses, weights, confusion and decision state match the per-segment
    path exactly (VERDICT r4 product-path work)."""
    root.common.dirs.snapshots = str(tmp_path)
    wf1 = fresh_mnist(max_epochs=4)
    l1, w1, _ = run_fused_depth(wf1, 1)
    wf3 = fresh_mnist(max_epochs=4)
    l3, w3, _ = run_fused_depth(wf3, 3)
    np.testing.assert_allclose(l1, l3, rtol=1e-5)
    for name in w1:
        np.testing.assert_allclose(w1[name], w3[name], rtol=1e-4,
                                   atol=1e-6, err_msg=name)
    for klass in (1, 2):
        np.testing.assert_array_equal(
            np.asarray(wf1.decision.epoch_metrics[klass]["confusion"]),
            np.asarray(wf3.decision.epoch_metrics[klass]["confusion"]),
            err_msg=f"class {klass}")
    assert wf1.decision.epoch_number == wf3.decision.epoch_number
    np.testing.assert_allclose(wf1.decision.best_metric,
                               wf3.decision.best_metric)
    # step accounting parity: eval minibatches book under eval_steps in
    # BOTH sync profiles (the deep flush must not count them as train)
    s1, s3 = wf1.fused_stats, wf3.fused_stats
    assert s1["train_steps"] == s3["train_steps"], (s1, s3)
    assert s1["eval_steps"] == s3["eval_steps"], (s1, s3)
    assert s1["images"] == s3["images"]


def test_fused_deep_pipeline_failstop_rollback(tmp_path):
    """A fail_iterations stop lands mid-speculation (later epochs already
    dispatched): the deep path must recompute the exact stopping state —
    tail update not adopted, speculated epochs discarded, host-side
    loader/step bookkeeping rewound — matching the per-segment path."""
    root.common.dirs.snapshots = str(tmp_path)
    root.mnist.learning_rate = 1e-4        # barely moves -> fails-stop
    try:
        def build():
            wf = fresh_mnist(max_epochs=50)
            wf.decision.fail_iterations = 2
            return wf

        wf1 = build()
        l1, w1, t1 = run_fused_depth(wf1, 1)
        assert len(l1) < 50, "did not stop early"
        wf4 = build()
        l4, w4, t4 = run_fused_depth(wf4, 4)
        np.testing.assert_allclose(l1, l4, rtol=1e-5)
        for name in w1:
            np.testing.assert_allclose(w1[name], w4[name], rtol=1e-4,
                                       atol=1e-7, err_msg=name)
        assert t1.steps_done == t4.steps_done
        assert wf1.loader.epoch_number == wf4.loader.epoch_number
        assert wf1.loader.samples_served == wf4.loader.samples_served
    finally:
        root.mnist.learning_rate = 0.1


def test_fused_deep_pipeline_respects_consumers(tmp_path):
    """Epoch-granular host consumers vs the deep path (r5 revision): an
    ACTIVE host-format snapshotter no longer forces segmented mode — the
    deep pipeline serves it at flush boundaries through the async writer
    (VERDICT r4 weak #3) and a checkpoint IS written.  Consumers the
    async writer cannot serve (plotters; async_snapshot=False; orbax
    format, a collective save) still disable deep mode."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    wf = fresh_mnist(max_epochs=3)
    trainer = FusedTrainer(wf)
    trainer.pipeline_depth = 4
    assert trainer._deep_eligible()        # active snapshotter: deep OK
    trainer.run()
    assert wf.snapshotter.destination is not None
    assert os.path.exists(wf.snapshotter.destination)
    assert wf.snapshotter.async_saves_written > 0

    # async off -> segmented fallback
    root.common.engine.async_snapshot = False
    try:
        wf2 = fresh_mnist(max_epochs=3)
        t2 = FusedTrainer(wf2)
        t2.pipeline_depth = 4
        assert not t2._deep_eligible()
    finally:
        root.common.engine.async_snapshot = True

    # orbax format (collective save) -> segmented fallback
    wf3 = fresh_mnist(max_epochs=3)
    wf3.snapshotter.format = "orbax"
    t3 = FusedTrainer(wf3)
    t3.pipeline_depth = 4
    assert not t3._deep_eligible()

    # plotters still disable deep mode
    wf4 = fresh_mnist(max_epochs=3)
    wf4.plotters = [object()]
    t4 = FusedTrainer(wf4)
    t4.pipeline_depth = 4
    assert not t4._deep_eligible()


def test_fused_failstop_stops_at_the_stopping_state(tmp_path):
    """A ``fail_iterations`` stop lands at an epoch's tail: the stopping
    epoch's tail update is not adopted and the loader stands where the
    unit engine's stands — losses, weights, steps and loader state at the
    stop equal the unit path's."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    root.mnist.learning_rate = 1e-4        # barely moves -> fails-stop
    try:
        def build():
            wf = fresh_mnist(max_epochs=50)
            wf.decision.fail_iterations = 2
            return wf

        wfu = build()
        lu, wu = run_unit(wfu)
        assert len(lu) < 50, "did not stop early"
        wff = build()
        losses = []
        wff.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        trainer = FusedTrainer(wff)
        trainer.run()
        np.testing.assert_allclose(lu, losses, rtol=1e-4)
        for f in wff.forwards:
            np.testing.assert_allclose(
                np.array(f.weights.map_read()), wu[f.name], rtol=2e-3,
                atol=2e-5, err_msg=f.name)
        assert trainer.steps_done == len(lu) * 5
        assert wfu.loader.epoch_number == wff.loader.epoch_number
        assert wfu.loader.samples_served == wff.loader.samples_served
        assert wfu.decision.epoch_number == wff.decision.epoch_number
    finally:
        root.mnist.learning_rate = 0.1


def test_fused_lr_schedule_matches_unit_path(tmp_path):
    """An LR schedule wired by StandardWorkflow (lr_adjust_config) must
    drive the fused path exactly like the graph engine (the fast path
    used to ignore LearningRateAdjust silently) — per-step hypers ride
    the scan as xs."""
    from znicz_tpu.core import prng
    from znicz_tpu.samples.mnist import MnistLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    def with_schedule():
        prng.reset(1013)
        root.mnist.loader.n_train = 300
        root.mnist.loader.n_valid = 60
        root.mnist.loader.n_test = 0
        root.mnist.loader.minibatch_size = 60
        root.common.dirs.snapshots = str(tmp_path)
        gd = {"learning_rate": 0.1, "gradient_moment": 0.9}
        wf = StandardWorkflow(
            name="MnistStdLR",
            loader=MnistLoader(name="loader", minibatch_size=60),
            layers=[{"type": "all2all_tanh",
                     "->": {"output_sample_shape": 100}, "<-": dict(gd)},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 10}, "<-": dict(gd)}],
            loss_function="softmax",
            decision_config={"max_epochs": 3},
            lr_adjust_config={"policy": "exp", "gamma": 0.9})
        wf.initialize(device=None)
        return wf

    lu, wu = run_unit(with_schedule())
    wff = with_schedule()
    lf, wf_ = run_fused(wff)
    assert len(lu) == len(lf) == 3
    np.testing.assert_allclose(lu, lf, rtol=1e-4)
    for name in wu:
        np.testing.assert_allclose(wu[name], wf_[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)
    # the schedule really advanced: 3 epochs x 5 train steps, minus the
    # final tail (gd_skip gates both the update and the adjust once
    # `complete` flips — identical in both engines)
    assert wff.lr_adjust.iteration == 14
    np.testing.assert_allclose(wff.gds[0].learning_rate,
                               0.1 * 0.9 ** 13, rtol=1e-6)


# -- the epoch's tail in the scan (ISSUE 31) ------------------------------------


def _stopping_state(wf):
    """Where a run left its loader and Decision."""
    loader, d = wf.loader, wf.decision
    return {"loader_epoch": int(loader.epoch_number),
            "samples_served": int(loader.samples_served),
            "epoch": int(d.epoch_number), "best_epoch": int(d.best_epoch),
            "best_metric": float(d.best_metric), "fails": int(d._fails),
            "complete": bool(d.complete), "improved": bool(d.improved),
            "epochs": len(d.epoch_history)}


@pytest.mark.parametrize("n_valid", [60, 0],
                         ids=["validation-set", "no-validation-set"])
def test_fused_tail_rides_the_scan_where_the_decision_can_say(n_valid,
                                                              tmp_path):
    """With a validation set the Decision rules on VALID, which is final
    before the epoch's TRAIN minibatches run: every tail but the run's
    last (``max_epochs``: its update is not adopted) is the last step of
    its epoch's scan.  Without one the tail's own loss decides, and every
    tail is evaluated, ruled on and updated alone, as it always was.
    Either way losses, weights, steps, loader and Decision state are the
    unit engine's."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    epochs = 3
    wfu = fresh_mnist(epochs, n_valid)
    lu, wu = run_unit(wfu)
    wff = fresh_mnist(epochs, n_valid)
    losses = []
    wff.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    trainer = FusedTrainer(wff)
    trainer.run()
    riding = epochs - 1 if n_valid else 0
    assert (trainer.stats["tails_in_scan"],
            trainer.stats["tails_alone"]) == (riding, epochs - riding)
    assert len(lu) == epochs
    np.testing.assert_allclose(lu, losses, rtol=1e-4)
    for f in wff.forwards:
        np.testing.assert_allclose(
            np.array(f.weights.map_read()), wu[f.name], rtol=2e-3,
            atol=2e-5, err_msg=f.name)
    assert trainer.steps_done == epochs * 5
    assert _stopping_state(wff) == pytest.approx(_stopping_state(wfu),
                                                 rel=1e-4)
    # the train-mode evaluation and the lone train step are the programs
    # of a tail alone: a run whose tails ride compiles the first once (its
    # last tail) and the second never
    sizes = trainer.jit_cache_sizes()
    assert sizes["_train_step"] == (0 if n_valid else 1)


def test_fused_tail_in_scan_steps_the_lr_schedule_like_the_unit_path(
        tmp_path):
    """The schedule advances after a tail in the scan as after any step:
    the rate of every applied update, step by step, is the unit path's."""
    from znicz_tpu.core import prng
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples.mnist import MnistLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    def with_schedule():                # test_fused_lr_schedule_...'s
        prng.reset(1013)
        root.mnist.loader.n_train = 300
        root.mnist.loader.n_valid = 60
        root.mnist.loader.n_test = 0
        root.common.dirs.snapshots = str(tmp_path)
        gd = {"learning_rate": 0.1, "gradient_moment": 0.9}
        wf = StandardWorkflow(
            name="MnistStdLR",
            loader=MnistLoader(name="loader", minibatch_size=60),
            layers=[{"type": "all2all_tanh",
                     "->": {"output_sample_shape": 100}, "<-": dict(gd)},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 10}, "<-": dict(gd)}],
            loss_function="softmax",
            decision_config={"max_epochs": 3},
            lr_adjust_config={"policy": "exp", "gamma": 0.9})
        wf.initialize(device=None)
        return wf

    wfu = with_schedule()
    gd, unit_rates = wfu.gds[0], []
    gd_run = gd.run

    def recording_run():                # gated like the update itself
        unit_rates.append(float(gd.learning_rate))
        gd_run()

    gd.run = recording_run
    wfu.run()

    wff = with_schedule()
    trainer = FusedTrainer(wff)
    name, fused_rates = wff.gds[0].forward.name, []
    hypers_rows = trainer._hypers_rows

    def recording_rows(k, advance_last=True):
        rows = hypers_rows(k, advance_last)
        fused_rates.extend(float(r[0]) for r in rows[name])
        return rows

    trainer._hypers_rows = recording_rows
    trainer.run()
    assert trainer.stats["tails_in_scan"] == 2
    # 5 + 5 in scans that end in their tail, 4 before the last tail,
    # whose update is not adopted in either engine
    assert len(unit_rates) == len(fused_rates) == 14
    np.testing.assert_allclose(fused_rates, unit_rates, rtol=1e-6)
    assert wff.lr_adjust.iteration == wfu.lr_adjust.iteration == 14


@pytest.mark.parametrize("kind", ["DecisionBase", "DecisionGD",
                                  "DecisionMSE"])
def test_the_rule_asked_ahead_is_the_rule_run_adopts(kind):
    """``tail_stops`` (asked once the epoch's validation is fed, before
    its TRAIN minibatches) and ``run()`` at the tail agree on every epoch
    of a ``fail_iterations`` stop, through the ``improvement_metric`` each
    class has; asking changes no state; asked before the validation is
    fed it answers only where the metric cannot matter."""
    import copy

    from znicz_tpu import decision as decision_mod
    from znicz_tpu.loader.base import TRAIN, VALID

    d = getattr(decision_mod, kind)(name="decision", max_epochs=50,
                                    fail_iterations=2)
    d.class_lengths = [0, 60, 120]

    def feed(klass, value, last=False, ended=False):
        d.minibatch_class, d.last_minibatch = klass, last
        d.class_ended = ended or last
        d.minibatch_loss = value
        d.minibatch_n_err, d.minibatch_size = int(value * 60), 60
        d.run()

    def state():
        return copy.deepcopy({k: v for k, v in vars(d).items() if k in (
            "best_metric", "best_epoch", "_fails", "_acc_loss",
            "_acc_batches", "_acc_n_err", "_acc_samples", "epoch_metrics",
            "epoch_history")}), bool(d.complete), bool(d.improved), \
            bool(d.gd_skip), bool(d.epoch_ended)

    # validation improves twice, stalls, improves, then stalls for good
    valid = [0.9, 0.5, 0.5, 0.4, 0.45, 0.4]
    said, early = [], []
    for epoch, metric in enumerate(valid):
        d.epoch_number = epoch
        before = state()
        early.append(d.tail_stops(epoch, validated=False))
        assert state() == before
        feed(VALID, metric, ended=True)
        before = state()
        ahead = d.tail_stops(epoch)
        assert state() == before
        assert early[-1] in (None, ahead)
        feed(TRAIN, 1.0 / (epoch + 1))  # nothing TRAIN reads moves the rule
        assert d.tail_stops(epoch) is ahead
        feed(TRAIN, 1.0 / (epoch + 1), last=True)
        assert bool(d.complete) is ahead, (epoch, ahead)
        said.append(ahead)
    assert said == [False] * 5 + [True]
    # one stall behind it, the next epoch's validation decides
    assert early == [False, False, False, None, False, None]
    # without fail_iterations the metric cannot matter: answered early
    d.fail_iterations, d.epoch_number = 0, 6
    assert d.tail_stops(6, validated=False) is False
    assert d.tail_stops(49, validated=False) is True
    # no validation set: improvement is judged on TRAIN, the tail decides
    d.class_lengths = [0, 0, 120]
    assert d.tail_stops(6) is None and d.tail_stops(49) is None


def test_a_callback_stop_leaves_its_epochs_last_update_applied(tmp_path):
    """The documented difference: a stop that an ``on_epoch_end``
    callback asks for cannot be seen ahead, so where that epoch's tail
    rode the scan its update is applied when the callback runs — the unit
    engine skips it.  The fused run stands exactly one step further: the
    unit engine's stopping state plus that tail's update."""
    from znicz_tpu.core import prng
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)

    def stop_after_two(d):
        if d.epoch_number == 1:
            d.complete.set(True)

    wfu = fresh_mnist(max_epochs=10)
    wfu.decision.on_epoch_end.append(stop_after_two)
    _, wu = run_unit(wfu)
    wff = fresh_mnist(max_epochs=10)
    wff.decision.on_epoch_end.append(stop_after_two)
    trainer = FusedTrainer(wff)
    trainer.run()
    assert len(wff.decision.epoch_history) == 2 == len(
        wfu.decision.epoch_history)
    assert trainer.steps_done == 10
    assert (trainer.stats["tails_in_scan"],
            trainer.stats["tails_alone"]) == (2, 0)
    got = {f.name: np.array(f.weights.map_read()) for f in wff.forwards}
    assert not all(np.allclose(got[n], wu[n], rtol=2e-3, atol=2e-5)
                   for n in wu), "the tail's update was skipped"
    # the unit engine stands at the tail with its update gated off: apply
    # it (the fused step on the unit engine's state, the tail's rows, the
    # tenth step's key) and the two runs meet
    tail = FusedTrainer(wfu)
    params, velocities, _ = tail.make_train_step()(
        tail.extract_params(), tail.extract_velocities(), tail.hypers(),
        tail._op_value(wfu.loader.original_data),
        tail._op_value(wfu.loader.original_labels),
        np.array(wfu.loader.minibatch_indices.mem, np.int32),
        np.int32(wfu.loader.minibatch_size),
        prng.get("fused_trainer").jax_key(9))
    assert bool(wfu.loader.last_minibatch)
    for f in wff.forwards:
        np.testing.assert_allclose(
            got[f.name], np.asarray(params[f.name]["weights"]), rtol=2e-3,
            atol=2e-5, err_msg=f.name)


@pytest.mark.parametrize("staged", [False, True],
                         ids=["resident", "staged"])
def test_a_snapshot_of_an_epoch_whose_tail_rode_resumes_the_run(staged,
                                                                tmp_path):
    """The epoch-end hook of a segment that holds a tail runs before the
    loader moves on — a staged source's lookahead too: the snapshot has
    the tail's update and a loader that stands at the boundary, so a run
    resumed from it continues the uninterrupted run's trajectory (a
    loader already in the next epoch would shuffle twice)."""
    from znicz_tpu import snapshotter as snap_mod
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.snapshotter import Snapshotter

    def build():
        return (streaming_mnist(4, budget=0) if staged
                else fresh_mnist(max_epochs=4))

    root.common.dirs.snapshots = str(tmp_path)
    wf = build()
    wf.snapshotter.interval = 1         # an ``epoch_N`` file every epoch
    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    trainer = FusedTrainer(wf)
    trainer.run()
    assert trainer.stats["tails_in_scan"] == 3
    want = {f.name: np.array(f.weights.map_read()) for f in wf.forwards}
    snap = Snapshotter.load(wf.snapshotter.snapshot_path("epoch_1"))
    assert snap["loader"]["last_minibatch"]
    assert snap["loader"]["epoch_number"] == 1 == snap["epoch"]

    resumed = []
    wf2 = build()
    wf2.decision.on_epoch_end.append(
        lambda d: resumed.append(d.epoch_metrics[2]["loss"]))
    snap_mod.restore(wf2, snap)
    FusedTrainer(wf2).run()
    assert bool(wf2.decision.complete) and wf2.decision.epoch_number == 3
    np.testing.assert_allclose(resumed, losses[2:], rtol=1e-4)
    for f in wf2.forwards:
        np.testing.assert_allclose(
            np.array(f.weights.map_read()), want[f.name], rtol=2e-3,
            atol=2e-5, err_msg=f.name)


def test_a_staged_source_predicts_the_segment_that_ends_in_the_tail(
        tmp_path):
    """The stager replays the collector's rule, the Decision's word
    included: a staged run's groups — the epoch's last scan with its tail
    in it, the last epoch's tail alone — are all predicted (one miss, the
    run's cold start), and the run ends on the resident run's weights."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    weights = {}
    for budget in (1 << 30, 0):         # resident, then host-staged
        wf = streaming_mnist(3, budget)
        trainer = FusedTrainer(wf)
        assert trainer.staging == (budget == 0)
        trainer.run()
        assert trainer.stats["tails_in_scan"] == 2
        weights[budget] = {f.name: np.array(f.weights.map_read())
                           for f in wf.forwards}
    st = trainer._stager.stats()
    # an epoch: one validation group, one scan of 4 + the tail; the last:
    # validation, the scan of 4, the tail alone
    assert st["stage_hits"] + st["stage_misses"] == 2 + 2 + 3
    assert st["stage_misses"] <= 1 and st["stage_evictions"] == 0, st
    for name, want in weights[1 << 30].items():
        np.testing.assert_array_equal(weights[0][name], want, err_msg=name)
