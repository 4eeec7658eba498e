"""Streaming loader (loader/streaming.py): host-staged segments and
u8-HBM-residency must train EXACTLY like the resident FullBatch path —
same losses, same weights, same confusion — across segment boundaries,
short tail minibatches and epoch reshuffles (VERDICT r3 item 1)."""

import os

import numpy as np
import pytest

from znicz_tpu import datasets
from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.loader.streaming import (HostArraySource, ImageFileSource,
                                        StreamingLoader, class_dir_source)


def _mnist_cfg(max_epochs=2):
    # n_train NOT divisible by minibatch_size: the epoch tail is short,
    # covering the padded-gather route in both regimes
    root.mnist.loader.n_train = 290
    root.mnist.loader.n_valid = 60
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = max_epochs


def _digits(u8=False):
    """The same procedural digits the MnistLoader would draw (same prng
    stream position), flattened sample-major."""
    cfg = root.mnist.loader
    total = int(cfg.n_train) + int(cfg.n_valid) + int(cfg.n_test)
    data, labels = datasets.load_or_generate(None, datasets.digits, total)
    data = data.reshape(total, -1)
    if u8:
        data = np.clip(np.round(data * 255.0), 0, 255).astype(np.uint8)
    return data, labels


class _StreamingMnistLoader(StreamingLoader):
    """Drop-in for MnistLoader: same digits data via a streaming source.
    Class attrs select the regime for the next construction."""

    u8 = False
    budget = 0          # 0 -> host-staged; big -> resident

    def __init__(self, workflow=None, name=None, **kwargs):
        cfg = root.mnist.loader
        data, labels = _digits(u8=type(self).u8)
        super().__init__(
            workflow=workflow, name=name,
            source=HostArraySource(data, labels),
            class_lengths=[int(cfg.n_test), int(cfg.n_valid),
                           int(cfg.n_train)],
            scale=(1.0 / 255.0 if type(self).u8 else 1.0), shift=0.0,
            device_budget_bytes=type(self).budget, **kwargs)


def _fresh(loader_cls=None, max_epochs=2):
    """MnistWorkflow with its loader class optionally swapped (the sample
    resolves MnistLoader as a module global)."""
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    _mnist_cfg(max_epochs)
    orig = mnist.MnistLoader
    if loader_cls is not None:
        mnist.MnistLoader = loader_cls
    try:
        wf = mnist.MnistWorkflow()
    finally:
        mnist.MnistLoader = orig
    wf.initialize(device=None)
    return wf


def _run_fused(wf, mesh=None):
    from znicz_tpu.parallel.fused import FusedTrainer

    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    FusedTrainer(wf, mesh=mesh).run()
    return losses, {f.name: np.array(f.weights.map_read())
                    for f in wf.forwards}


def test_staged_f32_matches_resident(tmp_path):
    """Host-staged streaming (budget 0) reproduces the resident FullBatch
    trajectory bit-for-bit: same samples, same order, same math — only the
    residency moved."""
    root.common.dirs.snapshots = str(tmp_path)
    lr, wr = _run_fused(_fresh())
    _StreamingMnistLoader.u8, _StreamingMnistLoader.budget = False, 0
    ls, ws = _run_fused(_fresh(_StreamingMnistLoader))
    np.testing.assert_allclose(lr, ls, rtol=1e-6)
    for name in wr:
        np.testing.assert_allclose(wr[name], ws[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)


def test_staged_streaming_actually_stages(tmp_path):
    root.common.dirs.snapshots = str(tmp_path)
    from znicz_tpu.parallel.fused import FusedTrainer

    _StreamingMnistLoader.u8, _StreamingMnistLoader.budget = False, 0
    wf = _fresh(_StreamingMnistLoader)
    trainer = FusedTrainer(wf)
    assert trainer.staging
    assert not wf.loader.device_resident
    assert wf.loader.original_data.mem is None      # nothing resident
    trainer.run()
    # 10-class CE starts at ln(10) ~= 2.30; two epochs must clearly train
    assert wf.decision.epoch_metrics[2]["loss"] < 2.0


def test_u8_resident_matches_u8_staged(tmp_path):
    """Regime 2 (whole u8 dataset in HBM, decode fused into the gather)
    and regime 3 (u8 staged per segment) are the same math."""
    root.common.dirs.snapshots = str(tmp_path)
    _StreamingMnistLoader.u8, _StreamingMnistLoader.budget = True, 1 << 30
    lr, wr = _run_fused(_fresh(_StreamingMnistLoader))
    _StreamingMnistLoader.budget = 0
    ls, ws = _run_fused(_fresh(_StreamingMnistLoader))
    np.testing.assert_allclose(lr, ls, rtol=1e-6)
    for name in wr:
        np.testing.assert_allclose(wr[name], ws[name], rtol=1e-5,
                                   atol=1e-7, err_msg=name)
    assert lr[-1] < lr[0]                        # and it actually trains


def test_u8_device_decode_matches_host_decode(tmp_path):
    """u8*scale+shift on device == the host pre-decoded f32 dataset (both
    are exact f32 ops), so a u8 streaming run must match a resident f32
    run over the SAME decoded values."""
    from znicz_tpu.samples import mnist

    root.common.dirs.snapshots = str(tmp_path)

    class _PreDecoded(mnist.MnistLoader):
        def load_data(self):
            cfg = root.mnist.loader
            data, labels = _digits(u8=True)
            self.original_data.mem = (data.astype(np.float32) / 255.0)
            self.original_labels.mem = labels
            self.class_lengths = [int(cfg.n_test), int(cfg.n_valid),
                                  int(cfg.n_train)]
            from znicz_tpu.loader.fullbatch import FullBatchLoader

            FullBatchLoader.load_data(self)

    lr, wr = _run_fused(_fresh(_PreDecoded))
    _StreamingMnistLoader.u8, _StreamingMnistLoader.budget = True, 0
    ls, ws = _run_fused(_fresh(_StreamingMnistLoader))
    np.testing.assert_allclose(lr, ls, rtol=1e-5)
    for name in wr:
        np.testing.assert_allclose(wr[name], ws[name], rtol=1e-4,
                                   atol=1e-6, err_msg=name)


def test_staged_data_parallel_8dev_matches_single(tmp_path):
    """Streaming composes with the data mesh: staged segments are put
    replicated, the in-step sharding constraint shards the gathered batch."""
    import jax

    root.common.dirs.snapshots = str(tmp_path)
    assert len(jax.devices()) >= 8
    from znicz_tpu.parallel.mesh import make_mesh

    _StreamingMnistLoader.u8, _StreamingMnistLoader.budget = False, 0
    l1, w1 = _run_fused(_fresh(_StreamingMnistLoader))
    l8, w8 = _run_fused(_fresh(_StreamingMnistLoader),
                        mesh=make_mesh(axes=("data",)))
    np.testing.assert_allclose(l1, l8, rtol=1e-4)
    for name in w1:
        np.testing.assert_allclose(w1[name], w8[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)


def test_streaming_unit_engine_path(tmp_path):
    """The unit-at-a-time engine drives the streaming loader through
    fill_minibatch (host gather + decode) — slow but identical semantics."""
    root.common.dirs.snapshots = str(tmp_path)
    _StreamingMnistLoader.u8, _StreamingMnistLoader.budget = False, 0
    lr, wr = _run_fused(_fresh())
    prng.reset(1013)
    wf = _fresh(_StreamingMnistLoader)
    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    wf.run()
    np.testing.assert_allclose(lr, losses, rtol=1e-4)


def _write_class_tree(base, n_per_class=4, size=(12, 12)):
    from PIL import Image

    rng = np.random.default_rng(7)
    for cname in ("cat", "dog"):
        d = os.path.join(base, cname)
        os.makedirs(d)
        for i in range(n_per_class):
            arr = rng.integers(0, 255, size + (3,), dtype=np.uint8)
            Image.fromarray(arr).save(os.path.join(d, f"{i}.png"))


def test_image_file_source_streams(tmp_path):
    """Decode-on-demand image files as the host source: rows decode only
    when a segment stages them; a tiny conv net trains one epoch."""
    base = str(tmp_path / "imgs")
    os.makedirs(base)
    _write_class_tree(base)
    src = class_dir_source(base, target_shape=(12, 12))
    assert len(src) == 8 and src.dtype == np.uint8
    rows = src.gather(np.array([0, 5], np.int32))
    assert rows.shape == (2, 12, 12, 3) and rows.dtype == np.uint8

    from znicz_tpu.all2all import All2AllSoftmax
    from znicz_tpu.core.workflow import Repeater, Workflow
    from znicz_tpu.decision import DecisionGD
    from znicz_tpu.evaluator import EvaluatorSoftmax
    from znicz_tpu.gd import GDSoftmax
    from znicz_tpu.parallel.fused import FusedTrainer

    class WF(Workflow):
        def __init__(self):
            super().__init__(name="ImgStreamWF")
            self.repeater = Repeater(self, name="repeater")
            self.repeater.link_from(self.start_point)
            self.loader = StreamingLoader(
                self, name="loader", source=src, minibatch_size=4,
                class_lengths=[0, 2, 6], device_budget_bytes=0)
            self.loader.link_from(self.repeater)
            fwd = All2AllSoftmax(self, name="fwd0",
                                 output_sample_shape=(2,))
            fwd.link_from(self.loader)
            fwd.link_attrs(self.loader, ("input", "minibatch_data"))
            self.forwards = [fwd]
            self.evaluator = EvaluatorSoftmax(self, name="evaluator",
                                              n_classes=2)
            self.evaluator.link_from(fwd)
            self.evaluator.link_attrs(fwd, "output")
            self.evaluator.link_attrs(
                self.loader, ("labels", "minibatch_labels"),
                ("batch_size", "minibatch_size"))
            self.decision = DecisionGD(self, name="decision", max_epochs=1)
            self.decision.link_from(self.evaluator)
            self.decision.link_attrs(
                self.loader, "minibatch_class", "last_minibatch",
                "class_ended", "epoch_number", "class_lengths",
                "minibatch_size")
            self.decision.link_attrs(
                self.evaluator, ("minibatch_loss", "loss"),
                ("minibatch_n_err", "n_err"), "confusion_matrix",
                "max_err_output_sum")
            gd = GDSoftmax(self, name="gd0", forward=fwd,
                           learning_rate=0.05, need_err_input=False)
            gd.link_from(self.decision)
            gd.link_attrs(self.evaluator, ("err_output", "err_output"))
            gd.gate_skip = self.decision.gd_skip
            self.gds = [gd]
            self.repeater.link_from(gd)
            self.end_point.link_from(self.decision)
            self.end_point.gate_block = ~self.decision.complete

    prng.reset(1013)
    wf = WF()
    wf.initialize(device=None)
    trainer = FusedTrainer(wf)
    assert trainer.staging
    trainer.run()
    assert np.isfinite(wf.decision.epoch_metrics[2]["loss"])


def test_streaming_rejects_nonlinear_normalizer():
    from znicz_tpu.normalization import MeanDispNormalizer

    with pytest.raises(ValueError, match="normalizer"):
        StreamingLoader(None, name="x",
                        source=np.zeros((4, 3), np.float32),
                        normalizer=MeanDispNormalizer())


def test_streaming_mse_without_targets_raises():
    """A StreamingLoader built without regression targets must fail an MSE
    fused run with a clear config error at run start, not an opaque crash
    deep inside the staging/operand path (ADVICE r4)."""
    from znicz_tpu.all2all import All2AllTanh
    from znicz_tpu.core.workflow import Repeater, Workflow
    from znicz_tpu.decision import DecisionMSE
    from znicz_tpu.evaluator import EvaluatorMSE
    from znicz_tpu.gd import GDTanh
    from znicz_tpu.parallel.fused import FusedTrainer

    data = np.random.RandomState(0).rand(32, 6).astype(np.float32)

    class WF(Workflow):
        def __init__(self):
            super().__init__(name="MseStreamWF")
            self.repeater = Repeater(self, name="repeater")
            self.repeater.link_from(self.start_point)
            self.loader = StreamingLoader(
                self, name="loader", source=HostArraySource(data),
                minibatch_size=8, class_lengths=[0, 8, 24],
                scale=1.0, device_budget_bytes=0)
            self.loader.link_from(self.repeater)
            fwd = All2AllTanh(self, name="fwd0", output_sample_shape=(6,))
            fwd.link_from(self.loader)
            fwd.link_attrs(self.loader, ("input", "minibatch_data"))
            self.forwards = [fwd]
            self.evaluator = EvaluatorMSE(self, name="evaluator")
            self.evaluator.link_from(fwd)
            self.evaluator.link_attrs(fwd, "output")
            self.evaluator.link_attrs(
                self.loader, ("target", "minibatch_targets"),
                ("batch_size", "minibatch_size"))
            self.decision = DecisionMSE(self, name="decision", max_epochs=1)
            self.decision.link_from(self.evaluator)
            self.decision.link_attrs(
                self.loader, "minibatch_class", "last_minibatch",
                "class_ended", "epoch_number", "class_lengths",
                "minibatch_size")
            self.decision.link_attrs(self.evaluator,
                                     ("minibatch_loss", "loss"))
            gd = GDTanh(self, name="gd0", forward=fwd, learning_rate=0.01,
                        need_err_input=False)
            gd.link_from(self.decision)
            gd.link_attrs(self.evaluator, ("err_output", "err_output"))
            gd.gate_skip = self.decision.gd_skip
            self.gds = [gd]
            self.repeater.link_from(gd)
            self.end_point.link_from(self.decision)
            self.end_point.gate_block = ~self.decision.complete

    wf = WF()
    wf.initialize(device=None)
    with pytest.raises(ValueError, match="regression targets"):
        FusedTrainer(wf).run()
