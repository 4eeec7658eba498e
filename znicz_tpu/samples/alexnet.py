"""AlexNet ImageNet sample — rebuild of the reference's
``znicz/samples/AlexNet`` workflow, BASELINE config[4].

Standard single-tower AlexNet (227x227x3 -> 1000): 5 convs (11/5/3/3/3) with
LRN after conv1/conv2, overlapping 3x3/s2 max pools, fc6/fc7 4096 with
dropout 0.5, softmax 1000.  Trains data-parallel: the FusedTrainer jits one
SPMD step over the device mesh; gradient psum rides ICI (the reference
shipped gradients to a ZeroMQ master instead — SURVEY.md §2.4).

Data: procedural 227x227 texture classes (no network in this environment);
point ``root.alexnet.loader.data_path`` at a real .npz for actual ImageNet.
"""

from __future__ import annotations

import numpy as np

from znicz_tpu import datasets
from znicz_tpu.core.config import root
from znicz_tpu.loader.fullbatch import FullBatchLoader
from znicz_tpu.standard_workflow import StandardWorkflow

root.alexnet.defaults({
    "loader": {"minibatch_size": 128, "n_train": 512, "n_valid": 128,
               "n_test": 0, "n_classes": 100, "image_size": 227,
               "data_path": "", "train_dir": "", "valid_dir": "",
               "stream": False, "stream_budget_mb": 0},
    "learning_rate": 0.01,
    "gradient_moment": 0.9,
    "weights_decay": 0.0005,
    "dropout": 0.5,
    "decision": {"max_epochs": 3, "fail_iterations": 0},
    "snapshotter": {"prefix": "alexnet", "interval": 0},
})


class AlexNetLoader(FullBatchLoader):
    def load_data(self):
        cfg = root.alexnet.loader
        n_train = int(cfg.get("n_train"))
        n_valid = int(cfg.get("n_valid"))
        n_test = int(cfg.get("n_test"))
        total = n_train + n_valid + n_test
        data, labels = datasets.load_or_generate(
            cfg.get("data_path") or None, datasets.tinyimages, total,
            size=int(cfg.get("image_size", 227)))
        labels = (labels % int(cfg.get("n_classes", 100))).astype(np.int32)
        self.original_data.mem = data
        self.original_labels.mem = labels
        self.class_lengths = [n_test, n_valid, n_train]
        super().load_data()


def make_layers(n_classes: int):
    cfg = root.alexnet
    gd = {"learning_rate": float(cfg.get("learning_rate")),
          "gradient_moment": float(cfg.get("gradient_moment")),
          "weights_decay": float(cfg.get("weights_decay"))}
    drop = float(cfg.get("dropout"))
    return [
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 96, "kx": 11, "ky": 11, "sliding": (4, 4)},
         "<-": dict(gd)},
        {"type": "norm"},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 256, "kx": 5, "ky": 5, "padding": (2, 2, 2, 2)},
         "<-": dict(gd)},
        {"type": "norm"},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 384, "kx": 3, "ky": 3, "padding": (1, 1, 1, 1)},
         "<-": dict(gd)},
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 384, "kx": 3, "ky": 3, "padding": (1, 1, 1, 1)},
         "<-": dict(gd)},
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 256, "kx": 3, "ky": 3, "padding": (1, 1, 1, 1)},
         "<-": dict(gd)},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "all2all_strict_relu", "->": {"output_sample_shape": 4096},
         "<-": dict(gd)},
        {"type": "dropout", "->": {"dropout_ratio": drop}},
        {"type": "all2all_strict_relu", "->": {"output_sample_shape": 4096},
         "<-": dict(gd)},
        {"type": "dropout", "->": {"dropout_ratio": drop}},
        {"type": "softmax", "->": {"output_sample_shape": n_classes},
         "<-": dict(gd)},
    ]


class AlexNetWorkflow(StandardWorkflow):
    """``root.alexnet.loader.train_dir`` (directory of class subdirs of
    image files — the reference's file-image route) switches the loader to
    ``FullBatchFileImageLoader`` with the ``image_size`` knob; the class
    count then comes from the directory tree.  With
    ``root.alexnet.loader.stream`` true the same directory feeds a
    ``StreamingLoader`` over a decode-on-demand ``ImageFileSource``
    instead — the ImageNet-at-scale route: nothing is decoded up front,
    HBM residency is capped by ``stream_budget_mb`` (0 = the engine
    default), and beyond it the fused driver stages minibatches straight
    from disk.  Otherwise data_path/.npz or the procedural stand-in feed
    the plain AlexNetLoader."""

    def __init__(self, **kwargs):
        cfg = root.alexnet
        train_dir = cfg.loader.get("train_dir", "")
        if train_dir and bool(cfg.loader.get("stream", False)):
            from znicz_tpu.loader.image import scan_class_dirs
            from znicz_tpu.loader.streaming import (ImageFileSource,
                                                    StreamingLoader)

            size = int(cfg.loader.get("image_size", 227))
            valid_dir = cfg.loader.get("valid_dir", "") or None
            # [valid | train] sample order matches the class offsets
            v_paths, v_labels = [], []
            if valid_dir:
                v_paths, v_labels, v_names = scan_class_dirs(valid_dir)
            t_paths, t_labels, names = scan_class_dirs(train_dir)
            if valid_dir:
                index_of = {n: i for i, n in enumerate(names)}
                v_labels = [index_of[v_names[l]] for l in v_labels]
            source = ImageFileSource(
                list(v_paths) + list(t_paths),
                list(v_labels) + list(t_labels), (size, size))
            budget_mb = float(cfg.loader.get("stream_budget_mb", 0))
            loader = StreamingLoader(
                name="loader", source=source,
                class_lengths=[0, len(v_paths), len(t_paths)],
                device_budget_bytes=int(budget_mb * 2**20) or None,
                minibatch_size=int(cfg.loader.get("minibatch_size")))
            n_classes = len(names)
        elif train_dir:
            import os

            from znicz_tpu.loader.image import FullBatchFileImageLoader

            size = int(cfg.loader.get("image_size", 227))
            loader = FullBatchFileImageLoader(
                name="loader", train_path=train_dir,
                valid_path=cfg.loader.get("valid_dir", "") or None,
                target_shape=(size, size),
                minibatch_size=int(cfg.loader.get("minibatch_size")))
            # class count = class SUBDIRS (scan_class_dirs' class_names
            # rule) — not the full per-file walk, which the loader
            # performs once itself at load_data
            n_classes = sum(
                os.path.isdir(os.path.join(train_dir, d))
                for d in os.listdir(train_dir))
        else:
            loader = AlexNetLoader(
                name="loader",
                minibatch_size=int(cfg.loader.get("minibatch_size")))
            n_classes = int(cfg.loader.get("n_classes", 100))
        super().__init__(
            name="AlexNetWorkflow", loader=loader,
            layers=make_layers(n_classes),
            loss_function="softmax",
            decision_config={
                "max_epochs": int(cfg.decision.get("max_epochs")),
                "fail_iterations": int(cfg.decision.get("fail_iterations"))},
            snapshotter_config={
                "prefix": cfg.snapshotter.get("prefix"),
                "interval": int(cfg.snapshotter.get("interval", 0))},
            **kwargs)


def run(device=None, fused: bool = True, mesh=None) -> AlexNetWorkflow:
    wf = AlexNetWorkflow()
    wf.initialize(device=device)
    if fused:
        from znicz_tpu.parallel.fused import FusedTrainer
        from znicz_tpu.parallel.mesh import train_mesh_from_config

        if mesh is None:
            mesh = train_mesh_from_config()
        FusedTrainer(wf, mesh=mesh).run()
        wf.print_stats()
    else:
        wf.run()
        wf.print_stats()
    return wf


if __name__ == "__main__":
    run()
