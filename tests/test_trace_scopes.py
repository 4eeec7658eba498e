"""The trace speaks the program's names (ISSUE 25): ``jax.named_scope`` in
the fused trainer's traced programs (``input``, one scope per forward
unit, ``loss``, ``update/<layer>`` — metadata only), leaf spans with ids
and parents in the epoch loop, the same spans as ``znicz:*`` annotations
in a profiler session, and the dispatch counter.  Nothing here reads a
clock figure or the compile cache's state."""

import contextlib
import math
import re

import numpy as np
import pytest

from znicz_tpu import datasets, telemetry
from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.loader.base import TRAIN, VALID

# -- three small workflows ------------------------------------------------------


def _mnist_cfg(max_epochs, n_train):
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = 120
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = max_epochs


def _fully_connected(max_epochs=1, n_train=180):
    from znicz_tpu.samples import mnist

    _mnist_cfg(max_epochs, n_train)
    return mnist.MnistWorkflow()


def _textures(layers, max_epochs):
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    class _Loader(FullBatchLoader):
        def load_data(self):
            data, labels = datasets.tinyimages(100, size=19)
            self.original_data.mem = data
            self.original_labels.mem = labels
            self.class_lengths = [0, 40, 60]
            super().load_data()

    return StandardWorkflow(
        name="TinyTextures", loader=_Loader(name="loader",
                                            minibatch_size=20),
        layers=layers, loss_function="softmax",
        decision_config={"max_epochs": max_epochs, "fail_iterations": 0})


GD = {"learning_rate": 0.02, "gradient_moment": 0.9}


def _conv_lrn_pool(max_epochs=1):
    return _textures([
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 8, "kx": 5, "ky": 5, "padding": (2, 2, 2, 2)},
         "<-": dict(GD)},
        {"type": "norm"},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": dict(GD)}], max_epochs)


def _conv_fused_tail(max_epochs=1):
    return _textures([
        {"type": "conv_strict_relu",
         "->": {"n_kernels": 8, "kx": 3, "ky": 3, "padding": (1, 1, 1, 1)},
         "<-": dict(GD)},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
        {"type": "all2all_strict_relu", "->": {"output_sample_shape": 32},
         "<-": dict(GD)},
        {"type": "dropout", "dropout_ratio": 0.5},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": dict(GD)}], max_epochs)


WORKFLOWS = {"fully_connected": (_fully_connected, False),
             "conv_lrn_pool": (_conv_lrn_pool, False),
             "conv_fused_tail": (_conv_fused_tail, True)}


@contextlib.contextmanager
def _built(which, tmp_path, max_epochs=1):
    """The workflow initialised and its trainer, with ``fused_tail``
    planning on where the workflow asks for it."""
    from znicz_tpu.parallel.fused import FusedTrainer

    build, fused_tail = WORKFLOWS[which]
    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.fused_tail = fused_tail
    prng.reset(1013)
    try:
        wf = build(max_epochs)
        wf.initialize(device=None)
        yield wf, FusedTrainer(wf)
    finally:
        root.common.engine.fused_tail = False


# -- device side: the compiled text carries the scopes -------------------------


def _compiled_text(trainer, program):
    """The compiled text of one of the trainer's four programs, lowered on
    shapes the epoch loop would hand it (a 3-step scan)."""
    loader = trainer.loader
    params = trainer.extract_params()
    velocities = trainer.extract_velocities()
    dataset = loader.original_data.devmem
    targets = loader.original_labels.devmem
    batch = int(loader.max_minibatch_size)
    idx = np.arange(batch, dtype=np.int32)
    idx_mat = np.stack([idx, idx, idx])
    bs_vec = np.full((3,), batch, np.int32)
    key = prng.get("fused_trainer").jax_key(0)
    if program == "train_step":
        lowered = trainer.make_train_step().lower(
            params, velocities, trainer.hypers(), dataset, targets, idx,
            np.int32(batch), key)
    elif program == "train_scan":
        lowered = trainer.make_train_scan().lower(
            params, velocities, trainer.tiled_hypers(3), dataset, targets,
            idx_mat, bs_vec, prng.get("fused_trainer").jax_base_key(),
            np.arange(3, dtype=np.int32))
    elif program == "eval_step":
        lowered = trainer.make_eval_step().lower(
            params, dataset, targets, idx, np.int32(batch), key, False)
    else:
        lowered = trainer.make_eval_scan().lower(
            params, dataset, targets, idx_mat, bs_vec)
    return lowered.compile().as_text()


def _scoped_units(trainer, train):
    """Names the forward pass opens a scope for: every unit but those a
    fused span absorbed, and (in evaluation) dropout, which traces
    nothing there."""
    from znicz_tpu.pallas_fused_block import (plan_fused_blocks,
                                              plan_fused_tail)

    plan = plan_fused_blocks(trainer.forwards)
    plan = {**plan_fused_tail(trainer.forwards, plan), **plan}
    names, i = [], 0
    while i < len(trainer.forwards):
        f = trainer.forwards[i]
        if train or not isinstance(f, trainer._dropout_cls):
            names.append(f.name)
        i += plan[i].span if i in plan else 1
    return names


@pytest.mark.parametrize("program", ["train_step", "train_scan",
                                     "eval_step", "eval_scan"])
@pytest.mark.parametrize("which", list(WORKFLOWS))
def test_compiled_text_names_the_models_units(which, program, tmp_path):
    with _built(which, tmp_path) as (wf, trainer):
        text = _compiled_text(trainer, program)
        train = program.startswith("train")
        units = _scoped_units(trainer, train)
        weighted = [f.name for f in trainer.forwards if f.has_weights]
    names = set(re.findall(r'op_name="([^"]+)"', text))
    parts = {part for name in names for part in name.split("/")}
    assert "input" in parts, sorted(names)[:20]
    assert any(p in ("loss", "jvp(loss)") for p in parts)
    for unit in units:
        assert (f"jvp({unit})" if train else unit) in parts, unit
    if train:
        for unit in weighted:
            assert f"transpose(jvp({unit}))" in parts, unit
            assert any(f"/update/{unit}/" in name for name in names), unit
    else:
        assert not any(p == "update" or p.startswith(("transpose(", "jvp("))
                       for p in parts)


@pytest.mark.parametrize("which", list(WORKFLOWS))
def test_scopes_are_metadata_only(which, tmp_path, monkeypatch):
    """Three train steps (every workflow here has three train minibatches:
    a two-step scan and the tail's step) leave the same bits with
    ``jax.named_scope`` taken out, in as many programs."""
    import jax

    def run():
        with _built(which, tmp_path) as (wf, trainer):
            trainer.scan_chunk = 2
            trainer.run()
            assert trainer.stats["train_steps"] == 3
            return ({f.name: np.array(f.weights.map_read())
                     for f in wf.forwards if f.has_weights},
                    dict(trainer.stats["jit_cache_sizes"]),
                    trainer.stats["compiles"])

    scoped = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = run()
    assert scoped[1:] == plain[1:]
    assert scoped[2] == sum(scoped[1].values())
    for name, weights in scoped[0].items():
        np.testing.assert_array_equal(weights, plain[0][name], err_msg=name)


# -- host side: leaf spans, ids and parents, the dispatch counter --------------


def _streaming_mnist():
    """The mnist sample over a host-staged streaming loader."""
    from znicz_tpu.loader.streaming import HostArraySource, StreamingLoader
    from znicz_tpu.samples import mnist

    class _Staged(StreamingLoader):
        def __init__(self, workflow=None, name=None, **kwargs):
            cfg = root.mnist.loader
            lengths = [int(cfg.n_test), int(cfg.n_valid), int(cfg.n_train)]
            data, labels = datasets.load_or_generate(
                None, datasets.digits, sum(lengths))
            super().__init__(
                workflow=workflow, name=name,
                source=HostArraySource(data.reshape(sum(lengths), -1),
                                       labels),
                class_lengths=lengths, device_budget_bytes=0, **kwargs)

    orig = mnist.MnistLoader
    mnist.MnistLoader = _Staged
    try:
        return mnist.MnistWorkflow()
    finally:
        mnist.MnistLoader = orig


def _two_epochs(tmp_path, scan_chunk, staged):
    """A two-epoch mnist run; returns the trainer and the ``train`` events
    its loop recorded."""
    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    root.common.engine.scan_chunk = scan_chunk
    try:
        _mnist_cfg(2, 300)
        wf = _streaming_mnist() if staged else _fully_connected(2, 300)
        wf.initialize(device=None)
        trainer = FusedTrainer(wf)
    finally:
        root.common.engine.scan_chunk = 8
    assert trainer.staging == staged and trainer.scan_chunk == scan_chunk
    ring = telemetry.tracer()
    ring.clear()
    trainer.run()
    return trainer, [e for e in ring.events() if e[0] == "train"]


def _within(child, parent, slack_us=2):
    """Timestamps are whole microseconds: allow the rounding."""
    return (parent[2] - slack_us <= child[2]
            and child[2] + child[3] <= parent[2] + parent[3] + slack_us)


@pytest.mark.parametrize("staged", [False, True],
                         ids=["resident", "staged"])
@pytest.mark.parametrize("scan_chunk", [1, 4])
def test_leaf_spans_nest_and_dispatches_are_counted(scan_chunk, staged,
                                                    tmp_path):
    trainer, events = _two_epochs(tmp_path, scan_chunk, staged)
    by_id = {e[5]["id"]: e for e in events}
    assert len(by_id) == len(events)
    kids = {}
    for e in events:
        parent = e[5]["parent"]
        if parent:
            assert parent in by_id, e
            assert _within(e, by_id[parent]), (e, by_id[parent])
            kids.setdefault(parent, []).append(e)
    names = [e[1] for e in events]
    tails = [e for e in events if e[1] == "tail"]
    assert len(tails) == 2 and names.count("epoch_hook") == 2
    for i, tail in enumerate(tails):
        assert tail[5]["parent"] == 0 and tail[5]["epoch"] == i
        leaves = [k[1] for k in sorted(kids[tail[5]["id"]],
                                       key=lambda k: k[2])]
        # the first epoch's tail rode its scan (the Decision said ahead
        # that the run goes on): the span is that segment's pull.  The
        # run's last is ruled on alone and never adopted (gd_skip closed)
        want = [["sync", "decide"], ["tail_eval", "sync", "decide"]][i]
        assert leaves == want, leaves
    assert (trainer.stats["tails_in_scan"],
            trainer.stats["tails_alone"]) == (1, 1)
    for name in ("flush", "eval"):
        for e in (e for e in events if e[1] == name):
            held = [k[1] for k in kids.get(e[5]["id"], [])]
            assert "sync" in held and "decide" in held, (name, held)
    if staged:
        staging = [e for e in events if e[1] == "stage"]
        assert staging and all(by_id[e[5]["parent"]][1].split(":")[0] in (
            "dispatch", "tail_eval", "eval") for e in staging)
    dispatching = [n for n in names if n.split(":")[0] in (
        "dispatch", "eval", "tail_eval", "tail_update")]
    # from the loader's geometry: each epoch validates and trains its
    # minibatches, the tail too, in groups of scan_chunk; the last
    # epoch's tail is one program more, its evaluation
    lengths, batch = trainer.loader.class_lengths, 60
    validating = math.ceil(math.ceil(lengths[VALID] / batch) / scan_chunk)
    training = math.ceil(lengths[TRAIN] / batch)
    assert trainer.stats["dispatches"] == len(dispatching) == (
        2 * validating + math.ceil(training / scan_chunk)
        + math.ceil((training - 1) / scan_chunk) + 1)
    # each kind's first call is not warm
    assert 0 < trainer.stats["warm_dispatches"] < len(dispatching)
    steps = [e[5]["step0"] for e in events
             if e[1].split(":")[0] == "dispatch"]
    assert steps == sorted(steps) and steps[0] == 0
    for stat in ("sync_wait_s", "decide_s", "epoch_hook_s"):
        assert trainer.stats[stat] > 0, stat


# -- the same spans on the profiler's clock ------------------------------------


def _profiled(tmp_path, enabled):
    """A two-epoch run inside a profiler session: the ``znicz:train:*``
    events of the trace's host plane, and the ring's ``train`` spans."""
    import jax
    from jax.profiler import ProfileData

    from benchmark.reduce import xplane

    trace_dir = str(tmp_path / "trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    telemetry.set_enabled(enabled)
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            _, events = _two_epochs(tmp_path, 4, False)
        finally:
            jax.profiler.stop_trace()
    finally:
        telemetry.set_enabled(True)
    profile = ProfileData.from_file(xplane.newest_xplane(trace_dir))
    annotations = [ev for plane in profile.planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("znicz:train:")]
    return annotations, events


def test_profiler_trace_holds_the_rings_spans(tmp_path):
    annotations, events = _profiled(tmp_path, True)
    spans = [e for e in events if "id" in (e[5] or {})]
    assert spans and len(annotations) == len(spans)
    count = {}
    for ev in annotations:
        count[ev.name] = count.get(ev.name, 0) + 1
    for e in spans:
        count[f"znicz:train:{e[1]}"] -= 1
    assert not any(count.values()), count
    # a dispatch is a step event: it carries the step it starts at
    steps = sorted(dict(ev.stats)["step_num"] for ev in annotations
                   if ev.name.startswith("znicz:train:dispatch"))
    assert steps == sorted(e[5]["step0"] for e in spans
                           if e[1].startswith("dispatch"))


def test_disabled_ring_records_nothing_and_annotates_nothing(tmp_path):
    annotations, events = _profiled(tmp_path, False)
    assert annotations == [] and events == []


def test_compile_cache_key_includes_the_names(monkeypatch, tmp_path):
    """The scopes are metadata, which jax's persistent cache leaves out of
    its key unless told: a tree without them would otherwise hand this one
    executables that name nothing."""
    import jax

    from znicz_tpu.backends import configure_compile_cache

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        jax.config.update(flag, False)
        assert configure_compile_cache() == str(tmp_path)
        assert getattr(jax.config, flag) is True
    finally:
        jax.config.update(flag, before)
