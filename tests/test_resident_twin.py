"""The resident set is laid out for its gather once (ISSUE 26): every
program of the fused trainer gathers from a prepared twin of the loader's
array (``FusedTrainer._resident``) — sample axis major-most, already in
the dtype the first unit consumes — and the results are the bits the
same steps give when fed ``jnp.take(raw, idx)`` directly.  The last tests
compile for the chip without the chip and are the only ones in ``tests/``
that describe a topology (one file: the TPU's library is one process's),
so the attention core's kernels (ISSUE 28) meet the chip's compiler here
too; nothing here reads a clock."""

import re

import numpy as np
import pytest

from znicz_tpu import datasets, telemetry
from znicz_tpu.core import prng
from znicz_tpu.core.config import root

GD = {"learning_rate": 0.02, "gradient_moment": 0.9}
LAYERS = [
    {"type": "conv_strict_relu",
     "->": {"n_kernels": 8, "kx": 5, "ky": 5, "padding": (2, 2, 2, 2)},
     "<-": dict(GD)},
    {"type": "norm"},
    {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
    {"type": "all2all_strict_relu", "->": {"output_sample_shape": 32},
     "<-": dict(GD)},
    {"type": "dropout", "dropout_ratio": 0.5},
    {"type": "softmax", "->": {"output_sample_shape": 10}, "<-": dict(GD)}]
BATCH, STEPS = 16, 3


def _textures(storage, max_epochs=2):
    """A small convolutional workflow over 104 resident 19x19 images:
    float32 in a ``FullBatchLoader``, or uint8 in a ``StreamingLoader``
    whose budget keeps the set on the device (decode in-graph)."""
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.loader.streaming import HostArraySource, StreamingLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    prng.reset(1013)
    data, labels = datasets.tinyimages(104, size=19)
    lengths = [0, 24, 80]
    if storage == "uint8":
        u8 = np.clip(np.round(data * 255.0), 0, 255).astype(np.uint8)
        loader = StreamingLoader(
            name="loader", source=HostArraySource(u8, labels),
            class_lengths=lengths, device_budget_bytes=1 << 30,
            minibatch_size=BATCH)
    else:
        class _Loader(FullBatchLoader):
            def load_data(self):
                self.original_data.mem = data
                self.original_labels.mem = labels
                self.class_lengths = lengths
                super().load_data()

        loader = _Loader(name="loader", minibatch_size=BATCH)
    wf = StandardWorkflow(
        name="TwinTextures", loader=loader, layers=LAYERS,
        loss_function="softmax",
        decision_config={"max_epochs": max_epochs, "fail_iterations": 0})
    wf.initialize(device=None)
    return wf


@pytest.fixture
def compute_dtype():
    saved = root.common.engine.get("compute_dtype", None)

    def set_dtype(value):
        root.common.engine.compute_dtype = value

    yield set_dtype
    root.common.engine.compute_dtype = saved


def _mesh(n):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1),
                ("data", "model"))


def _bits(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_same_bits(got, want, what):
    got, want = _bits(got), _bits(want)
    assert len(got) == len(want), what
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, what
        assert g.tobytes() == w.tobytes(), (what, g, w)


# -- bit parity ------------------------------------------------------------------


@pytest.mark.parametrize("storage,compute,devices", [
    ("float32", "bfloat16", 1),
    ("float32", "bfloat16", 4),
    ("uint8", "bfloat16", 1),
    ("float32", "float32", 1),
])
def test_programs_on_the_twin_match_raw_rows_bit_for_bit(
        storage, compute, devices, compute_dtype, tmp_path):
    """One scan of three steps, the tail's single step and one eval scan
    through the trainer's programs (which gather from the twin), against
    the same steps gathering the loader's own array in front of
    ``_update_core`` / ``loss_and_metrics``: the programs of the parent."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    compute_dtype(compute)
    wf = _textures(storage)
    trainer = FusedTrainer(wf, mesh=_mesh(devices) if devices > 1 else None)
    if trainer.mesh is not None:
        # a replicated set adopted by the loader, as the benchmark does
        from znicz_tpu.parallel.mesh import global_put, replicated

        wf.loader.original_data.devmem = global_put(
            wf.loader.original_data.devmem, replicated(trainer.mesh))
    raw = wf.loader.original_data.devmem
    params, velocities, twin, labels, put = trainer._device_state()
    assert raw.dtype == np.dtype(storage)
    # the twin is the set itself, in the dtype the first unit consumes
    # (on the CPU the default layout is sample-major already)
    want = "bfloat16" if (storage, compute) == ("float32", "bfloat16") \
        else storage
    assert twin.dtype == np.dtype(want) and twin.shape == raw.shape
    assert (twin is raw) == (want == storage)
    assert trainer.stats["resident_prepares"] == int(want != storage)

    rng = np.random.default_rng(7)
    first = 24                                  # the train rows
    idx_mat = rng.integers(first, 104, (STEPS, BATCH)).astype(np.int32)
    idx_tail = rng.integers(first, 104, (BATCH,)).astype(np.int32)
    eval_idx = np.arange(2 * BATCH, dtype=np.int32).reshape(2, BATCH) % 24
    bs_vec = np.array([BATCH, BATCH, BATCH - 3], np.int32)
    eval_bs = np.array([BATCH, 24 - BATCH], np.int32)
    step_nums = np.arange(STEPS, dtype=np.int32)
    gen = prng.get("fused_trainer")
    base_key, tail_key = gen.jax_base_key(), gen.jax_key(STEPS)
    hypers_mat, hypers = trainer.tiled_hypers(STEPS), trainer.hypers()
    nc = trainer._n_confusion()
    copy = jax.tree_util.tree_map

    def take(idx):
        return (jnp.take(raw, idx, axis=0), jnp.take(labels, idx, axis=0))

    def ref_scan(p, v, hypers_mat, idx_mat, bs_vec, base_key, step_nums):
        def unpack(xs):
            idx, bs, step, hyp = xs
            return (*take(idx), bs, step, hyp)

        (p, v, conf), ms = jax.lax.scan(
            trainer._train_body(base_key, unpack),
            (p, v, jnp.zeros((nc, nc), jnp.int32)),
            (idx_mat, bs_vec, step_nums, hypers_mat))
        return p, v, ms, conf

    def ref_step(p, v, hypers, idx, bs, key):
        return trainer._update_core(p, v, hypers, *take(idx), bs, key)

    def ref_eval(p, idx_mat, bs_vec):
        def unpack(xs):
            idx, bs = xs
            data, tgt = take(idx)
            return trainer._decode(data), tgt, bs

        conf, ms = jax.lax.scan(trainer._eval_body(p, unpack),
                                jnp.zeros((nc, nc), jnp.int32),
                                (idx_mat, bs_vec))
        return ms, conf

    want_scan = jax.jit(ref_scan)(
        copy(jnp.copy, params), copy(jnp.copy, velocities),
        put(hypers_mat), put(idx_mat), put(bs_vec), put(base_key),
        put(step_nums))
    want_step = jax.jit(ref_step)(
        copy(jnp.copy, want_scan[0]), copy(jnp.copy, want_scan[1]), hypers,
        put(idx_tail), np.int32(BATCH), tail_key)
    want_eval = jax.jit(ref_eval)(want_step[0], put(eval_idx), put(eval_bs))

    # the loader's own array and its twin are the same operand to a caller
    got_scan = trainer.make_train_scan()(
        params, velocities, put(hypers_mat), raw, labels, put(idx_mat), put(bs_vec), put(base_key), put(step_nums))
    _assert_same_bits(got_scan, want_scan, "train scan")
    got_step = trainer.make_train_step()(
        got_scan[0], got_scan[1], hypers, twin, labels, put(idx_tail),
        np.int32(BATCH), tail_key)
    _assert_same_bits(got_step, want_step, "tail step")
    got_eval = trainer.make_eval_scan()(
        got_step[0], twin, labels, put(eval_idx), put(eval_bs))
    _assert_same_bits(got_eval, want_eval, "eval scan")
    assert np.asarray(got_eval[1]).sum() == 24      # every valid row once
    assert trainer.stats["resident_prepares"] == int(want != storage)


# -- one twin per array -----------------------------------------------------------


def test_a_run_makes_one_twin_and_lets_it_go(compute_dtype, tmp_path):
    """Two epochs make one twin, in set-up, and the run returns it; a new
    array from the loader gets a new one; the benchmark's ``step_check``
    call — the trainer's ``_train_step`` handed the loader's own array,
    between runs — runs on a twin of its own.  A job with a validation
    set whose epochs are not one step over a multiple of ``scan_chunk``
    never ran that program (its tails ride the scan, the last one is
    only evaluated): the call compiles it, once, and nothing else."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.parallel.fused import FusedTrainer

    root.common.dirs.snapshots = str(tmp_path)
    compute_dtype("bfloat16")
    wf = _textures("float32", max_epochs=2)
    trainer = FusedTrainer(wf)
    telemetry.tracer().clear()
    trainer.run()
    stats = trainer.stats
    assert stats["resident_prepares"] == 1 and not trainer._twins
    dispatches, sizes = stats["dispatches"], trainer.jit_cache_sizes()
    assert stats["compiles"] == sum(sizes.values())
    names = [e[1] for e in telemetry.tracer().events() if e[0] == "train"]
    # made in set-up: before the first program is launched
    assert names.count("resident_prepare") == 1
    assert names.index("resident_prepare") < min(
        i for i, n in enumerate(names) if n.startswith("dispatch:"))

    # the loader hands a new array: the next run lays that one out
    raw = wf.loader.original_data.devmem = (
        wf.loader.original_data.devmem + 0.0)
    wf.decision.max_epochs = 3
    wf.decision.complete.set(False)
    trainer.run()
    assert stats["resident_prepares"] == 2 and not trainer._twins
    assert trainer.jit_cache_sizes() == sizes
    assert stats["dispatches"] > dispatches

    idx = np.arange(24, 24 + BATCH, dtype=np.int32)
    copy = jax.tree_util.tree_map
    trainer._train_step(
        copy(jnp.copy, trainer.extract_params()),
        copy(jnp.copy, trainer.extract_velocities()), trainer.hypers(),
        raw, wf.loader.original_labels.devmem, idx, np.int32(BATCH),
        prng.get("fused_trainer").jax_key(0))
    assert sizes["_train_step"] == 0 and stats["tails_in_scan"] == 1
    assert trainer.jit_cache_sizes() == {**sizes, "_train_step": 1}
    assert int(trainer._m_compiles.value) == stats["compiles"] + 1
    # a twin for that call alone: a trainer at rest holds no second set
    assert stats["resident_prepares"] == 3 and not trainer._twins


# -- the chip's compiler, without the chip ----------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to jax's persistent
    cache and cannot be read back without the chip: off around it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


@pytest.fixture
def tiny_alexnet(compute_dtype, tmp_path):
    """The benchmark's ``--tiny`` AlexNet: 67x67 images, batch 8, bf16
    compute."""
    from znicz_tpu.samples import alexnet

    cfg = root.alexnet.loader
    tiny = {"minibatch_size": 8, "n_train": 8, "n_valid": 8, "n_test": 0,
            "n_classes": 10, "image_size": 67}
    saved = {k: cfg.get(k) for k in tiny}
    root.common.dirs.snapshots = str(tmp_path)
    compute_dtype("bfloat16")
    prng.reset(1013)
    cfg.update(tiny)
    try:
        wf = alexnet.AlexNetWorkflow()
        wf.initialize(device=None)
        yield wf
    finally:
        cfg.update(saved)


#: the set the programs are compiled for (the workflow holds 16 images:
#: only shapes are lowered): a multiple of 128, so the device's default
#: layout puts the samples in the lanes; larger than the chip's fast
#: memory, where a smaller set is prefetched whole by every program; and
#: no layer's width
SAMPLES = 31 * 128


def whole_set_ops(text, samples):
    """Instructions of a compiled module whose result is an array of more
    than one axis with the resident set's leading dimension, parameters
    and tuple plumbing aside (tuples carry the set into the scan's loop;
    the labels, one axis, are prefetched whole into fast memory — 4
    bytes a sample)."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[(\d+),[^\]]*\]\S* "
                     r"([\w\-]+)\(", line)
        if m and int(m.group(1)) == samples and m.group(2) not in (
                "parameter", "get-tuple-element"):
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("storage", ["float32", "uint8"])
def test_no_program_rewrites_the_whole_set(storage, chips, topo,
                                           no_compile_cache, tiny_alexnet):
    """The train scan and the eval scan, compiled for ``v5e:2x2`` from
    the loader's own shape in its default layout (what callers hand
    them), hold no operation whose result is the whole resident set: the
    copy ``bf16[N,H,W,C]{2,1,3,0} copy(f32[N,H,W,C]{0,2,3,1})`` that led
    both cells' device time cannot come back unseen."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from znicz_tpu.parallel.fused import FusedTrainer

    wf = tiny_alexnet
    if chips == 1:
        mesh, place = None, SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices).reshape(chips, 1),
                    ("data", "model"))
        place = NamedSharding(mesh, PartitionSpec())
    trainer = FusedTrainer(wf, mesh=mesh)
    samples, steps, batch = SAMPLES, 2, 8 * chips

    def spec(x):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                    if not hasattr(x, "dtype") else x.dtype,
                                    sharding=place)

    def rows(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=place)

    tree = jax.tree_util.tree_map
    params = tree(spec, trainer.extract_params())
    dataset = rows(samples, 67, 67, 3, dtype=jnp.dtype(storage))
    assert tuple(wf.loader.original_data.shape[1:]) == dataset.shape[1:]
    # the twin: channels, then rows of whole 8 x 128 tiles, in the dtype
    # the first unit consumes; the device's default layout for THAT shape
    # is row-major, so no program declares a layout
    twin, (order, sample_shape) = trainer._resident(dataset)
    assert order == (0, 3, 1, 2) and sample_shape == (67, 67, 3)
    assert twin.shape == (samples, 3, 72, 128)
    assert twin.dtype == ("bfloat16" if storage == "float32" else "uint8")
    assert twin.format.layout is None
    train = trainer.make_train_scan().lower(
        params, tree(spec, trainer.extract_velocities()),
        tree(spec, trainer.tiled_hypers(steps)), dataset, rows(samples),
        rows(steps, batch), rows(steps),
        spec(prng.get("fused_trainer").jax_base_key()),
        rows(steps)).compile()
    evaluate = trainer.make_eval_scan().lower(
        params, dataset, rows(samples), rows(steps, batch),
        rows(steps)).compile()
    for name, compiled in (("train scan", train), ("eval scan", evaluate)):
        text = compiled.as_text()
        assert f"[{samples},3," in text, name           # the twin is there
        assert not whole_set_ops(text, samples), (
            name, whole_set_ops(text, samples))
    assert not trainer._twins and not trainer.stats["resident_prepares"]


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)],
                         ids=["full-layer", "window-layer"])
def test_the_attention_kernels_compile_at_the_cells_shapes(
        heads, window, topo, no_compile_cache, monkeypatch):
    """Mosaic takes the forward, dq and dk/dv kernels at Laguna's widths
    with the tiles the predicate chooses (interpret mode cannot show a
    refused slice or too much VMEM).  Two layers of one kind share one
    lowering of each kernel, and every call still carries ITS layer's
    scope in the compiled text: the benchmark's reader
    (``reduce/inner.py`` ``tag_of``) files each under ``attn_core`` by
    layer and direction.  The layers keep what a decoder layer keeps
    across ``jax.checkpoint`` (the core's output and log-sum-exp, ISSUE
    33), so no forward kernel stands in the recomputed pass."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from znicz_tpu import backends
    from znicz_tpu.ops import attention

    monkeypatch.setattr(backends, "pallas_interpret", lambda: False)
    place = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 8192, heads, 128), jnp.bfloat16,
                             sharding=place)
    k = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16, sharding=place)
    tiles = attention.core_tiles("tpu", q.shape, 8, q.dtype, window)
    assert tiles == ((512, 512) if window is None else (256, 256))

    def core(q, k, v):                  # scopes as ``forward_pass`` and
        with jax.named_scope("attn_core"):      # the decoder layer open them
            return attention._core(q, k, v, window, 512, tiles)

    def value(q, k, v):
        for layer in ("layer1", "layer2"):
            with jax.named_scope(layer):
                q = q + jax.checkpoint(
                    core, policy=jax.checkpoint_policies
                    .save_only_these_names(*attention.CORE_KEEPS))(q, k, v)
        return jnp.sum(q.astype(jnp.float32))

    before = attention.kernel_counts()["attn_kernel_lowerings"]
    text = jax.jit(jax.value_and_grad(value, (0, 1, 2))).lower(
        q, k, k).compile().as_text()
    assert attention.kernel_counts()["attn_kernel_lowerings"] - before == 3
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    inner = spec.load_module("reduce", "inner")
    assert sorted((name.split("/")[-2], inner.tag_of(name))
                  for name in calls) == sorted(
        (kernel, (layer, "attn_core", direction))
        for layer in ("layer1", "layer2")
        for kernel, direction in (("attn_core_forward", "forward"),
                                  ("attn_core_dq", "backward"),
                                  ("attn_core_dkv", "backward")))
