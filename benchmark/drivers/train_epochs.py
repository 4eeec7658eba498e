"""Driver ``train_epochs``: a training job on the path users run.

Builds the workflow through the configuration's sample module exactly as
the launcher does (``Workflow()``, ``initialize``, ``FusedTrainer(wf,
mesh=train_mesh_from_config()).run()``) and wraps it from outside; nothing
in the program is patched.  What it sets is public surface: dotted
``root.*`` overrides, the loader's ``original_data``/``labels`` ``devmem``
(the ``Array`` setter "adopt a freshly computed jax array"), the
snapshotter's attributes and ``decision.max_epochs`` as ``bench.py
--product`` sets them, and a ``decision.on_epoch_end`` callback, which also
ends the run by raising ``decision.complete``.

One ``trainer.run()`` call holds warm-up and window.  The clock reads sit
in the epoch-end callback, which the trainer calls right after it pulled
the epoch's last metrics from the device — so both ends of the window are
points at which values were pulled, and the work between them is exactly
``epochs x steps-per-epoch`` train steps plus the validation passes.  The
window closes at the first epoch boundary after ``--seconds`` that ends a
whole number of save periods (``snapshot.interval`` epochs), so that every
window of a job holds the same mix of epochs.  ``train_samples_per_s`` is
the samples trained in the window over its wall time.

``correct`` is decided outside the window: evaluation-mode logits against
the configuration's plain reference (``reference_parity``), one train step
of the system from the seeded initial weights against ``jax.grad`` of the
reference's loss and the paper's update rule in float32 (``step_check``),
finite losses, no compilation inside the window, and the program's own
zero-recompile proof.

Traffic parameters (``benchmark/traffic/<mix>.json``):

``warmup_epochs``   epochs before the window (every shape compiles there)
``snapshot``        attributes set on ``wf.snapshotter``: ``interval``
                    (epochs between ``epoch_N`` saves, 0 = best only),
                    ``min_save_interval_s`` (rate limit of best saves),
                    ``compression``
``trace_epochs``    epochs the traced run keeps the profiler on
``root``            further dotted overrides of the job
``tiny``            overrides of the above for the ``--tiny`` rehearsal
"""

from __future__ import annotations

import contextlib
import importlib
import math
import os
import shutil
import time
import types

import numpy as np


def stub_dataset(path: str, size: int) -> None:
    """One black image, so that the sample's loader finds a file and does
    not generate the whole set on the host; the real set is made on the
    device (``benchmark/generators/``) and adopted after ``initialize``."""
    if os.path.isfile(path):
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.npz"
    np.savez(tmp, data=np.zeros((1, size, size, 3), np.float32),
             labels=np.zeros((1,), np.int32))
    os.replace(tmp, path)


def apply_overrides(root, overrides: dict) -> None:
    for key, value in overrides.items():
        root.set_by_path(key.removeprefix("root."), value)


def system_logits(trainer, params, x):
    """Evaluation-mode logits through ``FusedTrainer.forward_pass`` with
    the casts ``loss_and_metrics`` applies for the compute dtype."""
    import jax
    import jax.numpy as jnp

    def run(params, x):
        if trainer.compute_dtype == np.dtype("float32"):
            out = trainer.forward_pass(params, x, None, False)
        else:
            def cast(t):
                return t.astype("bfloat16") if t.dtype == jnp.float32 else t

            out = trainer.forward_pass(jax.tree_util.tree_map(cast, params),
                                       cast(x), None, False, cast=cast)
        return out.astype(jnp.float32)

    return jax.jit(run)(params, x)


def reference_parity(cell, trainer, forwards, x) -> float:
    """Relative L2 error of the system's evaluation-mode logits of ``x``
    against the configuration's plain reference on the same weights."""
    import jax

    params = trainer.extract_params()
    layers = [(params[f.name]["weights"], params[f.name]["bias"])
              for f in forwards if f.has_weights]
    return relative_l2(system_logits(trainer, params, x),
                       jax.jit(cell.reference().forward)(layers, x))


def relative_l2(got, want) -> float:
    """``|got - want| / |want|`` in float32, on the device."""
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32).ravel()
    want = jnp.asarray(want, jnp.float32).ravel()
    err = float(jnp.linalg.norm(got - want)
                / (jnp.linalg.norm(want) + 1e-30))
    return err if np.isfinite(err) else float("inf")


def step_check(cell, trainer, forwards, init, data, labels, rows) -> dict:
    """One train step of the system from the seeded initial weights
    ``init`` on the train rows ``rows`` (one global batch) against the
    plain reference in float32: what the forward-only logits cannot see —
    the backward pass, the gradient all-reduce and the optimizer.

    The step is the trainer's own compiled ``_train_step`` (the program
    every epoch's tail runs; ``_step_core`` is shared with the scan).  It
    takes its hyperparameters as an argument, so two calls do:

    ``gradient``  learning rate 1, no momentum, no decay: the weights move
                  by minus the gradient, which is compared leaf by leaf
                  with ``jax.grad(reference.loss)`` on the same rows and
                  the same dropout masks (the system's draw for the
                  step's key, handed to the reference as data).  On four
                  chips the rows are one global batch and the reference
                  sees them whole, in chunks of a chip's batch.
    ``update``,   the job's own hyperparameters and a velocity as the step
    ``velocity``  before would have left it (minus learning rate times the
                  gradient, in the state's type): the change of the
                  weights and the new velocity against the paper's rule
                  with the configuration's numbers, on the reference's
                  gradient.

    Returns ``by_layer``, the largest of the three relative L2 errors per
    weighted layer (weights and bias) from the input to the output, and
    every reading under ``leaves``.  The error is the compute type's: its
    rounding moves max-pool winners and ReLU gates, and grows layer by
    layer on the way back, so each layer has its own tolerance."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.core import prng

    ref = cell.reference()
    opt = cell.config["architecture"]["optimizer"]
    tree = jax.tree_util.tree_map
    step = trainer._train_step or trainer.make_train_step()
    if trainer.mesh is None:
        def put(x):
            return x
    else:
        from znicz_tpu.parallel.mesh import global_put, replicated

        def put(x):
            return global_put(x, replicated(trainer.mesh))
    key = prng.get("fused_trainer").jax_key(0)
    idx = put(np.asarray(rows, np.int32))
    size = np.int32(len(rows))
    like = trainer.place_state(trainer.extract_velocities())
    hypers = trainer.hypers()
    probe = {name: tuple(np.float32(v) for v in (1, 1, 0, 0, 0, 0, 0, 0))
             for name in hypers}        # lr, lr_bias, then all off

    def system(velocities, hypers):     # the step donates what it is given
        new_p, new_v, _ = step(tree(jnp.copy, init), velocities, hypers,
                               data, labels, idx, size, key)
        return new_p, new_v

    moved, _ = system(tree(jnp.zeros_like, like), probe)
    grad_sys = tree(lambda a, b: a - b, init, moved)

    names = [f.name for f in forwards if f.has_weights]
    layers = [(init[n]["weights"], init[n]["bias"]) for n in names]
    masks = [f.make_mask(jax.random.fold_in(key, i),
                         (len(rows),) + tuple(
                             forwards[i - 1].output_sample_shape),
                         f.dropout_ratio)
             for i, f in enumerate(forwards) if hasattr(f, "dropout_ratio")]
    chunks = max(len(rows) // int(
        cell.config["architecture"]["batch_per_chip"]), 1)
    chunk = len(rows) // chunks         # equal chunks: the mean of means
    grad_fn = jax.jit(jax.grad(ref.loss))
    grad_ref = None
    for at in range(0, chunks * chunk, chunk):
        sel = idx[at:at + chunk]
        g = grad_fn(layers, jnp.take(data, sel, axis=0),
                    jnp.take(labels, sel, axis=0),
                    [m[at:at + chunk] for m in masks])
        grad_ref = g if grad_ref is None else tree(jnp.add, grad_ref, g)
    grad_ref = tree(lambda g: g / chunks, grad_ref)

    before = tree(lambda g, v: (-opt["learning_rate"] * g).astype(v.dtype),
                  grad_sys, like)
    new_p, new_v = system(tree(jnp.copy, before), hypers)
    leaves = {}
    for name, (gw, gb) in zip(names, grad_ref):
        for k, g in (("weights", gw), ("bias", gb)):
            w = init[name][k]
            w_ref, v_ref = ref.sgd_momentum(
                w, before[name][k], g, opt["learning_rate"],
                opt["momentum"],
                opt["weight_decay" if k == "weights"
                    else "weight_decay_bias"])
            leaves[f"{name}.{k}"] = {
                "gradient": relative_l2(grad_sys[name][k], g),
                "update": relative_l2(new_p[name][k] - w, w_ref - w),
                "velocity": relative_l2(new_v[name][k], v_ref),
                "gradient_norm": float(jnp.linalg.norm(g)),
            }
    kinds = ("gradient", "update", "velocity")
    return {"by_layer": [max(leaves[f"{name}.{k}"][kind] for kind in kinds
                             for k in ("weights", "bias"))
                         for name in names],
            "leaves": leaves, "rows": len(rows),
            "jit_cache_sizes_after": trainer.jit_cache_sizes()}


def within(by_layer, tolerance) -> bool:
    """Each weighted layer's reading under its own tolerance."""
    return len(by_layer) == len(tolerance) and all(
        err <= float(tol) for err, tol in zip(by_layer, tolerance))


def build(cell, seed: int, tiny: bool, cache_dir: str,
          phase=lambda name: None):
    """The cell's job as the launcher would build it: configuration and
    traffic overrides applied, the workflow initialised over the one-image
    stub, the real data set made on the device from ``seed`` and adopted
    by the loader, the trainer on the configuration's mesh.  Returns a
    namespace of what was built and the sections of the two files as they
    apply (``tiny`` overrides merged in)."""
    import jax

    from benchmark import spec
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.parallel.mesh import replicated, train_mesh_from_config

    cfg, job = cell.config, dict(cell.traffic)
    data_cfg = dict(cfg["data"])
    overrides = dict(cfg["root"])
    overrides.update(job.get("root", {}))
    parity_cfg, step_cfg = dict(cfg["parity"]), dict(cfg["step_check"])
    if tiny:
        small = cfg.get("tiny", {})
        overrides.update(small.get("root", {}))
        data_cfg.update(small.get("data", {}))
        parity_cfg.update(small.get("parity", {}))
        step_cfg.update(small.get("step_check", {}))
        job.update(job.get("tiny", {}))
    prng.seed_all(seed)
    apply_overrides(root, overrides)
    size = int(overrides[data_cfg["size_key"]])
    stub = os.path.join(cache_dir, f"stub_{size}.npz")
    stub_dataset(stub, size)
    apply_overrides(root, {data_cfg["path_key"]: stub})

    module = importlib.import_module(cfg["sample"])
    wf = getattr(module, cfg["workflow"])()
    wf.initialize(device=None)
    phase("workflow_initialized")
    mesh = train_mesh_from_config()
    n_devices = 1 if mesh is None else mesh.size
    if n_devices != cell.chips:
        raise RuntimeError(f"the configuration builds a mesh of "
                           f"{n_devices} device(s), the cell asks for "
                           f"{cell.chips}")
    lengths = list(wf.loader.class_lengths)
    data, labels = spec.load_module(
        "generators", data_cfg["generator"], cell.root).make(
            seed, sum(lengths), size,
            int(wf.forwards[-1].output_samples_number),
            sharding=None if mesh is None else replicated(mesh))
    wf.loader.original_data.devmem = data
    wf.loader.original_labels.devmem = labels
    jax.block_until_ready(data)
    phase("data_on_device")
    trainer = FusedTrainer(wf, mesh=mesh)
    # the seeded initial weights, kept on the device for ``step_check``
    # (0.25 GB of the peak is this copy)
    init = jax.tree_util.tree_map(
        jax.numpy.copy, trainer.place_state(trainer.extract_params()))
    return types.SimpleNamespace(
        wf=wf, trainer=trainer, mesh=mesh, n_devices=n_devices,
        data=data, labels=labels, lengths=lengths, size=size, init=init,
        job=job, parity=parity_cfg, step_check=step_cfg)


def run(ctx) -> dict:
    import jax

    from benchmark import flops
    from znicz_tpu import telemetry
    from znicz_tpu.loader.base import TRAIN, VALID

    built = build(ctx.cell, ctx.seed, ctx.tiny, ctx.cache_dir, ctx.phase)
    wf, trainer, job = built.wf, built.trainer, built.job
    data, labels, lengths = built.data, built.labels, built.lengths
    n_devices, size, total = built.n_devices, built.size, sum(lengths)
    loader, decision = wf.loader, wf.decision

    snap_dir = os.path.join(ctx.scratch_dir, "snapshots")
    shutil.rmtree(snap_dir, ignore_errors=True)
    wf.snapshotter.directory = snap_dir
    for key, value in job.get("snapshot", {}).items():
        setattr(wf.snapshotter, key, value)

    batch = int(loader.max_minibatch_size)
    steps_epoch = math.ceil(lengths[TRAIN] / batch)
    eval_epoch = sum(math.ceil(lengths[k] / batch) for k in (0, VALID))
    warmup = int(job["warmup_epochs"])
    trace_epochs = int(job["trace_epochs"])
    # a window holds whole save periods, so that each holds as many saves
    period = max(int(wf.snapshotter.interval), 1)
    stats, meter = trainer.stats, ctx.meter
    marks = {}                  # name -> (perf_counter, epoch, meter)
    epoch_t = []
    annotation = contextlib.ExitStack()     # the open bench:epoch span
    trace_dir = os.path.join(ctx.scratch_dir, "trace")

    def open_annotation(epoch):
        annotation.enter_context(
            jax.profiler.TraceAnnotation(f"bench:epoch:{epoch}"))

    def mark(name, epoch):
        marks[name] = (time.perf_counter(), epoch, meter.snapshot(),
                       int(wf.snapshotter.async_saves_written))

    def on_epoch_end(d):
        epoch = int(d.epoch_number)
        done = epoch + 1                    # epochs finished so far
        now = time.perf_counter()
        if done == warmup:
            # nothing of the warm-up may still be written in the window
            wf.snapshotter.flush_async()
            mark("start", epoch)
            ctx.phase("window_start")
            epoch_t.append(marks["start"][0])
            return
        if done < warmup:
            return
        epoch_t.append(now)
        if ctx.trace:
            if done == warmup + 1:
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
                with jax.profiler.TraceAnnotation("bench:sync"):
                    mark("sync", epoch)
                mark("trace_start", epoch)
                open_annotation(done)
            elif "trace_start" in marks and "trace_end" not in marks:
                annotation.close()
                if done == warmup + 1 + trace_epochs:
                    mark("trace_end", epoch)
                    jax.profiler.stop_trace()
                else:
                    open_annotation(done)
        if ((epoch - marks["start"][1]) % period == 0
                and now - marks["start"][0] >= ctx.seconds
                and ("trace_end" in marks or not ctx.trace)):
            mark("end", epoch)
            ctx.note_memory_peak()      # before the checks add theirs
            d.complete.set(True)

    decision.on_epoch_end.append(on_epoch_end)
    decision.max_epochs = 10 ** 9
    ctx.log({"phase": "built", "devices": n_devices, "batch": batch,
             "class_lengths": lengths, "steps_per_epoch": steps_epoch,
             "eval_steps_per_epoch": eval_epoch,
             "build_s": ctx.phase("trainer_built")})
    trainer.run()
    if "trace_start" in marks and "trace_end" not in marks:
        annotation.close()
        jax.profiler.stop_trace()
        raise RuntimeError("the traced window did not close")

    t_start, e_start, at_start, saved_start = marks["start"]
    t_end, e_end, at_end, saved_end = marks["end"]
    epochs = e_end - e_start
    window_s = t_end - t_start
    samples = epochs * lengths[TRAIN]
    history = decision.epoch_history
    bad_epochs = sum(not all(np.isfinite(v) for v in h.values())
                     for h in history[warmup:])
    compiles = int(stats.get("compiles", -1))
    jit_sizes = dict(stats.get("jit_cache_sizes", {}))
    ctx.phase("window_end")
    # agreement with the plain reference, outside the window; each
    # tolerance has its reason beside it in the configuration's file
    x = data[:min(int(built.parity["samples"]), total)]
    parity = reference_parity(ctx.cell, trainer, wf.forwards, x)
    first_train = lengths[0] + lengths[VALID]
    step = step_check(ctx.cell, trainer, wf.forwards, built.init, data,
                      labels, range(first_train, first_train + batch))
    ctx.phase("checked")
    checks = {
        "logits_within_tolerance": parity <= float(
            built.parity["tolerance"]),
        "step_within_tolerance": within(step["by_layer"],
                                        built.step_check["tolerance"]),
        "losses_finite": all(np.isfinite(v) for h in history
                             for v in h.values()),
        "no_compile_in_window": at_end["compiles"] == at_start["compiles"],
        "compiles_match_jit_caches": compiles == sum(jit_sizes.values()),
        "epochs_finished": len(history) == warmup + epochs,
    }
    snap = wf.snapshotter
    out = {
        "setup_s": t_start - ctx.t_backend,
        "setup_compile_s": at_start["seconds"],
        "setup_cache": {"hits": at_start["hits"],
                        "misses": at_start["misses"]},
        "values": {"train_samples_per_s": samples / window_s},
        "attempted": epochs * steps_epoch,
        "failed": bad_epochs * steps_epoch,
        "correct": all(checks.values()),
        "checks": checks,
        "window": {"seconds": window_s, "epochs": epochs,
                   "period_epochs": period,
                   "samples": samples, "train_steps": epochs * steps_epoch,
                   "eval_steps": epochs * eval_epoch,
                   "epoch_seconds": np.diff(epoch_t).tolist()},
        "parity": {"relative_l2": parity,
                   "tolerance": built.parity["tolerance"],
                   "dtype": str(trainer.compute_dtype),
                   "samples": int(x.shape[0])},
        "step_check": dict(step, tolerance=built.step_check["tolerance"]),
        "loss": {"train": [h["train"] for h in history],
                 "valid": [h.get("valid") for h in history],
                 "untrained": math.log(
                     int(wf.forwards[-1].output_samples_number))},
        "counters": {
            "fused_stats": {k: v for k, v in stats.items()
                            if isinstance(v, (int, float))},
            "jit_cache_sizes": jit_sizes,
            "snapshots_written": int(snap.async_saves_written),
            # files finished inside the window; a job saves once a period
            "snapshots_written_in_window": saved_end - saved_start,
            "snapshots_due_in_window": (
                epochs // period if snap.interval else 0),
            "snapshots_coalesced": int(snap.async_saves_coalesced),
            "snapshot_files": sorted(os.listdir(snap_dir))
            if os.path.isdir(snap_dir) else [],
        },
        "shape": {"devices": n_devices, "batch": batch,
                  "steps_per_epoch": steps_epoch,
                  "eval_steps_per_epoch": eval_epoch,
                  "train_flops_per_step": flops.train_flops(wf.forwards,
                                                            batch),
                  "forward_flops_per_step": flops.forward_flops(
                      wf.forwards, batch)},
    }
    if ctx.trace:
        from benchmark.reduce import xplane

        path = xplane.newest_xplane(trace_dir)
        if path is None:
            raise RuntimeError(f"no .xplane.pb under {trace_dir}")
        # what each fusion computes is read from the programs that ran
        texts = [m.to_string()
                 for exe in jax.devices()[0].client.live_executables()
                 for m in exe.hlo_modules()]
        trace = out["trace"] = xplane.reduce_trace(
            path, telemetry.tracer().events(), marks["sync"][0], texts)
        trace["train_steps"] = trace_epochs * steps_epoch
        trace["eval_steps"] = trace_epochs * eval_epoch
        trace["host_window_s"] = (marks["trace_end"][0]
                                  - marks["trace_start"][0])
        # device time of operations whose result is the whole resident
        # set (the float32 set cast on every dispatch): it grows with the
        # shard, a parameter of the benchmark, and not with batch or model
        whole = f"[{total},{size},{size},3]"
        trace["resident_set_ops_s"] = sum(
            t for n, t in trace["devices"][0]["ops_s"].items()
            if whole in n) if trace["devices"] else 0.0
    shutil.rmtree(snap_dir, ignore_errors=True)
    return out
