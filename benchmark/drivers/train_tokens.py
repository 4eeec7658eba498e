"""Driver ``train_tokens``: a decoder's training job on rows of token ids.

The job is built and run as ``train_epochs`` builds and runs an image job
(the configuration's sample module through the launcher's own calls,
public ``root.*`` overrides, the snapshotter's attributes, one
``trainer.run()`` with the clock reads in ``decision.on_epoch_end``; its
helpers are imported, nothing of it is edited): the window holds whole
epochs between epoch-end pulls and ``train_samples_per_s`` is the rows
trained in it over its wall time — a sample is one row of
``row_tokens`` ids; tokens a second go on the ``detail`` line.

What differs is the data (``benchmark/generators/tokens.py``: Zipf rows
made on the device from the seed) and what ``correct`` compares, all
outside the window and ordered so that the float32 reference fits beside
a trainer whose state fills most of the chip:

1. ``parity``: evaluation-mode logits of one timed batch through
   ``FusedTrainer.forward_pass`` on the TRAINED weights against the plain
   reference (relative L2); the same with the system's operands rounded
   to an 8-bit float, the control, which has to come out as NOT within
   the tolerance by the same comparison (``float8_control_fails``); and
   the share of (row, slot) expert choices that bfloat16 moves at equal
   inputs.
2. ``step_check``: the trained state is dropped and the SEEDED weights
   are made again (``init_params``).  The reference's gradient goes to the
   host; then the trainer's own compiled train step runs twice from the
   seeded weights and a zero state — once with ``lr 0, beta1 0``, which
   leaves the gradient in the first moment, once with the job's numbers —
   and gradient, weight change and both moments are compared tensor by
   tensor with the reference's AdamW on the reference's gradient, by
   parameter group.  Its control is a state left unchanged, which reads
   1 in every group and kind (``|0 - want| / |want|``) and has to come
   out as not within the tolerances (``unchanged_state_control_fails``).
3. the expert layers' counters: no pair of a held expert outside that
   expert's group of the grouped product (``rows_dropped``; what the
   product then WRITES is what 1 and 2 see: PR 27's first chip run read
   a gradient 1,500 times off there), and the rows routed to held experts
   a step and layer inside a band around ``experts_per_token x tokens x
   held / total`` (a counter that is dead or counts twice; the load
   itself swings with the seed).
4. finite losses, no compilation inside the window, ``compiles == sum of
   jit cache sizes``, every epoch finished.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``generator``,
``zipf``, ``warmup_epochs``, ``trace_epochs``, ``snapshot``, ``root``
(the job's shapes as overrides of the sample's), ``tiny``.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import math
import os
import shutil
import time
import types

import numpy as np


def helpers():
    """``train_epochs``' helpers (``apply_overrides``, ``relative_l2``,
    ``system_logits``): imported, not edited."""
    from benchmark import spec

    return spec.load_module("drivers", "train_epochs")


def model_and_share(cfg: dict, tiny: bool):
    """``(model, share)`` for the reference, from the configuration's
    FILE (the system reads its own copy, ``samples/laguna.py``; a
    difference between the two shows as a failed comparison): the model's
    ``config.json`` keys with the published values of the reduced ones put
    back, and what this chip holds."""
    if tiny:
        return cfg["tiny"]["model"], cfg["tiny"]["share"]
    model = {k: cfg[k] for k in cfg["model_keys"]}
    model.update(cfg["published"])
    return model, {"layers": cfg["num_hidden_layers"],
                   "experts_held": cfg["num_experts"],
                   "first_expert": cfg["first_expert"],
                   "vocab_held": cfg["vocab_size"]}


def reference_tree(forwards, params) -> dict:
    """The system's parameter tree in the reference's layout (the tensor
    names are the same; ``benchmark/references/laguna.py`` states them)."""
    first, last = forwards[0].name, forwards[-1].name
    return {"embed": params[first]["embed"],
            "layers": [dict(params[f.name]) for f in forwards[1:-1]],
            "norm": params[last]["norm"], "head": params[last]["weights"]}


def group_of(ref, key: str) -> str:
    return next(g for g, keys in ref.GROUPS.items() if key in keys)


class GroupErrors:
    """Relative L2 error by parameter group: ``|got - want|^2`` and
    ``|want|^2`` summed over the group's tensors, on the device."""

    def __init__(self):
        self.sums = {}

    def add(self, kind: str, group: str, got, want) -> None:
        import jax.numpy as jnp

        got = jnp.asarray(got, jnp.float32)
        want = jnp.asarray(want, jnp.float32)
        err, ref = (float(jnp.sum(jnp.square(got - want))),
                    float(jnp.sum(jnp.square(want))))
        s = self.sums.setdefault((kind, group), [0.0, 0.0])
        s[0] += err
        s[1] += ref

    def by_group(self) -> dict:
        out = {}
        for (kind, group), (err, ref) in sorted(self.sums.items()):
            value = math.sqrt(err / (ref + 1e-60))
            out.setdefault(group, {})[kind] = (
                value if np.isfinite(value) else float("inf"))
        return out


def reference_logits(ref, model, share, tree, ids):
    """The reference's logits, and the share of (row, slot) expert choices
    that rounding the router's operands to bfloat16 moves at equal inputs
    (the reference's own hidden states through its router in float32 and
    in bfloat16) — one program for both."""
    import jax
    import jax.numpy as jnp

    def run(tree, ids):
        taps = []
        logits = ref.forward(tree, ids, model, share, taps=taps)
        moved, total = 0.0, 0
        for p, xn in taps:
            want, _ = ref.routing(model, p, xn)
            low = {"router": p["router"].astype(jnp.bfloat16)}
            got, _ = ref.routing(model, low, xn.astype(jnp.bfloat16))
            same = (got[:, :, None] == want[:, None, :]).any(-1)
            moved += jnp.sum(~same)
            total += same.size
        return logits, moved / max(total, 1)

    return jax.jit(run)(tree, ids)


def parity(cell, model, share, trainer, forwards, ids) -> dict:
    import jax
    import jax.numpy as jnp

    te, ref = helpers(), cell.reference()
    params = trainer.extract_params()
    want, moved = reference_logits(ref, model, share,
                                   reference_tree(forwards, params), ids)
    got = te.system_logits(trainer, params, ids)
    out = {"relative_l2": te.relative_l2(got, want),
           "choices_moved_by_bfloat16": float(moved),
           "dtype": str(trainer.compute_dtype), "rows": int(ids.shape[0])}
    del got
    # the nearest precision below: every float operand through an 8-bit
    # float on its way into the compute type
    def low(t):
        if not jnp.issubdtype(t.dtype, jnp.floating):
            return t
        return t.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    fp8 = jax.jit(lambda p, x: trainer.forward_pass(
        jax.tree_util.tree_map(low, p), x, None, False,
        cast=low).astype(jnp.float32))(params, ids)
    out["relative_l2_float8"] = te.relative_l2(fp8, want)
    return out


def reseed(wf, state: bool = True) -> None:
    """Drop the trained state: the seeded weights again and, with
    ``state``, zero moments."""
    for f in wf.forwards:
        for key, value in f.init_params().items():
            f.tensors[key].devmem = value
    for gd in wf.gds:
        gd._velocities.clear()
        if state:
            gd._make_state(None)


def step_check(cell, model, share, wf, trainer, data, labels, rows) -> dict:
    """See the module's text, 2.  Returns ``by_group``: group ->
    ``gradient`` / ``update`` / ``m`` / ``v`` relative L2."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.core import prng

    ref = cell.reference()
    opt = cell.config["optimizer"]
    forwards = wf.forwards
    idx = np.asarray(rows, np.int32)
    # the reference first, before the moments take their room again
    reseed(wf, state=False)
    ids, targets = jnp.take(data, idx, axis=0), jnp.take(labels, idx, axis=0)
    # a row at a time (rows hold as many tokens each: the mean of the
    # rows' means), each row's gradient summed on the HOST: the float32
    # backward pass of two rows needs 15.6 GB of a 16 GiB chip
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        ref.loss, model=model, share=share, remat=True)))
    tree = reference_tree(forwards, trainer.extract_params())
    loss_ref, grad_ref = 0.0, None
    for r in range(len(idx)):
        value, g = grad_fn(tree, ids[r:r + 1], targets[r:r + 1])
        g = jax.tree_util.tree_map(np.asarray, g)
        loss_ref += float(value) / len(idx)
        grad_ref = g if grad_ref is None else jax.tree_util.tree_map(
            np.add, grad_ref, g)
    grad_ref = jax.tree_util.tree_map(lambda a: a / len(idx), grad_ref)
    del tree, g
    for gd in wf.gds:
        gd._make_state(None)

    step = trainer._train_step or trainer.make_train_step()
    key = prng.get("fused_trainer").jax_key(0)
    size = np.int32(len(idx))
    # the job's numbers, and a probe: lr, decay, beta1, beta2, eps
    names = list(trainer.hypers())
    hypers = {name: tuple(np.float32(opt[k]) for k in (
        "learning_rate", "weight_decay", "beta1", "beta2", "epsilon"))
        for name in names}
    probe = {name: tuple(np.float32(v) for v in (0, 0, 0, 0, 1))
             for name in names}

    def run(hypers):                    # the step donates what it is given
        return step(trainer.extract_params(), trainer.extract_velocities(),
                    hypers, data, labels, idx, size, key)

    def ref_leaves(f):
        """(tensor key, the reference gradient's leaf) of unit ``f``."""
        if f is forwards[0]:
            return [("embed", "embed", grad_ref["embed"])]
        if f is forwards[-1]:
            return [("norm", "norm", grad_ref["norm"]),
                    ("weights", "head", grad_ref["head"])]
        layer = grad_ref["layers"][forwards.index(f) - 1]
        return [(k, k, g) for k, g in layer.items()]

    errors = GroupErrors()
    _, state, (loss_sys, *_) = run(probe)
    for f in forwards:
        for key_sys, key_ref, g in ref_leaves(f):
            errors.add("gradient", group_of(ref, key_ref),
                       state[f.name][f"m_{key_sys}"], g)
    del state
    reseed(wf)
    new_p, new_s, _ = run(hypers)
    for f in forwards:
        init = f.init_params()
        for key_sys, key_ref, g in ref_leaves(f):
            w = init[key_sys]
            w_ref, m_ref, v_ref = jax.jit(functools.partial(
                ref.adamw, step=1.0, learning_rate=opt["learning_rate"],
                beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["epsilon"],
                weight_decay=(0.0 if key_ref in ref.NO_DECAY
                              else opt["weight_decay"])))(
                w, jnp.zeros_like(w), jnp.zeros_like(w), g)
            group = group_of(ref, key_ref)
            errors.add("update", group, new_p[f.name][key_sys] - w,
                       w_ref - w)
            errors.add("m", group, new_s[f.name][f"m_{key_sys}"], m_ref)
            errors.add("v", group, new_s[f.name][f"v_{key_sys}"], v_ref)
        del init
    return {"by_group": errors.by_group(),
            "loss": {"system": float(loss_sys), "reference": loss_ref},
            "rows": len(idx),
            "jit_cache_sizes_after": trainer.jit_cache_sizes()}


class Programs:
    """The compiled texts of the programs that ran, for the readers of
    ``benchmark/reduce/inner.py``; prints short, so that the run's detail
    file does not hold them."""

    def __init__(self, texts):
        self.texts = list(texts)

    def __repr__(self):
        return f"<{len(self.texts)} compiled texts>"


#: what a state left unchanged reads in every group and kind: the control
#: of ``step_check``'s tolerances
UNCHANGED = {"gradient": 1.0, "update": 1.0, "m": 1.0, "v": 1.0}


def within(by_group: dict, tolerance: dict) -> bool:
    """Each group's readings under its own two tolerances, no group
    missing: ``gradient`` holds the gradient and both moments (they differ
    by rounding only), ``update`` the weight change, whose first AdamW
    step is ``lr * sign(g)`` and flips whole where a small gradient
    element rounds across zero."""
    return set(by_group) == set(tolerance) and all(
        max(kinds["gradient"], kinds["m"], kinds["v"])
        <= float(tolerance[g]["gradient"])
        and kinds["update"] <= float(tolerance[g]["update"])
        for g, kinds in by_group.items())


def build(cell, seed: int, tiny: bool, phase=lambda name: None):
    import jax

    from benchmark import spec
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.parallel.mesh import train_mesh_from_config

    te = helpers()
    cfg, job = cell.config, dict(cell.traffic)
    overrides = dict(cfg["root"])
    overrides.update(job.get("root", {}))
    checks = {k: dict(cfg[k]) for k in ("parity", "step_check", "routing")}
    if tiny:
        small, small_job = cfg.get("tiny", {}), job.get("tiny", {})
        overrides.update(small.get("root", {}))
        overrides.update(small_job.get("root", {}))
        for k in checks:
            checks[k].update(small.get(k, {}))
        job.update({k: v for k, v in small_job.items() if k != "root"})
    prng.seed_all(seed)
    te.apply_overrides(root, overrides)
    module = importlib.import_module(cfg["sample"])
    wf = getattr(module, cfg["workflow"])()
    wf.initialize(device=None)
    phase("workflow_initialized")
    mesh = train_mesh_from_config()
    if (1 if mesh is None else mesh.size) != cell.chips:
        raise RuntimeError("the configuration's mesh does not match the "
                           "cell's chips")
    lengths = list(wf.loader.class_lengths)
    rows, tokens = wf.loader.original_data.shape
    data, labels = spec.load_module(
        "generators", job["generator"], cell.root).make(
            seed, int(rows), int(tokens), int(wf.forwards[-1].vocab),
            float(job["zipf"]))
    wf.loader.original_data.devmem = data
    wf.loader.original_labels.devmem = labels
    jax.block_until_ready(data)
    phase("data_on_device")
    trainer = FusedTrainer(wf, mesh=mesh)
    return types.SimpleNamespace(wf=wf, trainer=trainer, data=data,
                                 labels=labels, lengths=lengths, job=job,
                                 row_tokens=int(tokens), **checks)


def run(ctx) -> dict:
    import jax

    from znicz_tpu import telemetry
    from znicz_tpu.loader.base import TRAIN, VALID

    from benchmark import flops_decoder as flops
    built = build(ctx.cell, ctx.seed, ctx.tiny, ctx.phase)
    wf, trainer, job = built.wf, built.trainer, built.job
    data, labels, lengths = built.data, built.labels, built.lengths
    loader, decision = wf.loader, wf.decision
    model, share = model_and_share(ctx.cell.config, ctx.tiny)

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
    period = max(int(wf.snapshotter.interval), 1)
    stats, meter = trainer.stats, ctx.meter
    marks = {}          # name -> (perf_counter, epoch, meter, saves, rows)
    epoch_t = []
    annotation = contextlib.ExitStack()
    trace_dir = os.path.join(ctx.scratch_dir, "trace")

    def open_annotation(epoch):
        annotation.enter_context(
            jax.profiler.TraceAnnotation(f"bench:epoch:{epoch}"))

    def mark(name, epoch):
        marks[name] = (time.perf_counter(), epoch, meter.snapshot(),
                       int(wf.snapshotter.async_saves_written),
                       (int(stats.get("moe_rows_routed", 0)),
                        int(stats.get("moe_counted_steps", 0))))

    def on_epoch_end(d):
        epoch = int(d.epoch_number)
        done = epoch + 1
        now = time.perf_counter()
        if done == warmup:
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
    ctx.log({"phase": "built", "batch": batch, "row_tokens":
             built.row_tokens, "class_lengths": lengths,
             "steps_per_epoch": steps_epoch,
             "eval_steps_per_epoch": eval_epoch,
             "parameters": sum(int(a.size) for f in wf.forwards
                               for a in f.params().values()),
             "build_s": ctx.phase("trainer_built")})
    trainer.run()
    if "trace_start" in marks and "trace_end" not in marks:
        annotation.close()
        jax.profiler.stop_trace()
        raise RuntimeError("the traced window did not close")

    t_start, e_start, at_start, saved_start, rows_start = marks["start"]
    t_end, e_end, at_end, saved_end, rows_end = marks["end"]
    epochs = e_end - e_start
    window_s = t_end - t_start
    samples = epochs * lengths[TRAIN]
    history = decision.epoch_history
    bad_epochs = sum(not all(np.isfinite(v) for v in h.values())
                     for h in history[warmup:])
    compiles = int(stats.get("compiles", -1))
    jit_sizes = dict(stats.get("jit_cache_sizes", {}))
    counters = {k: v for k, v in stats.items()
                if isinstance(v, (int, float, dict))
                and k != "jit_cache_sizes"}
    ctx.phase("window_end")

    # the texts of the programs that ran, before the checks add theirs
    texts = [m.to_string()
             for exe in jax.devices()[0].client.live_executables()
             for m in exe.hlo_modules()] if ctx.trace else []
    first_train = lengths[0] + lengths[VALID]
    timed = range(first_train, first_train + batch)
    agreement = parity(ctx.cell, model, share, trainer, wf.forwards,
                       data[first_train:first_train + batch])
    ctx.phase("parity_checked")
    step = step_check(ctx.cell, model, share, wf, trainer, data, labels,
                      timed)
    ctx.phase("checked")

    sparse = [f for f in wf.forwards if getattr(f, "sparse", False)]
    counted_steps = max(int(stats.get("moe_counted_steps", 0)), 1)
    rows_step_layer = (int(stats.get("moe_rows_routed", 0))
                       / counted_steps / max(len(sparse), 1))
    expected = (batch * built.row_tokens * int(model["num_experts_per_tok"])
                * int(share["experts_held"]) / int(model["num_experts"]))
    lo, hi = built.routing["band"]
    limits = built.step_check["tolerance"]
    checks = {
        "logits_within_tolerance": agreement["relative_l2"] <= float(
            built.parity["tolerance"]),
        "float8_control_fails": not agreement["relative_l2_float8"] <= float(
            built.parity["tolerance"]),
        "step_within_tolerance": within(step["by_group"], limits),
        "unchanged_state_control_fails": not within(
            dict.fromkeys(limits, UNCHANGED), limits),
        "no_row_dropped": int(stats.get("moe_rows_dropped", -1)) == 0,
        "rows_routed_in_band": lo * expected <= rows_step_layer
        <= hi * expected,
        "losses_finite": all(np.isfinite(v) for h in history
                             for v in h.values()),
        "no_compile_in_window": at_end["compiles"] == at_start["compiles"],
        "compiles_match_jit_caches": compiles == sum(jit_sizes.values()),
        "epochs_finished": len(history) == warmup + epochs,
    }
    snap = wf.snapshotter
    rate = samples / window_s
    out = {
        "setup_s": t_start - ctx.t_backend,
        "setup_compile_s": at_start["seconds"],
        "setup_cache": {"hits": at_start["hits"],
                        "misses": at_start["misses"]},
        "values": {"train_samples_per_s": rate},
        "attempted": epochs * steps_epoch,
        "failed": bad_epochs * steps_epoch,
        "correct": all(checks.values()),
        "checks": checks,
        "window": {"seconds": window_s, "epochs": epochs,
                   "period_epochs": period, "samples": samples,
                   "tokens_per_s": rate * built.row_tokens,
                   "train_steps": epochs * steps_epoch,
                   "eval_steps": epochs * eval_epoch,
                   "epoch_seconds": np.diff(epoch_t).tolist()},
        "parity": dict(agreement, tolerance=built.parity["tolerance"]),
        "step_check": dict(step, tolerance=built.step_check["tolerance"]),
        "loss": {"train": [h["train"] for h in history],
                 "valid": [h.get("valid") for h in history],
                 "untrained": math.log(int(share["vocab_held"]))},
        "counters": {
            "fused_stats": counters,
            "jit_cache_sizes": jit_sizes,
            "rows_routed_per_step_and_layer": rows_step_layer,
            "rows_expected_per_step_and_layer": expected,
            "snapshots_written": int(snap.async_saves_written),
            "snapshots_written_in_window": saved_end - saved_start,
            "snapshot_files": sorted(os.listdir(snap_dir))
            if os.path.isdir(snap_dir) else [],
        },
        "shape": {"devices": 1, "batch": batch,
                  "row_tokens": built.row_tokens,
                  "steps_per_epoch": steps_epoch,
                  "eval_steps_per_epoch": eval_epoch,
                  # the work of the dot-rooted operations: everything but
                  # the experts' grouped products, which are custom calls
                  # (``reduce/xplane.category``: ``other``)
                  "train_flops_per_step": 3 * flops.dot_forward_flops(
                      model, share, batch, built.row_tokens),
                  "forward_flops_per_step": flops.dot_forward_flops(
                      model, share, batch, built.row_tokens),
                  "model": model, "share": share},
    }
    if ctx.trace:
        from benchmark.reduce import xplane

        path = xplane.newest_xplane(trace_dir)
        if path is None:
            raise RuntimeError(f"no .xplane.pb under {trace_dir}")
        trace = out["trace"] = xplane.reduce_trace(
            path, telemetry.tracer().events(), marks["sync"][0], texts)
        trace["train_steps"] = trace_epochs * steps_epoch
        trace["eval_steps"] = trace_epochs * eval_epoch
        trace["host_window_s"] = (marks["trace_end"][0]
                                  - marks["trace_start"][0])
        trace["programs"] = Programs(texts)
        # rows the held experts computed in the traced window's steps,
        # train and validation, all expert layers together
        rows0, rows1 = marks["trace_start"][4], marks["trace_end"][4]
        trace["moe_rows_routed"] = rows1[0] - rows0[0]
        trace["moe_counted_steps"] = rows1[1] - rows0[1]
    shutil.rmtree(snap_dir, ignore_errors=True)
    return out
