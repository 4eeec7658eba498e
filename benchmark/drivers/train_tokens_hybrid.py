"""Driver ``train_tokens_hybrid``: the training job of a decoder whose
layers are ONE part each — a state-space mixer, an attention block or an
expert layer — on rows of token ids, with an untied head and a router
whose selection bias the load moves.

The job is built and run as ``train_tokens`` builds and runs its own (its
helpers are imported — ``build``, ``reference_tree``, ``GroupErrors``,
``group_of``, ``within``, ``reseed``, ``Programs``, ``UNCHANGED`` — and
nothing of it is edited; the window and trace bookkeeping in ``run`` is
its ``run``'s, line for line, because that function cannot be had in
parts: PERF.md section 7 asks a ``benchmark`` PR to fold the three).  What
differs is what ``correct`` compares:

1. ``parity``: the evaluation-mode residual stream of a timed batch on the
   TRAINED weights through ``FusedTrainer.forward_pass(..., hidden=True)``,
   then the head's logits a block of rows at a time
   (``LMHead.logits_rows``) against the plain reference's — its
   state-space layers a recurrence over the positions — the squared error
   and the squared norm SUMMED over the blocks, one relative L2.  The
   control is the same pass with every float operand through an 8-bit
   float; it has to come out as NOT within the tolerance
   (``float8_control_fails``).  Also the share of (row, slot) expert
   choices that bfloat16 moves at equal inputs.
2. ``step_check``: the trainer's own compiled step from the SEEDED weights
   against the reference's gradient (a row at a time to the host) and
   AdamW by parameter group, at the rate of the job's FIRST step under
   its warm-up; the control is a state left unchanged.  The selection
   bias (the reference's ``LOAD_DRIVEN``) has no gradient and AdamW leaves
   it alone: its change is compared with the reference's ``balance_step``
   on the same rows, a group of its own.
3. the expert layers' counters (``no_row_dropped``, rows routed a step and
   layer in a band around ``tokens x experts a token x held / total``, the
   busiest held expert's rows over the mean under a limit), the layers by
   kind as the pattern gives them, a bias moved in every expert layer,
   every scan run and counted, the attention core in the kernels.
4. finite losses, no compilation inside the window, ``compiles == sum of
   jit cache sizes``, every epoch finished.

``shape.train_flops_per_step`` (what ``mxu_roofline`` divides) counts what
XLA runs as dot-rooted operations — projections, the scan's products, the
router, the shared experts, the head — and neither the core's pairs nor
the routed experts, which are custom calls
(``benchmark/flops_nemotron.py``).
"""

from __future__ import annotations

import contextlib
import functools
import math
import os
import shutil
import time

import numpy as np


def tokens():
    """``train_tokens``' helpers: imported, not edited."""
    from benchmark import spec

    return spec.load_module("drivers", "train_tokens")


def model_and_share(cfg: dict, tiny: bool):
    """``(model, share)`` for the reference, from the configuration's
    FILE: the model's ``config.json`` keys with the published values of
    the reduced ones put back and the file's ``assumed_keys`` laid over
    them, and what this chip holds."""
    if tiny:
        return cfg["tiny"]["model"], cfg["tiny"]["share"]
    model = {k: cfg[k] for k in cfg["model_keys"]}
    model.update(cfg["published"])
    model.update(cfg["assumed_keys"]["keys"])
    return model, {"layers": cfg["num_hidden_layers"],
                   "experts_held": cfg["n_routed_experts"],
                   "first_expert": cfg["first_expert"],
                   "vocab_held": cfg["vocab_size"]}


def parity(cell, model, share, trainer, forwards, ids, block: int) -> dict:
    import jax
    import jax.numpy as jnp

    tt, ref = tokens(), cell.reference()
    params = trainer.extract_params()
    tree = tt.reference_tree(forwards, params)
    head = forwards[-1]
    mixed = trainer.compute_dtype != np.dtype("float32")

    def reference(tree, ids):
        taps = []
        hidden = ref.final_hidden(tree, ids, model, share, taps=taps)
        moved, total = 0.0, 0
        for p, xn in taps:
            # float32 products here too: a TPU's default would round both
            # sides' operands to bfloat16 and move nothing
            with jax.default_matmul_precision("highest"):
                want, _ = ref.routing(model, p, xn)
                got, _ = ref.routing(model, jax.tree_util.tree_map(
                    lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                    p), xn.astype(jnp.bfloat16).astype(jnp.float32))
            same = (got[:, :, None] == want[:, None, :]).any(-1)
            moved += jnp.sum(~same)
            total += same.size
        return hidden, moved / max(total, 1)

    def bf16(t):
        return t.astype(jnp.bfloat16) if t.dtype == jnp.float32 else t

    def fp8(t):         # the nearest precision below, on the way in
        if not jnp.issubdtype(t.dtype, jnp.floating):
            return t
        return t.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)

    def errors(cast, params, tree, ids, want_hidden):
        """``(|got - want|^2, |want|^2)`` of the logits, summed over row
        blocks; the system's operands through ``cast``."""
        low = jax.tree_util.tree_map(cast, params) if cast else params
        got_hidden = trainer.forward_pass(low, ids, None, False, cast=cast,
                                          hidden=True)
        rows = got_hidden.shape[0] * got_hidden.shape[1]
        size = min(block, rows)
        assert rows % size == 0, (rows, size)

        def one(xs):
            got_rows, want_rows = xs
            got = head.logits_rows(
                low[head.name], cast(got_rows) if cast else got_rows).astype(
                    jnp.float32)
            want = ref.logits_of(tree, want_rows, model)
            return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))

        err, norm = jax.lax.map(one, (
            got_hidden.reshape(rows // size, size, -1),
            want_hidden.reshape(rows // size, size, -1)))
        return jnp.sum(err), jnp.sum(norm)

    def relative(cast, want_hidden):
        err, norm = jax.jit(functools.partial(errors, cast))(
            params, tree, ids, want_hidden)
        value = math.sqrt(float(err) / (float(norm) + 1e-60))
        return value if np.isfinite(value) else float("inf")

    want_hidden, moved = jax.jit(reference)(tree, ids)
    return {"relative_l2": relative(bf16 if mixed else None, want_hidden),
            "relative_l2_float8": relative(fp8, want_hidden),
            "choices_moved_by_bfloat16": float(moved),
            "dtype": str(trainer.compute_dtype), "rows": int(ids.shape[0]),
            "logit_rows_a_block": int(min(block, ids.size))}


def step_check(cell, model, share, wf, trainer, data, labels, rows) -> dict:
    """See the module's text, 2.  Returns ``by_group``: group ->
    ``gradient`` / ``update`` / ``m`` / ``v`` relative L2."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.core import prng

    tt, ref = tokens(), cell.reference()
    opt = cell.config["optimizer"]
    rate = ref.warmup_rate(0, opt["learning_rate"],
                           opt["schedule"]["steps"])
    forwards = wf.forwards
    idx = np.asarray(rows, np.int32)
    # the reference first, before the moments take their room again
    tt.reseed(wf, state=False)
    ids, targets = jnp.take(data, idx, axis=0), jnp.take(labels, idx, axis=0)
    # a row at a time (rows hold as many tokens each: the mean of the
    # rows' means), each row's gradient summed on the HOST
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        ref.loss, model=model, share=share, remat=True)))
    tree = tt.reference_tree(forwards, trainer.extract_params())
    loss_ref, grad_ref = 0.0, None
    for r in range(len(idx)):
        value, g = grad_fn(tree, ids[r:r + 1], targets[r:r + 1])
        g = jax.tree_util.tree_map(np.asarray, g)
        loss_ref += float(value) / len(idx)
        grad_ref = g if grad_ref is None else jax.tree_util.tree_map(
            np.add, grad_ref, g)
    grad_ref = jax.tree_util.tree_map(lambda a: a / len(idx), grad_ref)

    def moves(tree, ids):           # the load-driven tensors' own steps
        taps = []
        ref.final_hidden(tree, ids, model, share, taps=taps)
        return [ref.balance_step(model, p, xn) for p, xn in taps]

    moves_ref = iter(jax.jit(moves)(tree, ids))
    del tree, g
    for gd in wf.gds:
        gd._make_state(None)

    step = trainer._train_step or trainer.make_train_step()
    key = prng.get("fused_trainer").jax_key(0)
    size = np.int32(len(idx))
    # the job's numbers at its first step, and a probe that leaves the
    # gradient in the first moment: lr, decay, beta1, beta2, eps
    names = list(trainer.hypers())
    hypers = {name: tuple(np.float32(v) for v in (
        rate, opt["weight_decay"], opt["beta1"], opt["beta2"],
        opt["epsilon"])) for name in names}
    probe = {name: tuple(np.float32(v) for v in (0, 0, 0, 0, 1))
             for name in names}

    def run(hypers):                    # the step donates what it is given
        return step(trainer.extract_params(), trainer.extract_velocities(),
                    hypers, data, labels, idx, size, key)

    def ref_leaves(f):
        """(the system's key, the reference's, its gradient's leaf)."""
        if f is forwards[0]:
            return [("embed", "embed", grad_ref["embed"])]
        if f is forwards[-1]:
            return [("norm", "norm", grad_ref["norm"]),
                    ("weights", "head", grad_ref["head"])]
        layer = grad_ref["layers"][forwards.index(f) - 1]
        return [(k, k, g) for k, g in layer.items()]

    errors = tt.GroupErrors()

    def book(kind, group, sums):
        """``(|got - want|^2, |want|^2)`` into ``errors``, from ONE fused
        program a tensor."""
        acc = errors.sums.setdefault((kind, group), [0.0, 0.0])
        acc[0] += float(sums[0])
        acc[1] += float(sums[1])

    @jax.jit
    def sums_of(got, want):
        got, want = got.astype(jnp.float32), want.astype(jnp.float32)
        return jnp.sum(jnp.square(got - want)), jnp.sum(jnp.square(want))

    @functools.partial(jax.jit, static_argnames="decay")
    def against_adamw(w, g, w_new, m_new, v_new, decay):
        w_ref, m_ref, v_ref = ref.adamw(
            w, jnp.zeros_like(w), jnp.zeros_like(w), g, step=1.0,
            learning_rate=rate, beta1=opt["beta1"], beta2=opt["beta2"],
            eps=opt["epsilon"], weight_decay=decay)
        return (sums_of(w_new - w, w_ref - w), sums_of(m_new, m_ref),
                sums_of(v_new, v_ref))

    _, state, (loss_sys, *_) = run(probe)
    for f in forwards:
        for k, k_ref, g in ref_leaves(f):
            book("gradient", tt.group_of(ref, k_ref), sums_of(
                state[f.name].pop(f"m_{k}"), g))
    del state
    tt.reseed(wf)
    new_p, new_s, _ = run(hypers)
    for f in forwards:
        init = f.init_params()
        for k, k_ref, g in ref_leaves(f):
            group = tt.group_of(ref, k_ref)
            if k_ref in ref.LOAD_DRIVEN:
                book("update", group, sums_of(
                    new_p[f.name].pop(k) - init.pop(k), next(moves_ref)))
                for kind in "mv":
                    book(kind, group, sums_of(
                        new_s[f.name].pop(f"{kind}_{k}"), g))
                continue
            update, m, v = against_adamw(
                init.pop(k), g, new_p[f.name].pop(k),
                new_s[f.name].pop(f"m_{k}"), new_s[f.name].pop(f"v_{k}"),
                decay=0.0 if k_ref in ref.NO_DECAY else opt["weight_decay"])
            book("update", group, update)
            book("m", group, m)
            book("v", group, v)
        del init
    return {"by_group": errors.by_group(),
            "loss": {"system": float(loss_sys), "reference": loss_ref},
            "rows": len(idx), "learning_rate": rate,
            "jit_cache_sizes_after": trainer.jit_cache_sizes()}


def run(ctx) -> dict:
    import jax

    from znicz_tpu import telemetry
    from znicz_tpu.loader.base import TRAIN, VALID

    from benchmark import flops_nemotron as flops
    from benchmark import spec
    tt = tokens()
    built = tt.build(ctx.cell, ctx.seed, ctx.tiny, ctx.phase)
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
    epoch_t, epoch_rows = [], []
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
            epoch_rows.append(int(stats.get("moe_rows_routed", 0)))
            return
        if done < warmup:
            return
        epoch_t.append(now)
        epoch_rows.append(int(stats.get("moe_rows_routed", 0)))
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
    rates = [float(gd.learning_rate) for gd in wf.gds]
    ctx.phase("window_end")

    # the texts of the programs that ran, before the checks add theirs
    texts = [m.to_string()
             for exe in jax.devices()[0].client.live_executables()
             for m in exe.hlo_modules()] if ctx.trace else []
    first_train = lengths[0] + lengths[VALID]
    timed = range(first_train, first_train + batch)
    agreement = parity(ctx.cell, model, share, trainer, wf.forwards,
                       data[first_train:first_train + batch],
                       int(built.parity["logit_rows_a_block"]))
    ctx.phase("parity_checked")
    step = step_check(ctx.cell, model, share, wf, trainer, data, labels,
                      timed)
    ctx.phase("checked")

    pattern = model["hybrid_override_pattern"][:int(share["layers"])]
    layers = [f for f in wf.forwards if getattr(f, "sparse", False)]
    counted_steps = max(int(stats.get("moe_counted_steps", 0)), 1)
    rows_step_layer = (int(stats.get("moe_rows_routed", 0))
                       / counted_steps / max(len(layers), 1))
    tokens_step = batch * built.row_tokens
    expected = (tokens_step * int(model["num_experts_per_tok"])
                * int(share["experts_held"])
                / int(model["n_routed_experts"]))
    lo, hi = built.routing["band"]
    by_expert = stats.get("moe_rows_by_expert") or {}
    limits = built.step_check["tolerance"]
    on_tpu = jax.default_backend() == "tpu"
    checks = {
        "logits_within_tolerance": agreement["relative_l2"] <= float(
            built.parity["tolerance"]),
        "float8_control_fails": not agreement["relative_l2_float8"] <= float(
            built.parity["tolerance"]),
        "step_within_tolerance": tt.within(step["by_group"], limits),
        "unchanged_state_control_fails": not tt.within(
            dict.fromkeys(limits, tt.UNCHANGED), limits),
        "no_row_dropped": int(stats.get("moe_rows_dropped", -1)) == 0,
        "rows_routed_in_band": lo * expected <= rows_step_layer
        <= hi * expected,
        "rows_balanced": 0 < by_expert.get("max", 0) <= float(
            built.routing["max_over_mean"]) * by_expert.get("mean", 0),
        # the layers the pattern gives, by kind, and what each kind notes
        "layers_by_kind": [int(stats.get(k, -1)) for k in (
            "layers_mamba", "layers_experts", "layers_attention")]
        == [pattern.count(c) for c in "ME*"],
        "router_biases_moved": int(stats.get(
            "router_biases_moved", -1)) == len(layers) == pattern.count("E"),
        "scans_in_chunks": int(stats.get("ssm_scans_kernel", 0)) + int(
            stats.get("ssm_scans_composed", 0)) == pattern.count("M")
        and int(stats.get("ssm_chunks", 0)) == math.ceil(
            built.row_tokens / int(model["chunk_size"])),
        # on the chip the core runs in the kernels; the rehearsal's CPU
        # has none
        "cores_in_kernels": int(stats.get("attn_cores_kernel", 0))
        == (pattern.count("*") if on_tpu else 0),
        "losses_finite": all(np.isfinite(v) for h in history
                             for v in h.values()),
        "no_compile_in_window": at_end["compiles"] == at_start["compiles"],
        "compiles_match_jit_caches": compiles == sum(jit_sizes.values()),
        "epochs_finished": len(history) == warmup + epochs,
    }
    snap = wf.snapshotter
    rate = samples / window_s
    dot_forward = flops.dot_forward_flops(model, share, batch,
                                          built.row_tokens)
    kind = jax.devices()[0].device_kind
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
                   "epoch_seconds": np.diff(epoch_t).tolist(),
                   # rows the held experts computed, all layers and the
                   # epoch's 9 steps together: the load behind the rate
                   "epoch_rows_routed": np.diff(epoch_rows).tolist(),
                   "learning_rate_at_end": max(rates)},
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
        # the HBM peak of the device the run was on, for the reader of
        # ``ssm_scan_roofline`` (``None`` on a device the table lacks)
        "peaks_hbm": spec.read_json("benchmark", "peaks_hbm.json")[
            "devices"].get(kind),
        "shape": {"devices": 1, "batch": batch,
                  "row_tokens": built.row_tokens,
                  "steps_per_epoch": steps_epoch,
                  "eval_steps_per_epoch": eval_epoch,
                  # the dot-rooted operations only: the core's kernels and
                  # the experts' grouped products are custom calls
                  "train_flops_per_step": 3 * dot_forward,
                  "forward_flops_per_step": dot_forward,
                  "head_unit": wf.forwards[-1].name,
                  # ``layer_types``: what ``reduce/inner.py`` reads to
                  # tell a window layer's core from a full one's
                  "model": dict(model, layer_types=[
                      "full_attention" if c == "*" else c
                      for c in model["hybrid_override_pattern"]]),
                  "share": share},
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
        trace["programs"] = tt.Programs(texts)
        # rows the held experts computed in the traced window's steps,
        # train and validation, all layers together
        rows0, rows1 = marks["trace_start"][4], marks["trace_end"][4]
        trace["moe_rows_routed"] = rows1[0] - rows0[0]
        trace["moe_counted_steps"] = rows1[1] - rows0[1]
    shutil.rmtree(snap_dir, ignore_errors=True)
    return out
