"""FusedTrainer: the TPU-native fast path — one jitted SPMD train step for a
StandardWorkflow-shaped graph.

The unit-at-a-time engine (Workflow.run) preserves the reference's execution
semantics but pays one dispatch + host sync per unit.  The fused trainer
stages the whole minibatch pipeline

    gather(dataset, idx) -> forwards -> loss -> grads -> per-layer sgd_update

into ONE ``jax.jit`` with sharding annotations: dataset/batch sharded over
the mesh ``data`` axis, params replicated (or column-sharded over ``model``
for wide FC layers), gradients reduced by the psum XLA inserts — the
reference's entire master/slave ZeroMQ stack (SURVEY.md §3.4) becomes a
single compiled collective over ICI.

Semantics guaranteed identical to the unit path:
  - forward math IS the units' own pure ``apply`` (same code objects);
  - the update rule IS ``nn_units.sgd_update`` with each GD unit's own
    hyperparameters (per-layer lr/momentum/L1+L2/clip survive);
  - loss/cotangent match the evaluators (softmax-CE at logits; masked MSE);
  - dropout/stochastic pooling draw per-layer per-step keys from the same
    seeded stream design (mask reuse is implicit — fwd and bwd live in one
    autodiff graph).

Mixed precision: with ``root.common.engine.compute_dtype = "bfloat16"``,
the forward/backward graph runs in bf16 on the MXU while master params, velocity
and the update stay float32.

Unit-Array refresh cadence: training state lives in device arrays; the
units' ``Array`` views are refreshed by ``writeback`` only when an
epoch-granular consumer needs them (a wired plotter) and once at the end
of the run — NOT unconditionally every epoch (a device->host pull of the
whole state each time).  A due HOST-FORMAT snapshot no longer pays even that:
``snapshot_from_trees`` hands donation-safe device copies to the
snapshotter's background writer, which pulls and writes while the next
epoch computes (the deep pipeline checkpoints the same way at flush
boundaries).  Ad-hoc observers that read weights mid-run must account
for this.
"""

from __future__ import annotations

import contextlib
import functools
import sys as _sys
import time
from typing import Dict

import numpy as np

from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.nn_units import sgd_update


class FusedUnsupportedError(ValueError):
    """The workflow's graph cannot run on the fused path (e.g. tied
    weights).  The engine catches exactly this to fall back to the unit
    engine; any other error propagates (ADVICE r3: a blanket ValueError
    catch masked unrelated failures)."""


class FusedStagingUnsupportedError(FusedUnsupportedError):
    """A fused SLAVE cannot serve a host-staged streaming loader
    (FusedClient needs the dataset device-resident).  A dedicated type so
    the engine's slave fallback catches exactly the two known refusals —
    this and the base FusedUnsupportedError — instead of a blanket
    ``ValueError`` that would also swallow real config errors."""


def rematerialised(f, unit):
    """``unit``, the traced function of forward unit ``f``, under
    ``jax.checkpoint``: the backward pass keeps its inputs and the
    tensors ``f`` names (``remat_keeps``; none: the bare
    ``jax.checkpoint``) and recomputes the rest; ``f`` is told what was
    kept (``remat_kept``)."""
    import jax

    keeps = f.remat_kept = tuple(getattr(f, "remat_keeps", ()))
    policy = (jax.checkpoint_policies.save_only_these_names(*keeps)
              if keeps else None)
    return jax.checkpoint(unit, policy=policy)


def _default_order(sharding, shape, dtype):
    """Axis order, major to minor, of the layout the devices of
    ``sharding`` give an array of this shape and dtype by default."""
    from jax.experimental.layout import Layout

    dev = min(sharding.device_set, key=lambda d: d.id)
    return tuple(Layout.from_pjrt_layout(dev.client.get_default_layout(
        np.dtype(dtype), sharding.shard_shape(tuple(shape)),
        dev)).major_to_minor)


class _Rows:
    """The resident twin inside a traced program (``FusedTrainer.
    _resident``): ``take(idx)`` gives the rows as the loader's own array
    would give them — the pad cut off, the axes back in the loader's
    order.  Both are free where the rows' consumer wants the twin's
    physical order, which is how that order was chosen."""

    def __init__(self, twin, order, sample_shape):
        self.twin, self.order, self.sample_shape = twin, order, sample_shape

    def take(self, idx):
        import jax.numpy as jnp

        rows = jnp.take(self.twin, idx, axis=0)
        kept = tuple(self.sample_shape[a - 1] for a in self.order[1:])
        if rows.shape[1:] != kept:
            rows = rows[(slice(None),) + tuple(slice(n) for n in kept)]
        return jnp.transpose(rows, np.argsort(self.order))


class _ResidentJit:
    """A jitted step/scan/epoch that gathers from the resident set.

    Callers hand it the loader's own device array (or a
    ``jax.ShapeDtypeStruct`` of it, to ``lower``); the program takes that
    array's prepared twin (``FusedTrainer._resident``) and reads its rows
    through ``_Rows``.  One ``jax.jit`` per way the rows are laid out —
    one, for a trainer that keeps its set."""

    def __init__(self, trainer, fn, argnum, in_specs, out_specs, **jit_kw):
        self._trainer, self._fn, self._argnum = trainer, fn, argnum
        self._jit_kw = dict(jit_kw, **trainer._jit_shardings(in_specs,
                                                             out_specs))
        self._jitted = {}               # twin's (order, sample shape) -> jit

    def _resolve(self, args):
        import jax

        fn, n = self._fn, self._argnum
        twin, plan = self._trainer._resident(args[n])
        jitted = self._jitted.get(plan)
        if jitted is None:
            @functools.wraps(fn)        # the program keeps its name
            def on_twin(*args):
                return fn(*args[:n], _Rows(args[n], *plan), *args[n + 1:])

            jitted = self._jitted[plan] = jax.jit(
                fn if plan is None else on_twin, **self._jit_kw)
        return jitted, args[:n] + (twin,) + args[n + 1:]

    def __call__(self, *args):
        jitted, on_twin = self._resolve(args)
        out = jitted(*on_twin)
        n = self._argnum
        if on_twin[n] is not args[n] and not self._trainer._twins:
            # a twin made for this call (no run holds one): wait, so that
            # its HBM is free again when the caller goes on
            import jax

            jax.block_until_ready(out)
        return out

    def lower(self, *args):
        jitted, args = self._resolve(args)
        return jitted.lower(*args)

    def _cache_size(self) -> int:
        return sum(j._cache_size() for j in self._jitted.values())


class FusedTrainer:
    """Compile and drive fused steps for a built+initialized workflow with
    ``forwards``, ``gds``, ``loader``, ``evaluator``, ``decision``."""

    def __init__(self, workflow, mesh=None):
        from znicz_tpu.all2all import All2AllSoftmax
        from znicz_tpu.attention import SeqAll2AllSoftmax
        from znicz_tpu.dropout import DropoutForward
        from znicz_tpu.evaluator import EvaluatorSoftmax
        from znicz_tpu.pooling import StochasticPoolingBase

        self.scan_chunk = int(root.common.engine.get("scan_chunk",
                                                     type(self).scan_chunk))
        self.pipeline_depth = int(root.common.engine.get(
            "pipeline_depth", type(self).pipeline_depth))
        self.workflow = workflow
        self.forwards = list(workflow.forwards)
        self.loader = workflow.loader
        self.decision = workflow.decision
        self.mesh = mesh
        #: seq_parallel ring attention on the TRAINING mesh (ISSUE 18):
        #: with the knob on and a >1 ``model`` axis in this slice, every
        #: attention core shard_maps over THIS mesh (batch x sequence)
        #: instead of building a private ("sp",) device grid — one mesh
        #: serves the jitted steps AND the ring rotation
        if mesh is not None and "model" in mesh.axis_names \
                and mesh.shape["model"] > 1:
            from znicz_tpu.attention import (MultiHeadAttention,
                                             seq_parallel_size)

            if seq_parallel_size() > 1:
                for f in self.forwards:
                    if isinstance(f, MultiHeadAttention):
                        f.bind_sequence_mesh(mesh)
        self.loss_kind = ("softmax"
                          if isinstance(workflow.evaluator, EvaluatorSoftmax)
                          else "mse")
        #: the fused path sums the (C,C) confusion ON DEVICE (scan carry +
        #: ``epoch_conf``) and transfers it once per epoch, so the unit
        #: path's width-based auto-off (per-minibatch transfer cost) does
        #: not apply: confusion is ALWAYS collected unless the user
        #: explicitly disabled it on the evaluator.  ``None`` (evaluator
        #: not yet initialized) counts as unresolved, not as disabled
        #: (ADVICE r3 / VERDICT r3 missing #4).
        ev = workflow.evaluator
        if getattr(ev, "confusion_explicit", False):
            self.compute_confusion = bool(ev.compute_confusion)
        else:
            self.compute_confusion = True
        self._softmax_cls = All2AllSoftmax
        #: the per-position softmax head (ISSUE 15): like All2AllSoftmax,
        #: the fused path emits its LOGITS and derives loss/cotangent in
        #: the loss head (seq logits flatten tokens into the batch axis)
        self._seq_softmax_cls = SeqAll2AllSoftmax
        self._dropout_cls = DropoutForward
        self._stochpool_cls = StochasticPoolingBase
        self.gd_of = {gd.forward.name: gd for gd in workflow.gds}
        # tied weights (shared Arrays) need joint-update logic the fused
        # path doesn't implement — detect and refuse (unit path handles it)
        seen = {}
        for f in self.forwards:
            for k, arr in f.params().items():
                if id(arr) in seen:
                    raise FusedUnsupportedError(
                        f"fused trainer does not support tied weights "
                        f"({f.name}.{k} shares {seen[id(arr)]})")
                seen[id(arr)] = f"{f.name}.{k}"
        from znicz_tpu.lr_adjust import LearningRateAdjust

        #: a user-wired LearningRateAdjust unit advances once per TRAIN
        #: step here too (the unit graph runs it per lap, gated like the
        #: gds); scans take per-step hypers as xs so LR schedules apply
        #: with per-step granularity, exactly as in the unit path
        self._lr_adjust = next(
            (u for u in workflow.units
             if isinstance(u, LearningRateAdjust)), None)
        self._train_step = None
        self._train_scan = None
        self._eval_step = None
        self._eval_scan = None
        #: (loader's array, its prepared twin, how ``_Rows`` reads it) of
        #: the run in progress — see ``_resident``
        self._twins = []
        #: the live DeviceStager while a staged run is inside
        #: _run_segmented with async staging on (tests observe it)
        self._stager = None
        self._key0 = prng.get("fused_trainer").jax_key(0)
        self.steps_done = 0
        #: per-step timing accumulated by run() (SURVEY.md §5 Tracing —
        #: the fast path reports like the unit path's timing table does);
        #: surfaced by Workflow.print_stats and web_status /status.json
        #: via ``workflow.fused_stats``
        #: ``warm_*`` exclude each dispatch kind's FIRST call (which pays
        #: jit compilation) — the steady-state numbers; ``wall_s`` etc.
        #: are totals including compiles
        self.stats = {"train_steps": 0, "eval_steps": 0, "images": 0,
                      # ids consumed, where a sample is a row of ids
                      "tokens": 0,
                      "wall_s": 0.0, "steps_per_sec": 0.0,
                      "img_per_sec": 0.0, "last_step_ms": 0.0,
                      "warm_steps": 0, "warm_images": 0, "warm_wall_s": 0.0,
                      "warm_img_per_sec": 0.0,
                      # programs launched (train/eval steps, scans, whole
                      # epochs), and the host seconds spent blocked on the
                      # device's values, in the Decision and in the
                      # epoch-end hook: what the device waits for
                      "dispatches": 0, "warm_dispatches": 0,
                      "sync_wait_s": 0.0, "decide_s": 0.0,
                      "epoch_hook_s": 0.0,
                      # twins made of a resident set (``_resident``): one
                      # a run where the set needs one, none in steady state
                      "resident_prepares": 0,
                      # epoch tails that rode the epoch's last scan (the
                      # Decision said beforehand that the run goes on) and
                      # those evaluated, ruled on and updated alone
                      "tails_in_scan": 0, "tails_alone": 0}
        workflow.fused_stats = self.stats
        # telemetry (ISSUE 5): hot-loop metrics + spans.  The histogram
        # observes and the spans record only while telemetry is enabled
        # (what the layer costs on the chip: PERF.md section 6).
        from znicz_tpu import telemetry

        self._tracer = telemetry.tracer()
        _sc = self._scope = telemetry.scope("trainer")
        self._m_train_steps = _sc.counter("train_steps",
                                          "fused train steps dispatched")
        self._m_images = _sc.counter("images", "training images consumed")
        self._m_tokens = _sc.counter(
            "tokens", "training tokens consumed (samples that are rows of "
            "integer ids)")
        #: ids a sample holds (0: samples are not rows of ids), read off
        #: the loader's array when a run starts
        self._tokens_per_sample = 0
        #: registry gauges of what the counting units counted
        #: (``_book_counted``), made when the first count arrives
        self._counter_gauges = {}
        self._m_resident_prepares = _sc.counter(
            "resident_prepares", "resident sets laid out for their gather "
            "(a whole-set pass each: once per set, never per dispatch)")
        self._m_tails = {
            "tails_in_scan": _sc.counter(
                "tails_in_scan", "epoch tails dispatched as the last step "
                "of the epoch's last scan segment"),
            "tails_alone": _sc.counter(
                "tails_alone", "epoch tails evaluated, ruled on and "
                "updated alone (a stop, or no validation set)")}
        self._m_step_seconds = _sc.histogram(
            "step_seconds", "per-step wall time (pipelined intervals)",
            size=4096)
        #: compute dtype (activations + gradients; master weights stay
        #: f32): ``root.common.engine.compute_dtype`` is the canonical
        #: knob ("float32" | "bf16" | "bfloat16"; None, what a fixture
        #: that saved an unset knob restores, reads as unset)
        cd = root.common.engine.get("compute_dtype", None) or "float32"
        cd = {"bf16": "bfloat16"}.get(str(cd), str(cd))
        if cd not in ("float32", "bfloat16"):
            raise ValueError(
                f"root.common.engine.compute_dtype={cd!r}: must be "
                "'float32' or 'bf16'/'bfloat16'")
        self.compute_dtype = (np.dtype("float32") if cd == "float32"
                              else "bfloat16")
        #: the per-step compute_dtype label on /metrics (ISSUE 7
        #: satellite): a labeled gauge, so the TPU session's dashboards
        #: can tell WHICH precision a run's step timings belong to
        #: without a profiler
        _sc.gauge("compute_dtype", "active compute dtype (value always 1;"
                  " read the dtype label)", dtype=cd).set(1)
        #: trace-time tick per compiled fused executable (the serving
        #: layer's zero-recompile method, now on the training path):
        #: Python runs a jitted wrapper's body only when jax (re)traces,
        #: so ``compiles`` == executable-cache entries, cross-checkable
        #: against ``jit_cache_sizes()``
        self._m_compiles = _sc.counter(
            "compiles", "traces of the fused step/scan executables == "
            "jit cache entries")
        #: OPT-IN bf16 MASTER weights (root.common.engine.master_dtype =
        #: "bfloat16", fused path only): params are STORED bf16 — the
        #: per-step read+write of the full param set halves (AlexNet fc:
        #: the dominant non-MXU traffic after the r4 bf16 velocities) —
        #: while the update arithmetic stays f32 (cast up, update, cast
        #: back).  This CHANGES convergence semantics (weight rounding):
        #: an undecided lever (ROADMAP.md), never a cell's default or
        #: the anchors'.
        md = str(root.common.engine.get("master_dtype", "float32"))
        if md not in ("float32", "bfloat16"):
            raise ValueError(
                f"root.common.engine.master_dtype={md!r}: must be "
                "'float32' or 'bfloat16'")
        self._master_dtype = None if md == "float32" else "bfloat16"
        #: u8 storage decodes to ``u8*scale + shift`` in-graph
        #: (loader/streaming.py; plain f32 loaders never hit the decode)
        self._decode_params = (np.float32(getattr(self.loader, "scale", 1.0)),
                               np.float32(getattr(self.loader, "shift", 0.0)))

    @property
    def staging(self) -> bool:
        """True when the dataset is host-side and every dispatch's samples
        must be staged through host_gather + device_put (streaming regime 3
        — loader/streaming.py).  Resolved lazily: ``device_resident`` is
        decided by the loader's initialize."""
        ldr = self.loader
        return (bool(getattr(ldr, "streaming", False))
                and not ldr.device_resident)

    # -- state extraction ------------------------------------------------------

    def _op_value(self, arr):
        """An Array's value for the fused step's operands.  Multi-
        controller meshes take the HOST buffer: global_put re-distributes
        it shard-by-shard, and detouring through ``devmem`` would pay a
        full extra H2D+D2H round trip on local device 0 first."""
        if self.mesh is not None:
            import jax

            if jax.process_count() > 1:
                if arr.cross_host_sharded:
                    # devmem already spans hosts (e.g. restore_sharded
                    # placed it) — hand the global array straight through.
                    # But only while it is CURRENT: a host write since
                    # (map_write/map_invalidate) means the sharded buffer
                    # is stale, and host collection cannot reshard a
                    # cross-host Array implicitly — silently returning it
                    # would train on outdated state.
                    if arr.host_dirty:
                        raise RuntimeError(
                            "cross-host-sharded Array has a NEWER host "
                            "copy than its device shards; re-distribute "
                            "it explicitly (global_put / restore_sharded) "
                            "before extracting fused-step state")
                    # A DELETED buffer (donated into a prior step) must
                    # not fall through here: it would surface later as a
                    # confusing "Array has been deleted" inside jit
                    # (ADVICE r4).
                    if arr._devmem.is_deleted():
                        raise RuntimeError(
                            "param/velocity device buffer was donated "
                            "away; refresh the unit Arrays (writeback) "
                            "before re-extracting state")
                    return arr._devmem
                return arr.map_read()
        return arr.devmem

    def _cast_master(self, v):
        """Storage-dtype cast for a param leaf (jax array or host numpy)
        under the bf16-master option; identity otherwise."""
        md = self._master_dtype
        if md is None or str(v.dtype) == md:
            return v
        import ml_dtypes

        if isinstance(v, np.ndarray):
            return v.astype(ml_dtypes.bfloat16)
        return v.astype(md)

    def extract_params(self) -> Dict[str, Dict[str, object]]:
        return {f.name: {k: self._cast_master(self._op_value(a))
                         for k, a in f.params().items()}
                for f in self.forwards if f.has_weights}

    def extract_velocities(self):
        out = {}
        for f in self.forwards:
            gd = self.gd_of.get(f.name)
            if gd is not None and f.has_weights:
                out[f.name] = {k: self._op_value(a)
                               for k, a in gd._velocities.items()}
        return out

    def hypers(self):
        out = {}
        for f in self.forwards:
            gd = self.gd_of.get(f.name)
            if gd is not None and f.has_weights:
                # the GD unit's own row: the eight of ``sgd_update``, or
                # what the unit's ``apply_update`` takes
                out[f.name] = gd._hypers()
        return out

    def tiled_hypers(self, k: int):
        """Per-step hypers rows for a k-step scan with CONSTANT hypers —
        the one home for the scan's hypers-xs layout (callers without an
        LR schedule: dryrun, hypers_rows' fast path)."""
        return {name: np.tile(np.asarray(t, np.float32), (k, 1))
                for name, t in self.hypers().items()}

    def restore_sharded(self, path: str):
        """Cross-topology checkpoint resume (SURVEY §5 checkpoint row):
        load an orbax checkpoint saved under ANY mesh topology and deliver
        every param/velocity leaf already placed in THIS trainer's
        shardings — orbax/tensorstore reads each target shard directly, no
        host-gather round-trip.  Loader/decision/prng metadata is applied
        like the standard restore.  Returns the meta dict.

        Dtype: the checkpoint stores each leaf in whatever precision was
        configured WHEN IT WAS SAVED (``state_dtype`` may differ between
        the saving and resuming runs).  The restore template asks orbax
        for the leaf in the dtype of the LIVE Array — i.e. the currently
        configured precision — and any residual mismatch is cast
        explicitly below rather than left to tensorstore's implicit
        behavior (ADVICE r4)."""
        import jax
        from jax.sharding import SingleDeviceSharding

        from znicz_tpu import snapshotter as snap_mod

        def sds(name, k, shape, dtype):
            probe = jax.ShapeDtypeStruct(tuple(shape), dtype)
            sharding = (self.param_sharding(name, k, probe)
                        if self.mesh is not None
                        else SingleDeviceSharding(jax.local_devices()[0]))
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=sharding)

        units = {f.name: {k: sds(f.name, k, a.shape, a.dtype)
                          for k, a in f.params().items()}
                 for f in self.forwards if f.has_weights}
        vels = {gd.name: {k: sds(gd.forward.name, k, a.shape, a.dtype)
                          for k, a in gd._velocities.items()}
                for gd in self.workflow.gds}
        arrays = snap_mod.load_orbax_arrays(
            path, {"units": units, "velocities": vels})

        def adopt(leaf, a):
            a.devmem = (leaf if leaf.dtype == a.dtype
                        else leaf.astype(a.dtype))

        for f in self.forwards:
            if not f.has_weights:
                continue
            for k, a in f.params().items():
                adopt(arrays["units"][f.name][k], a)
            gd = self.gd_of.get(f.name)
            if gd is not None:
                for k, a in gd._velocities.items():
                    adopt(arrays["velocities"][gd.name][k], a)
        meta = snap_mod.load_orbax_meta(path)
        snap_mod.restore(self.workflow,
                         {**meta, "units": {}, "velocities": {}})
        return meta

    def snapshot_from_trees(self, params, velocities) -> Dict:
        """A snapshot dict built DIRECTLY from the fused device trees —
        no unit-Array writeback, no host round-trip on the training
        thread.  Param/velocity leaves stay device arrays; the
        snapshotter's async worker pulls them while the next epoch
        computes (VERDICT r4 item 4).  Velocities are saved in their live
        ``state_dtype`` (bf16 state -> bf16 checkpoint, half the bytes)."""
        from znicz_tpu import snapshotter as snap_mod

        snap = snap_mod.collect_meta(self.workflow)
        snap["config"] = root.to_dict()
        for f in self.forwards:
            if not f.has_weights:
                continue
            snap["units"][f.name] = dict(params[f.name])
            gd = self.gd_of.get(f.name)
            if gd is not None:
                snap["velocities"][gd.name] = dict(velocities[f.name])
        return snap

    def _async_snapshot_enabled(self, snap) -> bool:
        """Async (non-stalling) snapshots apply to host-format saves when
        ``root.common.engine.async_snapshot`` (default True) is on; orbax
        saves are multi-process collectives and stay synchronous."""
        return (snap is not None and snap.format != "orbax"
                and bool(root.common.engine.get("async_snapshot", True)))

    @staticmethod
    def _copy_fits(*trees) -> bool:
        """Whether the device has room for a second copy of ``trees``
        beside the first — what an async save takes (the copy is what
        makes it safe against donation).  A state that fills most of the
        chip (two moments of a large model) is saved synchronously
        instead: pulled leaf by leaf, no second copy.  True where the
        device does not say (the CPU)."""
        import jax

        need = sum(int(leaf.nbytes)
                   for leaf in jax.tree_util.tree_leaves(trees))
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            limit = stats.get("bytes_limit")
            if limit and stats.get("bytes_in_use", 0) + need > 0.95 * limit:
                return False
        return True

    def _drain_snapshots(self, suppress: bool) -> None:
        """Block until queued async saves are durably written.  With
        ``suppress`` (an exception already in flight) a writer error is
        swallowed rather than masking the real failure."""
        snap = getattr(self.workflow, "snapshotter", None)
        if snap is None:
            return
        try:
            snap.flush_async()
        except Exception:
            if not suppress:
                raise

    def writeback(self, params, velocities) -> None:
        """Push fused-step results back into the unit Arrays (snapshotter /
        plotters / unit-mode interop see the same state)."""
        for f in self.forwards:
            if f.has_weights:
                for k, a in f.params().items():
                    a.devmem = params[f.name][k]
                gd = self.gd_of.get(f.name)
                if gd is not None:
                    for k, a in gd._velocities.items():
                        a.devmem = velocities[f.name][k]

    # -- the pure step ---------------------------------------------------------

    def forward_pass(self, params, x, key, train: bool, cast=None,
                     counted=None, loss_of=None, hidden=False, moved=None):
        """Compose the units' pure applies; returns the last unit's output
        (LOGITS for a softmax last layer — loss and probs both derive from
        them, matching the evaluator's math).  ``cast`` re-casts activations
        between layers in mixed precision (matmul/conv accumulate f32 via
        preferred_element_type, outputs drop back to bf16).

        With ``root.common.engine.fused_elementwise`` on, every matched
        conv1/conv2-style block (Conv+bias+StrictRELU -> LRN -> exactly-
        tiling MaxPooling) runs as the raw conv plus ONE single-pass
        Pallas kernel whose custom vjp is the fused backward — the graph
        the GradientDescent* chain would otherwise differentiate op by op
        (pallas_fused_block; plan computed per trace, shapes unchanged).

        With ``root.common.engine.fused_tail`` on (ISSUE 7), the REST of
        the AlexNet shape fuses too: conv3-5-style bias+StrictRELU as one
        Pallas pass each way (``fused_bias_relu``), and the FC layers'
        bias+ReLU+dropout epilogue as one custom-vjp stage whose backward
        recomputes the masks from (input, bias, key) instead of loading
        them from HBM (``fused_fc_epilogue`` — the dropout key is the
        absorbed unit's own ``fold_in(key, i)`` draw, so masks are
        bit-identical to the unit path's).

        What is observed from the units.  A unit with ``apply_carried``
        returns ``(y, carry, counters)``: the counters go into the dict
        ``counted`` under the unit's name where the caller hands one in
        (``loss_and_metrics`` does, and returns them with the step's
        metrics); ``carry`` is what the unit hands the next one BESIDE its
        output (``None`` until a unit makes one; units without
        ``apply_carried`` neither see nor drop it).  An entry ``moves`` of
        the counters — ``{tensor key: step}``, what a train step adds to
        tensors of the unit that the loss has no gradient for — goes into
        the dict ``moved`` instead (``_update_core`` hands one in and
        applies it; an evaluation hands none, and the steps are never
        computed).  A unit that ``borrowed`` a tensor gets the owning
        unit's under its own key (a tied head).  A last unit with
        ``apply_loss`` takes the loss itself where ``loss_of`` —
        ``(targets, batch size)`` — is handed in: the result is then
        ``(loss sum, errors)``, not logits.  ``hidden`` stops before the
        last unit and returns what it would be given.  In training a unit
        is rematerialised (``jax.checkpoint`` around that unit alone)
        where it asks for that (``remat = True`` on the unit) — per unit,
        so that what is live in the backward pass is one unit's
        activations, not the network's.  The backward pass keeps the
        unit's input and the tensors the unit names (``remat_keeps``: names
        given with ``jax.ad_checkpoint.checkpoint_name``, a decoder layer's
        attention output and log-sum-exp; ``save_only_these_names``), and
        the unit is told what was kept (``remat_kept``); a unit that names
        nothing keeps its input alone."""
        import jax

        from znicz_tpu.ops.linear import linear
        from znicz_tpu.pallas_fused_block import (fused_bias_relu,
                                                  fused_block,
                                                  fused_fc_epilogue,
                                                  plan_fused_blocks,
                                                  plan_fused_tail)

        plan = plan_fused_blocks(self.forwards)
        tail_plan = plan_fused_tail(self.forwards, plan)
        h, carry = x, None
        last = self.forwards[-1]
        i = 0
        while i < len(self.forwards) - bool(hidden):
            f = self.forwards[i]
            p = params.get(f.name, {})
            for k, (owner, theirs) in getattr(f, "borrowed", {}).items():
                p = dict(p, **{k: params[owner][theirs]})
            blk = plan.get(i)
            tl = tail_plan.get(i) if blk is None else None
            span = blk.span if blk is not None else (
                tl.span if tl is not None else 1)

            def unit(p, h, carry, i=i, f=f, blk=blk, tl=tl):
                counters = {}
                if blk is not None:
                    h = f.apply_linear(p, h)
                    # dropout/stochpool never sit inside a fused block,
                    # so later units keep their own fold_in(key, i)
                    # indices
                    h = fused_block(h, p["bias"], blk.n, blk.alpha,
                                    blk.beta, blk.k, blk.pool)
                elif tl is not None:
                    if tl.kind == "conv_bias_relu":
                        h = f.apply_linear(p, h)
                        h = fused_bias_relu(h, p["bias"])
                    elif tl.kind == "seq_epilogue":
                        # position-wise FFN (ISSUE 15): the raw per-token
                        # matmul plus the SAME fused bias+ReLU custom-vjp
                        # epilogue fc6/fc7 ride (no dropout absorbed; the
                        # backward recomputes the gate from (y, bias))
                        from znicz_tpu.ops.linear import seq_linear

                        y = seq_linear(
                            h, p["weights"],
                            weights_transposed=f.weights_transposed)
                        h = fused_fc_epilogue(y, p["bias"], None, 0.0,
                                              False)
                    else:                           # fc_epilogue
                        y = linear(h, p["weights"],
                                   weights_transposed=f.weights_transposed)
                        masked = train and tl.dropout_index >= 0
                        k = (jax.random.fold_in(key, tl.dropout_index)
                             if masked else None)
                        y = fused_fc_epilogue(y, p["bias"], k, tl.ratio,
                                              masked)
                        h = y.reshape((x.shape[0],)
                                      + f.output_sample_shape)
                elif isinstance(f, self._dropout_cls):
                    if train:
                        k = jax.random.fold_in(key, i)
                        m = f.make_mask(k, h.shape, f.dropout_ratio)
                        h = h * m
                    # eval: identity
                elif isinstance(f, self._stochpool_cls):
                    win = f.windows(h)
                    if train:
                        k = jax.random.fold_in(key, i)
                        h, _ = f._select_stochastic(win, k)
                    else:
                        h, _ = f._select_expected(win)
                elif f is last and isinstance(f, self._softmax_cls):
                    h = linear(h, p["weights"], p.get("bias"),
                               weights_transposed=f.weights_transposed)
                    h = h.reshape((x.shape[0],) + f.output_sample_shape)
                elif f is last and isinstance(f, self._seq_softmax_cls):
                    # per-position logits (ISSUE 15): the softmax is
                    # folded into the loss head exactly like the
                    # All2AllSoftmax path
                    from znicz_tpu.ops.linear import seq_linear

                    h = seq_linear(h, p["weights"], p.get("bias"),
                                   weights_transposed=f.weights_transposed)
                elif f is last and loss_of is not None \
                        and hasattr(f, "apply_loss"):
                    h = f.apply_loss(p, h, *loss_of)
                elif f is last and hasattr(f, "apply_logits"):
                    h = f.apply_logits(p, h)
                elif hasattr(f, "apply_carried"):
                    h, carry, counters = f.apply_carried(p, h, carry)
                else:
                    h = f.apply(p, h)
                return h, carry, counters

            if train and getattr(f, "remat", False):
                unit = rematerialised(f, unit)
            # the device trace speaks the model's names: one scope per
            # forward unit (a fused block or tail span takes its first
            # unit's); jax names the backward ``transpose(jvp(<unit>))``
            with jax.named_scope(f.name):
                if cast is not None:
                    h = cast(h)
                h, carry, counters = unit(p, h, carry)
            moves = counters.pop("moves", None)
            if moved is not None and moves:
                moved[f.name] = moves
            if counted is not None and counters:
                counted[f.name] = counters
            i += span
        return h

    def loss_and_metrics(self, params, data, target, batch_size, key,
                         train: bool, moved=None):
        """``(loss, metrics)`` of one minibatch: ``metrics`` is ``(loss,
        n_err, confusion)`` and, where units counted something in this
        pass (``apply_carried``), a fourth entry ``{unit: counters}`` —
        it leaves the device with the loss, in the same pull.  ``moved``
        is ``forward_pass``'s."""
        import jax.numpy as jnp

        import jax

        counted = {}
        # a head that takes the loss itself (``forward_pass``)
        own_loss = self.loss_kind == "softmax" and hasattr(
            self.forwards[-1], "apply_loss")
        loss_of = (target, batch_size) if own_loss else None
        if self.compute_dtype == np.dtype("float32"):
            cast = None
            cparams = params
            out = self.forward_pass(cparams, data, key, train,
                                    counted=counted, loss_of=loss_of,
                                    moved=moved)
        else:
            def cast(t):
                return t.astype("bfloat16") if t.dtype == jnp.float32 else t

            # each layer's weights drop to the compute dtype under that
            # layer's name, the minibatch under ``input`` (sorted like
            # tree_map flattens a dict: the traced program is the same)
            cparams = {}
            for name in sorted(params):
                with jax.named_scope(name):
                    cparams[name] = jax.tree_util.tree_map(cast,
                                                           params[name])
            with jax.named_scope("input"):
                data = cast(data)
            out = self.forward_pass(cparams, data, key, train, cast=cast,
                                    counted=counted, loss_of=loss_of,
                                    moved=moved)
        with jax.named_scope("loss"):
            if own_loss:                # the head's: (loss sum, errors)
                if self.compute_confusion:
                    raise FusedUnsupportedError(
                        f"{self.forwards[-1].name} takes the loss itself "
                        f"and hands on no logits: no confusion matrix")
                loss = out[0] / jnp.maximum(batch_size * target.shape[1], 1)
                metrics = (loss, out[1].astype(jnp.int32),
                           jnp.zeros((1, 1), jnp.int32))
            else:
                loss, metrics = self._loss_head(out, target, batch_size)
        return loss, metrics + ((counted,) if counted else ())

    def _loss_head(self, out, target, batch_size):
        """The evaluator's math on the last unit's output (traced under
        the ``loss`` scope): ``(loss, (loss, n_err, confusion))``."""
        import jax
        import jax.numpy as jnp

        out = out.astype("float32")
        n = out.shape[0]
        valid = (jnp.arange(n) < batch_size)
        denom = jnp.maximum(batch_size, 1)
        if self.loss_kind == "softmax":
            logits = out
            labels = target
            if logits.ndim == 3:
                # sequence head (ISSUE 15): every token of every valid
                # row is one classification — flatten tokens into the
                # batch axis and keep the identical per-class math
                # (EvaluatorSeqSoftmax mirrors this; they must not
                # drift).  denom scales to tokens so the reported loss
                # stays a per-token mean.
                t = logits.shape[1]
                logits = logits.reshape(n * t, logits.shape[-1])
                labels = labels.reshape(n * t).astype(jnp.int32)
                valid = jnp.repeat(valid, t)
                denom = jnp.maximum(batch_size * t, 1)
            from znicz_tpu.pallas_fused_block import (fused_softmax_xent,
                                                      fused_tail_enabled)

            if fused_tail_enabled():
                # ISSUE 7: loss + logits-cotangent as ONE custom-vjp
                # epilogue (same formula; backward re-reads logits
                # instead of consuming saved softmax/logsumexp residuals)
                loss = fused_softmax_xent(logits, labels, valid, denom)
            else:
                logz = jax.nn.logsumexp(logits, axis=-1)
                ll = jnp.take_along_axis(logits, labels[:, None],
                                         axis=-1)[:, 0]
                loss = jnp.sum(jnp.where(valid, logz - ll, 0.0)) / denom
            pred = jnp.argmax(logits, axis=-1)
            n_err = jnp.sum((pred != labels) & valid)
            if self.compute_confusion:
                n_classes = logits.shape[-1]
                conf = jnp.zeros((n_classes, n_classes), jnp.int32).at[
                    pred, labels].add(valid.astype(jnp.int32))
            else:
                conf = jnp.zeros((1, 1), jnp.int32)
            return loss, (loss, n_err, conf)
        else:
            y = out.reshape(n, -1)
            t = target.reshape(n, -1)
            diff = (y - t) * valid[:, None]
            loss = 0.5 * jnp.sum(jnp.square(diff)) / denom
            return loss, (loss, jnp.int32(0), jnp.zeros((1, 1), jnp.int32))

    #: FC layers at least this wide get tensor-parallel row sharding when
    #: the mesh has a ``model`` axis (AlexNet's 4096-wide fc6/fc7)
    tp_threshold = 1024

    def param_sharding(self, name, k, arr):
        """Per-param placement: wide (out, in) FC weights shard their output
        rows over the ``model`` axis (and the matching bias over ``model``);
        everything else replicates.  The rule itself lives in the shared
        placement home (``parallel.mesh.param_sharding``); this method keeps
        the historical (name, k, arr) signature for serving/restore."""
        from znicz_tpu.parallel.mesh import param_sharding

        return param_sharding(self.mesh, arr, self.tp_threshold)

    @property
    def mesh_shape(self):
        """``{"data": dp, "model": mp}`` (None single-device) — the
        heartbeat form, piggybacked on slave registration."""
        from znicz_tpu.parallel.mesh import mesh_shape_dict

        return mesh_shape_dict(self.mesh)

    def place_state(self, tree):
        """Distribute a params/velocities tree onto the mesh per the
        shared ``param_sharding`` rule; identity when single-device (the
        tree is already placed by extraction)."""
        if self.mesh is None:
            return tree
        from znicz_tpu.parallel.mesh import place_tree

        return place_tree(self.mesh, tree, self.tp_threshold)

    def _state_shardings(self):
        """(params tree shardings, velocities tree shardings, replicated)
        for the live mesh (three ``None``: nothing declared, off a mesh) —
        the explicit ``in_shardings``/``out_shardings`` every mesh-jitted
        step/scan declares.  Params replicate or
        column-shard per ``param_sharding``; with the batch split over
        ``data``, jax.grad's gradients demand replication, so GSPMD
        inserts the ``lax.psum`` over the ``data`` axis INSIDE the
        executable — the intra-slice (ICI) tier of the two-tier
        reduction.  The host-side wire-v3 delta tier never sees it."""
        if self.mesh is None:
            return None, None, None
        from znicz_tpu.parallel.mesh import replicated, tree_shardings

        psh = tree_shardings(
            self.mesh,
            {f.name: dict(f.params())
             for f in self.forwards if f.has_weights},
            self.tp_threshold)
        vsh = tree_shardings(
            self.mesh,
            {f.name: dict(self.gd_of[f.name]._velocities)
             for f in self.forwards
             if f.has_weights and self.gd_of.get(f.name) is not None},
            self.tp_threshold)
        return psh, vsh, replicated(self.mesh)

    def _jit_shardings(self, in_specs, out_specs):
        """jax.jit kwargs: explicit shardings on a mesh, empty (the
        byte-identical historical jit call) single-device."""
        if self.mesh is None:
            return {}
        return {"in_shardings": in_specs, "out_shardings": out_specs}

    def _resident(self, raw, place=None, keep=False):
        """``(twin, plan)`` for the loader's resident set ``raw``: the
        array every program gathers from, and how ``_Rows`` reads it
        (None: like ``raw``).

        The device's default layout for a set of images puts the SAMPLE
        axis minor-most (v5e: ``f32[N,227,227,3]{0,2,3,1}`` — N fills the
        lanes), which no gather of rows can read in place, so XLA rewrote
        the whole set sample-major in front of every program's gather and
        fused the cast to the compute dtype into that pass: 7.8 GB moved
        per dispatch for 40 MB of use a step (PERF.md, PR 26).  The twin
        is that pass's result, made ONCE per array: sample axis major-
        most, the other axes in the order the compiler chooses for the
        gather (``_gather_layout``), already in the dtype
        ``loss_and_metrics`` casts to (f32 -> bf16 under bf16 compute; u8
        stays u8 and decodes in-graph) — a cast commutes with a gather
        bit for bit.

        The order is carried by the twin's SHAPE, not by a declared
        ``jax.experimental.layout.Format``: its axes are transposed into
        that order and the minor ones padded to whole tiles, which makes
        the device's default layout for the new shape the wanted one
        (checked; a device that answers otherwise gets no twin).  A
        program with a declared entry layout ran, but the chip's runtime
        refused its executable once it came back from jax's persistent
        compile cache (it expected the default layout's size; PERF.md,
        PR 26).  A set that is sample-major in its final dtype already
        (every set on the CPU in f32 compute) is its own twin: no copy,
        no second buffer.

        A run makes its set's twin in set-up (``_device_state``,
        ``keep``), finds it again by the IDENTITY of ``raw`` or of the
        twin itself (jax arrays are immutable), and lets it go when it
        ends: a trainer that is not running pins no second copy of the
        set (the benchmark's float32 reference needs those gigabytes
        after the run).  ``raw`` itself is left alone (the unit engine
        reads it).  A call between runs (a caller of ``make_train_step``
        with the loader's array) gets a twin for that call.  ``place``
        puts ``raw`` where the programs expect it (``global_put`` on a
        mesh; the twin is then written by each chip for itself).  A
        ``jax.ShapeDtypeStruct`` (``lower`` for a described chip) maps to
        the twin's shape; a host array goes as it is."""
        import jax
        import jax.numpy as jnp

        for known, twin, plan in self._twins:
            if raw is known or raw is twin:
                return twin, plan
        placed = raw if place is None else place(raw)
        is_spec = isinstance(placed, jax.ShapeDtypeStruct)
        if not is_spec and not isinstance(placed, jax.Array):
            return placed, None
        shape, sharding = tuple(placed.shape), placed.sharding
        dtype = placed.dtype
        if self.compute_dtype != np.dtype("float32") \
                and dtype == np.float32:
            dtype = jnp.bfloat16            # as loss_and_metrics casts
        rows_first = tuple(range(len(shape)))
        twin, plan = placed, None
        # the order the device holds ``raw`` in: an array's own layout, a
        # described device's default
        held = None if is_spec else placed.format.layout
        if held is not None:
            held = tuple(held.major_to_minor)
        elif sharding is not None:
            held = _default_order(sharding, shape, placed.dtype)
        if dtype != placed.dtype or (held and held[0] != 0):
            layout = self._gather_layout(shape, dtype, sharding)
            order = (0,) + tuple(a for a in layout.major_to_minor if a)
            # whole tiles on the minor axes (the outer level: T(8,128))
            outer = tuple((layout.tiling or ((),))[0])
            tile = (1,) * (len(shape) - len(outer)) + outer
            padded = tuple(
                shape[a] if a == 0 else -(-shape[a] // t) * t
                for a, t in zip(order, tile))
            if sharding is None or _default_order(
                    sharding, padded, dtype) == rows_first:
                if order != rows_first or padded != shape:
                    plan = (order, shape[1:])
                if is_spec:
                    twin = jax.ShapeDtypeStruct(padded, dtype,
                                                sharding=sharding)
                else:
                    pads = [(0, p - shape[a]) for a, p in zip(order, padded)]
                    with self._tracer.span("train", "resident_prepare",
                                           bytes=int(placed.nbytes)):
                        twin = jax.block_until_ready(jax.jit(
                            lambda a: jnp.pad(jnp.transpose(
                                a.astype(dtype), order), pads),
                            out_shardings=sharding)(placed))
                    self.stats["resident_prepares"] += 1
                    self._m_resident_prepares.inc()
        if keep:
            self._twins.append(
                (raw if isinstance(raw, jax.Array) else placed, twin, plan))
        return twin, plan

    def _gather_layout(self, shape, dtype, sharding):
        """The layout the compiler itself chooses (``Layout.AUTO``) for
        the operand of the programs' gather of one minibatch — a program
        of one instruction, compiled for the devices of ``sharding`` and
        never run.  (For AlexNet's set the whole train scan, compiled
        with ``AUTO`` on that operand, chooses the same ``major_to_minor``
        (0, 3, 1, 2): the rows' only consumers are the gather and the
        first unit.)"""
        import jax
        from jax.experimental.layout import Format, Layout

        rows = jax.ShapeDtypeStruct(
            (int(self.loader.max_minibatch_size),), np.int32,
            sharding=sharding)
        return jax.jit(
            lambda dataset, idx: jax.numpy.take(dataset, idx, axis=0),
            in_shardings=(Format(Layout.AUTO, sharding), sharding)).lower(
                jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),
                rows).compile().input_formats[0][0].layout

    def _decode(self, data):
        """Storage decode IN-GRAPH: u8 data (HBM u8-residency or a
        host-staged u8 segment — loader/streaming.py) decodes
        ``u8*scale + shift``, fused by XLA into whatever produced it, so
        HBM/link traffic stays 1 byte/value and the f32 tensor only ever
        exists inside the step."""
        import jax
        import jax.numpy as jnp

        if data.dtype == jnp.uint8:
            scale, shift = self._decode_params
            with jax.named_scope("input"):
                data = data.astype(jnp.float32) * scale + shift
        return data

    def _gather(self, dataset, targets, idx):
        """Rows ``idx`` of the resident set (its prepared twin:
        ``_resident``) and of its targets, in the twin's dtype and the
        targets' storage dtype.  Everything between the resident set and the
        first unit's operand — this gather, ``_decode``, the cast to the
        compute dtype — is traced under the scope ``input``."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("input"):
            return (dataset.take(idx) if isinstance(dataset, _Rows)
                    else jnp.take(dataset, idx, axis=0),
                    jnp.take(targets, idx, axis=0))

    def _gather_decode(self, dataset, targets, idx):
        data, tgt = self._gather(dataset, targets, idx)
        return self._decode(data), tgt

    def _step_core(self, params, velocities, hypers, dataset, targets, idx,
                   batch_size, key):
        """One pure train step (traced): gather -> fwd -> grads -> per-layer
        sgd update.  Shared by the single-step jit and the scan chunk.
        The gather hands RAW storage-dtype rows to ``_update_core``, which
        owns the decode (single decode point on the update path)."""
        return self._update_core(params, velocities, hypers,
                                 *self._gather(dataset, targets, idx),
                                 batch_size, key)

    def _update_core(self, params, velocities, hypers, data, tgt,
                     batch_size, key):
        """The post-gather step math: fwd -> grads -> per-layer sgd
        update, on an already-materialized minibatch (the gather path and
        the staged-direct path share it)."""
        import jax

        data = self._decode(data)
        if self.mesh is not None:
            # the minibatch is what shards over the data axis (XLA then
            # keeps the whole fwd/bwd batch-sharded and psums the grads
            # over ICI); for staged-direct inputs already sharded this
            # way the constraint is a no-op
            from znicz_tpu.parallel.mesh import data_sharding

            shard = data_sharding(self.mesh)
            with jax.named_scope("input"):
                data = jax.lax.with_sharding_constraint(data, shard)
                tgt = jax.lax.with_sharding_constraint(tgt, shard)

        def lf(p):
            moved = {}
            loss, metrics = self.loss_and_metrics(
                p, data, tgt, batch_size, key, train=True, moved=moved)
            return loss, (metrics, moved)

        # rematerialisation is per unit, inside ``forward_pass``
        grads, (metrics, moved) = jax.grad(lf, has_aux=True)(params)
        new_p, new_v = {}, {}
        for name, layer_p in params.items():
            rule = getattr(self.gd_of[name], "apply_update", None)
            if rule is not None:
                # the GD unit's own rule (AdamW): its tensors, moments
                # and step count in one call
                with jax.named_scope(f"update/{name}"):
                    new_p[name], new_v[name] = rule(
                        layer_p, grads[name], velocities[name],
                        hypers[name])
                continue
            lr, lrb, wd, wdb, l1l2, mom, momb, clip = hypers[name]
            new_p[name], new_v[name] = {}, {}
            for k, w in layer_p.items():
                with jax.named_scope(f"update/{name}"):
                    g = grads[name][k].astype("float32")
                    is_bias = (k == "bias")
                    # bf16-master: storage bf16, update arithmetic f32
                    # (the cast pair fuses into the update; traffic is
                    # what the storage dtype says)
                    w_in = (w if self._master_dtype is None
                            else w.astype("float32"))
                    p_new, v_new = sgd_update(
                        w_in, g, velocities[name][k],
                        lr=(lrb if is_bias else lr),
                        weights_decay=(wdb if is_bias else wd),
                        l1_vs_l2=l1l2,
                        momentum=(momb if is_bias else mom), clip=clip)
                    if self._master_dtype is not None:
                        p_new = p_new.astype(self._master_dtype)
                new_p[name][k], new_v[name][k] = p_new, v_new
        # what the units themselves move (``forward_pass``): the gradient
        # of these tensors is zero and the rule above left them alone
        for name, moves in moved.items():
            with jax.named_scope(f"update/{name}"):
                for k, step in moves.items():
                    new_p[name][k] = new_p[name][k] + step.astype(
                        new_p[name][k].dtype)
        return new_p, new_v, metrics

    def make_train_step(self):
        """The step takes ``hypers`` as a traced argument so per-epoch lr
        adjustment (LearningRateAdjust) never recompiles.  On a mesh the
        jit declares explicit shardings (``_state_shardings``): params
        pinned to their placements, batch operands replicated (the
        in-step gather + constraint shard the minibatch over ``data``).
        ``dataset`` is the loader's resident array or its twin: the
        program gathers from the twin (``_ResidentJit``)."""
        compiles = self._m_compiles
        psh, vsh, repl = self._state_shardings()

        def step(params, velocities, hypers, dataset, targets, idx,
                 batch_size, key):
            compiles.inc()              # trace-time tick (one per compile)
            return self._step_core(params, velocities, hypers, dataset,
                                   targets, idx, batch_size, key)

        return _ResidentJit(
            self, step, 3, (psh, vsh, repl, repl, repl, repl, repl, repl),
            (psh, vsh, repl), donate_argnums=(0, 1))

    def jit_cache_sizes(self) -> Dict[str, int]:
        """jax's own executable-cache entry counts for the live jitted
        step/scan functions (the pjit cache behind ``_cache_size``; absent
        entries mean the jax version does not expose it).  After warmup
        the SUM equals ``compiles`` and must stay put — the training-path
        zero-recompile proof (same method as serving's ModelRunner)."""
        out: Dict[str, int] = {}
        for name in ("_train_step", "_train_scan", "_eval_step",
                     "_eval_scan"):
            fn = getattr(self, name, None)
            if fn is None:
                continue
            try:
                out[name] = int(fn._cache_size())
            except Exception:           # pragma: no cover - jax-version dep
                pass
        return out

    def _n_confusion(self) -> int:
        return (self.forwards[-1].output_samples_number
                if self.loss_kind == "softmax" and self.compute_confusion
                else 1)

    def _train_body(self, base_key, unpack):
        """The ONE home of the scanned train-step body — the gather
        variant (resident datasets, xs carry indices) and the staged-
        direct variant (xs carry the minibatches themselves) share it via
        ``unpack(xs) -> (data, tgt, bs, step, hypers)``: carry = (params,
        velocities, confusion sum).  Per-step keys are ``fold_in(base,
        step)`` IN-GRAPH — identical to the sequential path's draws
        (eager key construction costs several dispatches each).
        Confusion SUMS on device in the carry:
        stacking K (C,C) matrices and pulling them per step was the
        real-training bottleneck on slow links (28MB/segment for the
        1000-class head); the Decision only accumulates."""
        import jax

        def body(carry, xs):
            p, v, conf_acc = carry
            data, tgt, bs, step, hypers = unpack(xs)
            key = jax.random.fold_in(base_key, step)
            p, v, (loss, n_err, conf, *counted) = self._update_core(
                p, v, hypers, data, tgt, bs, key)
            return (p, v, conf_acc + conf), (loss, n_err, *counted)

        return body

    def _train_scan_body(self, dataset, targets, base_key):
        """Gather variant of ``_train_body``: xs = (idx, batch_size,
        step_number, hypers row), rows gathered from the resident
        dataset (used by the segmented chunks and the deep epoch fn)."""
        def unpack(xs):
            idx, bs, step, hypers = xs
            return (*self._gather(dataset, targets, idx), bs, step, hypers)

        return self._train_body(base_key, unpack)

    def _eval_body(self, params, unpack):
        """The ONE home of the scanned eval body (params frozen — a pure
        map): carry = confusion sum; ``unpack(xs) -> (decoded data, tgt,
        bs)``."""

        def body(conf_acc, xs):
            data, tgt, bs = unpack(xs)
            _, (loss, n_err, conf, *counted) = self.loss_and_metrics(
                params, data, tgt, bs, self._key0, train=False)
            return conf_acc + conf, (loss, n_err, *counted)

        return body

    def _eval_scan_body(self, params, dataset, targets):
        """Gather variant of ``_eval_body``: xs = (idx, batch_size)."""
        def unpack(xs):
            idx, bs = xs
            return (*self._gather_decode(dataset, targets, idx), bs)

        return self._eval_body(params, unpack)

    def make_train_scan(self):
        """K steps in ONE dispatch via ``lax.scan`` over stacked
        (idx, batch_size, step_number) rows — K is static per (K,) shape.
        Each scanned step is the same ``_step_core`` with the same per-step
        key the sequential path would draw, so semantics are identical;
        what changes is dispatch count, which dominates wall time
        wherever a dispatch costs more than a step's compute.  Metrics
        come back stacked, one per step."""
        import jax

        import jax.numpy as jnp

        nc = self._n_confusion()
        compiles = self._m_compiles
        psh, vsh, repl = self._state_shardings()

        def chunk(params, velocities, hypers_mat, dataset, targets,
                  idx_mat, bs_vec, base_key, step_nums):
            compiles.inc()
            (p, v, conf_sum), ms = jax.lax.scan(
                self._train_scan_body(dataset, targets, base_key),
                (params, velocities, jnp.zeros((nc, nc), jnp.int32)),
                (idx_mat, bs_vec, step_nums, hypers_mat))
            return p, v, ms, conf_sum

        return _ResidentJit(
            self, chunk, 3,
            (psh, vsh, repl, repl, repl, repl, repl, repl, repl),
            (psh, vsh, repl, repl), donate_argnums=(0, 1))

    def make_eval_scan(self):
        """Metrics for K eval minibatches (TEST/VALID) in one dispatch —
        params don't change between eval steps, so the scan is a pure map;
        metrics come back stacked and are fed to the Decision in order."""
        import jax

        import jax.numpy as jnp

        nc = self._n_confusion()
        compiles = self._m_compiles
        psh, _, repl = self._state_shardings()

        def chunk(params, dataset, targets, idx_mat, bs_vec):
            compiles.inc()
            conf_sum, ms = jax.lax.scan(
                self._eval_scan_body(params, dataset, targets),
                jnp.zeros((nc, nc), jnp.int32), (idx_mat, bs_vec))
            return ms, conf_sum

        return _ResidentJit(self, chunk, 1,
                            (psh, repl, repl, repl, repl), (repl, repl))

    def make_eval_step(self):
        """Metrics-only step.  ``train`` is static: True replays the exact
        train-mode forward (dropout/stochastic masks from the same key) —
        used at an epoch tail that runs alone (``_run_segmented``) to let
        the Decision rule on this minibatch's metrics BEFORE the update
        is adopted, matching the unit path where gd_skip gates the final
        update off once ``complete`` flips."""
        compiles = self._m_compiles
        psh, _, repl = self._state_shardings()

        def step(params, dataset, targets, idx, batch_size, key, train):
            compiles.inc()
            data, tgt = self._gather_decode(dataset, targets, idx)
            _, metrics = self.loss_and_metrics(
                params, data, tgt, batch_size, key, train=train)
            return metrics

        # in_shardings entries cover the DYNAMIC args only (the static
        # ``train`` flag is excluded)
        return _ResidentJit(self, step, 1,
                            (psh, repl, repl, repl, repl, repl), repl,
                            static_argnums=(6,))

    # -- the epoch driver ------------------------------------------------------

    #: scan this many consecutive TRAIN steps per dispatch, or eval
    #: steps of one class (an epoch tail the Decision cannot let ride goes
    #: alone, preserving its gd_skip semantics).  1 disables scanning.
    scan_chunk = 8

    def _advance(self):
        """Advance the loader one minibatch and snapshot its state (the
        fused path consumes index state only — ``indices_only``)."""
        loader = self.loader
        loader.run()
        return {
            "idx": np.array(loader.minibatch_indices.mem, np.int32),
            "class": int(loader.minibatch_class),
            "size": int(loader.minibatch_size),
            "last_minibatch": bool(loader.last_minibatch),
            "class_ended": bool(loader.class_ended),
            "epoch_number": int(loader.epoch_number),
        }

    #: >1 enables the DEEP pipeline: whole epochs dispatched as single
    #: executables with every metric pull deferred by up to this many
    #: epochs (one fused scalar transfer per epoch).  Engages only when
    #: nothing consumes host state at epoch granularity (no plotters,
    #: snapshotter absent/gated) — see ``_deep_eligible``.  Identical
    #: training semantics: stops are rolled back to the exact stopping
    #: state (``root.common.engine.pipeline_depth``).  Kept by PR 30's
    #: ladder: 2 read +16.9 % on four chips, where the host's launches
    #: are a fifth of an epoch, and nothing on one (PERF.md section 6).
    pipeline_depth = 1

    @contextlib.contextmanager
    def _timed(self, stat, name, **args):
        """A ``train`` span whose seconds are also summed into
        ``stats[stat]`` (the counters run with telemetry off too)."""
        t0 = time.perf_counter()
        try:
            with self._tracer.span("train", name, **args):
                yield
        finally:
            self.stats[stat] += time.perf_counter() - t0

    def _sync(self, *values):
        """The blocking pull: device values as host arrays, under the
        ``sync`` span — where the host waits for the device."""
        import jax

        with self._timed("sync_wait_s", "sync"):
            return jax.tree_util.tree_map(np.asarray, values)

    def _feed_decision(self, mb, metrics):
        """One minibatch's HOST-side metrics (``_sync`` pulled them; the
        confusion matrix stays a device array) into the Decision.
        Callers wrap their group of feeds in the ``decide`` span."""
        loss, n_err, conf = metrics
        decision = self.decision
        decision.minibatch_class = mb["class"]
        decision.last_minibatch = mb["last_minibatch"]
        decision.class_ended = mb["class_ended"]
        decision.epoch_number = mb["epoch_number"]
        decision.class_lengths = self.loader.class_lengths
        decision.minibatch_size = mb["size"]
        decision.minibatch_loss = float(loss)
        if hasattr(decision, "minibatch_n_err"):
            decision.minibatch_n_err = int(n_err)
            # None = already accounted via a device-side running sum
            # (DecisionBase skips None); the matrix stays a DEVICE
            # array — the decision accumulates it on device and the
            # (C,C) transfer happens only when a consumer reads it
            decision.confusion_matrix = conf
        decision.run()

    def _book_counted(self, counted) -> None:
        """What the counting units counted in the steps just pulled into
        ``stats`` and the registry.  ``counted`` is the tail of a step's
        metrics: empty, or one ``{unit: counters}`` of host arrays
        (stacked over the steps where a scan ran them).  Each unit class
        says what its counts mean (``book_counters(stats, layers)``
        returns the stats it wrote)."""
        for by_unit in counted:
            by_class = {}
            for f in self.forwards:
                if f.name in by_unit:
                    by_class.setdefault(type(f), []).append(by_unit[f.name])
            for cls, layers in by_class.items():
                for name in cls.book_counters(self.stats, layers):
                    value = self.stats[name]
                    flat = ({f"{name}_{k}": v for k, v in value.items()}
                            if isinstance(value, dict) else {name: value})
                    for key, v in flat.items():
                        if key not in self._counter_gauges:
                            self._counter_gauges[key] = self._scope.gauge(
                                key, "counted on the device by the units")
                        self._counter_gauges[key].set(float(v))

    def _book_tail(self, which) -> None:
        """An epoch tail dispatched: ``tails_in_scan`` or ``tails_alone``."""
        self.stats[which] += 1
        self._m_tails[which].inc()

    def _reset_accounting(self):
        self._acct_seen = set()
        self._acct_last_end = None

    def _account(self, n_steps, n_images, t0, is_train, kind="train",
                 n_eval=0, n_dispatch=1):
        # charge [max(t0, last interval end), now]: with the pipeline,
        # segment N's flush happens during iteration N+1, whose own
        # t0 predates the flush — naive (now - t0) intervals overlap
        # and double-count wall time.  ``n_eval`` books the eval share of
        # a mixed (whole-epoch) interval under eval_steps.
        stats = self.stats
        now = time.perf_counter()
        start = t0 if self._acct_last_end is None \
            else max(t0, self._acct_last_end)
        dt = max(now - start, 1e-9)
        self._acct_last_end = now
        if self._tracer.enabled:            # the optional layer (ISSUE 5)
            self._m_step_seconds.observe(dt / max(n_steps + n_eval, 1))
        if is_train:
            # accounting, not overhead-sensitive spans: progress counters
            # keep moving even with telemetry disabled (a dashboard
            # watching train_steps must never read a live run as stalled)
            self._m_train_steps.inc(n_steps)
            self._m_images.inc(n_images)
            if self._tokens_per_sample:
                self._m_tokens.inc(n_images * self._tokens_per_sample)
        stats["wall_s"] += dt
        stats["last_step_ms"] = round(dt / (n_steps + n_eval) * 1e3, 3)
        if is_train:
            stats["train_steps"] += n_steps
            stats["images"] += n_images
            stats["tokens"] += n_images * self._tokens_per_sample
            stats["eval_steps"] += n_eval
        else:
            stats["eval_steps"] += n_steps + n_eval
        total = stats["train_steps"] + stats["eval_steps"]
        stats["steps_per_sec"] = round(total / stats["wall_s"], 2)
        stats["img_per_sec"] = round(
            stats["images"] / stats["wall_s"], 2)
        stats["dispatches"] += n_dispatch
        if kind in self._acct_seen:     # first call of a kind pays compile
            stats["warm_dispatches"] += n_dispatch
            stats["warm_steps"] += n_steps + n_eval
            stats["warm_images"] += n_images
            stats["warm_wall_s"] += dt
            if stats["warm_wall_s"] > 0:
                stats["warm_img_per_sec"] = round(
                    stats["warm_images"] / stats["warm_wall_s"], 2)
        self._acct_seen.add(kind)

    def _device_state(self):
        """Params/velocities/dataset/targets as device values (mesh
        placement applied; the dataset as its prepared twin —
        ``_resident``) plus ``put`` for per-dispatch host operands.
        In staging mode dataset/targets are None — every dispatch ships
        its own staged segment instead."""
        loader = self.loader
        params = self.extract_params()
        velocities = self.extract_velocities()
        if self.staging:
            dataset = targets = None
        elif self.loss_kind == "softmax":
            dataset = self._op_value(loader.original_data)
            targets = self._op_value(loader.original_labels)
        else:
            dataset = self._op_value(loader.original_data)
            targets = self._op_value(loader.original_targets)
        if self.mesh is None:
            if self.staging:
                # explicit async put: the staged segment's transfer starts
                # immediately and overlaps the in-flight dispatch, instead
                # of riding the next jit call's implicit transfer
                import jax

                return params, velocities, None, None, jax.device_put
            return (params, velocities,
                    self._resident(dataset, keep=True)[0], targets,
                    lambda x: x)
        from znicz_tpu.parallel.mesh import global_put, replicated

        repl = replicated(self.mesh)
        params = self.place_state(params)
        velocities = self.place_state(velocities)
        if dataset is not None:
            dataset = self._resident(
                dataset, lambda a: global_put(a, repl), keep=True)[0]
            targets = global_put(targets, repl)
        return (params, velocities, dataset, targets,
                lambda x: global_put(x, repl))

    def _stage_direct(self, idx_rows, put):
        """Assemble + ship ONE dispatch's samples (streaming regime 3) as
        (K, B, ...) minibatch tensors consumed DIRECTLY by the staged
        step/scan variants (no in-step gather).  Storage dtype crosses
        the link (u8 is 4x less traffic; decode happens in-graph).

        Placement: on a mesh the tensors are batch-sharded
        ``P(None, "data")``; in a MULTI-CONTROLLER run each process
        host-gathers ONLY the rows of the batch shards its own devices
        hold (jax.make_array_from_callback) — the SPMD analogue of the
        reference's master/slave per-slave minibatch feed: no host ever
        touches another host's samples.  Dispatch is async either way, so
        segment N+1's assembly overlaps segment N's compute."""
        loader = self.loader
        idx_mat = np.stack([np.asarray(r, np.int32) for r in idx_rows])
        n_steps, batch = idx_mat.shape
        if self.loss_kind == "softmax":
            tgt_gather = loader.host_gather_labels
            tgt_sample = ()
        else:
            tgt_gather = loader.host_gather_targets
            tgt_sample = tuple(loader.original_targets.mem.shape[1:])
        shape_d = (n_steps, batch) + tuple(loader.source.sample_shape)
        shape_t = (n_steps, batch) + tgt_sample
        if self.mesh is None:
            flat = idx_mat.reshape(-1)
            return (put(loader.host_gather(flat).reshape(shape_d)),
                    put(tgt_gather(flat).reshape(shape_t)))
        if batch % self.mesh.shape["data"]:
            # explicit batch-sharded placement needs divisibility (unlike
            # the in-step constraint, which pads) — stage replicated and
            # let the constraint shard.  Multi-controller loses the
            # gather-own-rows-only property for such batch sizes.
            flat = idx_mat.reshape(-1)
            return (put(loader.host_gather(flat).reshape(shape_d)),
                    put(tgt_gather(flat).reshape(shape_t)))
        from znicz_tpu.parallel.mesh import (put_sharded_segment,
                                             segment_sharding)

        sh = segment_sharding(self.mesh)
        return (put_sharded_segment(shape_d, sh, loader.host_gather,
                                    idx_mat),
                put_sharded_segment(shape_t, sh, tgt_gather, idx_mat))

    def _staging_donation(self) -> bool:
        """Donate the staged (K, B, ...) segment buffers into the direct
        train scan (``root.common.engine.staging_donate``, default on):
        with the async double-buffer at most two staged segments exist —
        the one the device is consuming (its HBM reusable for activations
        the instant the scan's slice reads it) and the one the stager is
        putting — the serving layer's ping-pong discipline on the
        training path.  Auto-off on CPU, where the runtime ignores
        donation (and warns per compile) — same backend resolution as
        ``ModelRunner.donate``."""
        import jax

        return (bool(root.common.engine.get("staging_donate", True))
                and jax.default_backend() != "cpu")

    def make_train_scan_direct(self):
        """The staged twin of ``make_train_scan``: K steps in one
        dispatch, with the K minibatches riding in the scan xs as
        (K, B, ...) tensors instead of being gathered from a resident
        dataset (same ``_train_body``).  Sliced per step, each (B, ...)
        batch keeps its ``data`` sharding — no gather, no resharding.
        The staged segment buffers are DONATED where the backend supports
        it (``_staging_donation``); callers must not reuse them after the
        dispatch (the run loop never does — each segment is staged
        fresh)."""
        import jax
        import jax.numpy as jnp

        nc = self._n_confusion()
        compiles = self._m_compiles
        donate = (0, 1, 3, 4) if self._staging_donation() else (0, 1)
        kw = {}
        if self.mesh is not None:
            # staged segments keep whatever placement _stage_direct chose
            # (batch-sharded, or the replicated fallback for batches the
            # data axis doesn't divide) — None = infer from the operand,
            # so BOTH placements hit the same executable family without
            # a reshard
            psh, vsh, repl = self._state_shardings()
            kw = self._jit_shardings(
                (psh, vsh, repl, None, None, repl, repl, repl),
                (psh, vsh, repl, repl))

        def chunk(params, velocities, hypers_mat, data_seg, tgt_seg,
                  bs_vec, base_key, step_nums):
            compiles.inc()
            (p, v, conf_sum), ms = jax.lax.scan(
                self._train_body(base_key, lambda xs: xs),
                (params, velocities, jnp.zeros((nc, nc), jnp.int32)),
                (data_seg, tgt_seg, bs_vec, step_nums, hypers_mat))
            return p, v, ms, conf_sum

        return jax.jit(chunk, donate_argnums=donate, **kw)

    def make_eval_scan_direct(self):
        import jax
        import jax.numpy as jnp

        nc = self._n_confusion()
        compiles = self._m_compiles
        kw = {}
        if self.mesh is not None:
            psh, _, repl = self._state_shardings()
            kw = self._jit_shardings((psh, None, None, repl),
                                     (repl, repl))

        def chunk(params, data_seg, tgt_seg, bs_vec):
            compiles.inc()

            def unpack(xs):
                data, tgt, bs = xs
                return self._decode(data), tgt, bs

            conf_sum, ms = jax.lax.scan(
                self._eval_body(params, unpack),
                jnp.zeros((nc, nc), jnp.int32),
                (data_seg, tgt_seg, bs_vec))
            return ms, conf_sum

        return jax.jit(chunk, **kw)

    def make_train_step_direct(self):
        """Tail-update twin of ``make_train_step`` for staged (1, B, ...)
        minibatch tensors.  NO data donation here: the tail path feeds
        the same staged buffers to the eval step first and (gd_skip
        permitting) this step second."""
        import jax

        compiles = self._m_compiles
        kw = {}
        if self.mesh is not None:
            psh, vsh, repl = self._state_shardings()
            kw = self._jit_shardings(
                (psh, vsh, repl, None, None, repl, repl),
                (psh, vsh, repl))

        def step(params, velocities, hypers, data_seg, tgt_seg,
                 batch_size, key):
            compiles.inc()
            with jax.named_scope("input"):
                data, tgt = data_seg[0], tgt_seg[0]
            return self._update_core(params, velocities, hypers, data, tgt,
                                     batch_size, key)

        return jax.jit(step, donate_argnums=(0, 1), **kw)

    def make_eval_step_direct(self):
        import jax

        compiles = self._m_compiles
        kw = {}
        if self.mesh is not None:
            psh, _, repl = self._state_shardings()
            kw = self._jit_shardings((psh, None, None, repl, repl), repl)

        def step(params, data_seg, tgt_seg, batch_size, key, train):
            compiles.inc()
            with jax.named_scope("input"):
                data, tgt = data_seg[0], tgt_seg[0]
            _, metrics = self.loss_and_metrics(
                params, self._decode(data), tgt, batch_size, key,
                train=train)
            return metrics

        return jax.jit(step, static_argnums=(5,), **kw)

    def _advance_lr(self):
        if self._lr_adjust is not None:
            self._lr_adjust.run()

    def _hypers_rows(self, k, advance_last=True):
        """Per-step hypers for a k-step scan, advancing any LR schedule
        between steps exactly like the unit graph does.  ``advance_last``
        False skips the advance after the final row — the deep path's
        epoch tail whose update will not be adopted (the adjust is gated
        like the gds — unit-path parity)."""
        if self._lr_adjust is None:
            return self.tiled_hypers(k)
        rows = []
        for i in range(k):
            rows.append({name: np.asarray(t, np.float32)
                         for name, t in self.hypers().items()})
            if i < k - 1 or advance_last:
                self._advance_lr()
        return {name: np.stack([r[name] for r in rows])
                for name in rows[0]}

    def run(self) -> None:
        """Train until the decision completes, mirroring the loader's
        epoch/class state machine but with fused steps.  Feeds the Decision
        unit per-minibatch so its improvement/stop/log semantics (and the
        snapshotter trigger) behave exactly like the unit path.

        Two host-sync profiles, identical training semantics:

          - default (``pipeline_depth`` 1; what the three cells run):
            consecutive TRAIN minibatches run as ONE ``lax.scan``
            dispatch of up to ``scan_chunk`` steps, with a one-deep flush
            pipeline; eval and each epoch's last segment feed the
            Decision synchronously, so epoch-granular consumers —
            snapshotter, plotters, an ``on_epoch_end`` callback — see
            every epoch when it ends.  The epoch's last TRAIN minibatch
            (the tail, on which ``complete`` may flip and whose update is
            then not adopted: ``gd_skip``) is the last step of that
            segment wherever the Decision can say BEFOREHAND that the run
            goes on (``Decision.tail_stops``: exact with a validation
            set, where the stop rule reads nothing of the tail's own);
            where it says stop, or cannot say (no validation set), the
            tail is evaluated, ruled on and only then updated, alone.  So
            the Decision's own stops (``max_epochs``,
            ``fail_iterations``) leave the unit engine's stopping state.
            **A stop that an ``on_epoch_end`` callback asks for by
            raising ``complete`` cannot be seen ahead: where that epoch's
            tail rode the scan its update IS applied when the callback
            runs** (the unit engine, and this loop without a validation
            set, skip it).  Every step's mathematics is the same; which
            step is such a run's last moves by one;
          - deep (``pipeline_depth`` > 1 and ``_deep_eligible``): whole
            epochs as single dispatches, metrics pulled one fused transfer
            per epoch, up to ``2 * depth`` epochs late.  What it buys is
            the host's launches between the programs of an epoch: a fifth
            of an epoch on four chips (PERF.md section 6).  It books no
            counting unit's counters and holds a state copy an in-flight
            epoch, so a decoder that fills the chip cannot run on it."""
        if self.loss_kind != "softmax" and \
                getattr(self.loader, "streaming", False) and \
                not self.loader.original_targets:
            raise ValueError(
                f"{self.loader.name}: a streaming loader with an MSE "
                "loss needs regression targets — build the StreamingLoader "
                "source with targets=")
        try:
            if self.pipeline_depth > 1 and self._deep_eligible():
                self._run_deep()
            else:
                self._run_segmented()
        finally:
            # the twin is the run's: its HBM goes back with the run
            self._twins.clear()
        # the zero-recompile proof, where print_stats / status.json /
        # chip_smoke.py can read it without a handle on the trainer
        self.stats["compiles"] = int(self._m_compiles.value)
        self.stats["jit_cache_sizes"] = self.jit_cache_sizes()
        # what the units noted on the host while the run's programs were
        # traced and lowered; each class says what its notes mean
        # (``run_stats(units)``: a decoder layer's is which way its
        # attention core ran)
        for cls in dict.fromkeys(type(f) for f in self.forwards):
            if hasattr(cls, "run_stats"):
                self.stats.update(cls.run_stats(
                    [f for f in self.forwards if type(f) is cls]))

    def _run_segmented(self) -> None:
        from znicz_tpu.loader.base import TRAIN

        wf = self.workflow
        loader, decision = self.loader, self.decision
        staging = self.staging
        if staging:
            if self._train_step is None:
                self._train_step = self.make_train_step_direct()
                self._eval_step = self.make_eval_step_direct()
                self._train_scan = self.make_train_scan_direct()
                self._eval_scan = self.make_eval_scan_direct()
        else:
            if self._train_step is None:
                self._train_step = self.make_train_step()
                self._eval_step = self.make_eval_step()
            if self._train_scan is None and self.scan_chunk > 1:
                self._train_scan = self.make_train_scan()
                self._eval_scan = self.make_eval_scan()
        self._reset_accounting()
        params, velocities, dataset, targets, put = self._device_state()
        rows = getattr(loader, "original_data", None)
        self._tokens_per_sample = (
            int(rows.shape[1]) if rows and len(rows.shape) == 2
            and np.issubdtype(rows.dtype, np.integer) else 0)
        feed_decision = self._feed_decision
        account = self._account
        advance_lr = self._advance_lr
        hypers_rows = self._hypers_rows

        def epoch_end_hook():
            # writeback is NEED-driven: device->host param+velocity pulls
            # cost a fixed per-epoch tax on slow host links (~100ms/RTT),
            # so pay it only when something will consume the state this
            # epoch — a due snapshot or a wired plotter (VERDICT r3
            # weak #3).  run() still does one final writeback at the end.
            # A due HOST-FORMAT snapshot doesn't even pay that: the trees
            # are device-copied (donation safety) and handed to the
            # snapshotter's background worker, which pulls and writes
            # while the next epoch computes (VERDICT r4 item 4).
            snap = getattr(wf, "snapshotter", None)
            snap_open = snap is not None and not bool(snap.gate_skip)
            snap_due = snap_open and snap.due(decision.epoch_number,
                                              decision.improved)
            snap_async = (snap_due and self._async_snapshot_enabled(snap)
                          and self._copy_fits(params, velocities))
            plotters = list(getattr(wf, "plotters", None) or [])
            if (snap_due and not snap_async) or plotters:
                self.writeback(params, velocities)
            if snap_open:
                snap.epoch_number = decision.epoch_number
                snap.improved = decision.improved
                if snap_async:
                    import jax
                    import jax.numpy as jnp

                    tags = snap.tags_for(decision.epoch_number,
                                         decision.improved)
                    if tags:
                        copy = jax.tree_util.tree_map
                        with span("train", "snapshot_copy"):
                            trees = (copy(jnp.copy, params),
                                     copy(jnp.copy, velocities))
                        snap.save_async(self.snapshot_from_trees(*trees),
                                        tags)
                elif snap_due:
                    snap.run()
            # wired plotters count as consumers, so whenever they run the
            # unit Arrays hold this epoch's weights.  Ad-hoc observers
            # (e.g. a decision.on_epoch_end callback reading weights)
            # see Arrays refreshed only on consumer epochs + at run end —
            # the documented cost of need-driven writeback (ImageSaver
            # stays unit-engine-only: it needs per-minibatch host data
            # the fast path never pulls)
            for plotter in plotters:
                plotter.run()

        from collections import deque

        span = self._tracer.span
        was_indices_only = loader.indices_only
        loader.indices_only = True
        fifo = deque()                  # advanced-but-unprocessed mbs
        inflight = None                 # (seg, kind, device results, t0)
        epoch_conf = None               # device-side confusion running sum

        # -- lookahead prefetch (loader/ingest.py): for host-staged
        # sources with a decode pool, advance the loader's index state
        # machine ahead of processing and SUBMIT future minibatches' rows
        # so their decode overlaps the in-flight dispatch's compute.
        # Bounded to ``prefetch_segments`` scan segments; never advances
        # past an epoch tail (last_minibatch), so the loader state the
        # snapshotter sees at epoch boundaries is identical to the
        # unprefetched run's.
        prefetch_segments = int(root.common.engine.get(
            "prefetch_segments", 2))
        can_prefetch = (
            staging and prefetch_segments > 0
            and getattr(loader, "prefetch_rows", None) is not None
            and getattr(loader.source, "prefetch", None) is not None)
        look_mbs = prefetch_segments * max(self.scan_chunk, 1)
        sel_cache = {}

        # -- async double-buffered device staging (ISSUE 7): a one-worker
        # stager assembles + device_puts the NEXT train segment while the
        # current one computes, so host gather/decode and the H2D copy
        # hide under the step instead of serializing against it.  The
        # prediction is the dispatch loop's own segment-collection rule
        # replayed over the lookahead fifo; a mispredicted segment falls
        # back to inline staging (counted — never wrong data).  Single-
        # controller only: the multi-process gather-own-rows callback
        # stays on the training thread.
        stager = None
        if staging and bool(root.common.engine.get("async_staging", True)):
            import jax as _jax

            if self.mesh is None or _jax.process_count() == 1:
                from znicz_tpu.loader.ingest import DeviceStager

                stager = DeviceStager(
                    lambda rows: self._stage_direct(rows, put))
                self._stager = stager       # observable (tests)
        # the lookahead must advance even for memcpy-cheap sources (no
        # decode pool): the stager needs the fifo to predict from
        look_mbs = max(look_mbs if can_prefetch else 0,
                       2 * max(self.scan_chunk, 1) if stager else 0)

        def stage_segment(seg):
            """Staged device tensors for a dispatch group — from the
            stager when armed (a predicted group is a cache pop; the
            fallback assembles inline and counts a miss)."""
            rows = [s["idx"] for s in seg]
            with span("train", "stage", steps=len(seg)):
                if stager is not None:
                    return stager.take(rows)
                return self._stage_direct(rows, put)

        def scans(mb, fed=True):
            """The TRAIN side of the segment-collection rule, for the
            dispatch loop and the stager's replay of it: a TRAIN
            minibatch runs as a step of a scan segment unless it is an
            epoch tail whose update the Decision cannot promise
            beforehand (a stop, or no validation set to judge on:
            ``Decision.tail_stops``).  A tail that scans ends its
            segment.  ``fed`` False (the replay's: an eval minibatch
            served before ``mb`` is not fed yet) gives ``None`` where the
            answer waits for that feed."""
            if mb["class"] != TRAIN or not mb["last_minibatch"]:
                return mb["class"] == TRAIN
            stops = decision.tail_stops(mb["epoch_number"], validated=fed)
            return None if stops is None and not fed else stops is False

        def upcoming_segments(fed):
            """The dispatch groups the loop WILL form from the fifo — the
            segment-collection rules replayed without consuming: TRAIN
            segments (consecutive ``scans`` minibatches, up to scan_chunk,
            ended by a tail), eval runs (same class, up to scan_chunk), a
            tail that does not scan as its own group.  Stops at the first
            group whose boundary the fifo cannot prove yet (the lookahead
            refill will), and at a tail the Decision cannot rule on yet:
            ``fed`` says whether it has every eval minibatch served so
            far, and an eval group in the fifo ends that."""
            groups, i, n = [], 0, len(fifo)
            while i < n:
                m = fifo[i]
                is_train = m["class"] == TRAIN
                scan = self._train_scan if is_train else self._eval_scan
                cap = self.scan_chunk if scan else 1
                seg = [m]
                i += 1
                while i < n and len(seg) < cap \
                        and not seg[-1]["last_minibatch"]:
                    nxt = fifo[i]
                    joins = (scans(nxt, fed) if is_train
                             else nxt["class"] == m["class"])
                    if joins is None:
                        return groups           # the Decision cannot say yet
                    if not joins:
                        break
                    seg.append(nxt)
                    i += 1
                if len(seg) < cap and i >= n \
                        and not seg[-1]["last_minibatch"]:
                    break                       # boundary not proven
                fed = fed and is_train
                groups.append(seg)
            return groups

        def submit_upcoming(fed=True):
            """Start staging the provable upcoming groups, oldest first,
            until the ping-pong is full (``stager.depth``)."""
            if stager is None:
                return
            for seg in upcoming_segments(fed):
                if stager.outstanding >= stager.depth:
                    break
                stager.submit([s["idx"] for s in seg])

        def local_rows(idx):
            """The rows of a minibatch THIS process will stage (multi-
            controller prefetch keeps _stage_direct's gather-own-rows-
            only property; single-host returns everything)."""
            if self.mesh is None:
                return idx
            import jax

            if jax.process_count() == 1:
                return idx
            batch = len(idx)
            if batch % self.mesh.shape["data"]:
                return idx      # replicated staging fallback: all rows
            mask = sel_cache.get(batch)
            if mask is None:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                sh = NamedSharding(self.mesh, P("data"))
                mask = np.zeros(batch, bool)
                pidx = jax.process_index()
                for d, ind in sh.devices_indices_map((batch,)).items():
                    if d.process_index == pidx:
                        mask[ind[0]] = True
                sel_cache[batch] = mask
            return idx[mask]

        def take_mb():
            return fifo.popleft() if fifo else self._advance()

        def extend_lookahead():
            if not (can_prefetch or stager is not None):
                return
            # a put-back mb (segment collection overshoot) may sit in the
            # fifo without having been submitted — cover it first
            if can_prefetch:
                for m in fifo:
                    if not m.get("pf"):
                        loader.prefetch_rows(local_rows(m["idx"]))
                        m["pf"] = True
            while len(fifo) < look_mbs and \
                    not (fifo and fifo[-1]["last_minibatch"]):
                nxt = self._advance()
                if can_prefetch:
                    loader.prefetch_rows(local_rows(nxt["idx"]))
                    nxt["pf"] = True
                fifo.append(nxt)

        def flush():
            """Sync + feed the in-flight TRAIN segment's metrics.  Runs
            AFTER the next segment is dispatched, so the host round-trip
            overlaps device compute (one-deep pipeline); non-tail TRAIN
            feeds cannot flip `complete`/`gd_skip`, so deferring them one
            segment changes no control flow — eval and a tail alone flush
            first, and a segment that holds a tail is flushed as soon as
            it is dispatched.  Confusion stays on device
            (``epoch_conf``), handed over once with the epoch tail."""
            nonlocal inflight, epoch_conf
            if inflight is None:
                return
            seg, kind, res, t0, step0 = inflight
            inflight = None
            # the host-sync span: waiting out the previous dispatch's
            # device work + pulling its metrics, then the Decision.  A
            # segment that holds the epoch's tail is the epoch's end: its
            # flush is the ``tail`` span (what the chips wait for there)
            tail = seg[-1]["last_minibatch"]
            name, args = (("tail", {"epoch": int(seg[-1]["epoch_number"])})
                          if tail else ("flush", {}))
            with span("train", name, steps=len(seg), kind=kind, step0=step0,
                      **args):
                if kind == "single":
                    loss, n_err, conf, *counted = res
                    epoch_conf = conf if epoch_conf is None \
                        else epoch_conf + conf
                    losses, n_errs, *counted = self._sync(loss, n_err,
                                                          *counted)
                    stacked = [(losses, n_errs, None)]
                else:
                    ms, conf_sum = res
                    epoch_conf = conf_sum if epoch_conf is None \
                        else epoch_conf + conf_sum
                    losses, n_errs, *counted = self._sync(*ms)
                    stacked = [(losses[i], n_errs[i], None)
                               for i in range(len(seg))]
                if tail:
                    # the epoch's confusion sum rides with the tail's feed
                    stacked[-1] = (*stacked[-1][:2], epoch_conf)
                    epoch_conf = None
                self._book_counted(counted)
                with self._timed("decide_s", "decide"):
                    for s, m in zip(seg, stacked):
                        feed_decision(s, m)
            account(len(seg), sum(s["size"] for s in seg), t0, True,
                    kind=f"train_{kind}_{len(seg)}")

        try:
            while not bool(decision.complete):
                t_iter = time.perf_counter()
                with span("train", "advance"):
                    mb = take_mb()
                is_train = (mb["class"] == TRAIN)
                if scans(mb):
                    # collect the segment of consecutive TRAIN minibatches
                    # that cannot flip `complete` — the non-tail ones, and
                    # the tail where the Decision says beforehand that the
                    # run goes on — and run it as one scan dispatch
                    seg = [mb]
                    max_seg = self.scan_chunk if self._train_scan else 1
                    with span("train", "advance", steps=max_seg):
                        while len(seg) < max_seg \
                                and not seg[-1]["last_minibatch"]:
                            nxt = take_mb()
                            if scans(nxt):
                                seg.append(nxt)
                            else:
                                fifo.appendleft(nxt)
                                break
                        holds_tail = seg[-1]["last_minibatch"]
                        if not holds_tail:  # (the loader stays in the epoch)
                            extend_lookahead()  # future segments' decode
                        if stager is not None:
                            submit_upcoming()
                    if stager is not None:
                        # ping-pong ordering (ISSUE 7): upcoming groups'
                        # assemblies are already in flight — sync the
                        # PREVIOUS segment FIRST so its device compute
                        # overlaps them, then take this segment's staged
                        # buffers (ready by then; the wait histogram is
                        # the proof the --ingest gate checks)
                        flush()
                    gen = prng.get("fused_trainer")

                    def seg_ops():
                        return (put(np.array([s["size"] for s in seg],
                                             np.int32)),
                                put(np.arange(self.steps_done,
                                              self.steps_done + len(seg),
                                              dtype=np.int32)))

                    # the dispatch span measures HOST dispatch time (the
                    # device work lands in flush()'s sync span); in a
                    # profiler session it is a named step
                    step0 = self.steps_done
                    kind = ("single" if len(seg) == 1 and not staging
                            else "scan")
                    with span("train", f"dispatch:{kind}", step=step0,
                              steps=len(seg)):
                        if staging:
                            # staged-direct: minibatches ride in the scan xs
                            # (even a lone step goes through the K=1 scan);
                            # with the async stager the buffers were
                            # assembled + put while the PREVIOUS segment
                            # computed
                            dseg, tseg = stage_segment(seg)
                            bs_vec, steps = seg_ops()
                            params, velocities, ms, conf_sum = \
                                self._train_scan(
                                    params, velocities,
                                    put(hypers_rows(len(seg))), dseg, tseg,
                                    bs_vec, put(gen.jax_base_key()), steps)
                            result = (ms, conf_sum)
                        elif len(seg) == 1:
                            key = gen.jax_key(self.steps_done)
                            params, velocities, result = self._train_step(
                                params, velocities, self.hypers(), dataset,
                                targets, put(seg[0]["idx"]),
                                np.int32(seg[0]["size"]), key)
                            advance_lr()
                        else:
                            idx_op = put(np.stack([s["idx"] for s in seg]))
                            bs_vec, steps = seg_ops()
                            params, velocities, ms, conf_sum = \
                                self._train_scan(
                                    params, velocities,
                                    put(hypers_rows(len(seg))), dataset,
                                    targets, idx_op, bs_vec,
                                    put(gen.jax_base_key()), steps)
                            result = (ms, conf_sum)
                    self.steps_done += len(seg)
                    # start staging the NEXT groups before anything
                    # blocks: their host assembly + H2D overlap this
                    # segment's compute
                    submit_upcoming()
                    if stager is None:
                        flush()         # previous segment, AFTER dispatch
                    inflight = (seg, kind, result, t_iter, step0)
                    if holds_tail:
                        # the epoch ends in this segment: pull it and let
                        # the Decision rule NOW, before the loader moves on
                        # — the epoch-end hook below sees the tail's state
                        self._book_tail("tails_in_scan")
                        flush()
                elif is_train:
                    self._book_tail("tails_alone")
                    flush()
                    # an epoch tail the Decision could not let ride (a
                    # stop, or no validation set: at most once a job
                    # where there is one): metrics first, Decision rules,
                    # and the update applies only if gd_skip stayed open
                    # (unit-path parity).  The epoch's device-side
                    # confusion sum rides along in this one transfer.
                    # The leaves say which of these the device waits for.
                    with span("train", "tail",
                              epoch=int(mb["epoch_number"])):
                        with span("train", "tail_eval"):
                            bs = np.int32(mb["size"])
                            # the step's key is a small device program
                            key = prng.get("fused_trainer").jax_key(
                                self.steps_done)
                            if staging:
                                dseg, tseg = stage_segment([mb])
                                loss, n_err, conf, *counted = \
                                    self._eval_step(params, dseg, tseg, bs,
                                                    key, True)
                            else:
                                idx = put(mb["idx"])
                                loss, n_err, conf, *counted = \
                                    self._eval_step(params, dataset,
                                                    targets, idx, bs, key,
                                                    True)
                            if epoch_conf is not None:
                                conf = epoch_conf + conf
                                epoch_conf = None
                        # what the units counted rides in this one pull
                        # (the update that follows runs the same forward)
                        loss, n_err, *counted = self._sync(loss, n_err,
                                                           *counted)
                        self._book_counted(counted)
                        with self._timed("decide_s", "decide"):
                            feed_decision(mb, (loss, n_err, conf))
                        applied = not bool(decision.gd_skip)
                        if applied:
                            with span("train", "tail_update",
                                      step=self.steps_done):
                                if staging:
                                    params, velocities, _ = \
                                        self._train_step(
                                            params, velocities,
                                            self.hypers(), dseg, tseg, bs,
                                            key)
                                else:
                                    params, velocities, _ = \
                                        self._train_step(
                                            params, velocities,
                                            self.hypers(), dataset,
                                            targets, idx, bs, key)
                                advance_lr()    # adj is gated like the gds
                        self.steps_done += 1
                    account(1, mb["size"], t_iter, True, kind="tail",
                            n_dispatch=1 + applied)
                else:
                    flush()
                    # TEST/VALID: params are frozen, so consecutive eval
                    # minibatches of the SAME class scan as a pure map in
                    # one dispatch (segments must not span the TEST|VALID
                    # boundary — the segment's summed confusion is booked
                    # to the first minibatch's class)
                    seg = [mb]
                    max_seg = self.scan_chunk if self._eval_scan else 1
                    with span("train", "eval", klass=int(mb["class"])):
                        with span("train", "advance", steps=max_seg):
                            while len(seg) < max_seg:
                                nxt = take_mb()
                                if nxt["class"] == mb["class"]:
                                    seg.append(nxt)
                                else:
                                    fifo.appendleft(nxt)
                                    break
                            extend_lookahead()
                            # the upcoming groups stage while this eval
                            # segment computes (the eval/train boundary is
                            # where each epoch's first train segment would
                            # otherwise pay the full assembly inline); its
                            # metrics are not fed yet
                            submit_upcoming(fed=False)
                        # segment confusion fed once, with the first step
                        if staging:
                            dseg, tseg = stage_segment(seg)
                            bs_vec = put(np.array([s["size"] for s in seg],
                                                  np.int32))
                            ms, conf = self._eval_scan(
                                params, dseg, tseg, bs_vec)
                        elif len(seg) == 1:
                            loss, n_err, conf, *counted = self._eval_step(
                                params, dataset, targets, put(mb["idx"]),
                                np.int32(mb["size"]), self._key0, False)
                            ms = (loss, n_err, *counted)
                        else:
                            idx_op = put(np.stack([s["idx"] for s in seg]))
                            bs_vec = put(np.array([s["size"] for s in seg],
                                                  np.int32))
                            ms, conf = self._eval_scan(
                                params, dataset, targets, idx_op, bs_vec)
                        # a lone step's scalars feed like a scan's stack
                        losses, n_errs, *counted = self._sync(*ms)
                        losses, n_errs = (np.atleast_1d(losses),
                                          np.atleast_1d(n_errs))
                        self._book_counted(counted)
                        with self._timed("decide_s", "decide"):
                            for i, s in enumerate(seg):
                                feed_decision(s, (losses[i], n_errs[i],
                                                  conf if i == 0 else None))
                    account(len(seg), 0, t_iter, False,
                            kind=f"eval_{len(seg)}")
                if bool(decision.epoch_ended):
                    with self._timed("epoch_hook_s", "epoch_hook",
                                     epoch=int(decision.epoch_number)):
                        epoch_end_hook()
                    # consume the flag: with the pipeline, the next loop
                    # iteration may not feed the decision before this
                    # check runs again, and a stale True would re-save
                    # the 'best' snapshot with weights already advanced
                    # past the epoch boundary
                    decision.epoch_ended.set(False)
                if look_mbs and not bool(decision.complete):
                    # refill the lookahead AFTER the epoch hook: a
                    # boundary snapshot must record the tail state, not a
                    # loader already advanced (and reshuffled) into the
                    # next epoch — resume parity depends on this ordering
                    with span("train", "advance"):
                        extend_lookahead()
                        submit_upcoming()
            flush()
            self.writeback(params, velocities)
        finally:
            loader.indices_only = was_indices_only
            if stager is not None:
                # drop any mispredicted in-flight segment (a stop can
                # land mid-prediction); staged buffers are just arrays —
                # nothing to unwind
                stager.close()
            # in the FINALLY: an interrupt mid-run must still land the
            # queued async saves (the writer thread is a daemon — without
            # this drain a Ctrl-C drops them); on the exception path the
            # drain must not mask the in-flight error with a writer error
            self._drain_snapshots(suppress=_sys.exc_info()[0] is not None)

    # -- the deep (whole-epoch) pipeline ---------------------------------------

    def _deep_eligible(self) -> bool:
        """Deep pipelining defers every host sync by up to
        ``pipeline_depth`` epochs, so it requires that nothing consumes
        host-side state at epoch granularity: no wired plotters.  An
        ACTIVE snapshotter no longer forces the segmented path (r4 weak
        #3 — the fast configuration couldn't checkpoint at all): a
        host-format snapshotter is served at FLUSH boundaries by the
        async writer, from the flushed epoch's own recorded state
        (loader/prng as of that epoch's tail), so the checkpoint is
        bit-equivalent to the segmented path's.  Only an orbax-format
        snapshotter (collective save) or async_snapshot=False still
        selects segmented mode.  Decision semantics are preserved
        exactly either way — metrics are fed in order, just later in
        wall time, and stops are rolled back to the exact stopping
        state.  What counting units count (``apply_carried``) is not
        booked on this path: its packed scalar vector holds loss and
        error counts only."""
        from znicz_tpu.core.mutable import Bool

        wf = self.workflow
        if self.staging:
            # host-staged streaming ships each dispatch's samples; a whole
            # deep-pipelined epoch would stage the full epoch at once —
            # use the segmented path, whose per-segment staging is the
            # double buffer
            return False
        if getattr(wf, "plotters", None):
            return False
        snap = getattr(wf, "snapshotter", None)
        if snap is not None:
            gate = snap.gate_skip
            # an epoch-wired gate (e.g. ~decision.epoch_ended) is derived
            # and OPENS at epoch ends — that snapshotter is active even
            # though the gate reads True between epochs.  Only a plain
            # constant-True skip counts as disabled.
            disabled = bool(gate) and not (
                isinstance(gate, Bool) and gate.derived)
            if not disabled and not self._async_snapshot_enabled(snap):
                return False
        return True

    def _collect_epoch(self):
        """Drive the loader through ONE full epoch; returns its recorded
        minibatches: eval class runs (loader order: TEST then VALID) and
        the TRAIN run whose last minibatch is the epoch tail."""
        from znicz_tpu.loader.base import TRAIN

        evals, train = [], []
        while True:
            mb = self._advance()
            if mb["class"] == TRAIN:
                train.append(mb)
                if mb["last_minibatch"]:
                    break
            else:
                assert not train, \
                    "deep pipeline expects eval classes before TRAIN"
                if evals and evals[-1][0] == mb["class"]:
                    evals[-1][1].append(mb)
                else:
                    evals.append((mb["class"], [mb]))
        return {"evals": evals, "train": train,
                "epoch_number": train[-1]["epoch_number"]}

    def _epoch_hypers(self, k, apply_tail: bool):
        """Hypers rows for one epoch's k+1 train steps (see
        ``_hypers_rows`` — the one home of the row-build loop)."""
        return self._hypers_rows(k + 1, advance_last=apply_tail)

    def make_epoch_fn(self, eval_layout, n_train: int):
        """The WHOLE epoch as ONE dispatch: eval scans on the incoming
        (pre-epoch) params in loader order, then the k non-tail train
        steps as one scan, then the tail step whose update is adopted
        only when ``apply_tail`` (the gd_skip prediction; a
        late-discovered stop re-dispatches with False).  Returns new
        params/velocities, one packed f32 scalar vector (per eval run:
        losses then n_errs; then train losses, train n_errs, tail loss,
        tail n_err) and stacked confusion sums (one per eval run + one
        for TRAIN incl. tail) — all metrics pullable in a single host
        transfer per epoch."""
        import jax
        import jax.numpy as jnp

        k = n_train - 1
        nc = self._n_confusion()

        def epoch(params, velocities, hypers_mat, dataset, targets,
                  train_idx, train_bs, eval_idx, eval_bs, base_key,
                  step_nums, apply_tail):
            scalars, confs = [], []
            ebody = self._eval_scan_body(params, dataset, targets)
            off = 0
            for _klass, n in eval_layout:
                conf_r, ms = jax.lax.scan(
                    ebody, jnp.zeros((nc, nc), jnp.int32),
                    (eval_idx[off:off + n], eval_bs[off:off + n]))
                scalars += [ms[0], ms[1].astype(jnp.float32)]
                confs.append(conf_r)
                off += n

            head = jax.tree_util.tree_map(lambda h: h[:k], hypers_mat)
            (p, v, conf_tr), tms = jax.lax.scan(
                self._train_scan_body(dataset, targets, base_key),
                (params, velocities, jnp.zeros((nc, nc), jnp.int32)),
                (train_idx[:k], train_bs[:k], step_nums[:k], head))
            key_t = jax.random.fold_in(base_key, step_nums[k])
            hyp_t = jax.tree_util.tree_map(lambda h: h[k], hypers_mat)
            p2, v2, (tl, tn, tconf, *_) = self._step_core(
                p, v, hyp_t, dataset, targets, train_idx[k], train_bs[k],
                key_t)
            p, v = jax.lax.cond(apply_tail,
                                lambda a, b, c, d: (a, b),
                                lambda a, b, c, d: (c, d), p2, v2, p, v)
            scalars += [tms[0], tms[1].astype(jnp.float32),
                        jnp.stack([tl, tn.astype(jnp.float32)])]
            confs.append(conf_tr + tconf)
            return p, v, jnp.concatenate(scalars), jnp.stack(confs)

        return _ResidentJit(self, epoch, 3, (None,) * 12, None)

    def _run_deep(self) -> None:
        """Whole-epoch dispatches with metric pulls deferred by up to
        ``2 * pipeline_depth`` epochs: the pipeline FILLS to 2x depth and
        then flushes ``depth`` epochs with their scalars pulled in ONE
        fused transfer (a per-epoch pull serializes the host loop at one
        link RTT per epoch — r4).  Costs scale with the window: up to
        ``2*depth - 1`` in-flight epochs each pin a params+velocities
        snapshot in HBM (AlexNet: ~366 MB per epoch -> ~5.5 GB at depth
        8), and a ``fail_iterations`` stop is discovered (and rolled
        back) up to that many epochs late.  Dispatch runs AHEAD of the
        Decision speculatively: every epoch's tail update except the
        last-by-max_epochs is applied optimistically (gd_skip only closes
        when ``complete`` flips — decision.py); when a flush reveals an
        earlier stop, the exact stopping state is recomputed from the
        recorded epoch inputs with ``apply_tail`` False and the
        speculated epochs are discarded, including the host-side
        LR-schedule/prng/loader bookkeeping."""
        import copy
        from collections import deque

        span = self._tracer.span
        decision, loader = self.decision, self.loader
        self._reset_accounting()
        params, velocities, dataset, targets, put = self._device_state()
        epoch_fn = None
        layout = None
        inflight = deque()
        was_indices_only = loader.indices_only
        loader.indices_only = True
        gen = prng.get("fused_trainer")

        concat_jit = {}

        def flush_batch(n):
            """Flush the n oldest in-flight epochs with their scalar
            vectors pulled in ONE fused transfer: on ~100ms-RTT hosts a
            per-epoch pull serializes the host loop at one RTT per epoch
            even though the device pipelines ahead (r4 product bench: the
            deep path stalled at ~67% of the scan rate).  Batching the
            pull amortizes the RTT over ``pipeline_depth`` epochs."""
            if n <= 1:
                flush_one()
                return
            import jax.numpy as jnp

            if n not in concat_jit:
                import jax

                concat_jit[n] = jax.jit(
                    lambda *xs: jnp.concatenate(xs))
            recs = [inflight[i] for i in range(n)]
            vals, = self._sync(
                concat_jit[n](*[r["scalars"] for r in recs]))
            size = vals.shape[0] // n
            for i in range(n):
                if bool(decision.complete):
                    break               # late stop: rest was rolled back
                flush_one(vals[i * size:(i + 1) * size])

        def flush_one(vals=None):
            nonlocal params, velocities
            rec = inflight.popleft()
            if vals is None:
                vals, = self._sync(rec["scalars"])  # one transfer/epoch
            confs = rec["confs"]
            off, ci = 0, 0
            k = len(rec["train"]) - 1
            with self._timed("decide_s", "decide",
                             epoch=rec["epoch_number"]):
                for _klass, mbs in rec["evals"]:
                    n = len(mbs)
                    losses = vals[off:off + n]
                    nerrs = vals[off + n:off + 2 * n]
                    off += 2 * n
                    for i, mb in enumerate(mbs):
                        self._feed_decision(
                            mb, (losses[i], nerrs[i],
                                 confs[ci] if i == 0 else None))
                    ci += 1
                losses = vals[off:off + k]
                nerrs = vals[off + k:off + 2 * k]
                off += 2 * k
                for i, mb in enumerate(rec["train"][:k]):
                    self._feed_decision(mb, (losses[i], nerrs[i], None))
                self._feed_decision(rec["train"][k],
                                    (vals[off], vals[off + 1], confs[ci]))
            # snapshot gating must be read NOW: an epoch-wired gate
            # (~decision.epoch_ended) is only open while the tail feed's
            # epoch_ended=True is live
            snap = getattr(self.workflow, "snapshotter", None)
            snap_open = snap is not None and not bool(snap.gate_skip)
            snap_due = snap_open and snap.due(decision.epoch_number,
                                              decision.improved)
            decision.epoch_ended.set(False)
            n_eval = sum(len(m) for _, m in rec["evals"])
            self._account(k + 1,
                          sum(mb["size"] for mb in rec["train"]),
                          rec["t0"], True, kind="epoch", n_eval=n_eval)
            if bool(decision.complete):
                # stop discovered (possibly late): recompute the exact
                # stopping state — same recorded inputs, tail update NOT
                # adopted — and discard the speculated epochs' device and
                # host state.  For a clean max_epochs stop the restores
                # are no-ops (the tail was already dispatched un-adopted
                # and nothing was speculated past it).
                if rec["applied_tail"] or inflight:
                    with span("train", "dispatch:rollback",
                              step=int(rec["step_nums"][0])):
                        params, velocities, _, _ = epoch_fn(
                            rec["params_in"], rec["vels_in"],
                            rec["hypers"], dataset, targets,
                            rec["train_idx"], rec["train_bs"],
                            rec["eval_idx"], rec["eval_bs"],
                            rec["base_key"], rec["step_nums"], False)
                    self.stats["dispatches"] += 1
                    inflight.clear()
                self.steps_done = rec["steps_end"]
                if self._lr_adjust is not None:
                    self._lr_adjust.restore_iteration(
                        rec["lr_iter_start"] + k)
                for name, state in rec["prng"].items():
                    prng.get(name).state.bit_generator.state = state
                loader.epoch_number, loader.samples_served = \
                    rec["loader_state"]
            if snap_open:
                snap.epoch_number = decision.epoch_number
                snap.improved = decision.improved
            if snap_due:
                with self._timed("epoch_hook_s", "epoch_hook",
                                 epoch=int(decision.epoch_number)):
                    # the flushed epoch's POST-epoch params: the next
                    # in-flight epoch's inputs, or the live trees (which
                    # for a just-rolled-back stop ARE the recomputed
                    # stopping state).  Deep dispatches never donate, so
                    # the refs are stable — no device copy needed.  The
                    # checkpoint records the epoch's OWN loader/prng
                    # state (captured at its tail), not the pipelined-
                    # ahead live state — resume parity.
                    tags = snap.tags_for(decision.epoch_number,
                                         decision.improved)
                    if tags:
                        post_p = (inflight[0]["params_in"] if inflight
                                  else params)
                        post_v = (inflight[0]["vels_in"] if inflight
                                  else velocities)
                        s = self.snapshot_from_trees(post_p, post_v)
                        s["loader"].update(rec["loader_snap"])
                        s["prng"] = rec["prng"]
                        snap.save_async(s, tags)

        try:
            final_dispatched = False
            while not bool(decision.complete):
                if final_dispatched:
                    # the epoch that must flip complete via max_epochs is
                    # already in flight: drain
                    assert inflight, "decision never completed"
                    flush_one()
                    continue
                t0 = time.perf_counter()
                lr_iter_start = (self._lr_adjust.iteration
                                 if self._lr_adjust is not None else 0)
                rec = self._collect_epoch()
                this_layout = (tuple((kl, len(m)) for kl, m
                                     in rec["evals"]), len(rec["train"]))
                if layout is None:
                    layout = this_layout
                    epoch_fn = self.make_epoch_fn(*layout)
                elif this_layout != layout:
                    raise RuntimeError(
                        f"epoch layout changed mid-training: {layout} "
                        f"-> {this_layout}")
                k = len(rec["train"]) - 1
                # predictable stop: the tail whose epoch hits max_epochs
                # is the last-ever update and is never adopted (matches
                # the segmented path, where Decision flips complete BEFORE
                # the tail update and gd_skip gates it off) — including
                # when resuming with loader.epoch_number already at or
                # past max_epochs - 1
                apply_tail = (rec["epoch_number"] + 1
                              < int(decision.max_epochs))
                final_dispatched = not apply_tail
                mb_len = len(rec["train"][0]["idx"])
                eval_mbs = [mb for _, ms in rec["evals"] for mb in ms]
                rec.update(
                    t0=t0, applied_tail=apply_tail,
                    lr_iter_start=lr_iter_start,
                    params_in=params, vels_in=velocities,
                    hypers=put(self._epoch_hypers(k, apply_tail)),
                    train_idx=put(np.stack(
                        [mb["idx"] for mb in rec["train"]])),
                    train_bs=put(np.array(
                        [mb["size"] for mb in rec["train"]], np.int32)),
                    eval_idx=put(
                        np.stack([mb["idx"] for mb in eval_mbs])
                        if eval_mbs
                        else np.zeros((0, mb_len), np.int32)),
                    eval_bs=put(np.array(
                        [mb["size"] for mb in eval_mbs], np.int32)),
                    base_key=put(gen.jax_base_key()),
                    step_nums=np.arange(self.steps_done,
                                        self.steps_done + k + 1,
                                        dtype=np.int32))
                with span("train", "dispatch:epoch", step=self.steps_done,
                          steps=k + 1):
                    params, velocities, scal, confs = epoch_fn(
                        params, velocities, rec["hypers"], dataset,
                        targets, rec["train_idx"], rec["train_bs"],
                        rec["eval_idx"], rec["eval_bs"], rec["base_key"],
                        rec["step_nums"], apply_tail)
                self.steps_done += k + 1
                rec.update(scalars=scal, confs=confs,
                           steps_end=self.steps_done,
                           prng={name: copy.deepcopy(
                               s.state.bit_generator.state)
                               for name, s in prng._streams.items()},
                           loader_state=(int(loader.epoch_number),
                                         int(loader.samples_served)),
                           # the state a snapshot of THIS epoch must
                           # record: its tail position and its composed
                           # shuffle order (the next epoch's shuffle has
                           # not run yet — it happens lazily on the next
                           # _advance)
                           loader_snap={
                               "epoch_number": rec["epoch_number"],
                               "samples_served": int(
                                   loader.samples_served),
                               "last_minibatch": True,
                               "shuffled_indices": np.array(
                                   loader._shuffled_indices)})
                inflight.append(rec)
                # let the pipeline FILL to 2x depth, then flush depth
                # epochs with one batched pull — steady state pays one
                # RTT per ``pipeline_depth`` epochs while keeping at
                # least depth epochs in flight
                if len(inflight) >= 2 * self.pipeline_depth:
                    flush_batch(self.pipeline_depth)
            self.writeback(params, velocities)
        finally:
            loader.indices_only = was_indices_only
            # see _run_segmented's finally for the rationale
            self._drain_snapshots(suppress=_sys.exc_info()[0] is not None)
