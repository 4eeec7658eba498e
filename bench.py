"""Benchmark harness: AlexNet fused-train-step throughput on the attached
chip (BASELINE.md north-star metric).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N}

Protocol (unsoftened AlexNet — VERDICT r1 item 3):
  - full 1000-class fc8 (the real AlexNet head);
  - 1024 resident training images (227x227x3) + 128 validation;
  - FRESH minibatch indices every step, drawn by driving the Loader state
    machine exactly like ``FusedTrainer.run`` does — the gather/input path
    varies per step and per epoch (reshuffle), nothing is cached;
  - the whole timed window is ONE ``lax.scan`` dispatch of STEPS train
    steps (the FusedTrainer's own scan path) — one executable launch, so
    the number measures device math, not per-dispatch link latency; the
    headline is the MEDIAN of three independently-timed windows
    (``elapsed_s_runs`` records all three);
  - a jax.profiler trace of a post-timing scan lands in ``bench_profile/``.

``vs_baseline`` divides by 500 img/s — the widely published cuDNN-Caffe
AlexNet training throughput on a K40, standing in for the reference's own
number, which is unobtainable here (BASELINE.md: reference mount empty, no
network).  Update BASELINE.json.published when a real number lands.

Timing barrier: the timed window ends by PULLING VALUES to the host (last
loss + one element of every updated param) — a barrier that is correct on
any backend: a value cannot arrive before the work that produces it.

Self-validation (VERDICT r2 item 1): the JSON line carries
``flops_per_step`` (analytic, from the built layer shapes — convention:
MACs x 2 for every conv/GEMM, backward = 2x forward for weighted layers,
i.e. train = 3x forward; elementwise/pool/LRN ops are not counted),
``xla_flops_per_step`` (XLA's own cost model for the compiled step, a
cross-check on the analytic number), ``tflops_per_sec``, ``mfu_vs_peak``
(against a bf16 peak table keyed on ``device_kind`` — a chip missing from
the table is an error, and so is a platform other than ``tpu``: this
protocol measures the chip and does not fall back), and ``loss_untrained`` /
``loss_first`` / ``loss_last``; the bench FAILS if any timed loss is
non-finite or the timed tail is not well below the untrained starting
loss (the tail alone may oscillate at convergence — STEPS steps over the
resident set is dozens of epochs).

``python bench.py --samples`` instead measures the BASELINE configs 0-3
finals (MNIST / CIFAR / MnistAE / Kohonen at their default sample configs)
and prints one JSON line per config — the numbers recorded in BASELINE.md's
"Measured" column.

``python bench.py --fused-elementwise`` runs the SAME headline protocol
with ``root.common.engine.fused_elementwise`` on — the conv1/conv2
bias+ReLU+LRN+maxpool block (and its backward) as one single-pass Pallas
kernel (znicz_tpu/pallas_fused_block.py).  The JSON line records the flag;
a with/without pair on the same host is the BASELINE.md "Fused elementwise
block" comparison.

``python bench.py --wire`` instead microbenchmarks the v3 comms codec
(znicz_tpu/parallel/wire.py) on an MNIST-shaped update payload: one JSON
line with bytes/update, encode+decode ms and ratio vs the v2
pickle wire, per wire dtype (f32/bf16/int8) plus the zlib'd params
broadcast — the wire-cost record that rides the trajectory files
alongside MFU (ISSUE 3).

``python bench.py --seq`` gates variable-length serving (ISSUE 15) in
one JSON line: the 2-D (batch x seq) bucket ladder vs a single-max-len
ladder on the charlm transformer under a skewed-short mixed-length
stream — goodput in REAL tokens/s (FAILS below 2x), warmup compiles ==
rungs x seq_rungs with zero recompiles over the stream, and a
bit-exact masked-parity probe co-batched with varying same-rung
neighbors.

``python bench.py --generate`` gates autoregressive generation serving
(ISSUE 16) in one JSON line: the prefill/decode KV-cache path with
continuous batching vs a naive re-prefill-per-token oracle driven over
the SAME server's scoring plane (FAILS below 10x tokens/s, with the
generation path's p99 inter-token latency no worse than the oracle's
per-token p99), a per-decoded-token bit-exactness probe (the probe's
logits streamed back BIT-IDENTICAL across co-batched rounds of varying
neighbor content, its tokens identical down to the solo run — each
token a pure function of its own prompt), and the zero-recompile proof
over the mixed prompt-length/generation-length stream (warmup compiles
== scoring buckets + the paged prefill/decode/copy executable family,
nothing after).

``python bench.py --prefix`` gates the paged-KV upgrades (ISSUE 19) in
one JSON line: a seeded shared-system-prompt stream must prefill <=
0.5x the prompt tokens of a prefix-cache-off run of the SAME stream
with bit-exact decoded outputs between the two; a long-prompt barrage
co-batched with paced decoders must hold the decoders' p99 inter-token
latency within 1.5x of the no-barrage band (chunked prefill bounds the
per-tick prefill work); on-device sampling must ship <= 1/64 of the
logits path's per-tick reply bytes with bit-identical greedy tokens;
and the whole mixed stream must recompile NOTHING, both jit caches
gated by strict equality.

``python bench.py --serve`` gates the dynamic-batching inference service
(znicz_tpu/serving/, ISSUE 4) in one JSON line: interleaved sequential-
batch-1 vs coalesced-saturation throughput (FAILS below 3x, measured
WITH admission control enabled), paced-load p99 vs 2x(max_delay +
in-stream measured batch service time), an interleaved admission-on/off
p50 overhead gate at the same operating point (FAILS above 2% — ISSUE
6), and a zero-recompiles-after-warmup proof over a mixed-size request
stream (bucket-ladder jit cache).  All gates are relative to same-host,
same-phase measurements, so they are TPU-independent.

``python bench.py --fleet`` gates the replica-fleet serving plane
(znicz_tpu/serving/balancer.py, ISSUE 12) in one JSON line: a
3-replica fleet behind the health-checked balancer under a seeded
kill-and-restart timetable must lose ZERO acknowledged requests
(ledger: accepted == replied + refused), keep goodput within band of a
fault-free window measured in the same process, complete a canary
rollover triggered MID-chaos with every reply's generation stamp
consistent with the wave, and auto-roll-back a forced
parity-regression canary with the fleet still serving the old
generation bit-exactly.

``python bench.py --shard`` gates pod-scale sharded serving
(znicz_tpu/serving/model.py mesh mode, ISSUE 13) on 8 virtual CPU
devices in one JSON line: per-device shard shapes exact (rows/dp on
every data-axis device, staged AND computed), zero recompiles across a
mixed-size stream on the dp-snapped ladder, per-rung parity vs the
single-device reference (tight numerical band — reduction tiling is
layout-dependent; 0 ULP batch-independence WITHIN each mesh), the
default 1x1 config byte-identical to single-device serving, and a
{data:4}-vs-{data:2,model:2} layout comparison (recorded; TPU protocol
in BASELINE.md).

``python bench.py --telemetry`` gates the unified telemetry layer
(znicz_tpu/telemetry/, ISSUE 5): interleaved enabled/disabled best-of
windows of the real fused training loop; FAILS if spans + hot-loop
metrics cost more than 2% per step.

``python bench.py --legacy`` re-runs the round-1 protocol (100-class head,
256 resident images, FIXED minibatch indices) so the two protocols can be
compared on the same host/build (ADVICE r2: the recorded r1 vs r2 numbers
came from different local runs and were not comparable).

``python bench.py --stream`` measures the streaming pipeline
(loader/streaming.py, VERDICT r3 item 1) in one JSON line with four parts:

  - ``value``: u8-HBM-resident throughput — the SAME scan protocol over a
    28x-tiled u8 dataset (28,672 images) whose **float32 form (17.7 GB)
    exceeds the chip's HBM**; it trains entirely from HBM because storage
    stays uint8 with the decode fused into the gather.  ``pct_of_resident``
    compares against a resident-f32 window timed in the same process —
    the ">=90% of resident" gate.
  - ``staged``: true host->device streaming — segments assembled on the
    host (native row gather) and shipped per dispatch, double-buffered by
    async dispatch.  Steady state obeys
    ``img/s = min(compute_img_s, H2D_bytes_per_s / bytes_per_sample)``;
    the JSON carries the MEASURED link bandwidth and the bandwidth needed
    to be compute-bound, so the number says by itself whether the host
    link or the chip binds.
  - ``decode``: the file-fed route's third roofline term (VERDICT r4
    item 1) — measured JPEG decode+resize rate through the training
    gather path (ImageFileSource), serial AND with the decode pool
    (loader/ingest.py), over synthetic 256x256 JPEGs resized to the
    network input.  ``roofline_img_s_3term`` =
    ``min(compute, link_bw/bytes_per_sample, decode_pooled)`` — the
    steady-state rate an image-FILE-fed training run sustains on this
    host; ``decode_bound`` says whether decode is the binding term.
  - the tiled content repeats 1024 base images, so the loss-descent
    self-check stays valid; the gather/decode path sees the full 28,672-row
    array (physically 4.4 GB of HBM), which is what is being measured.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

K40_ALEXNET_IMG_S = 500.0   # documented stand-in (see module docstring)

#: VERDICT r5 item 7 floors: the headline protocol FAILS below this MFU
#: (silent perf regressions must fail the bench, not pass unnoticed).
#: Applies only to the unmodified headline — labeled variants
#: (--batch/--master-bf16/
#: --fused-elementwise) report without the gate so a measured negative
#: can still be recorded.
MFU_FLOOR = 0.37
HEADLINE_GUARDS = True      # cleared by variant CLI flags in __main__

BATCH = 128
STEPS = 200     # one scan dispatch; long enough to amortize the final host
                # sync over the window; warmup is one full same-length
                # scan (compile reuse)
N_TRAIN = 1024
N_VALID = 128
N_CLASSES = 1000
PROFILE_DIR = "bench_profile"

#: dense bf16 peak TFLOP/s per chip, keyed by substrings of
#: ``jax.devices()[0].device_kind`` (public spec-sheet numbers).  The first
#: matching row wins; no match is an error, never a default.
PEAK_TFLOPS_BF16 = [
    (("v6",), 918.0),                  # v6e / Trillium
    (("v5", "lite"), 197.0),           # v5e ("TPU v5 lite")
    (("v5e",), 197.0),
    (("v5",), 459.0),                  # v5p
    (("v4",), 275.0),
    (("v3",), 123.0),
    (("v2",), 46.0),
]


def peak_tflops(device_kind: str) -> float:
    kind = device_kind.lower()
    for needles, peak in PEAK_TFLOPS_BF16:
        if all(n in kind for n in needles):
            return peak
    raise SystemExit(f"bench: device kind {device_kind!r} is not in "
                     f"PEAK_TFLOPS_BF16 — add its published peak (with "
                     f"the source) before measuring on it")


def analytic_train_flops(workflow, batch: int) -> int:
    """Analytic flops for ONE train step of the built workflow, from the
    actual initialized layer shapes.  Convention (stated in the module
    docstring): 2 flops per MAC; backward = 2x forward for every weighted
    layer (one GEMM/conv for d_input, one for d_weights) -> train = 3x
    forward MACs x 2.  Elementwise/pool/LRN/loss flops are excluded (<1%
    for AlexNet-class nets)."""
    from znicz_tpu.all2all import All2All
    from znicz_tpu.conv import Conv

    fwd_macs = 0
    for f in workflow.forwards:
        if isinstance(f, Conv):
            b, oh, ow, k = f.output.shape
            c = f.input.shape[-1]
            fwd_macs += batch * oh * ow * k * f.ky * f.kx * c
        elif isinstance(f, All2All):
            out_n = f.output_samples_number
            in_n = int(np.prod(f.input.shape[1:]))
            fwd_macs += batch * out_n * in_n
    return int(fwd_macs * 2 * 3)


def xla_flops(step, *args):
    """XLA's own cost model for the compiled step (best-effort; None when
    the platform/jax version does not expose it)."""
    try:
        cost = step.lower(*args).compile().cost_analysis()
        return int(cost["flops"]) if cost and "flops" in cost else None
    except Exception as exc:
        print(f"xla cost_analysis unavailable: {exc!r}", file=sys.stderr)
        return None


def _build_bench_workflow(legacy: bool = False):
    """The bench's AlexNet workflow + FusedTrainer (shared by the headline
    and --stream protocols)."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root

    prng.seed_all(1013)
    root.common.engine.precision = "bfloat16"   # params fp32, MXU bf16
    # velocities stored bf16 (r4): halves optimizer-state HBM traffic in
    # the fc update fusions; update math stays f32 and the semantics are
    # parity-tested (tests/test_fused.py bf16_state_dtype cases)
    root.common.engine.state_dtype = "bfloat16"
    root.alexnet.loader.minibatch_size = BATCH
    root.alexnet.loader.n_train = 2 * BATCH if legacy else N_TRAIN
    root.alexnet.loader.n_valid = BATCH if legacy else N_VALID
    root.alexnet.loader.n_classes = 100 if legacy else N_CLASSES
    root.alexnet.decision.max_epochs = 10_000   # bench drives steps itself

    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples.alexnet import AlexNetWorkflow

    wf = AlexNetWorkflow()
    wf.initialize(device=None)
    return wf, FusedTrainer(wf)


def _make_materialize():
    """Build the materialize closure: forces completion by pulling VALUES
    to the host in one fused transfer (see module docstring)."""
    import jax

    @jax.jit
    def probe(params, losses):
        import jax.numpy as jnp

        vals = [jnp.sum(losses).astype(jnp.float32)]
        for layer in params.values():
            for arr in layer.values():
                vals.append(arr[(0,) * arr.ndim].astype(jnp.float32))
        return jnp.stack(vals)

    def materialize(params, losses):
        return float(np.asarray(probe(params, losses))[0])

    return materialize


def main(legacy: bool = False) -> None:
    from znicz_tpu.core import prng

    import jax

    from znicz_tpu.loader.base import TRAIN

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: no TPU chip — jax.devices()[0] is "
                         f"{dev.platform} ({dev.device_kind}).  The default "
                         f"protocol measures the chip and does not fall "
                         f"back; run it through the chip tool.")
    peak = peak_tflops(dev.device_kind)
    wf, trainer = _build_bench_workflow(legacy)
    scan = trainer.make_train_scan()
    params = trainer.extract_params()
    vels = trainer.extract_velocities()
    dataset = wf.loader.original_data.devmem
    targets = wf.loader.original_labels.devmem
    # the scan takes per-step hypers rows (LR-schedule support);
    # the bench uses constant hypers
    hypers_mat = trainer.tiled_hypers(STEPS)

    wf.loader.indices_only = True     # the scan gathers on device itself

    def draw_minibatches(n):
        """n fresh TRAIN minibatches from the loader state machine (epoch
        boundaries reshuffle, exactly as in training) -> stacked index
        matrix + batch sizes.  ``legacy`` freezes the first minibatch
        (the r1 protocol's fixed-indices softening)."""
        idx, bs = [], []
        while len(idx) < n:
            wf.loader.run()
            if wf.loader.minibatch_class == TRAIN:
                idx.append(np.array(wf.loader.minibatch_indices.mem,
                                    np.int32))
                bs.append(wf.loader.minibatch_size)
        if legacy:
            idx = [idx[0]] * n
            bs = [bs[0]] * n
        return np.stack(idx), np.asarray(bs, np.int32)

    base_key = prng.get("bench").jax_base_key()

    def steps_from(start):
        return np.arange(start, start + STEPS, dtype=np.int32)

    materialize = _make_materialize()

    flops_step = analytic_train_flops(wf, BATCH)
    # warmup at the SAME scan length so the timed call reuses the compile
    idx_mat, bs_vec = draw_minibatches(STEPS)
    params, vels, ms, _conf = scan(params, vels, hypers_mat, dataset, targets,
                            idx_mat[:, :], bs_vec, base_key, steps_from(0))
    materialize(params, ms[0])
    warmup_losses = [float(l) for l in np.asarray(ms[0])]
    # XLA's cost model counts the scan (while-loop) body ONCE, so the
    # lowered scan's flops ARE the per-step flops
    xla_flops_step = xla_flops(
        scan, params, vels, hypers_mat, dataset, targets, idx_mat, bs_vec,
        base_key, steps_from(0))

    # three independently-timed windows, each restarted from the SAME
    # post-warmup state (device copies; the timed scans donate the
    # copies).  Restarting matters: letting the windows keep training
    # (800+ steps over 1024 resident images) drives the net into
    # bf16-overflow territory — the bench's own NaN check caught that.
    # The MEDIAN is the headline — robust to a one-off host hiccup.
    import jax.numpy as jnp

    base_params = jax.tree_util.tree_map(jnp.copy, params)
    base_vels = jax.tree_util.tree_map(jnp.copy, vels)
    runs = []
    losses_per_run = []
    for r in range(3):
        idx_mat, bs_vec = draw_minibatches(STEPS)
        p = jax.tree_util.tree_map(jnp.copy, base_params)
        v = jax.tree_util.tree_map(jnp.copy, base_vels)
        t0 = time.perf_counter()        # ~1ms of copies may drain in-queue
        p, v, ms, _conf = scan(p, v, hypers_mat, dataset, targets,
                        idx_mat, bs_vec, base_key, steps_from(STEPS))
        materialize(p, ms[0])
        runs.append(time.perf_counter() - t0)
        losses_per_run.append(ms[0])
    elapsed = float(np.median(runs))
    ms = (losses_per_run[int(np.argsort(runs)[1])],)

    # the timed window must be REAL training: every loss finite, and the
    # trajectory (warmup start -> timed tail) clearly descending.  The tail
    # alone may sit on a converged plateau (STEPS steps over N_TRAIN
    # resident images = dozens of epochs), so the decrease is asserted
    # against the untrained starting loss, with margin.
    losses = [float(l) for l in np.asarray(ms[0])]
    assert all(np.isfinite(l) for l in losses), f"non-finite loss: {losses}"
    tail = float(np.mean(losses[-10:]))
    assert tail < 0.5 * warmup_losses[0], (
        f"training did not progress: start {warmup_losses[0]:.4f} -> "
        f"timed tail mean {tail:.4f}")

    # post-timing profiler trace (never perturbs the measurement above)
    with jax.profiler.trace(PROFILE_DIR):
        params, vels, ms, _conf = scan(params, vels, hypers_mat, dataset,
                                       targets, idx_mat, bs_vec, base_key,
                                       steps_from(3000))
        materialize(params, ms[0])
    print(f"profiler trace -> {PROFILE_DIR}/", file=sys.stderr)

    img_s = BATCH * STEPS / elapsed
    tflops = flops_step * STEPS / elapsed / 1e12
    from znicz_tpu.core.config import root as _root

    print(json.dumps({
        "metric": ("alexnet_imagenet_train_throughput_legacy_r1_protocol"
                   if legacy else
                   "alexnet_imagenet_train_throughput" +
                   ("" if BATCH == 128 else f"_batch{BATCH}_variant")),
        "value": round(img_s, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(img_s / K40_ALEXNET_IMG_S, 3),
        "batch": BATCH, "steps": STEPS, "elapsed_s": round(elapsed, 4),
        "elapsed_s_runs": [round(r, 4) for r in runs],
        "flops_per_step": flops_step,
        "xla_flops_per_step": xla_flops_step,
        "flops_convention": "2*MACs, train=3x fwd, conv+GEMM only",
        "tflops_per_sec": round(tflops, 2),
        "device_kind": dev.device_kind,
        "platform": dev.platform,
        "peak_tflops_bf16": peak,
        "mfu_vs_peak": round(tflops / peak, 4),
        "mfu_floor": MFU_FLOOR if (not legacy and HEADLINE_GUARDS)
        else None,
        "fused_elementwise": bool(
            _root.common.engine.get("fused_elementwise", False)),
        "fused_tail": bool(_root.common.engine.get("fused_tail", False)),
        "compute_dtype": str(trainer.compute_dtype),
        "loss_untrained": round(warmup_losses[0], 4),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
    }))
    # VERDICT r5 item 7 floors, enforced AFTER the JSON line so a tripped
    # guard never destroys the measurement record it complains about (the
    # protocol explicitly wants negatives recorded), and via raise (not
    # assert) so ``python -O`` cannot strip the gate.
    if not trainer.compute_confusion:
        raise SystemExit(
            "confusion accumulation must stay ON in the bench protocol "
            "(the fused path sums it on device — bench.py measures that "
            "cost)")
    if not legacy and HEADLINE_GUARDS:
        mfu = tflops / peak
        if mfu < MFU_FLOOR:
            raise SystemExit(
                f"headline MFU {mfu:.4f} fell below the {MFU_FLOOR} floor "
                f"on {dev.device_kind} — a silent perf regression; "
                "investigate before re-recording (BASELINE.md ratchet)")


#: --product: min seconds between on-best snapshot saves (see the inline
#: comment at the assignment site)
SNAPSHOT_MIN_INTERVAL_S = 90.0


def product_main(epochs: int = 40) -> None:
    """``--product``: the PRODUCT path's throughput — ``FusedTrainer.run``
    driving the real AlexNetWorkflow (loader state machine, Decision,
    snapshotter gating, LR plumbing) at the bench protocol scale, NOT the
    raw scan (VERDICT r3 item 2: 'the hot loop IS the product').

    Two sync profiles measured in one process, BOTH with the snapshotter
    ACTIVE (gated on improvement, saving to a tmp dir — r5: the async
    writer serves it without stalling either path; VERDICT r4 item 4):
      - ``deep``: pipeline_depth>1 — whole epochs dispatched ahead, one
        fused metric pull per pipeline_depth epochs, snapshots written
        at flush boundaries by the background worker;
      - ``segmented``: default per-segment sync, snapshots handed to the
        same worker at epoch ends.

    ``warm_img_per_sec`` (compile-excluded, from the trainer's own stats)
    is the comparable number; the JSON also carries the wall total and
    the snapshot-writer counters (written / coalesced)."""
    import tempfile

    from znicz_tpu.core.config import root as _root

    results = {}
    for mode in ("deep", "segmented"):
        _root.common.engine.scan_chunk = 16
        _root.common.engine.pipeline_depth = 8 if mode == "deep" else 1
        wf, trainer = _build_bench_workflow()
        n_epochs = epochs if mode == "deep" else max(8, epochs // 2)
        _root.alexnet.decision.max_epochs = n_epochs
        wf.decision.max_epochs = n_epochs
        snap_dir = tempfile.mkdtemp(prefix="bench_snap_")
        wf.snapshotter.directory = snap_dir
        wf.snapshotter.compression = "raw"    # gzip of 300 MB would
        # dominate the writer's wall time on one core
        # each on-best save pulls the full ~300 MB param+velocity set
        # device->host and shares the host link with the training loop's
        # own transfers — rate-limit best-saves like an operator would
        wf.snapshotter.min_save_interval_s = SNAPSHOT_MIN_INTERVAL_S
        t0 = time.time()
        try:
            trainer.run()
            snapshots_on_disk = len(os.listdir(snap_dir))
        finally:
            import shutil

            shutil.rmtree(snap_dir, ignore_errors=True)
        stats = dict(trainer.stats)
        results[mode] = {
            "warm_img_per_sec": stats["warm_img_per_sec"],
            "img_per_sec_incl_compile": stats["img_per_sec"],
            "train_steps": stats["train_steps"],
            "epochs": n_epochs,
            "wall_s": round(time.time() - t0, 2),
            "pipeline_depth": trainer.pipeline_depth,
            "scan_chunk": trainer.scan_chunk,
            "final_train_loss": round(
                wf.decision.epoch_metrics[2]["loss"], 4),
            "snapshots_written": wf.snapshotter.async_saves_written,
            "snapshots_coalesced": wf.snapshotter.async_saves_coalesced,
            "snapshots_on_disk": snapshots_on_disk,
        }
        assert np.isfinite(results[mode]["final_train_loss"])
        # r4 weak #3 closure gates: the fast (deep) configuration now
        # checkpoints, and the segmented+snapshotter mode is no longer
        # collapsed by the writeback+pickle stall
        assert results[mode]["snapshots_written"] > 0, mode
    print(json.dumps({
        "metric": "alexnet_product_path_train_throughput",
        "value": results["deep"]["warm_img_per_sec"],
        "unit": "images/sec/chip",
        "vs_baseline": round(
            results["deep"]["warm_img_per_sec"] / K40_ALEXNET_IMG_S, 3),
        "epochs": epochs, "batch": BATCH,
        "snapshot_min_interval_s": SNAPSHOT_MIN_INTERVAL_S,
        "deep": results["deep"],
        "segmented_with_snapshotter": results["segmented"],
    }))


#: --stream protocol knobs
N_STREAM_TILE = 28     # 28 * 1024 = 28,672 u8 images in HBM; their f32
                       # form (28,672 * 618 KB = 17.7 GB) EXCEEDS v5e HBM
N_HOST_TILE = 8        # host-staged dataset: 8,192 u8 images (1.27 GB RAM)
STAGE_CHUNK = 8        # train steps per staged segment (1024 samples)
STAGE_SEGMENTS = 3     # timed staged segments
N_DECODE_JPG = 192     # synthetic JPEGs for the decode-rate term
N_DECODE_MEASURE = 128  # rows decoded per timed decode window
CHECK_LOSS = True      # False only for tiny-shape smoke runs (tests)


def stream_main() -> None:
    """The --stream protocol (module docstring): u8-HBM-residency at
    beyond-f32-HBM dataset scale, plus true host->device staging with a
    measured link-bandwidth roofline."""
    from znicz_tpu.core import prng

    import jax
    import jax.numpy as jnp

    wf, trainer = _build_bench_workflow()
    scan = trainer.make_train_scan()
    materialize = _make_materialize()
    loader = wf.loader
    dataset_f32 = loader.original_data.devmem
    labels_dev = loader.original_labels.devmem
    base_key = prng.get("bench").jax_base_key()
    rng = np.random.default_rng(1013)

    def draw_idx(n_steps, n_total):
        """Epoch-shuffled minibatch index rows over [0, n_total)."""
        out, perm = [], np.array([], np.int32)
        while len(out) < n_steps:
            if len(perm) < BATCH:
                perm = rng.permutation(n_total).astype(np.int32)
            out.append(perm[:BATCH])
            perm = perm[BATCH:]
        return np.stack(out)

    def copies(tree):
        return jax.tree_util.tree_map(jnp.copy, tree)

    hypers = trainer.tiled_hypers(STEPS)
    bs_vec = np.full(STEPS, BATCH, np.int32)
    steps0 = np.arange(STEPS, dtype=np.int32)
    # data layout is [test | valid | train] (AlexNetLoader), so TRAIN rows
    # start after the eval split — all protocols sample the train region,
    # exactly like main()'s loader-driven indices
    n_eval = int(dataset_f32.shape[0]) - N_TRAIN

    # ---- warmup + resident-f32 reference window (the main protocol) ------
    params, vels = trainer.extract_params(), trainer.extract_velocities()
    params, vels, ms, _ = scan(params, vels, hypers, dataset_f32,
                               labels_dev,
                               n_eval + draw_idx(STEPS, N_TRAIN),
                               bs_vec, base_key, steps0)
    materialize(params, ms[0])
    loss_untrained = float(np.asarray(ms[0])[0])
    base_params, base_vels = copies(params), copies(vels)
    t0 = time.perf_counter()
    p, v, ms, _ = scan(copies(base_params), copies(base_vels), hypers,
                       dataset_f32, labels_dev,
                       n_eval + draw_idx(STEPS, N_TRAIN),
                       bs_vec, base_key, steps0 + STEPS)
    materialize(p, ms[0])
    resident_img_s = BATCH * STEPS / (time.perf_counter() - t0)

    # ---- u8-resident: tiled u8 dataset whose f32 form exceeds HBM --------
    lo = float(jnp.min(dataset_f32))
    hi = float(jnp.max(dataset_f32))
    scale = np.float32((hi - lo) / 255.0)
    shift = np.float32(lo)
    trainer._decode_params = (scale, shift)   # read at (re)trace for u8

    @jax.jit
    def quantize_tile(d, l):
        # tile the TRAIN region only — every index into the tiled array
        # is then a train row
        u8 = jnp.clip(jnp.round((d[n_eval:] - shift) / scale),
                      0, 255).astype(jnp.uint8)
        return (jnp.tile(u8, (N_STREAM_TILE, 1, 1, 1)),
                jnp.tile(l[n_eval:], (N_STREAM_TILE,)))

    big_u8, big_labels = quantize_tile(dataset_f32, labels_dev)
    n_big = N_TRAIN * N_STREAM_TILE
    dataset_f32_gb = n_big * int(np.prod(dataset_f32.shape[1:])) * 4 / 2**30
    dataset_u8_gb = dataset_f32_gb / 4
    # compile for the u8 dtype/shape, then median-of-3 timed windows
    p, v, ms, _ = scan(copies(base_params), copies(base_vels), hypers,
                       big_u8, big_labels, draw_idx(STEPS, n_big), bs_vec,
                       base_key, steps0)
    materialize(p, ms[0])
    runs, losses_per_run = [], []
    for _ in range(3):
        idx = draw_idx(STEPS, n_big)
        p, v = copies(base_params), copies(base_vels)
        t0 = time.perf_counter()
        p, v, ms, _ = scan(p, v, hypers, big_u8, big_labels, idx, bs_vec,
                           base_key, steps0 + STEPS)
        materialize(p, ms[0])
        runs.append(time.perf_counter() - t0)
        losses_per_run.append([float(x) for x in np.asarray(ms[0])])
    u8_elapsed = float(np.median(runs))
    u8_img_s = BATCH * STEPS / u8_elapsed
    losses = losses_per_run[int(np.argsort(runs)[1])]
    assert all(np.isfinite(x) for x in losses), losses
    tail = float(np.mean(losses[-10:]))
    # CHECK_LOSS False is for tiny-shape smoke runs only (a handful of
    # steps cannot halve the loss); the real protocol always asserts
    assert not CHECK_LOSS or tail < 0.5 * loss_untrained, \
        (loss_untrained, tail)
    del big_u8, big_labels, p, v

    # ---- host-staged streaming + link roofline ---------------------------
    host_f32 = loader.original_data.mem[n_eval:]     # train rows only
    host_u8_base = np.clip(np.round((host_f32 - shift) / scale),
                           0, 255).astype(np.uint8)
    host_u8 = np.tile(host_u8_base, (N_HOST_TILE, 1, 1, 1))
    host_labels = np.tile(np.asarray(
        loader.original_labels.mem[n_eval:], np.int32), N_HOST_TILE)
    n_host = len(host_u8)
    bytes_per_sample = int(np.prod(host_u8.shape[1:]))

    # measured link bandwidth: one timed 64 MB u8 put, value-materialized
    probe_buf = host_u8.reshape(-1)[:64 << 20]
    x = jax.device_put(probe_buf)
    float(jnp.sum(x[:: 1 << 20].astype(jnp.float32)))      # warm the path
    t0 = time.perf_counter()
    x = jax.device_put(probe_buf)
    float(jnp.sum(x[:: 1 << 20].astype(jnp.float32)))
    h2d_gbps = len(probe_buf) / (time.perf_counter() - t0) / 2**30

    seg_hypers = trainer.tiled_hypers(STAGE_CHUNK)
    seg_bs = np.full(STAGE_CHUNK, BATCH, np.int32)
    local_idx = np.arange(STAGE_CHUNK * BATCH, dtype=np.int32).reshape(
        STAGE_CHUNK, BATCH)

    def stage(flat):
        return (jax.device_put(np.take(host_u8, flat, axis=0)),
                jax.device_put(np.take(host_labels, flat)))

    def staged_window(p, v, n_segments, step0):
        for s in range(n_segments):
            flat = draw_idx(STAGE_CHUNK, n_host).reshape(-1)
            buf, lab = stage(flat)
            p, v, ms, _ = scan(p, v, seg_hypers, buf, lab, local_idx,
                               seg_bs, base_key,
                               np.arange(step0 + s * STAGE_CHUNK,
                                         step0 + (s + 1) * STAGE_CHUNK,
                                         dtype=np.int32))
        materialize(p, ms[0])
        return [float(x) for x in np.asarray(ms[0])]

    p, v = copies(base_params), copies(base_vels)
    staged_window(p, v, 1, 0)                    # compile the staged shape
    p, v = copies(base_params), copies(base_vels)
    t0 = time.perf_counter()
    staged_losses = staged_window(p, v, STAGE_SEGMENTS, STAGE_CHUNK)
    staged_s = time.perf_counter() - t0
    staged_img_s = BATCH * STAGE_CHUNK * STAGE_SEGMENTS / staged_s
    assert all(np.isfinite(x) for x in staged_losses), staged_losses

    # ---- decode rate: the roofline's third term (VERDICT r4 item 1) ------
    # A synthetic JPEG tree at ImageNet-ish geometry (256x256 source files
    # decoded+resized to the network's 227x227 input), measured through
    # the same ImageFileSource gather path training uses — serial and
    # with the decode pool (loader/ingest.py).
    import shutil
    import tempfile

    from PIL import Image

    from znicz_tpu.loader.ingest import measure_decode_rate
    from znicz_tpu.loader.streaming import ImageFileSource

    sample_hw = tuple(dataset_f32.shape[1:3])
    jpg_dir = tempfile.mkdtemp(prefix="znicz_bench_jpg_")
    try:
        n_jpg = N_DECODE_JPG
        img_rng = np.random.default_rng(7)
        paths = []
        for i in range(n_jpg):
            p = os.path.join(jpg_dir, f"{i}.jpg")
            Image.fromarray(img_rng.integers(
                0, 255, (256, 256, 3), dtype=np.uint8)).save(p, quality=85)
            paths.append(p)
        src = ImageFileSource(paths, np.zeros(n_jpg, np.int32),
                              target_shape=sample_hw, workers=0)
        decode_serial = measure_decode_rate(src, n=N_DECODE_MEASURE)
        pooled_src = ImageFileSource(paths, np.zeros(n_jpg, np.int32),
                                     target_shape=sample_hw)  # default pool
        decode_pooled = measure_decode_rate(pooled_src, n=N_DECODE_MEASURE)
        decode_workers = (pooled_src._pool.workers
                          if pooled_src._pool is not None else 1)
    finally:
        shutil.rmtree(jpg_dir, ignore_errors=True)

    needed_gbps = u8_img_s * bytes_per_sample / 2**30
    link_img_s = h2d_gbps * 2**30 / bytes_per_sample
    dev = jax.devices()[0]
    print(json.dumps({
        "metric": "alexnet_stream_train_throughput_u8_resident",
        "value": round(u8_img_s, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(u8_img_s / K40_ALEXNET_IMG_S, 3),
        "batch": BATCH, "steps": STEPS,
        "elapsed_s_runs": [round(r, 4) for r in runs],
        "dataset_images": n_big,
        "dataset_f32_gb": round(dataset_f32_gb, 2),
        "dataset_u8_gb": round(dataset_u8_gb, 2),
        "resident_f32_img_s": round(resident_img_s, 2),
        "pct_of_resident": round(100 * u8_img_s / resident_img_s, 1),
        "loss_untrained": round(loss_untrained, 4),
        "loss_last": round(losses[-1], 4),
        "staged": {
            "img_s": round(staged_img_s, 2),
            "images": BATCH * STAGE_CHUNK * STAGE_SEGMENTS,
            "host_dataset_images": n_host,
            "bytes_per_sample_u8": bytes_per_sample,
            "h2d_gbps_measured": round(h2d_gbps, 4),
            "h2d_gbps_for_compute_bound": round(needed_gbps, 3),
            "link_bound": bool(h2d_gbps < needed_gbps),
            "roofline_img_s_at_measured_bw": round(
                min(u8_img_s, link_img_s), 2),
        },
        "decode": {
            # file-fed route (ImageFileSource): JPEG decode+resize to the
            # network input, through the training gather path
            "img_s_serial": round(decode_serial, 2),
            "img_s_pooled": round(decode_pooled, 2),
            "workers": int(decode_workers),
            "pool_speedup": round(decode_pooled / max(decode_serial, 1e-9),
                                  2),
            # min(compute, link, decode): the steady-state rate an
            # image-FILE-fed training run can sustain on this host
            "roofline_img_s_3term": round(
                min(u8_img_s, link_img_s, decode_pooled), 2),
            "decode_bound": bool(decode_pooled < min(u8_img_s, link_img_s)),
        },
        "device_kind": getattr(dev, "device_kind", "unknown"),
    }))


#: --wire payload: the MNIST sample's trainable shapes (the same layer
#: set the tests' master/slave runs ship every update), repeated TILE
#: times so the codec is timed on a multi-MB payload, not cache noise
WIRE_LAYER_SHAPES = {"fc1": {"weights": (784, 100), "bias": (100,)},
                     "fc2": {"weights": (100, 10), "bias": (10,)}}
WIRE_TILE = 8
WIRE_REPS = 5


def wire_main() -> None:
    """``--wire``: comms-codec microbench.  Builds a synthetic update
    (seeded normal deltas at MNIST layer shapes x WIRE_TILE + metrics
    with a confusion matrix), measures encode+decode wall time and
    bytes-on-wire per wire dtype against the v2 single-pickle wire, and
    the zlib'd f32 params broadcast (the cold path).  Pure host-side —
    no accelerator, no sockets — so the JSON line isolates codec cost
    from transport and compute."""
    import pickle
    import time as _time

    from znicz_tpu.parallel import wire

    rng = np.random.default_rng(1013)
    deltas = {}
    for t in range(WIRE_TILE):
        for name, layer in WIRE_LAYER_SHAPES.items():
            deltas[f"{name}_t{t}"] = {
                k: (rng.normal(0, 0.01, shape) * 0.1).astype(np.float32)
                for k, shape in layer.items()}
    metrics = {"loss": 1.0, "n_err": 3,
               "confusion": rng.integers(0, 60, (10, 10))}
    raw_bytes = sum(a.nbytes for layer in deltas.values()
                    for a in layer.values())

    def timed(fn):
        best = float("inf")
        for _ in range(WIRE_REPS):
            t0 = _time.perf_counter()
            out = fn()
            best = min(best, _time.perf_counter() - t0)
        return out, best * 1e3          # min over reps, in ms

    def update_msg(enc_deltas):
        return {"cmd": "update", "id": "bench", "job_id": 1,
                "deltas": enc_deltas, "metrics": metrics}

    # the v2 baseline: one pickle blob of the raw f32 update
    blob, pickle_enc_ms = timed(
        lambda: pickle.dumps(update_msg(deltas),
                             pickle.HIGHEST_PROTOCOL))
    _, pickle_dec_ms = timed(lambda: pickle.loads(blob))
    v2_bytes = len(blob)

    results = {"pickle_v2": {
        "bytes_per_update": v2_bytes,
        "encode_ms": round(pickle_enc_ms, 3),
        "decode_ms": round(pickle_dec_ms, 3),
        "ratio_vs_pickle_v2": 1.0}}
    for dtype in ("float32", "bfloat16", "int8"):
        enc = wire.DeltaEncoder(dtype)

        def encode():
            frames, _ = wire.encode_message(update_msg(enc.encode(deltas)))
            return frames
        frames, enc_ms = timed(encode)
        frames = [bytes(f) for f in frames]     # what the peer receives
        (dec, _), dec_ms = timed(lambda: wire.decode_message(frames))
        n_bytes = sum(len(f) for f in frames)
        err = max(float(np.max(np.abs(dec["deltas"][name][k]
                                      - deltas[name][k])))
                  for name in deltas for k in deltas[name])
        results[dtype] = {
            "bytes_per_update": n_bytes,
            "encode_ms": round(enc_ms, 3),
            "decode_ms": round(dec_ms, 3),
            "ratio_vs_pickle_v2": round(v2_bytes / n_bytes, 3),
            "max_abs_err": float(f"{err:.3e}"),
        }

    # cold path: the f32 params broadcast, zlib'd (fresh-init weights
    # compress well; converged ones less — this records the mechanism)
    bcast = {"job_id": 1, "params": deltas}
    frames, enc_ms = timed(
        lambda: wire.encode_message(bcast, compress="zlib")[0])
    frames = [bytes(f) for f in frames]
    _, dec_ms = timed(lambda: wire.decode_message(frames))
    plain = sum((bytes(f).__len__())
                for f in wire.encode_message(bcast)[0])
    results["params_zlib"] = {
        "bytes": sum(len(f) for f in frames),
        "encode_ms": round(enc_ms, 3),
        "decode_ms": round(dec_ms, 3),
        "ratio_vs_raw": round(plain / sum(len(f) for f in frames), 3),
    }

    print(json.dumps({
        "metric": "wire_codec_bytes_per_update_int8",
        "value": results["int8"]["bytes_per_update"],
        "unit": "bytes",
        "vs_baseline": results["int8"]["ratio_vs_pickle_v2"],
        "payload_f32_mb": round(raw_bytes / 2**20, 3),
        "tensors": sum(len(v) for v in deltas.values()),
        "wire": results,
    }))
    # the acceptance floor (ISSUE 3): int8 must beat the pickle wire by
    # >= 3.5x on this payload; enforced AFTER the JSON line so a tripped
    # gate never destroys the measurement it complains about
    if results["int8"]["ratio_vs_pickle_v2"] < 3.5:
        raise SystemExit(
            f"int8 wire ratio {results['int8']['ratio_vs_pickle_v2']} "
            "fell below the 3.5x floor vs the v2 pickle wire")


#: --agg protocol knobs (ISSUE 10): the O(slaves) -> O(fanout) proof.
#: Phase 1 (structural, scripted): 8 protocol-exact scripted slaves run
#: the same seeded job/update stream once as a STAR (all 8 on the
#: master) and once through a fanout-2 RELAY TREE (8 -> 4 -> 2 ->
#: master); the master's wire.Codec counts bytes-into-master and
#: messages decoded.  Both must drop to <= 0.35x the star's — the ~4x
#: the two aggregated tiers owe.  Phase 2 (semantic, seeded MNIST): a
#: real 4-slave training once as a star and once through a 2-level
#: tree (2 leaf relays under 1 mid relay) must land in the same
#: converged band — error-feedback residuals held at the leaves AND
#: per-relay, so quantization behavior is unchanged.  Gates fire AFTER
#: the JSON line so a trip never destroys the measurement.
AGG_SLAVES = 8
AGG_FANOUT = 2
AGG_RATIO_CEIL = 0.35
AGG_CONV_BAND = 25.0        # |star - tree| err_pct tolerance (async
#                             replicas differ run to run regardless of
#                             topology; both must land converged)
AGG_ERR_CEIL = 70.0
AGG_BASE_PORT = 18600

#: --agg phase 3 (ISSUE 11): the ELASTIC scenario — the same 8-slave
#: fanout-2 tree with quorum + bounded/weighted staleness on, run once
#: fault-free and once with a seeded SubtreePreempter killing mid-relay
#: 0's WHOLE subtree (1 mid + 2 leaf relays + 4 slaves = half the
#: fleet, >= the 1/3 the acceptance demands) mid-run and restarting it
#: ~5 s later.  Gates: the preempted run lands inside the fault-free
#: band, apply progress CONTINUES during the kill window, and the job
#: ledger balances (jobs_done + requeues + refusals == dispatched — no
#: gradient lost or double-applied across the re-plan).  The denser
#: job stream needs a calmer lr: at the sample default 0.1, 8 fully-
#: async replicas over 20 minibatches/epoch diverge with or without
#: the elastic knobs.
ELASTIC_MIN_SLAVES = 3
ELASTIC_STALENESS_BOUND = 50
ELASTIC_LR = 0.03
ELASTIC_EPOCHS = 5
ELASTIC_N_TRAIN = 1200
ELASTIC_SEED = 23
ELASTIC_BAND = 25.0


def _agg_make_workflow(tag: str, max_epochs: int = 3,
                       n_train: int = 300):
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = max_epochs
    root.common.dirs.snapshots = f"/tmp/bench_agg/{tag}"
    wf = mnist.MnistWorkflow()
    wf.initialize(device=None)
    return wf


def _agg_scripted_slave(endpoint: str, sid: str, register_msg: dict,
                        shapes: dict, errors: list) -> None:
    """A protocol-exact scripted slave: registers, pulls jobs, replies
    tiny constant deltas of the right shapes — all the wire traffic of
    a real slave with none of the compute, so the byte/decode counters
    measure TOPOLOGY, not this host's training speed."""
    import zmq

    from znicz_tpu.parallel import wire

    ctx = zmq.Context.instance()
    sock = ctx.socket(zmq.REQ)
    sock.setsockopt(zmq.RCVTIMEO, 60_000)
    sock.setsockopt(zmq.LINGER, 0)
    sock.connect(endpoint)

    def rpc(msg):
        frames, _ = wire.encode_message(dict(msg, id=sid))
        sock.send_multipart(frames)
        return wire.decode_message(sock.recv_multipart())[0]

    try:
        rep = rpc(register_msg)
        if not rep.get("ok"):
            raise RuntimeError(f"register refused: {rep.get('error')}")
        while True:
            rep = rpc({"cmd": "job"})
            if rep.get("done"):
                return
            if "job" not in rep:
                time.sleep(0.005)           # wait / transient
                continue
            job = rep["job"]
            deltas = None
            if rep.get("train"):
                deltas = {name: {k: np.full(shape, 1e-6, np.float32)
                                 for k, shape in layer.items()}
                          for name, layer in shapes.items()}
            if "minibatches" in job:
                metrics = [{"loss": 1.0, "n_err": 0}
                           for _ in job["minibatches"]]
            else:
                metrics = {"loss": 1.0, "n_err": 0}
            rpc({"cmd": "update", "job_id": rep["job_id"],
                 "deltas": deltas, "metrics": metrics})
    except Exception as exc:                # surface thread crashes
        errors.append((sid, repr(exc)))
        raise
    finally:
        sock.close(0)


def _agg_scripted_run(endpoints, master_endpoint, tag):
    """Drive AGG_SLAVES scripted slaves against ``endpoints[i]`` (the
    star: all the master; the tree: their leaf relays); returns the
    master server after completion."""
    import threading

    from znicz_tpu.network_common import handshake_request
    from znicz_tpu.server import Server

    # plentiful jobs (30 TRAIN minibatches/epoch for 8 slaves) so the
    # stream stays dense enough for pairs to FORM at every tier — the
    # regime the tree exists for; a trickle would measure idle polling
    wf = _agg_make_workflow(f"{tag}_m", max_epochs=2, n_train=1800)
    server = Server(wf, endpoint=master_endpoint, job_timeout=60.0)
    register = handshake_request(wf)
    shapes = {f.name: {k: tuple(a.shape) for k, a in f.params().items()}
              for f in wf.forwards if f.has_weights}
    errors: list = []
    threads = [threading.Thread(
        target=_agg_scripted_slave,
        args=(endpoints[i], f"{tag}{i}", register, shapes, errors),
        daemon=True) for i in range(AGG_SLAVES)]
    for t in threads:
        t.start()
    server.serve()
    for t in threads:
        t.join(timeout=60)
    if errors:
        raise SystemExit(f"scripted slaves crashed: {errors}")
    if any(t.is_alive() for t in threads):
        raise SystemExit("scripted slaves hung")
    if not bool(wf.decision.complete):
        raise SystemExit("scripted run did not complete")
    return server


def _agg_real_fleet(endpoints, master_endpoint, tag):
    """A real seeded 4-slave MNIST training over whatever topology sits
    between ``endpoints`` and the master; returns (server, err_pct)."""
    import threading

    from znicz_tpu.client import Client
    from znicz_tpu.server import Server

    wf = _agg_make_workflow(f"{tag}_m")
    server = Server(wf, endpoint=master_endpoint, job_timeout=60.0)
    slaves = [Client(_agg_make_workflow(f"{tag}_s{i}"),
                     endpoint=endpoints[i], slave_id=f"{tag}w{i}")
              for i in range(len(endpoints))]
    errors: list = []

    def worker(s):
        try:
            s.run()
        except BaseException as e:
            errors.append((s.slave_id, repr(e)))
            raise

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in slaves]
    for t in threads:
        t.start()
    server.serve()
    for t in threads:
        t.join(timeout=120)
    if errors:
        raise SystemExit(f"slaves crashed: {errors}")
    dec = wf.decision
    if not bool(dec.complete):
        raise SystemExit(f"{tag}: training did not complete")
    return server, float(dec.epoch_metrics[1]["err_pct"])


def _agg_elastic_run(tag, port, preempt: bool):
    """One elastic 8-slave fanout-2 tree run (ISSUE 11): quorum +
    bounded/weighted staleness on; with ``preempt``, a seeded
    :class:`SubtreePreempter` kills mid-relay 0's whole subtree
    mid-run and restarts it.  Returns ``(server, err_pct, marks)`` —
    ``marks`` holds the counter snapshots taken at kill and restart,
    the degraded-window progress evidence."""
    import threading

    from znicz_tpu.client import Client
    from znicz_tpu.core.config import root
    from znicz_tpu.parallel.chaos import (FaultSchedule, RelayHarness,
                                          SubtreePreempter)
    from znicz_tpu.parallel.relay import plan_tree
    from znicz_tpu.server import Server

    master_ep = f"tcp://127.0.0.1:{port}"
    plan = plan_tree(AGG_SLAVES, AGG_FANOUT, master_ep,
                     base_port=port + 1)
    from znicz_tpu.samples import mnist  # noqa: F401 -- the import
    # applies the sample's config DEFAULTS; reading prev_lr before it
    # would capture None and the restore below would poison the tree
    prev_lr = root.mnist.get("learning_rate")
    root.mnist.learning_rate = ELASTIC_LR
    preempter = None
    harnesses = []
    try:
        wf = _agg_make_workflow(f"{tag}_m", max_epochs=ELASTIC_EPOCHS,
                                n_train=ELASTIC_N_TRAIN)
        # job_timeout is the reap CEILING and must sit well inside the
        # down window: the epoch tail waits on the dead subtree's
        # in-flight jobs, and only the reaper frees it
        server = Server(wf, endpoint=master_ep, job_timeout=2.5,
                        slave_ttl=1.5, min_slaves=ELASTIC_MIN_SLAVES,
                        staleness_bound=ELASTIC_STALENESS_BOUND,
                        staleness_weight=True)
        harnesses = [RelayHarness(r["upstream"], r["bind"],
                                  relay_id=f"{tag}-r{i}",
                                  recv_timeout=1.0, max_reconnects=60,
                                  child_ttl=1.5)
                     for i, r in enumerate(plan["relays"])]
        for h in harnesses:
            h.start()
        wfs = [_agg_make_workflow(f"{tag}_s{i}",
                                  max_epochs=ELASTIC_EPOCHS,
                                  n_train=ELASTIC_N_TRAIN)
               for i in range(AGG_SLAVES)]
        clients = [Client(wfs[i], endpoint=plan["slave_endpoints"][i],
                          slave_id=f"{tag}w{i}")
                   for i in range(AGG_SLAVES)]
        errors, threads = [], {}

        def start_slave(i):
            def worker(c):
                try:
                    c.run(recv_timeout=1.0, max_reconnects=80,
                          backoff_base=0.05, backoff_cap=0.4,
                          connect_retries=80)
                except BaseException as e:
                    errors.append((c.slave_id, repr(e)))
                    raise
            t = threading.Thread(target=worker, args=(clients[i],),
                                 daemon=True)
            threads[i] = t
            t.start()

        for i in range(AGG_SLAVES):
            start_slave(i)
        marks = {}
        server_thread = threading.Thread(
            target=server.serve, kwargs={"linger": 6.0}, daemon=True)
        server_thread.start()
        if preempt:
            mid_bind = plan["relays"][0]["bind"]
            sub_relays = [0] + [j for j, r in enumerate(plan["relays"])
                                if r["upstream"] == mid_bind]
            sub_binds = {plan["relays"][j]["bind"] for j in sub_relays}
            sub_slaves = [i for i, ep
                          in enumerate(plan["slave_endpoints"])
                          if ep in sub_binds]

            def snap():
                return {"jobs_done": int(server.jobs_done),
                        "aggregated": int(server.aggregated_updates),
                        "weighted": int(server.weighted_applies),
                        "members": int(server.member_count())}

            def kill():
                for i in sub_slaves:
                    clients[i].preempt()
                for i in sub_slaves:
                    threads[i].join(timeout=10)
                for j in sub_relays:
                    harnesses[j].kill(timeout=10)
                marks["kill"] = snap()

            def restart():
                marks["restart"] = snap()
                for j in sub_relays:
                    harnesses[j].start()
                for i in sub_slaves:
                    clients[i] = Client(
                        wfs[i], endpoint=plan["slave_endpoints"][i],
                        slave_id=f"{tag}w{i}")
                    start_slave(i)

            marks["preempted"] = {"relays": len(sub_relays),
                                  "slaves": len(sub_slaves)}
            preempter = SubtreePreempter(
                FaultSchedule(ELASTIC_SEED),
                [("mid0-subtree", kill, restart)],
                kill_s=(0.2, 0.6), down_s=(4.5, 5.5))
            deadline = time.time() + 180
            while server.jobs_done < 12 and time.time() < deadline \
                    and server_thread.is_alive():
                time.sleep(0.05)
            if server.jobs_done < 12 or not server_thread.is_alive():
                # a dead/stalled warm-up must fail AS a warm-up
                # failure, not fire the kill anyway and trip the
                # progress gate with a misleading message
                raise SystemExit(
                    f"{tag}: warm-up failed before the preemption "
                    f"(jobs_done={server.jobs_done}, master alive="
                    f"{server_thread.is_alive()}) — enlarge the "
                    "workload or the deadline")
            preempter.start()       # seeded timetable, anchored mid-run
        server_thread.join(timeout=600)
        if server_thread.is_alive():
            raise SystemExit(f"{tag}: master hung")
        if preempter is not None and not preempter.join(60):
            raise SystemExit(f"{tag}: preempter hung")
        for t in threads.values():
            t.join(timeout=60)
        if errors:
            raise SystemExit(f"{tag}: slaves crashed: {errors}")
        if any(t.is_alive() for t in threads.values()):
            raise SystemExit(f"{tag}: slaves hung")
        dec = wf.decision
        if not bool(dec.complete):
            raise SystemExit(f"{tag}: training did not complete")
        return server, float(dec.epoch_metrics[1]["err_pct"]), marks
    finally:
        root.mnist.learning_rate = prev_lr
        if preempter is not None:
            preempter.stop()
        for h in harnesses:
            try:
                h.kill(timeout=5)
            except Exception:
                pass


def agg_main() -> None:
    """``--agg``: the relay-tree aggregation gate (ISSUE 10).  One JSON
    line with the star-vs-tree byte/decode ratios and the convergence
    band; FAILS (after printing) when bytes-into-master or the master's
    decode count at fanout 2 with 8 scripted slaves exceeds 0.35x the
    star's, or when the tree's seeded MNIST run leaves the star's
    convergence band."""
    from znicz_tpu.parallel.relay import Relay, plan_tree

    port = AGG_BASE_PORT

    # -- phase 1: scripted star ------------------------------------------------
    star_master = f"tcp://127.0.0.1:{port}"
    star = _agg_scripted_run([star_master] * AGG_SLAVES, star_master,
                             "star")
    star_bytes = int(star.bytes_in)
    star_decodes = int(star.codec.messages_in)

    # -- phase 1: scripted fanout-2 tree (8 -> 4 -> 2 -> master) ---------------
    tree_master = f"tcp://127.0.0.1:{port + 1}"
    plan = plan_tree(AGG_SLAVES, AGG_FANOUT, tree_master,
                     base_port=port + 2)
    relays = [Relay(r["upstream"], r["bind"], relay_id=f"agg-r{i}",
                    fanout=AGG_FANOUT).start()
              for i, r in enumerate(plan["relays"])]
    try:
        tree = _agg_scripted_run(plan["slave_endpoints"], tree_master,
                                 "tree")
    finally:
        for r in relays:
            r.stop()
    tree_bytes = int(tree.bytes_in)
    tree_decodes = int(tree.codec.messages_in)
    bytes_ratio = tree_bytes / max(1, star_bytes)
    decode_ratio = tree_decodes / max(1, star_decodes)

    # -- phase 2: seeded MNIST convergence, star vs 2-level tree ---------------
    conv_star_master = f"tcp://127.0.0.1:{port + 20}"
    srv_star, err_star = _agg_real_fleet(
        [conv_star_master] * 4, conv_star_master, "cstar")
    conv_tree_master = f"tcp://127.0.0.1:{port + 21}"
    mid = f"tcp://127.0.0.1:{port + 22}"
    leaf_a = f"tcp://127.0.0.1:{port + 23}"
    leaf_b = f"tcp://127.0.0.1:{port + 24}"
    relays = [Relay(conv_tree_master, mid, relay_id="agg-mid").start(),
              Relay(mid, leaf_a, relay_id="agg-leaf-a").start(),
              Relay(mid, leaf_b, relay_id="agg-leaf-b").start()]
    try:
        srv_tree, err_tree = _agg_real_fleet(
            [leaf_a, leaf_a, leaf_b, leaf_b], conv_tree_master, "ctree")
    finally:
        for r in relays:
            r.stop()

    # -- phase 3: the elastic scenario (ISSUE 11) ------------------------------
    srv_ff, err_ff, _ = _agg_elastic_run("eff", port + 40, preempt=False)
    srv_pre, err_pre, marks = _agg_elastic_run("epre", port + 60,
                                               preempt=True)
    ledger = srv_pre.jobs_ledger()

    print(json.dumps({
        "metric": "agg_bytes_into_master_ratio",
        "value": round(bytes_ratio, 4),
        "unit": "tree/star",
        "vs_baseline": round(1.0 / max(bytes_ratio, 1e-9), 2),
        "slaves": AGG_SLAVES, "fanout": AGG_FANOUT,
        "star": {"bytes_in": star_bytes, "decodes": star_decodes,
                 "jobs_done": star.jobs_done,
                 "updates": star.updates_received},
        "tree": {"bytes_in": tree_bytes, "decodes": tree_decodes,
                 "jobs_done": tree.jobs_done,
                 "updates": tree.updates_received,
                 "aggregated": tree.aggregated_updates,
                 "levels": plan["levels"]},
        "decode_ratio": round(decode_ratio, 4),
        "convergence": {"star_err_pct": err_star,
                        "tree_err_pct": err_tree,
                        "tree_aggregated":
                            srv_tree.aggregated_updates,
                        "star_aggregated":
                            srv_star.aggregated_updates},
        "elastic": {
            "fault_free_err_pct": err_ff,
            "preempted_err_pct": err_pre,
            "min_slaves": ELASTIC_MIN_SLAVES,
            "staleness_bound": ELASTIC_STALENESS_BOUND,
            "preempted": marks.get("preempted"),
            "kill": marks.get("kill"), "restart": marks.get("restart"),
            "stale_refused": srv_pre.stale_refused,
            "weighted_applies": srv_pre.weighted_applies,
            "replans": srv_pre.replans,
            "preemptions_ridden": srv_pre.preemptions_ridden,
            "reregistrations": srv_pre.reregistrations,
            "ledger": ledger,
        },
    }))
    # gates AFTER the JSON line (ISSUE 10 acceptance)
    if bytes_ratio > AGG_RATIO_CEIL:
        raise SystemExit(
            f"bytes-into-master ratio {bytes_ratio:.3f} exceeds the "
            f"{AGG_RATIO_CEIL} ceiling (star {star_bytes}, tree "
            f"{tree_bytes})")
    if decode_ratio > AGG_RATIO_CEIL:
        raise SystemExit(
            f"master decode-count ratio {decode_ratio:.3f} exceeds the "
            f"{AGG_RATIO_CEIL} ceiling (star {star_decodes}, tree "
            f"{tree_decodes})")
    if err_star >= AGG_ERR_CEIL or err_tree >= AGG_ERR_CEIL:
        raise SystemExit(
            f"convergence left the band: star {err_star}%, tree "
            f"{err_tree}% (ceiling {AGG_ERR_CEIL}%)")
    if abs(err_star - err_tree) >= AGG_CONV_BAND:
        raise SystemExit(
            f"star-vs-tree convergence gap {abs(err_star - err_tree):.1f}"
            f" exceeds the {AGG_CONV_BAND}-point band "
            f"(star {err_star}%, tree {err_tree}%)")
    if srv_tree.aggregated_updates <= 0 or tree.aggregated_updates <= 0:
        raise SystemExit("tree runs produced no aggregated updates — "
                         "the relays were not in the path")
    # -- elastic gates (ISSUE 11 acceptance) -----------------------------------
    if err_pre >= AGG_ERR_CEIL or err_ff >= AGG_ERR_CEIL:
        raise SystemExit(
            f"elastic convergence left the band: fault-free {err_ff}%, "
            f"preempted {err_pre}% (ceiling {AGG_ERR_CEIL}%)")
    if abs(err_pre - err_ff) >= ELASTIC_BAND:
        raise SystemExit(
            f"preempted run left the fault-free band: "
            f"|{err_pre} - {err_ff}| >= {ELASTIC_BAND}")
    k, r = marks.get("kill"), marks.get("restart")
    if not k or not r:
        raise SystemExit("the preemption never executed — no kill/"
                         "restart marks recorded")
    if r["jobs_done"] <= k["jobs_done"]:
        raise SystemExit(
            f"no apply progress during the kill window: jobs_done "
            f"{k['jobs_done']} -> {r['jobs_done']}")
    if r["aggregated"] <= k["aggregated"] and \
            r["weighted"] <= k["weighted"]:
        raise SystemExit(
            "no aggregated/weighted applies during the kill window: "
            f"{k} -> {r}")
    if not ledger["balanced"]:
        raise SystemExit(
            f"job ledger does not balance after the re-plan — a job "
            f"was lost or double-counted: {ledger}")
    if srv_pre.preemptions_ridden < 1 or srv_pre.replans < 1:
        raise SystemExit(
            "the elastic machinery never engaged: preemptions_ridden="
            f"{srv_pre.preemptions_ridden}, replans={srv_pre.replans}")
    if srv_pre.weighted_applies <= 0:
        raise SystemExit("no staleness-weighted applies in a fully-"
                         "async 8-slave run — the stamps are not "
                         "flowing")


#: --serve protocol knobs (ISSUE 4).  All gates are RELATIVE to numbers
#: measured on the same host in the same process, so they hold on this
#: TPU-less throttled-CPU container and transfer unchanged to a TPU
#: host.  The model is the MNIST MLP widened to 2048 so batch COMPUTE
#: genuinely dominates per-request codec/python overhead — the regime
#: dynamic batching exists for (a toy-thin model measures only
#: per-request overhead, which coalescing cannot amortize by design).
SERVE_MAX_BATCH = 32
SERVE_MAX_DELAY_MS = 20.0
SERVE_HIDDEN = 2048
SERVE_BASELINE_S = 2.0      # sequential batch-1 window
SERVE_LOAD_S = 3.0          # saturation (closed-loop) window
SERVE_PACED_S = 4.0         # paced-latency (open-loop) window
SERVE_MIXED_S = 1.5         # mixed-size recompile-proof window
SERVE_WINDOW = 2 * SERVE_MAX_BATCH   # closed-loop in-flight requests
SERVE_PACED_FRACTION = 0.7  # latency SLO operating point (of capacity;
#                             0.7 leaves headroom for this container's
#                             cgroup-share swings between the capacity
#                             measurement and the paced phase)
SERVE_LATENCY_ROUNDS = 3    # best-of rounds (shared-host load spikes)
SERVE_THROUGHPUT_FLOOR = 3.0
SERVE_P99_MULT = 2.0
#: admission/deadline overhead gate (ISSUE 6): interleaved
#: admission-ON/OFF paced windows at the same 0.7x operating point,
#: best-of per variant (telemetry-gate discipline: a cgroup load spike
#: must hit both variants, and it can only ever slow a window down).
#: The ON policy is a generous rate limit + fair queueing: the full
#: token-bucket/DRR/deadline code path runs on every request without
#: refusing any (refusals would change the measured population).
SERVE_ADMISSION_S = 2.0     # paced window per variant per round
SERVE_ADMISSION_ROUNDS = 4  # bounded interleaved pairs, early-exit
SERVE_ADMISSION_PCT = 2.0   # p50 overhead ceiling, percent


def _build_serve_workflow():
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root

    prng.reset(1013)
    root.mnist.loader.n_train = 512
    root.mnist.loader.n_valid = 64
    root.mnist.loader.minibatch_size = 64
    root.mnist.layers = [SERVE_HIDDEN, 10]

    from znicz_tpu.samples import mnist

    try:
        wf = mnist.MnistWorkflow()
    finally:
        root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    return wf


def serve_main() -> None:
    """``--serve``: the dynamic-batching inference gates (ISSUE 4), one
    JSON line.  Four phases against the SAME model on the same host:

      - sequential batch-1 baseline: a ``max_batch=1`` service driven
        one request at a time — the per-request service rate with no
        coalescing and no added delay;
      - saturation throughput: ``SERVE_WINDOW`` (= 2 x max_batch, the
        ping-pong design point: one full batch computing, one filling)
        single-row requests kept in flight CLOSED-LOOP — rows/s at
        offered load saturating max_batch (gate: >= 3x sequential);
      - paced latency: OPEN-LOOP arrivals at ``SERVE_PACED_FRACTION``
        of the measured capacity — the operating point a latency SLO is
        quoted at (closed-loop saturation latency is W/lambda, pure
        queueing; no service quotes its SLO at rho=1).  Gate: p99 <=
        2 x (max_delay_ms + batch_ms), where batch_ms is a full
        max_batch-row request's e2e service time measured at idle
        IMMEDIATELY before each round (this container's cgroup CPU
        share swings minute to minute — the bound must be measured
        under the conditions of the phase it bounds); best of
        ``SERVE_LATENCY_ROUNDS`` rounds, since a background load spike
        can only ever slow a round down;
      - mixed-size stream: request sizes sweep 1..max_batch while the
        compile counter is watched — the bucket ladder must absorb
        every shape (gate: ZERO recompiles after warmup, by the trace
        counter AND jax's own jit-cache size).

    Gates are enforced AFTER the JSON line so a tripped gate never
    destroys the measurement record it complains about."""
    import gc
    import time as _time

    from znicz_tpu.serving import (AdmissionPolicy, InferenceClient,
                                   InferenceServer)

    sys.setswitchinterval(1e-3)       # 3 busy threads on a shared core:
    # the default 5ms GIL slice adds multi-ms scheduling jitter straight
    # onto every latency quantile

    wf = _build_serve_workflow()
    sample_shape = tuple(int(d) for d in wf.forwards[0].input.shape[1:])
    rng = np.random.default_rng(1013)
    x1 = rng.normal(0, 1, (1,) + sample_shape).astype(np.float32)
    xb = rng.normal(0, 1, (SERVE_MAX_BATCH,) + sample_shape
                    ).astype(np.float32)

    # ---- both services up front: the sequential baseline and the
    # coalescing service are measured in INTERLEAVED windows (this
    # container's cgroup CPU share swings minute to minute — comparing
    # a quiet-moment baseline against a loaded-moment coalesced run
    # would make the RELATIVE gate noise, not signal; best-of windows
    # per service, since background load only ever slows a window down)
    # breaker OFF on both bench clients (breaker_failures=0): the
    # closed-loop phases deliberately overdrive the queue bound, and a
    # polite client backing off on shed would distort the very offered
    # load the saturation/shed behavior is measured under
    srv1 = InferenceServer(wf, max_batch=1, max_delay_ms=0.0).start()
    cli1 = InferenceClient(srv1.endpoint, timeout=120,
                           breaker_failures=0)
    # admission control ENABLED for every gated phase (ISSUE 6): the
    # rate limit is generous so nothing is refused, but every request
    # pays the token-bucket + fair-queue + deadline bookkeeping — the
    # coalescing and p99 gates must hold WITH the admission path on
    adm_on = AdmissionPolicy(rate_limit=1e9, rate_burst=1e9, fair=True)
    srv = InferenceServer(wf, max_batch=SERVE_MAX_BATCH,
                          max_delay_ms=SERVE_MAX_DELAY_MS,
                          queue_bound=8 * SERVE_MAX_BATCH,
                          admission=adm_on).start()
    compiles_warm = srv.runner.compiles   # every ladder rung compiled
    cli = InferenceClient(srv.endpoint, timeout=120, breaker_failures=0)

    submitted_at = {}

    def drive_closed(duration_s, sizes, lats=None):
        """Closed loop: keep SERVE_WINDOW requests in flight, cycling
        ``sizes`` rows per request; returns (rows, elapsed)."""
        rows = 0
        i = 0
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < duration_s:
            while cli.in_flight < SERVE_WINDOW:
                nrow = sizes[i % len(sizes)]
                i += 1
                rid = cli.submit(x1 if nrow == 1 else np.repeat(
                    x1, nrow, axis=0))
                submitted_at[rid] = _time.perf_counter()
            for rep in cli.collect(0.002):
                t_rep = _time.perf_counter()
                t_sub = submitted_at.pop(rep["req_id"], None)
                if lats is not None and t_sub is not None:
                    lats.append(t_rep - t_sub)
                if rep.get("ok"):
                    rows += rep["y"].shape[0]
        elapsed = _time.perf_counter() - t0
        while cli.in_flight:              # drain the tail — NOT counted:
            for rep in cli.collect(0.01):  # rows finishing after
                submitted_at.pop(rep["req_id"], None)  # `elapsed` froze
                # would inflate the measured rate (the sequential
                # baseline has no such tail to inflate it with)
        return rows, elapsed

    def drive_paced(duration_s, rate_qps, probe_every_s=0.25):
        """Open loop: single-row arrivals paced at ``rate_qps``, with a
        full max_batch-row PROBE request injected every
        ``probe_every_s`` — its e2e RTT is the measured batch service
        time under the exact conditions the latency quantiles are
        measured under (this container's cgroup CPU share is bursty;
        an idle-time batch_ms can be 4x off by the time the phase
        runs).  Returns (single-row latencies, probe latencies),
        seconds."""
        lats = []
        probe_lats = []
        probe_ids = set()
        t0 = _time.perf_counter()
        i = 0
        next_probe = probe_every_s
        while _time.perf_counter() - t0 < duration_s:
            now = _time.perf_counter()
            if now - t0 >= next_probe:
                next_probe += probe_every_s
                rid = cli.submit(xb)
                probe_ids.add(rid)
                submitted_at[rid] = _time.perf_counter()
            elif now - t0 >= i / rate_qps and \
                    cli.in_flight < 4 * SERVE_MAX_BATCH:
                rid = cli.submit(x1)
                submitted_at[rid] = _time.perf_counter()
                i += 1
            for rep in cli.collect(0.001):
                t_rep = _time.perf_counter()
                rid = rep["req_id"]
                t_sub = submitted_at.pop(rid, None)
                if t_sub is None:
                    continue
                (probe_lats if rid in probe_ids else lats).append(
                    t_rep - t_sub)
                probe_ids.discard(rid)
        while cli.in_flight:
            for rep in cli.collect(0.01):
                t_rep = _time.perf_counter()
                rid = rep["req_id"]
                t_sub = submitted_at.pop(rid, None)
                if t_sub is None:
                    continue
                (probe_lats if rid in probe_ids else lats).append(
                    t_rep - t_sub)
                probe_ids.discard(rid)
        return lats, probe_lats

    # ---- phases 1+2, interleaved: sequential baseline vs saturation ------
    for _ in range(20):
        cli1.infer(x1)                    # warm the batch-1 request path
    seq_qps = 0.0
    coalesced_qps = 0.0
    for _ in range(3):
        t0 = _time.perf_counter()
        n = 0
        while _time.perf_counter() - t0 < SERVE_BASELINE_S / 3:
            cli1.infer(x1)
            n += 1
        seq_qps = max(seq_qps, n / (_time.perf_counter() - t0))
        rows, elapsed = drive_closed(SERVE_LOAD_S / 3, sizes=[1])
        coalesced_qps = max(coalesced_qps, rows / elapsed)
    cli1.close()
    srv1.stop()
    occupancy = srv.batcher.occupancy()

    # ---- phase 3: paced latency at the SLO operating point ---------------
    gc.collect()
    gc.freeze()                           # long-lived state out of gen
    gc.disable()                          # scans; no multi-ms GC pauses
    # on the latency quantiles (re-enabled after the phase)
    rounds = []
    try:
        for _ in range(SERVE_LATENCY_ROUNDS):
            lats, probe_lats = drive_paced(
                SERVE_PACED_S, SERVE_PACED_FRACTION * coalesced_qps)
            a = np.asarray(lats) * 1e3
            bms = float(np.median(np.asarray(probe_lats) * 1e3))
            rounds.append({
                "batch_ms": round(bms, 2),
                "p50_ms": round(float(np.percentile(a, 50)), 2),
                "p99_ms": round(float(np.percentile(a, 99)), 2),
                "p99_bound_ms": round(
                    SERVE_P99_MULT * (SERVE_MAX_DELAY_MS + bms), 2),
                "n": len(lats),
            })
            if rounds[-1]["p99_ms"] <= rounds[-1]["p99_bound_ms"]:
                break                     # gate met; no need to re-roll
    finally:
        gc.enable()
    best = min(rounds, key=lambda r: r["p99_ms"] - r["p99_bound_ms"])

    # ---- phase 3b: admission/deadline overhead (interleaved on/off) ------
    adm_off = AdmissionPolicy(enabled=False)
    on_p50: list = []
    off_p50: list = []
    gc.collect()
    gc.disable()
    try:
        for _ in range(SERVE_ADMISSION_ROUNDS):
            for policy, dest in ((adm_off, off_p50), (adm_on, on_p50)):
                srv.batcher.set_admission(policy)
                lats, _ = drive_paced(
                    SERVE_ADMISSION_S,
                    SERVE_PACED_FRACTION * coalesced_qps)
                dest.append(float(np.percentile(
                    np.asarray(lats) * 1e3, 50)))
            if min(on_p50) <= min(off_p50) * (
                    1 + SERVE_ADMISSION_PCT / 100):
                break                     # gate met; stop burning time
    finally:
        gc.enable()
        srv.batcher.set_admission(adm_on)
    admission_overhead_pct = (min(on_p50) / min(off_p50) - 1.0) * 100

    # ---- phase 4: mixed-size stream (bucket-ladder proof) ----------------
    drive_closed(SERVE_MIXED_S,
                 sizes=[1, 2, 3, 5, 8, 13, 21, SERVE_MAX_BATCH, 7, 2, 30])
    recompiles = srv.runner.compiles - compiles_warm
    jit_cache = srv.runner.jit_cache_size()
    stats = srv.stats()
    cli.close()
    srv.stop()

    ratio = coalesced_qps / seq_qps
    print(json.dumps({
        "metric": "serving_coalesced_throughput",
        "value": round(coalesced_qps, 2),
        "unit": "requests/sec",
        "vs_baseline": round(ratio, 3),
        "sequential_batch1_qps": round(seq_qps, 2),
        "hidden_width": SERVE_HIDDEN,
        "max_batch": SERVE_MAX_BATCH,
        "max_delay_ms": SERVE_MAX_DELAY_MS,
        "closed_loop_window": SERVE_WINDOW,
        "mean_occupancy": occupancy if occupancy is None
        else round(occupancy, 4),
        "paced_fraction": SERVE_PACED_FRACTION,
        "latency": best,
        "latency_rounds": rounds,
        "admission": {
            "p50_on_ms": round(min(on_p50), 3),
            "p50_off_ms": round(min(off_p50), 3),
            "overhead_pct": round(admission_overhead_pct, 2),
            "rounds": len(on_p50),
            "overhead_ceiling_pct": SERVE_ADMISSION_PCT,
        },
        "generation": stats["generation"],
        "bucket_hits": stats["batcher"]["bucket_hits"],
        "compiles_after_warmup": compiles_warm,
        "recompiles_mixed_stream": recompiles,
        "jit_cache_size": jit_cache,
        "shed": stats["rejected"],
        "timed_out": stats["timed_out"],
        "throughput_floor": SERVE_THROUGHPUT_FLOOR,
    }))
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if ratio < SERVE_THROUGHPUT_FLOOR:
        failures.append(
            f"coalesced/sequential ratio {ratio:.2f} < "
            f"{SERVE_THROUGHPUT_FLOOR}x")
    if best["p99_ms"] > best["p99_bound_ms"]:
        failures.append(f"p99 {best['p99_ms']} ms > bound "
                        f"{best['p99_bound_ms']} ms "
                        f"(= {SERVE_P99_MULT} x ({SERVE_MAX_DELAY_MS} "
                        f"+ {best['batch_ms']}))")
    if recompiles:
        failures.append(f"{recompiles} recompiles during the mixed-size "
                        "stream (bucket ladder leak)")
    if admission_overhead_pct > SERVE_ADMISSION_PCT:
        failures.append(
            f"admission/deadline path adds "
            f"{admission_overhead_pct:.2f}% p50 at the "
            f"{SERVE_PACED_FRACTION}x operating point "
            f"(ceiling {SERVE_ADMISSION_PCT}%)")
    if failures:
        raise SystemExit("serving gates failed: " + "; ".join(failures))


#: --fleet protocol knobs (ISSUE 12).  Three gates over a real
#: 3-replica fleet behind the ReplicaBalancer, all RELATIVE to
#: same-process fault-free measurements (TPU-independent): (1) a seeded
#: kill-and-restart chaos run loses zero acknowledged requests (ledger
#: accepted == replied + refused) with goodput within band of
#: fault-free, (2) a canary rollover triggered MID-chaos completes with
#: every reply's generation stamp consistent with the wave, (3) a
#: forced parity-regression canary auto-rolls-back with the fleet still
#: serving the old generation bit-exactly.  The model is a thin MNIST
#: MLP — the fleet gates measure COORDINATION (failover, hedging,
#: rollover), not batch compute, so restart warmups must stay cheap on
#: this 1-core host.
FLEET_REPLICAS = 3
FLEET_HIDDEN = 256
FLEET_MAX_BATCH = 8
FLEET_RATE_QPS = 25.0       # open-loop offered load, single-row
FLEET_FAULTFREE_S = 8.0     # fault-free goodput window
FLEET_CHAOS_S = 24.0        # seeded kill/restart + rollover window
FLEET_SETTLE_S = 6.0        # post-chaos drain/heal window
FLEET_SWAP_AT_S = 5.0       # rollover trigger inside the chaos window
FLEET_GOODPUT_BAND = 0.45   # chaos goodput >= band x fault-free (2 of
#                             3 replicas die once each mid-window on a
#                             1-core host whose restarts recompile)
FLEET_SEED = 1207


def _build_fleet_workflow():
    """A thin MNIST MLP, seeded so every call builds BIT-IDENTICAL
    params — three replicas built this way answer bit-exactly alike,
    which is what the parity probes and per-generation oracles ride."""
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root

    prng.reset(1013)
    root.mnist.loader.n_train = 256
    root.mnist.loader.n_valid = 64
    root.mnist.loader.minibatch_size = 64
    root.mnist.layers = [FLEET_HIDDEN, 10]

    from znicz_tpu.samples import mnist

    try:
        wf = mnist.MnistWorkflow()
    finally:
        root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    return wf


def fleet_main() -> None:
    """``--fleet``: the replica-balancer gates (ISSUE 12), one JSON
    line; gates AFTER the line so a trip never destroys the record."""
    import shutil
    import tempfile
    import time as _time

    from znicz_tpu.parallel.chaos import (FaultSchedule, ReplicaHarness,
                                          SubtreePreempter)
    from znicz_tpu.serving import InferenceClient, ReplicaBalancer

    sys.setswitchinterval(1e-3)

    tmp = tempfile.mkdtemp(prefix="znicz_fleet_")
    wf0 = _build_fleet_workflow()
    wf0.snapshotter.directory = tmp
    path_a = wf0.snapshotter.save("fleet_a")
    path_b = os.path.join(tmp, "fleet_b" + path_a[path_a.index("."):])
    shutil.copy(path_a, path_b)     # SAME params, distinct path: the
    # healthy rollover (parity must hold bit-exactly across it)
    for f in wf0.forwards:          # the broken "upgrade": perturbed
        for k, a in f.params().items():
            a.mem = np.asarray(a.map_read()) * np.float32(1.25) \
                + np.float32(0.01)
    path_bad = wf0.snapshotter.save("fleet_bad")

    # canary_p99_mult is WIDE here on purpose: mid-chaos, both old
    # replicas can be down at once, so the freshly-warmed canary
    # absorbs a parked-request burst whose queueing p99 is legitimate
    # load, not a regression — the healthy-wave gate is coordination +
    # PARITY; the p99-regression verdict itself is pinned under
    # controlled timing by the tier-1 scripted-canary test
    balancer = ReplicaBalancer(
        replica_ttl_s=1.2, failover_timeout_s=1.0, failover_tries=4,
        hedge_floor_s=0.4, canary_requests=20, parity_every=3,
        canary_timeout_s=30.0, canary_p99_mult=100.0,
        min_replicas=2).start()

    from znicz_tpu.serving import InferenceServer

    wfs = [_build_fleet_workflow() for _ in range(FLEET_REPLICAS)]
    binds = ["tcp://127.0.0.1:*"] * FLEET_REPLICAS

    def make_factory(i):
        def make():
            return InferenceServer(
                wfs[i], bind=binds[i], snapshot=path_a,
                max_batch=FLEET_MAX_BATCH, max_delay_ms=2.0,
                queue_bound=64, announce=balancer.endpoint,
                replica_id=f"r{i}")
        return make

    harnesses = [ReplicaHarness(make_factory(i))
                 for i in range(FLEET_REPLICAS)]
    for i, h in enumerate(harnesses):
        h.start()
        binds[i] = h.server.endpoint    # restarts rebind the same port
    t0 = _time.perf_counter()
    while balancer.ready_count() < FLEET_REPLICAS:
        if _time.perf_counter() - t0 > 60:
            raise SystemExit("fleet never became ready")
        _time.sleep(0.05)

    cli = InferenceClient(balancer.endpoint, timeout=25.0,
                          resend_after_s=60.0, breaker_failures=0)
    rng = np.random.default_rng(FLEET_SEED)
    x1 = rng.normal(0, 1, (1, 28 * 28)).astype(np.float32)

    infer_rids = set()
    answers: dict = {}              # rid -> (t_wall, ok, gen)
    gen_events: list = []           # (t_wall, gen) of ok replies

    def pump(wait=0.002):
        for rep in cli.collect(wait):
            rid = rep.get("req_id")
            if rid not in infer_rids:
                continue
            if rid in answers:
                raise SystemExit(f"req {rid} answered twice — "
                                 f"exactly-once broken")
            ok = bool(rep.get("ok"))
            answers[rid] = (_time.perf_counter(), ok, rep.get("gen"))
            if ok:
                gen_events.append((_time.perf_counter(), rep["gen"]))

    def drive(duration_s, on_tick=None):
        """Open-loop single-row arrivals at FLEET_RATE_QPS; returns
        (ok replies landed in-window, elapsed)."""
        n0_ok = sum(1 for _, ok, _ in answers.values() if ok)
        t0 = _time.perf_counter()
        i = 0
        while _time.perf_counter() - t0 < duration_s:
            now = _time.perf_counter() - t0
            if now >= i / FLEET_RATE_QPS and cli.in_flight < 256:
                infer_rids.add(cli.submit(x1))
                i += 1
            if on_tick is not None:
                on_tick(now)
            pump()
        elapsed = _time.perf_counter() - t0
        return (sum(1 for _, ok, _ in answers.values() if ok) - n0_ok,
                elapsed)

    def drain(budget_s=20.0):
        t0 = _time.perf_counter()
        while cli.in_flight and _time.perf_counter() - t0 < budget_s:
            pump(0.02)

    # ---- phase 1: fault-free goodput ------------------------------------
    ok_ff, el_ff = drive(FLEET_FAULTFREE_S)
    drain()
    goodput_ff = ok_ff / el_ff
    ledger_ff = balancer.ledger()

    # ---- phase 2: seeded kill/restart chaos + MID-chaos rollover --------
    # r1/r2 each die once on their own seeded timetable while the wave
    # (canary r0) runs; r0 is preempted LATE — after the wave should
    # have promoted — so the heal path (restart -> boot snapshot ->
    # re-swap onto the fleet path) is exercised too
    # r1 and r2 die in SERIALIZED seeded windows (a rolling
    # preemption): overlapping both kills against the canary warm
    # would measure a one-survivor fleet, which the goodput band — not
    # the rollover gate — is the honest judge of
    preempters = [
        SubtreePreempter(FaultSchedule(FLEET_SEED + 1),
                         [("r1", harnesses[1].kill,
                           harnesses[1].restart)],
                         kill_s=(2.0, 5.0), down_s=(2.0, 3.0)),
        SubtreePreempter(FaultSchedule(FLEET_SEED + 2),
                         [("r2", harnesses[2].kill,
                           harnesses[2].restart)],
                         kill_s=(9.0, 12.0), down_s=(2.0, 3.0)),
        SubtreePreempter(FaultSchedule(FLEET_SEED + 3),
                         [("r0", harnesses[0].kill,
                           harnesses[0].restart)],
                         kill_s=(16.0, 19.0), down_s=(2.0, 3.0)),
    ]
    swap_state = {"sent": False, "t_sent": None, "rid": None}

    def maybe_swap(now):
        if not swap_state["sent"] and now >= FLEET_SWAP_AT_S:
            swap_state["sent"] = True
            swap_state["t_sent"] = _time.perf_counter()
            swap_state["rid"] = cli._send({"cmd": "swap",
                                          "path": path_b})

    for p in preempters:
        p.start()
    ok_chaos, el_chaos = drive(FLEET_CHAOS_S, on_tick=maybe_swap)
    for p in preempters:
        p.join(timeout=60)
    # settle: drain the tail, let restarted replicas re-announce and
    # heal onto the promoted path
    t_settle0 = _time.perf_counter()
    drive(FLEET_SETTLE_S)
    drain()
    goodput_chaos = ok_chaos / el_chaos
    ledger_chaos = balancer.ledger()
    history = list(balancer.rollover_history)
    promoted = [h for h in history if h["result"] == "promoted"]
    gens_seen = sorted({g for _, g in gen_events})
    pre_swap_gen2 = [1 for t, g in gen_events
                     if swap_state["t_sent"] is not None
                     and t < swap_state["t_sent"] and g != 1]
    late_old_gen = [1 for t, g in gen_events
                    if t > t_settle0 + FLEET_SETTLE_S * 0.7 and g != 2]
    unanswered = [rid for rid in infer_rids if rid not in answers]
    fleet_stats = balancer.stats()

    # ---- phase 3: forced parity regression must auto-roll-back ----------
    pre_y = cli.result(cli.submit(x1))["y"]
    cli._send({"cmd": "swap", "path": path_bad})
    t0 = _time.perf_counter()
    while not balancer.rollbacks and _time.perf_counter() - t0 < 40:
        r = cli.submit(x1)
        infer_rids.add(r)
        pump(0.01)
    drain()
    regression = balancer.rollover_history[-1] if \
        balancer.rollover_history else {}
    post_y = cli.result(cli.submit(x1))["y"]
    post_gen = cli.result(cli.submit(x1))["gen"]
    bitexact_after_rollback = bool(
        np.array_equal(pre_y, post_y))
    ledger_final = balancer.ledger()

    record = {
        "metric": "fleet_chaos_goodput",
        "value": round(goodput_chaos, 2),
        "unit": "ok_replies/sec",
        "vs_faultfree": round(goodput_chaos / max(goodput_ff, 1e-9), 3),
        "goodput_faultfree": round(goodput_ff, 2),
        "goodput_band": FLEET_GOODPUT_BAND,
        "replicas": FLEET_REPLICAS,
        "rate_qps": FLEET_RATE_QPS,
        "seed": FLEET_SEED,
        "preemptions": sum(p.preemptions for p in preempters),
        "ledger_faultfree": ledger_ff,
        "ledger_chaos": ledger_chaos,
        "ledger_final": ledger_final,
        "unanswered": len(unanswered),
        "gens_seen": gens_seen,
        "pre_swap_gen2_replies": len(pre_swap_gen2),
        "late_old_gen_replies": len(late_old_gen),
        "rollover_history": history,
        "regression": regression,
        "bitexact_after_rollback": bitexact_after_rollback,
        "post_rollback_gen": post_gen,
        "failovers": balancer.failovers,
        "hedges": balancer.hedges,
        "hedge_wins": balancer.hedge_wins,
        "hedge_delay_ms": fleet_stats["hedge_delay_ms"],
        "dup_replies_dropped": balancer.dup_replies_dropped,
        "heals": balancer.heals,
        "replicas_lost": balancer.replicas_lost,
        "parity_checks": balancer.parity_checks,
        "parity_mismatches": balancer.parity_mismatches,
    }
    print(json.dumps(record))
    cli.close()
    balancer.stop()
    for h in harnesses:
        h.kill()
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if not ledger_final["balanced"] or ledger_final["in_flight"]:
        failures.append(f"ledger leaked: {ledger_final}")
    if unanswered:
        failures.append(f"{len(unanswered)} acknowledged requests "
                        f"never answered (no reply, no refusal)")
    if goodput_chaos < FLEET_GOODPUT_BAND * goodput_ff:
        failures.append(
            f"chaos goodput {goodput_chaos:.1f}/s < "
            f"{FLEET_GOODPUT_BAND} x fault-free {goodput_ff:.1f}/s")
    if len(promoted) != 1:
        failures.append(f"expected exactly one promoted rollover "
                        f"mid-chaos, saw {history}")
    if gens_seen and (min(gens_seen) < 1 or max(gens_seen) > 2):
        failures.append(f"generation stamps outside the wave: "
                        f"{gens_seen}")
    if pre_swap_gen2:
        failures.append(f"{len(pre_swap_gen2)} replies stamped the NEW "
                        f"generation before the swap was even sent")
    if late_old_gen:
        failures.append(f"{len(late_old_gen)} replies still stamped "
                        f"the old generation after promote + heal "
                        f"settle")
    if regression.get("result") != "rolled_back":
        failures.append(f"forced parity regression did not auto-roll-"
                        f"back: {regression}")
    if not bitexact_after_rollback:
        failures.append("post-rollback fleet output differs from the "
                        "pre-swap generation (bit-exactness broken)")
    if balancer.parity_mismatches < 1:
        failures.append("the perturbed snapshot produced no parity "
                        "mismatch — the probe path cannot be live")
    shutil.rmtree(tmp, ignore_errors=True)
    if failures:
        raise SystemExit("fleet gates failed: " + "; ".join(failures))


#: --shard protocol knobs (ISSUE 13): the pod-scale sharded-serving
#: gates, run on 8 VIRTUAL CPU devices (znicz_tpu/virtdev.py — the same
#: provisioning conftest/the MULTICHIP dryruns use), so they hold on
#: this TPU-less container and verify STRUCTURE: exact per-device shard
#: shapes, jit-cache hygiene, parity.  Throughput across layouts is
#: recorded but NOT gated — 8 virtual devices time-slice one throttled
#: core, so layout timing here is scheduling noise; the real-TPU
#: protocol lives in BASELINE.md.  The model is the 2048-hidden MNIST
#: MLP (the --serve model): wide enough that the ``model`` axis engages
#: (FusedTrainer.tp_threshold = 1024) and that gemm reduction tiling is
#: genuinely layout-dependent — which is WHY cross-layout parity is a
#: tight numerical band, not 0 ULP: XLA's reduction order changes with
#: the per-device operand shape, the same reason PR 4 pinned the 0-ULP
#: contract per bucket executable.  WITHIN a fixed mesh the 0-ULP
#: batch-independence contract is gated bit-exactly.
SHARD_DEVICES = 8
SHARD_MAX_BATCH = 32
SHARD_HIDDEN = SERVE_HIDDEN
#: cross-layout parity band: max |y_layout - y_single| over a rung,
#: relative to max |y_single| (measured here: ~5e-7..1.1e-6 — f32
#: reduction-order noise over the K=784/2048 contractions; the band
#: leaves ~10x headroom while still failing any real math divergence,
#: which would show up orders of magnitude larger)
SHARD_PARITY_REL = 1e-5
SHARD_LAYOUTS = (("d4", (4, 1)), ("d2m2", (2, 2)))
SHARD_MIXED_SIZES = (1, 2, 3, 5, 8, 13, 21, 32, 7, 2, 30, 16, 4)
SHARD_WINDOW_S = 1.0        # per-layout closed-loop timing window


def shard_main() -> None:
    """``--shard``: the sharded-serving gates (ISSUE 13), one JSON
    line.  Against the SAME workflow, a single-device reference runner
    and one mesh-native runner per layout in ``SHARD_LAYOUTS``:

      - **shard shapes**: for every ladder rung, the staged batch and
        the computed result both hold EXACTLY rows/dp rows on each of
        the dp data-axis devices (``addressable_shards``) — the "no
        gather through device 0" placement proof;
      - **jit hygiene**: warmup compiles exactly one executable per
        rung; a mixed-size request stream (sizes 1..max_batch, padded
        by the dp-snapped ladder) causes ZERO recompiles, by the trace
        counter AND jax's own pjit cache size;
      - **parity**: per rung, the sharded result matches the
        single-device reference within ``SHARD_PARITY_REL`` (see the
        knob comment for why cross-LAYOUT is a band), and the 0-ULP
        batch-independence contract (offset/neighbor/pad independence)
        holds bit-exactly WITHIN each mesh;
      - **mesh 1x1**: a runner built under the default mesh config IS
        the single-device path — results byte-identical to the
        reference runner, rung by rung;
      - **layouts**: {data:4} vs {data:2,model:2} rows/s recorded (not
        gated on this host — see the knob comment).

    Gates are enforced AFTER the JSON line so a tripped gate never
    destroys the measurement record it complains about."""
    import time as _time

    from znicz_tpu.virtdev import provision_cpu_devices

    # BEFORE the first backend init (conftest discipline): this gate
    # verifies sharding STRUCTURE, which needs >= 8 devices regardless
    # of what hardware the host has
    provision_cpu_devices(SHARD_DEVICES)

    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.serving import BucketLadder, ModelRunner

    wf = _build_serve_workflow()
    sample_shape = tuple(int(d) for d in wf.forwards[0].input.shape[1:])
    rng = np.random.default_rng(1013)

    def pad(x, b):
        out = np.zeros((b,) + x.shape[1:], np.float32)
        out[:len(x)] = x
        return out

    # single-device reference: per-rung probe outputs
    ref = ModelRunner(wf)
    ref_ladder = BucketLadder(SHARD_MAX_BATCH)
    ref.warmup(ref_ladder)
    probes = {r: rng.normal(0, 1, (r,) + sample_shape).astype(np.float32)
              for r in BucketLadder(SHARD_MAX_BATCH, dp=max(
                  dp for _, (dp, _mp) in SHARD_LAYOUTS))}
    ref_y = {r: ref.infer(pad(probes[r], ref_ladder.bucket_for(r)))[:r]
             for r in probes}

    failures = []
    layouts = {}
    for tag, (dp, mp) in SHARD_LAYOUTS:
        runner = ModelRunner(
            wf, mesh=make_mesh((dp, mp), ("data", "model")))
        ladder = BucketLadder(SHARD_MAX_BATCH, dp=dp)
        if any(r % dp for r in ladder.rungs):
            failures.append(f"{tag}: ladder {ladder.rungs} not snapped "
                            f"to dp={dp}")
        warm = runner.warmup(ladder)
        rec = {"mesh": runner.mesh_shape, "devices": runner.device_count,
               "ladder": list(ladder.rungs), "compiles_warm": warm,
               "parity_rel": 0.0}
        # shard shapes + parity, rung by rung
        for rung in ladder:
            staged = runner.stage(pad(probes[rung]
                                      if rung in probes else
                                      rng.normal(0, 1, (rung,)
                                                 + sample_shape
                                                 ).astype(np.float32),
                                      rung))
            x_shards = [s.data.shape for s in staged.addressable_shards]
            y_dev, _gen = runner.infer_staged(staged)
            y_shards = [s.data.shape for s in y_dev.addressable_shards]
            want = rung // dp
            if (len(x_shards) != runner.device_count
                    or any(s[0] != want for s in x_shards)):
                failures.append(f"{tag}: rung {rung} staged shards "
                                f"{x_shards}, want {want} rows on each "
                                f"of {runner.device_count} devices")
            if any(s[0] != want for s in y_shards):
                failures.append(f"{tag}: rung {rung} result shards "
                                f"{y_shards}, want {want} rows each")
            if rung in probes:
                y = np.asarray(y_dev)[:rung]
                rel = float(np.max(np.abs(y - ref_y[rung]))
                            / max(np.max(np.abs(ref_y[rung])), 1e-30))
                rec["parity_rel"] = max(rec["parity_rel"], rel)
                if rel > SHARD_PARITY_REL:
                    failures.append(
                        f"{tag}: rung {rung} sharded-vs-single-device "
                        f"parity {rel:.2e} > {SHARD_PARITY_REL}")
        # 0-ULP batch-independence WITHIN this mesh: coalesced vs
        # alone-in-the-rung, plus garbage pad rows
        rung = ladder.rungs[min(1, len(ladder.rungs) - 1)]
        parts = [probes[rung][:rung // 2], probes[rung][rung // 2:]]
        alone = [runner.infer(pad(p, rung))[:len(p)] for p in parts]
        together = runner.infer(np.concatenate(parts))
        garbage = pad(parts[0], rung)
        garbage[len(parts[0]):] = 1e9
        if not (np.array_equal(together[:len(parts[0])], alone[0])
                and np.array_equal(together[len(parts[0]):], alone[1])
                and np.array_equal(
                    runner.infer(garbage)[:len(parts[0])], alone[0])):
            failures.append(f"{tag}: 0-ULP batch-independence broke "
                            f"on the sharded path (rung {rung})")
        # mixed-size stream: zero recompiles after warmup
        c0, j0 = runner.compiles, runner.jit_cache_size()
        for n in SHARD_MIXED_SIZES:
            runner.infer(pad(probes.get(
                n, rng.normal(0, 1, (n,) + sample_shape
                              ).astype(np.float32))[:n],
                ladder.bucket_for(n)))
        rec["recompiles_mixed_stream"] = runner.compiles - c0
        rec["jit_cache_size"] = runner.jit_cache_size()
        if runner.compiles != c0:
            failures.append(f"{tag}: {runner.compiles - c0} recompiles "
                            f"during the mixed-size stream")
        if j0 is not None and runner.jit_cache_size() != j0:
            failures.append(f"{tag}: jax jit cache grew "
                            f"{j0} -> {runner.jit_cache_size()} during "
                            f"the mixed-size stream")
        # layout timing (recorded, not gated on this host)
        xb = probes[SHARD_MAX_BATCH]
        rows = 0
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < SHARD_WINDOW_S:
            runner.infer(xb)
            rows += SHARD_MAX_BATCH
        rec["rows_per_s"] = round(rows / (_time.perf_counter() - t0), 1)
        rec["stage_copies"] = runner.stage_copies
        layouts[tag] = rec

    # mesh 1x1 (default config) must BE the single-device path
    one = ModelRunner(wf)       # mesh_from_config() -> None by default
    one.warmup(ref_ladder)
    one_exact = all(
        np.array_equal(one.infer(pad(probes[r],
                                     ref_ladder.bucket_for(r)))[:r],
                       ref_y[r]) for r in probes)
    if one.mesh is not None:
        failures.append("default mesh config did not resolve to the "
                        "single-device path")
    if not one_exact:
        failures.append("mesh 1x1 results differ from the single-device "
                        "reference (must be byte-identical)")

    print(json.dumps({
        "metric": "serving_sharded_structure",
        "value": max(rec["parity_rel"] for rec in layouts.values()),
        "unit": "max_rel_parity_vs_single_device",
        "devices_provisioned": SHARD_DEVICES,
        "hidden_width": SHARD_HIDDEN,
        "max_batch": SHARD_MAX_BATCH,
        "parity_band": SHARD_PARITY_REL,
        "mesh_1x1_byte_identical": bool(one_exact),
        "layouts": layouts,
        "single_device_rows_per_s": None,   # see layouts: CPU timing
        #                                     noise — BASELINE.md r18
        #                                     carries the TPU protocol
    }))
    # gates AFTER the JSON line (the record survives a trip)
    if failures:
        raise SystemExit("shard gates failed: " + "; ".join(failures))


#: --shard-train protocol knobs (ISSUE 18): the pod-sliced TRAINING
#: gates, on the same 8 virtual CPU devices as --shard and with the
#: same structure-not-throughput discipline.  One seeded single-slave
#: MNIST fleet per scenario — the oracle (train_shard off), mesh 1x1
#: under train_shard (must BE the single-device path, bit-exact), and
#: the {data:4, model:2} pod slice — so the wire protocol, the job
#: stream, and the Decision are identical across scenarios and every
#: difference is attributable to the slice.  The model is the wide
#: MNIST MLP (hidden >= tp_threshold) so the model axis engages the
#: column-sharded layout; n_train/minibatch give 5 TRAIN minibatches
#: per epoch, and segment_steps=4 pins the steady-state scan length so
#: the post-run replay exercises exactly the executables the fleet
#: compiled (k=4 segment + k=1 tail).  bytes-into-master is gated at
#: <= 1% drift vs the oracle: the intra-slice psum tier is FREE on the
#: wire — a sharded slave must not change what crosses the host
#: boundary.  Convergence band reuses the --agg discipline (seeded
#: async replicas; both runs must land converged, within a band of
#: each other — the {4,2} run differs from the oracle only by XLA
#: reduction-order noise amplified through training).
SHARD_TRAIN_HIDDEN = 2048
SHARD_TRAIN_EPOCHS = 3
SHARD_TRAIN_N_TRAIN = 300
SHARD_TRAIN_SEGMENT = 4
SHARD_TRAIN_BASE_PORT = 18900
SHARD_TRAIN_BYTES_DRIFT = 0.01


def _shard_train_workflow(tag: str):
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = SHARD_TRAIN_N_TRAIN
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = SHARD_TRAIN_EPOCHS
    root.common.dirs.snapshots = f"/tmp/bench_shard_train/{tag}"
    root.mnist.layers = [SHARD_TRAIN_HIDDEN, 10]
    try:
        wf = mnist.MnistWorkflow()
    finally:
        root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    return wf


def _shard_train_fleet(tag: str, port: int, dp: int, mp: int,
                       shard: bool):
    """One seeded single-slave fleet under the given engine-mesh
    config; returns ``(server, master_wf, slave, err_pct)`` with the
    slave's trainer still live for post-run inspection."""
    import threading

    from znicz_tpu.client import FusedClient
    from znicz_tpu.core.config import root
    from znicz_tpu.server import Server

    root.common.engine.train_shard = bool(shard)
    root.common.engine.mesh.data = int(dp)
    root.common.engine.mesh.model = int(mp)
    try:
        ep = f"tcp://127.0.0.1:{port}"
        wf = _shard_train_workflow(f"{tag}_m")
        server = Server(wf, endpoint=ep, job_timeout=120.0,
                        segment_steps=SHARD_TRAIN_SEGMENT)
        slave = FusedClient(_shard_train_workflow(f"{tag}_s"),
                            endpoint=ep, slave_id=f"{tag}w0")
        errors: list = []

        def worker():
            try:
                slave.run()
            except BaseException as e:
                errors.append((slave.slave_id, repr(e)))
                raise

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        server.serve()
        t.join(timeout=180)
        if errors:
            raise SystemExit(f"{tag}: slave crashed: {errors}")
        if t.is_alive():
            raise SystemExit(f"{tag}: slave hung")
        dec = wf.decision
        if not bool(dec.complete):
            raise SystemExit(f"{tag}: training did not complete")
        return server, wf, slave, float(dec.epoch_metrics[1]["err_pct"])
    finally:
        # the engine tree is process-global: leave it at the defaults
        root.common.engine.train_shard = False
        root.common.engine.mesh.data = 1
        root.common.engine.mesh.model = 1


def _shard_train_master_params(wf):
    return {f.name: {k: np.asarray(a.map_read())
                     for k, a in f.params().items()}
            for f in wf.forwards if f.has_weights}


def shard_train_main() -> None:
    """``--shard-train``: the pod-sliced training gates (ISSUE 18),
    one JSON line.  Three seeded single-slave fleets over the SAME
    wire protocol and job stream:

      - **oracle**: train_shard off — the single-device FusedClient;
      - **mesh 1x1**: train_shard ON with a 1x1 mesh must resolve to
        the single-device path — master's converged params
        byte-identical to the oracle's, err_pct equal;
      - **pod slice {data:4, model:2}**: shard shapes on the wide fc
        layer (8 addressable shards, hidden/mp rows each — the
        column-sharded layout, replicated over the data axis), the
        slice shape visible on the master (register piggyback), the
        SAME executable count as the oracle (explicit shardings add
        zero recompiles), zero recompiles on a post-run replay of the
        steady-state job shapes (k=4 segment + k=1 tail, numpy idx +
        committed state — both warmed argument forms), bytes-into-
        master within ``SHARD_TRAIN_BYTES_DRIFT`` of the oracle (the
        ICI psum tier is free on the wire), and seeded convergence
        inside the ``--agg``-style band.

    Gates fire AFTER the JSON line so a trip never destroys the
    measurement record."""
    from znicz_tpu.virtdev import provision_cpu_devices

    # BEFORE the first backend init (conftest discipline)
    provision_cpu_devices(SHARD_DEVICES)

    failures = []

    # single-device oracle
    srv_o, wf_o, sl_o, err_o = _shard_train_fleet(
        "sto", SHARD_TRAIN_BASE_PORT, 1, 1, shard=False)
    bytes_o = int(srv_o.bytes_in)
    comp_o = int(sl_o._trainer._m_compiles.value)
    if sl_o._trainer.mesh is not None:
        failures.append("oracle slave grew a mesh with train_shard off")

    # mesh 1x1 under train_shard: IS the single-device path, bit-exact
    srv_1, wf_1, sl_1, err_1 = _shard_train_fleet(
        "st1", SHARD_TRAIN_BASE_PORT + 1, 1, 1, shard=True)
    if sl_1._trainer.mesh is not None:
        failures.append("train_shard with a 1x1 mesh did not resolve "
                        "to the single-device path")
    p_o = _shard_train_master_params(wf_o)
    p_1 = _shard_train_master_params(wf_1)
    one_exact = (err_1 == err_o) and all(
        np.array_equal(p_1[n][k], p_o[n][k])
        for n in p_o for k in p_o[n])
    if not one_exact:
        failures.append("mesh 1x1 converged params differ from the "
                        "single-device oracle (must be byte-identical)")

    # the pod slice: {data:4, model:2}
    srv_s, wf_s, sl_s, err_s = _shard_train_fleet(
        "sts", SHARD_TRAIN_BASE_PORT + 2, 4, 2, shard=True)
    t = sl_s._trainer
    bytes_s = int(srv_s.bytes_in)
    comp_s = int(t._m_compiles.value)
    if t.mesh_shape != {"data": 4, "model": 2}:
        failures.append(f"slave mesh {t.mesh_shape}, want "
                        f"{{'data': 4, 'model': 2}}")
    meshes_seen = list(srv_s.slave_meshes.values())
    if meshes_seen != [{"data": 4, "model": 2}]:
        failures.append(f"master saw slave meshes {meshes_seen} — the "
                        f"register piggyback is broken")
    # shard shapes: the wide fc layer is column-sharded over the model
    # axis (hidden/mp rows per shard) and replicated over data
    shard_rec = {}
    for f in sl_s.workflow.forwards:
        if not f.has_weights:
            continue
        for k, arr in f.params().items():
            if arr.shape[0] != SHARD_TRAIN_HIDDEN:
                continue
            shards = [s.data.shape for s in
                      arr.devmem.addressable_shards]
            shard_rec[f"{f.name}.{k}"] = shards
            want = SHARD_TRAIN_HIDDEN // 2
            if (len(shards) != SHARD_DEVICES
                    or any(s[0] != want for s in shards)):
                failures.append(
                    f"{f.name}.{k}: shards {shards}, want dim0={want} "
                    f"on each of {SHARD_DEVICES} devices")
    if not shard_rec:
        failures.append(f"no param with dim0={SHARD_TRAIN_HIDDEN} "
                        f"found — the model axis never engaged")
    # jit hygiene: explicit shardings add ZERO executables vs the
    # oracle, and a post-run replay of the steady-state job shapes
    # (k=4 segment, k=1 tail; fresh numpy idx + committed state, the
    # two warmed argument forms) recompiles nothing
    if comp_s != comp_o:
        failures.append(f"sharded slave compiled {comp_s} executables "
                        f"vs oracle {comp_o} — sharding must not "
                        f"change the executable count")
    c0, j0 = int(t._m_compiles.value), dict(t.jit_cache_sizes())
    rng = np.random.default_rng(7)
    for k in (SHARD_TRAIN_SEGMENT, 1, SHARD_TRAIN_SEGMENT):
        idx = rng.integers(0, SHARD_TRAIN_N_TRAIN, (k, 60))
        mbs = [{"indices": idx[i].tolist(), "size": 60}
               for i in range(k)]
        sl_s._run_minibatch({"kind": "segment", "minibatches": mbs},
                            train=True)
    replay_recompiles = int(t._m_compiles.value) - c0
    if replay_recompiles:
        failures.append(f"{replay_recompiles} recompiles on the "
                        f"steady-state replay after warmup")
    if dict(t.jit_cache_sizes()) != j0:
        failures.append(f"jax jit cache grew {j0} -> "
                        f"{t.jit_cache_sizes()} on the replay")
    # two-tier reduction: the intra-slice psum is free on the wire —
    # bytes into the master must not drift
    drift = abs(bytes_s - bytes_o) / max(bytes_o, 1)
    if drift > SHARD_TRAIN_BYTES_DRIFT:
        failures.append(f"bytes into master drifted {drift:.2%} "
                        f"(oracle {bytes_o}, sharded {bytes_s}; "
                        f"ceiling {SHARD_TRAIN_BYTES_DRIFT:.0%})")
    # seeded convergence: the --agg discipline
    if abs(err_s - err_o) > AGG_CONV_BAND:
        failures.append(f"sharded err {err_s:.1f}% outside the band "
                        f"(oracle {err_o:.1f}%, band {AGG_CONV_BAND})")
    for tag, err in (("oracle", err_o), ("sharded", err_s)):
        if err > AGG_ERR_CEIL:
            failures.append(f"{tag} err {err:.1f}% > ceiling "
                            f"{AGG_ERR_CEIL}% — did not converge")

    print(json.dumps({
        "metric": "train_sharded_structure",
        "value": round(abs(err_s - err_o), 3),
        "unit": "abs_err_pct_delta_vs_single_device_oracle",
        "devices_provisioned": SHARD_DEVICES,
        "hidden_width": SHARD_TRAIN_HIDDEN,
        "mesh": {"data": 4, "model": 2},
        "err_pct": {"oracle": err_o, "mesh_1x1": err_1,
                    "sharded": err_s},
        "mesh_1x1_byte_identical": bool(one_exact),
        "bytes_into_master": {"oracle": bytes_o, "sharded": bytes_s,
                              "drift": round(drift, 5),
                              "ceiling": SHARD_TRAIN_BYTES_DRIFT},
        "compiles": {"oracle": comp_o, "sharded": comp_s},
        "replay_recompiles": replay_recompiles,
        "jit_cache_sizes": dict(t.jit_cache_sizes()),
        "shard_shapes": {k: [list(map(int, s)) for s in v]
                         for k, v in shard_rec.items()},
        "conv_band": AGG_CONV_BAND,
    }))
    # gates AFTER the JSON line (the record survives a trip)
    if failures:
        raise SystemExit("shard-train gates failed: "
                         + "; ".join(failures))


#: --seq protocol knobs (ISSUE 15): the variable-length serving gates.
#: The model is the charlm transformer widened so per-token COMPUTE
#: dominates per-request overhead (the --serve lesson: a toy-thin model
#: measures only codec/python overhead, which no ladder can win back);
#: the request stream is skewed SHORT (mean ~12 tokens vs a 64-token
#: window), the regime where a single-max-len ladder burns most of its
#: FLOPs on padding.  Gates are RELATIVE and interleaved best-of, per
#: the standing cgroup-swing discipline.
SEQ_MAX_BATCH = 8
SEQ_MAX_LEN = 256
SEQ_RUNGS = (8, 16, 32, 64, 128, 256)   # 4x6 executables to warm
SEQ_MODEL = {"vocab": 64, "embed": 256, "heads": 4, "ffn": 1024}
SEQ_MIXED_LENGTHS = (3, 5, 8, 12, 4, 16, 7, 9, 24, 6, 10, 32, 8, 14,
                     5, 100, 11, 4, 20, 8)
SEQ_WINDOW_S = 2.5          # per-service closed-loop window per round
SEQ_ROUNDS = 5              # interleaved best-of rounds (early exit on
#                             clearing the floor with margin): the 2-D
#                             service runs ~4x more batches per second
#                             than the 1-D baseline, so a cgroup-share
#                             dip taxes it harder — both services need
#                             a quiet-phase window before the ratio is
#                             meaningful
SEQ_GOODPUT_FLOOR = 2.0     # 2-D ladder vs single-max-len goodput
SEQ_PARITY_PROBES = 12      # co-batched masked-parity submissions
SEQ_WINDOW_INFLIGHT = 2 * SEQ_MAX_BATCH


def _build_seq_workflow():
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root

    prng.reset(1013)
    root.charlm.loader.update({"n_train": 64, "n_valid": 16,
                               "seq_len": SEQ_MAX_LEN})
    root.charlm.model.update(dict(SEQ_MODEL))

    from znicz_tpu.samples.charlm import CharLMWorkflow

    wf = CharLMWorkflow()
    wf.initialize(device=None)
    return wf


def seq_main() -> None:
    """``--seq``: the variable-length serving gates (ISSUE 15), one JSON
    line.  Three phases against the SAME charlm model on this host:

      - goodput: the 2-D (batch x seq) ladder service vs the single-
        max-len ladder service (every request padded to the full
        window client-side — exactly what a fixed-shape service forces
        a mixed-length stream to do), driven closed-loop with the SAME
        skewed-short stream in INTERLEAVED windows, best-of per
        service.  Goodput counts REAL tokens answered per second.
        Gate: 2-D >= SEQ_GOODPUT_FLOOR x single-max-len;
      - zero recompiles: the 2-D service compiles exactly its
        rungs x seq-rungs product at warmup and NOTHING over the mixed
        stream (trace counter + jax's own jit cache);
      - masked 0-ULP parity: a fixed probe request co-batched with
        every round of varying same-seq-rung neighbors (the batch's
        rows rung pinned, so the executable is fixed) must come back
        BIT-IDENTICAL every time — each reply a pure function of the
        request's own rows and own unpadded length.

    Gates are enforced AFTER the JSON line so a tripped gate never
    destroys the measurement record."""
    import time as _time

    from znicz_tpu.serving import InferenceClient, InferenceServer
    from znicz_tpu.serving.batcher import BucketLadder

    sys.setswitchinterval(1e-3)

    wf = _build_seq_workflow()
    vocab = SEQ_MODEL["vocab"]
    rng = np.random.default_rng(1013)

    from znicz_tpu.core.config import root

    root.common.serving.seq.rungs = list(SEQ_RUNGS)
    srv2d = InferenceServer(wf, max_batch=SEQ_MAX_BATCH,
                            max_delay_ms=5.0,
                            queue_bound=8 * SEQ_MAX_BATCH).start()
    assert srv2d.batcher.ladder.seq_rungs == list(SEQ_RUNGS)
    warm_compiles = srv2d.runner.compiles
    n_buckets = len(srv2d.batcher.ladder.buckets())
    # the single-max-len baseline: a plain 1-D ladder on the same
    # model — every request must arrive at the full trained window
    srv1d = InferenceServer(wf, max_batch=SEQ_MAX_BATCH,
                            max_delay_ms=5.0,
                            queue_bound=8 * SEQ_MAX_BATCH,
                            ladder=BucketLadder(SEQ_MAX_BATCH)).start()
    cli2d = InferenceClient(srv2d.endpoint, timeout=120,
                            breaker_failures=0)
    cli1d = InferenceClient(srv1d.endpoint, timeout=120,
                            breaker_failures=0)

    def req_of(length):
        return rng.integers(1, vocab, size=(1, length)).astype(np.uint8)

    def pad_full(x):
        out = np.zeros((x.shape[0], SEQ_MAX_LEN), np.uint8)
        out[:, :x.shape[1]] = x
        return out

    def drive(cli, duration_s, full_len):
        """Closed loop over the mixed-length stream; returns (real
        tokens answered, elapsed).  ``full_len``: pad every request to
        the full window client-side (the 1-D service's contract)."""
        tokens = 0
        real_of = {}
        i = 0
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < duration_s:
            while cli.in_flight < SEQ_WINDOW_INFLIGHT:
                length = SEQ_MIXED_LENGTHS[i % len(SEQ_MIXED_LENGTHS)]
                i += 1
                x = req_of(length)
                rid = cli.submit(pad_full(x) if full_len else x)
                real_of[rid] = length
            for rep in cli.collect(0.002):
                real = real_of.pop(rep["req_id"], 0)
                if rep.get("ok"):
                    tokens += real
        elapsed = _time.perf_counter() - t0
        while cli.in_flight:          # drain the tail, uncounted
            for rep in cli.collect(0.01):
                real_of.pop(rep["req_id"], None)
        return tokens, elapsed

    # warm both request paths
    for _ in range(4):
        cli2d.infer(req_of(12))
        cli1d.infer(pad_full(req_of(12)))

    goodput_2d = 0.0
    goodput_1d = 0.0
    for _ in range(SEQ_ROUNDS):
        tok, el = drive(cli1d, SEQ_WINDOW_S, full_len=True)
        goodput_1d = max(goodput_1d, tok / el)
        tok, el = drive(cli2d, SEQ_WINDOW_S, full_len=False)
        goodput_2d = max(goodput_2d, tok / el)
        if goodput_2d >= 1.15 * SEQ_GOODPUT_FLOOR * goodput_1d:
            break                     # floor cleared with margin

    # zero recompiles over the whole mixed stream
    recompiles = srv2d.runner.compiles - warm_compiles
    jit_cache = srv2d.runner.jit_cache_size()

    # masked 0-ULP parity: probe (4 rows, len 10 -> seq rung 16)
    # co-batched with a same-rung 4-row filler each round — the batch
    # must be the (8, 16) executable every round (the 0-ULP contract
    # is per executable; PR 4/12).  A scheduler stall > max_delay_ms
    # between the two submits can split them into (4, 16) batches —
    # such a round proves nothing either way, so it is detected via
    # the "8x8"->"8x16" bucket-hit counter and retried, bounded.
    probe = rng.integers(1, vocab, size=(4, 10)).astype(np.uint8)
    parity_replies = []
    split_rounds = 0
    j = 0
    attempts = 0
    while len(parity_replies) < SEQ_PARITY_PROBES \
            and attempts < 3 * SEQ_PARITY_PROBES:
        attempts += 1
        hits_before = srv2d.batcher.bucket_hits.get("8x16", 0)
        filler_len = 9 + (j % 8)              # rungs to 16, varies
        j += 1
        filler = rng.integers(1, vocab,
                              size=(4, filler_len)).astype(np.uint8)
        rid_p = cli2d.submit(probe)
        rid_f = cli2d.submit(filler)
        got = {}
        while len(got) < 2:
            for rep in cli2d.collect(0.05):
                got[rep["req_id"]] = rep
        assert got[rid_p].get("ok") and got[rid_f].get("ok"), got
        if srv2d.batcher.bucket_hits.get("8x16", 0) != hits_before + 1:
            split_rounds += 1                 # did not coalesce: retry
            continue
        parity_replies.append(got[rid_p]["y"])
    parity_exact = len(parity_replies) == SEQ_PARITY_PROBES and all(
        np.array_equal(parity_replies[0], y) for y in parity_replies[1:])

    pad_ratio = srv2d.batcher.pad_ratio()
    stats2d = srv2d.batcher.stats()
    for c in (cli2d, cli1d):
        c.close()
    for s in (srv2d, srv1d):
        s.stop()

    ratio = goodput_2d / max(goodput_1d, 1e-9)
    print(json.dumps({
        "metric": "seq_serving_goodput_ratio",
        "value": round(ratio, 3),
        "unit": "2d_ladder_vs_single_max_len_real_tokens_per_s",
        "goodput_2d_tok_s": round(goodput_2d, 1),
        "goodput_1d_tok_s": round(goodput_1d, 1),
        "goodput_floor": SEQ_GOODPUT_FLOOR,
        "max_batch": SEQ_MAX_BATCH,
        "max_len": SEQ_MAX_LEN,
        "seq_rungs": list(SEQ_RUNGS),
        "model": dict(SEQ_MODEL),
        "warm_compiles": warm_compiles,
        "buckets": n_buckets,
        "recompiles_mixed_stream": recompiles,
        "jit_cache_size": jit_cache,
        "parity_masked_bit_exact": bool(parity_exact),
        "parity_rounds": len(parity_replies),
        "parity_split_rounds_retried": split_rounds,
        "pad_ratio_by_bucket": pad_ratio,
        "padded_cells": stats2d["padded_cells"],
        "real_cells": stats2d["real_cells"],
    }))
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if ratio < SEQ_GOODPUT_FLOOR:
        failures.append(f"mixed-length goodput ratio {ratio:.2f} below "
                        f"the {SEQ_GOODPUT_FLOOR}x floor")
    if warm_compiles != n_buckets:
        failures.append(f"warmup compiled {warm_compiles} executables, "
                        f"expected rungs x seq_rungs = {n_buckets}")
    if recompiles:
        failures.append(f"{recompiles} recompiles during the mixed "
                        f"stream (must be 0)")
    if jit_cache is not None and jit_cache != warm_compiles:
        failures.append(f"jax jit cache {jit_cache} != warmup "
                        f"compiles {warm_compiles}")
    if not parity_exact:
        failures.append("probe replies differ across co-batched "
                        "neighbor lengths (masked 0-ULP contract)")
    if failures:
        raise SystemExit("seq gates failed: " + "; ".join(failures))


#: --generate protocol knobs (ISSUE 16): the generation-serving gates.
#: Same model-sizing lesson as --seq (compute must dominate per-token
#: overhead or the bench measures python, not the KV cache); the
#: trained window is 64 so oracle prefixes stay inside the scoring
#: ladder.  Gates are RELATIVE and interleaved best-of, per the
#: standing cgroup-swing discipline.
GEN_MAX_BATCH = 8
GEN_TRAIN_LEN = 64
GEN_SEQ_RUNGS = (8, 16, 64)      # prompt ladder == scoring seq ladder
GEN_PAGE_SIZE = 64               # KV page grain: coarse for the no-reuse path
                                 # (one page covers the 64-token window; the
                                 # --prefix bench runs the fine 16-token grain
                                 # where sharing pays for the gather)
GEN_SLOTS = 32                   # concurrent generations resident
GEN_PROMPTS = (3, 5, 8, 12, 4, 14, 7, 9, 6, 10)      # mixed lengths
GEN_MAX_NEW = (24, 40, 32, 48, 28, 36, 40, 44, 48, 32)  # mixed budgets
GEN_INFLIGHT = 24                # concurrent generations offered
ORACLE_INFLIGHT = 4              # concurrent oracle token loops
GEN_WINDOW_S = 2.5               # per-path closed-loop window per round
GEN_ROUNDS = 4                   # interleaved best-of rounds
GEN_TPS_FLOOR = 10.0             # generation vs re-prefill oracle
GEN_PARITY_ROUNDS = 4            # co-batched bit-exactness rounds
GEN_PROBE_LEN = 6
GEN_PROBE_NEW = 40               # fill crosses page boundaries mid-run


def generate_main() -> None:
    """``--generate``: the generation-serving gates (ISSUE 16), one
    JSON line.  Three phases against ONE server (generation enabled on
    the charlm transformer of --seq sizing):

      - tokens/s: closed-loop ``generate`` traffic (mixed prompt
        lengths x mixed max_new budgets) vs the naive re-prefill
        oracle — a client loop that emits each token by scoring its
        sequence's WHOLE prefix through the same server's classic
        plane and sampling client-side, i.e. exactly what a
        scoring-only service forces generation to do.  Interleaved
        best-of windows; gate: generation >= GEN_TPS_FLOOR x oracle,
        with generation's p99 inter-token gap (the scheduler's
        per-sequence emission histogram) no worse than the oracle's
        client-stamped per-token p99;
      - per-decoded-token bit-exactness: a greedy probe generation
        co-batched with rounds of same-shape neighbors whose CONTENT
        (and sampled continuations) vary — the probe's per-token
        logits must come back BIT-IDENTICAL every round (executables
        pinned by same-shape neighbors; each row's decode reads only
        its own KV pages), and its token stream must match the solo
        run exactly (crossing page-table rungs mid-generation);
      - zero recompiles: warmup compiles == scoring buckets + the
        paged prefill/decode x (batch rung, page rung) family + the
        COW copy, and NOTHING recompiles over the whole mixed stream.

    Gates are enforced AFTER the JSON line so a tripped gate never
    destroys the measurement record."""
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.serving import InferenceClient, InferenceServer

    sys.setswitchinterval(1e-3)

    prng.reset(1013)
    root.charlm.loader.update({"n_train": 64, "n_valid": 16,
                               "seq_len": GEN_TRAIN_LEN})
    root.charlm.model.update(dict(SEQ_MODEL))

    from znicz_tpu.samples.charlm import CharLMWorkflow

    wf = CharLMWorkflow()
    wf.initialize(device=None)
    vocab = SEQ_MODEL["vocab"]
    rng = np.random.default_rng(1013)

    root.common.serving.seq.rungs = list(GEN_SEQ_RUNGS)
    root.common.serving.generate.update({
        "enabled": True, "page_size": GEN_PAGE_SIZE,
        "slots": GEN_SLOTS})
    srv = InferenceServer(wf, max_batch=GEN_MAX_BATCH, max_delay_ms=5.0,
                          queue_bound=8 * GEN_MAX_BATCH).start()
    warm_compiles = srv.runner.compiles
    n_buckets = len(srv.batcher.ladder.buckets())
    gen_execs = srv.gen_sched.gen.executables()
    cli = InferenceClient(srv.endpoint, timeout=120, breaker_failures=0)

    def prompt_of(length):
        return rng.integers(1, vocab, size=length).astype(np.uint8)

    # warm both request paths (compiles all counted in warm_compiles'
    # baseline? no — warmup() already compiled every executable; these
    # drive the warmed shapes only)
    cli.infer(prompt_of(12)[None])
    cli.generate(prompt_of(5), max_new_tokens=4)

    def drive_generate(duration_s):
        """Closed-loop generation window: keep GEN_INFLIGHT generations
        going; returns (tokens emitted by finals landing inside the
        window, elapsed).  Inter-token cadence comes from the
        scheduler's own per-sequence emission histogram, so the
        throughput path ships no per-token partials."""
        toks = 0
        i = 0
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < duration_s:
            # hysteresis refill: submit in BURSTS so the scheduler's
            # prefill coalescing sees real batches, not singletons
            if cli.in_flight <= GEN_INFLIGHT - 4:
                while cli.in_flight < GEN_INFLIGHT:
                    plen = GEN_PROMPTS[i % len(GEN_PROMPTS)]
                    mnew = GEN_MAX_NEW[i % len(GEN_MAX_NEW)]
                    i += 1
                    cli.submit_generate(prompt_of(plen), mnew)
            for rep in cli.collect(0.002):
                if rep.get("ok"):
                    toks += len(rep["tokens"])
        elapsed = _time.perf_counter() - t0
        while cli.in_flight:            # drain the tail, uncounted
            cli.collect(0.01)
        return toks, elapsed

    def drive_oracle(duration_s):
        """The naive re-prefill oracle: ORACLE_INFLIGHT client-side
        token loops, each emitting its next token by scoring its whole
        prefix through the classic plane and argmaxing the last real
        position — O(prefix) recompute per emitted token."""
        toks = 0
        gaps = []
        i = 0

        def new_seq():
            nonlocal i
            plen = GEN_PROMPTS[i % len(GEN_PROMPTS)]
            mnew = GEN_MAX_NEW[i % len(GEN_MAX_NEW)]
            i += 1
            return {"prefix": list(prompt_of(plen)), "left": mnew,
                    "t_last": None}
        live = {}                       # rid -> seq state
        idle = [new_seq() for _ in range(ORACLE_INFLIGHT)]
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < duration_s:
            while idle:
                s = idle.pop()
                x = np.asarray(s["prefix"], np.uint8)[None]
                live[cli.submit(x)] = s
            for rep in cli.collect(0.002):
                s = live.pop(rep["req_id"], None)
                if s is None or not rep.get("ok"):
                    continue
                row = rep["y"][0, len(s["prefix"]) - 1]
                s["prefix"].append(int(np.argmax(row)))
                s["left"] -= 1
                now = _time.perf_counter()
                if s["t_last"] is not None:
                    gaps.append(now - s["t_last"])
                s["t_last"] = now
                toks += 1
                idle.append(new_seq() if s["left"] <= 0 else s)
        elapsed = _time.perf_counter() - t0
        while cli.in_flight:            # drain the tail, uncounted
            for rep in cli.collect(0.01):
                live.pop(rep["req_id"], None)
        return toks, elapsed, gaps

    gen_tps = oracle_tps = 0.0
    oracle_gaps = []
    for _ in range(GEN_ROUNDS):
        tok, el, gaps = drive_oracle(GEN_WINDOW_S)
        oracle_tps = max(oracle_tps, tok / el)
        oracle_gaps.extend(gaps)
        tok, el = drive_generate(GEN_WINDOW_S)
        gen_tps = max(gen_tps, tok / el)
        if gen_tps >= 1.15 * GEN_TPS_FLOOR * oracle_tps:
            break                       # floor cleared with margin

    gen_p99_ms = srv.gen_sched.inter_token_quantiles().get(
        "inter_token_p99_ms")
    oracle_p99_ms = round(float(np.percentile(oracle_gaps, 99)) * 1e3,
                          3) if oracle_gaps else None

    # per-decoded-token bit-exactness: solo reference, then co-batched
    # rounds — neighbor SHAPES fixed (lengths 5/7/8, same max_new, so
    # every tick's decode/prefill executable is pinned across rounds),
    # neighbor CONTENT and sampled continuations vary per round
    probe = prompt_of(GEN_PROBE_LEN)
    solo = cli.generate(probe, GEN_PROBE_NEW, return_logits=True)
    probe_logits = []
    probe_tokens = [solo["tokens"]]
    split_rounds = 0
    attempts = 0
    while len(probe_logits) < GEN_PARITY_ROUNDS \
            and attempts < 3 * GEN_PARITY_ROUNDS:
        attempts += 1
        pb = srv.gen_sched.prefill_batches
        rid_p = cli.submit_generate(probe, GEN_PROBE_NEW,
                                    return_logits=True)
        rids_n = [cli.submit_generate(prompt_of(n_len), GEN_PROBE_NEW,
                                      temperature=0.9,
                                      seed=1000 * attempts + k)
                  for k, n_len in enumerate((5, 7, 8))]
        reps = {}
        while any(r not in reps for r in [rid_p] + rids_n):
            for rep in cli.collect(0.02):
                reps[rep["req_id"]] = rep
        assert reps[rid_p].get("ok"), reps[rid_p]
        if srv.gen_sched.prefill_batches != pb + 1:
            split_rounds += 1           # did not co-batch: proves
            continue                    # nothing either way — retry
        probe_logits.append(reps[rid_p]["logits"])
        probe_tokens.append(reps[rid_p]["tokens"])
    parity_bit_exact = len(probe_logits) == GEN_PARITY_ROUNDS and all(
        np.array_equal(probe_logits[0], lg) for lg in probe_logits[1:])
    tokens_pure = all(np.array_equal(probe_tokens[0], t)
                      for t in probe_tokens[1:])

    # zero recompiles over everything that just ran
    recompiles = srv.runner.compiles - warm_compiles
    jit_cache = srv.runner.jit_cache_size()
    gen_jit_cache = srv.gen_sched.gen.jit_cache_size()
    gstats = srv.gen_sched.stats()
    cli.close()
    srv.stop()

    ratio = gen_tps / max(oracle_tps, 1e-9)
    print(json.dumps({
        "metric": "generate_serving_tokens_per_s_ratio",
        "value": round(ratio, 3),
        "unit": "kv_decode_vs_reprefill_oracle_tokens_per_s",
        "generate_tok_s": round(gen_tps, 1),
        "oracle_tok_s": round(oracle_tps, 1),
        "tps_floor": GEN_TPS_FLOOR,
        "inter_token_p99_ms": gen_p99_ms,
        "oracle_token_p99_ms": oracle_p99_ms,
        "model": dict(SEQ_MODEL),
        "train_len": GEN_TRAIN_LEN,
        "page_size": gstats["page_size"],
        "num_pages": gstats["num_pages"],
        "prefill_chunk": gstats["prefill_chunk"],
        "prompt_rungs": list(GEN_SEQ_RUNGS),
        "slots": GEN_SLOTS,
        "warm_compiles": warm_compiles,
        "scoring_buckets": n_buckets,
        "generation_executables": gen_execs,
        "recompiles_mixed_stream": recompiles,
        "jit_cache_size": jit_cache,
        "gen_jit_cache_size": gen_jit_cache,
        "parity_logits_bit_exact": bool(parity_bit_exact),
        "parity_tokens_pure": bool(tokens_pure),
        "parity_rounds": len(probe_logits),
        "parity_split_rounds_retried": split_rounds,
        "cow_copies": gstats["cow_copies"],
        "prefix_hits": gstats["prefix_hits"],
        "pages_leaked": gstats["pages_leaked"],
        "prefill_batches": gstats["prefill_batches"],
        "decode_batches": gstats["decode_batches"],
        "generated_tokens": gstats["generated_tokens"],
    }))
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if ratio < GEN_TPS_FLOOR:
        failures.append(f"generation tokens/s only {ratio:.2f}x the "
                        f"re-prefill oracle (floor {GEN_TPS_FLOOR}x)")
    if gen_p99_ms is not None and oracle_p99_ms is not None \
            and gen_p99_ms > oracle_p99_ms:
        failures.append(f"inter-token p99 {gen_p99_ms}ms worse than "
                        f"the oracle's per-token p99 {oracle_p99_ms}ms")
    if warm_compiles != n_buckets + gen_execs:
        failures.append(f"warmup compiled {warm_compiles}, expected "
                        f"scoring buckets {n_buckets} + generation "
                        f"executables {gen_execs}")
    if recompiles:
        failures.append(f"{recompiles} recompiles during the mixed "
                        f"stream (must be 0)")
    if gstats["pages_leaked"]:
        failures.append(f"{gstats['pages_leaked']} KV pages leaked "
                        f"(refcount invariant)")
    if jit_cache is not None and jit_cache != n_buckets:
        failures.append(f"scoring jit cache {jit_cache} != "
                        f"{n_buckets} buckets")
    if gen_jit_cache is not None and gen_jit_cache != gen_execs:
        failures.append(f"generation jit cache {gen_jit_cache} != "
                        f"{gen_execs} executables")
    if not parity_bit_exact:
        failures.append("probe logits differ across co-batched "
                        "neighbor-content rounds (bit-exactness "
                        "contract)")
    if not tokens_pure:
        failures.append("probe token stream depends on co-batched "
                        "neighbors (purity contract)")
    if failures:
        raise SystemExit("generate gates failed: " + "; ".join(failures))


#: --prefix protocol knobs (ISSUE 19): the paged-KV gates.  Sized to
#: the --seq/--generate transformer (window GEN_TRAIN_LEN=64, page 16,
#: chunk == page so prefix hits replay cold executables bit-exactly).
PFX_SHARED = 48                  # shared system-prompt tokens (3 pages)
PFX_STREAM = 10                  # shared-prefix requests per pass
PFX_TAILS = (4, 6, 8, 5, 7, 4, 8, 6, 5, 7)   # unique tail lengths
PFX_MAX_NEW = 4                  # greedy continuation per request
PFX_RATIO_CEIL = 0.5             # on/off prefilled-token ratio gate
PFX_STREAMERS = 4                # paced decoders in the latency phases
PFX_STREAM_NEW = 56              # tokens per decoder (fills to window)
PFX_TICK_MS = 40.0               # decode pacing (the band's metronome)
PFX_BARRAGE_LEN = 60             # long-prompt barrage (4 chunks each)
PFX_BARRAGE_INFLIGHT = 3         # barrage prompts resident
PFX_P99_BAND = 1.5               # barrage p99 <= band x this
PFX_BYTES_RATIO = 64             # logits-path bytes >= this x tokens-path


def prefix_main() -> None:
    """``--prefix``: the paged-KV gates (ISSUE 19), one JSON line.

    Four phases, two boots of the same charlm server:

      - prefill reduction: a seeded stream of PFX_STREAM prompts
        sharing a PFX_SHARED-token system prefix (unique short tails)
        runs against a prefix-cache-OFF boot (host sampling — also the
        logits-bytes baseline) and then a prefix-ON boot; the ON run
        must COMPUTE <= PFX_RATIO_CEIL x the prompt tokens the OFF run
        computed, with every decoded stream bit-exact between the two
        (chunk == page_size, so a hit replays the cold executables);
      - chunked-prefill latency: PFX_STREAMERS paced decoders
        (decode_tick_ms metronome) run once alone (the band) and once
        against a barrage of unique PFX_BARRAGE_LEN-token prompts; the
        decoders' client-stamped p99 inter-token gap under barrage
        must stay within PFX_P99_BAND x the band — a long prompt costs
        one bounded chunk per tick, never a whole-prompt stall;
      - on-device sampling bytes: the ON boot ships (b,) tokens per
        tick, the OFF boot (b, vocab) logits — fetched bytes per
        emitted token must differ by >= PFX_BYTES_RATIO (the vocab-64
        model's exact token/logits row ratio), greedy tokens already
        proven bit-identical by phase 1;
      - zero recompiles on the ON boot over everything above, both jit
        caches gated by strict equality.

    Gates are enforced AFTER the JSON line so a tripped gate never
    destroys the measurement record."""
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.serving import InferenceClient, InferenceServer

    sys.setswitchinterval(1e-3)

    prng.reset(1013)
    root.charlm.loader.update({"n_train": 64, "n_valid": 16,
                               "seq_len": GEN_TRAIN_LEN})
    root.charlm.model.update(dict(SEQ_MODEL))

    from znicz_tpu.samples.charlm import CharLMWorkflow

    wf = CharLMWorkflow()
    wf.initialize(device=None)
    vocab = SEQ_MODEL["vocab"]
    rng = np.random.default_rng(1013)
    shared = rng.integers(1, vocab, size=PFX_SHARED).astype(np.uint8)
    prompts = [np.concatenate(
                   [shared, rng.integers(1, vocab, size=t
                                         ).astype(np.uint8)])
               for t in PFX_TAILS]

    root.common.serving.seq.rungs = list(GEN_SEQ_RUNGS)

    def boot(prefix_on):
        root.common.serving.generate.update({
            "enabled": True, "page_size": GEN_PAGE_SIZE,
            "slots": 8, "prefix_cache": bool(prefix_on),
            "on_device_sampling": bool(prefix_on),
            "decode_tick_ms": PFX_TICK_MS if prefix_on else 0.0})
        srv = InferenceServer(wf, max_batch=GEN_MAX_BATCH,
                              max_delay_ms=5.0,
                              queue_bound=8 * GEN_MAX_BATCH).start()
        return srv, InferenceClient(srv.endpoint, timeout=120,
                                    breaker_failures=0)

    def shared_stream(srv, cli):
        """The shared-prefix pass: serial greedy generations; returns
        (token streams, prompt tokens computed, bytes fetched,
        tokens emitted)."""
        st0 = srv.gen_sched.stats()
        toks = [cli.generate(p, PFX_MAX_NEW)["tokens"] for p in prompts]
        st1 = srv.gen_sched.stats()
        return (toks,
                st1["prefill_tokens"] - st0["prefill_tokens"],
                st1["fetch_bytes"] - st0["fetch_bytes"],
                st1["generated_tokens"] - st0["generated_tokens"])

    # ---- OFF boot: the baseline side of phases 1 and 3 -----------------------
    srv, cli = boot(prefix_on=False)
    toks_off, prefill_off, bytes_off, emitted_off = shared_stream(srv, cli)
    cli.close()
    srv.stop()

    # ---- ON boot: everything else runs here ----------------------------------
    srv, cli = boot(prefix_on=True)
    warm_compiles = srv.runner.compiles
    n_buckets = len(srv.batcher.ladder.buckets())
    gen_execs = srv.gen_sched.gen.executables()
    toks_on, prefill_on, bytes_on, emitted_on = shared_stream(srv, cli)
    prefix_exact = all(np.array_equal(a, b)
                       for a, b in zip(toks_off, toks_on))
    prefill_ratio = prefill_on / max(prefill_off, 1)
    gstats_mid = srv.gen_sched.stats()

    def stream_phase(barrage):
        """PFX_STREAMERS streaming decoders, client-stamped; with
        ``barrage``, unique long prompts kept resident alongside.
        Returns the decoders' p99 inter-token gap in ms."""
        stamps = []
        streamer_rids = []
        for _ in range(PFX_STREAMERS):
            p = rng.integers(1, vocab, size=4).astype(np.uint8)
            s = []
            stamps.append(s)
            streamer_rids.append(cli.submit_generate(
                p, PFX_STREAM_NEW, stream=True,
                on_token=lambda tok, i, s=s:
                    s.append(_time.perf_counter())))
        pending = set(streamer_rids)
        barrage_live = set()
        barrage_done = 0
        while pending:
            if barrage:
                while len(barrage_live) < PFX_BARRAGE_INFLIGHT:
                    long_p = rng.integers(1, vocab, size=PFX_BARRAGE_LEN
                                          ).astype(np.uint8)
                    barrage_live.add(cli.submit_generate(long_p, 2))
            for rep in cli.collect(0.01):
                if rep.get("partial"):
                    continue
                rid = rep.get("req_id")
                pending.discard(rid)
                if rid in barrage_live:
                    barrage_live.discard(rid)
                    barrage_done += 1
        while cli.in_flight:            # drain the barrage tail
            cli.collect(0.02)
        gaps = [b - a for s in stamps for a, b in zip(s, s[1:])]
        return (round(float(np.percentile(gaps, 99)) * 1e3, 3),
                len(gaps), barrage_done)

    band_p99, band_gaps, _ = stream_phase(barrage=False)
    barrage_p99, barrage_gaps, barrage_n = stream_phase(barrage=True)

    recompiles = srv.runner.compiles - warm_compiles
    jit_cache = srv.runner.jit_cache_size()
    gen_jit_cache = srv.gen_sched.gen.jit_cache_size()
    gstats = srv.gen_sched.stats()
    cli.close()
    srv.stop()

    bytes_ratio = ((bytes_off / max(emitted_off, 1))
                   / max(bytes_on / max(emitted_on, 1), 1e-9))
    print(json.dumps({
        "metric": "prefix_cache_prefill_token_ratio",
        "value": round(prefill_ratio, 3),
        "unit": "prefix_on_vs_off_prompt_tokens_computed",
        "ratio_ceil": PFX_RATIO_CEIL,
        "prefill_tokens_off": int(prefill_off),
        "prefill_tokens_on": int(prefill_on),
        "prefix_outputs_bit_exact": bool(prefix_exact),
        "prefix_hits": gstats_mid["prefix_hits"],
        "prefix_tokens_avoided": gstats_mid["prefix_tokens_avoided"],
        "shared_prefix_tokens": PFX_SHARED,
        "model": dict(SEQ_MODEL),
        "page_size": gstats["page_size"],
        "num_pages": gstats["num_pages"],
        "prefill_chunk": gstats["prefill_chunk"],
        "decode_tick_ms": PFX_TICK_MS,
        "band_p99_ms": band_p99,
        "barrage_p99_ms": barrage_p99,
        "p99_band_factor": PFX_P99_BAND,
        "band_gaps": band_gaps,
        "barrage_gaps": barrage_gaps,
        "barrage_prompts_served": barrage_n,
        "fetch_bytes_per_token_off": round(bytes_off / max(emitted_off,
                                                           1), 1),
        "fetch_bytes_per_token_on": round(bytes_on / max(emitted_on,
                                                         1), 1),
        "bytes_ratio": round(bytes_ratio, 1),
        "bytes_ratio_floor": PFX_BYTES_RATIO,
        "warm_compiles": warm_compiles,
        "scoring_buckets": n_buckets,
        "generation_executables": gen_execs,
        "recompiles_mixed_stream": recompiles,
        "jit_cache_size": jit_cache,
        "gen_jit_cache_size": gen_jit_cache,
        "cow_copies": gstats["cow_copies"],
        "pages_leaked": gstats["pages_leaked"],
    }))
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if prefill_ratio > PFX_RATIO_CEIL:
        failures.append(f"prefix-on computed {prefill_ratio:.2f}x the "
                        f"off run's prompt tokens (ceil "
                        f"{PFX_RATIO_CEIL}x)")
    if not prefix_exact:
        failures.append("decoded streams diverge between prefix-on "
                        "and prefix-off (bit-exact reuse contract)")
    if barrage_p99 > PFX_P99_BAND * band_p99:
        failures.append(f"barrage p99 {barrage_p99}ms outside "
                        f"{PFX_P99_BAND}x the {band_p99}ms band "
                        f"(chunked prefill must bound the stall)")
    if bytes_ratio < PFX_BYTES_RATIO:
        failures.append(f"logits path only {bytes_ratio:.1f}x the "
                        f"token path's bytes/token (floor "
                        f"{PFX_BYTES_RATIO}x)")
    if recompiles:
        failures.append(f"{recompiles} recompiles during the mixed "
                        f"stream (must be 0)")
    if gstats["pages_leaked"]:
        failures.append(f"{gstats['pages_leaked']} KV pages leaked "
                        f"(refcount invariant)")
    if jit_cache is not None and jit_cache != n_buckets:
        failures.append(f"scoring jit cache {jit_cache} != "
                        f"{n_buckets} buckets")
    if gen_jit_cache is not None and gen_jit_cache != gen_execs:
        failures.append(f"generation jit cache {gen_jit_cache} != "
                        f"{gen_execs} executables")
    if failures:
        raise SystemExit("prefix gates failed: " + "; ".join(failures))


#: --telemetry protocol knobs (ISSUE 5).  Same de-flake discipline as
#: --serve / the PR-4 snapshot guard: enabled/disabled windows are
#: INTERLEAVED (this container's cgroup CPU share swings minute to
#: minute — a load spike must hit both variants), the comparison is
#: best-of per variant, and rounds early-exit once the gate holds.
TELEMETRY_EPOCHS = 3        # epochs per timed window
TELEMETRY_MAX_ROUNDS = 6    # bounded interleaved best-of pairs
TELEMETRY_GATE_PCT = 2.0    # enabled may cost at most this much


#: --elastic protocol knobs (ISSUE 17): zero-cold-start elasticity.
#: Two phases.  (A) The AOT executable cache on the FULL transformer
#: serving family (scoring buckets + prefill/decode/migrate): a cold
#: boot compiles + serializes every executable next to the snapshot, a
#: fresh process LOADS the family — gates are the boot-to-/readyz
#: ratio (cold >= ELASTIC_BOOT_RATIO_FLOOR x warm) and ZERO recompiles
#: over a mixed infer+generate stream after the load.  (B) The
#: autoscaling balancer riding a closed-loop traffic ramp plus seeded
#: preemption of HALF the initial fleet: scale-up must land (cache-
#: warm boot) within ELASTIC_SCALEUP_DEADLINE_S, goodput holds a band
#: of the pre-chaos baseline, the ledger stays exactly-once, and the
#: idle settle window drains the fleet back toward the quorum.  Phase
#: B rides the thin MNIST fleet model (it measures COORDINATION, same
#: reasoning as --fleet); phase A carries the compile-heavy family
#: where the cache earns its keep.  Both bands are RELATIVE, per the
#: standing cgroup-swing discipline.
ELASTIC_SEED = 1702
ELASTIC_BOOT_RATIO_FLOOR = 3.0  # cold boot >= 3x cache-warm boot
ELASTIC_REPLICAS = 4            # initial fleet; chaos preempts half
ELASTIC_MAX = 6                 # autoscale_max
ELASTIC_MIN = 2                 # min_replicas quorum
ELASTIC_BASE_QPS = 20.0         # open-loop baseline offered load
ELASTIC_BASE_S = 6.0
ELASTIC_CHAOS_S = 18.0          # ramp + preemption window
ELASTIC_SETTLE_S = 25.0         # idle window: scale-down must fire
ELASTIC_INFLIGHT = 64           # closed-loop ramp pressure
ELASTIC_SCALEUP_DEADLINE_S = 40.0
ELASTIC_GOODPUT_BAND = 0.5      # chaos goodput >= band x baseline
ELASTIC_GEN_STREAM = ((3, 24), (5, 40), (12, 30), (8, 44), (14, 36))


def elastic_main() -> None:
    """``--elastic``: the zero-cold-start elasticity gates (ISSUE 17),
    one JSON line; gates AFTER the line so a trip never destroys the
    record."""
    import shutil
    import tempfile
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.parallel.chaos import (FaultSchedule, FleetScaler,
                                          ReplicaHarness,
                                          SubtreePreempter)
    from znicz_tpu.serving import (InferenceClient, InferenceServer,
                                   ReplicaBalancer)

    sys.setswitchinterval(1e-3)
    tmp = tempfile.mkdtemp(prefix="znicz_elastic_")

    # ---- phase A: the AOT cache on the full transformer family ----------
    root.charlm.loader.update({"n_train": 64, "n_valid": 16,
                               "seq_len": GEN_TRAIN_LEN})
    root.charlm.model.update(dict(SEQ_MODEL))

    from znicz_tpu.samples.charlm import CharLMWorkflow

    def charlm_wf():
        prng.reset(1013)        # bit-identical params every build
        wf = CharLMWorkflow()
        wf.initialize(device=None)
        return wf

    wf_a = charlm_wf()
    wf_a.snapshotter.directory = os.path.join(tmp, "charlm")
    path_a = wf_a.snapshotter.save("elastic_a")
    root.common.serving.seq.rungs = list(GEN_SEQ_RUNGS)
    root.common.serving.generate.update({
        "enabled": True, "page_size": GEN_PAGE_SIZE,
        "slots": GEN_SLOTS})
    # dir="" -> the cache lands in aot_cache/ NEXT TO the snapshot
    root.common.serving.aot_cache.update({"enabled": True, "dir": ""})
    rng = np.random.default_rng(ELASTIC_SEED)
    vocab = SEQ_MODEL["vocab"]

    def prompt_of(length):
        return rng.integers(1, vocab, size=length).astype(np.uint8)

    def drive_mixed(cli):
        """The mixed stream the zero-recompile proof rides: scoring
        requests across the seq ladder + generations that cross the
        cache-rung migration."""
        for ln in (3, 10, 16, 40, 64, 7):
            cli.infer(prompt_of(ln)[None])
        for p_len, max_new in ELASTIC_GEN_STREAM:
            rep = cli.generate(prompt_of(p_len),
                               max_new_tokens=max_new)
            assert len(rep["tokens"]) >= 1

    boots = []
    ref_y = None
    probe = prompt_of(12)[None]     # ONE pinned probe — the parity
    # gate scores the same bytes through both boots
    for which in ("cold", "warm"):
        wf = charlm_wf()
        srv = InferenceServer(wf, snapshot=path_a,
                              max_batch=GEN_MAX_BATCH,
                              max_delay_ms=5.0,
                              queue_bound=8 * GEN_MAX_BATCH).start()
        cli = InferenceClient(srv.endpoint, timeout=120,
                              breaker_failures=0)
        y = cli.infer(probe)
        if ref_y is None:
            ref_y = y
        parity = bool(np.array_equal(ref_y, y))
        compiles_post_boot = srv.runner.compiles
        drive_mixed(cli)
        jit_total = (srv.runner.jit_cache_size() or 0) + \
            (srv.gen_sched.gen.jit_cache_size() or 0)
        boots.append({
            "which": which,
            "boot_to_ready_s": round(srv.boot_to_ready_s, 3),
            "warm_report": srv.warm_report,
            "parity_vs_cold": parity,
            "recompiles_mixed_stream":
                srv.runner.compiles - compiles_post_boot,
            "jit_cache_after_stream": jit_total,
            "aot": srv.runner._aot_cache.stats()})
        cli.close()
        srv.stop()
    cold, warm = boots
    boot_ratio = cold["boot_to_ready_s"] / max(
        warm["boot_to_ready_s"], 1e-9)
    # phase A config off before phase B's scoring-only fleet
    root.common.serving.generate.enabled = False
    root.common.serving.seq.rungs = None

    # ---- phase B: the autoscaler rides a ramp + preemption --------------
    fleet_dir = os.path.join(tmp, "fleet")
    wf_f = _build_fleet_workflow()
    wf_f.snapshotter.directory = fleet_dir
    path_f = wf_f.snapshotter.save("elastic_fleet")
    # prewarm the fleet family once so EVERY fleet boot below is
    # cache-warm — the elasticity story depends on it
    pre = InferenceServer(_build_fleet_workflow(), snapshot=path_f,
                          max_batch=FLEET_MAX_BATCH).start()
    fleet_cold_boot_s = pre.boot_to_ready_s
    pre.stop()

    balancer = ReplicaBalancer(
        replica_ttl_s=1.2, failover_timeout_s=1.0, failover_tries=4,
        hedge_floor_s=0.4, min_replicas=ELASTIC_MIN).start()

    wfs = [_build_fleet_workflow() for _ in range(ELASTIC_REPLICAS)]
    binds = ["tcp://127.0.0.1:*"] * ELASTIC_REPLICAS

    def make_factory(i):
        def make():
            return InferenceServer(
                wfs[i], bind=binds[i], snapshot=path_f,
                max_batch=FLEET_MAX_BATCH, max_delay_ms=2.0,
                queue_bound=64, announce=balancer.endpoint,
                replica_id=f"r{i}")
        return make

    harnesses = [ReplicaHarness(make_factory(i))
                 for i in range(ELASTIC_REPLICAS)]
    for i, h in enumerate(harnesses):
        h.start()
        binds[i] = h.server.endpoint

    class _SpawnedReplica:
        """FleetScaler handle for one autoscaler-spawned replica."""

        def __init__(self, i):
            self.replica_id = f"s{i}"
            self.server = None

        def start(self):
            self.server = InferenceServer(
                _build_fleet_workflow(), snapshot=path_f,
                max_batch=FLEET_MAX_BATCH, max_delay_ms=2.0,
                queue_bound=64, announce=balancer.endpoint,
                replica_id=self.replica_id).start()
            return self

        def kill(self):
            if self.server is not None:
                self.server.stop()

    class _HarnessHandle:
        """Retire adapter: a scale-down of an initial replica kills
        its harness for good (settle-phase only — the preemption
        schedule has already run by then)."""

        def __init__(self, rid, harness):
            self.replica_id = rid
            self._h = harness

        def kill(self):
            self._h.kill()

    scaler = FleetScaler(_SpawnedReplica)
    for i, h in enumerate(harnesses):
        scaler.adopt(_HarnessHandle(f"r{i}", h))

    t0 = _time.perf_counter()
    while balancer.ready_count() < ELASTIC_REPLICAS:
        if _time.perf_counter() - t0 > 120:
            raise SystemExit("elastic fleet never became ready")
        _time.sleep(0.05)

    cli = InferenceClient(balancer.endpoint, timeout=25.0,
                          resend_after_s=60.0, breaker_failures=0)
    x1 = rng.normal(0, 1, (1, 28 * 28)).astype(np.float32)
    infer_rids = set()
    answers: dict = {}
    warm_seen: dict = {}            # replica_id -> (warm_source, boot_s)

    def pump(wait=0.002):
        for rep in cli.collect(wait):
            rid = rep.get("req_id")
            if rid not in infer_rids:
                continue
            if rid in answers:
                raise SystemExit(f"req {rid} answered twice — "
                                 f"exactly-once broken")
            answers[rid] = bool(rep.get("ok"))

    def note_members():
        for row in balancer.stats()["replicas"]:
            if row["warm_source"] is not None:
                warm_seen[row["replica_id"]] = (row["warm_source"],
                                                row["boot_s"])

    def ok_count():
        return sum(1 for ok in answers.values() if ok)

    def drive_open(duration_s, qps):
        n0 = ok_count()
        t0 = _time.perf_counter()
        i = 0
        while _time.perf_counter() - t0 < duration_s:
            now = _time.perf_counter() - t0
            if now >= i / qps and cli.in_flight < 256:
                infer_rids.add(cli.submit(x1))
                i += 1
            pump()
        return ok_count() - n0, _time.perf_counter() - t0

    def drain(budget_s=25.0):
        t0 = _time.perf_counter()
        while cli.in_flight and _time.perf_counter() - t0 < budget_s:
            pump(0.02)

    # B1: pre-chaos baseline (autoscaler not armed yet)
    ok_base, el_base = drive_open(ELASTIC_BASE_S, ELASTIC_BASE_QPS)
    drain()
    goodput_base = ok_base / el_base
    note_members()

    # B2: arm the autoscaler, then ramp + preempt half the fleet
    balancer.enable_autoscale(
        scaler.spawn, scaler.retire, autoscale_max=ELASTIC_MAX,
        autoscale_high_load=0.75, autoscale_low_load=0.05,
        autoscale_up_after=2, autoscale_down_after=6,
        autoscale_eval_s=0.25, autoscale_cooldown_s=1.5,
        autoscale_drain_timeout_s=8.0,
        autoscale_boot_deadline_s=ELASTIC_SCALEUP_DEADLINE_S)
    preempters = [
        SubtreePreempter(FaultSchedule(ELASTIC_SEED + 1),
                         [("r0", harnesses[0].kill,
                           harnesses[0].restart)],
                         kill_s=(2.0, 4.0), down_s=(2.0, 3.0)),
        SubtreePreempter(FaultSchedule(ELASTIC_SEED + 2),
                         [("r1", harnesses[1].kill,
                           harnesses[1].restart)],
                         kill_s=(7.0, 9.0), down_s=(2.0, 3.0)),
    ]
    for p in preempters:
        p.start()
    t_ramp0 = _time.perf_counter()
    scaled_ready_at = None
    n0 = ok_count()
    while _time.perf_counter() - t_ramp0 < ELASTIC_CHAOS_S:
        while cli.in_flight < ELASTIC_INFLIGHT:
            infer_rids.add(cli.submit(x1))
        pump()
        if scaled_ready_at is None:
            for row in balancer.stats()["replicas"]:
                if row["replica_id"].startswith("s") and row["ready"]:
                    scaled_ready_at = _time.perf_counter() - t_ramp0
        note_members()
    el_chaos = _time.perf_counter() - t_ramp0
    for p in preempters:
        p.join(timeout=60)
    drain()
    ok_chaos = ok_count() - n0
    goodput_chaos = ok_chaos / el_chaos
    note_members()
    scale_ups = balancer.scale_ups

    # B3: idle settle — the low band must drain back down
    t0 = _time.perf_counter()
    while _time.perf_counter() - t0 < ELASTIC_SETTLE_S:
        pump(0.05)
        if balancer.scale_downs >= 1 and \
                _time.perf_counter() - t0 > 5.0:
            break
    scale_downs = balancer.scale_downs
    note_members()
    unanswered = [r for r in infer_rids if r not in answers]
    ledger = balancer.ledger()
    members_final = balancer.member_count()
    spawned_warm = {rid: ws for rid, ws in warm_seen.items()
                    if rid.startswith("s")}

    record = {
        "metric": "elastic_boot_ratio",
        "value": round(boot_ratio, 2),
        "unit": "cold_boot_over_cache_warm_boot",
        "ratio_floor": ELASTIC_BOOT_RATIO_FLOOR,
        "cold": cold,
        "warm": warm,
        "family": (cold["warm_report"] or {}).get("expected"),
        "fleet_cold_boot_s": round(fleet_cold_boot_s, 3),
        "seed": ELASTIC_SEED,
        "replicas": ELASTIC_REPLICAS,
        "goodput_base": round(goodput_base, 2),
        "goodput_chaos": round(goodput_chaos, 2),
        "goodput_band": ELASTIC_GOODPUT_BAND,
        "preemptions": sum(p.preemptions for p in preempters),
        "scale_ups": scale_ups,
        "scale_downs": scale_downs,
        "scaleup_ready_s": None if scaled_ready_at is None
        else round(scaled_ready_at, 2),
        "scaleup_deadline_s": ELASTIC_SCALEUP_DEADLINE_S,
        "warm_sources": warm_seen,
        "members_final": members_final,
        "unanswered": len(unanswered),
        "ledger": ledger,
        "failovers": balancer.failovers,
        "heals": balancer.heals,
        "replicas_lost": balancer.replicas_lost,
    }
    print(json.dumps(record))
    cli.close()
    balancer.stop()
    scaler.stop_all()
    for h in harnesses:
        h.kill()
    root.common.serving.aot_cache.update({"enabled": False, "dir": ""})
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if boot_ratio < ELASTIC_BOOT_RATIO_FLOOR:
        failures.append(
            f"cache-warm boot only {boot_ratio:.2f}x faster than cold "
            f"(floor {ELASTIC_BOOT_RATIO_FLOOR}x)")
    for b in (cold, warm):
        rep = b["warm_report"] or {}
        if not rep.get("ok"):
            failures.append(f"{b['which']} boot warm proof failed: "
                            f"{rep}")
        if b["recompiles_mixed_stream"]:
            failures.append(
                f"{b['recompiles_mixed_stream']} recompiles in the "
                f"{b['which']} boot's mixed stream (must be 0)")
        if b["jit_cache_after_stream"]:
            failures.append(
                f"{b['which']} boot: {b['jit_cache_after_stream']} "
                f"implicit jit cache entries slipped past the AOT "
                f"tables")
        if not b["parity_vs_cold"]:
            failures.append(f"{b['which']} boot answers diverged")
    wrep = warm["warm_report"] or {}
    if wrep.get("cache_hits") != wrep.get("expected"):
        failures.append(f"warm boot did not load the whole family "
                        f"from cache: {wrep}")
    if scale_ups < 1:
        failures.append("the ramp never triggered a scale-up")
    if scaled_ready_at is None:
        failures.append(
            f"no autoscaled replica became ready within the "
            f"{ELASTIC_CHAOS_S}s chaos window")
    elif scaled_ready_at > ELASTIC_SCALEUP_DEADLINE_S:
        failures.append(
            f"scale-up took {scaled_ready_at:.1f}s > deadline "
            f"{ELASTIC_SCALEUP_DEADLINE_S}s")
    bad_warm = {rid: ws for rid, ws in spawned_warm.items()
                if ws[0] != "cache_hit"}
    if bad_warm:
        failures.append(f"autoscaled replicas booted WITHOUT the "
                        f"cache: {bad_warm}")
    if goodput_chaos < ELASTIC_GOODPUT_BAND * goodput_base:
        failures.append(
            f"chaos goodput {goodput_chaos:.1f}/s < "
            f"{ELASTIC_GOODPUT_BAND} x baseline {goodput_base:.1f}/s")
    if sum(p.preemptions for p in preempters) < 2:
        failures.append("the seeded schedule preempted fewer than "
                        "half the initial fleet")
    if scale_downs < 1:
        failures.append("the idle settle never drained the grown "
                        "fleet (no scale-down)")
    if not ledger["balanced"] or ledger["in_flight"]:
        failures.append(f"ledger leaked: {ledger}")
    if unanswered:
        failures.append(f"{len(unanswered)} acknowledged requests "
                        f"never answered (no reply, no refusal)")
    shutil.rmtree(tmp, ignore_errors=True)
    if failures:
        raise SystemExit("elastic gates failed: " + "; ".join(failures))


#: --ingest gate knobs: the injected decode delay is calibrated to the
#: measured warm segment time (so the gate is structural, not an absolute
#: speed bet this host's swinging cgroup share can lose), clamped to
#: [floor, cap]; the gate then asserts the training thread's staged-
#: segment wait stays under INGEST_GATE_FRAC of the injected delay.
INGEST_DELAY_FLOOR_S = 0.02
INGEST_DELAY_CAP_S = 0.5
INGEST_GATE_FRAC = 0.5


def _build_ingest_workflow(delay_s: float, hidden: int, n_train: int,
                           n_valid: int, mb: int, max_epochs: int):
    """A host-staged streaming run (regime 3) whose decode path sleeps
    ``delay_s`` per segment gather — the injected stall the double buffer
    must absorb.  Shared by ``--ingest`` and the lean tier-1 test."""
    import time as _time

    from znicz_tpu.core import prng
    from znicz_tpu.core.mutable import Bool
    from znicz_tpu.loader.streaming import HostArraySource, StreamingLoader
    from znicz_tpu.standard_workflow import StandardWorkflow

    class DelayedSource(HostArraySource):
        """HostArraySource with a fixed sleep in the gather (decode)
        path — sleep, not spin: the injected stall must be absorbable by
        a thread that overlaps it, exactly like real PIL decode/IO."""

        delay_s = 0.0
        gathers = 0

        def gather(self, idx):
            type(self).gathers += 1
            if self.delay_s:
                _time.sleep(self.delay_s)
            return super().gather(idx)

    prng.reset(1013)
    rng = np.random.default_rng(7)
    n = n_train + n_valid
    data = (rng.random((n, 28, 28)) * 255).astype(np.uint8)
    labels = rng.integers(0, 10, n).astype(np.int32)
    src = DelayedSource(data, labels)
    src.delay_s = float(delay_s)
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    layers = [
        {"type": "all2all_strict_relu",
         "->": {"output_sample_shape": hidden}, "<-": dict(gd)},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": dict(gd)},
    ]
    wf = StandardWorkflow(
        name="IngestBench",
        loader=StreamingLoader(name="loader", source=src,
                               minibatch_size=mb,
                               class_lengths=[0, n_valid, n_train],
                               device_budget_bytes=0),
        layers=layers, loss_function="softmax",
        decision_config={"max_epochs": max_epochs, "fail_iterations": 0})
    wf.initialize(device=None)
    wf.snapshotter.gate_skip = Bool(True)   # measure ingest, not IO
    return wf, src


def run_ingest_overlap(delay_s: float = None, hidden: int = 2048,
                       n_train: int = 1024, n_valid: int = 128,
                       mb: int = 64, max_epochs: int = 3,
                       with_off: bool = True) -> dict:
    """The structural overlap measurement (ISSUE 7 satellite, the PR-6
    async-snapshot gate's shape): calibrate the warm segment time with no
    delay, inject ``delay_s`` (default: half the measured segment time,
    clamped) into the decode path, and record the training thread's
    per-segment staged wait — the double buffer absorbs the delay, so the
    wait must stay well under it even though EVERY segment's assembly
    slept that long on the stager worker.  Returns the measurement dict
    (gating is the caller's job — bench gates, the lean test asserts)."""
    import time as _time

    from znicz_tpu.core.config import root as _root
    from znicz_tpu.parallel.fused import FusedTrainer

    # phase 1 — calibrate: no delay, async staging on (warm compile too)
    wf, _src = _build_ingest_workflow(0.0, hidden, n_train, n_valid, mb,
                                      max_epochs=1)
    tr = FusedTrainer(wf)
    tr.run()
    warm_steps = max(tr.stats["warm_steps"], 1)
    step_s = (tr.stats["warm_wall_s"] / warm_steps
              if tr.stats["warm_wall_s"] > 0
              else tr.stats["wall_s"] / max(tr.stats["train_steps"], 1))
    segment_s = step_s * max(tr.scan_chunk, 1)
    if delay_s is None:
        delay_s = min(max(0.5 * segment_s, INGEST_DELAY_FLOOR_S),
                      INGEST_DELAY_CAP_S)
    # phase 2 — the gated run: delay injected, async staging ON
    wf2, src2 = _build_ingest_workflow(delay_s, hidden, n_train, n_valid,
                                       mb, max_epochs)
    t0 = _time.perf_counter()
    tr2 = FusedTrainer(wf2)
    tr2.run()
    on_wall = _time.perf_counter() - t0
    st = tr2._stager.stats() if tr2._stager is not None else None
    # phase 3 — context: same run, async staging OFF (every segment pays
    # the delay inline on the training thread); reported, not gated — the
    # structural gate above is what must hold on any host.  The lean
    # tier-1 test skips it (with_off=False): its assertions are all on
    # the ON run.
    off_wall = None
    if with_off:
        was_staging = _root.common.engine.get("async_staging", True)
        _root.common.engine.async_staging = False
        try:
            wf3, _ = _build_ingest_workflow(delay_s, hidden, n_train,
                                            n_valid, mb, max_epochs)
            t0 = _time.perf_counter()
            FusedTrainer(wf3).run()
            off_wall = _time.perf_counter() - t0
        finally:
            _root.common.engine.async_staging = was_staging
    return {
        "delay_ms": round(delay_s * 1e3, 2),
        "calibrated_segment_ms": round(segment_s * 1e3, 2),
        "scan_chunk": int(tr2.scan_chunk),
        "stager": st,
        "wait_ms_max": (None if st is None else st["wait_ms_max"]),
        "gate_frac": INGEST_GATE_FRAC,
        "segment_gathers": int(src2.gathers),
        "compiles": int(tr2._m_compiles.value),
        "jit_cache_sizes": tr2.jit_cache_sizes(),
        "wall_s_async_on": round(on_wall, 3),
        "wall_s_async_off": (None if off_wall is None
                             else round(off_wall, 3)),
        "on_vs_off": (round(off_wall / on_wall, 3)
                      if on_wall and off_wall is not None else None),
    }


def check_ingest_overlap(vals: dict, max_epochs: int) -> list:
    """The structural findings for one overlap run (shared by the bench
    gate and the tier-1 test; empty list = gate holds):

      - the stager engaged and (beyond the run's cold-start group) no
        dispatch group missed the double buffer;
      - the MEDIAN staged wait sits well under the injected delay — the
        hot loop (train segments following train segments) absorbed it;
      - waits near the delay are CONFINED to the per-epoch boundary
        groups: each epoch's first assembly cannot start before the tail
        is consumed (the lookahead must not advance past a tail — the
        snapshot at an epoch boundary must record tail state; resume
        parity), so one un-absorbed wait per epoch + the cold start is
        the structural floor, and MORE than that means the overlap broke.
    """
    bad = []
    st = vals["stager"]
    if st is None:
        return ["async staging did not engage (stager is None) — the "
                "gate requires the host-staged regime"]
    if st["stage_hits"] < 1 or st["stage_misses"] > 1:
        bad.append(f"dispatch groups missed the double buffer: "
                   f"hits={st['stage_hits']} misses={st['stage_misses']}")
    delay_ms = vals["delay_ms"]
    p50 = st["wait_ms_p50"]
    if p50 is None or p50 > INGEST_GATE_FRAC * delay_ms:
        bad.append(f"median staged wait {p50}ms is not well under the "
                   f"injected {delay_ms}ms decode delay — the hot loop "
                   "is not absorbing it")
    big = [w for w in st["wait_ms_window"]
           if w > INGEST_GATE_FRAC * delay_ms]
    if len(big) > max_epochs + 1:
        bad.append(f"{len(big)} staged waits exceeded "
                   f"{INGEST_GATE_FRAC} x the delay ({big}) — more than "
                   f"the {max_epochs} epoch-boundary groups + cold "
                   "start; steady-state segments are stalling")
    return bad


def ingest_main() -> None:
    """``--ingest``: the ingest/compute overlap gate (ISSUE 7), one JSON
    line; FAILS (after the line — the record survives a trip) per
    ``check_ingest_overlap``."""
    max_epochs = 3
    vals = run_ingest_overlap(max_epochs=max_epochs)
    st = vals["stager"]
    p50 = None if st is None else st["wait_ms_p50"]
    print(json.dumps({
        "metric": "ingest_overlap_wait_ms_p50",
        "value": p50,
        "unit": "ms",
        "vs_baseline": (round(p50 / vals["delay_ms"], 5)
                        if p50 is not None else None),
        **vals,
    }))
    bad = check_ingest_overlap(vals, max_epochs)
    if bad:
        raise SystemExit("ingest overlap gate failed:\n  "
                         + "\n  ".join(bad))


def telemetry_main() -> None:
    """``--telemetry``: the telemetry-layer overhead gate (ISSUE 5), one
    JSON line.  Drives the REAL fused training hot loop
    (``FusedTrainer.run`` over a small MNIST MLP) in interleaved windows
    with the telemetry layer enabled vs disabled
    (``telemetry.set_enabled``: spans + the trainer's step histogram —
    the optional layer; service accounting counters predate telemetry
    and run either way), and FAILS if the enabled best-of step time
    exceeds the disabled best-of by more than ``TELEMETRY_GATE_PCT``
    percent.  The gate is relative and same-process, so it holds on this
    TPU-less container and transfers unchanged to a TPU host."""
    import time as _time

    from znicz_tpu import telemetry
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root as _root
    from znicz_tpu.core.mutable import Bool
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    _root.mnist.loader.n_train = 2048
    _root.mnist.loader.n_valid = 256
    _root.mnist.loader.n_test = 0
    _root.mnist.loader.minibatch_size = 256
    _root.mnist.decision.max_epochs = 10_000    # windows drive epochs
    _root.mnist.layers = [256, 10]
    try:
        wf = mnist.MnistWorkflow()
    finally:
        _root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    wf.snapshotter.gate_skip = Bool(True)   # isolate the telemetry layer
    trainer = FusedTrainer(wf)
    d = wf.decision

    def window(enabled: bool) -> float:
        """Per-step wall time of one TELEMETRY_EPOCHS-epoch run
        continuation (the decision is re-armed; loader/prng state flows
        on, so every window runs the same kind of steps)."""
        telemetry.set_enabled(enabled)
        d.complete.set(False)
        d.max_epochs = int(d.epoch_number) + 1 + TELEMETRY_EPOCHS
        s0 = trainer.steps_done
        t0 = _time.perf_counter()
        trainer.run()
        dt = _time.perf_counter() - t0
        return dt / max(trainer.steps_done - s0, 1)

    window(True)                    # compile + cache warm, both variants
    window(False)
    best_on = best_off = float("inf")
    rounds = []
    overhead_pct = float("inf")
    for _ in range(TELEMETRY_MAX_ROUNDS):
        best_off = min(best_off, window(False))
        best_on = min(best_on, window(True))
        overhead_pct = 100.0 * (best_on / best_off - 1.0)
        rounds.append({"off_step_ms": round(best_off * 1e3, 4),
                       "on_step_ms": round(best_on * 1e3, 4),
                       "overhead_pct": round(overhead_pct, 3)})
        if overhead_pct <= TELEMETRY_GATE_PCT:
            break                   # gate met; no need to re-roll
    telemetry.set_enabled(True)
    print(json.dumps({
        "metric": "telemetry_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "vs_baseline": round(best_on / best_off, 5),
        "gate_pct": TELEMETRY_GATE_PCT,
        "step_ms_disabled": round(best_off * 1e3, 4),
        "step_ms_enabled": round(best_on * 1e3, 4),
        "epochs_per_window": TELEMETRY_EPOCHS,
        "rounds": rounds,
        "spans_recorded": telemetry.tracer().recorded,
        "metric_samples": sum(
            1 for ln in telemetry.render_prometheus().splitlines()
            if ln and not ln.startswith("#")),
    }))
    # gate AFTER the JSON line (the record survives a trip)
    if overhead_pct > TELEMETRY_GATE_PCT:
        raise SystemExit(
            f"telemetry overhead {overhead_pct:.3f}% exceeds the "
            f"{TELEMETRY_GATE_PCT}% gate on the training hot loop")


#: --obs protocol knobs (ISSUE 20): the fleet observability plane.
#: Three gates, one JSON line.  (1) The PR 5 overhead bar re-run on the
#: SERVING hot loop: interleaved set_enabled(on/off) windows over a
#: pipelined closed-loop infer stream against a real InferenceServer —
#: relative and same-process, so it holds on this swinging-cgroup host
#: and transfers to a TPU host unchanged.  (2) A seeded chaos run over
#: scripted replicas (zero warmup: the gate is about the JOURNAL, not
#: the model): a blackholed replica under flood forces a failover, a
#: forced-high autoscaler band spawns, and a parity-mismatching swap
#: rolls back — the event journal must contain that causal chain with
#: first-occurrence order failover < autoscale_up < rollback and
#: strictly monotone seqs.  (3) Stitching across REAL OS processes: two
#: subprocess charlm generation replicas announce to an in-process
#: balancer; one generation request must land in the fleet trace store
#: as a single trace_id crossing >=3 fleet origins on >=2 distinct OS
#: pids (client + balancer in this interpreter, frontend/scheduler
#: spans shipped back on heartbeats and reply summaries from a child).
OBS_SEED = 2008
OBS_GATE_PCT = 2.0          # enabled may cost at most this much
OBS_WINDOW_REQS = 300       # closed-loop requests per on/off window
OBS_INFLIGHT = 16           # client pipeline depth in the windows
OBS_MAX_ROUNDS = 6          # bounded interleaved best-of pairs
OBS_CHAOS_STAGE_S = 20.0    # per-stage flood budget in the chaos run
OBS_GEN_REPLICAS = 2        # subprocess generation replicas
OBS_GEN_BOOT_S = 300.0      # child compile+announce budget (1 core)
OBS_STITCH_S = 60.0         # generation stitching budget

#: The gate-3 child: a real OS process running one tiny charlm
#: generation replica that announces to the parent's balancer.  Spans
#: ride its heartbeats; params are seed-pinned so both children answer
#: bit-identically (routing stays free).
_OBS_CHILD = """
import sys
from znicz_tpu.core import prng
from znicz_tpu.core.config import root
root.charlm.loader.update({"n_train": 64, "n_valid": 16, "n_test": 0,
                           "seq_len": 32, "minibatch_size": 16})
root.charlm.model.update({"vocab": 32, "embed": 32, "heads": 2,
                          "ffn": 64})
root.common.serving.seq.rungs = [8, 32]
root.common.serving.generate.update({"enabled": True, "page_size": 8,
                                     "slots": 4})
prng.reset(1013)
from znicz_tpu.samples.charlm import CharLMWorkflow
from znicz_tpu.serving import InferenceServer
wf = CharLMWorkflow()
wf.initialize(device=None)
srv = InferenceServer(wf, max_batch=4, max_delay_ms=1.0,
                      announce=sys.argv[1],
                      replica_id=sys.argv[2]).start()
sys.stdin.read()        # parent closes stdin -> clean exit
srv.stop()
"""


def obs_main() -> None:
    """``--obs``: the fleet observability gates (ISSUE 20), one JSON
    line; gates AFTER the line so a trip never destroys the record."""
    import subprocess
    import time as _time

    from znicz_tpu import telemetry
    from znicz_tpu.parallel.chaos import FleetScaler, ScriptedReplica
    from znicz_tpu.serving import (InferenceClient, InferenceServer,
                                   ReplicaBalancer)

    sys.setswitchinterval(1e-3)
    telemetry.set_enabled(True)
    rng = np.random.default_rng(OBS_SEED)

    # ---- gate 1: serving hot-loop overhead, interleaved on/off ----------
    srv = InferenceServer(_build_fleet_workflow(),
                          max_batch=FLEET_MAX_BATCH, max_delay_ms=1.0,
                          queue_bound=64).start()
    cli = InferenceClient(srv.endpoint, timeout=30.0,
                          breaker_failures=0)
    x1 = rng.normal(0, 1, (1, 28 * 28)).astype(np.float32)

    def window(enabled: bool) -> float:
        """Per-request wall time of one pipelined closed-loop window
        (submission capped at OBS_INFLIGHT in flight)."""
        telemetry.set_enabled(enabled)
        sent = done = 0
        t0 = _time.perf_counter()
        while done < OBS_WINDOW_REQS:
            while sent < OBS_WINDOW_REQS and \
                    cli.in_flight < OBS_INFLIGHT:
                cli.submit(x1)
                sent += 1
            done += sum(1 for _ in cli.collect(0.001))
        return (_time.perf_counter() - t0) / OBS_WINDOW_REQS

    window(True)                    # compile + cache warm, both variants
    window(False)
    best_on = best_off = float("inf")
    rounds = []
    overhead_pct = float("inf")
    for _ in range(OBS_MAX_ROUNDS):
        best_off = min(best_off, window(False))
        best_on = min(best_on, window(True))
        overhead_pct = 100.0 * (best_on / best_off - 1.0)
        rounds.append({"off_req_ms": round(best_off * 1e3, 4),
                       "on_req_ms": round(best_on * 1e3, 4),
                       "overhead_pct": round(overhead_pct, 3)})
        if overhead_pct <= OBS_GATE_PCT:
            break                   # gate met; no need to re-roll
    telemetry.set_enabled(True)
    cli.close()
    srv.stop()

    # ---- gate 2: seeded chaos -> the journal's causal chain -------------
    cur0 = telemetry.journal().last_seq
    bal = ReplicaBalancer(replica_ttl_s=1.0, heartbeat_s=0.25,
                          failover_timeout_s=0.5, failover_tries=4,
                          hedge=False, canary_requests=6,
                          parity_every=2, canary_timeout_s=20.0,
                          min_replicas=2).start()
    reps = [ScriptedReplica(bal.endpoint, f"r{i}",
                            snapshots={"diff": 3.0}).start()
            for i in range(2)]
    t0 = _time.time()
    while bal.ready_count() < 2:
        if _time.time() - t0 > 20:
            raise SystemExit("obs chaos fleet never became ready")
        _time.sleep(0.02)
    cli2 = InferenceClient(bal.endpoint, timeout=10.0,
                           breaker_failures=0, resend_after_s=30.0)
    x4 = np.arange(4, dtype=np.float32).reshape(1, 4) + 1.0

    def flood(pred, budget_s=OBS_CHAOS_STAGE_S):
        """Closed-loop flood until ``pred`` holds (refusals during the
        swap wave are expected traffic, not errors)."""
        t0 = _time.perf_counter()
        while _time.perf_counter() - t0 < budget_s:
            try:
                cli2.result(cli2.submit(x4), timeout=8)
            except Exception:
                pass
            if pred():
                return True
        return pred()

    # stage A (preemption under flood): a blackholed replica swallows
    # dispatches; the failover timeout re-dispatches them
    hole = ScriptedReplica(bal.endpoint, "hole", blackhole=True).start()
    t0 = _time.time()
    while "hole" not in {m["replica_id"]
                         for m in bal.stats()["replicas"]}:
        if _time.time() - t0 > 10:
            raise SystemExit("blackhole replica never joined")
        _time.sleep(0.02)
    failover_ok = flood(lambda: bal.failovers >= 1)
    # stage B: a forced-high band spawns through the FleetScaler
    scaler = FleetScaler(
        lambda i: ScriptedReplica(bal.endpoint, f"s{i}",
                                  snapshots={"diff": 3.0}))
    bal.enable_autoscale(
        scaler.spawn, scaler.retire, autoscale_max=4,
        autoscale_high_load=-1.0, autoscale_low_load=-2.0,
        autoscale_up_after=2, autoscale_down_after=2,
        autoscale_eval_s=0.05, autoscale_cooldown_s=0.05,
        autoscale_drain_timeout_s=5.0)
    scale_ok = flood(lambda: bal.scale_ups >= 1)
    # neutralize the band (neither high nor low can fire) and clear the
    # blackhole so the swap wave's canary probes cannot be swallowed
    bal.enable_autoscale(
        scaler.spawn, scaler.retire, autoscale_max=4,
        autoscale_high_load=1e9, autoscale_low_load=-1.0)
    hole.kill()
    t0 = _time.time()
    while "hole" in {m["replica_id"]
                     for m in bal.stats()["replicas"]}:
        if _time.time() - t0 > 15:
            break
        _time.sleep(0.05)
    # stage C: a parity-mismatching swap must auto-roll-back
    cli2._send({"cmd": "swap", "path": "diff"})
    rollback_ok = flood(lambda: bal.rollbacks >= 1, budget_s=40.0)

    events = telemetry.journal().since(cur0)
    seqs = [e["seq"] for e in events]
    monotone = all(b > a for a, b in zip(seqs, seqs[1:]))
    first: dict = {}
    for e in events:
        first.setdefault(e["kind"], e["seq"])
    chain = [{"kind": k, "seq": first.get(k)}
             for k in ("failover", "autoscale_up", "rollback")]
    chain_ok = (None not in [c["seq"] for c in chain]
                and chain[0]["seq"] < chain[1]["seq"] < chain[2]["seq"])
    scale_evt = next((e for e in events
                      if e["kind"] == "autoscale_up"), {})
    cli2.close()
    bal.stop()
    scaler.stop_all()
    for r in reps:
        r.kill()

    # ---- gate 3: one generation request stitched across OS processes ----
    bal3 = ReplicaBalancer(replica_ttl_s=2.5, heartbeat_s=0.25).start()
    # this process holds jax, and with it the chip: the children are
    # pinned to the cpu outright (the gate checks stitching, not speed)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _OBS_CHILD, bal3.endpoint, f"g{i}"],
        stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL, env=env)
        for i in range(OBS_GEN_REPLICAS)]
    my_pid = str(os.getpid())

    def stitched_gen_trace():
        """A trace crossing >=3 fleet origins with at least one span
        from a DIFFERENT OS pid (gate-2 leftovers can't qualify: their
        spans all carry this interpreter's pid)."""
        for tid, members in telemetry.fleet_trace().traces().items():
            origins: list = []
            for o, _ in members:
                if o not in origins:
                    origins.append(o)
            pids = {o.rsplit("@", 1)[-1] for o in origins}
            if len(origins) >= 3 and any(p != my_pid for p in pids):
                if all(s.get("args", {}).get("trace_id") == tid
                       for _, s in members):
                    return tid, origins, pids, members
        return None

    stitched = None
    gen_replies = 0
    try:
        t0 = _time.time()
        while bal3.ready_count() < OBS_GEN_REPLICAS:
            for p in procs:
                if p.poll() is not None:
                    raise SystemExit(
                        f"obs generation child exited rc={p.returncode} "
                        f"before announcing")
            if _time.time() - t0 > OBS_GEN_BOOT_S:
                raise SystemExit("obs generation fleet never became "
                                 "ready")
            _time.sleep(0.2)
        boot_s = _time.time() - t0
        cli3 = InferenceClient(bal3.endpoint, timeout=90.0,
                               breaker_failures=0)
        deadline = _time.time() + OBS_STITCH_S
        while _time.time() < deadline and stitched is None:
            prompt = rng.integers(1, 32, size=6).astype(np.uint8)
            rep = cli3.generate(prompt, max_new_tokens=8, timeout=90)
            assert len(rep["tokens"]) >= 1
            gen_replies += 1
            _time.sleep(0.05)
            stitched = stitched_gen_trace()
        cli3.close()
    finally:
        for p in procs:
            try:
                p.stdin.close()
            except Exception:
                pass
        for p in procs:
            try:
                p.wait(timeout=20)
            except Exception:
                p.kill()
        bal3.stop()

    tid, origins, pids, members = stitched or (None, [], set(), [])
    names = sorted({s.get("name", "") for _, s in members})
    print(json.dumps({
        "metric": "obs_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "gate_pct": OBS_GATE_PCT,
        "req_ms_disabled": round(best_off * 1e3, 4),
        "req_ms_enabled": round(best_on * 1e3, 4),
        "window_reqs": OBS_WINDOW_REQS,
        "rounds": rounds,
        "seed": OBS_SEED,
        "chaos": {
            "events": len(events),
            "monotone_seqs": monotone,
            "chain": chain,
            "autoscale_load": scale_evt.get("load"),
            "failovers": failover_ok,
            "scale_ups": scale_ok,
            "rollbacks": rollback_ok,
        },
        "stitched": {
            "trace_id": tid,
            "origins": origins,
            "os_pids": sorted(pids),
            "spans": len(members),
            "names": names,
            "gen_replies": gen_replies,
            "fleet_boot_s": round(boot_s, 1),
        },
    }))
    # gates AFTER the JSON line (the record survives a trip)
    failures = []
    if overhead_pct > OBS_GATE_PCT:
        failures.append(
            f"observability overhead {overhead_pct:.3f}% exceeds the "
            f"{OBS_GATE_PCT}% gate on the serving hot loop")
    if not (failover_ok and scale_ok and rollback_ok):
        failures.append(
            f"chaos stages incomplete: failover={failover_ok} "
            f"autoscale={scale_ok} rollback={rollback_ok}")
    if not monotone:
        failures.append("journal seqs are not strictly monotone")
    if not chain_ok:
        failures.append(
            f"journal lacks the failover -> autoscale_up -> rollback "
            f"causal chain: {chain}")
    if "load" not in scale_evt:
        failures.append("the autoscale_up event does not carry the "
                        "load numbers that drove it")
    if stitched is None:
        failures.append(
            f"no generation trace stitched across >=3 fleet origins "
            f"and >=2 OS pids within {OBS_STITCH_S:.0f}s "
            f"({gen_replies} generations served)")
    elif len(pids) < 2:
        failures.append(f"stitched trace stayed inside one OS "
                        f"process: {sorted(pids)}")
    if failures:
        raise SystemExit("obs gates failed: " + "; ".join(failures))


def _gd_finals(decision) -> dict:
    from znicz_tpu.loader.base import TRAIN, VALID

    return {"final_train_loss": round(decision.epoch_metrics[TRAIN]["loss"], 6),
            "valid_err_pct": round(decision.epoch_metrics[VALID]["err_pct"], 3),
            "epochs": int(decision.epoch_number) + 1}


def _mse_finals(decision) -> dict:
    from znicz_tpu.loader.base import TRAIN, VALID

    return {"final_train_mse": round(decision.epoch_metrics[TRAIN]["loss"], 6),
            "valid_mse": round(decision.epoch_metrics[VALID]["loss"], 6),
            "epochs": int(decision.epoch_number) + 1}


def _som_finals(decision) -> dict:
    return {"final_qerror": round(decision.epoch_qerror[-1], 6),
            "first_qerror": round(decision.epoch_qerror[0], 6),
            "epochs": len(decision.epoch_qerror)}


#: BASELINE config index -> (sample module name, finals extractor)
SAMPLE_CONFIGS = [
    (0, "mnist", _gd_finals),
    (1, "cifar", _gd_finals),
    (2, "mnist_ae", _mse_finals),
    (3, "kohonen", _som_finals),
]

#: Anchor tolerance BANDS (VERDICT r4 item 6 — defend, don't re-record):
#: {config: {metric: (center, half_width)}}.  Centers are the BASELINE.md
#: anchors; a change that moves a seeded final outside its band makes
#: --samples exit non-zero until BASELINE.md documents a side-by-side
#: justification (both formulations, same seeds) and re-centers the band.
#: Runs are seeded and CPU-pinned, so the widths absorb jax-version and
#: platform drift, not run-to-run noise.
ANCHOR_BANDS = {
    0: {"final_train_loss": (0.0109, 0.005), "valid_err_pct": (0.875, 0.5)},
    1: {"final_train_loss": (0.9501, 0.05), "valid_err_pct": (44.0, 1.5)},
    2: {"final_train_mse": (2.0818, 0.1), "valid_mse": (2.1689, 0.1)},
    3: {"final_qerror": (0.0505, 0.02)},
}


def check_anchor(config: int, vals: dict) -> list:
    """Out-of-band findings for one config's finals: a list of
    {metric, value, center, band} dicts (empty = all within band)."""
    out = []
    for metric, (center, half) in ANCHOR_BANDS.get(config, {}).items():
        if abs(vals[metric] - center) > half:
            out.append({"metric": metric, "value": vals[metric],
                        "center": center, "band": half})
    return out


def measure_samples() -> None:
    """BASELINE configs 0-3 at their default sample configs; one JSON line
    each (the BASELINE.md "Measured" column), each checked against its
    ANCHOR_BANDS tolerance; exits non-zero on any out-of-band final."""
    import importlib

    from znicz_tpu.core import prng

    failures = []
    for config, name, finals in SAMPLE_CONFIGS:
        prng.reset(1013)
        module = importlib.import_module(f"znicz_tpu.samples.{name}")
        wf = module.run()
        vals = finals(wf.decision)
        bad = check_anchor(config, vals)
        failures += [{"sample": name, **f} for f in bad]
        band_checks = {
            metric: {"center": center, "band": half,
                     "ok": not any(f["metric"] == metric for f in bad)}
            for metric, (center, half) in ANCHOR_BANDS.get(config,
                                                           {}).items()}
        print(json.dumps({"config": config, "sample": name, **vals,
                          "anchor_bands": band_checks}))
    if failures:
        print(json.dumps({"anchor_band_failures": failures}),
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from znicz_tpu.backends import configure_compile_cache

    configure_compile_cache()
    args = sys.argv[1:]
    if "--batch" in args:
        # labeled protocol VARIANT (not the headline): e.g. --batch 512
        # amortizes the constant per-step weight+optimizer HBM traffic
        # over more images (VERDICT r3 item 3c)
        BATCH = int(args[args.index("--batch") + 1])
        STEPS = max(1, (200 * 128) // BATCH)    # same images per window
        HEADLINE_GUARDS = False
    if "--master-bf16" in args:
        # labeled VARIANT: bf16-STORED master weights (f32 update math) —
        # halves the per-step param read+write traffic but changes
        # convergence semantics (weight rounding); never the headline
        from znicz_tpu.core.config import root as _r

        _r.common.engine.master_dtype = "bfloat16"
        HEADLINE_GUARDS = False
    if "--fused-elementwise" in args:
        # labeled VARIANT until BASELINE.md records the with/without
        # numbers: route the conv1/conv2 LRN+ReLU+pool block through the
        # single-pass Pallas kernel (znicz_tpu/pallas_fused_block.py).
        # Same protocol, same loss gates; the JSON line records the flag
        # so with/without runs are directly comparable.
        from znicz_tpu.core.config import root as _r

        _r.common.engine.fused_elementwise = True
        HEADLINE_GUARDS = False
    if "--fused-tail" in args:
        # labeled VARIANT mirroring --fused-elementwise (ISSUE 7): the
        # conv3-5 bias+ReLU, FC bias+ReLU+dropout and softmax-xent+grad
        # epilogues run fused (root.common.engine.fused_tail).  Combine
        # with --fused-elementwise for the full-fusion run; the
        # BASELINE.md r12 protocol is the with/without ladder.
        from znicz_tpu.core.config import root as _r

        _r.common.engine.fused_tail = True
        HEADLINE_GUARDS = False
    if "--samples" in args:
        measure_samples()
    elif "--telemetry" in args:
        telemetry_main()
    elif "--obs" in args:
        obs_main()
    elif "--ingest" in args:
        ingest_main()
    elif "--wire" in args:
        wire_main()
    elif "--agg" in args:
        agg_main()
    elif "--serve" in args:
        serve_main()
    elif "--fleet" in args:
        fleet_main()
    elif "--shard" in args:
        shard_main()
    elif "--shard-train" in args:
        shard_train_main()
    elif "--seq" in args:
        seq_main()
    elif "--generate" in args:
        generate_main()
    elif "--prefix" in args:
        prefix_main()
    elif "--elastic" in args:
        elastic_main()
    elif "--stream" in args:
        stream_main()
    elif "--product" in args:
        product_main()
    else:
        main(legacy="--legacy" in args)
