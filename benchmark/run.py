"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                            --trace <0|1> [--tiny]

A new process per run.  It refuses any platform but ``tpu`` (and any
``device_kind`` missing from ``benchmark/peaks.json``), builds the cell's
model and data from ``--seed``, warms the cell's own shapes against the
persistent compile cache inside the checkout, measures for ``--seconds``
and prints the contract's one JSON object as the last line of standard
output: the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics (and ``breakdown``) with ``--trace 1``.  Everything else — epoch
losses, counters, the full operation table — goes on earlier lines and
into ``.znicz_cache/bench/<cell>/last_trace<0|1>.json``.

``--tiny`` is the rehearsal: it shrinks every size through the ``tiny``
sections of the configuration and traffic files, runs on the CPU (four
virtual devices for a four-chip cell) and marks its line
``"rehearsal": true`` — no number of such a run is a measurement.
"""

from __future__ import annotations

import time

T0_PERF = time.perf_counter()           # before every heavy import

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


class Context:
    """What a driver gets: the cell, the arguments, the clock's origin, a
    compile meter, scratch space inside the checkout, and ``log``."""

    def __init__(self, cell, args, meter, peaks, t_backend):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.tiny = bool(args.tiny)
        self.meter = meter
        self.peaks = peaks
        #: where ``setup_s`` starts: the moment jax reported its devices.
        #: Starting python, importing jax and attaching to the TPU runtime
        #: took 9.6 to 22 s over 24 runs of one command (PERF.md) — none
        #: of it the program's or the benchmark's work, so it goes on an
        #: earlier line (``backend_init_s``) and not into the metric.
        self.t_backend = t_backend
        self.phases = {}        # set-up phase -> seconds since T0_PERF
        self.memory_peak_bytes = 0
        from znicz_tpu.backends import cache_dir

        self.cache_dir = os.path.join(cache_dir(), "bench")
        self.scratch_dir = os.path.join(self.cache_dir, cell.name)
        os.makedirs(self.scratch_dir, exist_ok=True)

    def phase(self, name: str) -> float:
        """Note that set-up reached ``name``; seconds since the start."""
        self.phases[name] = time.perf_counter() - T0_PERF
        return self.phases[name]

    def note_memory_peak(self) -> int:
        """The peak on the fullest chip so far.  A driver calls it where
        its window ends, so that what its checks allocate afterwards is
        not counted as the system's."""
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        self.memory_peak_bytes = max(
            (int(s.get("peak_bytes_in_use", 0)) for s in stats), default=0)
        return self.memory_peak_bytes

    @staticmethod
    def log(record: dict) -> None:
        print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)

    from benchmark import spec

    bench = spec.load()
    faults = spec.check(bench)
    if faults:
        print("benchmark: BENCHMARK.json does not hold together:\n  "
              + "\n  ".join(faults), file=sys.stderr)
        return 2
    cell = spec.Cell(bench, args.workload)
    if not os.path.isdir(os.path.join(REPO, "znicz_tpu")):
        print("benchmark: the system under test (znicz_tpu/) is not in "
              "this checkout", file=sys.stderr)
        return 2

    if args.tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if os.environ["JAX_PLATFORMS"] == "cpu" and cell.chips > 1:
            from znicz_tpu.virtdev import provision_cpu_devices

            provision_cpu_devices(cell.chips, verify=False)
    import jax

    devices = jax.devices()
    dev = devices[0]
    t_backend = time.perf_counter()     # the chip is held: set-up starts
    if not args.tiny:
        if dev.platform != "tpu":
            print(f"benchmark: no TPU — jax.devices()[0] is {dev.platform} "
                  f"({dev.device_kind}).  The benchmark measures the chip "
                  f"and does not fall back; rehearse with --tiny.",
                  file=sys.stderr)
            return 2
        if len(devices) < cell.chips:
            print(f"benchmark: cell {cell.name} needs {cell.chips} chips, "
                  f"jax sees {len(devices)}", file=sys.stderr)
            return 2
    try:
        peaks = spec.peaks_for(dev.device_kind)
    except spec.SpecError as exc:
        if not args.tiny:
            print(f"benchmark: {exc}", file=sys.stderr)
            return 2
        peaks = None

    from benchmark.meter import CompileMeter
    from znicz_tpu.backends import configure_compile_cache
    from znicz_tpu.core.logger import setup_logging

    setup_logging()
    cache = configure_compile_cache()
    meter = CompileMeter()
    ctx = Context(cell, args, meter, peaks, t_backend)
    ctx.log({"phase": "start", "cell": cell.name, "seed": ctx.seed,
             "seconds": ctx.seconds, "trace": ctx.trace, "tiny": ctx.tiny,
             "platform": dev.platform, "kind": dev.device_kind,
             "devices": len(devices), "compile_cache": cache,
             "jax": jax.__version__,
             "backend_init_s": t_backend - T0_PERF,
             "import_s": ctx.phase("imported")})

    run = cell.driver().run(ctx)
    run["compile"] = meter.snapshot()
    run["setup_phases_s"] = dict(ctx.phases,
                                 backend_ready=t_backend - T0_PERF)
    run["peaks"] = peaks
    run["chips"] = cell.chips

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": (ctx.memory_peak_bytes
                                    or ctx.note_memory_peak())}
    metrics = {}
    if ctx.trace:
        reduction = run.get("trace") or {}
        device["busy_s"] = reduction.get("busy_s", 0.0)
        device["window_s"] = reduction.get("window_s", 0.0)
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        values = dict(run["values"], setup_s=run["setup_s"])
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]),
            "failed": int(run["failed"]),
            "metrics": metrics, "device": device}
    if ctx.trace:
        from benchmark.reduce import xplane

        line["breakdown"] = xplane.breakdown(run.get("trace") or {})
    if ctx.tiny:
        line["rehearsal"] = True        # not a measurement

    detail = {k: v for k, v in run.items() if k != "trace"}
    if ctx.trace and run.get("trace"):
        detail["trace"] = dict(run["trace"], devices=[
            dict(d, ops_s=dict(list(d["ops_s"].items())[:40]))
            for d in run["trace"]["devices"]])
    detail["line"] = line
    detail["total_s"] = time.perf_counter() - T0_PERF
    if peaks and "train_samples_per_s" in run["values"]:
        flop_s = (run["values"]["train_samples_per_s"]
                  * run["shape"]["train_flops_per_step"]
                  / run["shape"]["batch"])
        detail["model_flops_utilization"] = flop_s / (
            cell.chips * peaks["bf16_tflops"] * 1e12)
    with open(os.path.join(ctx.scratch_dir,
                           f"last_trace{int(ctx.trace)}.json"), "w") as f:
        json.dump(detail, f, indent=1, default=str)
    if ctx.trace and run.get("trace", {}).get("devices"):
        d0 = run["trace"]["devices"][0]
        ctx.log({"phase": "trace", "window_s": d0["window_s"],
                 "busy_s": d0["busy_s"], "category_s": d0["category_s"],
                 "gap_s_by_label": d0["gap_s_by_label"],
                 "idle_share_worst": run["trace"]["idle_share_worst"],
                 "resident_set_ops_share": run["trace"].get(
                     "resident_set_ops_s", 0.0) / d0["busy_s"]})
    ctx.log({"phase": "detail", **{k: detail[k] for k in (
        "checks", "window", "parity", "step_check", "loss", "counters",
        "shape",
        "compile", "setup_cache", "setup_phases_s", "total_s",
        "model_flops_utilization") if k in detail}})
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
