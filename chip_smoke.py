"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py [root.a.b=value ...]

One process, no subprocess, weights from a seed, no file git ignores read.
It refuses to run unless ``jax.devices()[0].platform == "tpu"`` and then
drives the main path once through the entry points a user would call:

  train    ``launcher.main(["alexnet", "--backend", "tpu", ...])`` at
           AlexNet's full width (227x227x3, the five convs, fc 4096/4096,
           1000 classes, batch 128, bf16 compute and optimizer state —
           the benchmark's ``alexnet`` configuration), 3 epochs of 4 scanned train steps
           plus validation through ``FusedTrainer.run``, Decision and the
           snapshotter gate;
  serve    ``launcher.main(["charlm", "--serve", ..., "--generate"])`` on
           a worker thread with an ``InferenceClient`` on this one:
           scoring requests of several lengths, generations alone, with an
           equal prompt and two at once — the donated ping-pong buffers
           and the donated K/V pools;
  kernels  every Pallas entry point compiled by Mosaic (never interpreted)
           at AlexNet's shapes, forward and backward, against the composed
           ops.

Each phase prints one JSON line (device, versions, compile-cache directory,
compile seconds apart from run seconds, peak HBM).  A failed check raises,
so no phase can fail and leave exit code 0; the last line of standard
output, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Dotted overrides on the command line pass through to both launcher calls
(four chips: ``root.common.engine.train_shard=True
root.common.engine.mesh.data=4``).
"""

from __future__ import annotations

import concurrent.futures
import importlib.metadata
import json
import os
import shutil
import socket
import sys
import tempfile
import threading
import time

import numpy as np

TRAIN_ARGS = [
    "root.alexnet.loader.minibatch_size=128",
    "root.alexnet.loader.n_train=512",
    "root.alexnet.loader.n_valid=128",
    "root.alexnet.loader.n_classes=1000",
    "root.alexnet.decision.max_epochs=3",
    "root.common.engine.compute_dtype=bfloat16",
    "root.common.engine.state_dtype=bfloat16",
]

#: the LRN every AlexNet block uses: n, alpha, beta, k
LRN = (5, 1e-4, 0.75, 2.0)

#: error ceilings (see ``err``), kernel vs the float32 composed ops:
#: (forward, backward)
KERNEL_TOL = {"float32": (1e-4, 2e-3), "bfloat16": (2e-2, 2e-2)}


class SmokeFailure(RuntimeError):
    """A phase ran and one of its checks did not hold."""


def check(ok, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


class CompileMeter:
    """Sums jax's own compile-time events (trace, lowering, backend compile
    or cache retrieval) and counts persistent-cache hits and misses, so each
    phase can report compile seconds apart from run seconds."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0       # backend compiles or cache retrievals
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self.EVENTS:
            self.seconds += seconds
            self.compiles += event == self.EVENTS[-1]

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def report(phase: str, meter: CompileMeter, before, t0: float, cache: str,
           **fields) -> None:
    """One JSON line for a finished phase."""
    import jax
    import jaxlib

    compile_s = meter.seconds - before[0]
    dev = jax.devices()[0]
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    print(json.dumps({
        "phase": phase, "ok": True,
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache_dir": cache,
        "compile_s": round(compile_s, 2),
        "run_s": round(time.perf_counter() - t0 - compile_s, 2),
        "cache_hits": meter.hits - before[1],
        "cache_misses": meter.misses - before[2],
        "peak_hbm_bytes": max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0),
        "bytes_in_use": [s.get("bytes_in_use", 0) for s in stats],
        **fields}), flush=True)


def train_phase(backend: str, overrides, scratch: str):
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.parallel.mesh import train_mesh_from_config

    launcher = Launcher(
        ["alexnet", "--backend", backend, *TRAIN_ARGS,
         f"root.common.dirs.snapshots={scratch}", *overrides])
    check(launcher.run() == 0, "train: the launcher did not return 0")
    wf = launcher.workflow
    history = wf.decision.epoch_history
    losses = [e["train"] for e in history]
    check(len(history) == int(wf.decision.max_epochs),
          f"train: {len(history)} epochs finished, want "
          f"{wf.decision.max_epochs}")
    check(all(np.isfinite(v) for e in history for v in e.values()),
          f"train: non-finite epoch loss in {history}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall: {losses}")
    # the launcher honoured the mesh the overrides asked for (none: one
    # device), and the state really sits in every device's memory
    mesh = train_mesh_from_config()
    n_devices = 1 if mesh is None else mesh.size
    for f in wf.forwards:
        for name, arr in f.params().items():
            devices = arr.devmem.sharding.device_set
            check(len(devices) == n_devices
                  and all(d.platform == backend for d in devices),
                  f"train: {f.name}.{name} lives on {devices}, want "
                  f"{n_devices} {backend} device(s)")
            for d in devices:
                memory = d.memory_stats()   # None where not reported
                check(memory is None or memory["bytes_in_use"] > 0,
                      f"train: nothing in use on {d}")
    stats = wf.fused_stats
    check(stats["compiles"] == sum(stats["jit_cache_sizes"].values()),
          f"train: {stats['compiles']} compiles vs jit caches "
          f"{stats['jit_cache_sizes']}")
    return {"train_loss": [round(v, 4) for v in losses],
            "valid_loss": [round(e["valid"], 4) for e in history],
            "valid_err_pct": round(
                wf.decision.epoch_metrics[1]["err_pct"], 2),
            "train_steps": stats["train_steps"],
            "compiles": stats["compiles"],
            "warm_img_per_sec": stats["warm_img_per_sec"],
            "param_devices": n_devices,
            "snapshots": sorted(os.listdir(scratch))}


def _free_endpoint() -> str:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return f"tcp://127.0.0.1:{s.getsockname()[1]}"


def serve_phase(backend: str, overrides, meter: CompileMeter):
    from znicz_tpu.launcher import Launcher
    from znicz_tpu.serving import InferenceClient

    rng = np.random.default_rng(1013)

    def tokens(n):
        return rng.integers(1, 32, size=n).astype(np.uint8)

    score = [tokens(5)[None], np.stack([tokens(17), tokens(17)]),
             np.stack([tokens(64)] * 3)]
    shared = tokens(20)             # > one 16-token page: the second
    #                                 request shares it, then copies on write
    alone, pair = [shared, shared], [tokens(5), tokens(33)]
    max_new = 8
    # one reply per scoring request (the generation prompts are scored
    # too), one per generation, and a last one after the final stats()
    n_requests = len(score) + 2 * (len(alone) + len(pair)) + 1
    endpoint = _free_endpoint()
    launcher = Launcher(
        ["charlm", "--backend", backend, "--serve", endpoint, "--generate",
         f"root.common.serving.max_requests={n_requests}", *overrides])
    served = concurrent.futures.Future()

    def serve():
        try:
            served.set_result(launcher.run())
        except BaseException as exc:    # re-raised by served.result()
            served.set_exception(exc)

    # a daemon: a failed check below must not leave the server holding
    # the process open
    threading.Thread(target=serve, daemon=True, name="chip-smoke-serve"
                     ).start()
    # nothing is re-sent (a duplicate would count toward max_requests) and
    # the breaker stays out of the way of the ping loop below
    cli = InferenceClient(endpoint, timeout=120.0, resend_after_s=3600.0,
                          breaker_failures=0)
    try:
        t_boot = time.perf_counter()
        while True:                 # warm-up compiles the whole family
            if served.done():       # .result() re-raises what killed it
                raise SmokeFailure(f"serve: the launcher returned "
                                   f"{served.result()} before serving")
            check(time.perf_counter() - t_boot < 600,
                  "serve: not ready within 600 s")
            try:
                cli.ping(timeout=5.0)
                break
            except TimeoutError:
                continue
        boot_s = time.perf_counter() - t_boot
        warm = cli.stats()
        warm_compiles = meter.compiles
        check(warm["warm_report"]["ok"],
              f"serve: warm-up proof failed: {warm['warm_report']}")
        for x in score:
            y = cli.infer(x)
            check(y.shape == x.shape + (32,) and np.isfinite(y).all(),
                  f"serve: infer {x.shape} -> {y.shape}, finite="
                  f"{bool(np.isfinite(y).all())}")
        replies = [cli.generate(p, max_new) for p in alone]
        rids = [cli.submit_generate(p, max_new) for p in pair]
        replies += [cli.result(r) for r in rids]
        for prompt, rep in zip(alone + pair, replies):
            toks = np.asarray(rep["tokens"])
            check(toks.shape == (max_new,) and toks.min() >= 0
                  and toks.max() < 32,
                  f"serve: generation returned {toks}")
            # the scoring plane must rate the prefill plane's greedy
            # pick as (one of) its best — two separate executables
            last = cli.infer(prompt[None])[0, -1]
            check(last[toks[0]] >= last.max() - 1e-3,
                  f"serve: generated token {toks[0]} scores "
                  f"{last[toks[0]]} under the scoring plane, max "
                  f"{last.max()}")
        check(np.array_equal(replies[0]["tokens"], replies[1]["tokens"]),
              f"serve: equal prompts, different greedy tokens: "
              f"{replies[0]['tokens']} vs {replies[1]['tokens']}")
        done = cli.stats()
        gen = done["generate"]
        check(done["model"]["compiles"] == warm["model"]["compiles"]
              and done["model"]["jit_cache_size"]
              == warm["model"]["jit_cache_size"]
              and meter.compiles == warm_compiles,
              f"serve: compiled after warm-up: traces "
              f"{warm['model']['compiles']} -> {done['model']['compiles']}"
              f", scoring jit cache {warm['model']['jit_cache_size']} -> "
              f"{done['model']['jit_cache_size']}, backend compiles "
              f"{warm_compiles} -> {meter.compiles}")
        check(gen["pages_leaked"] == 0 and gen["pages_active"]
              == gen["prefix_pages"],
              f"serve: pages leaked or still held: {gen}")
        cli.infer(score[0])         # the reply that reaches max_requests
    finally:
        cli.close()
    check(served.result(timeout=120) == 0,
          "serve: the launcher did not return 0")
    check(done["served"] == n_requests - 1 and not done["timed_out"]
          and not done["rejected"],
          f"serve: served {done['served']} of {n_requests - 1}, "
          f"timed_out {done['timed_out']}, rejected {done['rejected']}")
    return {"boot_to_ready_s": round(boot_s, 2),
            "executables": warm["warm_report"]["expected"],
            "compiles": done["model"]["compiles"],
            "donate": done["model"]["donate"],
            "served": done["served"],
            "prefix_hits": gen["prefix_hits"],
            "p50_ms": done["p50_ms"], "p99_ms": done["p99_ms"],
            "tokens": [np.asarray(r["tokens"]).tolist() for r in replies]}


def _composed_block(x, b):
    """bias + StrictRELU + LRN + 3x3/2 max pool as the trainer composes
    them when ``fused_elementwise`` is off."""
    import jax.numpy as jnp
    from jax import lax

    from znicz_tpu.lrn import lrn_ref

    y = lrn_ref(jnp.maximum(x + b, 0), *LRN)
    return lax.reduce_window(y, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "VALID")


def kernels_phase(batch: int = 128):
    import jax
    import jax.numpy as jnp

    from znicz_tpu.backends import pallas_interpret
    from znicz_tpu.pallas_fused_block import fused_bias_relu, fused_block

    check(not pallas_interpret(),
          "kernels: Pallas would run interpreted on this backend")
    cases = [   # name, kernel(x, bias), composed ops(x, bias), x shape
        ("fused_block", lambda x, b: fused_block(x, b, *LRN),
         _composed_block, (batch, 55, 55, 96)),
        ("fused_block", lambda x, b: fused_block(x, b, *LRN),
         _composed_block, (batch, 27, 27, 256)),
        ("fused_bias_relu", fused_bias_relu,
         lambda x, b: jnp.maximum(x + b, 0), (batch, 13, 13, 384)),
        ("fused_bias_relu", fused_bias_relu,
         lambda x, b: jnp.maximum(x + b, 0), (batch, 13, 13, 256)),
    ]

    def both_ways(fn):
        """out, d_x, d_bias of ``fn`` under one cotangent, as ONE jit —
        the forward kernel and the backward kernel compile together."""
        def run(x, b, cot):
            out, vjp = jax.vjp(fn, x, b)
            return (out,) + vjp(cot.astype(out.dtype))
        return jax.jit(run)

    def timed(fn, *args):
        out = jax.block_until_ready(fn(*args))      # compiles
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return out, round(best * 1e3, 3)

    def err(got, want):
        """99.9th-percentile error over the largest reference magnitude:
        the two pool subgradients route a tied window's gradient to
        different elements, and a handful of (near-)ties must not decide."""
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        return float(np.quantile(np.abs(got - want), 0.999)
                     / (np.abs(want).max() + 1e-30))

    def bf16_values(a):
        return jnp.asarray(a, jnp.bfloat16).astype(jnp.float32)

    rng = np.random.default_rng(1013)
    results = []
    for name, kernel, composed, shape in cases:
        # bf16-representable values: both dtypes see the same numbers, and
        # the float32 composed result is the reference for both (a bf16
        # plane is full of exact ties in the pool)
        b32 = bf16_values(0.1 * rng.normal(size=shape[-1:]))
        x32 = rng.normal(size=shape)
        # and pre-activations kept off the ReLU kink, where jnp.maximum's
        # subgradient is 1/2 and StrictRELU's (the kernels') is 0
        x32 = bf16_values(np.where(np.abs(x32 + b32) < 2 ** -5,
                                   x32 + 2 ** -3, x32))
        cot32 = jnp.asarray(rng.normal(
            size=jax.eval_shape(composed, x32, b32).shape), jnp.float32)
        want = jax.block_until_ready(both_ways(composed)(x32, b32, cot32))
        for dtype in ("float32", "bfloat16"):
            x, b, cot = (a.astype(dtype) for a in (x32, b32, cot32))
            got, kernel_ms = timed(both_ways(kernel), x, b, cot)
            _, composed_ms = timed(both_ways(composed), x, b, cot)
            errs = [err(g, w) for g, w in zip(got, want)]
            tol_f, tol_b = KERNEL_TOL[dtype]
            check(all(np.isfinite(np.asarray(g, np.float32)).all()
                      for g in got),
                  f"kernels: {name} {shape} {dtype} is not finite")
            check(errs[0] <= tol_f and max(errs[1:]) <= tol_b,
                  f"kernels: {name} {shape} {dtype} differs from the "
                  f"composed ops: out/dx/dbias errors {errs}, ceilings "
                  f"{tol_f}/{tol_b}")
            results.append({"kernel": name, "shape": list(shape),
                            "dtype": dtype,
                            "err": [float(f"{e:.2e}") for e in errs],
                            "kernel_ms": kernel_ms,
                            "composed_ms": composed_ms})
    return {"interpret": pallas_interpret(), "kernels": results}


def main(argv) -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU chip — jax.devices()[0] is "
              f"{dev.platform} ({dev.device_kind}).  This check proves "
              f"the program on the chip and does not fall back; run it "
              f"through the chip tool.", file=sys.stderr)
        return 2
    bad = [a for a in argv if "=" not in a]
    if bad:
        print(f"chip_smoke: arguments are dotted overrides "
              f"(root.a.b=value), got {bad}", file=sys.stderr)
        return 2

    from znicz_tpu import native
    from znicz_tpu.backends import configure_compile_cache

    def entries():
        return len(os.listdir(cache)) if os.path.isdir(cache) else 0

    cache = configure_compile_cache()
    entries_before = entries()
    meter = CompileMeter()
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        for phase, run in (
                ("train", lambda: train_phase("tpu", argv, scratch)),
                ("serve", lambda: serve_phase("tpu", argv, meter)),
                ("kernels", kernels_phase)):
            before, t0 = meter.snapshot(), time.perf_counter()
            report(phase, meter, before, t0, cache, **run())
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(json.dumps({
        "phase": "summary", "host_runtime": native.implementation(),
        "compile_s": round(meter.seconds, 2),
        "cache_hits": meter.hits, "cache_misses": meter.misses,
        "cache_entries_before": entries_before,
        "cache_entries_after": entries()}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
