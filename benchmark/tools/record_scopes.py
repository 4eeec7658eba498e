"""Record the small trace with the program's names kept under
``benchmark/testdata/``.

    python benchmark/tools/record_scopes.py <out>.xplane.pb

The fused trainer itself (``FusedTrainer.run``: scans, tail steps,
validation, Decision, epoch-end hook) over a three-unit network —
convolution, max pooling, softmax head, bf16 compute over a resident
float32 set, so that the whole-set cast is there; wide enough that the
operations outweigh the loops' own bookkeeping — for four epochs, the
last two under the profiler with the benchmark's own ``bench:sync`` and
``bench:epoch:N`` annotations, as the driver traces a cell.  Run it on the
chip against an EMPTY compile cache: an executable fetched from a cache
written before the scopes existed carries none (this tool turns the
persistent cache off).  Beside the trace go ``<out>.hlo.txt`` — the
compiled texts of the programs that ran, one after the other — and
``<out>.json``: the ring's events, the sync reading, the trainer's counters
and what the test expects.

The files are cut to what the reductions read, so that they stay small
enough to keep in the repository (the trace's operation names are whole HLO
lines): of the trace the first device plane's ``XLA Ops`` and ``XLA Modules``
lines (on the CPU the executor's ``hlo_op`` events) and the host plane's
``znicz:*``/``bench:*`` annotations, operation names without their operand
lists, no statistics but the three the reductions read; of the texts no
``backend_config`` and no source tables.  Cutting needs the ``XSpace``
protobuf classes (``tensorflow.tsl``); without them the files are written
whole.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KEPT_STATS = ("hlo_op", "hlo_module", "program_id", "run_id", "step_num")


def build():
    """The workflow initialised and its trainer."""
    from znicz_tpu import datasets
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.loader.fullbatch import FullBatchLoader
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.standard_workflow import StandardWorkflow

    prng.seed_all(7)
    root.common.engine.compute_dtype = "bfloat16"
    root.common.engine.scan_chunk = 4
    root.common.dirs.snapshots = tempfile.mkdtemp(prefix="scopes_snap_")

    class Loader(FullBatchLoader):
        def load_data(self):
            data, labels = datasets.tinyimages(840, size=43)
            self.original_data.mem = data
            self.original_labels.mem = labels
            self.class_lengths = [0, 240, 600]
            super().load_data()

    gd = {"learning_rate": 0.02, "gradient_moment": 0.9}
    wf = StandardWorkflow(
        name="ScopesRecord", loader=Loader(name="loader", minibatch_size=120),
        layers=[
            {"type": "conv_strict_relu",
             "->": {"n_kernels": 64, "kx": 5, "ky": 5,
                    "padding": (2, 2, 2, 2)}, "<-": dict(gd)},
            {"type": "max_pooling",
             "->": {"kx": 3, "ky": 3, "sliding": (2, 2)}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": dict(gd)}],
        loss_function="softmax",
        decision_config={"max_epochs": 4, "fail_iterations": 0})
    wf.initialize(device=None)
    return wf, FusedTrainer(wf)


def cut_text(text: str) -> str:
    """A compiled module's text without ``backend_config``, layouts,
    literals and source positions, and without the source tables between
    the header and the first computation."""
    from benchmark.reduce import xplane

    out, skipping, fused = [], False, False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skipping = True
        elif skipping and (line.startswith(("%", "ENTRY"))):
            skipping = False
        if skipping:
            continue
        at = line.find(", backend_config={")
        if at >= 0:
            depth, i = 0, line.index("{", at)
            for j in range(i, len(line)):
                depth += (line[j] == "{") - (line[j] == "}")
                if depth == 0:
                    break
            line = line[:at] + line[j + 1:]
        if line.startswith("HloModule"):
            out.append(line.split(",")[0])
            continue
        head, meta, rest = line.partition(", metadata={")
        head = re.sub(r", (?:sharding|frontend_attributes)=\{.*", "", head)
        head = xplane._LAYOUT.sub("", head)
        head = re.sub(r"constant\(.*\)", "constant()", head)
        if line.rstrip().endswith("{") and " = " not in line:
            head = re.sub(r" \(.*\{$", " () {", head)   # a computation
            fused = "fused_computation" in head or "_fusion" in head
        elif fused and re.search(r" (?:parameter|constant)\(", head):
            continue        # a fusion's body is read for its names only
        if meta:
            name = re.search(r'op_name="[^"]*"', rest)
            head += f", metadata={{{name.group(0)}}}" if name else ""
        out.append(head)
    return "\n".join(out) + "\n"


def cut_trace(raw: bytes) -> bytes:
    """The trace reduced to what ``reduce/xplane.py`` and
    ``reduce/scopes.py`` read (see the module's text)."""
    from benchmark.reduce import scopes

    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        print("record_scopes: no XSpace protobuf classes; trace kept whole",
              file=sys.stderr)
        return raw
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    kept = xplane_pb2.XSpace()
    device_done = False
    for plane in space.planes:
        device = (plane.name.startswith("/device:TPU:")
                  and plane.name.split(":")[-1].isdigit())
        if not (plane.name == "/host:CPU" or (device and not device_done)):
            continue
        device_done |= device
        stat_names = {k: m.name for k, m in plane.stat_metadata.items()}
        new = kept.planes.add(id=plane.id, name=plane.name)
        used_events, used_stats = set(), set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = []
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                stats = [s for s in ev.stats
                         if stat_names.get(s.metadata_id) in KEPT_STATS]
                if not device and not (
                        name.startswith(("znicz:", "bench:"))
                        or any(stat_names[s.metadata_id] == "hlo_op"
                               for s in stats)):
                    continue
                events.append((ev, stats))
            if not events:
                continue
            out = new.lines.add(id=line.id, name=line.name,
                                timestamp_ns=line.timestamp_ns,
                                duration_ps=line.duration_ps)
            for ev, stats in events:
                e = out.events.add(metadata_id=ev.metadata_id,
                                   offset_ps=ev.offset_ps,
                                   duration_ps=ev.duration_ps)
                for s in stats:
                    e.stats.add().CopyFrom(s)
                    used_stats.add(s.metadata_id)
                    if s.WhichOneof("value") == "ref_value":
                        used_stats.add(s.ref_value)
                used_events.add(ev.metadata_id)
        for k in used_events:
            name = plane.event_metadata[k].name
            if device and " = " in name:
                # "%x = shape opcode(operands), kind=.., calls=.." keeps
                # all that parse_op reads without the operand list
                head, _, rest = name.partition(" = ")
                shape_end = scopes.closing(rest, 0) + 1 if rest.startswith(
                    "(") else rest.index(" ")
                opcode = rest[shape_end:].lstrip().split("(")[0]
                tail = ", ".join(re.findall(r"(?:kind|calls)=[%\w.\-]+",
                                            rest[shape_end:]))
                name = (f"{head} = {rest[:shape_end].strip()} {opcode}()"
                        + (f", {tail}" if tail else ""))
            new.event_metadata[k].id = k
            new.event_metadata[k].name = name
        for k in used_stats:
            new.stat_metadata[k].CopyFrom(plane.stat_metadata[k])
    return kept.SerializeToString()


def main() -> int:
    import jax

    from benchmark.reduce import scopes, xplane
    from znicz_tpu import telemetry

    jax.config.update("jax_enable_compilation_cache", False)
    out = sys.argv[1]
    wf, trainer = build()
    tmp = tempfile.mkdtemp(prefix="scopes_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    marks = {}

    def on_epoch_end(decision):
        # as the driver: the profiler goes on at one epoch's end (inside
        # that tail's ``decide``) and off at another's
        epoch = int(decision.epoch_number)
        if epoch == 1:
            jax.profiler.start_trace(tmp, profiler_options=options)
            with jax.profiler.TraceAnnotation("bench:sync"):
                marks["sync"] = time.perf_counter()
            marks["stats_start"] = dict(trainer.stats)
            marks["annotation"] = jax.profiler.TraceAnnotation(
                "bench:epoch:2")
            marks["annotation"].__enter__()
        elif epoch == 2:
            marks["annotation"].__exit__(None, None, None)
            marks["annotation"] = jax.profiler.TraceAnnotation(
                "bench:epoch:3")
            marks["annotation"].__enter__()
        elif epoch == 3:
            marks["annotation"].__exit__(None, None, None)
            marks["stats_end"] = dict(trainer.stats)
            jax.profiler.stop_trace()

    wf.decision.on_epoch_end.append(on_epoch_end)
    telemetry.tracer().clear()
    trainer.run()
    path = xplane.newest_xplane(tmp)
    with open(path, "rb") as f:
        raw = f.read()
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "wb") as f:
        f.write(cut_trace(raw))
    shutil.rmtree(tmp, ignore_errors=True)

    texts = scopes.live_texts()
    ran = {name.split("(")[0]
           for name in scopes.reduce_scopes(out, texts)["executions"]}
    ran = {name.split("/")[0] for name in ran}
    texts = [cut_text(t) for t in texts
             if t.split(None, 2)[1].rstrip(",") in ran]
    with open(out.replace(".xplane.pb", ".hlo.txt"), "w") as f:
        f.write("".join(texts))
    reduction = scopes.reduce_scopes(out, texts)
    ring = [list(e) for e in telemetry.tracer().events()
            if e[0] == "train"
            and e[2] >= marks["sync"] * 1e6 - 1e6]
    with open(out.replace(".xplane.pb", ".json"), "w") as f:
        json.dump({
            "device_kind": jax.devices()[0].device_kind,
            # the first device plane alone is kept (``cut_trace``)
            "devices": 1,
            "sync_perf_s": marks["sync"], "ring_events": ring,
            "expected_gap_labels": sorted(
                {label for label, _ in reduction["longest_gaps"]}),
            "units": [f.name for f in wf.forwards],
            "steps": {"train": 2 * 5, "eval": 2 * 2},
            "stats_start": {k: v for k, v in marks["stats_start"].items()
                            if isinstance(v, (int, float))},
            "stats_end": {k: v for k, v in marks["stats_end"].items()
                          if isinstance(v, (int, float))},
            "recorded": {k: reduction[k] for k in (
                "executions", "input_executions", "unscoped_share",
                "tails", "epoch_hooks", "dispatch_spans")},
        }, f, indent=1)
    sizes = {p: os.path.getsize(p) for p in (
        out, out.replace(".xplane.pb", ".hlo.txt"),
        out.replace(".xplane.pb", ".json"))}
    print(json.dumps({"sizes": sizes, "raw_trace_bytes": len(raw),
                      "kind": jax.devices()[0].device_kind,
                      "reduction": {k: v for k, v in reduction.items()
                                    if k != "path"}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
