"""What the telemetry layer costs end to end, on one machine.

    python benchmark/tools/overhead.py --parent <unpacked parent tree>
        --cell <cell> [--runs 3] [--traced] --out chiprun_out/<tag>

Four sides — the parent tree and this tree, each as the driver runs it and
with ``root.common.telemetry.enabled=False`` (``tools/run_with.py``) —
``--runs`` times each at ``--trace 0``, one child process per run (the chip
belongs to the child), the order turned round from one repetition to the
next so that no side always runs first or last.  The four sides of a
repetition share a seed; every repetition has its own.  ``--traced`` adds
one ``--trace 1`` run of each tree at the end (the parent's shows that the
new readers report nothing there and do not raise).  The parent tree needs
this PR's ``benchmark/`` and ``BENCHMARK.json`` laid over it, as the driver
lays them.  Writes ``<out>.jsonl`` (one line per run) and prints the
medians.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OFF = "root.common.telemetry.enabled=False"


def one(tree, off, cell, seed, seconds, trace):
    cmd = [sys.executable, "benchmark/tools/run_with.py"] + [OFF] * off + [
        "--", "--workload", cell, "--seed", str(seed), "--seconds",
        str(seconds), "--trace", str(trace)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=tree, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        last = json.loads(lines[-1])
    except (IndexError, ValueError):
        last = None
    record = {"rc": proc.returncode, "wall_s": time.time() - t0,
              "line": last}
    if trace:
        record["earlier"] = [json.loads(ln) for ln in lines[:-1]
                             if ln.startswith('{"phase": "scopes"')
                             or ln.startswith('{"phase": "trace"')]
    if proc.returncode or last is None:
        record["stderr"] = proc.stderr[-3000:]
        record["stdout"] = proc.stdout[-1500:]
    return record


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--parent", required=True)
    parser.add_argument("--cell", required=True)
    parser.add_argument("--runs", type=int, default=3)
    parser.add_argument("--seed", type=int, default=2500000001)
    parser.add_argument("--traced", action="store_true")
    parser.add_argument("--out", required=True)
    args = parser.parse_args()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    sides = [("parent", os.path.abspath(args.parent), 0),
             ("change", REPO, 0), ("change", REPO, 1),
             ("parent", os.path.abspath(args.parent), 1)]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    values, worst = {}, 0
    with open(args.out + ".jsonl", "a") as log:
        def keep(record):
            nonlocal worst
            log.write(json.dumps(record) + "\n")
            log.flush()
            print(json.dumps(record)[:600], flush=True)
            worst = max(worst, abs(record["rc"]), int(not (
                record["line"] or {}).get("correct")))

        for rep in range(args.runs):
            turned = sides[rep % 4:] + sides[:rep % 4]
            for name, tree, off in turned:
                record = one(tree, off, args.cell, args.seed + rep, seconds,
                             0)
                record.update(side=name, telemetry="off" if off else "on",
                              rep=rep, seed=args.seed + rep,
                              cell=args.cell, trace=0)
                keep(record)
                metrics = (record["line"] or {}).get("metrics", {})
                for metric, m in metrics.items():
                    values.setdefault((name, record["telemetry"], metric),
                                      []).append(m["value"])
        if args.traced:
            for name, tree, _ in sides[:2][::-1]:
                record = one(tree, 0, args.cell, args.seed + args.runs,
                             seconds, 1)
                record.update(side=name, telemetry="on", cell=args.cell,
                              seed=args.seed + args.runs, trace=1)
                keep(record)
    print(json.dumps({"cell": args.cell, "medians": {
        "/".join(k): statistics.median(v) for k, v in values.items()},
        "all": {"/".join(k): v for k, v in values.items()}}))
    return worst


if __name__ == "__main__":
    sys.exit(main())
