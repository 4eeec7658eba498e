"""Run cells as the driver does and keep every last line.

    python benchmark/tools/measure.py --out chiprun_out/<tag> \
        [--seconds S] <cell>:<trace>:<seed>[,<seed>...] ...

A parent that never touches jax (the chip belongs to the child), one child
process per run, in the order given.  Writes ``<out>.jsonl`` — one line per
run: cell, seed, trace, exit code, wall seconds and the run's last line —
and copies each run's detail file beside it.  With no ``--seconds`` the
window is ``run_seconds`` of ``BENCHMARK.json``.  Stops at the first run
that fails or is not ``correct`` unless ``--keep-going``: chip time is not
spent on a fault.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--keep-going", action="store_true",
                        help="do not stop at the first run that fails")
    parser.add_argument("runs", nargs="+")
    args = parser.parse_args()
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    worst = 0
    with open(args.out + ".jsonl", "a") as log:
        for spec in args.runs:
            cell, trace, seeds = spec.split(":")
            for seed in seeds.split(","):
                cmd = [sys.executable, *bench["command"][1:], "--workload",
                       cell, "--seed", seed, "--seconds", str(seconds),
                       "--trace", trace] + (["--tiny"] * args.tiny)
                t0 = time.time()
                proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                                      text=True)
                wall = time.time() - t0
                lines = proc.stdout.strip().splitlines()
                try:
                    last = json.loads(lines[-1])
                except (IndexError, ValueError):
                    last = None
                record = {"cell": cell, "seed": int(seed),
                          "trace": int(trace), "rc": proc.returncode,
                          "wall_s": wall, "line": last}
                if proc.returncode or last is None:
                    record["stderr"] = proc.stderr[-4000:]
                    record["stdout"] = proc.stdout[-2000:]
                log.write(json.dumps(record) + "\n")
                log.flush()
                print(json.dumps(record)[:1500], flush=True)
                worst = max(worst, abs(proc.returncode),
                            int(not (last or {}).get("correct")))
                if worst and not args.keep_going:
                    return worst
                detail = os.path.join(REPO, ".znicz_cache", "bench", cell,
                                      f"last_trace{trace}.json")
                if os.path.isfile(detail):
                    shutil.copy(detail, f"{args.out}.{cell}.trace{trace}"
                                        f".seed{seed}.json")
    return worst


if __name__ == "__main__":
    sys.exit(main())
