"""``BENCHMARK.json`` and the files it names.

The harness is driven by data: a cell names a configuration and a traffic
mix, the traffic mix names its driver, a metric names its reader — and
each of those is a file under ``benchmark/`` found by that name.  Adding
a cell, a configuration, a mix or a per-layer metric is adding files and
one entry; nothing here knows any of their names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names does not hold together."""


def load(root: str = REPO) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json "
                    f"(have {[e['name'] for e in entries]})")


def read_json(*parts: str, root: str = REPO) -> dict:
    path = os.path.join(root, *parts)
    if not os.path.isfile(path):
        raise SpecError(f"missing file {os.path.relpath(path, root)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = REPO):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(root, "benchmark", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"missing file benchmark/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(bench: dict, group: str, cell: str) -> list:
    """The metrics of ``group`` that ``cell`` reports: those that list it
    under ``workloads``, and those that list no cells at all."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


class Cell:
    """One entry of ``workloads`` with its files resolved."""

    def __init__(self, bench: dict, name: str, root: str = REPO):
        self.bench = bench
        self.entry = by_name(bench["workloads"], name, "workload")
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg = by_name(bench["configs"], self.entry["config"],
                      "configuration")
        self.config = read_json(cfg["file"], root=root)
        self.traffic_name = self.entry["traffic"]
        self.traffic = read_json("benchmark", "traffic",
                                 f"{self.traffic_name}.json", root=root)
        self.driver_name = self.traffic["driver"]
        self.end_to_end = metrics_of(bench, "end_to_end", name)
        self.per_layer = metrics_of(bench, "per_layer", name)
        self.root = root

    def driver(self):
        return load_module("drivers", self.driver_name, self.root)

    def reference(self):
        return load_module("references", self.config["reference"],
                           self.root)

    def reader(self, metric: str):
        return load_module("layer_metrics", metric, self.root)


def peaks_for(device_kind: str, root: str = REPO) -> dict:
    """The published peaks of ``device_kind``; a device that is not in the
    table is an error, not a default."""
    table = read_json("benchmark", "peaks.json", root=root)
    try:
        return dict(table["devices"][device_kind], source=table["source"])
    except KeyError:
        raise SpecError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(have {sorted(table['devices'])}): add its published peaks "
            f"with their source before measuring on it") from None


def check(bench: dict, root: str = REPO) -> list:
    """Every rule of the contract that can be checked without running
    anything; returns the list of faults (empty: sound)."""
    faults = []

    def bad(msg):
        faults.append(msg)

    if set(bench) != TOP_KEYS:
        bad(f"top-level keys {sorted(bench)} != {sorted(TOP_KEYS)}")
        return faults
    if not (isinstance(bench["run_seconds"], int)
            and 1 <= bench["run_seconds"] <= 51):
        bad(f"run_seconds {bench['run_seconds']!r} not a whole number in "
            f"1..51")
    paths = bench["paths"]
    for word in bench["command"]:
        if os.path.exists(os.path.join(root, word)) and not any(
                word == p or word.startswith(p + "/") for p in paths):
            bad(f"command names {word!r}, a file outside paths")
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            if not NAME.match(e["name"]):
                bad(f"{group}: name {e['name']!r} outside [A-Za-z0-9_.-]")
            if (group, e["name"]) in names:
                bad(f"{group}: name {e['name']!r} twice")
            names.add((group, e["name"]))
    metric_names = [m["name"] for g in ("end_to_end", "per_layer")
                    for m in bench[g]]
    if len(set(metric_names)) != len(metric_names):
        bad("a metric name is used twice")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    if "setup_s" not in e2e:
        bad("end_to_end lacks setup_s")
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad(f"metric {m['name']}: unit {m['unit']!r} not allowed")
        if m["better"] not in ("lower", "higher"):
            bad(f"metric {m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            bad(f"metric {m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad(f"metric {m['name']} lists unknown cell {w!r}")
    for m in bench["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            bad(f"end_to_end {m['name']}: keys {sorted(m)}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad(f"end_to_end {m['name']}: source {m['source']!r}")
        if not 0.01 <= m["bound"] <= 0.1:
            bad(f"end_to_end {m['name']}: bound {m['bound']} outside "
                f"0.01..0.1")
    for m in bench["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            bad(f"per_layer {m['name']}: keys {sorted(m)}")
        if m["moves"] not in e2e:
            bad(f"per_layer {m['name']} moves unknown {m['moves']!r}")
        if not os.path.isfile(os.path.join(
                root, "benchmark", "layer_metrics", m["name"] + ".py")):
            bad(f"per_layer {m['name']}: no reader "
                f"benchmark/layer_metrics/{m['name']}.py")
    files = [c["file"] for c in bench["configs"]]
    if len(set(files)) != len(files):
        bad("two configurations share a file")
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"config {c['name']}: keys {sorted(c)}")
        if c["name"] not in used:
            bad(f"config {c['name']} is used by no cell")
        if not any(c["file"].startswith(p + "/") for p in paths):
            bad(f"config {c['name']}: file outside paths")
        for key in c["reduced"]:
            if not NAME.match(key):
                bad(f"config {c['name']}: reduced key {key!r}")
    pairs = set()
    four = 0
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"workload {w['name']}: keys {sorted(w)}")
        if w["chips"] not in (1, 4):
            bad(f"workload {w['name']}: chips {w['chips']!r}")
        four += w["chips"] == 4
        if not (NAME.match(w["traffic"]) and NAME.match(w["config"])):
            bad(f"workload {w['name']}: config/traffic name")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"] \
                or "\t" in w["why"]:
            bad(f"workload {w['name']}: why has {len(w['why'])} characters")
        if (w["config"], w["traffic"]) in pairs:
            bad(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            cell = Cell(bench, w["name"], root)
            cell.driver()
            cell.reference()
        except (SpecError, KeyError) as exc:
            bad(f"workload {w['name']}: {exc}")
            continue
        if cell.chips != int(cell.config["chips"]):
            bad(f"workload {w['name']}: {cell.chips} chips, its "
                f"configuration says {cell.config['chips']}")
        got = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in got or len(got) < 2:
            bad(f"workload {w['name']}: end-to-end metrics {sorted(got)}")
        if not cell.per_layer:
            bad(f"workload {w['name']}: no per-layer metric")
        for m in cell.per_layer:
            if m["moves"] not in got:
                bad(f"workload {w['name']}: {m['name']} moves "
                    f"{m['moves']}, which the cell does not report")
    if four > max(1, len(bench["workloads"]) // 4):
        bad(f"{four} cells on four chips of {len(bench['workloads'])}")
    return faults


def check_line(line: dict, metrics: list, traced: bool) -> list:
    """Faults of a run's last line against the contract."""
    faults = []
    keys = set(line) - ({"breakdown"} if traced else set())
    if keys != LINE_KEYS:
        faults.append(f"keys {sorted(line)}")
        return faults
    want = {m["name"] for m in metrics}
    if not set(line["metrics"]) <= want:
        faults.append(f"metrics {sorted(line['metrics'])} not all among "
                      f"{sorted(want)}")
    for name, m in line["metrics"].items():
        if set(m) != {"value", "unit"} or not isinstance(
                m["value"], (int, float)):
            faults.append(f"metric {name}: {m}")
    dev = set(line["device"])
    need = {"platform", "kind", "count", "memory_peak_bytes"}
    if traced:
        need |= {"busy_s", "window_s"}
    if not need <= dev:
        faults.append(f"device keys {sorted(dev)}")
    return faults
