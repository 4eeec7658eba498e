"""The harness's self-checks.  Run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Nothing here measures anything; the cells run only as ``--tiny``
rehearsals.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402
from benchmark.reduce import xplane  # noqa: E402

BENCH = spec.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_cell(root, *args):
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=ENV, capture_output=True, text=True, timeout=600)


def last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_benchmark_json_holds_together():
    assert spec.check(BENCH) == []
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end",
                                   "per_layer"])
def test_names_and_units(group):
    for entry in BENCH[group]:
        assert spec.NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert spec.UNIT.match(entry["unit"]), entry["unit"]
            assert len(entry["unit"]) <= 16


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist(name):
    cell = spec.Cell(BENCH, name)
    assert callable(cell.driver().run)
    assert callable(cell.reference().forward)
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert cell.config["reduced"] == spec.by_name(
        BENCH["configs"], cell.entry["config"], "configuration")["reduced"]


def test_a_fault_is_found():
    broken = json.loads(json.dumps(BENCH))
    broken["workloads"][0]["traffic"] = "no-such-mix"
    broken["end_to_end"][0]["unit"] = "samples per second"
    faults = spec.check(broken)
    assert any("no-such-mix" in f for f in faults)
    assert any("unit" in f for f in faults)


def test_interval_arithmetic():
    assert xplane._union([(0, 4), (1, 2), (3, 6), (8, 9)]) == [[0, 6],
                                                               [8, 9]]
    assert xplane._subtract([[0, 10]], [[2, 3], [5, 12]]) == 4.0
    assert xplane._subtract([[0, 2], [4, 6]], []) == 4.0
    # a while of 10 holding two children of 3 and 4, then a lone op of 2
    events = [(0, 10, "while.1", "control"), (1, 4, "fusion.1", "other"),
              (5, 9, "convolution.2", "mxu"), (12, 14, "fusion.1", "other")]
    got = sorted(xplane._self_times(events))
    assert got == sorted([("while.1", "control", 3.0),
                          ("fusion.1", "other", 3.0),
                          ("convolution.2", "mxu", 4.0),
                          ("fusion.1", "other", 2.0)])


def test_fusion_table():
    """What a fusion computes comes from the compiled text: the recorded
    TPU program's two ``kOutput`` fusions hold the convolution and the
    matrix product (the second with ONE operand), its ``kLoop`` fusions
    neither."""
    with open(os.path.join(REPO, "benchmark", "testdata",
                           "tpu.hlo.txt")) as f:
        table = xplane.fusion_table([f.read()])
    assert table[("maximum_multiply_fusion.2",
                  "fused_computation.clone.clone")] is True
    assert table[("fusion.20", "fused_computation.5.clone.clone")] is True
    assert table[("fusion.16", "fused_computation.1.clone.clone")] is False
    assert table[("fusion.20", "")] is True
    assert sum(v for k, v in table.items() if k[1]) == 2


def test_categories():
    assert xplane.category("%all-reduce-start.3", {}) == "collective"
    assert xplane.category("while.7", {}) == "control"
    assert xplane.category("convolution.4", {}) == "mxu"
    assert xplane.category("copy.1", {}) == "other"
    # a fusion is what the compiled text says it is, and unknown without
    assert xplane.category("convert_convert_fusion.2", {}) == "unknown"
    assert xplane.category("convert_convert_fusion.2", {
        ("convert_convert_fusion.2", ""): False}) == "other"
    assert xplane.category("conv_general_dilated.66", {
        ("conv_general_dilated.66", ""): True}) == "mxu"
    tpu = ("%fusion.543 = (bf16[256,5,5,96]{0,3,2,1:T(8,128)(2,1)S(1)}, "
           "f32[256,5,5,96]{0,3,2,1:T(8,128)S(1)}) fusion(f32[256,5,5,96]"
           "{0,3,2,1:T(8,128)S(1)} %custom-call.50), kind=kOutput, "
           "calls=%fused_computation.1")
    assert xplane.parse_op(tpu) == (
        "fusion.543", "fusion", "kOutput",
        "(bf16[256,5,5,96], f32[256,5,5,96])", "fused_computation.1")
    key = ("fusion.543", "fused_computation.1")
    assert xplane.category(tpu, {}) == "unknown"
    assert xplane.category(tpu, {key: True}) == "mxu"
    assert xplane.category(tpu, {key: False}) == "other"
    assert xplane.category(
        "%all-reduce-start.1 = f32[96]{0} all-reduce-start(f32[96]{0} %x), "
        "replica_groups={}", {}) == "collective"
    assert xplane.category(
        "%while.3 = (s32[]) while((s32[]) %t), body=%b", {}) == "control"
    assert xplane.op_label(tpu) == ("fusion.543 fusion/kOutput "
                                 "(bf16[256,5,5,96], f32[256,5,5,96])")


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(os.path.join(REPO, "benchmark", "testdata"))
    if f.endswith(".xplane.pb")))
def test_reduction_of_a_recorded_trace(name):
    """Three annotated runs of a four-step scan (conv, elementwise, matrix
    product): see ``benchmark/tools/record_testdata.py``."""
    path = os.path.join(REPO, "benchmark", "testdata", name)
    with open(path.replace(".xplane.pb", ".json")) as f:
        meta = json.load(f)
    with open(path.replace(".xplane.pb", ".hlo.txt")) as f:
        texts = [f.read()]
    red = xplane.reduce_trace(path, meta["ring_events"],
                              meta["sync_perf_s"], texts)
    assert len(red["devices"]) == meta["devices"]
    d0 = red["devices"][0]
    assert 0 < d0["busy_s"] <= d0["window_s"]
    assert 0 <= red["idle_share_device0"] < 1
    # one stream of operations: self times add up to the busy time
    assert sum(d0["ops_s"].values()) == pytest.approx(d0["busy_s"],
                                                      rel=0.02)
    assert d0["category_s"].get("mxu", 0) > 0
    assert d0["category_s"].get("other", 0) > 0
    assert "unknown" not in d0["category_s"]
    blind = xplane.reduce_trace(path)["devices"][0]["category_s"]
    assert blind.get("unknown", 0) > 0      # no text: nothing is guessed
    assert red["clock_offset_known"]
    labels = {label for label, _ in d0["longest_gaps"]}
    assert labels & set(meta["expected_gap_labels"]), labels
    shape = xplane.breakdown(red)
    assert len(shape["device_ops"]) <= 10 and shape["device_ops"]
    assert len(shape["idle_gaps"]) <= 10 and shape["idle_gaps"]


def test_refuses_a_cpu_without_tiny():
    proc = run_cell(REPO, "--workload", CELLS[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(str(tmp_path), "--workload", CELLS[0], "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--tiny")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("name,trace", [(CELLS[0], 0), (CELLS[-1], 1)])
def test_last_line_of_a_rehearsal(name, trace):
    proc = run_cell(REPO, "--workload", name, "--seed", "5", "--seconds",
                    "1", "--trace", str(trace), "--tiny")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = last_line(proc)
    assert line.pop("rehearsal") is True
    cell = spec.Cell(BENCH, name)
    metrics = cell.per_layer if trace else cell.end_to_end
    assert spec.check_line(line, metrics, bool(trace)) == []
    assert line["correct"] is True and line["failed"] == 0
    if trace:
        assert line["device"]["busy_s"] > 0
        assert line["breakdown"]["device_ops"]
    else:
        assert set(line["metrics"]) == {m["name"] for m in metrics}


def test_step_check_sees_a_weakened_update():
    """The check that stands for the backward pass, the all-reduce and
    the optimizer: the honest step passes, and a step that applies three
    quarters of every update (what an all-reduce that loses one of four
    shards does to a gradient) fails at the output layer, whose own noise
    is under a percent."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.backends import cache_dir

    cell = spec.Cell(BENCH, CELLS[0])
    driver = cell.driver()
    built = driver.build(cell, 5, True, os.path.join(cache_dir(), "bench"))
    first = built.lengths[0] + built.lengths[1]
    rows = range(first, first + int(built.wf.loader.max_minibatch_size))
    honest = driver.step_check(cell, built.trainer, built.wf.forwards,
                               built.init, built.data, built.labels, rows)
    assert driver.within(honest["by_layer"], built.step_check["tolerance"])
    assert honest["by_layer"][-1] < 0.02

    class Weakened:
        def __init__(self, trainer):
            self.inner, self.step = trainer, trainer.make_train_step()

        def __getattr__(self, name):
            return getattr(self.inner, name)

        def _train_step(self, params, velocities, *rest):
            old = jax.tree_util.tree_map(jnp.copy, params)
            new_p, new_v, metrics = self.step(params, velocities, *rest)
            return (jax.tree_util.tree_map(
                lambda a, b: b + 0.75 * (a - b), new_p, old), new_v, metrics)

    weak = driver.step_check(cell, Weakened(built.trainer),
                             built.wf.forwards, built.init, built.data,
                             built.labels, rows)
    assert weak["by_layer"][-1] > 0.2
    assert not driver.within(weak["by_layer"], [0.05] * 8)


DUMMY_DRIVER = '''
def run(ctx):
    assert ctx.cell.config["dummy_size"] == 3
    return {"setup_s": 0.5, "values": {"dummy_per_s": 7.0 * ctx.seed},
            "attempted": ctx.cell.traffic["requests"], "failed": 0,
            "correct": True, "dummy_counter": 42}
'''
DUMMY_READER = '''
def read(run):
    return run.get("dummy_counter")
'''


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A configuration, a traffic mix, a driver, a per-layer metric and a
    cell dropped in as new files plus entries: no file that was there is
    edited, and the command runs the new cell."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "znicz_tpu"), tmp_path / "znicz_tpu")
    before = {}
    for base, _, files in os.walk(tmp_path / "benchmark"):
        for f in files:
            p = os.path.join(base, f)
            before[p] = open(p, "rb").read()
    b = tmp_path / "benchmark"
    (b / "configs" / "dummy.json").write_text(json.dumps({
        "name": "dummy", "source": "none", "reference": "alexnet",
        "chips": 1, "dummy_size": 3, "reduced": []}))
    (b / "traffic" / "dummy-mix.json").write_text(json.dumps({
        "driver": "dummy_driver", "requests": 11}))
    (b / "drivers" / "dummy_driver.py").write_text(DUMMY_DRIVER)
    (b / "layer_metrics" / "dummy_count.py").write_text(DUMMY_READER)
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "dummy", "source": "none", "file":
                             "benchmark/configs/dummy.json", "reduced": [],
                             "why": "drop-in test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "drop-in test"})
    bench["end_to_end"].append({"name": "dummy_per_s", "unit": "x/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m and m["name"] not in ("setup_s",
                                                      "setup_compile_s"):
            m["workloads"] = list(CELLS)
    bench["per_layer"].append({"name": "dummy_count", "unit": "n",
                               "better": "higher", "source":
                               "program_counter", "layer": "dummy",
                               "moves": "dummy_per_s",
                               "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.check(bench, root) == []
    for trace, want in ((0, {"dummy_per_s": 21.0, "setup_s": 0.5}),
                        (1, {"dummy_count": 42.0})):
        proc = run_cell(root, "--workload", "dummy-cell", "--seed", "3",
                        "--seconds", "1", "--trace", str(trace), "--tiny")
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = last_line(proc)
        assert line["attempted"] == 11 and line["correct"] is True
        assert {k: v["value"] for k, v in line["metrics"].items()} == want
    for p, content in before.items():
        assert open(p, "rb").read() == content, p
