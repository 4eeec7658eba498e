"""``benchmark/reduce/scopes.py`` and its four readers, on a recorded chip
trace of the fused trainer (``testdata/tpu-scopes.*``, written by
``tools/record_scopes.py`` against an empty compile cache) and on compiled
text written by hand where the trace has no such case."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import spec  # noqa: E402
from benchmark.reduce import scopes, xplane  # noqa: E402

DATA = os.path.join(os.path.dirname(HERE), "testdata", "tpu-scopes")
READERS = ("input_ms_per_dispatch", "norm_pool_ms_per_step",
           "tail_idle_ms_per_epoch", "dispatch_host_ms")


@pytest.fixture(scope="module")
def recorded():
    with open(DATA + ".json") as f:
        meta = json.load(f)
    with open(DATA + ".hlo.txt") as f:
        texts = ["HloModule" + t for t in f.read().split("HloModule")[1:]]
    return meta, texts, DATA + ".xplane.pb"


def run_of(meta, path, reduction):
    """What a reader is handed, as far as these readers look."""
    return {"trace": {"path": path, "train_steps": meta["steps"]["train"],
                      "eval_steps": meta["steps"]["eval"]},
            "scopes": reduction}


@pytest.mark.parametrize("op_name, want", [
    ("jit(chunk)/jit(main)/while/body/closed_call/jvp(conv1)/jit(relu)/max",
     ("conv1", "forward")),
    ("jit(step)/jit(main)/transpose(jvp(fwd_norm_1))/mul",
     ("fwd_norm_1", "backward")),
    ("jit(step)/jit(main)/fwd_max_pooling_2/reduce_window_max",
     ("fwd_max_pooling_2", "forward")),
    ("jit(chunk)/while/body/checkpoint/transpose(jvp(fc6))/dot_general",
     ("fc6", "backward")),
    ("jit(step)/update/fc6/mul", ("update/fc6", "update")),
    ("jit(step)/input/gather", ("input", "forward")),
    ("jit(step)/jvp(loss)/reduce_sum", ("loss", "forward")),
    # jax's own naming inside a library function is not the program's
    ("jit(chunk)/while/body/closed_call/jit(_threefry_fold_in)/"
     "FusedTrainer._train_body.<locals>.body/add", None),
    ("jit(step)/jit(main)/add", None),
    # a tree without the scopes: jax's empty wrappers and its names for
    # traced functions are not scopes
    ("jit(chunk)/while/body/closed_call/jvp()/jit(relu)/max", None),
    ("jit(chunk)/while/body/closed_call/transpose(jvp())/mul", None),
    ("jit(chunk)/while/body/closed_call/FusedTrainer.loss_and_metrics/reduce",
     None),
    ("jit(step)/jvp(fc6)/FusedTrainer.loss_and_metrics/reduce",
     ("fc6", "forward")),
    ("dataset", None),
])
def test_scope_of_an_op_name(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_table_sums_to_the_busy_time_of_reduce_trace(recorded):
    meta, texts, path = recorded
    table = scopes.reduce_scopes(path, texts)
    whole = xplane.reduce_trace(path, hlo_texts=texts)["devices"][0]
    assert sum(table["scope_s"].values()) == pytest.approx(
        whole["busy_s"], rel=1e-3)
    assert table["busy_s"] == pytest.approx(whole["busy_s"], rel=1e-9)
    assert table["unscoped_share"] < scopes.MAX_UNSCOPED
    named = {key.split("|")[0] for key in table["scope_s"] if "|" in key}
    assert "input" in named and "loss" in named
    for unit in meta["units"]:
        assert unit in named, (unit, sorted(named))
    directions = {key.split("|")[1] for key in table["scope_s"]
                  if "|" in key}
    assert {"forward", "backward", "update"} <= directions


def test_two_programs_of_one_name_are_kept_apart(recorded):
    """The train step and the tail's evaluation step are both ``jit_step``
    and share instruction names; each execution is read through its own
    text."""
    meta, texts, path = recorded
    table = scopes.reduce_scopes(path, texts)
    runs = table["executions"]
    # two traced epochs: a train scan, a validation scan, and a tail of
    # one evaluation and one train step each
    for program in ("jit_step/train", "jit_step/eval", "jit_chunk/train",
                    "jit_chunk/eval"):
        assert runs.get(program) == 2, runs
    assert table["input_executions"] == 8
    assert not table["programs_unmatched"]
    steps = [p for p in scopes.programs_by_name(texts)["jit_step"]]
    assert sorted(p.role for p in steps) == ["eval", "train"]
    shared = set(steps[0].instr) & set(steps[1].instr)
    assert any(steps[0].scope[n] != steps[1].scope[n] for n in shared)
    # the device's own count is the trainer's
    counted = (meta["stats_end"]["dispatches"]
               - meta["stats_start"]["dispatches"])
    assert counted == table["input_executions"]


LOOP = """HloModule jit_chunk, is_scheduled=true

%fused_gather (p0: bf16[64,8], p1: s32[4]) -> bf16[4,8] {
  %p0 = bf16[64,8]{1,0} parameter(0)
  %p1 = s32[4]{0} parameter(1)
  ROOT %g = bf16[4,8]{1,0} gather(%p0, %p1), metadata={op_name="jit(chunk)/while/body/input/gather"}
}

%fused_two (q0: bf16[4,8], q1: bf16[8,8]) -> bf16[4,8] {
  %q0 = bf16[4,8]{1,0} parameter(0)
  %q1 = bf16[8,8]{1,0} parameter(1)
  %d = bf16[4,8]{1,0} dot(%q0, %q1), metadata={op_name="jit(chunk)/while/body/jvp(fc1)/dot_general"}
  ROOT %m = bf16[4,8]{1,0} maximum(%d, %d), metadata={op_name="jit(chunk)/while/body/jvp(relu2)/max"}
}

%body (arg: (s32[], bf16[64,8], bf16[8,8], bf16[8,8])) -> (s32[], bf16[64,8], bf16[8,8], bf16[8,8]) {
  %arg = (s32[], bf16[64,8]{1,0}, bf16[8,8]{1,0}, bf16[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %set = bf16[64,8]{1,0} get-tuple-element(%arg), index=1
  %w = bf16[8,8]{1,0} get-tuple-element(%arg), index=2
  %v = bf16[8,8]{1,0} get-tuple-element(%arg), index=3
  %idx = s32[4]{0} iota(), iota_dimension=0
  %rows = bf16[4,8]{1,0} fusion(%set, %idx), kind=kLoop, calls=%fused_gather
  %both = bf16[4,8]{1,0} fusion(%rows, %w), kind=kOutput, calls=%fused_two
  %fwd = bf16[4,8]{1,0} dot(%both, %w), metadata={op_name="jit(chunk)/while/body/jvp(fc1)/dot_general"}
  %bwd = bf16[8,8]{1,0} dot(%fwd, %v), metadata={op_name="jit(chunk)/while/body/transpose(jvp(fc1))/dot_general"}
  %new = bf16[8,8]{1,0} add(%bwd, %v), metadata={op_name="jit(chunk)/while/body/update/fc1/add"}
  ROOT %out = (s32[], bf16[64,8]{1,0}, bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%i, %set, %new, %v)
}

%cond (carg: (s32[], bf16[64,8], bf16[8,8], bf16[8,8])) -> pred[] {
  %carg = (s32[], bf16[64,8]{1,0}, bf16[8,8]{1,0}, bf16[8,8]{1,0}) parameter(0)
  %ci = s32[] get-tuple-element(%carg), index=0
  ROOT %lt = pred[] compare(%ci, %ci), direction=LT
}

ENTRY %main (dataset: f32[64,8], w0: f32[8,8], v0: f32[8,8]) -> bf16[8,8] {
  %dataset = f32[64,8]{1,0} parameter(0)
  %w0 = f32[8,8]{1,0} parameter(1)
  %v0 = f32[8,8]{1,0} parameter(2)
  %zero = s32[] constant(0)
  %copy.62 = bf16[64,8]{1,0} copy(%dataset)
  %wcopy = bf16[8,8]{1,0} copy(%w0)
  %vcopy = bf16[8,8]{1,0} copy(%v0)
  %orphan = bf16[8,8]{1,0} copy(%v0)
  %init = (s32[], bf16[64,8]{1,0}, bf16[8,8]{1,0}, bf16[8,8]{1,0}) tuple(%zero, %copy.62, %wcopy, %vcopy)
  %loop = (s32[], bf16[64,8]{1,0}, bf16[8,8]{1,0}, bf16[8,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %result = bf16[8,8]{1,0} get-tuple-element(%loop), index=2
}
"""


def test_an_instruction_without_metadata_inherits_or_is_unscoped():
    program = scopes.Program(LOOP)
    # the whole-set cast: its only consumer is the gather in the loop's
    # body, two tuples and a parameter away
    assert program.scope["copy.62"] == ("input", "forward")
    assert program.scope["rows"] == ("input", "forward")
    # its named consumer is fc1's forward product (the mixed fusion beside
    # it names nothing)
    assert program.scope["wcopy"] == ("fc1", "forward")
    # read by fc1's backward and by its update: one layer's, no direction
    assert program.scope["vcopy"] == ("fc1", "any")
    assert program.scope["both"] == scopes.MIXED
    assert program.mixed_units["both"] == "fc1+relu2"
    # nothing consumes it and its producer is a parameter: not guessed
    assert program.scope["orphan"] == scopes.UNSCOPED
    assert program.scope["loop"] == scopes.UNSCOPED
    assert program.role == "train" and program.has_input


def test_stale_cache_text_makes_every_reader_report_nothing(recorded):
    """An executable fetched from a compile cache written before the scopes
    existed has no metadata (the cache's key ignores it)."""
    meta, texts, path = recorded
    stale = [re.sub(r", metadata=\{[^}]*\}", "", t) for t in texts]
    table = scopes.reduce_scopes(path, stale)
    assert table["unscoped_share"] > 0.95
    assert set(table["scope_s"]) == {scopes.UNSCOPED}
    cell = spec.Cell(spec.load(), "alexnet-train-steady")
    for name in READERS:
        assert cell.reader(name).read(run_of(meta, path, table)) is None
        assert cell.reader(name).read({"trace": None}) is None


def test_readers_on_the_recorded_trace(recorded):
    meta, texts, path = recorded
    table = scopes.reduce_scopes(path, texts)
    cell = spec.Cell(spec.load(), "alexnet-train-steady")
    values = {name: cell.reader(name).read(run_of(meta, path, table))
              for name in READERS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["input_ms_per_dispatch"] == pytest.approx(
        1e3 * sum(t for k, t in table["scope_s"].items()
                  if k.startswith("input|")) / 8)
    assert values["norm_pool_ms_per_step"] > 0
    assert values["dispatch_host_ms"] == table["dispatch_ms_median"] > 0
    assert table["tails"] == 1 and table["dispatch_spans"] == 2
    leaves = table["tail_idle_s_by_leaf"]
    assert values["tail_idle_ms_per_epoch"] == pytest.approx(
        1e3 * sum(leaves.values()))
    assert set(leaves) <= {"tail", "tail_eval", "sync", "decide",
                           "tail_update", "epoch_hook", "snapshot_copy",
                           "stage"}


def test_gap_labels_from_the_trace_agree_with_the_ring_offset(recorded):
    """The spans are in the trace on its clock; shifting the ring by the
    sync reading has to give the same picture."""
    meta, texts, path = recorded
    own = scopes.reduce_scopes(path, texts)["longest_gaps"]
    ring = [tuple(e) for e in meta["ring_events"]]
    shifted = xplane.reduce_trace(path, ring, meta["sync_perf_s"],
                                  texts)["devices"][0]["longest_gaps"]
    assert len(own) == len(shifted) > 0
    agree = sum(a[0] == b[0] for a, b in zip(own, shifted))
    assert agree >= len(own) - 1, (own, shifted)
    assert not any(label.startswith(("bench:", "unattributed"))
                   for label, _ in own[:5]), own


@pytest.mark.parametrize("cell", ["alexnet-train-steady",
                                  "alexnet-train-dp4"])
def test_tiny_rehearsal_prints_the_four_metrics(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", "1", "--trace", "1", "--tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] and line.pop("rehearsal")
    assert not spec.check_line(line, spec.Cell(spec.load(), cell).per_layer,
                               traced=True)
    assert set(READERS) <= set(line["metrics"]), sorted(line["metrics"])
    table = next(ln for ln in lines if ln.get("phase") == "scopes")
    assert table["unscoped_share"] < scopes.MAX_UNSCOPED
    assert table["input_executions"] > 0
