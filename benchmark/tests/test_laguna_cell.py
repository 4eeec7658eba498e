"""The cell ``laguna-train-8k``: its rehearsal with ``--trace 0``
(``test_benchmark.py`` runs the last cell's with ``--trace 1``), its
operation counts, and its readers on runs that lack what they read."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops_decoder, spec                   # noqa: E402

BENCH = spec.load()
CELL = "laguna-train-8k"
NEW_METRICS = ("moe_ms_per_step", "attention_ms_per_step",
               "optimizer_ms_per_step", "experts_roofline",
               "attention_roofline", "moe_rows_max_over_mean", "train_mfu")


def test_the_cell_is_declared_as_the_issue_asks():
    cell = spec.Cell(BENCH, CELL)
    assert (cell.chips, cell.traffic_name, cell.driver_name) == (
        1, "rows-8k", "train_tokens")
    assert cell.entry["config"] == "laguna-xs2-ep8"
    assert {m["name"] for m in cell.end_to_end} == {"train_samples_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(NEW_METRICS)
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_samples_per_s"


def test_the_configuration_holds_the_catalog_row_and_the_cut():
    cfg = spec.Cell(BENCH, CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 32, 12544)
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                "vocab_size": 100352}
    # no width differs from the published configuration
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_key_value_heads"], cfg["sliding_window"]) == (
        2048, 128, 8192, 512, 8, 8, 512)
    assert cfg["parameters"]["state_bytes"] >= 11e9
    driver = spec.load_module("drivers", "train_tokens")
    model, share = driver.model_and_share(cfg, False)
    from znicz_tpu.samples import laguna

    program = laguna.MODELS["laguna-xs2"]
    assert {k: model[k] for k in program} == program
    assert share == laguna.PRESETS["xs2-ep8"]["share"]
    tiny_model, tiny_share = driver.model_and_share(cfg, True)
    assert tiny_model == laguna.MODELS["tiny"]
    assert tiny_share == laguna.PRESETS["tiny"]["share"]


@pytest.mark.parametrize("sizes", ["real", "tiny"])
def test_the_controls_fail_by_the_limits_the_file_holds(sizes):
    """Every limit lies under its control with room: a state left
    unchanged reads 1 in every group and kind and is not ``within``; the
    optimizer is the issue's, with no schedule beside it."""
    driver = spec.load_module("drivers", "train_tokens")
    cfg = spec.Cell(BENCH, CELL).config
    opt = cfg["optimizer"]
    assert (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"],
            opt["weight_decay"], opt["clipping"]) == (
        3e-4, 0.9, 0.95, 1e-8, 0.1, "none")
    assert "warmup_steps" not in opt and not any(
        "warmup" in key or "lr_adjust" in key for key in cfg["root"])
    limits = (cfg if sizes == "real" else cfg["tiny"])["step_check"][
        "tolerance"]
    reference = spec.load_module("references", "laguna")
    assert set(limits) == set(reference.GROUPS)
    assert not driver.within(dict.fromkeys(limits, driver.UNCHANGED), limits)
    for group in limits:            # one group unchanged is enough to fail
        sound = {g: dict.fromkeys(driver.UNCHANGED, 0.0) for g in limits}
        assert driver.within(sound, limits)
        assert not driver.within(dict(sound, **{group: driver.UNCHANGED}),
                                 limits)
    if sizes == "real":
        assert all(t["gradient"] <= 0.25 and t["update"] <= 0.75
                   for t in limits.values())
        assert cfg["parity"]["tolerance"] == 0.005


@pytest.mark.parametrize("seq,window,pairs", [
    (8, None, 36), (8, 3, 3 + 2 + 1 + 5 * 3), (8, 8, 36), (8, 100, 36),
    (8192, 512, 512 * 513 // 2 + 7680 * 512)])
def test_admitted_pairs(seq, window, pairs):
    assert flops_decoder.admitted_pairs(seq, window) == pairs
    if seq <= 8:
        brute = sum(1 for i in range(seq) for j in range(seq)
                    if j <= i and (window is None or i - j < window))
        assert brute == pairs


def test_the_step_counts_what_the_issue_reckoned():
    driver = spec.load_module("drivers", "train_tokens")
    model, share = driver.model_and_share(spec.Cell(BENCH, CELL).config,
                                          False)
    token = flops_decoder.token_forward_flops(model, share)
    attention = flops_decoder.attention_forward_flops(model, share, 2, 8192)
    experts = flops_decoder.expert_forward_flops_per_row(model) * 4
    assert 0.54e9 < token + experts < 0.56e9    # "0.55 GFLOP a token"
    assert 0.24e9 < attention / 16384 < 0.26e9  # "attention scores 0.25"
    whole = flops_decoder.window_flops(model, share, 2, 8192, 8, 1,
                                       8 * 4 * 16384, 8)
    assert whole["all"] == pytest.approx(
        25 * (16384 * (token + experts) + attention))
    full = flops_decoder.attention_forward_flops(model, share, 2, 8192,
                                                 "full")
    window = flops_decoder.attention_forward_flops(model, share, 2, 8192,
                                                   "window")
    assert full + window == attention and full > window


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(metric):
    """A run of a program without the scopes or the counter (the parent),
    and an untraced run: ``None``, no exception."""
    reader = spec.load_module("layer_metrics", metric)
    for run in ({}, {"trace": {}, "counters": {}, "shape": {}},
                {"trace": {"devices": [], "train_steps": 8, "eval_steps": 1,
                           "host_window_s": 1.0},
                 "shape": {"batch": 2}, "peaks": {"bf16_tflops": 197.0},
                 "counters": {"fused_stats": {"images": 3}}}):
        assert reader.read(dict(run)) is None


def test_last_line_of_the_rehearsal_without_a_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "0", "--tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line.pop("rehearsal") is True
    cell = spec.Cell(BENCH, CELL)
    assert spec.check_line(line, cell.end_to_end, False) == []
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_samples_per_s", "setup_s"}
    detail = next(json.loads(row) for row in lines
                  if row.startswith('{"phase": "detail"'))
    assert all(detail["checks"].values()), detail["checks"]
    stats = detail["counters"]["fused_stats"]
    assert stats["moe_rows_dropped"] == 0 and stats["tokens"] > 0
