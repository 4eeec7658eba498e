"""The cell ``zaya-train-32k``: how it is declared, the configuration's
count against a hand reckoning, ``flops_zaya`` against the issue's
numbers, the controls against the limits the file holds, its readers on
runs that lack what they read, and its rehearsal with ``--trace 0``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops_zaya, spec                      # noqa: E402

BENCH = spec.load()
CELL = "zaya-train-32k"
NEW_METRICS = ("step_mfu", "cca_ms_per_step", "cca_mix_ms_per_step",
               "cca_core_roofline", "mlp_router_ms_per_step",
               "experts_top1_roofline", "head_loss_ms_per_step",
               "head_loss_roofline", "top1_rows_max_over_mean",
               "experts_top1_ms_per_step", "adamw_ms_per_step")


def real():
    driver = spec.load_module("drivers", "train_tokens")
    return driver.model_and_share(spec.Cell(BENCH, CELL).config, False)


def test_the_cell_is_declared_as_the_issue_asks():
    cell = spec.Cell(BENCH, CELL)
    assert (cell.chips, cell.traffic_name, cell.driver_name) == (
        1, "rows-32k", "train_tokens_blocked")
    assert cell.entry["config"] == "zaya1-8b-ep2"
    assert cell.entry["why"] == (
        "1 row of 32,768 Zipf(1.1) ids a step, 8+1 steps an epoch, lr "
        "warm-up: held experts see 2,190 rows a step (busiest 2.5x), 1/2 "
        "the deployment's 4,096; half the head beside 4 of 40 layers weighs "
        "5x")               # the issue's, with the MEASURED load (review)
    entry = spec.by_name(BENCH["configs"], "zaya1-8b-ep2", "configuration")
    assert entry["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json "
        "(layers 0-3 of 40, experts 0-7 of 16, ids 0-131,135 of 262,272)")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert {m["name"] for m in cell.end_to_end} == {"train_samples_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(NEW_METRICS)
    unlisted = {m["name"] for m in cell.per_layer if "workloads" not in m}
    assert unlisted == {"train_gap_ms_per_step", "mxu_roofline",
                        "nonmxu_ms_per_step", "setup_compile_s"}
    for m in cell.per_layer:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_samples_per_s"
    # the new entries stand at the end of their lists
    assert BENCH["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in BENCH["per_layer"][-11:]] == list(NEW_METRICS)
    traffic = cell.traffic
    assert (traffic["warmup_epochs"], traffic["trace_epochs"],
            traffic["zipf"], traffic["generator"]) == (2, 1, 1.1, "tokens")
    assert traffic["snapshot"] == spec.read_json(
        "benchmark", "traffic", "rows-8k.json")["snapshot"]
    assert traffic["root"]["root.zaya.loader.seq_len"] == 32768
    assert traffic["root"]["root.zaya.loader.minibatch_size"] == 1


def test_the_configuration_holds_the_catalog_row_and_the_cut():
    cfg = spec.Cell(BENCH, CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 8, 131136)
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                                "vocab_size": 262272}
    # no width differs from the published configuration
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["router_hidden_size"],
            cfg["cca_time0"], cfg["cca_time1"],
            cfg["partial_rotary_factor"]) == (
        2048, 128, 2048, 1, 8, 2, 256, 2, 2, 0.5)
    assert cfg["tie_word_embeddings"] is True
    model, share = real()
    from znicz_tpu.samples import laguna, zaya

    assert model == laguna.MODELS["zaya1-8b"]
    assert share == zaya.PRESETS["zaya1-8b-ep2"]["share"]
    driver = spec.load_module("drivers", "train_tokens")
    tiny_model, tiny_share = driver.model_and_share(cfg, True)
    assert tiny_model == laguna.MODELS["zaya-tiny"]
    assert tiny_share == zaya.PRESETS["tiny"]["share"]
    assert cfg["assumed"] == zaya.ASSUMED
    assert set(cfg["assumed_why"]) == set(cfg["assumed"])
    assert cfg["assumed_keys"]["keys"] == zaya.ASSUMED_KEYS == {
        "scale_residual_merge": True}
    assert set(cfg["left_out"]) >= {"depth_skipping_expert"}
    opt = cfg["optimizer"]
    assert (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"],
            opt["weight_decay"], opt["clipping"]) == (
        3e-4, 0.9, 0.95, 1e-8, 0.1, "none")
    assert opt["schedule"]["policy"] == "warmup" \
        and opt["schedule"]["steps"] == 2000
    assert cfg["root"]["root.zaya.lr_adjust.policy"] == "warmup"


def test_the_parameter_count_against_a_hand_reckoning():
    """The file's count, the hand reckoning and the UNITS' own tensors
    agree; 16 bytes a parameter are 11.14 GB, 65 % of 16 GiB."""
    count = spec.Cell(BENCH, CELL).config["parameters"]
    d, hd, h, kv, r, e, f, vocab = 2048, 128, 8, 2, 256, 16, 2048, 131136
    block = (d * h * hd + 2 * d * kv * hd              # wq, wk, wv
             + 3 * (h + kv) * hd                       # taps and their bias
             + 2 * (h + kv) * hd * hd + (h + kv) * hd  # a matrix a tap, head
             + h * hd * d)                             # wo
    router = d * r + r + r + 2 * (r * r + r) + r * e + e   # and the bias
    small = 2 * d + kv + 8 * d      # norms, temperatures, merge vectors
    layer_0 = block + router + small + 8 * 3 * d * f
    held = layer_0 + 3 * (layer_0 + r) + vocab * d + d
    assert (block, router, small) == (5575680, 660496, 20482)
    assert held == count["held"] == 696249160
    # the issue's reading gave layer 0 a gamma too (256 more) and no layer
    # a selection bias (4 x 16 fewer)
    assert held + 256 - 64 == 696249352
    assert abs(held - 696.2e6) < 0.01 * 696.2e6
    assert count["state_bytes"] == 16 * held
    assert round(16 * held / 1e9, 2) == 11.14
    assert count["share_of_16_GiB"] == round(16 * held / 2 ** 34, 3) == 0.648
    # the units' own tensors, without building them
    from znicz_tpu import decoder
    from znicz_tpu.samples import laguna, zaya

    units = laguna.layers(dict(laguna.MODELS["zaya1-8b"],
                               **zaya.ASSUMED_KEYS),
                          zaya.PRESETS["zaya1-8b-ep2"]["share"],
                          {"learning_rate": 0, "weights_decay": 0,
                           "beta1": 0, "beta2": 0, "epsilon": 0})
    total = vocab * d + d               # the tied tensor once, the norm
    for unit in units[1:-1]:
        layer = decoder.DecoderLayer(None, name="probe", **unit["->"])
        layer.hidden = d
        total += sum(int(__import__("numpy").prod(shape))
                     for shape, _ in layer.param_shapes().values())
    assert units[-1]["->"]["tied"] is True
    assert total == held


@pytest.mark.parametrize("sizes", ["real", "tiny"])
def test_the_controls_fail_by_the_limits_the_file_holds(sizes):
    """Every limit lies under its control with room: a state left
    unchanged reads 1 in every group and kind and is not ``within``."""
    driver = spec.load_module("drivers", "train_tokens")
    cfg = spec.Cell(BENCH, CELL).config
    held = cfg if sizes == "real" else cfg["tiny"]
    limits = held["step_check"]["tolerance"]
    reference = spec.load_module("references", "zaya")
    assert set(limits) == set(reference.GROUPS)
    assert not driver.within(dict.fromkeys(limits, driver.UNCHANGED), limits)
    for group in limits:            # one group unchanged is enough to fail
        sound = {g: dict.fromkeys(driver.UNCHANGED, 0.0) for g in limits}
        assert driver.within(sound, limits)
        assert not driver.within(dict(sound, **{group: driver.UNCHANGED}),
                                 limits)
    lo, hi = held["routing"]["band"]
    assert 0 <= lo < 1 < hi <= 2        # at most 0.5-2 x at real sizes
    if sizes == "real":
        assert lo >= 0.5
        assert all(t["gradient"] <= 0.25 and t["update"] <= 0.75
                   for t in limits.values())
        assert held["parity"]["tolerance"] < 1
        assert 32768 % held["parity"]["logit_rows_a_block"] == 0


def test_the_step_counts_what_the_issue_reckoned():
    model, share = real()
    token = flops_zaya.token_forward_flops(model, share)
    assert flops_zaya.admitted_pairs(8) == 36
    core = flops_zaya.core_forward_flops(model, share, 1, 32768)
    # "67 M a token and layer for the core at 32,768"
    assert core / 32768 / 4 == pytest.approx(4 * 128 * 8 * 16384.5)
    assert 67.0e6 < core / 32768 / 4 < 67.2e6
    # "537 M for the head", "10.5 M for the block's projections", "25 M
    # for an expert"
    assert token["head"] == 2 * 131136 * 2048 == 537133056
    assert token["projections"] / 4 == 2 * 2048 * 128 * 20 == 10485760
    assert flops_zaya.expert_forward_flops_per_row(model) == 25165824
    assert token["mixing"] / 4 == 2 * 2 * 10 * 128 * 128
    assert token["router"] / 4 == 2 * (2048 * 256 + 2 * 256 * 256
                                       + 256 * 16)
    dots = flops_zaya.dot_forward_flops(model, share, 1, 32768)
    assert dots == 32768 * sum(token.values())      # no core, no experts
    whole = flops_zaya.window_flops(model, share, 1, 32768, 8, 1,
                                    9 * 4 * 16384, 9)
    assert whole["all"] == pytest.approx(
        25 * (dots + core + 4 * 16384 * 25165824))
    assert whole["head"] == 25 * 32768 * 537133056
    assert whole["core"] == 25 * core


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(metric):
    """A run of a program without the scopes or the counter (the parent),
    a run of the OTHER decoder, and an untraced run: ``None``, no
    exception."""
    reader = spec.load_module("layer_metrics", metric)
    laguna = spec.Cell(BENCH, "laguna-train-8k").config["tiny"]
    for run in ({}, {"trace": {}, "counters": {}, "shape": {}},
                {"trace": {"devices": [], "train_steps": 8, "eval_steps": 1,
                           "host_window_s": 1.0},
                 "shape": {"batch": 2}, "peaks": {"bf16_tflops": 197.0},
                 "counters": {"fused_stats": {"images": 3}}},
                {"trace": {"devices": [], "train_steps": 8, "eval_steps": 1,
                           "host_window_s": 1.0, "moe_rows_routed": 100,
                           "moe_counted_steps": 9},
                 "shape": {"batch": 2, "row_tokens": 64,
                           "model": laguna["model"],
                           "share": laguna["share"]},
                 "peaks": {"bf16_tflops": 197.0},
                 "counters": {"fused_stats": {
                     "moe_rows_by_expert": {"max": 3, "mean": 1, "min": 0}}}}):
        assert reader.read(dict(run)) is None


def test_last_line_of_the_rehearsal_without_a_trace():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483693", "--seconds", "1",
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
    assert set(detail["checks"]) >= {
        "logits_within_tolerance", "float8_control_fails",
        "step_within_tolerance", "unchanged_state_control_fails",
        "no_row_dropped", "rows_routed_in_band", "loss_in_blocks",
        "one_tied_tensor", "router_states_carried"}
    stats = detail["counters"]["fused_stats"]
    assert stats["moe_rows_dropped"] == 0 and stats["tokens"] > 0
    assert stats["loss_blocks"] > 1 and stats["tied_tensors"] == 1
    assert stats["router_states_carried"] == 3
