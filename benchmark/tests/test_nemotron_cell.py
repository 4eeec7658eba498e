"""The cell ``nemotron-train-8k``: how it is declared, the configuration's
count against a hand reckoning and the units' own tensors,
``flops_nemotron`` against the issue's numbers, the controls against the
limits the file holds, its readers on runs that lack what they read, and
its rehearsal with ``--trace 0``."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import flops_nemotron, spec                  # noqa: E402

BENCH = spec.load()
CELL = "nemotron-train-8k"
CONFIG = "nemotron-twotower-30b-ep16"
NEW_METRICS = ("hybrid_step_mfu", "ssm_ms_per_step", "ssm_scan_ms_per_step",
               "ssm_scan_roofline", "hybrid_experts_ms_per_step",
               "hybrid_experts_roofline", "hybrid_attention_ms_per_step",
               "hybrid_head_loss_ms_per_step", "hybrid_adamw_ms_per_step",
               "top6_rows_max_over_mean")


def real():
    driver = spec.load_module("drivers", "train_tokens_hybrid")
    return driver.model_and_share(spec.Cell(BENCH, CELL).config, False)


def test_the_cell_is_declared_as_the_issue_asks():
    cell = spec.Cell(BENCH, CELL)
    assert (cell.chips, cell.traffic_name, cell.driver_name) == (
        1, "rows-8k-hybrid", "train_tokens_hybrid")
    assert cell.entry["config"] == CONFIG
    assert "1/16 of the deployment's 12,288" in cell.entry["why"]
    entry = spec.by_name(BENCH["configs"], CONFIG, "configuration")
    assert entry["source"] == (
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-"
        "BF16/blob/main/config.json (layers 0-8 of 52, experts 0-7 of 128, "
        "ids 0-16,383 of 131,072)")
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert {m["name"] for m in cell.end_to_end} == {"train_samples_per_s",
                                                    "setup_s"}
    listed = {m["name"] for m in cell.per_layer if "workloads" in m}
    assert listed == set(NEW_METRICS)
    unlisted = {m["name"] for m in cell.per_layer if "workloads" not in m}
    assert unlisted == {"train_gap_ms_per_step", "mxu_roofline",
                        "nonmxu_ms_per_step", "setup_compile_s"}
    for m in BENCH["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_samples_per_s"
        else:                       # no accepted metric took the new cell
            assert CELL not in m.get("workloads", [])
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + len(NEW_METRICS)] == list(NEW_METRICS)
    traffic = cell.traffic
    assert (traffic["warmup_epochs"], traffic["trace_epochs"],
            traffic["zipf"], traffic["generator"]) == (2, 1, 1.1, "tokens")
    assert traffic["snapshot"] == spec.read_json(
        "benchmark", "traffic", "rows-8k.json")["snapshot"]
    assert traffic["root"]["root.nemotron.loader.seq_len"] == 8192
    assert traffic["root"]["root.nemotron.loader.minibatch_size"] == 2
    assert traffic["root"]["root.nemotron.loader.n_train"] == 16


def test_the_configuration_holds_the_catalog_row_and_the_cut():
    cfg = spec.Cell(BENCH, CELL).config
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (9, 8, 16384)
    assert cfg["published"] == {"num_hidden_layers": 52,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    # no width differs from the published configuration
    assert (cfg["hidden_size"], cfg["mamba_num_heads"],
            cfg["mamba_head_dim"], cfg["n_groups"], cfg["ssm_state_size"],
            cfg["conv_kernel"], cfg["chunk_size"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["intermediate_size"], cfg["expand"]) == (
        2688, 64, 64, 8, 128, 4, 128, 1856, 3712, 6, 2.5, 32, 2, 128, 1856,
        2)
    assert cfg["tie_word_embeddings"] is False
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert "16 chips share each layer" in cfg["deployment"]
    model, share = real()
    from znicz_tpu.samples import nemotron

    assert model == dict(nemotron.MODELS["nemotron-twotower-30b"],
                         **nemotron.ASSUMED_KEYS)
    assert share == nemotron.PRESETS["nemotron-twotower-30b-ep16"]["share"]
    driver = spec.load_module("drivers", "train_tokens_hybrid")
    tiny_model, tiny_share = driver.model_and_share(cfg, True)
    assert tiny_model == dict(nemotron.MODELS["nemotron-tiny"],
                              **nemotron.ASSUMED_KEYS)
    assert tiny_share == nemotron.PRESETS["tiny"]["share"]
    assert cfg["assumed"] == nemotron.ASSUMED
    assert set(cfg["assumed_why"]) == set(cfg["assumed"])
    assert cfg["assumed_keys"]["keys"] == nemotron.ASSUMED_KEYS == {
        "attention_positions": "none", "router_selection_bias": True}
    assert set(cfg["left_out"]) == {"second_tower", "block_diffusion",
                                    "the_exchange",
                                    "auxiliary_balance_loss"}
    opt = cfg["optimizer"]
    assert (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"],
            opt["weight_decay"], opt["clipping"]) == (
        3e-4, 0.9, 0.95, 1e-8, 0.1, "none")
    assert opt["schedule"]["policy"] == "warmup" \
        and opt["schedule"]["steps"] == 2000
    assert cfg["root"]["root.nemotron.lr_adjust.policy"] == "warmup"


def test_the_parameter_count_against_a_hand_reckoning():
    """The file's count, the hand reckoning, the issue's and the UNITS'
    own tensors agree; 16 bytes a parameter are 10.67 GB, 62 % of 16
    GiB."""
    import numpy as np

    count = spec.Cell(BENCH, CELL).config["parameters"]
    d = 2688
    mamba = (d * (4096 + 6144 + 64) + 4 * 6144 + 6144 + 3 * 64 + 4096
             + 4096 * d + d)
    attention = d * 4096 + 2 * d * 256 + 4096 * d + d
    outside = d * 128 + 128 + 2 * d * 3712 + d
    expert = 2 * d * 1856
    assert (mamba, attention, outside, expert) == (
        38744896, 23399040, 20302592, 9977856)
    assert count["mamba_layer"]["total"] == mamba
    assert count["attention_layer"]["total"] == attention
    assert count["expert_layer_outside_its_routed_experts"]["total"] \
        == outside
    assert count["expert_layer_with_8_held"] == outside + 8 * expert \
        == 100125440
    # a whole expert layer is 1.30 B parameters = 20.8 GB: no chip holds it
    assert round(16 * count["whole_expert_layer_of_128"] / 1e9, 1) == 20.8
    held = (4 * mamba + 4 * (outside + 8 * expert) + attention
            + 2 * 16384 * d + d)
    assert held == count["held"] == 666963456
    assert count["state_bytes"] == 16 * held
    assert round(16 * held / 1e9, 2) == 10.67
    assert count["share_of_16_GiB"] == round(16 * held / 2 ** 34, 3) == 0.621
    # the units' own tensors, without building them
    from znicz_tpu import decoder
    from znicz_tpu.samples import laguna, nemotron

    preset = nemotron.PRESETS["nemotron-twotower-30b-ep16"]
    units = laguna.layers(dict(preset["model"], **preset["assumed"]),
                          preset["share"],
                          {"learning_rate": 0, "weights_decay": 0,
                           "beta1": 0, "beta2": 0, "epsilon": 0})
    total = 2 * 16384 * d + d       # embedding, head, final norm
    for unit in units[1:-1]:
        layer = decoder.DecoderLayer(None, name="probe", **unit["->"])
        layer.hidden = d
        total += sum(int(np.prod(shape))
                     for shape, _ in layer.param_shapes().values())
    assert units[-1]["->"]["tied"] is False
    assert total == held


@pytest.mark.parametrize("sizes", ["real", "tiny"])
def test_the_controls_fail_by_the_limits_the_file_holds(sizes):
    """Every limit lies under its control with room: a state left
    unchanged reads 1 in every group and kind and is not ``within``."""
    driver = spec.load_module("drivers", "train_tokens")
    cfg = spec.Cell(BENCH, CELL).config
    held = cfg if sizes == "real" else cfg["tiny"]
    limits = held["step_check"]["tolerance"]
    reference = spec.load_module("references", "nemotron")
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
        # every limit at least a fifth under its control's reading of 1;
        # only the router, whose gradient the cut starves, passes 0.25
        assert all(t["gradient"] <= 0.5 and t["update"] <= 0.8
                   for t in limits.values())
        assert [g for g, t in limits.items() if t["gradient"] > 0.25] == [
            "router"]
        assert held["routing"]["max_over_mean"] < 2.41
        assert held["parity"]["tolerance"] < 1
        assert 16384 % held["parity"]["logit_rows_a_block"] == 0


def test_the_step_counts_what_the_issue_reckoned():
    model, share = real()
    token = flops_nemotron.token_forward_flops(model, share)
    # "323 M in the four Mamba layers (77 M of each layer's 81 M in its two
    # projections, 3.4 M in the scan)"
    assert token["ssm_proj"] / 4 == 2 * 2688 * (10304 + 4096) == 77414400
    assert token["ssm_scan"] / 4 == (2 * 128 * 128 * 8 + 2 * 128 * 64 * 64
                                     + 4 * 64 * 128 * 64) == 3407872
    assert token["ssm_conv"] / 4 == 2 * 4 * 6144
    mixers = token["ssm_proj"] + token["ssm_scan"] + token["ssm_conv"]
    assert 323e6 < mixers < 324e6 and 80.5e6 < mixers / 4 < 81.5e6
    # "192 M in the four expert layers (160 M of it the shared experts)"
    assert token["shared"] == 4 * 2 * 2 * 2688 * 3712 == 159645696
    routed = 6 * 8 / 128 * 4 * flops_nemotron.expert_forward_flops_per_row(
        model)
    assert flops_nemotron.expert_forward_flops_per_row(model) == 19955712
    assert 191e6 < token["shared"] + token["router"] + routed < 193e6
    # "114 M in the attention layer at 8,192 positions (181 M at 16,384,
    # 315 M at 32,768) and 88 M in the head"
    for seq, want in ((8192, 114e6), (16384, 181e6), (32768, 315e6)):
        core = flops_nemotron.core_forward_flops(model, share, 1, seq) / seq
        assert abs(token["attention_proj"] + core - want) < 0.6e6
    assert token["head"] == 2 * 16384 * 2688 == 88080384
    dots = flops_nemotron.dot_forward_flops(model, share, 2, 8192)
    assert dots == 16384 * (sum(token.values()) - token["ssm_conv"])
    whole = flops_nemotron.window_flops(model, share, 2, 8192, 8, 1,
                                        9 * 4 * 6144, 9)
    core = flops_nemotron.core_forward_flops(model, share, 2, 8192)
    assert whole["all"] == pytest.approx(25 * (
        16384 * sum(token.values()) + core + 4 * 6144 * 19955712))
    # "a step is about 35 TFLOP"
    assert 34.5e12 < whole["all"] * 3 / 25 < 36e12
    assert whole["scan"] == 25 * 16384 * (token["ssm_scan"]
                                          + token["ssm_conv"])
    # forward: xBC and dt read, y written; backward: those and dy read,
    # two cotangents written; bfloat16
    moved = flops_nemotron.scan_bytes(model, share, 2, 8192)
    assert moved["forward"] == 4 * 16384 * 2 * (6144 + 64 + 4096)
    assert moved["backward"] == 4 * 16384 * 2 * (2 * (6144 + 64) + 4096)
    assert whole["scan_bytes"] == 9 * moved["forward"] + 8 * moved["backward"]
    # HBM bounds the scan's roofline, not the MXU
    assert whole["scan_bytes"] / 819e9 > whole["scan"] / 197e12


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_reader_reports_nothing_where_there_is_nothing_to_read(metric):
    """A run of a program without the scopes or the counter (the parent),
    a run of ANOTHER decoder, and an untraced run: ``None``, no
    exception."""
    reader = spec.load_module("layer_metrics", metric)
    others = [spec.Cell(BENCH, c).config["tiny"]
              for c in ("laguna-train-8k", "zaya-train-32k")]
    runs = [{}, {"trace": {}, "counters": {}, "shape": {}},
            {"trace": {"devices": [], "train_steps": 8, "eval_steps": 1,
                       "host_window_s": 1.0},
             "shape": {"batch": 2}, "peaks": {"bf16_tflops": 197.0},
             "counters": {"fused_stats": {"images": 3}}}]
    runs += [{"trace": {"devices": [], "train_steps": 8, "eval_steps": 1,
                        "host_window_s": 1.0, "moe_rows_routed": 100,
                        "moe_counted_steps": 9},
              "shape": {"batch": 2, "row_tokens": 64, "head_unit": "head",
                        "model": other["model"], "share": other["share"]},
              "peaks": {"bf16_tflops": 197.0},
              "counters": {"fused_stats": {
                  "router_states_carried": 3,
                  "moe_rows_by_expert": {"max": 3, "mean": 1, "min": 0}}}}
             for other in others]
    for run in runs:
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
        "no_row_dropped", "rows_routed_in_band", "rows_balanced",
        "layers_by_kind", "router_biases_moved", "scans_in_chunks",
        "cores_in_kernels"}
    # both controls fail by the limits the comparison used
    parity = detail["parity"]
    assert parity["relative_l2"] <= parity["tolerance"] \
        < parity["relative_l2_float8"]
    assert set(detail["step_check"]["by_group"]) == set(
        detail["step_check"]["tolerance"])
    stats = detail["counters"]["fused_stats"]
    assert stats["moe_rows_dropped"] == 0 and stats["tokens"] > 0
    assert (stats["layers_mamba"], stats["layers_experts"],
            stats["layers_attention"], stats["ssm_chunks"]) == (4, 4, 1, 4)
    assert stats["router_biases_moved"] == 4 and stats["tied_tensors"] == 0
