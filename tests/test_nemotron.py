"""The third decoder (ISSUE 34): the tower of a ``nemotron_h``
``config.json`` at the tiny preset on the CPU — layers that are a Mamba-2
mixer, an expert layer or an attention block ALONE, experts of two
matrices around a squared ReLU, a sigmoid router whose selection bias the
load moves by a fixed step, attention without positions, an untied head —
against the plain reference ``benchmark/references/nemotron.py`` on seeded
weights, piece by piece and through ``FusedTrainer``.  The scan's own ops
are ``tests/test_ssm.py``'s.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from benchmark import spec                                  # noqa: E402
from znicz_tpu import decoder                               # noqa: E402
from znicz_tpu.core.config import root                      # noqa: E402
from znicz_tpu.ops import moe                               # noqa: E402
from znicz_tpu.samples import laguna, nemotron              # noqa: E402

ref = spec.load_module("references", "nemotron")
driver = spec.load_module("drivers", "train_tokens_hybrid")
tokens = spec.load_module("drivers", "train_tokens")
CELL = "nemotron-train-8k"
TINY = dict(nemotron.MODELS["nemotron-tiny"], **nemotron.ASSUMED_KEYS)
SHARE = nemotron.PRESETS["tiny"]["share"]


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def normal(seed, *shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


def layer_of(kind, name="probe", model=TINY, share=SHARE):
    layer = decoder.DecoderLayer(
        None, name=name, **laguna.hybrid_unit(model, share, kind))
    layer.hidden = int(model["hidden_size"])
    return layer


def tensors_of(layer, seed=5):
    """Tensors away from their start: gains near 1, the rest wide enough
    that every part of the layer matters."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    out = {}
    for key, (shape, std) in layer.param_shapes().items():
        if key == "ssm_a_log":
            out[key] = jnp.log(1 + 3 * jax.random.uniform(next(keys), shape))
        elif key == "ssm_dt_bias":
            out[key] = normal(3, *shape, scale=0.5) - 2.0
        elif std is None:
            out[key] = 1.0 + jax.random.normal(next(keys), shape) * 0.1
        else:
            out[key] = jax.random.normal(next(keys), shape) * (
                0.05 if key == "router_bias" else 0.15)
    return out


@pytest.fixture()
def job(tmp_path, restore_root):
    """``build(dtype)``: the tiny preset built as the benchmark's driver
    builds the cell."""
    root.common.dirs.snapshots = str(tmp_path)

    def build(dtype=None, seed=11):
        cell = spec.Cell(spec.load(), CELL)
        if dtype:
            root.common.engine.compute_dtype = dtype
            cell.config["tiny"]["root"][
                "root.common.engine.compute_dtype"] = dtype
        return cell, tokens.build(cell, seed, True)

    return build


# -- (a) a layer is ONE part, chosen by keys -----------------------------------


KEYS = {
    "M": {"norm_ssm", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
          "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out"},
    "E": {"norm_ffn", "router", "router_bias", "shared_up", "shared_down",
          "experts_up", "experts_down"},
    "*": {"norm_attn", "wq", "wk", "wv", "wo"},
}


@pytest.mark.parametrize("kind", sorted(KEYS))
def test_a_layer_holds_one_part_alone_and_matches_the_reference(kind):
    layer = layer_of(kind)
    assert set(layer.param_shapes()) == KEYS[kind]
    assert (layer.mixer, layer.feed_forward) == {
        "M": ("mamba", False), "*": ("attention", False),
        "E": (None, True)}[kind]
    assert layer.remat_keeps == (decoder.CORE_KEEPS if kind == "*" else ())
    assert layer.sparse == (kind == "E") == layer.moves_bias
    p, x = tensors_of(layer), normal(0, 2, 48, 64)
    with jax.default_matmul_precision("highest"):
        got, carry, counters = layer.apply_carried(p, x, None)
        want, _ = ref.layer(TINY, SHARE, kind, p, x, 16)
    assert carry is None and rel(got, want) < 2e-5
    assert set(counters) == ({"rows_by_expert", "rows_dropped", "moves"}
                             if kind == "E" else set())
    if kind == "M":
        assert (layer.scan_way, layer.scan_chunks) == ("composed", 3)


def test_the_units_arguments_are_read_off_the_dictionarys_keys():
    unit = laguna.hybrid_unit(TINY, SHARE, "M")
    assert (unit["ssm_heads"], unit["ssm_head_dim"], unit["ssm_groups"],
            unit["ssm_state"], unit["conv_kernel"], unit["ssm_chunk"],
            unit["dt_range"]) == (8, 8, 2, 16, 4, 16, (0.001, 0.1, 0.0001))
    assert unit["out_scale"] == pytest.approx(9 ** -0.5)
    assert laguna.hybrid_unit(dict(TINY, rescale_prenorm_residual=False),
                              SHARE, "M")["out_scale"] == 1.0
    # no rotation only where the dictionary says so
    assert laguna.hybrid_unit(TINY, SHARE, "*")["rope"] is None
    rotated = {k: v for k, v in TINY.items() if k != "attention_positions"}
    assert laguna.hybrid_unit(rotated, SHARE, "*")["rope"] == {
        "theta": 10000.0, "rotary_dim": 16}
    experts = laguna.hybrid_unit(TINY, SHARE, "E")
    assert (experts["activation"], experts["selection_bias"],
            experts["experts_total"], experts["experts_held"],
            experts["experts_per_token"], experts["routed_scale"]) == (
        "relu2", True, 8, 4, 2, 2.5)
    unbiased = {k: v for k, v in TINY.items()
                if k != "router_selection_bias"}
    assert "router_bias" not in layer_of("E", model=unbiased).param_shapes()
    gated = layer_of("E", model=dict(TINY, mlp_hidden_act="silu"))
    assert {"experts_gate", "shared_gate"} <= set(gated.param_shapes())
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        laguna.hybrid_unit(TINY, SHARE, "-")    # a dense part: no model yet
    units = laguna.layers(TINY, SHARE, nemotron.root.nemotron.optimizer)
    assert [u["type"] for u in units] == (
        ["token_embedding"] + ["decoder_layer"] * 9 + ["lm_head"])
    assert [u["->"]["mixer"] for u in units[1:-1]] == [
        {"M": "mamba", "*": "attention", "E": None}[c] for c in "MEMEM*EME"]
    assert units[-1]["->"] == {"vocab": 256, "norm_eps": 1e-5,
                               "tied": False}


def test_a_layer_without_rotation_differs_from_a_rotated_one():
    plain = layer_of("*")
    rotated = layer_of("*", model={k: v for k, v in TINY.items()
                                   if k != "attention_positions"})
    p, x = tensors_of(plain), normal(0, 2, 32, 64)
    assert rel(plain.apply(p, x), rotated.apply(p, x)) > 1e-3
    with pytest.raises(ValueError, match="mixer"):
        decoder.DecoderLayer(None, name="none", mixer=None,
                             feed_forward=False)
    with pytest.raises(ValueError, match="scan heads"):
        decoder.DecoderLayer(None, name="odd", mixer="mamba", ssm_heads=6,
                             ssm_head_dim=8, ssm_groups=4, ssm_state=16,
                             feed_forward=False)


def test_the_seeded_start_is_what_the_keys_give(restore_root):
    from znicz_tpu.core import prng

    prng.seed_all(7)
    layer = layer_of("M", name="start", model=dict(
        TINY, mamba_num_heads=64, n_groups=8))
    start = layer.init_params()
    dt = np.asarray(jax.nn.softplus(start["ssm_dt_bias"]))
    assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert dt.max() / dt.min() > 5              # log-uniform over the range
    a = np.exp(np.asarray(start["ssm_a_log"]))
    assert 1 <= a.min() and a.max() <= 16 and a.max() - a.min() > 5
    np.testing.assert_array_equal(np.asarray(start["ssm_d"]), 1.0)
    np.testing.assert_array_equal(np.asarray(start["ssm_norm"]), 1.0)
    np.testing.assert_array_equal(np.asarray(start["ssm_conv_b"]), 0.0)
    taps = np.asarray(start["ssm_conv_w"])
    assert np.abs(taps).max() <= 0.5 and np.abs(taps).max() > 0.4
    # the output projection starts 1/sqrt(depth) smaller than the input's
    ratio = float(jnp.std(start["ssm_out"]) / jnp.std(start["ssm_in"]))
    assert ratio == pytest.approx(9 ** -0.5, rel=0.05)
    again = layer.init_params()
    for key in start:
        np.testing.assert_array_equal(np.asarray(start[key]),
                                      np.asarray(again[key]))
    floored = layer_of("M", name="floored", model=dict(
        TINY, time_step_floor=0.05)).init_params()
    assert float(jax.nn.softplus(floored["ssm_dt_bias"]).min()) >= 0.0499


# -- (b) the experts and the biased choice against dense loops ------------------------


def test_relu2_experts_and_the_biased_choice_match_dense_loops():
    layer = layer_of("E")
    p = tensors_of(layer)
    x = normal(1, 96, 64)
    with jax.default_matmul_precision("highest"):
        experts, weights, move = moe.route_balanced(
            x, p["router"], p["router_bias"], 2, 2.5)
        want_e, want_w = ref.routing(TINY, p, x)
        assert rel(move, ref.balance_step(TINY, p, x)) < 1e-4
        got, counters = moe.held_experts(
            x, experts, weights, None, p["experts_up"], p["experts_down"], 0)
        want = ref.routed_part(TINY, SHARE, p, x)
        shared = moe.relu2(x, p["shared_up"], p["shared_down"])
        assert rel(got + shared, ref.experts_layer(TINY, SHARE, p, x)) < 1e-5
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want_e, -1))
    assert rel(jnp.sort(weights, -1), jnp.sort(want_w, -1)) < 1e-6
    assert rel(got, want) < 1e-5
    assert int(counters["rows_dropped"]) == 0
    assert int(counters["rows_by_expert"].sum()) == int(
        (np.asarray(experts) < 4).sum())
    # the weights come from s alone and sum to the scale
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, rtol=1e-5)
    s = jax.nn.sigmoid(x @ p["router"])
    np.testing.assert_allclose(
        np.asarray(weights), np.asarray(
            2.5 * jnp.take_along_axis(s, experts, -1)
            / jnp.take_along_axis(s, experts, -1).sum(-1, keepdims=True)),
        rtol=1e-5)
    # a bias that favours two experts sends every token there, and no
    # gradient reaches it
    lopsided = jnp.zeros(8).at[jnp.array([5, 6])].set(2.0)
    chosen, _, back = moe.route_balanced(x, p["router"], lopsided, 2, 2.5)
    assert set(np.unique(np.asarray(chosen))) == {5, 6}
    assert float(back[5]) < 0 and float(back[6]) < 0 < float(back[0])
    grad = jax.grad(lambda b: jnp.sum(moe.route_balanced(
        x, p["router"], b, 2, 2.5)[1] ** 2))(p["router_bias"])
    assert float(jnp.abs(grad).max()) == 0
    # without a bias: the program Laguna's layers always ran
    plain_e, plain_w = moe.route(x, p["router"], 2, 2.5)
    top, idx = jax.lax.top_k(s, 2)
    np.testing.assert_array_equal(np.asarray(plain_e), np.asarray(idx))
    assert rel(plain_w, 2.5 * top / top.sum(-1, keepdims=True)) < 1e-6


def loads(biased, top_k):
    chosen = jax.lax.top_k(biased, top_k)[1]
    return np.bincount(np.asarray(chosen).reshape(-1),
                       minlength=biased.shape[1])


@pytest.mark.parametrize("case", ["spread", "two_experts_win", "lump"])
def test_the_balance_step_evens_a_sigmoid_routers_load_at_six_a_token(case):
    """``balance_step`` at several experts a token, on sigmoid scores:
    the reference's loop over experts gives the same move, and a few
    steps bring the busiest expert towards its even share (a lump of
    identical tokens can only move whole)."""
    tokens_n, total, top_k = 256, 16, 6
    s = 0.5 * jax.nn.sigmoid(normal(0, tokens_n, total))
    if case == "two_experts_win":
        s = s.at[:, :2].add(0.45)
    if case == "lump":                  # a quarter of the tokens are one id
        s = s.at[:64].set(s[0])
    model = dict(TINY, n_routed_experts=total, num_experts_per_tok=top_k)
    p = {"router": jnp.eye(total), "router_bias": jnp.zeros(total)}
    logit = jnp.log(s) - jnp.log1p(-s)      # the router is the identity
    with jax.default_matmul_precision("highest"):
        assert rel(moe.balance_step(s, top_k),
                   ref.balance_step(model, p, logit)) < 1e-4
    share = tokens_n * top_k / total
    before = loads(s, top_k).max() / share
    bias = jnp.zeros(total)
    for _ in range(8):
        bias = bias + moe.balance_step(s + bias, top_k)
    after = loads(s + bias, top_k).max() / share
    assert abs(float(bias.mean())) < 1e-6
    limit = 1.7 if case == "lump" else 1.25
    assert after < limit and (after < before or before < limit), (before,
                                                                   after)


# -- (c) the shares add up -----------------------------------------------------------------


def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Guide section 4's share test: 16 chips hold one expert each of a
    layer's 16; the parts their layers give, the shared expert and the
    stream counted once, add up to the uncut reference's layer."""
    model = dict(TINY, n_routed_experts=16, num_experts_per_tok=6)
    whole = {"layers": 1, "experts_held": 16, "first_expert": 0}
    p = tensors_of(layer_of("E", model=model, share=whole))
    x = normal(0, 2, 32, 64)
    shares, rows = [], 0
    with jax.default_matmul_precision("highest"):
        for first in range(16):
            cut = dict(p, experts_up=p["experts_up"][first:first + 1],
                       experts_down=p["experts_down"][first:first + 1])
            share = {"experts_held": 1, "first_expert": first}
            y, _, counters = layer_of(
                "E", name=f"share_{first}", model=model,
                share=share).apply_carried(cut, x, None)
            shares.append(y)
            rows += int(counters["rows_by_expert"].sum())
            assert int(counters["rows_dropped"]) == 0
        # what every chip computes alike: the stream and the shared expert
        xn = ref.rms_norm(x, p["norm_ffn"], 1e-5)
        alike = x + ref.relu2(xn, p["shared_up"], p["shared_down"])
        want, _ = ref.layer(model, whole, "E", p, x, 16)
    assert rows == 2 * 32 * 6           # every pair lives on one chip
    assert rel(sum(shares) - 15 * alike, want) < 1e-5
    assert rel(shares[0], want) > 1e-2  # one share alone is not the layer


# -- (d) the system against the reference ---------------------------------------------


def test_system_matches_reference_logits_loss_gradients_and_adamw(job):
    """Seeded weights, float32 compute: logits (in blocks), loss, every
    tensor's gradient, one AdamW step under the warm-up's rate and the
    bias's move of the trainer's own compiled step against the plain
    reference."""
    cell, built = job("float32")
    model, share = driver.model_and_share(cell.config, True)
    assert model == TINY and share == SHARE
    wf, trainer = built.wf, built.trainer
    agreement = driver.parity(cell, model, share, trainer, wf.forwards,
                              built.data[2:4], 32)
    assert agreement["relative_l2"] < 1e-4
    assert agreement["relative_l2_float8"] > 20 * agreement["relative_l2"]
    params = trainer.extract_params()
    ids, targets = built.data[2:4], built.labels[2:4]
    grads = jax.jit(jax.grad(lambda p: trainer.loss_and_metrics(
        p, ids, targets, 2, trainer._key0, train=True)[0]))(params)
    want = jax.jit(jax.grad(lambda t: ref.loss(
        t, ids, targets, model, share, loss_block=32)))(
            tokens.reference_tree(wf.forwards, params))
    got = tokens.reference_tree(wf.forwards, grads)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_want, _ = jax.tree_util.tree_flatten_with_path(want)
    assert [k for k, _ in flat_got] == [k for k, _ in flat_want]
    assert len(flat_got) == 3 + 4 * 9 + 4 * 7 + 5
    for (path, g), (_, w) in zip(flat_got, flat_want):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:       # no gradient reaches the bias
            assert float(jnp.abs(g).max()) == 0 == float(jnp.abs(w).max())
        else:
            assert rel(g, w) < 2e-3, name
    step = driver.step_check(cell, model, share, wf, trainer, built.data,
                             built.labels, range(2, 4))
    assert step["learning_rate"] == pytest.approx(3e-4 / 2000)
    assert abs(step["loss"]["system"] - step["loss"]["reference"]) < 1e-4
    assert set(step["by_group"]) == set(ref.GROUPS)
    tight = {g: {"gradient": 2e-3, "update": 5e-2} for g in ref.GROUPS}
    assert tokens.within(step["by_group"], tight), step["by_group"]
    assert not tokens.within(step["by_group"], dict(
        tight, ssm_scan={"gradient": 1e-9, "update": 1.0}))
    assert not tokens.within(dict.fromkeys(tight, tokens.UNCHANGED), tight)


def test_every_tensor_is_in_a_group_and_decay_follows_the_reference(job):
    _, built = job()
    seen = set()
    for f in built.wf.forwards:
        for key in f.params():
            name = {"weights": "head"}.get(key, key)
            assert tokens.group_of(ref, name)
            seen.add(name)
            assert (key in f.decay_exempt) == (name in ref.NO_DECAY), key
    assert seen == {k for keys in ref.GROUPS.values() for k in keys}


def test_a_train_step_moves_the_bias_and_nothing_else_does(job):
    """The step adds the layer's move to ``router_bias`` (the reference's
    ``balance_step`` on the same rows); AdamW leaves the tensor alone (no
    gradient, no decay); an evaluation neither moves it nor returns a
    move; a snapshot holds it."""
    cell, built = job("float32")
    model, share = driver.model_and_share(cell.config, True)
    wf, trainer = built.wf, built.trainer
    layers = [f for f in wf.forwards[1:-1] if f.sparse]
    assert len(layers) == 4 and all(
        "router_bias" in f.params() and "router_bias" in f.decay_exempt
        for f in layers)
    params, state = trainer.extract_params(), trainer.extract_velocities()
    start = {f.name: jnp.asarray(normal(i, 8, scale=1e-2))
             for i, f in enumerate(layers)}
    params = {name: dict(p, **({"router_bias": start[name]}
                               if name in start else {}))
              for name, p in params.items()}
    ids, targets = built.data[2:4], built.labels[2:4]
    hypers = {name: tuple(np.float32(v) for v in (1e-3, 0.1, 0.9, 0.95,
                                                  1e-8))
              for name in trainer.hypers()}
    taps = []
    ref.final_hidden(tokens.reference_tree(wf.forwards, params), ids, model,
                     share, taps=taps)
    new_p, new_s, metrics = jax.jit(trainer._update_core)(
        params, state, hypers, ids, targets, np.int32(2), trainer._key0)
    assert len(taps) == 4
    for f, (p_ref, xn) in zip(layers, taps):
        want = ref.balance_step(model, p_ref, xn)
        assert float(jnp.abs(want).max()) > 0
        assert rel(new_p[f.name]["router_bias"] - start[f.name], want) < 1e-3
        assert float(jnp.abs(new_s[f.name]["m_router_bias"]).max()) == 0
        assert set(metrics[3][f.name]) == {"rows_by_expert", "rows_dropped"}
    assert set(metrics[3]) == {f.name for f in layers}
    _, evaluated = jax.jit(lambda p: trainer.loss_and_metrics(
        p, ids, targets, 2, trainer._key0, train=False))(params)
    assert set(evaluated[3][layers[0].name]) == {"rows_by_expert",
                                                 "rows_dropped"}
    snap = trainer.snapshot_from_trees(new_p, new_s)
    np.testing.assert_array_equal(
        np.asarray(snap["units"][layers[1].name]["router_bias"]),
        np.asarray(new_p[layers[1].name]["router_bias"]))


def test_the_sample_runs_through_the_launcher_and_notes_what_it_ran(
        tmp_path, restore_root):
    from znicz_tpu.launcher import Launcher

    root.common.dirs.snapshots = str(tmp_path)
    launcher = Launcher([
        os.path.join(REPO, "znicz_tpu", "samples", "nemotron.py"),
        "--backend", "cpu", "root.nemotron.preset=tiny",
        "root.nemotron.decision.max_epochs=2"])
    launcher.run()
    wf = launcher.workflow
    assert type(wf).__name__ == "NemotronWorkflow"
    stats = wf.fused_stats
    assert (stats["layers_mamba"], stats["layers_experts"],
            stats["layers_attention"]) == (4, 4, 1)
    assert (stats["ssm_scans_composed"], stats["ssm_scans_kernel"],
            stats["ssm_chunks"]) == (4, 0, 4)
    assert stats["router_biases_moved"] == 4
    assert stats["attn_cores_kept"] == 1        # the one layer with a core
    assert stats["attn_cores_composed"] == 1 and stats["tied_tensors"] == 0
    assert stats["moe_rows_dropped"] == 0 and stats["moe_rows_routed"] > 0
    assert stats["tokens"] == 2 * 8 * 64
    assert "router_states_carried" not in stats
    history = wf.decision.epoch_history
    assert len(history) == 2 and all(
        np.isfinite(v) for h in history for v in h.values())
    rate = float(wf.gds[0].learning_rate)
    assert rate == pytest.approx(3e-4 * 8 / 2000)   # step 7 of the warm-up
    biases = [np.asarray(f.tensors["router_bias"].map_read())
              for f in wf.forwards if getattr(f, "sparse", False)]
    assert all(np.abs(b).max() > 0 for b in biases)     # the load moved them
    assert all(abs(float(b.mean())) < 1e-6 for b in biases)     # centred


def test_the_preset_is_the_published_dictionary_key_for_key():
    cfg = spec.Cell(spec.load(), CELL).config
    model = nemotron.MODELS["nemotron-twotower-30b"]
    assert sorted(model) == cfg["model_keys"]
    for key in cfg["model_keys"]:
        assert model[key] == cfg["published"].get(key, cfg[key]), key
    assert nemotron.PRESETS["nemotron-twotower-30b-ep16"]["share"] == {
        "layers": 9, "experts_held": 8, "first_expert": 0,
        "vocab_held": 16384}
    pattern = model["hybrid_override_pattern"]
    assert len(pattern) == 52 == model["num_hidden_layers"]
    assert [pattern.count(c) for c in "ME*"] == [23, 23, 6]
    assert pattern[:9] == "MEMEM*EME"
    assert cfg["assumed"] == nemotron.ASSUMED
    assert cfg["assumed_keys"]["keys"] == nemotron.ASSUMED_KEYS
    assert set(cfg["left_out"]) >= {"second_tower", "block_diffusion"}


def test_the_compiled_step_names_the_mixers_scopes_for_their_readers(job):
    """``ssm_in``, ``ssm_conv``, ``ssm_scan`` and ``ssm_out`` stand in the
    compiled train step's metadata in all three directions, as
    ``benchmark/reduce/inner.py`` files them for ``ssm_ms_per_step``,
    ``ssm_scan_ms_per_step`` and ``ssm_scan_roofline``."""
    import re

    from benchmark.reduce import inner
    from znicz_tpu.core import prng

    _, built = job()
    trainer, loader = built.trainer, built.wf.loader
    batch = int(loader.max_minibatch_size)
    text = trainer.make_train_step().lower(
        trainer.extract_params(), trainer.extract_velocities(),
        trainer.hypers(), built.data, built.labels,
        np.arange(batch, dtype=np.int32), np.int32(batch),
        prng.get("fused_trainer").jax_key(0)).compile().as_text()
    seen = {}
    for name in set(re.findall(r'op_name="([^"]+)"', text)):
        tag = inner.tag_of(name)
        if tag:
            seen.setdefault(tag[1], set()).add(tag[2])
    for scope in ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out"):
        assert seen.get(scope) == {"forward", "recompute", "backward"}, (
            scope, seen.get(scope))
    # the attention layer keeps its core: no kernel-side recompute scope
    # is asked of it here (the CPU's core is composed); the expert
    # layers' scopes are the ones Laguna's readers know
    assert {"router", "dispatch", "experts", "combine",
            "shared_expert", "attn_core"} <= set(seen)
    assert "dense_ffn" not in seen and "attn_qkv" in seen
