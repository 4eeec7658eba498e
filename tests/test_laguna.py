"""The decoder stack (ISSUE 27): Laguna-XS.2's block at the tiny preset on
the CPU — the attention core against materialised scores, the expert layer
against dense per-expert loops (no row dropped; the shares add up), AdamW
against the rule, and the whole system through ``FusedTrainer`` against
the plain reference ``benchmark/references/laguna.py`` on seeded weights.
"""

import functools
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
from znicz_tpu.core import prng                             # noqa: E402
from znicz_tpu.core.config import root                      # noqa: E402
from znicz_tpu.ops import moe                               # noqa: E402
from znicz_tpu.ops.attention import (apply_rope, blocked_attention,
                                     rope_tables)          # noqa: E402

ref = spec.load_module("references", "laguna")
driver = spec.load_module("drivers", "train_tokens")

#: what holds for every decoder is one test with a case a family (ISSUE
#: 32): the cell, its driver and reference, the sample, its layers
FAMILIES = {
    "laguna": {"cell": "laguna-train-8k", "driver": "train_tokens",
               "reference": "laguna", "layers": 5, "sparse": 4, "top_k": 2},
    "zaya": {"cell": "zaya-train-32k", "driver": "train_tokens_blocked",
             "reference": "zaya", "layers": 4, "sparse": 4, "top_k": 1},
}


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def qkv(heads, kv, t=64, d=16, b=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (b, t, heads, d)),
            jax.random.normal(keys[1], (b, t, kv, d)),
            jax.random.normal(keys[2], (b, t, kv, d)),
            jax.random.normal(keys[3], (b, t, heads, d)))


# -- the attention core ------------------------------------------------------------


@pytest.mark.parametrize("window", [None, 16, 24], ids=["full", "window16",
                                                        "window24"])
@pytest.mark.parametrize("heads,kv", [(6, 2), (8, 2), (12, 2), (2, 2)],
                         ids=["48-style", "64-style", "6-a-group", "mha"])
def test_blocked_attention_matches_materialised_scores(window, heads, kv):
    q, k, v, ct = qkv(heads, kv)

    def blocked(q, k, v):
        return jnp.sum(blocked_attention(q, k, v, window, 16) * ct)

    def plain(q, k, v):
        return jnp.sum(ref.attention(q, k, v, window, 64) * ct)

    got = blocked_attention(q, k, v, window, 16)
    assert rel(got, ref.attention(q, k, v, window, 64)) < 2e-6
    for g, w in zip(jax.grad(blocked, (0, 1, 2))(q, k, v),
                    jax.grad(plain, (0, 1, 2))(q, k, v)):
        assert rel(g, w) < 5e-6


@pytest.mark.parametrize("window", [64, 100])
def test_a_window_of_the_whole_sequence_is_full_attention(window):
    q, k, v, _ = qkv(8, 2)
    np.testing.assert_allclose(blocked_attention(q, k, v, window, 16),
                               blocked_attention(q, k, v, None, 16),
                               rtol=1e-6, atol=1e-6)


def test_blocked_attention_reads_only_admitted_key_blocks():
    """A window layer reads at most two key blocks a query block: keys
    before the window may hold anything, NaN included."""
    q, k, v, _ = qkv(8, 2)
    poisoned = k.at[:, :16].set(jnp.nan)
    got = blocked_attention(q, poisoned, v, 16, 16)[:, 32:]
    want = blocked_attention(q, k, v, 16, 16)[:, 32:]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_a_block_that_does_not_divide_the_sequence_falls_back_whole():
    q, k, v, _ = qkv(4, 2, t=48)
    assert rel(blocked_attention(q, k, v, None, 32),
               ref.attention(q, k, v, None, 48)) < 2e-6


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention",
                                  "hybrid"])
def test_rotary_tables_match_the_reference(kind):
    from znicz_tpu.samples import laguna

    models = [m for m in laguna.MODELS.values()
              if kind in m["rope_parameters"]]
    assert len(models) == 2             # the published one and its tiny
    for model in models:
        rope = laguna.rope_of(model, kind)
        cos, sin = rope_tables(96, rope["rotary_dim"], rope["theta"],
                               rope.get("yarn"))
        rcos, rsin, dim = ref.rotary(model, kind, 96)
        assert dim == rope["rotary_dim"]
        x = jax.random.normal(jax.random.PRNGKey(1),
                              (2, 96, 3, model["head_dim"]))
        assert rel(apply_rope(x, cos, sin),
                   ref.rotate(x, rcos, rsin, dim)) < 1e-6
        if dim < model["head_dim"]:         # the rest passes through
            np.testing.assert_array_equal(
                apply_rope(x, cos, sin)[..., dim:], x[..., dim:])


# -- the expert layer ----------------------------------------------------------------


def expert_layer(seed=0, tokens=48, d=32, f=16, total=8, k=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {"router": jax.random.normal(keys[0], (d, total)) * 0.3,
         "experts_gate": jax.random.normal(keys[1], (total, d, f)) * 0.2,
         "experts_up": jax.random.normal(keys[2], (total, d, f)) * 0.2,
         "experts_down": jax.random.normal(keys[3], (total, f, d)) * 0.2,
         "shared_gate": jax.random.normal(keys[4], (d, f)) * 0.2,
         "shared_up": jax.random.normal(keys[5], (d, f)) * 0.2,
         "shared_down": jax.random.normal(keys[6], (f, d)) * 0.2}
    model = {"num_experts": total, "num_experts_per_tok": k,
             "moe_routed_scaling_factor": 2.5}
    return p, model, jax.random.normal(keys[7], (tokens, d))


def held_part(p, model, x, first, held):
    experts, weights = moe.route(x, p["router"],
                                 model["num_experts_per_tok"],
                                 model["moe_routed_scaling_factor"])
    sl = slice(first, first + held)
    return moe.held_experts(x, experts, weights, p["experts_gate"][sl],
                            p["experts_up"][sl], p["experts_down"][sl],
                            first)


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips' routed parts plus the shared expert counted ONCE are
    the uncut reference layer."""
    p, model, x = expert_layer()
    with jax.default_matmul_precision("highest"):
        whole = ref.ffn(model, {"first_expert": 0, "experts_held": 8}, p, x)
        parts = [held_part(p, model, x, first, 1)[0] for first in range(8)]
        shared = moe.swiglu(x, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
        assert rel(sum(parts) + shared, whole) < 1e-5
        # and a share of several experts is the sum of its experts' shares
        assert rel(held_part(p, model, x, 2, 4)[0], sum(parts[2:6])) < 1e-5


@pytest.mark.parametrize("first,held", [(0, 2), (3, 4), (6, 2), (0, 8)])
def test_a_share_matches_the_reference_given_the_same_share(first, held):
    p, model, x = expert_layer(seed=first)
    cut = dict(p, **{k: p[k][first:first + held] for k in (
        "experts_gate", "experts_up", "experts_down")})
    with jax.default_matmul_precision("highest"):
        got, counters = held_part(p, model, x, first, held)
        want = ref.routed_part(model, {"first_expert": first,
                                       "experts_held": held}, cut, x)
    assert rel(got, want) < 1e-5
    experts, _ = ref.routing(model, p, x)
    rows = [(np.asarray(experts) == e).sum()
            for e in range(first, first + held)]
    np.testing.assert_array_equal(counters["rows_by_expert"], rows)
    assert int(counters["rows_dropped"]) == 0


def test_no_row_is_dropped_when_every_token_picks_the_same_experts():
    p, model, x = expert_layer()
    tokens = x.shape[0]
    experts = jnp.tile(jnp.array([[5, 4]], jnp.int32), (tokens, 1))
    weights = jnp.full((tokens, 2), 1.25)
    sl = slice(4, 6)
    with jax.default_matmul_precision("highest"):
        got, counters = moe.held_experts(
            x, experts, weights, p["experts_gate"][sl], p["experts_up"][sl],
            p["experts_down"][sl], 4)
        want = 1.25 * sum(moe.swiglu(x, p["experts_gate"][e],
                                     p["experts_up"][e],
                                     p["experts_down"][e]) for e in (4, 5))
    np.testing.assert_array_equal(counters["rows_by_expert"],
                                  [tokens, tokens])
    assert int(counters["rows_dropped"]) == 0
    assert rel(got, want) < 1e-5
    # ... and when no token picks a held expert, nothing is added
    none, counters = moe.held_experts(
        x, experts, weights, p["experts_gate"][:2], p["experts_up"][:2],
        p["experts_down"][:2], 0)
    assert float(jnp.abs(none).max()) == 0.0
    np.testing.assert_array_equal(counters["rows_by_expert"], [0, 0])


def test_the_expert_layer_is_differentiable_like_the_reference():
    p, model, x = expert_layer(tokens=32)
    cut = dict(p, **{k: p[k][2:6] for k in ("experts_gate", "experts_up",
                                            "experts_down")})

    def system(cut, x):
        experts, weights = moe.route(x, cut["router"], 2, 2.5)
        return jnp.sum(jnp.square(moe.held_experts(
            x, experts, weights, cut["experts_gate"], cut["experts_up"],
            cut["experts_down"], 2)[0]))

    def plain(cut, x):
        return jnp.sum(jnp.square(ref.routed_part(
            model, {"first_expert": 2, "experts_held": 4}, cut, x)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(system, (0, 1))(cut, x)
        want = jax.grad(plain, (0, 1))(cut, x)
    for key in ("router", "experts_gate", "experts_up", "experts_down"):
        assert rel(got[0][key], want[0][key]) < 1e-4, key
    assert rel(got[1], want[1]) < 1e-4


# -- AdamW ---------------------------------------------------------------------------


@pytest.mark.parametrize("step", [1, 7])
@pytest.mark.parametrize("decay", [0.0, 0.1])
def test_adamw_update_is_the_reference_rule(step, decay):
    from znicz_tpu.nn_units import adamw_update

    keys = jax.random.split(jax.random.PRNGKey(step), 4)
    w, g, m = (jax.random.normal(k, (33, 17)) for k in keys[:3])
    v = jnp.square(jax.random.normal(keys[3], (33, 17)))
    got = adamw_update(w, g, m, v, lr=3e-4, beta1=0.9, beta2=0.95,
                       eps=1e-8, weight_decay=decay, step=float(step))
    want = ref.adamw(w, m, v, g, float(step), 3e-4, 0.9, 0.95, 1e-8, decay)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-9)


# -- the system against the reference ------------------------------------------------


@pytest.fixture()
def family_job(tmp_path, restore_root):
    """``build(family)``: a family's tiny preset built as the benchmark's
    driver builds its cell."""
    root.common.dirs.snapshots = str(tmp_path)

    def build(family, seed=11):
        cell = spec.Cell(spec.load(), FAMILIES[family]["cell"])
        return cell, driver.build(cell, seed, True)

    return build


@pytest.fixture()
def tiny_job(family_job):
    """The Laguna cell's tiny preset."""
    cell = spec.Cell(spec.load(), "laguna-train-8k")
    return cell, lambda seed=11: driver.build(cell, seed, True)


def test_system_matches_reference_logits_loss_gradient_and_adamw(tiny_job):
    """Seeded weights, float32 compute: logits, loss, the gradient by
    parameter group and one AdamW step (weights and both moments) of the
    trainer's own compiled step against the plain reference."""
    cell, build = tiny_job
    root.common.engine.compute_dtype = "float32"
    cell.config["tiny"]["root"]["root.common.engine.compute_dtype"] = \
        "float32"
    built = build()
    model, share = driver.model_and_share(cell.config, True)
    wf, trainer = built.wf, built.trainer
    ids = built.data[2:4]
    agreement = driver.parity(cell, model, share, trainer, wf.forwards, ids)
    assert agreement["relative_l2"] < 1e-4
    assert agreement["relative_l2_float8"] > 20 * agreement["relative_l2"]
    step = driver.step_check(cell, model, share, wf, trainer, built.data,
                             built.labels, range(2, 4))
    assert abs(step["loss"]["system"] - step["loss"]["reference"]) < 1e-4
    assert set(step["by_group"]) == set(ref.GROUPS)
    tight = {g: {"gradient": 2e-3, "update": 5e-2} for g in ref.GROUPS}
    assert driver.within(step["by_group"], tight)
    assert not driver.within(step["by_group"], dict(
        tight, router={"gradient": 1e-9, "update": 1.0}))
    for group, kinds in step["by_group"].items():
        assert set(kinds) == {"gradient", "update", "m", "v"}
        for kind, err in kinds.items():
            # the first step's update is lr * sign(g): a gradient element
            # near 0 that rounds across it flips a whole lr
            assert err < (5e-2 if kind == "update" else 2e-3), (group, kind)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_integer_ids_reach_the_embedding_unchanged_under_bf16(family,
                                                              family_job):
    """The resident twin, the gather and the compute-dtype cast leave
    int32 ids alone: what the embedding looks up under bf16 compute is
    what the loader holds."""
    _, built = family_job(family)
    wf, trainer = built.wf, built.trainer
    assert str(trainer.compute_dtype) == "bfloat16"
    raw = wf.loader.original_data.devmem
    assert raw.dtype == jnp.int32
    twin, plan = trainer._resident(raw)
    assert twin is raw and plan is None         # its own twin: no copy
    seen = {}
    embed = wf.forwards[0]
    apply = embed.apply

    def spy(params, x):
        seen["dtype"] = x.dtype
        seen["ids"] = x
        return apply(params, x)

    embed.apply = spy
    idx = np.array([3, 1], np.int32)

    def first_unit(params, dataset, idx):
        data, _ = trainer._gather(dataset, dataset, idx)
        trainer.loss_and_metrics(params, trainer._decode(data),
                                 jnp.zeros_like(data), 2, None, False)
        return seen.pop("ids")

    got = jax.jit(first_unit)(trainer.extract_params(), raw, idx)
    assert seen["dtype"] == jnp.int32
    np.testing.assert_array_equal(got, np.asarray(raw)[idx])


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_decay_skips_what_the_reference_says_it_skips(family, family_job):
    """``decay_exempt`` is derived from the layer's keys: it lists what
    the reference's ``NO_DECAY`` lists among the unit's tensors (norms,
    gates and a linear router; scales, shifts, temperatures, biases and
    ``gamma``), and an update with a zero gradient decays exactly the
    others."""
    _, built = family_job(family)
    reference = spec.load_module("references", FAMILIES[family]["reference"])
    renamed = {"weights": "head"}       # the untied head's own tensor
    for f in built.wf.forwards:
        assert {renamed.get(k, k) for k in f.decay_exempt} == {
            renamed.get(k, k) for k in f.params()} & set(reference.NO_DECAY)
    sparse = next(f for f in built.wf.forwards
                  if getattr(f, "sparse", False))
    gd = built.trainer.gd_of[sparse.name]
    params = {k: jnp.ones(a.shape, jnp.float32)
              for k, a in sparse.params().items()}
    grads = {k: jnp.zeros_like(v) for k, v in params.items()}
    state = {k: a.devmem for k, a in gd._velocities.items()}
    hypers = tuple(np.float32(v) for v in (0.5, 0.1, 0.9, 0.95, 1e-8))
    new_p, new_s = gd.apply_update(params, grads, state, hypers)
    for key, w in new_p.items():
        want = 1.0 if key in reference.NO_DECAY else 0.95
        np.testing.assert_allclose(w, want, rtol=1e-6, err_msg=key)
    assert int(new_s["step"]) == 1


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_what_a_layer_keeps_changes_memory_not_mathematics(family,
                                                           family_job):
    """Loss and gradient of a train step on seeded weights, float32, the
    composed path: with what a decoder layer keeps across
    rematerialisation as shipped (its core's output and log-sum-exp,
    ISSUE 33), with nothing kept (the bare ``jax.checkpoint``) and with
    no rematerialisation at all — equal, tensor by tensor, to float32
    rounding.  The traced program shows the difference: the core's
    forward pass (two exponentials a block pair: the probabilities and
    the running sum's correction) stands once a layer where the layer
    keeps its results, twice where it does not."""
    from znicz_tpu.ops.attention import CORE_KEEPS

    cell, _ = family_job(family)
    root.common.engine.compute_dtype = "float32"
    cell.config["tiny"]["root"]["root.common.engine.compute_dtype"] = \
        "float32"
    built = driver.build(cell, 11, True)
    trainer = built.trainer
    layers = built.wf.forwards[1:-1]
    assert len(layers) == FAMILIES[family]["layers"]
    assert all(f.remat and f.remat_keeps == CORE_KEEPS for f in layers)
    params = trainer.extract_params()
    ids, targets = built.data[2:4], built.labels[2:4]

    def exponentials(jaxpr):
        from jax._src.core import jaxprs_in_params

        return sum((eqn.primitive.name == "exp") + sum(
            exponentials(sub) for sub in jaxprs_in_params(eqn.params))
            for eqn in jaxpr.eqns)

    def value_and_gradient():
        traced = jax.jit(jax.value_and_grad(
            lambda p: trainer.loss_and_metrics(
                p, ids, targets, 2, trainer._key0, train=True)[0])
        ).trace(params)
        return (traced.lower().compile()(params),
                exponentials(traced.jaxpr.jaxpr))

    (loss, grads), exps_kept = value_and_gradient()
    assert [f.remat_kept for f in layers] == [CORE_KEEPS] * len(layers)
    for f in layers:
        f.remat_keeps = ()
    (loss_bare, grads_bare), exps_bare = value_and_gradient()
    assert [f.remat_kept for f in layers] == [()] * len(layers)
    for f in layers:
        f.remat = False
    (loss_all, grads_all), exps_all = value_and_gradient()
    assert exps_all <= exps_kept == exps_bare - 2 * len(layers)
    for other_loss, other in ((loss_bare, grads_bare), (loss_all, grads_all)):
        assert float(loss) == pytest.approx(float(other_loss), rel=1e-6)
        for name in grads:
            for key, g in grads[name].items():
                # float32 rounding: XLA fuses the three programs apart
                assert rel(g, other[name][key]) < 1e-5, (name, key)
    # the gradient passes through every core down to the first layer's
    assert float(jnp.abs(grads[layers[0].name]["wq"]).max()) > 0


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kept_cores_are_counted_by_a_training_run_not_an_evaluation(
        family, family_job):
    """``attn_cores_kept``: layers whose core's results cross
    rematerialisation — none while only an evaluation was traced, every
    decoder layer once a run traced its train step."""
    from znicz_tpu import decoder

    _, built = family_job(family)
    trainer, wf = built.trainer, built.wf
    layers = [f for f in wf.forwards if isinstance(f, decoder.DecoderLayer)]
    jax.jit(lambda p: trainer.loss_and_metrics(
        p, built.data[2:4], built.labels[2:4], 2, trainer._key0,
        train=False)[0]).trace(trainer.extract_params())
    assert decoder.DecoderLayer.run_stats(layers)["attn_cores_kept"] == 0
    assert "attn_cores_kept" not in trainer.stats
    wf.decision.max_epochs = 1
    trainer.run()
    assert trainer.stats["attn_cores_kept"] == len(layers) \
        == FAMILIES[family]["layers"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_sample_trains_through_the_launcher_and_counts(family, tmp_path,
                                                           restore_root):
    """``python -m znicz_tpu <sample>``'s path: StandardWorkflow ->
    FusedTrainer.run with loader, Decision and snapshotter; tokens and the
    expert layers' counters land in ``fused_stats``; nothing recompiles,
    schedule or not."""
    from znicz_tpu.launcher import Launcher

    fam = FAMILIES[family]
    launcher = Launcher([
        os.path.join(REPO, "znicz_tpu", "samples", f"{family}.py"),
        "--backend", "cpu", f"root.{family}.preset=tiny",
        f"root.{family}.decision.max_epochs=3",
        f"root.common.dirs.snapshots={tmp_path}"])
    assert launcher.run() == 0
    wf = launcher.workflow
    stats = wf.fused_stats
    history = wf.decision.epoch_history
    assert len(history) == 3
    assert history[-1]["train"] < history[0]["train"]
    assert stats["tokens"] == stats["images"] * 64 == 3 * 8 * 64
    assert stats["moe_rows_dropped"] == 0
    rows = stats["moe_rows_by_expert"]
    assert rows["max"] >= rows["mean"] >= rows["min"] >= 0
    # every step whose loss the host pulled is counted, validation too;
    # 4 expert layers, top_k a token, 128 tokens a step
    steps = stats["train_steps"] + stats["eval_steps"]
    assert stats["moe_counted_steps"] == steps == 3 * (4 + 1)
    assert 0 < stats["moe_rows_routed"] <= (
        fam["sparse"] * fam["top_k"] * 128 * steps)
    # the evaluation step (validation), the scan of 4 that ends in its
    # epoch's tail (two epochs), and for the run's last tail, which
    # max_epochs stops: the scan of the 3 before it and the train-mode
    # evaluation it is ruled on; its update is never run, so no train step
    assert stats["compiles"] == 4
    sizes = wf.fused_stats["jit_cache_sizes"]
    assert sizes["_train_step"] == 0 == sizes["_eval_scan"]
    assert (stats["tails_in_scan"], stats["tails_alone"]) == (2, 1)
    assert stats["attn_cores_composed"] == fam["layers"]
    assert stats["attn_cores_kept"] == fam["layers"]
    assert any(f.name.endswith(".pickle.gz") or f.name.endswith(".pickle")
               for f in tmp_path.iterdir())
    if family == "zaya":
        assert stats["router_states_carried"] == 3
        # the tiny preset's head runs its 128 rows in 2 blocks
        assert (stats["tied_tensors"], stats["loss_blocks"]) == (1, 2)
        assert stats["router_biases_moved"] == 4
        # 11 updates were applied (max_epochs stops the run at its last
        # tail, whose update is not adopted: the schedule is gated like
        # the update); the 12th would run at 12 / 2,000 of the rate
        assert float(wf.gds[0].learning_rate) == pytest.approx(
            3e-4 * 12 / 2000)
    else:
        assert "router_states_carried" not in stats
        assert stats["tied_tensors"] == 0
        assert float(wf.gds[0].learning_rate) == pytest.approx(3e-4)


def test_rows_no_group_computed_never_reach_result_or_gradient(monkeypatch):
    """The TPU's grouped product leaves the rows past the counts
    unwritten, forward and backward (the first chip run of PR 27 trained
    to NaN on them).  A product that writes NaN there stands in for it."""
    true_ragged_dot = jax.lax.ragged_dot

    def poison(out, sizes):
        past = (jnp.arange(out.shape[0]) >= jnp.sum(sizes))[:, None]
        return jnp.where(past, jnp.nan, out)

    @jax.custom_vjp
    def unwritten(a, w, sizes):
        return poison(true_ragged_dot(a, w, sizes), sizes)

    def fwd(a, w, sizes):
        return unwritten(a, w, sizes), (a, w, sizes)

    def bwd(res, ct):
        a, w, sizes = res
        _, vjp = jax.vjp(lambda a, w: true_ragged_dot(a, w, sizes), a, w)
        da, dw = vjp(ct)
        return poison(da, sizes), dw, None

    unwritten.defvjp(fwd, bwd)
    p, model, x = expert_layer(tokens=32)
    cut = {k: p[k][2:6] for k in ("experts_gate", "experts_up",
                                  "experts_down")}

    def system(cut, router, x):
        experts, weights = moe.route(x, router, 2, 2.5)
        return jnp.sum(jnp.square(moe.held_experts(
            x, experts, weights, cut["experts_gate"], cut["experts_up"],
            cut["experts_down"], 2)[0]))

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(system, (0, 1, 2))(cut, p["router"], x)
        monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
        got = jax.value_and_grad(system, (0, 1, 2))(cut, p["router"], x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_a_capacity_would_show_as_dropped_rows():
    """``rows_dropped`` counts the pairs of a held expert that the grouped
    product is not handed in that expert's group: 0 for the counts of the
    sort, and what a capacity of 3 rows an expert would clip."""
    experts = jnp.array([[0, 5], [0, 1], [0, 1], [0, 7], [0, 1], [1, 6]])
    order, inverse, key, rows = moe.dispatch(experts, 0, 2)
    np.testing.assert_array_equal(rows, [5, 4])
    assert int(moe.rows_outside_groups(key, inverse, rows)) == 0
    clipped = jnp.minimum(rows, 3)
    # expert 0 keeps 3 of its 5 rows; expert 1's group is then rows 3..5,
    # where one of its own 4 (rows 5..8) stands
    assert int(moe.rows_outside_groups(key, inverse, clipped)) == 2 + 3
