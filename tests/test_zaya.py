"""The second decoder (ISSUE 32): ZAYA1's block at the tiny preset on the
CPU — attention in a compressed latent with convolutional mixing, an MLP
router on a state carried from layer to layer, top-1 experts, a tied head
whose loss never builds the logits — against the plain reference
``benchmark/references/zaya.py`` on seeded weights, piece by piece and
through ``FusedTrainer``.  What holds for both decoders (the sample through
the launcher, int32 ids under bf16, the decay exemptions, the rotary
tables) is a case a family of one test in ``tests/test_laguna.py``.
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
from znicz_tpu.ops import cca, moe                          # noqa: E402

ref = spec.load_module("references", "zaya")
driver = spec.load_module("drivers", "train_tokens_blocked")
tokens = spec.load_module("drivers", "train_tokens")

def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def normal(seed, *shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


@pytest.fixture()
def job(tmp_path, restore_root):
    """``build(dtype)``: the tiny preset built as the benchmark's driver
    builds the cell."""
    root.common.dirs.snapshots = str(tmp_path)

    def build(dtype=None, seed=11):
        cell = spec.Cell(spec.load(), "zaya-train-32k")
        if dtype:
            root.common.engine.compute_dtype = dtype
            cell.config["tiny"]["root"][
                "root.common.engine.compute_dtype"] = dtype
        return cell, tokens.build(cell, seed, True)

    return build


# -- (c) the new pieces are causal, and are what an explicit loop gives --------


def loop_mix_channels(u, w, b):
    u, w, b = (np.asarray(t, np.float64) for t in (u, w, b))
    out = np.zeros_like(u)
    for t in range(u.shape[1]):
        out[:, t] = b + sum(
            w[j] * u[:, t - (w.shape[0] - 1 - j)] for j in range(w.shape[0])
            if t - (w.shape[0] - 1 - j) >= 0)
    return out


def loop_mix_heads(c, a, b):
    c, a, b = (np.asarray(t, np.float64) for t in (c, a, b))
    out = np.zeros_like(c)
    for t in range(c.shape[1]):
        for h in range(c.shape[2]):
            out[:, t, h] = b[h] + sum(
                c[:, t - (a.shape[0] - 1 - j), h] @ a[j, h]
                for j in range(a.shape[0]) if t - (a.shape[0] - 1 - j) >= 0)
    return out


def loop_shift_values(v):
    v = np.asarray(v, np.float64)
    out, half = v.copy(), v.shape[2] // 2
    out[:, 0, half:] = 0
    out[:, 1:, half:] = v[:, :-1, half:]
    return out


PIECES = {
    "channel_mixing": (lambda u: cca.mix_channels(
        u, normal(1, 2, 6, 8), normal(2, 6, 8)),
        lambda u: loop_mix_channels(u, normal(1, 2, 6, 8), normal(2, 6, 8))),
    "channel_mixing_3_taps": (lambda u: cca.mix_channels(
        u, normal(1, 3, 6, 8), normal(2, 6, 8)),
        lambda u: loop_mix_channels(u, normal(1, 3, 6, 8), normal(2, 6, 8))),
    "head_mixing": (lambda u: cca.mix_heads(
        u, normal(3, 2, 6, 8, 8), normal(4, 6, 8)),
        lambda u: loop_mix_heads(u, normal(3, 2, 6, 8, 8), normal(4, 6, 8))),
    "value_shift": (cca.shift_values, loop_shift_values),
}


@pytest.mark.parametrize("piece", sorted(PIECES))
def test_a_new_piece_is_causal_and_matches_an_explicit_loop(piece):
    fn, loop = PIECES[piece]
    u = normal(0, 2, 12, 6, 8)
    got = fn(u)
    assert rel(got, loop(u)) < 1e-6
    t = 7                       # positions after t change: up to t nothing moves
    later = u.at[:, t + 1:].set(normal(9, 2, 12 - t - 1, 6, 8))
    np.testing.assert_array_equal(np.asarray(fn(later))[:, :t + 1],
                                  np.asarray(got)[:, :t + 1])
    assert not np.allclose(np.asarray(fn(later))[:, t + 1:],
                           np.asarray(got)[:, t + 1:])


def test_the_whole_mixing_matches_the_reference_and_is_causal():
    model = dict(ref_model(), hidden_size=32)
    h, kv, hd = 4, 2, 16
    p = {"wq": normal(1, 32, h * hd, scale=.2),
         "wk": normal(2, 32, kv * hd, scale=.2),
         "wv": normal(3, 32, kv * hd, scale=.2),
         "mix_w": normal(4, 2, h + kv, hd), "mix_b": normal(5, h + kv, hd),
         "mix_heads": normal(6, 2, h + kv, hd, hd, scale=.3),
         "mix_heads_b": normal(7, h + kv, hd), "temp": normal(8, kv)}
    xn = normal(0, 2, 24, 32)
    from znicz_tpu.ops.attention import rope_tables

    def system(xn):
        b, t, _ = xn.shape
        return cca.mix((xn @ p["wq"]).reshape(b, t, h, hd),
                       (xn @ p["wk"]).reshape(b, t, kv, hd),
                       (xn @ p["wv"]).reshape(b, t, kv, hd), p,
                       *rope_tables(t, 8, 5e6))

    with jax.default_matmul_precision("highest"):
        got, want = system(xn), ref.mixed(model, p, xn, 24)
        for g, w in zip(got, want):
            assert rel(g, w) < 1e-5
        later = system(xn.at[:, 10:].set(normal(9, 2, 14, 32)))
    for g, w in zip(later, got):
        np.testing.assert_allclose(np.asarray(g)[:, :10],
                                   np.asarray(w)[:, :10], atol=1e-6)
    # q and k leave with length sqrt(head_dim), k times exp(temperature)
    np.testing.assert_allclose(np.linalg.norm(got[0], axis=-1), 4.0,
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.linalg.norm(got[1], axis=-1),
        np.broadcast_to(4.0 * np.exp(p["temp"]), got[1].shape[:-1]),
        rtol=1e-5)


def ref_model():
    from znicz_tpu.samples import laguna

    return dict(laguna.MODELS["zaya-tiny"])


# -- (f) the blocked head is the materialised one ------------------------------------


@pytest.mark.parametrize("block", [8, 16, 24, 40, 48],
                         ids=lambda b: f"block{b}")
def test_the_blocked_head_equals_the_materialised_one(block):
    """40 rows: blocks that divide them (8, 40), that do not (16, 24:
    the last block is padded) and one larger than the rows; value,
    error count and all three gradients."""
    rows, d, vocab = 40, 16, 50
    x, gain = normal(0, rows, d), 1 + normal(1, d, scale=.1)
    embed = normal(2, vocab, d, scale=.5)
    labels = jax.random.randint(jax.random.PRNGKey(3), (rows,), 0, vocab)
    valid = jnp.arange(rows) < 35

    def whole(x, gain, embed):
        logits = decoder.head_logits(x, gain, embed, 1e-5)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        miss = (jnp.argmax(logits, -1) != labels) & valid
        return jnp.sum(jnp.where(valid, logz - picked, 0.)), jnp.sum(miss)

    def blocked(x, gain, embed):
        return decoder.blocked_head_loss(x, gain, embed, labels, valid,
                                         1e-5, block)

    want, got = whole(x, gain, embed), jax.jit(blocked)(x, gain, embed)
    assert abs(float(got[0]) - float(want[0])) < 1e-4 * float(want[0])
    assert int(got[1]) == int(want[1]) > 0
    g_got = jax.grad(lambda *a: 0.5 * blocked(*a)[0], (0, 1, 2))(x, gain,
                                                                 embed)
    g_want = jax.grad(lambda *a: 0.5 * whole(*a)[0], (0, 1, 2))(x, gain,
                                                                embed)
    for g, w in zip(g_got, g_want):
        assert rel(g, w) < 1e-5


def test_the_block_rule_is_one_gib_of_float32_logits():
    assert decoder.loss_blocks(32768, 131136) == (17, 1928)
    assert 1928 * 131136 * 4 <= 1 << 30 < 2048 * 131136 * 4
    assert 17 * 1928 >= 32768 > 16 * 1928
    assert decoder.loss_blocks(16384, 12544) == (1, 16384)   # Laguna: whole
    assert decoder.loss_blocks(128, 64) == (1, 128)
    # the tiny preset's head: 128 rows of 256 ids in 2 blocks
    assert decoder.loss_blocks(128, 256, 65536) == (2, 64)


# -- (b) the shares add up -----------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_and_logits(restore_root):
    """Experts 0-3 and 4-7 of the tiny model's 8 and both halves of the
    vocabulary, with attention, router, norms and merges counted once,
    give the uncut reference's layer output and logits."""
    model = ref_model()
    d, total, vocab = 64, 8, 64

    def make(first, held):
        layer = decoder.DecoderLayer(
            None, name=f"layer_{first}", heads=4, kv_heads=2, head_dim=16,
            rope={"theta": 5e6, "rotary_dim": 8}, norm_eps=1e-5,
            attention="cca", router="mlp", router_width=16,
            residual_scale=True, expert_width=32, experts_total=total,
            experts_held=held, first_expert=first, experts_per_token=1)
        layer.hidden = d
        return layer

    whole = make(0, total)
    keys = iter(jax.random.split(jax.random.PRNGKey(5), 64))
    p = {key: (1.0 if std is None else 0.0)
         + jax.random.normal(next(keys), shape) * (
             0.1 if not std else 0.3 if key.startswith("router") else 0.15)
         for key, (shape, std) in whole.param_shapes().items()}
    x = normal(0, 2, 32, d)

    def cut(first, held, down=None):
        q = dict(p)
        for key in ("experts_gate", "experts_up", "experts_down"):
            q[key] = p[key][first:first + held]
        if down is not None:
            q["experts_down"] = q["experts_down"] * down
        return make(first, held).apply_carried(q, x, None)

    with jax.default_matmul_precision("highest"):
        (low, r_low, c_low), (high, r_high, c_high) = cut(0, 4), cut(4, 4)
        nothing, _, _ = cut(0, 4, down=0.0)     # the merge of no part
        want, r_want = ref.layer(model, {"experts_held": total,
                                         "first_expert": 0}, p, x, None, 32)
    assert rel(low + high - nothing, want) < 1e-5
    assert rel(r_low, r_want) < 1e-5 and rel(r_high, r_want) < 1e-5
    # every token's one expert lives on one side or the other
    assert int(c_low["rows_by_expert"].sum()
               + c_high["rows_by_expert"].sum()) == 2 * 32
    assert min(int(c_low["rows_by_expert"].sum()),
               int(c_high["rows_by_expert"].sum())) > 0
    embed, gain = normal(1, vocab, d, scale=.2), 1 + normal(2, d, scale=.1)
    rows = want.reshape(-1, d)
    halves = [decoder.head_logits(rows, gain, embed[half], 1e-5)
              for half in (slice(0, 32), slice(32, 64))]
    with jax.default_matmul_precision("highest"):
        assert rel(jnp.concatenate(halves, axis=-1), ref.logits_of(
            {"embed": embed, "norm": gain}, rows, model)) < 1e-5


# -- (a) the system against the reference ---------------------------------------------


def test_system_matches_reference_logits_loss_gradients_and_adamw(job):
    """Seeded weights, float32 compute: logits (in blocks), loss, every
    tensor's gradient, and one AdamW step under the warm-up's rate of the
    trainer's own compiled step against the plain reference."""
    cell, built = job("float32")
    model, share = tokens.model_and_share(cell.config, True)
    wf, trainer = built.wf, built.trainer
    assert wf.forwards[-1].blocks_for((2, 64, 64))[0] > 1   # in blocks
    agreement = driver.parity(cell, model, share, trainer, wf.forwards,
                              built.data[2:4], 32)
    assert agreement["relative_l2"] < 1e-4
    assert agreement["relative_l2_float8"] > 20 * agreement["relative_l2"]
    # every tensor's gradient
    params = trainer.extract_params()
    ids, targets = built.data[2:4], built.labels[2:4]
    grads = jax.jit(jax.grad(lambda p: trainer.loss_and_metrics(
        p, ids, targets, 2, trainer._key0, train=True)[0]))(params)
    want = jax.jit(jax.grad(lambda t: ref.loss(
        t, ids, targets, model, share, loss_block=32)))(
            driver.reference_tree(wf.forwards, params))
    got = driver.reference_tree(wf.forwards, grads)
    flat_got, _ = jax.tree_util.tree_flatten_with_path(got)
    flat_want, _ = jax.tree_util.tree_flatten_with_path(want)
    assert [k for k, _ in flat_got] == [k for k, _ in flat_want]
    assert len(flat_got) == 2 + 4 * 32 - 1   # layer 0 holds no gamma
    for (path, g), (_, w) in zip(flat_got, flat_want):
        assert rel(g, w) < 2e-3, jax.tree_util.keystr(path)
    step = driver.step_check(cell, model, share, wf, trainer, built.data,
                             built.labels, range(2, 4))
    assert step["learning_rate"] == pytest.approx(3e-4 / 2000)
    assert abs(step["loss"]["system"] - step["loss"]["reference"]) < 1e-4
    assert set(step["by_group"]) == set(ref.GROUPS)
    tight = {g: {"gradient": 2e-3, "update": 5e-2} for g in ref.GROUPS}
    assert tokens.within(step["by_group"], tight)
    assert not tokens.within(step["by_group"], dict(
        tight, router={"gradient": 1e-9, "update": 1.0}))
    assert not tokens.within(dict.fromkeys(tight, tokens.UNCHANGED), tight)


def test_every_tensor_is_in_a_group_and_decay_follows_the_reference(job):
    _, built = job()
    seen = set()
    for f in built.wf.forwards:
        for key in f.params():
            assert tokens.group_of(ref, key)
            seen.add(key)
            assert (key in f.decay_exempt) == (key in ref.NO_DECAY), key
    assert seen == {k for keys in ref.GROUPS.values() for k in keys}


# -- (d) the carried state -------------------------------------------------------------------


def test_the_router_state_goes_from_layer_to_layer_and_through_remat(job):
    _, built = job("float32")
    wf, trainer = built.wf, built.trainer
    layers = wf.forwards[1:-1]
    seen = []
    for f in layers:
        def spy(p, x, carry, f=f, inner=f.apply_carried):
            y, out, counters = inner(p, x, carry)
            seen.append((f.name, carry, out))
            return y, out, counters

        f.apply_carried = spy
    params = trainer.extract_params()
    ids, targets = built.data[2:4], built.labels[2:4]
    trainer.forward_pass(params, ids, None, False)      # eagerly: arrays
    assert [name for name, _, _ in seen] == [f.name for f in layers]
    assert seen[0][1] is None                           # layer 0: none
    for before, after in zip(seen, seen[1:]):
        assert after[1] is before[2]                    # l gets l - 1's
        assert after[2].shape == (2 * 64, 16)
    assert [f.received_state for f in layers] == [False, True, True, True]
    assert "router_gamma" not in layers[0].params()
    assert all("router_gamma" in f.params() for f in layers[1:])
    with pytest.raises(ValueError, match="state"):      # a state is owed
        layers[1].apply_counted(params[layers[1].name],
                                jnp.zeros((1, 64, 64)))

    def gradient():
        return jax.jit(jax.grad(lambda p: trainer.loss_and_metrics(
            p, ids, targets, 2, trainer._key0, train=True)[0]))(params)

    with_remat = gradient()
    for f in layers:
        f.remat = False
    without = gradient()
    for name in with_remat:
        for key, g in with_remat[name].items():
            assert rel(g, without[name][key]) < 1e-5, (name, key)
    # the state carries gradient back: layer 0's router_down moves layer
    # 3's choice weights
    assert float(jnp.abs(with_remat[layers[0].name]["router_down"]).max()) > 0


# -- the selection bias that the load moves (ROADMAP M13) --------------------------------


def loads(biased):
    return np.bincount(np.argmax(np.asarray(biased), axis=1),
                       minlength=biased.shape[1])


@pytest.mark.parametrize("case", ["spread", "one_expert_wins", "tiny_scale"])
def test_the_balance_step_is_the_move_to_an_even_share(case):
    """Against the reference's loop over experts; the whole move (twice
    the damped step) of ONE expert alone leaves it exactly its even
    share; and repeated on the same scores the loads even out, whatever
    the scores' scale."""
    tokens_n, experts = 512, 8
    p = jax.nn.softmax(normal(0, tokens_n, experts) * {
        "spread": 1.0, "one_expert_wins": 0.2, "tiny_scale": 1e-3}[case])
    if case == "one_expert_wins":       # a common mode: most choose 3
        p = p + 0.1 * (jnp.arange(experts) == 3)
    step = moe.balance_step(p, 1)
    assert abs(float(step.sum())) < 1e-6 * float(jnp.abs(step).max())
    share = tokens_n // experts
    uncentred = step - step[0]
    for e in range(experts):
        others = np.delete(np.asarray(p), e, axis=1).max(axis=1)
        margin = np.sort(np.asarray(p)[:, e] - others)[tokens_n - share]
        assert float(uncentred[e] - uncentred[0]) == pytest.approx(
            -0.5 * (margin - (np.sort(np.asarray(p)[:, 0] - np.delete(
                np.asarray(p), 0, axis=1).max(axis=1))[tokens_n - share])),
            rel=1e-4, abs=1e-9)
        alone = p.at[:, e].add(-margin * (1 + 1e-6))
        assert abs(int(loads(alone)[e]) - share) <= 1
    bias = jnp.zeros(experts)
    before = loads(p).max() / share
    for _ in range(12):
        bias = bias + moe.balance_step(p + bias, 1)
    after = loads(p + bias).max() / share
    assert after < 1.25 and (after < before or before < 1.25), (before,
                                                                after)


def test_a_train_step_moves_the_bias_and_nothing_else_does(job):
    """The step adds the layer's move to ``router_bias`` (the reference's
    ``balance_step`` on the same rows); AdamW leaves the tensor alone
    (no gradient, no decay); an evaluation neither moves it nor returns a
    move; a snapshot holds it."""
    cell, built = job("float32")
    model, share = tokens.model_and_share(cell.config, True)
    wf, trainer = built.wf, built.trainer
    layers = wf.forwards[1:-1]
    assert all("router_bias" in f.params() and "router_bias"
               in f.decay_exempt for f in layers)
    params, state = trainer.extract_params(), trainer.extract_velocities()
    start = {f.name: jnp.asarray(normal(i, 8, scale=1e-3))
             for i, f in enumerate(layers)}
    params = {name: dict(p, **({"router_bias": start[name]}
                               if name in start else {}))
              for name, p in params.items()}
    ids, targets = built.data[2:4], built.labels[2:4]
    hypers = {name: tuple(np.float32(v) for v in (1e-3, 0.1, 0.9, 0.95,
                                                  1e-8))
              for name in trainer.hypers()}
    taps = []
    ref.final_hidden(driver.reference_tree(wf.forwards, params), ids, model,
                     share, taps=taps)
    new_p, new_s, metrics = jax.jit(trainer._update_core)(
        params, state, hypers, ids, targets, np.int32(2), trainer._key0)
    for f, (p_ref, r) in zip(layers, taps):
        want = ref.balance_step(model, p_ref, r)
        assert float(jnp.abs(want).max()) > 0
        assert rel(new_p[f.name]["router_bias"] - start[f.name], want) < 1e-3
        assert float(jnp.abs(new_s[f.name]["m_router_bias"]).max()) == 0
        assert set(metrics[3][f.name]) == {"rows_by_expert", "rows_dropped"}
    _, evaluated = jax.jit(lambda p: trainer.loss_and_metrics(
        p, ids, targets, 2, trainer._key0, train=False))(params)
    assert set(evaluated[3][layers[0].name]) == {"rows_by_expert",
                                                 "rows_dropped"}
    snap = trainer.snapshot_from_trees(new_p, new_s)
    np.testing.assert_array_equal(
        np.asarray(snap["units"][layers[1].name]["router_bias"]),
        np.asarray(new_p[layers[1].name]["router_bias"]))
    # the choice is by p + bias, the weight stays p: a bias that favours
    # one expert sends every token there
    lopsided = dict(params[layers[0].name],
                    router_bias=jnp.zeros(8).at[5].set(1.0))
    _, _, counters = layers[0].apply_carried(
        lopsided, jnp.asarray(normal(3, 2, 64, 64)), None)
    assert int(counters["rows_by_expert"].sum()) == 0   # 5 is not held
    assert trainer.stats.get("router_biases_moved", 4) == 4


# -- (e) the tied tensor ------------------------------------------------------------------------


def test_the_tied_tensor_stands_once_and_sums_both_uses(job, tmp_path):
    from znicz_tpu import snapshotter

    _, built = job("float32")
    wf, trainer = built.wf, built.trainer
    embed, head = wf.forwards[0], wf.forwards[-1]
    assert head.borrowed == {"weights": (embed.name, "embed")}
    assert set(head.params()) == {"norm"}
    params = trainer.extract_params()
    assert set(params[head.name]) == {"norm"}
    # one moment pair, in the embedding's optimizer state
    state = trainer.extract_velocities()
    assert set(state[embed.name]) == {"m_embed", "v_embed", "step"}
    assert set(state[head.name]) == {"m_norm", "v_norm", "step"}
    ids, targets = built.data[2:4], built.labels[2:4]

    def loss(p):
        return trainer.loss_and_metrics(p, ids, targets, 2, trainer._key0,
                                        train=True)[0]

    tied = jax.jit(jax.grad(loss))(params)[embed.name]["embed"]
    # the two uses apart: the head on a tensor of its own
    apart = {name: dict(p) for name, p in params.items()}
    apart[head.name]["weights"] = params[embed.name]["embed"]
    head.borrowed = {}
    grads = jax.jit(jax.grad(loss))(apart)
    head.borrowed = {"weights": (embed.name, "embed")}
    lookup, logits = grads[embed.name]["embed"], grads[head.name]["weights"]
    assert float(jnp.abs(lookup).max()) > 0 < float(jnp.abs(logits).max())
    assert rel(tied, lookup + logits) < 1e-5
    # a snapshot names it once, and restores it
    snap = trainer.snapshot_from_trees(params, state)
    assert set(snap["units"][head.name]) == {"norm"}
    assert set(snap["units"][embed.name]) == {"embed"}
    held = snapshotter.collect(wf)
    kept = np.array(embed.tensors["embed"].map_read())
    embed.tensors["embed"].mem = np.zeros_like(kept)
    snapshotter.restore(wf, held)
    np.testing.assert_array_equal(embed.tensors["embed"].map_read(), kept)
    # decay applies once: the embedding's update decays it, the head's
    # update has nothing of it
    gd = trainer.gd_of[head.name]
    new_p, _ = gd.apply_update(
        {"norm": jnp.ones(64)}, {"norm": jnp.zeros(64)},
        {k: a.devmem for k, a in gd._velocities.items()},
        tuple(np.float32(v) for v in (0.5, 0.1, 0.9, 0.95, 1e-8)))
    assert set(new_p) == {"norm"}


# -- (g) the schedule ---------------------------------------------------------------------------


def test_the_warm_up_gives_every_step_its_own_row_and_compiles_nothing(job):
    from znicz_tpu.lr_adjust import LearningRateAdjust, WarmupPolicy

    _, built = job()
    wf, trainer = built.wf, built.trainer
    assert isinstance(wf.lr_adjust, LearningRateAdjust)
    assert trainer._lr_adjust is wf.lr_adjust
    rows = trainer._hypers_rows(4)
    for name, mat in rows.items():
        np.testing.assert_allclose(
            mat[:, 0], [3e-4 * (t + 1) / 2000 for t in range(4)], rtol=1e-6)
        assert (mat[:, 1:] == mat[0, 1:]).all()         # only the rate
    np.testing.assert_allclose(
        trainer._hypers_rows(2)[name][:, 0],
        [3e-4 * (t + 1) / 2000 for t in (4, 5)], rtol=1e-6)
    policy = WarmupPolicy(steps=3)
    assert [policy.first(1.0)] + [policy(1.0, it) for it in range(4)] == \
        pytest.approx([1 / 3, 2 / 3, 1, 1, 1])
    wf.lr_adjust.restore_iteration(0)
    assert float(wf.gds[0].learning_rate) == pytest.approx(3e-4 / 2000)
    assert ref.warmup_rate(5, 3e-4, 2000) == pytest.approx(
        float(rows[name][0, 0]) * 6)
