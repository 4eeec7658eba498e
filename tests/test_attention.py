"""Attention: single-device correctness, ring-attention sequence
parallelism over 8 virtual devices (exactness vs full attention), MHA unit
fwd/bwd."""

import numpy as np
import pytest

from znicz_tpu.memory import Array
from znicz_tpu.ops.attention import attention, ring_attention


def np_attention(q, k, v, causal=False):
    b, t, h, d = q.shape
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.triu(np.ones((t, t), bool), 1)
        s = np.where(mask[None, None], -np.inf, s)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_attention_matches_numpy(causal):
    rng = np.random.default_rng(31)
    q, k, v = (rng.normal(size=(2, 8, 2, 4)).astype(np.float32)
               for _ in range(3))
    got = np.array(attention(q, k, v, causal=causal))
    want = np_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_exact_over_8_shards(causal):
    import jax
    from jax.sharding import PartitionSpec as P

    from znicz_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(axes=("sp",))
    n = mesh.shape["sp"]
    assert n == 8
    rng = np.random.default_rng(33)
    T = 8 * n                                    # 8 tokens per device
    q, k, v = (rng.normal(size=(2, T, 2, 4)).astype(np.float32)
               for _ in range(3))

    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"))
    got = np.array(ring(q, k, v))
    want = np_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)


def test_mha_unit_fwd_bwd():
    from znicz_tpu.attention import GDMultiHeadAttention, MultiHeadAttention

    rng = np.random.default_rng(35)
    x = rng.normal(size=(2, 6, 8)).astype(np.float32)
    mha = MultiHeadAttention(name="mha", heads=2, causal=True)
    mha.input = Array(x)
    mha.initialize(device=None)
    mha.run()
    out = np.array(mha.output.map_read())
    assert out.shape == x.shape
    # oracle
    q = (x @ mha.proj["wq"].mem).reshape(2, 6, 2, 4)
    k = (x @ mha.proj["wk"].mem).reshape(2, 6, 2, 4)
    v = (x @ mha.proj["wv"].mem).reshape(2, 6, 2, 4)
    want = np_attention(q, k, v, causal=True).reshape(2, 6, 8) \
        @ mha.proj["wo"].mem
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)

    gd = GDMultiHeadAttention(name="mhagd", forward=mha, learning_rate=1.0,
                              need_err_input=True)
    err = rng.normal(size=out.shape).astype(np.float32)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    w0 = mha.proj["wo"].mem.copy()
    gd.run()
    dW = w0 - np.array(mha.proj["wo"].map_read())

    eps = 1e-2
    import jax.numpy as jnp

    def loss(wo):
        params = {kk: jnp.asarray(a.mem) for kk, a in mha.proj.items()}
        params["wo"] = jnp.asarray(wo)
        return float(jnp.sum(jnp.asarray(err) * mha.apply(params,
                                                          jnp.asarray(x))))

    for idx in [(0, 0), (5, 3)]:
        wp = w0.copy(); wp[idx] += eps
        wm = w0.copy(); wm[idx] -= eps
        num = (loss(wp) - loss(wm)) / (2 * eps)
        assert abs(num - dW[idx]) < 5e-2 * max(1.0, abs(num)), idx
    assert np.array(gd.err_input.map_read()).shape == x.shape

def test_attention_causal_offsets():
    """``attention(q_offset, k_offset)``: the global-position causal
    masking sharded blocks rely on.  A query block computed with its
    global offset over the full key set must equal the matching rows of
    full causal attention, and explicit offsets must reproduce a numpy
    oracle masking ``kpos > qpos``."""
    rng = np.random.default_rng(41)
    q, k, v = (rng.normal(size=(2, 8, 2, 4)).astype(np.float32)
               for _ in range(3))
    full = np.array(attention(q, k, v, causal=True))
    blk = np.array(attention(q[:, 4:], k, v, causal=True, q_offset=4))
    np.testing.assert_allclose(blk, full[:, 4:], rtol=1e-6, atol=1e-7)

    # numpy oracle with explicit global positions: queries at 4..7,
    # keys at 2..5 (k_offset=2) — key j visible iff 2+j <= 4+i
    qb, kb, vb = q[:, 4:], k[:, 2:6], v[:, 2:6]
    got = np.array(attention(qb, kb, vb, causal=True,
                             q_offset=4, k_offset=2))
    s = np.einsum("bqhd,bkhd->bhqk", qb, kb) / np.sqrt(4)
    qpos = 4 + np.arange(4)
    kpos = 2 + np.arange(4)
    s = np.where(kpos[None, None, None, :] > qpos[None, None, :, None],
                 -np.inf, s)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", p, vb)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_attention_k_valid_mask_length_independence():
    """``k_valid`` (ISSUE 15): masked pad keys carry exactly zero
    probability mass, so a row's output over its own L real keys equals
    the unpadded computation — for non-causal attention too, where the
    causal structure gives no free independence."""
    rng = np.random.default_rng(43)
    L, T = 5, 8
    q, k, v = (rng.normal(size=(2, T, 2, 4)).astype(np.float32)
               for _ in range(3))
    # garbage in the padded tail must be invisible behind the mask
    k[:, L:] = 1e3
    v[:, L:] = -1e3
    k_valid = np.zeros((2, T), bool)
    k_valid[:, :L] = True
    got = np.array(attention(q, k, v, k_valid=k_valid))
    want = np.array(attention(q, k[:, :L], v[:, :L]))
    np.testing.assert_allclose(got[:, :L], want[:, :L],
                               rtol=1e-5, atol=1e-6)


def test_gd_mha_grads_match_attention_oracle_and_fd():
    """Gradient-parity oracle for GDMultiHeadAttention (ISSUE 15
    satellite): the unit's applied updates (lr=1, no momentum/decay)
    must equal ``jax.grad`` of a loss built DIRECTLY on
    ``ops.attention.attention`` for every projection, with finite
    differences spot-checking the oracle itself."""
    import jax
    import jax.numpy as jnp

    from znicz_tpu.attention import GDMultiHeadAttention, MultiHeadAttention

    rng = np.random.default_rng(45)
    B, T, H, D, E = 2, 6, 2, 4, 8
    x = rng.normal(size=(B, T, E)).astype(np.float32)
    mha = MultiHeadAttention(name="mha_orc", heads=H, causal=True)
    mha.input = Array(x)
    mha.initialize(device=None)
    mha.run()
    err = rng.normal(size=(B, T, E)).astype(np.float32)

    gd = GDMultiHeadAttention(name="mha_orc_gd", forward=mha,
                              learning_rate=1.0, gradient_moment=0.0,
                              need_err_input=True)
    gd.err_output = Array(err)
    gd.initialize(device=None)
    w0 = {kk: np.array(a.map_read()) for kk, a in mha.proj.items()}
    gd.run()
    applied = {kk: w0[kk] - np.array(a.map_read())
               for kk, a in mha.proj.items()}

    def oracle(params, xx):
        q = (xx @ params["wq"]).reshape(B, T, H, D)
        k = (xx @ params["wk"]).reshape(B, T, H, D)
        v = (xx @ params["wv"]).reshape(B, T, H, D)
        o = attention(q, k, v, causal=True)
        return o.reshape(B, T, H * D) @ params["wo"]

    def loss(params):
        return jnp.sum(jnp.asarray(err) * oracle(params, jnp.asarray(x)))

    grads = jax.grad(loss)({kk: jnp.asarray(w) for kk, w in w0.items()})
    for kk in ("wq", "wk", "wv", "wo"):
        np.testing.assert_allclose(
            applied[kk], np.asarray(grads[kk]), rtol=2e-4, atol=1e-6,
            err_msg=f"GD update for {kk} != jax.grad of the "
                    f"ops.attention oracle")
    # finite differences validate the oracle itself (two entries per
    # matrix class: an input proj and the output proj)
    eps = 1e-2
    for kk, idx in (("wq", (1, 2)), ("wo", (3, 5))):
        wp = {m: w.copy() for m, w in w0.items()}
        wm = {m: w.copy() for m, w in w0.items()}
        wp[kk][idx] += eps
        wm[kk][idx] -= eps
        num = (loss({m: jnp.asarray(w) for m, w in wp.items()})
               - loss({m: jnp.asarray(w) for m, w in wm.items()})) \
            / (2 * eps)
        num = float(num)
        assert abs(num - applied[kk][idx]) < 5e-2 * max(1.0, abs(num)), \
            (kk, idx, num, applied[kk][idx])
    assert np.array(gd.err_input.map_read()).shape == x.shape


def test_seq_parallel_knob_routes_mha_through_ring():
    """``root.common.engine.seq_parallel`` (ISSUE 15): with the knob on,
    MultiHeadAttention.apply runs ring attention over an ("sp",) mesh of
    virtual devices and matches the dense path numerically; a seq length
    the mesh cannot split falls back to the dense core; the knob off is
    the bit-exact single-device path."""
    from znicz_tpu.core.config import root

    from znicz_tpu.attention import MultiHeadAttention

    rng = np.random.default_rng(47)
    x = rng.normal(size=(2, 32, 8)).astype(np.float32)

    def build(name):
        mha = MultiHeadAttention(name=name, heads=2, causal=True)
        mha.input = Array(x)
        mha.initialize(device=None)
        return mha

    base = build("mha_sp_off")
    base.run()
    ref = np.array(base.output.map_read())
    try:
        root.common.engine.seq_parallel = 8
        sp = build("mha_sp_on")
        assert sp._sp_mesh is not None and sp._sp_mesh.size == 8
        for kk, a in base.proj.items():            # identical weights
            sp.proj[kk].mem = np.array(a.map_read())
        sp.run()
        got = np.array(sp.output.map_read())
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-5)
        # a length the mesh cannot split (serving's short buckets)
        # falls back to the dense core instead of failing
        short = rng.normal(size=(2, 6, 8)).astype(np.float32)
        out = np.array(sp.apply(
            {kk: np.array(a.map_read()) for kk, a in sp.proj.items()},
            short))
        assert out.shape == short.shape
        # a non-divisible TRAINED length is refused readably
        bad = MultiHeadAttention(name="mha_sp_bad", heads=2, causal=True)
        bad.input = Array(rng.normal(size=(2, 30, 8)
                                     ).astype(np.float32))
        with pytest.raises(ValueError, match="seq_parallel"):
            bad.initialize(device=None)
    finally:
        root.common.engine.seq_parallel = 0


def test_sequence_parallel_training_grads_match_and_learn():
    """Long-context training end-to-end: grads flow THROUGH ring attention
    under shard_map over an ('sp',) mesh, match the single-device
    computation exactly, and a few SGD steps reduce the loss."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from znicz_tpu.ops.attention import attention, ring_attention
    from znicz_tpu.parallel.mesh import make_mesh

    B, T, H, D, E = 2, 32, 2, 8, 16
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(B, T, E)).astype(np.float32))
    y = jnp.asarray(np.roll(np.asarray(x), -1, axis=1))
    params = {k: jnp.asarray(rng.normal(size=(E, H * D)).astype(np.float32)
                             / np.sqrt(E))
              for k in ("wq", "wk", "wv")}
    params["wo"] = jnp.asarray(
        rng.normal(size=(H * D, E)).astype(np.float32) / np.sqrt(H * D))

    def model(p, x, ring):
        b, t, e = x.shape
        q = (x @ p["wq"]).reshape(b, t, H, D)
        k = (x @ p["wk"]).reshape(b, t, H, D)
        v = (x @ p["wv"]).reshape(b, t, H, D)
        o = (ring_attention(q, k, v, "sp", causal=True) if ring
             else attention(q, k, v, causal=True))
        return o.reshape(b, t, H * D) @ p["wo"]

    mesh = make_mesh((8,), ("sp",))

    def sp_loss(p, x, y):
        # x/y arrive sequence-sharded: (B, T/8, E) per device
        out = model(p, x, ring=True)
        local = jnp.mean(jnp.square(out - y))
        return jax.lax.pmean(local, "sp")

    spec = P(None, "sp", None)
    sharded_loss = jax.shard_map(sp_loss, mesh=mesh,
                                 in_specs=(P(), spec, spec), out_specs=P())

    def ref_loss(p, x, y):
        return jnp.mean(jnp.square(model(p, x, ring=False) - y))

    g_sp = jax.jit(jax.grad(sharded_loss))(params, x, y)
    g_ref = jax.jit(jax.grad(ref_loss))(params, x, y)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_sp[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=2e-4, atol=1e-6, err_msg=k)

    # a few sequence-parallel SGD steps actually learn
    @jax.jit
    def step(p, x, y):
        loss, g = jax.value_and_grad(sharded_loss)(p, x, y)
        return {k: p[k] - 0.3 * g[k] for k in p}, loss

    losses = []
    p = params
    for _ in range(30):
        p, loss = step(p, x, y)
        losses.append(float(loss))
    assert losses[-1] < 0.8 * losses[0], losses
    assert all(b <= a + 1e-6 for a, b in zip(losses, losses[1:])), losses
