"""Plain reference: one chip's share of a Laguna decoder — forward, loss,
and the AdamW rule — in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No kernels, no mixed
precision, no sorting or grouping of rows: the experts are a loop, the
attention scores are materialised (in query blocks, so that 8,192
positions fit beside the system under test).  It imports nothing of
``znicz_tpu``.

Source: https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json
(the keys this file reads are that file's; ``model`` below is that
dictionary).  Readings the config does not settle are marked ASSUMED; the
configuration's file lists them once, for program and reference alike.

The equations.  ``x`` is ``(T, hidden)``; layer ``l``::

    h = x + Attn_l(RMSNorm(x)),     y = h + FFN_l(RMSNorm(h))
    RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g

after the last layer a final RMSNorm and an untied head; the loss is the
mean next-token cross-entropy in float32.

``Attn_l``: ``H_l = num_attention_heads_per_layer[l]`` query heads (48 in
full layers, 64 in window layers), ``q = x W_q`` ``(T, H_l, 128)``,
``k, v = x W_k, x W_v`` ``(T, 8, 128)``, no biases and no q/k
normalisation (ASSUMED).  Positions are rotated into q and k in the
rotate-half pairing (ASSUMED): window layers rotate all 128 dimensions
with theta 10,000; full layers rotate the first 64
(``partial_rotary_factor`` 0.5) with YaRN frequencies (theta 500,000,
factor 64, original length 4,096, beta_fast 64, beta_slow 1; cos and sin
times ``attention_factor``) and pass the other 64 through.  Query head
``h`` reads KV head ``h // (H_l / 8)``.  Key ``j`` is admitted for query
``i`` iff ``j <= i`` (full) or ``0 <= i - j < sliding_window`` (window);
scale ``1/sqrt(128)``; softmax in float32.  ``gating``: head ``h``'s
output is multiplied by ``sigmoid(x^ . w_gate[:, h])``, ``x^`` the
layer's normed input — one gate a head (ASSUMED: a gate as wide as the
heads' output would add 0.63 B parameters to the published 33.4 B).  Then
``W_o``.

``FFN_0`` (``mlp_layer_types[0] == "dense"``) is a SwiGLU of width
``intermediate_size``: ``(silu(x W_g) * x W_u) W_d``.  ``FFN_l``, sparse:
``s = sigmoid(x W_r)`` over all ``num_experts``; ``S`` = the
``num_experts_per_tok`` largest of ``s``; ``w_e =
moe_routed_scaling_factor * s_e / sum_{j in S} s_j`` (ASSUMED: sigmoid
scores renormalised over the chosen, no selection bias, no auxiliary
loss); ``y = Shared(x) + sum_{e in S} w_e Expert_e(x)``, every expert and
the shared one a SwiGLU; the weight multiplies the expert's OUTPUT
(``moe_apply_router_weight_on_input`` false).

The share (``share``: ``layers``, ``experts_held``, ``first_expert``,
``vocab_held``): this chip holds layers ``0 .. layers - 1``, the routed
experts ``first_expert .. first_expert + experts_held - 1`` of every
sparse layer, and ids ``0 .. vocab_held - 1``.  The router still scores
and chooses over all ``num_experts`` and normalises over the chosen; the
sum over ``S`` keeps the held experts only — what the others would add
lives on other chips and is left out, as in the system under test.

Parameter layout, this file's own statement: ``{"embed": (vocab_held,
hidden), "layers": [per layer a dict], "norm": (hidden,), "head":
(vocab_held, hidden)}``; a layer holds ``norm_attn``, ``wq`` ``(hidden,
H_l * 128)``, ``wk``, ``wv`` ``(hidden, 8 * 128)``, ``w_gate`` ``(hidden,
H_l)``, ``wo`` ``(H_l * 128, hidden)``, ``norm_ffn`` and either
``ffn_gate``, ``ffn_up`` ``(hidden, width)``, ``ffn_down`` ``(width,
hidden)`` or ``router`` ``(hidden, num_experts)``, ``shared_gate``,
``shared_up``, ``shared_down`` and ``experts_gate``, ``experts_up``
``(experts_held, hidden, width)``, ``experts_down`` ``(experts_held,
width, hidden)``.
"""

from __future__ import annotations

import math

#: the groups a comparison reports by: group -> the tensors in it
GROUPS = {
    "embedding": ("embed",),
    "attention": ("wq", "wk", "wv", "wo"),
    "gate": ("w_gate",),
    "router": ("router",),
    "shared": ("shared_gate", "shared_up", "shared_down"),
    "experts": ("experts_gate", "experts_up", "experts_down"),
    "dense": ("ffn_gate", "ffn_up", "ffn_down"),
    "norms": ("norm_attn", "norm_ffn", "norm"),
    "head": ("head",),
}
#: tensors AdamW's decay skips (norms, gates, the router)
NO_DECAY = ("norm_attn", "norm_ffn", "norm", "w_gate", "router")


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(model: dict, kind: str, length: int):
    """``(cos, sin, rotary_dim)`` of ``rope_parameters[kind]`` for
    positions ``0 .. length - 1``; cos and sin are ``(length, rotary_dim /
    2)``, one column a frequency."""
    import numpy as np

    cfg = model["rope_parameters"][kind]
    dim = int(model["head_dim"] * cfg.get("partial_rotary_factor", 1))
    base = float(cfg["rope_theta"])
    freq = base ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = 1.0
    if cfg.get("rope_type") == "yarn":
        # Peng et al. 2023: frequencies that turn often over the original
        # length are kept, slow ones are divided by ``factor``, a linear
        # ramp between the two corrections
        span = cfg["original_max_position_embeddings"]

        def correction(turns):
            return dim * math.log(span / (turns * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction(cfg["beta_fast"])), 0)
        high = min(math.ceil(correction(cfg["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(dim // 2) - low)
                       / (0.001 if high == low else high - low), 0.0, 1.0)
        keep = 1.0 - ramp
        freq = freq / cfg["factor"] * (1.0 - keep) + freq * keep
        factor = float(cfg["attention_factor"])
    angle = np.outer(np.arange(length, dtype=np.float64), freq)
    return ((np.cos(angle) * factor).astype(np.float32),
            (np.sin(angle) * factor).astype(np.float32), dim)


def rotate(x, cos, sin, dim):
    """Rotate-half: the pair ``(x[i], x[i + dim/2])`` turns by frequency
    ``i``; dimensions past ``dim`` pass through.  ``x``: ``(batch, seq,
    heads, head_dim)``."""
    import jax.numpy as jnp

    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def attention(q, k, v, window, query_block: int):
    """Softmax attention with materialised masked scores, ``query_block``
    queries at a time against every key."""
    import jax
    import jax.numpy as jnp

    b, t, heads, d = q.shape
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)
    query_block = min(query_block, t)
    assert t % query_block == 0, (t, query_block)
    kpos = jnp.arange(t)

    def block(start):
        qi = jax.lax.dynamic_slice_in_dim(q, start, query_block, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k) / math.sqrt(d)
        qpos = start + jnp.arange(query_block)
        admitted = kpos[None, :] <= qpos[:, None]
        if window is not None:
            admitted &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(admitted[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block),
                      jnp.arange(0, t, query_block))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads, d)


def swiglu(x, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routing(model: dict, p: dict, x):
    """``(experts, weights)`` ``(tokens, num_experts_per_tok)`` over all
    the model's experts."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ p["router"])
    top, experts = jax.lax.top_k(s, int(model["num_experts_per_tok"]))
    scale = float(model["moe_routed_scaling_factor"])
    return experts, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def routed_part(model: dict, share: dict, p: dict, x):
    """``sum_{e in S, e held} w_e Expert_e(x)``: a loop over the held
    experts, each applied to every token and weighted by the token's
    weight for it (0 where the token did not choose it)."""
    import jax
    import jax.numpy as jnp

    experts, weights = routing(model, p, x)
    first = int(share["first_expert"])

    def one(x, e, w_gate, w_up, w_down):
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return w[:, None] * swiglu(x, w_gate, w_up, w_down)

    def step(acc, held):
        e, w_gate, w_up, w_down = held
        return acc + jax.checkpoint(one)(x, e, w_gate, w_up, w_down), None

    held = int(share["experts_held"])
    acc, _ = jax.lax.scan(step, jnp.zeros_like(x), (
        first + jnp.arange(held), p["experts_gate"], p["experts_up"],
        p["experts_down"]))
    return acc


def ffn(model: dict, share: dict, p: dict, x):
    """``FFN_l`` of a layer's normed input ``x`` ``(tokens, hidden)``."""
    if "ffn_gate" in p:
        return swiglu(x, p["ffn_gate"], p["ffn_up"], p["ffn_down"])
    return (swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
            + routed_part(model, share, p, x))


def layer(model: dict, share: dict, index: int, p: dict, x,
          query_block: int):
    import jax

    b, t, d = x.shape
    kind = model["layer_types"][index]
    heads = int(model["num_attention_heads_per_layer"][index])
    kv, hd = int(model["num_key_value_heads"]), int(model["head_dim"])
    eps = float(model["rms_norm_eps"])
    xn = rms_norm(x, p["norm_attn"], eps)
    cos, sin, dim = rotary(model, kind, t)
    q = rotate((xn @ p["wq"]).reshape(b, t, heads, hd), cos, sin, dim)
    k = rotate((xn @ p["wk"]).reshape(b, t, kv, hd), cos, sin, dim)
    v = (xn @ p["wv"]).reshape(b, t, kv, hd)
    window = (int(model["sliding_window"])
              if kind == "sliding_attention" else None)
    o = attention(q, k, v, window, query_block)
    if model.get("gating"):
        o = o * jax.nn.sigmoid(xn @ p["w_gate"])[..., None]
    h = x + o.reshape(b, t, heads * hd) @ p["wo"]
    hn = rms_norm(h, p["norm_ffn"], eps).reshape(b * t, d)
    return h + ffn(model, share, p, hn).reshape(b, t, d), hn


def forward(params, ids, model: dict, share: dict, query_block: int = 256,
            remat: bool = False, taps=None):
    """Logits ``(batch, seq, vocab_held)`` of ``ids`` ``(batch, seq)``.
    ``remat`` recomputes each layer on the way back (memory only);
    ``taps``, a list, receives ``(layer's tensors, the expert layer's
    normed input)`` of every sparse layer."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        x = params["embed"][ids]
        for index, p in enumerate(params["layers"]):
            def run(p, x, index=index):
                return layer(model, share, index, p, x, query_block)

            x, hn = (jax.checkpoint(run) if remat else run)(p, x)
            if taps is not None and "router" in p:
                taps.append((p, hn))
        x = rms_norm(x, params["norm"], float(model["rms_norm_eps"]))
        return x @ params["head"].T


def loss(params, ids, targets, model: dict, share: dict,
         query_block: int = 256, remat: bool = False):
    """Mean cross-entropy of every position's logits against
    ``targets`` ``(batch, seq)``."""
    import jax
    import jax.numpy as jnp

    logits = forward(params, ids, model, share, query_block, remat)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def adamw(w, m, v, g, step, learning_rate, beta1, beta2, eps,
          weight_decay):
    """Loshchilov & Hutter 2019, algorithm 2, at step ``step`` (from 1):
    returns ``(w, m, v)`` in float32."""
    import jax.numpy as jnp

    w, m, v, g = (t.astype(jnp.float32) for t in (w, m, v, g))
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    m_hat = m / (1.0 - beta1 ** step)
    v_hat = v / (1.0 - beta2 ** step)
    w = w - learning_rate * (m_hat / (jnp.sqrt(v_hat) + eps)
                             + weight_decay * w)
    return w, m, v
