"""Plain reference: one chip's share of a ZAYA1 decoder — forward, loss and
the AdamW rule — in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No kernels, no mixed
precision, no sorting or grouping of rows: the convolutions are explicit
shifts, the experts a loop, the attention scores materialised (a block of
QUERY rows at a time: 8 heads x 32,768 x 32,768 x 4 B would be 34 GB) and
the loss taken a block of rows at a time (32,768 x 131,136 float32 logits
would be 17 GB).  It imports nothing of ``znicz_tpu``.

Source: https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json (the
keys this file reads are that file's; ``model`` below is that dictionary);
the family is described in Zyphra's CCA paper, arXiv:2510.04476, and the
ZAYA1 report, arXiv:2511.17127.  Readings that neither settles are marked
ASSUMED; the configuration's file lists them once, each with its why, for
program and reference alike.  Departures from the published description
are marked DEPARTURE at their line.

The equations.  ``S`` positions, ``x`` the residual stream ``(S, hidden)``,
``r_prev`` the router state of the layer before.  ``H`` query heads, ``K``
KV heads of size ``D`` (8, 2, 128); query head ``i`` reads KV head ``g(i)
= i // (H / K)``::

    1  x^ = RMSNorm(x; g_a);  q~ = x^ W_q (H x D),  k~ = x^ W_k (K x D),
       u = [q~, k~]  (H + K heads)
    2  c_t = w_0 * u_{t-1} + w_1 * u_t + b      (one pair of taps a channel,
       cca_time0 = 2; u_{-1} = 0);  for every head h
       d_t^h = A_0^h c_{t-1}^h + A_1^h c_t^h + b^h   (A: D x D, cca_time1 =
       2, one group a head).  ASSUMED: no activation between them, biases
       present
    3  m_i = (q~_i + k~_g(i)) / 2,  n_j = mean over i in group j of m_i,
       q_i = d_i + m_i,  k_j = d_{H+j} + n_j
    4  v = x^ W_v (K x D); the second half of the KV heads holds the
       PREVIOUS token's value (v_{-1} = 0).  ASSUMED
    5  q_i <- sqrt(D) q_i / |q_i|,  k_j <- exp(tau_j) sqrt(D) k_j / |k_j|
       (tau one learned number a KV head, 0 at the start: ASSUMED), then
       rotary on the first D * partial_rotary_factor dimensions of every
       head of q and k (rope_theta; rotate-half and norm before rotation:
       ASSUMED)
    6  o_i = softmax(causal(q_i k_g(i)^T / sqrt(D))) v_g(i);  a = [o_i] W_o
    7  x <- (x + b_r) * s_r + (a + b_h) * s_h    (learned, scales 1 and
       shifts 0 at the start; the family's ``scale_residual_merge``, which
       this model's HF-format config dropped: ASSUMED)
    8  x^ = RMSNorm(x; g_f);  r = x^ W_d + b_d (hidden -> router_hidden_size);
       every layer but the first: r <- r + gamma * r_prev (gamma learned, 1
       at the start: ASSUMED, the family's "EDA"); r goes on to the next
       layer.  s = W_3 gelu(W_2 gelu(W_1 RMSNorm(r; g_r) + b_1) + b_2)
       (exact gelu: ASSUMED), p = softmax(s), e = argmax(p + beta), weight
       p_e.  beta (``router_bias``) starts at zero, no gradient reaches
       it, and every train step moves it by that step's load
       (``balance_step``).  DEPARTURE, ASSUMED: the report balances with a
       controller of its own that no source here settles; the rule taken
       moves beta_e by half of what would, the others held, leave expert e
       its even share of the step's tokens — a fixed step of 1e-3, as in
       the rule the family's descends from, is several times the whole
       spread of p at seeded weights
    9  y = p_e (silu(x^ G_e) * x^ U_e) D_e where expert e is held, else 0
       (the other chip's part: left out here and in the system alike);
       x <- merge(x, y) as in 7
    10 logits = RMSNorm(x; g) E^T over the held ids, E the embedding
       (tie_word_embeddings); mean cross-entropy over the positions

DEPARTURE: the family's depth-skipping expert (``zaya_use_mod`` in its
other configurations; "residual-scaled MoD") is left out: this model's
config has 16 router outputs and no key for it, and whether a skipped
token gets zero or a scaled copy of its input is settled by no source.

The share (``share``: ``layers``, ``experts_held``, ``first_expert``,
``vocab_held``): this chip holds layers ``0 .. layers - 1``, the experts
``first_expert .. first_expert + experts_held - 1`` of every layer, and
ids ``0 .. vocab_held - 1``.  The router scores and chooses over all
``num_experts``.

Parameter layout, this file's own statement: ``{"embed": (vocab_held,
hidden), "layers": [per layer a dict], "norm": (hidden,)}``; a layer holds
``norm_attn``, ``wq`` ``(hidden, H D)``, ``wk``, ``wv`` ``(hidden, K D)``,
``mix_w`` ``(2, H + K, D)``, ``mix_b`` ``(H + K, D)``, ``mix_heads`` ``(2,
H + K, D, D)``, ``mix_heads_b`` ``(H + K, D)``, ``temp`` ``(K,)``, ``wo``
``(H D, hidden)``, ``keep_scale_attn``, ``keep_shift_attn``,
``new_scale_attn``, ``new_shift_attn`` (s_r, b_r, s_h, b_h of 7),
``norm_ffn``, ``router_down`` ``(hidden, R)``, ``router_down_b``,
``router_gamma`` (every layer but the first), ``router_norm``,
``router_w1``, ``router_b1``, ``router_w2``, ``router_b2`` ``(R, R)`` /
``(R,)``, ``router_w3`` ``(R, num_experts)``, ``router_bias``
``(num_experts,)``, ``experts_gate``,
``experts_up`` ``(experts_held, hidden, width)``, ``experts_down``
``(experts_held, width, hidden)`` and the four merge vectors ``*_ffn``.
In a tap axis the LAST index is the current position's.
"""

from __future__ import annotations

import math

MERGE = tuple(f"{kind}_{part}" for part in ("attn", "ffn")
              for kind in ("keep_scale", "keep_shift", "new_scale",
                           "new_shift"))
#: the groups a comparison reports by: group -> the tensors in it
GROUPS = {
    "tied": ("embed",),
    "attention": ("wq", "wk", "wv", "wo"),
    "mixing": ("mix_w", "mix_b", "mix_heads", "mix_heads_b", "temp"),
    "scales": MERGE,
    "router": ("router_down", "router_down_b", "router_gamma",
               "router_norm", "router_w1", "router_b1", "router_w2",
               "router_b2", "router_w3"),
    "experts": ("experts_gate", "experts_up", "experts_down"),
    "norms": ("norm_attn", "norm_ffn", "norm"),
    # moved by the load, not by AdamW: compared with ``balance_step``
    "balance": ("router_bias",),
}
#: tensors that no gradient trains and ``balance_step`` moves
LOAD_DRIVEN = ("router_bias",)
#: tensors AdamW's decay skips: norms, scales, shifts, temperatures,
#: convolution and router biases, gamma
NO_DECAY = ("norm_attn", "norm_ffn", "norm", "router_norm", "temp",
            "mix_b", "mix_heads_b", "router_down_b", "router_b1",
            "router_b2", "router_gamma", "router_bias") + MERGE


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def previous(x):
    """``y[:, t] = x[:, t - 1]``, zero at ``t = 0``: ``x`` is ``(batch,
    seq, ...)``."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def rotary(model: dict, kind: str, length: int):
    """``(cos, sin, rotary_dim)`` of ``rope_parameters[kind]``; cos and
    sin are ``(length, rotary_dim / 2)``, one column a frequency."""
    import numpy as np

    cfg = model["rope_parameters"][kind]
    dim = int(model["head_dim"] * cfg.get("partial_rotary_factor", 1))
    freq = float(cfg["rope_theta"]) ** -(
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = np.outer(np.arange(length, dtype=np.float64), freq)
    return (np.cos(angle).astype(np.float32),
            np.sin(angle).astype(np.float32), dim)


def rotate(x, cos, sin, dim):
    """Rotate-half: the pair ``(x[i], x[i + dim/2])`` turns by frequency
    ``i``; dimensions past ``dim`` pass through."""
    import jax.numpy as jnp

    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], axis=-1)


def attention(q, k, v, query_block: int):
    """Causal softmax attention with materialised masked scores,
    ``query_block`` queries at a time against every key."""
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
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], s,
                      -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(0, t, query_block))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, heads, d)


def mixed(model: dict, p: dict, xn, length: int):
    """Equations 1-5: ``(q, k, v)`` for the core from the normed input
    ``xn`` ``(batch, seq, hidden)``."""
    import jax.numpy as jnp

    b, t, _ = xn.shape
    h, kv = int(model["num_attention_heads"]), int(
        model["num_key_value_heads"])
    hd = int(model["head_dim"])
    assert int(model["cca_time0"]) == 2 == int(model["cca_time1"])
    q0 = (xn @ p["wq"]).reshape(b, t, h, hd)
    k0 = (xn @ p["wk"]).reshape(b, t, kv, hd)
    u = jnp.concatenate([q0, k0], axis=2)
    c = p["mix_w"][0] * previous(u) + p["mix_w"][1] * u + p["mix_b"]
    d = (jnp.einsum("bthc,hcd->bthd", previous(c), p["mix_heads"][0])
         + jnp.einsum("bthc,hcd->bthd", c, p["mix_heads"][1])
         + p["mix_heads_b"])
    group = h // kv
    m = (q0 + jnp.repeat(k0, group, axis=2)) / 2
    n = m.reshape(b, t, kv, group, hd).mean(axis=3)
    q, k = d[:, :, :h] + m, d[:, :, h:] + n
    v = (xn @ p["wv"]).reshape(b, t, kv, hd)
    v = jnp.concatenate([v[:, :, :kv // 2], previous(v[:, :, kv // 2:])],
                        axis=2)

    def unit(x):
        return math.sqrt(hd) * x / jnp.sqrt(
            jnp.sum(x * x, axis=-1, keepdims=True))

    q = unit(q)
    k = jnp.exp(p["temp"])[:, None] * unit(k)
    cos, sin, dim = rotary(model, model["layer_types"][0], length)
    return rotate(q, cos, sin, dim), rotate(k, cos, sin, dim), v


def merge(p: dict, part: str, x, new):
    """Equation 7."""
    return ((x + p[f"keep_shift_{part}"]) * p[f"keep_scale_{part}"]
            + (new + p[f"new_shift_{part}"]) * p[f"new_scale_{part}"])


def router_state(p: dict, xn, r_prev):
    """Equation 8's state: ``xn`` is ``(tokens, hidden)``."""
    r = xn @ p["router_down"] + p["router_down_b"]
    return r if r_prev is None else r + p["router_gamma"] * r_prev


def probabilities(model: dict, p: dict, r):
    """Equation 8's ``p`` ``(tokens, num_experts)`` from the router's
    state ``r``."""
    import jax

    rn = rms_norm(r, p["router_norm"], float(model["rms_norm_eps"]))
    hid = jax.nn.gelu(rn @ p["router_w1"] + p["router_b1"],
                      approximate=False)
    hid = jax.nn.gelu(hid @ p["router_w2"] + p["router_b2"],
                      approximate=False)
    return jax.nn.softmax(hid @ p["router_w3"], axis=-1)


def routing(model: dict, p: dict, r):
    """``(expert, weight)`` ``(tokens,)`` over all the model's experts
    from the router's state ``r``: the expert with the largest ``p +
    beta``, its probability the weight."""
    import jax
    import jax.numpy as jnp

    assert int(model["num_experts_per_tok"]) == 1
    prob = probabilities(model, p, r)
    expert = jnp.argmax(jax.lax.stop_gradient(prob) + p["router_bias"],
                        axis=-1)
    return expert, jnp.take_along_axis(prob, expert[:, None], axis=-1)[:, 0]


def balance_step(model: dict, p: dict, r):
    """What a train step adds to ``router_bias`` ``(num_experts,)``, one
    expert at a time: the expert's margin at a token is its ``p + beta``
    less the best of the OTHER experts' there; the bias that would leave
    it ``tokens / num_experts`` tokens, the others held, is lower by that
    many-th largest margin; half of that, then all centred."""
    import jax.numpy as jnp

    a = probabilities(model, p, r) + p["router_bias"]
    tokens, experts = a.shape
    share = max(tokens // experts, 1)
    steps = []
    for e in range(experts):
        others = jnp.max(jnp.delete(a, e, axis=1), axis=1)
        steps.append(-0.5 * jnp.sort(a[:, e] - others)[tokens - share])
    steps = jnp.stack(steps)
    return steps - jnp.mean(steps)


def routed_part(model: dict, share: dict, p: dict, x, r):
    """Equation 9: a loop over the held experts, each applied to every
    token and weighted by the token's weight for it (0 where the token
    chose another)."""
    import jax
    import jax.numpy as jnp

    expert, weight = routing(model, p, r)

    def one(x, e, w_gate, w_up, w_down):
        w = jnp.where(expert == e, weight, 0.0)
        return w[:, None] * ((jax.nn.silu(x @ w_gate) * (x @ w_up))
                             @ w_down)

    def step(acc, held):
        return acc + jax.checkpoint(one)(x, *held), None

    held = int(share["experts_held"])
    acc, _ = jax.lax.scan(step, jnp.zeros_like(x), (
        int(share["first_expert"]) + jnp.arange(held), p["experts_gate"],
        p["experts_up"], p["experts_down"]))
    return acc


def layer(model: dict, share: dict, p: dict, x, r_prev, query_block: int):
    """``(x, r)`` after one layer."""
    b, t, d = x.shape
    eps = float(model["rms_norm_eps"])
    q, k, v = mixed(model, p, rms_norm(x, p["norm_attn"], eps), t)
    o = attention(q, k, v, query_block)
    x = merge(p, "attn", x, o.reshape(b, t, -1) @ p["wo"])
    xn = rms_norm(x, p["norm_ffn"], eps).reshape(b * t, d)
    r = router_state(p, xn, r_prev)
    y = routed_part(model, share, p, xn, r).reshape(b, t, d)
    return merge(p, "ffn", x, y), r


def final_hidden(params, ids, model: dict, share: dict,
                 query_block: int = 256, remat: bool = False, taps=None):
    """The residual stream ``(batch, seq, hidden)`` after the last layer
    held, before the final norm.  ``remat`` recomputes each layer on the
    way back (memory only); ``taps``, a list, receives ``(layer's tensors,
    the router's state)`` of every layer."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        x, r = params["embed"][ids], None
        for p in params["layers"]:
            def run(p, x, r):
                return layer(model, share, p, x, r, query_block)

            x, r = (jax.checkpoint(run) if remat else run)(p, x, r)
            if taps is not None:
                taps.append((p, r))
        return x


def logits_of(params, x, model: dict):
    """Equation 10's logits of rows ``x`` ``(..., hidden)`` of the
    residual stream."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return rms_norm(x.astype(jnp.float32),
                        params["norm"].astype(jnp.float32),
                        float(model["rms_norm_eps"])) @ params[
                            "embed"].astype(jnp.float32).T


def forward(params, ids, model: dict, share: dict, query_block: int = 256,
            remat: bool = False, taps=None):
    """Logits ``(batch, seq, vocab_held)`` of ``ids`` ``(batch, seq)``,
    whole: for sizes at which they fit."""
    return logits_of(params, final_hidden(params, ids, model, share,
                                          query_block, remat, taps), model)


def loss(params, ids, targets, model: dict, share: dict,
         query_block: int = 256, remat: bool = False,
         loss_block: int = 1024):
    """Mean cross-entropy of every position's logits against ``targets``
    ``(batch, seq)``, ``loss_block`` rows of logits at a time."""
    import jax
    import jax.numpy as jnp

    x = final_hidden(params, ids, model, share, query_block, remat)
    x = x.reshape(-1, x.shape[-1])
    loss_block = min(loss_block, x.shape[0])
    assert x.shape[0] % loss_block == 0, (x.shape, loss_block)

    def block(xs):
        rows, want = xs
        logits = logits_of(params, rows, model)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, want[:, None], axis=-1)[:, 0]
        return jnp.sum(logz - picked)

    sums = jax.lax.map(jax.checkpoint(block), (
        x.reshape(-1, loss_block, x.shape[-1]),
        targets.reshape(-1, loss_block)))
    return jnp.sum(sums) / x.shape[0]


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


def warmup_rate(step: int, learning_rate: float, steps: int) -> float:
    """The rate of train step ``step`` (from 0) under the job's linear
    warm-up: ``learning_rate * (step + 1) / steps`` while ``step <
    steps``."""
    return learning_rate * min(1.0, (step + 1) / steps)
