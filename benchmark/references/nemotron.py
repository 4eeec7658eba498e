"""Plain reference: one chip's share of the tower that a ``nemotron_h``
``config.json`` states — forward, loss, the selection bias's rule and the
AdamW rule — in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No kernels, no mixed
precision, no chunks, no sorting or grouping of rows: the state-space
layer is its RECURRENCE (a ``lax.scan`` over the positions, one state a
head), the convolution explicit shifts, the experts a loop over the held
ones, the attention scores materialised (a block of QUERY rows at a
time).  It imports nothing of ``znicz_tpu``.

Source:
https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json
(the keys this file reads are that file's; ``model`` below is that
dictionary with the configuration file's ``assumed_keys`` laid over it).
Readings that it does not settle are marked ASSUMED; the configuration's
file lists them once, each with its why, for program and reference alike.

The equations.  ``x`` the residual stream ``(S, hidden)``; layer ``l`` is
``x <- x + Part_l(RMSNorm(x; g_l))`` with ONE part, named by character
``l`` of ``hybrid_override_pattern``; eps ``layer_norm_epsilon``; no
biases but the convolution's.

``M`` (Mamba-2; ``H = mamba_num_heads`` heads of ``P = mamba_head_dim``,
``G = n_groups`` groups of state ``N = ssm_state_size``, ``K =
conv_kernel``; head ``h`` reads group ``h // (H / G)``)::

    1  [z | u | dt~] = x^ W_in          (H P | H P + 2 G N | H columns)
    2  u_t <- silu(sum_k w_k * u_{t-(K-1)+k} + b)   (one set of taps a
       channel, u before the row 0);  u = [x | B | C]
    3  dt = softplus(dt~ + dt_bias)   (time_step_limit (0, inf): no clamp),
       A_h = -exp(A_log_h)
    4  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T   (P x N a head, S = 0
       before the row);  y_t = S_t C_t + D_h x_t
    5  y <- RMSNorm over each of G runs of H P / G channels (y * silu(z))
       * g_n;  part = y W_out

``E`` (``n_routed_experts`` experts of ``moe_intermediate_size``,
``num_experts_per_tok`` a token)::

    6  s = sigmoid(x^ W_r) over ALL the experts; the num_experts_per_tok
       with the largest s + beta chosen (n_group 1, topk_group 1: no
       group limit); weight of a chosen e: routed_scaling_factor * s_e /
       sum of the chosen s (norm_topk_prob)
    7  part = sum over the chosen e HELD here of weight_e relu(x^ U_e)^2
       D_e  (mlp_hidden_act relu2: two matrices, no gate; the other
       chips' experts' part is left out here and in the system alike)
       + relu(x^ U_s)^2 D_s  (the shared expert, every token)
    8  beta (``router_bias``, present where the dictionary says
       ``router_selection_bias``) starts at zero, no gradient reaches it,
       and every train step moves it by that step's load
       (``balance_step``).  DEPARTURE, ASSUMED: the router whose keys
       these are moves its bias by a fixed ``1e-3 sign(mean load -
       load_e)`` (arXiv:2408.15664; arXiv:2412.19437 section 2.1.2); on
       this chip's cut that step is too small to steady the held experts'
       load within the window and the cell's rate followed the seed, so
       the rule taken is the one of ZAYA's cell: beta_e moves by half of
       what would, the others held, leave expert e its even share of the
       step's (token, slot) pairs

``*``: grouped-query attention, ``num_attention_heads`` query heads over
``num_key_value_heads`` KV heads of ``head_dim``, causal, scores ``q k /
sqrt(head_dim)``, NO positional rotation (ASSUMED: ``attention_positions:
none``; ``rope_theta`` is read by nothing); part ``= [o_h] W_o``.

Then ``logits = RMSNorm(x; g) W_head^T`` over the held ids (the head is
not tied), mean cross-entropy over the positions.

DEPARTURE: the published model's SECOND tower (a denoiser with adaLN
modulation and cross-tower conditioning) and its generation by diffusion
over blocks are left out; ``config.json`` states one tower and this is it.

The share (``share``: ``layers``, ``experts_held``, ``first_expert``,
``vocab_held``): this chip holds layers ``0 .. layers - 1``, the experts
``first_expert .. first_expert + experts_held - 1`` of every ``E`` layer,
and ids ``0 .. vocab_held - 1``.

Parameter layout, this file's own statement: ``{"embed": (vocab_held,
hidden), "layers": [per layer a dict], "norm": (hidden,), "head":
(vocab_held, hidden)}``.  An ``M`` layer holds ``norm_ssm``, ``ssm_in``
``(hidden, 2 H P + 2 G N + H)``, ``ssm_conv_w`` ``(K, H P + 2 G N)`` (the
LAST tap is the current position's), ``ssm_conv_b``, ``ssm_dt_bias``,
``ssm_a_log``, ``ssm_d`` ``(H,)``, ``ssm_norm`` ``(H P,)``, ``ssm_out``
``(H P, hidden)``; an ``E`` layer ``norm_ffn``, ``router`` ``(hidden,
n_routed_experts)``, ``router_bias`` ``(n_routed_experts,)``,
``shared_up`` ``(hidden, shared width)``, ``shared_down``,
``experts_up`` ``(experts_held, hidden, width)``, ``experts_down``
``(experts_held, width, hidden)``; a ``*`` layer ``norm_attn``, ``wq``
``(hidden, heads x head_dim)``, ``wk``, ``wv``, ``wo``.
"""

from __future__ import annotations

import math

#: the groups a comparison reports by: group -> the tensors in it
GROUPS = {
    "ssm_proj": ("ssm_in", "ssm_out"),
    "ssm_scan": ("ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
                 "ssm_d", "ssm_norm"),
    "attention": ("wq", "wk", "wv", "wo"),
    "experts": ("experts_up", "experts_down"),
    "shared": ("shared_up", "shared_down"),
    "router": ("router",),
    # moved by the load, not by AdamW: compared with ``balance_step``
    "balance": ("router_bias",),
    "norms": ("norm_ssm", "norm_attn", "norm_ffn", "norm"),
    "embedding": ("embed",),
    "head": ("head",),
}
#: tensors that no gradient trains and ``balance_step`` moves
LOAD_DRIVEN = ("router_bias",)
#: tensors AdamW's decay skips: norms, biases, A_log, D, dt_bias, the
#: router
NO_DECAY = GROUPS["norms"] + ("ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
                              "ssm_d", "ssm_norm", "router", "router_bias")


def rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def previous(x, by: int = 1):
    """``y[:, t] = x[:, t - by]``, zero before the row: ``x`` is ``(batch,
    seq, ...)``."""
    import jax.numpy as jnp

    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :by]), x[:, :-by]], axis=1)


def convolved(u, w, b):
    """Equation 2's sum: ``u`` ``(batch, seq, channels)``, ``w`` ``(K,
    channels)`` with the last tap the current position's."""
    taps = w.shape[0]
    return sum(w[k] * previous(u, taps - 1 - k) for k in range(taps)) + b


def recurrence(x, dt, a, b, c, d, block: int = 0):
    """Equation 4, position by position: ``x`` ``(batch, seq, H, P)``,
    ``dt`` ``(batch, seq, H)``, ``a`` and ``d`` ``(H,)``, ``b`` / ``c``
    ``(batch, seq, G, N)``.  ``block`` walks the positions in blocks of
    that many and keeps only the blocks' entry states for a backward pass
    (memory only: the arithmetic is the same step after step)."""
    import jax
    import jax.numpy as jnp

    bsz, t, heads, p = x.shape
    r = heads // b.shape[2]
    b, c = jnp.repeat(b, r, axis=2), jnp.repeat(c, r, axis=2)

    def step(state, xs):
        xt, dtt, bt, ct = xs
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + d[:, None] * xt

    def walk(state, xs):
        return jax.lax.scan(step, state, xs)

    xs = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    state = jnp.zeros((bsz, heads, p, b.shape[3]), x.dtype)
    if block and t > block:
        assert t % block == 0, (t, block)
        xs = tuple(v.reshape((t // block, block) + v.shape[1:]) for v in xs)
        _, y = jax.lax.scan(jax.checkpoint(walk), state, xs)
        y = y.reshape((t,) + y.shape[2:])
    else:
        _, y = walk(state, xs)
    return jnp.moveaxis(y, 0, 1)


def mamba(model: dict, p: dict, xn, block: int = 0):
    """Equations 1-5 on the normed input ``xn`` ``(batch, seq, hidden)``."""
    import jax
    import jax.numpy as jnp

    bsz, t, _ = xn.shape
    heads, hd = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    groups, n = int(model["n_groups"]), int(model["ssm_state_size"])
    inner, bc = heads * hd, groups * n
    proj = xn @ p["ssm_in"]
    z, u, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * bc],
                proj[..., 2 * inner + 2 * bc:])
    u = jax.nn.silu(convolved(u, p["ssm_conv_w"], p["ssm_conv_b"]))
    x = u[..., :inner].reshape(bsz, t, heads, hd)
    b = u[..., inner:inner + bc].reshape(bsz, t, groups, n)
    c = u[..., inner + bc:].reshape(bsz, t, groups, n)
    y = recurrence(x, jax.nn.softplus(dt + p["ssm_dt_bias"]),
                   -jnp.exp(p["ssm_a_log"]), b, c, p["ssm_d"], block)
    g = (y.reshape(bsz, t, inner) * jax.nn.silu(z)).reshape(
        bsz, t, groups, inner // groups)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                     + float(model["layer_norm_epsilon"]))
    return (g.reshape(bsz, t, inner) * p["ssm_norm"]) @ p["ssm_out"]


def attention(q, k, v, query_block: int):
    """Causal softmax attention with materialised masked scores,
    ``query_block`` queries at a time against every key; no rotation."""
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


def attended(model: dict, p: dict, xn, query_block: int):
    b, t, _ = xn.shape
    h, kv = int(model["num_attention_heads"]), int(
        model["num_key_value_heads"])
    hd = int(model["head_dim"])
    assert model.get("attention_positions") == "none", "ASSUMED: no rotation"
    o = attention((xn @ p["wq"]).reshape(b, t, h, hd),
                  (xn @ p["wk"]).reshape(b, t, kv, hd),
                  (xn @ p["wv"]).reshape(b, t, kv, hd), query_block)
    return o.reshape(b, t, h * hd) @ p["wo"]


def scores(p: dict, xn):
    """Equation 6's ``s`` ``(tokens, n_routed_experts)``."""
    import jax

    return jax.nn.sigmoid(xn @ p["router"])


def routing(model: dict, p: dict, xn):
    """``(experts, weights)``, each ``(tokens, num_experts_per_tok)``,
    over all the model's experts (equation 6)."""
    import jax
    import jax.numpy as jnp

    s = scores(p, xn)
    experts = jax.lax.top_k(jax.lax.stop_gradient(s) + p["router_bias"],
                            int(model["num_experts_per_tok"]))[1]
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    return experts, (float(model["routed_scaling_factor"]) * chosen
                     / jnp.sum(chosen, axis=-1, keepdims=True))


def balance_step(model: dict, p: dict, xn):
    """Equation 8: what a train step adds to ``router_bias``
    ``(n_routed_experts,)``, one expert at a time: the expert's margin at
    a token is its ``s + beta`` less the score that decides whether it is
    chosen there, the ``num_experts_per_tok``-th best of the OTHER
    experts'; the bias that would leave it ``tokens x num_experts_per_tok
    / n_routed_experts`` pairs, the others held, is lower by that many-th
    largest margin; half of that, then all centred."""
    import jax
    import jax.numpy as jnp

    a = scores(p, xn) + p["router_bias"]
    tokens, experts = a.shape
    top_k = int(model["num_experts_per_tok"])
    share = max(tokens * top_k // experts, 1)

    def one(e):
        others = jnp.where(jnp.arange(experts) == e, -jnp.inf, a)
        decides = jax.lax.top_k(others, top_k)[0][:, -1]
        return -0.5 * jnp.sort(a[:, e] - decides)[tokens - share]

    steps = jax.lax.map(one, jnp.arange(experts))
    return steps - jnp.mean(steps)


def relu2(x, w_up, w_down):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x @ w_up, 0.0)) @ w_down


def routed_part(model: dict, share: dict, p: dict, x):
    """Equation 7's routed sum: a loop over the held experts, each applied
    to every token and weighted by the token's weight for it (0 where the
    token did not choose it)."""
    import jax
    import jax.numpy as jnp

    experts, weights = routing(model, p, x)

    def one(x, e, w_up, w_down):
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return w[:, None] * relu2(x, w_up, w_down)

    def step(acc, held):
        return acc + jax.checkpoint(one)(x, *held), None

    held = int(share["experts_held"])
    acc, _ = jax.lax.scan(step, jnp.zeros_like(x), (
        int(share["first_expert"]) + jnp.arange(held), p["experts_up"],
        p["experts_down"]))
    return acc


def experts_layer(model: dict, share: dict, p: dict, xn, shared=True):
    """Equation 7 on the normed rows ``xn`` ``(tokens, hidden)``: the held
    experts' part and, with ``shared``, the shared expert's."""
    y = routed_part(model, share, p, xn)
    return y + relu2(xn, p["shared_up"], p["shared_down"]) if shared else y


def layer(model: dict, share: dict, kind: str, p: dict, x,
          query_block: int, block: int = 0):
    """``(x after the layer, the normed input)``."""
    b, t, d = x.shape
    eps = float(model["layer_norm_epsilon"])
    if kind == "M":
        xn = rms_norm(x, p["norm_ssm"], eps)
        return x + mamba(model, p, xn, block), xn
    if kind == "*":
        xn = rms_norm(x, p["norm_attn"], eps)
        return x + attended(model, p, xn, query_block), xn
    assert kind == "E", kind
    xn = rms_norm(x, p["norm_ffn"], eps)
    return x + experts_layer(model, share, p, xn.reshape(b * t, d)).reshape(
        b, t, d), xn.reshape(b * t, d)


def final_hidden(params, ids, model: dict, share: dict,
                 query_block: int = 256, remat: bool = False, taps=None):
    """The residual stream ``(batch, seq, hidden)`` after the last layer
    held, before the final norm.  ``remat`` recomputes each layer on the
    way back and walks the recurrence in blocks of 128 positions (memory
    only); ``taps``, a list, receives ``(layer's tensors, the router's
    normed input)`` of every ``E`` layer."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        x = params["embed"][ids]
        for kind, p in zip(model["hybrid_override_pattern"],
                           params["layers"]):
            def run(p, x, kind=kind):
                return layer(model, share, kind, p, x, query_block,
                             128 if remat else 0)

            x, xn = (jax.checkpoint(run) if remat else run)(p, x)
            if taps is not None and kind == "E":
                taps.append((p, xn))
        return x


def logits_of(params, x, model: dict):
    """The logits of rows ``x`` ``(..., hidden)`` of the residual
    stream."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return rms_norm(x.astype(jnp.float32),
                        params["norm"].astype(jnp.float32),
                        float(model["layer_norm_epsilon"])) @ params[
                            "head"].astype(jnp.float32).T


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
