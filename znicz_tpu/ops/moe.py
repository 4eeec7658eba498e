"""The expert layer's ops: a router over all the experts of the model (a
linear map with sigmoid scores, ``route``, or the same chosen by score
plus a selection bias, ``route_balanced``; or a small MLP on a state that
one layer hands the next, ``router_state`` / ``route_mlp``; a selection
bias is moved by each step's load, ``balance_step``), and the part of the
layer's result that the experts HELD here give — gated (``swiglu``) or
two matrices around a squared ReLU (``relu2``).

An expert-parallel deployment spreads a layer's routed experts over chips.
Each chip routes its tokens over all of them (the router keeps its
published width), normalises the weights over the chosen ones wherever
they live, and computes its own experts' part for the rows routed to them;
the exchange between chips adds the parts up.  On one chip there is no
exchange: the partial sum is what goes on.

No row is ever dropped: the ``(token, slot)`` pairs are sorted by expert
and go through ``jax.lax.ragged_dot`` with the per-expert row counts (on
the TPU a grouped matrix product whose work follows the rows that came,
not the buffer's size).  The buffer holds every pair, so any imbalance —
every token choosing the same experts — fits by construction; there is no
capacity factor.
"""

from __future__ import annotations


def route(x, w_router, top_k: int, scale: float):
    """``(experts, weights)``, each ``(tokens, top_k)``: the ``top_k``
    largest of ``sigmoid(x @ w_router)`` over ALL the model's experts and
    ``scale * s_e / sum of the chosen s``.  Scores in float32."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.dot(x, w_router,
                               preferred_element_type=jnp.float32))
    top, experts = jax.lax.top_k(s, top_k)
    return experts, scale * top / jnp.sum(top, axis=-1, keepdims=True)


def route_balanced(x, w_router, bias, top_k: int, scale: float):
    """``(experts, weights, move)``: ``route`` with the choice made by ``s
    + bias`` (``bias`` ``(experts,)``; no gradient reaches it) and the
    weights still ``scale * s_e / sum of the chosen s`` from the scores
    alone, and the move that this step's load asks of the bias
    (``balance_step``)."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.dot(x, w_router,
                               preferred_element_type=jnp.float32))
    biased = jax.lax.stop_gradient(s) + bias.astype(jnp.float32)
    experts = jax.lax.top_k(biased, top_k)[1]
    top = jnp.take_along_axis(s, experts, axis=-1)
    return (experts, scale * top / jnp.sum(top, axis=-1, keepdims=True),
            balance_step(biased, top_k))


def router_state(x, w_down, b_down, gamma=None, carried=None):
    """The router's state of one layer: ``x @ w_down + b_down`` (the
    hidden stream DOWN to the router's width), plus ``gamma *`` the state
    the layer before handed on, where there is one.  The result is both
    what this layer's router reads and what the next layer receives."""
    r = x @ w_down + b_down
    if carried is not None:
        r = r + gamma * carried
    return r


def route_mlp(r, norm, w1, b1, w2, b2, w3, bias, top_k: int, eps: float):
    """``(experts, weights, move)`` from the router's state ``r``
    ``(tokens, width)``: scores ``w3 . gelu(w2 . gelu(w1 . RMSNorm(r) +
    b1) + b2)`` over ALL the model's experts, a softmax ``p`` in float32,
    the ``top_k`` experts with the largest ``p + bias`` chosen
    (``experts`` ``(tokens, top_k)``), each one's probability its weight
    (no renormalisation over the chosen; no gradient reaches ``bias``
    ``(experts,)``), and the move that this step's load asks of the bias
    (``balance_step``)."""
    import jax
    import jax.numpy as jnp

    r32 = r.astype(jnp.float32)
    rn = (r32 * jax.lax.rsqrt(jnp.mean(jnp.square(r32), axis=-1,
                                       keepdims=True) + eps)
          * norm.astype(jnp.float32)).astype(r.dtype)
    h = jax.nn.gelu(rn @ w1 + b1, approximate=False)
    h = jax.nn.gelu(h @ w2 + b2, approximate=False)
    p = jax.nn.softmax(jnp.dot(h, w3, preferred_element_type=jnp.float32),
                       axis=-1)
    biased = jax.lax.stop_gradient(p) + bias.astype(jnp.float32)
    experts = jax.lax.top_k(biased, top_k)[1]
    return (experts, jnp.take_along_axis(p, experts, axis=-1),
            balance_step(biased, top_k))


#: how much of the move that would even one expert's load, the others'
#: biases held, a step makes: every expert moves at once, and where two
#: trade tokens both moves count, so half is the whole for a pair
BALANCE_DAMPING = 0.5


def balance_step(biased, top_k: int):
    """The move ``(experts,)`` float32 of a selection bias that this
    step's load asks for, from the scores the choice was made by
    (``biased`` ``(tokens, experts)``: probability plus bias).  Expert
    ``e``'s margin at a token is its score less the best score among the
    others that decides whether ``e`` is chosen there (the ``top_k``-th
    best of the others).  Lowering ``e``'s bias by the ``tokens * top_k /
    experts``-th largest margin would, the others held, leave it exactly
    its even share of the rows; the step is ``BALANCE_DAMPING`` of that,
    centred (a common shift chooses nothing).  Counts alone would not say
    how FAR to move: the scores' scale is the router's, 1e-4 at seeded
    weights and 1e-1 in a trained one, and a fixed step is too coarse for
    the first or too slow for the second."""
    import jax
    import jax.numpy as jnp

    tokens, experts = biased.shape
    top = jax.lax.top_k(biased, top_k + 1)[0]
    chosen = biased >= top[:, top_k - 1:top_k]
    margin = biased - jnp.where(chosen, top[:, top_k:],
                                top[:, top_k - 1:top_k])
    share = max(tokens * top_k // experts, 1)
    even = jnp.sort(margin.T, axis=-1)[:, tokens - share]
    step = -BALANCE_DAMPING * even
    return step - jnp.mean(step)


def dispatch(experts, first_expert: int, experts_held: int):
    """Sort the ``(token, slot)`` pairs by the expert held here.

    Returns ``(order, inverse, key, rows_by_expert)``: ``order`` lists
    the flat pairs, those of held expert 0 first, then 1, ..., pairs of
    experts that live elsewhere last; ``inverse`` is where each flat pair
    went; ``key`` is each flat pair's held expert (``experts_held`` where
    it lives elsewhere); ``rows_by_expert`` ``(experts_held,)`` int32
    counts the rows of each."""
    import jax.numpy as jnp

    local = experts.reshape(-1) - first_expert
    key = jnp.where((local >= 0) & (local < experts_held), local,
                    experts_held)
    order = jnp.argsort(key, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    rows = jnp.zeros((experts_held + 1,), jnp.int32).at[key].add(1)
    return order, inverse, key, rows[:experts_held]


def rows_outside_groups(key, inverse, group_sizes):
    """How many pairs of a held expert the grouped product is NOT handed
    in that expert's group: pair ``i`` stands at row ``inverse[i]``, and
    the product takes rows ``[start_e, start_e + group_sizes[e])`` for
    expert ``e``.  0 where the sizes are the counts of the sort; a
    capacity that clips a group's size leaves the rows past it outside
    (they would be computed with the next expert's weights, or not at
    all) and this counts them.  What the product then writes is not seen
    here: the comparison with a dense reference sees that."""
    import jax.numpy as jnp

    held = key < group_sizes.shape[0]
    start = jnp.cumsum(group_sizes) - group_sizes
    e = jnp.minimum(key, group_sizes.shape[0] - 1)
    inside = (inverse >= start[e]) & (inverse < start[e] + group_sizes[e])
    return jnp.sum(held & ~inside, dtype=jnp.int32)


def _pairs_of_tokens(top_k: int):
    """``rows[i] = x[order[i] // top_k]``: every token's row once for each
    of its ``top_k`` slots, in ``order``.  The transpose is written as the
    gather it is (``inverse`` says where each pair went; a token's
    cotangent is the sum over its slots), where autodiff would scatter-add
    one row at a time."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def spread(x, order, inverse):
        return jnp.take(x, order // top_k, axis=0)

    def fwd(x, order, inverse):
        return spread(x, order, inverse), inverse

    def bwd(inverse, ct):
        back = jnp.take(ct, inverse, axis=0)
        return (jnp.sum(back.reshape(-1, top_k, ct.shape[-1]), axis=1),
                None, None)

    spread.defvjp(fwd, bwd)
    return spread


def _permute():
    """``x[perm]`` for a permutation whose inverse is known: the transpose
    is ``ct[inverse]``, a gather again."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def permute(x, perm, inverse):
        return jnp.take(x, perm, axis=0)

    def fwd(x, perm, inverse):
        return permute(x, perm, inverse), inverse

    def bwd(inverse, ct):
        return jnp.take(ct, inverse, axis=0), None, None

    permute.defvjp(fwd, bwd)
    return permute


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x W_g) * x W_u) W_d``."""
    import jax

    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def relu2(x, w_up, w_down):
    """``relu(x W_u)^2 W_d``: two matrices, no gate."""
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def held_experts(x, experts, weights, w_gate, w_up, w_down,
                 first_expert: int):
    """The held experts' part of the layer's result, ``(tokens, hidden)``,
    and the counters ``rows_by_expert`` and ``rows_dropped``.

    ``x`` is ``(tokens, hidden)``; ``experts``/``weights`` come from
    ``route``; ``w_gate``/``w_up`` are ``(experts_held, hidden, width)``
    and ``w_down`` ``(experts_held, width, hidden)``, expert
    ``first_expert + e`` at index ``e``; ``w_gate`` ``None``: experts of
    two matrices around a squared ReLU (``relu2``).  The weight
    multiplies the expert's output."""
    import jax
    import jax.numpy as jnp
    from jax.lax import ragged_dot

    tokens, top_k = experts.shape
    n_held = w_up.shape[0]
    with jax.named_scope("dispatch"):
        order, inverse, key, rows = dispatch(experts, first_expert, n_held)
        rows_x = _pairs_of_tokens(top_k)(x, order, inverse)
    with jax.named_scope("experts"):
        # Rows past the counts belong to no group: the TPU's grouped
        # product does not write them, forward or backward, and what is
        # there instead is whatever the buffer held (NaN included).  The
        # mask on BOTH sides of every product keeps that out of the
        # result and, transposed, out of the gradient of ``x``.
        computed = (jnp.arange(order.shape[0]) < jnp.sum(rows))[:, None]

        def grouped(a, w):
            return jnp.where(computed, ragged_dot(
                jnp.where(computed, a, 0), w, rows), 0)

        if w_gate is None:
            h = jnp.square(jax.nn.relu(grouped(rows_x, w_up)))
        else:
            h = (jax.nn.silu(grouped(rows_x, w_gate))
                 * grouped(rows_x, w_up))
        out = grouped(h, w_down)
    with jax.named_scope("combine"):
        # back to (token, slot) order; pairs whose expert lives elsewhere
        # (the rows past the counts) add nothing
        out = _permute()(out, inverse, order)
        scale = jnp.where(key < n_held, weights.reshape(-1), 0.0)
        y = jnp.sum((out * scale[:, None].astype(out.dtype)).reshape(
            tokens, top_k, -1), axis=1)
        dropped = rows_outside_groups(key, inverse, rows)
    return y, {"rows_by_expert": rows, "rows_dropped": dropped}
