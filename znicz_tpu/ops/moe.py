"""The expert layer's ops: a router over all the experts of the model, and
the part of the layer's result that the experts HELD here give.

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


def held_experts(x, experts, weights, w_gate, w_up, w_down,
                 first_expert: int):
    """The held experts' part of the layer's result, ``(tokens, hidden)``,
    and the counters ``rows_by_expert`` and ``rows_dropped``.

    ``x`` is ``(tokens, hidden)``; ``experts``/``weights`` come from
    ``route``; ``w_gate``/``w_up`` are ``(experts_held, hidden, width)``
    and ``w_down`` ``(experts_held, width, hidden)``, expert
    ``first_expert + e`` at index ``e``.  The weight multiplies the
    expert's output."""
    import jax
    import jax.numpy as jnp
    from jax.lax import ragged_dot

    tokens, top_k = experts.shape
    n_held = w_gate.shape[0]
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

        h = jax.nn.silu(grouped(rows_x, w_gate)) * grouped(rows_x, w_up)
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
