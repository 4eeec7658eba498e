"""Operations a decoder's step NEEDS, computed from its configuration.

Convention as ``benchmark/flops.py``: 2 operations per multiply-accumulate,
a train step is 3 x forward (one product for the input's gradient, one for
the weights'), recomputed operations never count, elementwise work is left
out.  What is counted is what the MODEL needs, whatever implements it:

  - attention by the (query, key) pairs the mask ADMITS (a causal row ``i``
    admits ``i + 1`` keys, a window row ``min(i + 1, window)``), not by the
    blocks a kernel touches: ``4 x head_dim`` operations a pair and head
    (scores and the weighted sum);
  - the routed experts by the rows ACTUALLY routed to the experts held
    (the program's counter), ``3 x 2 x hidden x width`` operations a row
    (gate, up, down);
  - everything else (projections, attention gate, router, shared expert,
    dense feed-forward, head) by the tokens.

``model`` is the dictionary of the model's ``config.json``, ``share`` the
chip's share (``layers``, ``experts_held``, ``vocab_held``).
"""

from __future__ import annotations


def admitted_pairs(seq: int, window=None) -> int:
    """(query, key) pairs one head admits over one sequence."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layers_of(model: dict, share: dict):
    """``(heads, window or None, sparse)`` of each layer held."""
    for i in range(int(share["layers"])):
        yield (int(model["num_attention_heads_per_layer"][i]),
               int(model["sliding_window"])
               if model["layer_types"][i] == "sliding_attention" else None,
               model["mlp_layer_types"][i] != "dense")


def token_forward_flops(model: dict, share: dict) -> int:
    """Forward operations a token of everything that is neither attention
    scores nor a routed expert."""
    d, hd = int(model["hidden_size"]), int(model["head_dim"])
    kv = int(model["num_key_value_heads"])
    macs = int(share["vocab_held"]) * d                     # the head
    for heads, _, sparse in layers_of(model, share):
        macs += d * hd * (2 * heads + 2 * kv)               # q, o, k, v
        macs += d * heads if model.get("gating") else 0
        if sparse:
            macs += d * int(model["num_experts"])           # the router
            macs += 3 * d * int(model["shared_expert_intermediate_size"])
        else:
            macs += 3 * d * int(model["intermediate_size"])
    return 2 * macs


def attention_forward_flops(model: dict, share: dict, batch: int, seq: int,
                            kind=None) -> int:
    """Forward operations of the admitted pairs; ``kind`` ``"full"`` or
    ``"window"`` keeps those layers only."""
    total = 0
    for heads, window, _ in layers_of(model, share):
        if kind and kind != ("window" if window else "full"):
            continue
        total += (4 * int(model["head_dim"]) * heads * batch
                  * admitted_pairs(seq, window))
    return total


def expert_forward_flops_per_row(model: dict) -> int:
    return 3 * 2 * int(model["hidden_size"]) * int(
        model["moe_intermediate_size"])


def dot_forward_flops(model: dict, share: dict, batch: int, seq: int) -> int:
    """Forward operations of one step without the routed experts."""
    return (batch * seq * token_forward_flops(model, share)
            + attention_forward_flops(model, share, batch, seq))


def window_flops(model: dict, share: dict, batch: int, seq: int,
                 train_steps: int, eval_steps: int, rows_routed: float,
                 counted_steps: int) -> dict:
    """Operations of a traced window by part: ``attention``, ``experts``
    and ``all``.  The routed rows come from the counter, which counts
    train and validation steps alike (``counted_steps`` of them): every
    pass is taken at the counted steps' mean."""
    passes = 3 * train_steps + eval_steps
    rows_step = rows_routed / max(counted_steps, 1)
    experts = (expert_forward_flops_per_row(model) * rows_step * passes)
    attention = attention_forward_flops(model, share, batch, seq) * passes
    rest = batch * seq * token_forward_flops(model, share) * passes
    return {"attention": attention, "experts": experts,
            "all": attention + experts + rest}
