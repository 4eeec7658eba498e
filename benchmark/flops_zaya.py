"""Operations a ZAYA1 decoder's step NEEDS, computed from its
configuration.

Conventions as ``benchmark/flops_decoder.py``: 2 operations a
multiply-accumulate, a train step is 3 x forward, recomputed operations
never count, elementwise work (norms, the channel-wise mixing taps, the
query-key mean, rotary, merges) is left out.  What is counted is what the
MODEL needs, whatever implements it:

  - the attention core by the (query, key) pairs the causal mask ADMITS
    (row ``i`` admits ``i + 1`` keys): ``4 x head_dim`` operations a pair
    and query head;
  - the routed experts by the rows ACTUALLY routed to the experts held
    (the program's counter), ``3 x 2 x hidden x width`` a row;
  - by the tokens: the block's four projections, the head-wise mixing
    (``cca_time1`` matrices of ``head_dim x head_dim`` a head), the router
    (down-projection and its MLP), and the head over the ids held.

``model`` is the dictionary of the model's ``config.json``, ``share`` the
chip's share (``layers``, ``experts_held``, ``vocab_held``).
"""

from __future__ import annotations



def admitted_pairs(seq: int) -> int:
    """(query, key) pairs one head admits over one causal sequence."""
    return seq * (seq + 1) // 2


def token_forward_flops(model: dict, share: dict) -> dict:
    """Forward operations a token by part — ``projections``, ``mixing``,
    ``router`` (all layers held together) and ``head``: everything that is
    neither a core's pair nor a routed expert."""
    d, hd = int(model["hidden_size"]), int(model["head_dim"])
    h, kv = int(model["num_attention_heads"]), int(
        model["num_key_value_heads"])
    r = int(model["router_hidden_size"])
    layers = int(share["layers"])
    return {
        "projections": 2 * layers * d * hd * (2 * h + 2 * kv),
        "mixing": 2 * layers * int(model["cca_time1"]) * (h + kv) * hd * hd,
        "router": 2 * layers * (d * r + 2 * r * r
                                + r * int(model["num_experts"])),
        "head": 2 * int(share["vocab_held"]) * d,
    }


def core_forward_flops(model: dict, share: dict, batch: int,
                       seq: int) -> int:
    """Forward operations of the admitted pairs, all layers held."""
    return (4 * int(model["head_dim"]) * int(model["num_attention_heads"])
            * int(share["layers"]) * batch * admitted_pairs(seq))


def expert_forward_flops_per_row(model: dict) -> int:
    return 3 * 2 * int(model["hidden_size"]) * int(
        model["moe_intermediate_size"])


def dot_forward_flops(model: dict, share: dict, batch: int, seq: int) -> int:
    """Forward operations of one step that XLA runs as dot-rooted
    operations: by the tokens only, neither the core (Pallas kernels) nor
    the experts (grouped products): custom calls both."""
    return batch * seq * sum(token_forward_flops(model, share).values())


def window_flops(model: dict, share: dict, batch: int, seq: int,
                 train_steps: int, eval_steps: int, rows_routed: float,
                 counted_steps: int) -> dict:
    """Operations of a traced window by part: ``core``, ``experts``,
    ``head`` and ``all``.  The routed rows come from the counter, which
    counts train and validation steps alike (``counted_steps`` of them):
    every pass is taken at the counted steps' mean."""
    passes = 3 * train_steps + eval_steps
    by_token = token_forward_flops(model, share)
    rows_step = rows_routed / max(counted_steps, 1)
    experts = expert_forward_flops_per_row(model) * rows_step * passes
    core = core_forward_flops(model, share, batch, seq) * passes
    head = batch * seq * by_token["head"] * passes
    rest = batch * seq * (sum(by_token.values()) - by_token["head"]) * passes
    return {"core": core, "experts": experts, "head": head,
            "all": core + experts + head + rest}


# -- what the readers of ``benchmark/layer_metrics`` share --------------------------


def of_run(run: dict):
    """``window_flops`` of a traced run, or ``None`` where the run lacks
    what it is computed from (another model's run, a parent's)."""
    trace, shape = run.get("trace") or {}, run.get("shape") or {}
    model = shape.get("model") or {}
    if "moe_rows_routed" not in trace or "cca_time1" not in model:
        return None
    return window_flops(
        model, shape["share"], shape["batch"], shape["row_tokens"],
        trace["train_steps"], trace["eval_steps"], trace["moe_rows_routed"],
        trace["moe_counted_steps"])


def kernel_seconds(run: dict, prefix: str) -> float:
    """Self time on device 0 of the operations whose name starts with
    ``prefix`` (0.0 where the trace has none)."""
    devices = (run.get("trace") or {}).get("devices") or []
    ops = devices[0].get("ops_s", {}) if devices else {}
    return sum(t for name, t in ops.items() if name.startswith(prefix))


def roofline(run: dict, part: str, scope: str, kernels: str = ""):
    """Share of the bf16 peak that ``part``'s needed operations reach over
    the self time under the inner scope ``scope``, in per cent; ``None``
    where there is nothing to read.  With ``kernels``, the time of the
    operations so named is ADDED: the compiler's own kernels carry the
    compiler's name and no scope of the program's, and what the program
    runs around them under the scope (masks, the activation) is the
    part's time too."""
    from benchmark.reduce import inner

    flops, peaks = of_run(run), run.get("peaks")
    reduction = inner.of_run(run) if flops and peaks else None
    if not reduction:
        return None
    busy = inner.seconds(reduction, lambda _u, i, _d: i == scope) + (
        kernel_seconds(run, kernels) if kernels else 0.0)
    if busy <= 0:
        return None
    return 100.0 * flops[part] / (peaks["bf16_tflops"] * 1e12) / busy


def ms_per_step(run: dict, scopes: tuple):
    """Self time per step under the inner scopes ``scopes``, forward,
    recomputation and backward; ``None`` where there is nothing to
    read."""
    from benchmark.reduce import inner

    return inner.ms_per_step(run, lambda _u, i, _d: i in scopes)
