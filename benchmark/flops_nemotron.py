"""Operations and bytes that a step of the tower of a ``nemotron_h``
``config.json`` NEEDS, computed from its configuration.

Conventions as ``benchmark/flops_decoder.py``: 2 operations a
multiply-accumulate, a train step is 3 x forward, recomputed operations
never count, elementwise work (norms, activations, gates, decays) is left
out.  What is counted is what the MODEL needs, from shapes, whatever
implements it:

  - by the tokens: a state-space layer's two projections, its
    convolution (``2 x conv_kernel`` a channel) and its scan in the form
    the dictionary states (chunks of ``chunk_size``: the masked ``C B^T``
    product a group, that product applied to ``x`` a head, the chunk's
    state and the entry state's part a head — ``2 Q N G + 2 Q P H + 4 P N
    H`` a token); the attention layer's four projections; an expert
    layer's router and shared expert; the head over the ids held;
  - the attention core by the (query, key) pairs the causal mask ADMITS:
    ``4 x head_dim`` operations a pair and query head;
  - the routed experts by the rows ACTUALLY routed to the experts held
    (the program's counter), ``2 x 2 x hidden x width`` a row.

``scan_bytes`` is what the convolution and the scan must move once
whatever implements them: forward they read ``xBC`` and ``dt`` and write
``y``; backward they read ``xBC``, ``dt`` and ``y``'s cotangent and write
the cotangents of ``xBC`` and ``dt``; all in the compute dtype.

``model`` is the dictionary of the model's ``config.json``, ``share`` the
chip's share (``layers``, ``experts_held``, ``vocab_held``).
"""

from __future__ import annotations

from benchmark.flops_zaya import admitted_pairs, kernel_seconds


def kinds(model: dict, share: dict) -> str:
    """The characters of the layers held."""
    return model["hybrid_override_pattern"][:int(share["layers"])]


def scan_channels(model: dict) -> tuple:
    """``(inner, mixed)``: the scan's ``x`` channels and the
    convolution's (``x``, ``B`` and ``C``)."""
    inner = int(model["mamba_num_heads"]) * int(model["mamba_head_dim"])
    return inner, inner + 2 * int(model["n_groups"]) * int(
        model["ssm_state_size"])


def token_forward_flops(model: dict, share: dict) -> dict:
    """Forward operations a token by part, all layers held together:
    everything that is neither a core's pair nor a routed expert."""
    d = int(model["hidden_size"])
    h, p = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    g, n = int(model["n_groups"]), int(model["ssm_state_size"])
    q, taps = int(model["chunk_size"]), int(model["conv_kernel"])
    inner, mixed = scan_channels(model)
    heads, kv = int(model["num_attention_heads"]), int(
        model["num_key_value_heads"])
    hd = int(model["head_dim"])
    held = kinds(model, share)
    m, e, a = held.count("M"), held.count("E"), held.count("*")
    return {
        "ssm_proj": m * 2 * d * ((inner + mixed + h) + inner),
        "ssm_conv": m * 2 * taps * mixed,
        "ssm_scan": m * (2 * q * n * g + 2 * q * p * h + 4 * p * n * h),
        "attention_proj": a * 2 * d * hd * (2 * heads + 2 * kv),
        "router": e * 2 * d * int(model["n_routed_experts"]),
        "shared": e * 2 * 2 * d * int(model.get(
            "moe_shared_expert_intermediate_size", 0)),
        "head": 2 * int(share["vocab_held"]) * d,
    }


def core_forward_flops(model: dict, share: dict, batch: int,
                       seq: int) -> int:
    """Forward operations of the admitted pairs, all attention layers
    held."""
    return (4 * int(model["head_dim"]) * int(model["num_attention_heads"])
            * kinds(model, share).count("*") * batch * admitted_pairs(seq))


def expert_forward_flops_per_row(model: dict) -> int:
    return 2 * 2 * int(model["hidden_size"]) * int(
        model["moe_intermediate_size"])


def dot_forward_flops(model: dict, share: dict, batch: int, seq: int) -> int:
    """Forward operations of one step that XLA runs as dot-rooted
    operations: by the tokens, the scan's products included, and neither
    the core (Pallas kernels) nor the experts (grouped products) nor the
    convolution (elementwise)."""
    by_token = token_forward_flops(model, share)
    return batch * seq * (sum(by_token.values()) - by_token["ssm_conv"])


def scan_bytes(model: dict, share: dict, batch: int, seq: int,
               itemsize: int = 2) -> dict:
    """Bytes the convolution and the scan must read and write once a
    step, ``forward`` and ``backward``, all state-space layers held."""
    inner, mixed = scan_channels(model)
    dt = int(model["mamba_num_heads"])
    tokens = batch * seq * kinds(model, share).count("M")
    return {"forward": tokens * itemsize * (mixed + dt + inner),
            "backward": tokens * itemsize * (mixed + dt + inner
                                             + mixed + dt)}


def window_flops(model: dict, share: dict, batch: int, seq: int,
                 train_steps: int, eval_steps: int, rows_routed: float,
                 counted_steps: int) -> dict:
    """Operations of a traced window by part — ``core``, ``experts``,
    ``head``, ``scan`` (convolution and scan) and ``all`` — and
    ``scan_bytes``.  The routed rows come from the counter, which counts
    train and validation steps alike (``counted_steps`` of them): every
    pass is taken at the counted steps' mean."""
    passes = 3 * train_steps + eval_steps
    by_token = token_forward_flops(model, share)
    rows_step = rows_routed / max(counted_steps, 1)
    experts = expert_forward_flops_per_row(model) * rows_step * passes
    core = core_forward_flops(model, share, batch, seq) * passes
    tokens = batch * seq
    moved = scan_bytes(model, share, batch, seq)
    return {"core": core, "experts": experts,
            "head": tokens * by_token["head"] * passes,
            "scan": tokens * (by_token["ssm_conv"]
                              + by_token["ssm_scan"]) * passes,
            "scan_bytes": (moved["forward"] * (train_steps + eval_steps)
                           + moved["backward"] * train_steps),
            "all": core + experts + tokens * sum(by_token.values()) * passes}


# -- what the readers of ``benchmark/layer_metrics`` share --------------------------


def of_run(run: dict):
    """``window_flops`` of a traced run, or ``None`` where the run lacks
    what it is computed from (another model's run, a parent's)."""
    trace, shape = run.get("trace") or {}, run.get("shape") or {}
    model = shape.get("model") or {}
    if "moe_rows_routed" not in trace \
            or "hybrid_override_pattern" not in model:
        return None
    return window_flops(
        model, shape["share"], shape["batch"], shape["row_tokens"],
        trace["train_steps"], trace["eval_steps"], trace["moe_rows_routed"],
        trace["moe_counted_steps"])


def scoped_seconds(run: dict, match):
    """Self time on device 0 under the tags ``match(unit, inner,
    direction)`` accepts (``benchmark/reduce/inner.py``), or ``None``
    where the run is another model's or has no reduction."""
    from benchmark.reduce import inner

    reduction = inner.of_run(run) if of_run(run) else None
    return inner.seconds(reduction, match) if reduction else None


def ms_per_step(run: dict, match, kernels: str = ""):
    """Self time per step of the traced window under ``match``, plus,
    with ``kernels``, that of the operations so named (the compiler's own
    kernels carry no scope of the program's); ``None`` where there is
    nothing to read."""
    total = scoped_seconds(run, match)
    if total is None:
        return None
    total += kernel_seconds(run, kernels) if kernels else 0.0
    trace = run["trace"]
    steps = max(trace["train_steps"] + trace["eval_steps"], 1)
    return total / steps * 1e3 if total else None


def roofline(run: dict, part: str, match, kernels: str = "",
             bytes_part: str = ""):
    """Share of its roofline that ``part``'s needed work reaches over the
    self time under ``match`` (plus the operations named ``kernels``), in
    per cent: the needed operations over the bf16 peak or, with
    ``bytes_part``, the greater of that and the bytes it must move over
    the HBM peak (``run["peaks_hbm"]``: the driver's reading of
    ``benchmark/peaks_hbm.json`` for the device it ran on)."""
    flops, peaks = of_run(run), run.get("peaks")
    hbm = run.get("peaks_hbm")
    if bytes_part and not hbm:
        return None
    busy = scoped_seconds(run, match) if flops and peaks else None
    if busy is None:
        return None
    busy += kernel_seconds(run, kernels) if kernels else 0.0
    if busy <= 0:
        return None
    least = flops[part] / (peaks["bf16_tflops"] * 1e12)
    if bytes_part:
        least = max(least, flops[bytes_part] / (hbm["hbm_gb_per_s"] * 1e9))
    return 100.0 * least / busy
