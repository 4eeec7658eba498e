"""laguna sample: a sparse-expert decoder trained on ONE CHIP'S SHARE of an
expert-parallel deployment, through ``StandardWorkflow`` and the fused
trainer like every other sample.

    python -m znicz_tpu znicz_tpu/samples/laguna.py \\
        root.laguna.preset=tiny root.laguna.decision.max_epochs=2

    start -> repeater -> loader -> embed -> layer 0 .. n-1 -> head
                ^                                              |
                |                                       evaluator(seq)
                +-- gd_embed <- gd_layers <- gd_head <- snapshotter <- decision

The decoder is built from DATA: ``MODELS`` holds public configurations by
the keys of their ``config.json`` (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, ``rope_parameters`` ...), ``layers()``
turns one into the ``StandardWorkflow`` layer list, and a second decoder
is a second dictionary: ``samples/zaya.py`` selects ``zaya1-8b`` below
and shares loader, workflow and ``layers()`` with this file (a job names
its ``root`` namespace and its presets, ``LagunaWorkflow.namespace`` /
``.presets``).  The first is Laguna-XS.2
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json): 40
layers, hidden 2,048, 8 KV heads of 128, 48 query heads in full-attention
layers and 64 in window layers (window 512, one full layer in four), one
leading dense layer of width 8,192, then 256 routed experts of width 512
with 8 a token and a shared expert, vocabulary 100,352.

What the share arguments mean (``root.laguna.share``; the preset's value
where not set).  A chip cannot hold one whole expert layer with its
optimizer state (0.85 B parameters, 13.5 GB), so a deployment spreads each
layer over several chips and the rest of the depth over pipeline stages;
the sample trains what ONE of those chips holds:

``layers``         layers ``0 .. layers - 1`` of the stack (the stages
                   that follow hold the rest)
``experts_held``   routed experts this chip holds of ``num_experts``, from
``first_expert``   this one.  The router keeps all ``num_experts`` outputs
                   and chooses over all of them; the weights are
                   normalised over the chosen wherever they live; the
                   layer adds the shared expert and its OWN experts' part.
                   What the other chips' experts would add is left out —
                   there is no exchange on one chip and nothing stands in
                   for it
``vocab_held``     ids ``0 .. vocab_held - 1``: the embedding rows and
                   head columns of this chip's slice of the vocabulary;
                   the data draw their ids from the slice and the loss is
                   over it

Where ``config.json`` does not settle a reading, ``ASSUMED`` says which
one the units implement (the benchmark's configuration file repeats it
for the plain reference, ``benchmark/references/laguna.py``).

Training: AdamW (``GradientDescentAdamW``, a fixed learning rate) on
next-token cross-entropy, rows of ``seq_len`` int32 ids drawn
Zipf(``zipf``) over the slice, each row one document; bf16 compute with
float32 masters and moments under
``root.common.engine.compute_dtype=bfloat16``.  ``CharEmbedding``'s
``uint8`` ids (``samples/charlm.py``) are untouched by any of this.
"""

from __future__ import annotations

import numpy as np

from znicz_tpu.core.config import root
from znicz_tpu.evaluator import EvaluatorSeqSoftmax
from znicz_tpu.loader.fullbatch import FullBatchLoader
from znicz_tpu.standard_workflow import StandardWorkflow

#: public configurations, by the keys of their ``config.json``
MODELS = {
    "laguna-xs2": {
        "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "gating": True,
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "sliding_attention"] * 10,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
    },
    # https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json
    "zaya1-8b": {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_hidden_layers": 40, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5,
                       "rope_theta": 5000000, "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000,
                               "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272,
    },
    "zaya-tiny": {
        "cca_time0": 2, "cca_time1": 2, "head_dim": 16, "hidden_size": 64,
        "layer_types": ["hybrid"] * 8, "model_type": "zaya",
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_experts": 8, "num_experts_per_tok": 1,
        "num_hidden_layers": 8, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"}},
        "router_hidden_size": 16, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 512,
    },
    # the same block at sizes a CPU test finishes in seconds
    "tiny": {
        "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-06,
        "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "gating": True,
        "sliding_window": 16,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
                "original_max_position_embeddings": 16, "beta_slow": 1,
                "beta_fast": 4, "attention_factor": 1.1386294361119891,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}},
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "sliding_attention"] * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [4, 8, 8, 8] * 2,
    },
}

#: one chip's share of a deployment, and the job's shapes, by preset
PRESETS = {
    # one chip of 8 that share each layer of Laguna-XS.2 (experts
    # expert-parallel x 8, embedding and head vocabulary-parallel x 8),
    # layers 5-39 on further pipeline stages
    "xs2-ep8": {"model": "laguna-xs2",
                "share": {"layers": 5, "experts_held": 32,
                          "first_expert": 0, "vocab_held": 12544},
                "loader": {"seq_len": 8192, "minibatch_size": 2,
                           "n_train": 16, "n_valid": 2}},
    "tiny": {"model": "tiny",
             "share": {"layers": 5, "experts_held": 2, "first_expert": 0,
                       "vocab_held": 64},
             "loader": {"seq_len": 64, "minibatch_size": 2, "n_train": 8,
                        "n_valid": 2}},
}

#: readings ``config.json`` does not settle, as the units implement them
ASSUMED = {
    "gating": "per head: head h's output times sigmoid(x^ . w_g[:, h]), "
              "x^ the layer's normed input (a gate as wide as the heads' "
              "output would add 0.63 B parameters to the published 33.4 B)",
    "router": "sigmoid scores, the 8 largest chosen, weights "
              "moe_routed_scaling_factor * s / sum of the chosen s; no "
              "selection bias, no auxiliary loss",
    "qk_norm": "none",
    "rope_pairing": "rotate-half (dimension i pairs with i + rotary/2)",
    "init": "normal(0, 0.02), norm gains 1",
    "optimizer": "AdamW lr 3e-4, betas 0.9/0.95, eps 1e-8, decay 0.1 (not "
                 "on norms, gates, the router), no clipping, no schedule",
}

root.laguna.defaults({
    "preset": "xs2-ep8",
    "share": {},                # overrides of the preset's, key by key
    "loader": {"n_test": 0, "zipf": 1.1},   # and of its loader's
    "optimizer": {"learning_rate": 3e-4, "weights_decay": 0.1,
                  "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8},
    "decision": {"max_epochs": 2, "fail_iterations": 0},
    "snapshotter": {"prefix": "laguna", "interval": 0},
    "lr_adjust": {},            # no schedule: ``ASSUMED["optimizer"]``
    "head": {},                 # ``LMHead``'s own arguments
})


def settings(namespace: str = "laguna", presets: dict = None) -> dict:
    """``{"model", "share", "loader", "head"}`` as the job runs them: the
    preset with ``root.<namespace>.share`` / ``.loader`` / ``.head`` laid
    over it, the preset's ``assumed`` keys (readings the published
    dictionary does not carry) laid over the model's."""
    cfg = getattr(root, namespace)
    preset = (presets or PRESETS)[str(cfg.get("preset"))]
    model = preset["model"]         # a name in MODELS, or the dictionary
    return {"model": dict(MODELS[model] if isinstance(model, str)
                          else model, **preset.get("assumed", {})),
            "share": dict(preset["share"], **cfg.share.to_dict()),
            "loader": dict(preset["loader"], **cfg.loader.to_dict()),
            "head": dict(preset.get("head", {}), **cfg.head.to_dict())}


def rope_of(model: dict, kind: str) -> dict:
    """``DecoderLayer``'s ``rope`` from ``rope_parameters[kind]``."""
    cfg = model["rope_parameters"][kind]
    rope = {"theta": float(cfg["rope_theta"]),
            "rotary_dim": int(model["head_dim"]
                              * cfg.get("partial_rotary_factor", 1))}
    if rope["rotary_dim"] % 2:
        raise ValueError(f"rotary dimension {rope['rotary_dim']} is odd")
    if cfg.get("rope_type") == "yarn":
        rope["yarn"] = {k: cfg[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")}
    return rope


def family_keys(model: dict) -> dict:
    """What a ``DecoderLayer`` chooses by key, read off the model's
    dictionary: a model with ``cca_time0`` attends in a compressed latent
    with that many mixing taps; one with ``router_hidden_size`` routes by
    an MLP of that width on a state carried from layer to layer, with a
    selection bias that the load moves; one with ``scale_residual_merge``
    merges with learned scales — a key that a preset lays over a
    dictionary whose published form dropped it (``assumed``;
    ``samples/zaya.py`` ``ASSUMED_KEYS``)."""
    unit = {}
    if "cca_time0" in model:
        unit.update(attention="cca", mixing_taps=(int(model["cca_time0"]),
                                                  int(model["cca_time1"])))
    if "router_hidden_size" in model:
        unit.update(router="mlp",
                    router_width=int(model["router_hidden_size"]))
    if model.get("scale_residual_merge"):
        unit["residual_scale"] = True
    return unit


def hybrid_unit(model: dict, share: dict, kind: str) -> dict:
    """``DecoderLayer``'s arguments for one character of a model's
    ``hybrid_override_pattern``: every layer of such a model is ONE part
    — ``M`` a state-space mixer (``mamba_num_heads``, ``mamba_head_dim``,
    ``n_groups``, ``ssm_state_size``, ``conv_kernel``, ``chunk_size``,
    ``time_step_*``), ``*`` attention (rotated by ``rope_theta`` unless
    the dictionary says ``attention_positions: "none"``), ``E`` routed
    experts (``n_routed_experts``, ``moe_intermediate_size``,
    ``moe_shared_expert_intermediate_size``, ``routed_scaling_factor``;
    ``router_selection_bias`` gives the router a selection bias that
    the load moves).  ``mlp_hidden_act`` ``relu2`` makes every
    feed-forward two matrices around a squared ReLU;
    ``rescale_prenorm_residual`` starts each part's output projection
    ``1 / sqrt(num_hidden_layers)`` smaller."""
    unit = {"norm_eps": float(model["layer_norm_epsilon"]),
            "mixer": {"M": "mamba", "*": "attention"}.get(kind),
            "feed_forward": kind == "E",
            "activation": ("relu2" if model.get("mlp_hidden_act") == "relu2"
                           else "swiglu"),
            "out_scale": (int(model["num_hidden_layers"]) ** -0.5
                          if model.get("rescale_prenorm_residual") else 1.0)}
    if kind == "M":
        unit.update(
            ssm_heads=int(model["mamba_num_heads"]),
            ssm_head_dim=int(model["mamba_head_dim"]),
            ssm_groups=int(model["n_groups"]),
            ssm_state=int(model["ssm_state_size"]),
            conv_kernel=int(model["conv_kernel"]),
            ssm_chunk=int(model["chunk_size"]),
            dt_range=(float(model["time_step_min"]),
                      float(model["time_step_max"]),
                      float(model["time_step_floor"])))
    elif kind == "*":
        rotated = model.get("attention_positions", "rotary") != "none"
        unit.update(
            heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["head_dim"]),
            rope=({"theta": float(model["rope_theta"]), "rotary_dim": int(
                model["head_dim"] * model.get("partial_rotary_factor", 1))}
                if rotated else None))
    elif kind == "E":
        unit.update(
            expert_width=int(model["moe_intermediate_size"]),
            shared_width=int(model.get(
                "moe_shared_expert_intermediate_size", 0)),
            experts_total=int(model["n_routed_experts"]),
            experts_held=int(share["experts_held"]),
            first_expert=int(share["first_expert"]),
            experts_per_token=int(model["num_experts_per_tok"]),
            routed_scale=float(model.get("routed_scaling_factor", 1.0)),
            selection_bias=bool(model.get("router_selection_bias", False)))
    else:
        raise ValueError(f"hybrid_override_pattern holds {kind!r}")
    return unit


def layers(model: dict, share: dict, optimizer=None, head=None) -> list:
    """The ``StandardWorkflow`` layer list of ``share``'s part of
    ``model``: embedding, ``share["layers"]`` decoder layers, head;
    ``optimizer`` is the job's (``root.<namespace>.optimizer``), ``head``
    further arguments of ``LMHead`` (``settings()["head"]``).  A model
    with ``hybrid_override_pattern`` gives one character a layer
    (``hybrid_unit``); the others ``layer_types`` x ``mlp_layer_types``."""
    opt = optimizer or root.laguna.optimizer
    gd = {"learning_rate": float(opt.get("learning_rate")),
          "weights_decay": float(opt.get("weights_decay")),
          "beta1": float(opt.get("beta1")), "beta2": float(opt.get("beta2")),
          "epsilon": float(opt.get("epsilon"))}
    out = [{"type": "token_embedding",
            "->": {"vocab": int(share["vocab_held"]),
                   "hidden": int(model["hidden_size"])}, "<-": dict(gd)}]
    depth = int(model["num_hidden_layers"])
    heads = model.get("num_attention_heads_per_layer",
                      [model["num_attention_heads"]] * depth)
    mlps = model.get("mlp_layer_types", ["sparse"] * depth)
    pattern = model.get("hybrid_override_pattern")
    eps = float(model["layer_norm_epsilon"] if pattern
                else model["rms_norm_eps"])
    for i in range(int(share["layers"])):
        if pattern:
            out.append({"type": "decoder_layer", "<-": dict(gd),
                        "->": hybrid_unit(model, share, pattern[i])})
            continue
        kind = model["layer_types"][i]
        unit = {
            "heads": int(heads[i]),
            "kv_heads": int(model["num_key_value_heads"]),
            "head_dim": int(model["head_dim"]),
            "window": (int(model["sliding_window"])
                       if kind.endswith("sliding_attention") else None),
            "rope": rope_of(model, kind),
            "gating": bool(model.get("gating", False)),
            "norm_eps": float(model["rms_norm_eps"]),
            **family_keys(model)}
        if mlps[i] == "dense":
            unit["dense_width"] = int(model["intermediate_size"])
        else:
            unit.update(
                expert_width=int(model["moe_intermediate_size"]),
                shared_width=int(model.get(
                    "shared_expert_intermediate_size", 0)),
                experts_total=int(model["num_experts"]),
                experts_held=int(share["experts_held"]),
                first_expert=int(share["first_expert"]),
                experts_per_token=int(model["num_experts_per_tok"]),
                routed_scale=float(model.get("moe_routed_scaling_factor",
                                             1.0)))
            if unit.get("router") == "mlp":
                # the router's state comes from the sparse layer before
                unit["receives_state"] = any(
                    m != "dense" for m in mlps[:i])
        out.append({"type": "decoder_layer", "->": unit, "<-": dict(gd)})
    out.append({"type": "lm_head",
                "->": {"vocab": int(share["vocab_held"]),
                       "norm_eps": eps,
                       "tied": bool(model.get("tie_word_embeddings",
                                              False)),
                       **(head or {})},
                "<-": dict(gd)})
    return out


def zipf_rows(rng, n: int, seq_len: int, vocab: int, s: float):
    """``(n, seq_len + 1)`` int32 ids drawn Zipf(``s``) over ``vocab``
    (rank ``k`` with probability ~ ``k^-s``): a few hot ids, a long tail,
    so that routing is uneven."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -s
    return rng.choice(vocab, size=(n, seq_len + 1),
                      p=p / p.sum()).astype(np.int32)


class LagunaLoader(FullBatchLoader):
    """Rows of int32 ids, each one document; the labels are the row
    shifted by one.  ``job`` is the job's ``settings()``."""

    def __init__(self, workflow=None, name=None, job=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        self.job = job

    def load_data(self):
        cfg = self.job or settings()
        ldr, vocab = cfg["loader"], int(cfg["share"]["vocab_held"])
        lengths = [int(ldr.get("n_test", 0)), int(ldr["n_valid"]),
                   int(ldr["n_train"])]
        from znicz_tpu.core import prng

        rows = zipf_rows(prng.get(self.name).state, sum(lengths),
                         int(ldr["seq_len"]), vocab,
                         float(ldr.get("zipf", 1.1)))
        self.original_data.mem = rows[:, :-1].copy()
        self.original_labels.mem = rows[:, 1:].copy()
        self.class_lengths = lengths
        super().load_data()

    def create_minibatch_data(self):
        shape = (self.max_minibatch_size,) + tuple(
            self.original_data.shape[1:])
        self.minibatch_data.mem = np.zeros(shape, np.int32)
        self.minibatch_labels.mem = np.zeros(shape, np.int32)


class LagunaWorkflow(StandardWorkflow):
    """A decoder's training job; a second decoder's job subclasses it with
    its own ``root`` namespace and presets (``samples/zaya.py``).
    ``root.<namespace>.lr_adjust`` (``{"policy": ..., ...}``:
    ``lr_adjust.POLICIES``) wires a learning-rate schedule."""

    namespace = "laguna"
    presets = PRESETS

    def __init__(self, **kwargs):
        cfg = settings(self.namespace, self.presets)
        root_cfg = getattr(root, self.namespace)
        loader = LagunaLoader(
            name="loader", job=cfg,
            minibatch_size=int(cfg["loader"]["minibatch_size"]))
        super().__init__(
            name=type(self).__name__, loader=loader,
            layers=layers(cfg["model"], cfg["share"], root_cfg.optimizer,
                          cfg["head"]),
            loss_function="softmax",
            lr_adjust_config=root_cfg.lr_adjust.to_dict(),
            decision_config={
                "max_epochs": int(root_cfg.decision.get("max_epochs")),
                "fail_iterations": int(
                    root_cfg.decision.get("fail_iterations"))},
            snapshotter_config={
                "prefix": root_cfg.snapshotter.get("prefix"),
                "interval": int(root_cfg.snapshotter.get("interval", 0))},
            **kwargs)

    def link_evaluator(self):
        """Per-token softmax cross-entropy; no confusion matrix (12,544
        classes squared)."""
        last = self.forwards[-1]
        self.evaluator = EvaluatorSeqSoftmax(
            self, name="evaluator", n_classes=last.vocab,
            compute_confusion=False)
        self.evaluator.link_from(last)
        self.evaluator.link_attrs(last, "output")
        self.evaluator.link_attrs(self.loader,
                                  ("labels", "minibatch_labels"),
                                  ("batch_size", "minibatch_size"))


def run(device=None, mesh=None, workflow=LagunaWorkflow) -> LagunaWorkflow:
    wf = workflow()
    wf.initialize(device=device)
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.parallel.mesh import train_mesh_from_config

    if mesh is None:
        mesh = train_mesh_from_config()
    FusedTrainer(wf, mesh=mesh).run()
    wf.print_stats()
    return wf


if __name__ == "__main__":
    run()
