"""nemotron sample: the third decoder — the tower that the ``config.json``
of Nemotron-Labs-TwoTower-30B-A3B-Base states, trained on ONE CHIP'S SHARE
of a 16-way expert-parallel deployment.  It is a dictionary (``MODELS``
below, the keys of
https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16/blob/main/config.json)
and this file, which only selects it: loader, workflow and ``layers()``
are ``samples/laguna.py``'s.

    python -m znicz_tpu znicz_tpu/samples/nemotron.py \\
        root.nemotron.preset=tiny root.nemotron.decision.max_epochs=2

52 layers of hidden 2,688, each ``x + Part(RMSNorm(x))`` with ONE part
(``hybrid_override_pattern``: 23 ``M``, 23 ``E``, 6 ``*``): ``M`` a
Mamba-2 mixer (64 heads of 64, 8 groups of state 128, convolution of 4
taps, the scan in chunks of 128: ``ops/ssm.py``), ``E`` 128 routed experts
of width 1,856 with 6 a token plus a shared one of 3,712, every expert two
matrices around a squared ReLU, chosen by sigmoid scores plus a selection
bias that every train step's load moves (``ops/moe.py``
``route_balanced`` / ``balance_step``), ``*`` grouped-query attention (32 heads over 2 KV heads of
128) without positions; vocabulary 131,072, the head untied.  What the
layer does it chooses by the dictionary's keys
(``laguna.hybrid_unit``).

The SECOND tower of the published model (a denoiser: adaLN modulation,
cross-tower conditioning, bidirectional attention inside a block) and
generation by diffusion over blocks are NOT here (``LEFT_OUT``): the
dictionary describes one tower, and this trains it with the next-token
loss.

The share (``root.nemotron.share``; ``samples/laguna.py`` explains the
keys): 16 chips share each layer — experts expert-parallel x 16 (8 a
chip), embedding and head vocabulary-parallel x 8 (16,384 ids a chip) —
and this chip holds layers 0-8 (``MEMEM*EME``), experts 0-7, ids
0-16,383; layers 9-51 lie on further pipeline stages.

Training: AdamW under a linear warm-up — step ``t`` runs at ``3e-4 (t +
1) / 2,000`` (``lr_adjust.py`` ``warmup``), as ``samples/zaya.py``.
"""

from __future__ import annotations

from znicz_tpu.core.config import root
from znicz_tpu.samples import laguna

#: public configurations, by the keys of their ``config.json``
MODELS = {
    "nemotron-twotower-30b": {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 52, "num_key_value_heads": 2,
        "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_limit": [0, None], "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072,
    },
    # the same parts at sizes a CPU test finishes in seconds
    "nemotron-tiny": {
        "chunk_size": 16, "conv_kernel": 4, "head_dim": 16,
        "hidden_size": 64, "hybrid_override_pattern": "MEMEM*EME",
        "intermediate_size": 32, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 8, "mamba_num_heads": 8,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 32,
        "moe_shared_expert_intermediate_size": 64, "n_groups": 2,
        "n_routed_experts": 8, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts_per_tok": 2,
        "num_hidden_layers": 9, "num_key_value_heads": 2,
        "rescale_prenorm_residual": True, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "ssm_state_size": 16,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "vocab_size": 512,
    },
}

#: keys laid over the published dictionary (``laguna.settings``): what
#: the layer chooses by them, ``laguna.hybrid_unit`` says; ``ASSUMED``
#: below says which reading each stands for
ASSUMED_KEYS = {"attention_positions": "none",
                "router_selection_bias": True}

#: one chip's share of a deployment, and the job's shapes, by preset
PRESETS = {
    # one chip of 16 that share each layer, layers 9-51 on further
    # pipeline stages
    "nemotron-twotower-30b-ep16": {
        "model": MODELS["nemotron-twotower-30b"],
        "assumed": dict(ASSUMED_KEYS),
        "share": {"layers": 9, "experts_held": 8, "first_expert": 0,
                  "vocab_held": 16384},
        "loader": {"seq_len": 8192, "minibatch_size": 2, "n_train": 16,
                   "n_valid": 2}},
    "tiny": {"model": MODELS["nemotron-tiny"],
             "assumed": dict(ASSUMED_KEYS),
             "share": {"layers": 9, "experts_held": 4, "first_expert": 0,
                       "vocab_held": 256},
             "loader": {"seq_len": 64, "minibatch_size": 2, "n_train": 8,
                        "n_valid": 2}},
}

#: readings neither ``config.json`` nor the catalog's description settles,
#: as the units implement them
#: (``benchmark/configs/nemotron-twotower-30b-ep16.json`` repeats them for
#: the plain reference, each with its why)
ASSUMED = {
    "attention_positions": "none: q and k go to the core as projected; "
                           "rope_theta, partial_rotary_factor and "
                           "max_position_embeddings are read by nothing",
    "router_balance": "router_bias starts at zero and no gradient reaches "
                      "it; every train step moves it by that step's load: "
                      "bias_e -= 1/2 of the margin by which expert e would "
                      "have kept exactly its even share of the step's "
                      "(token, slot) pairs, the others' biases held, then "
                      "centred (ops/moe.py balance_step, the rule of "
                      "ZAYA's cell).  The router's own published rule, a "
                      "fixed 1e-3 * sign(mean load - load_e) a step, was "
                      "run first and left the cell's rate to the seed "
                      "(1.9 per cent over six seeds, PERF.md section 6)",
    "router": "sigmoid scores in float32, the 6 largest s + router_bias "
              "over all 128 (n_group 1, topk_group 1: no group limit), "
              "weights 2.5 * s / sum of the chosen s; no auxiliary loss",
    "unread_keys": "expand (the inner width is mamba_num_heads x "
                   "mamba_head_dim), n_group, topk_group, rope_theta, "
                   "partial_rotary_factor, num_logits_to_keep, "
                   "use_mamba_kernels, residual_in_fp32 (the stream is "
                   "the compute dtype's)",
    "init": "normal(0, 0.02); each part's output projection 1/sqrt(52) "
            "of that (rescale_prenorm_residual); gains 1; dt_bias the "
            "inverse softplus of dt log-uniform in [time_step_min, "
            "time_step_max] floored at time_step_floor; A_log = log U[1, "
            "16]; D = 1; convolution taps uniform in +-1/2, its bias 0",
    "optimizer": "AdamW betas 0.9/0.95, eps 1e-8, decay 0.1 (not on norms, "
                 "biases, A_log, D, dt_bias, the router), no clipping; lr "
                 "3e-4 after a linear warm-up over 2,000 steps",
}

#: what the published model has and this sample does not (ROADMAP)
LEFT_OUT = ("the second tower (a denoiser with adaLN modulation and "
            "cross-tower conditioning, bidirectional attention inside a "
            "block)", "generation by diffusion over blocks",
            "the exchange between the 16 chips",
            "an auxiliary balance loss (no key gives one)")

root.nemotron.defaults({
    "preset": "nemotron-twotower-30b-ep16",
    "share": {},                # overrides of the preset's, key by key
    "loader": {"n_test": 0, "zipf": 1.1},   # and of its loader's
    "optimizer": {"learning_rate": 3e-4, "weights_decay": 0.1,
                  "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8},
    "lr_adjust": {"policy": "warmup", "steps": 2000},
    "head": {},
    "decision": {"max_epochs": 2, "fail_iterations": 0},
    "snapshotter": {"prefix": "nemotron", "interval": 0},
})


class NemotronWorkflow(laguna.LagunaWorkflow):
    namespace = "nemotron"
    presets = PRESETS


def run(device=None, mesh=None) -> NemotronWorkflow:
    return laguna.run(device, mesh, workflow=NemotronWorkflow)


if __name__ == "__main__":
    run()
