"""zaya sample: the second decoder — ZAYA1-8B trained on ONE CHIP'S SHARE
of a 2-way expert-parallel deployment.  It is a second dictionary
(``samples/laguna.py`` ``MODELS["zaya1-8b"]``, the keys of
https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json) and this
file, which only selects it: loader, workflow and ``layers()`` are
``samples/laguna.py``'s.

    python -m znicz_tpu znicz_tpu/samples/zaya.py \\
        root.zaya.preset=tiny root.zaya.decision.max_epochs=2

40 identical layers, hidden 2,048; attention in a compressed latent (8
query heads over 2 KV heads of 128, queries and keys mixed along the
sequence by two causal convolutions, a query-key mean, half the value
heads one token late, q and k of fixed length with a learned temperature:
``ops/cca.py``); 16 experts of width 2,048 with ONE a token, chosen by an
MLP of width 256 on a state that every layer adds to the next one's, by
probability plus a selection bias that every train step moves by that
step's load (``ops/moe.py`` ``router_state`` / ``route_mlp`` /
``balance_step``); residual merges with
learned scales; vocabulary 262,272 with the head TIED to the embedding
(one tensor, one optimizer state, the loss a block of rows at a time:
``decoder.py`` ``LMHead``).  What the layer does differently from
Laguna's it chooses by the dictionary's keys (``laguna.family_keys``).

The share (``root.zaya.share``; ``samples/laguna.py`` explains the keys):
two chips share each layer — experts expert-parallel x 2 (8 a chip),
embedding and head vocabulary-parallel x 2 (131,136 ids a chip) — and
this chip holds layers 0-3, experts 0-7, ids 0-131,135; layers 4-39 lie
on further pipeline stages.

Training: AdamW under a linear warm-up — step ``t`` runs at ``3e-4 (t +
1) / 2,000`` (``lr_adjust.py`` ``warmup``; the scan takes each step's
rate as a row, nothing recompiles): these are the first steps of a job,
and a router that starts at the full rate collapses within an epoch
(``PERF.md``, PR 27).
"""

from __future__ import annotations

from znicz_tpu.core.config import root
from znicz_tpu.samples import laguna

#: keys laid over the published dictionary (``laguna.settings``): what the
#: layer chooses by them, ``laguna.family_keys`` says; ``ASSUMED`` below
#: says which reading each stands for
ASSUMED_KEYS = {"scale_residual_merge": True}

#: one chip's share of a deployment, and the job's shapes, by preset
PRESETS = {
    # one chip of 2 that share each layer of ZAYA1-8B, layers 4-39 on
    # further pipeline stages
    "zaya1-8b-ep2": {"model": "zaya1-8b", "assumed": dict(ASSUMED_KEYS),
                     "share": {"layers": 4, "experts_held": 8,
                               "first_expert": 0, "vocab_held": 131136},
                     "loader": {"seq_len": 32768, "minibatch_size": 1,
                                "n_train": 8, "n_valid": 1}},
    # the head's block shrunk so that 128 rows of 256 ids run in blocks too
    "tiny": {"model": "zaya-tiny", "assumed": dict(ASSUMED_KEYS),
             "head": {"loss_block_bytes": 65536},
             "share": {"layers": 4, "experts_held": 4, "first_expert": 0,
                       "vocab_held": 256},
             "loader": {"seq_len": 64, "minibatch_size": 2, "n_train": 8,
                        "n_valid": 2}},
}

#: readings neither ``config.json`` nor the catalog's description settles,
#: as the units implement them (``benchmark/configs/zaya1-8b-ep2.json``
#: repeats them for the plain reference, each with its why)
ASSUMED = {
    "mixing": "c_t = w_0 * u_{t-1} + w_1 * u_t + b over the 1,280 query "
              "and key channels, then per head d_t = A_0 c_{t-1} + A_1 c_t "
              "+ b (one group a head); no activation between, biases "
              "present, zero before the first position",
    "value_shift": "KV head 0 holds the current token's value, head 1 the "
                   "previous token's (zero at the first position)",
    "qk_norm": "q <- sqrt(128) q / |q|, k <- exp(tau) sqrt(128) k / |k|, "
               "tau one learned number a KV head, 0 at the start; before "
               "the rotation",
    "rope_pairing": "rotate-half over the first 64 dimensions",
    "residual_merge": "(x + b_r) * s_r + (a + b_h) * s_h, scales 1 and "
                      "shifts 0 at the start, four vectors a sub-block "
                      "(scale_residual_merge of the family's other "
                      "configurations; this one's published form dropped "
                      "the key, ASSUMED_KEYS lays it over)",
    "router": "r = x^ W_d + b_d (+ gamma * r of the layer before, gamma 1 "
              "at the start; layer 0 receives none and has no gamma); s = "
              "W_3 gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2), exact gelu; "
              "softmax p in float32; the expert with the largest p + "
              "beta, its probability p the weight",
    "router_balance": "beta starts at zero and no gradient reaches it; "
                      "every train step moves it by that step's load: "
                      "beta_e -= 1/2 of the margin by which expert e "
                      "would have kept exactly its even share of the "
                      "step's tokens, the others' biases held, then "
                      "centred (ops/moe.py balance_step).  The report's "
                      "own controller is not reproduced; a fixed step "
                      "(1e-3, as in the rule it descends from) is 2 to "
                      "20 times the whole spread of p at seeded weights",
    "init": "normal(0, 0.02); gains and scales 1; biases, shifts and "
            "temperatures 0",
    "optimizer": "AdamW betas 0.9/0.95, eps 1e-8, decay 0.1 (not on norms, "
                 "scales, shifts, temperatures, biases, gamma), no "
                 "clipping; lr 3e-4 after a linear warm-up over 2,000 steps",
}

#: what the family has and this sample does not (ROADMAP's reach queue)
LEFT_OUT = ("an expert that skips a token's feed-forward (the family's "
            "depth-skipping expert)",)

root.zaya.defaults({
    "preset": "zaya1-8b-ep2",
    "share": {},                # overrides of the preset's, key by key
    "loader": {"n_test": 0, "zipf": 1.1},   # and of its loader's
    "optimizer": {"learning_rate": 3e-4, "weights_decay": 0.1,
                  "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8},
    "lr_adjust": {"policy": "warmup", "steps": 2000},
    "head": {},
    "decision": {"max_epochs": 2, "fail_iterations": 0},
    "snapshotter": {"prefix": "zaya", "interval": 0},
})


class ZayaWorkflow(laguna.LagunaWorkflow):
    namespace = "zaya"
    presets = PRESETS


def run(device=None, mesh=None) -> ZayaWorkflow:
    return laguna.run(device, mesh, workflow=ZayaWorkflow)


if __name__ == "__main__":
    run()
