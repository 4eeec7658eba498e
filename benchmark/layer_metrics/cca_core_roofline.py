"""Share of the bf16 peak that the attention core's NEEDED operations
reach while it runs, for a ZAYA1 decoder (8 query heads over 2 KV heads).

Layer: attention core (``znicz_tpu/ops/attention.py``).  Source: the device
trace — ``4 x head_dim`` operations for every (query, key) pair the causal
mask ADMITS and query head, forward + 2 x backward, recomputation never
counted (``benchmark/flops_zaya.py``), over the peak in
``benchmark/peaks.json``, divided by the self time under the scope
``attn_core`` (``benchmark/reduce/inner.py``).  Pairs a tile computes and
the mask then excludes are time, not operations, so it cannot pass 100.
Nothing to read from a run of another model.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_zaya


def read(run):
    return flops_zaya.roofline(run, "core", "attn_core")
