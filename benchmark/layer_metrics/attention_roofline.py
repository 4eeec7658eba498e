"""Share of the bf16 peak that the attention core's NEEDED operations
reach while it runs.

Layer: attention core (``znicz_tpu/ops/attention.py``).  Source: the device
trace — ``4 x head_dim`` operations for every (query, key) pair the mask
ADMITS and head, forward + 2 x backward, recomputation never counted
(``benchmark/flops_decoder.py``), over the peak in
``benchmark/peaks.json``, divided by the self time under the scope
``attn_core`` (``benchmark/reduce/inner.py``).  Pairs a block computes and
the mask then excludes are time, not operations: the same work whatever
implements it, so it cannot pass 100.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import inner


def read(run):
    return inner.roofline(run, "attention", "attn_core")
