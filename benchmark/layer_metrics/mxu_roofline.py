"""Share of the bf16 peak that the convolution and matrix-product
operations reach while they run.

Layer: train kernels (XLA fusions).  Source: the device trace — the least
time the chip could take for the window's multiply-accumulates
(``benchmark/flops.py``: 3 x forward for a train step, 1 x for a validation
step, a chip's share of the batch, over the peak in
``benchmark/peaks.json``) divided by the self time of the operations the
reduction finds rooted in a convolution or dot on device 0 (the opcodes
and the fusions whose computation, in the compiled text of the programs
that ran, holds one).  Compute-bound by construction: bytes are not
counted.  Nothing to read where a fusion's content is unknown.  Moves
``train_samples_per_s``.
"""


def read(run):
    trace, peaks = run.get("trace"), run.get("peaks")
    if not trace or not trace.get("devices") or not peaks:
        return None
    by_kind = trace["devices"][0]["category_s"]
    mxu_s = by_kind.get("mxu", 0.0)
    if mxu_s <= 0 or by_kind.get("unknown", 0.0) > 0:
        return None
    shape = run["shape"]
    flop = (trace["train_steps"] * shape["train_flops_per_step"]
            + trace["eval_steps"] * shape["forward_flops_per_step"])
    least_s = flop / run["chips"] / (peaks["bf16_tflops"] * 1e12)
    return 100.0 * least_s / mxu_s
