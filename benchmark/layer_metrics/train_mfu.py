"""Share of the bf16 peak that the WHOLE STEP's needed operations reach
over the traced window's wall time.

Layer: train loop (``parallel/fused.py``).  Source: the device trace's
window on the host clock and the program's counter — every operation the
model needs in the traced window's train (x 3) and validation (x 1) steps:
projections, gate, router, shared expert, dense feed-forward and head by
the tokens, the experts by the rows actually routed, attention by the
admitted pairs (``benchmark/flops_decoder.py``; recomputation never
counted), over the peak in ``benchmark/peaks.json`` and the seconds
between the traced window's first and last epoch-end pull.  The bound a
later claim in this cell is held against.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import inner


def read(run):
    flops, peaks = inner.window_flops(run), run.get("peaks")
    seconds = (run.get("trace") or {}).get("host_window_s")
    if not flops or not peaks or not seconds:
        return None
    return 100.0 * flops["all"] / (peaks["bf16_tflops"] * 1e12) / (
        seconds * run.get("chips", 1))
