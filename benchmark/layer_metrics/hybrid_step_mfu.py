"""Share of the bf16 peak that the WHOLE STEP's needed operations reach
over the traced window's wall time, for a decoder whose layers are a
state-space mixer, an attention block or an expert layer alone.

Layer: train loop (``parallel/fused.py``).  Source: the device trace's
window on the host clock and the program's counter — every operation the
model needs in the traced window's train (x 3) and validation (x 1) steps:
the state-space layers' projections, convolution and scan, the attention
layer's projections, router, shared experts and head by the tokens, the
routed experts by the rows actually routed, the core by the admitted
pairs (``benchmark/flops_nemotron.py``; recomputation never counted), over
the peak in ``benchmark/peaks.json`` and the seconds between the traced
window's first and last epoch-end pull.  The share of the whole step that
bounds a later claim in this cell.  Nothing to read from a run of another
model.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_nemotron


def read(run):
    flops, peaks = flops_nemotron.of_run(run), run.get("peaks")
    seconds = (run.get("trace") or {}).get("host_window_s")
    if not flops or not peaks or not seconds:
        return None
    return 100.0 * flops["all"] / (peaks["bf16_tflops"] * 1e12) / (
        seconds * run.get("chips", 1))
