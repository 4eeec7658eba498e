"""Share of the bf16 peak that the held experts' NEEDED operations reach
while the grouped products run.

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the device trace
and the program's counter — ``3 x 2 x hidden x width`` operations a row
ACTUALLY routed to a held expert (``moe_rows_routed`` over the traced
window's steps, train and validation), forward + 2 x backward,
recomputation never counted (``benchmark/flops_decoder.py``), over the
peak in ``benchmark/peaks.json``, divided by the self time under the scope
``experts`` (``benchmark/reduce/inner.py``).  The same work whatever
implements it, so it cannot pass 100.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import inner


def read(run):
    return inner.roofline(run, "experts", "experts")
