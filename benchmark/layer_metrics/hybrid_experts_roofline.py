"""Share of the bf16 peak that the held experts' NEEDED operations reach
while the grouped products run: SIX experts of width 1,856 a token, two
matrices each.

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the device trace
and the program's counter — ``2 x 2 x hidden x width`` operations a row
ACTUALLY routed to a held expert (``moe_rows_routed`` over the traced
window's steps, train and validation), forward + 2 x backward,
recomputation never counted (``benchmark/flops_nemotron.py``), over the
peak in ``benchmark/peaks.json``, divided by the self time of the expert
computation: the operations named ``ragged-dot*`` (the compiler's own
kernels, which carry no scope of the program's) PLUS the self time under
the scope ``experts`` (the masks around each product and the activation
between them).  Nothing to read from a run of another model.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_nemotron


def read(run):
    return flops_nemotron.roofline(
        run, "experts", lambda _u, i, _d: i == "experts",
        kernels="ragged-dot")
