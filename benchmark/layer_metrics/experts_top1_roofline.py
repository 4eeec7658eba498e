"""Share of the bf16 peak that the held experts' NEEDED operations reach
while the grouped products run, ONE expert of width 2,048 a token.

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the device trace
and the program's counter — ``3 x 2 x hidden x width`` operations a row
ACTUALLY routed to a held expert (``moe_rows_routed`` over the traced
window's steps, train and validation), forward + 2 x backward,
recomputation never counted (``benchmark/flops_zaya.py``), over the peak
in ``benchmark/peaks.json``, divided by the self time of the expert
computation: the operations named ``ragged-dot*`` — XLA's TPU compiler
turns ``jax.lax.ragged_dot`` into kernels of its own that carry that name
and NO scope of the program's, so ``benchmark/reduce/inner.py`` files them
under the layer, not under ``experts`` (a first reading under the scope
alone gave 255 %: PERF.md section 6, PR 32) — PLUS the self time under the
scope ``experts`` (the masks around each product and the activation
between them); a lowering under another name falls under the scope again.
Nothing to read from a run of another model.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_zaya


def read(run):
    return flops_zaya.roofline(run, "experts", "experts",
                               kernels="ragged-dot")
