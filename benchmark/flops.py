"""Operations a step needs, computed from the built network's shapes.

A copy of ``bench.analytic_train_flops`` (PERF.md, Open questions, lists
the original) plus the forward-only count.  Convention: 2 operations per
multiply-accumulate; the backward pass of a weighted layer is two more
products of the same size (one for the input's gradient, one for the
weights'), so a train step is 3 x forward.  Only convolutions and dense
layers count — the work the MXU does; elementwise, pooling, LRN and the
loss are left out (under 1 % for AlexNet-class networks), and recomputed
operations never count.
"""

from __future__ import annotations

import math


def forward_macs(forwards, batch: int) -> int:
    """Multiply-accumulates of one forward pass over ``batch`` samples,
    from the initialised layer shapes of ``workflow.forwards``."""
    macs = 0
    for f in forwards:
        if hasattr(f, "n_kernels") and hasattr(f, "kx"):    # convolution
            _, oh, ow, k = f.output.shape
            macs += batch * oh * ow * k * f.ky * f.kx * f.input.shape[-1]
        elif hasattr(f, "output_samples_number"):           # dense
            macs += batch * f.output_samples_number * math.prod(
                f.input.shape[1:])
    return int(macs)


def forward_flops(forwards, batch: int) -> int:
    return 2 * forward_macs(forwards, batch)


def train_flops(forwards, batch: int) -> int:
    return 3 * forward_flops(forwards, batch)
