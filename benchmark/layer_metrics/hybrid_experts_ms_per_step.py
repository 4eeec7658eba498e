"""Device time per step of the expert layers: router (with the selection
bias's move), dispatch, the held experts' grouped products and what
stands between them, combine, and the shared expert — forward,
recomputation and backward.

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the device trace —
self time on device 0 under the scopes ``router``, ``dispatch``,
``experts``, ``combine`` and ``shared_expert`` inside the decoder layers'
own (``benchmark/reduce/inner.py``) PLUS the operations named
``ragged-dot*``: XLA's TPU compiler may turn ``jax.lax.ragged_dot`` into
kernels of its own that carry that name and no scope of the program's
(PERF.md section 6, PR 32).  Over the train and validation steps of the
traced window.  Nothing to read from a run of another model.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_nemotron

SCOPES = ("router", "dispatch", "experts", "combine", "shared_expert")


def read(run):
    return flops_nemotron.ms_per_step(
        run, lambda _u, i, _d: i in SCOPES, kernels="ragged-dot")
