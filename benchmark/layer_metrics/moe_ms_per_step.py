"""Device time per step of the expert layers: router, dispatch (sort and
gather), the held experts' grouped products, combine, and the shared
expert — forward, recomputation and backward.

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the device trace —
self time on device 0 under the scopes ``router``, ``dispatch``,
``experts``, ``combine`` and ``shared_expert`` inside the decoder layers'
own (``benchmark/reduce/inner.py``), over the train and validation steps of
the traced window; the earlier line ``{"phase": "scopes", "table":
"inner"}`` splits it.  Nothing to read from a program without these scopes
or where more than 5 % of the busy time carries no name.  Moves
``train_samples_per_s``.
"""

from benchmark.reduce import inner

SCOPES = ("router", "dispatch", "experts", "combine", "shared_expert")


def read(run):
    return inner.ms_per_step(run, lambda _u, i, _d: i in SCOPES)
