"""Device time per step of the top-1 expert layer past its router:
dispatch (sort and gather), the held experts' grouped products and what
stands between them, combine — forward, recomputation and backward.

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the device trace —
self time on device 0 under the scopes ``dispatch``, ``experts`` and
``combine`` inside the decoder layers' own (``benchmark/reduce/inner.py``)
PLUS the operations named ``ragged-dot*``: XLA's TPU compiler turns
``jax.lax.ragged_dot`` into kernels of its own that carry that name and no
scope of the program's (``experts_top1_roofline``), and they are most of
the layer.  Over the train and validation steps of the traced window.
The router is ``mlp_router_ms_per_step``'s.  Nothing to read from a run
of another model.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_zaya

SCOPES = ("dispatch", "experts", "combine")


def read(run):
    under = flops_zaya.ms_per_step(run, SCOPES) \
        if flops_zaya.of_run(run) else None
    if under is None:
        return None
    trace = run["trace"]
    steps = max(trace["train_steps"] + trace["eval_steps"], 1)
    return under + flops_zaya.kernel_seconds(run, "ragged-dot") / steps * 1e3
