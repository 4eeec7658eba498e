"""How uneven top-6 routing is: the busiest held expert's rows a step
over the mean over held experts (1 = even), the layers' extreme, averaged
over the run's steps.  The load-driven selection bias works on it
(``correct`` bounds it: ``routing.max_over_mean`` in the configuration's
file).

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the program's
counter ``moe_rows_by_expert`` in ``FusedTrainer.stats``, read by the
reader of ``moe_rows_max_over_mean`` (one quantity, one reading).  Nothing
to read from a program without the counter or without state-space layers.
Moves ``train_samples_per_s``.
"""

from benchmark import spec


def read(run):
    stats = (run.get("counters") or {}).get("fused_stats") or {}
    if "layers_mamba" not in stats:
        return None
    return spec.load_module("layer_metrics", "moe_rows_max_over_mean").read(
        run)
