"""How uneven the routing is: the busiest held expert's rows a step over
the mean over held experts (1 = even).

Layer: expert layer (``znicz_tpu/ops/moe.py``).  Source: the program's
counter ``moe_rows_by_expert`` in ``FusedTrainer.stats`` (counted on the
device in every step, pulled with the step's loss).
Nothing to read from a program without the counter.  Moves
``train_samples_per_s``.
"""


def read(run):
    rows = ((run.get("counters") or {}).get("fused_stats") or {}).get(
        "moe_rows_by_expert")
    if not rows or not rows.get("mean"):
        return None
    return rows["max"] / rows["mean"]
