"""Device idle time per step: what the train loop leaves between programs.

Layer: train loop (``parallel/fused.py``).  Source: the device trace —
the traced window of device 0 minus the union of its operations'
intervals, over the train and validation steps dispatched in that window.
Moves ``train_samples_per_s``.
"""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("devices"):
        return None
    d0 = trace["devices"][0]
    steps = trace["train_steps"] + trace["eval_steps"]
    return (d0["window_s"] - d0["busy_s"]) / steps * 1e3
