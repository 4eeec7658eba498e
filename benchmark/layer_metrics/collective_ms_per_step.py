"""Time per train step in which a collective operation (the gradient
all-reduce) runs on device 0.

Layer: placement (``parallel/mesh.py``).  Source: the device trace — union
of the collective operations' intervals over the train steps in the traced
window.  The ``XLA Ops`` line holds a synchronous all-reduce in full and an
asynchronous one by its ``-start`` and ``-done`` (the wait), so this is the
time the step's own stream spends on collectives.  Nothing to read on one
chip.  Moves ``train_samples_per_s``.
"""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("devices") or run["chips"] < 2:
        return None
    return (trace["devices"][0]["collective_s"]
            / max(trace["train_steps"], 1) * 1e3)
