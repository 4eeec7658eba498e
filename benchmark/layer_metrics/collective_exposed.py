"""Share of the collective time during which no compute operation runs on
that device: what the all-reduce costs after overlap.

Layer: placement (``parallel/mesh.py``).  Source: the device trace, device
0.  Nothing to read on one chip or where no collective ran.  Moves
``train_samples_per_s``.
"""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("devices") or run["chips"] < 2:
        return None
    d0 = trace["devices"][0]
    if d0["collective_s"] <= 0:
        return None
    return 100.0 * d0["collective_exposed_s"] / d0["collective_s"]
