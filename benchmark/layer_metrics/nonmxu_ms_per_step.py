"""Device time per step of everything that is not a convolution, a matrix
product or a collective: activations, LRN, pooling, the optimizer's update,
data movement.

Layer: train kernels (XLA fusions).  Source: the device trace — self time
of the operations in the ``other`` category on device 0 over the steps in
the traced window.  Nothing to read where a fusion's content is unknown.
Moves ``train_samples_per_s``.
"""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("devices"):
        return None
    by_kind = trace["devices"][0]["category_s"]
    if by_kind.get("unknown", 0.0) > 0:
        return None
    steps = trace["train_steps"] + trace["eval_steps"]
    return by_kind.get("other", 0.0) / steps * 1e3
