"""Device time per step of local response normalisation and pooling,
forward and backward: ``reduce-window``, ``select-and-scatter`` and the
elementwise passes around them.

Layer: train kernels (XLA fusions).  Source: the device trace — self time on
device 0 of the operations whose scope (``jax.named_scope``: the forward
unit's name, which the standard workflow derives from the layer type;
``benchmark/reduce/scopes.py``) is a unit with ``norm`` or ``pool`` in its
name, every direction, over the train and validation steps of the traced
window.  A fusion that spans such a unit and a neighbour (an LRN pass fused
into a convolution's epilogue) is in ``mixed`` and not here: the earlier
line ``{"phase": "scopes"}`` says how much.  Nothing to read where more than
5 % of the busy time carries no name.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import scopes


def read(run):
    reduction = scopes.named(run)
    if not reduction:
        return None
    trace = run["trace"]
    steps = trace["train_steps"] + trace["eval_steps"]
    seconds = scopes.scope_seconds(
        reduction, lambda unit: "norm" in unit or "pool" in unit)
    return seconds / max(steps, 1) * 1e3 if seconds else None
