"""Device time of the input path per dispatched program: gathering the
minibatch's rows from the resident set, decoding them, and the cast to the
compute dtype — which on the resident-f32 route is a cast of the *whole
shard*, paid once per program launched and not once per step.

Layer: input path (``parallel/fused.py`` ``_gather``/``_decode``).  Source:
the device trace — self time of the operations the compiled programs name
``input`` (``jax.named_scope``; ``benchmark/reduce/scopes.py``) on device 0,
over the executions of the programs that hold such a scope (train scans and
steps, evaluation steps and scans: the ``XLA Modules`` events).  Nothing to
read where more than 5 % of the busy time carries no name (a program from
before the scopes, or fetched from a compile cache that predates them).
Moves ``train_samples_per_s``.
"""

from benchmark.reduce import scopes


def read(run):
    reduction = scopes.named(run)
    if not reduction or not reduction["input_executions"]:
        return None
    return (scopes.scope_seconds(reduction, lambda unit: unit == "input")
            / reduction["input_executions"] * 1e3)
