"""Device idle time at an epoch's end: what the chip waits for while the
trainer evaluates the last minibatch, pulls its values, lets the Decision
rule, dispatches the last update and runs the epoch-end hook.

Layer: train loop (``parallel/fused.py`` ``_run_segmented``).  Source: the
program's own spans, read from the profiler's trace where ``TraceRing.span``
put them (``znicz:train:*``; ``benchmark/reduce/scopes.py``) — the idle gaps
of device 0 (10 us and longer) that fall inside a ``tail`` span, averaged
over the tails the traced window holds whole, plus those inside an
``epoch_hook`` span, averaged over the hooks.  The earlier line
``{"phase": "scopes"}`` splits it by leaf (``tail_eval``, ``sync``,
``decide``, ``tail_update``, ``snapshot_copy``).  Nothing to read from a
program without these spans, nor — like the other readers of
``reduce/scopes.py`` — where more than 5 % of the device's busy time carries
no name.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import scopes


def read(run):
    reduction = scopes.named(run)
    if not reduction or not reduction.get("tail_spans_in_trace"):
        return None
    return sum(reduction["tail_idle_s_by_leaf"].values()) * 1e3
