"""Host time to launch one train segment: staging where the data is
staged, the small ``put``s, and the call into the compiled scan or step
until it returns (the device works on; its time is in the ``sync`` spans).

Layer: train loop (``parallel/fused.py`` ``_run_segmented``).  Source: the
program's own spans, read from the profiler's trace where ``TraceRing.span``
put them (``benchmark/reduce/scopes.py``) — the median duration of the
``znicz:train:dispatch:*`` spans that start inside device 0's traced
window.  Nothing to read from a program without these spans, nor — like
the other readers of ``reduce/scopes.py`` — where more than 5 % of the
device's busy time carries no name.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import scopes


def read(run):
    reduction = scopes.named(run)
    if not reduction:
        return None
    return reduction.get("dispatch_ms_median")
