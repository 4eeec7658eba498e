"""Device time per step of the attention core (scores, softmax, weighted
sum; window and full layers together), forward, recomputation and
backward.

Layer: attention core (``znicz_tpu/ops/attention.py``
``blocked_attention``).  Source: the device trace — self time on device 0
under the scope ``attn_core`` inside the decoder layers' own
(``benchmark/reduce/inner.py``), over the train and validation steps of
the traced window; the earlier line ``{"phase": "scopes", "table":
"inner"}`` splits it into window and full layers
(``attn_core_ms_per_step``).  Nothing to read from a program without the
scope.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import inner


def read(run):
    return inner.ms_per_step(run, lambda _u, i, _d: i == "attn_core")
