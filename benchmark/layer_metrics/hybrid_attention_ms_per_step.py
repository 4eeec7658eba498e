"""Device time per step of the attention layer: norm and projections, the
core (32 query heads over 2 KV heads, no rotation) and the
out-projection — forward, recomputation and backward.

Layer: attention core (``znicz_tpu/decoder.py`` ``_attend_plain``,
``ops/attention.py``).  Source: the device trace — self time on device 0
under the scopes ``attn_qkv``, ``attn_core`` and ``attn_out`` inside the
decoder layers' own (``benchmark/reduce/inner.py``), over the train and
validation steps of the traced window.  Nothing to read from a run of
another model.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_nemotron

SCOPES = ("attn_qkv", "attn_core", "attn_out")


def read(run):
    return flops_nemotron.ms_per_step(run, lambda _u, i, _d: i in SCOPES)
