"""Device time per step of the state-space mixers: the in-projection, the
causal convolution, the chunked scan, the gated norm and the
out-projection — forward, recomputation and backward.

Layer: state-space mixer (``znicz_tpu/decoder.py`` ``_mix_scan``,
``ops/ssm.py``).  Source: the device trace — self time on device 0 under
the scopes ``ssm_in``, ``ssm_conv``, ``ssm_scan`` and ``ssm_out`` inside
the decoder layers' own (``benchmark/reduce/inner.py``), over the train
and validation steps of the traced window.  Nothing to read from a
program without these scopes.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_nemotron

SCOPES = ("ssm_in", "ssm_conv", "ssm_scan", "ssm_out")


def read(run):
    return flops_nemotron.ms_per_step(run, lambda _u, i, _d: i in SCOPES)
