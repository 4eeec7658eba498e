"""Device time per step of the causal convolution and the chunked scan
alone — what no matrix unit bounds — forward, recomputation and backward.

Layer: state-space mixer (``znicz_tpu/ops/ssm.py`` ``causal_conv``,
``chunked_scan``).  Source: the device trace — self time on device 0
under the scopes ``ssm_conv`` and ``ssm_scan``
(``benchmark/reduce/inner.py``), over the train and validation steps of
the traced window.  Nothing to read from a program without these scopes.
Moves ``train_samples_per_s``.
"""

from benchmark import flops_nemotron

SCOPES = ("ssm_conv", "ssm_scan")


def read(run):
    return flops_nemotron.ms_per_step(run, lambda _u, i, _d: i in SCOPES)
