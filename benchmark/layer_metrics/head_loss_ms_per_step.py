"""Device time per step of the tied head and its loss, a block of rows at
a time: final norm, logits, log-sum-exp, target logit, argmax and the
block's gradient (taken with its forward pass), then the backward pass's
scaling.

Layer: head and loss (``znicz_tpu/decoder.py`` ``blocked_head_loss``).
Source: the device trace — self time on device 0 under the scope
``head_loss`` inside the head's own (``benchmark/reduce/inner.py``), over
the train and validation steps of the traced window.  Nothing to read from
a program whose head takes its logits whole.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_zaya


def read(run):
    return flops_zaya.ms_per_step(run, ("head_loss",))
