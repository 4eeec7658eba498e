"""Share of the bf16 peak that the head's NEEDED operations reach while
the blocked head and loss run.

Layer: head and loss (``znicz_tpu/decoder.py`` ``blocked_head_loss``).
Source: the device trace — ``2 x ids held x hidden`` operations a token,
forward + 2 x backward (``benchmark/flops_zaya.py``), over the peak in
``benchmark/peaks.json``, divided by the self time under the scope
``head_loss`` (``benchmark/reduce/inner.py``).  Nothing to read from a
program whose head takes its logits whole.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_zaya


def read(run):
    return flops_zaya.roofline(run, "head", "head_loss")
