"""Device time per step of the untied head and its loss: final norm,
logits over the ids held (one block: 16,384 x 16,384 float32 is the block
rule's 1 GiB), log-sum-exp, target logit, argmax, and their backward.

Layer: head and loss (``znicz_tpu/decoder.py`` ``LMHead.apply_loss``).
Source: the device trace — self time on device 0 under the head's own
unit scope, every direction but the optimizer's, and under the scope
``loss`` (``benchmark/reduce/inner.py``; the driver names the head's unit
in ``shape.head_unit``), over the train and validation steps of the
traced window.  Nothing to read from a run of another model.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_nemotron


def read(run):
    head = (run.get("shape") or {}).get("head_unit")
    if not head:
        return None
    return flops_nemotron.ms_per_step(
        run, lambda u, _i, d: u in (head, "loss") and d != "update")
