"""Device time per step of AdamW over the 667 M parameters of the share:
two moments and the master weight read and written once each, and the
selection biases' moves.

Layer: optimizer (``znicz_tpu/nn_units.py`` ``adamw_update``).  Source:
the device trace — self time on device 0 under the scopes ``update/*``,
read by the reader of ``optimizer_ms_per_step`` (one quantity, one
reading; that metric lists the Laguna cell and a test of the accepted
benchmark holds its list, so this cell reports it under a name of its
own: PERF.md section 7 asks a ``benchmark`` PR to fold the three).
Nothing to read from a run of another model.  Moves
``train_samples_per_s``.
"""

from benchmark import flops_nemotron, spec


def read(run):
    if not flops_nemotron.of_run(run):
        return None
    return spec.load_module("layer_metrics", "optimizer_ms_per_step").read(
        run)
