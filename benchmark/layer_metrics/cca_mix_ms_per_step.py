"""Device time per step of what no other model's attention has: both
causal convolutions over queries and keys, the query-key mean, the value
shift, the normalisation with its temperature, and rotary — forward,
recomputation and backward.

Layer: attention block (``znicz_tpu/ops/cca.py``).  Source: the device
trace — self time on device 0 under the scope ``cca_mix`` inside the
decoder layers' own (``benchmark/reduce/inner.py``), over the train and
validation steps of the traced window.  Nothing to read from a program
without the scope.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_zaya


def read(run):
    return flops_zaya.ms_per_step(run, ("cca_mix",))
