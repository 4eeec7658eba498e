"""Device time per step of the optimizer's update of every tensor (AdamW:
two moments and the master weight read and written once each).

Layer: optimizer (``znicz_tpu/nn_units.py`` ``adamw_update``).  Source:
the device trace — self time on device 0 under the scopes ``update/*``
(``benchmark/reduce/inner.py``), over the train and validation steps of
the traced window.  Moves ``train_samples_per_s``.
"""

from benchmark.reduce import inner


def read(run):
    return inner.ms_per_step(run, lambda _u, _i, d: d == "update")
