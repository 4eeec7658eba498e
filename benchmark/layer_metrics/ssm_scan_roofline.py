"""Share of its roofline that the causal convolution and the scan reach
while they run.

Layer: state-space mixer (``znicz_tpu/ops/ssm.py``).  Source: the device
trace — the least time the chip could take for them, the GREATER of the
operations they need over the bf16 peak (``benchmark/peaks.json``) and the
bytes they must read and write once over the HBM peak
(``benchmark/peaks_hbm.json``), divided by the self time under the scopes
``ssm_conv`` and ``ssm_scan`` (``benchmark/reduce/inner.py``).  Both
counts come from SHAPES (``benchmark/flops_nemotron.py``: ``2 x
conv_kernel`` a convolved channel and ``2 Q N G + 2 Q P H + 4 P N H`` a
token for the scan in chunks of ``Q``, forward + 2 x backward; ``xBC`` and
``dt`` read and ``y`` written forward, those and ``y``'s cotangent read
and two cotangents written backward, in the compute dtype), so a scan
composed of XLA operations and one in a kernel read the same work.
Recomputation is time, not work.  Nothing to read from a program without
these scopes.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_nemotron

SCOPES = ("ssm_conv", "ssm_scan")


def read(run):
    return flops_nemotron.roofline(
        run, "scan", lambda _u, i, _d: i in SCOPES, bytes_part="scan_bytes")
