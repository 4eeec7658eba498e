"""Device time per step of the compressed-latent attention block: the
down-projections, the mixing, the core and the up-projection with its
merge — forward, recomputation and backward.

Layer: attention block (``znicz_tpu/decoder.py``, ``ops/cca.py``).
Source: the device trace — self time on device 0 under the scopes
``cca_down``, ``cca_mix``, ``attn_core`` and ``cca_up`` inside the decoder
layers' own (``benchmark/reduce/inner.py``), over the train and validation
steps of the traced window.  Nothing to read from a program without these
scopes.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_zaya

SCOPES = ("cca_down", "cca_mix", "attn_core", "cca_up")


def read(run):
    if flops_zaya.ms_per_step(run, ("cca_mix",)) is None:
        return None                 # another family's attention block
    return flops_zaya.ms_per_step(run, SCOPES)
