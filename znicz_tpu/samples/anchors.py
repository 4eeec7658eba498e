"""Seeded trajectory anchors of the four reference samples.

The port's correctness record against ``BASELINE.json``: each sample at
its default configuration and seed 1013, CPU-pinned, ends inside a band
around the final it was recorded at.  A change to a unit's math that
moves a final out of its band fails ``tests/test_perf_guards.py::
test_anchor_bands_enforced`` until the band is re-centred on a
side-by-side of both formulations under the same seeds.  The widths
absorb jax-version and platform drift, not run-to-run noise.
"""

from __future__ import annotations

import importlib


def _epoch_finals(train_name, valid_name, valid_field, digits):
    """Finals of a Decision that keeps per-class epoch metrics: the last
    epoch's train loss and one field of its validation metrics."""
    def finals(decision) -> dict:
        from znicz_tpu.loader.base import TRAIN, VALID

        metrics = decision.epoch_metrics
        return {train_name: round(metrics[TRAIN]["loss"], 6),
                valid_name: round(metrics[VALID][valid_field], digits),
                "epochs": int(decision.epoch_number) + 1}
    return finals


gd_finals = _epoch_finals("final_train_loss", "valid_err_pct", "err_pct", 3)
mse_finals = _epoch_finals("final_train_mse", "valid_mse", "loss", 6)


def som_finals(decision) -> dict:
    return {"final_qerror": round(decision.epoch_qerror[-1], 6),
            "first_qerror": round(decision.epoch_qerror[0], 6),
            "epochs": len(decision.epoch_qerror)}


#: BASELINE config index -> (sample module, finals extractor)
SAMPLE_CONFIGS = {
    0: ("mnist", gd_finals),
    1: ("cifar", gd_finals),
    2: ("mnist_ae", mse_finals),
    3: ("kohonen", som_finals),
}

#: {config: {metric: (center, half_width)}}
ANCHOR_BANDS = {
    0: {"final_train_loss": (0.0109, 0.005), "valid_err_pct": (0.875, 0.5)},
    1: {"final_train_loss": (0.9501, 0.05), "valid_err_pct": (44.0, 1.5)},
    2: {"final_train_mse": (2.0818, 0.1), "valid_mse": (2.1689, 0.1)},
    3: {"final_qerror": (0.0505, 0.02)},
}


def check_anchor(config: int, vals: dict) -> list:
    """Out-of-band findings for one config's finals: a list of
    {metric, value, center, band} dicts (empty = all within band)."""
    out = []
    for metric, (center, half) in ANCHOR_BANDS[config].items():
        if abs(vals[metric] - center) > half:
            out.append({"metric": metric, "value": vals[metric],
                        "center": center, "band": half})
    return out


def measure(config: int):
    """Run one sample as configured under the anchors' seed; returns its
    finals and ``check_anchor``'s findings for them."""
    from znicz_tpu.core import prng

    name, finals = SAMPLE_CONFIGS[config]
    prng.reset(1013)
    wf = importlib.import_module(f"znicz_tpu.samples.{name}").run()
    vals = finals(wf.decision)
    return vals, check_anchor(config, vals)
