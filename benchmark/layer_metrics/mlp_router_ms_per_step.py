"""Device time per step of the MLP router: the down-projection to the
router's width, the state carried from the layer before, its norm, the
MLP, the softmax and the choice — forward, recomputation and backward.

Layer: expert layer (``znicz_tpu/ops/moe.py`` ``router_state`` /
``route_mlp``).  Source: the device trace — self time on device 0 under the
scope ``router`` inside the decoder layers' own
(``benchmark/reduce/inner.py``), over the train and validation steps of
the traced window.  Nothing to read from a run whose router keeps no
state.  Moves ``train_samples_per_s``.
"""

from benchmark import flops_zaya


def read(run):
    stats = (run.get("counters") or {}).get("fused_stats") or {}
    if "router_states_carried" not in stats:
        return None
    return flops_zaya.ms_per_step(run, ("router",))
