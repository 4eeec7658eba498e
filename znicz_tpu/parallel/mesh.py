"""Device mesh construction — the rebuild's replacement for the reference's
master/slave topology (SURVEY.md §2.4): instead of a ZeroMQ star, an SPMD
mesh of TPU chips with named axes:

  - ``data``  — batch sharding (the reference's only strategy, made
    synchronous: psum over ICI instead of async pickle-over-TCP);
  - ``model`` — tensor-parallel sharding of wide FC layers (beyond-reference
    capability, used by AlexNet's fc layers when the mesh has a model axis).

Multi-host: call ``distributed_init()`` once per process before building the
mesh; jax.distributed wires DCN and ``jax.devices()`` becomes global.

This module is also the ONE home of the placement machinery both planes
share (ISSUE 18 — extracted from ``serving/model.py``'s PR 12 build-out):
mesh-from-config construction/refusals for serving AND training, the
``param_sharding`` rule (wide FC weights column-shard over ``model``),
params/velocities tree placement via ``global_put``, the batch
divisibility refusal, and direct per-shard segment staging.  Neither
``serving/model.py`` nor ``parallel/fused.py`` re-implements any of it.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np


def make_mesh(shape: Optional[Tuple[int, ...]] = None,
              axes: Sequence[str] = ("data",), devices=None):
    """Build a Mesh over ``devices`` (default: all).  shape=None puts every
    device on the first axis."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = (len(devs),) + (1,) * (len(axes) - 1)
    n = int(np.prod(shape))
    if n > len(devs):
        # a readable refusal instead of the raw XLA reshape failure:
        # on a CPU host the fix is virtual devices, and the operator
        # needs to know that BEFORE the first backend init
        raise ValueError(
            f"mesh shape {dict(zip(axes, shape))} needs {n} devices, "
            f"but jax sees only {len(devs)} "
            f"({jax.default_backend()} backend).  On a CPU host, "
            f"provision virtual devices BEFORE the first jax backend "
            f"init: znicz_tpu.virtdev.provision_cpu_devices({n}) or "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    grid = np.asarray(devs[:n]).reshape(shape)
    return Mesh(grid, tuple(axes))


def mesh_from_axes(dp, mp, plane: str = "mesh"):
    """Validate (data, model) axis sizes and build the mesh — or None for
    the 1x1 default, which keeps the caller on the exact single-device
    code path (bit-for-bit the pre-mesh behavior).  ``plane`` names the
    config tree in the refusal ("serving"/"training")."""
    dp, mp = int(dp), int(mp)
    if dp < 1 or mp < 1:
        raise ValueError(f"{plane} mesh axes must be >= 1, got "
                         f"data={dp} model={mp}")
    if dp * mp == 1:
        return None
    return make_mesh((dp, mp), ("data", "model"))


def serving_mesh_from_config():
    """The serving mesh per ``root.common.serving.mesh.*`` (read through
    a local alias so the config-knob lint tracks the keys), or None for
    the default 1x1."""
    from znicz_tpu.core.config import root

    mc = root.common.serving.mesh
    return mesh_from_axes(mc.get("data", 1), mc.get("model", 1), "serving")


def train_mesh_from_config():
    """The TRAINING mesh per ``root.common.engine.mesh.*`` — gated on
    ``root.common.engine.train_shard`` (default OFF: a slave without the
    gate is bit-for-bit the single-device slave, whatever the mesh knobs
    say).  None when gated off or 1x1."""
    from znicz_tpu.core.config import root

    if not root.common.engine.get("train_shard", False):
        return None
    mc = root.common.engine.mesh
    return mesh_from_axes(mc.get("data", 1), mc.get("model", 1), "training")


def mesh_shape_dict(mesh) -> Optional[Dict[str, int]]:
    """``{"data": dp, "model": mp}`` — the heartbeat/panel form of a
    mesh; None when single-device."""
    if mesh is None:
        return None
    return {str(a): int(mesh.shape[a]) for a in mesh.axis_names}


def data_sharding(mesh):
    """Batch-dim sharding over the ``data`` axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("data"))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def column_sharded(mesh):
    """(out, in) weight sharded by output columns over ``model`` —
    tensor parallelism for wide FC layers."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("model", None))


def param_sharding(mesh, arr, tp_threshold: int = 1024):
    """The ONE per-param placement rule (training and serving): wide
    (out, in) FC weights shard their output rows over the ``model`` axis
    (and the matching 1-D bias over ``model``); everything else
    replicates.  XLA/GSPMD propagates the activation shardings and
    inserts the collectives."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    if ("model" in mesh.axis_names
            and mesh.shape["model"] > 1
            and int(arr.shape[0]) >= tp_threshold
            and int(arr.shape[0]) % mesh.shape["model"] == 0):
        ndim = getattr(arr, "ndim", len(arr.shape))
        if ndim == 2:
            return NamedSharding(mesh, P("model", None))
        if ndim == 1:
            return NamedSharding(mesh, P("model"))
    return NamedSharding(mesh, P())


def tree_shardings(mesh, tree, tp_threshold: int = 1024):
    """NamedSharding tree for a two-level {unit: {param: leaf}} tree per
    ``param_sharding`` (leaves need only ``.shape``)."""
    return {name: {k: param_sharding(mesh, a, tp_threshold)
                   for k, a in layer.items()}
            for name, layer in tree.items()}


def place_tree(mesh, tree, tp_threshold: int = 1024):
    """Distribute a params/velocities tree onto the mesh per its
    shardings (``global_put``: each process contributes only the shards
    it owns — no device-0 round trip on multi-host)."""
    return {name: {k: global_put(a, param_sharding(mesh, a, tp_threshold))
                   for k, a in layer.items()}
            for name, layer in tree.items()}


def require_batch_divisible(rows: int, mesh) -> int:
    """The batch-vs-data-axis divisibility refusal (explicit sharded
    placement cannot pad); returns dp.  Shared by serving's stage and
    the training staging path."""
    dp = int(mesh.shape["data"])
    if int(rows) % dp:
        raise ValueError(
            f"batch of {rows} rows does not divide across "
            f"the mesh's data axis (dp={dp}); pad to a ladder rung "
            f"(rungs are snapped to multiples of dp)")
    return dp


def segment_sharding(mesh):
    """Staged (K, B, ...) segment tensors shard the BATCH dim:
    ``P(None, "data")`` — sliced per scan step, each (B, ...) minibatch
    keeps its ``data`` sharding with no resharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(None, "data"))


def put_sharded_segment(shape, sharding, gather, idx_mat):
    """Assemble + place ONE staged (K, B, ...) segment batch-sharded,
    DIRECTLY from the host (one transfer per device shard, never a
    gather through device 0).  In a MULTI-CONTROLLER run each process
    host-gathers ONLY the rows of the batch shards its own devices hold
    (jax.make_array_from_callback) — the SPMD analogue of the
    reference's per-slave minibatch feed: no host ever touches another
    host's samples."""
    import jax

    n_steps = int(idx_mat.shape[0])
    if jax.process_count() == 1:
        flat = idx_mat.reshape(-1)
        return jax.device_put(gather(flat).reshape(shape), sharding)

    def cb(index):
        # index: per-shard slices over (step, batch, *sample); only the
        # batch dim is sharded — gather exactly those rows
        ks = range(*index[0].indices(n_steps))
        rows = np.stack([gather(idx_mat[k, index[1]]) for k in ks])
        return rows[(slice(None), slice(None)) + tuple(index[2:])]

    return jax.make_array_from_callback(shape, sharding, cb)


def global_put(value, sharding):
    """``jax.device_put`` that also works when the sharding's mesh spans
    PROCESSES (multi-host): every process contributes the shards it owns
    from its host-replicated ``value`` via make_array_from_callback, so no
    cross-host device transfer is needed (jax refuses plain device_put to
    non-addressable devices).  Single-process meshes take the plain put."""
    import jax

    if jax.process_count() == 1:
        return jax.device_put(value, sharding)

    def put_leaf(v):
        if isinstance(v, jax.Array) and v.sharding == sharding:
            return v                     # already globally placed
        v = np.asarray(v)
        return jax.make_array_from_callback(v.shape, sharding,
                                            lambda idx, v=v: v[idx])

    return jax.tree_util.tree_map(put_leaf, value)


def distributed_init(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host bring-up over DCN (the reference's master/slave handshake
    collapses to jax.distributed).  No-op when single-process."""
    import jax

    if num_processes and num_processes > 1:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
