"""Pod-scale sharded serving (ISSUE 13): the mesh-native ModelRunner.

Covers: dp-snapped bucket ladders + readable non-divisible refusals,
per-device shard shapes (rows/dp on every data-axis device, staged AND
computed), the 0-ULP batch-independence contract WITHIN a mesh, the
cross-mesh parity band (1x1 vs 4x1 vs 2x2 — reduction tiling is
layout-dependent, so cross-LAYOUT parity is numerical, exactly the
reason PR 4 pinned its 0-ULP contract per bucket executable),
zero-recompiles on the sharded path, swap/rollback placement +
generation stamps, the stage copy-skip counter, capacity-weighted
balancer dispatch, and the e2e sharded service.  Soaks ride behind the
``slow`` marker.

Runs on the 8 virtual CPU devices conftest provisions (virtdev.py)."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.config import root

#: cross-layout parity band, relative to max|y| per rung (measured
#: ~1e-6 reduction-order noise on this stack; a real math divergence
#: lands orders of magnitude higher)
PARITY_REL = 1e-5


def _tiny_mnist_wf(n_train=120, layers=None):
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    if layers is not None:
        root.mnist.layers = list(layers)
    try:
        wf = mnist.MnistWorkflow()
    finally:
        root.mnist.layers = [100, 10]
    wf.initialize(device=None)
    return wf


def _mesh(dp, mp=1):
    from znicz_tpu.parallel.mesh import make_mesh

    return make_mesh((dp, mp), ("data", "model"))


def _pad(x, b):
    out = np.zeros((b,) + x.shape[1:], np.float32)
    out[:len(x)] = x
    return out


@pytest.fixture
def serving_mesh():
    """Set ``root.common.serving.mesh.*`` for a test and restore the
    (absent -> 1x1) default after — the global config tree must not
    leak a mesh into the rest of the suite."""
    def set_mesh(dp, mp=1):
        root.common.serving.mesh.data = int(dp)
        root.common.serving.mesh.model = int(mp)
    yield set_mesh
    delattr(root.common.serving, "mesh")


# -- ladder snapping + readable refusals --------------------------------------


def test_ladder_dp_snapping_and_mesh_refusals():
    from znicz_tpu.parallel.mesh import make_mesh
    from znicz_tpu.serving import BucketLadder

    # default rungs snap UP to multiples of dp (then dedupe)
    assert BucketLadder(32, dp=4).rungs == [4, 8, 16, 32]
    assert BucketLadder(8, dp=4).rungs == [4, 8]
    assert BucketLadder(24, dp=4).rungs == [4, 8, 16, 24]
    assert BucketLadder(32).rungs == [1, 2, 4, 8, 16, 32]  # dp=1 intact
    # explicit rungs that cannot split are refused readably
    with pytest.raises(ValueError, match="divide across"):
        BucketLadder(8, rungs=[2, 8], dp=4)
    # a max_batch that cannot split is refused at construction
    with pytest.raises(ValueError, match="multiple of dp"):
        BucketLadder(30, dp=4)
    # make_mesh refuses an over-sized mesh with the virtdev recipe in
    # the message, not a raw reshape failure (ISSUE 13 satellite)
    with pytest.raises(ValueError) as exc:
        make_mesh((16, 2), ("data", "model"))
    msg = str(exc.value)
    assert "provision_cpu_devices" in msg and "XLA_FLAGS" in msg


# -- the sharded runner contract ----------------------------------------------


def test_sharded_runner_shapes_parity_recompiles(serving_mesh):
    """One 1024-wide workflow, three layouts: shard shapes exact, 0-ULP
    batch independence within each mesh, cross-mesh parity in band,
    zero recompiles over a mixed stream, column-sharded FC weights on
    the model axis, and the e2e service under the mesh config."""
    from jax.sharding import PartitionSpec as P

    from znicz_tpu.serving import (BucketLadder, InferenceClient,
                                   InferenceServer, ModelRunner)

    wf = _tiny_mnist_wf(layers=[1024, 10])   # >= tp_threshold: the
    # model axis engages on the first FC layer
    rng = np.random.default_rng(7)
    x8 = rng.normal(0, 1, (8, 784)).astype(np.float32)
    ref = ModelRunner(wf)
    ref_y = {r: ref.infer(x8[:r]) for r in (2, 4, 8)}

    for dp, mp in ((4, 1), (2, 2)):
        runner = ModelRunner(wf, mesh=_mesh(dp, mp))
        assert runner.data_parallel == dp
        assert runner.device_count == dp * mp
        assert runner.mesh_shape == {"data": dp, "model": mp}
        ladder = BucketLadder(8, dp=dp)
        warm = runner.warmup(ladder)
        assert warm == len(ladder.rungs)
        if mp > 1:
            # the wide FC weight is column-sharded over ``model``
            specs = [leaf.sharding.spec
                     for layer in runner.params.values()
                     for leaf in layer.values()
                     if leaf.shape and leaf.shape[0] == 1024]
            assert P("model", None) in specs
        for rung in ladder:
            staged = runner.stage(x8[:rung])
            shards = [s.data.shape for s in staged.addressable_shards]
            assert len(shards) == dp * mp
            assert all(s[0] == rung // dp for s in shards)
            y_dev, gen = runner.infer_staged(staged)
            assert gen == 1
            assert all(s.data.shape[0] == rung // dp
                       for s in y_dev.addressable_shards)
            # cross-mesh parity: numerical band, per rung
            y = np.asarray(y_dev)[:rung]
            rel = np.max(np.abs(y - ref_y[rung])) \
                / max(np.max(np.abs(ref_y[rung])), 1e-30)
            assert rel <= PARITY_REL, (dp, mp, rung, rel)
        # 0-ULP batch independence WITHIN this mesh: coalescing,
        # offset and pad content cannot perturb a request's rows
        alone = [runner.infer(_pad(p, 8))[:len(p)]
                 for p in (x8[:5], x8[5:])]
        together = runner.infer(x8)
        assert np.array_equal(together[:5], alone[0])
        assert np.array_equal(together[5:], alone[1])
        garbage = _pad(x8[:5], 8)
        garbage[5:] = 1e9
        assert np.array_equal(runner.infer(garbage)[:5], alone[0])
        # mixed-size stream: every size pads to a rung, zero recompiles
        c0, j0 = runner.compiles, runner.jit_cache_size()
        for n in (1, 3, 8, 5, 2, 7, 4, 6):
            runner.infer(_pad(x8[:n], ladder.bucket_for(n)))
        assert runner.compiles == c0
        if j0 is not None:
            assert runner.jit_cache_size() == j0

    # e2e: the service built under the mesh CONFIG snaps its ladder,
    # serves mixed sizes bit-exactly vs its own runner, recompiles
    # nothing, and heartbeats its capacity
    serving_mesh(4, 1)
    srv = InferenceServer(wf, max_batch=8, max_delay_ms=2.0,
                          queue_bound=64).start()
    cli = InferenceClient(srv.endpoint, timeout=30)
    try:
        assert srv.runner.data_parallel == 4
        assert srv.batcher.ladder.rungs == [4, 8]
        compiles_warm = srv.runner.compiles
        for n in (1, 3, 8, 5):
            x = x8[:n]
            y = cli.infer(x)
            ref_b = srv.runner.infer(
                srv.runner.pad(x, srv.batcher.ladder.bucket_for(n)))[:n]
            assert np.array_equal(y, ref_b)
        assert srv.runner.compiles == compiles_warm
        hb = srv.heartbeat_payload()
        assert hb["device_count"] == 4
        assert hb["mesh"] == {"data": 4, "model": 1}
        assert srv.stats()["model"]["mesh"] == {"data": 4, "model": 1}
    finally:
        cli.close()
        srv.stop()


def test_sharded_swap_rollback_placement_and_stage_copies(
        tmp_path, serving_mesh):
    from jax.sharding import NamedSharding

    from znicz_tpu.serving import BucketLadder, ModelRunner

    wf = _tiny_mnist_wf()
    wf.snapshotter.directory = str(tmp_path)
    path_a = wf.snapshotter.save("gen1")
    runner = ModelRunner(wf, mesh=_mesh(4))
    ladder = BucketLadder(8, dp=4)
    runner.warmup(ladder)
    rng = np.random.default_rng(23)
    x = rng.normal(0, 1, (8, 784)).astype(np.float32)
    y1 = runner.infer(x)

    # perturb + save gen2 (bit-distinguishable outputs)
    for f in wf.forwards:
        for k, a in f.params().items():
            a.mem = np.asarray(a.map_read()) * np.float32(1.25) \
                + np.float32(0.01)
    path_b = wf.snapshotter.save("gen2")

    compiles = runner.compiles
    runner.swap(path_b, ladder)
    assert runner.compiles == compiles    # warm = sharded cache hits
    assert runner.generation == 2
    # the NEW tree landed in mesh placement: every leaf carries a
    # NamedSharding on THIS runner's mesh (replicated or model-sharded)
    for layer in runner.params.values():
        for leaf in layer.values():
            assert isinstance(leaf.sharding, NamedSharding)
            assert leaf.sharding.mesh == runner.mesh
    y2 = runner.infer(x)
    assert not np.array_equal(y1, y2)     # generations distinguishable
    # results still split rows/dp after the swap
    y_dev, gen = runner.infer_staged(runner.stage(x))
    assert gen == 2
    assert all(s.data.shape[0] == 2 for s in y_dev.addressable_shards)

    gen = runner.rollback()
    assert gen == 1 and runner.generation == 1
    assert runner.snapshot_path == path_a or runner.snapshot_path == ""
    assert np.array_equal(runner.infer(x), y1)    # bit-exact restore
    for layer in runner.params.values():
        for leaf in layer.values():
            assert leaf.sharding.mesh == runner.mesh

    # stage copy-skip satellite: a contiguous right-dtype batch stages
    # with NO host copy; strided or wrong-dtype input pays one, counted
    before = runner.stage_copies
    runner.stage(np.ascontiguousarray(x, runner.dtype))
    assert runner.stage_copies == before
    runner.stage(x[::2])                  # strided view: must copy
    assert runner.stage_copies == before + 1
    runner.stage(x.astype(np.float64))    # wrong dtype: must copy
    assert runner.stage_copies == before + 2
    # non-divisible batches are refused readably, not an XLA error
    with pytest.raises(ValueError, match="does not divide"):
        runner.stage(np.zeros((6, 784), np.float32))


# -- capacity-weighted fleet dispatch (ISSUE 13 satellite) --------------------


def test_balancer_capacity_weighted_dispatch_and_mesh_column():
    from znicz_tpu.serving import ReplicaBalancer
    from znicz_tpu.web_status import WebStatus

    bal = ReplicaBalancer(bind="tcp://127.0.0.1:*")

    def member(endpoint, queue_depth, device_count, mesh=None):
        return {"endpoint": endpoint, "last_seen": time.perf_counter(),
                "ready": True, "gen": 1, "queue_depth": queue_depth,
                "swapping": False, "draining": False,
                "snapshot_path": "", "device_count": device_count,
                "mesh": mesh, "p99_ms_by_bucket": {}}

    with bal._lock:
        # same raw queue depth, 8x the capacity: the pod slice must
        # rank FIRST (load normalized by device count), where the old
        # raw-sum ranking would have tied and round-robined
        bal._members["pod8"] = member(
            "tcp://127.0.0.1:7001", 4, 8, {"data": 4, "model": 2})
        bal._members["chip1"] = member("tcp://127.0.0.1:7002", 4, 1)
        order = bal._candidates()
    assert order[0] == "pod8"
    with bal._lock:
        # capacity-normalized, not absolute: 16 rows on 8 chips (2 per
        # chip) still beats 3 rows on one chip
        bal._members["pod8"]["queue_depth"] = 16
        bal._members["chip1"]["queue_depth"] = 3
        order = bal._candidates()
    assert order[0] == "pod8"
    # the fleet panel shows the mesh column
    stats = bal.stats()
    by_id = {m["replica_id"]: m for m in stats["replicas"]}
    assert by_id["pod8"]["mesh"] == {"data": 4, "model": 2}
    assert by_id["chip1"]["device_count"] == 1
    status = WebStatus(port=0).start()
    try:
        status.register_balancer(bal)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{status.port}/") as r:
            page = r.read().decode()
        assert "<th>mesh</th>" in page and "4x2 (8d)" in page
    finally:
        status.stop()
    # a legacy heartbeat without device_count defaults to 1 (no crash)
    with bal._lock:
        del bal._members["pod8"]["device_count"]
        assert bal._candidates()


# -- soak (slow) --------------------------------------------------------------


@pytest.mark.slow
def test_sharded_service_rollover_soak(tmp_path, serving_mesh):
    """Sustained mixed-size load on a {data:4} service with a swap and
    a rollback mid-stream: every reply bit-matches its stamped
    generation's per-rung oracle, nothing is lost, and the mixed
    stream + two rollovers cause zero recompiles."""
    from znicz_tpu.serving import InferenceClient, InferenceServer

    wf = _tiny_mnist_wf()
    wf.snapshotter.directory = str(tmp_path)
    serving_mesh(4, 1)
    srv = InferenceServer(wf, max_batch=8, max_delay_ms=1.0,
                          queue_bound=64).start()
    rng = np.random.default_rng(31)
    x1 = rng.normal(0, 1, (1, 784)).astype(np.float32)
    refs = {1: {b: srv.runner.infer(srv.runner.pad(x1, b))[:1]
                for b in srv.batcher.ladder.rungs}}
    for f in wf.forwards:
        for k, a in f.params().items():
            a.mem = np.asarray(a.map_read()) * np.float32(1.25) \
                + np.float32(0.01)
    path_b = wf.snapshotter.save("gen2")
    compiles_warm = srv.runner.compiles
    cli = InferenceClient(srv.endpoint, timeout=60)
    results = []
    errs = []
    stop = threading.Event()

    def load():
        try:
            while not stop.is_set():
                rep = cli.result(cli.submit(x1))
                results.append((rep["gen"], rep["y"]))
        except Exception as exc:          # pragma: no cover - failure
            errs.append(exc)

    t = threading.Thread(target=load, daemon=True)
    t.start()
    try:
        time.sleep(0.5)
        srv.swap_async(path_b).join(timeout=60)
        assert srv.runner.generation == 2
        refs[2] = {b: srv.runner.infer(srv.runner.pad(x1, b))[:1]
                   for b in srv.batcher.ladder.rungs}
        time.sleep(0.5)
        srv.runner.rollback()
        assert srv.runner.generation == 1
        time.sleep(0.5)
    finally:
        stop.set()
        t.join(timeout=30)
        cli.close()
        srv.stop()
    assert not errs
    gens = {g for g, _ in results}
    assert gens == {1, 2}                 # both generations served
    for g, y in results:
        assert any(np.array_equal(y, ref)
                   for ref in refs[g].values()), g
    # the oracle probes above ran through the same rung executables:
    # two rollovers + the stream added no compiles
    assert srv.runner.compiles == compiles_warm
