"""Pod-sliced training (ISSUE 18): mesh-sharded FusedTrainer steps.

Covers: the ``root.common.engine.train_shard`` gate and its mesh
refusals, the extraction proof (serving imports ONLY the shared
placement home, the param-sharding rule lives in exactly one file),
per-device shard shapes on 4x1 and 2x2 slices, 1x1-resolves-to-
single-device bit-exactness, the cross-layout convergence band
(reduction tiling is layout-dependent — same reason the serving
twin's cross-mesh parity is a band), the compiles==jit-cache
zero-recompile cross-check, sharded staged segments (``P(None,
"data")``, one transfer per shard) with DeviceStager telemetry, and
the meshed-slave-through-master e2e (register piggyback + web_status
mesh column).  The relay-leaf soak rides behind ``slow``.

Runs on the 8 virtual CPU devices conftest provisions (virtdev.py)."""

import pathlib
import threading

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.config import root

PKG = pathlib.Path(__file__).resolve().parents[1] / "znicz_tpu"


def _tiny_mnist_wf(n_train=120, layers=(1024, 10), max_epochs=2):
    """The shard-serving twin's workflow: hidden 1024 >= tp_threshold
    so the model axis engages the column-sharded layout."""
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = max_epochs
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


def _run_fused(wf, mesh=None):
    from znicz_tpu.parallel.fused import FusedTrainer

    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    t = FusedTrainer(wf, mesh=mesh)
    t.run()
    return t, losses, {f.name: np.array(f.weights.map_read())
                       for f in wf.forwards if f.has_weights}


@pytest.fixture
def engine_mesh(tmp_path):
    """Set the pod-slice knobs for a test and restore the defaults
    after — the global engine tree must not leak a mesh into the rest
    of the suite."""
    root.common.dirs.snapshots = str(tmp_path)

    def set_mesh(dp, mp=1, shard=True):
        root.common.engine.train_shard = bool(shard)
        root.common.engine.mesh.data = int(dp)
        root.common.engine.mesh.model = int(mp)
    yield set_mesh
    root.common.engine.train_shard = False
    try:
        delattr(root.common.engine, "mesh")
    except AttributeError:
        pass


# -- the config gate ----------------------------------------------------------


def test_train_mesh_config_gate_and_refusals(engine_mesh):
    from znicz_tpu.parallel.mesh import train_mesh_from_config

    # default OFF: single-device, whatever the mesh knobs say
    assert train_mesh_from_config() is None
    engine_mesh(4, 2, shard=False)
    assert train_mesh_from_config() is None
    # ON with 1x1 IS the single-device path
    engine_mesh(1, 1)
    assert train_mesh_from_config() is None
    # ON with a real slice
    engine_mesh(4, 1)
    m = train_mesh_from_config()
    assert m.axis_names == ("data", "model")
    assert (int(m.shape["data"]), int(m.shape["model"])) == (4, 1)
    # refusals are readable and name the plane
    engine_mesh(0, 2)
    with pytest.raises(ValueError, match="training mesh axes"):
        train_mesh_from_config()


# -- extraction proof (ISSUE 18 satellite 1) ----------------------------------


def test_serving_imports_only_the_shared_placement_home():
    """PR 12's placement machinery moved to parallel/mesh.py; the
    serving plane must now hold NO placement code of its own — only
    imports of the shared home."""
    src = (PKG / "serving" / "model.py").read_text()
    assert "from znicz_tpu.parallel.mesh import" in src
    for literal in ("make_array_from_callback", "NamedSharding(",
                    "PartitionSpec"):
        assert literal not in src, (
            f"serving/model.py still carries placement machinery "
            f"({literal}) — it must import parallel/mesh.py instead")


def test_param_sharding_rule_has_exactly_one_home():
    """The tp-threshold rule body (``shape[0] >= tp_threshold`` and
    the divisibility check) must exist in parallel/mesh.py and NOWHERE
    else — callers delegate, they do not duplicate."""
    owners = [p.relative_to(PKG).as_posix() for p in PKG.rglob("*.py")
              if ">= tp_threshold" in p.read_text()]
    assert owners == ["parallel/mesh.py"], owners


# -- shard shapes, bit-exactness, convergence band ----------------------------


def test_meshed_trainer_layouts_shapes_band_and_jit_hygiene(tmp_path):
    """One seeded run per layout (single-device, 4x1, 2x2): shard
    shapes per the param-sharding rule, losses/weights inside the
    cross-layout band, and compiles == jax's own executable-cache sum
    (the zero-recompile cross-check) on every layout."""
    root.common.dirs.snapshots = str(tmp_path)
    t1, l1, w1 = _run_fused(_tiny_mnist_wf())
    runs = {}
    for tag, (dp, mp) in (("d4", (4, 1)), ("d2m2", (2, 2))):
        t, ls, ws = _run_fused(_tiny_mnist_wf(), mesh=_mesh(dp, mp))
        runs[tag] = (t, ls, ws)
        assert t.mesh_shape == {"data": dp, "model": mp}
        # the wide fc layer: column-sharded over model (hidden/mp rows
        # per shard) when mp > 1, replicated otherwise; always one
        # shard per mesh device, never a device-0 gather
        wide = next(f for f in t.forwards
                    if f.has_weights and f.weights.shape[0] == 1024)
        shards = [s.data.shape
                  for s in wide.weights.devmem.addressable_shards]
        assert len(shards) == dp * mp
        assert all(s == (1024 // mp, 784) for s in shards), shards
        bshards = [s.data.shape
                   for s in wide.bias.devmem.addressable_shards]
        assert all(s == (1024 // mp,) for s in bshards), bshards
        # jit hygiene: the trace counter equals jax's cache entries
        sizes = t.jit_cache_sizes()
        if sizes:
            assert sum(sizes.values()) == int(t._m_compiles.value), sizes
        # cross-layout band (NOT 0 ULP: reduction tiling is layout-
        # dependent, exactly the serving twin's PARITY_REL rationale)
        np.testing.assert_allclose(l1, ls, rtol=1e-3)
        for name in w1:
            np.testing.assert_allclose(w1[name], ws[name], rtol=2e-3,
                                       atol=2e-5, err_msg=f"{tag}:{name}")
    assert l1[-1] < l1[0]                       # and it actually trains


def test_train_shard_mesh_1x1_is_bitexact_single_device(engine_mesh):
    """train_shard ON with a 1x1 mesh resolves to mesh=None — the
    IDENTICAL single-device path, bit for bit."""
    from znicz_tpu.parallel.mesh import train_mesh_from_config

    _, l_off, w_off = _run_fused(_tiny_mnist_wf(layers=(100, 10)))
    engine_mesh(1, 1)
    m = train_mesh_from_config()
    assert m is None
    _, l_on, w_on = _run_fused(_tiny_mnist_wf(layers=(100, 10)), mesh=m)
    assert l_off == l_on
    for name in w_off:
        assert np.array_equal(w_off[name], w_on[name]), name


def test_local_fused_engine_takes_the_config_mesh(engine_mesh):
    """``engine.train``'s local fused branch reads the gate a fused
    slave reads (ISSUE 21): off, it is the plain single-device trainer
    bit for bit whatever the mesh knobs say; on, every parameter lives
    on all four devices of the slice."""
    from znicz_tpu.engine import train

    def run_engine():
        wf = _tiny_mnist_wf(layers=(100, 10))
        losses = []
        wf.decision.on_epoch_end.append(
            lambda d: losses.append(d.epoch_metrics[2]["loss"]))
        train(wf)
        return wf, losses

    _, l_ref, w_ref = _run_fused(_tiny_mnist_wf(layers=(100, 10)))
    root.common.engine.fused = True
    try:
        engine_mesh(4, 1, shard=False)
        wf, l_off = run_engine()
        assert l_off == l_ref
        for f in wf.forwards:
            if f.has_weights:
                assert np.array_equal(f.weights.map_read(), w_ref[f.name])
        engine_mesh(4, 1)
        wf, l_on = run_engine()
        np.testing.assert_allclose(l_on, l_ref, rtol=1e-3)
        for f in wf.forwards:
            for arr in f.params().values():
                assert len(arr.devmem.sharding.device_set) == 4, f.name
    finally:
        root.common.engine.fused = False


# -- sharded staged segments (ISSUE 18 satellite 2) ---------------------------


def test_staged_segments_shard_over_data_with_telemetry(tmp_path):
    """Host-staged streaming on a (data, model) mesh: each staged
    (K, B, ...) segment is placed ``P(None, "data")`` — one transfer
    per shard, no device-0 gather — and the DeviceStager's ping-pong
    telemetry (stage hits/misses, h2d_copy_ms) covers the sharded
    path."""
    import jax
    from jax.sharding import PartitionSpec as P

    from znicz_tpu import datasets
    from znicz_tpu.loader.streaming import (HostArraySource,
                                            StreamingLoader)
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples import mnist

    root.common.dirs.snapshots = str(tmp_path)
    prng.reset(1013)
    root.mnist.loader.n_train = 240
    root.mnist.loader.n_valid = 60
    root.mnist.loader.n_test = 0
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = 2

    cfg = root.mnist.loader
    total = int(cfg.n_train) + int(cfg.n_valid)
    data, labels = datasets.load_or_generate(None, datasets.digits, total)

    class _Streaming(StreamingLoader):
        def __init__(self, workflow=None, name=None, **kwargs):
            super().__init__(
                workflow=workflow, name=name,
                source=HostArraySource(data.reshape(total, -1), labels),
                class_lengths=[0, int(cfg.n_valid), int(cfg.n_train)],
                scale=1.0, shift=0.0, device_budget_bytes=0, **kwargs)

    orig = mnist.MnistLoader
    mnist.MnistLoader = _Streaming
    try:
        wf = mnist.MnistWorkflow()
    finally:
        mnist.MnistLoader = orig
    wf.initialize(device=None)
    t = FusedTrainer(wf, mesh=_mesh(2, 2))
    assert t.staging
    # the staged segment itself: batch axis sharded over "data" (60 %
    # dp == 0), replicated over "model" — (K, B/dp, ...) per shard
    seg_d, seg_t = t._stage_direct(
        [np.arange(60, dtype=np.int32),
         np.arange(60, 120, dtype=np.int32)], put=None)
    assert seg_d.sharding.spec == P(None, "data")
    shapes = [s.data.shape for s in seg_d.addressable_shards]
    assert len(shapes) == 4 and all(s == (2, 30, 784) for s in shapes)
    assert seg_t.sharding.spec == P(None, "data")
    del seg_d, seg_t
    t.run()
    assert wf.decision.epoch_metrics[2]["loss"] < 2.0   # it trains
    st = t._stager.stats()
    assert st["stage_hits"] + st["stage_misses"] > 0
    assert st["h2d_ms_p50"] is not None     # the copies were timed
    jax.clear_caches()


# -- ring attention on the training mesh --------------------------------------


def test_bind_sequence_mesh_refusals_and_parity():
    """``bind_sequence_mesh`` rebinds MHA's shard_map onto a training
    mesh (batch over "data", ring blocks over "model"); a mesh whose
    seq axis cannot ring (size < 2) is refused; the bound path matches
    the dense core numerically."""
    from znicz_tpu.attention import MultiHeadAttention
    from znicz_tpu.memory import Array

    rng = np.random.default_rng(47)
    x = rng.normal(size=(2, 32, 8)).astype(np.float32)

    def build(name):
        mha = MultiHeadAttention(name=name, heads=2, causal=True)
        mha.input = Array(x)
        mha.initialize(device=None)
        return mha

    base = build("mha_tm_off")
    base.run()
    ref = np.array(base.output.map_read())
    bound = build("mha_tm_on")
    assert bound.bind_sequence_mesh(None) is False
    assert bound.bind_sequence_mesh(_mesh(4, 1)) is False   # no ring
    assert bound.bind_sequence_mesh(_mesh(2, 2)) is True
    assert bound._sp_spec == ("data", "model")
    for kk, a in base.proj.items():                # identical weights
        bound.proj[kk].mem = np.array(a.map_read())
    bound.run()
    np.testing.assert_allclose(np.array(bound.output.map_read()), ref,
                               rtol=2e-4, atol=1e-5)


def test_meshed_trainer_rebinds_charlm_attention(tmp_path):
    """seq_parallel on a meshed FusedTrainer rides the TRAINING mesh
    instead of the private ("sp",) mesh initialize() builds — one mesh
    per leaf, not two fighting over the same devices."""
    from znicz_tpu.attention import MultiHeadAttention
    from znicz_tpu.parallel.fused import FusedTrainer
    from znicz_tpu.samples.charlm import CharLMWorkflow

    root.common.dirs.snapshots = str(tmp_path)
    prng.reset(1013)
    root.charlm.loader.update({"n_train": 64, "n_valid": 32,
                               "n_test": 0, "seq_len": 32,
                               "minibatch_size": 32})
    root.charlm.model.update({"vocab": 32, "embed": 48, "heads": 2,
                              "ffn": 96})
    root.charlm.decision.max_epochs = 1
    try:
        root.common.engine.seq_parallel = 2
        wf = CharLMWorkflow()
        wf.initialize(device=None)
        mesh = _mesh(2, 2)
        t = FusedTrainer(wf, mesh=mesh)
        mha = next(f for f in t.forwards
                   if isinstance(f, MultiHeadAttention))
        assert mha._sp_mesh is mesh
        assert mha._sp_spec == ("data", "model")
    finally:
        root.common.engine.seq_parallel = 0


# -- meshed slave through the master (ISSUE 18 e2e) ---------------------------


def _fleet(endpoint, engine_mesh=None, dp=2, mp=2):
    """One seeded master + one FusedClient slave over `endpoint`;
    returns (server, master_wf, slave)."""
    from znicz_tpu.client import FusedClient
    from znicz_tpu.server import Server

    wf = _tiny_mnist_wf()
    server = Server(wf, endpoint=endpoint, job_timeout=60.0)
    slave = FusedClient(_tiny_mnist_wf(), endpoint=endpoint,
                        slave_id="pod0")
    errors = []

    def worker():
        try:
            slave.run()
        except BaseException as e:      # surface thread crashes
            errors.append(repr(e))
            raise

    th = threading.Thread(target=worker, daemon=True)
    th.start()
    server.serve()
    th.join(timeout=60)
    assert not errors, errors
    assert not th.is_alive()
    assert bool(wf.decision.complete)
    return server, wf, slave


def test_meshed_slave_e2e_piggyback_and_web_status(engine_mesh):
    """A pod-sliced FusedClient trains a real (tiny) fleet to
    completion: the slice shape rides the register handshake onto the
    master and into the web_status mesh column; the slave's params
    end up column-sharded; the wire saw a normal single slave."""
    from znicz_tpu.network_common import handshake_request
    from znicz_tpu.web_status import WebStatus

    engine_mesh(2, 2)
    server, wf, slave = _fleet("tcp://127.0.0.1:18930")
    assert slave.mesh_shape == {"data": 2, "model": 2}
    assert server.slave_meshes == {"pod0": {"data": 2, "model": 2}}
    assert int(server.bytes_in) > 0
    # web_status: the mesh column renders the slice (single-device
    # slaves show None -> "single-device")
    ws = WebStatus()
    ws.register_server(server)
    rows = ws.snapshot()["master"]["slaves"]
    assert [r["mesh"] for r in rows if r["id"] == "pod0"] == [
        {"data": 2, "model": 2}]
    # the piggyback is OPTIONAL on the wire: no mesh -> no key (an
    # older master ignores it either way)
    assert "mesh" not in handshake_request(wf)
    assert handshake_request(wf, mesh={"data": 2, "model": 2})[
        "mesh"] == {"data": 2, "model": 2}
    # the slave's wide layer really is sharded on its slice
    t = slave._trainer
    wide = next(f for f in t.forwards
                if f.has_weights and f.weights.shape[0] == 1024)
    shards = [s.data.shape
              for s in wide.weights.devmem.addressable_shards]
    assert len(shards) == 4 and all(s == (512, 784) for s in shards)
    # zero-recompile cross-check on the slave's executables
    sizes = t.jit_cache_sizes()
    if sizes:
        assert sum(sizes.values()) == int(t._m_compiles.value), sizes


@pytest.mark.slow
def test_sharded_slave_sends_the_master_the_bytes_of_a_single_device_one(
        engine_mesh):
    """Two-tier reduction: the gradient psum inside a slice is free on
    the wire, so the master takes in the same bytes from a {data: 2,
    model: 2} slave as from a single-device one — the difference is the
    register piggyback key and a few polls, under 1 % of a run."""
    engine_mesh(1, 1, shard=False)
    single, _, slave = _fleet("tcp://127.0.0.1:18932")
    assert slave.mesh_shape is None
    engine_mesh(2, 2)
    sharded, _, slave = _fleet("tcp://127.0.0.1:18933")
    assert slave.mesh_shape == {"data": 2, "model": 2}
    assert single.jobs_done == sharded.jobs_done
    want, got = int(single.bytes_in), int(sharded.bytes_in)
    assert abs(got - want) <= 0.01 * want, (want, got)


@pytest.mark.slow
def test_meshed_slave_through_relay_soak(engine_mesh):
    """The pod slice composes with the tree (ISSUE 10): a meshed leaf
    behind a relay trains to completion, and the relay's contributor
    manifest still attributes its jobs."""
    from znicz_tpu.client import FusedClient
    from znicz_tpu.parallel.chaos import RelayHarness
    from znicz_tpu.server import Server

    engine_mesh(2, 2)
    master_ep = "tcp://127.0.0.1:18940"
    relay_ep = "tcp://127.0.0.1:18941"
    wf = _tiny_mnist_wf()
    server = Server(wf, endpoint=master_ep, job_timeout=60.0)
    server_thread = threading.Thread(target=server.serve, daemon=True)
    server_thread.start()
    harness = RelayHarness(master_ep, relay_ep, relay_id="r0",
                           recv_timeout=1.0, max_reconnects=60)
    harness.start()
    try:
        slave = FusedClient(_tiny_mnist_wf(), endpoint=relay_ep,
                            slave_id="pod0")
        slave.run(recv_timeout=1.0, max_reconnects=80,
                  backoff_base=0.05, backoff_cap=0.4,
                  connect_retries=80)
        server_thread.join(timeout=60)
        assert not server_thread.is_alive()
    finally:
        harness.kill()
    assert slave.mesh_shape == {"data": 2, "model": 2}
    assert bool(wf.decision.complete)
    # the leaf's jobs are still attributed through the relay manifest
    assert server.jobs_by_slave.get("pod0", 0) > 0
