"""Core-engine tests: config, Bool gates, unit linking, workflow scheduling,
Array map/unmap (mirrors the reference's veles/tests/ coverage, SURVEY.md §4
"Core-engine tests")."""

import numpy as np
import pytest

from znicz_tpu.core.config import Config, apply_overrides, parse_override
from znicz_tpu.core.mutable import Bool
from znicz_tpu.core.units import TrivialUnit, Unit
from znicz_tpu.core.workflow import Repeater, Workflow
from znicz_tpu.memory import Array, roundup


class TestConfig:
    def test_auto_tree(self):
        cfg = Config("r")
        cfg.a.b.c = 3
        assert cfg.a.b.c == 3
        assert cfg.to_dict() == {"a": {"b": {"c": 3}}}

    def test_update_and_get(self):
        cfg = Config("r")
        cfg.update({"x": 1, "sub": {"y": "z"}})
        assert cfg.x == 1
        assert cfg.sub.y == "z"
        assert cfg.get("missing", 42) == 42
        assert cfg.sub.get("y") == "z"

    def test_overrides(self):
        cfg = Config("r")
        apply_overrides(cfg, ["a.b=3", "a.c=hello", "a.d=[1, 2]"])
        assert cfg.a.b == 3
        assert cfg.a.c == "hello"
        assert cfg.a.d == [1, 2]

    def test_parse_override_strips_root(self):
        key, value = parse_override("root.m.lr=0.01")
        assert key == "m.lr" and value == 0.01

    # the six knobs PR 30 removed with their experiments, and ``fuse``,
    # which was declared and never read
    @pytest.mark.parametrize("knob", [
        "precision", "remat", "lrn_pow", "lrn_autodiff", "pallas_lrn",
        "pool_bwd", "fuse"])
    def test_undeclared_engine_knob_is_refused_at_the_door(self, knob):
        """The tree autovivifies, so an engine knob that ENGINE_DEFAULTS
        does not declare (removed, or a typo) would silently train on the
        default: ``set_by_path`` — the launcher's, ``run_with.py``'s and
        the benchmark drivers' door — refuses it by name, from the root
        or from a subtree, and leaves the tree as it was."""
        from znicz_tpu.core.config import ENGINE_DEFAULTS

        assert knob not in ENGINE_DEFAULTS
        cfg = Config("root")
        with pytest.raises(KeyError) as err:
            apply_overrides(cfg, [f"root.common.engine.{knob}=2"])
        assert f"root.common.engine.{knob}" in str(err.value)
        assert "ENGINE_DEFAULTS" in str(err.value)
        with pytest.raises(KeyError, match=knob):
            cfg.common.engine.set_by_path(knob, 2)
        assert knob not in cfg.common.engine
        # declared knobs, nested ones too, and other trees pass
        apply_overrides(cfg, ["root.common.engine.scan_chunk=4",
                              "root.common.engine.mesh.data=4",
                              f"root.alexnet.{knob}=2"])
        assert cfg.common.engine.scan_chunk == 4
        assert cfg.common.engine.mesh.data == 4
        with pytest.raises(KeyError, match="mesh.dat"):
            cfg.set_by_path("common.engine.mesh.dat", 4)


class TestBool:
    def test_plain(self):
        b = Bool(False)
        assert not b
        b <<= True
        assert b

    def test_derived_tracks_source(self):
        a = Bool(False)
        n = ~a
        assert n
        a.set(True)
        assert not n

    def test_and_or(self):
        a, b = Bool(True), Bool(False)
        assert not (a & b)
        assert a | b
        b.set(True)
        assert a & b

    def test_on_change(self):
        seen = []
        a = Bool(False)
        a.on_change.append(lambda bb: seen.append(bool(bb)))
        a.set(True)
        a.set(True)  # no change -> no callback
        a.set(False)
        assert seen == [True, False]


class _Recorder(TrivialUnit):
    log_list: list = []

    def run(self):
        _Recorder.log_list.append(self.name)


class TestWorkflowScheduling:
    def setup_method(self):
        _Recorder.log_list = []

    def test_linear_chain(self):
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        b = _Recorder(w, name="b")
        a.link_from(w.start_point)
        b.link_from(a)
        w.end_point.link_from(b)
        w.initialize(device=_fake_device())
        w.run()
        assert _Recorder.log_list == ["a", "b"]

    def test_and_gate_join(self):
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        b = _Recorder(w, name="b")
        c = _Recorder(w, name="c")
        a.link_from(w.start_point)
        b.link_from(w.start_point)
        c.link_from(a, b)  # fires only after both
        w.end_point.link_from(c)
        w.initialize(device=_fake_device())
        w.run()
        assert _Recorder.log_list[-1] == "c"
        assert set(_Recorder.log_list) == {"a", "b", "c"}

    def test_repeater_loop_with_gate(self):
        w = Workflow(name="w")
        rep = Repeater(w, name="rep")
        body = _Recorder(w, name="body")
        counter = {"n": 0}

        class Decide(TrivialUnit):
            def run(self):
                counter["n"] += 1
                if counter["n"] >= 3:
                    self.workflow.complete.set(True)

        w.complete = Bool(False)
        dec = Decide(w, name="dec")
        rep.link_from(w.start_point)
        body.link_from(rep)
        dec.link_from(body)
        rep.link_from(dec)          # close the loop
        rep.gate_block = w.complete  # stop looping when complete
        w.end_point.link_from(dec)
        w.end_point.gate_block = ~w.complete
        w.initialize(device=_fake_device())
        w.run()
        assert counter["n"] == 3
        assert _Recorder.log_list == ["body"] * 3

    def test_gate_skip_propagates(self):
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        b = _Recorder(w, name="b")
        a.gate_skip = Bool(True)
        a.link_from(w.start_point)
        b.link_from(a)
        w.end_point.link_from(b)
        w.initialize(device=_fake_device())
        w.run()
        assert _Recorder.log_list == ["b"]

    def test_gate_block_stops_propagation(self):
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        b = _Recorder(w, name="b")
        a.gate_block = Bool(True)
        a.link_from(w.start_point)
        b.link_from(a)
        w.end_point.link_from(b)
        w.initialize(device=_fake_device())
        w.run()
        assert _Recorder.log_list == []

    def test_timing_collected(self):
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        a.link_from(w.start_point)
        w.end_point.link_from(a)
        w.initialize(device=_fake_device())
        w.run()
        assert a.run_count == 1
        assert "a" in w.print_stats()

    def test_graphviz_dump(self):
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        a.link_from(w.start_point)
        dot = w.generate_graph()
        assert '"start_point" -> "a";' in dot


class TestAttrLinks:
    def test_forwarding(self):
        a = Unit(name="a")
        b = Unit(name="b")
        a.output = 42
        b.link_attrs(a, ("input", "output"))
        assert b.input == 42
        a.output = 43          # rebinding source is visible
        assert b.input == 43

    def test_same_name(self):
        a = Unit(name="a")
        b = Unit(name="b")
        a.minibatch_size = 10
        b.link_attrs(a, "minibatch_size")
        assert b.minibatch_size == 10

    def test_write_detaches_one_way(self):
        a = Unit(name="a")
        b = Unit(name="b")
        a.v = 1
        b.link_attrs(a, "v")
        b.v = 99
        assert b.v == 99 and a.v == 1

    def test_two_way(self):
        a = Unit(name="a")
        b = Unit(name="b")
        a.v = 1
        b.link_attrs(a, "v", two_way=True)
        b.v = 7
        assert a.v == 7


class TestArray:
    def test_roundup(self):
        assert roundup(5, 8) == 8
        assert roundup(16, 8) == 16

    def test_host_device_roundtrip(self):
        arr = Array(np.arange(6, dtype=np.float32).reshape(2, 3))
        dev = arr.devmem
        assert dev.shape == (2, 3)
        host = arr.map_read()
        np.testing.assert_array_equal(host, np.arange(6).reshape(2, 3))

    def test_device_result_adoption(self):
        import jax.numpy as jnp

        arr = Array(np.zeros((2, 2), np.float32))
        arr.devmem = jnp.ones((2, 2), jnp.float32)
        np.testing.assert_array_equal(arr.map_read(), np.ones((2, 2)))

    def test_host_write_syncs_on_unmap(self):
        arr = Array(np.zeros(4, np.float32))
        _ = arr.devmem
        arr.map_write()[:] = 5.0
        np.testing.assert_array_equal(np.asarray(arr.devmem), [5.0] * 4)

    def test_sample_size(self):
        arr = Array(np.zeros((10, 3, 4), np.float32))
        assert arr.sample_size == 12
        assert len(arr) == 10

    def test_empty_read_raises(self):
        with pytest.raises(RuntimeError):
            Array().map_read()

    def test_donated_devmem_recovers_from_host(self):
        """A donating jit may consume a buffer that (CPU backend) aliases
        the Array's devmem; the Array must recover from its host copy —
        and refuse with a clear error when the device value was newer."""
        import jax

        eat = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
        arr = Array(np.ones((64, 1024), np.float32))
        _ = eat(arr.devmem)                  # donates (and deletes) it
        np.testing.assert_array_equal(
            np.asarray(arr.devmem), np.ones((64, 1024), np.float32))

        arr2 = Array(np.ones(4, np.float32))
        import jax.numpy as jnp

        arr2.devmem = jax.device_put(np.full(4, 2.0, np.float32))
        _ = eat2 = jax.jit(lambda x: x * 2, donate_argnums=(0,))(arr2.devmem)
        if arr2._devmem_deleted():           # small arrays may copy
            with pytest.raises(RuntimeError, match="donat"):
                arr2.map_read()

    def test_host_rewrite_cannot_corrupt_device_value(self):
        """jax.device_put on the CPU backend ZERO-COPIES large aligned
        numpy arrays — after unmap, in-place host writes would mutate the
        'immutable' jax array that queued computations still read (the
        hash-seed-dependent divergence found in r4).  map_write /
        map_invalidate must break the aliasing first."""
        for mapper in ("map_write", "map_invalidate"):
            # large enough to hit the zero-copy path (~60*784 f32 did)
            arr = Array(np.ones((64, 1024), np.float32))
            dev = arr.devmem                  # may alias arr's host buffer
            getattr(arr, mapper)()[...] = 7.0
            np.testing.assert_array_equal(
                np.asarray(dev), np.ones((64, 1024), np.float32),
                err_msg=mapper)
            # and the new host value still reaches the device on unmap
            np.testing.assert_array_equal(
                np.asarray(arr.devmem),
                np.full((64, 1024), 7.0, np.float32), err_msg=mapper)


def _fake_device():
    from znicz_tpu.backends import Device

    return Device(platform="cpu")


class TestPrng:
    def test_named_streams_deterministic(self):
        from znicz_tpu.core import prng

        a1 = prng.get("w1").normal(1.0, (4,))
        prng.reset(1013)
        a2 = prng.get("w1").normal(1.0, (4,))
        np.testing.assert_array_equal(a1, a2)

    def test_streams_independent_of_creation_order(self):
        from znicz_tpu.core import prng

        a = prng.get("alpha").normal(1.0, (3,))
        prng.reset(1013)
        _ = prng.get("beta").normal(1.0, (3,))
        a2 = prng.get("alpha").normal(1.0, (3,))
        np.testing.assert_array_equal(a, a2)


class TestReviewRegressions:
    """Regressions from the first code review."""

    def test_map_write_after_device_adoption_is_writable(self):
        import jax.numpy as jnp

        arr = Array()
        arr.devmem = jnp.zeros((3,), jnp.float32)
        buf = arr.map_write()
        buf[:] = 7.0  # must not raise "assignment destination is read-only"
        np.testing.assert_array_equal(np.asarray(arr.devmem), [7.0] * 3)

    def test_map_invalidate_empty_raises(self):
        with pytest.raises(RuntimeError):
            Array().map_invalidate()

    def test_gate_any_fanin_runs_once_per_wave(self):
        _Recorder.log_list = []
        w = Workflow(name="w")
        a = _Recorder(w, name="a")
        b = _Recorder(w, name="b")
        rep = Repeater(w, name="rep")
        tail = _Recorder(w, name="tail")
        a.link_from(w.start_point)
        b.link_from(w.start_point)
        rep.link_from(a, b)       # both fire in the same wave
        tail.link_from(rep)
        w.end_point.link_from(tail)
        w.initialize(device=_fake_device())
        w.run()
        assert _Recorder.log_list.count("tail") == 1

    def test_prng_key_uses_full_seed(self):
        from znicz_tpu.core import prng

        k1 = prng.get("s1").jax_key(0)
        k2 = prng.get("s2").jax_key(0)
        assert not np.array_equal(np.asarray(k1), np.asarray(k2))


def test_config_defaults_ignores_autovivified_reads():
    """A mere read of a config path must not block later defaults()."""
    from znicz_tpu.core.config import Config

    c = Config("t")
    _ = c.a.b                      # autovivified empty node
    c.defaults({"a": {"b": 5}, "x": 1})
    assert c.a.get("b") == 5
    assert c.get("x") == 1
    c2 = Config("t2")
    c2.a.b = 7                     # user-set leaf wins
    c2.defaults({"a": {"b": 5}})
    assert c2.a.get("b") == 7


def test_workflow_uniquifies_duplicate_unit_names():
    from znicz_tpu.core.units import TrivialUnit
    from znicz_tpu.core.workflow import Workflow

    wf = Workflow(name="dupwf")
    a = TrivialUnit(wf)
    b = TrivialUnit(wf)
    assert a.name != b.name
    assert len({u.name for u in wf.units}) == len(wf.units)
