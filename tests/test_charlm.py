"""charlm — the first sequence workload end-to-end (ISSUE 15): seeded
convergence band under FusedTrainer, fused-tail on/off parity, the unit
engine's seq evaluator, snapshot -> inference-load -> serving, the
master/slave role, and the launcher CLI (solo + --serve)."""

import threading

import numpy as np
import pytest

from znicz_tpu.core.config import root


def _tiny_charlm_cfg(tmp_path=None, max_epochs=2, seq_len=32):
    from znicz_tpu.core import prng

    prng.reset(1013)
    root.charlm.loader.update({"n_train": 384, "n_valid": 48, "n_test": 0,
                               "seq_len": seq_len, "minibatch_size": 32})
    root.charlm.model.update({"vocab": 32, "embed": 48, "heads": 2,
                              "ffn": 96})
    root.charlm.learning_rate = 1.0
    root.charlm.decision.max_epochs = max_epochs
    if tmp_path is not None:
        root.common.dirs.snapshots = str(tmp_path)


def _build(tmp_path=None, **kw):
    from znicz_tpu.samples.charlm import CharLMWorkflow

    _tiny_charlm_cfg(tmp_path, **kw)
    wf = CharLMWorkflow()
    wf.initialize(device=None)
    if tmp_path is not None:
        wf.snapshotter.directory = str(tmp_path)
    return wf


def _params_of(wf):
    return {f.name: {k: np.array(a.map_read())
                     for k, a in f.params().items()}
            for f in wf.forwards}


def _train_fused(tmp_path, fused_tail: bool, max_epochs=3):
    from znicz_tpu.engine import train

    root.common.engine.fused = True
    root.common.engine.fused_tail = fused_tail
    try:
        wf = _build(tmp_path, max_epochs=max_epochs)
        train(wf)
    finally:
        root.common.engine.fused = False
        root.common.engine.fused_tail = False
    return wf


def test_charlm_fused_converges_seeded_band(tmp_path):
    """The acceptance band: charlm trains under FusedTrainer to a
    seeded convergence band — token error on VALID collapses far below
    the ~97% random baseline for vocab 32 (the stride corpus needs
    CONTEXT, so the attention layer is load-bearing)."""
    wf = _train_fused(tmp_path, fused_tail=False, max_epochs=8)
    dec = wf.decision
    assert bool(dec.complete)
    valid = dec.epoch_metrics[1]
    # err_pct here counts TOKEN errors over VALID samples x seq_len
    err = valid["n_err"] / (48 * 32) * 100.0
    assert err < 50.0, (err, valid)


def test_charlm_fused_tail_parity(tmp_path):
    """The fused seq-FFN/softmax epilogues (fused_tail on) reproduce
    the composed path within the PR 7 parity regime over a short
    horizon (identical metrics, params to 5e-3 after 2 epochs —
    longer horizons diverge chaotically under momentum, exactly as
    PR 7 pinned for the AlexNet tail)."""
    wf_off = _train_fused(tmp_path / "off", fused_tail=False,
                          max_epochs=2)
    wf_on = _train_fused(tmp_path / "on", fused_tail=True, max_epochs=2)
    assert wf_on.decision.epoch_metrics[1]["n_err"] == pytest.approx(
        wf_off.decision.epoch_metrics[1]["n_err"], rel=0.05)
    p_off, p_on = _params_of(wf_off), _params_of(wf_on)
    for name in p_off:
        for k in p_off[name]:
            np.testing.assert_allclose(
                p_off[name][k], p_on[name][k], rtol=5e-3, atol=5e-4,
                err_msg=f"{name}.{k} fused-tail parity")
    # the seq epilogue actually matched: plan covers the FFN
    from znicz_tpu.pallas_fused_block import plan_fused_tail

    root.common.engine.fused_tail = True
    try:
        plan = plan_fused_tail(wf_on.forwards)
    finally:
        root.common.engine.fused_tail = False
    kinds = {spec.kind for spec in plan.values()}
    assert "seq_epilogue" in kinds, plan


def test_charlm_unit_engine_matches_fused_direction(tmp_path):
    """The unit-at-a-time engine (the reference execution semantics)
    trains the same graph: loss drops and the first-epoch VALID error
    lands near the fused run's (same seeded data, same update rule)."""
    from znicz_tpu.engine import train

    wf = _build(tmp_path, max_epochs=6)
    train(wf)
    dec = wf.decision
    assert bool(dec.complete)
    assert dec.epoch_metrics[1] is not None
    assert dec.epoch_metrics[1]["n_err"] < 0.60 * 48 * 32


def test_charlm_snapshot_serves_variable_length(tmp_path):
    """Snapshot -> snapshotter inference-load -> InferenceServer: the
    charlm checkpoint loads like any other (satellite 6), the service
    runs the 2-D ladder (declared by the workflow), variable-length
    requests come back (n, len, vocab) with zero recompiles after
    warmup, and a probe's rows are a bit-exact pure function of its own
    rows + own length within a pinned bucket."""
    from znicz_tpu import snapshotter
    from znicz_tpu.engine import train
    from znicz_tpu.serving import InferenceClient, InferenceServer

    wf = _build(tmp_path, max_epochs=1)
    train(wf)
    path = wf.snapshotter.save("charlm_serve_test")
    trained = _params_of(wf)

    fresh = _build()
    meta = snapshotter.load_inference(fresh, path)
    assert "units" not in meta
    for f in fresh.forwards:
        for k, a in f.params().items():
            np.testing.assert_array_equal(np.array(a.map_read()),
                                          trained[f.name][k])

    # a window wide enough that the probe and its neighbour below always
    # share a batch (full at 4 rows, it leaves at once): at 2 ms a busy
    # host split them, and the probe alone runs in another rows rung
    srv = InferenceServer(fresh, max_batch=4, max_delay_ms=100.0).start()
    cli = InferenceClient(srv.endpoint, timeout=60)
    try:
        ladder = srv.batcher.ladder
        assert ladder.seq_rungs is not None
        assert ladder.seq_rungs[-1] == 32      # the trained window
        warm = srv.runner.compiles
        assert warm == len(ladder.buckets())
        rng = np.random.default_rng(5)
        for L in (3, 9, 17, 32, 5):
            y = cli.infer(rng.integers(1, 32, size=(2, L)
                                       ).astype(np.uint8))
            assert y.shape == (2, L, 32), (L, y.shape)
            assert np.all(np.isfinite(y))
        assert srv.runner.compiles == warm      # zero recompiles
        # masked 0-ULP: probe co-batched with different same-rung
        # neighbors (rows rung pinned at 4) comes back bit-identical
        probe = rng.integers(1, 32, size=(2, 10)).astype(np.uint8)
        replies = []
        for fill_len in (9, 12, 16):
            fill = rng.integers(1, 32, size=(2, fill_len)
                                ).astype(np.uint8)
            rid_p, rid_f = cli.submit(probe), cli.submit(fill)
            got = {}
            while len(got) < 2:
                for rep in cli.collect(0.05):
                    got[rep["req_id"]] = rep
            assert got[rid_p].get("ok") and got[rid_f].get("ok")
            replies.append(got[rid_p]["y"])
        assert all(np.array_equal(replies[0], y) for y in replies[1:])
        # pad_ratio is measured and exported
        stats = srv.batcher.stats()
        assert stats["real_cells"] > 0
        assert isinstance(stats["pad_ratio"], dict)
    finally:
        cli.close()
        srv.stop()


def test_charlm_master_slave_trains(tmp_path):
    """The distributed role needs no special-casing: a charlm master
    serves jobs to a charlm slave over wire v3 and training completes
    with the deltas applied (satellite 6).  lr is kept at 0.3 here: the
    aggressive-lr momentum ramp the solo tests use grows delta norms
    past the master's 25x-running-median quarantine (the PR 2 fault
    model working exactly as designed — refuse-and-requeue), which is
    chaos-harness territory, not this role test's."""
    from znicz_tpu.client import Client
    from znicz_tpu.server import Server

    endpoint = "tcp://127.0.0.1:17693"

    def build_ms(tag):
        from znicz_tpu.samples.charlm import CharLMWorkflow

        _tiny_charlm_cfg(tag, max_epochs=2)
        root.charlm.learning_rate = 0.3
        wf = CharLMWorkflow()
        wf.initialize(device=None)
        wf.snapshotter.directory = str(tag)
        return wf

    master_wf = build_ms(tmp_path / "m")
    server = Server(master_wf, endpoint=endpoint, job_timeout=60.0)
    slave = Client(build_ms(tmp_path / "s"),
                   endpoint=endpoint, slave_id="charlm0")
    errors = []

    def worker():
        try:
            slave.run()
        except BaseException as e:
            errors.append(repr(e))
            raise

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    server.serve()
    t.join(timeout=60)
    assert not errors, errors
    assert not t.is_alive()
    assert bool(master_wf.decision.complete)
    assert server.jobs_done > 0
    assert server.jobs_by_slave.get("charlm0", 0) > 0


def test_launcher_charlm_solo_cli(tmp_path):
    """``python -m znicz_tpu charlm`` (satellite 6): the bundled-sample
    name resolves and a tiny solo run completes."""
    from znicz_tpu.launcher import SAMPLES, main

    assert "charlm" in SAMPLES
    rc = main([
        "charlm",
        "root.charlm.loader.n_train=96",
        "root.charlm.loader.n_valid=32",
        "root.charlm.loader.seq_len=16",
        "root.charlm.decision.max_epochs=1",
        f"root.common.dirs.snapshots={tmp_path}",
    ])
    assert rc == 0


def test_launcher_charlm_serve_cli(tmp_path):
    """``--serve`` on the charlm sample (satellite 6): the launcher
    builds the workflow without training, the service comes up on the
    2-D ladder, and variable-length uint8 requests are answered."""
    from znicz_tpu.launcher import main
    from znicz_tpu.serving import InferenceClient

    _tiny_charlm_cfg(tmp_path, seq_len=16)
    endpoint = "tcp://127.0.0.1:17694"
    root.common.serving.max_requests = 2
    rc = {}

    def run_cli():
        rc["code"] = main([
            "charlm", "--serve", endpoint,
            "root.charlm.loader.n_train=96",
            "root.charlm.loader.n_valid=32",
            "root.charlm.loader.seq_len=16",
            "root.common.serving.max_batch=4",   # 3x5 buckets to warm
        ])

    t = threading.Thread(target=run_cli)
    t.start()
    try:
        # resend_after_s past the timeout: a resend during the 2-D
        # warmup would burn the server's max_requests budget on a
        # duplicate and strand the second request
        cli = InferenceClient(endpoint, timeout=90, resend_after_s=120.0)
        try:
            y = cli.infer(np.ones((2, 5), np.uint8), timeout=90)
            assert y.shape == (2, 5, 32)
            y = cli.infer(np.ones((1, 16), np.uint8), timeout=90)
            assert y.shape == (1, 16, 32)
        finally:
            cli.close()
        t.join(timeout=60)
        assert not t.is_alive()
        assert rc["code"] == 0
    finally:
        root.common.serving.max_requests = None
        t.join(timeout=5)
