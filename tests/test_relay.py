"""Relay-tree gradient aggregation (ISSUE 10): O(log N) reduction over
wire v3 — planner/spec units, job batching, the LR-schedule-at-dispatch
satellite, codec byte-identity through a relay hop, per-child edge
quarantine with master counters intact, a lean 1-level tree training
run, dead-relay fallback, and (slow) a 2-level chaos soak."""

import threading
import time

import numpy as np
import pytest

from znicz_tpu.core.config import root
from znicz_tpu.parallel import wire


def _make_workflow(tmp_path, max_epochs=3):
    from znicz_tpu.core import prng
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 300
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    root.mnist.decision.max_epochs = max_epochs
    root.common.dirs.snapshots = str(tmp_path)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=None)
    return wf


def _handshake_fields(workflow):
    from znicz_tpu.network_common import handshake_request

    msg = handshake_request(workflow)
    del msg["cmd"]
    return msg


def _white_box_relay(n_children=3, fanout=3, **kwargs):
    """A Relay used WITHOUT sockets: pre-validated credentials, enough
    registered children that the flush threshold is never crossed by
    the test's buffered messages (no upstream to flush into)."""
    from znicz_tpu.parallel.relay import Relay

    kwargs.setdefault("flush_s", 999.0)
    relay = Relay("tcp://127.0.0.1:1", "tcp://127.0.0.1:2",
                  relay_id="wb-relay", fanout=fanout, **kwargs)
    relay._cred = (3, "cafebabecafebabe")
    now = time.time()
    for i in range(n_children):
        relay._children[f"s{i}"] = now
    return relay


# -- planner / CLI spec --------------------------------------------------------


def test_plan_tree_shapes_and_relay_spec():
    from znicz_tpu.parallel.relay import parse_relay_spec, plan_tree

    master = "tcp://127.0.0.1:5570"
    p = plan_tree(8, 2, master)
    assert p["levels"] == 2
    assert len(p["relays"]) == 6            # 2 mid + 4 leaf
    # top tier dials the master; every leaf endpoint is a relay of the
    # bottom tier; slaves spread across all leaf relays
    assert [r["upstream"] for r in p["relays"][:2]] == [master] * 2
    mid_binds = {r["bind"] for r in p["relays"][:2]}
    assert all(r["upstream"] in mid_binds for r in p["relays"][2:])
    leaf_binds = [r["bind"] for r in p["relays"][2:]]
    assert set(p["slave_endpoints"]) == set(leaf_binds)
    assert len(p["slave_endpoints"]) == 8
    # 2 slaves -> one relay proves the hop; 1 slave -> no relays at all
    assert len(plan_tree(2, 2, master)["relays"]) == 1
    assert plan_tree(1, 2, master) == {
        "relays": [], "slave_endpoints": [master], "levels": 0}

    assert parse_relay_spec("tcp://h:5570") == ("tcp://h:5570",
                                                "tcp://*:5571")
    assert parse_relay_spec("tcp://h:5570:5599") == ("tcp://h:5570",
                                                     "tcp://*:5599")
    assert parse_relay_spec("tcp://h:5570:tcp://*:9") == ("tcp://h:5570",
                                                          "tcp://*:9")
    with pytest.raises(ValueError, match="--relay spec"):
        parse_relay_spec("not-an-endpoint")
    # fanout 1 is a chain, not a tree — refused, never an infinite loop
    with pytest.raises(ValueError, match="fanout"):
        plan_tree(4, 1, master)
    # the launcher surfaces of the planner and the role exclusivity
    from znicz_tpu import launcher

    assert launcher.main(["--relay", "tcp://h:5570", "--master"]) == 2


def test_job_batch_request(tmp_path):
    """``{"cmd": "job", "count": k}`` returns up to k jobs under ONE
    params broadcast; a count-less request keeps the historical flat
    reply shape (old slaves unchanged)."""
    from znicz_tpu.server import Server

    master_wf = _make_workflow(tmp_path / "m")
    server = Server(master_wf)
    assert server._handle({"cmd": "register", "id": "r1", "relay": True,
                           **_handshake_fields(master_wf)})["ok"]
    assert "r1" in server.relays
    rep = server._handle({"cmd": "job", "id": "r1", "count": 3})
    assert "jobs" in rep and "params" in rep
    assert len(rep["jobs"]) == 3
    assert len(server._inflight) == 3
    jids = [e["job_id"] for e in rep["jobs"]]
    assert len(set(jids)) == 3
    for e in rep["jobs"]:
        assert "job" in e and "trace_id" in e and "train" in e
        assert "params" not in e            # ONE broadcast per batch
    # flat shape for a count-less request
    flat = server._handle({"cmd": "job", "id": "r1"})
    assert "job" in flat and "params" in flat and "jobs" not in flat


# -- LR schedules under master/slave (satellite) -------------------------------


def _attach_lr_schedule(wf, gamma=0.5):
    from znicz_tpu.lr_adjust import ExpPolicy, LearningRateAdjust

    adj = LearningRateAdjust(wf, name="lr_adjust")
    for gd in wf.gds:
        adj.add_gd(gd, ExpPolicy(gamma=gamma))
    return adj


def test_lr_schedule_evaluated_at_dispatch(tmp_path):
    """The master evaluates lr_adjust policies at dispatch and stamps
    scheduled (lr, lr_bias) on each TRAIN minibatch — the unit-path
    clock exactly (minibatch k at pol(base, k-1)); eval minibatches are
    unstamped and do not advance the iteration."""
    from znicz_tpu.loader.base import TRAIN
    from znicz_tpu.server import Server

    master_wf = _make_workflow(tmp_path / "m")
    _attach_lr_schedule(master_wf, gamma=0.5)
    base = float(master_wf.gds[0].learning_rate)
    server = Server(master_wf)
    assert server._handle({"cmd": "register", "id": "s1",
                           **_handshake_fields(master_wf)})["ok"]
    seen = []
    for _ in range(8):
        rep = server._handle({"cmd": "job", "id": "s1"})
        job = rep["job"]
        if job["class"] == TRAIN:
            seen.append(job["hypers"][master_wf.gds[0].forward.name][0])
        else:
            assert "hypers" not in job
        server._handle({"cmd": "update", "id": "s1",
                        "job_id": rep["job_id"], "deltas": None,
                        "metrics": {"loss": 1.0, "n_err": 0}})
    # mb 0 at base, mb k at base * 0.5^(k-1)
    expect = [base] + [base * 0.5 ** k for k in range(len(seen) - 1)]
    assert seen == pytest.approx(expect)
    assert server._lr_iteration == len(seen)
    # the iteration survives a crash-resume round trip
    path = str(tmp_path / "resume.pickle")
    server.save_resume(path)
    server2 = Server(_make_workflow(tmp_path / "m2"), resume_path=path)
    assert server2._lr_iteration == server._lr_iteration


def test_scheduled_hypers_rows_and_unit_slave_application(tmp_path):
    """Both engines apply the shipped schedule: scheduled_hypers_rows
    overrides exactly (lr, lr_bias) per step for the fused scan, and
    the unit slave writes the stamped rates into its gds before they
    run."""
    from znicz_tpu.client import Client, scheduled_hypers_rows
    from znicz_tpu.loader.base import TRAIN

    base = {"fc1": tuple(np.float32(v) for v in
                         (0.1, 0.2, 0.0, 0.0, 0.0, 0.9, 0.9, 0.0))}
    mbs = [{"hypers": {"fc1": (0.05, 0.07)}}, {}]
    rows = scheduled_hypers_rows(base, mbs)
    assert rows["fc1"].shape == (2, 8)
    assert rows["fc1"][0, 0] == np.float32(0.05)
    assert rows["fc1"][0, 1] == np.float32(0.07)
    np.testing.assert_array_equal(rows["fc1"][0, 2:],
                                  np.asarray(base["fc1"][2:], np.float32))
    np.testing.assert_array_equal(rows["fc1"][1],
                                  np.asarray(base["fc1"], np.float32))

    wf = _make_workflow(tmp_path / "s")
    client = Client(wf, slave_id="lr-unit")
    gd = wf.gds[0]
    job = {"indices": np.zeros(60, np.int32), "size": 60, "class": TRAIN,
           "hypers": {gd.forward.name: (0.0125, 0.025)}}
    client._run_one(job, train=True)
    assert gd.learning_rate == pytest.approx(0.0125)
    assert gd.learning_rate_bias == pytest.approx(0.025)


def test_lr_schedule_advances_end_to_end(tmp_path):
    """One unit slave through the full socket stack: after a 2-epoch
    run under an exp schedule the SLAVE's gds hold the master's last
    scheduled rate — the 'schedules do not advance' limitation is
    gone."""
    from znicz_tpu.client import Client
    from znicz_tpu.server import Server

    endpoint = "tcp://127.0.0.1:17650"
    master_wf = _make_workflow(tmp_path / "m", max_epochs=2)
    _attach_lr_schedule(master_wf, gamma=0.9)
    base = float(master_wf.gds[0].learning_rate)
    server = Server(master_wf, endpoint=endpoint, job_timeout=60.0)
    slave = Client(_make_workflow(tmp_path / "s", max_epochs=2),
                   endpoint=endpoint, slave_id="lr-slave")
    t = threading.Thread(target=slave.run, daemon=True)
    t.start()
    server.serve()
    t.join(timeout=60)
    assert not t.is_alive()
    assert bool(master_wf.decision.complete)
    # 2 epochs x 5 TRAIN mbs: the last one dispatched at iteration 9,
    # scheduled at pol(base, 8) — and the slave really applied it
    assert server._lr_iteration == 10
    assert slave.workflow.gds[0].learning_rate == \
        pytest.approx(base * 0.9 ** 8)


# -- codec byte-identity through a relay hop -----------------------------------


def test_codec_byte_identity_through_relay_hop():
    """f32 wire: a single contribution re-emerges from the relay's
    flush as byte-identical tensor frames (sum of one == the delta, no
    re-quantization); the flush encoding is deterministic (same state
    -> same bytes, the resend-same-bytes property); int8 wire: two
    relays fed identically produce identical flush bytes, and the
    decoded sum matches within one quantization step."""
    rng = np.random.default_rng(17)
    deltas = {"fc1": {"weights": rng.normal(
        0, 0.01, (32, 16)).astype(np.float32),
        "bias": rng.normal(0, 0.01, 16).astype(np.float32)}}

    relay = _white_box_relay(wire_dtype="float32")
    rep = relay._child_update({"cmd": "update", "id": "s0", "job_id": 7,
                               "deltas": deltas,
                               "metrics": {"loss": 1.0}}, "s0")
    assert rep["ok"] is True
    entries, summed = list(relay._buffer), dict(relay._sum)
    flush1, _ = wire.encode_message(relay._flush_message(entries, summed))
    flush2, _ = wire.encode_message(relay._flush_message(entries, summed))
    assert [bytes(f) for f in flush1] == [bytes(f) for f in flush2]
    child, _ = wire.encode_message(
        {"cmd": "update", "id": "s0", "job_id": 7, "deltas": deltas,
         "metrics": {"loss": 1.0}})
    # same bytes in == same tensor bytes out (frame 0 is the skeleton)
    assert [bytes(f) for f in flush1[1:]] == [bytes(f) for f in child[1:]]
    dec, _ = wire.decode_message(flush1)
    np.testing.assert_array_equal(dec["deltas"]["fc1"]["weights"],
                                  deltas["fc1"]["weights"])
    assert dec["contributors"][0]["job_id"] == 7
    assert dec["contributors"][0]["delta"] is True

    # int8 upward re-encode: deterministic and within quantization error
    flushes = []
    for _ in range(2):
        r = _white_box_relay(wire_dtype="int8")
        for jid, sid in ((1, "s0"), (2, "s1")):
            assert r._child_update(
                {"cmd": "update", "id": sid, "job_id": jid,
                 "deltas": deltas, "metrics": {"loss": 1.0}}, sid)["ok"]
        frames, _ = wire.encode_message(
            r._flush_message(list(r._buffer), dict(r._sum)))
        flushes.append([bytes(f) for f in frames])
    assert flushes[0] == flushes[1]
    dec, _ = wire.decode_message(flushes[0])
    want = 2.0 * deltas["fc1"]["weights"]
    got = dec["deltas"]["fc1"]["weights"]
    scale = float(np.max(np.abs(want))) / 127.0
    assert float(np.max(np.abs(got - want))) <= scale + 1e-7


# -- per-child quarantine at the edge, master counters intact ------------------


def test_edge_quarantine_and_master_requeue(tmp_path):
    """A poisoned child is refused AT THE RELAY (the partial sum stays
    clean), the refusal rides the manifest, and the master's books stay
    exact: quarantined_updates ticks, the child's job is re-queued, the
    healthy sibling's delta lands, jobs_done attributes to the leaf."""
    from znicz_tpu.server import Server

    master_wf = _make_workflow(tmp_path / "m")
    server = Server(master_wf)
    assert server._handle({"cmd": "register", "id": "wb-relay",
                           "relay": True,
                           **_handshake_fields(master_wf)})["ok"]
    rep = server._handle({"cmd": "job", "id": "wb-relay", "count": 2})
    jid_a, jid_b = (e["job_id"] for e in rep["jobs"])

    relay = _white_box_relay()
    shapes = {f.name: {k: a.shape for k, a in f.params().items()}
              for f in master_wf.forwards if f.has_weights}
    poisoned = {n: {k: np.full(s, np.nan, np.float32)
                    for k, s in layer.items()}
                for n, layer in shapes.items()}
    healthy = {n: {k: np.full(s, 1e-4, np.float32)
                   for k, s in layer.items()}
               for n, layer in shapes.items()}
    rep = relay._child_update({"cmd": "update", "id": "s0",
                               "job_id": jid_a, "deltas": poisoned,
                               "metrics": {"loss": 1.0}}, "s0")
    assert rep["ok"] is False and rep.get("quarantined")
    assert "non-finite" in rep["error"]
    assert relay.refusals == 1
    assert not relay._sum                   # the sum never saw it
    rep = relay._child_update({"cmd": "update", "id": "s1",
                               "job_id": jid_b, "deltas": healthy,
                               "metrics": {"loss": 1.0, "n_err": 0}},
                              "s1")
    assert rep["ok"] is True

    before = {f.name: {k: np.array(a.map_read())
                       for k, a in f.params().items()}
              for f in master_wf.forwards if f.has_weights}
    up = server._handle(dict(
        relay._flush_message(list(relay._buffer), dict(relay._sum)),
        cmd="update", id="wb-relay"))
    assert up["ok"] is True
    assert up["outcomes"][jid_a] == "quarantined"
    assert up["outcomes"][jid_b] == "ok"
    assert server.quarantined_updates == 1
    assert server.aggregated_updates == 1
    assert len(server._pending) == 1        # the poisoned job came back
    assert server.jobs_done == 1
    assert server.jobs_by_slave == {"s1": 1}
    for f in master_wf.forwards:            # exactly the healthy delta
        if f.has_weights:
            for k, a in f.params().items():
                np.testing.assert_allclose(
                    np.array(a.map_read()),
                    before[f.name][k] + healthy[f.name][k], rtol=1e-5)

    # an exploded COMBINED sum: requeue-per-child, the sum is
    # indivisible so neither contributor's input may land
    server._delta_norms.extend([1e-4] * 5)
    rep = server._handle({"cmd": "job", "id": "wb-relay", "count": 2})
    jids = [e["job_id"] for e in rep["jobs"]]
    exploded = {n: {k: np.full(s, 1e5, np.float32)
                    for k, s in layer.items()}
                for n, layer in shapes.items()}
    before = {f.name: {k: np.array(a.map_read())
                       for k, a in f.params().items()}
              for f in master_wf.forwards if f.has_weights}
    up = server._handle({
        "cmd": "update", "id": "wb-relay", "deltas": exploded,
        "contributors": [
            {"id": "s0", "job_id": jids[0], "delta": True,
             "metrics": {"loss": 1.0, "n_err": 0}},
            {"id": "s1", "job_id": jids[1], "delta": True,
             "metrics": {"loss": 1.0, "n_err": 0}}]})
    assert up["ok"] is False and up.get("quarantined")
    assert server.quarantined_updates == 3  # 1 edge + 2 requeued here
    # both contributors' jobs came back (the first refused job was
    # re-issued inside this very batch, so the queue holds exactly 2)
    assert len(server._pending) == 2
    for f in master_wf.forwards:
        if f.has_weights:
            for k, a in f.params().items():
                np.testing.assert_array_equal(np.array(a.map_read()),
                                              before[f.name][k])
    # a stale contributor is dropped and counted, not applied
    up = server._handle({
        "cmd": "update", "id": "wb-relay", "deltas": None,
        "contributors": [{"id": "s0", "job_id": 99999,
                          "metrics": {"loss": 1.0, "n_err": 0}}]})
    assert up["ok"] is True and up["outcomes"][99999] == "stale"
    assert server.stale_updates == 1

    # resend idempotence (review finding): a relay re-sends the SAME
    # flush bytes after a lost reply; on the second delivery every
    # contributor is stale and the summed delta must be DROPPED — the
    # star's one-job-one-accepted-update invariant, kept for trees
    server._delta_norms.clear()     # drop the tiny norms seeded above
    rep = server._handle({"cmd": "job", "id": "wb-relay"})
    flush = {"cmd": "update", "id": "wb-relay", "deltas": healthy,
             "contributors": [{"id": "s0", "job_id": rep["job_id"],
                               "delta": True,
                               "metrics": {"loss": 1.0, "n_err": 0}}]}
    assert server._handle(dict(flush))["ok"] is True      # applied once
    after_first = {f.name: {k: np.array(a.map_read())
                            for k, a in f.params().items()}
                   for f in master_wf.forwards if f.has_weights}
    resent = server._handle(dict(flush))                  # same bytes
    assert resent["ok"] is True
    assert resent["outcomes"][rep["job_id"]] == "stale"
    for f in master_wf.forwards:
        if f.has_weights:
            for k, a in f.params().items():
                np.testing.assert_array_equal(np.array(a.map_read()),
                                              after_first[f.name][k])


def test_malformed_metrics_aborts_indivisible_aggregate(tmp_path):
    """Review finding: a DELTA-BEARING contributor with malformed
    metrics cannot be refused individually — its gradient is baked into
    the indivisible sum, and the star's order is refuse-BEFORE-apply.
    The whole aggregate is refused: nothing lands, the malformed child
    takes the bounded bad-reply strike, the innocent sibling is
    re-queued without one — so when the re-dispatched jobs come back
    their gradients land exactly once."""
    from znicz_tpu.server import Server

    master_wf = _make_workflow(tmp_path / "m")
    server = Server(master_wf)
    assert server._handle({"cmd": "register", "id": "r", "relay": True,
                           **_handshake_fields(master_wf)})["ok"]
    rep = server._handle({"cmd": "job", "id": "r", "count": 2})
    jid_a, jid_b = (e["job_id"] for e in rep["jobs"])
    shapes = {f.name: {k: a.shape for k, a in f.params().items()}
              for f in master_wf.forwards if f.has_weights}
    summed = {n: {k: np.full(s, 2e-4, np.float32)
                  for k, s in layer.items()}
              for n, layer in shapes.items()}
    before = {f.name: {k: np.array(a.map_read())
                       for k, a in f.params().items()}
              for f in master_wf.forwards if f.has_weights}
    up = server._handle({
        "cmd": "update", "id": "r", "deltas": summed,
        "contributors": [
            {"id": "s0", "job_id": jid_a, "delta": True,
             "metrics": [{"loss": 1.0}]},    # malformed: list, not dict
            {"id": "s1", "job_id": jid_b, "delta": True,
             "metrics": {"loss": 1.0, "n_err": 0}}]})
    assert up["ok"] is False and "not a dict" in up["error"]
    assert up["outcomes"][jid_a] == "refused"
    assert up["outcomes"][jid_b] == "requeued"
    assert server.bad_updates == 1          # only the malformed child
    assert server.jobs_requeued == 1        # the innocent sibling
    assert server.jobs_done == 0
    assert len(server._pending) == 2        # both jobs come back
    for f in master_wf.forwards:            # NOTHING landed
        if f.has_weights:
            for k, a in f.params().items():
                np.testing.assert_array_equal(np.array(a.map_read()),
                                              before[f.name][k])


def test_edge_shape_check_survives_flush_windows_and_spares_evals():
    """Round-3 review findings: (a) the relay learns param shapes from
    the first ACCEPTED delta for its lifetime, so a wrong-shaped child
    arriving FIRST in a later flush window (when the sum is empty) is
    refused itself instead of seeding the aggregate and getting its
    healthy siblings refused; (b) when an incoming aggregate's delta is
    refused, delta-less contributors (eval metrics) pass through intact
    — nothing of theirs was in the refused sum; (c) a flush that never
    shipped (stop() mid-run) does not tick relay_flushes."""
    good = {"fc": {"w": np.full((4, 3), 1e-3, np.float32)}}
    bad_shape = {"fc": {"w": np.full((2, 2), 1e-3, np.float32)}}

    relay = _white_box_relay()
    assert relay._child_update({"cmd": "update", "id": "s0", "job_id": 1,
                                "deltas": good,
                                "metrics": {"loss": 1.0}}, "s0")["ok"]
    # simulate a completed flush window: sum empties, shapes persist
    relay._buffer, relay._buffer_msgs = [], 0
    relay._sum, relay._sum_t0 = {}, None
    rep = relay._child_update({"cmd": "update", "id": "s1", "job_id": 2,
                               "deltas": bad_shape,
                               "metrics": {"loss": 1.0}}, "s1")
    assert rep["ok"] is False and "shape" in rep["error"]
    assert not relay._sum                   # never seeded the aggregate
    assert relay._child_update({"cmd": "update", "id": "s2", "job_id": 3,
                                "deltas": good,
                                "metrics": {"loss": 1.0}}, "s2")["ok"]

    # (b) eval contributors survive a refused aggregate
    relay2 = _white_box_relay()
    poisoned = {"fc": {"w": np.full((4, 3), np.nan, np.float32)}}
    rep = relay2._child_update({
        "cmd": "update", "id": "low-relay",
        "deltas": poisoned,
        "contributors": [
            {"id": "a", "job_id": 10, "delta": True,
             "metrics": {"loss": 1.0}},
            {"id": "b", "job_id": 11,
             "metrics": {"loss": 0.5, "n_err": 2}}]}, "low-relay")
    assert rep["ok"] is False and rep.get("quarantined")
    by_jid = {e["job_id"]: e for e in relay2._buffer}
    assert by_jid[10].get("refused") and "non-finite" in by_jid[10][
        "refused"]
    assert "refused" not in by_jid[11]
    assert by_jid[11]["metrics"] == {"loss": 0.5, "n_err": 2}
    assert relay2.refusals == 1

    # (c) an undelivered flush is not counted
    relay3 = _white_box_relay()
    relay3._stop.set()
    relay3._buffer = [{"id": "x", "job_id": 1}]
    relay3._buffer_msgs = 1
    relay3._flush()                         # rpc returns None: no send
    assert relay3.flushes == 0


def test_relay_child_ttl_eviction():
    """A dead sibling must not inflate the flush threshold forever: a
    child silent past child_ttl leaves the table (the master's TTL rule
    at the relay tier) and a re-register brings it straight back."""
    relay = _white_box_relay(n_children=2, fanout=2, child_ttl=0.1)
    relay._children["s0"] = time.time() - 1.0   # long silent
    relay._evict_children()
    assert set(relay.children) == {"s1"}
    # threshold follows the live membership: one child -> flush at 1
    relay._buffer.append({"id": "s1", "job_id": 1})
    relay._buffer_msgs = 1
    assert relay._flush_due()
    # rate-limited: a second call inside 1s is a no-op by design
    relay._children["ghost"] = time.time() - 9.0
    relay._evict_children()
    assert "ghost" in relay.children
    relay._last_evict = 0.0
    relay._evict_children()
    assert "ghost" not in relay.children


# -- the lean tree run ---------------------------------------------------------


def test_one_level_tree_trains_and_accounts(tmp_path):
    """2 slaves -> 1 relay -> master: training completes in the quality
    band, the master decodes FEWER update messages than jobs (the
    aggregation actually happened), jobs_done attributes to the LEAF
    ids, and the web_status topology panel shows the tree."""
    import json
    import urllib.request

    from znicz_tpu.client import Client
    from znicz_tpu.parallel.relay import Relay
    from znicz_tpu.server import Server
    from znicz_tpu.web_status import WebStatus

    master_ep = "tcp://127.0.0.1:17651"
    relay_ep = "tcp://127.0.0.1:17652"
    master_wf = _make_workflow(tmp_path / "m")
    server = Server(master_wf, endpoint=master_ep, job_timeout=60.0)
    relay = Relay(master_ep, relay_ep, relay_id="t1-relay").start()
    slaves = [Client(_make_workflow(tmp_path / f"s{i}"),
                     endpoint=relay_ep, slave_id=f"leaf{i}")
              for i in range(2)]
    errors = []

    def worker(s):
        try:
            s.run()
        except BaseException as e:
            errors.append((s.slave_id, repr(e)))
            raise

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in slaves]
    status = WebStatus(port=0).start()
    try:
        status.register(master_wf)
        status.register_server(server)
        status.register_relay(relay)
        for t in threads:
            t.start()
        server.serve()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)

        dec = master_wf.decision
        assert bool(dec.complete)
        valid = dec.epoch_metrics[1]
        assert valid is not None and valid["err_pct"] < 70.0, valid
        # aggregation really happened, and the books balance on LEAVES
        assert server.aggregated_updates >= 1
        assert server.updates_received < server.jobs_done
        assert server.jobs_done == sum(server.jobs_by_slave.values())
        assert server.jobs_by_slave.get("leaf0", 0) > 0
        assert server.jobs_by_slave.get("leaf1", 0) > 0
        assert "t1-relay" not in server.jobs_by_slave
        assert "t1-relay" in server.relays
        assert relay.flushes >= 1
        assert relay.contributions >= server.jobs_done
        # every slave's view went through the relay: the master's only
        # direct member is the relay
        assert set(server.jobs_by_slave) == {"leaf0", "leaf1"}
        # the tree-topology panel
        with urllib.request.urlopen(
                f"http://127.0.0.1:{status.port}/status.json") as r:
            snap = json.load(r)
        master = snap["master"]
        assert [s["id"] for s in master["slaves"]] == ["t1-relay"]
        assert master["slaves"][0]["relay"] is True
        assert {s["id"] for s in master["leaves"]} == {"leaf0", "leaf1"}
        assert master["aggregated_updates"] == server.aggregated_updates
        assert snap["relays"][0]["id"] == "t1-relay"
        assert {c["id"] for c in snap["relays"][0]["children"]} == \
            {"leaf0", "leaf1"}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{status.port}/") as r:
            page = r.read().decode()
        assert "Relay t1-relay" in page and "(relay)" in page
    finally:
        status.stop()
        relay.stop()


def test_relay_death_children_fall_back_upstream(tmp_path):
    """Relay death mid-run: in-flight work requeues via the master's
    existing TTL reaper and the children — their reconnect budget to
    the dead relay spent — fall back to the UPSTREAM endpoint the relay
    advertised at register time, re-register, and finish the run."""
    from znicz_tpu.client import Client
    from znicz_tpu.parallel.chaos import RelayHarness
    from znicz_tpu.server import Server

    master_ep = "tcp://127.0.0.1:17653"
    relay_ep = "tcp://127.0.0.1:17654"
    master_wf = _make_workflow(tmp_path / "m")
    server = Server(master_wf, endpoint=master_ep, job_timeout=4.0)
    server_thread = threading.Thread(target=server.serve, daemon=True)
    server_thread.start()
    harness = RelayHarness(master_ep, relay_ep, relay_id="doomed-relay")
    harness.start()

    slaves = [Client(_make_workflow(tmp_path / f"s{i}"),
                     endpoint=relay_ep, slave_id=f"phx{i}")
              for i in range(2)]
    errors = []

    def worker(s):
        try:
            s.run(recv_timeout=0.75, max_reconnects=2,
                  backoff_base=0.05, backoff_cap=0.2)
        except BaseException as e:
            errors.append((s.slave_id, repr(e)))
            raise

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in slaves]
    for t in threads:
        t.start()
    deadline = time.time() + 60
    while server.jobs_done < 2 and time.time() < deadline:
        time.sleep(0.05)
    assert server.jobs_done >= 2
    harness.kill()                          # the relay dies for good

    server_thread.join(timeout=120)
    assert not server_thread.is_alive()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)

    dec = master_wf.decision
    assert bool(dec.complete)
    valid = dec.epoch_metrics[1]
    assert valid is not None and valid["err_pct"] < 70.0, valid
    # both children really switched to the advertised upstream
    for s in slaves:
        assert s.endpoint == master_ep, s.endpoint
        assert s.reconnects >= 1
    # post-fallback the leaves worked DIRECTLY for the master too; the
    # books still balance on leaf ids only
    assert server.jobs_done == sum(server.jobs_by_slave.values())
    assert set(server.jobs_by_slave) <= {"phx0", "phx1"}
    assert sum(server.jobs_by_slave.values()) == server.jobs_done


def test_fused_slaves_through_relay_with_lr_schedule(tmp_path):
    """The fused engine through the tree: a FusedClient working via a
    relay under a master-evaluated LR schedule — segment jobs, the
    scheduled per-step hypers rows, delta aggregation and decision
    accounting all compose."""
    from znicz_tpu.client import FusedClient
    from znicz_tpu.parallel.relay import Relay
    from znicz_tpu.server import Server

    master_ep = "tcp://127.0.0.1:17655"
    relay_ep = "tcp://127.0.0.1:17656"
    master_wf = _make_workflow(tmp_path / "m")
    _attach_lr_schedule(master_wf, gamma=0.9)
    server = Server(master_wf, endpoint=master_ep, job_timeout=60.0,
                    segment_steps=3)
    relay = Relay(master_ep, relay_ep, relay_id="f-relay").start()
    slave = FusedClient(_make_workflow(tmp_path / "s"),
                        endpoint=relay_ep, slave_id="fused-leaf")
    t = threading.Thread(target=slave.run, daemon=True)
    try:
        t.start()
        server.serve()
        t.join(timeout=120)
        assert not t.is_alive()
    finally:
        relay.stop()
    dec = master_wf.decision
    assert bool(dec.complete)
    valid = dec.epoch_metrics[1]
    assert valid is not None and valid["err_pct"] < 70.0, valid
    assert server._lr_iteration == 15       # the schedule advanced
    assert server.aggregated_updates >= 1
    assert server.jobs_by_slave.get("fused-leaf", 0) > 0
    assert server.jobs_done == sum(server.jobs_by_slave.values())


# -- the slow 2-level chaos soak -----------------------------------------------


@pytest.mark.slow
def test_tree_cuts_bytes_and_decodes_into_the_master(tmp_path):
    """The O(slaves) -> O(fanout) flip at its smallest size: the same
    seeded job with four slaves on the master (a star) and with the four
    behind ONE relay.  The relay sums its children's deltas and forwards
    one, and fetches their jobs in batches under one params broadcast, so
    the master takes in fewer bytes and decodes fewer messages."""
    from znicz_tpu.client import Client
    from znicz_tpu.parallel.relay import Relay
    from znicz_tpu.server import Server

    def fleet(tag, master_ep, slave_ep):
        wf = _make_workflow(tmp_path / f"{tag}-m")
        server = Server(wf, endpoint=master_ep, job_timeout=60.0)
        slaves = [Client(_make_workflow(tmp_path / f"{tag}-s{i}"),
                         endpoint=slave_ep, slave_id=f"{tag}{i}")
                  for i in range(4)]
        errors = []

        def worker(s):
            try:
                s.run()
            except BaseException as e:
                errors.append((s.slave_id, repr(e)))
                raise

        threads = [threading.Thread(target=worker, args=(s,), daemon=True)
                   for s in slaves]
        for t in threads:
            t.start()
        server.serve()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert not any(t.is_alive() for t in threads)
        assert bool(wf.decision.complete)
        return server

    star = fleet("star", "tcp://127.0.0.1:17670", "tcp://127.0.0.1:17670")
    relay = Relay("tcp://127.0.0.1:17671", "tcp://127.0.0.1:17672",
                  relay_id="cut-relay").start()
    try:
        tree = fleet("tree", "tcp://127.0.0.1:17671",
                     "tcp://127.0.0.1:17672")
    finally:
        relay.stop()
    assert star.aggregated_updates == 0 and tree.aggregated_updates >= 1
    assert tree.jobs_done == sum(tree.jobs_by_slave.values())
    per_job = [(int(s.bytes_in) / s.jobs_done,
                int(s.codec.messages_in) / s.jobs_done)
               for s in (star, tree)]
    (star_bytes, star_msgs), (tree_bytes, tree_msgs) = per_job
    assert tree_bytes < 0.8 * star_bytes, per_job
    assert tree_msgs < 0.8 * star_msgs, per_job


@pytest.mark.slow
def test_two_level_tree_chaos_soak(tmp_path):
    """Everything at once on a 2-level tree: seeded ChaosProxy
    drop/corrupt/dup/delay on the mid-relay -> master link (the relay's
    upstream machinery rides the same fault model as a slave's), a leaf
    relay killed and RESTARTED at the same bind mid-run (children
    reconnect + re-register through the existing path), 4 slaves.
    Training completes in the quality band with exact leaf
    accounting."""
    from znicz_tpu.client import Client
    from znicz_tpu.parallel.chaos import (ChaosProxy, FaultSchedule,
                                          RelayHarness)
    from znicz_tpu.parallel.relay import Relay
    from znicz_tpu.server import Server

    master_ep = "tcp://127.0.0.1:17660"
    proxy_front = "tcp://127.0.0.1:17661"   # mid relay dials this
    mid_ep = "tcp://127.0.0.1:17662"
    leaf_a = "tcp://127.0.0.1:17663"
    leaf_b = "tcp://127.0.0.1:17664"
    proxy = ChaosProxy(proxy_front, master_ep,
                       FaultSchedule(5, drop=0.05, corrupt=0.05,
                                     duplicate=0.04, delay=0.06,
                                     delay_s=(0.02, 0.2))).start()
    master_wf = _make_workflow(tmp_path / "m", max_epochs=4)
    server = Server(master_wf, endpoint=master_ep, job_timeout=6.0)
    server_thread = threading.Thread(
        target=server.serve, kwargs={"linger": 8.0}, daemon=True)
    server_thread.start()
    mid = Relay(proxy_front, mid_ep, relay_id="soak-mid",
                recv_timeout=1.0, max_reconnects=60).start()
    leaf_harness = RelayHarness(mid_ep, leaf_a, relay_id="soak-leaf-a",
                                recv_timeout=2.0, max_reconnects=60)
    leaf_harness.start()
    leaf2 = Relay(mid_ep, leaf_b, relay_id="soak-leaf-b",
                  recv_timeout=2.0, max_reconnects=60).start()

    slaves = [Client(_make_workflow(tmp_path / f"s{i}", max_epochs=4),
                     endpoint=(leaf_a if i < 2 else leaf_b),
                     slave_id=f"soak{i}") for i in range(4)]
    errors = []

    def worker(s):
        try:
            s.run(recv_timeout=1.0, max_reconnects=80,
                  backoff_base=0.05, backoff_cap=0.4,
                  connect_retries=80)
        except BaseException as e:
            errors.append((s.slave_id, repr(e)))
            raise

    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in slaves]
    try:
        for t in threads:
            t.start()
        deadline = time.time() + 120
        while server.jobs_done < 4 and time.time() < deadline:
            time.sleep(0.05)
        assert server.jobs_done >= 4
        leaf_harness.restart()              # leaf relay dies + comes back
        server_thread.join(timeout=300)
        assert not server_thread.is_alive()
        for t in threads:
            t.join(timeout=120)
    finally:
        proxy.stop()
        mid.stop()
        leaf_harness.kill()
        leaf2.stop()
    assert not errors, errors
    assert not any(t.is_alive() for t in threads)

    dec = master_wf.decision
    assert bool(dec.complete)
    valid = dec.epoch_metrics[1]
    assert valid is not None and valid["err_pct"] < 70.0, valid
    assert proxy.total_faults() > 0
    assert server.aggregated_updates >= 1
    assert server.jobs_done == sum(server.jobs_by_slave.values())
    assert set(server.jobs_by_slave) <= {f"soak{i}" for i in range(4)}
    # the relay rode the chaos out on its own reconnect machinery
    assert mid.upstream_reconnects >= 1 or proxy.counters["rep"][
        "corrupt"] + proxy.counters["req"]["drop"] == 0
