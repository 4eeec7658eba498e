"""Replica-fleet balancer (ISSUE 12): TTL'd heartbeat membership,
least-loaded dispatch, exactly-once failover, hedged retries, canary
rollover with auto-rollback + healing, the per-endpoint client breaker,
the aggregate /readyz + fleet panel, and the ChaosProxy soak (lean in
tier-1; the full soak rides the ``slow`` marker).

Most tests run against :class:`ScriptedReplica` — the model-free fake
replica harness (parallel/chaos.py) that speaks the replica protocol
(heartbeats, swap/rollback, replica_id-stamped replies) with a scripted
``y = x * scale(generation)`` forward, so fleet semantics are proven
without paying a single jit warmup.  One test runs a REAL
``InferenceServer`` replica end-to-end to pin the frontend's heartbeat/
stamp integration."""

import json
import time
import urllib.request

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.config import root


X1 = np.arange(4, dtype=np.float32).reshape(1, 4) + 1.0


def _fleet(n=2, snapshots=None, bal_kwargs=None, rep_kwargs=None):
    """A started balancer + n started scripted replicas."""
    from znicz_tpu.parallel.chaos import ScriptedReplica
    from znicz_tpu.serving import ReplicaBalancer

    kwargs = dict(replica_ttl_s=1.0, heartbeat_s=0.25,
                  failover_timeout_s=0.5, failover_tries=4,
                  hedge_floor_s=0.25, canary_requests=6,
                  parity_every=2, canary_timeout_s=20.0)
    kwargs.update(bal_kwargs or {})
    bal = ReplicaBalancer(**kwargs).start()
    reps = [ScriptedReplica(bal.endpoint, f"r{i}",
                            snapshots=dict(snapshots or {}),
                            **(rep_kwargs or {})).start()
            for i in range(n)]
    t0 = time.time()
    while bal.ready_count() < n:
        assert time.time() - t0 < 10, "fleet never became ready"
        time.sleep(0.02)
    return bal, reps


def _client(bal, **kw):
    from znicz_tpu.serving import InferenceClient

    kw.setdefault("timeout", 10.0)
    kw.setdefault("breaker_failures", 0)
    kw.setdefault("resend_after_s", 30.0)   # balancer failover, not
    # client resends, is under test — resends would mask lost replies
    return InferenceClient(bal.endpoint, **kw)


def _drive_until(cli, pred, budget=15.0, x=X1):
    t0 = time.time()
    while time.time() - t0 < budget:
        for _ in range(4):
            cli.result(cli.submit(x), timeout=8)
        if pred():
            return True
    return False


def _teardown(bal, reps, *clis):
    for c in clis:
        c.close()
    bal.stop()
    for r in reps:
        r.kill()


# -- membership + dispatch -----------------------------------------------------


def test_heartbeat_membership_ttl_and_spread():
    bal, reps = _fleet(2)
    cli = _client(bal)
    try:
        for _ in range(16):
            rep = cli.result(cli.submit(X1))
            # the balancer stamp + the replica stamp + the generation,
            # on every reply (the client breaker and A/B attribution
            # ride these)
            assert rep.get("lb") is True
            assert rep["replica_id"] in ("r0", "r1")
            assert rep["gen"] == 1
            assert np.array_equal(rep["y"], X1)
        # least-loaded over two idle replicas spreads the work
        assert reps[0].served > 0 and reps[1].served > 0
        st = bal.stats()
        assert st["total_replicas"] == 2 and st["ready_replicas"] == 2
        row = st["replicas"][0]
        for key in ("gen", "queue_depth", "in_flight",
                    "last_heartbeat_s", "snapshot_path",
                    "p99_ms_by_bucket"):
            assert key in row
        # TTL eviction: a silent replica leaves the membership
        reps[0].kill()
        t0 = time.time()
        while bal.member_count() > 1:
            assert time.time() - t0 < 10
            time.sleep(0.05)
        assert bal.replicas_lost == 1
        # ... and the survivor serves alone
        assert cli.result(cli.submit(X1))["replica_id"] == "r1"
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)


def test_exactly_once_failover_through_a_blackhole():
    """A replica that accepts requests and never answers: the balancer
    re-dispatches the SAME bytes after its failover timeout, and every
    request is answered exactly once — no double delivery, no
    silence."""
    import collections

    bal, reps = _fleet(2, bal_kwargs={"hedge": False},
                       rep_kwargs={})
    reps[0].kill()
    from znicz_tpu.parallel.chaos import ScriptedReplica

    hole = ScriptedReplica(bal.endpoint, "hole", blackhole=True).start()
    reps[0] = hole
    while bal.member_count() < 2 or "hole" not in {
            m["replica_id"] for m in bal.stats()["replicas"]}:
        time.sleep(0.02)
    cli = _client(bal)
    try:
        rids = [cli.submit(X1) for _ in range(10)]
        got = collections.Counter()
        t0 = time.time()
        while sum(got.values()) < 10 and time.time() - t0 < 12:
            for rep in cli.collect(0.05):
                got[rep["req_id"]] += 1
                assert rep["ok"], rep
        assert sorted(got) == sorted(rids)
        assert max(got.values()) == 1          # exactly once
        assert bal.failovers > 0
        assert hole.swallowed > 0              # the hole really ate some
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)


def test_hedged_retries_race_the_tail():
    """One replica stalls every 2nd request well past the hedge delay:
    the hedge races a second replica, the first reply wins, the loser
    is deduped — tail latency is bounded by the race, not the stall."""
    bal, reps = _fleet(1, bal_kwargs={"hedge_floor_s": 0.1,
                                      "failover_timeout_s": 3.0,
                                      "replica_ttl_s": 3.0},
                       rep_kwargs={"stall_s": 0.7, "stall_every": 2})
    from znicz_tpu.parallel.chaos import ScriptedReplica

    fast = ScriptedReplica(bal.endpoint, "fast").start()
    reps.append(fast)
    while bal.ready_count() < 2:
        time.sleep(0.02)
    cli = _client(bal)
    try:
        lats = []
        for _ in range(20):
            t0 = time.time()
            rep = cli.result(cli.submit(X1), timeout=8)
            lats.append(time.time() - t0)
            assert np.array_equal(rep["y"], X1)
        assert bal.hedges > 0 and bal.hedge_wins > 0
        assert bal.dup_replies_dropped > 0     # the stalled loser lands
        # late and is deduped, never double-delivered
        assert max(lats) < 0.7                 # the race beat the stall
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)


# -- canary rollover (promote / heal / auto-rollback) --------------------------


def test_canary_rollover_promote_heal_and_regression_rollback():
    snaps = {"same": 1.0, "diff": 3.0}
    bal, reps = _fleet(3, snapshots=snaps)
    cli = _client(bal)
    try:
        # (1) healthy wave: same params under a new path -> parity
        # probes agree, p99 in band, fleet promotes canary -> full
        rep = cli.result(cli._send({"cmd": "swap", "path": "same"}))
        assert rep["ok"] and rep["swap_started"] and rep["canary"]
        assert _drive_until(cli, lambda: bal.rollovers == 1)
        assert bal.parity_checks > 0 and bal.parity_mismatches == 0
        assert bal.rollover_history[-1]["result"] == "promoted"
        gens = {cli.result(cli.submit(X1))["gen"] for _ in range(6)}
        assert gens == {2}
        assert bal.stats()["fleet_path"] == "same"
        # a second swap while one runs is refused readably
        from znicz_tpu.serving import InferenceError

        # (2) healing: a restarted replica boots with its boot snapshot
        # and an off-fleet generation; the balancer re-swaps it onto
        # the promoted path, restoring generation lockstep
        reps[0].kill()
        time.sleep(0.1)
        reps[0].restart()
        assert _drive_until(cli, lambda: bal.member_count() == 3 and all(
            m["gen"] == 2 and m["snapshot_path"] == "same"
            for m in bal.stats()["replicas"]))
        assert bal.heals == 1                  # debounced: exactly one
        # (3) forced regression: genuinely different params under an
        # expect-parity swap -> shadow probes mismatch -> auto-rollback,
        # losing generation's record preserved for the postmortem
        rep = cli.result(cli._send({"cmd": "swap", "path": "diff"}))
        assert rep["ok"]
        assert _drive_until(cli, lambda: bal.rollbacks == 1)
        record = bal.rollover_history[-1]
        assert record["result"] == "rolled_back"
        assert "parity" in record["reason"]
        assert record["parity_mismatches"] >= 1
        assert record["old_gen"] == 2 and record["new_gen"] == 3
        # the fleet still serves the OLD generation bit-exactly, stamp
        # included (ModelRunner.rollback restores the retained tuple)
        for _ in range(6):
            rep = cli.result(cli.submit(X1))
            assert rep["gen"] == 2
            assert np.array_equal(rep["y"], X1)
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)


def test_canary_p99_regression_rolls_back():
    """The OTHER regression trigger: a new generation whose answers
    agree bit-exactly but arrive slow.  The scripted 'upgrade' stalls
    every reply 0.35s; with hedging off and the failover timeout above
    the stall, the canary's p99 blows the `canary_p99_mult` band and
    the wave rolls back — the fleet ends on the old (fast) generation,
    losing wave recorded with both p99s for the postmortem."""
    snaps = {"slow": {"scale": 1.0, "stall_s": 0.35}}
    bal, reps = _fleet(3, snapshots=snaps,
                       bal_kwargs={"hedge": False,
                                   "failover_timeout_s": 2.0,
                                   "canary_requests": 5,
                                   "canary_p99_mult": 3.0,
                                   "parity_every": 1000})
    cli = _client(bal, timeout=15.0)
    try:
        rep = cli.result(cli._send({"cmd": "swap", "path": "slow",
                                    "parity": False}))
        assert rep["ok"]
        assert _drive_until(cli, lambda: bal.rollbacks == 1, budget=25)
        record = bal.rollover_history[-1]
        assert record["result"] == "rolled_back"
        assert "p99" in record["reason"]
        assert record["canary_p99_ms"] > 3.0 * record["old_p99_ms"]
        gens = {cli.result(cli.submit(X1))["gen"] for _ in range(4)}
        assert gens == {1}                     # stamp restored too
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)


def test_rollover_refused_below_health_floor():
    """No ready replicas / non-uniform generations refuse the wave
    readably instead of half-flipping a fleet."""
    from znicz_tpu.serving import InferenceError, ReplicaBalancer

    bal = ReplicaBalancer().start()
    cli = _client(bal)
    try:
        with pytest.raises(InferenceError, match="no ready replicas"):
            cli.result(cli._send({"cmd": "swap", "path": "x"}))
        with pytest.raises(InferenceError, match="needs a snapshot"):
            cli.result(cli._send({"cmd": "swap"}))
    finally:
        cli.close()
        bal.stop()


# -- per-endpoint client breaker (ISSUE 12 satellite) --------------------------


def test_client_breaker_is_per_endpoint_behind_a_balancer():
    """Service-scoped failures stamped with a replica_id by a balancer
    reply open THAT replica's window — never the whole-service breaker
    (the balancer is already routing around the sick replica)."""
    from znicz_tpu.serving import InferenceError

    # a 1-replica fleet whose replica sheds service-scoped, and a
    # failover budget of 1 so the shed is FORWARDED, not retried
    bal, reps = _fleet(1, bal_kwargs={"failover_tries": 1,
                                      "hedge": False},
                       rep_kwargs={"refuse": ("shed", "service")})
    cli = _client(bal, breaker_failures=3, breaker_window=6)
    try:
        for _ in range(5):
            with pytest.raises(InferenceError):
                cli.result(cli.submit(X1))
        # the sick replica's window opened; the service breaker did NOT
        assert cli.breaker_state == "closed"
        assert cli.breaker_state_for("r0") == "open"
        assert cli.replica_breaker_opens == 1
        assert cli.replica_breakers()["r0"]["failures"] >= 3
        cli.submit(X1)                         # no CircuitOpenError
    finally:
        _teardown(bal, reps, cli)


def test_client_breaker_still_global_against_a_direct_runner():
    """The same stamped refusals WITHOUT the balancer's ``lb`` stamp
    (a direct runner) keep feeding the whole-service breaker."""
    from znicz_tpu.parallel.chaos import ScriptedReplica
    from znicz_tpu.serving import (CircuitOpenError, InferenceClient,
                                   InferenceError)

    # the scripted replica doubles as a direct service: its replies
    # carry replica_id but no lb stamp
    from znicz_tpu.serving import ReplicaBalancer

    bal = ReplicaBalancer().start()     # just a heartbeat sink
    sick = ScriptedReplica(bal.endpoint, "sick",
                           refuse=("shed", "service")).start()
    cli = InferenceClient(sick.endpoint, timeout=5.0,
                          breaker_failures=3, breaker_window=6,
                          resend_after_s=30.0)
    try:
        opened = False
        for _ in range(8):
            try:
                cli.result(cli.submit(X1))
            except InferenceError:
                continue
            except CircuitOpenError:
                opened = True
                break
        assert opened or cli.breaker_state == "open"
        assert cli.breaker_opens >= 1
        assert cli.replica_breakers() == {}    # per-endpoint untouched
    finally:
        cli.close()
        sick.kill()
        bal.stop()


# -- aggregate readiness + fleet panel (ISSUE 12 satellite) --------------------


def test_web_status_aggregate_readyz_and_fleet_panel():
    from znicz_tpu.web_status import WebStatus

    bal, reps = _fleet(2, bal_kwargs={"min_replicas": 2})
    status = WebStatus(port=0).start()
    status.register_balancer(bal)
    base = f"http://127.0.0.1:{status.port}"

    def get(path):
        try:
            with urllib.request.urlopen(base + path) as r:
                return r.status, r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.read().decode()

    try:
        code, body = get("/readyz")
        ready = json.loads(body)
        assert code == 200 and ready["ready"]
        assert ready["ready_replicas"] == 2 and ready["total"] == 2
        assert ready["min_replicas"] == 2
        code, _ = get("/healthz")
        assert code == 200
        # the fleet panel: per-replica rows + the ledger line
        code, body = get("/status.json")
        snap = json.loads(body)
        rows = snap["balancer"]["replicas"]
        assert {r["replica_id"] for r in rows} == {"r0", "r1"}
        assert all("last_heartbeat_s" in r and "gen" in r for r in rows)
        assert snap["balancer"]["ledger"]["balanced"]
        _, html_body = get("/")
        assert "Replica fleet" in html_body
        # below quorum: the AGGREGATE goes 503 (one process dying would
        # never have flipped the old per-process answer)
        reps[0].kill()
        t0 = time.time()
        while True:
            code, body = get("/readyz")
            if code == 503:
                break
            assert time.time() - t0 < 10
            time.sleep(0.05)
        assert "below the min_replicas quorum" in json.loads(
            body)["reason"]
    finally:
        status.stop()
        _teardown(bal, reps)


# -- real-replica integration --------------------------------------------------


def _tiny_wf():
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 120
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    wf = mnist.MnistWorkflow()
    wf.initialize(device=None)
    return wf


def test_real_replica_announces_and_serves_through_balancer():
    """One REAL InferenceServer behind the balancer: the frontend's
    heartbeat loop registers membership, piggybacks per-bucket p99, and
    stamps replica_id/gen on replies the balancer forwards."""
    from znicz_tpu.serving import InferenceServer, ReplicaBalancer

    from znicz_tpu.serving import InferenceClient

    bal = ReplicaBalancer(replica_ttl_s=2.0).start()
    srv = InferenceServer(_tiny_wf(), max_batch=4, max_delay_ms=1.0,
                          announce=bal.endpoint,
                          replica_id="real-0").start()
    cli = InferenceClient(bal.endpoint, timeout=20.0,
                          breaker_failures=0)
    try:
        t0 = time.time()
        while bal.ready_count() < 1:
            assert time.time() - t0 < 20
            time.sleep(0.05)
        x = np.zeros((1, 28 * 28), np.float32)
        direct = srv.runner.infer(srv.runner.pad(x, 1))[:1]
        for _ in range(5):
            rep = cli.result(cli.submit(x))
            assert rep["lb"] and rep["replica_id"] == "real-0"
            assert rep["gen"] == 1
            # through-the-balancer result == the runner's own forward
            assert np.array_equal(rep["y"], direct)
        assert srv.heartbeats_out > 0
        member = bal.stats()["replicas"][0]
        assert member["replica_id"] == "real-0"
        # per-bucket p99 telemetry piggybacked once traffic flowed
        t0 = time.time()
        while not member["p99_ms_by_bucket"]:
            assert time.time() - t0 < 10
            time.sleep(0.1)
            member = bal.stats()["replicas"][0]
        assert 1 in member["p99_ms_by_bucket"]  # rung-1 latencies
        # rollback is a REPLICA control command (the balancer's wave
        # machinery sends it over the data plane); with nothing
        # retained it is a readable refusal
        from znicz_tpu.serving import InferenceClient, InferenceError

        direct = InferenceClient(srv.endpoint, timeout=10.0,
                                 breaker_failures=0)
        try:
            with pytest.raises(InferenceError,
                               match="no previous generation"):
                direct.result(direct._send({"cmd": "rollback"}))
        finally:
            direct.close()
        assert bal.ledger()["balanced"]
    finally:
        cli.close()
        srv.stop()
        bal.stop()


# -- autoscaler (ISSUE 17) -----------------------------------------------------


def test_autoscaler_spawns_to_cap_and_drains_back_to_quorum():
    """The elasticity control loop over scripted replicas: a forced
    'high' band spawns through the FleetScaler up to ``autoscale_max``
    (pending-spawn reservations stop over-spawn at the cap), then a
    forced 'low' band drains-then-retires back down to — and never
    below — the ``min_replicas`` quorum, with traffic served and the
    ledger balanced throughout."""
    from znicz_tpu.parallel.chaos import FleetScaler, ScriptedReplica

    bal, reps = _fleet(2, bal_kwargs=dict(min_replicas=2))
    scaler = FleetScaler(
        lambda i: ScriptedReplica(bal.endpoint, f"s{i}"))
    for r in reps:
        scaler.adopt(r)
    cli = _client(bal)
    from znicz_tpu import telemetry

    seq0 = telemetry.journal().last_seq
    try:
        # high_load < 0 forces every eval 'high' — a deterministic ramp
        bal.enable_autoscale(
            scaler.spawn, scaler.retire, autoscale_max=4,
            autoscale_high_load=-1.0, autoscale_low_load=-2.0,
            autoscale_up_after=2, autoscale_down_after=2,
            autoscale_eval_s=0.05, autoscale_cooldown_s=0.05,
            autoscale_drain_timeout_s=5.0)
        t0 = time.time()
        while bal.member_count() < 4:
            assert time.time() - t0 < 15, "never scaled to the cap"
            time.sleep(0.02)
        assert bal.scale_ups >= 2
        # the journal says why: every scale-up is an event that carries
        # the load numbers that drove it, seqs strictly increasing
        events = telemetry.journal().since(seq0)
        ups = [e for e in events if e["kind"] == "autoscale_up"]
        assert len(ups) >= 2, events
        assert all({"load", "parked", "members", "pending"} <= set(e)
                   for e in ups), ups
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(set(seqs)) and seqs[0] > seq0
        st = bal.stats()["autoscale"]
        assert st["enabled"] and st["max"] == 4
        # at the cap: no spawns pile up past it
        time.sleep(0.3)
        assert bal.member_count() == 4
        assert scaler.counts["spawned"] == 2
        for _ in range(8):
            assert cli.result(cli.submit(X1))["lb"] is True
        # force 'low': drain-then-retire to the quorum, not past it.
        # Retired members are evicted immediately, but a last
        # heartbeat can race the kill and re-add one briefly — the
        # cooldown sits ABOVE the 1.0s replica TTL so even that
        # corpse is gone before the next decision
        bal.enable_autoscale(
            scaler.spawn, scaler.retire, autoscale_max=4,
            autoscale_high_load=1e9, autoscale_low_load=1e9,
            autoscale_cooldown_s=1.5)
        t0 = time.time()
        while bal.member_count() > 2:
            assert time.time() - t0 < 25, "never drained to quorum"
            time.sleep(0.05)
        time.sleep(0.5)
        assert bal.member_count() == 2          # quorum floor holds
        assert bal.scale_downs == 2
        assert scaler.counts["retired"] == 2
        assert not bal.stats()["autoscale"]["retiring"]
        assert cli.result(cli.submit(X1))["lb"] is True
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)
        scaler.stop_all()


def test_scale_down_never_counts_a_healing_replica_as_capacity():
    """The ISSUE 17 satellite bugfix, as a regression test: a replica
    mid-heal is serving STALE params and about to swap — it must not
    count as servable capacity, or an idle band retires the last
    HEALTHY replica while the heal is still in flight.  With one of
    two replicas healing, the scale-down gate sees ONE servable
    replica and (min_replicas=1) refuses to act; the moment the heal
    clears, the same band drains exactly one."""
    from znicz_tpu.parallel.chaos import FleetScaler, ScriptedReplica

    bal, reps = _fleet(2, bal_kwargs=dict(min_replicas=1))
    scaler = FleetScaler(
        lambda i: ScriptedReplica(bal.endpoint, f"s{i}"))
    for r in reps:
        scaler.adopt(r)
    cli = _client(bal)
    try:
        with bal._lock:                 # r1 enters its heal window
            bal._healing["r1"] = time.time()
        bal.enable_autoscale(
            scaler.spawn, scaler.retire,
            autoscale_high_load=1e9, autoscale_low_load=1e9,
            autoscale_down_after=1, autoscale_eval_s=0.05,
            autoscale_cooldown_s=0.2)
        time.sleep(0.6)                 # many idle 'low' evals
        assert bal.scale_downs == 0 and bal.member_count() == 2
        st = bal.stats()
        assert st["autoscale"]["servable"] == 1
        rows = {r["replica_id"]: r for r in st["replicas"]}
        assert rows["r1"]["healing"] and not rows["r0"]["healing"]
        with bal._lock:                 # heal lands: r1 back on fleet
            bal._healing.pop("r1")
        t0 = time.time()
        while bal.member_count() > 1:
            assert time.time() - t0 < 15, "never drained post-heal"
            time.sleep(0.05)
        assert bal.scale_downs == 1
        assert cli.result(cli.submit(X1))["lb"] is True
        assert bal.ledger()["balanced"]
    finally:
        _teardown(bal, reps, cli)
        scaler.stop_all()


# -- chaos soak (ISSUE 12 satellite) -------------------------------------------


def _free_port_endpoint():
    """A concrete loopback endpoint: ChaosProxy does not expose a
    resolved wildcard bind."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"tcp://127.0.0.1:{port}"


def test_chaos_soak_lean():
    """Lean soak: proxy corruption/drop/dup/delay + one kill/restart."""
    _run_soak(_free_port_endpoint(), n_requests=50, kills=True,
              swap=False)


def _run_soak(front, n_requests, kills, swap):
    from znicz_tpu.parallel.chaos import ChaosProxy, FaultSchedule
    from znicz_tpu.serving import InferenceClient

    snaps = {"v2": 1.0}
    bal, reps = _fleet(2, snapshots=snaps,
                       bal_kwargs={"failover_timeout_s": 0.8,
                                   "replica_ttl_s": 1.5,
                                   "canary_requests": 4})
    schedule = FaultSchedule(seed=4242, drop=0.05, corrupt=0.05,
                             duplicate=0.05, delay=0.08,
                             delay_s=(0.02, 0.1))
    proxy = ChaosProxy(front, bal.endpoint, schedule).start()
    cli = InferenceClient(front, timeout=20.0, resend_after_s=0.5,
                          max_resends=30, breaker_failures=0)
    answered = {}
    try:
        swapped = False
        for i in range(n_requests):
            rid = cli.submit(X1)
            rep = cli.result(rid, timeout=15)
            assert rid not in answered      # client-visible exactly-once
            answered[rid] = rep
            assert np.array_equal(rep["y"], X1), (i, rep)
            if kills and i == n_requests // 3:
                reps[0].kill()
            if kills and i == 2 * n_requests // 3:
                reps[0].restart()
            if swap and not swapped and i == n_requests // 2:
                try:
                    cli.result(cli._send(
                        {"cmd": "swap", "path": "v2"}), timeout=15)
                except Exception:
                    pass                    # reply lost to chaos; the
                    # wave still runs server-side
                swapped = True
        assert len(answered) == n_requests
        assert bal.codec.bad_frames == proxy.counters["req"]["corrupt"]
        assert bal.ledger()["balanced"]
        return dict(bad_frames=bal.codec.bad_frames,
                    failovers=bal.failovers,
                    hedges=bal.hedges,
                    rollovers=bal.rollovers)
    finally:
        proxy.stop()
        _teardown(bal, reps, cli)


@pytest.mark.slow
def test_chaos_soak_full():
    """The full soak: more traffic, kill + restart racing hedges AND a
    rollover wave mid-chaos."""
    _run_soak(_free_port_endpoint(), n_requests=150, kills=True,
              swap=True)
