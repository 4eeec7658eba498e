"""Fleet observability plane tests (ISSUE 20): bounded span export
that never blocks a heartbeat, the fleet ``/metrics`` merge preserving
every pinned per-process series, event-journal ring wraparound with
monotone seqs, SLO burn-rate math against hand-computed windows, the
relay-tree trace_id propagation fix (a leaf's trace_id must appear in
master-side spans), and the stitched-trace e2e on a 1-balancer/
2-replica fleet."""

import json
import re
import time
import urllib.request

import numpy as np
import pytest

from znicz_tpu import telemetry
from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.telemetry.events import EventJournal, FleetEventStore
from znicz_tpu.telemetry.fleet import (FleetMetricsStore, FleetTraceStore,
                                       SloTracker, SpanExporter,
                                       registry_snapshot,
                                       render_fleet_prometheus)
from znicz_tpu.telemetry.trace import TraceRing


# -- span export: bounded, drops-oldest, never blocks ------------------------


def test_span_exporter_bounded_drops_oldest_and_filters():
    ring = TraceRing(capacity=4096, enabled=True)
    exp = SpanExporter("rep@1", capacity=8)
    ring.add_sink(exp)
    t0 = time.perf_counter()
    # spans WITHOUT a trace_id never enter the export buffer
    for i in range(5):
        ring.add("serving", "untraced", t0, 0.001)
    assert exp.pending() == 0
    for i in range(20):
        ring.add("serving", f"s{i}", t0, 0.001, {"trace_id": f"t{i}"})
    # bounded at capacity; the OLDEST spans were evicted, counted
    assert exp.pending() == 8
    assert exp.dropped == 12 and exp.offered == 20
    batch = exp.drain(limit=3)
    assert [s["name"] for s in batch] == ["s12", "s13", "s14"]
    assert exp.pending() == 5
    # drain-all empties; a second drain is a cheap no-op
    assert len(exp.drain()) == 5
    assert exp.drain() == []
    # peek is non-destructive and trace-scoped
    ring.add("serving", "mine", t0, 0.002, {"trace_id": "T"})
    ring.add("serving", "other", t0, 0.002, {"trace_id": "U"})
    assert [s["name"] for s in exp.peek_trace("T")] == ["mine"]
    assert exp.pending() == 2


def test_span_export_never_blocks_heartbeat_carrier():
    """A flooded exporter must keep the heartbeat path O(batch): the
    drain is bounded by span_export_batch and the buffer sheds oldest
    under pressure rather than growing or stalling."""
    ring = TraceRing(capacity=1 << 15, enabled=True)
    exp = SpanExporter("rep@1", capacity=256)
    ring.add_sink(exp)
    t0 = time.perf_counter()
    for i in range(10_000):
        ring.add("serving", "flood", t0, 0.0, {"trace_id": f"t{i}"})
    assert exp.pending() == 256             # bounded under flood
    t1 = time.perf_counter()
    batch = exp.drain(128)                  # one carrier's worth
    dt = time.perf_counter() - t1
    assert len(batch) == 128 and dt < 0.5
    assert exp.dropped == 10_000 - 256


# -- fleet /metrics merge -----------------------------------------------------


def _validate_exposition(text: str):
    """Strict exposition shape (the test_telemetry discipline): every
    sample line's metric name must be TYPEd exactly once."""
    typed = {}
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            assert name not in typed, f"duplicate TYPE for {name}"
            typed[name] = kind
    n = 0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = re.split(r"[{ ]", line, 1)[0]
        base = re.sub(r"_(sum|count|total|bucket)$", "", name)
        assert name in typed or base in typed, f"untyped sample {line!r}"
        n += 1
    return n


def test_fleet_metrics_merge_preserves_local_series_and_members():
    from znicz_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    sc = reg.scope("serving")
    c = sc.counter("served", "requests served")
    c.inc(7)
    h = sc.histogram("request_latency_seconds", "latency")
    h.observe(0.25)
    local = reg.render_prometheus()

    member = MetricsRegistry()
    msc = member.scope("serving")
    msc.counter("served", "requests served").inc(3)
    msc.counter("rejected", "requests refused").inc(1)
    store = FleetMetricsStore()
    store.update("r0@999", registry_snapshot(member))

    text = render_fleet_prometheus(reg, store)
    _validate_exposition(text)
    # every LOCAL series line survives verbatim in the merged superset
    for line in local.splitlines():
        if line and not line.startswith("#"):
            assert line in text, f"local series lost: {line!r}"
    # member children appear under the same family with member=<origin>
    assert re.search(r'^znicz_served_total\{[^}]*member="r0@999"[^}]*\} 3',
                     text, re.M)
    # member-only families land at the end, TYPEd once
    assert re.search(r'^znicz_rejected_total\{[^}]*member="r0@999"', text,
                     re.M)
    # the structured rollup sums counters across members
    roll = store.rollup()
    json.loads(json.dumps(roll))
    fam = roll["families"]["znicz_served_total"]
    assert fam["members"]["r0@999"] == 3.0


def test_fleet_metrics_store_tolerates_wire_garbage():
    store = FleetMetricsStore()
    for garbage in (None, 17, "families", [], {"nope": 1}):
        store.update("evil@1", garbage)     # silently ignored
    assert store.members() == {}


# -- event journal ------------------------------------------------------------


def test_event_ring_wraparound_keeps_seq_monotone():
    j = EventJournal(capacity=8, origin="m@1")
    seqs = [j.emit("failover", "serving", i=i) for i in range(30)]
    assert seqs == list(range(1, 31))       # monotone despite wraparound
    assert j.dropped == 22
    events = j.since(0)
    assert len(events) == 8
    assert [e["seq"] for e in events] == list(range(23, 31))
    # the gap is detectable: oldest retained seq > a stale cursor
    assert events[0]["seq"] > 5
    # non-primitive fields are coerced, not raised
    j.emit("rollback", "serving", why={"complex": object()})
    assert isinstance(j.since(30)[0]["why"], str)


def test_fleet_event_store_dedups_and_assigns_monotone_mseq():
    store = FleetEventStore(capacity=64)
    a = EventJournal(capacity=16, origin="a@1")
    b = EventJournal(capacity=16, origin="b@2")
    for i in range(3):
        a.emit("failover", "serving", i=i)
        b.emit("autoscale_up", "serving", i=i)
    batch_a = a.since(0)
    assert store.ingest("a@1", batch_a) == 3
    # re-delivered piggyback batch (sender retry): ingested ZERO times
    assert store.ingest("a@1", batch_a) == 0
    assert store.ingest("b@2", b.since(0)) == 3
    merged = store.since(0)
    assert [e["mseq"] for e in merged] == list(range(1, 7))
    assert store.cursor("a@1") == 3
    # a fresh event after the cursor merges exactly once
    a.emit("rollback", "serving")
    assert store.ingest("a@1", a.since(store.cursor("a@1"))) == 1


# -- SLO burn math ------------------------------------------------------------


def test_slo_burn_rates_match_hand_computed_windows():
    now = [1000.0]
    slo = SloTracker("serving", window_fast_s=60.0, window_slow_s=600.0,
                     bucket_s=5.0, clock=lambda: now[0])
    slo.add_objective("availability", target=0.99)
    # slow window: 95 good + 5 bad spread over 500s
    for i in range(100):
        now[0] = 1000.0 + i * 5.0
        slo.record("availability", ok=(i % 20 != 0))
    now[0] = 1000.0 + 99 * 5.0
    # hand-computed: fast window (60s) holds the last 12 buckets ->
    # one bad (i=80 at t=1400 is outside; i=... the bads land every
    # 100s, so exactly 0 or 1 in the fast window). Compute explicitly:
    lo_fast = int((now[0] - 60.0) / 5.0)
    fast_obs = [i for i in range(100) if int((1000.0 + i * 5.0) / 5.0)
                > lo_fast]
    fast_bad = sum(1 for i in fast_obs if i % 20 == 0)
    want_fast = (fast_bad / len(fast_obs)) / 0.01 \
        if fast_obs else None
    got_fast = slo.burn_rate("availability", 60.0)
    assert got_fast == pytest.approx(want_fast)
    lo_slow = int((now[0] - 600.0) / 5.0)
    slow_obs = [i for i in range(100) if int((1000.0 + i * 5.0) / 5.0)
                > lo_slow]
    slow_bad = sum(1 for i in slow_obs if i % 20 == 0)
    want_slow = (slow_bad / len(slow_obs)) / 0.01
    assert slo.burn_rate("availability", 600.0) == \
        pytest.approx(want_slow)
    snap = slo.snapshot()
    obj = snap["objectives"]["availability"]
    assert obj["fast_burn"] == pytest.approx(want_fast)
    assert obj["slow_burn"] == pytest.approx(want_slow)
    # state matrix: fast>=1 and slow>=1 -> burning; fast only -> warn
    assert obj["state"] == ("burning" if want_fast is not None
                            and want_fast >= 1.0 and want_slow >= 1.0
                            else "warn" if want_fast is not None
                            and want_fast >= 1.0 else "ok")
    want_remaining = 1.0 - (slow_bad / len(slow_obs)) / 0.01
    assert obj["budget_remaining"] == pytest.approx(
        max(-1.0, min(1.0, want_remaining)))   # clamped for the panel


def test_slo_latency_objective_and_empty_windows():
    now = [0.0]
    slo = SloTracker("serving", clock=lambda: now[0])
    slo.add_objective("p99", target=0.9, threshold=0.250, unit="s")
    # no observations: burn is None, state ok, budget intact
    assert slo.burn_rate("p99", 60.0) is None
    assert slo.snapshot()["objectives"]["p99"]["state"] == "ok"
    now[0] = 10.0
    for lat in (0.1, 0.2, 0.3, 0.4):        # 2 good, 2 bad vs 250ms
        slo.record_latency("p99", lat)
    assert slo.burn_rate("p99", 60.0) == pytest.approx(
        (2 / 4) / 0.1)                      # bad_frac / error budget
    # a latency feed for an objective WITHOUT a threshold is a no-op
    slo.add_objective("availability", target=0.99)
    slo.record_latency("availability", 5.0)
    assert slo.burn_rate("availability", 60.0) is None


# -- relay-tree trace_id propagation (ISSUE 20 satellite) ---------------------


def _tiny_wf(tmp_path):
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = 120
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    root.common.dirs.snapshots = str(tmp_path)
    wf = mnist.MnistWorkflow()
    wf.initialize(device=None)
    return wf


def test_leaf_trace_id_reaches_master_side_spans(tmp_path):
    """A leaf's trace_id travels the contributor manifest through a
    relay flush and lands on master-side ``aggregate_contrib`` spans,
    and the relay's own edge-validate span is tagged with it — the
    training half of cross-process stitching."""
    from znicz_tpu.network_common import handshake_request
    from znicz_tpu.parallel.relay import Relay
    from znicz_tpu.server import Server

    telemetry.set_enabled(True)
    telemetry.tracer().clear()
    wf = _tiny_wf(tmp_path)
    server = Server(wf)
    msg = handshake_request(wf)
    del msg["cmd"]
    assert server._handle({"cmd": "register", "id": "obs-relay",
                           "relay": True, **msg})["ok"]
    job = server._handle({"cmd": "job", "id": "obs-relay", "count": 1})
    job = job if "job_id" in job else dict(job, **job.get("jobs", [{}])[0])
    jid, tid = job["job_id"], job["trace_id"]
    assert tid

    relay = Relay("tcp://127.0.0.1:1", "tcp://127.0.0.1:2",
                  relay_id="obs-relay", fanout=3, flush_s=999.0)
    relay._cred = (3, "cafebabecafebabe")
    now = time.time()
    for sid in ("s0", "s1", "s2"):      # flush threshold never crossed
        relay._children[sid] = now
    shapes = {f.name: {k: a.shape for k, a in f.params().items()}
              for f in wf.forwards if f.has_weights}
    deltas = {n: {k: np.full(s, 1e-4, np.float32)
                  for k, s in layer.items()}
              for n, layer in shapes.items()}
    rep = relay._child_update({"cmd": "update", "id": "s0",
                               "job_id": jid, "trace_id": tid,
                               "deltas": deltas,
                               "metrics": {"loss": 1.0, "n_err": 0}},
                              "s0")
    assert rep["ok"]
    # the relay's edge-validate span carries the contributor's trace_id
    edge = [e for e in telemetry.tracer().events()
            if e[0] == "relay" and e[1] == "edge_validate"
            and e[5] and e[5].get("trace_id") == tid]
    assert edge, "edge_validate span must carry the leaf trace_id"
    up = server._handle(dict(
        relay._flush_message(list(relay._buffer), dict(relay._sum)),
        cmd="update", id="obs-relay"))
    assert up["ok"] and up["outcomes"][jid] == "ok"
    # ... and the master parents one span per contributor to it
    master = [e for e in telemetry.tracer().events()
              if e[0] == "master" and e[1] == "aggregate_contrib"
              and e[5] and e[5].get("trace_id") == tid]
    assert master, "leaf trace_id must appear in master-side spans"
    assert master[0][5]["leaf"] == "s0"


def test_relay_flush_forwards_leaf_obs_payloads():
    """Spans/events a leaf piggybacked on its update must survive the
    relay hop: buffered (bounded) and re-shipped upstream as
    ``fwd_obs`` with the LEAF's origin intact."""
    from znicz_tpu.parallel.relay import Relay

    relay = Relay("tcp://127.0.0.1:1", "tcp://127.0.0.1:2",
                  relay_id="fwd-relay", fanout=3, flush_s=999.0)
    relay._cred = (3, "cafebabecafebabe")
    now = time.time()
    for sid in ("s0", "s1", "s2"):      # flush threshold never crossed
        relay._children[sid] = now
    leaf_spans = [{"cat": "train", "name": "minibatch", "ts": 1,
                   "dur": 2, "tid": 0, "args": {"trace_id": "T-1"}}]
    leaf_events = [{"kind": "preemption", "plane": "training",
                    "seq": 1, "ts": 0.0, "origin": "slave-7@42"}]
    rep = relay._child_update({"cmd": "update", "id": "s0", "job_id": 1,
                               "trace_id": "T-1", "spans": leaf_spans,
                               "events": leaf_events,
                               "origin": "slave-7@42",
                               "metrics": {"loss": 1.0}}, "s0")
    assert rep["ok"]
    with relay._lock:
        fwd = list(relay._obs_fwd)
    assert fwd and fwd[0]["origin"] == "slave-7@42"
    assert fwd[0]["spans"] == leaf_spans
    # bounded drop-oldest: a flood of child payloads keeps the newest
    for i in range(100):
        relay._buffer_child_obs({"spans": [{"cat": "t", "name": f"n{i}",
                                            "ts": 0, "dur": 0,
                                            "tid": 0}],
                                 "origin": f"s{i}@1"}, f"s{i}")
    with relay._lock:
        assert len(relay._obs_fwd) == 32
        assert relay._obs_fwd[-1]["origin"] == "s99@1"


# -- stitched-trace e2e (1 balancer / 2 replicas) -----------------------------


def test_stitched_trace_e2e_balancer_two_replicas(tmp_path):
    """The serving half of the tentpole, end to end over real sockets:
    client -> balancer -> real replica frontends, spans exported on
    heartbeats/replies/self-drain, assembled by trace_id in the fleet
    store, with the fleet endpoints serving the merged views."""
    from znicz_tpu.serving import (InferenceClient, InferenceServer,
                                   ReplicaBalancer)
    from znicz_tpu.web_status import WebStatus

    telemetry.set_enabled(True)
    bal = ReplicaBalancer(replica_ttl_s=2.0, heartbeat_s=0.2).start()
    wf = _tiny_wf(tmp_path)
    srvs = [InferenceServer(wf, max_batch=4, max_delay_ms=1.0,
                            announce=bal.endpoint,
                            replica_id=f"obs-r{i}").start()
            for i in range(2)]
    cli = InferenceClient(bal.endpoint, timeout=20.0,
                          breaker_failures=0)
    status = WebStatus(port=0).start()
    base = f"http://127.0.0.1:{status.port}"
    try:
        t0 = time.time()
        while bal.ready_count() < 2:
            assert time.time() - t0 < 30, "fleet never became ready"
            time.sleep(0.05)
        x = np.zeros((1, 28 * 28), np.float32)
        store = telemetry.fleet_trace()
        # the store is the process's: traces an earlier test of this
        # worker left (a master/slave job crosses three origins too) are
        # not this fleet's
        before = set(store.traces())

        def best_stitched():
            best = (None, [])
            for tid, members in store.traces().items():
                origins = list(dict.fromkeys(o for o, _ in members))
                if tid not in before and len(origins) > len(best[1]):
                    best = (tid, origins)
            return best

        deadline = time.time() + 30
        stitched = (None, [])
        while time.time() < deadline:
            rep = cli.result(cli.submit(x))
            assert rep["lb"] and rep["ok"]
            time.sleep(0.05)
            stitched = best_stitched()
            if len(stitched[1]) >= 3:
                break
        tid, origins = stitched
        assert len(origins) >= 3, f"stitched only {origins}"
        # the merged Chrome trace renders one pid per origin
        chrome = store.chrome_trace(tid)
        json.loads(json.dumps(chrome))
        assert sorted(chrome["fleet"]["origins"]) == sorted(origins)
        names = {ev["name"] for ev in chrome["traceEvents"]}
        assert "request" in names           # client/balancer side
        # both replicas eventually contribute spans to the store
        all_origins = {o for o, _ in store.spans()}
        deadline = time.time() + 20
        while time.time() < deadline and not any(
                o.startswith("obs-r1") or o.startswith("obs-r0")
                for o in all_origins):
            cli.result(cli.submit(x))
            time.sleep(0.05)
            all_origins = {o for o, _ in store.spans()}
        assert any(o.startswith("obs-r") for o in all_origins), \
            f"no replica-origin spans in {all_origins}"
        # fleet endpoints: merged /metrics keeps pinned local series
        # AND carries member rows; /events.json + /slo.json are JSON
        with urllib.request.urlopen(f"{base}/metrics", timeout=10) as r:
            text = r.read().decode()
        _validate_exposition(text)
        assert re.search(r'member="', text), \
            "fleet-merged /metrics has no member series"
        for series in ("znicz_served_total", "znicz_requests_in_total"):
            assert re.search(rf"^{series}\{{", text, re.M), series
        with urllib.request.urlopen(f"{base}/trace.json?fleet=1",
                                    timeout=10) as r:
            fleet_trace = json.loads(r.read().decode())
        assert fleet_trace["fleet"]["origins"]
        with urllib.request.urlopen(f"{base}/slo.json", timeout=10) as r:
            slo = json.loads(r.read().decode())
        assert "serving" in slo["planes"]
        with urllib.request.urlopen(f"{base}/events.json?fleet=1",
                                    timeout=10) as r:
            json.loads(r.read().decode())
    finally:
        status.stop()
        cli.close()
        for s in srvs:
            s.stop()
        bal.stop()


#: a real OS process running one tiny charlm generation replica that
#: announces to the parent's balancer; spans ride its heartbeats
_GEN_REPLICA = """
import sys
from znicz_tpu.core import prng
from znicz_tpu.core.config import root
root.charlm.loader.update({"n_train": 64, "n_valid": 16, "n_test": 0,
                           "seq_len": 32, "minibatch_size": 16})
root.charlm.model.update({"vocab": 32, "embed": 32, "heads": 2,
                          "ffn": 64})
root.common.serving.seq.rungs = [8, 32]
root.common.serving.generate.update({"enabled": True, "page_size": 8,
                                     "slots": 4})
prng.reset(1013)
from znicz_tpu.samples.charlm import CharLMWorkflow
from znicz_tpu.serving import InferenceServer
wf = CharLMWorkflow()
wf.initialize(device=None)
srv = InferenceServer(wf, max_batch=4, max_delay_ms=1.0,
                      announce=sys.argv[1],
                      replica_id=sys.argv[2]).start()
sys.stdin.read()        # parent closes stdin -> clean exit
srv.stop()
"""


@pytest.mark.slow
def test_generation_trace_stitched_across_os_processes():
    """One generation request under one ``trace_id`` crosses >= 3 fleet
    origins of which at least one lives in ANOTHER OS process: client
    and balancer here, the replica a child that exports its spans on its
    heartbeats."""
    import os
    import subprocess
    import sys

    from znicz_tpu.serving import InferenceClient, ReplicaBalancer

    telemetry.set_enabled(True)
    bal = ReplicaBalancer(replica_ttl_s=2.5, heartbeat_s=0.25).start()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    child = subprocess.Popen(
        [sys.executable, "-c", _GEN_REPLICA, bal.endpoint, "gen-child"],
        stdin=subprocess.PIPE, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL, env=env, cwd=repo)
    my_pid = str(os.getpid())

    def stitched():
        for tid, members in telemetry.fleet_trace().traces().items():
            origins = list(dict.fromkeys(o for o, _ in members))
            pids = {o.rsplit("@", 1)[-1] for o in origins}
            if len(origins) >= 3 and pids - {my_pid} and all(
                    s.get("args", {}).get("trace_id") == tid
                    for _, s in members):
                return tid, origins, pids
        return None

    cli = None
    try:
        t0 = time.time()
        while bal.ready_count() < 1:
            assert child.poll() is None, child.returncode
            assert time.time() - t0 < 300, "replica never announced"
            time.sleep(0.2)
        cli = InferenceClient(bal.endpoint, timeout=90.0,
                              breaker_failures=0)
        rng = np.random.default_rng(2008)
        found, deadline = None, time.time() + 60
        while found is None and time.time() < deadline:
            prompt = rng.integers(1, 32, size=6).astype(np.uint8)
            rep = cli.generate(prompt, max_new_tokens=8, timeout=90)
            assert len(rep["tokens"]) >= 1
            time.sleep(0.05)
            found = stitched()
        assert found is not None, "no trace stitched across processes"
        tid, origins, pids = found
        assert len(origins) >= 3 and len(pids) >= 2, (origins, pids)
    finally:
        if cli is not None:
            cli.close()
        child.stdin.close()
        try:
            child.wait(timeout=20)
        except subprocess.TimeoutExpired:
            child.kill()
        bal.stop()
