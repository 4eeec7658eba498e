"""Inference service frontend: ZMQ ROUTER + dynamic batcher + model
runner (ISSUE 4).

Transport is the SAME wire-v3 codec the master/slave stack speaks
(parallel/wire.py): every request/reply is multipart — one metadata
frame plus one raw zero-copy buffer frame per tensor — so request
payloads and result tensors never pass through pickle.  Clients connect
DEALER sockets (many requests in flight, no REQ lockstep); the ROUTER
envelope is carried through the batcher untouched and prepended to the
reply, so replies route regardless of arrival order.

Threading:

  - the ROUTER thread owns the socket AND the codec: it decodes
    requests, enqueues them on the batcher, answers control commands
    (``ping``/``stats``) inline, refuses undecodable frames
    (``bad_frames`` — the master's fault model extends to serving), and
    drains the outbound reply queue;
  - ONE compute thread drives the donated ping-pong: it coalesces a
    batch, stages it (async H2D), dispatches the jitted forward
    (donating the staged buffer), then — while the device computes —
    coalesces AND stages the NEXT batch before materializing the
    result, so staging of batch N+1 overlaps compute of batch N (the
    ``loader/ingest.py`` overlap discipline).

Fault model (README "Serving" + "Serving robustness"): an undecodable
or corrupted request frame is refused with an error reply and counted,
never fatal; every ADMISSION refusal (shed / oversized / rate_limited /
deadline) is answered with a readable reason AND the ``policy`` slug
that refused it; a request whose deadline (client-shipped budget, else
``request_ttl_s``) passes is answered ``timed_out`` at assemble time —
and a computed result that misses the deadline is dropped, never
shipped.  The service survives a ChaosProxy soak (tests/
test_serving.py) and swaps snapshots live (``swap`` control command /
SIGHUP) without losing a single accepted request.
"""

from __future__ import annotations

import logging
import math
import queue
import threading
import time
import zlib
from typing import Dict, List, Optional

import numpy as np

from znicz_tpu.core.config import root

from znicz_tpu.telemetry.metrics import registered_property

from .batcher import (AdmissionPolicy, BucketLadder, DynamicBatcher,
                      GenerationScheduler, GenSeq, Request)
from .model import ModelRunner

#: serving config home: ``root.common.serving.*`` (CLI dotted overrides
#: reach it like every other knob).  EVERY ``root.common.serving.*``
#: key the codebase reads must appear here — tests/
#: test_no_adhoc_counters.py lints for silently-ignored config.
DEFAULTS = {"max_batch": 32, "max_delay_ms": 5.0, "queue_bound": 256,
            "request_ttl_s": 5.0, "max_requests": None, "web_port": None,
            # variable-length workloads (ISSUE 15): with max_len > 0 the
            # bucket ladder grows a SECOND (sequence) axis — requests of
            # any length 1..max_len are padded up to power-of-two seq
            # rungs (or the explicit ``rungs`` list, which must end at
            # max_len), coalesced only with same-rung neighbors, and
            # replies are sliced back to each request's own length.
            # Importing a sequence sample (charlm) defaults max_len to
            # its trained window.
            "seq": {"max_len": 0, "rungs": None},
            # generation serving (ISSUE 16, paged in ISSUE 19):
            # prefill/decode split over a block-paged KV pool with
            # continuous batching, prefix reuse, and fused sampling.
            # Off by default — scoring-only services pay nothing.
            # With enabled=True: ``max_new_tokens`` caps any one
            # request's decode budget, ``page_size`` sets the KV page
            # (tokens per page — the sharing/COW granularity),
            # ``num_pages`` sizes the pool (0 = auto: slots x pages
            # per full context), ``prefill_chunk`` bounds the prompt
            # tokens one tick may prefill per request (the inter-token
            # p99 shield; defaults to page_size, which also makes
            # prefix hits bit-exact vs cold prefills),
            # ``prefix_cache`` arms content-addressed prefix-page
            # sharing, ``on_device_sampling`` ships (b,) sampled
            # tokens per tick instead of (b, vocab) logits, ``slots``
            # bounds concurrent generations, ``decode_tick_ms`` paces
            # the decode cadence (0 = free-running), and
            # ``pending_bound`` sheds prompt arrivals past it
            "generate": {"enabled": False, "max_new_tokens": 256,
                         "page_size": 16, "num_pages": 0,
                         "prefill_chunk": 0, "prefix_cache": True,
                         "on_device_sampling": True, "slots": 8,
                         "decode_tick_ms": 0.0, "pending_bound": 64},
            # serving mesh (ISSUE 13; serving/model.py reads it through
            # a local alias): NamedSharding axis sizes — requests split
            # over ``data``, wide FC tails column-shard over ``model``.
            # 1x1 = the single-device path, bit-exact
            "mesh": {"data": 1, "model": 1},
            # AOT executable cache (ISSUE 17; serving/aot_cache.py): with
            # enabled=True warmed executables are serialized into a
            # content-addressed cache next to the snapshot (``dir``
            # overrides the location) and a restarted replica LOADS its
            # whole family instead of compiling it — the zero-cold-start
            # lever (tests/test_aot_cache.py: a warm boot compiles
            # nothing).  Off by default: long-lived replicas pay nothing
            "aot_cache": {"enabled": False, "dir": ""},
            # fleet observability (ISSUE 20; read through a local alias
            # like the admission subtree): slow-request exemplar window
            # (the N slowest requests with their span breakdown on
            # /status.json), the heartbeat metrics-snapshot cadence
            # (every Nth beat carries the full registry snapshot), and
            # the serving-plane SLO objectives — ADVISORY burn rates on
            # /slo.json and a new /readyz field, never a gate flip
            "obs": {"exemplars": 8, "exemplar_window_s": 60.0,
                    "metrics_every_beats": 8,
                    "slo_availability": 0.999, "slo_p99_ms": 250.0,
                    "slo_ttft_ms": 500.0, "slo_inter_token_ms": 100.0,
                    "slo_fast_window_s": 60.0,
                    "slo_slow_window_s": 600.0},
            "admission": {"enabled": True, "rate_limit": 0.0,
                          "rate_burst": 0.0, "fair": True, "quantum": 0,
                          "client_queue_bound": 0},
            # replica-fleet balancer knobs (ISSUE 12; serving/
            # balancer.py reads them through a local alias, like the
            # admission subtree above): heartbeat cadence + TTL'd
            # membership, hedged-retry timing, exactly-once failover
            # budgets, and the canary-rollover verdict thresholds
            "balance": {"heartbeat_s": 0.25, "replica_ttl_s": 1.5,
                        "min_replicas": 1, "hedge": True,
                        "hedge_floor_s": 0.05, "hedge_cap_s": 2.0,
                        "hedge_p99_mult": 1.5,
                        "failover_timeout_s": 1.0, "failover_tries": 3,
                        "park_bound": 256, "canary_fraction": 0.34,
                        "canary_requests": 30, "canary_p99_mult": 3.0,
                        "canary_timeout_s": 30.0, "parity_every": 4,
                        "heal_backoff_s": 30.0,
                        # autoscaler (ISSUE 17; armed by ReplicaBalancer.
                        # enable_autoscale): a control loop over the
                        # per-replica capacity-weighted load — spawn when
                        # the fleet-mean (queue_depth + in_flight)/
                        # device_count sits above ``autoscale_high_load``
                        # (or requests park) for ``autoscale_up_after``
                        # consecutive evals, drain-then-retire the
                        # least-loaded SERVABLE replica when below
                        # ``autoscale_low_load`` for ``autoscale_down_
                        # after`` evals — hysteresis both ways, one
                        # action per ``autoscale_cooldown_s``, never
                        # below the ``min_replicas`` quorum, never past
                        # ``autoscale_max``
                        "autoscale": False, "autoscale_max": 8,
                        "autoscale_high_load": 4.0,
                        "autoscale_low_load": 0.5,
                        "autoscale_up_after": 2,
                        "autoscale_down_after": 8,
                        "autoscale_eval_s": 0.5,
                        "autoscale_cooldown_s": 5.0,
                        "autoscale_drain_timeout_s": 10.0,
                        "autoscale_boot_deadline_s": 60.0}}


def _cfg(name: str, override):
    if override is not None:
        return override
    return root.common.serving.get(name, DEFAULTS[name])


def _admission_from_config() -> AdmissionPolicy:
    # the admission subtree is bound to a local alias: znicz-lint's
    # config-knob checker (znicz_tpu/analysis/config_knob.py) resolves
    # every .get() read THROUGH the alias against the DEFAULTS table,
    # so the old "spell the literal chain at each read site" workaround
    # (the regex lint was blind to aliasing) is retired
    d = DEFAULTS["admission"]
    adm = root.common.serving.admission
    return AdmissionPolicy(
        rate_limit=float(adm.get("rate_limit", d["rate_limit"])),
        rate_burst=float(adm.get("rate_burst", d["rate_burst"])),
        fair=bool(adm.get("fair", d["fair"])),
        quantum=int(adm.get("quantum", d["quantum"])),
        client_queue_bound=int(adm.get("client_queue_bound",
                                       d["client_queue_bound"])),
        enabled=bool(adm.get("enabled", d["enabled"])))


class InferenceServer:
    """Serve a workflow's frozen forward over ZMQ.

    ``bind`` may use a wildcard port (``tcp://127.0.0.1:*``); the
    resolved address is in ``endpoint`` once serving starts.  Drive
    blocking (``serve()``) or on a background thread (``start()`` /
    ``stop()``).  ``max_requests`` makes serve() return after answering
    that many inference requests (bench/launcher tests)."""

    def __init__(self, workflow, bind: str = "tcp://127.0.0.1:*",
                 snapshot: str = "", max_batch: Optional[int] = None,
                 max_delay_ms: Optional[float] = None,
                 queue_bound: Optional[int] = None,
                 request_ttl_s: Optional[float] = None,
                 ladder: Optional[BucketLadder] = None,
                 max_requests: Optional[int] = None,
                 admission: Optional[AdmissionPolicy] = None,
                 warmup: bool = True,
                 announce: Optional[str] = None,
                 replica_id: Optional[str] = None):
        import uuid

        from znicz_tpu.parallel import wire

        self.bind = bind
        #: fleet membership (ISSUE 12): when set, the router thread
        #: heartbeats this balancer endpoint with readiness + queue
        #: depth + per-bucket p99 piggybacked, and every reply carries
        #: the ``replica_id`` stamp the client's per-endpoint breaker
        #: keys on
        self.announce = announce
        self.replica_id = replica_id or f"replica-{uuid.uuid4().hex[:6]}"
        self.endpoint: Optional[str] = None      # resolved at serve()
        self.runner = ModelRunner(workflow, snapshot=snapshot)
        max_batch = int(_cfg("max_batch", max_batch))
        # mesh-aware ladder (ISSUE 13): default rungs snap to multiples
        # of the data-axis size so every batch splits evenly; an
        # explicit ladder that cannot split is refused HERE, readably,
        # not as an XLA sharding error at the first request
        dp = self.runner.data_parallel
        # 2-D seq ladder config (ISSUE 15; read through a local alias
        # like the admission subtree, so the config-knob lint resolves
        # the keys against DEFAULTS)
        d_seq = DEFAULTS["seq"]
        sq = root.common.serving.seq
        # a sequence workflow DECLARES its serving window
        # (workflow.serving_seq_len — charlm sets it to the trained
        # seq_len); explicit root.common.serving.seq.max_len config
        # wins, including an explicit 0 to force fixed-shape serving
        declared = int(getattr(workflow, "serving_seq_len", 0) or 0)
        seq_max_len = int(sq.get("max_len",
                                 declared or d_seq["max_len"]) or 0)
        seq_rungs = sq.get("rungs", d_seq["rungs"])
        if ladder is None:
            ladder = BucketLadder(max_batch, dp=dp, max_len=seq_max_len,
                                  seq_rungs=seq_rungs)
        elif dp > 1 and ladder.dp != dp:
            # re-validate an explicit ladder against THIS runner's mesh
            # through the one home of the divisibility check/message
            ladder = BucketLadder(ladder.max_batch, ladder.rungs, dp=dp,
                                  max_len=ladder.max_len,
                                  seq_rungs=ladder.seq_rungs)
        #: variable-length mode: requests carry (n, len, *tail) arrays,
        #: len <= seq_max_len; the trained sample shape's axis 0 is the
        #: max sequence length
        self.seq_max_len = ladder.max_len or None
        #: resolved lazily by _resolve_seq_out(): True when the model's
        #: output carries the SEQ axis (replies sliced to each
        #: request's own length), False for seq-reducing heads
        self._seq_out: Optional[bool] = None
        if self.seq_max_len is not None:
            trained = int(self.runner.sample_shape[0]) \
                if self.runner.sample_shape else 0
            if trained and self.seq_max_len > trained:
                raise ValueError(
                    f"root.common.serving.seq.max_len={self.seq_max_len} "
                    f"exceeds the model's trained sequence length "
                    f"{trained} (positions past the trained window "
                    f"have no embedding)")
            # the masked-parity contract rides the CAUSAL mask: a real
            # position never attends its row's padded tail.  A
            # non-causal attention unit would hand PAD keys softmax
            # mass and make replies a function of the co-batched rung
            # — refuse at startup, not as silently-wrong answers
            from znicz_tpu.attention import MultiHeadAttention

            non_causal = [f.name for f in workflow.forwards
                          if isinstance(f, MultiHeadAttention)
                          and not f.causal]
            if non_causal:
                raise ValueError(
                    f"variable-length serving needs causal attention, "
                    f"but unit(s) {non_causal} attend bidirectionally — "
                    f"padded tails would leak probability mass into "
                    f"real positions.  Make the unit causal (mask pad "
                    f"keys via ops.attention k_valid in a custom "
                    f"apply), or serve fixed-shape "
                    f"(root.common.serving.seq.max_len=0)")
        self.batcher = DynamicBatcher(
            max_batch=max_batch,
            max_delay_ms=float(_cfg("max_delay_ms", max_delay_ms)),
            queue_bound=int(_cfg("queue_bound", queue_bound)),
            ladder=ladder,
            admission=admission or _admission_from_config())
        self.request_ttl_s = float(_cfg("request_ttl_s", request_ttl_s))
        # generation serving (ISSUE 16, paged in ISSUE 19; knobs read
        # through a local alias like the admission subtree): a paged
        # GenerationRunner (block-paged KV pool + prefix cache +
        # chunked-prefill/decode executables with fused sampling)
        # under a continuous-batching scheduler, driven by the SAME
        # compute thread
        d_gen = DEFAULTS["generate"]
        gn = root.common.serving.generate
        self.gen_sched: Optional[GenerationScheduler] = None
        if bool(gn.get("enabled", d_gen["enabled"])):
            if self.seq_max_len is None:
                raise ValueError(
                    "generation serving rides the variable-length "
                    "plane (the context window IS the seq window) — "
                    "set root.common.serving.seq.max_len alongside "
                    "root.common.serving.generate.enabled")
            page_size = int(gn.get("page_size", d_gen["page_size"]))
            slots = int(gn.get("slots", d_gen["slots"]))
            num_pages = int(gn.get("num_pages", d_gen["num_pages"]))
            if num_pages <= 0:
                # auto pool: every slot can hold one full context —
                # admission (slots) and allocation can't deadlock
                num_pages = slots * (-(-self.seq_max_len // page_size))
            chunk = int(gn.get("prefill_chunk", d_gen["prefill_chunk"]))
            if chunk <= 0:
                # chunk == page_size keeps prefill grids aligned with
                # page boundaries — prefix hits replay the exact
                # executables a cold prefill runs (bit-exact reuse)
                chunk = page_size
            gr = self.runner.enable_generation(
                page_size=page_size, num_pages=num_pages, slots=slots,
                prefill_chunk=chunk,
                prefix_cache=bool(gn.get("prefix_cache",
                                         d_gen["prefix_cache"])))
            self.gen_sched = GenerationScheduler(
                gr,
                max_new_cap=int(gn.get("max_new_tokens",
                                       d_gen["max_new_tokens"])),
                pending_bound=int(gn.get("pending_bound",
                                         d_gen["pending_bound"])),
                decode_tick_ms=float(gn.get("decode_tick_ms",
                                            d_gen["decode_tick_ms"])),
                on_device_sampling=bool(
                    gn.get("on_device_sampling",
                           d_gen["on_device_sampling"])),
                replica_id=self.replica_id)
        self.max_requests = max_requests
        self._warmup = warmup
        # AOT executable cache (ISSUE 17; read through a local alias
        # like the admission subtree): resolved here, armed at serve()
        # right before warmup so a bad directory fails start() readably
        d_aot = DEFAULTS["aot_cache"]
        aot = root.common.serving.aot_cache
        self._aot_enabled = bool(aot.get("enabled", d_aot["enabled"]))
        self._aot_dir = str(aot.get("dir", d_aot["dir"]) or "")
        #: the boot-time warm proof (ModelRunner.warm_proof) recorded
        #: once warmup finished — in AOT mode /readyz GATES on it
        self.warm_report: Optional[Dict] = None
        self.boot_to_ready_s: Optional[float] = None
        self.codec = wire.Codec(owner="serving")    # router-thread only
        # -- telemetry (ISSUE 5): serving counters + the request-latency
        # ring histogram live in the registry (component="serving");
        # the class-level properties preserve the historical names
        from znicz_tpu import telemetry

        _sc = telemetry.scope("serving")
        self._m = {name: _sc.counter(name, help)
                   for name, help in self.COUNTERS.items()}
        # boot-to-/readyz distribution (ISSUE 17): cold compiles vs
        # cache-warm loads land in visibly different buckets here —
        # the fleet's elasticity latency on /metrics
        self._m_boot = telemetry.scope("warmup").histogram(
            "warmup_boot_to_ready_seconds",
            "serve() entry -> /readyz true (warmup included)", size=64)
        self._m_latency = _sc.histogram(
            "request_latency_seconds",
            "e2e request latency (enqueue -> reply handoff)", size=8192)
        # per-rung latency rings (ISSUE 12): the heartbeat's
        # p99-by-bucket payload — what the balancer's least-loaded
        # dispatch and hedge-delay derivation feed on
        self._m_lat_bucket = {
            r: _sc.histogram("bucket_latency_seconds",
                             "request latency per ladder rung "
                             "(enqueue -> compute done)", size=512,
                             bucket=str(r))
            for r in self.batcher.ladder}
        d_bal = DEFAULTS["balance"]
        bal = root.common.serving.balance
        self.heartbeat_s = float(bal.get("heartbeat_s",
                                         d_bal["heartbeat_s"]))
        self._tracer = telemetry.tracer()
        # -- fleet observability (ISSUE 20; knobs read through a local
        # alias like the admission subtree): this replica's fleet
        # identity, the span exporter the heartbeat/reply carriers
        # drain, the slow-request exemplar window, and the serving SLO
        # tracker (advisory burn rates — /readyz reports, never gates)
        d_obs = DEFAULTS["obs"]
        obs = root.common.serving.obs
        telemetry.set_identity(self.replica_id)
        self._exporter = telemetry.exporter()
        self._exemplar_cap = int(obs.get("exemplars", d_obs["exemplars"]))
        self._exemplar_window_s = float(obs.get(
            "exemplar_window_s", d_obs["exemplar_window_s"]))
        self._metrics_every = max(1, int(obs.get(
            "metrics_every_beats", d_obs["metrics_every_beats"])))
        self._exemplars: List[Dict] = []    # N slowest, newest window
        self._exemplar_lock = threading.Lock()
        self._hb_beats = 0
        self._hb_ev_seq = 0                 # journal piggyback cursor
        self.slo = telemetry.register_slo(telemetry.SloTracker(
            "serving",
            window_fast_s=float(obs.get("slo_fast_window_s",
                                        d_obs["slo_fast_window_s"])),
            window_slow_s=float(obs.get("slo_slow_window_s",
                                        d_obs["slo_slow_window_s"]))))
        self.slo.add_objective(
            "availability",
            target=float(obs.get("slo_availability",
                                 d_obs["slo_availability"])))
        self.slo.add_objective(
            "latency_p99", target=0.99, unit="s",
            threshold=float(obs.get("slo_p99_ms",
                                    d_obs["slo_p99_ms"])) / 1e3)
        self.slo.add_objective(
            "ttft", target=0.99, unit="s",
            threshold=float(obs.get("slo_ttft_ms",
                                    d_obs["slo_ttft_ms"])) / 1e3)
        self.slo.add_objective(
            "inter_token", target=0.99, unit="s",
            threshold=float(obs.get("slo_inter_token_ms",
                                    d_obs["slo_inter_token_ms"])) / 1e3)
        self.started_at: Optional[float] = None
        #: optional FaultSchedule for the router loop's built-in
        #: ingress fault hook (ISSUE 14 cross-plane soak); the live
        #: TransportLoop sits on ``_transport`` while serving
        self.transport_chaos = None
        self._transport = None
        self._outbound: "queue.Queue" = queue.Queue()
        self._wake_addr: Optional[str] = None    # set at serve() bind
        self._stop = threading.Event()
        self._ready = threading.Event()
        self._serve_error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._compute_thread: Optional[threading.Thread] = None
        self._swap_thread: Optional[threading.Thread] = None
        self._swap_gate = threading.Lock()  # one swap_async admit at a time
        self.log = logging.getLogger("znicz.serving")

    # -- counters shorthand ----------------------------------------------------

    #: serving counters registered under component="serving" (ISSUE 5):
    #: name -> HELP text
    COUNTERS = {
        "requests_in": "decoded infer/generate requests",
        "served": "answered with a result",
        "timed_out": "answered timed_out (deadline/TTL)",
        "rejected": "answered shed/oversized/rate_limited",
        "expired_results": "computed results dropped: deadline passed "
                           "post-compute",
        "serve_errors": "fatal serve-loop failures surfaced to start()",
        "heartbeats_out": "balancer heartbeats sent (fleet membership)",
    }

    # (the historical attribute properties are generated from COUNTERS
    # right after the class body)

    @property
    def bad_frames(self) -> int:
        return self.codec.bad_frames

    def qps(self) -> Optional[float]:
        if self.started_at is None or not self.served:
            return None
        return self.served / max(time.perf_counter() - self.started_at,
                                 1e-9)

    def latency_quantiles(self) -> Dict[str, Optional[float]]:
        lat = self._m_latency.window()      # the last <=8192 requests
        if lat.size == 0:
            return {"p50_ms": None, "p99_ms": None, "mean_ms": None}
        a = lat * 1e3
        return {"p50_ms": round(float(np.percentile(a, 50)), 3),
                "p99_ms": round(float(np.percentile(a, 99)), 3),
                "mean_ms": round(float(np.mean(a)), 3)}

    def p99_ms_by_bucket(self) -> Dict[int, float]:
        """``{ladder rung: p99 ms}`` over each rung's recent window —
        the telemetry the replica piggybacks on every heartbeat."""
        out: Dict[int, float] = {}
        for rung, hist in self._m_lat_bucket.items():
            w = hist.window()
            if w.size:
                out[rung] = round(float(np.percentile(w, 99)) * 1e3, 3)
        return out

    def heartbeat_payload(self) -> Dict:
        """One heartbeat message (ISSUE 12): membership identity plus
        the piggybacked ``/readyz`` state, queue depth and per-bucket
        p99 the balancer's least-loaded dispatch keys on.

        Fleet observability (ISSUE 20) rides the same beat: a bounded
        batch of exported spans and fresh journal events on EVERY beat,
        the full registry snapshot every ``metrics_every_beats``-th —
        the balancer merges all three into the fleet plane.  The extra
        keys are additive; a pre-ISSUE-20 balancer ignores them."""
        from znicz_tpu import telemetry

        hb = self._heartbeat_base()
        hb["origin"] = telemetry.identity()
        spans = self._exporter.drain(telemetry.span_export_batch())
        if spans:
            hb["spans"] = spans
        ev = telemetry.journal().since(self._hb_ev_seq,
                                       limit=telemetry.span_export_batch())
        if ev:
            self._hb_ev_seq = ev[-1]["seq"]
            hb["events"] = ev
        self._hb_beats += 1
        if self._hb_beats % self._metrics_every == 1 \
                or self._metrics_every == 1:
            hb["metrics"] = telemetry.registry_snapshot(
                telemetry.registry())
        return hb

    def _heartbeat_base(self) -> Dict:
        return {"cmd": "heartbeat",
                "replica_id": self.replica_id,
                "endpoint": self.endpoint,
                "ready": self.ready(),
                "draining": self.draining,
                "swapping": self.runner.swapping,
                "gen": self.runner.generation,
                "snapshot_path": self.runner.snapshot_path,
                "queue_depth": self.batcher.queue_depth,
                "served": self.served,
                # capacity (ISSUE 13): the balancer normalizes its
                # least-loaded score by device_count so a 1-chip and an
                # 8-chip replica stop drawing equal traffic
                "device_count": self.runner.device_count,
                "mesh": self.runner.mesh_shape,
                # warmup provenance (ISSUE 17): the fleet panel's warm
                # columns + the autoscaler's boot visibility
                "warm_source": self.runner.warm_source,
                "warm_hits": int(self.runner._warm["hits"]),
                "warm_misses": int(self.runner._warm["misses"]),
                "boot_s": self.boot_to_ready_s,
                "p99_ms_by_bucket": self.p99_ms_by_bucket()}

    def _note_request(self, ok: bool, latency_s: float, req_id,
                      trace_id, bucket=None, kind: str = "infer",
                      breakdown: Optional[Dict] = None) -> None:
        """Feed one finished request into the SLO tracker and (when it
        ranks) the slow-request exemplar window (ISSUE 20).  The span
        peek runs ONLY for requests slow enough to keep — the hot loop
        pays one lock + one float compare."""
        self.slo.record("availability", ok)
        self.slo.record_latency("latency_p99", latency_s)
        latency_ms = round(latency_s * 1e3, 3)
        with self._exemplar_lock:
            now = time.time()
            horizon = now - self._exemplar_window_s
            self._exemplars = [e for e in self._exemplars
                               if e["t"] >= horizon]
            if len(self._exemplars) >= self._exemplar_cap \
                    and latency_ms <= self._exemplars[-1]["latency_ms"]:
                return
            ex = {"req_id": req_id, "trace_id": trace_id,
                  "latency_ms": latency_ms, "bucket": bucket,
                  "kind": kind, "ok": ok, "t": now}
            if breakdown:
                ex["breakdown_ms"] = dict(breakdown)
            if trace_id and self._tracer.enabled:
                spans = self._exporter.peek_trace(str(trace_id), limit=8)
                if spans:
                    ex["spans"] = [{"cat": s.get("cat"),
                                    "name": s.get("name"),
                                    "dur_ms": round(
                                        s.get("dur", 0) / 1e3, 3)}
                                   for s in spans]
            self._exemplars.append(ex)
            self._exemplars.sort(key=lambda e: -e["latency_ms"])
            del self._exemplars[self._exemplar_cap:]

    def slow_requests(self) -> List[Dict]:
        """The current exemplar window, slowest first (ISSUE 20
        satellite — the ``/status.json`` serving panel row)."""
        horizon = time.time() - self._exemplar_window_s
        with self._exemplar_lock:
            return [dict(e) for e in self._exemplars
                    if e["t"] >= horizon]

    def stats(self) -> Dict:
        """The serving panel / bench record, one dict."""
        out = {"endpoint": self.endpoint,
               "replica_id": self.replica_id,
               "requests_in": self.requests_in,
               "served": self.served,
               "rejected": self.rejected,
               "timed_out": self.timed_out,
               "expired_results": self.expired_results,
               "ready": self.ready(),
               "draining": self.draining,
               "generation": self.runner.generation,
               "bad_frames": self.codec.bad_frames,
               "bytes_in": self.codec.bytes_in,
               "bytes_out": self.codec.bytes_out,
               "qps": None if self.qps() is None
               else round(self.qps(), 2)}
        out.update(self.latency_quantiles())
        out["p99_ms_by_bucket"] = self.p99_ms_by_bucket()
        out["announce"] = self.announce
        out["heartbeats_out"] = self.heartbeats_out
        out["boot_to_ready_s"] = self.boot_to_ready_s
        out["warm_report"] = self.warm_report
        out["slow_requests"] = self.slow_requests()
        out["batcher"] = self.batcher.stats()
        out["model"] = self.runner.stats()
        if self.gen_sched is not None:
            out["generate"] = self.gen_sched.stats()
        return out

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> "InferenceServer":
        self._thread = threading.Thread(target=self.serve, daemon=True,
                                        name="znicz-serve")
        self._thread.start()
        # serve() sets _ready on success AND on failure, so this waits
        # out a warm-up of any length (a cold compile of the executable
        # family on the chip takes minutes) and still returns on an error
        self._ready.wait()
        if self._serve_error is not None:
            # bind conflict / bad snapshot / warmup failure: surface the
            # REAL cause immediately instead of a generic bind message
            raise RuntimeError(
                f"inference server failed on {self.bind}: "
                f"{self._serve_error!r}") from self._serve_error
        return self

    def join(self, timeout: Optional[float] = None) -> None:
        """Block until a ``start()``ed server exits (``max_requests``
        reached, ``stop()`` called, or a fatal serve error)."""
        if self._thread is not None:
            self._thread.join(timeout)

    def stop(self) -> None:
        self._stop.set()
        self.batcher.close()
        if self.gen_sched is not None:
            self.gen_sched.close()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    # -- health/readiness + rollover (ISSUE 6) ---------------------------------

    @property
    def draining(self) -> bool:
        """True once stop() (or a fatal serve error) began winding the
        service down — queued work still drains, new work is refused."""
        return self._stop.is_set()

    def alive(self) -> bool:
        """Liveness (the ``/healthz`` answer): the serve loop has not
        died on an error and its thread (when ``start()``-driven) is
        still running."""
        return self._serve_error is None and (
            self._thread is None or self._thread.is_alive())

    def ready(self) -> bool:
        """Readiness (the ``/readyz`` answer): up, not draining, and
        not mid-rollover — False exactly while warming or draining, the
        membership signal a replica tier's health checks need."""
        return (self._ready.is_set() and self._serve_error is None
                and not self._stop.is_set() and not self.runner.swapping)

    def swap_async(self, path: str) -> threading.Thread:
        """Start a zero-downtime snapshot rollover on a background
        thread (the wire ``swap`` command and the launcher's SIGHUP
        land here); serving continues on the old generation until the
        warmed flip.  Raises RuntimeError while another swap runs —
        atomically: the wire command (router thread) and SIGHUP (main
        thread) can race here, and a check-then-start race would ack
        both callers while one swap dies in the background."""
        with self._swap_gate:
            if (self._swap_thread is not None
                    and self._swap_thread.is_alive()):
                raise RuntimeError("swap already in progress")
            t = threading.Thread(target=self._swap, args=(path,),
                                 daemon=True, name="znicz-swap")
            self._swap_thread = t
            t.start()
        return t

    def _swap(self, path: str) -> None:
        try:
            meta = self.runner.swap(path, self.batcher.ladder)
            self.log.info("snapshot rollover -> generation %d (%s, "
                          "epoch %s)", self.runner.generation, path,
                          meta.get("epoch"))
        except Exception:
            # counted by the runner (swap_failures); the old generation
            # keeps serving — a broken snapshot must never take the
            # service down
            self.log.exception(
                "snapshot swap from %r failed; generation %d unchanged",
                path, self.runner.generation)

    # -- the ROUTER loop -------------------------------------------------------

    def serve(self) -> None:
        """Blocking serve; any failure (bind conflict, warmup compile
        error) is recorded for ``start()`` to re-raise with its real
        cause, and always unblocks a waiting ``start()``."""
        try:
            self._serve()
        except BaseException as exc:
            self._serve_error = exc
            self._m["serve_errors"].inc()
            raise
        finally:
            self._ready.set()

    def _serve(self) -> None:
        from znicz_tpu.transport import TransportLoop

        t_boot = time.perf_counter()    # boot-to-/readyz clock (ISSUE 17)
        loop = self._transport = TransportLoop(
            "serving", stop=self._stop, instance=self.replica_id)
        if self.transport_chaos is not None:
            loop.inject_faults(self.transport_chaos)
        sock = None
        state = {"next_hb": 0.0}
        try:
            sock = loop.bind_router(self.bind)
            self.endpoint = loop.resolved_endpoint(sock)
            # outbound wake-up: the compute thread pokes this inproc
            # pair when it enqueues replies, so a finished batch ships
            # on the NEXT poll wake instead of waiting out the poll
            # timeout (the reply tax was the whole sequential-baseline
            # RTT otherwise)
            self._wake_addr = f"inproc://znicz-serve-wake-{id(self)}"
            wake_r = loop.bind_pull(self._wake_addr)
            # fleet membership (ISSUE 12): a DEALER to the balancer,
            # owned by THIS router thread like the codec — heartbeats
            # ride the tick cadence, acks are drained and discarded
            hb = loop.connect_dealer(self.announce) if self.announce \
                else None
            if self._aot_enabled:
                # arm the AOT executable cache (ISSUE 17) BEFORE any
                # warmup dispatch: warmup then loads cached executables
                # where they exist and serializes the ones it compiles
                self.runner.enable_aot_cache(self._aot_dir)
            if self._warmup:
                # compile every rung BEFORE taking traffic: first-
                # request latency must not eat a compile, and the
                # zero-recompile gate needs its baseline
                self.runner.warmup(self.batcher.ladder)
            if self.seq_max_len is not None:
                # resolve the output-shape probe now (cache hits after
                # warmup), never on the compute thread mid-traffic
                self._resolve_seq_out()
            if self.gen_sched is not None and self._warmup:
                # the generation executable families (prefill x prompt
                # rungs, decode x cache rungs, migrations) compile
                # up-front too — the zero-recompile gate's baseline
                self.gen_sched.gen.warmup()
            if self._warmup:
                # the strict warm-family proof (ISSUE 17, the PR-15
                # jit-cache-equality discipline): in AOT mode /readyz
                # must NOT flip true on a partially loaded family —
                # raising here lands in _serve_error, so ready() stays
                # False and start() surfaces the real cause
                expected = len(self.batcher.ladder.buckets())
                if self.gen_sched is not None:
                    expected += self.gen_sched.gen.executables()
                self.warm_report = self.runner.warm_proof(expected)
                if self.runner.aot_enabled \
                        and not self.warm_report["ok"]:
                    raise RuntimeError(
                        f"AOT warmup proof failed — refusing to flip "
                        f"/readyz on a partial executable family: "
                        f"{self.warm_report}")
            self.started_at = time.perf_counter()
            self._compute_thread = threading.Thread(
                target=self._compute_loop, daemon=True,
                name="znicz-infer")
            self._compute_thread.start()
            loop.register(sock,
                          lambda frames: self._handle(sock, frames),
                          drain=True)
            loop.register(wake_r, lambda _token: None, drain=True)
            if hb is not None:
                loop.register(hb, lambda _ack: None, drain=True)

            def tick() -> None:
                if self.max_requests is not None and \
                        self.served + self.timed_out + self.rejected \
                        >= self.max_requests:
                    loop.stop()
                    return
                if hb is not None:
                    now = time.perf_counter()
                    if now >= state["next_hb"]:
                        state["next_hb"] = now + self.heartbeat_s
                        hb.send_multipart(
                            [b""] + self.codec.encode(
                                self.heartbeat_payload()), copy=False)
                        self._m["heartbeats_out"].inc()
                self._drain_outbound(sock)

            loop.add_tick(tick)
            self.boot_to_ready_s = time.perf_counter() - t_boot
            self._m_boot.observe(self.boot_to_ready_s)
            tick()                      # first heartbeat pre-poll
            self._ready.set()
            loop.run(poll_ms=5)
        finally:
            self._stop.set()
            self.batcher.close()
            if self.gen_sched is not None:
                self.gen_sched.close()
            if self._compute_thread is not None:
                self._compute_thread.join(timeout=30)
            if sock is not None:
                self._drain_outbound(sock)  # flush final replies
            loop.close()

    def _drain_outbound(self, sock) -> None:
        n = 0
        t0 = time.perf_counter()
        while True:
            try:
                envelope, rep, t_enqueued = self._outbound.get_nowait()
            except queue.Empty:
                break
            if t_enqueued is not None:
                self._m_latency.observe(time.perf_counter() - t_enqueued)
            # copy=False: result frames are memoryviews of arrays owned
            # by the reply dicts, never mutated after encode
            sock.send_multipart(
                list(envelope) + self.codec.encode(rep), copy=False)
            n += 1
        if n and self._tracer.enabled:
            self._tracer.add("serving", "reply", t0,
                             time.perf_counter() - t0, {"replies": n})

    def _handle(self, sock, frames: List[bytes]) -> None:
        from znicz_tpu.parallel import wire

        envelope, payload = wire.split_envelope(frames)
        if not envelope and frames:
            # a bare-DEALER peer whose metadata frame is garbage: no
            # delimiter, no magic — but this socket is a ROUTER, so the
            # FIRST frame is always the peer identity; peel it so the
            # refusal below stays routable
            envelope, payload = list(frames[:1]), list(frames[1:])
        try:
            req, _ = self.codec.decode(payload)
            if not isinstance(req, dict):
                raise wire.WireError(
                    f"decodes to {type(req).__name__}, not a request dict")
        except Exception as exc:
            self.log.warning("refused undecodable request (%d frames): %s "
                             "— bad_frames=%d", len(frames), exc,
                             self.codec.bad_frames + 1)
            sock.send_multipart(
                list(envelope)
                + self.codec.refusal(exc, legacy=False,
                                     replica_id=self.replica_id))
            return
        cmd = req.get("cmd")
        rid = req.get("req_id")
        if cmd == "ping":
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": True, "pong": True, "req_id": rid,
                 "replica_id": self.replica_id}))
            return
        if cmd == "stats":
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": True, "stats": self.stats(), "req_id": rid,
                 "replica_id": self.replica_id}))
            return
        if cmd == "swap":
            # zero-downtime rollover trigger (ISSUE 6): load+warm runs
            # on a background thread, this reply ships immediately; the
            # caller polls stats()["generation"] for completion
            path = req.get("path")
            if not isinstance(path, str) or not path:
                sock.send_multipart(list(envelope) + self.codec.encode(
                    {"ok": False, "req_id": rid,
                     "replica_id": self.replica_id,
                     "error": "swap needs a snapshot 'path'"}))
                return
            try:
                self.swap_async(path)
            except RuntimeError as exc:
                sock.send_multipart(list(envelope) + self.codec.encode(
                    {"ok": False, "req_id": rid,
                     "replica_id": self.replica_id,
                     "error": str(exc)}))
                return
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": True, "swap_started": True, "req_id": rid,
                 "replica_id": self.replica_id,
                 "generation": self.runner.generation}))
            return
        if cmd == "rollback":
            # fleet canary auto-rollback (ISSUE 12): restore the
            # retained previous generation — instant and disk-free, so
            # it runs inline on this router thread (no load, no warm)
            try:
                gen = self.runner.rollback()
            except RuntimeError as exc:
                sock.send_multipart(list(envelope) + self.codec.encode(
                    {"ok": False, "req_id": rid,
                     "replica_id": self.replica_id, "error": str(exc)}))
                return
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": True, "rolled_back": True, "req_id": rid,
                 "replica_id": self.replica_id, "generation": gen}))
            return
        if cmd == "generate":
            self._handle_generate(sock, envelope, req, rid)
            return
        if cmd != "infer":
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": f"unknown cmd {cmd!r}"}))
            return
        x = req.get("x")
        if not isinstance(x, np.ndarray) or x.ndim < 1:
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": "infer request carries no tensor 'x'"}))
            return
        if x.ndim == len(self.runner.sample_shape):
            x = x[None]                     # single sample shorthand
        seq_len = None
        if self.seq_max_len is not None:
            # variable-length mode (ISSUE 15): axis 1 is the request's
            # OWN sequence length (any 1..max_len — over-long requests
            # fall through to the batcher's readable oversized
            # refusal); trailing dims must still match the model
            if x.ndim != 1 + len(self.runner.sample_shape) or \
                    tuple(x.shape[2:]) != self.runner.sample_shape[1:]:
                sock.send_multipart(list(envelope) + self.codec.encode(
                    {"ok": False, "req_id": rid,
                     "replica_id": self.replica_id,
                     "error": f"sequence request shape {x.shape} does "
                              f"not match (n, len<= {self.seq_max_len}"
                              f", *{self.runner.sample_shape[1:]})"}))
                return
            seq_len = int(x.shape[1])
        elif tuple(x.shape[1:]) != self.runner.sample_shape:
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": f"sample shape {tuple(x.shape[1:])} != model "
                          f"input {self.runner.sample_shape}"}))
            return
        if not np.can_cast(x.dtype, self.runner.dtype,
                           casting="same_kind"):
            # e.g. float samples sent to a u8-storage model: the
            # assemble cast would silently wrap/truncate them into
            # garbage bytes and the service would answer confidently
            # wrong — refuse readably like a wrong shape instead
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": f"sample dtype {x.dtype} cannot safely cast "
                          f"to the model's storage dtype "
                          f"{self.runner.dtype}"}))
            return
        self._m["requests_in"].inc()
        client = self._client_id(req, envelope)
        # deadline ingress (ISSUE 6): the client's shipped budget
        # becomes a LOCAL absolute deadline here (budgets, not
        # timestamps, cross the wire — clocks differ); the server's
        # request_ttl_s stays the cap.  Re-checked at assemble time and
        # post-compute: expired work is never computed, never shipped.
        deadline_s = self._deadline_s(req)
        if deadline_s <= 0:
            self._m["timed_out"].inc()
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "timed_out": True, "req_id": rid,
                 "replica_id": self.replica_id,
                 "policy": "deadline", "trace_id": req.get("trace_id"),
                 "error": f"deadline budget "
                          f"{req.get('deadline_ms')}ms already "
                          f"expended — refused at ingress"}))
            return
        reason = self.batcher.submit(
            Request(x, x.shape[0], reply_to=list(envelope), req_id=rid,
                    trace_id=req.get("trace_id"), client=client,
                    deadline_s=deadline_s, seq_len=seq_len))
        if reason is not None:
            self._m["rejected"].inc()
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "rejected": True, "req_id": rid,
                 "replica_id": self.replica_id,
                 "policy": getattr(reason, "policy", "refused"),
                 "scope": getattr(reason, "scope", "service"),
                 "trace_id": req.get("trace_id"), "error": str(reason)}))

    def _client_id(self, req, envelope) -> str:
        """Admission identity: explicit ``client`` metadata when the
        peer ships one (the InferenceClient does), else a digest of the
        ROUTER envelope — still distinct per client through a proxy,
        because the client's own identity frame rides inside."""
        client = req.get("client")
        if isinstance(client, str) and client:
            return client
        return "peer-%08x" % (zlib.crc32(
            b"".join(bytes(f) for f in envelope)) & 0xFFFFFFFF)

    def _deadline_s(self, req) -> float:
        """Relative deadline budget for one request: the client-shipped
        ``deadline_ms`` capped by ``request_ttl_s``.  Non-finite
        budgets are garbage: min(nan, ttl) is nan, and a nan deadline
        fails every later expiry check — a client could disable the
        TTL outright with one bad float."""
        deadline_s = self.request_ttl_s
        budget_ms = req.get("deadline_ms")
        if budget_ms is not None:
            try:
                budget_s = float(budget_ms) / 1e3
            except (TypeError, ValueError):
                budget_s = float("nan")
            if math.isfinite(budget_s):
                deadline_s = min(budget_s, deadline_s)
        return deadline_s

    def _handle_generate(self, sock, envelope, req, rid) -> None:
        """The ``generate`` request kind (ISSUE 16): a 1-D token
        prompt in, ``max_new_tokens`` autoregressive tokens out —
        streamed per-token (``stream``) or returned whole.  Queued on
        the continuous-batching scheduler; the final reply ships from
        the compute loop."""
        if self.gen_sched is None:
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": "generation serving is disabled — start the "
                          "service with root.common.serving.generate."
                          "enabled=True"}))
            return
        x = req.get("x")
        if not isinstance(x, np.ndarray) or x.ndim != 1 or x.size < 1 \
                or not np.issubdtype(x.dtype, np.number):
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": "generate request needs a non-empty 1-D "
                          "numeric token prompt 'x'"}))
            return
        self._m["requests_in"].inc()
        deadline_s = self._deadline_s(req)
        if deadline_s <= 0:
            self._m["timed_out"].inc()
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "timed_out": True, "req_id": rid,
                 "replica_id": self.replica_id,
                 "policy": "deadline", "trace_id": req.get("trace_id"),
                 "error": f"deadline budget "
                          f"{req.get('deadline_ms')}ms already "
                          f"expended — refused at ingress"}))
            return
        client = self._client_id(req, envelope)
        dup = rid is not None and self.gen_sched.in_flight(client, rid)
        try:
            seq = GenSeq(
                x, max_new=int(req.get("max_new_tokens", 0) or 0),
                temperature=float(req.get("temperature", 0.0) or 0.0),
                top_k=int(req.get("top_k", 0) or 0),
                seed=req.get("seed"),
                stream=bool(req.get("stream", False)),
                return_logits=bool(req.get("return_logits", False)),
                return_logprobs=bool(req.get("return_logprobs", False)),
                reply_to=list(envelope), req_id=rid,
                trace_id=req.get("trace_id"),
                client=client,
                deadline_s=deadline_s)
        except (TypeError, ValueError) as exc:
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "req_id": rid,
                 "replica_id": self.replica_id,
                 "error": f"bad generate parameters: {exc}"}))
            return
        if self._tracer.enabled:
            # zero-duration arrival marker: the replica frontend's hop
            # in the stitched fleet trace (ISSUE 20)
            self._tracer.add("serving", "generate_rx",
                             time.perf_counter(), 0.0,
                             {"trace_id": req.get("trace_id"),
                              "req_id": rid})
        reason = self.gen_sched.submit(seq)
        if reason is None and dup:
            # a resend matched an in-flight generation: answer with a
            # heartbeat partial — refreshes the client's resend timer
            # (generations outlive the resend window routinely; a
            # silent dedup would let a healthy long generation burn the
            # client's resend cap into a give-up)
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": True, "partial": True, "heartbeat": True,
                 "req_id": rid, "replica_id": self.replica_id,
                 "trace_id": req.get("trace_id")}))
            return
        if reason is not None:
            self._m["rejected"].inc()
            sock.send_multipart(list(envelope) + self.codec.encode(
                {"ok": False, "rejected": True, "req_id": rid,
                 "replica_id": self.replica_id,
                 "policy": getattr(reason, "policy", "refused"),
                 "scope": getattr(reason, "scope", "service"),
                 "trace_id": req.get("trace_id"),
                 "error": str(reason)}))
        # accepted (or deduplicated onto an in-flight generation):
        # tokens arrive from the compute loop's scheduler rounds

    # -- the compute loop (donated ping-pong) ----------------------------------

    def _resolve_seq_out(self) -> bool:
        """Does the model's output carry the SEQ axis?  Probed ONCE by
        comparing output axis 1 across two different seq rungs (a class
        axis cannot track the rung) — never per batch, where a class
        count colliding with one rung would truncate logits.  A
        single-rung seq ladder whose one rung equals the output width
        cannot be disambiguated — refused readably rather than
        guessed (slicing a class axis answers confidently wrong)."""
        if self._seq_out is None:
            lad = self.batcher.ladder
            r0 = lad.rungs[0]
            shapes = []
            for s in lad.seq_rungs[:2]:
                y = self.runner.infer(np.zeros(
                    self.runner.bucket_shape((r0, s)), self.runner.dtype))
                shapes.append(y.shape[1] if y.ndim >= 2 else None)
            matched = [shapes[i] == lad.seq_rungs[i]
                       for i in range(len(shapes))]
            if len(matched) == 1 and matched[0]:
                raise ValueError(
                    f"cannot tell whether the model output's axis 1 "
                    f"({shapes[0]}) is the sequence axis or a class "
                    f"axis that happens to equal the single seq rung "
                    f"{lad.seq_rungs[0]} — give the seq ladder a "
                    f"second rung (root.common.serving.seq.rungs) so "
                    f"the probe can disambiguate")
            self._seq_out = all(matched)
        return self._seq_out

    def _assemble(self, batch: List[Request]):
        """Coalesced requests -> (live requests, staged device buffer).
        Deadline-expired requests (client budget, else the TTL) are
        answered ``timed_out`` here — computing them would waste a
        batch slot on an answer nobody is waiting for.  Returns None
        when the whole batch expired."""
        now = time.perf_counter()
        live = []
        for r in batch:
            deadline = (r.t_enqueued + self.request_ttl_s
                        if r.t_deadline is None else r.t_deadline)
            if now > deadline:
                self._m["timed_out"].inc()
                self._outbound.put((r.reply_to, {
                    "ok": False, "timed_out": True, "req_id": r.req_id,
                    "replica_id": self.replica_id,
                    "policy": "deadline", "trace_id": r.trace_id,
                    "error": f"request expired before compute (deadline "
                             f"budget spent queueing; ttl cap "
                             f"{self.request_ttl_s:g}s)"}, None))
                self._note_request(False, now - r.t_enqueued, r.req_id,
                                   r.trace_id)
                continue
            live.append(r)
        if not live:
            return None
        rows = sum(r.n for r in live)
        bucket = self.batcher.ladder.bucket_for(rows)
        # 2-D mode: the batcher pinned ONE seq rung for this batch; the
        # assemble buffer is (rows_rung, seq_rung, *tail), zero-filled —
        # the padded tail of every row is PAD id 0, and each request's
        # own length (its padding mask) rides the Request to reply time
        seq = live[0].seq_rung
        shape = ((bucket,) + self.runner.sample_shape if seq is None
                 else (bucket, seq) + self.runner.sample_shape[1:])
        with self._tracer.span("serving", "assemble", rows=rows,
                               bucket=bucket, requests=len(live),
                               seq=seq or 0):
            x = np.zeros(shape, self.runner.dtype)
            off = 0
            for r in live:
                if seq is None:
                    x[off:off + r.n] = np.asarray(r.x, self.runner.dtype) \
                        .reshape((r.n,) + self.runner.sample_shape)
                else:
                    x[off:off + r.n, :r.seq_len] = \
                        np.asarray(r.x, self.runner.dtype).reshape(
                            (r.n, r.seq_len) + self.runner.sample_shape[1:])
                off += r.n
            staged = self.runner.stage(x)
        return live, staged

    def _finish(self, live: List[Request], y_dev, gen: int,
                t_dispatch: Optional[float] = None) -> None:
        y = np.asarray(y_dev)               # the sync point
        if t_dispatch is not None and self._tracer.enabled:
            # dispatch -> materialized: the batch's device-compute span
            # (staging of batch N+1 overlaps inside it by design)
            self._tracer.add(
                "serving", "batch_compute", t_dispatch,
                time.perf_counter() - t_dispatch,
                {"rows": sum(r.n for r in live), "requests": len(live),
                 "trace_id": live[0].trace_id if live else None})
        now = time.perf_counter()
        # per-rung latency ring (ISSUE 12): enqueue -> compute done for
        # this batch's ladder rung — the heartbeat's p99-by-bucket feed
        # (histograms carry their own locks; this runs on the compute
        # thread while the router thread reads)
        rung = self.batcher.ladder.bucket_for(sum(r.n for r in live)) \
            if live else None
        off = 0
        for r in live:
            if rung is not None:
                self._m_lat_bucket[rung].observe(now - r.t_enqueued)
            if r.t_deadline is not None and now > r.t_deadline:
                # the post-compute deadline check: a late result is
                # DROPPED, never shipped — the client already moved on,
                # and shipping it would spend reply bandwidth on an
                # answer nobody is waiting for
                self._m["timed_out"].inc()
                self._m["expired_results"].inc()
                self._outbound.put((r.reply_to, {
                    "ok": False, "timed_out": True, "req_id": r.req_id,
                    "replica_id": self.replica_id,
                    "policy": "deadline", "trace_id": r.trace_id,
                    "error": "result ready past the deadline — dropped, "
                             "not shipped"}, None))
                self._note_request(False, now - r.t_enqueued, r.req_id,
                                   r.trace_id, bucket=rung)
                off += r.n
                continue
            # slice-copy: each reply owns its rows (the padded tail is
            # dropped here — pad rows never leave the server; on a seq
            # output the padded TOKEN positions are sliced off too, back
            # to the request's own length).  ``gen`` names the snapshot
            # generation that answered — ONE per batch by construction
            # (the runner reads (params, gen) atomically), the rollover
            # proof's per-reply assertion.
            yr = y[off:off + r.n]
            # seq-shaped outputs only (probed once at startup — a
            # seq-REDUCING model ships its rows whole; per-batch shape
            # comparison would truncate logits whenever a class count
            # collides with the pinned rung): cut the reply back to
            # the request's own length
            if r.seq_rung is not None and self._resolve_seq_out() \
                    and yr.ndim >= 2:
                yr = yr[:, :r.seq_len]
            self._outbound.put((r.reply_to, {
                "ok": True, "req_id": r.req_id, "trace_id": r.trace_id,
                "gen": gen, "replica_id": self.replica_id,
                "y": np.array(yr)}, r.t_enqueued))
            off += r.n
            self._m["served"].inc()
            self._note_request(True, now - r.t_enqueued, r.req_id,
                               r.trace_id, bucket=rung)

    def _compute_loop(self) -> None:
        import zmq

        wake = zmq.Context.instance().socket(zmq.PUSH)
        wake.setsockopt(zmq.LINGER, 0)
        wake.connect(self._wake_addr)

        def poke():
            try:
                wake.send(b"", zmq.NOBLOCK)
            except zmq.Again:           # router already has wakes queued
                pass

        gs = self.gen_sched

        def gen_step() -> bool:
            # one continuous-batching round (migrate / decode tick /
            # prefill batch); its replies queue for the router thread
            worked, replies = gs.step()
            self._ship_gen(replies, poke)
            return worked or bool(replies)

        staged = None
        try:
            while True:
                if staged is None:
                    # with generation work ready RIGHT NOW the classic
                    # queue gets a zero-wait poll (decode cadence must
                    # not wait out the coalescing window)
                    timeout = 0.0 if (gs is not None
                                      and gs.work_ready()) else 0.05
                    batch = self.batcher.next_batch(timeout=timeout)
                    if batch is None:
                        if self._stop.is_set():
                            if gs is not None:
                                # abandon queued/live generations with
                                # readable draining replies
                                self._ship_gen(gs.drain(), poke)
                            return
                        if gs is not None and not gen_step() \
                                and timeout == 0.0:
                            # ready-but-stalled edge (every active
                            # sequence waiting on a migration slot):
                            # don't spin hot against the pool
                            time.sleep(0.001)
                        continue
                    staged = self._assemble(batch)
                    if staged is None:
                        poke()          # TTL refusals queued: ship them
                        continue
                live, x_dev = staged
                # dispatch is async; the staged buffer is DONATED into
                # the step (ping-pong half 1)
                t_dispatch = time.perf_counter()
                y_dev, gen = self.runner.infer_staged(x_dev)
                staged = None
                # while the device computes batch N, grab-and-stage what
                # is ALREADY queued as batch N+1 (ping-pong half 2: at
                # most two input buffers ever exist — the donated one
                # and this one).  wait_fill=False: a coalescing window
                # here would hold batch N's finished replies hostage
                nxt = self.batcher.next_batch(timeout=0.0,
                                              wait_fill=False)
                if nxt is not None:
                    staged = self._assemble(nxt)
                self._finish(live, y_dev, gen, t_dispatch)
                poke()                  # replies queued: wake the router
                if gs is not None and gs.work_ready():
                    gen_step()          # interleave under mixed traffic
        except Exception:
            # a compute-thread death must not strand clients silently
            self.log.exception("inference compute loop died")
            self._stop.set()
            self.batcher.close()
            if self.gen_sched is not None:
                self.gen_sched.close()
        finally:
            wake.close(0)

    def _note_gen_final(self, rep) -> None:
        """Generation final bookkeeping (ISSUE 20): SLO feeds
        (availability, TTFT, inter-token from the scheduler's timing
        breakdown), the slow-request exemplar window, and the
        stitched-trace reply summary — the replica's spans for this
        trace ride the final back so the client/balancer can stitch
        without waiting for the next heartbeat.  Finals only: the
        infer hot loop and streamed partials never pay this."""
        from znicz_tpu import telemetry

        if rep.get("rejected"):
            return              # intentional refusal: not a miss
        ok = bool(rep.get("ok"))
        t = rep.get("timing_ms") or {}
        total = t.get("total")
        if total is not None:
            self._note_request(ok, total / 1e3, rep.get("req_id"),
                               rep.get("trace_id"), kind="generate",
                               breakdown=t)
        else:
            self.slo.record("availability", ok)
        if ok:
            ttft = t.get("ttft")
            if ttft is not None:
                self.slo.record_latency("ttft", ttft / 1e3)
                toks = rep.get("tokens")
                n = int(getattr(toks, "size", 0) or 0)
                if n > 1 and total is not None and total > ttft:
                    self.slo.record_latency(
                        "inter_token",
                        (total - ttft) / 1e3 / (n - 1))
        tid = rep.get("trace_id")
        if ok and tid and self._tracer.enabled:
            spans = self._exporter.peek_trace(str(tid))
            if spans:
                rep["spans"] = spans
                rep["origin"] = telemetry.identity()

    def _ship_gen(self, replies, poke=None) -> None:
        """Queue generation replies for the router thread.  Finals
        count into served/timed_out/rejected (and so toward
        ``max_requests``); streamed partials are progress, not
        answers."""
        for env, rep in replies:
            if env is None:
                continue
            if not rep.get("partial"):
                if rep.get("ok"):
                    self._m["served"].inc()
                elif rep.get("timed_out"):
                    self._m["timed_out"].inc()
                else:
                    self._m["rejected"].inc()
                self._note_gen_final(rep)
            self._outbound.put((env, rep, None))
        if replies and poke is not None:
            poke()


for _name, _help in InferenceServer.COUNTERS.items():
    setattr(InferenceServer, _name, registered_property(_name, _help))
del _name, _help
