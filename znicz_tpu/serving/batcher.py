"""Dynamic request batcher for the inference service (ISSUE 4).

Small-request serving throughput is dominated by two costs: the per-
dispatch overhead of running the model (a batch-1 forward pays the same
dispatch/jit-call price as a batch-32 one) and jit-cache hygiene (every
distinct batch shape is a fresh XLA compile).  The batcher attacks both:

  - **Coalescing** (clipper/triton-style): a bounded queue of requests is
    drained into batches under a ``(max_batch, max_delay_ms)`` policy —
    a batch closes as soon as it holds ``max_batch`` rows, or when
    ``max_delay_ms`` has elapsed since its first row was taken (latency
    is bounded by construction; an idle service adds no delay because
    the window only starts once a request exists).
  - **Bucket ladder**: each closed batch is padded up to the next rung
    of a fixed ladder (powers of two up to ``max_batch`` by default), so
    the jit cache holds AT MOST ``len(ladder)`` executables and a mixed-
    size request stream causes ZERO recompiles after warmup
    (``ModelRunner.compiles`` is the proof counter).
  - **Backpressure**: the queue is bounded in ROWS; a submit that would
    exceed ``queue_bound`` is shed immediately (counted, refused with a
    readable reason) instead of growing an unbounded backlog whose every
    entry would time out anyway.
  - **Admission control** (ISSUE 6): per-client token-bucket rate
    limits and weighted fair queueing.  Each client gets its own
    subqueue; ``next_batch`` drains them with deficit round robin
    (rows-weighted: each visit banks ``quantum`` rows, a request is
    taken when its client's deficit covers it), so one flooding client
    degrades only itself — its excess is refused ``rate_limited`` at
    submit, and whatever it does get queued cannot starve other
    clients' drain share.  Every refusal is a :class:`Refusal`: still
    the readable string the frontend always shipped, now carrying the
    ``policy`` name (``shed`` / ``oversized`` / ``rate_limited`` /
    ``deadline`` / ``draining``) so a client can tell WHICH policy
    refused it.  Config home: ``root.common.serving.admission.*``.

**Continuous batching** (ISSUE 16, paged in ISSUE 19):
:class:`GenerationScheduler` runs the autoregressive generation plane
next to the classic batcher.  Prefill and decode dispatch as SEPARATE
bucket families: every tick, the decode steps of ALL live generations
sharing a page-table rung coalesce into one (decode-rung x page-rung)
executable — requests join mid-batch as their prefill lands and leave
mid-batch the tick they finish (their KV pages release immediately,
claimable the same tick).  Long prompts prefill in fixed
``prefill_chunk`` token chunks co-scheduled with decode ticks, so a
prompt's length bounds how MANY ticks it spans, never how long one
tick runs; prompts sharing indexed prefix pages skip them outright
(prefix cache, copy-on-write on divergence).  Sampling (greedy, or
seeded temperature/top-k) is fused into the executables, so a token
stream is a deterministic pure function of its own prompt + sampling
params + the pinned executables — co-batched neighbors are invisible
— and a tick's reply is token-sized, not vocab-sized.

Threading contract: ``submit`` may be called from the frontend's router
thread; ``next_batch`` from the single compute thread.  All state is
guarded by one condition variable.  The scheduler's ``submit`` is
router-thread too; ``step`` (all compute + slot bookkeeping) runs ONLY
on the compute thread — one lock guards the handoff queue.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional, Sequence

from znicz_tpu.telemetry.metrics import registered_property


class BucketLadder:
    """The fixed ladder of padded batch sizes.  Default rungs are the
    powers of two up to ``max_batch`` (plus ``max_batch`` itself when it
    is not a power of two) — a ladder that over-pads by at most 2x while
    keeping the executable count logarithmic in ``max_batch``.

    ``dp`` is the serving mesh's data-axis size (ISSUE 13): every rung
    must split evenly across the data-parallel devices, so default
    rungs are SNAPPED UP to the next multiple of ``dp`` (then deduped —
    the ladder only ever gets shorter) and explicit rungs that do not
    divide are refused readably rather than discovered as an XLA
    sharding error at the first request.

    **2-D (batch x seq) mode** (ISSUE 15): with ``max_len > 0`` the
    ladder grows a SECOND axis of sequence rungs (powers of two up to
    ``max_len``, or explicit ``seq_rungs``) for variable-length
    workloads: a request is padded UP on both axes — its batch lands on
    ``bucket_for(rows)`` and its OWN sequence length on
    ``seq_bucket_for(len)`` — so the jit cache holds at most
    ``len(rungs) * len(seq_rungs)`` executables (``buckets()``
    enumerates them for warmup) and a mixed-length stream still causes
    ZERO recompiles after warmup.  A request's seq rung depends only on
    its OWN length, never on co-batched neighbors — that is what keeps
    the 0-ULP batch-independence contract a per-(rows, seq)-executable
    property under variable length.  dp snapping applies to the batch
    axis only (devices shard rows, never tokens)."""

    def __init__(self, max_batch: int, rungs: Optional[Sequence[int]] = None,
                 dp: int = 1, max_len: int = 0,
                 seq_rungs: Optional[Sequence[int]] = None):
        self.max_batch = int(max_batch)
        self.dp = int(dp)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if self.dp < 1:
            raise ValueError(f"dp must be >= 1, got {dp}")
        if self.max_batch % self.dp:
            raise ValueError(
                f"max_batch={self.max_batch} does not divide across the "
                f"mesh's data axis (dp={self.dp}); pick a max_batch "
                f"that is a multiple of dp")
        snapped = rungs is None
        if rungs is None:
            rungs = []
            r = 1
            while r < self.max_batch:
                rungs.append(r)
                r *= 2
            rungs.append(self.max_batch)
            # mesh-aware snap: each rung up to the next multiple of dp
            rungs = [-(-r // self.dp) * self.dp for r in rungs]
        rungs = sorted(set(int(r) for r in rungs))
        if not rungs or rungs[0] < 1 or rungs[-1] != self.max_batch:
            raise ValueError(
                f"bucket ladder {rungs} must be positive and end at "
                f"max_batch={self.max_batch}")
        if not snapped:
            bad = [r for r in rungs if r % self.dp]
            if bad:
                raise ValueError(
                    f"bucket ladder rungs {bad} do not divide across "
                    f"the mesh's data axis (dp={self.dp}); every rung "
                    f"must be a multiple of dp so each device holds "
                    f"exactly rows/dp rows")
        self.rungs: List[int] = rungs
        self.max_len = int(max_len)
        if self.max_len < 0:
            raise ValueError(f"max_len must be >= 0, got {max_len}")
        if self.max_len == 0:
            if seq_rungs:
                raise ValueError(
                    "seq_rungs given without max_len — set "
                    "root.common.serving.seq.max_len to enable the "
                    "2-D ladder")
            self.seq_rungs: Optional[List[int]] = None
        else:
            if seq_rungs is None:
                seq_rungs = []
                s = 1
                while s < self.max_len:
                    seq_rungs.append(s)
                    s *= 2
                seq_rungs.append(self.max_len)
            seq_rungs = sorted(set(int(s) for s in seq_rungs))
            if not seq_rungs or seq_rungs[0] < 1 \
                    or seq_rungs[-1] != self.max_len:
                raise ValueError(
                    f"seq ladder {seq_rungs} must be positive and end "
                    f"at max_len={self.max_len}")
            self.seq_rungs = seq_rungs

    def bucket_for(self, n: int) -> int:
        """Smallest rung >= n (n must be within the ladder)."""
        for r in self.rungs:
            if n <= r:
                return r
        raise ValueError(f"{n} rows exceed the ladder's top rung "
                         f"{self.rungs[-1]}")

    def seq_bucket_for(self, n: int) -> int:
        """Smallest SEQ rung >= n (2-D mode only) — a function of the
        request's OWN length, so co-batched neighbors can never move a
        request to a different executable's seq axis."""
        if self.seq_rungs is None:
            raise ValueError("ladder has no seq axis (max_len unset)")
        for s in self.seq_rungs:
            if n <= s:
                return s
        raise ValueError(f"sequence of {n} tokens exceeds the ladder's "
                         f"top seq rung {self.seq_rungs[-1]}")

    def buckets(self) -> List:
        """Every executable shape the jit cache may hold: the batch
        rungs (1-D mode), or the (rows, seq) product (2-D mode) —
        the warmup set and the ``compiles == len(buckets())`` bound."""
        if self.seq_rungs is None:
            return list(self.rungs)
        return [(r, s) for r in self.rungs for s in self.seq_rungs]

    @staticmethod
    def bucket_key(rows: int, seq: Optional[int] = None):
        """The stats/telemetry key for one bucket: the plain rung int
        (1-D, the historical shape) or ``"RxS"`` (2-D — a string so
        /status.json keeps it as a JSON key verbatim)."""
        return int(rows) if seq is None else f"{int(rows)}x{int(seq)}"

    def __iter__(self):
        return iter(self.rungs)

    def __repr__(self):
        if self.seq_rungs is not None:
            return f"BucketLadder({self.rungs} x seq{self.seq_rungs})"
        return f"BucketLadder({self.rungs})"


#: "no client is mid-visit" marker for the DRR drain.  A dedicated
#: sentinel, NOT None: None is also the shared-queue KEY when fairness
#: is off, and conflating the two made the drain skip that queue's
#: quantum banking forever (an infinite loop under the queue lock the
#: first time a retired per-client queue coexisted with the shared one)
_NO_VISIT = object()


class Refusal(str):
    """A refusal reason: the plain readable string the frontend always
    shipped, additionally carrying the ``policy`` slug (``shed`` /
    ``oversized`` / ``rate_limited`` / ``deadline`` / ``draining``) the
    reply names, so a refused client can react per policy (back off on
    ``rate_limited``, split on ``oversized``, ...) without parsing
    prose.  ``scope`` says WHOSE limit refused: ``"client"`` (this
    caller's own quota/bound — the service is healthy) vs
    ``"service"`` (global overload/shutdown) — the client circuit
    breaker counts only service-scoped sheds as failures, so a caller
    bumping its own fair-share bound never opens its breaker against a
    healthy service."""

    policy = "refused"
    scope = "service"

    def __new__(cls, policy: str, reason: str, scope: str = "service"):
        self = super().__new__(cls, reason)
        self.policy = policy
        self.scope = scope
        return self


# the per-client rate limiter now lives in the transport core (ISSUE
# 14) so the MASTER's ingress meters per-slave rates with the SAME
# primitive; re-exported here under its historical home
from znicz_tpu.transport.admission import TokenBucket        # noqa: E402


class AdmissionPolicy:
    """Admission-control knobs (config home
    ``root.common.serving.admission.*``):

      - ``rate_limit``: rows/s each client may sustain (0 = unlimited);
      - ``rate_burst``: token-bucket capacity in rows (0 = auto:
        ``max(rate_limit, max_batch)``);
      - ``fair``: per-client subqueues drained deficit-round-robin
        (off = the historical single FIFO);
      - ``quantum``: DRR rows banked per visit (0 = auto:
        ``max_batch // 4``, min 1);
      - ``client_queue_bound``: queued rows ONE client may hold
        (0 = no per-client cap — the global ``queue_bound`` is the
        only backpressure);
      - ``enabled``: master switch (toggled mid-traffic by
        tests/test_serving.py::test_batcher_admission_toggle_mid_traffic).
    """

    __slots__ = ("rate_limit", "rate_burst", "fair", "quantum",
                 "client_queue_bound", "enabled")

    def __init__(self, rate_limit: float = 0.0, rate_burst: float = 0.0,
                 fair: bool = True, quantum: int = 0,
                 client_queue_bound: int = 0, enabled: bool = True):
        self.rate_limit = float(rate_limit)
        self.rate_burst = float(rate_burst)
        self.fair = bool(fair)
        self.quantum = int(quantum)
        self.client_queue_bound = int(client_queue_bound)
        self.enabled = bool(enabled)


class Request:
    """One queued inference request: ``x`` is the (n_rows, *sample) host
    array, ``reply_to`` an opaque routing token the frontend uses to
    answer (the ROUTER envelope), ``req_id`` the client's correlation
    id.  ``t_enqueued`` feeds the latency stats; ``t_deadline`` (ISSUE
    6) is the ABSOLUTE local deadline the frontend derived at ingress
    from the client's shipped budget (or its own TTL) — checked at
    assemble time and again post-compute, so expired work is never
    computed and never shipped.  ``client`` keys the admission
    subqueue/bucket."""

    __slots__ = ("x", "n", "reply_to", "req_id", "trace_id", "client",
                 "t_enqueued", "t_deadline", "seq_len", "seq_rung")

    def __init__(self, x, n: int, reply_to=None, req_id=None,
                 trace_id=None, client=None, deadline_s=None,
                 seq_len=None):
        self.x = x
        self.n = int(n)
        #: variable-length workloads (ISSUE 15): the request's OWN
        #: unpadded sequence length — the padding-mask information the
        #: frontend keeps per request (pad tokens are PAD-id rows it
        #: appends at assemble, and the reply is sliced back to this
        #: length).  ``seq_rung`` is assigned at submit from the
        #: ladder's seq axis; batches only ever coalesce ONE rung.
        self.seq_len = None if seq_len is None else int(seq_len)
        self.seq_rung = None
        self.reply_to = reply_to
        self.req_id = req_id
        #: optional cross-process correlation id carried in the wire-v3
        #: metadata (ISSUE 5) — echoed in the reply, tagged on spans
        self.trace_id = trace_id
        #: admission identity (frontend: explicit ``client`` metadata,
        #: else a digest of the ROUTER envelope)
        self.client = client
        self.t_enqueued = time.perf_counter()
        self.t_deadline = (None if deadline_s is None
                           else self.t_enqueued + float(deadline_s))


class DynamicBatcher:
    """Bounded request queue + the coalescing policy (module docstring).

    ``submit`` returns None on acceptance or a human-readable refusal
    reason (shed/oversized) — the frontend ships the reason back so a
    client sees WHY it was refused instead of timing out.
    """

    #: batcher counters registered under component="batcher" (ISSUE 5):
    #: name -> HELP text
    COUNTERS = {
        "submitted": "accepted requests",
        "shed": "refused: queue at bound",
        "oversized": "refused: n > max_batch",
        "rate_limited": "refused: client over its rate limit",
        "batches": "batches closed",
        "batched_requests": "requests inside closed batches",
        "batched_rows": "real rows inside closed batches",
        "padded_rows": "pad rows added by the ladder",
        "real_cells": "real cells (rows x own tokens) inside closed "
                      "batches — the pad_ratio denominator",
        "padded_cells": "pad cells added by the (2-D) ladder: bucket "
                        "area minus real cells — the padded-compute "
                        "numerator",
    }

    #: per-client accounting table bound (plain state, not registry
    #: series: client ids are ephemeral uuids — labeled families would
    #: leak a series per client forever)
    MAX_CLIENT_STATS = 32

    #: token-bucket table bound: past this, fully-refilled buckets
    #: (state == freshly built — dropping one is invisible to its
    #: client) are swept; clients churning faster than this refill are
    #: evicted oldest-first.  Without a bound the table grows one
    #: entry per ephemeral client id ever seen (uuid per
    #: InferenceClient instance) for the life of the service.
    MAX_BUCKETS = 1024

    def __init__(self, max_batch: int = 32, max_delay_ms: float = 5.0,
                 queue_bound: int = 256,
                 ladder: Optional[BucketLadder] = None,
                 admission: Optional[AdmissionPolicy] = None):
        from znicz_tpu import telemetry

        self.ladder = ladder or BucketLadder(max_batch)
        self.max_batch = self.ladder.max_batch
        self.max_delay_s = float(max_delay_ms) / 1e3
        self.queue_bound = int(queue_bound)
        #: per-client subqueues (key None = the shared FIFO when
        #: fairness is off / admission disabled)
        self._queues: "collections.OrderedDict[object, collections.deque]" \
            = collections.OrderedDict()
        self._rr: collections.deque = collections.deque()  # DRR rotation
        self._deficit: Dict[object, float] = {}
        self._visiting = _NO_VISIT          # DRR visit marker (quantum
        #                                     banks once per visit)
        self._client_rows: Dict[object, int] = {}
        #: bounded per-client admission accounting for the panel
        self.clients: "collections.OrderedDict[str, Dict]" \
            = collections.OrderedDict()
        self._rows = 0                      # rows currently queued
        self._cond = threading.Condition()
        self._closed = False
        self.set_admission(admission or AdmissionPolicy())
        # -- accounting (the serving panel's inputs), homed in the
        # telemetry registry; historical attribute names preserved by
        # the class-level properties below
        _sc = telemetry.scope("batcher")
        self._m = {name: _sc.counter(name, help)
                   for name, help in self.COUNTERS.items()}
        # per-bucket families (ISSUE 15): keys are the ladder's
        # bucket_key form — plain rung ints in 1-D mode (the historical
        # shape), "RxS" strings on a 2-D ladder.  padded/real cells per
        # bucket make pad_ratio a measured, per-executable quantity.
        self._m_bucket_hits = {}
        self._m_real_cells = {}
        self._m_pad_cells = {}
        for b in self.ladder.buckets():
            key = (self.ladder.bucket_key(b) if isinstance(b, int)
                   else self.ladder.bucket_key(*b))
            self._m_bucket_hits[key] = _sc.counter(
                "bucket_hits", "batches closed per ladder bucket",
                bucket=str(key))
            self._m_real_cells[key] = _sc.counter(
                "bucket_real_cells",
                "real cells (rows x own tokens) per ladder bucket",
                bucket=str(key))
            self._m_pad_cells[key] = _sc.counter(
                "bucket_padded_cells",
                "pad cells (bucket area - real) per ladder bucket",
                bucket=str(key))
        _sc.gauge("queue_depth", "rows queued, not yet batched",
                  fn=telemetry.weak_fn(self, lambda b: b._rows))

    # -- registry-backed counters under their historical names ------------
    # (properties generated from COUNTERS after the class body)

    @property
    def bucket_hits(self) -> Dict:
        """``{bucket: batches closed at that bucket}`` snapshot
        (historical read shape; the counters live in the registry).
        Keys are rung ints (1-D) or ``"RxS"`` strings (2-D)."""
        return {r: c.value for r, c in self._m_bucket_hits.items()}

    def pad_ratio(self) -> Dict:
        """``{bucket: padded cells / real cells}`` — the padded-compute
        ratio per executable (ISSUE 15): how many pad cells the ladder
        computed per real cell.  Buckets that never closed a batch are
        omitted; 0.0 means every batch left exactly full."""
        out = {}
        for key, real in self._m_real_cells.items():
            r = real.value
            if r:
                out[key] = round(self._m_pad_cells[key].value / r, 4)
        return out

    # -- admission -------------------------------------------------------------

    def set_admission(self, policy: AdmissionPolicy) -> None:
        """Install (or swap — the bench's on/off overhead toggle) the
        admission policy.  Auto knobs resolve against this batcher;
        token buckets restart (new rates must not inherit old debt).
        Already-queued requests drain under the rotation regardless —
        only the submit-side keying/limits change."""
        from znicz_tpu.transport import AdmissionTable

        with self._cond:
            self.admission = policy
            self._rate_burst = policy.rate_burst or max(
                policy.rate_limit, float(self.max_batch))
            self._quantum = policy.quantum or max(1, self.max_batch // 4)
            # the bounded per-client bucket table is the transport
            # core's (ISSUE 14 — ONE home for the lazy-build /
            # lossless-sweep / oldest-first-eviction discipline, shared
            # with the master's ingress); rebuilt so new rates never
            # inherit old debt
            self._table = AdmissionTable(policy.rate_limit,
                                         self._rate_burst,
                                         max_peers=self.MAX_BUCKETS)

    @property
    def _client_bound(self) -> int:
        """The effective per-client queued-rows cap — derived LIVE (not
        cached at set_admission time) so mutating ``queue_bound`` at
        runtime cannot leave a stale fair-share bound above the whole
        queue."""
        return self.admission.client_queue_bound or self.queue_bound

    def _client_stat(self, client) -> Dict:
        key = str(client)
        st = self.clients.get(key)
        if st is None:
            while len(self.clients) >= self.MAX_CLIENT_STATS:
                self.clients.popitem(last=False)    # oldest first seen
            st = self.clients[key] = {
                "requests": 0, "rows": 0, "accepted": 0,
                "rate_limited": 0, "shed": 0}
        return st

    def admission_stats(self) -> Dict:
        adm = self.admission
        with self._cond:
            # under the lock: the router/compute threads mutate
            # _queues/clients mid-iteration otherwise (web_status
            # scrapes from its own HTTP thread)
            active = sum(1 for q in self._queues.values() if q)
            clients = {k: dict(v) for k, v in self.clients.items()}
        return {
            "enabled": adm.enabled,
            "fair": adm.fair,
            "rate_limit_rows_per_s": adm.rate_limit,
            "rate_burst_rows": self._rate_burst,
            "quantum_rows": self._quantum,
            "client_queue_bound": self._client_bound,
            "rate_limited": self.rate_limited,
            "active_clients": active,
            "clients": clients,
        }

    # -- producer side ---------------------------------------------------------

    def submit(self, req: Request) -> Optional[Refusal]:
        if req.n < 1 or req.n > self.max_batch:
            self._m["oversized"].inc()
            return Refusal(
                "oversized",
                f"request of {req.n} rows exceeds max_batch="
                f"{self.max_batch} (split it client-side)",
                scope="client")
        if self.ladder.seq_rungs is not None:
            # 2-D mode: the seq rung is a function of the request's OWN
            # length (frontend validated 1 <= len <= max_len already;
            # this is the defensive in-process-caller check)
            if req.seq_len is None or req.seq_len < 1 \
                    or req.seq_len > self.ladder.max_len:
                self._m["oversized"].inc()
                return Refusal(
                    "oversized",
                    f"sequence length {req.seq_len} outside the seq "
                    f"ladder (1..{self.ladder.max_len})", scope="client")
            req.seq_rung = self.ladder.seq_bucket_for(req.seq_len)
        adm = self.admission
        with self._cond:
            if self._closed:
                return Refusal("draining", "service is shutting down")
            key = None
            took = 0
            if adm.enabled:
                st = self._client_stat(req.client)
                st["requests"] += 1
                st["rows"] += req.n
                if adm.rate_limit > 0:
                    if not self._table.try_take(req.client, req.n):
                        self._m["rate_limited"].inc()
                        st["rate_limited"] += 1
                        return Refusal(
                            "rate_limited",
                            f"client over its rate limit "
                            f"({adm.rate_limit:g} rows/s, burst "
                            f"{self._rate_burst:g}) — rate_limited",
                            scope="client")
                    took = req.n
                if adm.fair:
                    key = req.client
                    # explicit per-client cap only: with
                    # client_queue_bound=0 the effective bound equals
                    # queue_bound and client_rows <= total rows, so the
                    # global check below already subsumes this one
                    if (adm.client_queue_bound > 0
                            and self._client_rows.get(key, 0) + req.n
                            > self._client_bound):
                        self._m["shed"].inc()
                        st["shed"] += 1
                        if took:
                            self._table.refund(req.client, took)
                        return Refusal(
                            "shed",
                            f"client queue at its fair-share bound "
                            f"({self._client_rows.get(key, 0)} rows "
                            f"queued, bound {self._client_bound}) — shed",
                            scope="client")
            if self._rows + req.n > self.queue_bound:
                self._m["shed"].inc()
                if adm.enabled:
                    st["shed"] += 1
                if took:
                    self._table.refund(req.client, took)
                return Refusal(
                    "shed",
                    f"queue at bound ({self._rows} rows queued, "
                    f"bound {self.queue_bound}) — shed")
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = collections.deque()
                self._rr.append(key)
            q.append(req)
            self._rows += req.n
            self._client_rows[key] = self._client_rows.get(key, 0) + req.n
            if adm.enabled:
                st["accepted"] += 1
            self._m["submitted"].inc()
            self._cond.notify()
            return None

    @property
    def queue_depth(self) -> int:
        """Rows currently queued (not yet taken into a batch)."""
        return self._rows

    def close(self) -> None:
        """Wake every waiter; ``next_batch`` drains what is queued and
        then returns None forever."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    # -- consumer side ---------------------------------------------------------

    def _pop(self, key, idx: int = 0) -> Request:
        """Dequeue entry ``idx`` of ``key``'s subqueue (cond held).
        idx > 0 is the 2-D drain reaching past a mismatched-rung head
        (``_match``); earlier entries keep their relative order."""
        q = self._queues[key]
        if idx:
            q.rotate(-idx)
            req = q.popleft()
            q.rotate(idx)
        else:
            req = q.popleft()
        self._rows -= req.n
        if key in self._client_rows:
            self._client_rows[key] -= req.n
        return req

    @staticmethod
    def _match(q, space: int, seq_rung) -> int:
        """Index of the first queued request that fits ``space`` rows
        AND the pinned seq rung, or -1.  With no pinned rung (a 1-D
        ladder, or the FIRST take of any batch) only the HEAD is
        considered — the historical strict-FIFO drain.  With a pinned
        rung the scan reaches PAST mismatched-RUNG requests only
        (head-of-line blocking would otherwise fragment a mixed-length
        stream into 1-row batches — the dispatch-overhead regime
        coalescing exists to avoid): the first SAME-rung request is
        taken if it fits and otherwise ends the scan, so same-rung
        requests always drain in arrival order (a smaller later
        request never overtakes an older one that merely missed the
        remaining space).  Skipped requests keep their
        deadline/admission state untouched."""
        for idx, req in enumerate(q):
            if seq_rung is not None and req.seq_rung != seq_rung:
                continue                # reach past OTHER rungs only
            return idx if req.n <= space else -1
        return -1

    def _take_one(self, space: int,
                  seq_rung: Optional[int] = None) -> Optional[Request]:
        """One request under deficit round robin, or None when nothing
        queued fits ``space`` rows (requests are never split; cond
        held).  A visited client banks ``quantum`` rows once per visit
        and keeps its turn while its banked deficit covers its head —
        rows-weighted fairness across clients, plain FIFO within one.
        A client whose queue empties is retired (classic DRR: an idle
        queue banks nothing).

        ``seq_rung`` (2-D ladders, ISSUE 15) restricts the take to
        requests whose OWN seq rung matches the batch being built —
        coalescing by nearest seq rung without touching the
        deadline/admission discipline: a mismatched head simply ends
        that client's visit exactly like a head too big for the
        remaining space (FIFO within a client is preserved)."""

        rr = self._rr
        if self._rows == 0 or not rr:
            return None
        if len(rr) == 1:
            # one subqueue (single client, or fairness off): plain FIFO,
            # no deficit bookkeeping on the hot path
            idx = self._match(self._queues[rr[0]], space, seq_rung)
            if idx >= 0:
                return self._pop(rr[0], idx)
            return None
        # ONE scan per take: queues do not change under the lock until
        # _pop, so each client's matched index stays valid through
        # however many DRR rotations deficit banking needs (re-scanning
        # per visit made 2-D assembly O(batch x queued) twice over)
        matches = {key: idx for key, q in self._queues.items() if q
                   for idx in (self._match(q, space, seq_rung),)
                   if idx >= 0}
        if not matches:
            return None                     # nothing fits: close batch
        cap = float(max(self._quantum, self.max_batch))
        while True:
            key = rr[0]
            q = self._queues.get(key)
            if not q:
                rr.popleft()                # retire the idle client
                self._deficit.pop(key, None)
                self._queues.pop(key, None)
                self._client_rows.pop(key, None)
                if self._visiting == key:
                    self._visiting = _NO_VISIT
                continue
            if self._visiting != key:
                self._visiting = key
                self._deficit[key] = min(
                    self._deficit.get(key, 0.0) + self._quantum, cap)
            idx = matches.get(key, -1)
            if idx >= 0 and self._deficit.get(key, 0.0) >= q[idx].n:
                self._deficit[key] -= q[idx].n
                return self._pop(key, idx)
            # nothing fits (space/rung), or deficit not yet banked:
            # this visit ends, next client's turn
            rr.rotate(-1)
            self._visiting = _NO_VISIT

    def next_batch(self, timeout: float = 0.2,
                   wait_fill: bool = True) -> Optional[List[Request]]:
        """The next coalesced batch, or None when nothing arrived within
        ``timeout``.  Blocks up to ``timeout`` for the FIRST request;
        from that moment the ``max_delay_ms`` window runs, during which
        further requests are folded in until ``max_batch`` rows are
        reached.  A request that does not fit the remaining space stays
        queued for the next batch (requests are never split); with
        multiple clients queued, requests are drained deficit-round-
        robin across the per-client subqueues (module docstring).

        ``wait_fill=False`` skips the window: only already-queued
        requests are taken.  That is the PIPELINED grab — the compute
        loop calls it while the previous batch is still on the device,
        and waiting out a window there would hold the finished batch's
        replies hostage to the next batch's coalescing (measured +1
        ``max_delay`` on p99)."""
        with self._cond:
            deadline = time.perf_counter() + max(timeout, 0.0)
            while self._rows == 0:
                if self._closed:
                    return None
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    return None
                self._cond.wait(remaining)
            first = self._take_one(self.max_batch)
            if first is None:               # pragma: no cover - defensive
                return None
            batch = [first]
            rows = first.n
            # 2-D ladders: the FIRST request pins the batch's seq rung;
            # only same-rung requests coalesce into it (different rungs
            # close this batch and immediately form their own)
            seq_rung = first.seq_rung
            flush_at = time.perf_counter() + self.max_delay_s
            while rows < self.max_batch:
                req = self._take_one(self.max_batch - rows, seq_rung)
                if req is not None:
                    batch.append(req)
                    rows += req.n
                    continue
                if self._rows:
                    break                   # queued but nothing fits
                remaining = flush_at - time.perf_counter()
                if not wait_fill or remaining <= 0 or self._closed:
                    break
                self._cond.wait(remaining)
        bucket = self.ladder.bucket_for(rows)
        self._m["batches"].inc()
        self._m["batched_requests"].inc(len(batch))
        self._m["batched_rows"].inc(rows)
        self._m["padded_rows"].inc(bucket - rows)
        # padded-compute accounting (ISSUE 15): real cells are each
        # request's rows x its OWN length; the executable computes the
        # full bucket area — the difference is pure padding FLOPs
        if seq_rung is None:
            key = self.ladder.bucket_key(bucket)
            real = rows
            area = bucket
        else:
            key = self.ladder.bucket_key(bucket, seq_rung)
            real = sum(r.n * r.seq_len for r in batch)
            area = bucket * seq_rung
        self._m["real_cells"].inc(real)
        self._m["padded_cells"].inc(area - real)
        self._m_bucket_hits[key].inc()
        self._m_real_cells[key].inc(real)
        self._m_pad_cells[key].inc(area - real)
        return batch

    # -- stats -----------------------------------------------------------------

    def occupancy(self) -> Optional[float]:
        """Mean real rows per closed batch / max_batch (None before the
        first batch) — 1.0 means every batch left full."""
        if not self.batches:
            return None
        return self.batched_rows / (self.batches * self.max_batch)

    def stats(self) -> Dict:
        occ = self.occupancy()
        return {
            "max_batch": self.max_batch,
            "max_delay_ms": self.max_delay_s * 1e3,
            "queue_bound": self.queue_bound,
            "queue_depth": self.queue_depth,
            "submitted": self.submitted,
            "shed": self.shed,
            "oversized": self.oversized,
            "rate_limited": self.rate_limited,
            "batches": self.batches,
            "batched_requests": self.batched_requests,
            "batched_rows": self.batched_rows,
            "padded_rows": self.padded_rows,
            "real_cells": self.real_cells,
            "padded_cells": self.padded_cells,
            "pad_ratio": self.pad_ratio(),
            "seq_rungs": (None if self.ladder.seq_rungs is None
                          else list(self.ladder.seq_rungs)),
            "mean_occupancy": None if occ is None else round(occ, 4),
            "bucket_hits": dict(self.bucket_hits),
            "admission": self.admission_stats(),
        }


class GenSeq:
    """One generation request through its whole life: pending (prompt
    queued) -> active (holding a page-table cache; prefilling in
    ``prefill_chunk`` token chunks, then decoding one token per tick)
    -> finished.  ``prefilled`` counts prompt positions whose k/v are
    in the cache (prefix-cache hits start it > 0); ``t`` is the total
    cache fill once decoding starts.  ``pages`` is the request's page
    table — plain host ints, so "cache growth" is a list append.

    Sampling is per-sequence and deterministic under a seed on BOTH
    paths: the fused in-graph sampler keys off ``seed_val`` (device
    path), the host fallback off a seeded ``np.random.Generator`` —
    either way neighbors share nothing."""

    __slots__ = ("prompt", "prompt_len", "max_new", "temperature",
                 "top_k", "rng", "seed_val", "stream", "return_logits",
                 "return_logprobs", "reply_to", "req_id", "trace_id",
                 "client", "t_enqueued", "t_deadline", "pages",
                 "prefilled", "t", "tokens", "logits", "logprobs",
                 "gen", "t_last", "order", "t_admitted", "t_first")

    def __init__(self, prompt, max_new: int, temperature: float = 0.0,
                 top_k: int = 0, seed=None, stream: bool = False,
                 return_logits: bool = False,
                 return_logprobs: bool = False, reply_to=None,
                 req_id=None, trace_id=None, client=None,
                 deadline_s=None):
        import numpy as np

        self.prompt = np.asarray(prompt).reshape(-1)
        self.prompt_len = int(self.prompt.shape[0])
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.rng = (np.random.default_rng(seed)
                    if self.temperature > 0 else None)
        self.seed_val = (int(seed) & 0xFFFFFFFF if seed is not None
                         else int(np.random.default_rng()
                                  .integers(0, 2**32)))
        self.stream = bool(stream)
        self.return_logits = bool(return_logits)
        self.return_logprobs = bool(return_logprobs)
        self.reply_to = reply_to
        self.req_id = req_id
        self.trace_id = trace_id
        self.client = client
        self.t_enqueued = time.perf_counter()
        self.t_deadline = (None if deadline_s is None
                           else self.t_enqueued + float(deadline_s))
        self.pages: List[int] = []      # the request's page table
        self.prefilled = 0              # prompt positions cached so far
        self.t = 0                      # cache fill (positions written)
        self.tokens: List[int] = []     # emitted so far
        self.logits = [] if return_logits else None
        self.logprobs = [] if return_logprobs else None
        self.gen = None                 # snapshot generation stamp
        self.t_last = None              # last emit time (inter-token)
        self.order = 0                  # arrival index (FIFO grouping)
        self.t_admitted = None          # admission time (queue-wait end)
        self.t_first = None             # first-token time (TTFT end)

    def sample(self, row) -> int:
        """Next token from one (vocab,) logits row — the HOST sampling
        path (``on_device_sampling`` off): greedy argmax at temperature
        0 (deterministic, tie -> lowest id, bit-identical to the fused
        in-graph argmax), else seeded softmax sampling over the
        optional top-k cut."""
        import numpy as np

        if self.temperature <= 0:
            return int(np.argmax(row))
        z = row.astype(np.float64) / self.temperature
        if self.top_k > 0 and self.top_k < z.shape[0]:
            cut = np.partition(z, -self.top_k)[-self.top_k]
            z = np.where(z >= cut, z, -np.inf)
        z -= z.max()
        p = np.exp(z)
        p /= p.sum()
        return int(self.rng.choice(z.shape[0], p=p))


def _host_logp(row, token: int) -> float:
    """log p(token) under one (vocab,) logits row, float64 host math —
    the ``return_logprobs`` fallback when logits were fetched anyway."""
    import numpy as np

    z = row.astype(np.float64)
    z -= z.max()
    return float(z[token] - np.log(np.exp(z).sum()))


class GenerationScheduler:
    """Continuous batching over a paged :class:`GenerationRunner`
    (module docstring).  ``submit`` enqueues from the router thread;
    ``step`` — called by the frontend's compute loop — runs one
    scheduling round on the compute thread:

      1. expire pending/active sequences past their deadline (partial
         tokens ship with the ``deadline`` policy reply);
      2. admit pending requests into the ``slots`` concurrency bound —
         admission runs the prefix-cache lookup, so a request whose
         prompt shares indexed full pages starts with those pages
         CLAIMED (read-only, refcounted) and only its tail to prefill;
      3. ONE decode tick: every fully-prefilled sequence's next token,
         grouped by page-table rung in FIFO chunks of the top decode
         rung — finished sequences release their pages mid-round, and
         a sequence at the context window force-finishes ``truncated``;
      4. ONE prefill chunk batch: up to a prefill rung of
         still-prefilling sequences each advance by ``prefill_chunk``
         tokens — a long prompt costs one BOUNDED chunk between decode
         ticks (chunked prefill), never a whole-prompt stall of the
         decode cadence.  Page allocation (and copy-on-write of shared
         pages about to be appended into) happens here on the host;
         allocation pressure stalls a row for a tick, never the batch.

    Device->host fetches follow ``on_device_sampling``: on, a tick
    ships (b,) sampled tokens (plus logprobs when asked); off, it
    ships (b, vocab) logits and samples on the host — same executable
    family either way, and greedy tokens are bit-identical across the
    knob.

    Returns the replies to ship: streamed per-token partials (opt-in)
    and whole-stream finals.  A resent ``generate`` request matching an
    in-flight ``(client, req_id)`` is deduplicated — generation is NOT
    idempotent compute, but the final reply still is (resend-same-bytes
    semantics hold end to end)."""

    COUNTERS = {
        "gen_submitted": "accepted generate requests",
        "gen_refused": "refused generate requests (policy in the reply)",
        "gen_dedup": "resent generate requests matched to an in-flight "
                     "generation (answered by the original)",
        "prefill_batches": "prefill chunk dispatches — the prompt side "
                           "of the prefill/decode split",
        "prefill_seqs": "sequences whose prefill completed",
        "prefill_tokens": "prompt tokens actually COMPUTED by prefill "
                          "chunks (prefix-cache hits skip theirs)",
        "decode_batches": "decode tick dispatches — the token side of "
                          "the prefill/decode split",
        "decode_tokens": "tokens emitted by decode ticks",
        "generated_tokens": "tokens emitted in total (prefill's first + "
                            "every decode)",
        "cow_copies": "shared prefix pages copy-on-written at the "
                      "first divergent append",
        "fetch_bytes": "bytes fetched device->host by generation ticks "
                       "(tokens or logits — the on-device-sampling "
                       "lever)",
        "gen_finished": "generations completed to max_new_tokens",
        "gen_truncated": "generations force-finished at the context "
                         "window",
        "gen_timed_out": "generations abandoned at their deadline "
                         "(partial tokens shipped)",
    }

    def __init__(self, gen_runner, max_new_cap: int = 256,
                 pending_bound: int = 64, decode_tick_ms: float = 0.0,
                 on_device_sampling: bool = True, replica_id: str = ""):
        from znicz_tpu import telemetry

        self.gen = gen_runner
        self.max_new_cap = int(max_new_cap)
        self.pending_bound = int(pending_bound)
        self.decode_tick_s = float(decode_tick_ms) / 1e3
        self.on_device = bool(on_device_sampling)
        self.replica_id = replica_id
        self._lock = threading.Lock()
        self._pending: collections.deque = collections.deque()
        self._active: List[GenSeq] = []
        #: in-flight (client, req_id) pairs — the resend dedup set
        self._inflight = set()
        self._closed = False
        self._order = 0
        self._next_tick = 0.0
        _sc = telemetry.scope("generate")
        self._m = {name: _sc.counter(name, help)
                   for name, help in self.COUNTERS.items()}
        self._m_inter_token = _sc.histogram(
            "inter_token_seconds",
            "gap between consecutive emitted tokens of one sequence",
            size=8192)
        # ISSUE 20 satellite: TTFT plus its queue-wait/compute split —
        # where the first-token latency is SPENT, not just its size
        self._m_ttft = _sc.histogram(
            "ttft_seconds",
            "time to first token (enqueue -> first emitted token)",
            size=2048)
        self._m_queue_wait = _sc.histogram(
            "gen_queue_wait_seconds",
            "pending-queue wait (enqueue -> admission to a KV slot)",
            size=2048)
        self._m_compute = _sc.histogram(
            "gen_compute_seconds",
            "admission -> first token (prefill compute + tick pacing)",
            size=2048)
        #: page-pressure episode latch: journal the TRANSITION into
        #: pressure once, not every stalled tick
        self._page_pressure = False
        self._t_shed_emit = 0.0         # queue-shed journal rate limit
        #: scheduler spans carry each request's trace_id so the fleet
        #: exporter can stitch decode/prefill ticks into the request's
        #: cross-process timeline (ISSUE 20)
        self._tracer = telemetry.tracer()
        _sc.gauge("kv_occupancy", "allocated KV pages / pool pages",
                  fn=telemetry.weak_fn(self, lambda s: s.gen.occupancy()))
        _sc.gauge("active", "generations holding KV pages",
                  fn=telemetry.weak_fn(self, lambda s: len(s._active)))
        _sc.gauge("pending", "generations queued for admission",
                  fn=telemetry.weak_fn(self, lambda s: len(s._pending)))

    # -- producer side (router thread) -----------------------------------------

    def submit(self, seq: GenSeq) -> Optional[Refusal]:
        """Queue one generation, or refuse readably.  A resend of an
        in-flight (client, req_id) is absorbed (None — the original
        generation answers it)."""
        if seq.prompt_len < 1 or seq.prompt_len > self.gen.max_ctx:
            self._m["gen_refused"].inc()
            return Refusal(
                "oversized",
                f"prompt of {seq.prompt_len} tokens outside the "
                f"context window (1..{self.gen.max_ctx})",
                scope="client")
        if seq.max_new < 1 or seq.max_new > self.max_new_cap:
            self._m["gen_refused"].inc()
            return Refusal(
                "oversized",
                f"max_new_tokens={seq.max_new} outside 1.."
                f"{self.max_new_cap} "
                f"(root.common.serving.generate.max_new_tokens)",
                scope="client")
        key = (seq.client, seq.req_id)
        with self._lock:
            if self._closed:
                return Refusal("draining", "service is shutting down")
            if seq.req_id is not None and key in self._inflight:
                self._m["gen_dedup"].inc()
                return None
            if len(self._pending) >= self.pending_bound:
                self._m["gen_refused"].inc()
                now = time.perf_counter()
                if now - self._t_shed_emit > 1.0:
                    # journal the shed EPISODE (>= 1/s), not every
                    # refusal — a flood must not wash the ring
                    self._t_shed_emit = now
                    from znicz_tpu import telemetry
                    telemetry.emit(
                        "page_shed", "serving", reason="queue_bound",
                        replica=self.replica_id,
                        pending=len(self._pending),
                        bound=self.pending_bound,
                        active=len(self._active))
                return Refusal(
                    "shed",
                    f"generation queue at bound ({len(self._pending)} "
                    f"pending, bound {self.pending_bound}) — shed")
            seq.order = self._order
            self._order += 1
            self._pending.append(seq)
            self._inflight.add(key)
            self._m["gen_submitted"].inc()
            return None

    def in_flight(self, client, req_id) -> bool:
        """Is this (client, req_id) currently queued or generating?
        The frontend answers a RESEND of an in-flight generation with a
        heartbeat partial — the client's resend timer refreshes without
        re-executing anything, so a long generation (queued behind slot
        pressure or just slow) never burns the resend cap of a healthy
        service."""
        with self._lock:
            return (client, req_id) in self._inflight

    def close(self) -> None:
        with self._lock:
            self._closed = True

    # -- consumer side (compute thread) ----------------------------------------

    def work_available(self) -> bool:
        return bool(self._pending or self._active)

    def work_ready(self, now: Optional[float] = None) -> bool:
        """True when step() would do compute RIGHT NOW (pending
        admission, sequences mid-prefill, or the decode tick pacing
        window open) — the compute loop's busy/idle poll hint."""
        if self._pending:
            return True
        if not self._active:
            return False
        if any(s.prefilled < s.prompt_len for s in self._active):
            return True
        now = time.perf_counter() if now is None else now
        return now >= self._next_tick

    def _retire(self, seq: GenSeq) -> None:
        """Drop a sequence from the live sets (lock taken here; page
        release is the caller's — compute thread owns the pool)."""
        with self._lock:
            if seq in self._active:
                self._active.remove(seq)
            self._inflight.discard((seq.client, seq.req_id))

    def _release(self, seq: GenSeq) -> None:
        """Return every page reference the request holds — shared
        prefix pages survive via the index's own refs."""
        if seq.pages:
            self.gen.release_pages(seq.pages)
            seq.pages = []

    def _final(self, seq: GenSeq, replies, truncated: Optional[str] = None,
               counter: str = "gen_finished") -> None:
        import numpy as np

        self._release(seq)
        self._retire(seq)
        self._m[counter].inc()
        if self._tracer.enabled and seq.trace_id:
            # the whole admitted lifetime, tagged for fleet stitching
            t0 = seq.t_admitted if seq.t_admitted is not None \
                else seq.t_enqueued
            t1 = seq.t_last if seq.t_last is not None \
                else time.perf_counter()
            self._tracer.add("generate", "sequence", t0,
                             max(t1 - t0, 0.0),
                             {"trace_id": seq.trace_id,
                              "req_id": seq.req_id,
                              "tokens": len(seq.tokens)})
        rep = {"ok": True, "req_id": seq.req_id,
               "replica_id": self.replica_id,
               "tokens": np.asarray(seq.tokens, np.int32),
               "gen": seq.gen, "prompt_len": seq.prompt_len,
               "trace_id": seq.trace_id,
               "timing_ms": self._timing_ms(seq)}
        if truncated:
            rep["truncated"] = truncated
        if seq.logits is not None:
            rep["logits"] = (np.stack(seq.logits) if seq.logits
                             else np.zeros((0, 0), np.float32))
        if seq.logprobs is not None:
            rep["logprobs"] = np.asarray(seq.logprobs, np.float32)
        replies.append((seq.reply_to, rep))

    @staticmethod
    def _timing_ms(seq: GenSeq) -> Dict[str, Optional[float]]:
        """Per-request latency breakdown for the final reply (the
        frontend's slow-request exemplars render it): where the
        request's wall time went, in ms.  None where a phase never
        happened (e.g. expired before admission)."""
        def ms(a, b):
            return None if a is None or b is None \
                else round((b - a) * 1e3, 3)

        end = seq.t_last if seq.t_last is not None else None
        return {"queue_wait": ms(seq.t_enqueued, seq.t_admitted),
                "ttft": ms(seq.t_enqueued, seq.t_first),
                "compute": ms(seq.t_admitted, seq.t_first),
                "total": ms(seq.t_enqueued, end)}

    def _expire(self, seq: GenSeq, replies) -> None:
        import numpy as np

        self._release(seq)
        self._retire(seq)
        self._m["gen_timed_out"].inc()
        replies.append((seq.reply_to, {
            "ok": False, "timed_out": True, "req_id": seq.req_id,
            "replica_id": self.replica_id, "policy": "deadline",
            "tokens": np.asarray(seq.tokens, np.int32),
            "gen": seq.gen, "trace_id": seq.trace_id,
            "error": "deadline expired mid-generation "
                     f"({len(seq.tokens)} of {seq.max_new} tokens "
                     "emitted — shipped partial)"}))

    def _emit(self, seq: GenSeq, token: int, row, logp, now: float,
              replies) -> None:
        seq.tokens.append(int(token))
        if seq.logits is not None:
            seq.logits.append(row.copy())
        if seq.logprobs is not None:
            seq.logprobs.append(logp)
        if seq.t_last is not None:
            self._m_inter_token.observe(now - seq.t_last)
        else:
            # first token of the sequence: TTFT plus where it went
            # (queue wait before admission vs compute after)
            seq.t_first = now
            self._m_ttft.observe(now - seq.t_enqueued)
            self._m_compute.observe(now - (seq.t_admitted
                                           if seq.t_admitted is not None
                                           else seq.t_enqueued))
        seq.t_last = now
        self._m["generated_tokens"].inc()
        if seq.stream and seq.reply_to is not None:
            replies.append((seq.reply_to, {
                "ok": True, "partial": True, "req_id": seq.req_id,
                "replica_id": self.replica_id, "token": int(token),
                "i": len(seq.tokens) - 1, "trace_id": seq.trace_id}))

    # -- page bookkeeping ------------------------------------------------------

    def _page_writable(self, seq: GenSeq, idx: int) -> bool:
        """Make page slot ``idx`` of the request's table privately
        writable: allocate at the boundary, copy-on-write a shared
        (refcount > 1) page.  False -> allocation pressure; the caller
        stalls that row one tick (its claimed pages are kept and the
        row retries next round)."""
        if idx == len(seq.pages):
            page = self.gen.alloc_page()
            if page is None:
                return False
            seq.pages.append(page)
            return True
        page = seq.pages[idx]
        if self.gen.page_ref[page] > 1:
            fresh = self.gen.alloc_page()
            if fresh is None:
                return False
            self.gen.copy_page(page, fresh)
            self.gen.decref(page)
            seq.pages[idx] = fresh
            self._m["cow_copies"].inc()
        return True

    def _ensure_chunk(self, seq: GenSeq) -> bool:
        """Make every page the next prefill chunk writes writable."""
        ps = self.gen.page_size
        t0 = seq.prefilled
        end = min(t0 + self.gen.prefill_chunk, seq.prompt_len)
        for idx in range(t0 // ps, -(-end // ps)):
            if not self._page_writable(seq, idx):
                return False
        return True

    # -- fetch policy ----------------------------------------------------------

    def _fetch(self, chunk, out):
        """Device->host transfer for one dispatch, per the
        ``on_device_sampling`` knob: tokens (+ logprobs on request) on
        the device path, full logits on the host path or when a row
        asked for them.  ``fetch_bytes`` counts the PADDED transfer —
        the wire cost, which is what the sampling fusion shrinks.
        Returns host ``(tokens, logps, logits)`` sliced to real rows
        (None where not fetched)."""
        import numpy as np

        tok_dev, logp_dev, logits_dev, _ = out
        n = len(chunk)
        need_logits = ((not self.on_device)
                       or any(s.return_logits for s in chunk))
        need_logp = (self.on_device
                     and any(s.return_logprobs for s in chunk))
        toks = logps = logits = None
        if self.on_device:
            full = np.asarray(tok_dev)
            self._m["fetch_bytes"].inc(int(full.nbytes))
            toks = full[:n]
        if need_logp:
            full = np.asarray(logp_dev)
            self._m["fetch_bytes"].inc(int(full.nbytes))
            logps = full[:n]
        if need_logits:
            full = np.asarray(logits_dev)
            self._m["fetch_bytes"].inc(int(full.nbytes))
            logits = full[:n]
        return toks, logps, logits

    def _emit_row(self, seq: GenSeq, i: int, fetched, now: float,
                  replies) -> None:
        """Emit one row of a fetched dispatch (sample on host if the
        device tokens weren't shipped)."""
        toks, logps, logits = fetched
        row = None if logits is None else logits[i]
        token = int(toks[i]) if toks is not None else seq.sample(row)
        logp = None
        if seq.return_logprobs:
            logp = (float(logps[i]) if logps is not None
                    else _host_logp(row, token))
        self._emit(seq, token, row, logp, now, replies)

    def step(self):
        """One scheduling round (class docstring).  Returns ``(worked,
        replies)``: whether any compute dispatched, and the
        ``(reply_to, payload)`` pairs to ship."""
        import numpy as np

        replies: List = []
        worked = False
        now = time.perf_counter()
        # 1. deadlines — pending first (never prefill doomed work)
        with self._lock:
            doomed_p = [s for s in self._pending
                        if s.t_deadline is not None and now > s.t_deadline]
            for s in doomed_p:
                self._pending.remove(s)
            doomed_a = [s for s in self._active
                        if s.t_deadline is not None and now > s.t_deadline]
        for s in doomed_p + doomed_a:
            self._expire(s, replies)
        # 2. admission into the concurrency bound; the prefix lookup
        # claims shared full pages (refcounted, read-only) so a hit
        # request starts with only its tail to prefill
        admitted: List[GenSeq] = []
        with self._lock:
            while (self._pending
                   and len(self._active) + len(admitted) < self.gen.slots):
                admitted.append(self._pending.popleft())
            self._active.extend(admitted)
        for seq in admitted:
            seq.t_admitted = now
            self._m_queue_wait.observe(now - seq.t_enqueued)
            if self.gen.prefix is not None:
                pages, covered = self.gen.prefix.lookup(seq.prompt)
                seq.pages = pages
                # full coverage still recomputes the LAST prompt token
                # (a 1-token chunk) — the sampled continuation needs
                # that position's logits, and the write (not the
                # content) is what diverges: it COWs the shared page
                seq.prefilled = min(covered, seq.prompt_len - 1)
        # 3. one decode tick over fully-prefilled sequences, grouped by
        # page-table rung — DISPATCHED, not yet fetched
        chunks = []
        stalled = 0             # rows page-pressure held back this round
        if self._active and now >= self._next_tick:
            groups: Dict[int, List[GenSeq]] = {}
            ticked = False
            for seq in sorted([s for s in self._active
                               if s.prefilled >= s.prompt_len],
                              key=lambda s: s.order):
                ticked = True
                if seq.t >= self.gen.max_ctx:
                    self._final(seq, replies, truncated="context window "
                                "exhausted", counter="gen_truncated")
                    continue
                if not self._page_writable(seq, seq.t
                                           // self.gen.page_size):
                    stalled += 1
                    continue            # page pressure: stall a tick
                groups.setdefault(
                    self.gen._page_rung(max(len(seq.pages), 1)),
                    []).append(seq)
            # dispatch EVERY chunk of the tick before fetching any:
            # chunk N's device compute overlaps chunk N-1's host-side
            # emit and reply shipping (decode_async contract)
            chunk_max = self.gen.decode_rungs[-1]
            for rung in sorted(groups):
                grp = groups[rung]
                for lo in range(0, len(grp), chunk_max):
                    chunk = grp[lo:lo + chunk_max]
                    out = self.gen.decode_async(
                        [s.pages for s in chunk],
                        [s.tokens[-1] for s in chunk],
                        [s.t for s in chunk],
                        [s.temperature for s in chunk],
                        [s.top_k for s in chunk],
                        [s.seed_val for s in chunk])
                    chunks.append((chunk, out))
                    self._m["decode_batches"].inc()
                    self._m["decode_tokens"].inc(len(chunk))
                    worked = True
            if ticked and self.decode_tick_s > 0:
                self._next_tick = now + self.decode_tick_s
        # 4. ONE prefill chunk batch: up to a prefill rung of
        # still-prefilling sequences advance by one bounded chunk.
        # Dispatched BETWEEN the decode dispatches and their fetches —
        # prompt compute overlaps this tick's decode emit.
        batch: List[GenSeq] = []
        for seq in sorted([s for s in self._active
                           if s.prefilled < s.prompt_len],
                          key=lambda s: s.order):
            if len(batch) >= self.gen.prefill_rungs[-1]:
                break
            if self._ensure_chunk(seq):
                batch.append(seq)
            else:
                stalled += 1
        pf = None
        t0s: List[int] = []
        nn: List[int] = []
        if batch:
            c = self.gen.prefill_chunk
            x = np.zeros((len(batch), c), self.gen.runner.dtype)
            for i, seq in enumerate(batch):
                t0 = seq.prefilled
                n_new = min(c, seq.prompt_len - t0)
                x[i, :n_new] = seq.prompt[t0:t0 + n_new]
                t0s.append(t0)
                nn.append(n_new)
            pf = self.gen.prefill_async(
                x, t0s, nn, [s.pages for s in batch],
                [s.temperature for s in batch],
                [s.top_k for s in batch],
                [s.seed_val for s in batch])
            self._m["prefill_batches"].inc()
            self._m["prefill_tokens"].inc(sum(nn))
            worked = True
        # fetch + emit: decode chunks first (oldest dispatches), then
        # the prefill batch's completions
        for chunk, out in chunks:
            fetched = self._fetch(chunk, out)
            t_emit = time.perf_counter()
            if self._tracer.enabled:
                self._tracer.add(
                    "generate", "decode_tick", now, t_emit - now,
                    {"trace_id": chunk[0].trace_id, "rows": len(chunk)})
            for i, seq in enumerate(chunk):
                seq.t += 1
                seq.gen = out[3]
                self._emit_row(seq, i, fetched, t_emit, replies)
                if len(seq.tokens) >= seq.max_new:
                    self._final(seq, replies)
        if pf is not None:
            fetched = self._fetch(batch, pf)
            t_emit = time.perf_counter()
            if self._tracer.enabled:
                self._tracer.add(
                    "generate", "prefill_chunk", now, t_emit - now,
                    {"trace_id": batch[0].trace_id, "rows": len(batch),
                     "tokens": sum(nn)})
            for i, seq in enumerate(batch):
                seq.prefilled = t0s[i] + nn[i]
                if seq.prefilled < seq.prompt_len:
                    continue        # mid-prompt chunk: sample discarded
                seq.t = seq.prompt_len
                seq.gen = pf[3]
                if self.gen.prefix is not None:
                    self.gen.prefix.register(seq.prompt, seq.pages)
                self._m["prefill_seqs"].inc()
                self._emit_row(seq, i, fetched, t_emit, replies)
                if len(seq.tokens) >= seq.max_new:
                    self._final(seq, replies)
        self._note_page_pressure(stalled, now)
        return worked, replies

    def _note_page_pressure(self, stalled: int, now: float) -> None:
        """Journal the page-pressure TRANSITION: the first round where
        allocation held rows back after a clean round emits ONE event
        with the load numbers; subsequent stalled rounds of the same
        episode stay silent (the latch resets on a clean round)."""
        if stalled and not self._page_pressure:
            from znicz_tpu import telemetry
            telemetry.emit(
                "page_shed", "serving", reason="page_pressure",
                replica=self.replica_id, stalled_rows=stalled,
                kv_occupancy=round(self.gen.occupancy(), 4),
                active=len(self._active))
        self._page_pressure = bool(stalled)

    def drain(self) -> List:
        """Abandon every queued/live generation (service shutdown):
        readable ``draining`` replies for all, pages released."""
        replies: List = []
        with self._lock:
            pending = list(self._pending)
            self._pending.clear()
            active = list(self._active)
        for seq in pending + active:
            self._release(seq)
            self._retire(seq)
            self._m["gen_refused"].inc()
            replies.append((seq.reply_to, {
                "ok": False, "rejected": True, "req_id": seq.req_id,
                "replica_id": self.replica_id, "policy": "draining",
                "trace_id": seq.trace_id,
                "error": "service is shutting down — generation "
                         "abandoned"}))
        return replies

    # -- stats -----------------------------------------------------------------

    def inter_token_quantiles(self) -> Dict[str, Optional[float]]:
        import numpy as np

        w = self._m_inter_token.window()
        if w.size == 0:
            return {"inter_token_p50_ms": None, "inter_token_p99_ms": None}
        return {"inter_token_p50_ms":
                round(float(np.percentile(w, 50)) * 1e3, 3),
                "inter_token_p99_ms":
                round(float(np.percentile(w, 99)) * 1e3, 3)}

    def ttft_quantiles(self) -> Dict[str, Optional[float]]:
        """TTFT and its queue-wait/compute split, p50/p99 in ms (None
        on an empty window) — the web panel's generation row."""
        import numpy as np

        out: Dict[str, Optional[float]] = {}
        for key, hist in (("ttft", self._m_ttft),
                          ("queue_wait", self._m_queue_wait),
                          ("compute", self._m_compute)):
            w = hist.window()
            for q in (50, 99):
                out[f"{key}_p{q}_ms"] = (
                    None if w.size == 0
                    else round(float(np.percentile(w, q)) * 1e3, 3))
        return out

    def stats(self) -> Dict:
        with self._lock:
            pending = len(self._pending)
            active = len(self._active)
        out = {"pending": pending, "active": active,
               "max_new_tokens": self.max_new_cap,
               "pending_bound": self.pending_bound,
               "decode_tick_ms": self.decode_tick_s * 1e3,
               "on_device_sampling": self.on_device}
        out.update({name: self._m[name].value for name in self.COUNTERS})
        out.update(self.inter_token_quantiles())
        out.update(self.ttft_quantiles())
        out.update({k: v for k, v in self.gen.stats().items()
                    if k != "jit_cache_size"})
        return out




# historical counter attributes, generated from COUNTERS (name + HELP
# defined exactly once)
for _name, _help in DynamicBatcher.COUNTERS.items():
    setattr(DynamicBatcher, _name, registered_property(_name, _help))
for _name, _help in GenerationScheduler.COUNTERS.items():
    setattr(GenerationScheduler, _name, registered_property(_name, _help))
del _name, _help
