"""Host ingest engine: parallel image decode + bounded prefetch (VERDICT r4
item 1 — the file-fed north star was serial-PIL host-decode-bound).

The chip consumes ~11.4k img/s (bench headline); one PIL decode+resize on
the staging thread delivers a few hundred.  The reference's file-image
loaders (SURVEY.md §2.1 image-loaders row) existed precisely to feed
accelerators from disk at training rate, so the rebuild gets a real ingest
engine:

  - ``DecodePool``: an N-worker decode pool.  PIL's JPEG/PNG decode and
    resize release the GIL inside libjpeg/zlib, so threads scale to real
    multiples of the serial rate without shipping arrays across process
    boundaries (a process pool would pay a pickle+pipe copy per row).
  - A **bounded prefetch cache**: ``submit(indices)`` starts decode
    futures for rows a FUTURE segment will need; ``take(indices)`` serves
    the current segment — cache hits consume the already-running future,
    misses decode in the pool right then (still parallel).  Entries pop
    on consumption, and ``max_outstanding_rows`` caps memory, so the
    cache is a queue, not a leak.
  - The fused driver (``FusedTrainer._run_segmented``) keeps a lookahead
    fifo of advanced-but-unprocessed minibatches and submits their rows
    as soon as the indices are known — segment N+1's (and N+2's) decode
    overlaps segment N's device compute.  In a multi-controller run only
    the rows of batch shards this process's devices hold are submitted
    (the gather-own-rows-only property of ``_stage_direct`` extends to
    the prefetcher).

Decode is deterministic, so pooled results are BIT-IDENTICAL to serial
decode regardless of worker count or arrival order (tests/test_ingest.py).

Steady-state throughput becomes the three-term roofline

    img/s = min(compute rate, link_bw / bytes_per_sample, decode rate)

(``measure_decode_rate`` below measures the decode term).
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, Optional

import numpy as np

#: default cap on cached + in-flight prefetched rows.  227x227x3 u8 rows
#: are ~151 KB, so 8192 rows bound the cache at ~1.2 GB — a few staged
#: segments' worth at bench shapes, far below host RAM.
DEFAULT_MAX_OUTSTANDING_ROWS = 8192


def default_workers() -> int:
    """Worker count when neither the source nor the config pins one:
    ``root.common.engine.decode_workers`` wins, else one thread per CPU
    (capped — decode threads beyond ~16 fight the staging thread for
    memory bandwidth before they add decode rate)."""
    from znicz_tpu.core.config import root

    cfg = root.common.engine.get("decode_workers", None)
    if cfg is not None:
        return int(cfg)
    return min(os.cpu_count() or 1, 16)


class DecodePool:
    """N-worker decode pool with a bounded prefetch cache.

    ``decode_row(i) -> np.ndarray`` decodes ONE row by global index; it
    must be pure (same i -> same bytes) — that is what makes pooled
    ingest bit-identical to serial decode.

    Threading contract (ISSUE 7): ``submit`` runs on the training
    thread (the fused driver's lookahead) while ``take`` may run on the
    ``DeviceStager`` worker — the futures dict is guarded by a lock;
    workers only ever run ``decode_row``.
    """

    def __init__(self, decode_row: Callable[[int], np.ndarray],
                 workers: Optional[int] = None,
                 max_outstanding_rows: int = DEFAULT_MAX_OUTSTANDING_ROWS):
        import threading

        self._decode_row = decode_row
        self._workers = workers
        self._ex = None
        self._futures: Dict[int, object] = {}
        self._lock = threading.Lock()
        self.max_outstanding_rows = int(max_outstanding_rows)
        #: prefetch_hits: take() rows served by an already-submitted
        #: future (the queue was non-empty when the segment arrived);
        #: decode_misses: rows the segment had to decode on demand
        self.stats = {"prefetch_hits": 0, "decode_misses": 0,
                      "rows_decoded": 0, "rows_prefetched": 0}

    @property
    def workers(self) -> int:
        if self._workers is None:
            self._workers = default_workers()
        return max(1, int(self._workers))

    def _executor(self):
        if self._ex is None:
            from concurrent.futures import ThreadPoolExecutor

            self._ex = ThreadPoolExecutor(
                self.workers, thread_name_prefix="znicz-decode")
        return self._ex

    def submit(self, indices) -> int:
        """Start decode futures for rows a future take() will consume.
        Already-cached rows are skipped; past ``max_outstanding_rows``
        the rest of the batch is dropped (the later take() decodes them
        on demand — prefetch is an optimization, never a requirement).
        Returns the number of rows newly submitted."""
        ex = self._executor()
        n = 0
        with self._lock:
            for i in np.unique(np.asarray(indices)):
                i = int(i)
                if i in self._futures:
                    continue
                if len(self._futures) >= self.max_outstanding_rows:
                    break
                self._futures[i] = ex.submit(self._decode_row, i)
                n += 1
            self.stats["rows_prefetched"] += n
        return n

    def take(self, indices) -> np.ndarray:
        """Rows for ``indices``, in order (duplicates allowed — padded
        tail minibatches repeat their last index).  Prefetched rows are
        consumed from the cache; the rest decode across the pool now."""
        ex = self._executor()
        local: Dict[int, object] = {}
        futs = []
        with self._lock:
            for i in np.asarray(indices).reshape(-1):
                i = int(i)
                f = local.get(i)
                if f is None:
                    f = self._futures.pop(i, None)
                    if f is None:
                        self.stats["decode_misses"] += 1
                        f = ex.submit(self._decode_row, i)
                    else:
                        self.stats["prefetch_hits"] += 1
                    local[i] = f
                futs.append(f)
            self.stats["rows_decoded"] += len(futs)
        rows = [f.result() for f in futs]
        return np.stack(rows)

    @property
    def outstanding_rows(self) -> int:
        with self._lock:
            return len(self._futures)

    def close(self) -> None:
        if self._ex is not None:
            self._ex.shutdown(wait=False, cancel_futures=True)
            self._ex = None
        with self._lock:
            self._futures.clear()


class DeviceStager:
    """Async double-buffered device staging (ISSUE 7): background
    workers (one per buffer) run ``assemble(idx_rows) -> staged device
    tensors`` — host gather (decode-pool take), ``np.stack``, and the
    async ``device_put`` — for upcoming segments WHILE the current one
    computes, so the training thread's per-segment staging cost
    collapses to a dictionary pop.  With donation on (TPU — the trainer donates staged
    buffers into the scan) at most two staged segments exist at any
    moment: the one the device is consuming and the one being put — the
    serving layer's donated ping-pong pair, now feeding training.

    ``submit(idx_rows)`` starts staging a PREDICTED future segment
    (bounded at ``depth`` outstanding; extra submits are dropped —
    staging ahead is an optimization, never a requirement).  ``take(
    idx_rows)`` serves the segment about to be dispatched: a key match
    consumes the in-flight future (``stage_hits``; the blocking time is
    the ``ingest_wait_ms`` histogram — the number the overlap gate
    bounds), anything else assembles inline on the calling thread
    (``stage_misses``).

    Keys are the exact stacked index rows, so a mispredicted segment
    (decision completed early, scan boundary moved) can never serve
    wrong data — it is simply dropped and the real one assembled
    inline.  Assembly is pure data work (gather + put — no RNG, no
    loader state), so concurrent assemblies cannot reorder anything
    observable; ``close`` drops pending work without waiting."""

    def __init__(self, assemble: Callable[[np.ndarray], tuple],
                 depth: int = 2):
        from znicz_tpu import telemetry

        self._assemble = assemble
        self.depth = max(1, int(depth))
        self._ex = None
        self._pending: Dict[bytes, object] = {}   # key -> Future
        self._stale: set = set()    # pending keys marked at the last miss
        _sc = telemetry.scope("ingest")
        self._tracer = telemetry.tracer()
        #: the training thread's blocking time per take() — the overlap
        #: check's subject (tests/test_ingest.py::test_ingest_overlap_gate):
        #: with the double buffer absorbing an injected decode delay this
        #: stays well under it
        self._m_wait_ms = _sc.histogram(
            "ingest_wait_ms", "training-thread wait per staged segment "
            "(ms); the ingest overlap check bounds this", size=2048)
        #: worker-side assemble+put time (host gather through device_put
        #: dispatch) — where a decode/link stall actually shows up
        self._m_h2d_ms = _sc.histogram(
            "h2d_copy_ms", "host gather + device_put dispatch per staged "
            "segment (ms), measured on the stager worker", size=2048)
        self._m_occupancy = _sc.gauge(
            "staging_occupancy", "staged segments in flight or ready "
            "(ping-pong bound: depth)")
        self._m_hits = _sc.counter(
            "stage_hits", "take() segments served by a background-staged "
            "future")
        self._m_misses = _sc.counter(
            "stage_misses", "take() segments assembled inline (not "
            "predicted, or capacity-dropped)")
        self._m_evictions = _sc.counter(
            "stage_evictions", "pending predictions dropped on a take() "
            "miss (stale — their slot and buffers are reclaimed)")

    @staticmethod
    def key_of(idx_rows) -> bytes:
        """Hashable identity of a segment: the exact stacked index rows
        (small int32 matrices — hashing is microseconds)."""
        mat = np.stack([np.asarray(r, np.int32) for r in idx_rows])
        return mat.shape[0].to_bytes(4, "little") + mat.tobytes()

    def _executor(self):
        if self._ex is None:
            from concurrent.futures import ThreadPoolExecutor

            # one worker PER buffer: the dispatch loop runs ahead of
            # device compute, so adjacent segments' assemblies must be
            # able to overlap each other, not just the compute
            self._ex = ThreadPoolExecutor(
                self.depth, thread_name_prefix="znicz-stage")
        return self._ex

    def _timed_assemble(self, idx_rows):
        t0 = time.perf_counter()
        out = self._assemble(idx_rows)
        dt = time.perf_counter() - t0
        self._m_h2d_ms.observe(dt * 1e3)
        if self._tracer.enabled:
            self._tracer.add("ingest", "stage", t0, dt,
                             {"steps": len(idx_rows)})
        return out

    def submit(self, idx_rows) -> bool:
        """Start staging a predicted segment; False when already pending
        or the ping-pong is full."""
        key = self.key_of(idx_rows)
        if key in self._pending or len(self._pending) >= self.depth:
            return False
        self._pending[key] = self._executor().submit(
            self._timed_assemble, list(idx_rows))
        self._m_occupancy.set(len(self._pending))
        return True

    def take(self, idx_rows):
        """The staged tensors for EXACTLY these index rows — from the
        in-flight future when predicted, assembled inline otherwise.  A
        pending prediction that survives from one miss to the NEXT miss
        is stale and gets evicted — a hot loop serves predictions within
        a take or two, so anything a full miss-to-miss interval old was
        predicted wrong and would otherwise pin its ping-pong slot (and
        staged device buffers) forever.  (Eviction must NOT fire on the
        first miss alone: the cold-start take legitimately misses while
        CORRECT predictions for the next groups sit pending.)"""
        key = self.key_of(idx_rows)
        fut = self._pending.pop(key, None)
        if fut is None:
            stale = self._stale & set(self._pending)
            for k in stale:
                del self._pending[k]
            if stale:
                self._m_evictions.inc(len(stale))
            self._stale = set(self._pending)
            self._m_occupancy.set(len(self._pending))
            self._m_misses.inc()
            return self._timed_assemble(idx_rows)
        self._stale.discard(key)
        self._m_occupancy.set(len(self._pending))
        self._m_hits.inc()
        t0 = time.perf_counter()
        out = fut.result()
        dt = time.perf_counter() - t0
        self._m_wait_ms.observe(dt * 1e3)
        if self._tracer.enabled:
            self._tracer.add("ingest", "wait", t0, dt,
                             {"steps": len(idx_rows)})
        return out

    @property
    def outstanding(self) -> int:
        return len(self._pending)

    def stats(self) -> Dict[str, float]:
        waits = self._m_wait_ms.window()
        return {"stage_hits": self._m_hits.value,
                "stage_misses": self._m_misses.value,
                "stage_evictions": self._m_evictions.value,
                "outstanding": len(self._pending),
                "wait_ms_p50": self._m_wait_ms.quantile(0.5),
                "wait_ms_max": (float(np.max(waits)) if len(waits)
                                else None),
                "wait_ms_window": [round(float(w), 3) for w in waits],
                "h2d_ms_p50": self._m_h2d_ms.quantile(0.5)}

    def close(self) -> None:
        if self._ex is not None:
            self._ex.shutdown(wait=False, cancel_futures=True)
            self._ex = None
        self._pending.clear()
        self._stale.clear()
        self._m_occupancy.set(0)


def measure_decode_rate(source, n: int = 256,
                        workers: Optional[int] = None) -> float:
    """Measured decode throughput (img/s) of a file-backed source — the
    roofline's third term.  Decodes ``n`` rows
    through the source's own gather path (pooled when the source has a
    pool, serial otherwise) and times it cold-cache-fair: the same rows
    are decoded twice and the SECOND pass is timed, so the OS page cache
    state matches steady training (epochs revisit files)."""
    n = min(int(n), len(source))
    idx = np.arange(n, dtype=np.int32)
    if workers is not None and hasattr(source, "with_workers"):
        source = source.with_workers(workers)
    source.gather(idx)                      # warm page cache + pool
    t0 = time.perf_counter()
    source.gather(idx)
    return n / max(time.perf_counter() - t0, 1e-9)
