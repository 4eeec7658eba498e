"""Trace spans (ISSUE 5 tentpole, part 2): a bounded in-memory ring of
begin/end events with explicit timestamps, exportable as Chrome
trace-event JSON (load ``/trace.json`` in Perfetto or
``chrome://tracing``).

Span sites: unit ``run()`` (core/workflow.py); the fused trainer's loop
(parallel/fused.py, ``cat == "train"``: ``advance``, ``stage``,
``dispatch:<kind>``, ``flush``, ``eval``, ``tail`` with its leaves
``tail_eval`` / ``sync`` / ``decide`` / ``tail_update``, and
``epoch_hook`` with ``snapshot_copy``; PERF.md section 3 has the table of
what reads each); device staging (loader/ingest.py); wire codec
encode/decode (parallel/wire.py); relay and master REP handling
(parallel/relay.py, server.py); serving batch assemble / compute / reply,
generation and the balancer (serving/); and snapshot writes
(snapshotter.py).  Cross-process correlation rides the ``trace_id`` /
``job_id`` keys the wire-v3 metadata frames carry end-to-end (optional
dict keys — old peers decode fine): two processes' trace files can be
joined on ``args.trace_id``.

One primitive, two clocks' worth of readers: ``TraceRing.span()`` records
name, start, end, its own ``id`` and the ``parent`` span that caused it
(the span this thread was inside; 0 for none) into the ring on
``perf_counter``, and — while the ring is enabled and ``jax`` is already
imported — enters ``jax.profiler.TraceAnnotation("znicz:<cat>:<name>")``
around the same body (a ``StepTraceAnnotation`` when the site passes
``step=``), so a profiler session (the launcher's ``--profile-dir``, the
benchmark's traced run) holds the program's spans on the profiler's own
clock with nothing to arm.  ``add()`` stays for sites that reuse a timing
they already took; such events carry no id and no annotation.

Cost discipline: recording one span is two ``perf_counter()`` reads, one
thread-local swap, one annotation object and one deque append (the
deque's ``maxlen`` gives the bounded ring for free — appends past
capacity evict the oldest event without locking).  When the ring is
disabled, ``span()`` returns a shared no-op context manager, so
instrumented hot paths pay one attribute check and emit no annotation.
What the layer costs on the chip is measured by the benchmark's cells
with ``root.common.telemetry.enabled`` on and off (PERF.md section 6).
"""

from __future__ import annotations

import collections
import itertools
import os
import sys
import threading
import time
from typing import Dict, List, Optional

#: default ring capacity (events); override per-TraceRing, or via
#: root.common.telemetry.trace_capacity for the process-wide ring —
#: which is created lazily on first use, so set the override any time
#: BEFORE the first telemetry consumer (Codec/Server/trainer/...) is
#: constructed (importing telemetry alone does not latch it)
DEFAULT_CAPACITY = 16384

#: what a span is called in the profiler's trace: ``znicz:<cat>:<name>``
ANNOTATION_PREFIX = "znicz:"


class _NullSpan:
    """Shared do-nothing context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """One live span: on entry it becomes its thread's current span (so
    spans opened inside it name it as their ``parent``) and enters its
    profiler annotation; on exit it restores the outer span and records
    itself."""

    __slots__ = ("_ring", "cat", "name", "args", "id", "_outer",
                 "_annotation", "_t0")

    def __init__(self, ring: "TraceRing", cat: str, name: str, args,
                 annotation):
        self._ring = ring
        self.cat = cat
        self.name = name
        self.args = args
        self.id = next(ring._ids)
        self._annotation = annotation

    def __enter__(self):
        local = self._ring._local
        self._outer = getattr(local, "span", None)
        local.span = self
        if self._annotation is not None:
            self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._ring._local.span = self._outer
        self.args["id"] = self.id
        self.args["parent"] = self._outer.id if self._outer is not None else 0
        self._ring.add(self.cat, self.name, self._t0, dur, self.args)
        return False


class TraceRing:
    """Bounded ring of complete ("X") trace events.

    Events are stored as plain tuples ``(cat, name, ts_us, dur_us, tid,
    args)``; the Chrome trace-event dicts are built only at export.
    ``deque.append`` is atomic under the GIL, so the EVENT path takes no
    lock; ``events()`` snapshots via ``list(deque)`` for the same
    reason — export never blocks recording.  The lifetime ``recorded``
    counter is the one piece that needs read-modify-write, so it rides
    its own micro-lock (spans arrive concurrently from the training,
    router, compute and snapshot-writer threads; a bare ``+=`` would
    silently drop increments).
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 enabled: bool = True):
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._events: collections.deque = collections.deque(
            maxlen=self.capacity)
        self.recorded = 0               # lifetime count (ring may evict)
        self._count_lock = threading.Lock()
        self._sinks: List = []          # fleet span exporters (ISSUE 20)
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._local = threading.local()     # .span: this thread's current

    def add_sink(self, sink) -> None:
        """Register a callable fed every recorded event tuple (the fleet
        ``SpanExporter``).  Sinks must be non-blocking and non-raising;
        the empty-list check keeps the no-sink hot path at one ``if``."""
        self._sinks.append(sink)

    # -- recording -------------------------------------------------------------

    def span(self, cat: str, name: str, step: Optional[int] = None,
             **args):
        """Context manager recording one complete event around its body;
        a no-op singleton while disabled.  The event's ``args`` gain the
        span's ``id`` and its ``parent`` (the span this thread was inside
        when it opened, 0 for none).  ``step`` is the train step the
        span's work starts at: kept in ``args`` as ``step0``, and it makes
        the profiler annotation a ``StepTraceAnnotation``."""
        if not self.enabled:
            return NULL_SPAN
        if step is not None:
            args["step0"] = int(step)
        annotation = None
        jax = sys.modules.get("jax")
        if jax is not None:
            # never imports jax: a process that has not touched it (a
            # balancer, a client) must not start holding a chip for a span
            label = f"{ANNOTATION_PREFIX}{cat}:{name}"
            annotation = (jax.profiler.TraceAnnotation(label)
                          if step is None else
                          jax.profiler.StepTraceAnnotation(
                              label, step_num=int(step)))
        return _Span(self, cat, name, args, annotation)

    def add(self, cat: str, name: str, t0_s: float, dur_s: float,
            args: Optional[Dict] = None) -> None:
        """Record a complete event from an ALREADY-MEASURED interval
        (perf_counter seconds) — the workflow unit loop reuses its own
        timing instead of paying a second pair of clock reads."""
        if not self.enabled:
            return
        evt = (cat, name, int(t0_s * 1e6), max(int(dur_s * 1e6), 0),
               threading.get_ident(), args)
        self._events.append(evt)
        with self._count_lock:
            self.recorded += 1
        if self._sinks:
            for sink in self._sinks:
                sink(evt)

    def instant(self, cat: str, name: str, **args) -> None:
        """Zero-duration marker event."""
        self.add(cat, name, time.perf_counter(), 0.0, args or None)

    # -- export ----------------------------------------------------------------

    def events(self) -> List[tuple]:
        return list(self._events)

    def clear(self) -> None:
        self._events.clear()

    def chrome_trace(self) -> Dict:
        """The ring as a Chrome trace-event JSON object (Perfetto /
        chrome://tracing load it directly).  Snapshot-then-build: the
        caller can serialize and write the result with no ring state
        shared with recorders."""
        pid = os.getpid()
        out = []
        for cat, name, ts, dur, tid, args in self.events():
            ev = {"name": name, "cat": cat, "ph": "X", "ts": ts,
                  "dur": dur, "pid": pid, "tid": tid}
            if args:
                ev["args"] = args
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms"}
