"""znicz-lint (ISSUE 9): the checkers themselves cannot silently rot.

Every rule is exercised on fixture snippets — at least one known TRUE
POSITIVE (the checker fires) and one known TRUE NEGATIVE (it stays
quiet) each, including the lock-guarded-write negative, the
``.get(variable)`` dynamic-read negative, and the pragma/baseline
suppression paths.  The final test is the tier-1 gate: the whole
analyzer over ``znicz_tpu/`` must come back with ZERO unbaselined
findings, inside a lean wall-clock budget.

(The config-knob alias-resolution fixtures live with the historical
lint names in tests/test_no_adhoc_counters.py.)
"""

import json
import pathlib
import textwrap
import time

from znicz_tpu.analysis import (DEFAULT_BASELINE, Finding, Module, run)
from znicz_tpu.analysis.__main__ import main as cli_main
from znicz_tpu.analysis.config_knob import ConfigKnobChecker
from znicz_tpu.analysis.counters import CounterRegistryChecker
from znicz_tpu.analysis.jit_purity import JitPurityChecker
from znicz_tpu.analysis.threads import ThreadSharedStateChecker

PKG = pathlib.Path(__file__).resolve().parent.parent / "znicz_tpu"


def _module(code, rel="fixture.py"):
    return Module(pathlib.Path(rel), rel, textwrap.dedent(code))


def _check(checker, code, rel="fixture.py"):
    return list(checker.check(_module(code, rel)))


# -- thread-shared-state -------------------------------------------------------

_RACY = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.stats = {}

        def start(self):
            self._thread = threading.Thread(target=self._loop)
            self._thread.start()

        def _loop(self):
            self.stats["n"] = 1          # unlocked worker mutation

        def snapshot(self):
            return dict(self.stats)      # ... read on the caller thread
"""


def test_thread_shared_state_true_positive():
    found = _check(ThreadSharedStateChecker(), _RACY)
    assert len(found) == 1
    assert "Worker.stats" in found[0].message
    assert "_loop()" in found[0].message
    assert "snapshot()" in found[0].message


def test_thread_shared_state_emits_per_write_site():
    """One finding PER unlocked write site — a NEW mutation of an
    already-baselined attribute must be the N+1th identical finding
    (live under the baseline count cap), not deduped away."""
    found = _check(ThreadSharedStateChecker(), """
        import threading

        class W:
            def start(self):
                threading.Thread(target=self._loop).start()
            def _loop(self):
                self.accepted = 1
                self.accepted = 2
            def outcomes(self):
                return self.accepted
    """)
    assert len(found) == 2
    assert found[0].key == found[1].key          # same line-free key
    assert found[0].line != found[1].line


def test_thread_shared_state_lock_guarded_negative():
    """The same shape with the write under ``with self._lock`` is the
    canonical true negative."""
    found = _check(ThreadSharedStateChecker(), """
        import threading

        class Worker:
            def __init__(self):
                self._lock = threading.Lock()
                self.stats = {}

            def start(self):
                self._thread = threading.Thread(target=self._loop)
                self._thread.start()

            def _loop(self):
                with self._lock:
                    self.stats["n"] = 1

            def snapshot(self):
                with self._lock:
                    return dict(self.stats)
    """)
    assert not found, [f.message for f in found]


def test_thread_shared_state_more_negatives():
    # no thread spawned at all -> no worker, no findings
    assert not _check(ThreadSharedStateChecker(), """
        class Plain:
            def f(self):
                self.stats = {}
            def g(self):
                return self.stats
    """)
    # Event/Queue traffic is the thread-safe API, not shared raw state;
    # attrs only the worker touches are private to it
    assert not _check(ThreadSharedStateChecker(), """
        import threading, queue

        class Worker:
            def __init__(self):
                self._stop = threading.Event()
                self._q = queue.Queue()

            def start(self):
                threading.Thread(target=self._loop).start()

            def _loop(self):
                self._scratch = []            # worker-private
                self._scratch.append(1)
                while not self._stop.is_set():
                    self._q.put(1)

            def stop(self):
                self._stop.set()
                return self._q.get()
    """)
    # transitive: the helper called FROM the worker loop is worker code
    found = _check(ThreadSharedStateChecker(), """
        import threading

        class Worker:
            def start(self):
                threading.Thread(target=self._loop).start()
            def _loop(self):
                self._tick()
            def _tick(self):
                self.done_jobs = 1
            def progress(self):
                return self.done_jobs
    """)
    assert len(found) == 1 and "_tick()" in found[0].message


# -- jit-purity ----------------------------------------------------------------


def test_jit_purity_true_positives():
    found = _check(JitPurityChecker(), """
        import jax

        @jax.jit
        def step(x):
            print("stepping")         # side effect
            counters.inc()            # telemetry at trace time
            state.last = x            # attribute mutation
            return float(x) + x.item()   # two tracer leaks
    """)
    kinds = "\n".join(f.message for f in found)
    assert len(found) == 5, kinds
    assert "print()" in kinds and ".inc()" in kinds
    assert "attribute mutation" in kinds
    assert "float()" in kinds and ".item()" in kinds


def test_jit_purity_discovery_forms():
    """jit-by-assignment, defvjp-registered bwd, and pallas kernels are
    all discovered; the wrapper-shares-the-name shape is NOT swept in."""
    checker = JitPurityChecker()
    found = _check(checker, """
        import jax

        def f(x):
            print(x)
            return x
        g = jax.jit(f)
    """)
    assert len(found) == 1
    found = _check(checker, """
        import jax

        def bwd(res, ct):
            print(ct)
            return (ct,)
        h.defvjp(fwd, bwd)
    """)
    assert len(found) == 1
    found = _check(checker, """
        from jax.experimental import pallas as pl

        def kernel(x_ref, o_ref):
            print("in kernel")
            o_ref[...] = x_ref[...]

        def call(x):
            return pl.pallas_call(kernel, out_shape=None)(x)
    """)
    assert len(found) == 1
    # public wrapper named like the inner traced def (a memoised
    # custom-vjp factory): the int()/float() hyper normalization in the
    # WRAPPER is trace-free and must stay quiet
    assert not _check(checker, """
        import functools, jax

        def _make():
            @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
            def lrn(x, n):
                return x * n
            return lrn

        def lrn(x, n=5):
            return _make()(x, int(n))
    """)


def test_jit_purity_recompile_hazards():
    checker = JitPurityChecker()
    found = _check(checker, """
        import jax

        def f(x, shape):
            return x
        g = jax.jit(f, static_argnames=("shape",))
        y = g(x, shape=[1, 2])        # unhashable static -> TypeError
        z = g(x, f"{n}x{m}")          # f-string-derived static
    """)
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 2, msgs
    assert "unhashable list" in msgs and "f-string" in msgs
    # hashable statics at call sites are the true negative
    assert not _check(checker, """
        import jax

        def f(x, shape):
            return x
        g = jax.jit(f, static_argnames=("shape",))
        y = g(x, shape=(1, 2))
    """)


def test_jit_purity_true_negative_pure_fn():
    assert not _check(JitPurityChecker(), """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(params, x):
            y = jnp.dot(params, x)
            return y / jnp.float32(2)
    """)
    # impure code OUTSIDE any traced function is none of this rule's
    # business
    assert not _check(JitPurityChecker(), """
        def host_loop(x):
            print(x)
            return float(x)
    """)


# -- config-knob (alias fixtures live in test_no_adhoc_counters.py) ------------


def test_config_knob_scope_rules():
    """Class-body subtree bindings are NOT trackable locals (reads go
    through self.<name> from anywhere) — the binding itself is flagged
    as an escape; module-level aliases are visible inside functions
    defined textually ABOVE the assignment (defs run after the module
    body finishes)."""
    checker = ConfigKnobChecker(PKG)
    found = _check(checker, """
        from znicz_tpu.core.config import root
        class C:
            ADM = root.common.serving.admission
            def f(self):
                return self.ADM.get("rate_limi", 0)
    """)
    assert len(found) == 1
    assert "stored outside the local scope" in found[0].message
    found = _check(checker, """
        from znicz_tpu.core.config import root
        def f():
            return adm.get("rate_limi", 0)
        adm = root.common.serving.admission
    """)
    assert len(found) == 1 and "rate_limi" in found[0].message


def test_config_knob_fixture_pair():
    checker = ConfigKnobChecker(PKG)
    found = _check(checker, """
        from znicz_tpu.core.config import root
        a = root.common.engine.get("bogus", 1)
    """)
    assert len(found) == 1 and "bogus" in found[0].message
    assert not _check(checker, """
        from znicz_tpu.core.config import root
        a = root.common.engine.get("scan_chunk", 8)
        b = root.common.serving.get(name, DEFAULTS[name])   # dynamic
        c = root.mnistr.decision.max_epochs                 # other tree
    """)


# -- counter-registry ----------------------------------------------------------


def test_counter_registry_fixture_pair():
    checker = CounterRegistryChecker(allowlist=())
    found = _check(checker, """
        class S:
            def f(self):
                self.bad_frames += 1
    """)
    assert len(found) == 1
    assert not _check(checker, """
        class S:
            def f(self):
                self._pos += 1
                self.timestamp += dt     # no counter suffix
    """)
    # the telemetry registry implements itself
    assert not _check(checker, """
        class Counter:
            def inc(self):
                self.count += 1
    """, rel="telemetry/metrics.py")
    # allowlisted state with a justification stays quiet
    assert not _check(CounterRegistryChecker(
        allowlist={("kohonen.py", "total")}), """
        class K:
            def f(self):
                self.total += batch
    """, rel="kohonen.py")


# -- suppression paths ---------------------------------------------------------


def test_pragma_suppression(tmp_path):
    code = textwrap.dedent("""
        class S:
            def f(self):
                self.bad_frames += 1   # znicz: ignore[counter-registry]
                self.good_frames += 1
    """)
    (tmp_path / "mod.py").write_text(code)
    analysis = run(tmp_path, rules=["counter-registry"],
                   baseline_path=None)
    assert len(analysis.findings) == 1          # unpragma'd line stays
    assert "good_frames" in analysis.findings[0].message
    assert len(analysis.pragma_suppressed) == 1
    # pragma on the line ABOVE works too; the wrong rule name does not
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        class S:
            def f(self):
                # znicz: ignore[counter-registry]
                self.bad_frames += 1
                # znicz: ignore[thread-shared-state]
                self.good_frames += 1
    """))
    analysis = run(tmp_path, rules=["counter-registry"],
                   baseline_path=None)
    assert len(analysis.findings) == 1
    assert "good_frames" in analysis.findings[0].message


def test_baseline_suppression_and_count_cap(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        class S:
            def f(self):
                self.bad_frames += 1
            def g(self):
                self.bad_frames += 1
    """))
    analysis = run(tmp_path, rules=["counter-registry"],
                   baseline_path=None)
    assert len(analysis.findings) == 2
    entry = dict(analysis.findings[0].to_json(),
                 reason="fixture: accepted for the test")
    del entry["line"], entry["severity"]
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"entries": [entry]}))
    # count defaults to 1: one finding absorbed, the second stays LIVE
    analysis = run(tmp_path, rules=["counter-registry"],
                   baseline_path=baseline)
    assert len(analysis.findings) == 1
    assert len(analysis.baselined) == 1
    assert analysis.baselined[0][1] == "fixture: accepted for the test"
    # count=2 absorbs both; a stale entry (nothing matches) is reported
    baseline.write_text(json.dumps({"entries": [
        dict(entry, count=2),
        dict(entry, message="never matches anything", reason="stale")]}))
    analysis = run(tmp_path, rules=["counter-registry"],
                   baseline_path=baseline)
    assert not analysis.findings and len(analysis.baselined) == 2
    assert len(analysis.stale_baseline) == 1
    # a stale entry fails the gate: CI must not stay green behind a
    # dead entry a regression could crawl back through
    assert not analysis.clean
    assert "znicz-lint: clean" not in analysis.render_text()
    rc = cli_main([str(tmp_path), "--rules", "counter-registry",
                   "--baseline", str(baseline)])
    assert rc == 1


# -- the tier-1 gate -----------------------------------------------------------


def test_package_is_clean_under_the_analyzer():
    """THE gate (ISSUE 9 acceptance): zero unbaselined findings over
    znicz_tpu/, every baseline entry still matching something, inside a
    lean wall-clock budget (<15s; shows up in the conftest 10-slowest
    table if it ever grows)."""
    t0 = time.perf_counter()
    analysis = run(PKG)
    wall = time.perf_counter() - t0
    assert not analysis.parse_errors, \
        [f.render() for f in analysis.parse_errors]
    assert not analysis.findings, "unbaselined findings — fix them or " \
        "baseline with a justification (znicz_tpu/analysis/" \
        "baseline.json):\n  " + "\n  ".join(
            f.render() for f in analysis.findings)
    assert not analysis.stale_baseline, (
        "stale baseline entries (matched nothing — the finding was "
        "fixed or the message drifted): %r" % analysis.stale_baseline)
    assert analysis.baselined, "the committed baseline went empty — " \
        "if every finding is truly fixed, delete the entries AND this " \
        "assert together"
    assert wall < 15.0, f"analyzer self-run took {wall:.1f}s"


def test_cli_text_and_json(tmp_path, capsys):
    # the package gate through the real CLI entry point
    assert cli_main([]) == 0
    out = capsys.readouterr().out
    assert "znicz-lint: clean" in out
    # --json over a dirty fixture tree: exit 1 + machine-readable counts
    (tmp_path / "mod.py").write_text(
        "class S:\n    def f(self):\n        self.bad_frames += 1\n")
    rc = cli_main([str(tmp_path), "--json", "--baseline", "none",
                   "--rules", "counter-registry"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["clean"] is False
    assert data["counts"] == {"counter-registry": 1}
    assert data["findings"][0]["path"] == "mod.py"
    assert data["findings"][0]["line"] == 3
    # per-rule selection rejects unknown rules loudly
    try:
        cli_main(["--rules", "bogus-rule"])
    except SystemExit as exc:
        assert exc.code == 2
    else:  # pragma: no cover
        raise AssertionError("unknown rule accepted")


def test_default_baseline_is_the_committed_file():
    assert DEFAULT_BASELINE == PKG / "analysis" / "baseline.json"
    assert DEFAULT_BASELINE.exists()
    entries = json.loads(DEFAULT_BASELINE.read_text())["entries"]
    assert all(e.get("reason") for e in entries), \
        "every baseline entry needs its one-line justification"


def test_finding_render_and_key():
    f = Finding("r", "a/b.py", 7, "msg")
    assert f.render() == "a/b.py:7: r: msg"
    assert f.key == ("r", "a/b.py", "msg")
    assert f.to_json()["severity"] == "error"


# -- transport-core (ISSUE 14: the unified dataplane) --------------------------

_ZMQ_FORKED = """
    import zmq

    def serve(self):
        ctx = zmq.Context.instance()
        sock = ctx.socket(zmq.ROUTER)
        sock.bind("tcp://127.0.0.1:5555")
        poller = zmq.Poller()
        poller.register(sock, zmq.POLLIN)

    class S:
        def up(self):
            import zmq
            self._sock = zmq.Context.instance().socket(zmq.PULL)
            self._sock.bind("inproc://x")
"""

_ZMQ_RIDES_COMMON = """
    import zmq

    def serve(self):
        from znicz_tpu.network_common import bind_with_retry, make_poller

        ctx = zmq.Context.instance()
        sock = ctx.socket(zmq.ROUTER)
        bind_with_retry(sock, "tcp://127.0.0.1:5555")
        back = ctx.socket(zmq.DEALER)
        back.connect("tcp://127.0.0.1:5556")      # connect: no race
        poller = make_poller(sock, back)

    def not_a_socket(self):
        server = HTTPServer()
        server.bind(("127.0.0.1", 0))             # not a ZMQ socket
"""

_DISPATCH_FORKED = """
    import zmq

    def serve(self):
        from znicz_tpu.network_common import bind_with_retry, make_poller

        ctx = zmq.Context.instance()
        sock = ctx.socket(zmq.ROUTER)
        bind_with_retry(sock, "tcp://127.0.0.1:5555")
        poller = make_poller(sock)
        while True:
            if poller.poll(20):                  # hand-rolled dispatch
                sock.recv_multipart()
"""

_RECONNECT_FORKED = """
    import time
    import zmq

    def fetch(self, ctx):
        for attempt in range(8):
            sock = ctx.socket(zmq.REQ)           # fresh-socket retry
            try:
                sock.send(b"x")
                return sock.recv()
            except zmq.Again:
                time.sleep(0.25 * (2 ** attempt))  # raw backoff too
            finally:
                sock.close(0)
"""

_CLIENT_RIDES_CORE = """
    def fetch(self, endpoint):
        from znicz_tpu.transport import Endpoint, RetryPolicy

        ep = Endpoint(endpoint, retry=RetryPolicy.for_training_client())
        for attempt in range(8):
            try:
                return ep.rpc_message({"cmd": "job"})
            except Exception:
                ep.backoff(attempt + 1)

    def single_socket_wait(self):
        # .poll on a bare SOCKET is a wait, not a dispatch loop
        while self._sock.poll(20):
            self._sock.recv()

    def lifecycle(self, ctx):
        import zmq
        sock = ctx.socket(zmq.DEALER)            # created ONCE,
        try:                                     # closed once: not a
            sock.connect("tcp://127.0.0.1:1")    # reconnect cycle
        finally:
            sock.close(0)
"""


def test_transport_core_fixture_pairs():
    from znicz_tpu.analysis.transport_core import TransportCoreChecker

    findings = _check(TransportCoreChecker(), _ZMQ_FORKED)
    # two raw binds (name + self-attr receivers) and one raw Poller
    assert len(findings) == 3
    assert sum("Poller" in f.message for f in findings) == 1
    assert sum("bind_with_retry" in f.message for f in findings) == 2
    assert not _check(TransportCoreChecker(), _ZMQ_RIDES_COMMON)
    # network_common and the transport package itself are sanctioned
    assert not _check(TransportCoreChecker(), _ZMQ_FORKED,
                      rel="network_common.py")
    assert not _check(TransportCoreChecker(), _DISPATCH_FORKED,
                      rel="transport/core.py")


def test_transport_core_dispatch_and_reconnect():
    from znicz_tpu.analysis.transport_core import TransportCoreChecker

    dispatch = _check(TransportCoreChecker(), _DISPATCH_FORKED)
    assert sum("dispatch loop" in f.message for f in dispatch) == 1
    reconnect = _check(TransportCoreChecker(), _RECONNECT_FORKED)
    assert sum("reconnect cycle" in f.message for f in reconnect) == 1
    assert sum("backoff sleep" in f.message for f in reconnect) == 1
    assert not _check(TransportCoreChecker(), _CLIENT_RIDES_CORE)
