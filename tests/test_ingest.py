"""Host ingest engine (loader/ingest.py): parallel
decode must be BIT-IDENTICAL to serial decode, the prefetch cache must be
bounded and actually hit (the staging queue stays non-empty in steady
state), and the fused streaming run over an image-file source must train
the same trajectory with 8 workers as with 0."""

import os

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.config import root
from znicz_tpu.loader.ingest import (DecodePool, default_workers,
                                     measure_decode_rate)
from znicz_tpu.loader.streaming import StreamingLoader, class_dir_source

from tests.test_streaming import _write_class_tree


def _tree(tmp_path, n_per_class=8, size=(12, 12)):
    base = str(tmp_path / "imgs")
    os.makedirs(base)
    _write_class_tree(base, n_per_class=n_per_class, size=size)
    return base


def test_pooled_decode_matches_serial(tmp_path):
    """Same files, same indices (duplicates included — padded tails repeat
    their last index): 8 decode workers produce the exact bytes the serial
    path does, in the exact order."""
    base = _tree(tmp_path)
    serial = class_dir_source(base, target_shape=(10, 11), workers=0)
    pooled = class_dir_source(base, target_shape=(10, 11), workers=8)
    idx = np.array([3, 0, 7, 3, 3, 12, 1, 0], np.int32)
    np.testing.assert_array_equal(serial.gather(idx), pooled.gather(idx))
    # and again after prefetch seeded the cache
    pooled.prefetch(np.array([5, 6, 2], np.int32))
    idx2 = np.array([5, 2, 6, 5, 9], np.int32)
    np.testing.assert_array_equal(serial.gather(idx2), pooled.gather(idx2))


def test_decode_pool_cache_and_bounds():
    """DecodePool contract: prefetched rows are served as hits and popped
    on consumption; the outstanding-row cap bounds the cache; duplicate
    takes decode once."""
    calls = []

    def decode(i):
        calls.append(i)
        return np.full((2, 2), i, np.uint8)

    pool = DecodePool(decode, workers=2, max_outstanding_rows=4)
    assert pool.submit([0, 1, 2]) == 3
    assert pool.submit([2, 3, 4, 5]) == 1          # 2 dup-skipped; cap at 4
    assert pool.outstanding_rows == 4
    rows = pool.take([0, 1, 1, 1, 2, 3, 4])        # 4 was never submitted
    np.testing.assert_array_equal(rows[:, 0, 0],
                                  np.array([0, 1, 1, 1, 2, 3, 4]))
    st = pool.stats
    assert st["prefetch_hits"] == 4                # 0,1,2,3
    assert st["decode_misses"] == 1                # 4 (dups of 1 are free)
    assert pool.outstanding_rows == 0              # popped on consumption
    assert sorted(calls) == [0, 1, 2, 3, 4]        # each row decoded once
    pool.close()


def test_default_workers_config_override():
    try:
        root.common.engine.decode_workers = 3
        assert default_workers() == 3
    finally:
        root.common.engine.decode_workers = None
    assert default_workers() >= 1


def _build_stream_wf(src, max_epochs=2):
    from znicz_tpu.all2all import All2AllSoftmax
    from znicz_tpu.core.workflow import Repeater, Workflow
    from znicz_tpu.decision import DecisionGD
    from znicz_tpu.evaluator import EvaluatorSoftmax
    from znicz_tpu.gd import GDSoftmax

    class WF(Workflow):
        def __init__(self):
            super().__init__(name="IngestWF")
            self.repeater = Repeater(self, name="repeater")
            self.repeater.link_from(self.start_point)
            self.loader = StreamingLoader(
                self, name="loader", source=src, minibatch_size=4,
                class_lengths=[0, 4, 12], device_budget_bytes=0)
            self.loader.link_from(self.repeater)
            fwd = All2AllSoftmax(self, name="fwd0",
                                 output_sample_shape=(2,))
            fwd.link_from(self.loader)
            fwd.link_attrs(self.loader, ("input", "minibatch_data"))
            self.forwards = [fwd]
            self.evaluator = EvaluatorSoftmax(self, name="evaluator",
                                              n_classes=2)
            self.evaluator.link_from(fwd)
            self.evaluator.link_attrs(fwd, "output")
            self.evaluator.link_attrs(
                self.loader, ("labels", "minibatch_labels"),
                ("batch_size", "minibatch_size"))
            self.decision = DecisionGD(self, name="decision",
                                       max_epochs=max_epochs)
            self.decision.link_from(self.evaluator)
            self.decision.link_attrs(
                self.loader, "minibatch_class", "last_minibatch",
                "class_ended", "epoch_number", "class_lengths",
                "minibatch_size")
            self.decision.link_attrs(
                self.evaluator, ("minibatch_loss", "loss"),
                ("minibatch_n_err", "n_err"), "confusion_matrix",
                "max_err_output_sum")
            gd = GDSoftmax(self, name="gd0", forward=fwd,
                           learning_rate=0.05, need_err_input=False)
            gd.link_from(self.decision)
            gd.link_attrs(self.evaluator, ("err_output", "err_output"))
            gd.gate_skip = self.decision.gd_skip
            self.gds = [gd]
            self.repeater.link_from(gd)
            self.end_point.link_from(self.decision)
            self.end_point.gate_block = ~self.decision.complete

    wf = WF()
    wf.initialize(device=None)
    return wf


def _run_stream(base, workers, max_epochs=2):
    from znicz_tpu.parallel.fused import FusedTrainer

    prng.reset(4242)
    src = class_dir_source(base, target_shape=(12, 12), workers=workers)
    wf = _build_stream_wf(src, max_epochs=max_epochs)
    losses = []
    wf.decision.on_epoch_end.append(
        lambda d: losses.append(d.epoch_metrics[2]["loss"]))
    FusedTrainer(wf).run()
    weights = {f.name: np.array(f.weights.map_read())
               for f in wf.forwards}
    return losses, weights, wf.loader.ingest_stats


def test_fused_streaming_prefetch_parity_and_hits(tmp_path):
    """The e2e ingest proof (VERDICT r4 item 1 'done' criteria): a fused
    image-file streaming run with a decode pool (a) trains bit-for-bit the
    trajectory of the serial-decode run, and (b) keeps the staging queue
    non-empty — after the first segment every staged row is served by an
    already-submitted decode future (prefetch hit), not an on-demand miss."""
    base = _tree(tmp_path)
    l0, w0, st0 = _run_stream(base, workers=0)
    assert st0 is None                        # serial path has no pool
    l1, w1, st1 = _run_stream(base, workers=4)
    np.testing.assert_array_equal(l0, l1)
    for k in w0:
        np.testing.assert_array_equal(w0[k], w1[k])
    assert st1 is not None
    assert st1["prefetch_hits"] > 0
    # only the run's very first staged segment may miss (its minibatches
    # were advanced before any lookahead existed); with minibatch_size 4
    # that bounds misses at one padded eval batch — everything after is
    # fed from the prefetch queue at the training step rate
    assert st1["decode_misses"] <= 4, st1
    total = st1["prefetch_hits"] + st1["decode_misses"]
    assert st1["prefetch_hits"] >= total - 4


# -- async double-buffered device staging (ISSUE 7) ----------------------------


def test_device_stager_contract():
    """DeviceStager unit contract: a submitted key is served as a hit
    (result identity preserved), an unknown key assembles inline as a
    miss, a prediction still pending from one miss to the NEXT miss is
    stale and evicted (it would otherwise pin its ping-pong slot
    forever — but a single miss must not evict, or the cold-start take
    would throw away the correct predictions staged behind it), the
    ping-pong bound caps outstanding work, and close() clears pending."""
    import time

    from znicz_tpu.loader.ingest import DeviceStager

    calls = []

    def assemble(rows):
        calls.append(len(rows))
        time.sleep(0.01)
        return ("staged", DeviceStager.key_of(rows))

    st = DeviceStager(assemble, depth=2)
    a = [np.array([0, 1], np.int32)]
    b = [np.array([2, 3], np.int32), np.array([4, 5], np.int32)]
    c = [np.array([6, 7], np.int32)]
    assert st.submit(a) and st.submit(b)
    assert not st.submit(a)                      # dup-skipped
    assert not st.submit(c)                      # ping-pong full
    assert st.outstanding == 2
    out = st.take(a)                             # hit
    assert out == ("staged", DeviceStager.key_of(a))
    assert st.outstanding == 1
    out = st.take(c)                             # never staged: inline miss
    assert out == ("staged", DeviceStager.key_of(c))
    # first miss: b is only MARKED stale, not evicted (cold-start rule)
    assert st.outstanding == 1
    s = st.stats()
    assert s["stage_hits"] == 1 and s["stage_misses"] == 1
    assert s["stage_evictions"] == 0
    d = [np.array([8, 9], np.int32)]
    out = st.take(d)                             # second miss: b is stale
    assert out == ("staged", DeviceStager.key_of(d))
    assert st.outstanding == 0                   # ...evicted, slot freed
    s = st.stats()
    assert s["stage_misses"] == 2 and s["stage_evictions"] == 1
    assert len(calls) == 4                       # a, b, c, d each once
    assert st.submit(a)                          # the slot is usable again
    assert st.take(a) == ("staged", DeviceStager.key_of(a))
    st.close()
    assert st.outstanding == 0


#: The injected decode delay is calibrated to the measured warm segment
#: time (so the check is structural, not an absolute speed bet a shared
#: host can lose), clamped to [floor, cap]; the training thread's
#: staged-segment wait must stay under GATE_FRAC of the injected delay.
INGEST_DELAY_FLOOR_S = 0.02
INGEST_DELAY_CAP_S = 0.5
INGEST_GATE_FRAC = 0.5


def _build_ingest_workflow(delay_s, hidden, n_train, n_valid, mb,
                           max_epochs):
    """A host-staged streaming run (regime 3) whose decode path sleeps
    ``delay_s`` per segment gather — the injected stall the double buffer
    must absorb."""
    import time

    from znicz_tpu.core.mutable import Bool
    from znicz_tpu.loader.streaming import HostArraySource
    from znicz_tpu.standard_workflow import StandardWorkflow

    class DelayedSource(HostArraySource):
        """HostArraySource with a fixed sleep in the gather (decode)
        path — sleep, not spin: the injected stall must be absorbable by
        a thread that overlaps it, exactly like real PIL decode/IO."""

        delay_s = 0.0

        def gather(self, idx):
            if self.delay_s:
                time.sleep(self.delay_s)
            return super().gather(idx)

    prng.reset(1013)
    rng = np.random.default_rng(7)
    n = n_train + n_valid
    data = (rng.random((n, 28, 28)) * 255).astype(np.uint8)
    labels = rng.integers(0, 10, n).astype(np.int32)
    src = DelayedSource(data, labels)
    src.delay_s = float(delay_s)
    gd = {"learning_rate": 0.01, "gradient_moment": 0.9}
    layers = [
        {"type": "all2all_strict_relu",
         "->": {"output_sample_shape": hidden}, "<-": dict(gd)},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": dict(gd)},
    ]
    wf = StandardWorkflow(
        name="IngestOverlap",
        loader=StreamingLoader(name="loader", source=src,
                               minibatch_size=mb,
                               class_lengths=[0, n_valid, n_train],
                               device_budget_bytes=0),
        layers=layers, loss_function="softmax",
        decision_config={"max_epochs": max_epochs, "fail_iterations": 0})
    wf.initialize(device=None)
    wf.snapshotter.gate_skip = Bool(True)   # measure ingest, not IO
    return wf


def run_ingest_overlap(hidden, n_train, n_valid, mb, max_epochs):
    """Calibrate the warm segment time with no delay, inject half of it
    (clamped) into the decode path, and return the injected delay in ms
    and the stager's statistics of the delayed run: the double buffer
    absorbs the delay, so the training thread's per-segment staged wait
    must stay well under it even though EVERY segment's assembly slept
    that long on the stager's worker."""
    from znicz_tpu.parallel.fused import FusedTrainer

    tr = FusedTrainer(_build_ingest_workflow(0.0, hidden, n_train, n_valid,
                                             mb, max_epochs=1))
    tr.run()
    warm_steps = max(tr.stats["warm_steps"], 1)
    step_s = (tr.stats["warm_wall_s"] / warm_steps
              if tr.stats["warm_wall_s"] > 0
              else tr.stats["wall_s"] / max(tr.stats["train_steps"], 1))
    delay_s = min(max(0.5 * step_s * max(tr.scan_chunk, 1),
                      INGEST_DELAY_FLOOR_S), INGEST_DELAY_CAP_S)
    tr2 = FusedTrainer(_build_ingest_workflow(delay_s, hidden, n_train,
                                              n_valid, mb, max_epochs))
    tr2.run()
    assert tr2._stager is not None, "async staging did not engage"
    return delay_s * 1e3, tr2._stager.stats()


def check_ingest_overlap(delay_ms, st, max_epochs):
    """The structural findings for one overlap run (empty = it holds):

      - beyond the run's cold-start group, no dispatch group missed the
        double buffer;
      - the MEDIAN staged wait sits well under the injected delay — the
        hot loop (train segments following train segments) absorbed it;
      - waits near the delay are CONFINED to the per-epoch boundary
        groups: each epoch's first assembly cannot start before the tail
        is consumed (the lookahead must not advance past a tail — the
        snapshot at an epoch boundary must record tail state; resume
        parity), so one un-absorbed wait per epoch + the cold start is
        the structural floor, and MORE than that means the overlap broke.
    """
    bad = []
    if st["stage_hits"] < 1 or st["stage_misses"] > 1:
        bad.append(f"dispatch groups missed the double buffer: "
                   f"hits={st['stage_hits']} misses={st['stage_misses']}")
    p50 = st["wait_ms_p50"]
    if p50 is None or p50 > INGEST_GATE_FRAC * delay_ms:
        bad.append(f"median staged wait {p50}ms is not well under the "
                   f"injected {delay_ms}ms decode delay — the hot loop "
                   "is not absorbing it")
    big = [w for w in st["wait_ms_window"]
           if w > INGEST_GATE_FRAC * delay_ms]
    if len(big) > max_epochs + 1:
        bad.append(f"{len(big)} staged waits exceeded "
                   f"{INGEST_GATE_FRAC} x the delay ({big}) — more than "
                   f"the {max_epochs} epoch-boundary groups + cold "
                   "start; steady-state segments are stalling")
    return bad


@pytest.mark.parametrize("size", [
    dict(hidden=128, n_train=160, n_valid=32, mb=32, max_epochs=2),
    pytest.param(dict(hidden=2048, n_train=1024, n_valid=128, mb=64,
                      max_epochs=3), marks=pytest.mark.slow),
], ids=["lean", "soak"])
def test_ingest_overlap_gate(size):
    """The structural ingest/compute overlap check: a fixed delay
    injected into the decode path is absorbed by the double buffer — the
    training thread's staged-segment waits stay well under it except at
    the structurally-unhidable epoch boundaries (see
    ``check_ingest_overlap``)."""
    delay_ms, st = run_ingest_overlap(**size)
    bad = check_ingest_overlap(delay_ms, st, size["max_epochs"])
    assert not bad, (bad, delay_ms, st)
    # the injected delay really was paid by SOMEONE (the stager worker):
    # every staged segment's assembly slept it
    assert st["h2d_ms_p50"] >= delay_ms


def test_measure_decode_rate(tmp_path):
    """The roofline's third term is measured and finite, serial and
    pooled, and the pooled measurement is the pool's work: every row of
    both passes decoded exactly once, by one executor.  What this guards
    is the pool rebuilding per item or decoding rows twice; how much
    faster a pool is than a loop is the host's business (on a shared
    two-to-eight-core box it read 0.14x to 0.9x of serial within one
    day), so no ratio of the two rates is asserted."""
    base = _tree(tmp_path, n_per_class=16, size=(32, 32))
    src = class_dir_source(base, target_shape=(24, 24), workers=0)
    serial = measure_decode_rate(src, n=32)
    assert np.isfinite(serial) and serial > 0
    assert src.ingest_stats is None             # no pool was built
    pooled_src = src.with_workers(4)
    pooled = measure_decode_rate(pooled_src, n=32)
    assert np.isfinite(pooled) and pooled > 0
    executor = pooled_src.pool()._ex
    assert executor is not None
    assert pooled_src.ingest_stats == {
        "prefetch_hits": 0, "decode_misses": 64, "rows_decoded": 64,
        "rows_prefetched": 0}
    measure_decode_rate(pooled_src, n=32)
    assert pooled_src.pool()._ex is executor
    assert pooled_src.ingest_stats["rows_decoded"] == 128
