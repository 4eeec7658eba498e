"""AOT executable cache + zero-cold-start boot (ISSUE 17): the
content-addressed on-disk cache unit (store/load roundtrip, version
divergence, tamper/corruption refusal), the scoring-family cold→warm
roundtrip with bit-exact parity and the strict warm proof, the
generation-family roundtrip, swap-on-a-warm-boot staying compile-free,
and the e2e server boot gating /readyz on the proof.

Everything here runs against real jax executables —
``serialize_executable`` roundtrips are the subject under test, so
there is nothing to fake.  The whole module is skipped on jax builds
without serialization support (the cache degrades to compile-every-
boot there by design)."""

import os
import pickle

import numpy as np
import pytest

from znicz_tpu.core import prng
from znicz_tpu.core.config import root

from znicz_tpu.serving import aot_cache

VOCAB = 32


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache():
    """An executable that jax FETCHED from its persistent compile cache
    does not survive ``serialize_executable`` on the CPU ("Function ...
    not found" when the copy runs), and the launcher turns that cache on
    for the whole process: a test of another file on this worker, or a
    warm ``.znicz_cache/jax``, failed four to six tests here (PERF.md
    section 7, since PR 22).  These tests compile their own."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _tiny_mnist_wf(n_train=120):
    from znicz_tpu.samples import mnist

    prng.reset(1013)
    root.mnist.loader.n_train = n_train
    root.mnist.loader.n_valid = 60
    root.mnist.loader.minibatch_size = 60
    wf = mnist.MnistWorkflow()
    wf.initialize(device=None)
    return wf


def _charlm_wf(seq_len=32):
    from znicz_tpu.samples.charlm import CharLMWorkflow

    prng.reset(1013)
    root.charlm.loader.update({"n_train": 64, "n_valid": 16, "n_test": 0,
                               "seq_len": seq_len, "minibatch_size": 16})
    root.charlm.model.update({"vocab": VOCAB, "embed": 32, "heads": 2,
                              "ffn": 64})
    wf = CharLMWorkflow()
    wf.initialize(device=None)
    return wf


def _warm_runner(tmp_path, ladder):
    """A fresh tiny-mnist runner with the cache armed, warmed over
    ``ladder``."""
    from znicz_tpu.serving import ModelRunner

    runner = ModelRunner(_tiny_mnist_wf())
    assert runner.enable_aot_cache(str(tmp_path))
    runner.warmup(ladder)
    return runner


# -- cache unit ----------------------------------------------------------------


def test_cache_unit_roundtrip_version_divergence_and_refusals(tmp_path):
    """The ExecutableCache alone, over a toy jitted function: a stored
    entry loads back callable and bit-identical; a family-key change
    (an XLA/jax upgrade, a mesh change...) is a CLEAN miss — the
    filename itself diverges, no refusal; a tampered or truncated file
    is REFUSED (counted, logged) and never returned."""
    import jax

    fam = {"toy": 1, "jax": "a"}
    cache = aot_cache.ExecutableCache(str(tmp_path), fam)
    x = np.arange(4, dtype=np.float32)
    jitted = jax.jit(lambda v: v * 2.0 + 1.0)
    compiled = jitted.lower(x).compile()
    entry = {"kind": "toy", "shape": [4]}
    assert cache.load(entry) is None          # absent: silent miss
    assert cache.store(entry, compiled)
    fn = cache.load(entry)
    assert fn is not None
    np.testing.assert_array_equal(np.asarray(fn(x)),
                                  np.asarray(compiled(x)))
    assert cache.counts["refusals"] == 0

    # version divergence: same directory, different family digest
    bumped = aot_cache.ExecutableCache(str(tmp_path),
                                       {**fam, "jax": "b"})
    assert bumped.load(entry) is None
    assert bumped.counts["refusals"] == 0     # clean miss, not refusal

    # a tampered key inside an otherwise valid pickle is refused
    path = cache._path(entry)
    with open(path, "rb") as f:
        blob = pickle.load(f)
    blob["key"]["entry"] = {"kind": "evil"}
    with open(path, "wb") as f:
        pickle.dump(blob, f)
    assert cache.load(entry) is None
    assert cache.counts["refusals"] == 1

    # a truncated/garbage file is refused, not crashed on
    with open(path, "wb") as f:
        f.write(b"not a pickle")
    assert cache.load(entry) is None
    assert cache.counts["refusals"] == 2

    # ... and a fresh store overwrites the refused entry for good
    assert cache.store(entry, compiled)
    assert cache.load(entry) is not None
    assert cache.stats()["stores"] == 2


def test_family_key_is_structural_not_weights(tmp_path):
    """Two runners over the SAME architecture but different weights
    share a family digest (a retrained canary keeps hitting); changing
    the architecture diverges it."""
    from znicz_tpu.serving import ModelRunner

    a = aot_cache.family_key(ModelRunner(_tiny_mnist_wf()))
    b = aot_cache.family_key(ModelRunner(_tiny_mnist_wf(n_train=180)))
    assert a == b
    c = aot_cache.family_key(ModelRunner(_charlm_wf()))
    assert a != c
    # the key pins the toolchain: an XLA upgrade invalidates everything
    for field in ("jax", "jaxlib", "backend", "units", "sample_shape",
                  "dtype", "mesh", "donate"):
        assert field in a


# -- scoring family cold -> warm ----------------------------------------------


def test_scoring_cold_then_warm_roundtrip(tmp_path):
    """The tentpole contract on the scoring family: a cold boot
    compiles + stores every rung, a fresh runner over the same
    directory LOADS the whole family (zero compiles), answers are
    bit-exact, traffic over mixed sizes never recompiles, and the
    strict warm proof holds on both sides."""
    from znicz_tpu.serving import BucketLadder, ModelRunner

    ladder = BucketLadder(8)
    n = len(ladder.rungs)
    cold = _warm_runner(tmp_path, ladder)
    assert cold.compiles == n
    assert cold._warm == {"hits": 0, "misses": n}
    assert cold.warm_source == "compiled"
    assert cold._aot_cache.counts["stores"] == n
    proof = cold.warm_proof(n)
    # the explicit lower().compile() path never touches jax's implicit
    # jit cache — the strictness lever the proof rides
    assert proof["ok"] and proof["mode"] == "aot"
    assert proof["jit_cache_size"] == 0
    assert len(os.listdir(tmp_path)) == n

    rng = np.random.default_rng(7)
    xs = [rng.normal(0, 1, (b, 784)).astype(np.float32)
          for b in ladder.rungs]
    refs = [cold.infer(x) for x in xs]

    warm = ModelRunner(_tiny_mnist_wf())
    assert warm.enable_aot_cache(str(tmp_path))
    # warmup returns the compile count — ZERO on a cache-warm boot
    assert warm.warmup(ladder) == 0
    assert warm.compiles == 0                  # the whole point
    assert warm._warm == {"hits": n, "misses": 0}
    assert warm.warm_source == "cache_hit"
    proof = warm.warm_proof(n)
    assert proof["ok"] and proof["cache_hits"] == n
    assert proof["compiles"] == 0 and proof["jit_cache_size"] == 0
    # bit-exact: the deserialized executable IS the compiled one
    for x, ref in zip(xs, refs):
        np.testing.assert_array_equal(warm.infer(x), ref)
    # a mixed traffic stream stays compile-free post-load
    for rows in (1, 3, 7, 8, 2, 5, 4, 6):
        warm.infer(np.zeros((ladder.bucket_for(rows), 784), np.float32))
    assert warm.compiles == 0
    assert warm.jit_cache_size() == 0


def test_corrupt_entry_refused_recompiled_and_healed(tmp_path):
    """One corrupt file in an otherwise warm cache: the boot refuses it
    readably, recompiles JUST that entry, re-stores it, and reports
    ``mixed`` — the next boot is fully warm again."""
    from znicz_tpu.serving import BucketLadder, ModelRunner

    ladder = BucketLadder(8)
    n = len(ladder.rungs)
    _warm_runner(tmp_path, ladder)
    victim = sorted(os.listdir(tmp_path))[0]
    with open(os.path.join(tmp_path, victim), "wb") as f:
        f.write(b"\x80corrupt")

    mixed = ModelRunner(_tiny_mnist_wf())
    assert mixed.enable_aot_cache(str(tmp_path))
    mixed.warmup(ladder)
    assert mixed._warm == {"hits": n - 1, "misses": 1}
    assert mixed.compiles == 1
    assert mixed.warm_source == "mixed"
    counts = mixed._aot_cache.counts
    assert counts["refusals"] == 1 and counts["stores"] == 1
    assert mixed.warm_proof(n)["ok"]           # family complete either way

    healed = ModelRunner(_tiny_mnist_wf())
    assert healed.enable_aot_cache(str(tmp_path))
    healed.warmup(ladder)
    assert healed._warm == {"hits": n, "misses": 0}
    assert healed.compiles == 0


def test_swap_on_a_warm_boot_stays_compile_free(tmp_path):
    """A canary/heal swap on a cache-warm replica: same architecture,
    new weights — the swap's warm loop replays the AOT tables (the
    executable is a pure function of avals, not weights), so the
    rollover costs ZERO compiles and the family digest still hits."""
    from znicz_tpu import snapshotter
    from znicz_tpu.serving import BucketLadder, ModelRunner

    wf = _tiny_mnist_wf()
    wf.snapshotter.directory = str(tmp_path / "snaps")
    path = wf.snapshotter.save("gen2")

    ladder = BucketLadder(8)
    cache_dir = tmp_path / "aot"
    _warm_runner(cache_dir, ladder)            # populate the cache

    warm = ModelRunner(_tiny_mnist_wf())
    assert warm.enable_aot_cache(str(cache_dir))
    warm.warmup(ladder)
    assert warm.compiles == 0
    rep = warm.swap(path, ladder)          # returns snapshot metadata
    assert "epoch" in rep and warm.generation == 2
    assert warm.compiles == 0                  # swap warmed from tables
    assert warm.jit_cache_size() == 0
    assert warm.snapshot_path == path


# -- generation family --------------------------------------------------------


def test_generation_family_roundtrip_and_parity(tmp_path):
    """The paged generation executables (prefill/decode per (batch
    rung, page rung), plus the COW copy) roundtrip the cache too: a
    fresh runner loads every entry the drive touched with zero
    compiles and decodes the same tokens bit-for-bit, including across
    a page-table rung step (1 -> 2 pages) and a COW copy."""
    from znicz_tpu.serving.model import ModelRunner

    def boot():
        r = ModelRunner(_charlm_wf())
        assert r.enable_aot_cache(str(tmp_path))
        return r.enable_generation(page_size=8, num_pages=8, slots=2,
                                   prefill_chunk=8, prefix_cache=False,
                                   prefill_rungs=[1], decode_rungs=[1])

    def drive(g):
        rng = np.random.default_rng(17)
        prompt = rng.integers(1, VOCAB, size=5).astype(np.uint8)
        pages = [g.alloc_page()]
        x = np.zeros((1, 8), g.runner.dtype)
        x[0, :5] = prompt
        tok, _, _, _ = g.prefill(x, [0], [5], [pages], [0.0], [0], [0])
        toks = [int(tok[0])]
        t = 5
        for _ in range(6):                     # crosses the page boundary
            if t % g.page_size == 0:
                pages.append(g.alloc_page())
            tok, _, _, _ = g.decode([pages], [toks[-1]], [t],
                                    [0.0], [0], [0])
            toks.append(int(tok[0]))
            t += 1
        dst = g.alloc_page()                   # the COW executable too
        g.copy_page(pages[0], dst)
        g.release_pages(pages + [dst])
        return toks

    cold = boot()
    fam = cold.executables()
    ref = drive(cold)
    # every executable the drive touched was compiled + stored
    stores = cold.runner._aot_cache.counts["stores"]
    assert stores == cold.runner.compiles > 0

    warm = boot()
    assert drive(warm) == ref                  # bit-identical decode
    assert warm.runner.compiles == 0
    assert warm.runner._warm["misses"] == 0
    assert warm.runner._warm["hits"] == stores
    assert warm.jit_cache_size() == 0
    assert fam == warm.executables()
    assert warm.pages_active() == 0 and warm.pages_leaked() == 0


@pytest.mark.slow
def test_generation_full_warmup_roundtrip(tmp_path):
    """``GenerationRunner.warmup()`` (the boot path) over the cache:
    cold stores the full paged family — (prefill rungs + decode rungs)
    x page rungs + the copy — warm loads it: ``loaded == family`` with
    zero compiles, the /readyz equality for the generation plane."""
    from znicz_tpu.serving.model import ModelRunner

    def boot():
        r = ModelRunner(_charlm_wf())
        assert r.enable_aot_cache(str(tmp_path))
        return r.enable_generation(page_size=8, num_pages=8, slots=2,
                                   prefill_chunk=8,
                                   prefill_rungs=[1], decode_rungs=[1])

    cold = boot()
    fam = cold.warmup()
    assert fam == cold.executables()
    assert fam == 2 * len(cold.page_rungs) + 1
    assert cold.runner.compiles == fam
    assert cold.runner._aot_cache.counts["stores"] == fam

    warm = boot()
    # warmup returns the runner's compile count — zero on a warm boot
    assert warm.warmup() == 0
    assert warm.runner.compiles == 0
    assert warm.runner._warm == {"hits": fam, "misses": 0}
    assert warm.jit_cache_size() == 0
    assert warm.stats()["aot_loaded"] == fam


# -- e2e server boot ----------------------------------------------------------


def test_e2e_server_boots_warm_and_gates_readyz_on_the_proof(tmp_path):
    """Two InferenceServer boots over one cache directory: the first
    compiles + stores (warm_report mode=aot, ok), the second loads the
    whole family (cache_hit, zero compiles), serves bit-exact answers,
    and ships the warm columns in its stats/heartbeat payloads."""
    from znicz_tpu.serving import InferenceClient, InferenceServer

    root.common.serving.aot_cache.update(
        {"enabled": True, "dir": str(tmp_path)})
    try:
        boots = []
        ref = None
        x = np.arange(784, dtype=np.float32).reshape(1, 784) / 784.0
        for _ in range(2):
            srv = InferenceServer(_tiny_mnist_wf(), max_batch=8).start()
            cli = InferenceClient(srv.endpoint, timeout=30)
            try:
                y = cli.infer(x)
                ref = y if ref is None else ref
                np.testing.assert_array_equal(y, ref)
                st = cli.stats()
                boots.append((srv.warm_report, st,
                              srv.boot_to_ready_s))
            finally:
                cli.close()
                srv.stop()
        (cold, cold_st, cold_boot), (warm, warm_st, warm_boot) = boots
        n = cold["expected"]
        assert cold["ok"] and cold["mode"] == "aot"
        assert cold["cache_misses"] == n and cold["cache_hits"] == 0
        assert warm["ok"] and warm["cache_hits"] == n
        assert warm["compiles"] == 0 and warm["jit_cache_size"] == 0
        assert warm["warm_source"] == "cache_hit"
        assert warm_st["model"]["warm_source"] == "cache_hit"
        assert warm_st["model"]["aot_loaded"] == n
        assert warm_st["boot_to_ready_s"] is not None
        assert cold_boot > 0 and warm_boot > 0
    finally:
        root.common.serving.aot_cache.update(
            {"enabled": False, "dir": ""})
