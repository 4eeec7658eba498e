"""ModelRunner: a trained workflow frozen into an inference-only jitted
forward (ISSUE 4).

The forward IS ``FusedTrainer.forward_pass`` with ``train=False`` — the
same pure composition of the units' own ``apply`` code the training fast
path differentiates, so serving computes exactly the function training
optimized (the batched-vs-unbatched 0-ULP parity test in
tests/test_serving.py rides on the row-independence of that graph).
Params are extracted once at construction and pinned on device; every
call passes them as an un-donated operand, so one params tree serves
every bucket's executable.

**Bucketed jit cache**: the runner jits ONE function of ``(params, x)``;
each distinct padded batch shape (a ladder rung) compiles exactly once
and is a cache hit forever after.  ``compiles`` counts TRACES — the
counter ticks inside the traced function body, which Python only runs
when jax actually (re)traces, i.e. once per cache entry — and
``jit_cache_size()`` cross-checks it against jax's own pjit cache, so
"zero recompiles after warmup" is provable from the outside
(tests/test_serving.py::test_warmup_compiles_ladder_then_zero_recompiles).

**Donated ping-pong staging**: ``stage`` starts an async host->device
put and ``infer_staged`` DONATES that buffer into the jitted call
(``donate_argnums``), so at any moment at most two input buffers exist —
the one the device is consuming (its memory reusable for activations
the instant the gather reads it) and the one the next batch is staging
into.  The frontend's compute loop overlaps stage(N+1) with compute(N),
the same overlap discipline as ``loader/ingest.py``'s prefetch.

**Zero-downtime snapshot rollover** (ISSUE 6): :meth:`swap` loads a new
snapshot's params, bucket-warms them through every ladder rung, then
flips ``(params, generation)`` as ONE atomic tuple — serving continues
on the old generation throughout, and because every dispatch reads the
tuple exactly once, every request is answered entirely by one snapshot
generation (the ``gen`` id in each reply proves it).  A failed load or
warm leaves the served generation untouched.

**Autoregressive generation** (ISSUE 16, block-paged since ISSUE 19):
:meth:`enable_generation` builds a :class:`GenerationRunner` — a
block-paged KV pool with content-addressed prefix reuse
(:class:`PrefixCache`) plus three more jitted functions
(prefill-chunk, decode, page-copy; greedy/top-k sampling fused into
the first two) that share the runner's ``compiles`` counter, so the
zero-recompile contract extends over the whole generation executable
family: ``(prefill_rungs + decode_rungs) x page_rungs + 1``
executables, warmed up front, zero traces after.

**Pod-scale sharding** (ISSUE 13): with ``root.common.serving.mesh.*``
set (``data``/``model`` axis sizes; default 1x1 = exactly the
single-device path above), the runner goes mesh-native: params are
replicated (or column-sharded over ``model`` for wide FC layers) via
``FusedTrainer.param_sharding`` + ``mesh.global_put``, the forward is
jitted with explicit ``in_shardings``/``out_shardings``, and every
staged batch is split along the ``data`` axis — each device holds
exactly ``rows/dp`` rows, placed DIRECTLY from the host (one transfer
per device shard, never a gather through device 0).  The bucket
ladder's rungs are snapped to multiples of ``dp`` so every executable
splits evenly, which keeps the jit cache bounded and the
zero-recompile contract intact on the sharded path.  The 0-ULP
batch-independence contract extends UNCHANGED to a fixed mesh (a
request's rows are a pure function of its rows + the rung executable,
wherever its rows land across devices); across DIFFERENT mesh layouts
results agree only numerically — reduction tiling is layout-dependent,
the same reason PR 4 pinned parity per bucket executable
(tests/test_shard_serving.py holds the band).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from znicz_tpu.telemetry.metrics import registered_property


def mesh_from_config():
    """The serving mesh, or None for the default 1x1 — which keeps the
    runner on the exact single-device code path (bit-for-bit the
    pre-mesh behavior).  Kept under its historical name; the config
    read and every other piece of placement machinery live in the ONE
    shared home, ``parallel/mesh.py`` (ISSUE 18 extraction)."""
    from znicz_tpu.parallel.mesh import serving_mesh_from_config

    return serving_mesh_from_config()


class ModelRunner:
    """Freeze a built+initialized workflow's params into a jitted
    inference forward.  ``snapshot`` restores params first (the
    snapshotter's inference-load path — no velocities, no trainer
    state).  The output is the last unit's output: LOGITS for a softmax
    head (clients softmax if they want probabilities), the raw
    reconstruction for MSE heads."""

    def __init__(self, workflow, snapshot: str = "",
                 donate: Optional[bool] = None, mesh=None):
        import jax

        from znicz_tpu.parallel.fused import FusedTrainer

        if donate is None:
            # donation is a TPU/GPU lever; the CPU runtime ignores it
            # (and warns per compile), so auto-resolve by backend — the
            # serving STRUCTURE (stage N+1 while N computes) is identical
            # either way, only the buffer reuse is backend-dependent
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)

        if snapshot:
            from znicz_tpu import snapshotter

            snapshotter.load_inference(workflow, snapshot)
        self.workflow = workflow
        #: the serving mesh (ISSUE 13): explicit arg wins, else
        #: ``root.common.serving.mesh.*``; None = single-device (the
        #: pre-mesh code path, bit-exact)
        self.mesh = mesh if mesh is not None else mesh_from_config()
        self._trainer = FusedTrainer(workflow, mesh=self.mesh)
        if self.mesh is not None:
            from znicz_tpu.parallel.mesh import data_sharding

            self._data_sharding = data_sharding(self.mesh)
        else:
            self._data_sharding = None
        #: (params tree, generation id) — read ONCE per dispatch, flipped
        #: as one tuple by swap(): per-request snapshot atomicity
        self._active = (self._place_params(
            self._trainer.extract_params()), 1)
        #: the snapshot file the LIVE generation came from (boot
        #: ``snapshot`` arg, updated by swap/rollback) — heartbeats
        #: carry it so a fleet balancer can heal a restarted replica
        #: back onto the promoted snapshot
        self.snapshot_path: str = snapshot or ""
        #: the RETAINED previous generation ``(params, gen, path)`` —
        #: set by a successful swap(), consumed by rollback(); costs one
        #: extra params tree in memory, which is what buys an instant,
        #: bit-exact, disk-free fleet rollback
        self._previous: Optional[Tuple] = None
        #: generation high-water mark: swap always allocates hwm+1, so
        #: a rollback-then-retry cycle can never hand two DIFFERENT
        #: param sets the same generation stamp
        self._gen_hwm = 1
        self._swap_lock = threading.Lock()  # one rollover at a time
        #: True while swap() loads/warms (the /readyz "warming" signal)
        self.swapping = False
        self._dispatch_no = 0               # compute-fault stream cursor
        self._dispatch_lock = threading.Lock()  # cursor is shared by the
        #                                     compute thread AND swap()'s
        #                                     warmup dispatches
        self._chaos = None                  # FaultSchedule, or None
        self._m_stalls = None
        #: GenerationRunner once enable_generation() ran (ISSUE 16)
        self.gen_runner: Optional["GenerationRunner"] = None
        #: per-sample input shape the service accepts (requests carry
        #: (n, *sample_shape) arrays)
        self.sample_shape: Tuple[int, ...] = tuple(
            int(d) for d in workflow.forwards[0].input.shape[1:])
        mem = getattr(workflow.loader.original_data, "mem", None)
        #: staging dtype — u8 datasets keep their 1-byte wire/HBM form,
        #: the in-graph decode (trainer._decode) widens on device
        self.dtype = np.dtype(mem.dtype) if mem is not None \
            else np.dtype(np.float32)
        from znicz_tpu import telemetry

        _sc = telemetry.scope("model")
        #: traces of _fwd == cache entries (registry counter; the
        #: ``compiles`` property preserves the historical name)
        self._m = {"compiles": _sc.counter(
            "compiles",
            "traces of the jitted forward == jit cache entries"),
            "swaps": _sc.counter(
                "swaps", "completed snapshot rollovers"),
            "swap_failures": _sc.counter(
                "swap_failures",
                "rollovers refused/failed (old generation kept serving)"),
            "rollbacks": _sc.counter(
                "rollbacks",
                "retained-previous generation restored (fleet canary "
                "auto-rollback path)"),
            "stage_copies": _sc.counter(
                "stage_copies",
                "host batches copied before staging (non-contiguous or "
                "wrong-dtype input; the frontend's assemble path never "
                "pays this)")}
        _sc.gauge("generation", "live snapshot generation id",
                  fn=telemetry.weak_fn(self, lambda r: r.generation))
        _sc.gauge("mesh_devices", "devices in the serving mesh (1 = "
                  "single-device)",
                  fn=telemetry.weak_fn(self, lambda r: r.device_count))
        self._tracer = telemetry.tracer()
        compiles = self._m["compiles"]
        key = self._trainer._key0       # eval path never consumes it

        def fwd(params, x):
            # trace-time tick: Python runs this body once per compile
            # (cache hits replay the compiled executable only)
            compiles.inc()
            t = self._trainer
            return t.forward_pass(params, t._decode(x), key, train=False)

        donate = (1,) if self.donate else ()
        if self.mesh is None:
            self._fwd = jax.jit(fwd, donate_argnums=donate)
        else:
            # explicit shardings (SNIPPETS [3]): params pinned to their
            # param_sharding placements, the batch split over ``data``
            # in AND out — GSPMD propagates through the forward and
            # inserts the model-axis collectives where column-sharded
            # FC weights demand them
            self._fwd = jax.jit(
                fwd, donate_argnums=donate,
                in_shardings=(self._param_shardings(self.params),
                              self._data_sharding),
                out_shardings=self._data_sharding)
        # weak_fn: the process-global registry must not pin this
        # runner's jitted executables + device params after the service
        # drops it (a dead ref renders NaN)
        _sc.gauge("jit_cache_size", "jax's own executable-cache entries",
                  fn=telemetry.weak_fn(
                      self, lambda r: r.jit_cache_size()))
        #: AOT dispatch table (ISSUE 17): {(shape, dtype): executable},
        #: consulted BEFORE the jitted forward once enable_aot_cache
        #: ran.  In AOT mode every executable enters the table by
        #: deserialize or by explicit lower+compile — jax's own jit
        #: call cache stays EMPTY, which is what makes the boot proof
        #: strict: jit_cache_size() == 0 and table size == family size
        #: means NOTHING was traced through the implicit path.
        self._aot: Dict = {}
        self._aot_cache = None          # ExecutableCache, or None
        #: this runner's own warm tally (the cache's registry counters
        #: are process-wide; proofs and heartbeats read these)
        self._warm = {"hits": 0, "misses": 0}

    compiles = registered_property(
        "compiles", "traces of the jitted forward == jit cache entries")
    swaps = registered_property(
        "swaps", "completed snapshot rollovers")
    swap_failures = registered_property(
        "swap_failures", "rollovers refused/failed")
    rollbacks = registered_property(
        "rollbacks", "retained-previous generation restored")
    stage_copies = registered_property(
        "stage_copies", "host batches copied before staging")

    @property
    def params(self):
        """The LIVE generation's params tree (historical attribute)."""
        return self._active[0]

    @property
    def generation(self) -> int:
        """Snapshot generation id stamped on every reply; bumps on each
        completed :meth:`swap`."""
        return self._active[1]

    # -- mesh placement (ISSUE 13) ---------------------------------------------

    @property
    def device_count(self) -> int:
        """Devices this runner computes on (the mesh size; 1 when
        single-device) — piggybacked on fleet heartbeats so the
        balancer can weight dispatch by capacity."""
        return 1 if self.mesh is None else int(self.mesh.size)

    @property
    def data_parallel(self) -> int:
        """The mesh's ``data``-axis size (1 when single-device): every
        ladder rung must be a multiple of this."""
        return 1 if self.mesh is None else int(self.mesh.shape["data"])

    @property
    def mesh_shape(self) -> Optional[Dict[str, int]]:
        """``{"data": dp, "model": mp}`` (None when single-device) —
        the heartbeat/panel form of the mesh."""
        from znicz_tpu.parallel.mesh import mesh_shape_dict

        return mesh_shape_dict(self.mesh)

    def _param_shardings(self, params):
        """The params tree's NamedSharding tree per the shared
        ``param_sharding`` rule (wide FC weights column-shard over
        ``model``).  Mesh-mode only."""
        from znicz_tpu.parallel.mesh import tree_shardings

        return tree_shardings(self.mesh, params,
                              self._trainer.tp_threshold)

    def _place_params(self, params):
        """Distribute a params tree onto the mesh per its shardings
        (the shared ``place_tree``).  Identity when single-device: the
        tree is already placed by extraction."""
        if self.mesh is None:
            return params
        from znicz_tpu.parallel.mesh import place_tree

        return place_tree(self.mesh, params, self._trainer.tp_threshold)

    # -- the two halves of the ping-pong ---------------------------------------

    def stage(self, x: np.ndarray):
        """Host batch -> device buffer.  The put is dispatched
        asynchronously, so calling this while a previous ``infer_staged``
        is still computing overlaps the H2D copy with that compute.

        An input already contiguous in the staging dtype is handed to
        the put as-is (the frontend's assemble buffer always is); only
        mismatched inputs pay a host copy (``stage_copies``).  On a
        mesh the put places each device's ``rows/dp`` shard DIRECTLY
        from the host buffer — one transfer per shard, no gather
        through device 0 — so the batch is born in the layout the
        sharded executable consumes."""
        import jax

        if not (isinstance(x, np.ndarray) and x.dtype == self.dtype
                and x.flags["C_CONTIGUOUS"]):
            self._m["stage_copies"].inc()
            x = np.ascontiguousarray(x, self.dtype)
        if self.mesh is None:
            return jax.device_put(x)
        from znicz_tpu.parallel.mesh import require_batch_divisible

        dp = require_batch_divisible(x.shape[0], self.mesh)
        if self._tracer.enabled:
            with self._tracer.span("model", "stage_sharded",
                                   rows=int(x.shape[0]), shards=dp,
                                   rows_per_shard=int(x.shape[0]) // dp):
                return jax.device_put(x, self._data_sharding)
        return jax.device_put(x, self._data_sharding)

    def _maybe_stall(self) -> None:
        """Chaos compute-fault hook (ISSUE 6): one ``decide_compute``
        decision per dispatch; a ``stall`` sleeps here — the seeded
        slow-compute fault the rollover/fairness soaks run under.  The
        cursor advances under a lock: during a swap the background
        warmup dispatches race the compute thread, and a lost increment
        would let two dispatches replay one stream index."""
        with self._dispatch_lock:
            no = self._dispatch_no
            self._dispatch_no += 1
            chaos = self._chaos
        if chaos is None:
            return
        action, s = chaos.decide_compute(no)
        if action == "stall":
            self._m_stalls.inc()
            time.sleep(s)

    def infer_staged(self, x_dev) -> Tuple[object, int]:
        """Dispatch the forward on an already-staged (device) batch and
        return ``(un-materialized device result, generation id)`` —
        params and generation are read as one tuple, so the whole batch
        is answered by exactly one snapshot generation.  ``x_dev`` is
        DONATED (where the backend supports donation — see ``donate``);
        callers must not reuse it after this call either way."""
        self._maybe_stall()
        params, gen = self._active
        return self._fwd_call(params, x_dev), gen

    def inject_compute_faults(self, schedule) -> None:
        """Arm the seeded compute-fault hook: ``schedule`` (a chaos
        ``FaultSchedule``) decides per dispatch whether this runner
        stalls (``decide_compute``); counted in the chaos fault family
        like the proxy's wire faults."""
        from znicz_tpu import telemetry

        if self._m_stalls is None:
            self._m_stalls = telemetry.scope("chaos").counter(
                "faults", "injected proxy fault decisions",
                direction="compute", action="stall")
        self._chaos = schedule

    # -- conveniences ----------------------------------------------------------

    def infer(self, x: np.ndarray) -> np.ndarray:
        """Synchronous forward of one host batch (tests, warmup, the
        sequential baseline)."""
        y_dev, _ = self.infer_staged(self.stage(x))
        return np.asarray(y_dev)

    def pad(self, x: np.ndarray, bucket: int) -> np.ndarray:
        """Zero-pad a (n, *sample) batch up to ``bucket`` rows.  The
        forward is row-independent, so pad rows cannot perturb real
        rows; the caller slices the first n output rows back out."""
        n = x.shape[0]
        if n == bucket:
            return x
        out = np.zeros((bucket,) + tuple(x.shape[1:]), self.dtype)
        out[:n] = x
        return out

    def bucket_shape(self, bucket) -> Tuple[int, ...]:
        """The staged input shape for one ladder bucket: ``(rung,
        *sample)`` for a plain batch rung, ``(rows, seq, *sample[1:])``
        for a 2-D ``(rows, seq)`` bucket (ISSUE 15 — the seq axis
        replaces the trained max length in axis 1)."""
        if isinstance(bucket, tuple):
            rows, seq = bucket
            return (int(rows), int(seq)) + tuple(self.sample_shape[1:])
        return (int(bucket),) + tuple(self.sample_shape)

    def warmup(self, ladder) -> int:
        """Compile every ladder bucket's executable up front (the full
        rows x seq product on a 2-D ladder); returns the compile count
        afterwards — the zero-recompiles baseline the serving gates
        compare against (``compiles == len(ladder.buckets())``)."""
        for bucket in ladder.buckets():
            self.infer(np.zeros(self.bucket_shape(bucket), self.dtype))
        return self.compiles

    def swap(self, path: str, ladder=None) -> Dict:
        """Zero-downtime snapshot rollover (ISSUE 6): load ``path``
        through the inference path, bucket-warm the NEW params through
        every ``ladder`` rung, then flip ``(params, generation)``
        atomically.  Runs on the CALLING thread (the frontend drives it
        from a background thread); dispatches keep serving the OLD
        generation until the flip, so no request is lost and none mixes
        generations.  Warming costs no recompiles — the new tree has
        the same shapes/dtypes, so every rung is a jit cache hit; it
        pre-pays device transfer and catches a broken snapshot while
        the old generation still serves.  A second concurrent swap, a
        non-covering snapshot, or a warm failure raises and leaves the
        live generation untouched (``swap_failures`` counts it).
        Returns the snapshot's metadata."""
        from znicz_tpu import snapshotter

        if not self._swap_lock.acquire(blocking=False):
            self._m["swap_failures"].inc()
            raise RuntimeError("swap already in progress")
        try:
            self.swapping = True
            try:
                meta = snapshotter.load_inference(self.workflow, path)
                # the NEW tree lands in the SAME placement the live one
                # serves from (replicated/column-sharded on a mesh), so
                # the flip below swaps like for like and the warmed
                # rungs are jit cache hits on the sharded executables
                params = self._place_params(
                    self._trainer.extract_params())
                buckets = ladder.buckets() if ladder is not None else ()
                # warm through _fwd_call: on an AOT-warm boot the jit
                # call cache is EMPTY by design, and warming through
                # self._fwd directly would recompile every rung
                for bucket in buckets:
                    self._maybe_stall()
                    x = np.zeros(self.bucket_shape(bucket), self.dtype)
                    np.asarray(self._fwd_call(params, self.stage(x)))
                # retain the losing side for a disk-free rollback(); the
                # hwm (not generation+1) allocates the new id, so a
                # rolled-back-then-retried rollover never reuses a stamp
                old_params, old_gen = self._active
                self._previous = (old_params, old_gen, self.snapshot_path)
                self._gen_hwm += 1
                self._active = (params, self._gen_hwm)
                self.snapshot_path = path
                self._m["swaps"].inc()
                return meta
            except Exception:
                self._m["swap_failures"].inc()
                raise
        finally:
            self.swapping = False
            self._swap_lock.release()

    def rollback(self) -> int:
        """Restore the RETAINED previous generation (the fleet canary
        auto-rollback): an instant, disk-free ``(params, generation)``
        flip back to exactly the tuple the last :meth:`swap` displaced —
        bit-exact by construction, generation STAMP restored too, so a
        rolled-back fleet is indistinguishable from one that never
        swapped.  One-shot: the retained tuple is consumed.  Raises
        RuntimeError when nothing is retained or a swap is mid-flight
        (the live generation is never disturbed either way)."""
        if not self._swap_lock.acquire(blocking=False):
            raise RuntimeError("swap in progress — rollback refused")
        try:
            if self._previous is None:
                raise RuntimeError(
                    "no previous generation retained (nothing was "
                    "swapped, or it was already rolled back)")
            params, gen, path = self._previous
            self._previous = None
            self._active = (params, gen)
            self.snapshot_path = path
            self._m["rollbacks"].inc()
            return gen
        finally:
            self._swap_lock.release()

    def enable_generation(self, page_size: int, num_pages: int,
                          slots: int, prefill_chunk: int,
                          prefix_cache: bool = True,
                          prefill_rungs=None, decode_rungs=None
                          ) -> "GenerationRunner":
        """Build the autoregressive generation path (ISSUE 16, paged
        since ISSUE 19): a block-paged KV pool with prefix reuse plus
        jitted prefill-chunk/decode/copy functions (sampling fused)
        over this runner's live params.  Idempotent per runner; returns
        the :class:`GenerationRunner`."""
        if self.gen_runner is None:
            self.gen_runner = GenerationRunner(
                self, page_size=page_size, num_pages=num_pages,
                slots=slots, prefill_chunk=prefill_chunk,
                prefix_cache=prefix_cache, prefill_rungs=prefill_rungs,
                decode_rungs=decode_rungs)
        return self.gen_runner

    def jit_cache_size(self) -> Optional[int]:
        """jax's own executable-cache entry count for the jitted forward
        (the jax._src pjit cache behind ``_cache_size``); None where the
        jax version does not expose it.  After warmup this equals
        ``compiles`` and must stay put."""
        try:
            return int(self._fwd._cache_size())
        except Exception:               # pragma: no cover - jax-version dep
            return None

    # -- AOT executable cache (ISSUE 17) ---------------------------------------

    def enable_aot_cache(self, directory: str = "") -> bool:
        """Arm the on-disk AOT executable cache (serving/aot_cache.py):
        warmup and dispatch misses probe the cache before compiling,
        and fresh compiles are serialized back.  ``directory`` defaults
        to ``aot_cache/`` next to this runner's snapshot."""
        from znicz_tpu.serving import aot_cache

        if not directory:
            if not self.snapshot_path:
                raise ValueError(
                    "enable_aot_cache needs an explicit directory when "
                    "the runner was not booted from a snapshot")
            directory = aot_cache.dir_for_snapshot(self.snapshot_path)
        self._aot_cache = aot_cache.ExecutableCache(
            directory, aot_cache.family_key(self))
        return True

    @property
    def aot_enabled(self) -> bool:
        return self._aot_cache is not None

    def _aot_exec(self, table: Dict, key, entry: Dict, jitfn, args):
        """AOT-mode dispatch for one executable: replay the table,
        else deserialize from the cache (VALIDATED by executing it
        where donation allows — a loaded executable that cannot run
        this very call is refused and recompiled, never trusted), else
        ``lower().compile()`` explicitly and serialize the result.
        The explicit lower path traces (ticking ``compiles``) but
        never touches jax's implicit jit call cache — the strictness
        lever behind :meth:`warm_proof`.  Shared by the scoring
        forward and the GenerationRunner's three jits (their tables
        differ; the cache + accounting is the runner's)."""
        fn = table.get(key)
        if fn is not None:
            return fn(*args)
        cache = self._aot_cache
        fn = cache.load(entry)
        if fn is not None:
            if self.donate:
                # donated buffers would be consumed by a validation
                # call; the content digest + key check already pin the
                # aval signature, so trust the decode on this path
                table[key] = fn
                self._warm["hits"] += 1
                cache.hit()
                return fn(*args)
            try:
                out = fn(*args)
            except Exception as exc:
                cache.refuse(entry, exc)
            else:
                table[key] = fn
                self._warm["hits"] += 1
                cache.hit()
                return out
        compiled = jitfn.lower(*args).compile()
        cache.store(entry, compiled)
        table[key] = compiled
        self._warm["misses"] += 1
        cache.miss()
        return compiled(*args)

    def _fwd_call(self, params, x_dev):
        """The forward dispatch every scoring path funnels through
        (infer_staged AND swap's warm loop): plain jit call until
        :meth:`enable_aot_cache`, the AOT table after."""
        if self._aot_cache is None:
            return self._fwd(params, x_dev)
        key = (tuple(int(d) for d in x_dev.shape), str(x_dev.dtype))
        entry = {"kind": "fwd", "shape": list(key[0]), "dtype": key[1]}
        return self._aot_exec(self._aot, key, entry, self._fwd,
                              (params, x_dev))

    @property
    def warm_source(self) -> Optional[str]:
        """Where this boot's executables came from: ``cache_hit``
        (all loaded), ``compiled`` (all traced), ``mixed``, or None
        before any warmup — the per-replica heartbeat/panel label."""
        h, m = self._warm["hits"], self._warm["misses"]
        if h and m:
            return "mixed"
        if h:
            return "cache_hit"
        if m or self.compiles:
            return "compiled"
        return None

    def warm_proof(self, expected: int) -> Dict:
        """The strict warm-family proof /readyz gates on (ISSUE 17,
        same discipline as PR 15's jit-cache equality): ``expected``
        is the full executable family size (ladder buckets + the
        generation family).  AOT mode proves ``loaded == expected``
        AND jax's own jit caches are EMPTY (zero implicit traces
        slipped past the tables); jit mode proves the PR-15 equality
        ``compiles == expected == jit_cache_size``."""
        gen = self.gen_runner
        jit_total = self.jit_cache_size() or 0
        if gen is not None:
            jit_total += gen.jit_cache_size() or 0
        if self.aot_enabled:
            loaded = len(self._aot) + (len(gen._aot)
                                       if gen is not None else 0)
            ok = loaded == int(expected) and jit_total == 0
            mode = "aot"
        else:
            loaded = jit_total
            ok = self.compiles == int(expected) == jit_total
            mode = "jit"
        cache = self._aot_cache
        return {"mode": mode, "expected": int(expected),
                "loaded": int(loaded), "compiles": int(self.compiles),
                "jit_cache_size": int(jit_total),
                "cache_hits": int(self._warm["hits"]),
                "cache_misses": int(self._warm["misses"]),
                "cache_refusals": int(cache.counts["refusals"])
                if cache is not None else 0,
                "warm_source": self.warm_source, "ok": bool(ok)}

    def stats(self) -> Dict:
        return {"compiles": self.compiles,
                "donate": self.donate,
                "aot_enabled": self.aot_enabled,
                "aot_loaded": len(self._aot),
                "warm_source": self.warm_source,
                "warm_hits": int(self._warm["hits"]),
                "warm_misses": int(self._warm["misses"]),
                "jit_cache_size": self.jit_cache_size(),
                "generation": self.generation,
                "swapping": self.swapping,
                "swaps": self.swaps,
                "swap_failures": self.swap_failures,
                "rollbacks": self.rollbacks,
                "stage_copies": self.stage_copies,
                "snapshot_path": self.snapshot_path,
                "previous_retained": self._previous is not None,
                "sample_shape": list(self.sample_shape),
                "dtype": str(self.dtype),
                "mesh": self.mesh_shape,
                "device_count": self.device_count}


def batch_rungs(max_batch: int) -> Tuple[int, ...]:
    """Power-of-two batch rungs up to and including ``max_batch`` —
    the default prefill/decode coalescing ladder."""
    n = int(max_batch)
    rungs = []
    r = 1
    while r < n:
        rungs.append(r)
        r *= 2
    rungs.append(n)
    return tuple(rungs)


def _sample_tokens(logits, temp, top_k, seeds, t):
    """Fused in-graph sampling (ISSUE 19): greedy argmax where
    ``temp <= 0`` (tie -> lowest id, matching the host sampler bit for
    bit), else seeded gumbel-max over the optional per-row top-k cut.
    ``seeds`` is (b,) uint32; each row's key is
    ``fold_in(PRNGKey(seed), t)`` — deterministic per (request seed,
    position), independent of co-batched neighbors and batch padding.
    Returns ((b,) int32 tokens, (b,) f32 logprob of the chosen token
    under the raw logits)."""
    import jax
    import jax.numpy as jnp

    b, v = logits.shape
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = logits / jnp.maximum(temp, 1e-20)[:, None]
    srt = jnp.sort(z, axis=-1)                         # ascending
    kk = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    kth = srt[jnp.arange(b), v - kk]                   # kth-largest
    z = jnp.where(z < kth[:, None], -jnp.inf, z)

    def noise(seed, pos):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
        return jax.random.gumbel(key, (v,), jnp.float32)

    sampled = jnp.argmax(z + jax.vmap(noise)(seeds, t),
                         axis=-1).astype(jnp.int32)
    tok = jnp.where(temp > 0, sampled, greedy)
    logp = jax.nn.log_softmax(logits, axis=-1)[jnp.arange(b), tok]
    return tok, logp


class PrefixCache:
    """Page-granularity content-addressed prefix index (ISSUE 19).

    Pages are keyed by a CHAIN hash: page ``i`` of a prompt hashes
    (hash of pages ``[0..i)``, tokens ``[i*ps .. (i+1)*ps)``), so a
    lookup can only match a page whose ENTIRE preceding context matches
    too — content addressing over the prefix, not the page in
    isolation.  The index holds one refcount on every registered page;
    requests that hit share the page READ-ONLY (refcount++), and the
    first divergent append copy-on-writes (scheduler-driven, via
    :meth:`GenerationRunner.copy_page`).  Eviction is LRU over entries
    nobody but the index holds (refcount == 1) and runs only under
    allocation pressure — a cached page costs nothing until the pool
    actually wants it back.

    Bit-exactness: a hit replays k/v that the SAME prefill executable
    grid computed (registration indexes only canonically-computed
    pages — a COW'd recompute page is skipped because its hash is
    already indexed), so with ``prefill_chunk == page_size`` a
    prefix-hit generation decodes bit-identically to a cold one."""

    def __init__(self, gen: "GenerationRunner"):
        from collections import OrderedDict

        self.gen = gen
        #: chain-hash -> page id, in LRU order (move_to_end on hit)
        self._index = OrderedDict()
        self._by_page: Dict[int, bytes] = {}

    def __len__(self) -> int:
        return len(self._index)

    def _hashes(self, prompt):
        """Chain hashes of every FULL page of ``prompt``."""
        import hashlib

        ps = self.gen.page_size
        out = []
        h = b"znicz-prefix-v1"
        for i in range(len(prompt) // ps):
            h = hashlib.blake2b(
                h + np.asarray(prompt[i * ps:(i + 1) * ps],
                               np.int32).tobytes(),
                digest_size=16).digest()
            out.append(h)
        return out

    def lookup(self, prompt):
        """Claim the longest indexed run of ``prompt``'s full pages:
        returns ``(pages, covered_tokens)`` with one reference taken on
        each matched page (the request's own; drop via
        ``release_pages``)."""
        pages = []
        for h in self._hashes(prompt):
            page = self._index.get(h)
            if page is None:
                break
            self._index.move_to_end(h)
            self.gen.addref(page)
            pages.append(page)
        covered = len(pages) * self.gen.page_size
        m = self.gen._pm
        if pages:
            m["hits"].inc()
            m["tokens_avoided"].inc(covered)
            m["flops_avoided"].inc(covered * self.gen.flops_per_token)
        else:
            m["misses"].inc()
        return pages, covered

    def register(self, prompt, pages) -> None:
        """Index ``prompt``'s full pages once its prefill completed.
        Already-indexed hashes keep their existing page (first writer
        wins); fresh ones take one index-owned reference on the
        request's page."""
        for i, h in enumerate(self._hashes(prompt)):
            if h in self._index or pages[i] in self._by_page:
                continue
            self.gen.addref(pages[i])
            self._index[h] = pages[i]
            self._by_page[pages[i]] = h

    def evict_one(self) -> bool:
        """Drop the least-recently-used entry whose page only the index
        holds (refcount == 1) — frees exactly one page.  False when
        every indexed page is currently shared with a live request."""
        for h, page in self._index.items():
            if self.gen.page_ref[page] == 1:
                del self._index[h]
                del self._by_page[page]
                self.gen.decref(page)
                self.gen._pm["evictions"].inc()
                # structured journal (ISSUE 20): eviction with the
                # pressure numbers — the counter above only counts
                from znicz_tpu import telemetry
                telemetry.emit(
                    "prefix_evict", "serving", page=int(page),
                    indexed=len(self._index),
                    kv_occupancy=round(self.gen.occupancy(), 4))
                return True
        return False


class GenerationRunner:
    """The autoregressive generation compute plane (ISSUE 16), block-
    paged with prefix reuse and fused sampling (ISSUE 19).

    **Pool**: per attention layer, ONE ``(num_pages + 1, page_size,
    heads, head_dim)`` device array for keys and one for values —
    committed at creation (an uncommitted first-call pool leaves a
    stale lowering per shape that jax silently re-lowers under
    steady-state traffic).  Page index ``num_pages`` is SCRATCH — pad
    batch rows gather from and scatter into it, so a pad row can never
    touch a real request's page and every real row stays a pure
    function of its own pages (the per-decoded-token bit-exactness
    contract rides on this).  A request's cache is a host-side page
    list; dispatches carry it as a (batch, P) int32 page table padded
    to power-of-two page-count rungs ``P``.  Growing a request's cache
    is a host-side list append — the old per-rung slot pools and the
    rung-migration executable family are gone entirely; one whole-page
    COPY executable remains, for copy-on-write.

    **Prefix reuse**: :class:`PrefixCache` — full pages are content-
    addressed by a chain hash of the tokens they hold, shared read-only
    across requests via refcount, copy-on-write on the first divergent
    append.

    **Executables** (all tick the owning runner's ``compiles`` counter,
    so the serving gates' zero-recompile proof covers generation):

      - prefill: one per (prefill batch rung x page rung) — runs the
        forward over ONE fixed-width ``prefill_chunk`` token chunk at
        per-row global offsets ``t0`` (long prompts prefill across
        ticks, the cache carried by the page table — chunked prefill
        bounds the work any single tick can absorb), scatters the
        chunk's k/v into the pool (pad tokens -> scratch), and samples
        each row's next token at its last real position in-graph;
      - decode: one per (decode batch rung x page rung) — gathers the
        co-batched requests' pages, appends this step's k/v row at each
        row's own depth ``t``, attends the length-1 query over
        ``[0..t]``, samples in-graph.  O(t) per token;
      - copy: whole-page copy (src -> dst), the COW move.

    Sampling is FUSED into both compute executables — they return
    ``(tokens, logprobs, logits, pools)`` and transfers happen per
    FETCHED array, so the scheduler's on-device-sampling mode ships
    (b,) int32 tokens instead of (b, vocab) logits per tick.  The
    executable family is one and the same either way, which makes
    greedy bit-identity across the knob free and keeps
    ``return_logits`` costless until requested.

    Single-device only (the serving mesh and generation compose
    later); compute calls are serialized by the frontend's compute
    thread — page bookkeeping is not locked, by that contract."""

    def __init__(self, runner: ModelRunner, page_size: int,
                 num_pages: int, slots: int, prefill_chunk: int,
                 prefix_cache: bool = True, prefill_rungs=None,
                 decode_rungs=None):
        import jax
        import jax.numpy as jnp

        from znicz_tpu import telemetry
        from znicz_tpu.attention import (CharEmbedding, MultiHeadAttention,
                                         SeqAll2All)
        from znicz_tpu.ops.attention import paged_append, paged_gather
        from znicz_tpu.ops.linear import seq_linear

        if runner.mesh is not None:
            raise ValueError(
                "generation serving is single-device for now (the "
                "KV-cache pool does not shard); drop "
                "root.common.serving.mesh for this replica")
        self.runner = runner
        tr = runner._trainer
        forwards = runner.workflow.forwards
        last = forwards[-1]
        if not forwards or not isinstance(forwards[0], CharEmbedding):
            raise ValueError(
                "generation serving needs a CharEmbedding first unit "
                "(token ids in, one position per token)")
        if not isinstance(last, tr._seq_softmax_cls):
            raise ValueError(
                "generation serving needs a per-position softmax head "
                "(SeqAll2AllSoftmax) as the last unit")
        self._attn = []
        for f in forwards[1:-1]:
            if isinstance(f, MultiHeadAttention):
                if not f.causal:
                    raise ValueError(
                        f"{f.name}: generation requires causal "
                        f"attention (a KV cache IS the causal prefix)")
                self._attn.append(f)
            elif isinstance(f, (SeqAll2All, tr._dropout_cls)):
                pass                       # position-wise / eval-identity
            else:
                raise ValueError(
                    f"{f.name}: unit {type(f).__name__} has no decode "
                    f"form — generation serves CharEmbedding + causal "
                    f"MultiHeadAttention + SeqAll2All* stacks")
        if not self._attn:
            raise ValueError("generation serving needs at least one "
                             "MultiHeadAttention unit (nothing to cache)")
        self.max_len = int(forwards[0].max_len)
        self.page_size = int(page_size)
        if self.page_size < 2:
            raise ValueError(f"page_size must be >= 2, got {page_size}")
        self.num_pages = int(num_pages)
        pages_per_seq = -(-self.max_len // self.page_size)
        if self.num_pages < pages_per_seq:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one full context "
                f"window ({pages_per_seq} pages of {self.page_size} "
                f"for max_len={self.max_len})")
        #: scratch page index — pad rows' page; never allocated
        self.scratch = self.num_pages
        rungs = []
        r = 1
        while r < pages_per_seq:
            rungs.append(r)
            r *= 2
        rungs.append(r)
        #: page-table width rungs: powers of two up to a full context's
        #: page count — the executable family's second axis
        self.page_rungs = tuple(rungs)
        #: the context window: positions ``[0 .. max_ctx)`` are the most
        #: any one request (prompt + generated) may occupy
        self.max_ctx = self.max_len
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError("generation needs >= 1 concurrency slot")
        self.prefill_rungs = tuple(prefill_rungs) if prefill_rungs \
            else batch_rungs(4)
        self.decode_rungs = tuple(decode_rungs) if decode_rungs \
            else batch_rungs(self.slots)
        shapes = {f.name: (f.heads, f.head_dim) for f in self._attn}
        #: the pool: {layer: (num_pages+1, page_size, heads, dim)} x (k, v)
        # commit the fresh pools to an explicit device: every later pool
        # array is a COMMITTED donated jit output, and an uncommitted
        # first-call pool would leave one stale lowering that jax
        # silently re-lowers (cache growth without a retrace) the first
        # time steady-state traffic replays that shape
        dev = jax.local_devices()[0]
        self.pk = {n: jax.device_put(
                       jnp.zeros((self.num_pages + 1, self.page_size,
                                  h, d), jnp.float32), dev)
                   for n, (h, d) in shapes.items()}
        self.pv = {n: jax.device_put(
                       jnp.zeros((self.num_pages + 1, self.page_size,
                                  h, d), jnp.float32), dev)
                   for n, (h, d) in shapes.items()}
        #: host-side page allocator (compute-thread only, like the old
        #: slot free lists): free stack + per-page refcounts
        self._free_pages = list(range(self.num_pages))
        self.page_ref = np.zeros(self.num_pages, np.int32)
        #: ~2 flops per weight per token — the prefill-FLOPs-avoided
        #: counter's conversion rate
        self.flops_per_token = 2 * sum(
            int(arr.mem.size) for f in forwards
            for arr in f.params().values() if arr.mem is not None)
        _pc = telemetry.scope("prefix_cache")
        self._pm = {
            "hits": _pc.counter(
                "hits", "prompt prefix lookups that matched (>= 1 "
                "full page shared)"),
            "misses": _pc.counter(
                "misses", "prompt prefix lookups that matched nothing"),
            "evictions": _pc.counter(
                "evictions", "indexed prefix pages evicted under "
                "allocation pressure (LRU, idle entries only)"),
            "tokens_avoided": _pc.counter(
                "tokens_avoided", "prompt tokens NOT prefilled thanks "
                "to prefix-page hits"),
            "flops_avoided": _pc.counter(
                "flops_avoided", "prefill flops avoided by prefix "
                "reuse (tokens_avoided x ~2 flops/weight)"),
        }
        _pc.gauge("indexed_pages", "pages held by the prefix index",
                  fn=telemetry.weak_fn(
                      self, lambda s: float(len(s.prefix))
                      if s.prefix is not None else 0.0))
        _pc.gauge("shared_pages", "pages referenced by > 1 holder",
                  fn=telemetry.weak_fn(
                      self, lambda s: float((s.page_ref > 1).sum())))
        _pc.gauge("page_occupancy", "allocated pages / pool pages",
                  fn=telemetry.weak_fn(self, lambda s: s.occupancy()))
        self.prefix = PrefixCache(self) if prefix_cache else None
        compiles = runner._m["compiles"]
        seq_softmax = tr._seq_softmax_cls
        dropout = tr._dropout_cls
        n_pages, psz = self.num_pages, self.page_size

        def run_prefill(params, pk, pv, table, x, t0, n_new,
                        temp, top_k, seeds):
            compiles.inc()      # znicz: ignore[jit-purity] — trace tick
            toks = tr._decode(x)
            h = None
            rows = {}
            for f in forwards:
                p = params.get(f.name, {})
                if isinstance(f, CharEmbedding):
                    h = f.apply_offset(p, toks, t0)
                elif isinstance(f, MultiHeadAttention):
                    h, k_rows, v_rows = f.apply_prefill_chunk(
                        p, h, paged_gather(pk[f.name], table),
                        paged_gather(pv[f.name], table), t0)
                    rows[f.name] = (k_rows, v_rows)
                elif f is last and isinstance(f, seq_softmax):
                    h = seq_linear(h, p["weights"], p.get("bias"),
                                   weights_transposed=f.weights_transposed)
                elif isinstance(f, dropout):
                    pass
                else:
                    h = f.apply(p, h)
            b, c = x.shape[:2]
            width = table.shape[1]
            logits = h[jnp.arange(b), n_new - 1]
            # persist the chunk's k/v: token j of row i lands on page
            # table[i, (t0+j) // page_size] at offset (t0+j) %
            # page_size; pad tokens (j >= n_new) land on scratch
            pos = t0[:, None] + jnp.arange(c)
            page = table[jnp.arange(b)[:, None],
                         jnp.clip(pos // psz, 0, width - 1)]
            page = jnp.where(jnp.arange(c)[None, :] < n_new[:, None],
                             page, n_pages)
            off = pos % psz
            pk = {n: pk[n].at[page, off].set(rows[n][0]) for n in pk}
            pv = {n: pv[n].at[page, off].set(rows[n][1]) for n in pv}
            tok, logp = _sample_tokens(logits, temp, top_k, seeds,
                                       t0 + n_new - 1)
            return tok, logp, logits, pk, pv

        def run_decode(params, pk, pv, table, tokens, t,
                       temp, top_k, seeds):
            compiles.inc()      # znicz: ignore[jit-purity] — trace tick
            toks = tr._decode(tokens)
            h = None
            rows = {}
            for f in forwards:
                p = params.get(f.name, {})
                if isinstance(f, CharEmbedding):
                    h = f.apply_decode(p, toks, t)
                elif isinstance(f, MultiHeadAttention):
                    h, k_row, v_row = f.apply_decode(
                        p, h, paged_gather(pk[f.name], table),
                        paged_gather(pv[f.name], table), t)
                    rows[f.name] = (k_row, v_row)
                elif f is last and isinstance(f, seq_softmax):
                    h = seq_linear(h, p["weights"], p.get("bias"),
                                   weights_transposed=f.weights_transposed)
                elif isinstance(f, dropout):
                    pass
                else:
                    h = f.apply(p, h)
            logits = h[:, 0]
            pk = {n: paged_append(pk[n], table, rows[n][0], t)
                  for n in pk}
            pv = {n: paged_append(pv[n], table, rows[n][1], t)
                  for n in pv}
            tok, logp = _sample_tokens(logits, temp, top_k, seeds, t)
            return tok, logp, logits, pk, pv

        def run_copy(pk, pv, src, dst):
            compiles.inc()      # znicz: ignore[jit-purity] — trace tick
            pk = {n: pk[n].at[dst].set(pk[n][src]) for n in pk}
            pv = {n: pv[n].at[dst].set(pv[n][src]) for n in pv}
            return pk, pv

        dn = runner.donate
        self._prefill = jax.jit(run_prefill,
                                donate_argnums=(1, 2) if dn else ())
        self._decode = jax.jit(run_decode,
                               donate_argnums=(1, 2) if dn else ())
        self._copy = jax.jit(run_copy,
                             donate_argnums=(0, 1) if dn else ())
        #: AOT dispatch table (ISSUE 17), keyed ("prefill", b, P) /
        #: ("decode", b, P) / ("copy",) — the same grid warmup() walks,
        #: so a cache-warm boot loads the whole generation family
        #: through the owning runner's _aot_exec
        self._aot: Dict = {}

    # -- page bookkeeping (compute-thread only) --------------------------------

    def _page_rung(self, n_pages: int) -> int:
        """Smallest page-table width rung holding ``n_pages`` pages."""
        for r in self.page_rungs:
            if r >= n_pages:
                return r
        raise ValueError(
            f"{n_pages} pages exceed the top rung "
            f"{self.page_rungs[-1]} — the context window bounds this")

    def alloc_page(self) -> Optional[int]:
        """Claim one free page (refcount 1).  Under pressure, evict an
        idle prefix-index page LRU-first; None when every page is held
        by a live request (the scheduler stalls that row a tick)."""
        if not self._free_pages and self.prefix is not None:
            self.prefix.evict_one()
        if not self._free_pages:
            return None
        page = self._free_pages.pop()
        self.page_ref[page] = 1
        return page

    def addref(self, page: int) -> None:
        """One more holder of a shared (read-only) page."""
        self.page_ref[page] += 1

    def decref(self, page: int) -> None:
        """Drop one reference; the page frees at zero."""
        self.page_ref[page] -= 1
        assert self.page_ref[page] >= 0, f"page {page} over-released"
        if self.page_ref[page] == 0:
            self._free_pages.append(page)

    def release_pages(self, pages) -> None:
        """Return a finished/failed request's page references
        immediately — the continuous-batching lever: pages shared with
        the prefix index or other requests survive via their remaining
        refs; private ones are claimable this very tick."""
        for page in pages:
            self.decref(page)

    def pages_active(self) -> int:
        return self.num_pages - len(self._free_pages)

    def pages_leaked(self) -> int:
        """Invariant probe (must be 0): pages neither free nor
        referenced are lost to the allocator forever."""
        return int(self.num_pages - len(self._free_pages)
                   - int((self.page_ref > 0).sum()))

    def occupancy(self) -> float:
        """Allocated pages / pool pages, the KV-pool pressure gauge."""
        return self.pages_active() / float(self.num_pages)

    # -- compute (compute-thread only) -----------------------------------------

    def _batch_rung(self, rungs, n: int) -> int:
        for r in rungs:
            if r >= n:
                return r
        raise ValueError(f"batch of {n} exceeds top rung {rungs[-1]}"
                         f" — the scheduler chunks above this")

    def _run_jit(self, key, jitfn, args):
        """One generation dispatch: plain jit call until the owning
        runner armed its AOT cache, the shared AOT table after.  The
        key's ints are both the table key and the cache entry; the
        entry also carries the paged geometry, so cache entries from a
        differently-paged boot can never collide."""
        r = self.runner
        if r._aot_cache is None:
            return jitfn(*args)
        entry = {"kind": key[0], "key": [int(k) for k in key[1:]],
                 "paged": [self.page_size, self.num_pages,
                           self.prefill_chunk]}
        return r._aot_exec(self._aot, key, entry, jitfn, args)

    def _table(self, page_lists, b: int) -> np.ndarray:
        """Pad per-row page lists into the (b, P) int32 dispatch table:
        P is the page rung over the widest row, unused slots point at
        scratch (positions there sit past every row's fill, so masking
        never lets them matter)."""
        width = self._page_rung(max([len(p) for p in page_lists] + [1]))
        tbl = np.full((b, width), self.scratch, np.int32)
        for i, pages in enumerate(page_lists):
            tbl[i, :len(pages)] = pages
        return tbl

    def _sampling_args(self, b, temps, top_ks, seeds):
        tp = np.zeros((b,), np.float32)
        tp[:len(temps)] = temps
        tk = np.zeros((b,), np.int32)
        tk[:len(top_ks)] = top_ks
        sd = np.zeros((b,), np.uint32)
        sd[:len(seeds)] = seeds
        return tp, tk, sd

    def prefill_async(self, x: np.ndarray, t0s, n_new, page_lists,
                      temps, top_ks, seeds):
        """Dispatch one prefill CHUNK over co-batched rows — row ``i``
        holds prompt tokens ``x[i, :n_new[i]]`` at global positions
        starting ``t0s[i]``, its cache (covering ``[0 .. t0+n_new)``)
        listed in ``page_lists[i]`` — WITHOUT syncing results back:
        returns ((b,) DEVICE next tokens, (b,) DEVICE logprobs,
        (b, vocab) DEVICE logits, snapshot generation).  Rows pad to a
        prefill batch rung against the scratch page.  The sampled
        token is the row's next token only when this chunk completes
        its prompt — intermediate chunks' samples are discarded."""
        n, c = x.shape
        if c != self.prefill_chunk:
            raise ValueError(f"chunk width {c} != prefill_chunk "
                             f"{self.prefill_chunk}")
        b = self._batch_rung(self.prefill_rungs, n)
        xb = np.zeros((b, c), self.runner.dtype)
        xb[:n] = x
        t0 = np.zeros((b,), np.int32)
        t0[:n] = t0s
        nn = np.ones((b,), np.int32)
        nn[:n] = n_new
        tbl = self._table(list(page_lists) + [[]] * (b - n), b)
        tp, tk, sd = self._sampling_args(b, temps, top_ks, seeds)
        self.runner._maybe_stall()
        params, gen = self.runner._active
        tok, logp, logits, self.pk, self.pv = self._run_jit(
            ("prefill", b, tbl.shape[1]), self._prefill,
            (params, self.pk, self.pv, tbl, xb, t0, nn, tp, tk, sd))
        return tok, logp, logits, gen

    def prefill(self, x: np.ndarray, t0s, n_new, page_lists,
                temps, top_ks, seeds):
        """Synchronous :meth:`prefill_async` (host arrays, sliced to
        the real rows)."""
        tok, logp, logits, gen = self.prefill_async(
            x, t0s, n_new, page_lists, temps, top_ks, seeds)
        n = len(page_lists)
        return (np.asarray(tok)[:n], np.asarray(logp)[:n],
                np.asarray(logits)[:n], gen)

    def decode_async(self, page_lists, tokens, ts, temps, top_ks,
                     seeds):
        """Dispatch one decode step over co-batched requests — feed
        each row's ``tokens[i]`` at its own depth ``ts[i]``, append
        k/v into its paged cache — WITHOUT syncing results back:
        returns ((b,) DEVICE next tokens, (b,) DEVICE logprobs,
        (b, vocab) DEVICE logits, snapshot generation).  The scheduler
        dispatches every chunk of a tick before fetching any, so chunk
        N's compute overlaps chunk N-1's host-side emit."""
        n = len(page_lists)
        b = self._batch_rung(self.decode_rungs, n)
        tbl = self._table(list(page_lists) + [[]] * (b - n), b)
        tk_in = np.zeros((b,), self.runner.dtype)
        tk_in[:n] = tokens
        tt = np.zeros((b,), np.int32)
        tt[:n] = ts
        tp, tk, sd = self._sampling_args(b, temps, top_ks, seeds)
        self.runner._maybe_stall()
        params, gen = self.runner._active
        tok, logp, logits, self.pk, self.pv = self._run_jit(
            ("decode", b, tbl.shape[1]), self._decode,
            (params, self.pk, self.pv, tbl, tk_in, tt, tp, tk, sd))
        return tok, logp, logits, gen

    def decode(self, page_lists, tokens, ts, temps, top_ks, seeds):
        """Synchronous :meth:`decode_async` (host arrays, sliced to
        the real rows)."""
        tok, logp, logits, gen = self.decode_async(
            page_lists, tokens, ts, temps, top_ks, seeds)
        n = len(page_lists)
        return (np.asarray(tok)[:n], np.asarray(logp)[:n],
                np.asarray(logits)[:n], gen)

    def copy_page(self, src: int, dst: int) -> None:
        """Whole-page copy (the COW move): duplicate page ``src`` into
        ``dst`` across every layer's k and v pools.  Reference
        bookkeeping is the caller's."""
        self.pk, self.pv = self._run_jit(
            ("copy",), self._copy,
            (self.pk, self.pv, np.int32(src), np.int32(dst)))

    # -- contract surface ------------------------------------------------------

    def executables(self) -> int:
        """The warmed generation executable count — the zero-recompile
        gate's expected jit-cache contribution."""
        return ((len(self.prefill_rungs) + len(self.decode_rungs))
                * len(self.page_rungs) + 1)

    def warmup(self) -> int:
        """Compile the full generation executable family up front (all
        rows against the scratch page — no real page is touched);
        returns the owning runner's total ``compiles`` afterwards."""
        c = self.prefill_chunk
        for b in self.prefill_rungs:
            for width in self.page_rungs:
                self.prefill(np.zeros((b, c), self.runner.dtype),
                             np.zeros(b, np.int32),
                             np.ones(b, np.int32),
                             [[self.scratch] * width] * b,
                             np.zeros(b, np.float32),
                             np.zeros(b, np.int32),
                             np.zeros(b, np.uint32))
        for b in self.decode_rungs:
            for width in self.page_rungs:
                self.decode([[self.scratch] * width] * b,
                            np.zeros(b, np.int64),
                            np.zeros(b, np.int32),
                            np.zeros(b, np.float32),
                            np.zeros(b, np.int32),
                            np.zeros(b, np.uint32))
        self.copy_page(self.scratch, self.scratch)
        return self.runner.compiles

    def jit_cache_size(self) -> Optional[int]:
        """Sum of jax's own cache entries across the three generation
        jits (None where the jax version hides it) — after warmup this
        equals :meth:`executables` and must stay put."""
        try:
            return int(self._prefill._cache_size()
                       + self._decode._cache_size()
                       + self._copy._cache_size())
        except Exception:           # pragma: no cover - jax-version dep
            return None

    def stats(self) -> Dict:
        return {"page_size": self.page_size,
                "num_pages": self.num_pages,
                "page_rungs": list(self.page_rungs),
                "prefill_chunk": self.prefill_chunk,
                "max_ctx": self.max_ctx,
                "slots": self.slots,
                "prefill_rungs": list(self.prefill_rungs),
                "decode_rungs": list(self.decode_rungs),
                "pages_active": self.pages_active(),
                "pages_free": len(self._free_pages),
                "pages_shared": int((self.page_ref > 1).sum()),
                "pages_leaked": self.pages_leaked(),
                "prefix_enabled": self.prefix is not None,
                "prefix_pages": (len(self.prefix)
                                 if self.prefix is not None else 0),
                "prefix_hits": int(self._pm["hits"].value),
                "prefix_misses": int(self._pm["misses"].value),
                "prefix_evictions": int(self._pm["evictions"].value),
                "prefix_tokens_avoided":
                    int(self._pm["tokens_avoided"].value),
                "occupancy": self.occupancy(),
                "executables": self.executables(),
                "aot_loaded": len(self._aot),
                "jit_cache_size": self.jit_cache_size()}
