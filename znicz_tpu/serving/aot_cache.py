"""AOT executable cache (ISSUE 17): serialize every warmed executable
into a content-addressed on-disk cache next to the snapshot, so a
restarted replica LOADS its executable family instead of compiling it.

The paper's economics assume workers die and return constantly; after
PR 15/16 one replica's family is 12 scoring buckets + 22 generation
executables, and a cold compile of that family dominates
boot-to-/readyz.  This cache turns a heal/preemption/canary reboot
into a deserialize pass.

**Mechanism** — ``jax.experimental.serialize_executable``:
``serialize(compiled)`` captures a lowered+compiled executable (XLA
binary + in/out tree defs) and ``deserialize_and_load`` rebuilds a
callable WITHOUT recompiling.  This is deliberately NOT ``jax.export``:
an exported StableHLO module re-runs XLA compilation on load, which
pays the exact cost the cache exists to skip (measured: export-load ~=
cold compile; serialize-load ~3 orders faster on larger families).

**Key design** — one cache file per executable, filename =
``sha256(canonical-JSON({family, entry}))``:

  - the FAMILY key fingerprints everything that determines lowering:
    every unit's param shapes+dtypes (a structural digest — a canary
    snapshot with new weights but the same architecture still hits),
    sample shape, staging dtype, mesh shape, donation flag, and the
    jax/jaxlib/backend/platform versions (an XLA upgrade silently
    invalidates the whole family — different digest, clean miss);
  - the ENTRY key names one executable within the family: the scoring
    bucket shape, or the generation (kind, rungs) tuple — paged
    generation entries (ISSUE 19: ``prefill``/``decode`` keyed (batch
    rung, page rung), plus the ``copy`` COW move) also carry the
    (page_size, num_pages, prefill_chunk) geometry, so two boots with
    different paging never share an entry.

A version bump, mesh change, or architecture change can therefore
never load a stale executable — the filename itself diverges.  Entries
that DO resolve but fail to decode (truncated file, foreign pickle,
tampered key, deserialize error, or — on backends where execution
validates — a first-call failure) are REFUSED readably: counted,
logged with the reason, and recompiled; a refused entry is overwritten
by the fresh store.  The cache is advisory, never trusted.

Wire-in: ``ModelRunner.enable_aot_cache`` (model.py) builds one
``ExecutableCache`` per runner and routes every warmup/dispatch miss
through ``_aot_exec``; counters land in the ``warmup`` telemetry scope
(``znicz_warmup_cache_{hits,misses,stores,refusals}_total``) — the
fleet panel's warm columns read them.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
from typing import Dict, Optional

log = logging.getLogger("znicz.serving")


def dir_for_snapshot(snapshot_path: str) -> str:
    """The cache directory for a snapshot: ``aot_cache/`` NEXT TO the
    snapshot file, so the cache travels with the weights it warms (a
    fleet pulling one promoted snapshot path shares one warm cache)."""
    return os.path.join(
        os.path.dirname(os.path.abspath(snapshot_path)), "aot_cache")


def family_key(runner) -> Dict:
    """The structural fingerprint of one runner's executable family.

    Structural, not byte-content: param SHAPES/dtypes per unit, never
    the weights — swapping in a retrained canary of the same
    architecture keeps hitting (the executable is a pure function of
    avals), while any shape/dtype/mesh/version drift changes the
    digest and misses cleanly."""
    import jax
    import jaxlib

    units = {name: {k: [list(map(int, a.shape)), str(a.dtype)]
                    for k, a in sorted(layer.items())}
             for name, layer in sorted(runner.params.items())}
    try:
        platform_version = str(jax.devices()[0].client.platform_version)
    except Exception:                   # pragma: no cover - backend dep
        platform_version = ""
    return {"units": units,
            "sample_shape": list(map(int, runner.sample_shape)),
            "dtype": str(runner.dtype),
            "mesh": runner.mesh_shape,
            "donate": bool(runner.donate),
            "jax": str(jax.__version__),
            "jaxlib": str(jaxlib.__version__),
            "backend": str(jax.default_backend()),
            "platform_version": platform_version}


class ExecutableCache:
    """One snapshot directory's executable cache for one family.

    ``load``/``store`` move single executables; ``hit``/``miss`` are
    ticked by the runner's dispatch once an entry is VALIDATED (a
    loaded executable that fails its first call is refused, not hit),
    so ``hits + misses == family size`` after warmup and ``misses ==
    compiles`` is the cache half of the boot proof."""

    COUNTERS = {
        "warmup_cache_hits": "executables loaded from the AOT cache "
                             "instead of compiled",
        "warmup_cache_misses": "executables compiled (absent or refused "
                               "cache entry)",
        "warmup_cache_stores": "freshly compiled executables serialized "
                               "into the cache",
        "warmup_cache_refusals": "cache entries refused (corrupt/stale/"
                                 "version-mismatched/failed validation) "
                                 "— recompiled, never trusted",
        "warmup_cache_store_failures": "serialize/write failures (cache "
                                       "stays cold for that entry; "
                                       "serving unaffected)",
    }

    def __init__(self, directory: str, family: Dict):
        from znicz_tpu import telemetry

        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.family = family
        _sc = telemetry.scope("warmup")
        self._m = {name: _sc.counter(name, help)
                   for name, help in self.COUNTERS.items()}
        #: per-instance tallies (the registry counters are process-wide
        #: and latest-wins; proofs read THIS cache's own numbers)
        self._n = {"hits": 0, "misses": 0, "stores": 0, "refusals": 0,
                   "store_failures": 0}

    def _key(self, entry: Dict) -> Dict:
        return {"family": self.family, "entry": entry}

    def _path(self, entry: Dict) -> str:
        digest = hashlib.sha256(
            json.dumps(self._key(entry), sort_keys=True,
                       separators=(",", ":")).encode()).hexdigest()
        return os.path.join(self.directory, digest[:32] + ".aot")

    def load(self, entry: Dict):
        """Deserialize one entry's executable, or None (absent, or
        refused — corrupt pickle, key mismatch from a digest collision
        or tamper, deserialize failure).  The caller validates and
        ticks hit/miss; refusals are counted HERE so every unreadable
        entry surfaces in ``znicz_warmup_cache_refusals_total``."""
        path = self._path(entry)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
            if blob.get("key") != self._key(entry):
                raise ValueError("cached key does not match the "
                                 "requested entry (stale or tampered)")
            import jax
            from jax.experimental import serialize_executable as se

            # load onto the devices it was compiled for: the default is
            # EVERY device of the backend, which a single-device (or
            # sub-mesh) executable refuses at its first call
            by_id = {d.id: d for d in jax.devices()}
            return se.deserialize_and_load(
                *blob["payload"],
                execution_devices=[by_id[i] for i in blob["devices"]])
        except Exception as exc:
            self.refuse(entry, exc)
            return None

    def store(self, entry: Dict, compiled) -> bool:
        """Serialize one freshly compiled executable (atomic write —
        a half-written entry must never survive a crash to be refused
        on every boot after).  A failure leaves the cache cold for
        this entry and serving untouched."""
        from znicz_tpu.snapshotter import atomic_write_bytes

        try:
            from jax.experimental import serialize_executable as se

            payload = se.serialize(compiled)
            devices = [d.id for d in
                       compiled.runtime_executable().local_devices()]
            atomic_write_bytes(self._path(entry), pickle.dumps(
                {"key": self._key(entry), "payload": payload,
                 "devices": devices},
                protocol=pickle.HIGHEST_PROTOCOL))
        except Exception as exc:
            self._n["store_failures"] += 1
            self._m["warmup_cache_store_failures"].inc()
            log.warning("aot cache: store failed for %s: %s", entry, exc)
            return False
        self._n["stores"] += 1
        self._m["warmup_cache_stores"].inc()
        return True

    def hit(self) -> None:
        self._n["hits"] += 1
        self._m["warmup_cache_hits"].inc()

    def miss(self) -> None:
        self._n["misses"] += 1
        self._m["warmup_cache_misses"].inc()

    def refuse(self, entry: Dict, exc: BaseException) -> None:
        """A readable refusal: the entry exists but cannot be trusted —
        log WHY (the heal/preemption postmortem reads this), count it,
        and let the caller recompile + overwrite."""
        self._n["refusals"] += 1
        self._m["warmup_cache_refusals"].inc()
        log.warning("aot cache: refused entry %s (%s: %s) — recompiling",
                    entry, type(exc).__name__, exc)

    @property
    def counts(self) -> Dict[str, int]:
        return dict(self._n)

    def stats(self) -> Dict:
        return {"directory": self.directory, **self._n}
