"""Global dotted config tree.

TPU-native rebuild of the reference's ``veles/config.py`` (SURVEY.md §2.1
"Config"): a global attribute-tree ``root`` that sample configs mutate
(``root.mnistr.decision.max_epochs = 3``) and that the CLI can override with
dotted ``key.path=value`` arguments.  Unlike the reference we also support
snapshot/restore of subtrees to plain dicts (used by the snapshotter to make
checkpoints self-describing).
"""

from __future__ import annotations

import ast
from typing import Any, Dict, Iterator, Tuple


class Config:
    """An attribute tree node.  Accessing an unknown attribute creates a child
    ``Config``, so configs can be assigned deeply without pre-declaration::

        root.mnist.loader.minibatch_size = 60
    """

    def __init__(self, path: str = "") -> None:
        # NB: use object.__setattr__ to dodge our own __setattr__ guard.
        object.__setattr__(self, "_path", path)
        object.__setattr__(self, "_children", {})

    # -- tree access ---------------------------------------------------------

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        children = object.__getattribute__(self, "_children")
        if name not in children:
            children[name] = Config(self._join(name))
        return children[name]

    def __setattr__(self, name: str, value: Any) -> None:
        if name.startswith("_"):
            object.__setattr__(self, name, value)
            return
        if isinstance(value, dict):
            node = Config(self._join(name))
            node.update(value)
            value = node
        self._children[name] = value

    def __delattr__(self, name: str) -> None:
        self._children.pop(name, None)

    def _join(self, name: str) -> str:
        return f"{self._path}.{name}" if self._path else name

    # -- dict-ish API --------------------------------------------------------

    def update(self, values: Dict[str, Any]) -> "Config":
        """Recursively merge a plain dict into this subtree."""
        for key, value in values.items():
            if isinstance(value, dict):
                child = getattr(self, key)
                if not isinstance(child, Config):
                    child = Config(self._join(key))
                    self._children[key] = child
                child.update(value)
            else:
                setattr(self, key, value)
        return self

    def defaults(self, values: Dict[str, Any]) -> "Config":
        """Like update(), but existing leaves win — sample modules use this
        so user/CLI overrides set before import are not clobbered."""
        for key, value in values.items():
            existing = self._children.get(key)
            # An empty Config node is what a mere *read* autovivifies —
            # treat it as absent (same rule get() uses), not as user-set.
            is_vacant = (existing is None or
                         (isinstance(existing, Config) and not existing))
            if isinstance(value, dict):
                if existing is not None and isinstance(existing, Config):
                    existing.defaults(value)
                elif is_vacant:
                    setattr(self, key, value)
                # else: user set a leaf where we default a subtree — user wins
            elif is_vacant:
                setattr(self, key, value)
        return self

    def get(self, name: str, default: Any = None) -> Any:
        """Return a leaf value, or ``default`` if absent or still a bare node."""
        value = self._children.get(name, default)
        if isinstance(value, Config) and not value._children:
            return default
        return value

    def items(self) -> Iterator[Tuple[str, Any]]:
        return iter(self._children.items())

    def __contains__(self, name: str) -> bool:
        return name in self._children

    def __bool__(self) -> bool:
        return bool(self._children)

    def to_dict(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for key, value in self._children.items():
            out[key] = value.to_dict() if isinstance(value, Config) else value
        return out

    def __repr__(self) -> str:
        return f"Config({self._path!r}: {self.to_dict()!r})"

    # -- dotted-path access (CLI overrides) ----------------------------------

    def set_by_path(self, dotted: str, value: Any) -> None:
        """Set a leaf from dotted input that comes from outside the
        program (the launcher's overrides, ``benchmark/tools/run_with.py``,
        the benchmark's drivers).  The tree autovivifies, so this is where
        an engine knob that ``ENGINE_DEFAULTS`` does not declare — a typo,
        or a knob that was removed — is refused instead of silently
        training on the default."""
        parts = dotted.split(".")
        full = self._join(dotted).split(".")
        if full[:3] == ["root", "common", "engine"]:
            table: Any = ENGINE_DEFAULTS
            for part in full[3:]:
                if not isinstance(table, dict):
                    break
                if part not in table:
                    raise KeyError(
                        f"{'.'.join(full)}: no engine knob "
                        f"{'.'.join(full[3:])!r} is declared in "
                        "znicz_tpu.core.config.ENGINE_DEFAULTS")
                table = table[part]
        node: Config = self
        for part in parts[:-1]:
            node = getattr(node, part)
            if not isinstance(node, Config):
                raise KeyError(f"{dotted}: {part} is a leaf, not a subtree")
        setattr(node, parts[-1], value)

    def get_by_path(self, dotted: str, default: Any = None) -> Any:
        parts = dotted.split(".")
        node: Any = self
        for part in parts[:-1]:
            if not isinstance(node, Config):
                return default
            node = node._children.get(part)
        if not isinstance(node, Config):
            return default
        return node.get(parts[-1], default)


def parse_override(arg: str) -> Tuple[str, Any]:
    """Parse one CLI override ``a.b.c=value``; value via literal_eval with a
    string fallback (so ``root.x.path=/tmp/foo`` works unquoted)."""
    if "=" not in arg:
        raise ValueError(f"override must look like key.path=value, got {arg!r}")
    key, raw = arg.split("=", 1)
    key = key.strip()
    if key.startswith("root."):
        key = key[len("root."):]
    try:
        value = ast.literal_eval(raw)
    except (ValueError, SyntaxError):
        value = raw
    return key, value


def apply_overrides(cfg: "Config", args: list[str]) -> None:
    for arg in args:
        key, value = parse_override(arg)
        cfg.set_by_path(key, value)


#: The global config tree, mirroring the reference's ``veles.config.root``.
root = Config("root")

# Engine-wide defaults (the reference kept these under root.common.*).
root.common.engine.seed = 1013
root.common.engine.backend = "auto"      # "tpu" | "cpu" | "auto"
root.common.dirs.snapshots = "snapshots"
root.common.dirs.cache = ".znicz_cache"
root.common.dirs.datasets = "datasets"

#: Declaration table for every ``root.common.engine.*`` knob the package
#: reads (ISSUE 7 satellite — the serving DEFAULTS discipline extended to
#: the engine tree).  The Config tree autovivifies, so an undeclared or
#: typo'd knob silently reads as its default forever under dotted CLI
#: overrides; tests/test_no_adhoc_counters.py greps every literal
#: ``root.common.engine`` access in the package and fails on keys missing
#: here.  Values are the DOCUMENTED defaults (the read sites keep their
#: own — this table declares, it does not apply).
ENGINE_DEFAULTS = {
    # core
    "seed": 1013,
    "backend": "auto",            # "tpu" | "cpu" | "auto"
    "fused": False,               # launcher --fused (fast-path engine)
    # precision
    "compute_dtype": "float32",   # "float32" | "bf16"/"bfloat16"
    "master_dtype": "float32",    # bf16-STORED master weights (variant)
    "state_dtype": "float32",     # optimizer-state (velocity) storage
    # fused-trainer shape
    "scan_chunk": 8,
    "pipeline_depth": 1,          # >1: whole-epoch dispatches (fused.py)
    "async_snapshot": True,
    # fusion experiments / kernels
    "fused_elementwise": False,   # conv1/conv2 single-pass Pallas block
    "fused_tail": False,          # ISSUE 7: conv3-5 + FC + loss epilogues
    # ingest / staging (ISSUE 7)
    "prefetch_segments": 2,
    "decode_workers": None,
    "stream_budget_mb": None,
    "native_shuffle": False,
    "async_staging": True,        # double-buffered device staging
    "staging_donate": True,       # donate staged buffers (non-CPU)
    "xla_latency_hiding": False,  # XLA latency-hiding-scheduler flags
    # snapshots
    "snapshot_format": "pickle",
    "snapshot_sharded": False,
    "snapshot_min_interval_s": 0.0,
    # master/slave roles + wire
    "mode": "",                   # "" | "master" | "slave"
    "master_bind": "tcp://*:5570",
    "master_resume": "",
    "slave_endpoint": None,
    "job_segment": 1,
    "job_prefetch": True,
    "job_timeout_mult": 8.0,
    "slave_ttl": 60.0,
    "slave_reconnects": 8,
    "slave_backoff_base": 0.25,
    "slave_backoff_cap": 5.0,
    # unified transport core (ISSUE 14)
    "slave_breaker_failures": 4,  # consecutive transport failures that
    #                               open the training client's breaker
    #                               (fail-fast to a dead master); 0 off
    "ingress_rate_limit": 0.0,    # per-slave JOB requests/s the master
    #                               admits (flood -> wait); 0 = off
    "ingress_rate_burst": 0.0,    # bucket capacity; 0 = auto (1s rate)
    "job_deadline": True,         # stamp deadline_ms budgets on jobs;
    #                               expired jobs drop at slave/relay
    # fleet observability (ISSUE 20): training-plane SLO — apply
    # progress (accepted delta applies vs refused/stale/quarantined),
    # advisory burn rates on /slo.json, never a readiness gate
    "obs_slo_apply_progress": 0.99,
    "obs_slo_fast_window_s": 60.0,
    "obs_slo_slow_window_s": 600.0,
    "quarantine_norm_mult": 25.0,
    "master_snapshot_s": 10.0,
    "wire_dtype": "float32",      # "float32" | "bfloat16" | "int8"
    "wire_compress": "none",      # "none" | "zlib" | "lz4"
    # relay-tree aggregation (ISSUE 10)
    "tree_fanout": 2,             # children per relay; job-batch factor
    "relay_flush_s": 0.05,        # max buffered-contribution age
    "relay_child_ttl": 30.0,      # relay-tier child eviction window (a
    #                               tree wants a SHORTER leaf TTL than
    #                               the master's relay TTL: slave_ttl)
    # sequence workloads (ISSUE 15)
    "seq_parallel": 0,            # ring-attention sp mesh size for
    #                               MultiHeadAttention (0/1 = off; the
    #                               single-device path, bit-exact)
    # pod-sliced training (ISSUE 18): each slave/relay leaf a mesh slice
    "train_shard": False,         # gate; OFF = single-device bit-exact
    #                               whatever the mesh knobs say
    "mesh": {                     # the training slice (train_shard on):
        "data": 1,                # batch sharding over ICI (psum tier)
        "model": 1,               # column-sharded wide FC weights
    },
    # elastic async training (ISSUE 11)
    "min_slaves": 0,              # quorum gate; 0 = no gate
    "staleness_bound": 0,         # refuse deltas staler than this many
    #                               applies (re-queued); 0 = unbounded
    "staleness_weight": False,    # scale applies by 1/(1+staleness)
    "elastic_rehome": False,      # master redirects orphan leaves that
    #                               register directly to a live relay
}
