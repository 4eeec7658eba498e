"""Device abstraction (rebuild of ``veles/backends.py``).

The reference discovered OpenCL/CUDA devices, owned contexts/queues and
compiled kernels.  On TPU all of that is PJRT+XLA's job, so ``Device`` shrinks
to: which jax backend ("tpu"/"cpu"), which jax device(s), and — the genuinely
new part — the **mesh** used for SPMD sharding (the rebuild's replacement for
the reference's master/slave distribution, SURVEY.md §2.4).
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Optional, Sequence, Tuple

import numpy as np

#: XLA latency-hiding-scheduler flags (ISSUE 7, lever c): reorder the TPU
#: schedule so async copies (the staged-segment H2D puts, collective
#: permutes) overlap compute instead of serializing at their use sites —
#: the compiler-side half of the ingest/compute overlap the DeviceStager
#: provides on the host side.  Published flag set (the standard pairing
#: quoted in the JAX/maxtext perf guides); TPU-only semantics, harmless
#: but useless text on CPU — the knob below gates them off by default.
LATENCY_HIDING_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_tpu_host_transfer_overlap_limit=8",
    "--xla_latency_hiding_scheduler_rerun=2",
)


def configure_xla_flags(environ=None) -> Tuple[str, ...]:
    """Append the latency-hiding-scheduler flags to ``XLA_FLAGS`` when
    ``root.common.engine.xla_latency_hiding`` is on (default OFF — an
    undecided lever, ROADMAP.md "Undecided levers").  MUST run before the first jax backend
    initialization — the launcher calls it right after config/overrides
    are applied; if a backend already exists the env change is inert, so
    this warns instead of silently lying.  Idempotent (flags already
    present are not duplicated).  Returns the flags newly appended."""
    from znicz_tpu.core.config import root

    if environ is None:
        environ = os.environ
    if not bool(root.common.engine.get("xla_latency_hiding", False)):
        return ()
    current = environ.get("XLA_FLAGS", "")
    # dedup by flag NAME, not full string: a flag the operator already
    # set (any value) is respected, never shadowed by an appended
    # duplicate (XLA parses last-wins)
    fresh = tuple(f for f in LATENCY_HIDING_XLA_FLAGS
                  if f.split("=", 1)[0] not in current)
    if not fresh:
        return ()
    jax = sys.modules.get("jax")
    # the inert-after-init refusal applies to the REAL process env only
    # (a scratch dict is a harness inspecting what WOULD be applied)
    if jax is not None and environ is os.environ:
        try:
            initialized = bool(
                jax._src.xla_bridge._backends)  # noqa: SLF001
        except Exception:               # pragma: no cover - jax internals
            initialized = False
        if initialized:
            print("warning: xla_latency_hiding set after the jax backend "
                  "initialized — XLA_FLAGS changes are inert now; set the "
                  "knob via config/CLI overrides (the launcher applies "
                  "them before building the workflow)", file=sys.stderr)
            return ()
    environ["XLA_FLAGS"] = (current + " " + " ".join(fresh)).strip()
    return fresh


def checkout_dir() -> str:
    """The checkout root (the directory holding ``znicz_tpu/``), found
    from this file — never from the working directory."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_dir() -> str:
    """``root.common.dirs.cache``, a relative value resolved against the
    checkout: what the program builds (the native library, compiled
    executables) is found again from any working directory."""
    from znicz_tpu.core.config import root

    return os.path.join(checkout_dir(),
                        str(root.common.dirs.get("cache", ".znicz_cache")))


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory
    in effect.  Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it
    itself and the program sets no directory in code — whoever runs the
    program owns the placement.  Otherwise the cache lives at
    ``<cache_dir>/jax``: a fixed path (it is part of the cache key, so a
    directory that moves never hits).  Every executable is kept,
    however quick its compile, so a second run of the same program adds
    no entry; the key includes the operations' metadata, so an entry is
    only ever fetched by the source that wrote it.  Call before the
    first compile."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # the names the program gives its operations (``jax.named_scope`` in
    # the fused step) are metadata, which the cache's key leaves out by
    # default: a tree without them, or with other unit names, would hand
    # this one executables whose trace names nothing — silently, wherever
    # two checkouts share one directory (the chip tool's machines do)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(cache_dir(), "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@functools.cache
def pallas_interpret() -> bool:
    """Whether the Pallas kernels run interpreted: only on the ``cpu``
    backend, which has no Mosaic compiler (said once, at INFO).  Any
    other backend compiles them, and a kernel its compiler refuses fails
    loudly instead of quietly running interpreted."""
    import jax

    if jax.default_backend() != "cpu":
        return False
    import logging

    logging.getLogger("znicz").info(
        "Pallas kernels run in interpret mode on the cpu backend")
    return True


class Device:
    """A compute placement: one jax device for unit-at-a-time execution plus
    an optional mesh for fused SPMD train steps."""

    def __init__(self, platform: str = "auto",
                 mesh_shape: Optional[Tuple[int, ...]] = None,
                 mesh_axes: Sequence[str] = ("data",)) -> None:
        import jax

        if platform == "auto":
            platform = jax.default_backend()
        self.platform = platform
        self.jax_devices = jax.devices(platform)
        # unit-at-a-time placement must be a device THIS process owns:
        # under jax.distributed, jax.devices()[0] is global device 0,
        # which other processes cannot address
        self.jax_device = jax.local_devices(backend=platform)[0]
        self._mesh = None
        self._mesh_shape = mesh_shape
        self._mesh_axes = tuple(mesh_axes)

    # -- constructors --------------------------------------------------------

    @classmethod
    def auto(cls) -> "Device":
        from znicz_tpu.core.config import root

        return cls(platform=root.common.engine.get("backend", "auto"))

    @classmethod
    def cpu(cls) -> "Device":
        return cls(platform="cpu")

    # -- mesh ----------------------------------------------------------------

    @property
    def mesh(self):
        """The jax Mesh for SPMD steps; defaults to all devices on one
        ``data`` axis (pure data parallelism, the reference's only mode)."""
        if self._mesh is None:
            from jax.sharding import Mesh

            shape = self._mesh_shape or (len(self.jax_devices),)
            n = int(np.prod(shape))
            devs = np.asarray(self.jax_devices[:n]).reshape(shape)
            self._mesh = Mesh(devs, self._mesh_axes)
        return self._mesh

    def set_mesh(self, shape: Tuple[int, ...], axes: Sequence[str]) -> None:
        self._mesh = None
        self._mesh_shape = tuple(shape)
        self._mesh_axes = tuple(axes)

    @property
    def n_devices(self) -> int:
        return len(self.jax_devices)

    @property
    def is_tpu(self) -> bool:
        return self.platform not in ("cpu",)

    def __repr__(self) -> str:
        return (f"Device({self.platform}, n={self.n_devices}, "
                f"mesh_axes={self._mesh_axes})")
