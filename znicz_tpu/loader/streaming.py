"""Streaming loaders: datasets larger than HBM feed the fused scan path
(rebuild of the reference's file-image minibatch streaming, SURVEY.md §2.1
image-loaders row / §3.1 hot loop — the reference assembled every minibatch
on the host and shipped it to the device per step).

TPU-native design, three residency regimes behind ONE loader:

  1. **f32-resident** (small data): behaves exactly like FullBatchLoader —
     the dataset is one HBM array, the fused step gathers on device.
  2. **u8-resident** (medium data): the dataset stays in HBM in its STORAGE
     dtype (uint8) and is decoded to f32 *inside* the jitted step, fused
     into the gather (`FusedTrainer._gather_decode`).  4x more samples
     resident than the f32 layout — an AlexNet set whose f32 form exceeds
     a v5e's 16 GB trains entirely from HBM.  Decode is VPU elementwise
     work that XLA fuses into the first conv's input pipeline; throughput
     is indistinguishable from f32-resident (bench `--stream`).
  3. **host-staged** (large data): the dataset lives on the host (numpy,
     memmap, or decode-on-demand image files).  The fused driver stages
     each scan segment as (K, B, ...) minibatch tensors consumed
     directly by the scan xs — `host_gather` assembles the rows (native
     C++ row gather when available) and ships them batch-sharded over
     the mesh's ``data`` axis (u8 over the wire, decode on device).  In
     a MULTI-HOST run each process gathers ONLY the rows of the batch
     shards its own devices hold (`FusedTrainer._stage_direct`) — the
     SPMD analogue of the reference's per-slave minibatch feed.
     Dispatch is async, so segment N+1's host assembly + transfer
     overlap segment N's device compute (double buffering without
     threads — there is nothing to wait on until the metrics flush).
     Steady state (three-term roofline, bench --stream measures each):
     ``img/s = min(compute rate, H2D bytes/s / bytes-per-sample,
     decode rate)`` — u8 staging needs ~1.6 GB/s for AlexNet-227 at the
     r3 compute rate, i.e. a PCIe-attached TPU host is compute-bound
     on the link; bench --stream records the measured link bandwidth
     next to the throughput so the number explains itself.  The DECODE term is
     served by the host ingest engine (loader/ingest.py): file-backed
     sources decode on an N-worker pool, and the fused driver's
     lookahead prefetches future segments' rows so decode overlaps
     device compute (VERDICT r4 item 1).

The residency regime is chosen at initialize: ``device_budget_bytes``
(kwarg or ``root.common.engine.stream_budget_mb``) caps what may sit in
HBM; a dataset within budget is uploaded once (regime 1/2 by storage
dtype), beyond it stays host-side (regime 3).

Normalization: host-staged u8 data reaches the graph as
``u8 * scale + shift`` (linear decode, the image-pipeline norm).  Nonlinear
normalizers need the f32 path (FullBatchLoader) — asserted, not silent.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from znicz_tpu import native
from znicz_tpu.loader.base import Loader

#: default HBM budget for keeping the dataset resident (bytes); overridden
#: by ``root.common.engine.stream_budget_mb`` or the loader kwarg
DEFAULT_DEVICE_BUDGET = 4 << 30


class HostArraySource:
    """A sample-major numpy (or memmap) array as the streaming data source.
    ``data`` keeps its storage dtype (uint8 passes through to the device
    untouched; float32 is gathered with the native row-gather)."""

    def __init__(self, data: np.ndarray, labels: Optional[np.ndarray] = None,
                 targets: Optional[np.ndarray] = None):
        if data.dtype not in (np.uint8, np.float32):
            data = np.asarray(data, np.float32)
        self.data = data
        self.labels = (None if labels is None
                       else np.asarray(labels, np.int32))
        self.targets = (None if targets is None
                        else np.asarray(targets, np.float32))

    def __len__(self) -> int:
        return len(self.data)

    @property
    def sample_shape(self) -> Tuple[int, ...]:
        return tuple(self.data.shape[1:])

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nbytes(self) -> int:
        return int(self.data.nbytes)

    def gather(self, idx: np.ndarray) -> np.ndarray:
        """Contiguous sample rows for ``idx`` (storage dtype preserved)."""
        if self.data.dtype == np.float32 and not isinstance(
                self.data, np.memmap):
            return native.gather_f32(self.data, idx).reshape(
                (len(idx),) + self.sample_shape)
        return np.ascontiguousarray(np.take(self.data, idx, axis=0))

    def whole(self) -> np.ndarray:
        return np.ascontiguousarray(self.data)


class ImageFileSource:
    """Decode-on-demand image files (the reference's file-image route at
    beyond-HBM scale): rows are decoded u8 only when a segment stages them.
    ``paths``/``labels`` aligned; images resized to ``target_shape``.

    Decode runs on a ``DecodePool`` (loader/ingest.py): ``workers`` threads
    decode a gather's rows in parallel, and ``prefetch(idx)`` starts decode
    for rows a FUTURE segment will stage — the fused driver submits its
    lookahead so decode overlaps device compute.  ``workers`` defaults to
    ``root.common.engine.decode_workers`` (else one per CPU, capped);
    ``workers=0`` forces the serial path.  Pooled and serial decode are
    bit-identical (decode is pure), so the parallelism is invisible to
    training math."""

    def __init__(self, paths: Sequence[str], labels: Sequence[int],
                 target_shape: Tuple[int, int], grayscale: bool = False,
                 workers: Optional[int] = None):
        assert len(paths) == len(labels)
        self.paths = list(paths)
        self.labels = np.asarray(labels, np.int32)
        self.target_shape = tuple(target_shape)
        self.grayscale = bool(grayscale)
        self.targets = None
        self.workers = workers
        self._pool = None

    def __len__(self) -> int:
        return len(self.paths)

    @property
    def sample_shape(self) -> Tuple[int, ...]:
        h, w = self.target_shape
        return (h, w) if self.grayscale else (h, w, 3)

    @property
    def dtype(self):
        return np.dtype(np.uint8)

    @property
    def nbytes(self) -> int:
        return len(self) * int(np.prod(self.sample_shape))

    def _decode_u8(self, path: str) -> np.ndarray:
        from PIL import Image

        with Image.open(path) as img:
            img = img.convert("L" if self.grayscale else "RGB")
            img = img.resize((self.target_shape[1], self.target_shape[0]))
            return np.asarray(img, np.uint8)

    def _decode_row(self, i: int) -> np.ndarray:
        return self._decode_u8(self.paths[i])

    def pool(self):
        """The lazily-created decode pool, or None in serial mode
        (``workers=0``).  Even ``workers=1`` keeps the pool: a single
        worker cannot raise the decode RATE, but prefetched rows still
        decode on the worker thread while the training thread waits on
        device compute — the overlap matters on any host."""
        if self._pool is None:
            from znicz_tpu.loader.ingest import DecodePool, default_workers

            w = (default_workers() if self.workers is None
                 else int(self.workers))
            if w < 1:
                return None
            self._pool = DecodePool(self._decode_row, workers=w)
        return self._pool

    def with_workers(self, workers: int) -> "ImageFileSource":
        """A sibling source over the same files with a different worker
        count (measurement helper — ingest.measure_decode_rate)."""
        return ImageFileSource(self.paths, self.labels, self.target_shape,
                               self.grayscale, workers=workers)

    def prefetch(self, idx: np.ndarray) -> int:
        """Start decoding rows a future gather will consume (bounded;
        see DecodePool.submit).  Returns rows newly submitted."""
        pool = self.pool()
        return pool.submit(idx) if pool is not None else 0

    @property
    def ingest_stats(self) -> Optional[dict]:
        return None if self._pool is None else dict(self._pool.stats)

    def gather(self, idx: np.ndarray) -> np.ndarray:
        pool = self.pool()
        if pool is not None:
            return pool.take(idx)
        return np.stack([self._decode_u8(self.paths[i]) for i in idx])

    def whole(self) -> np.ndarray:
        return self.gather(np.arange(len(self)))


class StreamingLoader(Loader):
    """Loader over a host data source; serves all three residency regimes.

    kwargs beyond Loader's:
      - ``source``: HostArraySource / ImageFileSource (or a raw numpy
        array, wrapped automatically);
      - ``class_lengths``: [test, valid, train] split (default: all TRAIN);
      - ``scale``/``shift``: the on-device u8 decode ``u8*scale + shift``
        (default 1/255, 0 — [0,1] images);
      - ``device_budget_bytes``: HBM residency cap (see module docstring).
    """

    streaming = True

    def __init__(self, workflow=None, name=None, source=None,
                 class_lengths=None, scale=1.0 / 255.0, shift=0.0,
                 device_budget_bytes=None, **kwargs):
        super().__init__(workflow=workflow, name=name, **kwargs)
        from znicz_tpu.memory import Array

        if isinstance(source, np.ndarray):
            source = HostArraySource(source)
        self.source = source
        self._class_lengths_arg = class_lengths
        self.scale = float(scale)
        self.shift = float(shift)
        self.device_budget_bytes = device_budget_bytes
        #: set by initialize: True -> original_data holds the whole dataset
        #: (storage dtype) and the fused path runs its resident gather;
        #: False -> the fused path stages segments via host_gather
        self.device_resident = False
        self.original_data = Array()
        self.original_labels = Array()
        self.original_targets = Array()
        self.minibatch_targets = Array()
        if kwargs.get("normalizer") is not None:
            raise ValueError(
                f"{name}: nonlinear normalizers need the f32-resident "
                "FullBatchLoader; streaming decode is linear scale/shift")

    # -- geometry / split ------------------------------------------------------

    def _budget(self) -> int:
        if self.device_budget_bytes is not None:
            return int(self.device_budget_bytes)
        from znicz_tpu.core.config import root

        mb = root.common.engine.get("stream_budget_mb", None)
        return (int(mb) << 20) if mb is not None else DEFAULT_DEVICE_BUDGET

    def load_data(self) -> None:
        if self.source is None:
            raise ValueError(f"{self.name}: source not set")
        n = len(self.source)
        if self._class_lengths_arg is not None:
            self.class_lengths = list(self._class_lengths_arg)
            if sum(self.class_lengths) != n:
                raise ValueError(
                    f"{self.name}: class_lengths {self.class_lengths} "
                    f"!= {n} source samples")
        else:
            self.class_lengths = [0, 0, n]
        if self.source.labels is not None:
            self.original_labels.mem = np.asarray(self.source.labels,
                                                  np.int32)
        if getattr(self.source, "targets", None) is not None:
            self.original_targets.mem = self.source.targets
        self.device_resident = self.source.nbytes <= self._budget()
        if self.device_resident:
            self.original_data.mem = self.source.whole()

    def create_minibatch_data(self) -> None:
        self.minibatch_data.mem = np.zeros(
            (self.max_minibatch_size,) + tuple(self.source.sample_shape),
            np.float32)
        if self.original_labels.mem is not None:
            self.minibatch_labels.mem = np.zeros(self.max_minibatch_size,
                                                 np.int32)
        if self.original_targets.mem is not None:
            self.minibatch_targets.mem = np.zeros(
                (self.max_minibatch_size,)
                + self.original_targets.mem.shape[1:], np.float32)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        for arr in (self.original_data, self.original_labels,
                    self.original_targets, self.minibatch_targets):
            arr.initialize(device)

    def train_labels(self):
        return (self.original_labels.mem
                if self.original_labels.mem is not None else None)

    # -- the streaming surface (consumed by FusedTrainer) ----------------------

    def host_gather(self, idx: np.ndarray) -> np.ndarray:
        """Sample rows for global indices, STORAGE dtype (u8 ships as u8;
        the device decodes)."""
        return self.source.gather(np.asarray(idx, np.int32))

    def prefetch_rows(self, idx: np.ndarray) -> int:
        """Hint that a FUTURE host_gather will need these rows: sources
        with a decode pool (ImageFileSource) start decoding them now so
        the decode overlaps device compute (loader/ingest.py).  No-op for
        memcpy-cheap sources.  Returns rows newly submitted."""
        fn = getattr(self.source, "prefetch", None)
        return int(fn(np.asarray(idx, np.int32))) if fn is not None else 0

    @property
    def ingest_stats(self) -> Optional[dict]:
        """Decode-pool counters (prefetch_hits / decode_misses / ...) or
        None when the source has no pool."""
        return getattr(self.source, "ingest_stats", None)

    def host_gather_labels(self, idx: np.ndarray) -> np.ndarray:
        return np.take(self.original_labels.mem,
                       np.asarray(idx, np.int32), axis=0)

    def host_gather_targets(self, idx: np.ndarray) -> np.ndarray:
        return np.ascontiguousarray(np.take(
            self.original_targets.mem, np.asarray(idx, np.int32), axis=0))

    @property
    def decode_needed(self) -> bool:
        return self.source.dtype == np.uint8

    # -- unit-engine path ------------------------------------------------------

    def fill_minibatch(self) -> None:
        """Host gather + decode into the f32 minibatch buffers (the unit
        engine's per-step route; the fused path never calls this)."""
        idx = np.asarray(self.minibatch_indices.mem, np.int32)
        rows = self.host_gather(idx)
        data = self.minibatch_data.map_invalidate()
        if rows.dtype == np.uint8:
            data[...] = rows.astype(np.float32) * self.scale + self.shift
        else:
            data[...] = rows
        if self.original_labels.mem is not None:
            self.minibatch_labels.map_invalidate()[...] = \
                self.host_gather_labels(idx)
        if self.original_targets.mem is not None:
            self.minibatch_targets.map_invalidate()[...] = \
                self.host_gather_targets(idx)


def class_dir_source(base: str, target_shape: Tuple[int, int],
                     grayscale: bool = False,
                     workers: Optional[int] = None) -> ImageFileSource:
    """<base>/<class>/*.img -> a decode-on-demand source (the directory
    layout of loader/image.py, without the resident decode)."""
    from znicz_tpu.loader.image import scan_class_dirs

    paths, labels, _names = scan_class_dirs(base)
    return ImageFileSource(paths, labels, target_shape, grayscale,
                           workers=workers)
