"""ctypes bindings for the C++ host runtime (``native/znicz_native.cpp``).

The reference's native layer was hand-written device kernels plus libzmq;
here the device side belongs to XLA and the HOST data path is the native
C++ piece: xorshift128+ PRNG (the reference's rand kernel family),
Fisher-Yates shuffling, minibatch row gather, u8->f32 decode.

The shared library is built on first use with g++ (cached under
``root.common.dirs.cache``, resolved against the checkout); every function
has a numpy fallback so the framework works without a toolchain, and the
first use says at INFO which of the two this process got.  Consumers: the
Loader's opt-in ``native_shuffle`` path
(``root.common.engine.native_shuffle`` or the per-loader kwarg), the image
loader's u8->f32 decode, and host-side minibatch assembly via
``gather_f32``.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

log = logging.getLogger("znicz")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _source_path() -> str:
    from znicz_tpu.backends import checkout_dir

    return os.path.join(checkout_dir(), "native", "znicz_native.cpp")


def build() -> Optional[str]:
    """Compile the shared library; returns its path or None.  The library
    is named by a hash of its source, so a library left in the cache by
    another revision is never loaded in place of this one's source."""
    from znicz_tpu.backends import cache_dir

    src = _source_path()
    if not os.path.exists(src):
        return None
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    cache = cache_dir()
    os.makedirs(cache, exist_ok=True)
    out = os.path.join(cache, f"libznicz_native-{digest}.so")
    if os.path.exists(out):
        return out
    # build under a private name, then rename: a concurrent or killed
    # build must never leave a half-written library under the final name
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return out
    except (OSError, subprocess.SubprocessError):
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        _lib = _load_library()
        log.info("host runtime: %s", _describe(_lib))
        return _lib


def _load_library() -> Optional[ctypes.CDLL]:
    path = build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    f32p = ctypes.POINTER(ctypes.c_float)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.znicz_seed.argtypes = [u64p, ctypes.c_uint64]
    lib.znicz_fill_uniform.argtypes = [u64p, f32p, ctypes.c_size_t,
                                       ctypes.c_float, ctypes.c_float]
    lib.znicz_fill_normal.argtypes = [u64p, f32p, ctypes.c_size_t,
                                      ctypes.c_float]
    lib.znicz_shuffle_i32.argtypes = [u64p, i32p, ctypes.c_size_t]
    lib.znicz_gather_f32.argtypes = [f32p, i32p, f32p, ctypes.c_size_t,
                                     ctypes.c_size_t]
    lib.znicz_u8_to_f32.argtypes = [u8p, f32p, ctypes.c_size_t,
                                    ctypes.c_float, ctypes.c_float]
    lib.znicz_native_abi.restype = ctypes.c_int
    if lib.znicz_native_abi() != 1:
        return None
    return lib


def _describe(lib: Optional[ctypes.CDLL]) -> str:
    return "numpy fallback" if lib is None else f"native {lib._name}"


def implementation() -> str:
    """Which host-runtime implementation this process uses: the loaded
    C++ library's path, or the numpy fallback."""
    return _describe(_load())


def available() -> bool:
    return _load() is not None


class XorShift128P:
    """The reference's device RNG family, as a host stream.  Deterministic
    across the native and numpy implementations is NOT guaranteed — the
    native path is bit-exact xorshift128+; the fallback delegates to
    numpy's PCG (both seeded, both reproducible within their path)."""

    def __init__(self, seed: int):
        self._native = available()
        if self._native:
            self.state = np.zeros(2, np.uint64)
            _lib.znicz_seed(self.state.ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint64)), ctypes.c_uint64(seed))
        else:
            self._rng = np.random.default_rng(seed)

    def _sp(self):
        return self.state.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))

    def fill_uniform(self, out: np.ndarray, low: float, high: float) -> None:
        assert out.dtype == np.float32 and out.flags.c_contiguous
        if self._native:
            _lib.znicz_fill_uniform(
                self._sp(), out.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_float)),
                out.size, low, high)
        else:
            out[...] = self._rng.uniform(low, high, out.shape)

    def fill_normal(self, out: np.ndarray, stddev: float) -> None:
        assert out.dtype == np.float32 and out.flags.c_contiguous
        if self._native:
            _lib.znicz_fill_normal(
                self._sp(), out.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_float)),
                out.size, stddev)
        else:
            out[...] = self._rng.normal(0, stddev, out.shape)

    def shuffle(self, arr: np.ndarray) -> None:
        assert arr.dtype == np.int32 and arr.flags.c_contiguous
        if self._native:
            _lib.znicz_shuffle_i32(
                self._sp(), arr.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int32)), arr.size)
        else:
            self._rng.shuffle(arr)


def gather_f32(src: np.ndarray, idx: np.ndarray,
               dst: Optional[np.ndarray] = None) -> np.ndarray:
    """Row gather src[idx] -> dst (native memcpy loop or numpy take).
    Indices are validated up front — the C path is unchecked memcpy."""
    rows = np.ascontiguousarray(src.reshape(len(src), -1), np.float32)
    idx = np.ascontiguousarray(idx, np.int32)
    if idx.size and (idx.min() < 0 or idx.max() >= len(rows)):
        raise IndexError(f"gather index out of range [0, {len(rows)})")
    out_shape = (len(idx),) + src.shape[1:]
    if dst is None:
        dst = np.empty(out_shape, np.float32)
    elif not (dst.flags.c_contiguous and dst.dtype == np.float32):
        raise ValueError("dst must be a C-contiguous float32 buffer "
                         "(reshape of a strided view would write a copy)")
    if available():
        flat = dst.reshape(len(idx), -1)
        _lib.znicz_gather_f32(
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            len(idx), rows.shape[1])
    else:
        np.take(rows, idx, axis=0, out=dst.reshape(len(idx), -1))
    return dst


def u8_to_f32(src: np.ndarray, scale: float = 1.0 / 255.0,
              shift: float = 0.0) -> np.ndarray:
    src = np.ascontiguousarray(src, np.uint8)
    dst = np.empty(src.shape, np.float32)
    if available():
        _lib.znicz_u8_to_f32(
            src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            src.size, scale, shift)
    else:
        dst[...] = src.astype(np.float32) * scale + shift
    return dst
