"""Native (C++) input-pipeline runtime, bound through ctypes.

The reference's data path bottoms out in torch's native DataLoader worker
machinery; this is the TPU build's equivalent: a dependency-free C++ core
(src/prefetch.cpp) that assembles batches with a multithreaded row-gather
and prefetches them on a background thread, so host batch assembly
overlaps device compute instead of serializing with it.

Build model: compiled on first use with the system ``g++`` into
``_build/librlt_native-<digest>.so``, the digest taken over the source
and the compiler flags — so a tree copied to another machine (where
file times mean nothing) rebuilds exactly when the source differs, and
the flags name no host CPU (no ``-march=native``), so an artefact that
does travel with the tree still runs.  Per-pid temp + atomic rename
lets concurrent worker processes race safely.  If no toolchain is
available the library degrades to ``None`` and callers fall back to
the pure-Python path (``native_available()`` says which ran) — the same
optional-dependency gating the framework applies to Ray and Tune
(utils/imports.py).
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

_log = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src", "prefetch.cpp")
_BUILD_DIR = os.path.join(_HERE, "_build")
_CXXFLAGS = ["-O3", "-shared", "-fPIC", "-pthread", "-std=c++17"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


def _lib_path() -> str:
    """The artefact for THIS source and these flags (module docstring)."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_BUILD_DIR,
                        f"librlt_native-{h.hexdigest()[:16]}.so")


def _compile(lib_path: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXXFLAGS, _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        _log.warning("native build failed (%s); using pure-Python path", e)
        return False
    os.replace(tmp, lib_path)
    return True


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    p = ctypes.c_void_p
    i64 = ctypes.c_int64
    lib.rlt_prefetcher_create.restype = p
    lib.rlt_prefetcher_create.argtypes = [ctypes.c_int, ctypes.c_int,
                                          ctypes.c_int]
    lib.rlt_prefetcher_set_array.argtypes = [p, ctypes.c_int, p, i64]
    lib.rlt_prefetcher_set_slot.argtypes = [p, ctypes.c_int, ctypes.c_int, p]
    lib.rlt_prefetcher_start.argtypes = [p, ctypes.POINTER(i64), i64, i64,
                                         ctypes.c_int]
    lib.rlt_prefetcher_next.restype = i64
    lib.rlt_prefetcher_next.argtypes = [p, ctypes.POINTER(i64)]
    lib.rlt_prefetcher_release.argtypes = [p, i64]
    lib.rlt_prefetcher_stop.argtypes = [p]
    lib.rlt_prefetcher_destroy.argtypes = [p]
    lib.rlt_gather.argtypes = [p, i64, ctypes.POINTER(i64), i64, p,
                               ctypes.c_int]
    return lib


def load_library() -> Optional[ctypes.CDLL]:
    """The native library, building it if stale/missing; None if
    unavailable (no toolchain) or disabled via RLT_NATIVE=0."""
    global _lib, _lib_failed
    if os.environ.get("RLT_NATIVE", "1") == "0":
        return None
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path) and not _compile(lib_path):
                _lib_failed = True
                return None
            _lib = _bind(ctypes.CDLL(lib_path))
        except OSError as e:
            _log.warning("native library unusable (%s)", e)
            _lib_failed = True
            _lib = None
        return _lib


def native_available() -> bool:
    return load_library() is not None


def default_threads() -> int:
    env = os.environ.get("RLT_NATIVE_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            _log.warning("ignoring malformed RLT_NATIVE_THREADS=%r", env)
    return min(4, os.cpu_count() or 1)


def _as_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.c_void_p)


class NativePrefetcher:
    """Batch prefetcher over a fixed set of source arrays.

    Per epoch, Python hands it the index order and iterates.  Each batch
    is yielded with OWNERSHIP: the consumer keeps the arrays forever
    (same semantics as the pure-Python path's fresh ``take()`` copies);
    the wrapper installs a freshly allocated buffer into the vacated ring
    slot before releasing it to the producer, so no yielded batch is ever
    overwritten — even while an async device transfer is still reading it.
    """

    def __init__(self, arrays: list[np.ndarray], batch_size: int,
                 queue_depth: int = 3, n_threads: Optional[int] = None):
        lib = load_library()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        # sources must stay alive and contiguous for the prefetcher's life
        self._sources = [np.ascontiguousarray(a) for a in arrays]
        self.batch_size = int(batch_size)
        # depth < 2 would let a stale kReady satisfy the next batch's wait
        self.queue_depth = max(2, int(queue_depth))
        self._handle = lib.rlt_prefetcher_create(
            len(self._sources), self.queue_depth,
            n_threads or default_threads())
        self._slots: list[list[np.ndarray]] = []
        for a_i, a in enumerate(self._sources):
            row_bytes = a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
            lib.rlt_prefetcher_set_array(self._handle, a_i, _as_ptr(a),
                                         row_bytes)
        for s in range(self.queue_depth):
            slot_bufs = []
            for a_i, a in enumerate(self._sources):
                buf = np.empty((self.batch_size,) + a.shape[1:],
                               dtype=a.dtype)
                lib.rlt_prefetcher_set_slot(self._handle, s, a_i,
                                            _as_ptr(buf))
                slot_bufs.append(buf)
            self._slots.append(slot_bufs)

    def iter_epoch(self, indices: np.ndarray):
        """Yield one list of per-array batches (caller-owned) per batch,
        in ``indices`` order (partial final batch included, matching the
        Python path)."""
        lib, h = self._lib, self._handle
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        n = len(idx)
        lib.rlt_prefetcher_start(
            h, idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
            self.batch_size, 0)
        nrows = ctypes.c_int64()
        try:
            while True:
                slot = lib.rlt_prefetcher_next(h, ctypes.byref(nrows))
                if slot < 0:
                    break
                rows = int(nrows.value)
                bufs = self._slots[slot]
                # hand these buffers to the consumer; give the slot fresh
                # ones (np.empty is lazy — pages fault in the producer
                # thread, off the consumer's critical path).  set_slot
                # before release: the producer only reads slot pointers
                # after seeing the slot free under the same mutex.
                fresh = [np.empty_like(b) for b in bufs]
                for a_i, nb in enumerate(fresh):
                    lib.rlt_prefetcher_set_slot(h, int(slot), a_i,
                                                _as_ptr(nb))
                self._slots[slot] = fresh
                lib.rlt_prefetcher_release(h, slot)
                yield [b[:rows] for b in bufs]
        finally:
            lib.rlt_prefetcher_stop(h)  # abort-on-early-exit

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.rlt_prefetcher_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def gather(src: np.ndarray, indices: np.ndarray,
           out: Optional[np.ndarray] = None,
           n_threads: Optional[int] = None) -> np.ndarray:
    """Threaded ``src[indices]`` for 1+-D contiguous arrays; falls back to
    numpy fancy indexing when the native library is unavailable."""
    lib = load_library()
    src = np.ascontiguousarray(src)
    idx = np.ascontiguousarray(indices, dtype=np.int64)
    if lib is None:
        result = src[idx]
        if out is not None:
            out[:len(idx)] = result
            return out[:len(idx)]
        return result
    if out is None:
        out = np.empty((len(idx),) + src.shape[1:], dtype=src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:],
                                                 dtype=np.int64))
    lib.rlt_gather(_as_ptr(src), row_bytes,
                   idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                   len(idx), _as_ptr(out), n_threads or default_threads())
    return out[:len(idx)]
