"""ctypes bindings for the native C++ token-batch loader (csrc/data_loader.cpp).

The loader mmaps a binary token stream and prefetches shuffled
``[batch, seq+1]`` int32 batches on background C++ threads (bounded ring
buffer) — the training loop's IO runs off the Python GIL entirely. The
shared object is built with g++ on first use and cached next to the source;
environments without a toolchain fall back to a numpy implementation with
identical semantics.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import time
from typing import Iterator, Optional

import numpy as np

_BUILD_LOCK = threading.Lock()
_LIB = None
_LIB_FAILED = False


class DataLoaderStallError(RuntimeError):
    """``next_batch`` produced nothing within ``stall_timeout_s`` — the
    producer threads are wedged (dead filesystem, mmap fault) rather than
    slow. The resilience watchdog treats this as a stall, not a crash."""


def _csrc_path() -> str:
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "csrc",
        "data_loader.cpp")


def _so_path() -> str:
    return os.path.join(os.path.dirname(_csrc_path()),
                        "libnxd_data_loader.so")


def _compile_and_bind():
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
         _csrc_path(), "-o", _so_path()],
        check=True, capture_output=True)
    return _bind(ctypes.CDLL(_so_path()))


def _bind(lib):
    lib.nxd_loader_create.restype = ctypes.c_void_p
    lib.nxd_loader_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_long, ctypes.c_long,
        ctypes.c_long, ctypes.c_int, ctypes.c_int]
    lib.nxd_loader_num_sequences.restype = ctypes.c_long
    lib.nxd_loader_num_sequences.argtypes = [ctypes.c_void_p]
    lib.nxd_loader_next.restype = ctypes.c_int
    lib.nxd_loader_next.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_int32)]
    lib.nxd_loader_destroy.argtypes = [ctypes.c_void_p]
    return lib


def build_native() -> None:
    """Compile ``csrc/data_loader.cpp`` now and make it the library every
    later :class:`TokenBatchLoader` binds, whatever ``.so`` was on disk.
    Raises when the toolchain fails — for entry scripts that must measure
    the native path or nothing. Call before the first
    loader is made in the process."""
    global _LIB, _LIB_FAILED
    with _BUILD_LOCK:
        _LIB = _compile_and_bind()
        _LIB_FAILED = False


def _load_native():
    global _LIB, _LIB_FAILED
    if _LIB is not None or _LIB_FAILED:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None or _LIB_FAILED:
            return _LIB
        src, so = _csrc_path(), _so_path()
        try:
            if (not os.path.exists(so)
                    or os.path.getmtime(so) < os.path.getmtime(src)):
                _LIB = _compile_and_bind()
            else:
                _LIB = _bind(ctypes.CDLL(so))
        except Exception:
            _LIB_FAILED = True
            _LIB = None
    return _LIB


class TokenBatchLoader:
    """Iterator of ``{"input_ids": [B,S], "labels": [B,S]}`` int32 batches
    from a flat binary token file (uint16 or uint32)."""

    def __init__(self, path: str, batch: int, seqlen: int, seed: int = 0,
                 dtype: str = "uint16", nthreads: int = 2,
                 capacity: int = 8, force_python: bool = False,
                 stall_timeout_s: Optional[float] = None):
        self.path = path
        self.batch = batch
        self.seqlen = seqlen
        self.seed = seed
        # wall-clock budget per next_batch (None = block forever); the
        # blocking produce runs on a helper thread so a wedged native ring
        # buffer surfaces as DataLoaderStallError instead of a silent hang
        self.stall_timeout_s = stall_timeout_s
        # heartbeat for external stall detection (resilience.Watchdog)
        self.last_batch_at = time.monotonic()
        self.dtype = np.dtype(dtype)
        if self.dtype.itemsize not in (2, 4):
            raise ValueError("token dtype must be uint16 or uint32")
        if nthreads < 1:
            raise ValueError("nthreads must be >= 1 (zero producer threads "
                             "would deadlock next_batch)")
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._handle = None
        self._lib = None if force_python else _load_native()
        if self._lib is not None:
            self._handle = self._lib.nxd_loader_create(
                path.encode(), self.dtype.itemsize, batch, seqlen, seed,
                nthreads, capacity)
            if not self._handle:
                raise ValueError(
                    f"native loader rejected {path!r} (missing, or fewer "
                    f"than {batch} sequences of length {seqlen + 1})")
            self.num_sequences = int(
                self._lib.nxd_loader_num_sequences(self._handle))
            self.native = True
        else:
            self._tokens = np.memmap(path, dtype=self.dtype, mode="r")
            self.num_sequences = len(self._tokens) // (seqlen + 1)
            if self.num_sequences < batch:
                raise ValueError(
                    f"{path!r} has fewer than {batch} sequences of length "
                    f"{seqlen + 1}")
            self._rng = np.random.RandomState(seed)
            self.native = False

    def _produce(self) -> np.ndarray:
        n = self.batch * (self.seqlen + 1)
        if self._handle is not None:
            out = np.empty((n,), np.int32)
            rc = self._lib.nxd_loader_next(
                self._handle, out.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_int32)))
            if rc != 0:
                raise RuntimeError("native loader stopped")
            return out.reshape(self.batch, self.seqlen + 1)
        idx = self._rng.randint(0, self.num_sequences, self.batch)
        per = self.seqlen + 1
        return np.stack([
            np.asarray(self._tokens[i * per:(i + 1) * per],
                       dtype=np.int32) for i in idx])

    def next_batch(self) -> dict:
        if self.stall_timeout_s is None:
            ids = self._produce()
        else:
            box = {}

            def run():
                try:
                    box["ids"] = self._produce()
                except BaseException as e:  # re-raised on the caller
                    box["err"] = e

            # daemon: a wedged producer blocked in C must not pin the
            # interpreter open after the caller gave up on it
            t = threading.Thread(target=run, daemon=True,
                                 name="nxd-loader-next")
            t.start()
            t.join(timeout=self.stall_timeout_s)
            if t.is_alive():
                raise DataLoaderStallError(
                    f"data loader produced no batch within "
                    f"{self.stall_timeout_s:.1f}s (native={self.native})")
            if "err" in box:
                raise box["err"]
            ids = box["ids"]
        self.last_batch_at = time.monotonic()
        return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}

    def __iter__(self) -> Iterator[dict]:
        while True:
            yield self.next_batch()

    def close(self) -> None:
        if self._handle is not None:
            self._lib.nxd_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass
