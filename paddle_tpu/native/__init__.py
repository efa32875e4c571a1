"""ctypes bindings to the C++ native runtime (native/src/*.cc).

TPU-native C++ equivalents of the reference's C++ runtime layer (SURVEY.md
§2.1): host arena allocator (memory/allocation/
auto_growth_best_fit_allocator.cc), blocking reader queue
(operators/reader/blocking_queue.h), RecordEvent profiler
(platform/profiler.cc), MultiSlot data feed (framework/data_feed.cc).
The library is built lazily with `make -C native` on first use, from the
sources git tracks (native/build/ is ignored). A build that FAILS raises:
the DataLoader's prefetch queue is on the trainer's main path, and a quiet
fall to the python queue would hide a broken toolchain for good. Only a
checkout without native/Makefile (no sources to build from) answers
`available() -> False`."""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_LIB_PATH = os.path.join(_REPO, "native", "build",
                         "libpaddle_tpu_native.so")
_lock = threading.Lock()
_lib = None
_tried = False


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if not os.path.exists(_LIB_PATH):
            mk = os.path.join(_REPO, "native")
            if not os.path.exists(os.path.join(mk, "Makefile")):
                return None
            try:
                subprocess.run(["make", "-C", mk], check=True,
                               capture_output=True, timeout=120)
            except (OSError, subprocess.SubprocessError) as e:
                _tried = False      # the next call builds (and raises) again
                tail = getattr(e, "stderr", b"") or b""
                raise RuntimeError(
                    "paddle_tpu: building the native runtime failed "
                    "(make -C %s): %s\n%s" % (
                        mk, e, tail[-2000:].decode(errors="replace"))) from e
        lib = ctypes.CDLL(_LIB_PATH)
        # signatures
        lib.pt_arena_create.restype = ctypes.c_void_p
        lib.pt_arena_create.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
        lib.pt_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_arena_alloc.restype = ctypes.c_void_p
        lib.pt_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.pt_arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.pt_arena_stats.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_uint64)]
        lib.pt_allocator_create.restype = ctypes.c_void_p
        lib.pt_allocator_create.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_uint64, ctypes.c_int]
        lib.pt_allocator_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_allocator_alloc.restype = ctypes.c_void_p
        lib.pt_allocator_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t]
        lib.pt_allocator_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.pt_allocator_stats.argtypes = [ctypes.c_void_p,
                                           ctypes.POINTER(ctypes.c_uint64)]
        lib.pt_queue_create.restype = ctypes.c_void_p
        lib.pt_queue_create.argtypes = [ctypes.c_size_t]
        lib.pt_queue_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_queue_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int64]
        lib.pt_queue_pop.argtypes = [ctypes.c_void_p,
                                     ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.c_int64]
        lib.pt_queue_close.argtypes = [ctypes.c_void_p]
        lib.pt_queue_size.restype = ctypes.c_size_t
        lib.pt_queue_size.argtypes = [ctypes.c_void_p]
        lib.pt_prof_enable.argtypes = [ctypes.c_int]
        lib.pt_prof_begin.restype = ctypes.c_int64
        lib.pt_prof_begin.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.pt_prof_end.argtypes = [ctypes.c_int64]
        lib.pt_prof_instant.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.pt_prof_dump_json.restype = ctypes.c_size_t
        lib.pt_prof_dump_json.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.pt_prof_num_events.restype = ctypes.c_size_t
        lib.pt_feed_create.restype = ctypes.c_void_p
        lib.pt_feed_create.argtypes = [ctypes.POINTER(ctypes.c_int),
                                       ctypes.c_int, ctypes.c_int]
        lib.pt_feed_destroy.argtypes = [ctypes.c_void_p]
        lib.pt_feed_add_file.restype = ctypes.c_int
        lib.pt_feed_add_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.pt_feed_start.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pt_feed_next.restype = ctypes.c_int
        lib.pt_feed_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_int64)),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64)]
        lib.pt_native_version.restype = ctypes.c_char_p
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def version() -> Optional[str]:
    lib = _load()
    return lib.pt_native_version().decode() if lib else None


class HostArena:
    """Best-fit host staging arena (reference:
    auto_growth_best_fit_allocator.cc)."""

    def __init__(self, chunk_bytes=8 << 20, alignment=64):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.pt_arena_create(chunk_bytes, alignment)

    def alloc(self, nbytes: int) -> int:
        p = self._lib.pt_arena_alloc(self._h, nbytes)
        if not p:
            raise MemoryError(f"arena alloc of {nbytes} failed")
        return p

    def free(self, ptr: int):
        self._lib.pt_arena_free(self._h, ptr)

    def stats(self) -> dict:
        out = (ctypes.c_uint64 * 6)()
        self._lib.pt_arena_stats(self._h, out)
        return {"reserved": out[0], "in_use": out[1], "allocs": out[2],
                "frees": out[3], "chunks": out[4], "peak": out[5]}

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pt_arena_destroy(self._h)
            self._h = None


class HostAllocator:
    """Strategy-selected host allocator with limit + retry tier
    (reference: memory/allocation/allocator_facade.h:41 AllocatorFacade
    over FLAGS_allocator_strategy, retry_allocator.cc).

    strategy: "auto_growth" (grow by chunks on demand) or
    "naive_best_fit" (one fixed pool carved up-front — `limit_bytes` if
    given, else `chunk_bytes` — and NEVER grown). `retry_ms` > 0 makes a
    failed allocation WAIT for concurrent frees up to the deadline before
    raising (the reference's RetryAllocator)."""

    def __init__(self, strategy="auto_growth", chunk_bytes=8 << 20,
                 alignment=64, limit_bytes=0, retry_ms=0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        if strategy not in ("auto_growth", "naive_best_fit"):
            raise ValueError(f"unknown allocator strategy {strategy!r}")
        self._lib = lib
        self._h = lib.pt_allocator_create(strategy.encode(), chunk_bytes,
                                          alignment, limit_bytes, retry_ms)

    def alloc(self, nbytes: int) -> int:
        p = self._lib.pt_allocator_alloc(self._h, nbytes)
        if not p:
            raise MemoryError(f"allocator alloc of {nbytes} failed "
                              "(limit/pool exhausted after retry window)")
        return p

    def free(self, ptr: int):
        self._lib.pt_allocator_free(self._h, ptr)

    def stats(self) -> dict:
        out = (ctypes.c_uint64 * 6)()
        self._lib.pt_allocator_stats(self._h, out)
        return {"reserved": out[0], "in_use": out[1], "allocs": out[2],
                "frees": out[3], "chunks": out[4], "peak": out[5]}

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pt_allocator_destroy(self._h)
            self._h = None


class NativeQueue:
    """Bounded blocking queue of python objects (reference:
    operators/reader/blocking_queue.h). Objects are pinned in a local
    registry; the C++ side moves opaque ids."""

    def __init__(self, capacity=8):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._h = lib.pt_queue_create(capacity)
        self._reg = {}
        self._next = 1
        self._mu = threading.Lock()

    def push(self, obj, timeout_ms=-1) -> bool:
        with self._mu:
            token = self._next
            self._next += 1
            self._reg[token] = obj
        rc = self._lib.pt_queue_push(self._h, ctypes.c_void_p(token),
                                     timeout_ms)
        if rc != 0:
            with self._mu:
                self._reg.pop(token, None)
        return rc == 0

    def pop(self, timeout_ms=-1):
        """Returns the object, or None on timeout/closed-drained."""
        out = ctypes.c_void_p()
        rc = self._lib.pt_queue_pop(self._h, ctypes.byref(out), timeout_ms)
        if rc != 0:
            return None
        with self._mu:
            return self._reg.pop(out.value)

    def close(self):
        self._lib.pt_queue_close(self._h)

    def __len__(self):
        return self._lib.pt_queue_size(self._h)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pt_queue_destroy(self._h)
            self._h = None


class TraceRecorder:
    """Host-side RecordEvent spans → chrome://tracing JSON (reference:
    platform/profiler.cc, tools/timeline.py)."""

    def __init__(self):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib

    def enable(self, on=True):
        self._lib.pt_prof_enable(1 if on else 0)

    def begin(self, name: str, category="op") -> int:
        return self._lib.pt_prof_begin(name.encode(), category.encode())

    def end(self, handle: int):
        self._lib.pt_prof_end(handle)

    def instant(self, name: str, category="marker"):
        self._lib.pt_prof_instant(name.encode(), category.encode())

    def num_events(self) -> int:
        return self._lib.pt_prof_num_events()

    def dump_json(self) -> str:
        n = self._lib.pt_prof_dump_json(None, 0)
        buf = ctypes.create_string_buffer(n)
        self._lib.pt_prof_dump_json(buf, n)
        return buf.value.decode()

    def clear(self):
        self._lib.pt_prof_clear()


class MultiSlotFeed:
    """Threaded MultiSlot text parser (reference: framework/data_feed.cc).

    slot_types: "int64" or "float32" per slot. next_batch() returns, per
    slot, (offsets int64[rows+1], values np.ndarray) — ragged rows as
    LoD-style offsets (mask/segment-id friendly)."""

    INT64, FLOAT32 = 0, 1

    def __init__(self, slot_types: Sequence[str], batch_size: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._types = [self.INT64 if t in ("int64", "int") else self.FLOAT32
                       for t in slot_types]
        arr = (ctypes.c_int * len(self._types))(*self._types)
        self._h = lib.pt_feed_create(arr, len(self._types), batch_size)
        self._n = len(self._types)

    def add_file(self, path: str):
        if self._lib.pt_feed_add_file(self._h, path.encode()) != 0:
            raise FileNotFoundError(path)

    def start(self, num_threads=2):
        self._lib.pt_feed_start(self._h, num_threads)

    def next_batch(self):
        """Returns list of (offsets, values) per slot, or None at end."""
        import numpy as np
        offs = (ctypes.POINTER(ctypes.c_int64) * self._n)()
        data = (ctypes.c_void_p * self._n)()
        lens = (ctypes.c_int64 * self._n)()
        rows = self._lib.pt_feed_next(self._h, offs, data, lens)
        if rows == 0:
            return None
        out = []
        for s in range(self._n):
            o = np.ctypeslib.as_array(offs[s], shape=(rows + 1,)).copy()
            n = int(lens[s])
            np_dt = np.int64 if self._types[s] == self.INT64 else np.float32
            if n == 0:
                v = np.empty((0,), np_dt)
            else:
                ct = ctypes.c_int64 if self._types[s] == self.INT64 \
                    else ctypes.c_float
                ptr = ctypes.cast(data[s], ctypes.POINTER(ct))
                v = np.ctypeslib.as_array(ptr, shape=(n,)).copy()
            out.append((o, v))
        return out

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.pt_feed_destroy(self._h)
            self._h = None
