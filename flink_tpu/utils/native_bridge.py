"""ctypes binding for the native host-runtime library (native/*.cpp).

Builds native/libflink_tpu_native.so on demand with g++ (cached by source
mtime) and exposes typed wrappers. Every caller has a pure-Python/numpy
form, so a missing compiler costs speed, not capability — but never
quietly: a build or load that fails warns once with the reason, and
`load_error()` keeps it for anyone who must refuse to run without the
library (chip_smoke.py does).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRCS = [
    os.path.join(_REPO_ROOT, "native", "flink_tpu_native.cpp"),
    os.path.join(_REPO_ROOT, "native", "spill_store.cpp"),
]
_SRC = _SRCS[0]
_LIB = os.path.join(_REPO_ROOT, "native", "libflink_tpu_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[str] = None


def _build() -> Optional[str]:
    """Compile native/*.cpp into _LIB. Returns why it failed, else None.
    The compiler writes beside the target and the result is renamed into
    place, so a process starting next to this one never loads half a
    file."""
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             "-o", tmp, *_SRCS],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, _LIB)
    except FileNotFoundError:
        return "g++ not found"
    except subprocess.TimeoutExpired:
        return "g++ did not finish within 120 s"
    except subprocess.CalledProcessError as e:
        return "g++ failed: " + e.stderr.decode(errors="replace")[-2000:]
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return None


def _load() -> ctypes.CDLL:
    missing = [src for src in _SRCS if not os.path.exists(src)]
    if missing:
        raise OSError(f"native sources missing: {missing}")
    if (
        not os.path.exists(_LIB)
        or os.path.getmtime(_LIB) < max(os.path.getmtime(s) for s in _SRCS)
    ):
        why = _build()
        if why is not None:
            raise OSError(why)
    lib = ctypes.CDLL(_LIB)
    _declare(lib)
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    """Loads (building if stale/missing) the native library; None if
    unavailable, in which case `load_error()` says why."""
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    with _lock:
        if _lib is None and _load_error is None:
            try:
                _lib = _load()
            except OSError as e:
                _load_error = str(e)
                warnings.warn(
                    f"native library unavailable ({_load_error}); the host "
                    "key dictionary, CSV codec, segment ring, spill store "
                    "and record lane staging run their numpy/Python forms",
                    RuntimeWarning,
                )
    return _lib


def load_error() -> Optional[str]:
    """Why the last `get_lib()` could not build or load the library."""
    return _load_error


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.kd_new.restype = c.c_void_p
    lib.kd_new.argtypes = [c.c_int64, c.c_int]
    lib.kd_free.argtypes = [c.c_void_p]
    lib.kd_size.restype = c.c_int64
    lib.kd_size.argtypes = [c.c_void_p]
    lib.kd_lookup_or_insert_i64.restype = c.c_int64
    lib.kd_lookup_or_insert_i64.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_void_p, c.c_void_p,
    ]
    lib.kd_lookup_or_insert_fixed.restype = c.c_int64
    lib.kd_lookup_or_insert_fixed.argtypes = [
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_void_p, c.c_void_p,
    ]
    lib.codec_parse_csv.restype = c.c_int64
    lib.codec_parse_csv.argtypes = [
        c.c_char_p, c.c_int64, c.c_int64, c.c_void_p, c.c_int64, c.c_void_p, c.c_void_p,
    ]
    lib.ring_new.restype = c.c_void_p
    lib.ring_new.argtypes = [c.c_int64, c.c_int64]
    lib.ring_free.argtypes = [c.c_void_p]
    lib.ring_offer.restype = c.c_int
    lib.ring_offer.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.ring_poll.restype = c.c_int64
    lib.ring_poll.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
    lib.ring_available.restype = c.c_int64
    lib.ring_available.argtypes = [c.c_void_p]
    lib.ring_free_segments.restype = c.c_int64
    lib.ring_free_segments.argtypes = [c.c_void_p]
    lib.ss_create.restype = c.c_void_p
    lib.ss_create.argtypes = [c.c_int64, c.c_char_p]
    lib.ss_free.argtypes = [c.c_void_p]
    lib.ss_mem_entries.restype = c.c_int64
    lib.ss_mem_entries.argtypes = [c.c_void_p]
    lib.ss_num_runs.restype = c.c_int64
    lib.ss_num_runs.argtypes = [c.c_void_p]
    lib.ss_put_batch.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64]
    lib.ss_get_batch.restype = c.c_int64
    lib.ss_get_batch.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p, c.c_int64]
    lib.ss_flush.restype = c.c_int64
    lib.ss_flush.argtypes = [c.c_void_p]
    lib.ss_compact.restype = c.c_int64
    lib.ss_compact.argtypes = [c.c_void_p]
    lib.ss_manifest.restype = c.c_int64
    lib.ss_manifest.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
    lib.ss_gc.restype = c.c_int64
    lib.ss_gc.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.ss_restore.restype = c.c_int64
    lib.ss_restore.argtypes = [c.c_void_p, c.c_char_p, c.c_int64]
    lib.ss_clear.argtypes = [c.c_void_p]
    lib.ss_set_next_run_id.argtypes = [c.c_void_p, c.c_int64]
    lib.ss_purge_below.restype = c.c_int64
    lib.ss_purge_below.argtypes = [c.c_void_p, c.c_uint64]
    lib.stage_record_lanes.restype = None
    lib.stage_record_lanes.argtypes = [
        c.c_int64, c.c_void_p, c.c_void_p, c.c_void_p, c.c_void_p,
        c.c_void_p, c.c_void_p, c.c_int64, c.c_int64, c.c_int64, c.c_void_p,
        c.c_void_p, c.c_void_p, c.c_int64,
    ]


class NativeKeyDict:
    """Batch key dictionary over the C++ open-addressing table."""

    def __init__(self, initial_capacity: int = 1 << 12, string_mode: bool = False):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.kd_new(initial_capacity, 1 if string_mode else 0)
        self.string_mode = string_mode

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.kd_free(self._handle)
            self._handle = None

    def __len__(self) -> int:
        return self._lib.kd_size(self._handle)

    def lookup_or_insert_i64(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        n = len(keys)
        out_ids = np.empty(n, dtype=np.int32)
        out_new = np.empty(n, dtype=np.uint8)
        size = self._lib.kd_lookup_or_insert_i64(
            self._handle,
            keys.ctypes.data_as(ctypes.c_void_p),
            n,
            out_ids.ctypes.data_as(ctypes.c_void_p),
            out_new.ctypes.data_as(ctypes.c_void_p),
        )
        return out_ids, out_new.astype(bool), int(size)

    def lookup_or_insert_bytes(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray, int]:
        """keys: numpy fixed-width bytes array (dtype 'S<w>')."""
        assert keys.dtype.kind == "S", keys.dtype
        keys = np.ascontiguousarray(keys)
        width = keys.dtype.itemsize
        n = len(keys)
        out_ids = np.empty(n, dtype=np.int32)
        out_new = np.empty(n, dtype=np.uint8)
        size = self._lib.kd_lookup_or_insert_fixed(
            self._handle,
            keys.ctypes.data_as(ctypes.c_void_p),
            width,
            n,
            out_ids.ctypes.data_as(ctypes.c_void_p),
            out_new.ctypes.data_as(ctypes.c_void_p),
        )
        return out_ids, out_new.astype(bool), int(size)


def parse_csv(
    data: bytes, max_rows: int, key_width: int = 32
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """C++ CSV fast path: b"key,value,ts\\n"* -> (keys S<w>, values f64,
    timestamps i64, rows)."""
    lib = get_lib()
    if lib is None:
        raise RuntimeError("native library unavailable")
    out_keys = np.zeros(max_rows, dtype=f"S{key_width}")
    out_vals = np.empty(max_rows, dtype=np.float64)
    out_ts = np.empty(max_rows, dtype=np.int64)
    rows = lib.codec_parse_csv(
        data,
        len(data),
        max_rows,
        out_keys.ctypes.data_as(ctypes.c_void_p),
        key_width,
        out_vals.ctypes.data_as(ctypes.c_void_p),
        out_ts.ctypes.data_as(ctypes.c_void_p),
    )
    return out_keys[:rows], out_vals[:rows], out_ts[:rows], int(rows)


class SegmentRing:
    """Bounded SPSC ring of fixed-size segments (backpressure when full)."""

    def __init__(self, segment_size: int = 32 * 1024, num_segments: int = 64):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self._handle = lib.ring_new(segment_size, num_segments)
        self.segment_size = segment_size

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.ring_free(self._handle)
            self._handle = None

    def offer(self, data: bytes) -> bool:
        return bool(self._lib.ring_offer(self._handle, data, len(data)))

    def poll(self) -> Optional[bytes]:
        buf = ctypes.create_string_buffer(self.segment_size)
        n = self._lib.ring_poll(self._handle, buf, self.segment_size)
        if n < 0:
            return None
        return buf.raw[:n]

    def __len__(self) -> int:
        return self._lib.ring_available(self._handle)

    def free_segments(self) -> int:
        return self._lib.ring_free_segments(self._handle)


class NativeSpillStore:
    """Batched u64 -> fixed-width-bytes store over the C++ LSM
    (native/spill_store.cpp); the host spill tier for state beyond HBM."""

    def __init__(self, value_width: int, directory: str):
        lib = get_lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        os.makedirs(directory, exist_ok=True)
        self._lib = lib
        self.width = value_width
        self.dir = directory
        self._handle = lib.ss_create(value_width, directory.encode())
        # never reuse run ids of files already on disk (old manifests may
        # still reference them)
        max_id = 0
        for name in os.listdir(directory):
            if name.startswith("run-") and name.endswith(".spill"):
                try:
                    max_id = max(max_id, int(name[4:-6]))
                except ValueError:
                    pass
        if max_id:
            lib.ss_set_next_run_id(self._handle, max_id + 1)

    def __del__(self):
        if getattr(self, "_handle", None):
            self._lib.ss_free(self._handle)
            self._handle = None

    def put_batch(self, keys: np.ndarray, values: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        values = np.ascontiguousarray(values)
        assert values.nbytes == len(keys) * self.width
        self._lib.ss_put_batch(
            self._handle, keys.ctypes.data, values.ctypes.data, len(keys)
        )

    def get_batch(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (values uint8[n, width], found bool[n])."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.zeros((len(keys), self.width), dtype=np.uint8)
        found = np.zeros(len(keys), dtype=np.uint8)
        self._lib.ss_get_batch(
            self._handle, keys.ctypes.data, out.ctypes.data, found.ctypes.data, len(keys)
        )
        return out, found.astype(bool)

    def flush(self) -> int:
        rid = self._lib.ss_flush(self._handle)
        if rid < 0:
            raise OSError(f"spill flush failed in {self.dir}")
        return rid

    def compact(self) -> int:
        rid = self._lib.ss_compact(self._handle)
        if rid < 0:
            raise OSError(f"spill compact failed in {self.dir}")
        return rid

    @property
    def mem_entries(self) -> int:
        return self._lib.ss_mem_entries(self._handle)

    @property
    def num_runs(self) -> int:
        return self._lib.ss_num_runs(self._handle)

    def checkpoint(self) -> str:
        """Flush and return the manifest (newline-joined immutable run file
        names) — successive checkpoints share unchanged runs."""
        self.flush()
        cap = 1 << 16
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = self._lib.ss_manifest(self._handle, buf, cap)
            if n >= 0:
                return buf.raw[:n].decode()
            cap = -n + 1

    def clear(self) -> None:
        self._lib.ss_clear(self._handle)

    def purge_below(self, threshold: int) -> int:
        """Drop every entry with key < threshold (retention cut). Returns
        entries dropped; raises on I/O error while rewriting a run."""
        n = self._lib.ss_purge_below(self._handle, ctypes.c_uint64(threshold))
        if n < 0:
            raise OSError(f"spill purge failed in {self.dir}")
        return int(n)

    def gc(self, retained_manifests) -> int:
        """Unlink run files referenced by neither the live run list nor any
        retained checkpoint manifest (the shared-state registry's
        unregisterUnusedState analogue). Pass the manifests the checkpoint
        retention window still holds."""
        blob = "\n".join(m for m in retained_manifests if m).encode()
        n = self._lib.ss_gc(self._handle, blob, len(blob))
        if n < 0:
            raise OSError(f"spill gc failed in {self.dir}")
        return int(n)

    def restore(self, manifest: str) -> None:
        """Replace the store's contents with the manifest's runs (rollback)."""
        m = manifest.encode()
        n = self._lib.ss_restore(self._handle, m, len(m))
        if n < 0:
            raise OSError(f"spill restore failed from manifest in {self.dir}")
