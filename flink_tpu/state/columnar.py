"""Columnar device state: HBM-resident per-(key, slice) accumulator columns.

The `JaxColumnarStateBackend` sibling of the reference's HeapKeyedStateBackend
(HeapKeyedStateBackend.java:85): instead of a hash map of (key, window) →
accumulator objects mutated per record (CopyOnWriteStateMap.java:108), state
is a dict of dense [K, S] device arrays — K = distinct-key capacity, S =
slice-ring capacity — plus a host-side key dictionary mapping raw keys to
dense row ids and a slice ring that reuses columns as windows expire.

Snapshots pull the arrays to host (device→host is the step-aligned barrier,
SURVEY.md §7 stage 5) together with the key dictionary and ring frontiers.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from flink_tpu.ops.aggregators import DeviceAggregator
from flink_tpu.ops import segment_ops


class KeyDictionary:
    """Raw key -> dense row id. `dense_int` mode skips the dict entirely for
    pre-densified integer keys (sources that emit key ids, e.g. benchmark
    generators and the keyBy shuffle's re-densified output).

    Batches of int64 or string keys take the native C++ open-addressing path
    (native/flink_tpu_native.cpp KeyDict via utils/native_bridge) — the host
    equivalent of the reference's native state-store key handling; arbitrary
    Python keys fall back to a dict loop."""

    def __init__(self, dense_int: bool = False):
        self.dense_int = dense_int
        self._map: Dict[Any, int] = {}
        self._keys: List[Any] = []
        self._native = None
        self._native_mode: str = ""  # '', 'i64', 'bytes', 'off' (fallback)

    def __len__(self) -> int:
        return len(self._keys)

    @property
    def num_ids(self) -> int:
        return len(self._keys)

    # -- native fast paths -------------------------------------------------
    def _try_native(self, keys: np.ndarray):
        """Returns (ids, size) via the C++ dict, or None to fall back."""
        if self._native_mode == "off":
            return None
        from flink_tpu.utils import native_bridge

        as_i64 = as_bytes = None
        if keys.dtype != object and np.issubdtype(keys.dtype, np.integer):
            mode, as_i64 = "i64", np.ascontiguousarray(keys, dtype=np.int64)
        else:
            try:
                as_bytes = np.asarray(keys, dtype=np.bytes_)
                mode = "bytes"
            except (TypeError, UnicodeEncodeError, ValueError):
                return None
        if self._native_mode and self._native_mode != mode:
            return None  # mixed key types: stay on the generic path
        if self._native is None:
            if native_bridge.get_lib() is None:
                self._native_mode = "off"
                return None
            self._native = native_bridge.NativeKeyDict(string_mode=(mode == "bytes"))
            self._native_mode = mode
            if self._keys:  # restore path: re-seed the table in id order
                if mode == "i64":
                    self._native.lookup_or_insert_i64(
                        np.asarray(self._keys, dtype=np.int64)
                    )
                else:
                    seed = np.asarray(self._keys, dtype=np.bytes_)
                    self._bytes_width = max(((seed.dtype.itemsize + 7) // 8) * 8, 24)
                    self._native.lookup_or_insert_bytes(
                        seed.astype(f"S{self._bytes_width}")
                    )
        if mode == "i64":
            ids, new, size = self._native.lookup_or_insert_i64(as_i64)
        else:
            # fixed-width byte keys: one width for the dictionary's lifetime
            # (padding with NULs is consistent; a longer key than the chosen
            # width cannot be represented -> permanent fallback)
            if not hasattr(self, "_bytes_width"):
                self._bytes_width = max(((as_bytes.dtype.itemsize + 7) // 8) * 8, 24)
            if as_bytes.dtype.itemsize > self._bytes_width:
                self._native_mode = "off"
                self._native = None
                return None
            as_bytes = as_bytes.astype(f"S{self._bytes_width}")
            ids, new, size = self._native.lookup_or_insert_bytes(as_bytes)
        if new.any():
            # tolist(): plain Python scalars, not np.int64/np.str_ (user-facing)
            self._keys.extend(keys[new].tolist())
        return ids, size

    def lookup_or_insert(self, keys: np.ndarray) -> Tuple[np.ndarray, int]:
        """Map a batch of raw keys to dense ids, inserting unseen keys.
        Returns (ids int32[B], required_capacity)."""
        if self.dense_int:
            ids = keys.astype(np.int32)
            hi = int(ids.max()) + 1 if ids.size else 0
            if hi > len(self._keys):
                self._keys.extend(range(len(self._keys), hi))
            return ids, len(self._keys)
        native = self._try_native(keys)
        if native is not None:
            return native[0], native[1]
        m = self._map
        if not m and self._keys:  # fell back after native use: rebuild map
            m = self._map = {k: i for i, k in enumerate(self._keys)}
        out = np.empty(len(keys), dtype=np.int32)
        for i, k in enumerate(keys):
            kid = m.get(k)
            if kid is None:
                kid = len(self._keys)
                m[k] = kid
                self._keys.append(k)
            out[i] = kid
        return out, len(self._keys)

    def key_at(self, kid: int):
        return self._keys[kid]

    def lookup(self, key) -> Optional[int]:
        """Dense id for a key, or None if never seen (read-only)."""
        if self.dense_int:
            k = int(key)
            return k if 0 <= k < len(self._keys) else None
        kid = self._map.get(key)
        if kid is None and self._keys and not self._map:
            # native-dict mode keeps _map empty; fall back to a scan
            try:
                kid = self._keys.index(key)
            except ValueError:
                kid = None
        return kid

    def keys_for(self, kids: np.ndarray) -> List:
        return list(map(self._keys.__getitem__, kids.tolist()))

    def snapshot(self) -> dict:
        return {"dense_int": self.dense_int, "keys": list(self._keys)}

    @staticmethod
    def restore(snap: dict) -> "KeyDictionary":
        d = KeyDictionary(snap["dense_int"])
        d._keys = list(snap["keys"])
        d._map = {} if d.dense_int else {k: i for i, k in enumerate(d._keys)}
        return d


@dataclasses.dataclass
class RingFrontiers:
    """Host-tracked slice-ring accounting (all in absolute slice indices)."""

    purged_to: int = None      # slices < purged_to are recycled  # type: ignore[assignment]
    min_used: int = None       # smallest slice ever written       # type: ignore[assignment]
    max_used: int = None       # largest slice ever written        # type: ignore[assignment]


class ColumnarWindowState:
    """Device arrays + key dictionary + ring accounting for one shard."""

    PURGE_CHUNK = 8

    def __init__(
        self,
        agg: DeviceAggregator,
        *,
        key_capacity: int = 1 << 12,
        num_slices: int = 64,
        dense_int_keys: bool = False,
        device=None,
        ingest_kernel: str = "scatter",
    ):
        self.agg = agg
        self.K = key_capacity
        self.S = num_slices
        self.device = device
        self.keydict = KeyDictionary(dense_int_keys)
        self.frontiers = RingFrontiers()
        self.acc, self.count = segment_ops.init_state_arrays(agg, self.K, self.S)
        if ingest_kernel == "sort":
            from flink_tpu.ops.sorted_ingest import make_sorted_ingest_fn

            self._ingest = make_sorted_ingest_fn(agg, track_touch=True)
        else:
            self._ingest = segment_ops.make_ingest_fn(agg, track_touch=True)
        self._fire = segment_ops.make_fire_fn(agg, masked=False)
        self._fire_masked = segment_ops.make_fire_fn(agg, masked=True)
        self._purge = segment_ops.make_purge_fn(agg, self.PURGE_CHUNK)
        self.last_touch = None  # bool[K,S] from the most recent ingest

    # ------------------------------------------------------------------
    def ensure_key_capacity(self, required: int) -> None:
        if required <= self.K:
            return
        new_k = self.K
        while new_k < required:
            new_k *= 2
        self.acc, self.count = segment_ops.grow_keys(self.acc, self.count, self.agg, new_k)
        if self.last_touch is not None:
            import jax.numpy as jnp
            pad = jnp.zeros((new_k - self.K, self.S), dtype=self.last_touch.dtype)
            self.last_touch = jnp.concatenate([self.last_touch, pad], axis=0)
        self.K = new_k

    def ring_pos(self, slices: np.ndarray) -> np.ndarray:
        return (slices % self.S).astype(np.int32)

    def ingest(self, kid: np.ndarray, slices_abs: np.ndarray, vals: np.ndarray) -> None:
        """Scatter a prepared batch into the columns.
        kid == INVALID_INDEX lanes are dropped."""
        f = self.frontiers
        valid = kid != segment_ops.INVALID_INDEX
        live = slices_abs[valid]
        if live.size:
            lo, hi = int(live.min()), int(live.max())
            f.min_used = lo if f.min_used is None else min(f.min_used, lo)
            f.max_used = hi if f.max_used is None else max(f.max_used, hi)
        spos = np.where(valid, slices_abs % self.S, segment_ops.INVALID_INDEX).astype(np.int32)
        self.acc, self.count, self.last_touch = self._ingest(
            self.acc, self.count, kid.astype(np.int32), spos, vals
        )

    def fire(self, slice_range: range, *, touch_mask: bool = False):
        """Combine the window's slices; returns (result, counts, mask) device arrays."""
        positions = np.asarray([s % self.S for s in slice_range], dtype=np.int32)
        if touch_mask:
            return self._fire_masked(self.acc, self.count, positions, self.last_touch)
        return self._fire(self.acc, self.count, positions)

    def purge_slices(self, slices_abs: List[int]) -> None:
        """Reset columns of expired absolute slices (chunked)."""
        for i in range(0, len(slices_abs), self.PURGE_CHUNK):
            chunk = slices_abs[i : i + self.PURGE_CHUNK]
            positions = np.full(self.PURGE_CHUNK, segment_ops.INVALID_INDEX, dtype=np.int32)
            positions[: len(chunk)] = [s % self.S for s in chunk]
            self.acc, self.count = self._purge(self.acc, self.count, positions)

    def reset_all(self) -> None:
        self.acc, self.count = segment_ops.init_state_arrays(self.agg, self.K, self.S)
        self.last_touch = None

    def state_bytes(self) -> int:
        """HBM footprint of the resident device arrays (observability
        gauge; key-dictionary host memory not included)."""
        n = sum(int(getattr(a, "nbytes", 0)) for a in self.acc.values())
        n += int(getattr(self.count, "nbytes", 0))
        if self.last_touch is not None:
            n += int(getattr(self.last_touch, "nbytes", 0))
        return n

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        return {
            "acc": {k: np.asarray(v) for k, v in self.acc.items()},
            "count": np.asarray(self.count),
            "keydict": self.keydict.snapshot(),
            "frontiers": dataclasses.asdict(self.frontiers),
            "K": self.K,
            "S": self.S,
        }

    def restore(self, snap: dict) -> None:
        import jax.numpy as jnp

        self.K = snap["K"]
        self.S = snap["S"]
        self.acc = {k: jnp.asarray(v) for k, v in snap["acc"].items()}
        self.count = jnp.asarray(snap["count"])
        self.keydict = KeyDictionary.restore(snap["keydict"])
        self.frontiers = RingFrontiers(**snap["frontiers"])
        self.last_touch = None
