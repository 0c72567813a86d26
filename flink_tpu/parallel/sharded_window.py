"""Sharded window operator: key-group data parallelism over a device mesh.

The multi-device form of runtime/tpu_window_operator.py: accumulator columns
get a leading shard axis ([n_shards, K, S], sharded over the mesh's
"shards" axis), records are routed to the shard owning their key group
(KeyGroupRangeAssignment semantics — shard = key_group * n // max_parallelism,
matching computeOperatorIndexForKeyGroup), and every device step runs as a
shard_map program so ingest/fire/purge execute on all shards simultaneously
with zero host round-trips between shards.

Routing happens host-side here (records enter through one host in the local
runtime); the pure-device all-to-all route (ops/exchange.py) is the
multi-host ingest path where each host feeds its local devices and the
shuffle rides ICI.

Snapshot/rescale: state is keyed by (key → key group), not by device, so a
snapshot taken at n shards restores onto m shards by re-routing every key to
its new owner (the reference's key-group re-sharding on restore,
StateAssignmentOperation).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.ops import segment_ops
from flink_tpu.ops.aggregators import DeviceAggregator, ONE
from flink_tpu.parallel.mesh import SHARD_AXIS
from flink_tpu.state.columnar import KeyDictionary, RingFrontiers


@functools.lru_cache(maxsize=None)
def _make_sharded_ingest(agg: DeviceAggregator, mesh: Mesh, axis: str):
    def body(acc, count, kid, spos, vals):
        # per-shard views [1, ...]: strip and restore the leading axis
        acc1 = {k: v[0] for k, v in acc.items()}
        new_acc = {}
        for f in agg.fields:
            src = (
                jnp.ones(vals[0].shape, dtype=f.dtype)
                if f.source == ONE
                else vals[0].astype(f.dtype)
            )
            ref = acc1[f.name].at[kid[0], spos[0]]
            op = {"add": ref.add, "min": ref.min, "max": ref.max}[f.scatter]
            new_acc[f.name] = op(src, mode="drop")[None]
        new_count = count[0].at[kid[0], spos[0]].add(
            jnp.ones(kid[0].shape, dtype=count.dtype), mode="drop"
        )[None]
        touch = (
            jnp.zeros(count[0].shape, dtype=jnp.bool_)
            .at[kid[0], spos[0]]
            .set(True, mode="drop")[None]
        )
        return new_acc, new_count, touch

    s3 = P(axis, None, None)
    s2 = P(axis, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=({f.name: s3 for f in agg.fields}, s3, s2, s2, s2),
        out_specs=({f.name: s3 for f in agg.fields}, s3, s3),
    )
    return jax.jit(fn, donate_argnums=(0, 1))


@functools.lru_cache(maxsize=None)
def _make_sharded_fire(agg: DeviceAggregator, mesh: Mesh, axis: str, masked: bool):
    def body(acc, count, positions, touch=None):
        combined = {}
        for f in agg.fields:
            cols = jnp.take(acc[f.name][0], positions, axis=1)  # [K, spw]
            red = {"add": cols.sum, "min": cols.min, "max": cols.max}[f.scatter]
            combined[f.name] = red(axis=1)
        cnt = jnp.take(count[0], positions, axis=1).sum(axis=1)
        mask = cnt > 0
        if masked:
            mask = mask & jnp.take(touch[0], positions, axis=1).any(axis=1)
        result = agg.extract(combined).astype(agg.result_dtype)
        return result[None], cnt[None], mask[None]

    s3 = P(axis, None, None)
    s2 = P(axis, None)
    in_specs = ({f.name: s3 for f in agg.fields}, s3, P())
    if masked:
        in_specs = in_specs + (s3,)
    fn = shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=(s2, s2, s2)
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _make_sharded_purge(agg: DeviceAggregator, mesh: Mesh, axis: str, num_positions: int):
    def body(acc, count, positions):
        K = count.shape[1]
        col_idx = jnp.broadcast_to(positions[None, :], (K, num_positions))
        row_idx = jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int32)[:, None], (K, num_positions)
        )
        new_acc = {}
        for f in agg.fields:
            ident = jnp.full((K, num_positions), f.identity, dtype=f.dtype)
            new_acc[f.name] = acc[f.name][0].at[row_idx, col_idx].set(ident, mode="drop")[None]
        zeros = jnp.zeros((K, num_positions), dtype=count.dtype)
        new_count = count[0].at[row_idx, col_idx].set(zeros, mode="drop")[None]
        return new_acc, new_count

    s3 = P(axis, None, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=({f.name: s3 for f in agg.fields}, s3, P()),
        out_specs=({f.name: s3 for f in agg.fields}, s3),
    )
    return jax.jit(fn, donate_argnums=(0, 1))


class ShardedColumnarState:
    """[n_shards, K, S] accumulator columns sharded over the mesh, with one
    host key dictionary per shard (keys are disjoint across shards by
    key-group ownership)."""

    PURGE_CHUNK = 8

    def __init__(
        self,
        agg: DeviceAggregator,
        mesh: Mesh,
        *,
        key_capacity: int = 1 << 12,
        num_slices: int = 64,
        dense_int_keys: bool = False,
        axis: str = SHARD_AXIS,
    ):
        self.agg = agg
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.K = key_capacity
        self.S = num_slices
        self.keydicts = [KeyDictionary(dense_int_keys) for _ in range(self.n)]
        self.frontiers = RingFrontiers()
        self._sharding3 = NamedSharding(mesh, P(axis, None, None))
        self._sharding2 = NamedSharding(mesh, P(axis, None))
        self._init_arrays()
        self._ingest = _make_sharded_ingest(agg, mesh, axis)
        self._fire = _make_sharded_fire(agg, mesh, axis, False)
        self._fire_masked = _make_sharded_fire(agg, mesh, axis, True)
        self._purge = _make_sharded_purge(agg, mesh, axis, self.PURGE_CHUNK)
        self.last_touch = None

    def _init_arrays(self):
        self.acc = {
            f.name: jax.device_put(
                np.full((self.n, self.K, self.S), f.identity, dtype=f.dtype), self._sharding3
            )
            for f in self.agg.fields
        }
        self.count = jax.device_put(
            np.zeros((self.n, self.K, self.S), dtype=np.int32), self._sharding3
        )

    def ensure_key_capacity(self, required: int) -> None:
        if required <= self.K:
            return
        new_k = self.K
        while new_k < required:
            new_k *= 2
        pad_n = new_k - self.K
        acc_h = {k: np.asarray(v) for k, v in self.acc.items()}
        cnt_h = np.asarray(self.count)
        for f in self.agg.fields:
            filler = np.full((self.n, pad_n, self.S), f.identity, dtype=f.dtype)
            acc_h[f.name] = np.concatenate([acc_h[f.name], filler], axis=1)
        cnt_h = np.concatenate(
            [cnt_h, np.zeros((self.n, pad_n, self.S), dtype=np.int32)], axis=1
        )
        self.acc = {k: jax.device_put(v, self._sharding3) for k, v in acc_h.items()}
        self.count = jax.device_put(cnt_h, self._sharding3)
        self.K = new_k
        self.last_touch = None

    def ingest(self, kid: np.ndarray, slices_abs: np.ndarray, vals: np.ndarray) -> None:
        """kid/slices/vals are [n, B] routed arrays (INVALID-padded)."""
        f = self.frontiers
        valid = kid != segment_ops.INVALID_INDEX
        live = slices_abs[valid]
        if live.size:
            lo, hi = int(live.min()), int(live.max())
            f.min_used = lo if f.min_used is None else min(f.min_used, lo)
            f.max_used = hi if f.max_used is None else max(f.max_used, hi)
        spos = np.where(valid, slices_abs % self.S, segment_ops.INVALID_INDEX).astype(np.int32)
        kid_d = jax.device_put(kid.astype(np.int32), self._sharding2)
        spos_d = jax.device_put(spos, self._sharding2)
        vals_d = jax.device_put(vals, self._sharding2)
        self.acc, self.count, self.last_touch = self._ingest(
            self.acc, self.count, kid_d, spos_d, vals_d
        )

    def fire(self, slice_range: range, *, touch_mask: bool = False):
        positions = np.asarray([s % self.S for s in slice_range], dtype=np.int32)
        if touch_mask:
            if self.last_touch is None:
                return None  # nothing ingested since restore: no refire
            return self._fire_masked(self.acc, self.count, positions, self.last_touch)
        return self._fire(self.acc, self.count, positions)

    def purge_slices(self, slices_abs: List[int]) -> None:
        for i in range(0, len(slices_abs), self.PURGE_CHUNK):
            chunk = slices_abs[i : i + self.PURGE_CHUNK]
            positions = np.full(self.PURGE_CHUNK, segment_ops.INVALID_INDEX, dtype=np.int32)
            positions[: len(chunk)] = [s % self.S for s in chunk]
            self.acc, self.count = self._purge(self.acc, self.count, positions)

    def reset_all(self) -> None:
        self._init_arrays()
        self.last_touch = None

    def snapshot(self) -> dict:
        return {
            "acc": {k: np.asarray(v) for k, v in self.acc.items()},
            "count": np.asarray(self.count),
            "keydicts": [d.snapshot() for d in self.keydicts],
            "frontiers": dataclasses.asdict(self.frontiers),
            "n": self.n,
            "K": self.K,
            "S": self.S,
        }


def __getattr__(name):
    """Back-compat: ShardedTpuWindowOperator subclasses the runtime's
    TpuWindowOperator and therefore moved to
    runtime/sharded_window_operator.py when `parallel` became an ARCH001
    layer (may import core/ops/state/config, never runtime). The lazy
    module attribute keeps the historical import path working without a
    module-level runtime edge."""
    if name == "ShardedTpuWindowOperator":
        from flink_tpu.runtime.sharded_window_operator import (
            ShardedTpuWindowOperator,
        )

        return ShardedTpuWindowOperator
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
