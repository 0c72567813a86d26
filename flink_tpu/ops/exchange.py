"""On-device keyBy exchange: the ICI all-to-all replacing the network shuffle.

The reference's keyBy moves serialized records through Netty with
credit-based flow control (KeyGroupStreamPartitioner →
RecordWriter.emit:105 → … → RemoteInputChannel.onBuffer:590). On a TPU
slice there is no serialization and no credit protocol: the shuffle is ONE
`lax.all_to_all` over ICI inside a shard_map program — records stay columnar
end to end, and "flow control" is the static step batch size.

Lane protocol: each source shard holds B lanes (kid, slice-pos, value);
destination shard = key_group * n // max_parallelism, computed on device
from the key-group column. Lanes are routed positionally: the send buffer is
[n_shards, B] per column with non-destination lanes masked INVALID, so the
all-to-all needs no compaction/sort (bandwidth cost n×B lanes; dense
compaction via on-device sort is a later optimization once profiling says
the exchange is bandwidth-bound).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.ops.segment_ops import INVALID_INDEX


def keyby_exchange_fn(n_shards: int, max_parallelism: int, axis_name: str):
    """Per-shard body: route lanes to their key-group owners.

    inputs (per-shard view):
      key_groups: i32[B]   (INVALID_INDEX for padding lanes)
      columns:    dict of [B] arrays to route alongside (kid/spos/values)
    returns dict of [n_shards * B] arrays: the lanes this shard received
    (INVALID-masked lanes preserved as padding).
    """

    def body(key_groups: jnp.ndarray, columns: Dict[str, jnp.ndarray]):
        B = key_groups.shape[0]
        valid = key_groups != INVALID_INDEX
        dst = jnp.where(
            valid,
            key_groups * jnp.int32(n_shards) // jnp.int32(max_parallelism),
            jnp.int32(-1),
        )
        # send buffer row d = lanes destined for shard d, else INVALID
        rows = jnp.arange(n_shards, dtype=jnp.int32)[:, None]          # [n, 1]
        route = rows == dst[None, :]                                    # [n, B]
        out = {}
        for name, col in columns.items():
            if col.dtype in (jnp.int32, jnp.int64):
                pad = jnp.array(INVALID_INDEX, dtype=col.dtype)
            else:
                pad = jnp.zeros((), dtype=col.dtype)
            send = jnp.where(route, col[None, :], pad)                  # [n, B]
            recv = jax.lax.all_to_all(
                send, axis_name, split_axis=0, concat_axis=0, tiled=False
            )                                                           # [n, B]
            out[name] = recv.reshape(n_shards * B)
        kg_send = jnp.where(route, key_groups[None, :], jnp.int32(INVALID_INDEX))
        kg_recv = jax.lax.all_to_all(
            kg_send, axis_name, split_axis=0, concat_axis=0, tiled=False
        ).reshape(n_shards * B)
        return kg_recv, out

    return body


def make_keyby_exchange(mesh: Mesh, max_parallelism: int, axis_name: str = "shards"):
    """Jitted whole-mesh exchange: [n, B] sharded columns -> [n, n*B] sharded."""
    n = mesh.shape[axis_name]
    body = keyby_exchange_fn(n, max_parallelism, axis_name)

    def mesh_fn(key_groups, columns):
        # per-shard views arrive as [1, B]; strip/restore the leading axis
        kg, cols = body(key_groups[0], {k: v[0] for k, v in columns.items()})
        return kg[None], {k: v[None] for k, v in cols.items()}

    spec = P(axis_name, None)
    fn = shard_map(
        mesh_fn,
        mesh=mesh,
        in_specs=(spec, spec),
        out_specs=(spec, spec),
    )
    return jax.jit(fn)
