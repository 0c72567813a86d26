"""Fully-fused SPMD window step: keyBy all-to-all → scatter ingest → window
fire → psum global merge, as ONE shard_map program.

This is the pure-device hot path for multi-chip deployments: each shard
feeds its locally-ingested lanes, the keyBy shuffle rides ICI inside the
compiled program (no host round-trip between shuffle and state update —
compare the reference's record path §3.3, which crosses the Netty network
boundary between RecordWriter.emit and the downstream WindowOperator), and
the global-window merge (Nexmark Q7-style global max/count) is a `psum`/
`pmax` collective instead of a singleton downstream operator.

Key ids here are *globally dense* (source-assigned), so owner shards index
state rows directly after the exchange; the host-routed operator
(parallel/sharded_window.py) is the general path for arbitrary keys.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.ops.aggregators import DeviceAggregator, ONE
from flink_tpu.ops.exchange import keyby_exchange_fn
from flink_tpu.ops.segment_ops import INVALID_INDEX


@functools.lru_cache(maxsize=None)
def make_spmd_step(mesh: Mesh, max_parallelism: int, agg: DeviceAggregator,
                   axis: str = "shards"):
    """Build the jitted fused step.

    step(acc {f:[n,K,S]}, count [n,K,S],
         key_groups [n,B] i32, kid [n,B] i32 (global dense), spos [n,B] i32,
         vals [n,B] f32, fire_positions [spw] i32)
      -> (acc', count', result [n,K], mask [n,K], global_count scalar-per-shard [n])
    """
    n = mesh.shape[axis]
    exchange = keyby_exchange_fn(n, max_parallelism, axis)

    def body(acc, count, key_groups, kid, spos, vals, fire_positions):
        acc1 = {k: v[0] for k, v in acc.items()}
        count1 = count[0]

        # 1. keyBy shuffle over ICI
        kg_r, cols = exchange(
            key_groups[0], {"kid": kid[0], "spos": spos[0], "vals": vals[0]}
        )
        kid_r, spos_r, vals_r = cols["kid"], cols["spos"], cols["vals"]

        # 2. scatter-combine ingest into this shard's columns
        new_acc = {}
        for f in agg.fields:
            src = (
                jnp.ones(vals_r.shape, dtype=f.dtype)
                if f.source == ONE
                else vals_r.astype(f.dtype)
            )
            ref = acc1[f.name].at[kid_r, spos_r]
            op = {"add": ref.add, "min": ref.min, "max": ref.max}[f.scatter]
            new_acc[f.name] = op(src, mode="drop")
        new_count = count1.at[kid_r, spos_r].add(
            jnp.ones(kid_r.shape, dtype=count1.dtype), mode="drop"
        )

        # 3. window fire: segment-reduce over the window's slice columns
        combined = {}
        for f in agg.fields:
            cols_f = jnp.take(new_acc[f.name], fire_positions, axis=1)
            red = {"add": cols_f.sum, "min": cols_f.min, "max": cols_f.max}[f.scatter]
            combined[f.name] = red(axis=1)
        cnt = jnp.take(new_count, fire_positions, axis=1).sum(axis=1)
        mask = cnt > 0
        result = agg.extract(combined).astype(agg.result_dtype)

        # 4. global merge across shards (the psum that replaces a singleton
        #    downstream global-window operator)
        global_count = jax.lax.psum(cnt.sum(), axis)

        return (
            {k: v[None] for k, v in new_acc.items()},
            new_count[None],
            result[None],
            mask[None],
            global_count[None],
        )

    s3 = P(axis, None, None)
    s2 = P(axis, None)
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=({f.name: s3 for f in agg.fields}, s3, s2, s2, s2, s2, P()),
        out_specs=({f.name: s3 for f in agg.fields}, s3, s2, s2, P(axis)),
    )
    return jax.jit(fn, donate_argnums=(0, 1))
