"""filter -> key_by -> windowed count on the fused device chain.

The high-key-cardinality variant: no join, the key is a column of the record.
Every UDF is `traceable=True` (DeviceChainRunner, chained XLA superscan; on a
mesh the sharded superscan with its in-scan all-to-all).
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.jobs.common import assigner_of, column_index, from_source


@functools.lru_cache(maxsize=None)
def _udfs(key_col: int, type_col: int, view: int):
    import jax.numpy as jnp

    def is_view(col):
        return col[:, type_col] < view + 0.5

    def key_of(col):
        return col[:, key_col].astype(jnp.int32)

    return is_view, key_of


def build(env, source, sink, cfg: Dict, tables: Dict) -> None:
    sem = cfg["reference"]
    is_view, key_of = _udfs(
        column_index(cfg, sem["key"]["column"]),
        column_index(cfg, sem["filter"]["column"]),
        int(sem["filter"]["keep_below"]) - 1)
    ds = from_source(env, source, cfg)
    ds = ds.filter(is_view, traceable=True)
    keyed = ds.key_by(key_of, traceable=True)
    keyed.window(assigner_of(cfg["window"])).aggregate("count").sink_to(sink)
