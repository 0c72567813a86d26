"""key_by -> windowed SUM of a value column on the fused device chain.

The keyed aggregation with a VALUE field: no filter (every record counts),
the key and the value are columns of the record, both UDFs `traceable=True`
(DeviceChainRunner, chained XLA superscan: a count ring and an f32 sum ring,
on a TPU the matmul histogram with its exact three-term split). The sink
receives one `(key, sum)` pair per (window, key) that holds a record.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.jobs.common import assigner_of, column_index, from_source


@functools.lru_cache(maxsize=None)
def _udfs(key_col: int, value_col: int):
    import jax.numpy as jnp

    def key_of(col):
        return col[:, key_col].astype(jnp.int32)

    def value_of(col):
        return col[:, value_col]

    return key_of, value_of


def build(env, source, sink, cfg: Dict, tables: Dict) -> None:
    sem = cfg["reference"]
    key_of, value_of = _udfs(column_index(cfg, sem["key"]["column"]),
                             column_index(cfg, sem["value"]["column"]))
    records = from_source(env, source, cfg)
    records.key_by(key_of, traceable=True) \
        .window(assigner_of(cfg["window"])) \
        .aggregate("sum", value_fn=value_of, value_traceable=True) \
        .sink_to(sink)
