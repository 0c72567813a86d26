"""The advertising topology on the fused device chain.

from_source -> filter(event_type == view) -> map(project + join ad ->
campaign) -> key_by(campaign) -> tumbling window -> count -> sink, every UDF
`traceable=True`, so the executor picks DeviceChainRunner and the chained XLA
superscan. The join is a gather over the campaign table inside the traced
prologue. UDFs are built once per table and kept, so a second job in the
process finds the compiled program again (the program caches by identity).
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.jobs.common import assigner_of, column_index, from_source


@functools.lru_cache(maxsize=None)
def _udfs(table_key: tuple, ad_col: int, type_col: int, view: int):
    import jax.numpy as jnp
    import numpy as np

    table = jnp.asarray(np.asarray(table_key, np.int32))

    def is_view(col):
        return col[:, type_col] < view + 0.5

    def project_and_join(col):
        ad = col[:, ad_col].astype(jnp.int32)
        campaign = jnp.take(table, ad, axis=0)
        return jnp.stack([campaign.astype(jnp.float32), col[:, ad_col]], axis=1)

    def campaign_of(col):
        return col[:, 0].astype(jnp.int32)

    return is_view, project_and_join, campaign_of


def build(env, source, sink, cfg: Dict, tables: Dict) -> None:
    sem = cfg["reference"]
    is_view, join, campaign_of = _udfs(
        tuple(int(x) for x in tables[sem["key"]["table"]]),
        column_index(cfg, sem["key"]["column"]),
        column_index(cfg, sem["filter"]["column"]),
        int(sem["filter"]["keep_below"]) - 1)
    ds = from_source(env, source, cfg)
    ds = ds.filter(is_view, traceable=True)
    ds = ds.map(join, traceable=True)
    keyed = ds.key_by(campaign_of, traceable=True)
    keyed.window(assigner_of(cfg["window"])).aggregate("count").sink_to(sink)
