"""The advertising topology with a key selector the tracer cannot see.

The same stream and the same results as `ysb_traced`, but filter, join and
key selector are numpy column functions (`vectorized=True`): the chain runs on
the host, keys go through the host key dictionary (native library), and the
window runs the Pallas superscan, as `chip_smoke.py` leg 2 selects it.
"""

from __future__ import annotations

import functools
from typing import Dict

from benchmarks.jobs.common import assigner_of, column_index, from_source


@functools.lru_cache(maxsize=None)
def _udfs(table_key: tuple, ad_col: int, type_col: int, view: int):
    import numpy as np

    table = np.asarray(table_key, np.int64)

    def is_view(col):
        return col[:, type_col] < view + 0.5

    def campaign_of(col):
        return table[col[:, ad_col].astype(np.int64)]

    return is_view, campaign_of


def build(env, source, sink, cfg: Dict, tables: Dict) -> None:
    sem = cfg["reference"]
    is_view, campaign_of = _udfs(
        tuple(int(x) for x in tables[sem["key"]["table"]]),
        column_index(cfg, sem["key"]["column"]),
        column_index(cfg, sem["filter"]["column"]),
        int(sem["filter"]["keep_below"]) - 1)
    ds = from_source(env, source, cfg)
    ds = ds.filter(is_view, vectorized=True)
    keyed = ds.key_by(campaign_of, vectorized=True)
    keyed.window(assigner_of(cfg["window"])).aggregate("count").sink_to(sink)
