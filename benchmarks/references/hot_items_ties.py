"""Plain reference of NEXMark Query 5 as `q5.sql` states it: per hopping
window, every auction whose bid count is the window's maximum.

Named by a configuration's `reference.module`. Independent of the code under
test: it imports nothing of `flink_tpu` and takes nothing the program made.
It takes `keyed_window_count`'s exact [windows, keys] matrix of bids per
auction (numpy `bincount` per slice over the same seeded cycle) and keeps, in
every window that holds a bid, each cell equal to the window's maximum; every
other cell is zero. `reference.compare` then holds the job to one row per
tied auction per window: a row for any other auction is a wrong cell, a
missing tied auction a missing cell.

`semantics` is `keyed_window_count`'s block (`filter`, `key`, `keys`,
`tables`); the signatures are that module's.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from benchmarks.references import keyed_window_count as kwc
from benchmarks.stream import Cycle

make_tables = kwc.make_tables


def maxima(counts: np.ndarray) -> np.ndarray:
    """[windows, keys] counts -> the same shape holding, per window with a
    count, that count at every key that reaches the window's maximum; zero
    elsewhere."""
    top = counts.max(axis=1, keepdims=True)
    return np.where((counts == top) & (top > 0), counts, 0).astype(counts.dtype)


def expected(cycle: Cycle, semantics: Dict, tables: Dict, window: Dict,
             events: int, jitter_ms: int,
             replay: Optional[Tuple[int, int]] = None):
    """([windows, keys] int32 with every maximum of each window that holds a
    bid, first window index); `replay` as in `keyed_window_count`."""
    counts, j0 = kwc.expected(cycle, semantics, tables, window, events,
                              jitter_ms, replay=replay)
    return maxima(counts), j0
