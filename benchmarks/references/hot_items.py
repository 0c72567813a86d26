"""Plain reference of NEXMark Query 5, Hot Items: per hopping window, the
auction with the most bids and that count.

Named by a configuration's `reference.module`. Independent of the code under
test: it imports nothing of `flink_tpu` and takes nothing the program made.
It takes `keyed_window_count`'s exact [windows, keys] matrix of bids per
auction (numpy `bincount` per slice over the same seeded cycle) and keeps,
in every window that holds a bid, ONE cell: the largest count, at the lowest
auction id that reaches it (`np.argmax` returns the first maximum). Every
other cell is zero, so `reference.compare` holds the job to one row per
window: a row for any other auction is a wrong cell, a missing winner a
missing cell.

`semantics` is `keyed_window_count`'s block (`filter`, `key`, `keys`,
`tables`); the signatures are that module's.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from benchmarks.references import keyed_window_count as kwc
from benchmarks.stream import Cycle

make_tables = kwc.make_tables


def hottest(counts: np.ndarray) -> np.ndarray:
    """[windows, keys] counts -> the same shape holding, per window with a
    count, its maximum at the lowest key that reaches it; zero elsewhere."""
    out = np.zeros_like(counts)
    rows = np.flatnonzero(counts.any(axis=1))
    best = counts[rows].argmax(axis=1)
    out[rows, best] = counts[rows, best]
    return out


def expected(cycle: Cycle, semantics: Dict, tables: Dict, window: Dict,
             events: int, jitter_ms: int,
             replay: Optional[Tuple[int, int]] = None):
    """([windows, keys] int32 with one non-zero cell per window that holds a
    bid, first window index); `replay` as in `keyed_window_count`."""
    counts, j0 = kwc.expected(cycle, semantics, tables, window, events,
                              jitter_ms, replay=replay)
    return hottest(counts), j0
