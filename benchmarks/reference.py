"""The comparison that decides `correct`, over what a plain reference expects.

A configuration names its plain reference (`reference.module`, a file under
`references/`): it gives the [windows, keys] matrix of values the job has
to emit for the events it was handed. Here are the parts every such
configuration shares: turning the sink's rows into that matrix, the numbers
compared with their limits, and the verdict. Imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

#: numbers compared, each with its limit (all exact: the limit is 0)
LIMITS = {name: ("<=", 0) for name in (
    "cells_wrong", "cells_missing", "cells_twice", "rows_outside",
    "records_in_gap", "windows_missing")}


def fired_matrix(rows: List[tuple], n_win: int, K: int, j0: int, window: Dict):
    """Emitted (key, count) rows -> ([windows, keys] int32, how often each
    cell was emitted, rows outside the stream). A result row carries
    `window.end - 1`, which identifies its window."""
    size, slide = int(window["size_ms"]), int(window["slide_ms"])
    empty = np.empty(0, np.int64)
    keys = np.concatenate([r[0] for r in rows] or [empty])
    vals = np.concatenate([np.asarray(r[1]) for r in rows] or [empty])
    start = np.concatenate([r[2] for r in rows] or [empty]) + 1 - size
    j = start // slide - j0
    ok = ((start % slide == 0) & (j >= 0) & (j < n_win)
          & (keys >= 0) & (keys < K))
    cell = j[ok] * K + keys[ok]
    got = np.zeros(n_win * K, np.int32)
    got[cell] = vals[ok]
    times = np.bincount(cell, minlength=n_win * K)
    return (got.reshape(n_win, K), times.reshape(n_win, K),
            int(len(ok) - ok.sum()))


def compare(rows: List[tuple], expect: np.ndarray, j0: int, window: Dict,
            records_in: int, events_sent: int, windows_due_missing: int = 0) -> Dict:
    """Every number compared, beside its limit. A cell is emitted iff
    records fell into it, and holds their count."""
    n_win, K = expect.shape
    got, times, outside = fired_matrix(rows, n_win, K, j0, window)
    due = expect > 0
    numbers = {
        "cells_wrong": int(((got != expect) & (times > 0)).sum()),
        "cells_missing": int((due & (times == 0)).sum()),
        "cells_twice": int((times > 1).sum()),
        "rows_outside": int(outside),
        "records_in_gap": abs(int(records_in) - int(events_sent)),
        "windows_missing": int(windows_due_missing),
    }
    return {
        "numbers": numbers,
        "cells_compared": int(due.sum()),
        "rows_compared": int(sum(len(r[0]) for r in rows)),
    }


def rows_of(counts: np.ndarray, j0: int, window: Dict) -> List[tuple]:
    """A [windows, keys] matrix as the rows a sink would have received: one
    (key, count) row per non-empty cell, stamped `window.end - 1`."""
    j, k = np.nonzero(counts)
    ts = (j + j0) * int(window["slide_ms"]) + int(window["size_ms"]) - 1
    return [(k.astype(np.int64), counts[j, k].astype(np.int64),
             ts.astype(np.int64))]


def verdict(numbers: Dict, limits: Dict) -> bool:
    """True iff every number compared is inside its limit."""
    ok = True
    for name, (op, bound) in limits.items():
        value = numbers[name]
        ok &= value <= bound if op == "<=" else value >= bound
    return bool(ok)
