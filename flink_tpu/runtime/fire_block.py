"""One fired window's emissions as columns, from the fused operator to the
edge of the downstream hand-over.

A fire of the fused window operator exists as two numpy columns (the live
key ids, their results) and two scalars (the window, its timestamp) before
any row does. `FireBlock` carries it in that form; rows are built from a
block at most once, by whole-column calls (`tolist` + `zip`), by whoever
needs rows: `rows_of` for `drain_output()`'s callers, `downstream_batch`
for the runner's hand-over; a null-key window behind the operator takes
one row of a block without building the others (`reduce_block`), a SQL plan
that keeps each window's maxima the tied rows alone (`window_maxima`). The
other window operators (oracle,
TpuWindowOperator, session, global) drain `(key, window, result, ts)` rows;
`downstream_batch` and `fires_of` take those too.
"""

from __future__ import annotations

from itertools import chain, groupby, repeat
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from flink_tpu.utils.arrays import obj_array

Row = Tuple[Any, Any, Any, int]


def _column(col) -> Sequence:
    """Python scalars of a column: the same `int` / `float` `.item()` gives."""
    return col.tolist() if isinstance(col, np.ndarray) else col


class FireBlock:
    """The rows one window fired: `keys[i]` emitted `results[i]`, all at
    `ts`. Columns are ndarrays or sequences of equal length. `keys is None`
    says the rows carry no key and downstream takes the bare result
    (`execution.window.columnar-output`: one packed row per fire)."""

    __slots__ = ("window", "keys", "results", "ts", "seq")

    def __init__(self, window, keys: Optional[Sequence], results: Sequence,
                 ts: int, seq: Optional[int] = None):
        self.window = window
        self.keys = keys
        self.results = results
        self.ts = ts
        self.seq = seq      # the dispatch that fired it (the stage clock's)

    def __len__(self) -> int:
        return len(self.results)

    def rows(self) -> Iterator[Row]:
        keys = repeat(None) if self.keys is None else _column(self.keys)
        return zip(keys, repeat(self.window), _column(self.results),
                   repeat(self.ts))


def rows_of(blocks: Sequence[FireBlock]) -> List[Row]:
    """`(key, window, result, ts)` rows of Python scalars, fire after fire."""
    return list(chain.from_iterable(b.rows() for b in blocks))


def blocks_of(rows: Sequence[Row]) -> List[FireBlock]:
    """Rows back into blocks, one per run of rows that share window and
    timestamp (a restored checkpoint's undrained emissions)."""
    blocks: List[FireBlock] = []
    for (w, t, keyless), run in groupby(
            rows, key=lambda r: (r[1], r[3], r[0] is None)):
        keys, _w, results, _t = zip(*run)
        blocks.append(FireBlock(w, None if keyless else keys, results, t))
    return blocks


def fires_of(drained: Sequence) -> Iterator[Tuple[Any, int]]:
    """`(window, ts)` once per block, or per row where an operator drains
    rows."""
    for d in drained:
        yield (d.window, d.ts) if type(d) is FireBlock else (d[1], d[3])


def reduce_block(block: FireBlock, agg) -> Optional[Tuple[Any, Any]]:
    """The one `(key, result)` row of `block` that `agg` (a
    `PositionalAggregate`: `max_by` / `min_by` over position 0, the key, or
    1, the result) keeps of the block's rows, by whole-column calls; None
    where only the rows themselves can say (no key column, a column that is
    no plain numeric ndarray, a NaN, another position): the caller then
    takes `downstream_batch`. All rows of a block share one timestamp, so
    for a window that holds them all the row returned stands for them."""
    if block.keys is None or agg.position not in (0, 1) or not len(block):
        return None
    column = block.keys if agg.position == 0 else block.results
    if not isinstance(column, np.ndarray) or column.dtype.kind not in "iuf" \
            or (column.dtype.kind == "f" and np.isnan(column).any()):
        return None
    i = agg.pick(column)
    return _scalar(block.keys[i]), _scalar(block.results[i])


def window_maxima(blocks: Sequence[FireBlock]) -> Optional[List[FireBlock]]:
    """Of each window's fires (the blocks that share a timestamp), the rows
    whose result is the window's maximum, every tied key, in ascending key
    order, as one block a window; by whole-column calls (one `max` and one
    equality mask a block). None where only the rows can say (no key
    column, a column that is no plain numeric ndarray, a NaN): the caller
    then takes `downstream_batch`."""
    windows: Dict[int, List[FireBlock]] = {}
    for b in blocks:
        if b.keys is None or not all(
                isinstance(c, np.ndarray) and c.dtype.kind in "iuf"
                for c in (b.keys, b.results)) \
                or (b.results.dtype.kind == "f" and np.isnan(b.results).any()):
            return None
        if len(b):
            windows.setdefault(b.ts, []).append(b)
    out = []
    for ts, group in windows.items():
        top = max(b.results.max() for b in group)
        keep = [b.results == top for b in group]
        keys = np.concatenate([b.keys[m] for b, m in zip(group, keep)])
        order = np.argsort(keys, kind="stable")
        results = np.concatenate([b.results[m] for b, m in zip(group, keep)])
        out.append(FireBlock(group[0].window, keys[order], results[order], ts,
                             group[0].seq))
    return out


def _scalar(v):
    return v.item() if isinstance(v, np.generic) else v


def downstream_batch(drained: Sequence,
                     bare: bool) -> Tuple[np.ndarray, np.ndarray]:
    """`(vals, ts)` as a window step hands them downstream: a 1-D object
    array of `(key, result)` pairs of Python scalars (the bare result where
    `bare`, a window function's output, or where a row carries no key) and
    the i64 timestamps. `drained` is one operator's drain: blocks (the fused
    operator) or rows (every other window operator). A block costs O(1)
    Python calls plus whole-column work."""
    if type(drained[0]) is not FireBlock:
        vals = obj_array([r if (bare or k is None) else (k, r)
                          for (k, _w, r, _t) in drained])
        return vals, np.asarray([t for (_k, _w, _r, t) in drained],
                                dtype=np.int64)
    vals = np.empty(sum(map(len, drained)), dtype=object)
    ts = np.empty(len(vals), dtype=np.int64)
    at = 0
    for b in drained:
        end = at + len(b)
        results = _column(b.results)
        # fromiter, not a slice assignment: a list of equal-length tuples
        # would be read as a 2-D array, and a list copy costs a pass
        vals[at:end] = np.fromiter(
            results if (bare or b.keys is None)
            else zip(_column(b.keys), results),
            dtype=object, count=end - at)
        ts[at:end] = b.ts
        at = end
    return vals, ts
