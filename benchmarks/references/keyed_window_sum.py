"""Plain reference of a keyed SUM of a value column per event-time window.

Named by a configuration's `reference.module`. Independent of the code under
test: it imports nothing of `flink_tpu` and takes nothing the program made.
The same seeded cycle the reader served is replayed with numpy
`bincount(..., weights=value)` in float64 into per-slice key sums (a slice
is the gcd of the window's size, its slide and the cycle, so a cycle spans a
whole number of slices and lap l adds the cycle's sums `l *
slices_per_cycle` rows further on); a window is a difference of the
cumulative sum over slices. The values are integers, every window's sum
stays far below 2^53, so float64 holds each exactly and the cast to int32
(the matrix `reference.compare` takes) loses nothing while a sum is below
2^31; the configuration keeps them below 2^24, where the program's f32 is
exact too.

`semantics` is the configuration's `reference` block:
  {"key": {"column": c},      key = c
   "value": {"column": c},    the column summed, integer valued
   "keys": K,                 size of the key space
   "filter", "tables"         as `keyed_window_count` reads them (optional)}

`reference.compare` takes a cell as due where `expect > 0`: a (window, key)
whose records all carry the value 0 is emitted by the job with the sum 0 and
is not due here, so it is neither missing nor wrong (the job's 0 equals the
matrix's 0); what the comparison loses there is only the check that the row
was emitted. At the configuration's density a cell of a full window holds
~122 records and a price is 0 once in 9 362 draws: all of them 0 never
happens. It does happen a few times a run (fewer than 40 cells of ~55 M) in
the partial windows at both ends of a run, where a cell holds one record
(`benchmarks/tests/test_purchases_sum.py` counts them on one seed).

The signatures are `keyed_window_count`'s: `make_tables(semantics)`,
`expected(cycle, semantics, tables, window, events, jitter_ms, replay=None)`
-> ([windows, keys] int32, first window index).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from benchmarks.references import keyed_window_count as kwc
from benchmarks.stream import T0_MS, Cycle

make_tables = kwc.make_tables


def expected(cycle: Cycle, semantics: Dict, tables: Dict, window: Dict,
             events: int, jitter_ms: int,
             replay: Optional[Tuple[int, int]] = None):
    """Exact per-window, per-key sums of the value column over the first
    `events` events of the stream. Returns (sums int32 [n_windows, keys],
    j0): row r is window `j0 + r`, which starts at `(j0 + r) * slide_ms`.
    `replay=(lo, hi)` sums the events [lo, hi) of lap 0 a second time: the
    CONTROL, a stream delivered at-least-once instead of exactly-once."""
    K = int(semantics["keys"])
    rs = kwc.slice_ms_of(window, cycle.cycle_ms)
    cs = cycle.cycle_ms // rs                      # slices per cycle
    s_lo = (T0_MS - jitter_ms) // rs               # first slice that can hold a record
    laps, rest = divmod(events, cycle.events)
    n_slices = (laps + (1 if rest else 0)) * cs + (T0_MS // rs - s_lo) + 1
    value_col = semantics["value"]["column"]

    def sums(lo, hi):
        key, keep = kwc._keys_kept(cycle, semantics, tables, lo, hi)
        rel = cycle.ts[lo:hi][keep] // rs - s_lo
        weights = cycle.column(value_col)[lo:hi][keep].astype(np.float64)
        return np.bincount(rel * K + key, weights=weights,
                           minlength=(cs + 2) * K)[
            :(cs + 2) * K].reshape(cs + 2, K)

    total = np.zeros((n_slices + cs + 2, K), np.float64)
    if laps:
        full = np.zeros((cs + 2, K), np.float64)
        for lo in range(0, cycle.events, 1 << 22):
            full += sums(lo, min(lo + (1 << 22), cycle.events))
        for lap in range(laps):
            total[lap * cs: lap * cs + cs + 2] += full
    for lo in range(0, rest, 1 << 22):
        total[laps * cs: laps * cs + cs + 2] += sums(lo, min(lo + (1 << 22), rest))
    if replay is not None:
        total[: cs + 2] += sums(*replay)
    total = total[:n_slices]

    spw = int(window["size_ms"]) // rs             # slices per window
    step = int(window["slide_ms"]) // rs
    # window j starts at slice j*step (absolute); the first that can hold a
    # record is the first whose last slice reaches s_lo
    j0 = -(-(s_lo - spw + 1) // step)
    j_hi = (s_lo + n_slices - 1) // step           # last window that starts inside
    csum = np.zeros((n_slices + 1, K), np.float64)
    np.cumsum(total, axis=0, out=csum[1:])
    starts = np.arange(j0, j_hi + 1) * step - s_lo  # relative slice of each start
    a = np.clip(starts, 0, n_slices)
    b = np.clip(starts + spw, 0, n_slices)
    return np.rint(csum[b] - csum[a]).astype(np.int32), int(j0)
