"""Plain reference of a keyed, filtered count per event-time window.

Named by a configuration's `reference.module`. Independent of the code under
test: it imports nothing of `flink_tpu` and takes nothing the program made.
The same seeded cycle the reader served is replayed with numpy `bincount`
into per-slice key histograms (copied from `chip_smoke.py`'s
`Stream.reference`, then tiled: a cycle spans a whole number of slices, so
lap l adds the cycle's histogram `l * slices_per_cycle` rows further on); a
window is the sum of the slices it covers.

`semantics` is the configuration's `reference` block:
  {"filter": {"column": c, "keep_below": v},        keep rows with c < v
   "key": {"column": c, "table": "ad_to_campaign"}  key = table[c]  (or
          {"column": c}                              key = c)
   "keys": K,                                       size of the key space
   "tables": {name: parameters}}                    static join tables
The job counts records per key per window; every count is exact.

A reference module gives `make_tables(semantics)` (static data both the job and
the reference read) and `expected(cycle, semantics, tables, window, events,
jitter_ms, replay=None)` -> ([windows, keys] int32, first window index).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import numpy as np

from benchmarks.stream import T0_MS, Cycle


def ad_to_campaign(table_cfg: Dict) -> np.ndarray:
    """The join table of the advertising topology: `ads` ad ids, each owned
    by one of `campaigns` campaigns, `ads // campaigns` ads apiece, assigned
    by a fixed permutation (static reference data, not part of the stream)."""
    ads, campaigns = int(table_cfg["ads"]), int(table_cfg["campaigns"])
    rng = np.random.RandomState(int(table_cfg["table_seed"]))
    return (rng.permutation(ads) // (ads // campaigns)).astype(np.int32)


_TABLES = {"ad_to_campaign": ad_to_campaign}


def make_tables(semantics: Dict) -> Dict[str, np.ndarray]:
    return {name: _TABLES[name](tc)
            for name, tc in semantics.get("tables", {}).items()}


def _keys_kept(cycle: Cycle, semantics: Dict, tables: Dict, lo: int, hi: int):
    keep = np.ones(hi - lo, bool)
    f = semantics.get("filter")
    if f:
        keep = cycle.column(f["column"])[lo:hi] < f["keep_below"]
    k = semantics["key"]
    key = cycle.column(k["column"])[lo:hi].astype(np.int64)
    if "table" in k:
        key = tables[k["table"]][key].astype(np.int64)
    return key[keep], keep


def slice_ms_of(window: Dict, cycle_ms: int) -> int:
    return math.gcd(math.gcd(int(window["size_ms"]), int(window["slide_ms"])),
                    int(cycle_ms))


def expected(cycle: Cycle, semantics: Dict, tables: Dict, window: Dict,
                  events: int, jitter_ms: int,
                  replay: Optional[Tuple[int, int]] = None):
    """Exact per-window, per-key counts of the first `events` events of the
    stream. Returns (counts int32 [n_windows, keys], j0): row r is window
    `j0 + r`, which starts at `(j0 + r) * slide_ms`. `replay=(lo, hi)` counts
    the events [lo, hi) of lap 0 a second time: the CONTROL, a stream
    delivered at-least-once instead of exactly-once."""
    K = int(semantics["keys"])
    rs = slice_ms_of(window, cycle.cycle_ms)
    cs = cycle.cycle_ms // rs                      # slices per cycle
    s_lo = (T0_MS - jitter_ms) // rs               # first slice that can hold a record
    laps, rest = divmod(events, cycle.events)
    n_slices = (laps + (1 if rest else 0)) * cs + (T0_MS // rs - s_lo) + 1

    def hist(lo, hi):
        key, keep = _keys_kept(cycle, semantics, tables, lo, hi)
        rel = cycle.ts[lo:hi][keep] // rs - s_lo
        return np.bincount(rel * K + key, minlength=(cs + 2) * K)[
            :(cs + 2) * K].reshape(cs + 2, K)

    total = np.zeros((n_slices + cs + 2, K), np.int64)
    if laps:
        full = np.zeros((cs + 2, K), np.int64)
        for lo in range(0, cycle.events, 1 << 22):
            full += hist(lo, min(lo + (1 << 22), cycle.events))
        for lap in range(laps):
            total[lap * cs: lap * cs + cs + 2] += full
    for lo in range(0, rest, 1 << 22):
        total[laps * cs: laps * cs + cs + 2] += hist(lo, min(lo + (1 << 22), rest))
    if replay is not None:
        total[: cs + 2] += hist(*replay)
    total = total[:n_slices]

    spw = int(window["size_ms"]) // rs             # slices per window
    step = int(window["slide_ms"]) // rs
    # window j starts at slice j*step (absolute); the first that can hold a
    # record is the first whose last slice reaches s_lo
    j0 = -(-(s_lo - spw + 1) // step)
    j_hi = (s_lo + n_slices - 1) // step           # last window that starts inside
    csum = np.zeros((n_slices + 1, K), np.int64)
    np.cumsum(total, axis=0, out=csum[1:])
    starts = np.arange(j0, j_hi + 1) * step - s_lo  # relative slice of each start
    a = np.clip(starts, 0, n_slices)
    b = np.clip(starts + spw, 0, n_slices)
    return (csum[b] - csum[a]).astype(np.int32), int(j0)
