"""The benchmark's traffic generator: one cycle of a seeded event stream.

One general generator, driven by two data files: the configuration's
`stream` block (which columns a record has and how each is drawn: uniform,
or the distribution its `dist` names) and the traffic mix (event-time
density, cycle length, jitter). Set-up builds ONE
cycle with vectorised numpy; the reader (`reader.py`) serves views of it lap
after lap and adds `lap * cycle_ms` to the timestamps it hands over. Nothing
is generated inside the timed window.

The stream is a pure function of (seed, position): splitmix64 of the event
index, as `chip_smoke.py`'s `Stream` draws it (copied; the program keeps its
own). A cycle spans a whole number of reference slices, so the plain
reference (`reference.py`) tiles the per-lap slice histograms exactly.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
from typing import Dict, List

import numpy as np

T0_MS = 100_000            # first event time; keeps every window start >= 0
_GOLDEN = 0x9E3779B97F4A7C15
BUILD_THREADS = 6          # set-up only; the timed window runs one thread


def mix(idx: np.ndarray, seed: int, salt: int = 0) -> np.ndarray:
    """splitmix64 of (index, seed, salt) as uint64."""
    with np.errstate(over="ignore"):
        x = idx.astype(np.uint64) + np.uint64((seed * _GOLDEN + salt * 0xD1B54A32D192ED03)
                                              & 0xFFFFFFFFFFFFFFFF)
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


@dataclasses.dataclass
class Cycle:
    """One cycle of the stream, plus one batch of wrap-around so that every
    batch start inside the cycle is served as one contiguous view."""

    values: np.ndarray        # f32 [events + wrap, n_columns]
    ts: np.ndarray            # i64 [events + wrap], lap 0
    events: int               # events in one cycle
    cycle_ms: int             # event time one cycle spans
    columns: List[str]
    events_per_s: float       # event-time density (events per event second)

    def column(self, name: str) -> np.ndarray:
        return self.values[: self.events, self.columns.index(name)]


def cycle_events(traffic: Dict) -> int:
    """Events in one cycle: density x cycle length, a whole number."""
    rate = traffic["density_events_per_event_s"]
    n = rate * traffic["cycle_ms"]
    if n % 1000:
        raise ValueError(
            f"density {rate}/s x cycle {traffic['cycle_ms']} ms is not a "
            "whole number of events")
    return int(n // 1000)


def base_timestamps(idx: np.ndarray, traffic: Dict) -> np.ndarray:
    """Creation (event) time of event `idx` of lap 0, before jitter: event i
    is created at T0 + i / density."""
    rate = int(traffic["density_events_per_event_s"])
    return T0_MS + (idx * 1000) // rate


def zipf_cdf(mod: int, dist: Dict) -> np.ndarray:
    """P(value <= k) for value k drawn with weight 1 / (k + 1)**s."""
    w = 1.0 / np.arange(1, mod + 1, dtype=np.float64) ** float(dist.get("s", 1.0))
    return np.cumsum(w) / w.sum()


#: how a column's bit field becomes its value: `dist.kind` -> the table of
#: cumulative probabilities over [0, mod) the field is looked up in
DISTRIBUTIONS = {"zipf": zipf_cdf}


def build_cycle(stream_cfg: Dict, traffic: Dict, seed: int, wrap: int) -> Cycle:
    """Draw one cycle from the seed, a chunk at a time (the chunk stays in the
    CPU's cache). `stream_cfg["columns"]` lists the record's columns in order;
    each is `{"name", "mod"}` (integer below `mod`, stored f32, exact; uniform,
    or `"dist": {"kind": "zipf", "s": 1.0}`: the field, read as a fraction of
    its range, is looked up in the distribution's cumulative table, so the
    field needs 64 values or more per value of the column) or
    `{"name", "kind": "event_time_ms"}` (the record's own copy of its
    creation time, ms within the cycle). Columns take disjoint bit fields of
    splitmix64 hashes in `draw_order`: the first two (the ones the job reads)
    bits [0,24) and [24,40) of the hash whose bits [40,64) give the jitter,
    every further four 16-bit fields of one more hash."""
    n = cycle_events(traffic)
    jitter_mod = np.uint32(int(traffic["jitter_ms"]) + 1)
    cols = stream_cfg["columns"]
    order = stream_cfg.get("draw_order") or [c["name"] for c in cols if "mod" in c]
    by_name = {c["name"]: (j, c) for j, c in enumerate(cols)}
    plan = []                       # (column index, hash number, shift, mask, mod, cdf)
    for rank, name in enumerate(order):
        j, c = by_name[name]
        mod = int(c["mod"])
        if rank == 0:
            field = (0, 0, 0xFFFFFF)
        elif rank == 1:
            field = (0, 24, 0xFFFF)
        else:
            field = (1 + (rank - 2) // 4, 16 * ((rank - 2) % 4), 0xFFFF)
        dist = c.get("dist")
        if dist is not None and dist["kind"] not in DISTRIBUTIONS:
            raise ValueError(f"column {name}: no distribution {dist['kind']!r} "
                             f"(has {sorted(DISTRIBUTIONS)})")
        if mod * (64 if dist else 1) > field[2] + 1:
            raise ValueError(f"column {name}: mod {mod} needs more bits than "
                             f"its field has")
        cdf = DISTRIBUTIONS[dist["kind"]](mod, dist) if dist else None
        plan.append((j,) + field + (mod, cdf))
    for c in cols:
        if "mod" not in c and c.get("kind") != "event_time_ms":
            raise ValueError(f"column {c['name']}: neither mod nor a known kind")
    n_hashes = 1 + max((p[1] for p in plan), default=0)
    values = np.empty((n + wrap, len(cols)), dtype=np.float32)
    ts_all = np.empty(n + wrap, dtype=np.int64)
    step = 1 << 18

    def fill(lo: int) -> None:
        idx = np.arange(lo, min(lo + step, n), dtype=np.int64)
        hashes = [mix(idx, seed, salt) for salt in range(n_hashes)]
        base = base_timestamps(idx, traffic)
        jitter = (hashes[0] >> np.uint64(40)).astype(np.uint32) % jitter_mod
        ts_all[lo:lo + len(idx)] = base - jitter
        for j, h, shift, mask, mod, cdf in plan:
            bits = ((hashes[h] >> np.uint64(shift)) & np.uint64(mask)).astype(np.uint32)
            if cdf is not None:
                u = (bits.astype(np.float64) + 0.5) / (mask + 1.0)
                bits = np.minimum(np.searchsorted(cdf, u), mod - 1)
            elif mod & (mod - 1):
                bits %= np.uint32(mod)
            else:
                bits &= np.uint32(mod - 1)
            values[lo:lo + len(idx), j] = bits
        for j, c in enumerate(cols):
            if c.get("kind") == "event_time_ms":
                values[lo:lo + len(idx), j] = base - T0_MS

    # numpy releases the interpreter lock inside its loops: a few threads
    # fill disjoint chunks. They are joined here, before any job starts.
    with concurrent.futures.ThreadPoolExecutor(max_workers=BUILD_THREADS) as pool:
        list(pool.map(fill, range(0, n, step)))
    values[n:] = values[:wrap]
    ts_all[n:] = ts_all[:wrap] + int(traffic["cycle_ms"])
    return Cycle(values, ts_all, n, int(traffic["cycle_ms"]),
                 [c["name"] for c in cols],
                 float(traffic["density_events_per_event_s"]))
