"""The plain reference against a slower, plainer one: every window counted
by masking the whole replayed stream. Covers the tiling of laps, the jitter
that reaches back across a lap's first slice, the join table and the control."""

import numpy as np
import pytest

from benchmarks import reference as ref
from benchmarks.references import keyed_window_count as kwc
from benchmarks.stream import build_cycle

YSB = {"columns": [{"name": "user_id", "mod": 100}, {"name": "ad_id", "mod": 1000},
                   {"name": "event_type", "mod": 3},
                   {"name": "event_time", "kind": "event_time_ms"}],
       "draw_order": ["ad_id", "event_type", "user_id"]}
TABLE = {"ads": 1000, "campaigns": 100, "table_seed": 7}
SEM = {"filter": {"column": "event_type", "keep_below": 1},
       "key": {"column": "ad_id", "table": "t"}, "keys": 100}
TRAFFIC = {"density_events_per_event_s": 20_000, "cycle_ms": 2000, "jitter_ms": 200}


def brute(cycle, tables, window, events):
    laps = -(-events // cycle.events)
    ts = np.concatenate([cycle.ts[:cycle.events] + l * cycle.cycle_ms
                         for l in range(laps)])[:events]
    ad = np.tile(cycle.column("ad_id"), laps)[:events].astype(np.int64)
    keep = np.tile(cycle.column("event_type"), laps)[:events] < 1
    key = tables["t"][ad]
    out = {}
    size, slide = window["size_ms"], window["slide_ms"]
    for j in range(int(ts.min() - size) // slide, int(ts.max()) // slide + 1):
        m = keep & (ts >= j * slide) & (ts < j * slide + size)
        if m.any():
            out[j] = np.bincount(key[m], minlength=100)
    return out


@pytest.mark.parametrize("window", [{"size_ms": 10000, "slide_ms": 10000},
                                    {"size_ms": 10000, "slide_ms": 1000},
                                    {"size_ms": 4000, "slide_ms": 2000}])
@pytest.mark.parametrize("events", [40_000 * 3 + 12_345, 40_000, 17])
def test_window_counts_match_brute_force(window, events):
    cycle = build_cycle(YSB, TRAFFIC, 2**31 + 11, wrap=4096)
    tables = {"t": kwc.ad_to_campaign(TABLE)}
    counts, j0 = kwc.expected(cycle, SEM, tables, window, events, 200)
    want = brute(cycle, tables, window, events)
    got = {j0 + r: counts[r] for r in range(len(counts)) if counts[r].any()}
    assert sorted(got) == sorted(want)
    for j in want:
        assert np.array_equal(got[j], want[j]), j


def test_rows_round_trip_and_control_fails():
    cycle = build_cycle(YSB, TRAFFIC, 5, wrap=4096)
    tables = {"t": kwc.ad_to_campaign(TABLE)}
    window = {"size_ms": 10000, "slide_ms": 1000}
    events = 100_000
    expect, j0 = kwc.expected(cycle, SEM, tables, window, events, 200)
    sound = ref.compare(ref.rows_of(expect, j0, window), expect, j0, window,
                        events, events)
    assert ref.verdict(sound["numbers"], ref.LIMITS)
    assert sound["cells_compared"] == int((expect > 0).sum())
    broken, _ = kwc.expected(cycle, SEM, tables, window, events, 200,
                                  replay=(0, 4096))
    control = ref.compare(ref.rows_of(broken, j0, window), expect, j0, window,
                          events, events)
    assert not ref.verdict(control["numbers"], ref.LIMITS)
    assert control["numbers"]["cells_wrong"] > 0


def test_compare_sees_missing_twice_and_outside():
    cycle = build_cycle(YSB, TRAFFIC, 9, wrap=4096)
    tables = {"t": kwc.ad_to_campaign(TABLE)}
    window = {"size_ms": 10000, "slide_ms": 10000}
    expect, j0 = kwc.expected(cycle, SEM, tables, window, 50_000, 200)
    (k, v, ts), = ref.rows_of(expect, j0, window)
    rows = [(k[1:], v[1:], ts[1:]),                       # one cell missing
            (k[5:6], v[5:6], ts[5:6]),                    # one cell twice
            (np.array([3]), np.array([1]), np.array([12_345]))]   # no such window
    n = ref.compare(rows, expect, j0, window, 50_000, 49_999)["numbers"]
    assert (n["cells_missing"], n["cells_twice"], n["rows_outside"],
            n["records_in_gap"]) == (1, 1, 1, 1)


def test_a_column_is_drawn_from_the_distribution_it_names():
    stream = {"columns": [{"name": "k", "mod": 4096,
                           "dist": {"kind": "zipf", "s": 1.0}},
                          {"name": "e", "mod": 3}]}
    cycle = build_cycle(stream, TRAFFIC, 11, wrap=4096)
    share = np.bincount(cycle.column("k").astype(int), minlength=4096) / cycle.events
    want = 1.0 / np.arange(1, 4097)
    want /= want.sum()
    assert abs(share[:8] - want[:8]).max() < 0.01
    assert share[0] > 1.8 * share[1] > 2.5 * share[3]
    # the column beside it stays uniform
    e = np.bincount(cycle.column("e").astype(int), minlength=3) / cycle.events
    assert abs(e - 1 / 3).max() < 0.02
    with pytest.raises(ValueError, match="no distribution"):
        build_cycle({"columns": [{"name": "k", "mod": 8, "dist": {"kind": "pareto"}}]},
                    TRAFFIC, 1, wrap=16)
