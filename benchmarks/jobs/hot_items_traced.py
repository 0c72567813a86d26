"""NEXMark Query 5, Hot Items, on the fused device chain: bids per auction
over a hopping window, then the auction with the most bids of every window.

Two operators. The keyed half is `keyed_count_traced`'s chain (filter ->
key_by -> windowed count, every UDF `traceable=True`: DeviceChainRunner,
chained XLA superscan). Behind it a window over the whole stream as long as
the hop, so that it holds exactly one fire's rows, keeps the row with the
largest count, the lowest auction id among equals (`window_all(...)
.max_by(1)`). The sink receives one `(auction, num)` pair per window,
stamped `window.end - 1` of the hopping window.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.jobs.common import assigner_of, column_index, from_source
from benchmarks.jobs.keyed_count_traced import _udfs


def build(env, source, sink, cfg: Dict, tables: Dict) -> None:
    from flink_tpu.api.windowing.assigners import TumblingEventTimeWindows

    sem = cfg["reference"]
    # that job's traced filter (`event_kind < 46`: a bid) and key (`auction`)
    is_bid, auction_of = _udfs(
        column_index(cfg, sem["key"]["column"]),
        column_index(cfg, sem["filter"]["column"]),
        int(sem["filter"]["keep_below"]) - 1)
    bids = from_source(env, source, cfg).filter(is_bid, traceable=True)
    counts = bids.key_by(auction_of, traceable=True) \
        .window(assigner_of(cfg["window"])).aggregate("count")
    counts.window_all(
        TumblingEventTimeWindows.of(int(cfg["window"]["slide_ms"]))) \
        .max_by(1).sink_to(sink)
