"""What every job builder shares: the window assigner and the watermark
strategy the configuration's file states."""

from __future__ import annotations

from typing import Dict


def assigner_of(window: Dict):
    from flink_tpu.api.windowing.assigners import (
        SlidingEventTimeWindows, TumblingEventTimeWindows)

    if window["size_ms"] == window["slide_ms"]:
        return TumblingEventTimeWindows.of(int(window["size_ms"]))
    return SlidingEventTimeWindows.of(int(window["size_ms"]),
                                      int(window["slide_ms"]))


def from_source(env, source, cfg: Dict):
    from flink_tpu.core.watermarks import WatermarkStrategy

    return env.from_source(
        source,
        watermark_strategy=WatermarkStrategy.for_bounded_out_of_orderness(
            int(cfg["out_of_orderness_ms"])))


def column_index(cfg: Dict, name: str) -> int:
    return [c["name"] for c in cfg["stream"]["columns"]].index(name)
