"""The algorithm's bytes for a stretch of the stream: the shape function of
`superscan_roofline_pct`.

Counted from the shapes of the work, never from what an implementation does
(one-hot matmul, scatter, Pallas): the window program is memory-bound, and
the least it can move is

  per event   the staged record and its i32 slice index, read once: the
              bytes and the share of source events that reach the device
              program are stated in the configuration's file (`roofline`)
  per fire    the window's slices of the live keys' count ring (i32), read,
              and one output row of counts, written
  per slide   one slice of the live keys' ring, written (the purge)

Live keys are the configuration's key space, not the capacity the program
allocates. On a mesh each device does its share: divide by the devices.
"""

from __future__ import annotations

import math
from typing import Dict


def algorithm_bytes(cfg: Dict, events: int, fires: int, devices: int = 1) -> float:
    r = cfg["roofline"]
    keys = int(cfg["reference"]["keys"])
    w = cfg["window"]
    slice_ms = math.gcd(int(w["size_ms"]), int(w["slide_ms"]))
    slices_per_window = int(w["size_ms"]) // slice_ms
    slices_per_slide = int(w["slide_ms"]) // slice_ms
    per_event = r["staged_bytes_per_event"] * r["share_of_events_reaching_device"]
    per_fire = keys * 4 * (slices_per_window + 1) + keys * 4 * slices_per_slide
    return (events * per_event + fires * per_fire) / max(devices, 1)


def least_seconds(nbytes: float, peaks: Dict) -> float:
    return nbytes / (peaks["hbm_gbps"] * 1e9)
