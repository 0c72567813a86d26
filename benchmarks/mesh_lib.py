"""What the two collective readers of the exchange layer share: a mesh job's
collectives in the device trace. A job on one chip runs none: the readers
then return None, like any reader that finds nothing to read
(`layer_lib.py`)."""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import layer_lib
from benchmarks import trace_reduce as tr

IN_COLLECTIVES, EXPOSED = 0, 1      # the parts `tr.collective_times` gives


def collective_ms(ctx: Dict, part: int) -> Optional[float]:
    """ms per dispatch of the window program spent in collective ops
    (IN_COLLECTIVES), or in the part of them during which no other op ran on
    that device (EXPOSED), on the device where that is largest."""
    prog = layer_lib.program(ctx)
    if prog is None:
        return None
    lo, hi = ctx["trace_window"]
    times = tr.collective_times(ctx["trace"], lo, hi)
    if not any(t[IN_COLLECTIVES] for t in times.values()):
        return None
    return max(t[part] for t in times.values()) / prog[0] / 1e6

