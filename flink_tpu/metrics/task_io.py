"""Per-task busy/idle/backpressure accounting and the job thread's stage clock.

The reference tracks these in TaskIOMetricGroup (busyTimeMsPerSecond,
idleTimeMsPerSecond, backPressuredTimeMsPerSecond; TaskIOMetricGroup.java:48)
and samples them for the REST backpressure handlers
(JobVertexBackPressureHandler). The stepped executor's analogue:

- **busy** — time the run loop spends pushing a batch through the runner
  DAG (device dispatch included), minus time blocked on downstream credits;
- **backpressured** — time blocked inside an exchange sender waiting for
  credits (dataplane OutputChannel.send), i.e. the downstream stage's
  backlog surfacing in THIS task's loop — the "writer blocks on
  LocalBufferPool" condition;
- **idle** — everything else: source poll timeouts, starved stage-input
  channels, scheduling gaps.

Lifetime ratios are maintained continuously from these counters; the
windowed `*MsPerSecond` gauges are sampled on the run loop's
processing-time tick every `observability.sampling.interval-ms` (the
backpressure-sampling period), so REST/dashboard readers see the RECENT
state of the task, not its lifetime average.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Optional


def backpressure_level(ratio: float) -> str:
    """The reference's backpressure classification thresholds
    (JobVertexBackPressureHandler: ok <= 0.10 < low <= 0.5 < high)."""
    if ratio <= 0.10:
        return "ok"
    if ratio <= 0.5:
        return "low"
    return "high"


class TaskIOMetrics:
    """Busy/idle/backPressured time accounting for one task run loop."""

    def __init__(self):
        self.busy_s = 0.0
        self.loop_s = 1e-9
        # callables returning cumulative seconds blocked on credits (one per
        # exchange sender feeding a downstream stage)
        self._bp_sources: List[Callable[[], float]] = []
        # windowed sample state
        self._last_sample_t = time.monotonic()
        self._last = (0.0, 0.0, 0.0)          # (busy, bp, loop) at last sample
        self._rates = {"busy": 0.0, "idle": 0.0, "backPressured": 0.0}

    def add_backpressure_source(self, fn: Callable[[], float]) -> None:
        self._bp_sources.append(fn)

    def backpressured_s(self) -> float:
        return sum(fn() for fn in self._bp_sources)

    # -- run-loop feed -----------------------------------------------------
    def record_step(self, busy_dt: float, loop_dt: float) -> None:
        """One source turn: `busy_dt` spent pushing (includes any credit
        waits — they are separated out at read time), `loop_dt` total."""
        self.busy_s += busy_dt
        self.loop_s += loop_dt

    # -- lifetime ratios ---------------------------------------------------
    def ratios(self) -> Dict[str, float]:
        bp = min(self.backpressured_s(), self.busy_s)
        busy = self.busy_s - bp
        loop = max(self.loop_s, busy + bp, 1e-9)
        idle = max(loop - busy - bp, 0.0)
        return {
            "busyRatio": busy / loop,
            "idleRatio": idle / loop,
            "backPressuredRatio": bp / loop,
        }

    # -- windowed sampling -------------------------------------------------
    def maybe_sample(self, interval_ms: int, now: float = None) -> None:
        """Fold the deltas since the last sample into the msPerSecond rates;
        called from the processing-time tick (cheap: pure arithmetic)."""
        now = time.monotonic() if now is None else now
        dt = now - self._last_sample_t
        if dt * 1000.0 < max(interval_ms, 1):
            return
        bp_total = min(self.backpressured_s(), self.busy_s)
        d_busy = self.busy_s - self._last[0]
        d_bp = bp_total - self._last[1]
        d_loop = self.loop_s - self._last[2]
        self._last = (self.busy_s, bp_total, self.loop_s)
        self._last_sample_t = now
        del d_loop  # wall clock, not loop time, is the msPerSecond base
        wall = max(dt, 1e-9)
        bp = max(d_bp, 0.0)
        busy = max(d_busy - bp, 0.0)
        idle = max(wall - busy - bp, 0.0)
        self._rates = {
            "busy": min(busy / wall, 1.0) * 1000.0,
            "backPressured": min(bp / wall, 1.0) * 1000.0,
            "idle": min(idle / wall, 1.0) * 1000.0,
        }

    def ms_per_second(self, kind: str) -> float:
        return self._rates[kind]

    def register(self, group) -> None:
        """Register the TaskIOMetricGroup-analogue gauges on `group`."""
        r = self.ratios
        # per-task fractions (each bounded per task) fold MEAN
        group.gauge("busyTimeRatio", lambda: r()["busyRatio"], fold="mean")
        group.gauge("idleTimeRatio", lambda: r()["idleRatio"], fold="mean")
        group.gauge("backPressuredTimeRatio",
                    lambda: r()["backPressuredRatio"], fold="mean")
        group.gauge("busyTimeMsPerSecond",
                    lambda: self.ms_per_second("busy"), fold="mean")
        group.gauge("idleTimeMsPerSecond",
                    lambda: self.ms_per_second("idle"), fold="mean")
        group.gauge("backPressuredTimeMsPerSecond",
                    lambda: self.ms_per_second("backPressured"), fold="mean")


#: the stages of the job's thread, in the order a batch meets them. Each is a
#: `flink_tpu.<stage>` span in any profiler capture and a row of the
#: per-operator `stages` table (docs/observability.md tabulates this tuple).
STAGES = (
    "source.poll", "source.watermark", "chain.host", "keys.lookup",
    "normalize", "stage.fill", "stage.shard", "stage.put", "dispatch",
    "resolve", "emit", "drain", "fire.reduce", "table.output", "sink.write",
    "keys.stats",
)
SPAN_PREFIX = "flink_tpu."

_open = threading.local()       # .top: this thread's innermost open stage
_OFF = contextlib.nullcontext()


def stage(clock: "Optional[StageClock]", name: str, seq: Optional[int] = None):
    """A stage of `clock`, or the shared no-op when the clock is off
    (`observability.device-timing.enabled` false): a site then costs this
    `is None` test."""
    return _OFF if clock is None else _Stage(clock, name, seq)


def dispatch_stage(clock: "Optional[StageClock]", name: str):
    """A stage of the dispatch being staged: its span carries `clock.seq`,
    so stage.fill .. emit of one dispatch can be followed in a capture."""
    return _OFF if clock is None else _Stage(clock, name, clock.seq)


def section(clock: "Optional[StageClock]"):
    """An outer section of `clock` (see StageClock), or the no-op."""
    return _OFF if clock is None else clock.section()


def tag_dispatch(program: str) -> None:
    """Name the program on this thread's open `dispatch` span (called by
    `CompileTracker.call`, the seam every window program goes through)."""
    top = getattr(_open, "top", None)
    if top is not None and top.name == "dispatch" and top.span is not None:
        top.span.set_metadata(program=program)


class _Stage:
    __slots__ = ("clock", "name", "seq", "t0", "child_ns", "parent", "span")

    def __init__(self, clock, name, seq):
        self.clock = clock
        self.name = name
        self.seq = seq

    def __enter__(self):
        self.span = None
        annotation = self.clock.annotation
        if annotation.is_enabled():      # a profiler capture is running
            if self.seq is None:
                self.span = annotation(SPAN_PREFIX + self.name)
            else:
                self.span = annotation(SPAN_PREFIX + self.name, seq=self.seq)
            self.span.__enter__()
        self.parent = getattr(_open, "top", None)
        _open.top = self
        self.child_ns = 0
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        _open.top = self.parent
        if self.parent is not None:
            self.parent.child_ns += dt
        rec = self.clock.stages[self.name]
        rec[0] += 1
        rec[1] += dt - self.child_ns      # self time: nested stages taken out
        if self.span is not None:
            self.span.__exit__(*exc)
        return False


def merge_stage_tables(clocks) -> Dict[str, Dict[str, float]]:
    """One `stages` table summed over several clocks (the job's view)."""
    out: Dict[str, Dict[str, float]] = {}
    for n in STAGES:
        count = sum(c.stages[n][0] for c in clocks)
        if count:
            out[n] = {"count": count, "ms": round(
                sum(c.stages[n][1] for c in clocks) / 1e6, 3)}
    return out


class StageClock:
    """Where one operator's share of the job's thread goes. A named stage
    (`stage(clock, name)`, one of STAGES) is a `flink_tpu.<name>` span on the
    profiler's clock while a capture runs, and count + self time in
    `stages`; the link counters are added at the stages that move the bytes.
    `section()` is the outer section round a whole `process_batch` /
    `process_watermark` / resolving drain: host time in the dispatch and
    resolve sections, nested stages included (`deviceTimeMsTotal`,
    `deviceDispatches`, `deviceDispatchMs`). An observer: wrap
    already-synchronous sections only, never add a block_until_ready."""

    def __init__(self, histogram=None):
        # imported here: control-plane processes load this module without jax
        from jax.profiler import TraceAnnotation

        self.annotation = TraceAnnotation
        self.stages: Dict[str, List[int]] = {n: [0, 0] for n in STAGES}
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.events_staged = 0
        # the newest traced-chain dispatch: fields staged / fields of the
        # record (both 0 on the host-keyed path, which ships key ids)
        self.columns_staged = 0
        self.record_columns = 0
        self.rows_emitted = 0
        # fires appended to an output lane, one block each (fire_block.py):
        # rowsEmitted / fireBlocks = rows per fire
        self.fire_blocks = 0
        # rows of fire blocks that a null-key window behind the operator
        # reduced as columns (stage fire.reduce), and rows that came out
        self.fire_rows_reduced = 0
        self.fire_rows_kept = 0
        # data steps staged, by how their slice plan was made: from the
        # step's two timestamp extremes, or per record under a late mask
        self.steps_planned_scalar = 0
        self.steps_planned_masked = 0
        # host staging sets (lane arrays) a dispatch took: fresh, or from
        # the pipeline's pool (`_StagingPool`)
        self.staging_sets_allocated = 0
        self.staging_sets_reused = 0
        # steps with records whose lanes were written by the one native
        # call of their dispatch, or by numpy (`_fill`)
        self.steps_staged_native = 0
        self.steps_staged_numpy = 0
        self.seq = 0            # the dispatch being staged (`dispatches` so far)
        self.total_s = 0.0
        self.dispatches = 0     # outer sections entered
        self._hist = histogram

    class _Section:
        __slots__ = ("clock", "t0")

        def __init__(self, clock: "StageClock"):
            self.clock = clock

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            dt = time.perf_counter() - self.t0
            self.clock.total_s += dt
            self.clock.dispatches += 1
            if self.clock._hist is not None:
                self.clock._hist.update(dt * 1000.0)
            return False

    def section(self) -> "_Section":
        return StageClock._Section(self)

    def staged(self, arrays, events: int = 0, columns=None,
               reused=None) -> None:
        """Host arrays handed to `jax.device_put` in a stage.put, and the
        events they carry; `columns` = (fields staged, fields of the
        record) where the dispatch ships a traced chain's record; `reused`
        = whether its staging set came from the pool (None: no set)."""
        self.h2d_bytes += sum(a.nbytes for a in arrays if a is not None)
        self.events_staged += events
        if columns is not None:
            self.columns_staged, self.record_columns = columns
        if reused is not None:
            if reused:
                self.staging_sets_reused += 1
            else:
                self.staging_sets_allocated += 1

    def lanes_written(self, native: int, numpy: int) -> None:
        """A stage.fill's steps with records, by who wrote their lanes: the
        dispatch's one native call, or numpy."""
        self.steps_staged_native += native
        self.steps_staged_numpy += numpy

    def planned(self, masked: bool) -> None:
        """One data step's slice plan reached staging (StepPlan.masked)."""
        if masked:
            self.steps_planned_masked += 1
        else:
            self.steps_planned_scalar += 1

    def stage_table(self) -> Dict[str, Dict[str, float]]:
        return merge_stage_tables((self,))

    def link(self) -> Dict[str, int]:
        return {"h2dBytes": self.h2d_bytes, "d2hBytes": self.d2h_bytes,
                "eventsStaged": self.events_staged,
                "columnsStaged": self.columns_staged,
                "recordColumns": self.record_columns,
                "rowsEmitted": self.rows_emitted,
                "fireBlocks": self.fire_blocks,
                "fireRowsReduced": self.fire_rows_reduced,
                "fireRowsKept": self.fire_rows_kept, "dispatches": self.seq,
                "stepsPlannedScalar": self.steps_planned_scalar,
                "stepsPlannedMasked": self.steps_planned_masked,
                "stagingSetsAllocated": self.staging_sets_allocated,
                "stagingSetsReused": self.staging_sets_reused,
                "stepsStagedNative": self.steps_staged_native,
                "stepsStagedNumpy": self.steps_staged_numpy}

    def register(self, group) -> None:
        group.gauge("deviceTimeMsTotal", lambda: self.total_s * 1000.0,
                    fold="sum", kind="counter")
        group.gauge("deviceDispatches", lambda: self.dispatches,
                    fold="sum", kind="counter")
