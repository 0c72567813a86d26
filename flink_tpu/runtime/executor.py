"""Local pipeline executor: the host-driven stepped dataflow loop.

The reference drives records through a per-task mailbox loop
(StreamTask.java:205 processInput :655, MailboxProcessor.runMailboxLoop
:214) with operators chained by direct calls (OperatorChain.java:108). Here
execution is *stepped*: the source reader yields a columnar batch, the batch
flows through push-based StepRunners (a fused stateless chain, then a keyed
window step backed by the device operator, then sinks), and one combined
watermark is advanced between steps (core/watermarks.py valve). There is no
per-record scheduling — the device program IS the inner loop.

Operator selection mirrors WindowOperatorBuilder.java:79: the keyed window
step uses the batched TpuWindowOperator when the aggregate has a columnar
device form, the assigner is sliceable and event-time, and no custom
trigger/evictor is set; otherwise the per-record oracle operator (same
semantics, CPU).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from flink_tpu.api.functions import (
    AggregateFunction,
    ProcessFunction,
    ReduceAggregate,
    null_key,
)
from flink_tpu.chaos import plan as _chaos
from flink_tpu.config import (
    Configuration,
    ExecutionOptions,
    ObservabilityOptions,
    ParallelOptions,
    PipelineOptions,
)
from flink_tpu.core.time import MAX_WATERMARK, MIN_TIMESTAMP, MIN_WATERMARK
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.graph.transformation import Step, StepGraph, Transformation
from flink_tpu.ops.aggregators import PositionalAggregate, resolve
from flink_tpu.runtime.fire_block import (
    FireBlock,
    downstream_batch,
    fires_of,
    reduce_block,
    window_maxima,
)
from flink_tpu.runtime.oracle_window_operator import OracleWindowOperator
from flink_tpu.runtime.tpu_window_operator import TpuWindowOperator
from flink_tpu.runtime.timers import InternalTimerService
from flink_tpu.metrics.emission_latency import (
    EmissionLatencyTracker,
    merge_snapshots as _merge_emission_snapshots,
    watermark_lag_ms,
)
from flink_tpu.metrics.registry import MetricRegistry
from flink_tpu.metrics.task_io import (
    StageClock,
    TaskIOMetrics,
    merge_stage_tables,
    section,
    stage,
)
from flink_tpu.state.heap import HeapKeyedStateBackend, value_state
from flink_tpu.utils.arrays import as_device_column, canonical_column, obj_array
from flink_tpu.core.keygroups import KeyGroupRange


@dataclasses.dataclass
class JobExecutionResult:
    job_name: str
    runtime_ms: float
    records_in: int
    metrics: Dict[str, Any]


# ---------------------------------------------------------------------------
# step runners (push-based; each pushes into `downstream`)
# ---------------------------------------------------------------------------

class _FanOut:
    """Downstream edge set of one runner. Runners emit through
    `self.downstream` exactly as in a linear pipeline; the fan-out routes to
    every consumer's input gate (ordinal), which is how one runner feeds
    multiple sinks and how two-input operators distinguish their sides."""

    __slots__ = ("edges",)

    def __init__(self):
        self.edges: List = []   # (runner, input_ordinal)

    def __bool__(self) -> bool:
        return bool(self.edges)

    def add(self, runner: "StepRunner", ordinal: int) -> None:
        self.edges.append((runner, ordinal))

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        for r, o in self.edges:
            r.on_batch_n(o, values, timestamps)

    def on_fires(self, drained: Sequence, bare: bool,
                 clock: Optional[StageClock] = None) -> None:
        """One window operator's drain (fire_block.py: blocks of columns or
        rows) to every consumer. A consumer that can take the fires as they
        are does (`StepRunner.on_fires_n`); every other gets the `(vals,
        ts)` batch `downstream_batch` builds, once. `clock` is the draining
        operator's: the stages of the hand-over are its thread's time."""
        batch = None
        for r, o in self.edges:
            if r.on_fires_n(o, drained, bare, clock):
                continue
            if batch is None:
                batch = downstream_batch(drained, bare)
            r.on_batch_n(o, *batch)

    def on_watermark(self, watermark: int) -> None:
        for r, o in self.edges:
            r.on_watermark_n(o, watermark)

    def on_marker(self, wall_ms: float) -> None:
        for r, _o in self.edges:
            r.on_marker(wall_ms)

    def on_end(self) -> None:
        for r, o in self.edges:
            r.on_end_n(o)


class StepRunner:
    downstream: Optional[_FanOut] = None
    #: the stage clock this runner's sites report to (metrics/task_io.py);
    #: None = observability.device-timing.enabled off
    stage_clock: Optional[StageClock] = None
    sides: Optional[Dict[str, _FanOut]] = None   # side-output channels by tag
    num_inputs: int = 1

    def side_channel(self, tag_id: str) -> _FanOut:
        if self.sides is None:
            self.sides = {}
        if tag_id not in self.sides:
            self.sides[tag_id] = _FanOut()
        return self.sides[tag_id]

    def emit_side(self, tag_id: str, values, timestamps) -> None:
        if self.sides and tag_id in self.sides:
            self.sides[tag_id].on_batch(values, timestamps)

    def register_metrics(self, group) -> None:
        # operator-scope IO metrics (TaskIOMetricGroup.java:48 analogue)
        self.records_in_counter = group.counter("numRecordsIn")
        # source->operator transit latency per latency marker (the
        # per-operator LatencyStats histogram of the reference): updated as
        # each marker PASSES this operator, so a slow stage shows up as the
        # step where the percentile jumps
        self._marker_hist = group.histogram("latencyMs")

    # -- input-gate protocol (multi-input valve) --------------------------
    def on_batch_n(self, ordinal: int, values: np.ndarray,
                   timestamps: np.ndarray) -> None:
        self.on_batch(values, timestamps)

    def on_fires_n(self, ordinal: int, drained: Sequence, bare: bool,
                   clock: Optional[StageClock]) -> bool:
        """An upstream window operator's drain before any row is built
        (`_FanOut.on_fires`). True = taken; False = hand me the rows."""
        return False

    def on_watermark_n(self, ordinal: int, watermark: int) -> None:
        """Per-gate watermark: min-combine across gates before processing
        (StatusWatermarkValve.java semantics)."""
        if self.num_inputs <= 1:
            self.on_watermark(watermark)
            return
        wms = self.__dict__.setdefault("_gate_wms", {})
        wms[ordinal] = max(wms.get(ordinal, MIN_WATERMARK), watermark)
        if len(wms) < self.num_inputs:
            return
        combined = min(wms.values())
        if combined > self.__dict__.get("_combined_wm", MIN_WATERMARK):
            self.__dict__["_combined_wm"] = combined
            self.on_watermark(combined)

    def on_end_n(self, ordinal: int) -> None:
        ended = self.__dict__.setdefault("_ended_gates", set())
        ended.add(ordinal)
        if len(ended) >= self.num_inputs:
            self.on_end()

    # -- processing -------------------------------------------------------
    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        raise NotImplementedError

    def on_watermark(self, watermark: int) -> None:
        if self.downstream:
            self.downstream.on_watermark(watermark)
        if self.sides:
            for f in self.sides.values():
                f.on_watermark(watermark)

    def on_marker(self, wall_ms: float) -> None:
        """Latency marker (LatencyMarker analogue): a wall-clock stamp from
        the source that flows straight through every operator — windows and
        buffers forward it immediately, so a sink's (now - stamp) measures
        true pipeline transit latency rather than event-time residence.
        Each operator it passes records (now - stamp) into its own latency
        histogram before forwarding."""
        h = getattr(self, "_marker_hist", None)
        if h is not None:
            h.update(time.time() * 1000.0 - wall_ms)
        if self.downstream:
            self.downstream.on_marker(wall_ms)
        if self.sides:
            for f in self.sides.values():
                f.on_marker(wall_ms)

    def on_processing_time(self, now_ms: int) -> None:
        """Wall-clock tick driven by the run loop (ProcessingTimeService
        analogue); runners with processing-time timers fire them here."""

    def on_end(self) -> None:
        if self.downstream:
            self.downstream.on_end()
        if self.sides:
            for f in self.sides.values():
                f.on_end()

    def snapshot(self) -> dict:
        return {}

    def restore(self, snap: dict) -> None:
        pass


def make_emission_tracker(uid: str, config: Configuration):
    """Per-operator emission-latency tracker, or None when the plane is
    off (observability.emission-latency.enabled). One policy for every
    windowed runner family — classic/fused/session/global/join — so the
    /jobs/:id/latency fold always sees one key shape."""
    if not config.get(ObservabilityOptions.EMISSION_LATENCY_ENABLED):
        return None
    return EmissionLatencyTracker(
        uid,
        outlier_pct=config.get(
            ObservabilityOptions.EMISSION_LATENCY_OUTLIER_PCT),
        outlier_floor_ms=config.get(
            ObservabilityOptions.EMISSION_LATENCY_OUTLIER_FLOOR_MS),
        ring_size=config.get(
            ObservabilityOptions.EMISSION_LATENCY_OUTLIER_RING),
        min_samples=config.get(
            ObservabilityOptions.EMISSION_LATENCY_OUTLIER_MIN_SAMPLES),
    )


def _fused_chunk(batch_size: int) -> int:
    """Superscan ingest chunk for a configured batch size: the next power
    of two, clamped to [256, 4096] — one policy for the classic fused
    window runner and the fused device chain, so the two paths can never
    silently drift to different dispatch geometries."""
    return min(4096, max(256, 1 << (max(batch_size, 1) - 1).bit_length()))


def _mesh_for_config(config: Configuration, key_capacity: int):
    """The job's device mesh when multichip execution applies, else None.

    parallel.mesh.enabled makes the mesh a slot resource of this process:
    the requested device count (0 = all visible) is clamped to what the
    backend exposes, then rounded DOWN to the largest divisor of the
    operator's key capacity so the contiguous key-group ranges divide
    evenly — a capacity/mesh mismatch degrades the mesh, never the
    key-range semantics. A request for several devices that ends on one
    chip is warned about here and shows in the job's report
    (`meshDevices` gauge, JobExecutionResult.metrics["mesh_devices"])."""
    if not config.get(ParallelOptions.MESH_ENABLED):
        return None
    import jax

    from flink_tpu.parallel.mesh import build_mesh, usable_mesh_size

    want = config.get(ParallelOptions.MESH_DEVICES)
    visible = len(jax.devices())
    n = usable_mesh_size(want, visible, key_capacity)
    if n <= 1:
        if want != 1:
            import warnings

            warnings.warn(
                f"parallel.mesh.enabled asked for "
                f"{want or 'all visible'} devices but the job runs on ONE "
                f"chip: {visible} device(s) visible, key capacity "
                f"{key_capacity}",
                RuntimeWarning,
            )
        return None
    return build_mesh(n)


def _mesh_exchange_kwargs(config: Configuration) -> dict:
    """The skew-adaptive exchange options threaded to FusedWindowOperator
    (ignored off the mesh): the map-side combiner and the key-group
    routing table (docs/multichip.md). Single-sourced so the classic and
    traced-chain construction sites can never drift."""
    return {
        "mesh_local_combine": config.get(ParallelOptions.MESH_LOCAL_COMBINE),
        "mesh_skew_routing": config.get(ParallelOptions.MESH_SKEW_REBALANCE),
        "mesh_key_groups": config.get(ParallelOptions.MESH_KEY_GROUPS),
    }


def _latency_kwargs(config: Configuration) -> dict:
    """The latency-mode option bundle threaded to FusedWindowOperator —
    empty (NOT latency=None) when execution.latency.target-ms is off, so
    the default config constructs the operator exactly as before the mode
    existed. Single-sourced like _mesh_exchange_kwargs: both fused
    construction sites (WindowStepRunner and _init_fused) consume it."""
    from flink_tpu.config import LatencyOptions as _L

    target = config.get(_L.TARGET_MS)
    if target is None or int(target) <= 0:
        return {}
    from flink_tpu.scheduler.latency_controller import LatencySpec

    return {"latency": LatencySpec(
        target_ms=int(target),
        max_inflight=config.get(_L.MAX_INFLIGHT),
        floor_steps=config.get(_L.FLOOR_STEPS),
        readback_steps=config.get(_L.READBACK_STEPS),
        min_dwell_ms=config.get(_L.MIN_DWELL_MS),
        hysteresis_pct=config.get(_L.HYSTERESIS_PCT),
    )}


def _tier_for_config(config: Configuration):
    """The fused window path's TierConfig when the million-key state
    plane applies (state.tier.enabled), else None. Tiering needs the host
    key dictionary, so the traced-chain runner (dense device keying)
    never receives one."""
    from flink_tpu.config import StateTierOptions as _ST

    if not config.get(_ST.TIER_ENABLED):
        return None
    from flink_tpu.state.tier_manager import TierConfig

    return TierConfig(
        hot_key_capacity=config.get(_ST.HOT_KEY_CAPACITY),
        eviction_policy=config.get(_ST.EVICTION_POLICY),
        admission_min_count=config.get(_ST.ADMISSION_MIN_COUNT),
        cold_dir=config.get(_ST.COLD_DIR) or None,
        changelog_enabled=config.get(_ST.CHANGELOG_ENABLED),
        changelog_dir=config.get(_ST.CHANGELOG_DIR) or None,
        materialize_interval=config.get(_ST.CHANGELOG_MATERIALIZE_INTERVAL),
        retained_bases=config.get(_ST.CHANGELOG_RETAINED_BASES),
    )


class MeshRescaleRequested(BaseException):
    """Control-flow signal, not a failure: the run loop reached a step
    boundary with a pending mesh-rescale request. Carries the target
    device count and the step-aligned state capture the rebuilt runtime
    restores from (checkpoint rewind across device counts — the snapshot
    is canonical [K, S], so any mesh size re-shards it). BaseException so
    ordinary `except Exception` operator guards can never swallow it.

    With `routing` set this is a skew REBALANCE, not a resize: the mesh
    size stays `target` (== current) and the rebuilt runtime applies the
    new key-group -> device assignment BEFORE restoring the capture —
    placement changes ride the same exactly-once capture/restore
    machinery, and checkpoints stay canonical [K, S] throughout."""

    def __init__(self, target: int, snapshot: dict, routing=None):
        super().__init__(
            f"mesh rescale to {target} devices" if routing is None
            else f"mesh key-group rebalance over {target} devices")
        self.target = int(target)
        self.snapshot = snapshot
        self.routing = routing


def _columnarize_records(vals, where: str):
    """Record-mode (object column) → numeric column, for UDFs declared
    traceable=True: the declared contract is a numeric column function, so
    the host fallback paths must feed them the same representation the
    fused device path stages (CHAIN_FUSION is a perf switch, never a
    semantics switch). Raises if the records do not columnarize."""
    arr = np.asarray(vals.tolist() if isinstance(vals, np.ndarray)
                     else list(vals))
    if arr.dtype == object:
        raise TypeError(
            f"{where} is declared traceable=True and requires numeric "
            "record columns; these records do not columnarize — drop "
            "traceable=True to run per-record instead"
        )
    return arr


class ChainRunner(StepRunner):
    """Fused stateless chain: map/filter/flat_map applied per batch
    (OperatorChain ChainingOutput analogue, StreamingJobGraphGenerator.java:1730).

    Vectorized transforms (declared with vectorized=True at the API, plus
    map_batch) execute as whole-column array ops — the chain stays columnar
    end to end and a filter+projection before a window costs two numpy
    kernels per step instead of a Python loop per record. Scalar transforms
    fall back to per-record application; mixed chains switch representation
    at segment boundaries."""

    def __init__(self, transforms: List[Transformation]):
        self.transforms = transforms

    @staticmethod
    def _to_column(vals, columnar: bool = False) -> np.ndarray:
        """Normalize a transform's output. `columnar=True` marks the output
        of a vectorized/traceable fn, which is by contract a whole column —
        numeric arrays of ANY rank pass through (a traceable UDF written
        with jnp ops returns a jax array; objectifying its rows would
        silently de-columnarize the fusion-off fallback path)."""
        if isinstance(vals, np.ndarray):
            return vals
        arr = np.asarray(vals)
        if arr.dtype.kind in "OUSifub" and (arr.ndim == 1 or columnar):
            return arr
        return obj_array(list(vals))

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        with stage(self.stage_clock, "chain.host"):
            vals, ts = self._apply(values, timestamps)
        if len(ts) and self.downstream:
            self.downstream.on_batch(vals, ts)

    def on_fires_n(self, ordinal: int, drained: Sequence, bare: bool,
                   clock: Optional[StageClock]) -> bool:
        """A chain whose first transform keeps each window's maxima
        (`window_maxima_rows`: the SQL plan of planner/rules
        rewrite_window_maxima) takes a fused window's fires as columns: the
        rows of a window that tie at its maximum are picked by whole-column
        calls (stage `fire.reduce`, a window at a time), rows are built of
        those alone (stage `table.output`, a kept block at a time), both on
        the emitting operator's clock with the firing dispatch's `seq=`; the
        rest of the chain takes them as a batch. Anything else goes the row
        way, where the transform's own function does the same: False."""
        rows_of = (self.transforms[0].config.get("window_maxima_rows")
                   if self.transforms else None)
        if rows_of is None or bare or type(drained[0]) is not FireBlock:
            return False
        windows: Dict[int, List[FireBlock]] = {}
        for b in drained:
            windows.setdefault(b.ts, []).append(b)
        kept = []
        for blocks in windows.values():
            with stage(clock, "fire.reduce", blocks[0].seq):
                window = window_maxima(blocks)
            if window is None:
                return False
            kept.extend(window)
        if clock is not None:
            clock.fire_rows_reduced += sum(map(len, drained))
            clock.fire_rows_kept += sum(map(len, kept))
        vals: List = []
        for b in kept:
            with stage(clock, "table.output", b.seq):
                vals.extend(rows_of(b))
        ts = np.repeat(np.fromiter((b.ts for b in kept), dtype=np.int64,
                                   count=len(kept)),
                       [len(b) for b in kept])
        vals, ts = self._apply(obj_array(vals), ts, first=1)
        if len(ts) and self.downstream:
            self.downstream.on_batch(vals, ts)
        return True

    def _apply(self, values: np.ndarray, timestamps: np.ndarray,
               first: int = 0):
        vals = values
        ts = np.asarray(timestamps, dtype=np.int64)
        for t in self.transforms[first:]:
            if len(ts) == 0:
                break
            fn = t.config["fn"]
            vec = t.config.get("vectorized", False)
            if t.config.get("traceable"):
                if getattr(vals, "dtype", None) == object:
                    # fusion-off / mixed-chain fallback of a traceable UDF
                    # fed by a record-mode segment: same columnarization
                    # the fused device path performs at ingest
                    vals = _columnarize_records(vals, f"{t.kind} '{t.name}'")
                # canonical-dtype contract: the fused path computes on
                # canonical columns, so the fallback must too (same checked
                # cast — identical inputs, identical results)
                vals = canonical_column(vals, f"{t.kind} '{t.name}'")
            if t.kind == "map":
                if vec:
                    vals = self._to_column(fn(vals), columnar=True)
                else:
                    vals = obj_array([fn(v) for v in vals])
            elif t.kind == "map_ts":
                if vec:
                    vals = self._to_column(fn(vals, ts), columnar=True)
                else:
                    vals = obj_array([fn(v, int(x)) for v, x in zip(vals, ts)])
            elif t.kind == "filter":
                if vec:
                    mask = np.asarray(fn(vals), dtype=bool)
                else:
                    mask = np.fromiter(
                        (bool(fn(v)) for v in vals), dtype=bool, count=len(vals)
                    )
                vals = vals[mask]
                ts = ts[mask]
            elif t.kind == "map_batch":
                # whole-batch transform (amortized device dispatch: model
                # inference, vectorized UDFs)
                vals = self._to_column(fn(list(vals) if not vec else vals),
                                       columnar=vec)
                if len(vals) != len(ts):
                    # a hard error, not an assert: asserts vanish under
                    # `python -O`, and a 1:N map_batch would silently
                    # corrupt timestamp alignment for everything downstream
                    raise ValueError(
                        f"map_batch '{t.name}' returned {len(vals)} values "
                        f"for {len(ts)} input records; map_batch must be "
                        "1:1 (use flat_map for 1:N transforms)"
                    )
            elif t.kind == "flat_map":
                if vec:
                    out, src_idx = (fn(vals, ts)
                                    if t.config.get("with_timestamps")
                                    else fn(vals))
                    vals = self._to_column(out, columnar=True)
                    ts = ts[np.asarray(src_idx, dtype=np.int64)]
                else:
                    new_vals, new_ts = [], []
                    for v, x in zip(vals, ts):
                        for out in fn(v):
                            new_vals.append(out)
                            new_ts.append(int(x))
                    vals = obj_array(new_vals)
                    ts = np.asarray(new_ts, dtype=np.int64)
            else:
                raise NotImplementedError(t.kind)
        return vals, ts


def _max_source_out_of_orderness(step: Step) -> Optional[int]:
    """Largest bounded-out-of-orderness delay (ms) among the source
    watermark strategies feeding `step`, walking the step DAG back to its
    source transformations. Returns None when any reachable source uses a
    generator whose bound is not statically knowable (punctuated/custom)."""
    from flink_tpu.core.watermarks import BoundedOutOfOrdernessWatermarks

    bound = 0
    seen = set()
    stack = [step]
    while stack:
        s = stack.pop()
        if id(s) in seen:
            continue
        seen.add(id(s))
        for edge in s.inputs:
            producer = edge[0]
            if isinstance(producer, Step):
                stack.append(producer)
                continue
            cfg = producer.config
            if "out_of_orderness_hint" in cfg:
                # carved stage boundary (runtime/stages.py): the channel
                # strategy only forwards watermarks, but the hint carries
                # the ORIGINAL job sources' disorder bound across it
                hint = cfg["out_of_orderness_hint"]
                if hint is None:
                    return None
                bound = max(bound, hint)
                continue
            strategy = cfg.get("watermark_strategy")
            if strategy is None:
                continue     # no watermarks: never advances event time
            gen = strategy.create_generator()
            if not isinstance(gen, BoundedOutOfOrdernessWatermarks):
                return None
            bound = max(bound, gen._delay)
    return bound


def _session_disorder_within_gap(step: Step, assigner) -> bool:
    """Device-session routing gate: the device operator's late contract
    (drop records whose standalone session is already expired) matches the
    merging oracle only while watermark out-of-orderness stays BELOW the
    session gap — with bound >= gap a record can arrive late enough that
    the oracle would still merge it into an open session the device path
    already expired, i.e. silent data loss. Refuse the device operator for
    such pipelines and fall back to the oracle with a warning.

    Deliberate fail-OPEN on an unknowable bound (custom/punctuated
    generators return None): demoting those would leave users of custom
    strategies no way to ever select the device operator, and the common
    in-repo opaque case (stage boundaries) now carries the real bound via
    out_of_orderness_hint. A custom generator's author owns keeping its
    effective lag below the session gap — the DEVICE_SESSIONS option
    description states the contract; set it false to force the oracle."""
    bound = _max_source_out_of_orderness(step)
    if bound is None or bound < assigner.gap:
        return True
    import warnings

    warnings.warn(
        f"session windows: watermark out-of-orderness bound ({bound} ms) >= "
        f"session gap ({assigner.gap} ms) — using the per-record oracle "
        "operator instead of the device session operator, whose late "
        "contract would silently drop records the oracle merges. Shrink the "
        "out-of-orderness bound below the gap to re-enable the device path, "
        "or set execution.window.device-sessions false to silence this.",
        RuntimeWarning,
    )
    return False


class WindowStepRunner(StepRunner):
    """Keyed window aggregation step wrapping the device or oracle operator."""

    def __init__(self, step: Step, config: Configuration):
        t = step.terminal
        cfg = t.config
        assigner = cfg["assigner"]
        aggregate = cfg["aggregate"]
        self.key_selector = cfg["key_selector"]
        self.key_vectorized = cfg.get("key_vectorized", False)
        self.key_traceable = cfg.get("key_traceable", False)
        self.value_fn = cfg.get("value_fn") or (lambda v: v)
        self.value_vectorized = cfg.get("value_vectorized", False) and cfg.get("value_fn")
        self.window_fn = cfg.get("window_fn")
        device_agg = resolve(aggregate)
        use_device = (
            device_agg is not None
            and assigner.slice_ms is not None
            and assigner.is_event_time
            and cfg.get("trigger") is None
            and cfg.get("evictor") is None
            and self.window_fn is None
        )
        max_par = config.get(PipelineOptions.MAX_PARALLELISM)
        from flink_tpu.ops.aggregators import ONE

        self._needs_value = device_agg is None or any(
            f.source != ONE for f in device_agg.fields
        )
        from flink_tpu.api.windowing.assigners import EventTimeSessionWindows, GlobalWindows
        from flink_tpu.runtime.tpu_global_window_operator import (
            TpuGlobalWindowOperator,
            supported_trigger,
        )

        count_spec = supported_trigger(cfg.get("trigger"))
        use_fused = (
            use_device
            and cfg["allowed_lateness"] == 0
            and not cfg["side_output_late"]
            and config.get(ExecutionOptions.FUSED_WINDOWS)
            and all(f.scatter in ("add", "min", "max") for f in device_agg.fields)
        )
        if (
            isinstance(assigner, GlobalWindows)
            and device_agg is not None
            and count_spec is not None
            and cfg.get("evictor") is None
            and self.window_fn is None
        ):
            n, purging = count_spec
            self.op = TpuGlobalWindowOperator(
                device_agg,
                count_n=n,
                purging=purging,
                key_capacity=config.get(ExecutionOptions.KEY_CAPACITY),
            )
            self.device = True
        elif (
            isinstance(assigner, EventTimeSessionWindows)
            and device_agg is not None
            and assigner.is_event_time
            and config.get(ExecutionOptions.DEVICE_SESSIONS)
            and cfg.get("trigger") is None
            and cfg.get("evictor") is None
            and self.window_fn is None
            and cfg["allowed_lateness"] == 0
            and not cfg["side_output_late"]
            and _session_disorder_within_gap(step, assigner)
        ):
            # device-path sessions: per-slice fragments + vectorized
            # gap-merge (the MergingWindowSet re-design; see
            # runtime/tpu_session_operator.py)
            from flink_tpu.runtime.tpu_session_operator import (
                TpuSessionWindowOperator,
            )

            self.op = TpuSessionWindowOperator(
                assigner,
                device_agg,
                key_capacity=min(1 << 10, config.get(ExecutionOptions.KEY_CAPACITY)),
            )
            self.device = True
        elif use_fused:
            # the flagship path: T-step compiled superscan, one dispatch +
            # one async readback per superbatch (WindowOperatorBuilder.java:79
            # buildAsyncWindowOperator :472 is the reference's swap precedent)
            from flink_tpu.runtime.fused_window_operator import FusedWindowOperator

            batch_size = config.get(ExecutionOptions.BATCH_SIZE)
            # start small, grow by doubling with the key dictionary —
            # superscan cost scales with key capacity, so tiny jobs must
            # not pay for the configured maximum up front. With the state
            # tier enabled (state.tier.*) capacity is FIXED at the hot
            # cap instead: the vocabulary evicts, capacity never grows.
            tier = _tier_for_config(config)
            if tier is not None:
                capacity = tier.hot_key_capacity
            else:
                capacity = min(1 << 10,
                               config.get(ExecutionOptions.KEY_CAPACITY))
            self.op = FusedWindowOperator(
                assigner,
                device_agg,
                key_capacity=capacity,
                superbatch_steps=config.get(ExecutionOptions.SUPERBATCH_STEPS),
                chunk=_fused_chunk(batch_size),
                columnar_output=config.get(ExecutionOptions.COLUMNAR_OUTPUT),
                # multichip (parallel.mesh.*): the same fused operator runs
                # SPMD over the mesh; None keeps today's single-chip path
                mesh=_mesh_for_config(config, capacity),
                tier=tier,
                **_mesh_exchange_kwargs(config),
                **_latency_kwargs(config),
            )
            self.device = True
        elif use_device:
            # the per-batch classic path honors the state tier too, via
            # its grow-only hot/cold id split (ids past the hot cap
            # aggregate in the cold tier) — no vocabulary/eviction here,
            # but HBM stays bounded when the fused path is switched off
            tier = _tier_for_config(config)
            tier_kwargs = {}
            if tier is not None and cfg["allowed_lateness"] == 0:
                tier_kwargs = dict(
                    hot_key_capacity=tier.hot_key_capacity,
                    cold_tier_dir=tier.cold_dir,
                )
            self.op = TpuWindowOperator(
                assigner,
                device_agg,
                allowed_lateness=cfg["allowed_lateness"],
                key_capacity=config.get(ExecutionOptions.KEY_CAPACITY),
                emit_late_to_side_output=cfg["side_output_late"],
                columnar_output=config.get(ExecutionOptions.COLUMNAR_OUTPUT),
                **tier_kwargs,
            )
            self.device = True
        else:
            agg_fn = aggregate
            if device_agg is not None and not isinstance(aggregate, AggregateFunction):
                agg_fn = device_agg.python_equivalent()
            self.op = OracleWindowOperator(
                assigner,
                agg_fn,
                trigger=cfg.get("trigger"),
                allowed_lateness=cfg["allowed_lateness"],
                max_parallelism=max_par,
                window_function=self.window_fn,
                evictor=cfg.get("evictor"),
                emit_late_to_side_output=cfg["side_output_late"],
            )
            self.device = False
        # a window over the null key with a builtin positional aggregate
        # takes an upstream fire as columns (on_fires_n): one row of a block
        # stands for all of them only where nothing counts or keeps the
        # elements themselves
        self._block_agg = (
            aggregate
            if isinstance(aggregate, PositionalAggregate)
            and self.key_selector is null_key
            and cfg.get("trigger") is None
            and cfg.get("evictor") is None
            else None
        )
        self.processing_time = not assigner.is_event_time
        self.uid = t.uid
        # SQL-originated window steps (flink_tpu/planner lowering) are
        # marked so the job can report which execution path SQL selected
        # (job.sqlFusedSelected gauge + /jobs/:id visibility)
        self.sql_origin = bool(cfg.get("sql_origin"))
        self._init_drain()
        self._init_stage_clock(config)
        self._init_device_stats(config)
        self._init_emission_plane(config)

    def _init_drain(self) -> None:
        """What `_drain` drains: the fused operator hands its fires over as
        blocks of columns (runtime/fire_block.py), every other window
        operator as rows; `downstream_batch` takes either. Only the fused
        operator's drain is a blocking device readback (deferred superbatch
        resolution); everywhere else drain is a host list swap and timing
        it would inflate deviceDispatches."""
        blocks = getattr(self.op, "drain_blocks", None)
        self._drain_resolves_device = blocks is not None
        self._drain_fires = blocks or self.op.drain_output

    def _init_stage_clock(self, config: Configuration) -> None:
        """The operator's stage clock (metrics/task_io.py): named stages of
        the job's thread inside this runner, its operator and its pipeline,
        plus the outer sections round the already-synchronous dispatch and
        resolve calls (host clock; never adds syncs)."""
        self.stage_clock = (
            StageClock()
            if self.device
            and config.get(ObservabilityOptions.DEVICE_TIMING_ENABLED)
            else None
        )
        attach = getattr(self.op, "attach_stage_clock", None)
        if attach is not None and self.stage_clock is not None:
            attach(self.stage_clock)

    def _init_emission_plane(self, config: Configuration) -> None:
        """Emission-latency plane (observability.emission-latency.*).
        Device operators stamp INLINE at their own deferred-resolve /
        fire-loop sites (the host-visibility instant of a fired window);
        the host oracle has no tracker surface, so the runner stamps its
        drained rows instead — drain IS the oracle's visibility point."""
        self.emission_tracker = make_emission_tracker(self.uid, config)
        self._emission_lateness = getattr(self.op, "allowed_lateness", 0)
        self._emission_at_drain = False
        if self.emission_tracker is not None:
            if hasattr(type(self.op), "emission_tracker"):
                self.op.emission_tracker = self.emission_tracker
            else:
                self._emission_at_drain = True

    def _init_device_stats(self, config: Configuration) -> None:
        """Device-plane observability (metrics/device_stats.py + key_stats):
        a CompileTracker wrapped around the operator's jit entry points
        (operators without the attach surface — oracle, session, global —
        simply skip it) and a throttled key-stats collector over the
        operator's device-resident per-key counts. Gated like device
        timing; per-batch host cost is one clock compare."""
        self.device_stats = None
        self.key_stats = None
        self._roofline_peaks = None
        if not (self.device
                and config.get(ObservabilityOptions.DEVICE_STATS_ENABLED)):
            return
        from flink_tpu.metrics.device_stats import (
            CompileTracker,
            platform_peaks,
        )

        attach = getattr(self.op, "attach_device_stats", None)
        if attach is not None:
            tracker = CompileTracker(
                history_size=config.get(
                    ObservabilityOptions.DEVICE_RECOMPILE_HISTORY_SIZE),
                storm_threshold=config.get(
                    ObservabilityOptions.DEVICE_RECOMPILE_STORM_THRESHOLD),
                storm_window_ms=config.get(
                    ObservabilityOptions.DEVICE_RECOMPILE_STORM_WINDOW_MS),
                cost_analysis=config.get(
                    ObservabilityOptions.DEVICE_COST_ANALYSIS_ENABLED),
                memory_analysis=config.get(
                    ObservabilityOptions.DEVICE_MEMORY_ANALYSIS_ENABLED),
            )
            attach(tracker)
            self.device_stats = tracker
            self._roofline_peaks = platform_peaks(
                config.get(ObservabilityOptions.DEVICE_HBM_GBPS),
                config.get(ObservabilityOptions.DEVICE_PEAK_TFLOPS))
        loads_fn = getattr(self.op, "key_loads", None)
        if loads_fn is not None:
            from flink_tpu.config import PipelineOptions as _PO
            from flink_tpu.metrics.key_stats import KeyStatsCollector

            self.key_stats = KeyStatsCollector(
                loads_fn,
                num_key_groups=config.get(_PO.MAX_PARALLELISM),
                top_k=config.get(
                    ObservabilityOptions.DEVICE_KEY_STATS_TOP_K),
                row_bytes_fn=getattr(self.op, "state_row_bytes", None),
                ready_fn=getattr(self.op, "key_stats_ready", None),
                interval_ms=config.get(
                    ObservabilityOptions.DEVICE_KEY_STATS_INTERVAL_MS),
                # mesh operators additionally expose per-device local
                # loads, so the skew fold sees the worst DEVICE too;
                # single-chip operators keep a clean gauge surface
                mesh_loads_fn=(
                    getattr(self.op, "per_device_key_loads", None)
                    if getattr(self.op, "mesh_devices", lambda: 1)() > 1
                    else None),
                mesh_exchange_fn=getattr(
                    self.op, "per_device_exchange", None),
            )

    def _device_stats_tick(self) -> None:
        ks = self.key_stats
        if ks is not None and ks.due():
            # the fold reads the ring the newest dispatch writes: its
            # readback waits for that dispatch, on the job's thread
            with stage(self.stage_clock, "keys.stats"):
                ks.collect()

    def device_roofline(self) -> Dict[str, float]:
        """hbmUtilizationPct / flopsUtilizationPct: XLA's cost analysis over
        the stage clock's outer sections (0.0 when device timing is
        ungated). NOTE the denominator is HOST time in the dispatch and
        resolve sections (`deviceTimeMsTotal`), not device time, and the
        figure still feeds scheduler/signals.py: to be re-sourced from a
        device trace or removed (ROADMAP.md). Empty when there are no peaks
        to divide by: the device kind has no DEVICE_PEAKS row and none are
        configured."""
        from flink_tpu.metrics.device_stats import roofline_pct

        if self._roofline_peaks is None:
            return {}
        tracker, timer = self.device_stats, self.stage_clock
        if tracker is None or timer is None:
            return {"hbmUtilizationPct": 0.0, "flopsUtilizationPct": 0.0}
        hbm, tflops = self._roofline_peaks
        return roofline_pct(tracker.bytes_accessed_total(),
                            tracker.flops_total(), timer.total_s,
                            hbm, tflops)

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        # chaos seam (device dispatch boundary): one is-None check per
        # batch when chaos is off; an injected error surfaces exactly like
        # a real dispatch failure and rides the normal restart path
        hook = _chaos.HOOK
        if hook is not None:
            hook("device", self.uid)
        if self.key_traceable and len(timestamps):
            # fusion-off fallback of a traceable program: columnarize
            # record-mode sources and cast to the canonical dtype exactly
            # like the fused ingest would, so CHAIN_FUSION stays a perf
            # switch, never a semantics switch
            if getattr(values, "dtype", None) == object:
                values = _columnarize_records(values, "key_by selector")
            values = canonical_column(values, "key_by selector input")
        if self.device:
            clock = self.stage_clock
            with stage(clock, "chain.host"):
                keys, nums = self._keys_and_values(values)
            with section(clock):
                self.op.process_batch(keys, nums, timestamps)
            self._device_stats_tick()
        else:
            if self.processing_time:
                # PT windows: assignment & timers use wall clock, not event ts
                now = int(time.time() * 1000)
                timestamps = np.full(len(values), now, dtype=np.int64)
            # vectorized selectors see a one-row column per record here;
            # np.asarray on the result keeps jnp-written (traceable) fns
            # usable — a bare jax scalar is unhashable as an oracle key
            key_of = (
                (lambda v: np.asarray(
                    self.key_selector(np.asarray(v)[None, ...]))[0])
                if self.key_vectorized
                else self.key_selector
            )
            val_of = (
                (lambda v: np.asarray(
                    self.value_fn(np.asarray(v)[None, ...]))[0])
                if self.value_vectorized
                else self.value_fn
            )
            for v, ts in zip(values, timestamps):
                self.op.process_record(key_of(v), val_of(v), int(ts))
            if self.processing_time:
                self.op.advance_processing_time(int(time.time() * 1000))
                self._drain()

    def on_fires_n(self, ordinal: int, drained: Sequence, bare: bool,
                   clock: Optional[StageClock]) -> bool:
        """The fast path of `window_all(...).max_by / min_by` behind a fused
        window: each FireBlock is reduced over its keys by whole-column
        calls (stage `fire.reduce`, on the emitting operator's clock, with
        the `seq=` of the dispatch that fired the block) and the window
        operator gets one partial row per block. The rows of a block share
        one timestamp, hence every window, and the aggregate keeps the
        first among equals, so the partial row is the row the window would
        have kept of them. Anything else (rows, a block only its rows can
        judge, a block at or behind the watermark, whose rows the operator
        has to count as late one by one) goes the row way: False."""
        agg = self._block_agg
        if agg is None or bare or type(drained[0]) is not FireBlock:
            return False
        wm = self.op.timer_service.current_watermark
        rows = []
        for b in drained:
            with stage(clock, "fire.reduce", b.seq):
                row = reduce_block(b, agg) if b.ts > wm else None
            if row is None:
                return False
            rows.append(row)
        if clock is not None:
            clock.fire_rows_reduced += sum(map(len, drained))
            clock.fire_rows_kept += len(rows)
        self.on_batch_n(ordinal, obj_array(rows), np.fromiter(
            (b.ts for b in drained), dtype=np.int64, count=len(drained)))
        return True

    def _keys_and_values(self, values: np.ndarray):
        """The key column and the f32 value column of one batch (the key
        selector and value function of the host-keyed path)."""
        if self.key_vectorized:
            keys = np.asarray(self.key_selector(values))
        else:
            raw_keys = [self.key_selector(v) for v in values]
            keys = np.asarray(raw_keys)
            if keys.ndim != 1 or keys.dtype.kind not in "iuUS":
                keys = obj_array(raw_keys)
        # typed key columns (int/str) unlock the native C++ dictionary
        if keys.ndim != 1 or keys.dtype.kind not in "iuUSO":
            keys = obj_array(list(keys))
        if self._needs_value:
            if self.value_vectorized:
                nums = np.asarray(self.value_fn(values), dtype=np.float32)
            else:
                nums = np.asarray(
                    [self.value_fn(v) for v in values], dtype=np.float32
                )
        else:  # pure-count aggregates ignore the value column
            nums = np.zeros(len(values), dtype=np.float32)
        return keys, nums

    def on_watermark(self, watermark: int) -> None:
        if self.device and self.key_stats is not None:
            # fold BEFORE the watermark's purge sweep so a due collection
            # sees the state the advance is about to retire
            self._device_stats_tick()
        with section(self.stage_clock):
            self.op.process_watermark(watermark)
        self._drain()
        # fused operators emit asynchronously (superbatch granularity):
        # forward only the watermark their resolved output already covers,
        # so downstream never sees a watermark ahead of pending fires
        safe = getattr(self.op, "emitted_watermark", None)
        if safe is not None:
            watermark = min(watermark, safe)
        if watermark > MIN_WATERMARK:
            self._forward_watermark(watermark)

    def _forward_watermark(self, watermark: int) -> None:
        if self.downstream:
            self.downstream.on_watermark(watermark)
        if self.sides:
            for f in self.sides.values():
                f.on_watermark(watermark)

    def on_end(self) -> None:
        self._drain()
        super().on_end()

    def on_processing_time(self, now_ms: int) -> None:
        # PT windows fire from the shared ProcessingTimeService tick, not
        # only when their own source produces a batch
        self._device_stats_tick()
        if self.processing_time:
            self.op.advance_processing_time(now_ms)
            self._drain()

    def _drain(self) -> None:
        op_sides = getattr(self.op, "side_output", None)
        if op_sides:
            for tag_id, rows in list(op_sides.items()):
                if rows and self.sides and tag_id in self.sides:
                    vals = obj_array([(k, v) for (k, v, _t) in rows])
                    tss = np.asarray([t for (_k, _v, t) in rows], dtype=np.int64)
                    self.emit_side(tag_id, vals, tss)
                # rows without a consumer are dropped, not accumulated
                op_sides[tag_id] = []
        clock = self.stage_clock
        # an outer section of the fused operator only (kept so that
        # deviceDispatches counts what it always counted); other
        # operators' drain is a host list swap and is not timed
        with section(clock if self._drain_resolves_device else None):
            out = self._drain_fires()
        if out and self._emission_at_drain:
            tr, lateness = self.emission_tracker, self._emission_lateness
            for w, t in fires_of(out):
                tr.record_fire(getattr(w, "end", int(t) + 1),
                               lateness_ms=lateness)
        if out and self.downstream:
            with stage(clock, "drain"):
                self.downstream.on_fires(
                    out, self.window_fn is not None, clock)

    def register_metrics(self, group) -> None:
        super().register_metrics(group)
        group.gauge("numLateRecordsDropped",
                    lambda: self.op.num_late_records_dropped,
                    fold="sum", kind="counter")

        def _wm():
            return getattr(
                self.op,
                "current_watermark",
                getattr(getattr(self.op, "timer_service", None),
                        "current_watermark", 0),
            )

        # watermark position: the job-level combined watermark is what
        # EVERY subtask has reached, so the fold is MIN
        group.gauge("currentWatermark", _wm, fold="min")
        if self.emission_tracker is not None:
            # emission-latency plane: flat log-bucket snapshot (declared
            # "emission" — folds bucket-wise EXACTLY across shards) +
            # wall-vs-watermark lag (worst shard -> MAX)
            group.gauge("emissionLatencyMs", self.emission_tracker.snapshot,
                        fold="emission", kind="histogram")
            group.gauge("watermarkLagMs", lambda: watermark_lag_ms(_wm()),
                        fold="max")
        if self.stage_clock is not None:
            self.stage_clock._hist = group.histogram("deviceDispatchMs")
            self.stage_clock.register(group)
        state_bytes = getattr(self.op, "state_bytes", None)
        if state_bytes is not None:
            # HBM-resident state footprint of this operator's device arrays
            group.gauge("stateBytes", state_bytes, fold="sum")
        key_count = getattr(self.op, "state_key_count", None)
        if key_count is not None:
            group.gauge("stateKeyCount", key_count, fold="sum")
        # device plane: compile counters, roofline, phase counters, key
        # telemetry — all on the operator scope so laggard kernels are
        # attributable per step
        if self.device_stats is not None:
            self.device_stats.register(group)
            if self._roofline_peaks is not None:
                # roofline fractions are each shard's own chip's view -> MEAN
                group.gauge(
                    "hbmUtilizationPct",
                    lambda: self.device_roofline()["hbmUtilizationPct"],
                    fold="mean")
                group.gauge(
                    "flopsUtilizationPct",
                    lambda: self.device_roofline()["flopsUtilizationPct"],
                    fold="mean")
            phases = getattr(self.op, "phase_totals", None)
            if callable(phases):
                group.gauge("phaseIngestRecords",
                            lambda: phases()["ingestRecords"],
                            fold="sum", kind="counter")
                group.gauge("phaseFireSteps",
                            lambda: phases()["fireSteps"],
                            fold="sum", kind="counter")
                group.gauge("phasePurgeSteps",
                            lambda: phases()["purgeSteps"],
                            fold="sum", kind="counter")
                group.gauge("phaseOneSliceSteps",
                            lambda: phases()["oneSliceSteps"],
                            fold="sum", kind="counter")
        if self.key_stats is not None:
            self.key_stats.register(group)
        # state-tier gauges (state/tier_manager.py): counters/sizes SUM
        # across shards — each shard owns its key range; tierHotFillRatio
        # (a per-shard fraction) MEANs. Eviction/promotion totals are
        # monotone, so the history plane records them as churn RATES.
        tier_gauges = getattr(self.op, "tier_gauges", None)
        if callable(tier_gauges) and tier_gauges() is not None:
            for key, kind in (("vocabSize", None), ("residentKeys", None),
                              ("evictions", "counter"),
                              ("promotions", "counter"),
                              ("spilledBytes", "counter"),
                              ("changelogBytes", "counter")):
                group.gauge(key, lambda k=key: self.op.tier_gauges().get(k),
                            fold="sum", kind=kind)
            group.gauge("tierHotFillRatio",
                        lambda: self.op.tier_gauges().get("tierHotFillRatio"),
                        fold="mean")
        # latency-mode controller gauges (execution.latency.target-ms):
        # registered only when the mode is on, folded MAX across shards
        # (the deepest rung / fullest ring / most geometries is the job's
        # latency view) — the controller's rung/ring/ladder decisions
        # surface in /jobs/:id/device and /latency
        latency_gauges = getattr(self.op, "latency_gauges", None)
        if callable(latency_gauges) and latency_gauges() is not None:
            for key in ("latencyModeActive", "currentBatchRung",
                        "inflightDepth", "ladderRecompiles"):
                group.gauge(key,
                            lambda k=key: self.op.latency_gauges().get(k),
                            fold="max")

    def snapshot(self) -> dict:
        return {"operator": self.op.snapshot()}

    def restore(self, snap: dict) -> None:
        self.op.restore(snap["operator"])


class DeviceChainRunner(WindowStepRunner):
    """Whole-graph fusion runner (graph/fusion.py): one runner for a fused
    device chain — the traceable map/filter/map_ts prologue, key/value
    extraction, and the windowed aggregation compile into ONE jitted
    multi-step device program (`lax.scan` over T batches) with
    device-resident intermediates. Raw source columns are the only thing
    the host stages; the post-transform columns, key column and value
    column never materialize host-side.

    This is the reference's operator chaining taken to its TPU-native
    conclusion (StreamingJobGraphGenerator chains operators into direct
    calls; XLA chains them into one program). Inherits the watermark
    clamping, drain, metrics, and snapshot surfaces of WindowStepRunner —
    only construction and ingest differ."""

    def __init__(self, step: Step, plan, config: Configuration):
        self._init_fused(plan.terminal, plan.transforms, config)

    def _init_fused(self, t, transforms, config: Configuration,
                    assigners=None) -> None:
        """Shared construction of the fused device surface (also used by
        SharedWindowRunner, which passes the group's `assigners` — any new
        option threaded to FusedWindowOperator lands on both paths)."""
        from flink_tpu.runtime.fused_window_operator import FusedWindowOperator
        from flink_tpu.runtime.fused_window_pipeline import TracedPrologue

        cfg = t.config
        prologue = TracedPrologue(
            transforms=tuple(
                (tr.kind, tr.config["fn"]) for tr in transforms),
            key_fn=cfg["key_selector"],
            value_fn=cfg.get("value_fn"),
        )
        batch_size = config.get(ExecutionOptions.BATCH_SIZE)
        # dense device keying cannot grow mid-dispatch: capacity is the
        # configured bound, and an out-of-range traced key raises at
        # resolve (never silently aliases another key's row)
        capacity = config.get(ExecutionOptions.KEY_CAPACITY)
        self.op = FusedWindowOperator(
            None if assigners is not None else cfg["assigner"],
            cfg["aggregate"],
            key_capacity=capacity,
            superbatch_steps=config.get(ExecutionOptions.SUPERBATCH_STEPS),
            chunk=_fused_chunk(batch_size),
            columnar_output=config.get(ExecutionOptions.COLUMNAR_OUTPUT),
            prologue=prologue,
            # multichip SPMD (parallel.mesh.*): the fused USER job — not a
            # hand-built kernel — shards over the mesh; the traced prologue
            # runs on each device's slice and one in-scan all-to-all per
            # step is the keyBy exchange
            mesh=_mesh_for_config(config, capacity),
            **_mesh_exchange_kwargs(config),
            **_latency_kwargs(config),
            **({} if assigners is None else {"assigners": list(assigners)}),
        )
        self.device = True
        self.window_fn = None
        self.processing_time = False
        self.uid = t.uid
        self.sql_origin = bool(cfg.get("sql_origin"))
        self._init_drain()
        self._init_stage_clock(config)
        self._init_device_stats(config)
        self._init_emission_plane(config)
        self._warned_object_columns = False

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        hook = _chaos.HOOK   # chaos seam: fused-chain dispatch boundary
        if hook is not None:
            hook("device", self.uid)
        if len(timestamps) == 0:
            return   # idle poll / watermark-only step: nothing to stage
        clock = self.stage_clock
        with stage(clock, "chain.host"):
            vals = self._device_column(values)
        with section(clock):
            self.op.process_raw_batch(vals, timestamps)
        self._device_stats_tick()

    def _device_column(self, vals):
        if getattr(vals, "dtype", None) == object or not isinstance(vals, np.ndarray):
            # record-mode source: one columnarization pass per batch. A
            # columnar source (numeric ndarray batches) or the binary wire
            # (frombuffer views, runtime/stages.py) skips this entirely.
            if not self._warned_object_columns:
                self._warned_object_columns = True
                import warnings

                warnings.warn(
                    "fused device chain fed by a record-mode source: paying "
                    "a per-batch columnarization pass; switch the source to "
                    "columnar numeric batches to feed the device directly",
                    RuntimeWarning,
                )
            return _columnarize_records(vals, "fused device chain")
        return as_device_column(vals)


class SharedWindowSiblingRunner(StepRunner):
    """Placeholder runner for a non-leader member of a shared-partial
    window group (graph/window_sharing.py): it owns the member's
    downstream edges, and the group leader pushes this member's resolved
    emissions, watermarks, and end-of-input into them. Its own input
    edges are never wired (the leader consumes the stream once — wiring
    them would double-ingest), so every on_* here is unreachable."""

    def __init__(self, step: Step, spec: int):
        self.uid = step.terminal.uid
        self.spec = spec
        self.sql_origin = bool(step.terminal.config.get("sql_origin"))

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        raise AssertionError(
            "shared-window sibling received a direct batch; its input "
            "edges must not be wired")


class SharedWindowRunner(DeviceChainRunner):
    """Shared-partials runner (graph/window_sharing.py): ONE traced device
    program serves N correlated window() siblings — gcd-granule partials
    ingest once, every member window fires its own slice run from the
    shared ring (Factor Windows), and each member's emissions route to
    its own downstream edges through its sibling runner. Construction
    mirrors DeviceChainRunner (the sharing bar equals the fusion bar);
    only emission routing and watermark/end fan-out differ."""

    def __init__(self, step: Step, shared_plan, config: Configuration):
        self.shared_plan = shared_plan
        self._init_fused(shared_plan.terminals[0], shared_plan.transforms,
                         config, assigners=shared_plan.assigners)
        # spec index -> the runner owning that member's downstream edges
        # (spec 0 = this leader); siblings register in build_runners
        self.member_runners: List[StepRunner] = [self]

    def _spec_fanouts(self):
        for spec, r in enumerate(self.member_runners):
            yield spec, r.downstream, (r.sides or None)

    def _drain(self) -> None:
        clock = self.stage_clock
        with section(clock if self._drain_resolves_device else None):
            drained = [self.op.drain_spec_blocks(s)
                       for s in range(len(self.member_runners))]
        for spec, fan, _sides in self._spec_fanouts():
            out = drained[spec]
            if out and fan:
                with stage(clock, "drain"):
                    # the base _drain's hand-over: sharing must never
                    # change what downstream receives
                    fan.on_fires(out, False, clock)

    def _forward_watermark(self, watermark: int) -> None:
        for _spec, fan, sides in self._spec_fanouts():
            if fan:
                fan.on_watermark(watermark)
            if sides:
                for f in sides.values():
                    f.on_watermark(watermark)

    def on_marker(self, wall_ms: float) -> None:
        # markers fan out to EVERY member's downstream, like watermarks —
        # sharing must not blind the sibling sinks' latency histograms
        h = getattr(self, "_marker_hist", None)
        if h is not None:
            h.update(time.time() * 1000.0 - wall_ms)
        for _spec, fan, sides in self._spec_fanouts():
            if fan:
                fan.on_marker(wall_ms)
            if sides:
                for f in sides.values():
                    f.on_marker(wall_ms)

    def on_end(self) -> None:
        self._drain()
        for _spec, fan, sides in self._spec_fanouts():
            if fan:
                fan.on_end()
            if sides:
                for f in sides.values():
                    f.on_end()


class KeyedReduceRunner(StepRunner):
    """Rolling keyed reduce (KeyedStream.reduce): emits the running reduce
    per input record (reference: StreamGroupedReduceOperator semantics)."""

    def __init__(self, step: Step, config: Configuration):
        t = step.terminal
        self.key_selector = t.config["key_selector"]
        self.reduce_fn = t.config["reduce_fn"]
        max_par = config.get(PipelineOptions.MAX_PARALLELISM)
        self.state = HeapKeyedStateBackend(KeyGroupRange(0, max_par - 1), max_par)
        self.state.register(value_state("rolling"))
        self.uid = t.uid

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        out = []
        for v in values:
            key = self.key_selector(v)
            self.state.set_current_key(key)
            cur = self.state.get("rolling")
            nxt = v if cur is None else self.reduce_fn(cur, v)
            self.state.put("rolling", nxt)
            out.append(nxt)
        if out and self.downstream:
            self.downstream.on_batch(obj_array(out), timestamps)

    def snapshot(self) -> dict:
        return {"state": self.state.snapshot()}

    def restore(self, snap: dict) -> None:
        self.state.restore(snap["state"])


class KeyedProcessRunner(StepRunner):
    """KeyedProcessFunction with event-time timers (oracle path)."""

    def __init__(self, step: Step, config: Configuration):
        t = step.terminal
        self.key_selector = t.config["key_selector"]
        self._init_keyed(t, config)

    def _init_keyed(self, t: Transformation, config: Configuration) -> None:
        self.fn: ProcessFunction = t.config["process_fn"]
        max_par = config.get(PipelineOptions.MAX_PARALLELISM)
        self.state = HeapKeyedStateBackend(
            KeyGroupRange(0, max_par - 1), max_par, auto_register=True)
        self.timers = InternalTimerService(
            self._on_event_timer, self._on_proc_timer)  # both bind dynamically
        self._out: List = []
        self._out_ts: List[int] = []
        self._side_buf: Dict[str, tuple] = {}
        self.uid = t.uid

    class _TimerService:
        def __init__(self, runner, key):
            self._r = runner
            self._key = key

        def register_event_time_timer(self, time: int) -> None:
            self._r.timers.register_event_time_timer(self._key, None, time)

        def register_processing_time_timer(self, time: int) -> None:
            self._r.timers.register_processing_time_timer(self._key, None, time)

        def current_watermark(self) -> int:
            return self._r.timers.current_watermark

        def state(self):
            return self._r.state

    def _ctx(self, key, timestamp):
        def side(tag, value):
            tag_id = getattr(tag, "tag_id", tag)
            buf = self._side_buf.setdefault(tag_id, ([], []))
            buf[0].append(value)
            buf[1].append(timestamp)

        return ProcessFunction.Context(timestamp, self._TimerService(self, key), side)

    def _on_event_timer(self, time, key, _ns) -> None:
        self.state.set_current_key(key)
        on_timer = getattr(self.fn, "on_timer", None)
        if on_timer is None:
            return
        for out in on_timer(time, self._ctx(key, time)):
            self._out.append(out)
            self._out_ts.append(time)

    def _on_proc_timer(self, time, key, _ns) -> None:
        """Same user callback (onTimer), but outputs carry NO event
        timestamp (MIN_TIMESTAMP sentinel) — the reference erases
        timestamps on processing-time timer output rather than leaking
        wall-clock epochs into the event-time domain."""
        self.state.set_current_key(key)
        on_timer = getattr(self.fn, "on_timer", None)
        if on_timer is None:
            return
        for out in on_timer(time, self._ctx(key, time)):
            self._out.append(out)
            self._out_ts.append(MIN_TIMESTAMP)

    def on_processing_time(self, now_ms: int) -> None:
        self.timers.advance_processing_time(now_ms)
        self._flush()

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        for v, ts in zip(values, timestamps):
            key = self.key_selector(v)
            self.state.set_current_key(key)
            for out in self.fn.process_element(v, self._ctx(key, int(ts))):
                self._out.append(out)
                self._out_ts.append(int(ts))
        self._flush()

    def on_watermark(self, watermark: int) -> None:
        self.timers.advance_watermark(watermark)
        self._flush()
        super().on_watermark(watermark)

    def _flush(self):
        if self._out:
            if self.downstream:
                self.downstream.on_batch(
                    obj_array(self._out),
                    np.asarray(self._out_ts, dtype=np.int64))
            # clear even without a consumer (a step may be reachable only
            # through its side output) — unconsumed output must not pile up
            self._out, self._out_ts = [], []
        if self._side_buf:
            for tag_id, (vals, tss) in self._side_buf.items():
                if vals:
                    self.emit_side(
                        tag_id, obj_array(vals),
                        np.asarray(tss, dtype=np.int64))
            self._side_buf = {}

    def snapshot(self) -> dict:
        return {"state": self.state.snapshot(), "timers": self.timers.snapshot()}

    def restore(self, snap: dict) -> None:
        self.state.restore(snap["state"])
        self.timers.restore(snap["timers"])


class CepRunner(StepRunner):
    """Keyed CEP pattern-matching step (CepOperator.java:83 analogue)."""

    def __init__(self, step: Step, config: Configuration):
        from flink_tpu.cep.operator import CepOperator

        t = step.terminal
        self.key_selector = t.config["key_selector"]
        self.op = CepOperator(t.config["pattern"], t.config.get("select_fn"))
        self.uid = t.uid

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        for v, ts in zip(values, timestamps):
            self.op.process_record(self.key_selector(v), v, int(ts))

    def on_watermark(self, watermark: int) -> None:
        self.op.process_watermark(watermark)
        out = self.op.drain_output()
        if out and self.downstream:
            vals = obj_array([r for (_k, _w, r, _t) in out])
            ts = np.asarray([t for (_k, _w, _r, t) in out], dtype=np.int64)
            self.downstream.on_batch(vals, ts)
        super().on_watermark(watermark)

    def snapshot(self) -> dict:
        return {"operator": self.op.snapshot()}

    def restore(self, snap: dict) -> None:
        self.op.restore(snap["operator"])


class UnionRunner(StepRunner):
    """N-way stream union: batches pass through; the base-class valve
    min-combines the input watermarks (DataStream.union, UnionTransformation
    — the reference wires union as extra input edges; here an explicit
    pass-through gate keeps the valve bookkeeping in one place)."""

    def __init__(self, step: Step):
        self.num_inputs = len(step.inputs)
        self.uid = step.terminal.uid

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        if self.downstream:
            self.downstream.on_batch(values, timestamps)


class CoMapRunner(StepRunner):
    """Non-keyed connected-stream transform: fn1 on input 0, fn2 on input 1
    (ConnectedStreams.map/flatMap, CoStreamMap/CoStreamFlatMap analogue)."""

    num_inputs = 2

    def __init__(self, step: Step):
        t = step.terminal
        self.fns = (t.config["fn1"], t.config["fn2"])
        self.flat = t.kind == "co_flat_map"
        self.uid = t.uid

    def on_batch_n(self, ordinal: int, values, timestamps) -> None:
        fn = self.fns[ordinal]
        ts = np.asarray(timestamps, dtype=np.int64)
        if self.flat:
            out, out_ts = [], []
            for v, tt in zip(values, ts):
                for o in fn(v):
                    out.append(o)
                    out_ts.append(int(tt))
            if out and self.downstream:
                self.downstream.on_batch(
                    obj_array(out), np.asarray(out_ts, dtype=np.int64))
        else:
            if len(ts) and self.downstream:
                self.downstream.on_batch(obj_array([fn(v) for v in values]), ts)

    def on_batch(self, values, timestamps) -> None:  # pragma: no cover
        raise AssertionError("CoMapRunner consumes via input gates")


class KeyedCoProcessRunner(KeyedProcessRunner):
    """Keyed two-input process function with shared per-key state and
    event-time timers (KeyedCoProcessFunction / CoProcessOperator analogue:
    both inputs key into the SAME state backend, which is the whole point of
    connect() vs union()). Inherits context/timer/flush/snapshot machinery
    from KeyedProcessRunner; only the two-gate dispatch differs."""

    num_inputs = 2

    def __init__(self, step: Step, config: Configuration):
        t = step.terminal
        self.key_selectors = (t.config["key_selector1"], t.config["key_selector2"])
        self._init_keyed(t, config)

    def on_batch_n(self, ordinal: int, values, timestamps) -> None:
        ks = self.key_selectors[ordinal]
        process = (self.fn.process_element1 if ordinal == 0
                   else self.fn.process_element2)
        for v, ts in zip(values, np.asarray(timestamps, dtype=np.int64)):
            key = ks(v)
            self.state.set_current_key(key)
            for out in process(v, self._ctx(key, int(ts))):
                self._out.append(out)
                self._out_ts.append(int(ts))
        self._flush()

    def on_batch(self, values, timestamps) -> None:  # pragma: no cover
        raise AssertionError("KeyedCoProcessRunner consumes via input gates")


class BroadcastProcessRunner(StepRunner):
    """Broadcast state pattern (BroadcastConnectedStream.process /
    CoBroadcastWithNonKeyedOperator): input gate 1 carries the broadcast
    stream, whose elements update operator-wide broadcast state; gate 0
    elements read it through an immutable view — the reference's read-only
    non-broadcast side contract, enforced here with a mapping proxy."""

    num_inputs = 2

    def __init__(self, step: Step, config: Configuration):
        import types

        t = step.terminal
        self.fn = t.config["process_fn"]
        self.state: Dict[Any, Any] = {}
        self._view = types.MappingProxyType(self.state)  # live read-only view
        self._out: List = []
        self._out_ts: List[int] = []
        self.uid = t.uid

    def on_batch_n(self, ordinal: int, values, timestamps) -> None:
        ts = np.asarray(timestamps, dtype=np.int64)
        if ordinal == 1:
            for v in values:
                self.fn.process_broadcast_element(v, self.state)
            return
        view = self._view
        for v, tt in zip(values, ts):
            for out in self.fn.process_element(v, view):
                self._out.append(out)
                self._out_ts.append(int(tt))
        if self._out:
            if self.downstream:
                self.downstream.on_batch(
                    obj_array(self._out),
                    np.asarray(self._out_ts, dtype=np.int64))
            self._out, self._out_ts = [], []

    def on_batch(self, values, timestamps) -> None:  # pragma: no cover
        raise AssertionError("BroadcastProcessRunner consumes via input gates")

    def snapshot(self) -> dict:
        return {"broadcast": dict(self.state)}

    def restore(self, snap: dict) -> None:
        import types

        self.state = dict(snap["broadcast"])
        self._view = types.MappingProxyType(self.state)


class WindowJoinRunner(StepRunner):
    """Keyed event-time window join / coGroup.

    The reference implements join as coGroup over tagged inputs flowing into
    one WindowOperator (JoinedStreams.java:101 'Join is implemented on top
    of CoGroup', CoGroupedStreams.java WithWindow.apply): elements of both
    sides buffer per (key, window); when the watermark passes the window
    end, join emits one result per left x right pair, coGroup emits one
    result per window from both element lists. Late elements (window already
    fired) are dropped, matching WindowOperator.isWindowLate."""

    num_inputs = 2

    def __init__(self, step: Step, config: Configuration):
        t = step.terminal
        self.key_selectors = (t.config["key_selector1"], t.config["key_selector2"])
        self.assigner = t.config["assigner"]
        if not self.assigner.is_event_time:
            raise ValueError("window joins support event-time assigners")
        self.join_fn = t.config.get("join_fn")
        self.cogroup = t.kind == "co_group"
        # (key, window_start, window_end) -> ([left...], [right...])
        self._buf: Dict[tuple, tuple] = {}
        self._wm = MIN_WATERMARK
        self.num_late_dropped = 0
        self.uid = t.uid

    def on_batch_n(self, ordinal: int, values, timestamps) -> None:
        ks = self.key_selectors[ordinal]
        for v, ts in zip(values, np.asarray(timestamps, dtype=np.int64)):
            key = ks(v)
            for w in self.assigner.assign_windows(v, int(ts)):
                if w.end - 1 <= self._wm:
                    self.num_late_dropped += 1
                    continue
                sides = self._buf.get((key, w.start, w.end))
                if sides is None:
                    sides = ([], [])
                    self._buf[(key, w.start, w.end)] = sides
                sides[ordinal].append(v)

    def on_batch(self, values, timestamps) -> None:  # pragma: no cover
        raise AssertionError("WindowJoinRunner consumes via input gates")

    def on_watermark(self, watermark: int) -> None:
        self._wm = max(self._wm, watermark)
        out, out_ts = [], []
        # fire in (window end, key-insertion) order, mirroring the oracle's
        # timer ordering
        ripe = [k for k in self._buf if k[2] - 1 <= self._wm]
        ripe.sort(key=lambda k: k[2])
        for k in ripe:
            left, right = self._buf.pop(k)
            max_ts = k[2] - 1
            if self.cogroup:
                out.append(self.join_fn(left, right))
                out_ts.append(max_ts)
            else:
                for lv in left:
                    for rv in right:
                        out.append(self.join_fn(lv, rv))
                        out_ts.append(max_ts)
        if out and self.downstream:
            self.downstream.on_batch(
                obj_array(out), np.asarray(out_ts, dtype=np.int64))
        super().on_watermark(watermark)

    def snapshot(self) -> dict:
        return {
            "buf": {k: (list(l), list(r)) for k, (l, r) in self._buf.items()},
            "wm": self._wm,
            "late": self.num_late_dropped,
        }

    def restore(self, snap: dict) -> None:
        self._buf = {k: (list(l), list(r)) for k, (l, r) in snap["buf"].items()}
        self._wm = snap["wm"]
        self.num_late_dropped = snap["late"]


class SinkRunner(StepRunner):
    def __init__(self, step: Step):
        sink = step.terminal.config["sink"]
        self.writer = sink.create_writer()
        self.committer = sink.create_committer()
        self.uid = step.terminal.uid
        self._latency_hist = None

    def register_metrics(self, group) -> None:
        super().register_metrics(group)
        # O3: per-marker pipeline latency at the sink (source wall clock ->
        # sink arrival; the reference's LatencyMarker histogram)
        self._latency_hist = group.histogram("pipelineLatencyMs")

    def on_marker(self, wall_ms: float) -> None:
        if self._latency_hist is not None:
            self._latency_hist.update(time.time() * 1000.0 - wall_ms)
        super().on_marker(wall_ms)

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        with stage(self.stage_clock, "sink.write"):
            self.writer.write_batch(values, timestamps)

    def commit_epoch(self, epoch_id: str = "final") -> None:
        if self.committer is not None:
            self.committer.commit(self.writer.prepare_commit(epoch_id))

    def on_end(self) -> None:
        self.commit_epoch("final")
        self.writer.close()

    def snapshot(self) -> dict:
        # collect-style sinks are stateful: emissions before the cut belong
        # to the checkpoint (post-cut emissions of a failed attempt are
        # discarded and re-fired on replay — the shard-task contract)
        store = getattr(self.writer, "store", None)
        return {"collected": list(store)} if store is not None else {}

    def restore(self, snap: dict) -> None:
        store = getattr(self.writer, "store", None)
        if store is not None and "collected" in snap:
            store[:] = snap["collected"]


class IterationHeadRunner(StepRunner):
    """Iteration head (StreamIterationHead.java analogue on the stepped
    executor): forwards the initial stream and re-injects feedback batches
    that its tail enqueues. Watermarks cross only the initial edge — as in
    the reference, feedback edges carry no watermarks — and the end-of-input
    signal is HELD until the run loop drains feedback to quiescence (the
    stepped analogue of the reference's iteration await-timeout
    termination: here bounded inputs terminate exactly when the loop body
    stops feeding records back)."""

    def __init__(self, step: Step):
        t = step.terminal
        self.uid = t.uid
        self.max_rounds = int(t.config.get("max_rounds", 10000))
        self._feedback: deque = deque()     # (values, timestamps) batches
        self._end_held = False
        self._held_wm: Optional[int] = None
        self._closed = False

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        if self.downstream:
            self.downstream.on_batch(values, timestamps)

    def on_watermark(self, watermark: int) -> None:
        if watermark >= MAX_WATERMARK - 1 and not self._closed:
            # the sources' final flush must not fire downstream windows while
            # feedback can still inject records for them
            self._held_wm = max(self._held_wm or MIN_WATERMARK, watermark)
            return
        super().on_watermark(watermark)

    def on_end(self) -> None:
        self._end_held = True   # released by finish_iteration()

    # -- feedback edge (called by the tail / the run loop) -----------------
    def enqueue_feedback(self, values, timestamps) -> None:
        if len(timestamps):
            self._feedback.append(
                (values, np.asarray(timestamps, dtype=np.int64))
            )

    def has_feedback(self) -> bool:
        return bool(self._feedback)

    def drain_round(self) -> int:
        """Re-inject the batches queued at round start; batches their
        processing enqueues belong to the next round. Returns records sent."""
        n_batches = len(self._feedback)
        sent = 0
        for _ in range(n_batches):
            values, ts = self._feedback.popleft()
            sent += len(ts)
            if self.downstream:
                self.downstream.on_batch(values, ts)
        return sent

    def finish_iteration(self) -> None:
        """Quiescence reached: release the held final watermark/end."""
        self._closed = True
        if self._held_wm is not None:
            StepRunner.on_watermark(self, self._held_wm)
            self._held_wm = None
        if self._end_held:
            StepRunner.on_end(self)

    def snapshot(self) -> dict:
        if not self._feedback:
            return {}
        return {
            "feedback": [(obj_array(list(v)), ts.copy())
                         for v, ts in self._feedback]
        }

    def restore(self, snap: dict) -> None:
        self._feedback = deque(
            (v, np.asarray(ts, dtype=np.int64))
            for v, ts in snap.get("feedback", ())
        )


class IterationTailRunner(StepRunner):
    """Iteration tail (StreamIterationTail.java analogue): every batch it
    receives is queued on its head's feedback edge. Watermarks and end
    signals stop here — they never cross a feedback edge."""

    def __init__(self, step: Step):
        t = step.terminal
        self.uid = t.uid
        self.head_transform_id = t.config["head"].id
        self.head: Optional[IterationHeadRunner] = None  # wired in build_runners

    def on_batch(self, values: np.ndarray, timestamps: np.ndarray) -> None:
        self.head.enqueue_feedback(values, timestamps)

    def on_watermark(self, watermark: int) -> None:
        pass

    def on_end(self) -> None:
        pass


def _make_runner(step: Step, config: Configuration) -> StepRunner:
    if step.terminal is None:
        return ChainRunner(step.chain)
    kind = step.terminal.kind
    if kind == "window_aggregate":
        return WindowStepRunner(step, config)
    if kind == "reduce":
        return KeyedReduceRunner(step, config)
    if kind == "process_keyed":
        return KeyedProcessRunner(step, config)
    if kind == "async_map":
        from flink_tpu.runtime.async_io import AsyncMapRunner

        return AsyncMapRunner(step.terminal, config)
    if kind == "cep":
        return CepRunner(step, config)
    if kind == "sink":
        return SinkRunner(step)
    if kind == "union":
        return UnionRunner(step)
    if kind in ("co_map", "co_flat_map"):
        return CoMapRunner(step)
    if kind == "co_process":
        return KeyedCoProcessRunner(step, config)
    if kind == "broadcast_process":
        return BroadcastProcessRunner(step, config)
    if kind in ("window_join", "co_group"):
        # device reroute: eligible event-time window equi-joins run on the
        # bucketed-ring pipeline; every refusal is a catalogued
        # JoinUnsupported reason, and the host runner stays the oracle
        if kind == "window_join":
            from flink_tpu.joins.spec import JoinUnsupported
            from flink_tpu.runtime.device_join_operator import DeviceJoinRunner

            try:
                return DeviceJoinRunner(step, config)
            except JoinUnsupported:
                pass
        return WindowJoinRunner(step, config)
    if kind == "group_agg":
        from flink_tpu.runtime.group_agg_operator import GroupAggRunner

        return GroupAggRunner(step, config)
    if kind == "regular_join":
        from flink_tpu.runtime.stream_join_operator import StreamingJoinRunner

        return StreamingJoinRunner(step, config)
    if kind == "iteration_head":
        return IterationHeadRunner(step)
    if kind == "iteration_tail":
        return IterationTailRunner(step)
    if kind == "stage_output":
        from flink_tpu.runtime.stages import StageOutputRunner

        return StageOutputRunner(step)
    raise NotImplementedError(kind)


def build_runners(graph: StepGraph, config: Configuration):
    """Build the runner DAG: one runner per step, fan-out edges wired by
    input ordinal. Returns (runners in topo order, source feed map
    {source_transformation_id: [(entry_runner, ordinal)]}).

    Whole-graph fusion (graph/fusion.py) happens here: eligible window
    steps get a DeviceChainRunner that absorbs the pure traceable chain
    step feeding them — the absorbed step gets no runner, and the fused
    runner consumes the absorbed step's input edges directly."""
    from flink_tpu.graph.fusion import plan_device_chains

    plans, absorbed = {}, set()
    if config.get(ExecutionOptions.CHAIN_FUSION) and \
            config.get(ExecutionOptions.FUSED_WINDOWS):
        plans, absorbed = plan_device_chains(graph)

    # sharing optimizer (graph/window_sharing.py): correlated window
    # siblings collapse into ONE shared-partial runner; non-leader members
    # get placeholder runners whose downstream edges the leader feeds, and
    # their input edges are NOT wired (the leader consumes the stream once)
    shared_of: Dict[int, tuple] = {}    # id(step) -> (plan, spec)
    edge_silent: set = set()            # member steps with unwired inputs
    if plans and config.get(ExecutionOptions.SHARED_PARTIALS):
        from flink_tpu.graph.window_sharing import plan_shared_windows

        for sw in plan_shared_windows(graph, plans):
            for spec, member in enumerate(sw.members):
                shared_of[id(member)] = (sw, spec)
                plans.pop(id(member), None)
                if spec > 0:
                    edge_silent.add(id(member))
            if sw.absorbed is not None:
                absorbed.add(id(sw.absorbed))

    runner_of: Dict[int, StepRunner] = {}
    runners: List[StepRunner] = []
    for step in graph.steps:
        if id(step) in absorbed:
            continue
        if id(step) in shared_of:
            sw, spec = shared_of[id(step)]
            if spec == 0:
                r = SharedWindowRunner(step, sw, config)
            else:
                r = SharedWindowSiblingRunner(step, spec)
        elif id(step) in plans:
            r = DeviceChainRunner(step, plans[id(step)], config)
        else:
            r = _make_runner(step, config)
        if len(step.inputs) > 1:
            r.num_inputs = len(step.inputs)
        runner_of[id(step)] = r
        runners.append(r)
    # leaders learn their members' runners (spec order) for emission fanout
    for step in graph.steps:
        ent = shared_of.get(id(step))
        if ent is not None and ent[1] == 0:
            sw, _spec = ent
            leader = runner_of[id(step)]
            leader.member_runners = [runner_of[id(m)] for m in sw.members]

    feeds: Dict[int, List] = {}
    for step in graph.steps:
        if id(step) in absorbed or id(step) in edge_silent:
            continue
        r = runner_of[id(step)]
        if id(step) in shared_of:
            step_inputs = shared_of[id(step)][0].inputs
        elif id(step) in plans:
            step_inputs = plans[id(step)].inputs
        else:
            step_inputs = step.inputs
        for edge in step_inputs:
            entity, ordinal = edge[0], edge[1]
            tag = edge[2] if len(edge) > 2 else None
            if isinstance(entity, Transformation):       # a source feeds this
                if tag is not None:
                    raise ValueError("sources have no side-output channels")
                feeds.setdefault(entity.id, []).append((r, ordinal))
            elif tag is not None:
                runner_of[id(entity)].side_channel(tag).add(r, ordinal)
            else:
                up = runner_of[id(entity)]
                if up.downstream is None:
                    up.downstream = _FanOut()
                up.downstream.add(r, ordinal)
    for r in runners:
        if r.downstream is None:
            r.downstream = _FanOut()
    # feedback edges: tail -> head, matched by the head transformation the
    # tail's closeWith recorded (the runtime-only cycle)
    heads = {
        step.terminal.id: runner_of[id(step)]
        for step in graph.steps
        if step.terminal is not None and step.terminal.kind == "iteration_head"
    }
    for r in runners:
        if isinstance(r, IterationTailRunner):
            if r.head_transform_id not in heads:
                raise ValueError(
                    "iteration tail closed with a head that is not part of "
                    "this pipeline"
                )
            r.head = heads[r.head_transform_id]
    return runners, feeds


def register_runner_metrics(runners: List[StepRunner], registry: MetricRegistry) -> None:
    for i, r in enumerate(runners):
        r.register_metrics(
            registry.group("job", "operator", getattr(r, "uid", f"chain-{i}"))
        )


class JobCancelledException(Exception):
    pass


class JobRuntime:
    """One running attempt of a job: the stepped loop plus the
    checkpoint-capture/restore surface (task-side checkpointing, §3.4
    analogue — here capture happens between steps so alignment is free)."""

    class _SourceDriver:
        """One source's read state: enumerator/reader/watermark generator
        plus the entry gates it feeds (SourceOperator analogue)."""

        def __init__(self, transform: Transformation, feeds: List):
            cfg = transform.config
            self.uid = transform.uid
            self.source = cfg["source"]
            strategy: Optional[WatermarkStrategy] = cfg.get("watermark_strategy")
            self.generator = strategy.create_generator() if strategy else None
            self.assigner = strategy.timestamp_assigner if strategy else None
            self.enumerator = self.source.create_enumerator()
            self.reader = self.source.create_reader()
            self.current_split = None
            self.done = False
            self.finished_signalled = False
            self.feeds = feeds              # [(runner, ordinal)]
            self.last_marker_wall = 0.0     # marker-interval throttle state

        def emit_batch(self, values, ts) -> None:
            for r, o in self.feeds:
                r.on_batch_n(o, values, ts)

        def emit_watermark(self, wm: int) -> None:
            for r, o in self.feeds:
                r.on_watermark_n(o, wm)

        def emit_marker(self, wall_ms: float) -> None:
            for r, _o in self.feeds:
                r.on_marker(wall_ms)

        def finish(self) -> None:
            """End of this source: flush its contribution to every valve and
            close its gates (idempotent)."""
            if self.finished_signalled:
                return
            self.finished_signalled = True
            self.emit_watermark(MAX_WATERMARK - 1)
            for r, o in self.feeds:
                r.on_end_n(o)

        def snapshot(self) -> dict:
            return {
                "pending_splits": self.enumerator.snapshot(),
                "current_split": self.current_split,
                "reader_position": self.reader.snapshot_position(),
                "done": self.done,
                "generator": self.generator.snapshot() if self.generator else None,
            }

        def restore(self, snap: dict) -> None:
            self.enumerator.restore(snap["pending_splits"])
            self.current_split = snap["current_split"]
            self.done = snap["done"]
            if self.current_split is not None:
                self.reader.add_split(self.current_split)
                self.reader.restore_position(snap["reader_position"])
            if self.generator is not None and snap.get("generator") is not None:
                self.generator.restore(snap["generator"])

    def __init__(self, graph: StepGraph, config: Configuration,
                 registry: Optional[MetricRegistry] = None,
                 traces=None):
        from flink_tpu.utils.compile_cache import configure_compile_cache

        # every way a job starts in a process (local executor, MiniCluster,
        # a TaskExecutor's graph task) builds its device programs below
        configure_compile_cache()
        self.graph = graph
        self.config = config
        self.traces = traces    # optional TraceRegistry for device spans
        self.runners, feeds = build_runners(graph, config)
        # the job thread's stages outside any device operator (source.poll,
        # the host chains, sink.write) report to one job-level clock
        self.stage_clock = (
            StageClock()
            if config.get(ObservabilityOptions.DEVICE_TIMING_ENABLED)
            else None)
        for r in self.runners:
            if isinstance(r, (ChainRunner, SinkRunner)):
                r.stage_clock = self.stage_clock
        self.sources = [
            JobRuntime._SourceDriver(t, feeds.get(t.id, []))
            for t in graph.sources
        ]
        # OperatorCoordinator SPI (D15): operator functions declaring
        # create_coordinator() get a job-scope coordinator + event bus.
        # Candidates: terminal runners' fn, chain transforms' fns, and both
        # sides of co-transforms; keys are deterministic across rebuilds so
        # coordinator state survives restore.
        from flink_tpu.runtime.coordination import wire as _wire_coordinator

        self.coordinators = {}
        for idx, r in enumerate(self.runners):
            candidates = []
            if getattr(r, "fn", None) is not None:
                candidates.append((getattr(r, "uid", f"coordinator@{idx}"),
                                   r.fn))
            for j, f in enumerate(getattr(r, "fns", ()) or ()):
                candidates.append(
                    (f"{getattr(r, 'uid', f'coordinator@{idx}')}#{j}", f))
            for t in getattr(r, "transforms", ()) or ():
                f = t.config.get("fn")
                if f is not None:
                    candidates.append((t.uid, f))
            for uid, f in candidates:
                coord = _wire_coordinator(f)
                if coord is not None:
                    self.coordinators[uid] = coord
        self.iteration_heads = [
            r for r in self.runners if isinstance(r, IterationHeadRunner)
        ]
        self.records_in = 0
        # observability: job-scope throughput, busy/idle/backpressure
        # ratios (TaskIOMetricGroup analogue), step latency, device time
        self.registry = registry or MetricRegistry()
        register_runner_metrics(self.runners, self.registry)
        job_group = self.registry.group("job")
        self.records_meter = job_group.meter("numRecordsInPerSecond")
        self.step_latency = job_group.histogram("stepLatencyMs")
        self._last_pt_tick = 0.0
        self.io = TaskIOMetrics()
        for r in self.runners:
            bp = getattr(r, "backpressure_seconds", None)
            if bp is not None:   # stage-output senders blocked on credits
                self.io.add_backpressure_source(bp)
        self.io.register(job_group)
        job_group.gauge("numRecordsIn", lambda: self.records_in,
                        fold="sum", kind="counter")
        # mesh-as-slot-resource visibility: 1 on the single-chip path, the
        # actual shard count when parallel.mesh.enabled promoted the job —
        # dashboards and the autoscaler read THIS, not the requested config
        # (fold MAX: each shard reports ITS mesh size — summing would
        # misreport a plain 2-shard job as a 2-device mesh)
        job_group.gauge("meshDevices", self.mesh_devices, fold="max")
        # SQL front-door visibility: present only for SQL-originated jobs
        # (planner-lowered window terminals carry sql_origin). 1 when every
        # SQL window step selected the fused DeviceChainRunner — the
        # reroute gate dashboards and the sql_path bench read; 0 means the
        # planner fell back (or translation rerouted) to interpreted-style
        # execution for at least one of them.
        sql_runners = [r for r in self.runners
                       if getattr(r, "sql_origin", False)]
        if sql_runners:
            from flink_tpu.runtime.device_join_operator import DeviceJoinRunner

            # fold MIN: the job is "fully fused" only when EVERY shard is
            job_group.gauge(
                "sqlFusedSelected",
                lambda rs=tuple(sql_runners): int(all(
                    isinstance(r, (DeviceChainRunner, DeviceJoinRunner))
                    for r in rs)),
                fold="min")
        job_group.gauge("deviceTimeMsTotal", lambda: sum(
            r.stage_clock.total_s * 1000.0
            for r in self.runners
            if getattr(r, "stage_clock", None) is not None),
            fold="sum", kind="counter")
        # device plane: job-level compile/roofline/skew gauges — these are
        # the keys the TM heartbeat ships and the autoscaler's signal
        # extractor reads (job.device.*, job.keySkew); compile events also
        # ride the TraceRegistry as 'device'-scope spans when one is bound
        trackers = [r.device_stats for r in self.runners
                    if getattr(r, "device_stats", None) is not None]
        collectors = [r.key_stats for r in self.runners
                      if getattr(r, "key_stats", None) is not None]
        if trackers:
            dg = job_group.add_group("device")
            dg.gauge("numCompiles",
                     lambda: sum(t.num_compiles for t in trackers),
                     fold="sum", kind="counter")
            dg.gauge("numRecompiles",
                     lambda: sum(t.num_recompiles for t in trackers),
                     fold="sum", kind="counter")
            dg.gauge("compileTimeMsTotal", lambda: round(
                sum(t.compile_ms_total for t in trackers), 3),
                fold="sum", kind="counter")
            dg.gauge("recompileStorm",
                     lambda: max(t.recompile_storm() for t in trackers),
                     fold="max")
            roofed = [r for r in self.runners
                      if getattr(r, "_roofline_peaks", None) is not None]
            if roofed:
                dg.gauge("hbmUtilizationPct", lambda: max(
                    r.device_roofline()["hbmUtilizationPct"]
                    for r in roofed), fold="mean")
                dg.gauge("flopsUtilizationPct", lambda: max(
                    r.device_roofline()["flopsUtilizationPct"]
                    for r in roofed), fold="mean")
        if collectors:
            def _job_skew(cs=collectors):
                skews = [s for s in (c.skew() for c in cs) if s is not None]
                return max(skews) if skews else None

            job_group.gauge("keySkew", _job_skew, fold="max")
        if traces is not None and trackers:
            from flink_tpu.metrics.device_stats import compile_event_span

            for t in trackers:
                if t.on_event is None:
                    t.on_event = (lambda ev, _tr=traces:
                                  _tr.report(compile_event_span(ev)))
        # emission-latency plane (observability.emission-latency.*): the
        # job-level p99 gauge is the bench/autoscaler surface (folds MAX
        # across shards), and outlier EmissionStall spans ride the same
        # trace plane as checkpoint/recovery spans — the MiniCluster's
        # TraceRegistry here; the TM heartbeat span buffer wires its own
        # sink in cluster.py before any fire can happen
        em_trackers = tuple(
            r.emission_tracker for r in self.runners
            if getattr(r, "emission_tracker", None) is not None)
        if em_trackers:
            job_group.gauge(
                "p99EmissionLatencyMs",
                lambda ts=em_trackers: _merge_emission_snapshots(
                    [t.snapshot() for t in ts]).get("p99", 0.0),
                fold="max")
            if traces is not None:
                from flink_tpu.metrics.traces import Span

                for t in em_trackers:
                    if t.span_sink is None:
                        t.span_sink = (
                            lambda scope, name, s, e, a, _tr=traces:
                            _tr.report(Span(scope, name, s, e, a)))
        # profiler capture surface (observability.profiler.*): the REST
        # /jobs/:id/device payload reports where captures landed — the
        # per-attempt jax.profiler trace used to be write-only
        self.profiler_captures = 0
        self.last_profiler_capture_dir: Optional[str] = None
        # the newest capture read back: ms per execution of each window
        # program under each of its phases (metrics/device_phases.py)
        self.profiler_phase_ms: Optional[Dict[str, Any]] = None
        self._marker_interval = config.get(ObservabilityOptions.MARKER_INTERVAL_MS)
        self._sampling_interval = config.get(ObservabilityOptions.SAMPLING_INTERVAL_MS)

    # -- checkpoint surface ----------------------------------------------
    def capture(self) -> dict:
        runner_snaps = {}
        for r in self.runners:
            snap = r.snapshot()
            if snap:
                runner_snaps[getattr(r, "uid", f"runner-{id(r)}")] = snap
        return {
            "sources": {d.uid: d.snapshot() for d in self.sources},
            "runners": runner_snaps,
            "coordinators": {
                uid: c.checkpoint() for uid, c in self.coordinators.items()
            },
            "records_in": self.records_in,
        }

    def restore(self, snap: dict) -> None:
        if "sources" in snap:
            for d in self.sources:
                if d.uid in snap["sources"]:
                    d.restore(snap["sources"][d.uid])
        else:
            # single-source snapshot from the pre-DAG layout
            legacy = dict(snap["source"])
            legacy["generator"] = snap.get("generator")
            self.sources[0].restore(legacy)
        for r in self.runners:
            uid = getattr(r, "uid", None)
            if uid is not None and uid in snap["runners"]:
                r.restore(snap["runners"][uid])
        for uid, c in self.coordinators.items():
            if uid in snap.get("coordinators", {}):
                c.restore(snap["coordinators"][uid])
        self.records_in = snap["records_in"]

    def commit_sinks(self, checkpoint_id: int) -> None:
        for r in self.runners:
            if isinstance(r, SinkRunner):
                r.commit_epoch(str(checkpoint_id))

    def mesh_devices(self) -> int:
        """Devices this attempt's keyed state is sharded over (worst
        operator; 1 = single-chip)."""
        return max(
            (int(fn()) for fn in (
                getattr(getattr(r, "op", None), "mesh_devices", None)
                for r in self.runners) if fn is not None),
            default=1,
        )

    # -- skew-aware key-group routing (parallel.mesh.skew-rebalance) ----
    def _routed_ops(self):
        for r in self.runners:
            op = getattr(r, "op", None)
            if op is not None and callable(
                    getattr(op, "routing_version", None)) \
                    and op.routing_version() is not None:
                yield op

    def mesh_routing_version(self) -> Optional[int]:
        """Highest routing-table version across mesh operators (None when
        no operator carries a table)."""
        versions = [op.routing_version() for op in self._routed_ops()]
        return max(versions) if versions else None

    def mesh_group_loads(self):
        """(group_loads [G], current assignment [G], mesh size) of the
        first routed operator — the skew rebalancer's decision input;
        None when no operator carries a routing table or no data has
        landed on device yet."""
        for op in self._routed_ops():
            loads = op.mesh_group_loads()
            if loads is not None and loads.sum() > 0:
                return loads, op.pipe.routing.assign, op.mesh_devices()
        return None

    def set_mesh_routing(self, assign) -> None:
        """Apply a key-group assignment to every routed operator (the
        rebuilt attempt of a rebalance, AFTER restore — restore may adopt
        a grown snapshot K and rebuild the table for the new capacity).
        An assignment sized for a DIFFERENT group count is skipped, not
        an error: the geometry changed between decision and application
        (capacity growth mid-flight), and the rebalancer simply
        re-decides from live skew under the new table."""
        assign = np.asarray(assign)
        for op in self._routed_ops():
            if assign.shape[0] != op.pipe.routing.G:
                continue
            op.set_routing_assignment(assign)

    def operator_state_bytes(self) -> Dict[str, int]:
        """Per-operator state footprint from the operators' own
        state_bytes() (the same source as the stateBytes gauges) — the
        per-operator breakdown attached to completed checkpoint records."""
        out: Dict[str, int] = {}
        for idx, r in enumerate(self.runners):
            fn = getattr(getattr(r, "op", None), "state_bytes", None)
            if fn is None:
                continue
            try:
                out[getattr(r, "uid", f"runner-{idx}")] = int(fn())
            except Exception:   # a torn-down operator must not fail a
                continue        # checkpoint's bookkeeping
        return out

    def device_snapshot(self) -> Dict[str, Any]:
        """The device-plane payload (/jobs/:id/device): merged compile
        block, per-operator cost/roofline/phase/key telemetry, and the
        profiler capture surface. Plain data, JSON-safe."""
        from flink_tpu.metrics.device_stats import (
            empty_device_payload,
            merge_compile_payloads,
        )

        payload = empty_device_payload()
        ops: Dict[str, Any] = {}
        compile_payloads = []
        for idx, r in enumerate(self.runners):
            tracker = getattr(r, "device_stats", None)
            ks = getattr(r, "key_stats", None)
            timer = getattr(r, "stage_clock", None)
            tier_fn = getattr(getattr(r, "op", None), "tier_payload", None)
            has_tier = callable(tier_fn) and tier_fn() is not None
            routing_fn = getattr(getattr(r, "op", None), "routing_payload",
                                 None)
            has_routing = callable(routing_fn) and routing_fn() is not None
            if tracker is None and ks is None and not has_tier \
                    and not has_routing:
                continue
            entry: Dict[str, Any] = {}
            if timer is not None:
                entry["deviceTimeMsTotal"] = round(timer.total_s * 1000.0, 3)
                entry["deviceDispatches"] = timer.dispatches
                entry["stages"] = timer.stage_table()
                entry["link"] = timer.link()
            if tracker is not None:
                cp = tracker.payload()
                compile_payloads.append(cp)
                entry["compile"] = cp
                entry.update(r.device_roofline())
            phases = getattr(getattr(r, "op", None), "phase_totals", None)
            if callable(phases):
                entry["phases"] = phases()
            for counters in ("ring_counters", "prologue_counters"):
                read = getattr(getattr(r, "op", None), counters, None)
                if callable(read):
                    entry.update(read())
            if ks is not None:
                entry["keys"] = ks.payload()
            tier_payload = getattr(getattr(r, "op", None), "tier_payload",
                                   None)
            if callable(tier_payload):
                tp = tier_payload()
                if tp is not None:
                    entry["tier"] = tp
            # skew-aware key-group routing (parallel.mesh.skew-rebalance):
            # table version + assignment, next to the per-device skew it
            # exists to fix
            routing_payload = getattr(getattr(r, "op", None),
                                      "routing_payload", None)
            if callable(routing_payload):
                rp = routing_payload()
                if rp is not None:
                    entry["routing"] = rp
            ops[getattr(r, "uid", f"runner-{idx}")] = entry
        payload["operators"] = ops
        # the whole job thread: the job-level clock and every operator's
        clocks = {id(c): c for c in
                  [self.stage_clock] + [getattr(r, "stage_clock", None)
                                        for r in self.runners]
                  if c is not None}
        payload["stages"] = merge_stage_tables(clocks.values())
        payload["compile"] = merge_compile_payloads(
            compile_payloads,
            history_size=self.config.get(
                ObservabilityOptions.DEVICE_RECOMPILE_HISTORY_SIZE))
        payload["enabled"] = bool(ops)
        payload["profiler"] = {
            "enabled": self.config.get(ObservabilityOptions.PROFILER_ENABLED),
            "captures": self.profiler_captures,
            "last_capture_dir": self.last_profiler_capture_dir,
        }
        if self.profiler_phase_ms is not None:
            # the time counterpart of the operators' `phases` step counts
            payload["profiler"]["phaseMs"] = self.profiler_phase_ms
        return payload

    # -- the loop ---------------------------------------------------------
    def run(
        self,
        coordinator=None,
        cancel_check: Optional[Callable[[], bool]] = None,
        savepoint_request: Optional[Callable[[], Optional[str]]] = None,
        rescale_request: Optional[Callable[[], Optional[int]]] = None,
        rebalance_request: Optional[Callable[[], Optional[Any]]] = None,
    ) -> None:
        batch_size = self.config.get(ExecutionOptions.BATCH_SIZE)
        if coordinator is not None:
            coordinator.register_on_complete(self.commit_sinks)
        profiling = False
        profile_dir = self.config.get(ObservabilityOptions.PROFILER_DIR)
        if self.config.get(ObservabilityOptions.PROFILER_ENABLED):
            try:
                import jax.profiler

                jax.profiler.start_trace(profile_dir)
                profiling = True
            except Exception as e:  # noqa: BLE001 — observability never
                import warnings      # fails the job

                warnings.warn(f"jax.profiler trace capture unavailable: {e!r}",
                              RuntimeWarning)
        try:
            self._run_loop(batch_size, coordinator, cancel_check,
                           savepoint_request, rescale_request,
                           rebalance_request)
        finally:
            if profiling:
                try:
                    import jax.profiler

                    jax.profiler.stop_trace()
                    # the capture is no longer write-only: count it and
                    # remember where it landed, for /jobs/:id/device
                    self.profiler_captures += 1
                    self.last_profiler_capture_dir = profile_dir
                except Exception as e:   # observability never fails the job
                    logging.getLogger(__name__).debug(
                        "jax.profiler stop_trace failed: %r", e)
                else:
                    self._read_capture(profile_dir)

    def _read_capture(self, profile_dir: str) -> None:
        """The capture this attempt just closed, read back once: the device
        programs' time under each of their phases, for `/jobs/:id/device`."""
        from flink_tpu.metrics import device_phases

        try:
            self.profiler_phase_ms = device_phases.per_execution(
                device_phases.phase_table(profile_dir))
        except _chaos.InjectedCrash:
            raise
        except Exception as e:   # noqa: BLE001 — observability never fails
            logging.getLogger(__name__).debug(   # the job
                "the profiler's capture could not be read: %r", e)

    def _run_loop(
        self,
        batch_size: int,
        coordinator,
        cancel_check: Optional[Callable[[], bool]],
        savepoint_request: Optional[Callable[[], Optional[str]]],
        rescale_request: Optional[Callable[[], Optional[int]]] = None,
        rebalance_request: Optional[Callable[[], Optional[Any]]] = None,
    ) -> None:
        for d in self.sources:
            if d.current_split is None and not d.done:
                d.current_split = d.enumerator.next_split()
                if d.current_split is not None:
                    d.reader.add_split(d.current_split)
                else:
                    d.done = True
            if d.done:
                # zero-split or restored-as-done sources must still flush
                # their watermark/end contribution, or every multi-input
                # valve downstream stalls for the whole run
                d.finish()

        # round-robin over sources, one batch per turn; checkpoints align at
        # batch boundaries regardless of which source produced the batch
        while any(not d.done for d in self.sources):
            for d in self.sources:
                if d.done:
                    continue
                loop_t0 = time.perf_counter()
                if cancel_check is not None and cancel_check():
                    raise JobCancelledException()
                with stage(self.stage_clock, "source.poll"):
                    batch = d.reader.poll_batch(batch_size)
                if batch is None:
                    d.current_split = d.enumerator.next_split()
                    busy_dt = 0.0
                    if d.current_split is None:
                        d.done = True
                        # a finished source must not hold back the combined
                        # watermark of still-running inputs
                        busy_t0 = time.perf_counter()
                        d.finish()
                        busy_dt = time.perf_counter() - busy_t0
                    else:
                        d.reader.add_split(d.current_split)
                    self.io.record_step(busy_dt, time.perf_counter() - loop_t0)
                    continue
                values = batch.values
                ts = batch.timestamps
                if d.assigner is not None:
                    ts = np.asarray(
                        [d.assigner(v, int(t)) for v, t in zip(values, ts)],
                        dtype=np.int64,
                    )
                self.records_in += len(batch)
                self.records_meter.mark(len(batch))
                busy_t0 = time.perf_counter()
                # latency marker stamped BEFORE the synchronous push so the
                # sink's (now - stamp) measures this batch's real transit.
                # A stage-input reader forwards the UPSTREAM stage's marker
                # (take_marker) so transit accumulates across the dataplane
                # instead of resetting at every stage boundary; fresh stamps
                # honor observability.latency-markers.interval-ms.
                t_mark = None
                take = getattr(d.reader, "take_marker", None)
                if take is not None:
                    t_mark = take()
                elif self._marker_interval >= 0:
                    now_wall = time.time() * 1000.0
                    if now_wall - d.last_marker_wall >= self._marker_interval:
                        d.last_marker_wall = now_wall
                        t_mark = now_wall
                d.emit_batch(values, ts)
                if t_mark is not None:
                    d.emit_marker(t_mark)
                if d.generator is not None:
                    with stage(self.stage_clock, "source.watermark"):
                        wm = (
                            d.generator.on_batch_np(ts)
                            if hasattr(d.generator, "on_batch_np")
                            else None
                        )
                        if wm is None:
                            for v, t in zip(values, ts):
                                d.generator.on_event(v, int(t))
                            wm = d.generator.on_periodic_emit()
                    if wm is not None and wm > MIN_WATERMARK:
                        d.emit_watermark(wm)
                if self.iteration_heads:
                    # run feedback to quiescence at the step boundary so
                    # checkpoints capture (almost) no in-flight feedback
                    self._drain_iterations()
                step_dt = time.perf_counter() - busy_t0
                self.step_latency.update(step_dt * 1000)
                # step boundary: checkpoints/savepoints align here for free
                if coordinator is not None:
                    coordinator.maybe_trigger(self.capture)
                if savepoint_request is not None:
                    path = savepoint_request()
                    if path is not None:
                        self._write_savepoint(path)
                if rescale_request is not None:
                    target = rescale_request()
                    if target is not None and target != self.mesh_devices():
                        # mesh rescale: hand a step-aligned capture to the
                        # job master, which rebuilds this runtime over the
                        # new device count and restores — checkpoint rewind
                        # across mesh sizes, exactly-once by construction
                        # (the capture IS the checkpoint path's capture)
                        raise MeshRescaleRequested(target, self.capture())
                if rebalance_request is not None:
                    assign = rebalance_request()
                    if assign is not None:
                        # skew rebalance: same capture/restore machinery as
                        # a rescale, same mesh size, new key-group routing
                        # — the rebuilt attempt applies the table, then
                        # restores the canonical capture (placement never
                        # changes a result)
                        raise MeshRescaleRequested(
                            self.mesh_devices(), self.capture(),
                            routing=assign)
                now_ms = time.time() * 1000.0
                if now_ms - self._last_pt_tick >= 50.0:
                    # ProcessingTimeService tick: drive wall-clock timers
                    # and sample the busy/idle/backpressure window
                    self._last_pt_tick = now_ms
                    for r in self.runners:
                        r.on_processing_time(int(now_ms))
                    self.io.maybe_sample(self._sampling_interval)
                self.io.record_step(step_dt, time.perf_counter() - loop_t0)

        # end of input: every source's final watermark + end signal has been
        # (or is now) delivered, firing all remaining windows downstream
        for d in self.sources:
            d.finish()
        if self.iteration_heads:
            # iteration heads held the final watermark/end; drain remaining
            # feedback to quiescence, then release them
            self._drain_iterations()
            for h in self.iteration_heads:
                h.finish_iteration()

    def _drain_iterations(self) -> None:
        """Round-robin feedback rounds across iteration heads until every
        feedback queue is empty (termination = the loop body stopped feeding
        records back). Each head's own max_rounds bounds the rounds in which
        IT still had feedback, so one non-converging loop trips its own
        (possibly tight) bound regardless of other loops in the job."""
        rounds = {id(h): 0 for h in self.iteration_heads}
        while any(h.has_feedback() for h in self.iteration_heads):
            for h in self.iteration_heads:
                if not h.has_feedback():
                    continue
                rounds[id(h)] += 1
                if rounds[id(h)] > h.max_rounds:
                    raise RuntimeError(
                        f"iteration '{h.uid}' did not reach quiescence "
                        f"within max_rounds={h.max_rounds}; the loop body "
                        "must eventually stop emitting feedback records "
                        "(or raise iterate(max_rounds=...))"
                    )
                h.drain_round()

    def _write_savepoint(self, path: str) -> None:
        from flink_tpu.checkpoint.storage import FsCheckpointStorage

        data = self.capture()
        data["savepoint"] = True
        FsCheckpointStorage(path).save(0, data)


class LocalPipelineExecutor:
    """Single-host execution (LocalExecutor/MiniCluster analogue,
    flink-clients LocalExecutor.java:49); one synchronous attempt, no
    recovery — fault tolerance lives in runtime/minicluster.py."""

    def __init__(self, config: Optional[Configuration] = None):
        self.config = config or Configuration()

    def execute(self, graph: StepGraph, job_name: str = "job") -> JobExecutionResult:
        runtime = JobRuntime(graph, self.config)
        t0 = time.perf_counter()
        runtime.run()
        runtime_ms = (time.perf_counter() - t0) * 1000
        return JobExecutionResult(
            job_name=job_name,
            runtime_ms=runtime_ms,
            records_in=runtime.records_in,
            # the job's own account of where it ran: the mesh size it got
            # (not the one asked for) and, per operator, which device
            # programs were compiled and dispatched (/jobs/:id/device shape)
            metrics={"records_in": runtime.records_in,
                     "mesh_devices": runtime.mesh_devices(),
                     "device": runtime.device_snapshot(),
                     **sql_plans(graph)},
        )


def sql_plans(graph: StepGraph) -> Dict[str, List[Dict[str, Any]]]:
    """`{"sql": [...]}`: the plan report of each SQL statement in the job
    (`SqlPlanReport.summary()`, stamped on the statement's result by the
    table layer: path, fallback reason and detail, plan), or {} where the
    job ran no SQL."""
    plans = [t.config["sql_plan"]
             for s in graph.steps for t in (*s.chain, s.terminal)
             if t is not None and "sql_plan" in t.config]
    return {"sql": plans} if plans else {}
