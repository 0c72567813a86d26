"""DataStream-style fluent API.

Capability parity with the reference's DataStream V1 surface
(flink-runtime .../streaming/api/datastream/DataStream.java:111,
KeyedStream.java:94 window() :705, WindowedStream.java reduce :181 /
aggregate :310, StreamExecutionEnvironment.java:1823 execute()): fluent
map/flatMap/filter/keyBy/window/aggregate/sink chains recording a
Transformation DAG, executed by the stepped local executor (and, sharded,
by the parallel executor).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Union

from flink_tpu.api.functions import (
    AggregateFunction,
    as_key_selector,
    null_key,
)
from flink_tpu.api.windowing.assigners import WindowAssigner
from flink_tpu.api.windowing.triggers import Trigger
from flink_tpu.api.windowing.evictors import Evictor
from flink_tpu.config import Configuration, PipelineOptions
from flink_tpu.core.watermarks import WatermarkStrategy
from flink_tpu.graph.transformation import Transformation, plan
from flink_tpu.connectors.source import CollectionSource, Source
from flink_tpu.connectors.sink import CollectSink, Sink
from flink_tpu.ops.aggregators import max_by_agg, min_by_agg


class StreamExecutionEnvironment:
    """Entry point (StreamExecutionEnvironment.java). Holds config and the
    set of sink transformations; execute() plans and runs."""

    def __init__(self, config: Optional[Configuration] = None):
        self.config = config or Configuration()
        self._sinks: List[Transformation] = []
        # non-sink plan roots (iteration tails): reachable only through
        # close_with, so they must be planned explicitly
        self._roots: List[Transformation] = []

    @staticmethod
    def get_execution_environment(config: Optional[Configuration] = None) -> "StreamExecutionEnvironment":
        return StreamExecutionEnvironment(config)

    # -- config -----------------------------------------------------------
    def set_parallelism(self, parallelism: int) -> "StreamExecutionEnvironment":
        self.config.set(PipelineOptions.PARALLELISM, parallelism)
        return self

    def set_max_parallelism(self, max_parallelism: int) -> "StreamExecutionEnvironment":
        self.config.set(PipelineOptions.MAX_PARALLELISM, max_parallelism)
        return self

    @property
    def parallelism(self) -> int:
        return self.config.get(PipelineOptions.PARALLELISM)

    @property
    def max_parallelism(self) -> int:
        return self.config.get(PipelineOptions.MAX_PARALLELISM)

    # -- sources ----------------------------------------------------------
    def from_source(
        self,
        source: Source,
        watermark_strategy: Optional[WatermarkStrategy] = None,
        name: str = "source",
    ) -> "DataStream":
        t = Transformation(
            "source", name, [], {"source": source, "watermark_strategy": watermark_strategy}
        )
        return DataStream(self, t)

    def from_collection(
        self,
        items: Sequence,
        timestamp_fn: Optional[Callable] = None,
        watermark_strategy: Optional[WatermarkStrategy] = None,
    ) -> "DataStream":
        return self.from_source(
            CollectionSource(items, timestamp_fn), watermark_strategy, name="collection"
        )

    # -- execution --------------------------------------------------------
    def execute(self, job_name: Optional[str] = None):
        from flink_tpu.runtime.executor import LocalPipelineExecutor

        if not self._sinks:
            raise RuntimeError("No sinks defined; nothing to execute")
        graph = plan(self._sinks + self._roots)
        executor = LocalPipelineExecutor(self.config)
        return executor.execute(graph, job_name or self.config.get(PipelineOptions.NAME))

    def execute_async(self, job_name: Optional[str] = None):
        """Submit to the in-process mini-cluster (Dispatcher analogue)."""
        from flink_tpu.runtime.minicluster import MiniCluster

        if len(self._sinks) != 1:
            raise RuntimeError("exactly one sink required")
        graph = plan([self._sinks[0]] + self._roots)
        return MiniCluster.get_shared().submit(graph, self.config, job_name)


class DataStream:
    def __init__(self, env: StreamExecutionEnvironment, transform: Transformation):
        self.env = env
        self.transform = transform

    def _derive(self, kind: str, name: str, config: dict) -> "DataStream":
        return DataStream(self.env, Transformation(kind, name, [self.transform], config))

    # -- record-local ops --------------------------------------------------
    def map(self, fn: Callable, name: str = "map", vectorized: bool = False,
            traceable: bool = False) -> "DataStream":
        """Per-record transform. With vectorized=True, fn receives the whole
        value column (numpy array) and must return an equal-length column —
        the chain then executes as array ops instead of a Python loop (the
        TPU-native form of operator chaining: the reference fuses chained
        operators into direct calls, StreamingJobGraphGenerator.java:1730;
        here a chain fuses into columnar kernels).

        traceable=True (implies vectorized) additionally declares fn to be a
        pure jax-traceable column function (array ufunc ops only, no data-
        dependent shapes or host calls): the chain then qualifies for
        whole-graph fusion, compiling together with a downstream keyed
        window aggregate into ONE jitted device program (docs/fusion.md)."""
        fn = fn.map if hasattr(fn, "map") else fn
        return self._derive("map", name, {
            "fn": fn, "vectorized": vectorized or traceable,
            "traceable": traceable,
        })

    def map_batch(self, fn: Callable, name: str = "map_batch") -> "DataStream":
        """1:1 transform over the whole step batch at once (list -> list of
        equal length) — the amortization point for device inference."""
        t = Transformation("map_batch", name, [self.transform], {"fn": fn})
        return DataStream(self.env, t)

    def map_with_timestamp(self, fn: Callable, name: str = "map_ts",
                           vectorized: bool = False,
                           traceable: bool = False) -> "DataStream":
        """map over (value, event_timestamp_ms) pairs. Vectorized form:
        fn(values_column, timestamps_column) -> values_column. traceable=True
        declares a jax-traceable column fn eligible for whole-graph fusion
        (see map())."""
        return self._derive("map_ts", name, {
            "fn": fn, "vectorized": vectorized or traceable,
            "traceable": traceable,
        })

    def flat_map(self, fn: Callable, name: str = "flat_map",
                 vectorized: bool = False) -> "DataStream":
        """1:N transform. Vectorized form: fn(values_column) returns
        (out_values, source_index) where source_index[i] is the input row
        out_values[i] came from (used to propagate timestamps)."""
        fn = fn.flat_map if hasattr(fn, "flat_map") else fn
        return self._derive("flat_map", name, {"fn": fn, "vectorized": vectorized})

    def filter(self, fn: Callable, name: str = "filter",
               vectorized: bool = False, traceable: bool = False) -> "DataStream":
        """Predicate filter. Vectorized form: fn(values_column) returns a
        boolean mask over the column. traceable=True declares a
        jax-traceable mask fn eligible for whole-graph fusion (see map())."""
        fn = fn.filter if hasattr(fn, "filter") else fn
        return self._derive("filter", name, {
            "fn": fn, "vectorized": vectorized or traceable,
            "traceable": traceable,
        })

    def async_map(
        self,
        fn: Callable,
        *,
        capacity: int = 100,
        timeout_ms: Optional[float] = None,
        ordered: bool = True,
        retry=None,
        name: str = "async_map",
    ) -> "DataStream":
        """Async I/O with bounded concurrency (AsyncDataStream.orderedWait /
        unorderedWait semantics; AsyncWaitOperator analogue)."""
        from flink_tpu.runtime.async_io import NO_RETRY

        return self._derive(
            "async_map",
            name,
            {
                "fn": fn,
                "capacity": capacity,
                "timeout_ms": timeout_ms,
                "ordered": ordered,
                "retry": retry or NO_RETRY,
            },
        )

    def get_side_output(self, tag) -> "DataStream":
        """The stream of this operator's side output for `tag`
        (SingleOutputStreamOperator.getSideOutput / OutputTag). Works on the
        result of process()-style operators that call ctx.output(tag, v) and
        on windowed streams with side_output_late_data()."""
        from flink_tpu.api.functions import OutputTag

        if not isinstance(tag, OutputTag):
            tag = OutputTag(str(tag))
        t = Transformation("side_output", f"side:{tag.tag_id}",
                           [self.transform], {"tag": tag})
        return DataStream(self.env, t)

    # -- multi-input topologies (DataStream.java:111) ----------------------
    def union(self, *others: "DataStream") -> "DataStream":
        """Merge streams of the same type; watermarks min-combine across the
        inputs (DataStream.union / UnionTransformation)."""
        if not others:
            return self
        t = Transformation(
            "union", "union",
            [self.transform] + [o.transform for o in others], {},
        )
        return DataStream(self.env, t)

    def connect(self, other: "DataStream") -> "ConnectedStreams":
        """Pair two streams for co-processing with shared state
        (DataStream.connect / ConnectedStreams)."""
        return ConnectedStreams(self.env, self, other)

    def join(self, other: "DataStream") -> "JoinBuilder":
        """Keyed windowed join (JoinedStreams.java:101):
        a.join(b).where(ks_a).equal_to(ks_b).window(assigner).apply(fn)."""
        return JoinBuilder(self.env, self, other, cogroup=False)

    def co_group(self, other: "DataStream") -> "JoinBuilder":
        """Keyed windowed coGroup (CoGroupedStreams.java): apply(fn) receives
        (left_elements, right_elements) once per key x window."""
        return JoinBuilder(self.env, self, other, cogroup=True)

    # -- partitioning ------------------------------------------------------
    def _partition_hint(self, kind: str) -> "DataStream":
        """Explicit repartitioning (DataStream.rebalance/broadcast/...).

        Locally these are pass-through views (one parallel instance); the
        distributed scheduler reads the hint to choose the exchange pattern,
        and key_by remains the only data-moving partitioner on the stepped
        executor (records route by key group)."""
        return DataStream(
            self.env, Transformation(kind, kind, [self.transform], {})
        )

    def rebalance(self) -> "DataStream":
        """Round-robin redistribution (RebalancePartitioner)."""
        return self._partition_hint("rebalance")

    def rescale(self) -> "DataStream":
        """Local-group round-robin (RescalePartitioner)."""
        return self._partition_hint("rescale")

    def shuffle(self) -> "DataStream":
        """Uniform-random redistribution (ShufflePartitioner)."""
        return self._partition_hint("shuffle")

    def broadcast(self) -> "DataStream":
        """Every downstream instance sees every record (BroadcastPartitioner)."""
        return self._partition_hint("broadcast")

    def forward(self) -> "DataStream":
        """Pin to the local downstream instance (ForwardPartitioner)."""
        return self._partition_hint("forward")

    def global_(self) -> "DataStream":
        """Route everything to instance 0 (GlobalPartitioner)."""
        return self._partition_hint("global")

    def slot_sharing_group(self, name: str) -> "DataStream":
        """Put the operator that produced this stream into slot-sharing
        group `name` (DataStream.slotSharingGroup). Downstream operators
        inherit the group unless they declare their own. On the distributed
        cluster, each named group deploys as its own pipeline stage in its
        own slot, connected by credit-controlled exchanges — isolating
        heavyweight operators AND running the stages concurrently; locally
        (one process) groups are a no-op, like the reference's local
        environments."""
        self.transform.config["slot_sharing_group"] = name
        return self

    def iterate(self, max_rounds: int = 10000) -> "IterativeStream":
        """Open an iteration (DataStream.iterate / IterativeStream.java):
        the returned stream carries this stream's records plus every record
        later fed back via close_with(). Watermarks do not cross the
        feedback edge (reference semantics); with bounded inputs the job
        finishes when the loop body stops emitting feedback records, and
        `max_rounds` bounds non-converging loop bodies.

            it = stream.iterate()
            body = it.map(step_fn)
            it.close_with(body.filter(still_going))   # feedback edge
            body.filter(done).sink_to(...)            # loop exit
        """
        t = Transformation(
            "iteration_head", "iterate", [self.transform],
            {"max_rounds": max_rounds},
        )
        return IterativeStream(self.env, t)

    def key_by(self, key_selector: Callable, name: str = "key_by",
               vectorized: bool = False, traceable: bool = False) -> "KeyedStream":
        """Partition by key. Vectorized form: key_selector(values_column)
        returns the whole key column — keeps the hot ingest path columnar.

        traceable=True (implies vectorized) declares the selector to be a
        pure jax-traceable column function returning NON-NEGATIVE INTEGER
        keys below `execution.state.key-capacity`: the key column is then
        computed on device and a downstream eligible window aggregate fuses
        with this step's chain into one device program (docs/fusion.md)."""
        vectorized = vectorized or traceable
        sel = as_key_selector(key_selector) if not vectorized else key_selector
        t = Transformation(
            "key_by", name, [self.transform],
            {"key_selector": sel, "vectorized": vectorized,
             "traceable": traceable},
        )
        return KeyedStream(self.env, t)

    def window_all(self, assigner: WindowAssigner) -> "AllWindowedStream":
        """A window over the WHOLE stream (DataStream.windowAll /
        AllWindowedStream.java): every record falls under one null key, at
        parallelism 1, and the window's rows are emitted bare (no key in
        front). It is `key_by(<null key>).window(assigner)`; where the
        stream is a fused window's fires and the aggregate is `max_by` /
        `min_by`, each fire is reduced as columns on its way in
        (docs/windows.md)."""
        return AllWindowedStream(self, assigner)

    # -- sinks -------------------------------------------------------------
    def sink_to(self, sink: Sink, name: str = "sink") -> "DataStreamSink":
        t = Transformation("sink", name, [self.transform], {"sink": sink})
        self.env._sinks.append(t)
        return DataStreamSink(self.env, t)

    def print(self) -> "DataStreamSink":
        from flink_tpu.connectors.sink import PrintSink

        return self.sink_to(PrintSink(), name="print")

    def collect(self) -> CollectSink:
        """Convenience: attach a CollectSink and return it (results after
        env.execute())."""
        sink = CollectSink()
        self.sink_to(sink, name="collect")
        return sink


class IterativeStream(DataStream):
    """The head of an iteration (IterativeStream.java analogue); close_with
    wires the feedback edge back to this head."""

    def close_with(self, feedback: DataStream) -> DataStream:
        """Feed `feedback`'s records back into the iteration head
        (IterativeStream.closeWith). Returns the feedback stream."""
        tail = Transformation(
            "iteration_tail", "iteration_tail", [feedback.transform],
            {"head": self.transform},
        )
        self.env._roots.append(tail)
        return feedback


class DataStreamSink:
    def __init__(self, env, transform):
        self.env = env
        self.transform = transform

    def uid(self, uid: str) -> "DataStreamSink":
        self.transform.uid = uid
        return self


class ConnectedStreams:
    """Two paired streams (ConnectedStreams.java): co-transforms see both
    inputs; keyed variants share per-key state across the two inputs."""

    def __init__(self, env: StreamExecutionEnvironment,
                 first: DataStream, second: DataStream):
        self.env = env
        self.first = first
        self.second = second

    def map(self, fn1: Callable, fn2: Callable, name: str = "co_map") -> DataStream:
        t = Transformation(
            "co_map", name, [self.first.transform, self.second.transform],
            {"fn1": fn1, "fn2": fn2},
        )
        return DataStream(self.env, t)

    def flat_map(self, fn1: Callable, fn2: Callable,
                 name: str = "co_flat_map") -> DataStream:
        t = Transformation(
            "co_flat_map", name, [self.first.transform, self.second.transform],
            {"fn1": fn1, "fn2": fn2},
        )
        return DataStream(self.env, t)

    def key_by(self, key_selector1: Callable, key_selector2: Callable) -> "ConnectedStreams":
        """Key both inputs; a subsequent process() shares keyed state/timers
        across the two inputs (the point of connect over union)."""
        cs = ConnectedStreams(self.env, self.first, self.second)
        cs._ks = (as_key_selector(key_selector1), as_key_selector(key_selector2))
        return cs

    def process(self, co_process_fn, name: str = "co_process") -> DataStream:
        """Keyed: KeyedCoProcessFunction (process_element1/process_element2 +
        optional on_timer) with shared per-key state — requires
        key_by(ks1, ks2). Broadcast: when the second stream is
        .broadcast(), a BroadcastProcessFunction
        (process_element(value, state_view) / process_broadcast_element
        (value, state)) with operator-wide broadcast state — the reference's
        broadcast state pattern (BroadcastConnectedStream.process)."""
        ks = getattr(self, "_ks", None)
        if ks is not None:
            t = Transformation(
                "co_process", name,
                [self.first.transform, self.second.transform],
                {"process_fn": co_process_fn,
                 "key_selector1": ks[0], "key_selector2": ks[1]},
            )
            return DataStream(self.env, t)
        if self.second.transform.kind == "broadcast":
            t = Transformation(
                "broadcast_process", name,
                [self.first.transform, self.second.transform],
                {"process_fn": co_process_fn},
            )
            return DataStream(self.env, t)
        raise ValueError(
            "connect(...).process requires key_by(ks1, ks2), or a "
            ".broadcast() second stream for the broadcast state pattern"
        )


class JoinBuilder:
    """where/equalTo/window/apply builder for joins and coGroups
    (JoinedStreams.java:101, CoGroupedStreams.java)."""

    def __init__(self, env, first: DataStream, second: DataStream, cogroup: bool):
        self.env = env
        self.first = first
        self.second = second
        self.cogroup = cogroup
        self._ks1: Optional[Callable] = None
        self._ks2: Optional[Callable] = None
        self._assigner: Optional[WindowAssigner] = None

    def where(self, key_selector: Callable) -> "JoinBuilder":
        self._ks1 = as_key_selector(key_selector)
        return self

    def equal_to(self, key_selector: Callable) -> "JoinBuilder":
        self._ks2 = as_key_selector(key_selector)
        return self

    def window(self, assigner: WindowAssigner) -> "JoinBuilder":
        self._assigner = assigner
        return self

    def apply(self, fn: Callable, name: Optional[str] = None) -> DataStream:
        """Join: fn(left, right) per matching pair. CoGroup: fn(lefts,
        rights) once per key x window."""
        if self._ks1 is None or self._ks2 is None:
            raise ValueError("join requires where(...) and equal_to(...)")
        if self._assigner is None:
            raise ValueError("join requires a window(...) assigner")
        kind = "co_group" if self.cogroup else "window_join"
        t = Transformation(
            kind, name or kind,
            [self.first.transform, self.second.transform],
            {"key_selector1": self._ks1, "key_selector2": self._ks2,
             "assigner": self._assigner, "join_fn": fn},
        )
        return DataStream(self.env, t)


class KeyedStream(DataStream):
    """Keyed partitioned stream (KeyedStream.java:94)."""

    @property
    def key_selector(self) -> Callable:
        return self.transform.config["key_selector"]

    def window(self, assigner: WindowAssigner) -> "WindowedStream":
        return WindowedStream(self, assigner)

    def _scalar_key_selector(self) -> Callable:
        """Per-record view of the key selector (vectorized selectors are
        adapted for the per-record oracle/CPU operators)."""
        sel = self.key_selector
        if self.transform.config.get("vectorized"):
            import numpy as np

            return lambda v: sel(np.asarray(v)[None, ...])[0]
        return sel

    # rolling (non-windowed) keyed reduce — oracle/CPU path
    def reduce(self, fn: Callable, name: str = "keyed_reduce") -> "DataStream":
        t = Transformation(
            "reduce", name, [self.transform],
            {"reduce_fn": fn, "key_selector": self._scalar_key_selector()},
        )
        return DataStream(self.env, t)

    def process(self, process_fn, name: str = "keyed_process") -> "DataStream":
        """Low-level keyed ProcessFunction with timers (oracle/CPU path)."""
        t = Transformation(
            "process_keyed",
            name,
            [self.transform],
            {"process_fn": process_fn, "key_selector": self._scalar_key_selector()},
        )
        return DataStream(self.env, t)

    def continuous_aggregate(
        self,
        specs,
        key_fields,
        out_names,
        mini_batch: Optional[bool] = None,
        generate_update_before: bool = True,
        device: Optional[bool] = None,
        name: str = "group_agg",
    ) -> "DataStream":
        """Continuous (non-windowed) group aggregation emitting a retract
        changelog — the reference's GroupAggFunction
        (flink-table-runtime .../aggregate/GroupAggFunction.java:33).

        `specs` is a list of (func, col) with func in COUNT/SUM/AVG/MIN/MAX
        (col ignored for COUNT); `key_fields` name the key parts and
        `out_names` the aggregate outputs in emitted rows. Input rows may
        themselves carry changelog kinds (table/changelog.py), so cascaded
        aggregations compose. `mini_batch=True` emits one transition per
        distinct key per batch (MiniBatchGroupAggFunction analogue);
        False gives the exact per-record reference emission order.
        `device=True` keeps linear accumulators in HBM with one scatter-add
        dispatch per batch."""
        t = Transformation(
            "group_agg", name, [self.transform],
            {
                "key_selector": self._scalar_key_selector(),
                "specs": list(specs),
                "key_fields": list(key_fields),
                "out_names": list(out_names),
                "mini_batch": mini_batch,
                "generate_update_before": generate_update_before,
                "device": device,
            },
        )
        return DataStream(self.env, t)


class WindowedStream:
    """Builder for windowed aggregations (WindowedStream.java;
    the builder decides oracle vs device operator the same way
    WindowOperatorBuilder.java:79 selects sync vs async operators)."""

    def __init__(self, keyed: KeyedStream, assigner: WindowAssigner):
        self._keyed = keyed
        self._assigner = assigner
        self._trigger: Optional[Trigger] = None
        self._evictor: Optional[Evictor] = None
        self._allowed_lateness = 0
        self._side_output_late = False

    def trigger(self, trigger: Trigger) -> "WindowedStream":
        self._trigger = trigger
        return self

    def evictor(self, evictor: Evictor) -> "WindowedStream":
        self._evictor = evictor
        return self

    def allowed_lateness(self, lateness_ms: int) -> "WindowedStream":
        self._allowed_lateness = lateness_ms
        return self

    def side_output_late_data(self) -> "WindowedStream":
        self._side_output_late = True
        return self

    def _agg_transform(self, aggregate, value_fn, window_fn, name,
                       value_vectorized: bool = False,
                       value_traceable: bool = False) -> DataStream:
        t = Transformation(
            "window_aggregate",
            name,
            [self._keyed.transform],
            {
                "assigner": self._assigner,
                "aggregate": aggregate,
                "value_fn": value_fn,
                "value_vectorized": value_vectorized or value_traceable,
                "value_traceable": value_traceable,
                "window_fn": window_fn,
                "trigger": self._trigger,
                "evictor": self._evictor,
                "allowed_lateness": self._allowed_lateness,
                "side_output_late": self._side_output_late,
                "key_selector": self._keyed.key_selector,
                "key_vectorized": self._keyed.transform.config.get("vectorized", False),
                "key_traceable": self._keyed.transform.config.get("traceable", False),
            },
        )
        return DataStream(self._keyed.env, t)

    def aggregate(
        self,
        aggregate: Union[str, AggregateFunction, Any],
        value_fn: Optional[Callable] = None,
        window_fn=None,
        name: str = "window_aggregate",
        value_vectorized: bool = False,
        value_traceable: bool = False,
    ) -> DataStream:
        """`aggregate` is a builtin name ('sum'/'count'/'min'/'max'/'mean'),
        a DeviceAggregator (device path), or an AggregateFunction (oracle).
        `value_fn` extracts the numeric column for device aggregation; with
        value_vectorized=True it maps the whole values column at once, and
        value_traceable=True additionally declares it jax-traceable so the
        extraction runs inside the fused device program (docs/fusion.md)."""
        return self._agg_transform(aggregate, value_fn, window_fn, name,
                                   value_vectorized=value_vectorized,
                                   value_traceable=value_traceable)

    def reduce(self, fn: Callable, name: str = "window_reduce") -> DataStream:
        from flink_tpu.api.functions import ReduceAggregate

        return self._agg_transform(ReduceAggregate(fn), None, None, name)

    def sum(self, value_fn: Optional[Callable] = None) -> DataStream:
        return self.aggregate("sum", value_fn, name="window_sum")

    def count(self) -> DataStream:
        return self.aggregate("count", name="window_count")

    def max(self, value_fn: Optional[Callable] = None) -> DataStream:
        return self.aggregate("max", value_fn, name="window_max")

    def min(self, value_fn: Optional[Callable] = None) -> DataStream:
        return self.aggregate("min", value_fn, name="window_min")

    def max_by(self, position: int) -> DataStream:
        """The window's row whose field `position` is largest, whole
        (WindowedStream.maxBy(int)); the first to arrive among equals."""
        return self.aggregate(max_by_agg(position), name="window_max_by")

    def min_by(self, position: int) -> DataStream:
        """The window's row whose field `position` is smallest, whole
        (WindowedStream.minBy(int)); the first to arrive among equals."""
        return self.aggregate(min_by_agg(position), name="window_min_by")

    def process(self, window_fn, name: str = "window_process") -> DataStream:
        """Buffered window with ProcessWindowFunction (no pre-aggregation)."""
        return self._agg_transform(None, None, window_fn, name)


class AllWindowedStream(WindowedStream):
    """`DataStream.window_all`: the windowed stream of one null key. Every
    builder of WindowedStream applies; what the window emits has no key,
    so downstream receives the bare result."""

    def __init__(self, stream: DataStream, assigner: WindowAssigner):
        super().__init__(stream.key_by(null_key, name="window_all"),
                         assigner)
