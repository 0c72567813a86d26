"""MiniCluster: in-process job management with failure recovery.

The control-plane-lite of the reference's Dispatcher/JobMaster/
MiniCluster stack (Dispatcher.submitJob :835 → JobMaster → scheduler;
test-cluster form runtime/minicluster/MiniCluster.java:160): jobs are
submitted asynchronously, each runs attempts on its own thread; on failure
the restart strategy (checkpoint/restart.py — ExponentialDelay/FixedDelay/
FailureRate parity) decides backoff or terminal failure, and each retry
restores from the latest completed checkpoint (region failover degenerates
to whole-pipeline restart in a linear topology). Savepoints are triggered
through the client and written through the same snapshot path
(SavepointType semantics: manually triggered, never auto-discarded).
"""

from __future__ import annotations

import enum
import threading
import time
import traceback
import uuid
from typing import Any, Dict, Optional

from flink_tpu.checkpoint.coordinator import CheckpointCoordinator
from flink_tpu.checkpoint.restart import restart_strategy_from_config
from flink_tpu.checkpoint.storage import (
    FsCheckpointStorage,
    MemoryCheckpointStorage,
)
from flink_tpu.config import CheckpointingOptions, Configuration, ParallelOptions
from flink_tpu.lint.contracts import absorbs_faults
from flink_tpu.graph.transformation import StepGraph
from flink_tpu.runtime.executor import (
    JobCancelledException,
    JobRuntime,
    MeshRescaleRequested,
)


def _effective_mesh_target(runtime: JobRuntime, target: int) -> Optional[int]:
    """Clamp a mesh-rescale target EXACTLY like runner construction will:
    visible devices and the largest divisor of the operators'
    construction-time key capacity (NOT the grown pipe.K — the rebuilt
    operator starts from the construction capacity again, so clamping
    against grown state would accept targets the rebuild cannot reach and
    tear the job down for a no-op). None = the job has no mesh-capable
    operator; otherwise the device count the rebuild will actually
    produce."""
    caps = [
        op.mesh_capacity()
        for op in (getattr(r, "op", None) for r in runtime.runners)
        if op is not None and hasattr(op, "mesh_capacity")
    ]
    if not caps:
        return None
    import jax

    from flink_tpu.parallel.mesh import usable_mesh_size

    return usable_mesh_size(max(1, int(target)), len(jax.devices()),
                            min(caps))


def _is_device_loss(e: BaseException) -> bool:
    """Does this failure look like the device plane died under the job?
    Real chip/host loss surfaces as an XLA runtime error from the dispatch;
    chaos drills inject the same seam with a `device`-scoped marker. Walks
    the cause chain (cycle-safe) so wrapping never hides the origin."""
    seen = set()
    cur: Optional[BaseException] = e
    while cur is not None and id(cur) not in seen:
        seen.add(id(cur))
        if "XlaRuntimeError" in type(cur).__name__:
            return True
        if "[chaos-injected:device" in str(cur):
            return True
        cur = cur.__cause__ or cur.__context__
    return False


class JobStatus(enum.Enum):
    CREATED = "CREATED"
    RUNNING = "RUNNING"
    RESTARTING = "RESTARTING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    CANCELED = "CANCELED"


class JobClient:
    """Client handle (JobClient/RestClusterClient surface: status, cancel,
    savepoint)."""

    def __init__(self, job_id: str, job_name: str):
        self.job_id = job_id
        self.job_name = job_name
        self._status = JobStatus.CREATED
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._cancel = threading.Event()
        self._savepoint_path: Optional[str] = None
        self._savepoint_done = threading.Event()
        self.error: Optional[BaseException] = None
        self.records_in = 0
        self.num_restarts = 0
        self.num_checkpoints = 0
        # multichip (parallel.mesh.*): live mesh-size rescales performed on
        # this job (checkpoint rewind + key-group re-shard across device
        # counts) and the pending target the run loop picks up at the next
        # step boundary
        self.mesh_rescales = 0
        self.last_mesh_rescale_duration_ms = 0.0
        self._mesh_rescale_target: Optional[int] = None
        # skew-aware key-group routing (parallel.mesh.skew-rebalance):
        # completed routing-table rebalances on this job + the policy
        # object that decided them (scheduler/rebalancer.py)
        self.mesh_rebalances = 0
        self.last_mesh_rebalance_duration_ms = 0.0
        self.rebalancer = None

    def latency_report(self) -> dict:
        """Emission-latency + stall-attribution report (/jobs/:id/latency
        shape; the JM's job_latency builds the identical payload from
        shard-folded snapshots): per-operator log-bucket histograms and
        watermark lag from the live registry, outlier EmissionStall spans
        attributed against the job's control-plane spans."""
        from flink_tpu.metrics.emission_latency import build_latency_report
        from flink_tpu.metrics.registry import metrics_snapshot

        registry = getattr(self, "metrics", None)
        snap = metrics_snapshot(registry.all_metrics()) if registry else {}
        log = getattr(self, "span_log", None)
        spans = [s.to_dict() for s in log.spans] if log is not None else []
        return build_latency_report(snap, spans)

    def history_report(self, metric: Optional[str] = None,
                       since: Optional[float] = None) -> dict:
        """Metric time-series rings (/jobs/:id/history?metric=&since=
        shape; the JM's job_history builds the identical payload from
        shard-folded snapshots): per-key bounded point lists sampled on
        the processing-time tick — counters as windowed rates, gauges as
        values, histograms as per-sample p50/p99 sub-series."""
        history = getattr(self, "history", None)
        if history is None:
            return {"enabled": False, "series": {}, "sample_count": 0}
        payload = history.payload(
            metric=metric or None,
            since_ms=float(since) if since not in (None, "") else None)
        payload["enabled"] = True
        return payload

    def doctor_report(self) -> dict:
        """Ranked bottleneck diagnosis (/jobs/:id/doctor shape; identical
        payload on the distributed path): the job doctor joined over the
        history rings and this job's span log."""
        from flink_tpu.metrics.doctor import diagnose

        history = getattr(self, "history", None)
        window_ms = float(getattr(self, "doctor_window_ms", 60000.0))
        if history is None:
            return {"verdict": "unknown", "score": 0.0, "diagnoses": [],
                    "window_ms": window_ms, "samples": 0,
                    "watchdog_events": 0}
        log = getattr(self, "span_log", None)
        spans = [s.to_dict() for s in log.spans] if log is not None else []
        return diagnose(history, spans, window_ms=window_ms)

    # -- status -----------------------------------------------------------
    def status(self) -> JobStatus:
        return self._status

    def _set_status(self, status: JobStatus) -> None:
        with self._lock:
            self._status = status
        if status in (JobStatus.FINISHED, JobStatus.FAILED, JobStatus.CANCELED):
            self._done.set()

    def wait(self, timeout: Optional[float] = None) -> JobStatus:
        self._done.wait(timeout)
        if not self._done.is_set():
            raise TimeoutError(f"job {self.job_id} still {self._status}")
        if self._status == JobStatus.FAILED and self.error is not None:
            raise RuntimeError(f"job {self.job_id} failed") from self.error
        return self._status

    # -- operations -------------------------------------------------------
    def cancel(self) -> None:
        self._cancel.set()

    def trigger_savepoint(self, path: str, timeout: float = 30.0) -> str:
        """Requests a savepoint at the next step boundary; blocks until
        written (stop-with-savepoint arrives with the drain protocol)."""
        self._savepoint_done.clear()
        self._savepoint_path = path
        if not self._savepoint_done.wait(timeout):
            raise TimeoutError("savepoint not taken (job finished or stalled?)")
        return path

    def _poll_savepoint_request(self) -> Optional[str]:
        path = self._savepoint_path
        if path is not None:
            self._savepoint_path = None
            return path
        return None

    def rescale_mesh(self, devices: int) -> None:
        """Request a live mesh-size rescale of a RUNNING mesh job (the
        manual sibling of the autoscaler's decision): at the next step
        boundary the job captures its state, rebuilds over `devices`
        devices, and restores — exactly-once, no restart counted. No-op
        on jobs without parallel.mesh.enabled."""
        self._mesh_rescale_target = max(1, int(devices))

    def _poll_mesh_rescale(self) -> Optional[int]:
        t = self._mesh_rescale_target
        if t is not None:
            self._mesh_rescale_target = None
        return t

    # -- queryable state (S13: KvStateServer/ClientProxy analogue) ---------
    def query_state(self, uid: str, key) -> dict:
        """Point lookup into the RUNNING job's keyed state. Safe without
        locks: device state arrays are immutable (replaced atomically per
        step) and heap tables are only read here.

        Returns, per operator type:
          device window op : {"slices": {abs_slice: {field: value, count}},
                              "watermark": wm}
          oracle window op / keyed ops : {"states": {name: {repr(ns): value}},
                              "watermark": wm}
          rolling reduce   : {"value": current}
        """
        runtime = getattr(self, "_runtime", None)
        if runtime is None:
            raise RuntimeError("job has no running attempt")
        import numpy as np

        for r in runtime.runners:
            if getattr(r, "uid", None) != uid:
                continue
            op = getattr(r, "op", None)
            if op is not None and hasattr(op, "query_state_for"):
                # fused window operator folds ring + buffered views itself
                return {
                    "slices": op.query_state_for(key),
                    "watermark": op.current_watermark,
                }
            if op is not None and hasattr(op, "state") and hasattr(op.state, "keydict"):
                state = op.state
                kid = state.keydict.lookup(key)
                if kid is None:
                    return {"slices": {}, "watermark": op.current_watermark}
                count = np.asarray(state.count)[kid]
                acc = {k: np.asarray(v)[kid] for k, v in state.acc.items()}
                f = state.frontiers
                slices = {}
                if f.min_used is not None:
                    lo = f.min_used if f.purged_to is None else max(f.purged_to, f.min_used)
                    for s in range(lo, f.max_used + 1):
                        pos = s % state.S
                        if count[pos] > 0:
                            entry = {name: arr[pos].item() for name, arr in acc.items()}
                            entry["count"] = int(count[pos])
                            slices[s] = entry
                return {"slices": slices, "watermark": op.current_watermark}
            if op is not None and hasattr(op, "state"):  # oracle/heap ops
                backend = op.state
                backend.set_current_key(key)
                states = {}
                for name in backend.descriptors:
                    for ns in backend.namespaces_for_key(name, key):
                        states.setdefault(name, {})[repr(ns)] = backend.get(name, ns)
                wm = getattr(op, "timer_service", None)
                return {
                    "states": states,
                    "watermark": wm.current_watermark if wm else None,
                }
            if hasattr(r, "state"):  # KeyedReduceRunner et al.
                r.state.set_current_key(key)
                return {"value": r.state.get("rolling")}
        raise KeyError(f"no queryable operator {uid!r}")


class MiniCluster:
    _shared: Optional["MiniCluster"] = None

    def __init__(self, security=None):
        self.jobs: Dict[str, JobClient] = {}
        # the cluster's transport-security identity (auth ON by default):
        # in-process jobs never cross a socket, but everything layered on a
        # MiniCluster that DOES (RestServer bearer derivation, distributed
        # hand-off) shares this one resolved secret/cluster-id
        from flink_tpu.security.transport import SecurityConfig

        self.security = SecurityConfig.resolve() if security is None else security

    @classmethod
    def get_shared(cls) -> "MiniCluster":
        if cls._shared is None:
            cls._shared = MiniCluster()
        return cls._shared

    def submit(
        self,
        graph: StepGraph,
        config: Configuration,
        job_name: Optional[str] = None,
        savepoint_restore_path: Optional[str] = None,
    ) -> JobClient:
        job_id = uuid.uuid4().hex[:16]
        client = JobClient(job_id, job_name or f"job-{job_id}")
        self.jobs[job_id] = client
        thread = threading.Thread(
            target=self._run_job,
            args=(client, graph, config, savepoint_restore_path),
            name=f"jobmaster-{job_id}",
            daemon=True,
        )
        thread.start()
        return client

    # ------------------------------------------------------------------
    def _run_job(
        self,
        client: JobClient,
        graph: StepGraph,
        config: Configuration,
        savepoint_restore_path: Optional[str],
    ) -> None:
        # chaos.* config group: a job config can run a fault drill on the
        # in-process path too (tests/scenarios install plans through
        # testing.harness.fault_injection instead; this never stacks).
        # The plan is uninstalled when THIS job ends — a process-wide hook
        # leaking past the drill would fault every later job for no reason
        from flink_tpu.chaos import plan as _chaos

        chaos_plan = _chaos.FaultPlan.from_config(config)
        installed_chaos = False
        if chaos_plan is not None and _chaos.active_plan() is None:
            _chaos.install_plan(chaos_plan)
            installed_chaos = True
        try:
            self._run_job_inner(client, graph, config, savepoint_restore_path)
        finally:
            if installed_chaos and _chaos.active_plan() is chaos_plan:
                _chaos.uninstall_plan()

    @absorbs_faults('driver failover boundary: the caught failure increments the attempt counter and re-runs the job per the restart strategy; injected faults ride this path by design')
    def _run_job_inner(
        self,
        client: JobClient,
        graph: StepGraph,
        config: Configuration,
        savepoint_restore_path: Optional[str],
    ) -> None:
        from flink_tpu.config import ObservabilityOptions
        from flink_tpu.metrics.checkpoint_stats import (
            CheckpointStatsTracker,
            ExceptionHistory,
            failing_task,
        )
        from flink_tpu.metrics.otel import OtlpJsonTraceReporter
        from flink_tpu.metrics.registry import MetricRegistry
        from flink_tpu.metrics.traces import TraceRegistry, job_trace_id

        client.metrics = MetricRegistry()
        # one correlation id per job: every span this job emits (checkpoint
        # lifecycle, restarts) carries it, and any process that knows the
        # job id derives the same id (traces.job_trace_id) — JM- and
        # TM-side spans stitch into one trace
        client.trace_id = job_trace_id(client.job_id)
        client.traces = TraceRegistry(trace_id=client.trace_id)
        # OTel-shape export: buffered OTLP/JSON, served at /jobs/<id>/traces
        client.otel = OtlpJsonTraceReporter(service_name="flink-tpu")
        client.traces.add_reporter(client.otel)
        # raw-span log for /jobs/:id/latency stall attribution: outlier
        # EmissionStall spans joined against the same registry's
        # checkpoint/recovery/compile spans by interval overlap (bounded —
        # a long-running job must not grow it without limit)
        from flink_tpu.metrics.traces import InMemoryTraceReporter

        client.span_log = InMemoryTraceReporter(max_spans=512)
        client.traces.add_reporter(client.span_log)
        # history plane + health watchdog (ISSUE-19): the client samples
        # its own folded registry view on the processing-time tick (the
        # cancel_check step boundary below); watchdog breaches land in the
        # same trace registry as every other control-plane span
        from flink_tpu.metrics.doctor import HealthWatchdog
        from flink_tpu.metrics.history import MetricHistory
        from flink_tpu.metrics.traces import Span

        client.history = MetricHistory(
            interval_ms=config.get(ObservabilityOptions.HISTORY_INTERVAL_MS),
            retention_points=config.get(
                ObservabilityOptions.HISTORY_RETENTION_POINTS))
        client.doctor_window_ms = float(
            config.get(ObservabilityOptions.DOCTOR_WINDOW_MS))
        client.watchdog = None
        if config.get(ObservabilityOptions.DOCTOR_ENABLED):
            def _health_sink(scope, name, start_ms, end_ms, attrs,
                             _c=client):
                _c.traces.report(Span(scope, name, start_ms, end_ms,
                                      dict(attrs, jobId=_c.job_id)))

            client.watchdog = HealthWatchdog(
                _health_sink,
                min_gap_ms=float(config.get(
                    ObservabilityOptions.DOCTOR_WATCHDOG_MIN_GAP_MS)),
                p99_breach_ms=config.get(
                    ObservabilityOptions.DOCTOR_P99_BREACH_MS))
        interval = config.get(CheckpointingOptions.INTERVAL_MS)
        chk_dir = config.get(CheckpointingOptions.DIRECTORY)
        storage = FsCheckpointStorage(chk_dir) if chk_dir else MemoryCheckpointStorage()
        # fault-tolerance observability: per-checkpoint stats (bounded ring
        # + the standard gauges on the job's registry, so /metrics and
        # /jobs/:id/checkpoints see them) and a bounded exception/recovery
        # history replacing a single overwritten error
        job_group = client.metrics.group("job")
        client.checkpoint_stats = CheckpointStatsTracker(
            history_size=config.get(ObservabilityOptions.CHECKPOINT_HISTORY_SIZE))
        client.checkpoint_stats.register_metrics(job_group)
        client.exceptions = ExceptionHistory(
            size=config.get(ObservabilityOptions.EXCEPTION_HISTORY_SIZE))
        client.exceptions.register_metrics(job_group)
        # elastic autoscaler: an in-process job runs as ONE task, so the
        # slot-parallelism axis has nothing to rescale — but with a device
        # MESH (parallel.mesh.enabled) the mesh size IS a parallelism axis
        # this process owns, and the coordinator gets a real executor:
        # decisions turn into live checkpoint-rewind + key-group re-shard
        # onto a different device count at a step boundary. Without a mesh
        # the coordinator stays observe-only (decision log only).
        from flink_tpu.config import AutoscalerOptions

        mesh_enabled = config.get(ParallelOptions.MESH_ENABLED)
        mesh_autoscale = (mesh_enabled
                          and config.get(ParallelOptions.MESH_AUTOSCALE))
        # skew-aware key-group routing (parallel.mesh.skew-rebalance): the
        # scheduler-side policy decides, the run loop executes at a
        # step-aligned boundary through the rescale capture/restore
        # machinery. Gauges register whenever the mesh is on, so the
        # observability surface is uniform (0 / version until a table
        # exists — the numRescales pattern above).
        skew_rebalance = (mesh_enabled
                          and config.get(ParallelOptions.MESH_SKEW_REBALANCE))
        if mesh_enabled:
            # per-mesh facts every shard would report identically -> MAX
            # (the _REBALANCE_GAUGES rule, now declared at registration)
            job_group.gauge("meshRebalances",
                            lambda: client.mesh_rebalances,
                            fold="max", kind="counter")
            job_group.gauge("lastRebalanceDurationMs",
                            lambda: client.last_mesh_rebalance_duration_ms,
                            fold="max")
            job_group.gauge(
                "routingTableVersion",
                lambda: (getattr(client, "_runtime", None) is not None
                         and client._runtime.mesh_routing_version()) or 0,
                fold="max")
        if skew_rebalance:
            from flink_tpu.scheduler.rebalancer import SkewRebalancer

            client.rebalancer = SkewRebalancer(
                skew_threshold=config.get(
                    ParallelOptions.MESH_REBALANCE_SKEW_THRESHOLD),
                interval_ms=config.get(
                    ParallelOptions.MESH_REBALANCE_INTERVAL_MS))
        if config.get(AutoscalerOptions.ENABLED):
            from flink_tpu.metrics.registry import metrics_snapshot
            from flink_tpu.scheduler import AutoscalerCoordinator

            mesh_executor = None
            if mesh_autoscale:
                def mesh_executor(job_id, target, reason, _c=client):
                    rt = getattr(_c, "_runtime", None)
                    if rt is None:
                        return False, "no running attempt"
                    # pre-apply the SAME clamp the rebuild will apply
                    # (_effective_mesh_target), so an unreachable target
                    # — no mesh-capable operator, no shard_map backend,
                    # or a device count the construction-time capacity
                    # cannot divide — reads as rejected instead of
                    # tearing the job down for a no-op rebuild and
                    # re-firing every stabilization window
                    eff = _effective_mesh_target(rt, int(target))
                    if eff is None:
                        return False, "job has no mesh-capable operator"
                    cur = rt.mesh_devices()
                    if eff == cur:
                        return False, f"mesh already at {cur} device(s)"
                    _c._mesh_rescale_target = eff
                    return True, f"mesh rescale {cur} -> {eff} requested"

            client.autoscaler = AutoscalerCoordinator.from_config(
                config, rescale_executor=mesh_executor)
            # without a mesh executor these read a constant 0 — registered
            # anyway so the gauge surface matches the distributed JM and
            # dashboards scrape one shape
            job_group.gauge("numRescales", lambda: client.mesh_rescales,
                            fold="max", kind="counter")
            job_group.gauge("lastRescaleDurationMs",
                            lambda: client.last_mesh_rescale_duration_ms,
                            fold="max")
            client._autoscaler_metrics = (
                lambda c=client: metrics_snapshot(c.metrics.all_metrics()))
        coordinator = (
            CheckpointCoordinator(
                storage,
                interval,
                config.get(CheckpointingOptions.MAX_RETAINED),
                traces=client.traces,
                stats=client.checkpoint_stats,
                tolerable_failures=config.get(
                    CheckpointingOptions.TOLERABLE_FAILED_CHECKPOINTS),
            )
            if interval > 0
            else None
        )
        if coordinator is not None:
            coordinator.register_on_complete(
                lambda _cp, c=client, co=coordinator:
                    setattr(c, "num_checkpoints", co.num_completed))
        strategy = restart_strategy_from_config(config)
        attempt = 0
        # mesh-size override for the NEXT attempt: set by a live rescale
        # (autoscaler decision or manual rescale_mesh) and by the
        # device-loss degrade policy; None = the configured size
        mesh_override: Optional[int] = None
        pending_rescale: Optional[dict] = None
        # routing assignment for the NEXT attempt: set by a skew rebalance
        # (applied to the rebuilt runtime BEFORE restore, so the canonical
        # capture lands in the new placement)
        pending_rebalance: Optional[dict] = None

        restore_snap = None
        restore_ms = 0.0
        # open recovery span: created at failure, closed only when the
        # REBUILT attempt reaches RUNNING — the interval must cover the
        # runtime rebuild + state restore so emission-stall attribution
        # can overlap post-restore window-fire latency against it
        restart_span = None
        if savepoint_restore_path is not None:
            sp_storage = FsCheckpointStorage(savepoint_restore_path)
            latest = sp_storage.latest()
            if latest is None:
                client.error = FileNotFoundError(
                    f"no savepoint at {savepoint_restore_path}"
                )
                client._set_status(JobStatus.FAILED)
                return
            t_restore = time.perf_counter()
            restore_snap = sp_storage.load(latest[1])
            restore_ms = (time.perf_counter() - t_restore) * 1000.0

        while True:
            cfg = config
            if mesh_override is not None:
                cfg = config.clone()
                cfg.set(ParallelOptions.MESH_DEVICES, mesh_override)
            runtime = JobRuntime(graph, cfg, registry=client.metrics,
                                 traces=client.traces)
            client._runtime = runtime  # queryable-state surface (S13)
            if coordinator is not None:
                # each attempt gets its full tolerable-failed-checkpoints
                # budget (the coordinator outlives restarts)
                coordinator.reset_failure_streak()
                # per-operator breakdown for completed checkpoint records
                # comes from THIS attempt's operators
                coordinator.state_bytes_fn = runtime.operator_state_bytes
            try:
                if restore_snap is not None:
                    runtime.restore(restore_snap)
                    if pending_rescale is None and pending_rebalance is None:
                        # a live mesh rescale/rebalance restores from its
                        # own step-aligned capture, not a stored checkpoint
                        # — stamping a "restored checkpoint None" record
                        # would pollute the checkpoint-restore telemetry
                        client.checkpoint_stats.report_restore(
                            restore_snap.get("checkpoint_id"), restore_ms)
                client._set_status(JobStatus.RUNNING)
                # the restarted attempt is live again: close the recovery
                # timeline record (downtime = fail -> RUNNING)
                client.exceptions.complete_recovery(
                    restored_checkpoint_id=(restore_snap or {}).get(
                        "checkpoint_id"),
                    restore_duration_ms=restore_ms,
                    events_replayed=(
                        client.records_in - restore_snap.get("records_in", 0)
                        if restore_snap is not None else client.records_in),
                )
                if restart_span is not None:
                    # failure -> RUNNING: same downtime interval the
                    # recovery timeline records
                    client.traces.report(restart_span.set_attribute(
                        "restoredCheckpoint", bool(restore_snap)).end())
                    restart_span = None
                if pending_rescale is not None:
                    # the rebuilt attempt is serving at the new mesh size:
                    # stamp the completed rescale (counter + duration) and
                    # close the loop back into the autoscaler's learning
                    # history, target-tagged like the distributed JM does
                    duration_ms = (time.perf_counter()
                                   - pending_rescale["t0"]) * 1000.0
                    client.mesh_rescales += 1
                    client.last_mesh_rescale_duration_ms = duration_ms
                    auto = getattr(client, "autoscaler", None)
                    if auto is not None:
                        auto.rescale_completed(
                            client.job_id, duration_ms,
                            target=runtime.mesh_devices())
                    pending_rescale = None
                if pending_rebalance is not None:
                    # apply the rebalanced routing table AFTER restore:
                    # restore may ADOPT a grown snapshot K (classic keyed
                    # path) and rebuild the table for the new capacity —
                    # applying first would silently discard the
                    # assignment (or raise on a G mismatch) and the
                    # rebalancer would re-decide the same move forever.
                    # The capture is canonical [K, S], so re-laying the
                    # restored rows under the new table is pure placement
                    runtime.set_mesh_routing(pending_rebalance["assign"])
                    # the rebuilt attempt is serving under the new routing
                    # table: stamp the completed rebalance and restart the
                    # policy's interval clock so the new placement gets
                    # fresh traffic before it is judged again
                    duration_ms = (time.perf_counter()
                                   - pending_rebalance["t0"]) * 1000.0
                    client.mesh_rebalances += 1
                    client.last_mesh_rebalance_duration_ms = duration_ms
                    if client.rebalancer is not None:
                        client.rebalancer.rebalance_completed()
                    pending_rebalance = None

                def cancel_check():
                    client.records_in = runtime.records_in  # progress gauge
                    auto = getattr(client, "autoscaler", None)
                    if auto is not None:
                        # throttled: maybe_observe snapshots the registry
                        # only when an autoscaler.interval-ms tick is due.
                        # On a mesh job the parallelism the policy sees IS
                        # the mesh size (the axis its executor rescales)
                        auto.maybe_observe(
                            client.job_id,
                            runtime.mesh_devices() if mesh_autoscale else 1,
                            client._autoscaler_metrics)
                    # history sampling on the same processing-time tick
                    # (the autoscaler's throttled-snapshot pattern): the
                    # cheap due() gate runs every step, the registry
                    # snapshot only on a due interval tick
                    if client.history.due():
                        from flink_tpu.metrics.registry import (
                            metrics_snapshot,
                        )

                        client.history.sample(
                            metrics_snapshot(client.metrics.all_metrics()))
                        if client.watchdog is not None:
                            client.watchdog.observe(client.history)
                    return client._cancel.is_set()

                def poll_mesh_rescale(rt=runtime):
                    # manual rescale_mesh targets arrive unclamped; apply
                    # the construction-time clamp HERE so an unreachable
                    # target (or one landing on the current size) never
                    # costs a stop-the-world rebuild that changes nothing
                    t = client._poll_mesh_rescale()
                    if t is None:
                        return None
                    eff = _effective_mesh_target(rt, t)
                    if eff is None or eff == rt.mesh_devices():
                        return None
                    return eff

                def poll_rebalance(rt=runtime):
                    # skew rebalance, polled at every step boundary: the
                    # interval throttle gates FIRST (one clock read per
                    # step) — only a due tick pays the per-group load
                    # readback and the balanced replan
                    reb = client.rebalancer
                    if reb is None or not reb.due():
                        return None
                    info = rt.mesh_group_loads()
                    if info is None:
                        return None
                    loads, assign, n = info
                    return reb.maybe_decide(loads, assign, n)

                runtime.run(
                    coordinator=coordinator,
                    cancel_check=cancel_check,
                    savepoint_request=lambda: self._savepoint_hook(client, runtime),
                    rescale_request=(poll_mesh_rescale
                                     if mesh_enabled else None),
                    rebalance_request=(poll_rebalance
                                       if skew_rebalance else None),
                )
                client.records_in = runtime.records_in
                client._set_status(JobStatus.FINISHED)
                return
            except JobCancelledException:
                client._set_status(JobStatus.CANCELED)
                return
            except MeshRescaleRequested as mr:
                # deliberate live rescale OR skew rebalance, not a
                # failure: rebuild the runtime (same device count for a
                # rebalance) and restore from the step-aligned capture the
                # run loop handed us (checkpoint rewind + key-group
                # re-shard/re-route; no restart counted, no backoff,
                # restart_attempts untouched)
                client.records_in = runtime.records_in
                mesh_override = mr.target
                restore_snap = mr.snapshot
                restore_ms = 0.0
                if mr.routing is not None:
                    pending_rebalance = {"t0": time.perf_counter(),
                                         "assign": mr.routing}
                    cause = (f"mesh key-group rebalance over {mr.target} "
                             "device(s)")
                    kind = "rebalance"
                else:
                    pending_rescale = {"t0": time.perf_counter(),
                                       "target": mr.target}
                    cause = f"mesh rescale to {mr.target} device(s)"
                    kind = "rescale"
                client._set_status(JobStatus.RESTARTING)
                client.exceptions.begin_recovery(
                    client.num_restarts,
                    cause=cause,
                    events_at_failure=client.records_in,
                    kind=kind)
                continue
            except BaseException as e:  # noqa: BLE001 — failover boundary
                attempt += 1
                client.error = e
                # a mid-rescale/-rebalance failure must not stamp a
                # completed-rescale/-rebalance duration (PR-6 outcome
                # hygiene): the job degraded into the plain restart path
                # instead — the restarted attempt resets to the identity
                # routing table, consistent with the canonical checkpoint
                # it restores (the rebalancer re-decides from live skew)
                pending_rescale = None
                pending_rebalance = None
                if (mesh_enabled
                        and config.get(
                            ParallelOptions.MESH_DEGRADE_ON_DEVICE_LOSS)
                        and runtime.mesh_devices() > 1
                        and _is_device_loss(e)):
                    # chip/host loss: restart the job at a REDUCED mesh
                    # size — the canonical [K, S] checkpoint re-shards over
                    # whatever devices survive (halving per restart,
                    # floor 1 = single-chip)
                    mesh_override = max(1, runtime.mesh_devices() // 2)
                # bounded exception history (ExceptionHistoryEntry analogue):
                # timestamp, failing-operator attribution, root-cause chain
                client.exceptions.record_failure(
                    repr(e),
                    task=failing_task(e) or client.job_name,
                    restart_number=attempt - 1,
                    exception=e,
                )
                delay = strategy.next_delay_ms(attempt)
                if delay is None:
                    client._set_status(JobStatus.FAILED)
                    return
                client.num_restarts = attempt
                client._set_status(JobStatus.RESTARTING)
                client.exceptions.begin_recovery(
                    attempt, cause=repr(e),
                    events_at_failure=client.records_in)
                if restart_span is not None:
                    # the previous recovery never reached RUNNING (the
                    # rebuilt attempt failed during restore) — close its
                    # span so the trace stays bounded
                    client.traces.report(restart_span.set_attribute(
                        "reachedRunning", False).end())
                restart_span = client.traces.span("recovery", "JobRestart") \
                    .set_attribute("attempt", attempt) \
                    .set_attribute("delayMs", delay) \
                    .set_attribute("cause", repr(e)[:200])
                time.sleep(delay / 1000.0)
                t_restore = time.perf_counter()
                restore_snap = coordinator.latest_snapshot() if coordinator else None
                restore_ms = (time.perf_counter() - t_restore) * 1000.0

    def _savepoint_hook(self, client: JobClient, runtime: JobRuntime) -> Optional[str]:
        path = client._poll_savepoint_request()
        if path is not None:
            runtime._write_savepoint(path)
            client._savepoint_done.set()
            return None  # runtime already wrote it
        return None
