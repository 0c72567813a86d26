"""FusedWindowOperator: the product-path driver of FusedWindowPipeline.

Round 1 left the fused superscan as a bench-only side-car; this adapter
makes it the operator the executor actually selects (the swap boundary the
reference models as WindowOperatorBuilder.java:79 choosing
buildAsyncWindowOperator :472). It presents the same operator surface as
TpuWindowOperator — process_batch / process_watermark / drain_output /
snapshot / restore — while internally buffering steps and dispatching one
compiled T-step superscan per superbatch.

Two host-side layers de-brittle the raw pipeline (whose planner rejects
batches spanning > nsb slices, > fires_per_step fires per step, > out_rows
fires per dispatch, or slices beyond the ring):

- StepNormalizer splits raw (batch, watermark) steps into planner-safe
  steps: slice-span splitting (adds commute, so splitting a batch at the
  same watermark is semantics-preserving), intermediate-watermark
  insertion so no step fires more than fires_per_step windows (the
  watermark is a lower bound; staging its advance is always safe), and
  ring-overflow hold-back (records too far in the future wait on host
  until the purge frontier opens ring space — the fused sibling of
  TpuWindowOperator._future).
- The dispatch grouper packs normalized steps into fixed-T superbatches
  (padding with empty steps so ONE executable serves every dispatch) and
  cuts a dispatch early before planned fires exceed out_rows.

Watermark visibility: emissions materialize when a superbatch resolves, so
the operator exposes `emitted_watermark` — the watermark downstream may
safely observe (everything at or below it has been emitted). The step
runner forwards min(watermark, emitted_watermark), which preserves the
no-late-data contract downstream (a delayed watermark is always correct).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from flink_tpu.api.windowing.assigners import WindowAssigner
from flink_tpu.core.time import MAX_WATERMARK, MIN_WATERMARK
from flink_tpu.lint.contracts import inflight_ring
from flink_tpu.metrics.task_io import dispatch_stage, stage
from flink_tpu.ops.aggregators import ONE, VALUE, resolve
from flink_tpu.runtime.fire_block import FireBlock, blocks_of, rows_of
from flink_tpu.runtime.fused_window_pipeline import (
    FusedWindowPipeline,
    StepPlan,
)
from flink_tpu.scheduler.latency_controller import (
    LatencySpec,
    SuperbatchController,
)
from flink_tpu.state.columnar import KeyDictionary


def _ceil_div(a: int, b: int) -> int:
    return -((-a) // b)


@dataclasses.dataclass
class _Step:
    """One planner-safe step: a (possibly empty) batch plus the watermark in
    effect after it, annotated with how many windows its advance fires."""

    kid: np.ndarray
    vals: Optional[np.ndarray]
    ts: np.ndarray
    wm: int
    n_fires: int
    # the step's finished slice plan (relative slice index, smin, smax),
    # where the normalizer proved it from the batch's two timestamp
    # extremes: staging copies it and plans nothing, the pipeline's
    # _PlanCursor checks the span it claims. None: staging plans the step
    # itself (late records, hold-back remainders, span splits, empty steps)
    plan: Optional[StepPlan] = None


class StepNormalizer:
    """Host-side simulation of the fused planner's frontier state, used to
    pre-split raw steps so the pipeline's `stage` never raises, and the place a
    data step is planned: a batch whose two timestamp extremes prove that no
    record is late, none lies beyond the ring and it spans fewer than NSB
    slices leaves as ONE step carrying its finished slice plan
    (`FusedWindowPipeline.plan_scalar`: srel, smin, smax), which staging
    copies. Any other batch takes the masked path (late mask, hold-back,
    span split) and its steps leave bare, for staging to plan with the same
    pipeline functions. The geometry is the pipeline's own (delegates, one
    source of truth); a plan or a frontier that diverged from the
    pipeline's would be a planner error, so `_PlanCursor`'s checks stay on
    as the assertions of every step, planned here or there."""

    def __init__(self, pipe: FusedWindowPipeline, raw_payload: bool = False):
        self.p = pipe
        # payload column type: dense int32 key ids (classic), or the raw
        # record columns of a traced chain (whole-graph fusion) — the
        # normalizer only ever row-indexes the payload, so the frontier
        # math is identical; the cast is the single dtype-touching point
        self._cast = (
            (lambda a: np.asarray(a)) if raw_payload
            else (lambda a: np.asarray(a, np.int32))
        )
        self.wm = MIN_WATERMARK
        self.fire_cursor: Optional[int] = None
        self.max_seen: Optional[int] = None
        self.min_used: Optional[int] = None
        self.purged_to: Optional[int] = None
        # far-future records held until the ring can take them
        self._future: List[Tuple[np.ndarray, Optional[np.ndarray], np.ndarray]] = []
        self.num_future_held = 0

    # geometry delegates (identical formulas; single source of truth)
    def _j_fired_upto(self, wm: int) -> int:
        return self.p._j_fired_upto(wm)

    def _min_live_slice(self, wm: int) -> int:
        return self.p._min_live_slice(wm)

    def _fire_wm(self, j: int) -> int:
        """Smallest watermark at which window j fires."""
        return self.p.offset + j * self.p.slide_ms + self.p.size_ms - 1

    # ------------------------------------------------------------------
    def push(self, kid: np.ndarray, vals: Optional[np.ndarray], ts: np.ndarray) -> List[_Step]:
        """Normalize one data batch (no watermark advance)."""
        out: List[_Step] = []
        self._append_data(out, kid, vals, ts)
        return out

    def advance(self, wm: int) -> List[_Step]:
        """Normalize one watermark advance into fire-bounded steps.

        Held-back future records are re-injected BETWEEN staged fire steps,
        not after the loop: each staged step's watermark is additionally
        capped so it never passes a held record's slice lifetime before the
        purge frontier has opened ring space and the record was re-ingested
        (a watermark jump past a held slice would reclassify on-time records
        as late — the reference only drops records late on arrival,
        WindowOperator.java:440-446)."""
        out: List[_Step] = []
        if wm <= self.wm:
            return out
        while True:
            target = wm
            held_floor = self._held_min_slice()
            if held_floor is not None:
                # largest watermark at which slice `held_floor` is still
                # live (single-sourced with the pipeline; the shared-
                # partial pipeline widens it to its longest member window)
                cap_wm = self.p._wm_keeping_slice_live(held_floor)
                target = min(wm, max(cap_wm, self.wm))
            step_wm, n_fires = self._stage_fire_step(target)
            out.append(_Step(
                np.empty(0, np.int32), None, np.empty(0, np.int64), step_wm, n_fires
            ))
            self._commit_wm(step_wm, n_fires)
            held_before = self.num_future_held
            self._drain_future(out)
            if step_wm >= wm:
                break
            if step_wm >= target and target < wm:
                # the held-record cap is the binding constraint; progress
                # requires the drain to have re-ingested something. With
                # S - NSB >= slide_slices (guaranteed by the default ring
                # sizing) the drain always succeeds at the cap; the guard
                # below only trips on pathological geometry, where the old
                # behavior (advance past; records counted late) resumes.
                if self.num_future_held >= held_before and \
                        self._held_min_slice() == held_floor:
                    out.extend(self._advance_uncapped(wm))
                    break
        return out

    def _advance_uncapped(self, wm: int) -> List[_Step]:
        """Fallback staged advance without the held-record cap."""
        out: List[_Step] = []
        while self.wm < wm:
            step_wm, n_fires = self._stage_fire_step(wm)
            out.append(_Step(
                np.empty(0, np.int32), None, np.empty(0, np.int64), step_wm, n_fires
            ))
            self._commit_wm(step_wm, n_fires)
            self._drain_future(out)
        return out

    def _stage_fire_step(self, target: int):
        """(step_wm, n_fires) of the next staged advance toward `target`:
        the largest watermark whose fire load fits one step's fire slots.
        The shared-partial normalizer overrides this with the per-spec
        form (each member window's slot budget binds independently)."""
        p = self.p
        n_fires = 0
        step_wm = target
        if self.fire_cursor is not None and self.max_seen is not None:
            cap = min(self._j_fired_upto(target), p._j_newest(self.max_seen))
            n_fires = max(0, cap - self.fire_cursor + 1)
            if n_fires > p.F:
                # stage the advance: fire exactly F windows this step
                cap = self.fire_cursor + p.F - 1
                step_wm = min(target, self._fire_wm(cap))
                n_fires = p.F
        return step_wm, n_fires

    def _held_min_slice(self) -> Optional[int]:
        if not self._future:
            return None
        return min(self.p.slice_span(t)[0] for _, _, t in self._future)

    def pad_step(self, wm: Optional[int] = None) -> _Step:
        """An empty no-op step. `wm` defaults to the normalizer's committed
        watermark but MUST be the enclosing group's last real step watermark
        when steps remain queued behind the group (a pad stamped with a
        future watermark would perform the whole jump in one step and
        exceed fires_per_step)."""
        w = self.wm if wm is None else wm
        return _Step(np.empty(0, np.int32), None, np.empty(0, np.int64), w, 0)

    def end_steps(self) -> List[_Step]:
        """End of input: fire everything still buffered (MAX_WATERMARK)."""
        return self.advance(MAX_WATERMARK - 1)

    # ------------------------------------------------------------------
    def _commit_wm(self, wm: int, n_fires: int) -> None:
        if wm <= self.wm:
            return
        j_hi = self._j_fired_upto(wm)
        if self.fire_cursor is not None and j_hi >= self.fire_cursor:
            self.fire_cursor = j_hi + 1
        new_min_live = self._min_live_slice(wm)
        self.purged_to = (
            new_min_live if self.purged_to is None else max(self.purged_to, new_min_live)
        )
        self.wm = wm

    def _ring_limit(self, smin: int) -> int:
        """First slice beyond the ring for a batch whose oldest live slice
        is `smin` (ring-overflow hold-back): a record at slice s needs the
        full span [oldest-live-slice, s] resident. Before the first
        watermark the oldest live slice is the smallest slice ever ACCEPTED
        (min_used), not this batch's min — otherwise a far-future batch
        would alias cells still owned by earlier data
        (TpuWindowOperator._ring_floor)."""
        floor = smin
        if self.min_used is not None:
            floor = min(floor, self.min_used)
        if self.wm > MIN_WATERMARK:
            floor = max(floor, self._min_live_slice(self.wm))
        if self.purged_to is not None:
            floor = max(floor, self.purged_to)
        return floor + self.p.S - self.p.NSB

    def _append_data(self, out: List[_Step], kid, vals, ts) -> None:
        p = self.p
        n = len(ts)
        if n == 0:
            return
        ts = np.asarray(ts, np.int64)
        plan = p.plan_scalar(ts, self.wm, self._ring_limit)
        if plan is not None:
            # hot path (in-order stream, batch within one slice block):
            # single step, NO column copy, no mask and no group sort — on
            # the fused chain path this forwards the raw source column
            # untouched
            out.append(_Step(
                self._cast(kid),
                None if vals is None else np.asarray(vals),
                ts, self.wm, 0, plan=plan,
            ))
            self._note_data(plan.smin, plan.smax)
            return

        # masked path: late records, hold-back, span split
        s_abs, keep = p.live_slices(ts, self.wm)  # late records: the
        # pipeline drops/counts them itself; they must not affect splits
        if not keep.any():
            out.append(_Step(self._cast(kid), vals, ts, self.wm, 0))
            return

        limit = self._ring_limit(int(s_abs[keep].min()))
        over = keep & (s_abs >= limit)
        if over.any():
            idx = np.flatnonzero(over)
            self._future.append((
                np.asarray(kid)[idx],
                None if vals is None else np.asarray(vals)[idx],
                ts[idx],
            ))
            self.num_future_held += len(idx)
            sel = ~over
            kid, ts = np.asarray(kid)[sel], ts[sel]
            vals = None if vals is None else np.asarray(vals)[sel]
            s_abs, keep = s_abs[sel], keep[sel]
            if len(ts) == 0:
                return
            if not keep.any():
                # only late rows survived the hold-back filter: ship them as
                # a zero-fire step (the pipeline drops+counts them itself)
                out.append(_Step(self._cast(kid), vals, ts, self.wm, 0))
                return

        # slice-span splitting: sub-steps each touching < nsb distinct slices
        smin = int(s_abs[keep].min())
        smax = int(s_abs[keep].max())
        group = np.where(keep, (s_abs - smin) // p.NSB, 0)
        for gval in np.unique(group):
            sel = group == gval
            out.append(_Step(
                self._cast(np.asarray(kid)[sel]),
                None if vals is None else np.asarray(vals)[sel],
                ts[sel],
                self.wm, 0,
            ))
        self._note_data(smin, smax)

    def _drain_future(self, out: List[_Step]) -> None:
        if not self._future:
            return
        fut, self._future = self._future, []
        self.num_future_held = 0
        for kid, vals, ts in fut:
            self._append_data(out, kid, vals, ts)  # still-unfit rows re-buffer

    def note_slices(self, smin: int, smax: int) -> None:
        """Tier-promotion sibling of the pipeline's note_external_slices:
        rows written into the ring outside a pushed step must count as
        resident data for the normalizer's fire capping and ring-floor
        math too, or the two frontier mirrors diverge."""
        self._note_data(smin, smax)

    def _note_data(self, smin: int, smax: int) -> None:
        """Frontier + fire-cursor updates for newly-resident slices (the
        shared-partial normalizer substitutes per-spec cursors)."""
        self.max_seen = smax if self.max_seen is None else max(self.max_seen, smax)
        self.min_used = smin if self.min_used is None else min(self.min_used, smin)
        cand = self.p._j_oldest(smin)
        if self.wm > MIN_WATERMARK:
            cand = max(cand, self._j_fired_upto(self.wm) + 1)
        self.fire_cursor = cand if self.fire_cursor is None else min(self.fire_cursor, cand)

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        return {
            "wm": self.wm,
            "fire_cursor": self.fire_cursor,
            "max_seen": self.max_seen,
            "min_used": self.min_used,
            "purged_to": self.purged_to,
            "future": [
                (k.tolist(), None if v is None else v.tolist(), t.tolist())
                for k, v, t in self._future
            ],
            # payload dtypes of the held columns: the raw-payload cast is
            # dtype-free np.asarray, and a tolist() round-trip would promote
            # float32 columns to float64 — tripping the fused pipeline's
            # fixed-geometry check on the first post-restore dispatch
            "future_kdt": [str(np.asarray(k).dtype) for k, _v, _t in self._future],
        }

    def restore(self, snap: dict) -> None:
        self.wm = snap["wm"]
        self.fire_cursor = snap["fire_cursor"]
        self.max_seen = snap["max_seen"]
        self.min_used = snap.get("min_used")
        self.purged_to = snap["purged_to"]
        kdts = snap.get("future_kdt")  # absent in pre-fusion snapshots
        self._future = [
            (self._cast(k) if kdts is None
             else np.asarray(k, np.dtype(kdts[i])),
             None if v is None else np.asarray(v, np.float32),
             np.asarray(t, np.int64))
            for i, (k, v, t) in enumerate(snap["future"])
        ]
        self.num_future_held = sum(len(t) for _, _, t in self._future)


class SharedStepNormalizer(StepNormalizer):
    """StepNormalizer over a SharedWindowPipeline (shared partials): one
    shared ingest/ring frontier, per-window-spec fire cursors, each member
    window's fire-slot budget binding the staged advance independently."""

    def __init__(self, pipe, raw_payload: bool = False):
        super().__init__(pipe, raw_payload)
        self.fire_cursors: List[Optional[int]] = [None] * len(pipe.specs)

    def _note_data(self, smin: int, smax: int) -> None:
        p = self.p
        self.max_seen = smax if self.max_seen is None else max(self.max_seen, smax)
        self.min_used = smin if self.min_used is None else min(self.min_used, smin)
        for i in range(len(p.specs)):
            cand = p._spec_j_oldest(i, smin)
            if self.wm > MIN_WATERMARK:
                cand = max(cand, p._spec_j_fired_upto(i, self.wm) + 1)
            cur = self.fire_cursors[i]
            self.fire_cursors[i] = cand if cur is None else min(cur, cand)

    def _stage_fire_step(self, target: int):
        p = self.p
        if self.max_seen is None:
            return target, 0
        step_wm = target
        Fp = p.F_per_spec
        for i, spec in enumerate(p.specs):
            cur = self.fire_cursors[i]
            if cur is None:
                continue
            cap = min(p._spec_j_fired_upto(i, target),
                      self.max_seen // spec.sl)
            if cap - cur + 1 > Fp:
                step_wm = min(step_wm, p._spec_fire_wm(i, cur + Fp - 1))
        # fire counts settle AFTER the binding spec lowered step_wm
        # (n_i(wm) is monotone in wm, so every spec fits its budget there)
        total = 0
        for i, spec in enumerate(p.specs):
            cur = self.fire_cursors[i]
            if cur is None:
                continue
            cap = min(p._spec_j_fired_upto(i, step_wm),
                      self.max_seen // spec.sl)
            total += max(0, cap - cur + 1)
        return step_wm, total

    def _commit_wm(self, wm: int, n_fires: int) -> None:
        if wm <= self.wm:
            return
        p = self.p
        for i in range(len(p.specs)):
            j_hi = p._spec_j_fired_upto(i, wm)
            cur = self.fire_cursors[i]
            if cur is not None and j_hi >= cur:
                self.fire_cursors[i] = j_hi + 1
        new_min_live = p._min_live_slice(wm)   # min over specs: the
        # longest member window holds every slice it still needs
        self.purged_to = (
            new_min_live if self.purged_to is None
            else max(self.purged_to, new_min_live)
        )
        self.wm = wm

    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["fire_cursors"] = list(self.fire_cursors)
        return snap

    def restore(self, snap: dict) -> None:
        super().restore(snap)
        self.fire_cursors = list(snap["fire_cursors"])


@inflight_ring("_inflight", drained_by="_resolve_inflight")
class FusedWindowOperator:
    """Operator-boundary adapter: same surface as TpuWindowOperator, fused
    superbatch execution underneath. One outstanding dispatch is kept in
    flight (resolve of dispatch i overlaps device execution of i+1).

    With `assigners` (shared partials, graph/window_sharing.py) the
    operator runs N correlated window shapes over ONE shared-granule ring
    and routes each member's emissions into its own output lane
    (`drain_spec_output`); requires the traced-chain prologue (dense
    device keying), and the state tier does not apply."""

    def __init__(
        self,
        assigner: Optional[WindowAssigner],
        aggregate,
        *,
        key_capacity: int = 1 << 12,
        superbatch_steps: int = 32,
        dense_int_keys: bool = False,
        num_slices: Optional[int] = None,
        nsb: int = 4,
        fires_per_step: int = 4,
        out_rows: int = 256,
        chunk: int = 4096,
        columnar_output: bool = False,
        prologue=None,
        mesh=None,
        tier=None,
        assigners=None,
        mesh_local_combine: bool = False,
        mesh_skew_routing: bool = False,
        mesh_key_groups: int = 0,
        latency: Optional[LatencySpec] = None,
    ):
        self.agg = resolve(aggregate)
        if self.agg is None:
            raise ValueError(f"aggregate {aggregate!r} has no device form")
        # million-key state plane (state/tier_manager.py): a TierConfig
        # bounds the RESIDENT key set to hot_key_capacity HBM rows; the
        # vocabulary demotes/promotes rows through the cold tier and the
        # emission merges both tiers. Host-keyed path only — a traced
        # chain's dense device keying has no host vocabulary to evict from.
        if tier is not None:
            if prologue is not None:
                raise ValueError(
                    "state.tier.enabled needs the host key dictionary; "
                    "a traced device chain keys on device (dense ids)")
            key_capacity = tier.hot_key_capacity
            dense_int_keys = False
            # dense ids are RECYCLED under eviction: packed columnar
            # output would alias keys downstream
            columnar_output = False
        # whole-graph fusion (graph/fusion.py): with a TracedPrologue the
        # pipeline compiles chain transforms + key/value extraction into the
        # superscan itself; steps then carry RAW source columns and keying
        # is dense-int on device (no host key dictionary on the hot path)
        self.prologue = prologue
        self.mesh = mesh
        self._construction_key_capacity = key_capacity
        self.spec_outputs = None
        if assigners is not None:
            if prologue is None:
                raise ValueError(
                    "shared-partial windows run the traced-chain path "
                    "(dense device keying); a prologue is required")
            if tier is not None:
                raise ValueError(
                    "state.tier does not apply to the shared-partial path")
            self.spec_outputs = [[] for _ in assigners]
        if mesh is not None:
            # multichip SPMD (parallel.mesh.*): same operator surface, the
            # dispatch runs sharded over the mesh with the keyBy shuffle as
            # an in-scan all-to-all; snapshots stay canonical [K, S], so
            # this operator checkpoints/restores across mesh sizes
            from flink_tpu.parallel.sharded_superscan import (
                ShardedFusedPipeline,
            )

            self.pipe = ShardedFusedPipeline(
                mesh, assigner, self.agg,
                key_capacity=key_capacity, num_slices=num_slices, nsb=nsb,
                fires_per_step=fires_per_step, out_rows=out_rows,
                chunk=chunk, prologue=prologue, assigners=assigners,
                # skew-adaptive exchange (parallel.mesh.local-combine /
                # .skew-rebalance): pure perf switches over the same exact
                # results — see docs/multichip.md
                local_combine=mesh_local_combine,
                skew_routing=mesh_skew_routing,
                num_key_groups=mesh_key_groups,
            )
        elif assigners is not None:
            from flink_tpu.runtime.fused_window_pipeline import (
                SharedWindowPipeline,
            )

            self.pipe = SharedWindowPipeline(
                assigners, self.agg,
                key_capacity=key_capacity, num_slices=num_slices, nsb=nsb,
                fires_per_step=fires_per_step, out_rows=out_rows, chunk=chunk,
                prologue=prologue,
            )
        else:
            self.pipe = FusedWindowPipeline(
                assigner, self.agg,
                key_capacity=key_capacity, num_slices=num_slices, nsb=nsb,
                fires_per_step=fires_per_step, out_rows=out_rows, chunk=chunk,
                prologue=prologue,
            )
        self.T = superbatch_steps
        self.keydict = KeyDictionary(dense_int_keys or prologue is not None)
        self.tier = None
        if tier is not None:
            from flink_tpu.state.tier_manager import TieredStateManager

            self.tier = TieredStateManager(self.agg, self.pipe.S, tier)
            self.tier.attach_device(self.pipe.gather_key_rows,
                                    self.pipe.clear_key_rows,
                                    self.pipe.write_cells)
        self.norm = (
            SharedStepNormalizer(self.pipe, raw_payload=True)
            if assigners is not None
            else StepNormalizer(self.pipe, raw_payload=prologue is not None)
        )
        self._steps: List[_Step] = []
        # bounded in-flight dispatch ring: (DeferredEmissions, wm,
        # purged_to) entries, resolved FIFO. Depth 1 (the default) is
        # byte-identical to the historical single `_inflight` slot —
        # dispatch N+1 enqueues, THEN N resolves; latency mode deepens the
        # ring so N+1 stages and launches while N's copies land.
        self._inflight: Deque[tuple] = deque()
        self._max_inflight = 1
        # latency mode (execution.latency.target-ms): the adaptive rung
        # controller + donated carries + streaming readback. None keeps
        # every hot-path decision identical to throughput mode.
        self.latency = latency
        self._controller: Optional[SuperbatchController] = None
        self._ladder_geoms: set = set()   # distinct dispatch depths seen
        if latency is not None and latency.target_ms > 0:
            self._controller = SuperbatchController(
                full_steps=superbatch_steps,
                target_ms=latency.target_ms,
                floor_steps=latency.floor_steps,
                min_dwell_ms=latency.min_dwell_ms,
                hysteresis_pct=latency.hysteresis_pct,
            )
            self._max_inflight = max(int(latency.max_inflight), 1)
            self.pipe.donate_carry = True
            if mesh is None and latency.readback_steps > 0:
                # streaming fire readback is single-chip XLA only:
                # splitting the mesh dispatch would multiply the per-step
                # all-to-all collective count, so the mesh keeps
                # span-granular readback (docs/latency.md)
                self.pipe.readback_steps = int(latency.readback_steps)
        # one FireBlock per fire (runtime/fire_block.py), never rows
        self.output: List[FireBlock] = []
        self.emitted_watermark = MIN_WATERMARK
        self.current_watermark = MIN_WATERMARK
        self.columnar_output = columnar_output
        self._needs_value = any(f.source == VALUE for f in self.agg.fields)

    # ------------------------------------------------------------------
    def process_record(self, key, value, timestamp: int) -> None:
        self.process_batch(
            np.asarray([key]),
            np.asarray([0.0 if value is None else value], np.float32),
            np.asarray([timestamp], np.int64),
        )

    def process_batch(self, keys: np.ndarray, values: np.ndarray,
                      timestamps: np.ndarray) -> None:
        if self.prologue is not None:
            raise RuntimeError(
                "this operator runs a traced chain prologue; feed it raw "
                "columns via process_raw_batch"
            )
        if len(timestamps) == 0:
            return
        if self.tier is not None:
            self._process_batch_tiered(np.asarray(keys), values,
                                       np.asarray(timestamps, np.int64))
            return
        clock = self.stage_clock
        with stage(clock, "keys.lookup"):
            ids, required = self.keydict.lookup_or_insert(np.asarray(keys))
            self.pipe.ensure_key_capacity(required)
        vals = np.asarray(values, np.float32) if self._needs_value else None
        with stage(clock, "normalize"):
            steps = self.norm.push(ids.astype(np.int32), vals,
                                   np.asarray(timestamps, np.int64))
        self._push_steps(steps)
        self._maybe_dispatch()

    # ------------------------------------------------------------------
    # tiered-state path (state/tier_manager.py)
    # ------------------------------------------------------------------
    def _tier_span(self):
        """(floor, device_hi, ring_limit): the live slice span the tier
        may move rows within. floor mirrors the normalizer's ring-floor
        math (min ever used, clamped by the purge frontier, cold touches
        included); ring_limit = floor + S - NSB is the hold-back bound —
        a promotion writing past it would alias ring positions earlier
        data still owns."""
        p = self.pipe
        touched = self.tier._touched
        cands = [x for x in (p.min_used_slice,
                             min(touched) if touched else None)
                 if x is not None]
        if not cands:
            return None, None, None
        lo = min(cands)
        if p.purged_to is not None:
            lo = max(lo, p.purged_to)
        hi = p.max_seen_slice if p.max_seen_slice is not None else lo
        return lo, hi, lo + p.S - p.NSB

    def _process_batch_tiered(self, keys: np.ndarray, values,
                              ts: np.ndarray) -> None:
        tier = self.tier
        s_abs = np.asarray(self.pipe._slice_of(ts))
        wm = self.norm.wm
        late = (s_abs < self.norm._min_live_slice(wm)
                if wm > MIN_WATERMARK else np.zeros(len(ts), bool))
        # an eviction reassigns dense ids — every buffered/in-flight step
        # (and its pending emissions, which map ids back to keys at
        # resolve) must land BEFORE the vocabulary moves; the check
        # over-approximates, so a flush can be spurious but never missed
        if tier.vocab.would_evict(keys):
            self.flush_all()
        vals = (np.asarray(values, np.float32)
                if self._needs_value and values is not None else None)
        with stage(self.stage_clock, "keys.lookup"):
            routed = tier.route(keys, s_abs, vals, np.asarray(late, bool))
        if routed.demotions or routed.promotions:
            lo, hi, limit = self._tier_span()
            tier.apply_demotions(routed.demotions, lo, hi)
            span = tier.apply_promotions(routed.promotions, lo,
                                         None if limit is None
                                         else limit - 1, limit)
            if span is not None:
                # promoted rows are resident data the planner never saw
                # as steps: both frontier mirrors must account for them
                # or windows covering only promoted slices never fire
                self.pipe.note_external_slices(*span)
                self.norm.note_slices(*span)
        tier.journal_vocab_ops()
        ids = routed.ids
        live_hot = (ids >= 0) & ~np.asarray(late, bool)
        if live_hot.any():
            tier.note_hot_cells(ids[live_hot].astype(np.int64),
                                s_abs[live_hot])
        with stage(self.stage_clock, "normalize"):
            steps = self.norm.push(ids.astype(np.int32), vals, ts)
        self._push_steps(steps)
        self._maybe_dispatch()

    def process_raw_batch(self, values: np.ndarray,
                          timestamps: np.ndarray) -> None:
        """Whole-graph fusion ingest: raw source columns, untouched by any
        host transform — the traced prologue (chain + key/value extraction)
        runs inside the compiled dispatch."""
        if len(timestamps) == 0:
            return
        with stage(self.stage_clock, "normalize"):
            steps = self.norm.push(values, None,
                                   np.asarray(timestamps, np.int64))
        self._push_steps(steps)
        self._maybe_dispatch()

    def process_watermark(self, watermark: int) -> None:
        if watermark <= self.current_watermark:
            return
        self.current_watermark = watermark
        with stage(self.stage_clock, "normalize"):
            steps = self.norm.advance(watermark)
        # a single-step advance rides the preceding data step (the pipeline
        # fires after ingesting step t's batch, so batch-then-advance in one
        # step is exactly the executor's batch-then-watermark order)
        if (
            steps
            and self._steps
            and self._steps[-1].n_fires == 0
            and len(steps[0].ts) == 0
        ):
            self._steps[-1].wm = steps[0].wm
            self._steps[-1].n_fires = steps[0].n_fires
            steps = steps[1:]
        self._push_steps(steps)
        if watermark >= MAX_WATERMARK - 1:
            self.flush_all()
        else:
            self._maybe_dispatch()

    def advance_processing_time(self, time: int) -> None:
        pass  # event-time only

    # ------------------------------------------------------------------
    def _push_steps(self, steps: List[_Step]) -> None:
        """Append planner-safe steps + feed the latency controller's
        windowed arrival estimate (watermark-only steps count: they occupy
        superbatch slots, so they are part of the fill rate)."""
        self._steps.extend(steps)
        if self._controller is not None and steps:
            self._controller.observe(len(steps))

    def _dispatch_target(self) -> int:
        """Steps a full dispatch cuts at: the adaptive rung under latency
        mode, the fixed span otherwise."""
        if self._controller is None:
            return self.T
        return self._controller.steps()

    def _maybe_dispatch(self) -> None:
        target = self._dispatch_target()
        while len(self._steps) >= target:
            self._dispatch(self._take_group(target=target))
            target = self._dispatch_target()

    def flush_all(self) -> None:
        """Dispatch every buffered step and resolve all in-flight output.
        Tail groups pad to the next power of two instead of T, so snapshots
        mid-superbatch compile at most log2(T) extra executable shapes
        instead of paying a full T-step dispatch per checkpoint."""
        while len(self._steps) >= self.T:
            self._dispatch(self._take_group())
        while self._steps:
            self._dispatch(self._take_group(tail=True))
        self._resolve_inflight()

    def _take_group(self, tail: bool = False,
                    target: Optional[int] = None) -> List[_Step]:
        limit = self.T if target is None else target
        group: List[_Step] = []
        fires = 0
        while self._steps and len(group) < limit:
            s = self._steps[0]
            if fires + s.n_fires > self.pipe.R and group:
                break  # out_rows budget: cut the dispatch early
            fires += s.n_fires
            group.append(self._steps.pop(0))
        target = (1 << max(len(group) - 1, 0).bit_length()) if tail else limit
        # pads carry the LAST REAL step's watermark, not the normalizer's
        # committed one — steps still queued behind an early cut have lower
        # watermarks, and a future-stamped pad would do the whole jump in
        # one step and blow fires_per_step
        pad_wm = group[-1].wm if group else None
        while len(group) < target:
            group.append(self.norm.pad_step(pad_wm))  # bounded executable shapes
        return group

    def _dispatch(self, group: List[_Step]) -> None:
        wms = [s.wm for s in group]
        clock = self.stage_clock
        seq = 0
        if clock is not None:
            # this dispatch's number: its dispatch span, the pipeline's
            # stage.fill / stage.put inside it, and later its resolve and
            # emit spans all carry it
            clock.seq = seq = clock.seq + 1
        # the enqueue: everything of the pipeline's call that is not
        # staging; CompileTracker.call names the program on the span
        with dispatch_stage(clock, "dispatch"):
            # under a traced prologue s.kid is the raw record, s.vals None
            d = self.pipe.process_superbatch(
                [(s.kid, s.vals, s.ts, s.plan) for s in group], wms,
                defer=True)
        if self._controller is not None:
            self._ladder_geoms.add(len(group))
        # the purge frontier as of THIS dispatch's staging: cold-tier rows
        # below it may only be deleted after this dispatch's emissions
        # have resolved (they read the cold rows of the windows that just
        # fired) — a lagged frontier each ring entry carries to its own
        # resolve, so purge_below always advances with resolution order
        self._inflight.append((d, group[-1].wm, self.pipe.purged_to, seq))
        # depth 1 reproduces the historical slot byte-for-byte: the new
        # dispatch enqueues first, THEN the previous one resolves
        while len(self._inflight) > self._max_inflight:
            self._resolve_oldest()

    #: the runner's stage clock (metrics/task_io.py); None = off
    stage_clock = None
    #: the dispatch whose fires are being emitted: every FireBlock carries
    #: it on, so the stages a block meets downstream share its `seq=`
    _firing_seq = 0

    def attach_stage_clock(self, clock) -> None:
        self.stage_clock = clock
        self.pipe.attach_stage_clock(clock)

    # emission-latency plane: set by the runner when the plane is on;
    # stamped at the DEFERRED RESOLVE below — the only point where a
    # fired window's rows become host-visible — never at dispatch
    emission_tracker = None

    def _resolve_inflight(self) -> None:
        """Drain the whole in-flight ring (FIFO). Every barrier that needs
        the operator quiescent — flush_all (and thus snapshot), routing
        swaps, tier evictions — lands here, so exactly-once capture points
        see an empty ring regardless of its configured depth."""
        while self._inflight:
            self._resolve_oldest()

    def _resolve_oldest(self) -> None:
        d, wm, purged_to, seq = self._inflight.popleft()
        tracker = self.emission_tracker
        clock = self.stage_clock
        with stage(clock, "resolve", seq):
            fired = d.resolve()
        if clock is not None:
            clock.d2h_bytes += d.nbytes
        self._firing_seq = seq
        for window, counts, fields in fired:
            if tracker is not None:
                w = window[1] if type(window) is tuple else window
                tracker.record_fire(w.end)
            with stage(clock, "emit", seq):
                self._emit(window, counts, fields)
        if wm > self.emitted_watermark:
            self.emitted_watermark = wm
        if self.tier is not None:
            self.tier.purge_below(purged_to)

    def _emit(self, window, counts, fields) -> None:
        if self.spec_outputs is not None:
            # shared partials: the pipeline tags each fire with its member
            # window spec; route the emission to that member's output lane
            spec, win = window
            self._emit_dense_rows(win, counts, fields,
                                  self.spec_outputs[spec])
            return
        if self.tier is not None:
            self._emit_tiered(window, counts, fields)
            return
        if self.prologue is not None:
            self._emit_dense_rows(window, counts, fields, self.output)
            return
        counts = np.asarray(counts)[: len(self.keydict)]
        live = np.flatnonzero(counts > 0)
        if live.size == 0:
            return
        self._emit_keydict_rows(window, counts, fields, live)

    def _emit_dense_rows(self, window, counts, fields, lane: list) -> None:
        """Dense-device-keying emission (traced prologue): the emitted key
        IS the id the traced selector produced — every capacity row may be
        live. `lane` selects the output lane (shared partials route per
        member window spec)."""
        counts = np.asarray(counts)
        live = np.flatnonzero(counts > 0)
        if live.size == 0:
            return
        fdict: Dict[str, Any] = {
            f.name: (counts if f.source == ONE
                     else np.asarray(fields[f.name]))
            for f in self.agg.fields
        }
        result = np.asarray(self.agg.extract(fdict))
        self._append_fire(lane, window, live, result[live])

    def _emit_keydict_rows(self, window, counts, fields, live) -> None:
        fdict: Dict[str, Any] = {}
        for f in self.agg.fields:
            if f.source == ONE:
                fdict[f.name] = counts
            else:
                fdict[f.name] = np.asarray(fields[f.name])[: len(self.keydict)]
        result = np.asarray(self.agg.extract(fdict))
        self._append_fire(self.output, window, live, result[live],
                          self.keydict.keys_for)

    def _append_fire(self, lane: list, window, live, results,
                     keys_of=None) -> None:
        """One fire onto its output lane as ONE block of columns
        (runtime/fire_block.py): `live` are the dense ids that fired,
        ascending, `results` their column; `keys_of` maps ids to the
        emitted keys where the id is not the key itself. Nothing here runs
        per row: whoever needs rows builds them from the block."""
        ts = window.max_timestamp()
        if self.columnar_output:
            # one packed row per fire: (window, dense key ids, values) —
            # downstream sees O(1) rows regardless of key cardinality
            # (map ids back through .keydict when raw keys are needed)
            block = FireBlock(window, None, [(window, live, results)], ts,
                              self._firing_seq)
        else:
            block = FireBlock(
                window, live if keys_of is None else keys_of(live), results,
                ts, self._firing_seq)
        lane.append(block)
        self._count_fire(live.size)

    def _count_fire(self, rows: int) -> None:
        if self.stage_clock is not None:
            self.stage_clock.rows_emitted += int(rows)
            self.stage_clock.fire_blocks += 1

    def _emit_tiered(self, window, counts, fields) -> None:
        """Emission merging both tiers: resident keys fire from the device
        rows, cold keys from the cold store. A key whose data is SPLIT
        across tiers for this window (partial promotion left far-future
        rows cold) combines per the field scatter ops before extraction,
        so placement can never change a result. Resident rows first, in
        ascending id order, then the cold-only keys, as one block."""
        p = self.pipe
        j = (window.start - p.offset) // p.slide_ms
        slice_range = range(j * p.sl, j * p.sl + p.spw)
        counts = np.asarray(counts).astype(np.int64).copy()
        vals = {f.name: np.asarray(fields[f.name]).copy()
                for f in self.agg.fields if f.source != ONE}
        cold = self.tier.cold_fire(slice_range)
        combine = {"add": lambda a, b: a + b, "min": min, "max": max}
        extras: List[tuple] = []   # (key, counts, {field: value}) cold-only
        if cold is not None:
            ckids, cfields, ccounts = cold
            vocab = self.tier.vocab
            for i, cid in enumerate(ckids):
                key = vocab.key_of_cold_id(int(cid))
                hid = None if key is None else vocab.resident_id(key)
                if hid is not None:
                    counts[hid] += int(ccounts[i])
                    for f in self.agg.fields:
                        if f.source == ONE:
                            continue
                        vals[f.name][hid] = combine[f.scatter](
                            vals[f.name][hid].item(),
                            cfields[f.name][i].item())
                elif key is not None:
                    extras.append((key, int(ccounts[i]),
                                   {n: cfields[n][i] for n in cfields}))
        live = np.flatnonzero(counts > 0)
        keys: List[Any] = []
        results: List[Any] = []
        if live.size:
            fdict = {f.name: (counts if f.source == ONE else vals[f.name])
                     for f in self.agg.fields}
            result = np.asarray(self.agg.extract(fdict))
            keys += map(self.tier.vocab.key_of_id, live.tolist())
            results += result[live].tolist()
        if extras:
            e_counts = np.asarray([e[1] for e in extras], np.int64)
            fdict_e = {
                f.name: (e_counts if f.source == ONE
                         else np.asarray([e[2][f.name] for e in extras],
                                         np.dtype(f.dtype)))
                for f in self.agg.fields
            }
            keys += [e[0] for e in extras]
            results += np.asarray(self.agg.extract(fdict_e)).tolist()
        if keys:
            self.output.append(
                FireBlock(window, keys, results, window.max_timestamp(),
                          self._firing_seq))
            self._count_fire(len(keys))

    def drain_blocks(self) -> List[FireBlock]:
        """The fires since the last drain, one block each: the runner's
        hand-over (executor.py builds the downstream batch from them)."""
        out = self.output
        self.output = []
        return out

    def drain_spec_blocks(self, spec: int) -> List[FireBlock]:
        """Shared partials: drain one member window's output lane (the
        shared runner routes lane i to member i's downstream edges)."""
        out = self.spec_outputs[spec]
        self.spec_outputs[spec] = []
        return out

    def drain_output(self) -> List[Tuple[Any, Any, Any, int]]:
        """The same fires as `(key, window, result, ts)` rows of Python
        scalars, for callers that want rows."""
        return rows_of(self.drain_blocks())

    def drain_spec_output(self, spec: int) -> List[Tuple[Any, Any, Any, int]]:
        return rows_of(self.drain_spec_blocks(spec))

    def query_state_for(self, key) -> Dict[int, Dict[str, Any]]:
        """Point lookup (queryable state): {abs_slice: {field..., count}}
        for one key, folding device ring cells, buffered steps, and
        held-back future records into one consistent view."""
        if self.prologue is not None:
            raise RuntimeError(
                "queryable state is unavailable on the fused chain path: "
                "buffered steps hold raw pre-transform columns, so a "
                "consistent per-key view would need the traced UDFs on host"
            )
        if self.tier is not None:
            raise RuntimeError(
                "queryable state is unavailable on the tiered path: a "
                "key's cells may be split across the HBM ring and the "
                "cold store mid-movement; read the window emissions "
                "instead"
            )
        kid = self.keydict.lookup(key)
        if kid is None:
            return {}
        pipe = self.pipe
        # canonical [K, S] view: the sharded pipeline holds [n, K_local, S]
        # and the contiguous key ranges make the reshape exact (a no-op on
        # the single-chip layout)
        count = np.asarray(pipe._count).reshape(pipe.K, pipe.S)[kid]
        acc = {k: np.asarray(v).reshape(pipe.K, pipe.S)[kid]
               for k, v in pipe._state.items()}
        slices: Dict[int, Dict[str, Any]] = {}
        lo = pipe.purged_to if pipe.purged_to is not None else pipe.min_used_slice
        hi = pipe.max_seen_slice
        if lo is not None and hi is not None:
            for s in range(lo, hi + 1):
                pos = s % pipe.S
                if count[pos] > 0:
                    entry = {name: arr[pos].item() for name, arr in acc.items()}
                    entry["count"] = int(count[pos])
                    slices[s] = entry
        combine = {"add": lambda a, b: a + b, "min": min, "max": max}
        pending = [(s.kid, s.vals, s.ts) for s in self._steps] + self.norm._future
        for kid_arr, val_arr, ts_arr in pending:
            sel = np.flatnonzero(np.asarray(kid_arr) == kid)
            for i in sel:
                s = int((int(ts_arr[i]) - pipe.offset) // pipe.g)
                entry = slices.setdefault(s, {"count": 0})
                entry["count"] = entry.get("count", 0) + 1
                for f in self.agg.fields:
                    if f.source != VALUE:
                        continue
                    v = float(val_arr[i]) if val_arr is not None else 1.0
                    entry[f.name] = combine[f.scatter](entry.get(f.name, f.identity), v)
        return slices

    # ------------------------------------------------------------------
    @property
    def num_late_records_dropped(self) -> int:
        return self.pipe.num_late_records_dropped

    # -- device-plane observability ------------------------------------
    def attach_device_stats(self, tracker, phase_counters: bool = True) -> None:
        """Wire a CompileTracker (metrics/device_stats.py) around every
        superscan dispatch and thread the ingest/fire/purge phase counters
        through the compiled scan carry. Must be called before the first
        batch — the phase flag is part of the executable cache key."""
        self.pipe.attach_device_stats(tracker, phase_counters=phase_counters)

    def phase_totals(self) -> Dict[str, int]:
        """Cumulative per-phase superscan step counters (resolved
        dispatches only): records ingested, fire slots executed, steps
        that purged — where a laggard kernel's device time goes — and
        steps whose live records lay in one slice (a dispatch's pad steps
        among them; on a mesh summed over the shards): the steps the
        matmul ingest contracts K segments for, not K * NSB."""
        t = self.pipe.phase_totals
        return {"ingestRecords": int(t[0]), "fireSteps": int(t[1]),
                "purgeSteps": int(t[2]), "oneSliceSteps": int(t[3])}

    def key_loads(self):
        """Device-resident per-key record counts for the key-stats fold."""
        return self.pipe.key_loads()

    def per_device_key_loads(self):
        """[n, K_local] per-device local loads on the mesh path (None on a
        single chip): the per-device skew fold's input — a globally even
        key histogram can still pile every hot key-group on one device."""
        fn = getattr(self.pipe, "per_device_key_loads", None)
        return fn() if fn is not None else None

    def per_device_exchange(self):
        """[n, 2] records the mesh exchange delivered to each device and
        lanes it ingested for them (None on a single chip, and on the mesh
        until a traced-chain dispatch has resolved)."""
        fn = getattr(self.pipe, "per_device_exchange", None)
        return fn() if fn is not None else None

    def mesh_devices(self) -> int:
        """Devices this operator's state is sharded over (1 = single chip)."""
        return int(getattr(self.pipe, "n", 1))

    # -- skew-aware key-group routing (parallel.mesh.skew-rebalance) ----
    def routing_version(self):
        """Version of the mesh routing table (None off the mesh or with
        static routing)."""
        fn = getattr(self.pipe, "routing_version", None)
        return fn() if callable(fn) else None

    def routing_payload(self):
        """/jobs/:id/device routing block (None without a table)."""
        fn = getattr(self.pipe, "routing_payload", None)
        return fn() if callable(fn) else None

    def mesh_group_loads(self):
        """Per-key-group resident loads [G] — the rebalancer's decision
        input; None without a routing table."""
        fn = getattr(self.pipe, "mesh_group_loads", None)
        return fn() if callable(fn) else None

    def set_routing_assignment(self, assign) -> int:
        """Apply a new key-group -> device map at an operator-quiescent
        point: any in-flight dispatch resolves FIRST (its fire rows were
        produced under the old table and must canonicalize under it), then
        the table swaps and the device rows re-lay. Exactly-once by
        construction — canonical state and cursors never change."""
        self._resolve_inflight()
        return self.pipe.set_routing_assignment(assign)

    def mesh_capacity(self) -> int:
        """The key capacity the mesh clamp used at CONSTRUCTION time — a
        rescale-target pre-check must clamp against this, not the grown
        pipe.K: a rebuilt operator starts from this capacity again (the
        grown snapshot re-adopts K at restore), so a target reachable only
        under the grown K would tear the job down for a no-op rebuild."""
        return int(self._construction_key_capacity)

    def key_stats_ready(self) -> bool:
        """O(1) host probe: has any superbatch dispatch landed data in the
        device ring yet? (Steps buffer host-side first — a key-stats fold
        before the first dispatch would read an empty ring.)"""
        return self.pipe.max_seen_slice is not None

    def state_row_bytes(self) -> int:
        return self.pipe.state_row_bytes()

    # -- observability gauges ------------------------------------------
    def state_bytes(self) -> int:
        """HBM footprint of the slice-ring arrays (0 until the pipeline's
        first dispatch materializes them)."""
        state = getattr(self.pipe, "_state", None) or {}
        n = sum(int(getattr(a, "nbytes", 0)) for a in state.values())
        n += int(getattr(getattr(self.pipe, "_count", None), "nbytes", 0) or 0)
        return n

    def ring_counters(self) -> Dict[str, int]:
        """The device-resident slice rings of this operator, for its block
        of `metrics["device"]`: `valueFields`, the aggregate's VALUE fields
        (each a ring of its own beside the count's; 0 for a count), and
        `ringBytes`, the bytes of all of them (K x S x 4 a ring)."""
        return {"valueFields": sum(f.source == VALUE for f in self.agg.fields),
                "ringBytes": self.state_bytes()}

    def prologue_counters(self) -> Dict[str, int]:
        """The traced prologue's gathers, for this operator's block of
        `metrics["device"]`: `prologueGathersLowered`, those done as a
        one-hot contraction over a small constant table
        (`ops/table_lookup.py`), and `prologueGathersKept`, those left a
        gather — as its program was traced (0 / 0 without a prologue or
        before the first trace)."""
        lowered, kept = (self.prologue.gathers() if self.prologue is not None
                         else (0, ()))
        return {"prologueGathersLowered": lowered,
                "prologueGathersKept": len(kept)}

    def state_key_count(self) -> int:
        if self.tier is not None:
            return self.tier.vocab.vocab_size
        return len(self.keydict)

    # -- state-tier observability --------------------------------------
    def tier_gauges(self):
        """The tier gauge family (vocabSize/residentKeys/evictions/
        promotions/spilledBytes/changelogBytes/tierHotFillRatio), or None
        when tiering is off — the runner registers one gauge per key."""
        return None if self.tier is None else self.tier.gauges()

    def tier_payload(self):
        """/jobs/:id/device tier block (None when tiering is off)."""
        return None if self.tier is None else self.tier.payload()

    # -- latency-mode observability ------------------------------------
    def latency_gauges(self):
        """The latency-mode controller gauge family, or None when the mode
        is off — registered by the runner next to the tier family, folded
        MAX across shards (cluster._LATENCY_CONTROLLER_GAUGES), surfaced
        in /jobs/:id/device and the /jobs/:id/latency report."""
        if self._controller is None:
            return None
        return {
            "latencyModeActive": 1,
            "currentBatchRung": int(self._controller.current_steps()),
            "inflightDepth": len(self._inflight),
            "ladderRecompiles": len(self._ladder_geoms),
        }

    def _reset_dispatch_ring(self) -> None:
        """Restore/rebuild quiescence: discard unresolved in-flight
        handles (their fires re-run from the restored state) and re-hold
        the controller's full-span rung — pre-failure arrival samples
        describe a stream position that no longer exists."""
        self._inflight.clear()
        if self._controller is not None:
            self._controller.reset()

    def _pack_output(self):
        """Undrained emissions ride every checkpoint; in the tiered
        incremental path they dominate the per-interval delta, so scalar
        numeric rows pack columnar (~3x smaller pickled than a list of
        (key, TimeWindow, value, ts) tuples). Non-scalar rows fall back
        to the raw list."""
        rows = rows_of(self.output)
        from flink_tpu.core.time import TimeWindow as _TW

        if rows and all(
                isinstance(r[1], _TW) and np.isscalar(r[2]) for r in rows):
            return {
                "packed": True,
                "keys": [r[0] for r in rows],
                "starts": np.asarray([r[1].start for r in rows], np.int64),
                "ends": np.asarray([r[1].end for r in rows], np.int64),
                "vals": np.asarray([r[2] for r in rows]),
                "ts": np.asarray([r[3] for r in rows], np.int64),
            }
        return {"packed": False, "rows": list(rows)}

    @staticmethod
    def _unpack_output(packed) -> list:
        if not packed.get("packed"):
            return list(packed["rows"])
        from flink_tpu.core.time import TimeWindow as _TW

        return [
            (k, _TW(int(s), int(e)), v.item(), int(t))
            for k, s, e, v, t in zip(
                packed["keys"], packed["starts"], packed["ends"],
                packed["vals"], packed["ts"])
        ]

    def _tier_meta(self) -> dict:
        """Host-side stream position + operator state that rides every
        tiered checkpoint (full or incremental): what restore_changelog
        overlays on the reconstructed arrays."""
        p = self.pipe
        return {
            "watermark": p.watermark,
            "fire_cursor": p.fire_cursor,
            "purged_to": p.purged_to,
            "min_used_slice": p.min_used_slice,
            "max_seen_slice": p.max_seen_slice,
            "num_late_dropped": p.num_late_records_dropped,
            "norm": self.norm.snapshot(),
        }

    def _envelope(self) -> dict:
        """The transient operator surface that rides the checkpoint
        ENVELOPE, not the state changelog: resolved-but-undrained
        emissions are output, not keyed state — journaling them would
        charge every interval delta for rows the pre-checkpoint flush
        regenerates wholesale."""
        return {
            "output": self._pack_output(),
            "emitted_watermark": self.emitted_watermark,
            "current_watermark": self.current_watermark,
        }

    def _apply_tier_meta(self, meta: dict, envelope: dict) -> None:
        self.norm.restore(meta["norm"])
        self._steps = []
        self._reset_dispatch_ring()
        self.output = blocks_of(self._unpack_output(envelope["output"]))
        self.emitted_watermark = envelope["emitted_watermark"]
        self.current_watermark = envelope["current_watermark"]

    def snapshot(self) -> dict:
        # flush buffered steps so keyed state lives in exactly one place
        # (the device arrays); fires this triggers land in "output" below
        # and ride the checkpoint, so they are re-emitted after restore
        # rather than lost (their fire_cursor has already advanced)
        self.flush_all()
        if self.tier is not None:
            meta = self._tier_meta()
            if self.tier.log is not None:
                # incremental: ONE cells entry + a (base, offset) handle —
                # checkpoint bytes scale with the interval delta
                return {"tier_changelog": self.tier.checkpoint(
                    meta, self.pipe.gather_cells,
                    lambda: self.pipe.snapshot()),
                    **self._envelope()}
            return {"pipe": self.pipe.snapshot(),
                    "tier": self.tier.full_snapshot(),
                    "meta": meta, **self._envelope()}
        snap_extra = {}
        if self.spec_outputs is not None:
            # shared partials: undrained per-member lanes ride the
            # checkpoint like the plain output list
            snap_extra["spec_outputs"] = [rows_of(x) for x in self.spec_outputs]
        return {
            **snap_extra,
            "pipe": self.pipe.snapshot(),
            "keydict": self.keydict.snapshot(),
            "normalizer": self.norm.snapshot(),
            # self-describing metadata so offline tools (state processor)
            # can fold not-yet-dispatched steps into (key, slice) cells
            "fields": [
                (f.name, f.scatter, f.identity, f.source, np.dtype(f.dtype).str)
                for f in self.agg.fields
            ],
            "geometry": {"g": self.pipe.g, "offset": self.pipe.offset},
            # resolved-but-undrained emissions: their fires are already
            # committed in device state, so dropping them at restore would
            # lose output — they ride the checkpoint instead
            # (as rows: the checkpoint's format, state_processor reads it)
            "output": rows_of(self.output),
            "emitted_watermark": self.emitted_watermark,
            "current_watermark": self.current_watermark,
        }

    def restore(self, snap: dict) -> None:
        if "tier_changelog" in snap:
            if self.tier is None:
                raise RuntimeError(
                    "this checkpoint is an incremental (changelog) tiered "
                    "snapshot; the restoring operator has state.tier "
                    "disabled")
            out = self.tier.restore_changelog(snap["tier_changelog"])
            self.pipe.restore(out["pipe"])
            self._apply_tier_meta(out["meta"], snap)
            return
        if "tier" in snap:
            if self.tier is None:
                raise RuntimeError(
                    "this checkpoint is a tiered snapshot; the restoring "
                    "operator has state.tier disabled")
            self.pipe.restore(snap["pipe"])
            self.tier.restore_full(snap["tier"])
            self._apply_tier_meta(snap["meta"], snap)
            return
        if self.tier is not None:
            # the reverse direction must fail as loudly as the forward
            # one: restoring a classic (grow-only keydict) snapshot into
            # a tiered operator would route new keys through an EMPTY
            # vocabulary whose recycled dense ids alias the restored
            # rows' old keys — silent misattribution, never an error
            raise RuntimeError(
                "this checkpoint is a classic (untired) snapshot; the "
                "restoring operator has state.tier enabled — restore it "
                "with tiering off, or take a fresh tiered checkpoint")
        self.pipe.restore(snap["pipe"])
        self.keydict = KeyDictionary.restore(snap["keydict"])
        self.norm.restore(snap["normalizer"])
        self._steps = []
        self.emitted_watermark = snap["emitted_watermark"]
        self.current_watermark = snap["current_watermark"]
        self._reset_dispatch_ring()
        self.output = blocks_of(snap["output"])
        if self.spec_outputs is not None:
            self.spec_outputs = [blocks_of(x) for x in snap["spec_outputs"]]
