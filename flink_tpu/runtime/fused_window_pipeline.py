"""FusedWindowPipeline: whole-stream windowed aggregation, N steps per dispatch.

The throughput sibling of TpuWindowOperator (same semantic contracts,
different execution granularity). TpuWindowOperator dispatches one device
program per batch and syncs per fire; over a high-latency host<->device link
every interaction costs a fixed round trip, so this pipeline compiles a
`lax.scan` over T steps — ingest, fire, purge fused — into ONE device
program, with all per-step control decisions (ring columns, fire slots,
purge masks) precomputed on host from the watermark schedule and staged as
device arrays. Outputs land in a compact [R, K] on-device buffer read back
once per dispatch.

This is the moral analogue of the reference's record batching across the
network boundary (RecordWriter flushes buffers, not records:
flink-runtime/.../api/writer/RecordWriter.java:105): amortize the fixed
per-interaction cost, keep the semantics per-element.

Semantics preserved (parity-tested against OracleWindowOperator):
- slice-decomposed window assignment (TimeWindow.getWindowStartWithOffset),
- EventTimeTrigger firing: window j fires when wm >= end(j)-1, in j order,
  after the batch that advanced the watermark was ingested,
- fire-then-purge ordering at the same watermark (WindowOperator.onEventTime
  fires the trigger before cleanup at the same timestamp),
- too-late records (newest containing window already cleaned) dropped and
  counted, matching isWindowLate (WindowOperator.java:609).

Restrictions of the fused path (callers fall back to TpuWindowOperator):
event-time only, add-combining aggregates (sum/count/mean-style),
allowed_lateness == 0, dense int keys or pre-densified key ids.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import weakref
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from flink_tpu.api.windowing.assigners import WindowAssigner
from flink_tpu.core.time import MIN_WATERMARK, TimeWindow
from flink_tpu.metrics import device_phases
from flink_tpu.metrics.task_io import dispatch_stage
from flink_tpu.ops.aggregators import DeviceAggregator, ONE, VALUE, resolve
from flink_tpu.utils import native_bridge
from flink_tpu.utils.arrays import canonical_column


def _ceil_div(a: int, b: int) -> int:
    return -((-a) // b)


@dataclasses.dataclass
class _PlannedFire:
    row: int          # output-buffer row
    j: int            # window index
    step: int         # step within the dispatch
    spec: int = 0     # window spec (shared-partial pipelines; 0 otherwise)


class StepPlan(NamedTuple):
    """One data step's slice plan (FusedWindowPipeline.plan_step): what
    staging copies into `srel_h` / `idx_h` and hands the plan cursor.

    srel: int32 [n], each record's slice relative to `smin` (-1 = late, in
      the masked form only); or an int for the whole step: 0 when every
      record lies in slice `smin`, -1 when every record is late.
    smin, smax: the live records' slice span (None: no live record).
    late: records below the live frontier (dropped and counted at staging).
    masked: False = planned from the two scalars `ts.min()` / `ts.max()`
      (no record late, span < NSB: no per-record mask was ever built)."""

    srel: Any
    smin: Optional[int]
    smax: Optional[int]
    late: int = 0
    masked: bool = False


class ColumnLayout(NamedTuple):
    """The staged form of a rank-1 record ([n, width] per batch): one
    lane-dense [T, B] array per field the traced chain reads, nothing for
    the fields it does not read."""

    columns: Tuple[int, ...]   # fields staged, ascending
    width: int                 # fields of the record
    dtype: str                 # the record's canonical dtype

    def __str__(self) -> str:  # the CompileTracker signature's "columns"
        return "+".join(map(str, self.columns)) + f"/{self.width}"


@dataclasses.dataclass(frozen=True)
class TracedPrologue:
    """The traced pre-stage of a fused device chain (whole-graph fusion,
    graph/fusion.py): chain transforms applied to the raw value column
    INSIDE the compiled superscan, then key/value extraction. All callables
    must be pure jax-traceable column functions; `key_fn` must return
    non-negative int keys < the pipeline's key capacity (checked against a
    max-key reduction carried through the scan and raised at resolve time —
    an out-of-range key must never silently alias another key's row)."""

    transforms: Tuple[Tuple[str, Any], ...]   # ('map'|'filter'|'map_ts', fn)
    key_fn: Any
    value_fn: Optional[Any] = None            # None: the column IS the value

    @property
    def needs_ts(self) -> bool:
        return any(kind == "map_ts" for kind, _fn in self.transforms)

    def column_layout(self, raw_shape, raw_dtype,
                      needs_vals: bool) -> Optional[ColumnLayout]:
        """How a record of `raw_shape` per event is staged: per-field
        arrays of the fields the chain reads when the record is rank 1,
        None (the record array as it is) for scalars and higher ranks."""
        if len(raw_shape) != 1:
            return None
        return _column_layout(self, int(raw_shape[0]),
                              np.dtype(raw_dtype).name, bool(needs_vals))

    def gathers(self) -> Tuple[int, Tuple[str, ...]]:
        """(gathers `apply` lowered to a table lookup, why each other one was
        kept) when it was last traced, by this prologue or an equal one (the
        programs are cached by prologue): the prologue's
        `prologueGathersLowered` / `prologueGathersKept`; (0, ()) before."""
        return _TRACED_GATHERS.get(self, (0, ()))

    def apply(self, raw, srel, ts, key_bounds, *, K: int, NSB: int,
              needs_vals: bool, layout: Optional[ColumnLayout] = None):
        """One scan step of the chain, traced: record lanes -> (live, keys,
        idx, vals, key_bounds). THE prologue of the single-chip, shared and
        sharded programs, and what `column_layout` reads the fields from.

        raw: the record lanes [B, ...], or with a `layout` the staged
        fields ([B] each). The record handed to the user's functions is
        rebuilt with zeros for the fields that were not staged — fields no
        equation of this very trace reads; XLA folds each static slice of
        the stack to its operand, so no [B, width] array exists on the
        device."""
        import jax
        import jax.numpy as jnp

        from flink_tpu.ops import table_lookup

        if layout is None:
            col = raw
        else:
            staged = dict(zip(layout.columns, raw))
            unread = jnp.zeros(srel.shape, layout.dtype)
            col = jnp.stack([staged.get(c, unread)
                             for c in range(layout.width)], axis=1)
        mask = srel >= 0
        # every callable goes through `table_lookup.call`: a gather from a
        # small constant integer table becomes a one-hot contraction, a
        # callable without one is called as it is; `gathers` reads its choices
        lowerings = []

        def call(fn, *args):
            out, low = table_lookup.call(fn, *args)
            lowerings.append(low)
            return out

        # one nested scope per element of the chain: a capture's phase table
        # (metrics/device_phases.py) then has a row per transform
        for i, (kind, fn) in enumerate(self.transforms):
            with jax.named_scope(device_phases.transform_scope(i, kind)):
                if kind == "map":
                    col = call(fn, col)
                elif kind == "map_ts":
                    col = call(fn, col, ts)
                else:  # filter
                    mask = mask & jnp.asarray(call(fn, col)).astype(bool)
        with jax.named_scope(device_phases.KEY):
            keys = jnp.asarray(call(self.key_fn, col)).astype(jnp.int32)
            live = mask & (keys >= 0) & (keys < K)
            idx = jnp.where(live, keys * NSB + srel, jnp.int32(-1))
            idx = idx.astype(jnp.int32)
        if needs_vals:
            with jax.named_scope(device_phases.VALUE):
                vcol = (call(self.value_fn, col)
                        if self.value_fn is not None else col)
                # dead/pad rows hold uninitialized staging bytes that can
                # decode as NaN/inf; zero them BEFORE ingest or any shuffle —
                # the matmul histogram multiplies the zero one-hot by the raw
                # value, and 0 * NaN = NaN would poison every sum in the chunk
                # (the scatter path drops by index, but identical inputs keep
                # both ingest forms bit-identical)
                vals = jnp.where(
                    live, jnp.asarray(vcol).astype(jnp.float32), 0.0)
        else:
            vals = jnp.zeros((1,), jnp.float32)
        _TRACED_GATHERS[self] = (sum(low.lowered for low in lowerings),
                                 tuple(why for low in lowerings
                                       for why in low.kept))
        # key range observed over every SURVIVING record (pre range clamp):
        # an out-of-range key is a hard error at resolve, never a silent
        # drop or a silent alias of another key's (or shard's) row
        with jax.named_scope(device_phases.BOUNDS):
            key_bounds = jnp.stack([
                jnp.maximum(key_bounds[0],
                            jnp.max(jnp.where(mask, keys, jnp.int32(-1)))),
                jnp.minimum(key_bounds[1],
                            jnp.min(jnp.where(mask, keys, jnp.int32(0)))),
            ])
        return live, keys, idx, vals, key_bounds


#: `TracedPrologue.gathers`: each prologue's lowered / kept gathers from its
#: latest trace; weak, so an entry lives as long as an equal prologue does
#: (a cached program's key holds one)
_TRACED_GATHERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

#: rows of the record the column analysis traces the chain on (column
#: functions are per-record: the host chain runs them on any batch length)
_ANALYSIS_ROWS = 8


@functools.lru_cache(maxsize=256)
def _column_layout(pro: TracedPrologue, width: int, dtype: str,
                   needs_vals: bool) -> ColumnLayout:
    """Which fields of a [n, width] record the chain reads, from the jaxpr
    of `pro.apply` — conservatively. Every equation that consumes the
    record variable has to be a `slice` over all rows with unit stride
    (what `col[:, c]` and `col[:, a:b]` with static bounds lower to); the
    union of their field ranges is the answer. Any other consumer — a
    matmul, a reduction over the fields, `dynamic_slice` (a traced or a
    negative index), a gather, a nested jit / scan taking the record — reads
    every field."""
    import jax
    import jax.numpy as jnp
    from jax import dtypes as _jdt

    B = _ANALYSIS_ROWS
    cdtype = np.dtype(_jdt.canonicalize_dtype(dtype))
    every = ColumnLayout(tuple(range(width)), width, cdtype.name)

    def chain(rec, srel, ts, key_bounds):
        return pro.apply(rec, srel, ts, key_bounds, K=1, NSB=1,
                         needs_vals=needs_vals)

    lanes = jax.ShapeDtypeStruct((B,), jnp.int32)
    ts = (jax.ShapeDtypeStruct((B,), _jdt.canonicalize_dtype(np.int64))
          if pro.needs_ts else None)
    jaxpr = jax.make_jaxpr(chain)(
        jax.ShapeDtypeStruct((B, width), cdtype), lanes, ts,
        jax.ShapeDtypeStruct((2,), jnp.int32)).jaxpr
    rec = jaxpr.invars[0]   # never an output: `apply` hands on derived lanes
    read = set()
    for eqn in jaxpr.eqns:
        if not any(v is rec for v in eqn.invars):
            continue
        p = eqn.params
        if (eqn.primitive.name != "slice"
                or p["strides"] not in (None, (1, 1))
                or p["start_indices"][0] != 0
                or p["limit_indices"][0] != B):
            return every
        read.update(range(p["start_indices"][1], p["limit_indices"][1]))
    return every._replace(columns=tuple(sorted(read)))


#: compiled chained-superscan executables, shared across pipeline instances
#: (FIFO-bounded; entries keep the user fns alive, which is what makes
#: identity-keyed caching safe)
_CHAINED_CACHE: Dict[tuple, Any] = {}
_CHAINED_CACHE_MAX = 128


def _give_back(handle) -> None:
    """Return the staging set a resolved dispatch read to its pool."""
    lease, handle.lease = handle.lease, None
    if lease is not None:
        lease.release()


class DeferredEmissions:
    """Handle for fires of one dispatch; the device->host copy runs async."""

    #: the staging set the dispatch was staged from (`_StagingPool`), given
    #: back at resolve: the fire rows are read, so the program that read
    #: the set has run and every transfer out of it has completed
    lease = None

    def __init__(self, pipe: "FusedWindowPipeline", fires, count_out, outs,
                 key_bounds=None, key_capacity: Optional[int] = None,
                 phase_counts=None):
        self._pipe = pipe
        self._fires = fires
        self._count_out = count_out
        self._outs = outs
        # int32: [max_seen, min_seen], then on a mesh (routed, lanes) per shard
        self._key_bounds = key_bounds
        self._key_capacity = key_capacity
        # int32[PHASE_COUNTS] per-phase step counters of this dispatch,
        # [n, PHASE_COUNTS] from a mesh (device-plane observability); folded
        # into the pipeline's totals at resolve so the readback rides the
        # same async copy as the fire rows
        self._phase_counts = phase_counts
        #: bytes resolve() reads back (the stage clock's d2hBytes)
        self.nbytes = sum(
            int(getattr(a, "nbytes", 0))
            for a in (count_out, *outs.values(), key_bounds, phase_counts)
            if a is not None)
        try:
            count_out.copy_to_host_async()
            for v in outs.values():
                v.copy_to_host_async()
            if key_bounds is not None:
                key_bounds.copy_to_host_async()
            if phase_counts is not None:
                phase_counts.copy_to_host_async()
        except AttributeError:
            pass

    def resolve(self):
        if self._phase_counts is not None:
            self._pipe.phase_totals += np.asarray(
                self._phase_counts,
                dtype=np.int64).reshape(-1, PHASE_COUNTS).sum(axis=0)
            self._phase_counts = None
        if self._key_bounds is not None:
            bounds = np.asarray(self._key_bounds)
            hi, lo = int(bounds[0]), int(bounds[1])
            if bounds.size > 2:     # a mesh dispatch: its exchange counts
                self._pipe.deployment.note_exchange(bounds[2:])
            if hi >= self._key_capacity or lo < 0:
                raise ValueError(
                    f"traced key selector produced keys in [{lo}, {hi}] "
                    f"outside [0, {self._key_capacity}): the fused device "
                    "chain uses dense integer keys and cannot grow capacity "
                    "mid-dispatch. Raise 'execution.state.key-capacity' "
                    "above the largest key the selector can emit (and keep "
                    "keys non-negative), or drop traceable=True on key_by "
                    "to use the host key dictionary."
                )
            self._key_bounds = None     # checked and folded once
        count_np = np.asarray(self._count_out)
        outs_np = {k: np.asarray(v) for k, v in self._outs.items()}
        _give_back(self)
        return [
            (
                self._pipe._window_of_fire(pf),
                count_np[pf.row],
                {k: v[pf.row] for k, v in outs_np.items()},
            )
            for pf in self._fires
        ]


class _StreamedEmissions:
    """Composite deferred handle for a step-group streamed dispatch
    (latency mode): one DeferredEmissions per (readback_steps, B) group,
    each of which started its async device->host copy the moment its
    group's scan was enqueued — fires from early step groups become
    host-visible while later groups are still computing. resolve()
    concatenates the per-group resolutions in group order, reproducing
    the whole-span handle's emission order and payloads exactly. The
    staging set goes back once every group has resolved."""

    lease = None

    def __init__(self, parts: List[DeferredEmissions]):
        self._parts = parts
        self.nbytes = sum(p.nbytes for p in parts)

    def resolve(self):
        out = []
        for p in self._parts:
            out.extend(p.resolve())
        _give_back(self)
        return out


class _PlanCursor:
    """The fire/purge planning state machine for one dispatch.

    The staging loop (`FusedWindowPipeline._fill`) drives it, for data
    steps and for `plan_superbatch`'s bounds alike.
    """

    def __init__(self, pipe: "FusedWindowPipeline"):
        self.p = pipe
        self.wm = pipe.watermark
        self.fire_cursor = pipe.fire_cursor
        self.purged_to = pipe.purged_to
        self.min_used = pipe.min_used_slice
        self.max_seen = pipe.max_seen_slice

    def observe(self, smin: int, smax: int) -> None:
        """Account for a step whose live records occupy slices [smin, smax]."""
        p = self.p
        if smax - smin >= p.NSB:
            raise ValueError(
                f"batch spans {smax - smin + 1} slices > nsb={p.NSB}; "
                "raise nsb or shrink batches"
            )
        if self.purged_to is not None and smin < self.purged_to:
            raise AssertionError("late-drop check should bound smin")
        if self.max_seen is not None and self.max_seen - smin >= p.S:
            # Pre-watermark inverted skew: this batch's slices lie >= S
            # slices BELOW data already resident. Hold-back (StepNormalizer)
            # only bounds the future direction — past-direction space never
            # reopens (the purge frontier moves forward), so this is a
            # configuration limit, not a transient: the resident span must
            # fit the ring.
            raise ValueError(
                f"slice ring too small for this skew: batch slice "
                f"{smin} is {self.max_seen - smin} slices below the "
                f"newest resident slice {self.max_seen}, but the ring "
                f"holds only num_slices={p.S}. Raise "
                f"'execution.window.num-slices' above the expected "
                f"pre-watermark timestamp skew (in slices), or "
                f"advance the watermark sooner so old slices purge."
            )
        self.min_used = smin if self.min_used is None else min(self.min_used, smin)
        self.max_seen = smax if self.max_seen is None else max(self.max_seen, smax)
        self._note_fire_candidate(smin)

    def _note_fire_candidate(self, smin: int) -> None:
        p = self.p
        cand = p._j_oldest(smin)
        if self.wm > MIN_WATERMARK:
            cand = max(cand, p._j_fired_upto(self.wm) + 1)
        self.fire_cursor = cand if self.fire_cursor is None else min(self.fire_cursor, cand)

    def advance(self, t: int, new_wm: int, fire_pos, fire_valid, fire_row,
                purge_mask, fires: list) -> None:
        """Watermark advance after step t: plan fires (window order) + purge."""
        p = self.p
        if new_wm <= self.wm:
            return
        self._plan_fires(t, new_wm, fire_pos, fire_valid, fire_row, fires)
        # purge columns whose slices expired
        new_min_live = p._min_live_slice(new_wm)
        if self.min_used is not None:
            lo = self.min_used if self.purged_to is None else max(self.purged_to, self.min_used)
            hi_p = min(new_min_live, self.max_seen + 1)
            if hi_p - lo >= p.S:
                purge_mask[t, :] = 0
            elif hi_p > lo:
                dead = (np.arange(lo, hi_p) % p.S).astype(np.int64)
                purge_mask[t, dead] = 0
        self.purged_to = new_min_live if self.purged_to is None else max(self.purged_to, new_min_live)
        self.wm = new_wm

    def _plan_fires(self, t: int, new_wm: int, fire_pos, fire_valid,
                    fire_row, fires: list) -> None:
        p = self.p
        if self.fire_cursor is not None and self.max_seen is not None:
            hi = min(p._j_fired_upto(new_wm), p._j_newest(self.max_seen))
            slot = 0
            for j in range(self.fire_cursor, hi + 1):
                if slot >= p.F:
                    raise ValueError(
                        f"{hi + 1 - self.fire_cursor} windows fire in one step "
                        f"> fires_per_step={p.F}"
                    )
                if len(fires) >= p.R:
                    raise ValueError(f"more than out_rows={p.R} fires per dispatch")
                row = len(fires)
                fires.append(_PlannedFire(row, j, t))
                fire_pos[t, slot] = (j * p.sl) % p.S
                fire_valid[t, slot] = 1
                fire_row[t, slot] = row
                slot += 1
            if p._j_fired_upto(new_wm) >= self.fire_cursor:
                self.fire_cursor = p._j_fired_upto(new_wm) + 1

    def commit(self) -> None:
        p = self.p
        p.watermark = self.wm
        p.fire_cursor = self.fire_cursor
        p.purged_to = self.purged_to
        p.min_used_slice = self.min_used
        p.max_seen_slice = self.max_seen


def _longest(steps) -> int:
    """Records of the longest step (at least 1: a width is never 0)."""
    return max(max((len(s[2]) for s in steps), default=0), 1)


class Staged(NamedTuple):
    """One group of steps on the device: what `stage` hands `dispatch`.

    payload: the payload that filled it (a record job's watermark-only
      group is staged as key ids) — it picks the program.
    xs: its arrays; the first is the one whose -1 marks a dead lane: key
      ids (idx, vals); a record (srel, its staged fields or the record
      array[, ts]).
    plan: smin_pos, fire_pos, fire_valid, fire_row, purge_mask; on a mesh
      the five side by side in one [T, 1 + 3F + S] array (`_place`).
    layout: how a rank-1 record was split into fields (None: it was not).
    lease: the host staging set the lanes came from (`_StagingPool`;
      None: the payload stages none), handed on to the dispatch's
      emissions, which give it back at resolve."""

    payload: Any
    xs: tuple
    plan: tuple
    fires: list
    layout: Optional[ColumnLayout] = None
    lease: Any = None

    @property
    def T(self) -> int:
        return int(self.plan[0].shape[0])

    def scan_xs(self):
        """(the arrays as a program's scan takes them, the record's part of
        the dispatch's CompileTracker signature): a record goes first, as
        the traced chain takes it — the tuple of its staged fields, or the
        record array —, then srel[, ts]."""
        if not self.payload.record:
            return self.xs, {}
        if self.layout is None:
            raw, end = self.xs[1], 2
            sig = {"raw_dtype": str(raw.dtype), "columns": "record"}
        else:
            end = 1 + len(self.layout.columns)
            raw = self.xs[1:end]
            sig = {"raw_dtype": self.layout.dtype, "columns": str(self.layout)}
        return (raw,) + self.xs[:1] + self.xs[end:], sig

    def rows(self, lo: int, hi: int) -> "Staged":
        """Steps [lo, hi) of the group (latency mode's step groups)."""
        return self._replace(
            xs=tuple(a[lo:hi] for a in self.xs),
            plan=tuple(a[lo:hi] for a in self.plan),
            fires=[pf for pf in self.fires if lo <= pf.step < hi])


class _StagingLease:
    """One dispatch's hold on a staging set: its host lane arrays."""

    __slots__ = ("pool", "geometry", "arrays", "reused")

    def __init__(self, pool, geometry, arrays, reused: bool):
        self.pool = pool            # None: the set is not given back
        self.geometry = geometry
        self.arrays = arrays
        self.reused = reused

    def release(self) -> None:
        pool, self.pool = self.pool, None
        if pool is not None:
            pool._give_back(self)


class _StagingPool:
    """The host lane arrays of a pipeline's dispatches, reused across them.

    A set (srel or idx, the staged fields, ts or vals) is taken in `_fill`
    and given back when the dispatch that staged it resolves: its program
    has run by then, so every transfer out of the set is complete. A set
    taken from the pool skips the page faults and zeroing of fresh host
    memory; its lanes hold an earlier dispatch's data, which the payload's
    `pad` marks dead as it marks `np.empty`'s garbage. The pool holds no
    more sets than were out at once, so its depth follows the in-flight
    ring, and it keeps the sets of its newest geometries only: a ragged
    tail or the end-of-input flush does not pile up full-size sets.

    A `device_put` that aliased a set's memory (a CPU backend's zero copy)
    would let the next fill change the device array: each new set is
    probed at its first placement, and a geometry that aliased takes a
    fresh set every dispatch, never given back."""

    #: how many geometries keep their free sets: the newest ones taken
    KEEP = 2

    def __init__(self):
        self._free: Dict[tuple, List[tuple]] = {}   # oldest geometry first
        self._aliased: set = set()

    def take(self, geometry) -> _StagingLease:
        """A set of `geometry` ((shape, dtype, fill) per array; fill None:
        `np.empty`): a free one, else a fresh one."""
        if geometry in self._aliased:
            return _StagingLease(None, geometry, _fresh(geometry), False)
        free = self._free.pop(geometry, [])
        self._free[geometry] = free
        while len(self._free) > self.KEEP:
            del self._free[next(iter(self._free))]
        if free:
            return _StagingLease(self, geometry, free.pop(), True)
        return _StagingLease(self, geometry, _fresh(geometry), False)

    def placed(self, lease: _StagingLease, device_arrays) -> None:
        """Probe a new set of the pool against the device arrays made from
        it (a reused set was probed when it was new)."""
        if lease.reused or lease.pool is None:
            return
        if _aliases(lease.arrays, device_arrays):
            self._aliased.add(lease.geometry)
            self._free.pop(lease.geometry, None)
            lease.pool = None

    def _give_back(self, lease: _StagingLease) -> None:
        free = self._free.get(lease.geometry)
        if free is not None:
            free.append(lease.arrays)


def _fresh(geometry) -> tuple:
    return tuple(np.empty(shape, dtype) if fill is None
                 else np.full(shape, fill, dtype)
                 for shape, dtype, fill in geometry)


def _aliases(host_arrays, device_arrays) -> bool:
    """Whether a buffer of `device_arrays` lies in the memory of one of
    `host_arrays`; a buffer whose address cannot be read counts as one."""
    spans = [(a.ctypes.data, a.ctypes.data + a.nbytes) for a in host_arrays]
    for x in device_arrays:
        try:
            ptrs = [s.data.unsafe_buffer_pointer()
                    for s in x.addressable_shards]
        except (AttributeError, RuntimeError):   # unknown: keep fresh sets
            return True
        if any(lo <= ptr < hi for ptr in ptrs for lo, hi in spans):
            return True
    return False


class _KeyIdPayload:
    """Steps carry dense key ids and, where the aggregate reads them,
    values. Staged as idx = kid * NSB + srel per lane and vals; the width
    is a chunk multiple."""

    record = False

    def alloc(self, p: "FusedWindowPipeline", steps):
        """(the staging set's lease, how many of its arrays carry one entry
        per lane, the record's layout)."""
        T = len(steps)
        B = -(-_longest(steps) // p.chunk) * p.chunk
        # value-less aggregates (count) carry a zero [T,1] placeholder
        # instead of shipping a dead [T,B] f32 column to the device; with
        # values every lane is written or padded
        vals = ((T, B), np.float32, None) if p._needs_vals else \
            ((T, 1), np.float32, 0)
        lease = p._staging.take((((T, B), np.int32, None), vals))
        return lease, 2 if p._needs_vals else 1, None

    def native(self, p, xs_h, layout) -> Optional["_NativeLanes"]:
        """The dispatch's native lane writer (None: `write` + `pad` stage
        every step)."""
        return None

    def pad(self, xs_h, lanes: int, t: int, live: int) -> None:
        """Mark step t's lanes from `live` on dead: idx -1, value 0 (the
        matmul histogram multiplies a dead lane's value by a zero one-hot)."""
        xs_h[0][t, live:] = -1
        if lanes > 1:
            xs_h[1][t, live:] = 0

    def write(self, p, xs_h, layout, t: int, n: int, step,
              plan: StepPlan) -> int:
        """Fill step t's first n lanes; returns the lanes left alive."""
        if plan.smin is None:       # every record late: the whole row dead
            return 0
        idx_h, vals_h = xs_h
        kid, vals = np.asarray(step[0]), step[1]
        row = idx_h[t, :n]
        np.multiply(kid, p.NSB, out=row, casting="unsafe")
        if isinstance(plan.srel, np.ndarray):   # else 0
            row += plan.srel
        keep = None
        if plan.masked:     # late records: srel -1
            keep = plan.srel >= 0
            row[~keep] = -1
        # kid -1 = a cold-routed record (state/tier_manager.py): it rides
        # the step so fires over its slices get PLANNED, but it must never
        # scatter into a hot row — mask to the same -1 the ingest drops
        # (pad-row semantics)
        if int(kid.min()) < 0:
            row[kid < 0] = -1
        if p._needs_vals:
            vals_h[t, :n] = (0.0 if vals is None else vals if keep is None
                             else np.where(keep, vals, 0.0))
        return n

    def columns(self, p, layout):
        """(fields staged, fields of the record) for the stage clock."""
        return None


class _BoundsPayload(_KeyIdPayload):
    """`plan_superbatch`'s steps: slice bounds and no lanes — the caller
    stages idx (and vals) on the device itself; dispatched as key ids."""

    def alloc(self, p, steps):
        return None, 0, None

    def write(self, p, xs_h, layout, t, n, step, plan) -> int:
        return 0


class _RecordPayload:
    """Steps carry the raw source record BEFORE any chain transform: the
    traced prologue runs inside the compiled program. Staged as the fields
    of `_layout()` (or the record array), srel, and ts where the chain
    reads it; late records are masked to srel -1 by the plan, so the
    traced program never sees them as live."""

    record = True

    def alloc(self, p: "FusedWindowPipeline", steps):
        from jax import dtypes as _jdt

        T = len(steps)
        # staged width quantized to power-of-two multiples of the chunk:
        # ragged last batches land on a few bounded shapes (log2 many)
        # instead of compiling a fresh (T, B) executable per width, while
        # tiny tails keep tiny staging buffers — pad rows are srel -1 and
        # never touch state
        B = p.chunk * (
            1 << max(0, -(-_longest(steps) // p.chunk) - 1).bit_length())

        for step in steps:
            if not len(step[2]):
                continue
            arr = np.asarray(step[0])
            if p._raw_shape is None:
                if arr.dtype == object:
                    raise TypeError(
                        "the fused device chain needs numeric record "
                        "columns; this source yields Python objects — use a "
                        "columnar source (numeric ndarray batches) or drop "
                        "traceable=True to stay on the host chain"
                    )
                p._raw_shape, p._raw_dtype = arr.shape[1:], arr.dtype
            elif arr.shape[1:] != p._raw_shape or arr.dtype != p._raw_dtype:
                raise ValueError(
                    f"record column geometry changed mid-stream: "
                    f"{arr.dtype}{list(arr.shape[1:])} after "
                    f"{p._raw_dtype}{list(p._raw_shape)} — the fused "
                    "chain executable is shaped on a fixed column layout"
                )

        # np.empty or a pooled set, not zeros: pad rows are srel -1 — every
        # traced consumer masks on that before touching raw/ts, so the
        # 16MB+ staging memset per dispatch would be pure waste. Buffers are
        # allocated in jax's CANONICAL dtype (x64-off: float64→float32,
        # int64→int32): device_put of a non-canonical array re-casts the
        # whole buffer host-side every dispatch — a full extra copy, and the
        # garbage pad bytes overflow the narrowing float cast
        # (RuntimeWarning). Real rows cast at fill.
        layout = p._layout()
        geometry = (((T, B), np.int32, None),)      # srel
        if layout is None:
            geometry += (((T, B) + p._raw_shape,
                          _jdt.canonicalize_dtype(p._raw_dtype), None),)
        else:
            geometry += tuple(((T, B), np.dtype(layout.dtype), None)
                              for _c in layout.columns)
        if p.prologue.needs_ts:
            geometry += (((T, B), _jdt.canonicalize_dtype(np.int64), None),)
        return p._staging.take(geometry), len(geometry), layout

    def native(self, p, xs_h, layout) -> Optional["_NativeLanes"]:
        """The dispatch's native lane writer where the record is staged as
        fields, no timestamps are staged and the native library is loaded
        (else None)."""
        if layout is None or p.prologue.needs_ts:
            return None
        lib = native_bridge.get_lib()
        if lib is None or np.dtype(layout.dtype).itemsize not in (1, 2, 4, 8):
            return None
        return _NativeLanes(lib, xs_h, layout)

    def pad(self, xs_h, lanes: int, t: int, live: int) -> None:
        """Mark step t's lanes from `live` on dead: srel -1, the rest left
        as they are (every traced consumer masks on srel)."""
        xs_h[0][t, live:] = -1

    def write(self, p, xs_h, layout, t: int, n: int, step,
              plan: StepPlan) -> int:
        raw = step[0]
        xs_h[0][t, :n] = plan.srel
        # checked canonical cast: an int64/float64 source column narrowing
        # into the staging dtype must not silently wrap (same contract as
        # the timestamp guard below); the host fallback casts through the
        # same helper, so both paths compute on identical canonical inputs.
        # With a layout the check covers the staged fields: a field no
        # traced function reads never reaches one
        if layout is None:
            xs_h[1][t, :n] = canonical_column(
                raw, "fused chain record column")
        else:
            rec = np.asarray(raw)
            for field_h, c in zip(xs_h[1:], layout.columns):
                field_h[t, :n] = canonical_column(
                    rec[:, c], f"fused chain record column {c}")
        if p.prologue.needs_ts:
            ts_h = xs_h[-1]
            ts_arr = np.asarray(step[2], dtype=np.int64)
            if ts_h.dtype.itemsize < 8 and (
                int(ts_arr.max()) > np.iinfo(ts_h.dtype).max
                or int(ts_arr.min()) < np.iinfo(ts_h.dtype).min
            ):
                raise TypeError(
                    "traceable map_with_timestamp under the fused "
                    "chain stages timestamps in the backend's "
                    f"canonical {ts_h.dtype} (jax x64 is disabled) "
                    "and these event timestamps do not fit — they "
                    "would silently wrap inside the traced UDF. "
                    "Rebase event time near zero, enable jax x64, "
                    "or drop traceable=True to run the host chain."
                )
            ts_h[t, :n] = ts_arr
        return n

    def columns(self, p, layout):
        if layout is not None:
            return len(layout.columns), layout.width
        width = int(np.prod(p._raw_shape))
        return width, width


#: native threads that write one dispatch's record lanes, the job's thread
#: one of them; no more than the cores this process may run on. Four: on a
#: TPU v5e host `ysb_catchup`'s fill reads 5.4-5.8 ms a dispatch with one
#: writer, 3.6-3.7 with two, 2.6-2.7 with four (the record rows it reads
#: come cold from memory; PERF.md, Findings)
_LANE_WRITERS = min(4, len(os.sched_getaffinity(0)))


class _NativeLanes:
    """The record steps of one dispatch whose lanes ONE native call writes
    (`stage_record_lanes`, native/flink_tpu_native.cpp), made through
    ctypes, so with the GIL released: srel, its dead tail and each staged
    field, every record row read once, no Python per step. It takes a step
    where that call writes what `_RecordPayload.write` and `pad` write: a
    step without records (its dead row), or one whose record is an ndarray
    already in the staged dtype (`canonical_column` has nothing to cast or
    check) with unit-strided fields, its plan's srel a scalar or an int32
    array (a masked plan's -1 for a late record is copied as `write`
    copies it). `_fill` stages every other step with numpy, as before."""

    __slots__ = ("lib", "xs_h", "dtype", "cols", "rows", "live", "src",
                 "stride", "srel", "srel_src", "keep")

    def __init__(self, lib, xs_h, layout: ColumnLayout):
        self.lib, self.xs_h = lib, xs_h
        self.dtype = np.dtype(layout.dtype)
        self.cols = layout.columns
        self.rows: List[int] = []
        self.live: List[int] = []
        self.src: List[int] = []
        self.stride: List[int] = []
        self.srel: List[int] = []
        self.srel_src: List[int] = []
        self.keep: List[np.ndarray] = []   # what `src` / `srel_src` point into

    def take(self, t: int, n: int, step, plan: Optional[StepPlan]) -> bool:
        """Queue step t for the native call, if it writes the step."""
        src = stride = srel = srel_src = 0
        if n:
            rec = step[0]
            if (not isinstance(rec, np.ndarray) or rec.dtype != self.dtype or rec.ndim != 2
                    or rec.shape[0] != n
                    or rec.strides[1] != self.dtype.itemsize):
                return False
            if isinstance(plan.srel, np.ndarray):
                srel_arr = plan.srel
                if (srel_arr.dtype != np.int32 or srel_arr.shape != (n,)
                        or not srel_arr.flags.c_contiguous):
                    return False
                self.keep.append(srel_arr)
                srel_src = srel_arr.ctypes.data
            elif isinstance(plan.srel, (int, np.integer)):
                srel = int(plan.srel)
            else:
                return False
            self.keep.append(rec)
            src, stride = rec.ctypes.data, rec.strides[0]
        self.rows.append(t)
        self.live.append(n)
        self.src.append(src)
        self.stride.append(stride)
        self.srel.append(srel)
        self.srel_src.append(srel_src)
        return True

    def write(self) -> int:
        """Write every step taken; returns how many of them carry records."""
        if not self.rows:
            return 0
        fields = self.xs_h[1:1 + len(self.cols)]
        args = (np.array(self.rows, np.int64), np.array(self.live, np.int64),
                np.array(self.src, np.uint64),
                np.array(self.stride, np.int64),
                np.array(self.srel, np.int32),
                np.array(self.srel_src, np.uint64))
        offsets = np.array(self.cols, np.int64) * self.dtype.itemsize
        dst = np.array([a.ctypes.data for a in fields], np.uint64)
        srel_h = self.xs_h[0]
        self.lib.stage_record_lanes(
            len(self.rows), *(a.ctypes.data for a in args),
            srel_h.shape[1], self.dtype.itemsize, len(self.cols),
            offsets.ctypes.data, srel_h.ctypes.data, dst.ctypes.data,
            _LANE_WRITERS)
        return sum(1 for n in self.live if n)


_KEY_IDS, _BOUNDS, _RECORD = _KeyIdPayload(), _BoundsPayload(), _RecordPayload()

class FusedWindowPipeline:
    """One shard's keyed window aggregation, executed T steps per dispatch."""

    def __init__(
        self,
        assigner: WindowAssigner,
        aggregate,
        *,
        key_capacity: int,
        num_slices: Optional[int] = None,
        nsb: int = 4,                 # max distinct slices touched per batch
        fires_per_step: int = 2,
        out_rows: int = 64,           # max fires per dispatch
        chunk: int = 8192,
        exact_sums: bool = True,
        backend: str = "auto",        # 'auto' | 'xla' | 'pallas'
        pallas_interpret: bool = False,
        plan_only: bool = False,      # host planner/cursors only, no device state
        prologue: Optional[TracedPrologue] = None,
    ):
        agg = resolve(aggregate)
        if agg is None:
            raise ValueError(f"aggregate {aggregate!r} has no device form")
        for f in agg.fields:
            if f.scatter not in ("add", "min", "max"):
                raise ValueError(
                    f"fused pipeline supports add/min/max-combining fields; "
                    f"{f.name!r} uses {f.scatter!r} (use TpuWindowOperator)"
                )
        if assigner.slice_ms is None or not assigner.is_event_time:
            raise ValueError(f"{assigner!r} is not a sliceable event-time assigner")
        self.agg = agg
        self.K = key_capacity
        self.NSB = nsb
        self.F = fires_per_step
        self.R = out_rows
        self.chunk = chunk
        self.exact_sums = exact_sums
        self.prologue = prologue
        if prologue is not None:
            # the traced chain prologue runs inside the XLA superscan; the
            # pallas kernel consumes prebuilt idx streams and has no
            # prologue slot (on TPU the XLA superscan still runs on device)
            backend = "xla"
        self.backend = backend
        self.pallas_interpret = pallas_interpret
        # traced-chain state: fixed raw-column geometry (the compiled chain
        # executables live in the module-level _CHAINED_CACHE, keyed on the
        # prologue + aggregate + geometry, so a re-built pipeline for the
        # same program re-uses the jitted program instead of recompiling)
        self._raw_shape: Optional[tuple] = None
        self._raw_dtype = None
        self._pallas: Optional[bool] = None   # decided at first dispatch
        self._kernel_layout = False           # states in pallas slice-major form
        # device-plane observability (metrics/device_stats.py): an attached
        # CompileTracker wraps every dispatch; phase_counters threads the
        # ingest/fire/purge counters through the XLA superscan carry
        # (accumulated into phase_totals at resolve). Both are wired by
        # attach_device_stats BEFORE the first dispatch — phase_counters is
        # part of the executable cache key.
        self.compile_tracker = None
        self.stage_clock = None
        self.phase_counters = False
        # [ingest, fire, purge, one-slice steps]
        self.phase_totals = np.zeros(PHASE_COUNTS, np.int64)
        # latency-mode dispatch shape (scheduler/latency_controller.py),
        # flipped by the operator when execution.latency.target-ms is on:
        # donate_carry donates the [K, S] scan carry to the executable
        # (kills the state copy on the hot path — part of every executable
        # cache key, so flag-off jobs never share a donated program);
        # readback_steps > 0 splits a T-step dispatch into (T/readback_steps)
        # chained step-group programs so fired rows start their async
        # device->host copy per group instead of at span completion.
        self.donate_carry = False
        self.readback_steps = 0

        self.g = assigner.slice_ms
        self.sl = assigner.slide_slices
        self.spw = assigner.slices_per_window
        self.offset = assigner.offset_ms
        self.size_ms = self.spw * self.g
        self.slide_ms = self.sl * self.g
        # shared-partials (SharedWindowPipeline): per-fire-slot slice-run
        # lengths; None = the classic uniform-SPW program
        self._fire_spws: Optional[Tuple[int, ...]] = None
        if num_slices is None:
            num_slices = 1 << (self.spw + nsb + 8 - 1).bit_length()
        self.S = num_slices

        self._value_fields = [f for f in agg.fields if f.source == VALUE]
        self._needs_vals = bool(self._value_fields)

        self.plan_only = plan_only
        if plan_only:
            # pure host planner (e.g. the sharded pipeline's control plane):
            # never allocate the [K, S] device arrays
            self._state = {}
            self._count = None
        else:
            import jax.numpy as jnp

            self._state = {
                f.name: jnp.full((self.K, self.S), f.identity, jnp.dtype(f.dtype))
                for f in agg.fields
                if f.source == VALUE
            }
            self._count = jnp.zeros((self.K, self.S), jnp.int32)

        # host-side stream position
        self.watermark = MIN_WATERMARK
        self.fire_cursor: Optional[int] = None
        self.purged_to: Optional[int] = None
        self.min_used_slice: Optional[int] = None
        self.max_seen_slice: Optional[int] = None
        self.num_late_records_dropped = 0

        # staging (below): what a step of this job carries, and the host
        # arrays it is staged in
        self._payload = _KEY_IDS if prologue is None else _RECORD
        self._staging = _StagingPool()
        # weakref to the mesh pipeline that plans through this one
        self._mesh = None

    # ------------------------------------------------------------------
    # backend selection + state layout
    # ------------------------------------------------------------------
    def _use_pallas(self) -> bool:
        """Decide (once) whether dispatches run on the fused pallas kernel.

        'auto' picks pallas on a real TPU backend when the aggregate has a
        matmul form (add-combining fields only) and the geometry fits VMEM;
        everything else stays on the XLA superscan (which also serves the
        shard_map/multi-chip path and CPU CI).
        """
        if self._pallas is None:
            if self.backend == "xla":
                self._pallas = False
            else:
                # imported here: a job that never runs the kernel (a traced
                # chain, the mesh planner) does not pay for loading pallas
                from flink_tpu.ops import pallas_superscan

                ok = pallas_superscan.supports(
                    self.agg, self.K, self.R, self.S, self.NSB, self.chunk
                )
                if self.backend == "pallas":
                    if not ok:
                        raise ValueError(
                            "pallas superscan does not support this "
                            "aggregate/geometry (need add-combining or "
                            "bounded-domain max fields, K%128==0, "
                            "VMEM-sized state)"
                        )
                    self._pallas = True
                else:
                    import jax

                    self._pallas = ok and jax.default_backend() == "tpu"
        return self._pallas

    def _to_kernel_layout(self) -> None:
        if self._kernel_layout:
            return
        from flink_tpu.ops import pallas_superscan as ps

        self._count = ps.to_kernel_layout(self._count, self.K, self.S)
        self._state = {
            k: ps.to_kernel_layout(v, self.K, self.S)
            for k, v in self._state.items()
        }
        self._kernel_layout = True

    def _to_canonical(self) -> None:
        if not self._kernel_layout:
            return
        from flink_tpu.ops import pallas_superscan as ps

        self._count = ps.from_kernel_layout(self._count, self.K, self.S)
        self._state = {
            k: ps.from_kernel_layout(v, self.K, self.S)
            for k, v in self._state.items()
        }
        self._kernel_layout = False

    def _require_state(self) -> None:
        if getattr(self, "plan_only", False):
            raise RuntimeError(
                "this FusedWindowPipeline is plan_only (host planner); it "
                "has no device state to snapshot/restore/grow"
            )

    def ensure_key_capacity(self, required: int) -> None:
        """Grow the key dimension (next pow2) when the dictionary outgrows K;
        existing rows keep their accumulators, new rows start at identity.
        The superscan executable is per-K (cache-keyed), so growth costs one
        recompile — amortized by doubling, like the columnar backend's
        ensure_key_capacity."""
        if required <= self.K:
            return
        self._require_state()
        self._to_canonical()
        import jax.numpy as jnp

        new_k = 1 << (required - 1).bit_length()
        pad = new_k - self.K
        self._state = {
            f.name: jnp.concatenate(
                [self._state[f.name],
                 jnp.full((pad, self.S), f.identity, jnp.dtype(f.dtype))]
            )
            for f in self.agg.fields
            if f.source == VALUE
        }
        self._count = jnp.concatenate(
            [self._count, jnp.zeros((pad, self.S), jnp.int32)]
        )
        self.K = new_k
        self._pallas = None  # geometry changed; re-decide backend

    # ------------------------------------------------------------------
    # tiered-state row surface (state/tier_manager.py): the tier manager
    # moves whole key rows between the HBM ring and the cold tier through
    # these accessors. All of them run OFF the dispatch hot path
    # (demotion/promotion happens between superbatches, cell gathers at
    # checkpoint time), so they use eager device ops, canonical layout.
    # ------------------------------------------------------------------
    def gather_key_rows(self, kids: np.ndarray):
        """Read whole rows: (counts np[m, S], {field: np[m, S]})."""
        self._require_state()
        self._to_canonical()
        import jax.numpy as jnp

        k = jnp.asarray(np.asarray(kids, np.int32))
        counts = np.asarray(self._count[k])
        fields = {n: np.asarray(a[k]) for n, a in self._state.items()}
        return counts, fields

    def clear_key_rows(self, kids: np.ndarray) -> None:
        """Reset rows to identity (the demotion cut)."""
        self._require_state()
        self._to_canonical()
        import jax.numpy as jnp

        k = jnp.asarray(np.asarray(kids, np.int32))
        self._count = self._count.at[k].set(0)
        idents = {f.name: f.identity for f in self.agg.fields
                  if f.source == VALUE}
        self._state = {
            n: a.at[k].set(jnp.asarray(idents[n], a.dtype))
            for n, a in self._state.items()
        }

    def write_cells(self, kids: np.ndarray, spos: np.ndarray,
                    counts: np.ndarray, fields: Dict[str, np.ndarray]) -> None:
        """Set individual ring cells (the promotion scatter). Target rows
        must hold identity at the written positions (fresh or cleared) —
        the caller's tier invariant, so .set never clobbers live data."""
        self._require_state()
        self._to_canonical()
        import jax.numpy as jnp

        k = jnp.asarray(np.asarray(kids, np.int32))
        s = jnp.asarray(np.asarray(spos, np.int32))
        self._count = self._count.at[k, s].set(
            jnp.asarray(np.asarray(counts, np.int32)))
        self._state = {
            n: a.at[k, s].set(jnp.asarray(
                np.asarray(fields[n]), a.dtype))
            for n, a in self._state.items()
        }

    def gather_cells(self, kids: np.ndarray, spos: np.ndarray):
        """Point-read cells: (counts np[m], {field: np[m]}) — the
        changelog delta's checkpoint-time value source."""
        self._require_state()
        self._to_canonical()
        import jax.numpy as jnp

        k = jnp.asarray(np.asarray(kids, np.int32))
        s = jnp.asarray(np.asarray(spos, np.int32))
        counts = np.asarray(self._count[k, s])
        fields = {n: np.asarray(a[k, s]) for n, a in self._state.items()}
        return counts, fields

    def note_external_slices(self, smin: int, smax: int) -> None:
        """Account for rows placed into the ring OUTSIDE a planned step
        (tier promotion): the fire planner must treat the span as
        resident data or windows covering only promoted slices would
        never fire. Mirrors _PlanCursor.observe's frontier updates; the
        fire-cursor candidate clamps to already-fired windows so a
        promotion can never re-fire."""
        self.min_used_slice = (smin if self.min_used_slice is None
                               else min(self.min_used_slice, smin))
        self.max_seen_slice = (smax if self.max_seen_slice is None
                               else max(self.max_seen_slice, smax))
        cand = self._j_oldest(smin)
        if self.watermark > MIN_WATERMARK:
            cand = max(cand, self._j_fired_upto(self.watermark) + 1)
        self.fire_cursor = (cand if self.fire_cursor is None
                            else min(self.fire_cursor, cand))

    # ------------------------------------------------------------------
    # window geometry (identical formulas to TpuWindowOperator)
    # ------------------------------------------------------------------
    def _slice_of(self, ts: np.ndarray) -> np.ndarray:
        return (ts - np.int64(self.offset)) // np.int64(self.g)

    # ------------------------------------------------------------------
    # per-step slice plan: the one place a step's timestamps become slice
    # indices. StepNormalizer plans each step it emits and the step carries
    # the plan to staging; staging plans only steps that arrive bare.
    # ------------------------------------------------------------------
    def slice_span(self, ts: np.ndarray) -> Tuple[int, int]:
        """(smin, smax) of a non-empty timestamp column from its two
        extremes: `_slice_of` is monotone, so they are exact."""
        return ((int(ts.min()) - self.offset) // self.g,
                (int(ts.max()) - self.offset) // self.g)

    def live_slices(self, ts: np.ndarray, wm: int):
        """The masked form's per-record arrays: (slice ids, live mask) at
        watermark `wm` — late records are those whose slice was purged."""
        s_abs = self._slice_of(ts)
        if wm > MIN_WATERMARK:
            return s_abs, s_abs >= self._min_live_slice(wm)
        return s_abs, np.ones(len(ts), dtype=bool)

    def plan_scalar(self, ts: np.ndarray, wm: int,
                    limit_of=None) -> Optional[StepPlan]:
        """The step's plan from two scalars, or None where they cannot prove
        it: some record is late at `wm`, the step spans NSB or more slices,
        or (`limit_of(smin)`, the normalizer's hold-back bound) some record
        lies beyond the ring. No i64 division per record: a step inside one
        slice has srel 0, any other divides `ts - slice_start(smin)`, which
        lies in [0, NSB * g), in int32."""
        smin, smax = self.slice_span(ts)
        if smax - smin >= self.NSB:
            return None
        if wm > MIN_WATERMARK and smin < self._min_live_slice(wm):
            return None
        if limit_of is not None and smax >= limit_of(smin):
            return None
        if smin == smax:
            return StepPlan(0, smin, smax)
        narrow = self.NSB * self.g <= np.iinfo(np.int32).max
        srel = np.empty(len(ts), np.int32 if narrow else np.int64)
        np.subtract(ts, self.offset + smin * self.g, out=srel,
                    casting="unsafe")
        np.floor_divide(srel, self.g, out=srel)
        return StepPlan(srel.astype(np.int32, copy=False), smin, smax)

    def plan_masked(self, ts: np.ndarray, wm: int) -> StepPlan:
        """The same plan per record: late records masked to srel -1 and
        counted, the span taken over the live ones. The reference for
        plan_scalar, and the path of out-of-order streams."""
        s_abs, keep = self.live_slices(ts, wm)
        late = int(len(ts) - keep.sum())
        if late == len(ts):
            return StepPlan(-1, None, None, late, True)
        live = s_abs[keep] if late else s_abs
        smin = int(live.min())
        srel = np.where(keep, s_abs - smin, -1).astype(np.int32)
        return StepPlan(srel, smin, int(live.max()), late, True)

    def plan_step(self, ts: np.ndarray, wm: int) -> StepPlan:
        """Plan one non-empty data step at watermark `wm`: from two scalars
        where they suffice, per record otherwise."""
        plan = self.plan_scalar(ts, wm)
        return plan if plan is not None else self.plan_masked(ts, wm)

    def _take_plan(self, plan: Optional[StepPlan], ts,
                   cur: "_PlanCursor", t: int, smin_pos) -> StepPlan:
        """Staging's use of a step's plan (made here if the step came
        bare): count it, and hand its span to the plan cursor — whose
        checks stay on as the assertions of a plan made elsewhere."""
        if plan is None:
            plan = self.plan_step(np.asarray(ts, dtype=np.int64), cur.wm)
        self.num_late_records_dropped += plan.late
        clock = self.stage_clock
        if clock is not None:
            clock.planned(plan.masked)
        if plan.smin is not None:
            cur.observe(plan.smin, plan.smax)
            smin_pos[t] = plan.smin % self.S
        return plan

    def _j_fired_upto(self, wm: int) -> int:
        return (wm + 1 - self.offset - self.size_ms) // self.slide_ms

    def _min_live_slice(self, wm: int) -> int:
        return (self._j_fired_upto(wm) + 1) * self.sl

    def _j_newest(self, s: int) -> int:
        return s // self.sl

    def _j_oldest(self, s: int) -> int:
        return _ceil_div(s - self.spw + 1, self.sl)

    def _window_of(self, j: int) -> TimeWindow:
        start = self.offset + j * self.slide_ms
        return TimeWindow(start, start + self.size_ms)

    def _window_of_fire(self, pf: "_PlannedFire") -> TimeWindow:
        """Window of a planned fire (shared-partial pipelines dispatch on
        pf.spec; the single-window pipeline ignores it)."""
        return self._window_of(pf.j)

    def _wm_keeping_slice_live(self, s: int) -> int:
        """Largest watermark at which slice `s` has not been purged
        (_min_live_slice(wm) <= s) — the held-record watermark cap the
        StepNormalizer stages against. Single source for the formula so
        the shared-partial pipeline can widen it to its longest window."""
        return self.offset + (s // self.sl) * self.slide_ms + self.size_ms - 1 - 1

    def _cursor(self) -> "_PlanCursor":
        """Fire/purge planning state machine factory (the shared-partial
        pipeline substitutes its multi-spec cursor)."""
        return _PlanCursor(self)

    # ------------------------------------------------------------------
    # compiled superscan
    # ------------------------------------------------------------------
    def _superscan(self, T: int, B: int):
        return _build_superscan(
            self.agg, self.K, self.S, self.NSB, self.F, self.R,
            self.spw, self.chunk, self.exact_sums, T, B,
            phases=self.phase_counters, fire_spws=self._fire_spws,
            donate=self.donate_carry,
        )

    # ------------------------------------------------------------------
    # device-plane observability (metrics/device_stats.py)
    # ------------------------------------------------------------------
    def attach_device_stats(self, tracker, phase_counters: bool = True) -> None:
        """Attach a CompileTracker (and opt into the per-phase superscan
        counters). Must run before the first dispatch: the phase flag is
        part of the executable cache key."""
        self.compile_tracker = tracker
        self.phase_counters = bool(phase_counters)

    def _signature(self, program_extra: Dict[str, Any]) -> Dict[str, Any]:
        """Shape signature of the next dispatch — the key the tracker
        diffs for recompile cause attribution (K change = ring doubling,
        T/B change = batch-geometry churn, dtype change = dtype change)."""
        sig: Dict[str, Any] = {
            "K": self.K, "S": self.S, "NSB": self.NSB, "F": self.F,
            "R": self.R,
            "dtype": "+".join(str(np.dtype(f.dtype))
                              for f in self._value_fields) or "count",
        }
        sig.update(program_extra)
        return sig

    def attach_stage_clock(self, clock) -> None:
        """The operator's stage clock (metrics/task_io.py): stage.fill and
        stage.put report to it."""
        self.stage_clock = clock

    def _tracked(self, program: str, fn, args: tuple, extra: Dict[str, Any]):
        """Dispatch through the attached CompileTracker (or directly)."""
        if self.compile_tracker is None:
            return fn(*args)
        return self.compile_tracker.call(
            program, fn, args, self._signature(extra))

    def key_loads(self):
        """Device-resident per-key record counts ([K] int32): the input of
        the key-stats fold (metrics/key_stats.py) — one segment-sum over
        the count ring that is already in HBM. None before the first
        dispatch materializes state (or on a plan-only planner)."""
        count = getattr(self, "_count", None)
        if count is None:
            return None
        if self._kernel_layout:
            from flink_tpu.ops import pallas_superscan as ps

            count = ps.from_kernel_layout(count, self.K, self.S)
        return count.sum(axis=1)

    def state_row_bytes(self) -> int:
        """HBM bytes per key row (all slice cells of one key across count
        + value fields) — the key-stats state-bytes histogram scale."""
        n = 4 * self.S  # int32 count ring
        for f in self._value_fields:
            n += np.dtype(f.dtype).itemsize * self.S
        return n

    # ------------------------------------------------------------------
    # staging and dispatch: one loop fills the host arrays, one method puts
    # them on the device, one method calls the compiled program. Three
    # decisions, each behind one thing: the payload (what a step carries:
    # `_KeyIdPayload` / `_RecordPayload`, chosen in the constructor), the
    # placement (`_place`: how host arrays reach devices; the mesh pipeline
    # substitutes its own through `self.deployment`) and the program
    # (`_program`: which compiled function runs and how its result is read)
    # ------------------------------------------------------------------
    def process_superbatch(self, steps, watermarks, *, defer: bool = False):
        """Run T = len(steps) steps in one dispatch: `stage`, `dispatch`.

        steps: `(key_ids int32[n], values f32[n] | None, timestamps
        int64[n][, StepPlan])`; under a traced prologue the first entry is
        the raw record column [n, ...] BEFORE any chain transform and the
        second is None (the chain extracts the values). The optional plan
        is the normalizer's finished slice plan, copied as given; a bare
        step is planned here by the same `plan_step`. watermarks[i] is the
        watermark after step i. Returns one (window, count_row[K], {field:
        row[K]}) per fired window, in fire order; row entries for keys with
        count 0 are meaningless.

        defer=True returns a DeferredEmissions handle immediately after
        enqueuing the dispatch and starting the async device->host copy;
        call .resolve() later. The next dispatch may be enqueued before
        resolving (the state carry stays on device)."""
        return self.dispatch(self.stage(steps, watermarks), defer=defer)

    def stage(self, steps, watermarks, *, payload=None) -> Staged:
        """Host planning + device staging of one dispatch (separable from
        `dispatch` so a caller can overlap staging group i+1 with running
        group i). The only stage.fill / stage.put sections of the package.

        The host plans fires/purges from the timestamps alone (a traced
        chain never changes timestamps and a filter only removes records,
        so timestamp-derived slice bounds stay valid upper bounds; windows
        planned over filtered-out slices fire empty rows, which emission
        drops)."""
        import jax

        assert len(steps) == len(watermarks)
        if payload is None:
            payload = self._payload
            if payload.record and not any(len(s[2]) for s in steps):
                # watermark-only group: with zero rows the prologue is
                # irrelevant, so it is staged as key ids (every lane dead)
                # and `dispatch` runs the classic fire/purge program over
                # the same device state — tracing the chained program would
                # apply the user's column fns to a placeholder column
                # (crashing any 2-D selector), and this also covers the
                # restore-then-watermark ordering where the record geometry
                # is still unknown but restored state must fire
                payload = _KEY_IDS
        clock = self.stage_clock
        with dispatch_stage(clock, "stage.fill"):
            xs_h, lanes, layout, plan_np, fires, lease = self._fill(
                payload, steps, watermarks)
            xs_h, plan_h, shardings = self.deployment._place(
                payload, xs_h, lanes, plan_np)
        with dispatch_stage(clock, "stage.put"):
            xs, plan = jax.device_put((xs_h, plan_h), shardings)
            if lease is not None:
                self._staging.placed(lease, xs)
            if clock is not None:
                clock.staged(xs_h + plan_np, sum(len(s[2]) for s in steps),
                             payload.columns(self, layout),
                             None if lease is None else lease.reused)
        return Staged(payload, xs, plan, fires, layout, lease)

    def _fill(self, payload, steps, watermarks):
        """THE staging loop: each step's plan taken (or made), its lanes
        written by the payload, the tail of its row marked dead, the
        fire / purge plan advanced by the plan cursor. The record steps
        the payload's native writer takes are written after the loop, all
        by one native call (`_NativeLanes`). Returns (the payload's host
        arrays, how many of them carry one entry per lane, the record's
        ColumnLayout, the five plan arrays, fires, the lease of the staging
        set the arrays are: None without one)."""
        T = len(steps)
        lease, lanes, layout = payload.alloc(self, steps)
        xs_h = () if lease is None else lease.arrays
        smin_pos = np.zeros(T, dtype=np.int32)
        fire_pos = np.zeros((T, self.F), dtype=np.int32)
        fire_valid = np.zeros((T, self.F), dtype=np.int32)
        fire_row = np.zeros((T, self.F), dtype=np.int32)
        purge_mask = np.ones((T, self.S), dtype=np.int32)
        fires: List[_PlannedFire] = []

        cur = self._cursor()
        native = payload.native(self, xs_h, layout)
        numpy_steps = 0
        for t, step in enumerate(steps):
            plan = step[3] if len(step) > 3 else None
            n = len(step[2])
            if n or plan is not None:
                plan = self._take_plan(plan, step[2], cur, t, smin_pos)
            if native is None or not native.take(t, n, step, plan):
                live = 0
                if n or plan is not None:
                    live = payload.write(self, xs_h, layout, t, n, step, plan)
                if lanes:
                    # np.empty or pooled staging: only the pad tails (whole
                    # rows of empty or all-late steps) are set, to the -1
                    # every consumer masks on before touching the other
                    # arrays (and a key id's value to 0)
                    payload.pad(xs_h, lanes, t, live)
                    numpy_steps += n > 0
            cur.advance(t, watermarks[t], fire_pos, fire_valid, fire_row,
                        purge_mask, fires)
        cur.commit()
        native_steps = 0 if native is None else native.write()
        if self.stage_clock is not None:
            self.stage_clock.lanes_written(native_steps, numpy_steps)
        return (xs_h, lanes, layout,
                (smin_pos, fire_pos, fire_valid, fire_row, purge_mask), fires,
                lease)

    def _place(self, payload, xs_h, lanes, plan_np):
        """Placement: the payload's host arrays and the plan as they are
        handed to `jax.device_put`, and the shardings of the two (None: all
        on the default device). The pallas kernel consumes flat [T*B] lane
        streams; flattening on host is free (the arrays are contiguous), so
        no device reshape is needed."""
        if self._program(payload).flat:
            xs_h = tuple(a.reshape(-1) if i < lanes else a
                         for i, a in enumerate(xs_h))
        return xs_h, plan_np, None

    def _program(self, payload):
        if payload.record:
            return _CHAINED
        return _PALLAS if self._use_pallas() else _SCAN

    @property
    def deployment(self):
        """Who places staged arrays, holds the device state and picks the
        program: this pipeline, or the mesh pipeline that plans through
        it."""
        return self if self._mesh is None else self._mesh()

    def dispatch(self, staged: Staged, *, defer: bool = False):
        """Enqueue one staged group on its compiled program and wrap the
        fire rows it used in a DeferredEmissions.

        Streaming fire readback (latency mode, `readback_steps`): the
        T-step dispatch runs as T/Tg chained (Tg, B) programs carrying
        state on device, so each group's fired rows start their async
        device->host copy when the group's scan is enqueued instead of at
        span completion. Fire rows are planned with GLOBAL output-buffer
        indices across the span — each group's fresh output buffer
        populates only its own fires' rows — so resolving the per-group
        handles in order reproduces the whole-span emission order and
        payloads byte-for-byte (and the per-group key_bounds check still
        covers every surviving record: the groups partition the steps).
        Pow2 ladder rungs make T % Tg == 0 whenever Tg fits; geometries
        that do not divide, and the default, are one group."""
        dev = self.deployment
        prog = dev._program(staged.payload)
        T = staged.T
        B = prog.width(staged.xs[0], T)
        Tg = self.readback_steps
        if not (prog.grouped and 0 < Tg < T and T % Tg == 0):
            Tg = T
        run = prog.build(dev, Tg, B, staged.layout)
        parts: List[DeferredEmissions] = []
        done = 0
        for lo in range(0, T, Tg):
            # one group slices nothing: no op is added to the cycle
            group = staged if Tg == T else staged.rows(lo, lo + Tg)
            count_out, outs, key_bounds, pc = prog.call(
                dev, run, group, Tg, B)
            # rows are assigned in fire order across the WHOLE span: the
            # highest row this group can populate is the cumulative count
            done += len(group.fires)
            count_out, outs = prog.fire_rows(dev, count_out, outs, done)
            parts.append(DeferredEmissions(
                self, group.fires, count_out, outs, key_bounds=key_bounds,
                key_capacity=self.K, phase_counts=pc))
        deferred = parts[0] if len(parts) == 1 else _StreamedEmissions(parts)
        deferred.lease = staged.lease
        return deferred if defer else deferred.resolve()

    def plan_superbatch(self, slice_bounds, watermarks):
        """Host planning from per-step slice BOUNDS only — for callers that
        stage the record stream themselves (e.g. the benchmark's on-device
        generator, which synthesizes `idx = key_id * NSB + (slice - smin)`
        directly in HBM and never ships per-record data over the host link).

        slice_bounds: [(smin_abs, smax_abs)] per step — inclusive bounds on
        the absolute slices the step's records can occupy. The caller must
        guarantee no record falls outside its step's bounds and no record is
        late (bounds below the live frontier trip the plan cursor's check).

        Returns (staged, smin_abs[int32 T]): the staging loop run over a
        payload without lanes. Hand `dispatch` the result with the lanes
        filled in: `staged._replace(xs=(idx_dev, vals_dev))`."""
        steps = [(None, None, (), StepPlan(0, smin, smax))
                 for smin, smax in slice_bounds]
        staged = self.stage(steps, watermarks, payload=_BOUNDS)
        return staged, np.array([b[0] for b in slice_bounds], dtype=np.int32)

    def _layout(self) -> Optional[ColumnLayout]:
        """The staged form of this stream's record (None: the record array
        as it is — scalars, higher ranks, or no record seen yet)."""
        if self._raw_shape is None:
            return None
        return self.prologue.column_layout(
            self._raw_shape, self._raw_dtype, self._needs_vals)

    def _chained_superscan(self, T: int, B: int,
                           layout: Optional[ColumnLayout] = None):
        # module-level memo: the key holds STRONG references to the user
        # fns (via the frozen TracedPrologue), so identity-hashed entries
        # can never collide with a recycled id; builtin DeviceAggregators
        # are memoized singletons, custom ones identity-hash conservatively.
        # The layout names the staged fields and the record's width: two
        # chains that read different fields never share an executable
        key = (self.prologue, self.agg, self.K, self.S, self.NSB, self.F,
               self.R, self.spw, self.chunk, self.exact_sums, T, B,
               self.phase_counters, self._fire_spws, self.donate_carry,
               layout)
        fn = _CHAINED_CACHE.get(key)
        if fn is None:
            while len(_CHAINED_CACHE) >= _CHAINED_CACHE_MAX:
                _CHAINED_CACHE.pop(next(iter(_CHAINED_CACHE)))
            fn = _CHAINED_CACHE[key] = self._build_chained_superscan(
                T, B, layout)
        return fn

    def _build_chained_superscan(self, T: int, B: int,
                                 layout: Optional[ColumnLayout] = None):
        """Compile prologue + T-step superscan into one program. On CPU
        backends ingest uses direct scatter-adds ([K, S] is cache-resident
        and the MXU one-hot matmuls that win on TPU lose badly on a scalar
        core); on TPU the matmul-histogram ingest is kept."""
        import jax
        import jax.numpy as jnp

        from flink_tpu.ops.superscan import default_ingest

        pro = self.prologue
        ingest = default_ingest()
        phases = self.phase_counters
        step = make_superscan_step(
            self.agg, self.K, self.S, self.NSB, self.F, self.R,
            self.spw, self.chunk, self.exact_sums, ingest=ingest,
            phase_counters=phases, fire_spws=self._fire_spws,
        )
        K, NSB = self.K, self.NSB
        needs_vals = self._needs_vals
        needs_ts = pro.needs_ts

        def body(carry, args):
            inner, key_bounds = carry
            if needs_ts:
                raw, srel, ts = args[0], args[1], args[2]
                rest = args[3:]
            else:
                raw, srel = args[0], args[1]
                ts = None
                rest = args[2:]
            with jax.named_scope(device_phases.PROLOGUE):
                _live, _keys, idx, vals, key_bounds = pro.apply(
                    raw, srel, ts, key_bounds, K=K, NSB=NSB,
                    needs_vals=needs_vals, layout=layout)
            inner, _ = step(inner, (idx, vals) + rest)
            return (inner, key_bounds), None

        def run_fused_chained_superscan(state, count, outs, count_out, *xs):
            kb0 = jnp.asarray([-1, 0], jnp.int32)
            inner0 = (state, count, outs, count_out)
            if phases:
                inner0 = inner0 + (jnp.zeros((PHASE_COUNTS,), jnp.int32),)
            (inner, key_bounds), _ = jax.lax.scan(body, (inner0, kb0), xs)
            if phases:
                state, count, outs, count_out, pc = inner
                return state, count, outs, count_out, key_bounds, pc
            state, count, outs, count_out = inner
            return state, count, outs, count_out, key_bounds

        if self.donate_carry:
            # latency mode: the [K, S] carry buffers are dead the moment
            # the dispatch is enqueued (the pipeline rebinds to the outputs
            # unconditionally), so hand them to XLA for in-place reuse —
            # the deferred handles hold OUTPUT buffers, never the carry
            return jax.jit(run_fused_chained_superscan,
                           donate_argnums=(0, 1))
        return jax.jit(run_fused_chained_superscan)

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        self._require_state()
        self._to_canonical()  # snapshots use the [K, S] layout across backends
        return {
            "state": {k: np.asarray(v) for k, v in self._state.items()},
            "count": np.asarray(self._count),
            "watermark": self.watermark,
            "fire_cursor": self.fire_cursor,
            "purged_to": self.purged_to,
            "min_used_slice": self.min_used_slice,
            "max_seen_slice": self.max_seen_slice,
            "num_late_dropped": self.num_late_records_dropped,
        }

    def restore(self, snap: dict) -> None:
        self._require_state()
        import jax.numpy as jnp

        self._state = {k: jnp.asarray(v) for k, v in snap["state"].items()}
        self._count = jnp.asarray(snap["count"])
        self._kernel_layout = False
        self.K = int(self._count.shape[0])  # capacity may have grown pre-snapshot
        self._pallas = None
        self.watermark = snap["watermark"]
        self.fire_cursor = snap["fire_cursor"]
        self.purged_to = snap["purged_to"]
        self.min_used_slice = snap["min_used_slice"]
        self.max_seen_slice = snap["max_seen_slice"]
        self.num_late_records_dropped = snap["num_late_dropped"]


@functools.lru_cache(maxsize=None)
def _row_slicer(n: int):
    import jax

    return jax.jit(lambda b: b[:n])


def _slice_rows(buf, n: int):
    return _row_slicer(n)(buf)


def _used_rows(fired: int) -> int:
    """Rows a dispatch of `fired` fires is read back at: padded to a few
    stable shapes, so the program that cuts them is reused across
    dispatches."""
    return -(-max(fired, 1) // 16) * 16


def _used_fire_rows(count_out, outs, fired: int):
    """Read back only the fire rows a dispatch used: rows are assigned in
    fire order, so `fired` fires fill the first `fired` of the R rows. (The
    mesh cuts its per-shard slabs inside its own fire-shape program:
    `sharded_superscan._fire_shaper`.)"""
    used = _used_rows(fired)
    if used < count_out.shape[0]:
        count_out = _slice_rows(count_out, used)
        outs = {k: _slice_rows(v, used) for k, v in outs.items()}
    return count_out, outs


class _ChipProgram:
    """One compiled window program of a single chip, as `dispatch` uses it
    (`p` is the pipeline that holds the device state): `name` (the
    CompileTracker program; the device module is jit_run_<name>), `flat`
    (it reads [T*B] lane streams, not [T, B]), `grouped` (latency mode may
    run it per step group), `build`, `call`."""

    flat = False
    grouped = False

    def width(self, a, T: int) -> int:
        """Lanes per step, read off a staged lane array."""
        return a.shape[1] if a.ndim >= 2 else a.shape[0] // T

    def _lanes(self, p, staged: Staged, T: int, B: int):
        """The key-id lanes in this program's form. The backend decision
        can legitimately flip between staging and dispatch
        (ensure_key_capacity growth, restore): re-shape then."""
        idx, vals = staged.xs
        shape = (T * B,) if self.flat else (T, B)
        if idx.shape != shape:
            idx = idx.reshape(shape)
        if p._needs_vals and vals.shape != shape:
            vals = vals.reshape(shape)
        return idx, vals

    def fire_rows(self, p, count_out, outs, fired: int):
        return _used_fire_rows(count_out, outs, fired)


class _ScanProgram(_ChipProgram):
    """The XLA superscan: `fused_superscan` over key ids, or with the
    traced prologue inside the scan `fused_chained_superscan` over a
    record. States in canonical [K, S] form; zeroed [R, K] fire buffers go
    in, (state, count, outs, count_out[, key_bounds][, phase counts]) comes
    out."""

    grouped = True

    def __init__(self, chained: bool):
        self.chained = chained
        self.name = "fused_chained_superscan" if chained else "fused_superscan"

    def build(self, p, T: int, B: int, layout):
        p._to_canonical()
        if self.chained:
            return p._chained_superscan(T, B, layout)
        return p._superscan(T, B)

    def call(self, p, run, staged: Staged, T: int, B: int):
        import jax.numpy as jnp

        xs, record_sig = (staged.scan_xs() if self.chained
                          else (self._lanes(p, staged, T, B), {}))
        outs0 = {
            f.name: jnp.zeros((p.R, p.K), jnp.dtype(f.dtype))
            for f in p._value_fields
        }
        count_out0 = jnp.zeros((p.R, p.K), jnp.int32)
        p._state, p._count, outs, count_out, *tail = p._tracked(
            self.name, run,
            (p._state, p._count, outs0, count_out0) + xs + staged.plan,
            {"T": T, "B": B, **record_sig})
        key_bounds = tail.pop(0) if self.chained else None
        return count_out, outs, key_bounds, tail[0] if tail else None


class _PallasProgram(_ChipProgram):
    """The fused pallas kernel (ops/pallas_superscan.py): key ids only,
    states in its slice-major kernel layout, flat [T*B] lane streams, the
    plan first; fire rows come back in kernel form (`rows_to_keys`)."""

    name = "pallas_superscan"
    flat = True

    def build(self, p, T: int, B: int, layout):
        from flink_tpu.ops import pallas_superscan as ps

        p._to_kernel_layout()
        return ps.build_superscan(
            p.agg, p.K, p.S, p.NSB, p.F, p.spw, p.R, T, B, p.chunk,
            p.exact_sums, p.pallas_interpret, fire_spws=p._fire_spws,
        )

    def call(self, p, run, staged: Staged, T: int, B: int):
        from flink_tpu.ops import pallas_superscan as ps

        names = [f.name for f in p._value_fields]
        idx, vals = self._lanes(p, staged, T, B)
        p._count, field_states, count_out, field_outs = p._tracked(
            self.name, run,
            staged.plan + (p._count, tuple(p._state[n] for n in names),
                           idx, vals if p._needs_vals else None),
            {"T": T, "B": B})
        p._state = dict(zip(names, field_states))
        outs = {n: ps.rows_to_keys(o, p.R, p.K)
                for n, o in zip(names, field_outs)}
        return ps.rows_to_keys(count_out, p.R, p.K), outs, None, None


_SCAN, _CHAINED, _PALLAS = _ScanProgram(False), _ScanProgram(True), _PallasProgram()

#: the per-step ingest/fire/purge body now lives in ops/superscan.py (a
#: pure device-kernel builder, importable from `parallel/` without a
#: runtime edge — ARCH001); re-exported here for existing callers
from flink_tpu.ops.superscan import (  # noqa: E402,F401
    PHASE_COUNTS,
    make_superscan_step,
)


@functools.lru_cache(maxsize=None)
def _build_superscan(agg, K, S, NSB, F, R, SPW, chunk, exact, T, B,
                     phases: bool = False, fire_spws=None,
                     donate: bool = False):
    """Compiled T-step superscan; module-level cache so every pipeline with
    identical geometry (incl. warmup instances) shares one executable.
    With `phases` the program additionally returns the int32[4] per-phase
    step counters threaded through the scan carry (device-plane
    observability); the flag is part of the cache key, so gated jobs and
    ungated jobs never share an executable shape. `fire_spws` (shared
    partials) is likewise part of the key: per-slot slice-run lengths.
    `donate` (latency mode) donates the [K, S] state/count carry inputs to
    XLA for in-place reuse — callers rebind to the outputs unconditionally,
    so the old buffers are dead at enqueue; keyed so throughput jobs never
    share a donated executable."""
    import jax
    import jax.numpy as jnp

    step = make_superscan_step(agg, K, S, NSB, F, R, SPW, chunk, exact,
                               phase_counters=phases, fire_spws=fire_spws)
    jit = (functools.partial(jax.jit, donate_argnums=(0, 1)) if donate
           else jax.jit)

    # the function's name is the program's name on the device: the trace's
    # module reads jit_run_<CompileTracker program>
    if phases:
        @jit
        def run_fused_superscan(state, count, outs, count_out, idx, vals,
                                smin_pos, fire_pos, fire_valid, fire_row,
                                purge_mask):
            carry0 = (state, count, outs, count_out,
                      jnp.zeros((PHASE_COUNTS,), jnp.int32))
            (state, count, outs, count_out, pc), _ = jax.lax.scan(
                step, carry0,
                (idx, vals, smin_pos, fire_pos, fire_valid, fire_row,
                 purge_mask),
            )
            return state, count, outs, count_out, pc

        return run_fused_superscan

    @jit
    def run_fused_superscan(state, count, outs, count_out, idx, vals,
                            smin_pos, fire_pos, fire_valid, fire_row,
                            purge_mask):
        (state, count, outs, count_out), _ = jax.lax.scan(
            step,
            (state, count, outs, count_out),
            (idx, vals, smin_pos, fire_pos, fire_valid, fire_row, purge_mask),
        )
        return state, count, outs, count_out

    return run_fused_superscan


# ---------------------------------------------------------------------------
# shared-partial multi-window pipeline (Factor Windows, PAPERS.md
# arXiv 2008.12379): correlated window shapes over ONE gcd-granule ring
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _WindowSpec:
    """One member window of a shared-partial group, in shared-granule
    units: window j of this spec covers slices [j*sl, j*sl + spw)."""

    spw: int
    sl: int
    size_ms: int
    slide_ms: int


@dataclasses.dataclass(frozen=True)
class _SharedGridView:
    """Synthetic sliceable-assigner view the base pipeline initializes
    from: granule = the group gcd, spw = the LONGEST member (ring sizing,
    ring-floor math), sl = the SHORTEST slide (conservative frontier)."""

    slice_ms: int
    slices_per_window: int
    slide_slices: int
    offset_ms: int
    is_event_time: bool = True


class _SharedPlanCursor(_PlanCursor):
    """The multi-spec fire planner: one shared ingest/purge frontier,
    per-window-spec fire cursors, fire slots partitioned per spec."""

    def __init__(self, pipe: "SharedWindowPipeline"):
        super().__init__(pipe)
        self.fire_cursors = list(pipe.fire_cursors)

    def _note_fire_candidate(self, smin: int) -> None:
        p = self.p
        for i in range(len(p.specs)):
            cand = p._spec_j_oldest(i, smin)
            if self.wm > MIN_WATERMARK:
                cand = max(cand, p._spec_j_fired_upto(i, self.wm) + 1)
            cur = self.fire_cursors[i]
            self.fire_cursors[i] = cand if cur is None else min(cur, cand)

    def _plan_fires(self, t: int, new_wm: int, fire_pos, fire_valid,
                    fire_row, fires: list) -> None:
        p = self.p
        if self.max_seen is None:
            return
        Fp = p.F_per_spec
        for i, spec in enumerate(p.specs):
            cur = self.fire_cursors[i]
            if cur is None:
                continue
            hi = min(p._spec_j_fired_upto(i, new_wm),
                     self.max_seen // spec.sl)
            slot = i * Fp
            n = 0
            for j in range(cur, hi + 1):
                if n >= Fp:
                    raise ValueError(
                        f"window spec {i}: {hi + 1 - cur} windows fire in "
                        f"one step > fires_per_step={Fp}")
                if len(fires) >= p.R:
                    raise ValueError(
                        f"more than out_rows={p.R} fires per dispatch")
                row = len(fires)
                fires.append(_PlannedFire(row, j, t, spec=i))
                fire_pos[t, slot + n] = (j * spec.sl) % p.S
                fire_valid[t, slot + n] = 1
                fire_row[t, slot + n] = row
                n += 1
            if p._spec_j_fired_upto(i, new_wm) >= cur:
                self.fire_cursors[i] = p._spec_j_fired_upto(i, new_wm) + 1

    def commit(self) -> None:
        super().commit()
        self.p.fire_cursors = list(self.fire_cursors)


class SharedWindowPipeline(FusedWindowPipeline):
    """N correlated window shapes over ONE shared slice ring.

    The Factor-Windows execution form: a job computing several windows
    over the same keyed stream (1m/5m/1h dashboards) pays for ONE scan —
    ingest lands gcd-granule partials once, and every member window
    derives its result from those shared partials at fire time (its own
    slice-run length per fire slot, `fire_spws` in the superscan step).
    Against N independent fused runs this saves (N-1) full ingest scans —
    the dominant cost — which is the sharing factor the planner
    (graph/window_sharing.py) estimates.

    Differences from the base pipeline, all planner-side:
    - per-spec fire cursors (`fire_cursors`); the fire slot space is
      partitioned F_per_spec slots per member;
    - the purge frontier is the MIN over members' live frontiers (a slice
      purges only when the LONGEST window is done with it);
    - `_window_of_fire` returns `(spec_index, TimeWindow)` — ONLY the
      shared-partial operator consumes these deferred handles, and it
      routes each emission to its member window's output.

    All member assigners must be sliceable, event-time, and share one
    offset; the shared granule is the gcd of their slice granules, and
    each member's decomposition onto it must be exact
    (WindowAssigner.slices_on — the degenerate-shape contract)."""

    def __init__(self, assigners, aggregate, *, key_capacity: int,
                 num_slices: Optional[int] = None, nsb: int = 4,
                 fires_per_step: int = 4, out_rows: int = 256,
                 chunk: int = 4096, exact_sums: bool = True,
                 backend: str = "auto", pallas_interpret: bool = False,
                 plan_only: bool = False, prologue=None):
        import math

        if len(assigners) < 2:
            raise ValueError("shared partials need >= 2 window shapes")
        offs = {a.offset_ms for a in assigners}
        if len(offs) != 1:
            raise ValueError(
                f"shared partials need one common window offset, got {offs}")
        for a in assigners:
            if a.slice_ms is None or not a.is_event_time:
                raise ValueError(f"{a!r} is not a sliceable event-time "
                                 "assigner")
        g = 0
        for a in assigners:
            g = math.gcd(g, a.slice_ms)
        specs = []
        for a in assigners:
            spw, sl = a.slices_on(g)   # exact or ValueError
            specs.append(_WindowSpec(spw, sl, spw * g, sl * g))
        n = len(specs)
        view = _SharedGridView(
            slice_ms=g,
            slices_per_window=max(s.spw for s in specs),
            slide_slices=min(s.sl for s in specs),
            offset_ms=assigners[0].offset_ms,
        )
        super().__init__(
            view, aggregate, key_capacity=key_capacity,
            num_slices=num_slices, nsb=nsb,
            fires_per_step=n * fires_per_step, out_rows=out_rows,
            chunk=chunk, exact_sums=exact_sums, backend=backend,
            pallas_interpret=pallas_interpret, plan_only=plan_only,
            prologue=prologue,
        )
        self.specs = tuple(specs)
        self.F_per_spec = fires_per_step
        self._fire_spws = tuple(
            s.spw for s in specs for _ in range(fires_per_step))
        self.fire_cursors = [None] * n

    # -- per-spec geometry ---------------------------------------------
    def _spec_j_fired_upto(self, i: int, wm: int) -> int:
        s = self.specs[i]
        return (wm + 1 - self.offset - s.size_ms) // s.slide_ms

    def _spec_j_oldest(self, i: int, smin: int) -> int:
        s = self.specs[i]
        return _ceil_div(smin - s.spw + 1, s.sl)

    def _spec_fire_wm(self, i: int, j: int) -> int:
        s = self.specs[i]
        return self.offset + j * s.slide_ms + s.size_ms - 1

    def _spec_window_of(self, i: int, j: int) -> TimeWindow:
        s = self.specs[i]
        start = self.offset + j * s.slide_ms
        return TimeWindow(start, start + s.size_ms)

    # -- shared frontier overrides -------------------------------------
    def _min_live_slice(self, wm: int) -> int:
        return min(
            (self._spec_j_fired_upto(i, wm) + 1) * s.sl
            for i, s in enumerate(self.specs)
        )

    def _wm_keeping_slice_live(self, s: int) -> int:
        # largest wm with min-over-specs of min_live <= s: the LONGEST
        # holder wins (any one spec keeping the slice live keeps it live)
        return max(self._spec_fire_wm(i, s // sp.sl) - 1
                   for i, sp in enumerate(self.specs))

    def _window_of_fire(self, pf: "_PlannedFire"):
        return (pf.spec, self._spec_window_of(pf.spec, pf.j))

    def _cursor(self) -> _SharedPlanCursor:
        return _SharedPlanCursor(self)

    # -- snapshot surface ----------------------------------------------
    def snapshot(self) -> dict:
        snap = super().snapshot()
        snap["fire_cursors"] = list(self.fire_cursors)
        return snap

    def restore(self, snap: dict) -> None:
        super().restore(snap)
        self.fire_cursors = list(snap["fire_cursors"])


# ---------------------------------------------------------------------------
# global-window pipeline: keyed-partial -> cross-segment fold, [S] state
# ---------------------------------------------------------------------------

class FusedGlobalWindowPipeline:
    """Per-window GLOBAL aggregation (the Nexmark Q7 shape) on the
    superscan schedule: the host planner (a plan-only FusedWindowPipeline
    — one source of truth for fire/purge math) plans dispatches exactly
    like the keyed path, but device state collapses from [K, S] to a [S]
    slice ring of partials and every fire folds its slice run into ONE
    scalar. The dense per-batch keyed reduction (and its [R, K] readback
    + host-side fold over keys) is replaced by a keyed-partial →
    cross-segment fold — the single-chip analogue of the mesh's
    psum/pmax merge; readbacks shrink to R scalars. Unbounded min/max
    have a device form here (the fold is elementwise — no scatter unit,
    no bounded-domain declaration).

    On TPU the whole T-step dispatch runs as one pallas kernel
    (ops/pallas_superscan.build_global_superscan) with the ring resident
    in a single VMEM row; elsewhere (and for geometries the kernel
    refuses) the XLA scan form (ops/superscan.make_global_scan_step)
    keeps identical semantics. Staged inputs are interchangeable with the
    keyed pipeline's (`idx = kid * NSB + srel` streams fold by
    `idx % NSB`), so callers that stage on device — the bench's threefry
    generator — switch paths without re-staging."""

    def __init__(self, assigner, aggregate, *, num_slices: Optional[int] = None,
                 nsb: int = 4, fires_per_step: int = 2, out_rows: int = 64,
                 chunk: int = 8192, backend: str = "auto",
                 pallas_interpret: bool = False):
        self._planner = FusedWindowPipeline(
            assigner, aggregate, key_capacity=128, num_slices=num_slices,
            nsb=nsb, fires_per_step=fires_per_step, out_rows=out_rows,
            chunk=chunk, backend="xla", plan_only=True,
        )
        self.agg = self._planner.agg
        self.S = self._planner.S
        self.NSB = nsb
        self.F = fires_per_step
        self.R = out_rows
        self.chunk = chunk
        self.backend = backend
        self.pallas_interpret = pallas_interpret
        self._value_fields = [f for f in self.agg.fields if f.source == VALUE]
        self._needs_vals = bool(self._value_fields)
        self._pallas: Optional[bool] = None
        self.compile_tracker = None
        self.phase_counters = False
        import jax.numpy as jnp

        from flink_tpu.ops.aggregators import scan_identity

        self._count = jnp.zeros((self.S,), jnp.int32)
        self._state = {
            f.name: jnp.full((self.S,),
                             scan_identity(jnp.dtype(f.dtype), f.scatter),
                             jnp.dtype(f.dtype))
            for f in self._value_fields
        }

    # planner-geometry delegation (the sharded pipeline's pattern)
    @property
    def planner(self):
        return self._planner

    def __getattr__(self, name):
        if name == "_planner":
            raise AttributeError(name)
        return getattr(self._planner, name)

    def attach_device_stats(self, tracker, phase_counters: bool = True) -> None:
        """Wire a CompileTracker around the global-superscan dispatch and
        (non-pallas, like the keyed pipeline) thread the ingest/fire/purge
        phase counters through the scan carry. Must run before the first
        dispatch — the phase flag is part of the executable cache key."""
        self.compile_tracker = tracker
        self.phase_counters = bool(phase_counters)

    def _use_pallas(self) -> bool:
        if self._pallas is None:
            from flink_tpu.ops import pallas_superscan as ps

            ok = ps.supports_global(self.agg, self.S, self.R, self.NSB,
                                    self.chunk)
            if self.backend == "xla":
                self._pallas = False
            elif self.backend == "pallas":
                if not ok:
                    raise ValueError(
                        "pallas global superscan does not support this "
                        "aggregate/geometry (need add/min/max fields, "
                        "S<=32, R<=128, chunk-aligned batches)")
                self._pallas = True
            else:
                import jax

                self._pallas = ok and (jax.default_backend() == "tpu"
                                       or self.pallas_interpret)
        return self._pallas

    def process_superbatch(self, steps, watermarks, *, defer: bool = False):
        return self.dispatch(self._planner.stage(steps, watermarks),
                             defer=defer)

    def dispatch(self, staged: Staged, *, defer: bool = False):
        """The planner's `stage` / `plan_superbatch` (reached through
        `__getattr__`) stage for this dispatch as for the keyed one."""
        import jax.numpy as jnp

        from flink_tpu.ops.aggregators import scan_identity

        idx_d, vals_d = staged.xs
        smin_pos, fire_pos, fire_valid, fire_row, purge_mask = staged.plan
        fires = staged.fires
        T = staged.T
        B = idx_d.shape[1] if idx_d.ndim == 2 else idx_d.shape[0] // T
        names = [f.name for f in self._value_fields]

        use_pallas = self._use_pallas()
        CH = self.chunk
        if use_pallas:
            # staged inputs are chunk-padded (the planner's `stage`), so CH stays
            # self.chunk; externally staged widths halve down to the largest
            # divisor. A width the kernel cannot chunk (below MIN_CHUNK)
            # falls back to the XLA scan for THIS dispatch — identical
            # semantics — unless the caller forced backend="pallas".
            from flink_tpu.ops import pallas_superscan as ps

            while CH > 1 and B % CH != 0:
                CH //= 2
            if B % CH != 0 or CH % ps.MIN_CHUNK != 0:
                if self.backend == "pallas":
                    raise ValueError(
                        f"pallas global superscan cannot chunk batch width "
                        f"{B} (chunks must divide B and be multiples of "
                        f"{ps.MIN_CHUNK}); stage through the pipeline or "
                        "use backend='auto' to allow the XLA scan fallback")
                use_pallas = False

        if use_pallas:
            from flink_tpu.ops import pallas_superscan as ps

            LANE = ps.LANE
            idx_flat = idx_d if idx_d.ndim == 1 else idx_d.reshape(-1)
            vals_flat = None
            if self._needs_vals:
                vals_flat = vals_d if vals_d.ndim == 1 else vals_d.reshape(-1)
            run = ps.build_global_superscan(
                self.agg, self.S, self.NSB, self.F, self._planner.spw,
                self.R, T, B, CH, self.pallas_interpret,
            )
            count_row = jnp.zeros((1, LANE), jnp.int32).at[0, :self.S].set(
                self._count)
            state_rows = tuple(
                jnp.full((1, LANE),
                         scan_identity(self._state[n].dtype,
                                       self.agg.field(n).scatter),
                         self._state[n].dtype).at[0, :self.S].set(
                    self._state[n])
                for n in names
            )
            out = run(smin_pos, fire_pos, fire_valid, fire_row, purge_mask,
                      count_row, state_rows, idx_flat, vals_flat) \
                if self.compile_tracker is None else \
                self.compile_tracker.call(
                    "pallas_global_superscan", run,
                    (smin_pos, fire_pos, fire_valid, fire_row, purge_mask,
                     count_row, state_rows, idx_flat, vals_flat),
                    {"T": T, "B": B, "S": self.S, "scope": "global"})
            count_state, field_states, count_out_row, field_out_rows = out
            self._count = count_state[0, :self.S]
            self._state = {
                n: s[0, :self.S] for n, s in zip(names, field_states)
            }
            count_out = count_out_row[0, :self.R]
            outs = {n: o[0, :self.R]
                    for n, o in zip(names, field_out_rows)}
        else:
            from flink_tpu.ops.superscan import build_global_superscan

            if idx_d.ndim == 1:
                idx_d = idx_d.reshape(T, B)
            if self._needs_vals and vals_d.ndim == 1:
                vals_d = vals_d.reshape(T, B)
            run = build_global_superscan(
                self.agg, self.S, self.NSB, self.F, self.R,
                self._planner.spw, T, B, phases=self.phase_counters,
            )
            outs0 = {
                f.name: jnp.full(
                    (self.R,),
                    scan_identity(jnp.dtype(f.dtype), f.scatter),
                    jnp.dtype(f.dtype))
                for f in self._value_fields
            }
            count_out0 = jnp.zeros((self.R,), jnp.int32)
            args = (self._state, self._count, outs0, count_out0, idx_d,
                    vals_d, smin_pos, fire_pos, fire_valid, fire_row,
                    purge_mask)
            if self.compile_tracker is None:
                out = run(*args)
            else:
                out = self.compile_tracker.call(
                    "global_superscan", run, args,
                    {"T": T, "B": B, "S": self.S, "scope": "global"})
            if self.phase_counters:
                self._state, self._count, outs, count_out, pc = out
            else:
                self._state, self._count, outs, count_out = out

        deferred = DeferredEmissions(
            self._planner, fires, count_out, outs,
            phase_counts=(pc if self.phase_counters and not use_pallas
                          else None))
        deferred.lease = staged.lease
        return deferred if defer else deferred.resolve()

    def snapshot(self) -> dict:
        return {
            "count": np.asarray(self._count),
            "state": {k: np.asarray(v) for k, v in self._state.items()},
            "watermark": self._planner.watermark,
            "fire_cursor": self._planner.fire_cursor,
            "purged_to": self._planner.purged_to,
            "min_used_slice": self._planner.min_used_slice,
            "max_seen_slice": self._planner.max_seen_slice,
            "num_late_dropped": self._planner.num_late_records_dropped,
        }

    def restore(self, snap: dict) -> None:
        import jax.numpy as jnp

        self._count = jnp.asarray(snap["count"])
        self._state = {k: jnp.asarray(v) for k, v in snap["state"].items()}
        self._planner.watermark = snap["watermark"]
        self._planner.fire_cursor = snap["fire_cursor"]
        self._planner.purged_to = snap["purged_to"]
        self._planner.min_used_slice = snap["min_used_slice"]
        self._planner.max_seen_slice = snap["max_seen_slice"]
        self._planner.num_late_records_dropped = snap["num_late_dropped"]
