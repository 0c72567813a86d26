"""Sharded fused superscan: the flagship kernel composed with the ICI shuffle.

`FusedWindowPipeline` runs the whole T-step window dispatch as one compiled
program on one chip; `ShardedTpuWindowOperator` scales keyed window state
across a mesh but dispatches per step. This module composes the two: ONE
`shard_map` program per dispatch in which every step (a) routes its records
to their key-range owners with a `lax.all_to_all` over ICI — the in-scan
analogue of the reference's network shuffle (KeyGroupStreamPartitioner →
RecordWriter.emit:105) — and (b) runs the shared superscan ingest/fire/purge
body (`ops/superscan.make_superscan_step`) on the shard's local key range.
Data parallelism over sources, key parallelism over state, zero host
involvement between steps.

Keys partition into contiguous ranges: shard = kid // K_local, and since the
segment encoding is `idx = kid * NSB + srel`, localizing is one subtract
(`idx - base * NSB`). Routing uses the positional lane protocol of
`ops/exchange.py`: the send buffer is [n, B] with non-destination lanes
INVALID, so the all-to-all needs no data-dependent compaction; each shard
then ingests n*B lanes per step (mostly INVALID, dropped for free by the
one-hot/scatter semantics).

Two skew-adaptive layers compose on top (both pure perf switches, off by
default, measured under zipf keys in PR 30 — docs/multichip.md
"Pre-exchange local combine" / "Skew-aware key-group routing"):
`local_combine` segment-reduces each shard's lanes by
(destination, key, rel-slice) BEFORE the all-to-all, so only dense
partials cross ICI (exact for decomposable aggregates; others route raw
transparently), and `skew_routing` replaces the static owner function
with a KeyGroupRouting table (parallel/routing.py) whose remaps are a
replicated-table swap plus one canonical host round trip — never a
recompile, never a semantics change (snapshots stay canonical [K, S]).

With a `TracedPrologue` (whole-graph fusion, PR 7) the pipeline additionally
runs the user's traceable map/filter/map_ts chain + key/value extraction
INSIDE the per-shard program, BEFORE the shuffle: each device transforms its
slice of the raw source columns, bins the surviving records by owning
key-group, and one all-to-all replaces what used to be a host dataplane hop.
This is what lets `DeviceChainRunner` point a fused user job — not just the
bench kernel — at the mesh.

Fire/purge control is replicated (all shards fire the same window rows);
each shard writes its own [R, K_local] slab and the host concatenates along
the key axis at resolve. The traced-chain program also counts, per shard,
the records the exchange delivered and the lanes its ingest read for them;
the two ride the key-bounds vector the dispatch reads back
(`per_device_exchange`, `perDevice[i].routed` / `.lanes`). Snapshots are
canonical [K, S] global arrays,
interchangeable with single-chip `FusedWindowPipeline` snapshots — which
makes n -> m shard rescaling a restore.

Layering: `parallel` sits below the runtime (ARCH001 — it may import
core/ops/state/config, never runtime/api/table). The single-chip planner it
drives (`FusedWindowPipeline`, plan-only: pure host cursor state, no device
arrays) is imported lazily at construction, the sanctioned function-scoped
escape hatch.

Validated on the virtual 8-device CPU mesh (tests/test_sharded_superscan.py,
tests/test_multichip_runtime.py) and dry-run by the driver via
__graft_entry__.dryrun_multichip; on real hardware the same program rides
ICI.
"""

from __future__ import annotations

import functools
import weakref
from typing import Any, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flink_tpu.metrics.device_phases import EXCHANGE, PROLOGUE
from flink_tpu.metrics.task_io import dispatch_stage
from flink_tpu.ops.aggregators import VALUE, combine_reduce, decomposable
from flink_tpu.ops.superscan import (
    PHASE_COUNTS,
    default_ingest,
    make_segment_partials,
    make_superscan_step,
)
from flink_tpu.parallel.routing import KeyGroupRouting


class ShardedFusedPipeline:
    """Keyed window aggregation over a device mesh, T steps per dispatch.

    Presents the same pipeline surface `FusedWindowOperator` drives on one
    chip (process_superbatch / stage / dispatch / ensure_key_capacity /
    snapshot / restore plus the planner-geometry delegates), so the
    operator adapter — and through it DeviceChainRunner — is mesh-agnostic.
    Staging and dispatch are the planner's own (`FusedWindowPipeline.stage`
    / `.dispatch`, reached through `__getattr__`): the planner fills the
    same host arrays as on one chip and asks its `deployment`, this object,
    for the two things a mesh changes: the placement (`_place`) and the
    program (`_program`).
    """

    def __init__(
        self,
        mesh: Mesh,
        assigner,
        aggregate,
        *,
        key_capacity: int,
        num_slices: Optional[int] = None,
        nsb: int = 4,
        fires_per_step: int = 2,
        out_rows: int = 64,
        chunk: int = 1024,
        exact_sums: bool = True,
        axis: str = "shards",
        prologue=None,
        assigners=None,
        local_combine: bool = False,
        skew_routing: bool = False,
        num_key_groups: int = 0,
    ):
        # runtime import is function-scoped: parallel/ sits below runtime in
        # the layer DAG (ARCH001), and the planner is pure host state
        from flink_tpu.runtime.fused_window_pipeline import (
            FusedWindowPipeline,
            SharedWindowPipeline,
        )

        self.mesh = mesh
        self.axis = axis
        self._replicated = NamedSharding(mesh, P())
        self.n = mesh.shape[axis]
        if key_capacity % self.n != 0:
            raise ValueError(
                f"key_capacity {key_capacity} must divide over {self.n} shards"
            )
        # the planner (and the canonical geometry/cursor state) is a
        # plan-only single-chip pipeline over the GLOBAL key space; its
        # device arrays are never dispatched. With `assigners` (shared
        # partials) it is the multi-spec planner: the per-shard program
        # below picks up its per-slot fire_spws, so correlated windows
        # share one scan ON THE MESH exactly like single-chip.
        if assigners is not None:
            self._planner = SharedWindowPipeline(
                assigners, aggregate,
                key_capacity=key_capacity, num_slices=num_slices, nsb=nsb,
                fires_per_step=fires_per_step, out_rows=out_rows, chunk=chunk,
                exact_sums=exact_sums, backend="xla", plan_only=True,
                prologue=prologue,
            )
        else:
            self._planner = FusedWindowPipeline(
                assigner, aggregate,
                key_capacity=key_capacity, num_slices=num_slices, nsb=nsb,
                fires_per_step=fires_per_step, out_rows=out_rows, chunk=chunk,
                exact_sums=exact_sums, backend="xla", plan_only=True,
                prologue=prologue,
            )
        self._planner._mesh = weakref.ref(self)
        self.agg = self._planner.agg
        self.prologue = prologue
        self.K = key_capacity
        self.K_local = key_capacity // self.n
        self.S = self._planner.S
        self.NSB = nsb
        self.F = self._planner.F   # total fire slots (N*F when shared)
        self.R = out_rows
        self.chunk = chunk
        self.exact = exact_sums
        self._value_fields = [f for f in self.agg.fields if f.source == VALUE]
        self._needs_vals = bool(self._value_fields)
        # pre-exchange local combine (parallel.mesh.local-combine): shards
        # segment-reduce their lanes by (dst, key, rel-slice) BEFORE the
        # all-to-all, so a hot key crosses ICI as at most n partials per
        # slice. Exact only for decomposable aggregates — a
        # non-decomposable spec transparently keeps the route-raw exchange
        self.local_combine = bool(local_combine) and decomposable(self.agg)
        # skew-aware key-group routing (parallel.mesh.skew-rebalance): the
        # static `dst = kid // K_local` owner function becomes a
        # device-resident [G] routing table; remapping groups is a table
        # swap + host state re-layout, never a recompile. None = static.
        self._num_key_groups = int(num_key_groups)
        self.routing: Optional[KeyGroupRouting] = (
            KeyGroupRouting(key_capacity, self.n, num_key_groups)
            if skew_routing else None)
        self._g_dst = self._g_slot = self._perm_dev = None
        if self.routing is not None:
            self._refresh_route_tables()
        self._init_state()
        self.exchange_totals = np.zeros((self.n, 2), np.int64)
        self._fn_cache: Dict[tuple, Any] = {}
        # latency mode (scheduler/latency_controller.py): donate the
        # sharded [n, Kl, S] scan carry to the executable. Streaming fire
        # readback (readback_steps) stays single-chip only — splitting the
        # mesh dispatch would multiply the per-step all-to-all count, so
        # the mesh path keeps span-granular readback by design. Set here
        # explicitly: __getattr__ would otherwise forward the read to the
        # plan-only planner and a write would shadow it confusingly.
        self.donate_carry = False

    # ------------------------------------------------------------------
    # planner-geometry delegation: StepNormalizer, DeferredEmissions, and
    # the operator adapter read the frontier/geometry surface of a
    # single-chip pipeline (g/sl/spw/offset/size_ms/slide_ms, the
    # watermark/fire/purge cursors, _j_*/_slice_of/_window_of,
    # phase_totals, num_late_records_dropped, the attached CompileTracker
    # and the phase-counter flag: `attach_device_stats`). On the mesh that state
    # lives in the plan-only planner — one source of truth for the window
    # math — so every attribute this class does not define itself
    # forwards there wholesale: a per-member delegate list would drift
    # (a forgotten entry surfaces only as a mesh-path AttributeError).
    # ------------------------------------------------------------------
    @property
    def planner(self):
        return self._planner

    def __getattr__(self, name):
        if name == "_planner":   # guard: no recursion before __init__ set it
            raise AttributeError(name)
        return getattr(self._planner, name)

    # ------------------------------------------------------------------
    def key_loads(self):
        """Global per-key record counts ([K], canonical key order) for the
        key-stats fold — one reshape + segment-sum over the sharded count
        ring (+ one gather when a routing table permutes the layout)."""
        count = getattr(self, "_count", None)
        if count is None:
            return None
        loads = count.reshape(self.K, self.S).sum(axis=1)
        if self.routing is not None:
            loads = jnp.take(loads, self._perm_dev, axis=0)
        return loads

    # ------------------------------------------------------------------
    # skew-aware key-group routing (parallel/routing.py): the table is a
    # pair of replicated [G] device arrays the compiled program gathers
    # from — remapping is a table swap plus ONE host round trip of the
    # canonical state, never a recompile. All mutators run off the
    # dispatch hot path (callers resolve in-flight dispatches first).
    # ------------------------------------------------------------------
    def _refresh_route_tables(self) -> None:
        # replicated over the mesh, as the programs that gather from them
        # take them: a dispatch finds them where it reads them
        r = self.routing
        self._g_dst, self._g_slot, self._perm_dev = jax.device_put(
            tuple(np.asarray(a, np.int32)
                  for a in (r.assign, r.slot, r.perm)), self._replicated)

    def routing_version(self) -> Optional[int]:
        return None if self.routing is None else self.routing.version

    def routing_payload(self) -> Optional[dict]:
        return None if self.routing is None else self.routing.payload()

    def mesh_group_loads(self):
        """Per-key-group resident record loads [G] (canonical groups) —
        the skew rebalancer's decision input. None without a table."""
        if self.routing is None:
            return None
        loads = self.key_loads()
        if loads is None:
            return None
        return self.routing.group_loads(np.asarray(loads))

    def set_routing_assignment(self, assign) -> int:
        """Swap in a new group->device map: pull the canonical [K, S]
        state under the OLD table, bump the table, re-lay rows under the
        new one. Exact by construction — canonical state never changes,
        only its placement. Returns the new table version."""
        if self.routing is None:
            raise RuntimeError(
                "skew routing is disabled (parallel.mesh.skew-rebalance)")
        count, state = self._canonical_arrays()
        self.routing = self.routing.with_assignment(assign)
        self._refresh_route_tables()
        self._put_canonical(count, state)
        return self.routing.version

    def _canonical_arrays(self):
        """(count [K, S], {field: [K, S]}) in canonical key order."""
        count = np.asarray(self._count).reshape(self.K, self.S)
        state = {
            name: np.asarray(a).reshape(self.K, self.S)
            for name, a in self._state.items()
        }
        if self.routing is not None:
            count = self.routing.to_canonical(count)
            state = {k: self.routing.to_canonical(v)
                     for k, v in state.items()}
        return count, state

    def per_device_key_loads(self):
        """Per-device local per-key record counts ([n, K_local]): the
        input of the per-device skew fold — an even GLOBAL histogram can
        still leave one device owning every hot key-group, and the mesh
        telemetry must see that device, not device 0's view."""
        count = getattr(self, "_count", None)
        if count is None:
            return None
        return count.sum(axis=2)

    def key_stats_ready(self) -> bool:
        return self._planner.max_seen_slice is not None

    def note_exchange(self, per_shard) -> None:
        """Fold one resolved dispatch's exchange counts (the tail of its
        key-bounds vector: `routed`, `lanes` per shard) into the totals."""
        self.exchange_totals += np.asarray(
            per_shard, np.int64).reshape(self.n, 2)

    def per_device_exchange(self):
        """[n, 2] int64 since this pipeline was built, resolved dispatches
        only: records the keyBy exchange delivered to each device, and
        lanes that device's ingest read for them. None until a dispatch of
        the traced-chain program has resolved: the key-id program reads no
        key bounds back, so it has nothing to carry the counts."""
        return self.exchange_totals if self.exchange_totals.any() else None

    # ------------------------------------------------------------------
    def _shard_spec(self, *tail):
        return NamedSharding(self.mesh, P(self.axis, *tail))

    def _init_state(self) -> None:
        n, Kl, S = self.n, self.K_local, self.S
        # created sharded: no whole-array stop on device 0
        spec = self._shard_spec(None, None)
        self._count = jnp.zeros((n, Kl, S), jnp.int32, device=spec)
        self._state = {
            f.name: jnp.full((n, Kl, S), f.identity, jnp.dtype(f.dtype),
                             device=spec)
            for f in self._value_fields
        }

    def ensure_key_capacity(self, required: int) -> None:
        """Grow the GLOBAL key dimension when the host dictionary outgrows
        K (classic keyed path only — traced chains fix capacity up front).
        Growth is to the next power of two rounded up to a multiple of the
        mesh size, so the contiguous key ranges keep dividing evenly; the
        canonical [K, S] grow-then-reshard costs one host round trip and
        one recompile, amortized by doubling exactly like the single-chip
        pipeline."""
        if required <= self.K:
            return
        new_k = 1 << (required - 1).bit_length()
        if new_k % self.n != 0:
            new_k = -(-new_k // self.n) * self.n
        n, S = self.n, self.S
        pad = new_k - self.K
        count, state = self._canonical_arrays()
        count = np.concatenate(
            [count, np.zeros((pad, S), np.int32)])
        idents = {f.name: (f.identity, np.dtype(f.dtype))
                  for f in self._value_fields}
        state = {
            k: np.concatenate([v, np.full((pad, S), *idents[k])])
            for k, v in state.items()
        }
        self.K = new_k
        self.K_local = new_k // n
        self._planner.K = new_k
        if self.routing is not None:
            # the table is sized to K: rebuild at identity over the grown
            # capacity (the rebalancer re-fires from fresh skew telemetry;
            # carrying an old-K assignment forward would be shape-invalid)
            self.routing = KeyGroupRouting(
                new_k, n, self._num_key_groups,
                version=self.routing.version + 1)
            self._refresh_route_tables()
        self._put_canonical(count, state)
        self._fn_cache.clear()   # executables captured the old K_local

    # ------------------------------------------------------------------
    # ------------------------------------------------------------------
    # exchange variants: the shared pieces the classic and raw builds
    # compose. `_dst_and_local` is THE owner function — static contiguous
    # ranges, or the routing table's group lookup; `_exchange_partials`
    # is the map-side combiner's exchange (dense per-destination partials
    # over ICI, folded per scatter kind on the receive side).
    # ------------------------------------------------------------------
    def _dst_and_local(self, g_tables):
        """fn(valid, kid, srel) -> (dst, local segment idx), -1 invalid."""
        n, Kl, NSB = self.n, self.K_local, self.NSB
        if g_tables is None:
            def fn(valid, kid, srel):
                dst = jnp.where(valid, kid // Kl, -1)
                lidx = jnp.where(valid, (kid % Kl) * NSB + srel, -1)
                return dst, lidx
            return fn
        g_dst, g_slot = g_tables
        Kg = self.routing.Kg

        def fn(valid, kid, srel):
            g = jnp.where(valid, kid // Kg, 0)
            dst = jnp.where(valid, g_dst[g], -1)
            lidx = jnp.where(
                valid, (g_slot[g] * Kg + kid % Kg) * NSB + srel, -1)
            return dst, lidx
        return fn

    def _exchange_partials(self, partials_fn, step, scatters):
        """fn(carry, pidx, vals, plan_row): segment-reduce this shard's
        lanes into flat [n*Kl*NSB] per-destination partials, ONE
        all-to-all per channel (count + each value field), fold across
        source shards by the field's own combiner, ingest pre-reduced.
        Returns (carry, records delivered to this shard)."""
        n, Kl, NSB, axis = self.n, self.K_local, self.NSB, self.axis

        def fn(carry, pidx, vals, plan_row):
            with jax.named_scope(EXCHANGE):
                cpart, parts = partials_fn(pidx, vals)
                rc = jax.lax.all_to_all(
                    cpart.reshape(n, Kl * NSB), axis, split_axis=0,
                    concat_axis=0, tiled=False)
                cpart_l = rc.sum(axis=0).reshape(Kl, NSB)
                parts_l = []
                for p, sc in zip(parts, scatters):
                    rp = jax.lax.all_to_all(
                        p.reshape(n, Kl * NSB), axis, split_axis=0,
                        concat_axis=0, tiled=False)
                    parts_l.append(
                        combine_reduce(sc)(rp, 0).reshape(Kl, NSB))
            carry, _ = step(
                carry, (cpart_l, tuple(parts_l)) + tuple(plan_row))
            # the records this shard was handed: its partial counts' sum
            return carry, cpart_l.sum()
        return fn

    def _make_step(self, lanes: int, phases: bool):
        chunk = self.chunk
        while lanes % chunk != 0:
            chunk //= 2
        return make_superscan_step(
            self.agg, self.K_local, self.S, self.NSB, self.F, self.R,
            self._planner.spw, chunk,
            self.exact,
            ingest="partials" if self.local_combine else default_ingest(),
            phase_counters=phases, fire_spws=self._planner._fire_spws,
        )

    def _make_partials_fn(self, B: int):
        pchunk = self.chunk
        while B % pchunk != 0:
            pchunk //= 2
        fn, _vf = make_segment_partials(
            self.agg, self.n * self.K_local * self.NSB, pchunk, self.exact,
            ingest=default_ingest())
        return fn

    def _build(self, T: int, B: int):
        phases = self.phase_counters
        combine = self.local_combine
        routed = self.routing is not None
        key = ("classic", T, B, phases, combine,
               None if not routed else self.routing.G, self.donate_carry)
        if key in self._fn_cache:
            return self._fn_cache[key]

        n, Kl, S, axis = self.n, self.K_local, self.S, self.axis
        NSB, R = self.NSB, self.R
        # the per-shard superscan body runs on K_local keys over n*B lanes
        step = self._make_step(n * B, phases)
        nf = len(self._value_fields)
        partials_fn = self._make_partials_fn(B) if combine else None
        scatters = [f.scatter for f in self._value_fields]

        def per_shard(count, state_t, idx, vals, *rest):
            if routed:
                *rest, g_dst, g_slot = rest
                owner = self._dst_and_local((g_dst, g_slot))
            else:
                owner = self._dst_and_local(None)
            (plan,) = rest
            smin_pos, fire_pos, fire_valid, fire_row, purge_mask = \
                self._split_plan(plan)
            # leading mesh dim is 1 inside the shard
            count = count[0]
            idx = idx[0]
            if nf:
                vals = vals[0]
            state = {
                f.name: state_t[i][0]
                for i, f in enumerate(self._value_fields)
            }
            base = jax.lax.axis_index(axis).astype(jnp.int32) * Kl
            if combine:
                exchange = self._exchange_partials(
                    partials_fn, step, scatters)

            def routed_step(carry, args):
                idx_row, vals_row, *plan_row = args
                valid = idx_row >= 0
                kid = idx_row // NSB
                if combine:
                    dst, lidx = owner(valid, kid, idx_row % NSB)
                    pidx = jnp.where(valid, dst * (Kl * NSB) + lidx, -1)
                    carry, _delivered = exchange(
                        carry, pidx, vals_row, plan_row)
                    return carry, None
                with jax.named_scope(EXCHANGE):
                    if routed:
                        # route-raw under a table: the sender localizes (the
                        # receiver cannot invert an arbitrary table from a
                        # global idx without a second lookup)
                        dst, lidx = owner(valid, kid, idx_row % NSB)
                        send_payload, localize = lidx, (lambda r: r)
                    else:
                        # destination = owner of the record's key range
                        dst = jnp.where(valid, kid // Kl, -1)
                        # localize: idx - base*NSB keeps srel intact
                        send_payload = idx_row
                        localize = lambda r: jnp.where(      # noqa: E731
                            r >= 0, r - base * NSB, -1)
                    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
                    route = rows == dst[None, :]                       # [n, B]
                    send_idx = jnp.where(route, send_payload[None, :], -1)
                    recv_idx = jax.lax.all_to_all(
                        send_idx, axis, split_axis=0, concat_axis=0, tiled=False
                    ).reshape(-1)                                      # [n*B]
                    local_idx = localize(recv_idx)
                    if nf:
                        send_v = jnp.where(route, vals_row[None, :], 0.0)
                        recv_v = jax.lax.all_to_all(
                            send_v, axis, split_axis=0, concat_axis=0, tiled=False
                        ).reshape(-1)
                    else:
                        recv_v = vals_row  # [1] placeholder
                return step(carry, (local_idx, recv_v, *plan_row))

            outs0 = {
                f.name: jnp.zeros((R, Kl), jnp.dtype(f.dtype))
                for f in self._value_fields
            }
            count_out0 = jnp.zeros((R, Kl), jnp.int32)
            carry0 = (state, count, outs0, count_out0)
            if phases:
                carry0 = carry0 + (jnp.zeros((PHASE_COUNTS,), jnp.int32),)
            carry, _ = jax.lax.scan(
                routed_step,
                carry0,
                (idx, vals, smin_pos, fire_pos, fire_valid, fire_row,
                 purge_mask),
            )
            if phases:
                state, count, outs, count_out, pc = carry
            else:
                state, count, outs, count_out = carry
            names = [f.name for f in self._value_fields]
            out = (
                count[None], tuple(state[nm][None] for nm in names),
                count_out[None], tuple(outs[nm][None] for nm in names),
            )
            if phases:
                out = out + (pc[None],)   # [1, PHASE_COUNTS] per shard
            return out

        out_specs = (
            P(axis, None, None),
            (P(axis, None, None),) * nf,
            P(axis, None, None),                      # count_out [n,R,Kl]
            (P(axis, None, None),) * nf,
        )
        if phases:
            # phase counters [n, PHASE_COUNTS]
            out_specs = out_specs + (P(axis, None),)
        in_specs = (
            P(axis, None, None),                      # count [n,Kl,S]
            (P(axis, None, None),) * nf,              # field states
            P(axis, None, None),                      # idx [n,T,B]
            P(axis, None, None) if nf else P(None, None),  # vals
            P(None, None),                 # plan [T, 1+3F+S] (replicated)
        )
        if routed:
            in_specs = in_specs + (P(None), P(None))  # routing tables [G]
        sharded = shard_map(
            per_shard,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        # latency mode donates the carry (args 0/1: count + field states);
        # dispatch rebinds to the outputs, so the inputs die at enqueue
        def run_sharded_superscan(*args):   # the program's name on the device
            return sharded(*args)

        fn = (jax.jit(run_sharded_superscan, donate_argnums=(0, 1))
              if self.donate_carry else jax.jit(run_sharded_superscan))
        self._fn_cache[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _place(self, payload, xs_h, lanes, plan_np):
        """Placement on the mesh (`FusedWindowPipeline.stage` calls it
        inside stage.fill): a step's lanes are dealt contiguously over the
        n source shards (any split works — the in-scan all-to-all
        re-routes every record to its key owner), and `device_put` hands
        each device its own lanes: nothing is first committed whole to
        device 0. Arrays without lanes (a value-less aggregate's [T, 1]
        placeholder) and the plan are replicated over the mesh, as the two
        programs' `in_specs` name them: every argument of a mesh dispatch
        arrives committed where the program reads it, and the call copies
        nothing from device to device. The five plan arrays go side by side
        as ONE [T, 1 + 3F + S] array (`_split_plan` inside the program): a
        replicated array is a transfer per device, and five of them cost
        `stage.put` 1.5 ms a dispatch on four chips (PERF.md, PR 31)."""
        with dispatch_stage(self.stage_clock, "stage.shard"):
            # the first array's -1 marks a dead lane: pad lanes are dead
            xs_h = tuple(
                self._deal_lanes(a, 0 if i else -1) if i < lanes else a
                for i, a in enumerate(xs_h))
            smin_pos, *rest = plan_np
            plan = np.concatenate([smin_pos[:, None], *rest], axis=1)
        return xs_h, (plan,), (tuple(
            self._shard_spec(*([None] * (a.ndim - 1))) if i < lanes
            else self._replicated
            for i, a in enumerate(xs_h)), self._replicated)

    def _split_plan(self, plan):
        """[T, 1 + 3F + S] -> smin_pos [T], fire_pos / fire_valid /
        fire_row [T, F], purge_mask [T, S]: `_place`'s packing undone,
        inside the program."""
        F = self.F
        return (plan[:, 0], plan[:, 1:1 + F], plan[:, 1 + F:1 + 2 * F],
                plan[:, 1 + 2 * F:1 + 3 * F], plan[:, 1 + 3 * F:])

    def _deal_lanes(self, a: np.ndarray, fill) -> np.ndarray:
        """[T, B, ...] -> [n, T, Bs, ...]: every step's lanes dealt
        contiguously over the n source shards, B padded with `fill` so each
        shard gets an equal lane count."""
        T, B = a.shape[:2]
        n = self.n
        Bs = -(-B // n)
        if Bs * n != B:
            a = np.concatenate(
                [a, np.full((T, Bs * n - B) + a.shape[2:], fill, a.dtype)],
                axis=1)
        return np.swapaxes(a.reshape((T, n, Bs) + a.shape[2:]), 0, 1)

    def _program(self, payload):
        return _MESH_CHAINED if payload.record else _MESH_CLASSIC

    def _canonical_fire_rows(self, count_out, outs, fired):
        """[n, R, K_local] per-shard fire slabs -> [used, K] canonical key
        order, `used` the rows the dispatch's `fired` fires filled: one
        enqueue of `_fire_shaper`'s program for the count slab and every
        field's (the deferred readback moves only those rows)."""
        from flink_tpu.runtime.fused_window_pipeline import _used_rows

        used = min(_used_rows(fired), count_out.shape[1])
        rows = _fire_shaper(used)(
            (count_out, *outs.values()), self._perm_dev)
        return rows[0], dict(zip(outs, rows[1:]))

    # ------------------------------------------------------------------
    # traced-chain path (whole-graph fusion over the mesh): every shard
    # runs the user's traceable chain + key extraction on ITS slice of the
    # raw source columns, then ONE all-to-all per step routes each record
    # to its key-range owner — the keyBy shuffle as an ICI collective
    # inside the compiled scan, replacing the host dataplane hop
    # ------------------------------------------------------------------
    def _build_raw(self, T: int, B: int, layout=None):
        phases = self.phase_counters
        combine = self.local_combine
        routed = self.routing is not None
        # layout (the planner's ColumnLayout): the staged fields and the
        # record's width — chains reading different fields never share one
        key = ("raw", T, B, phases, combine,
               None if not routed else self.routing.G, self.donate_carry,
               layout)
        if key in self._fn_cache:
            return self._fn_cache[key]

        n, Kl, K, S, axis = self.n, self.K_local, self.K, self.S, self.axis
        NSB, R = self.NSB, self.R
        # the per-shard superscan body ingests n*B post-shuffle lanes
        step = self._make_step(n * B, phases)
        nf = len(self._value_fields)
        partials_fn = self._make_partials_fn(B) if combine else None
        scatters = [f.scatter for f in self._value_fields]
        pro = self.prologue
        needs_ts = pro.needs_ts
        ingest_width = n * Kl * NSB if combine else n * B

        def per_shard(count, state_t, raw, srel, *rest):
            if routed:
                *rest, g_dst, g_slot = rest
                owner = self._dst_and_local((g_dst, g_slot))
            else:
                owner = self._dst_and_local(None)
            count = count[0]
            raw = jax.tree.map(lambda a: a[0], raw)
            srel = srel[0]
            if needs_ts:
                ts, rest = rest[0][0], rest[1:]
            else:
                ts = None
            (plan,) = rest
            smin_pos, fire_pos, fire_valid, fire_row, purge_mask = \
                self._split_plan(plan)
            state = {
                f.name: state_t[i][0]
                for i, f in enumerate(self._value_fields)
            }
            base = jax.lax.axis_index(axis).astype(jnp.int32) * Kl
            if combine:
                exchange = self._exchange_partials(
                    partials_fn, step, scatters)

            def routed_step(carry, args):
                inner, key_bounds, handed = carry
                if needs_ts:
                    raw_row, srel_row, ts_row = args[0], args[1], args[2]
                    plan_row = args[3:]
                else:
                    raw_row, srel_row = args[0], args[1]
                    ts_row = None
                    plan_row = args[2:]
                # the traced chain runs on THIS shard's raw lanes, before
                # any routing: filter/projection/keying happen where the
                # data landed, only survivors cross the interconnect
                with jax.named_scope(PROLOGUE):
                    live, keys, idx, vals, key_bounds = pro.apply(
                        raw_row, srel_row, ts_row, key_bounds, K=K, NSB=NSB,
                        needs_vals=bool(nf), layout=layout)
                if combine:
                    # the map-side combiner: this shard's survivors
                    # segment-reduce by (owner, key, rel-slice) and ONLY
                    # the dense partials cross the interconnect — a hot
                    # key costs n partials per slice, not its tuple mass
                    dst, lidx = owner(live, keys, srel_row)
                    pidx = jnp.where(live, dst * (Kl * NSB) + lidx, -1)
                    inner, delivered = exchange(inner, pidx, vals, plan_row)
                    return (inner, key_bounds, handed + delivered), None
                # the keyBy exchange: bin by owning key range, one
                # all-to-all over the mesh interconnect per step
                with jax.named_scope(EXCHANGE):
                    if routed:
                        # route-raw under a table: sender-side localization
                        dst, send_payload = owner(live, keys, srel_row)
                        localize = lambda r: r                 # noqa: E731
                    else:
                        dst = jnp.where(live, keys // Kl, -1)
                        send_payload = idx
                        localize = lambda r: jnp.where(        # noqa: E731
                            r >= 0, r - base * NSB, -1)
                    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
                    route = rows == dst[None, :]                     # [n, B]
                    send_idx = jnp.where(route, send_payload[None, :], -1)
                    recv_idx = jax.lax.all_to_all(
                        send_idx, axis, split_axis=0, concat_axis=0, tiled=False
                    ).reshape(-1)                                    # [n*B]
                    local_idx = localize(recv_idx)
                    if nf:
                        send_v = jnp.where(route, vals[None, :], 0.0)
                        recv_v = jax.lax.all_to_all(
                            send_v, axis, split_axis=0, concat_axis=0,
                            tiled=False,
                        ).reshape(-1)
                    else:
                        recv_v = jnp.zeros((1,), jnp.float32)
                inner, _ = step(inner, (local_idx, recv_v) + plan_row)
                delivered = jnp.sum((local_idx >= 0).astype(jnp.int32))
                return (inner, key_bounds, handed + delivered), None

            outs0 = {
                f.name: jnp.zeros((R, Kl), jnp.dtype(f.dtype))
                for f in self._value_fields
            }
            count_out0 = jnp.zeros((R, Kl), jnp.int32)
            inner0 = (state, count, outs0, count_out0)
            if phases:
                inner0 = inner0 + (jnp.zeros((PHASE_COUNTS,), jnp.int32),)
            kb0 = jnp.asarray([-1, 0], jnp.int32)
            xs = (raw, srel)
            if needs_ts:
                xs = xs + (ts,)
            xs = xs + (smin_pos, fire_pos, fire_valid, fire_row, purge_mask)
            (inner, key_bounds, handed), _ = jax.lax.scan(
                routed_step, (inner0, kb0, jnp.int32(0)), xs)
            if phases:
                state, count, outs, count_out, pc = inner
            else:
                state, count, outs, count_out = inner
            names = [f.name for f in self._value_fields]
            out = (
                count[None], tuple(state[nm][None] for nm in names),
                count_out[None], tuple(outs[nm][None] for nm in names),
                key_bounds[None],                         # [1, 2] per shard
                # what the exchange handed this shard over the dispatch
                # beside what its ingest read: n*B lanes a step, or the
                # n*Kl*NSB partial cells of the map-side combiner
                jnp.stack([handed, jnp.int32(T * ingest_width)])[None],
            )
            if phases:
                out = out + (pc[None],)
            return out

        if layout is not None:
            raw_spec = (P(axis, None, None),) * len(layout.columns)
        else:
            raw_spec = P(axis, None, None,
                         *([None] * len(self._planner._raw_shape or ())))
        out_specs = (
            P(axis, None, None),
            (P(axis, None, None),) * nf,
            P(axis, None, None),
            (P(axis, None, None),) * nf,
            P(axis, None),                                # key bounds [n,2]
            P(axis, None),                    # exchange (routed, lanes) [n,2]
        )
        if phases:
            out_specs = out_specs + (P(axis, None),)
        in_specs = (
            P(axis, None, None),                          # count [n,Kl,S]
            (P(axis, None, None),) * nf,                  # field states
            raw_spec,                       # raw [n,T,Bs,...] or its fields
            P(axis, None, None),                          # srel [n,T,Bs]
        )
        if needs_ts:
            in_specs = in_specs + (P(axis, None, None),)  # ts [n,T,Bs]
        in_specs = in_specs + (
            P(None, None),                 # plan [T, 1+3F+S] (replicated)
        )
        if routed:
            in_specs = in_specs + (P(None), P(None))      # routing tables
        sharded = shard_map(
            per_shard, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs, check_vma=False,
        )

        def run_sharded_chained_superscan(*args):
            out = sharded(*args)
            if phases:
                count, states, count_out, outs, kb, xc, pc = out
            else:
                count, states, count_out, outs, kb, xc = out
                pc = None
            # global key bounds: worst over shards (each shard saw only
            # its own pre-shuffle lanes); after them, in the one vector the
            # dispatch reads back anyway, every shard's exchange counts
            kb_g = jnp.concatenate([
                jnp.stack([kb[:, 0].max(), kb[:, 1].min()]),
                xc.reshape(-1)])
            if phases:
                return count, states, count_out, outs, kb_g, pc
            return count, states, count_out, outs, kb_g

        fn = (jax.jit(run_sharded_chained_superscan, donate_argnums=(0, 1))
              if self.donate_carry
              else jax.jit(run_sharded_chained_superscan))
        self._fn_cache[key] = fn
        return fn


    # ------------------------------------------------------------------
    # tiered-state row surface (state/tier_manager.py): same contract as
    # the single-chip pipeline's accessors — these MUST shadow the planner
    # delegation (the plan-only planner has no device state). All run off
    # the dispatch hot path (demotion/promotion between superbatches,
    # cell gathers at checkpoint), so the simple canonical round trip —
    # pull [K, S], mutate on host, re-shard — is the whole implementation;
    # note_external_slices needs no shadow (it mutates the planner's host
    # cursors, which ARE the mesh pipeline's canonical cursor state).
    # ------------------------------------------------------------------
    def gather_key_rows(self, kids):
        k = np.asarray(kids, np.int64)
        count, state = self._canonical_arrays()
        return count[k], {name: v[k] for name, v in state.items()}

    def _put_canonical(self, count: np.ndarray,
                       state: "Dict[str, np.ndarray]") -> None:
        n, Kl, S = self.n, self.K_local, self.S
        if self.routing is not None:
            count = self.routing.to_device_layout(np.asarray(count))
            state = {k: self.routing.to_device_layout(np.asarray(v))
                     for k, v in state.items()}
        self._count = jax.device_put(
            np.asarray(count).reshape(n, Kl, S),
            self._shard_spec(None, None))
        self._state = {
            name: jax.device_put(
                np.asarray(v).reshape(n, Kl, S),
                self._shard_spec(None, None))
            for name, v in state.items()
        }

    def clear_key_rows(self, kids) -> None:
        k = np.asarray(kids, np.int64)
        count, state = self._canonical_arrays()
        count = count.copy()
        count[k] = 0
        idents = {f.name: f.identity for f in self._value_fields}
        new_state = {}
        for name, arr in state.items():
            arr = arr.copy()
            arr[k] = idents[name]
            new_state[name] = arr
        self._put_canonical(count, new_state)

    def write_cells(self, kids, spos, counts, fields) -> None:
        k = np.asarray(kids, np.int64)
        s = np.asarray(spos, np.int64)
        count, state = self._canonical_arrays()
        count = count.copy()
        count[k, s] = np.asarray(counts)
        new_state = {}
        for name, arr in state.items():
            arr = arr.copy()
            arr[k, s] = np.asarray(fields[name], arr.dtype)
            new_state[name] = arr
        self._put_canonical(count, new_state)

    def gather_cells(self, kids, spos):
        k = np.asarray(kids, np.int64)
        s = np.asarray(spos, np.int64)
        count, state = self._canonical_arrays()
        return (count[k, s],
                {name: v[k, s] for name, v in state.items()})

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """Canonical [K, S] global arrays — interchangeable with single-chip
        FusedWindowPipeline snapshots (restore re-shards, so n -> m shard
        rescaling is just snapshot + restore). A routing table un-permutes
        before writing, so checkpoints are routing-independent too: any
        mesh size with any table restores the same snapshot."""
        count, state = self._canonical_arrays()
        snap = {
            "state": state,
            "count": count,
            "watermark": self._planner.watermark,
            "fire_cursor": self._planner.fire_cursor,
            "purged_to": self._planner.purged_to,
            "min_used_slice": self._planner.min_used_slice,
            "max_seen_slice": self._planner.max_seen_slice,
            "num_late_dropped": self._planner.num_late_records_dropped,
        }
        # shared-partials planner: per-spec fire cursors are part of the
        # canonical form (SharedWindowPipeline.snapshot writes them too —
        # a mesh checkpoint must restore into a single-chip shared
        # operator and vice versa)
        cursors = getattr(self._planner, "fire_cursors", None)
        if cursors is not None:
            snap["fire_cursors"] = list(cursors)
        return snap

    def restore(self, snap: dict) -> None:
        count = snap["count"]
        state = dict(snap["state"])
        snap_k = int(count.shape[0])
        if snap_k % self.n != 0:
            # a grown snapshot K (classic keyed path: pow2 rounded to the
            # OLD mesh's multiple) need not divide the NEW mesh — e.g. a
            # K=1024 checkpoint rescaled onto 6 devices. Identity-pad up
            # to the next multiple: rows beyond the key dictionary are
            # never addressed (dense ids < len(keydict) <= snap_k), so
            # padding is exact — and failing here instead would wedge the
            # job in a restart loop against the same checkpoint
            pad = -(-snap_k // self.n) * self.n - snap_k
            count = np.concatenate(
                [count, np.zeros((pad, self.S), count.dtype)])
            idents = {f.name: (f.identity, np.dtype(f.dtype))
                      for f in self._value_fields}
            state = {
                k: np.concatenate(
                    [v, np.full((pad, self.S), *idents[k])])
                for k, v in state.items()
            }
            snap_k += pad
        if snap_k != self.K:
            # capacity may have grown pre-snapshot (classic keyed path):
            # adopt the snapshot's K, exactly like the single-chip restore
            self.K = snap_k
            self.K_local = snap_k // self.n
            self._planner.K = snap_k
            if self.routing is not None:
                # table is sized to K: rebuild at identity for the adopted
                # capacity (the snapshot is canonical — any table is a
                # valid placement of it)
                self.routing = KeyGroupRouting(
                    snap_k, self.n, self._num_key_groups,
                    version=self.routing.version + 1)
                self._refresh_route_tables()
            self._fn_cache.clear()
        self._put_canonical(count, state)
        self._planner.watermark = snap["watermark"]
        self._planner.fire_cursor = snap["fire_cursor"]
        self._planner.purged_to = snap["purged_to"]
        self._planner.min_used_slice = snap["min_used_slice"]
        self._planner.max_seen_slice = snap["max_seen_slice"]
        self._planner.num_late_records_dropped = snap["num_late_dropped"]
        if getattr(self._planner, "fire_cursors", None) is not None:
            self._planner.fire_cursors = list(snap["fire_cursors"])


@functools.lru_cache(maxsize=None)
def _fire_shaper(used: int):
    """The program that shapes a mesh dispatch's fire slabs for the
    readback, one per `used` rows as `_row_slicer` is one per slice (and,
    inside jit's own cache, one with a routing table and one without):
    each [n, R, K_local] slab is cut to its first `used` rows on every
    shard, the contiguous key ranges are concatenated to [used, K], and
    under a routing table the columns are permuted back to canonical key
    order (`perm`; None without a table)."""

    def shape_fire_rows(slabs, perm):
        def canonical(slab):
            n, _, k_local = slab.shape
            rows = jnp.transpose(slab[:, :used], (1, 0, 2)).reshape(
                used, n * k_local)
            return rows if perm is None else jnp.take(rows, perm, axis=1)

        return tuple(canonical(slab) for slab in slabs)

    return jax.jit(shape_fire_rows)


class _MeshProgram:
    """One of the two sharded window programs as
    `FusedWindowPipeline.dispatch` uses it (the contract of its
    `_ChipProgram`; `p` is the mesh pipeline): `sharded_superscan` over key
    ids, or with the traced prologue before the exchange
    `sharded_chained_superscan` over a record. States go in as [n, Kl, S]
    slabs with the plan after the lanes; the fire buffers are zeroed inside
    the program. Streaming fire readback stays single-chip only — splitting
    the mesh dispatch would multiply the per-step all-to-all count."""

    flat = False
    grouped = False

    def __init__(self, chained: bool):
        self.chained = chained
        self.name = ("sharded_chained_superscan" if chained
                     else "sharded_superscan")

    def width(self, a, T: int) -> int:
        return int(a.shape[2])      # lanes per source shard: [n, T, Bs]

    def build(self, p: ShardedFusedPipeline, T: int, B: int, layout):
        return p._build_raw(T, B, layout) if self.chained else p._build(T, B)

    def call(self, p, run, staged, T: int, B: int):
        names = [f.name for f in p._value_fields]
        xs, record_sig = staged.scan_xs()
        args = (p._count, tuple(p._state[nm] for nm in names)) + xs \
            + staged.plan
        if p.routing is not None:
            args = args + (p._g_dst, p._g_slot)
        p._count, states, count_out, field_outs, *tail = p._tracked(
            self.name, run, args, {"T": T, "B": B, "n": p.n, **record_sig})
        p._state = dict(zip(names, states))
        key_bounds = tail.pop(0) if self.chained else None
        # phase counters [n, PHASE_COUNTS]: read back per shard, folded at
        # resolve
        return (count_out, dict(zip(names, field_outs)), key_bounds,
                tail[0] if tail else None)

    def fire_rows(self, p, count_out, outs, fired: int):
        return p._canonical_fire_rows(count_out, outs, fired)


_MESH_CLASSIC, _MESH_CHAINED = _MeshProgram(False), _MeshProgram(True)
