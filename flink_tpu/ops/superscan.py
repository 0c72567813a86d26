"""The per-step superscan body: ingest/fire/purge over the [K, S] slice ring.

Shared by the single-chip fused superscan (runtime/fused_window_pipeline),
the chained whole-graph-fusion program, and the shard_map sharded superscan
(parallel/sharded_superscan — each shard runs this on its local key range).
It lives in `ops` because it is a pure device-kernel builder over a
DeviceAggregator: no runtime state, no host planning — exactly the layer
matmul_hist and pallas_superscan occupy, and the reason `parallel/` can
compose with it without importing the runtime (ARCH001).
"""

from __future__ import annotations

import functools as _functools


#: the counts a step threads through its carry with `phase_counters`: records
#: ingested, fire slots executed, steps that purged, steps whose live records
#: lay in one slice
PHASE_COUNTS = 4


def default_ingest() -> str:
    """THE backend-dependent ingest choice, single-sourced: programs built
    fresh per job (the chained single-chip superscan and both sharded
    builds) use direct scatter-adds off-TPU — the [K, S] ring is
    cache-resident on a scalar core and the dense one-hot MXU contraction
    does K*NSB work per record there. On TPU the matmul-histogram form
    wins. (The classic single-chip `_build_superscan` keeps its historical
    explicit 'matmul' on every backend for executable-cache and bench
    continuity.) Identical math either way — both are pure adds into the
    same cells."""
    import jax

    return "matmul" if jax.default_backend() == "tpu" else "scatter"


def read_window(ring, first, spw, scatter):
    """Fold one window out of a slice ring whose LAST axis is the ring
    ([K, S] keyed, [S] global): the `spw` slices from ring position `first`
    on, wrapping at S. One dense pass: the cells outside the window are
    masked to the combiner's neutral element and the whole ring axis goes
    through the field's `combine_reduce` — the same cells in the same
    dtype as indexing them out, so counts, integer sums, min and max are
    bit-equal to it (a float sum adds its cells in ring order). Never
    `ring[..., pos]` with a position vector: along the minor axis that is
    one single-element gather per cell once a window has several slices.
    Nor `spw` unrolled slices: the program then grows with the window, and
    the shared-partials program of tests/test_bench_correlated.py fell to
    0.18x its independent plans on the CPU backend (that test's floor: 0.3)."""
    import jax.numpy as jnp

    from flink_tpu.ops.aggregators import combine_reduce, scan_identity

    S = ring.shape[-1]
    inside = (jnp.arange(S, dtype=jnp.int32) - first) % S < spw
    neutral = jnp.asarray(scan_identity(ring.dtype, scatter), ring.dtype)
    return combine_reduce(scatter)(
        jnp.where(inside, ring, neutral), ring.ndim - 1)


def make_superscan_step(agg, K, S, NSB, F, R, SPW, chunk, exact,
                        ingest: str = "matmul", phase_counters: bool = False,
                        fire_spws=None):
    """The per-step ingest/fire/purge body, shared by the single-chip
    superscan and the shard_map sharded superscan (each shard runs this on
    its local key range).

    `ingest` selects how add-combining fields land in the [K, S] ring:
    'matmul' (default, unchanged) re-expresses the scatter as MXU one-hot
    histograms — the TPU form; 'scatter' uses direct scatter-adds, which is
    what wins on CPU backends (the [K, S] ring is cache-resident and the
    dense one-hot contraction does K*NSB work per record on a scalar
    core). Identical math either way: both are pure adds into the same
    cells, counts exact in int32.

    The matmul histogram is as wide as the step's records are: a step whose
    live lanes all lie in the step's lowest slice (`idx % NSB == 0`; nearly
    every step of an in-order stream, a batch being far shorter than a
    slice) contracts K segments, slice 0 of the partial, where a step that
    straddles a slice boundary contracts K * NSB. One `lax.cond` per step
    picks, on a predicate reduced from the step's own lanes, and yields the
    step's [NSB, K] partials, which the fold adds into the ring outside it:
    the ring is never an operand of that conditional (XLA:TPU would give it
    its default layout there and turn it over in every step, PERF.md
    section 6, PRs 33 and 36).

    'partials' consumes PRE-REDUCED per-step partials instead of record
    lanes — the receive side of the mesh map-side combiner
    (parallel.mesh.local-combine): the idx slot of `args` carries the
    step's [K, NSB] count partial and the vals slot a tuple of [K, NSB]
    per-VALUE-field partials (aligned with the aggregator's VALUE fields,
    min/max cells holding their scan identity where untouched). Ingest
    becomes one dense column combine per field — the same
    add/min/max ops the lane scatter applies, so the ring state is exact;
    fire and purge are the identical shared body.

    `phase_counters` (device-plane observability) threads an
    int32[PHASE_COUNTS] counter through the carry — [records ingested, fire
    slots executed, steps that purged, steps whose live records lay in one
    slice] — so a dispatch's device time can be attributed to the
    ingest/fire/purge phases, and the narrow histogram's engagement read,
    without any extra host sync (the counts ride the same async readback as
    the fire rows). The carry becomes a 5-tuple; callers opt in, so the
    default executable shape is unchanged.

    A fire reads the ring through `read_window`, whatever the window's
    length: one masked dense pass over the [K, S] ring per field, folded
    along S by the field's combiner, written as one [K] row of the [R, K]
    fire buffer. The purge's conditional works on the ring's transpose, so
    that the scan carries the ring as ingest and fire use it (key-minor).

    `fire_spws` (shared-partials, graph/window_sharing.py): per-fire-slot
    window lengths in slices, length F, replacing the uniform SPW — one
    ring of gcd-granule partials serves several correlated window shapes
    (Factor Windows), each firing its own slice-run length from the shared
    state. None keeps the classic single-shape program byte-identical."""
    import jax
    import jax.numpy as jnp

    from flink_tpu.metrics.device_phases import (
        FIRE, FOLD, FOLD_VALUE, HIST, HIST_VALUE, INGEST, PURGE, SCATTER,
        SCATTER_VALUE)
    from flink_tpu.ops import matmul_hist
    from flink_tpu.ops.aggregators import VALUE

    spws = tuple(fire_spws) if fire_spws is not None else (SPW,) * F
    if len(spws) != F:
        raise ValueError(f"fire_spws has {len(spws)} slots, expected F={F}")
    vfields = [
        (f.name, jnp.dtype(f.dtype), f.scatter, f.identity)
        for f in agg.fields
        if f.source == VALUE
    ]
    nseg = K * NSB

    def step(carry, args):
        if phase_counters:
            # `phase_c`, not `pc`: the ingest paths below use `pc` for
            # their partial-count histograms
            state, count, outs, count_out, phase_c = carry
        else:
            state, count, outs, count_out = carry
        idx, vals, smin_pos, fire_pos, fire_valid, fire_row, purge_mask = args
        cols = (smin_pos + jnp.arange(NSB, dtype=jnp.int32)) % S

        if ingest == "partials":
            # pre-reduced ingest (the map-side combiner's receive side):
            # idx is the step's [K, NSB] count partial, vals the tuple of
            # per-VALUE-field [K, NSB] partials — one dense column combine
            # per field, same add/min/max semantics as the lane scatter
            with jax.named_scope(INGEST):
                cpart = idx
                with jax.named_scope(FOLD):
                    count = count.at[:, cols].add(cpart)
                new_state = {}
                for (name, dt, scatter, _ident), part in zip(vfields, vals):
                    with jax.named_scope(FOLD_VALUE):
                        upd = getattr(state[name].at[:, cols], scatter)
                        new_state[name] = upd(part.astype(dt))
                state = new_state if vfields else state
            return _fire_purge(
                state, count, outs, count_out, phase_c if phase_counters
                else None, cpart.sum(), ~jnp.any(cpart[:, 1:] != 0),
                (fire_pos, fire_valid, fire_row, purge_mask))

        with jax.named_scope(INGEST):
            # ingest: MXU histograms over (key, rel-slice) segments for
            # add-combining fields (or direct scatter-adds on CPU backends);
            # min/max fields always scatter-combine (no matmul form exists for
            # order statistics — the scatter unit is the cost of supporting
            # them on the fused path at all). Nested scopes name its pieces
            # for a capture's phase table (metrics/device_phases.py): HIST
            # the step's [K, NSB] partial, FOLD that partial added into the
            # ring's columns, SCATTER a per-record scatter into the ring;
            # the same pieces for the VALUE fields under names of their own
            # (HIST_VALUE, FOLD_VALUE, SCATTER_VALUE)
            kid = idx // NSB
            srel = idx % NSB
            col = (smin_pos + srel) % S
            safe_kid = jnp.where(idx >= 0, kid, K)  # OOB rows drop
            # no live lane above the step's lowest slice (a dead lane's -1
            # has srel NSB - 1): what the matmul histogram's width hangs on
            one_slice = ~jnp.any((idx >= 0) & (srel != 0))
            # CPU add-ingest form: XLA lowers a FLAT 1-D index scatter ~2x
            # faster than the 2-D (kid, col) scatter, so adds go through a
            # [K*NSB] staging histogram folded densely into the ring columns —
            # gated on the dense fold (nseg per step) staying small next to
            # the batch, so huge-K geometries keep the direct scatter
            flat_adds = ingest != "matmul" and nseg <= 16 * idx.shape[0]
            if ingest == "matmul":
                def hists(seg, nsegs, as_partial):
                    # the count's histogram, then one per add-combining
                    # VALUE field, each shaped into the step's [NSB, K]
                    # partial where it is made
                    pc = as_partial(
                        matmul_hist.count_hist(seg, nsegs, chunk=chunk))
                    with jax.named_scope(HIST_VALUE):
                        return pc, {
                            name: as_partial(matmul_hist.weighted_hist(
                                seg, vals, nsegs, chunk=chunk, exact=exact))
                            for name, _dt, scatter, _ident in vfields
                            if scatter == "add"}

                def narrow():
                    # [K] histograms over the key alone (a dead lane's
                    # -1 // NSB stays out of range): slice 0 of the step's
                    # partial, the slices above it zero
                    return hists(idx // NSB, K, lambda h: jnp.pad(
                        h[None], ((0, NSB - 1), (0, 0))))

                def wide():
                    return hists(idx, nseg, lambda h: h.reshape(K, NSB).T)

                def fold(ring, part):
                    # part's slices into their ring columns, the live ones
                    # only: a loop the ring is carried through, never a
                    # conditional's operand
                    def add_column(i, ring):
                        column = jax.lax.dynamic_slice_in_dim(
                            ring, cols[i], 1, axis=1)
                        return jax.lax.dynamic_update_slice_in_dim(
                            ring, column + part[i][:, None].astype(ring.dtype),
                            cols[i], axis=1)

                    return jax.lax.fori_loop(
                        0, jnp.where(one_slice, 1, NSB), add_column, ring)

                # the step's partials as the fold reads them, key-minor
                # [NSB, K]; the ring stays outside the conditional
                with jax.named_scope(HIST):
                    pc, add_parts = (jax.lax.cond(one_slice, narrow, wide)
                                     if NSB > 1 else wide())
                with jax.named_scope(FOLD):
                    count = fold(count, pc)
            elif flat_adds:
                # dead rows carry idx -1, which jax would WRAP to the last
                # segment (numpy negative indexing; mode="drop" only drops
                # past-the-end) — remap them to nseg so the drop is real
                with jax.named_scope(HIST):
                    safe_idx = jnp.where(idx >= 0, idx, nseg)
                    pc = jnp.zeros((nseg,), jnp.int32).at[safe_idx].add(
                        jnp.int32(1), mode="drop").reshape(K, NSB)
                with jax.named_scope(FOLD):
                    count = count.at[:, cols].add(pc)
            else:
                with jax.named_scope(SCATTER):
                    count = count.at[safe_kid, col].add(
                        jnp.int32(1), mode="drop")
            new_state = {}
            for name, dt, scatter, ident in vfields:
                if scatter == "add" and ingest == "matmul":
                    with jax.named_scope(FOLD_VALUE):
                        new_state[name] = fold(state[name], add_parts[name])
                elif scatter == "add" and flat_adds:
                    with jax.named_scope(HIST_VALUE):
                        ph = jnp.zeros((nseg,), dt).at[
                            jnp.where(idx >= 0, idx, nseg)].add(
                            vals.astype(dt), mode="drop").reshape(K, NSB)
                    with jax.named_scope(FOLD_VALUE):
                        new_state[name] = state[name].at[:, cols].add(ph)
                elif scatter == "add":
                    with jax.named_scope(SCATTER_VALUE):
                        new_state[name] = state[name].at[safe_kid, col].add(
                            vals.astype(dt), mode="drop")
                else:
                    with jax.named_scope(SCATTER_VALUE):
                        upd = getattr(state[name].at[safe_kid, col], scatter)
                        new_state[name] = upd(vals.astype(dt), mode="drop")
            state = new_state if vfields else state
        return _fire_purge(
            state, count, outs, count_out,
            phase_c if phase_counters else None,
            jnp.sum((idx >= 0).astype(jnp.int32)), one_slice,
            (fire_pos, fire_valid, fire_row, purge_mask))

    def _fire_purge(state, count, outs, count_out, phase_c, ingested,
                    one_slice, plan):
        """Fire + purge, shared verbatim by the lane-scatter and
        pre-reduced ('partials') ingest forms — the combine path must be a
        different INGEST, never a different fire/purge."""
        fire_pos, fire_valid, fire_row, purge_mask = plan

        with jax.named_scope(FIRE):
            # fire: combine the window's slice columns, write compact rows.
            # The WHOLE fire body sits under the cond, the ring read included:
            # most steps fire nothing, and the per-slot read+combine is the
            # dominant per-step fixed cost when computed eagerly (at K=8192,
            # SPW=10, F=2 that is 20x the ingest work of an 8k batch) —
            # identical results, the eager crow was discarded unless
            # fire_valid was set anyway
            def write_fire(f, bufs):
                row = jnp.clip(fire_row[f], 0, R - 1)

                def do_fire(b):
                    outs, count_out = b
                    crow = read_window(count, fire_pos[f], spws[f], "add")
                    count_out = jax.lax.dynamic_update_index_in_dim(
                        count_out, crow, row, 0)
                    new_outs = {}
                    for name, _dt, scatter, _ident in vfields:
                        vrow = read_window(
                            state[name], fire_pos[f], spws[f], scatter)
                        new_outs[name] = jax.lax.dynamic_update_index_in_dim(
                            outs[name], vrow, row, 0)
                    return (new_outs if vfields else outs), count_out

                return jax.lax.cond(fire_valid[f] > 0, do_fire, lambda b: b, bufs)

            bufs = (outs, count_out)
            for f in range(F):
                bufs = write_fire(f, bufs)
            outs, count_out = bufs

        with jax.named_scope(PURGE):
            # purge expired ring columns (reset to the field's identity); under
            # a cond for the same reason — the S*K multiply/where is pure
            # identity on the all-ones masks most steps carry.
            # The conditional takes the ring TRANSPOSED, [S, K]: XLA:TPU gives
            # a conditional's operands their default layout, and [K, 32]'s
            # is slice-minor where ingest and fire work key-minor, so the
            # scan carried the ring slice-minor (32 lanes padded to 128) and
            # turned all of it over twice in EVERY step, ~4.4 ms a dispatch
            # at K = 65536 (PERF.md section 6, PR 33). [S, K]'s default layout
            # is key-minor, and there the transposes are layout changes that
            # move nothing (tests/test_tpu_compile.py holds the carry to it).
            def do_purge(sc):
                state_t, count_t = sc
                count_t = count_t * purge_mask[:, None]
                state_t = {
                    name: jnp.where(
                        purge_mask[:, None] > 0,
                        state_t[name],
                        jnp.asarray(ident, dt),
                    )
                    for name, dt, _scatter, ident in vfields
                }
                return state_t, count_t

            purged = jnp.any(purge_mask == 0)
            state_t, count_t = jax.lax.cond(
                purged, do_purge, lambda sc: sc,
                ({name: v.T for name, v in state.items()}, count.T))
            state = {name: v.T for name, v in state_t.items()}
            count = count_t.T
        if phase_counters:
            phase_c = phase_c + jnp.stack([
                ingested.astype(jnp.int32),
                jnp.sum(fire_valid).astype(jnp.int32),
                purged.astype(jnp.int32),
                one_slice.astype(jnp.int32),
            ])
            return (state, count, outs, count_out, phase_c), None
        return (state, count, outs, count_out), None

    return step


def make_segment_partials(agg, nseg, chunk, exact, ingest: str = "matmul"):
    """The map-side combiner's send side (parallel.mesh.local-combine):
    build fn(idx, vals) segment-reducing ONE step's record lanes into
    dense flat partials over `nseg` destination segments — count plus one
    partial per VALUE field, each pre-reduced by the field's own scatter
    combiner (add/min/max), untouched cells holding the scan identity so
    merging them downstream is a no-op. Lanes with idx < 0 drop.

    `ingest` mirrors the ring-ingest choice: 'matmul' builds add partials
    as MXU one-hot histograms (the TPU form; matmul_hist's exact bf16
    3-term split for float adds when `exact`), anything else uses direct
    flat scatters. Min/max partials always scatter — no matmul form
    exists for order statistics, exactly like the ring ingest.

    Returns (fn, vfields) where vfields is the (name, dtype, scatter,
    identity) tuple list the partials align with."""
    import jax.numpy as jnp

    from flink_tpu.ops import matmul_hist
    from flink_tpu.ops.aggregators import VALUE, scan_identity

    vfields = [
        (f.name, jnp.dtype(f.dtype), f.scatter, f.identity)
        for f in agg.fields
        if f.source == VALUE
    ]

    def partials(idx, vals):
        safe = jnp.where(idx >= 0, idx, nseg)   # OOB segment drops
        if ingest == "matmul":
            cpart = matmul_hist.count_hist(idx, nseg, chunk=chunk)
        else:
            cpart = jnp.zeros((nseg,), jnp.int32).at[safe].add(
                jnp.int32(1), mode="drop")
        parts = []
        for name, dt, scatter, _ident in vfields:
            if scatter == "add" and ingest == "matmul":
                p = matmul_hist.weighted_hist(
                    idx, vals, nseg, chunk=chunk, exact=exact).astype(dt)
            elif scatter == "add":
                p = jnp.zeros((nseg,), dt).at[safe].add(
                    vals.astype(dt), mode="drop")
            else:
                init = jnp.full((nseg,), scan_identity(dt, scatter), dt)
                p = getattr(init.at[safe], scatter)(
                    vals.astype(dt), mode="drop")
            parts.append(p)
        return cpart, tuple(parts)

    return partials, vfields


def make_global_scan_step(agg, S, NSB, F, R, SPW, fire_spws=None,
                          phase_counters: bool = False):
    """The per-step body of the GLOBAL-window superscan: keyed-partial →
    cross-segment fold, no [K, S] ring at all.

    Nexmark-Q7-shaped aggregates (a per-window GLOBAL max/min/sum with
    keyed pre-aggregation only as an implementation detail) do not need
    per-key state: each batch folds to [NSB] per-rel-slice partials with
    one masked whole-column reduction per slice (ops/segment_ops.
    bounded_segment_fold — no scatter unit, no one-hot matrices), the
    partials fold into a tiny [S] slice ring, and a window fire folds its
    SPW slice cells into ONE scalar. This replaces the dense per-batch
    keyed reduction (the [K, S] nibble-histogram path plus a [R, K]
    readback and a host-side max over keys) with the single-chip analogue
    of the mesh's psum/pmax cross-shard merge — and the readback shrinks
    from R*K rows to R scalars.

    Unbounded min/max get a device form here for free: the fold is
    elementwise, so no bounded-domain (max8) declaration is needed.

    idx lanes may carry either bare rel-slices or the keyed encoding
    `kid * NSB + srel` (the staged streams the keyed superscan consumes);
    both reduce to the same rel-slice via `idx % NSB`, negatives drop.
    """
    import jax
    import jax.numpy as jnp

    from flink_tpu.ops.aggregators import VALUE, scan_identity
    from flink_tpu.ops.segment_ops import bounded_segment_fold

    spws = tuple(fire_spws) if fire_spws is not None else (SPW,) * F
    if len(spws) != F:
        raise ValueError(f"fire_spws has {len(spws)} slots, expected F={F}")
    vfields = [
        (f.name, jnp.dtype(f.dtype), f.scatter, f.identity)
        for f in agg.fields
        if f.source == VALUE
    ]

    def step(carry, args):
        if phase_counters:
            state, count, outs, count_out, phase_c = carry
        else:
            state, count, outs, count_out = carry
        idx, vals, smin_pos, fire_pos, fire_valid, fire_row, purge_mask = args

        # ingest: [NSB] partials per batch, folded into the [S] ring
        srel = jnp.where(idx >= 0, idx % NSB, -1)
        cols = (smin_pos + jnp.arange(NSB, dtype=jnp.int32)) % S
        cpart = bounded_segment_fold(
            jnp.ones(idx.shape, jnp.int32), srel, NSB, "add", 0)
        count = count.at[cols].add(cpart)
        new_state = {}
        for name, dt, scatter, _ident in vfields:
            part = bounded_segment_fold(
                vals.astype(dt), srel, NSB, scatter,
                scan_identity(dt, scatter))
            upd = getattr(state[name].at[cols], scatter)
            new_state[name] = upd(part)
        state = new_state if vfields else state

        # fire: fold the window's slice cells into one scalar per slot
        def write_fire(f, bufs):
            row = jnp.clip(fire_row[f], 0, R - 1)

            def do_fire(b):
                outs, count_out = b
                count_out = count_out.at[row].set(
                    read_window(count, fire_pos[f], spws[f], "add"))
                new_outs = {}
                for name, _dt, scatter, _ident in vfields:
                    folded = read_window(
                        state[name], fire_pos[f], spws[f], scatter)
                    new_outs[name] = outs[name].at[row].set(folded)
                return (new_outs if vfields else outs), count_out

            return jax.lax.cond(fire_valid[f] > 0, do_fire, lambda b: b, bufs)

        bufs = (outs, count_out)
        for f in range(F):
            bufs = write_fire(f, bufs)
        outs, count_out = bufs

        # purge expired cells back to identity
        def do_purge(sc):
            state, count = sc
            count = count * purge_mask
            if vfields:
                state = {
                    name: jnp.where(
                        purge_mask > 0, state[name],
                        jnp.asarray(scan_identity(dt, scatter), dt))
                    for name, dt, scatter, _ident in vfields
                }
            return state, count

        purged = jnp.any(purge_mask == 0)
        state, count = jax.lax.cond(
            purged, do_purge, lambda sc: sc, (state, count))
        if phase_counters:
            phase_c = phase_c + jnp.stack([
                jnp.sum((idx >= 0).astype(jnp.int32)),
                jnp.sum(fire_valid).astype(jnp.int32),
                purged.astype(jnp.int32),
                (~jnp.any(srel > 0)).astype(jnp.int32),
            ])
            return (state, count, outs, count_out, phase_c), None
        return (state, count, outs, count_out), None

    return step


@_functools.lru_cache(maxsize=None)
def build_global_superscan(agg, S, NSB, F, R, SPW, T, B,
                           fire_spws=None, phases: bool = False):
    """Compiled T-step global-window superscan (lax.scan over
    make_global_scan_step; module-level cache like _build_superscan).

    run(state {field: [S]}, count [S] i32, outs {field: [R]},
        count_out [R] i32, idx [T, B] i32, vals [T, B] f32,
        smin_pos, fire_pos, fire_valid, fire_row, purge_mask)
      -> (state, count, outs, count_out[, phase_counters])"""
    import jax
    import jax.numpy as jnp

    step = make_global_scan_step(agg, S, NSB, F, R, SPW,
                                 fire_spws=fire_spws, phase_counters=phases)

    # the function's name is the program's name on the device: the trace's
    # module reads jit_run_<CompileTracker program>
    @jax.jit
    def run_global_superscan(state, count, outs, count_out, idx, vals,
                             smin_pos, fire_pos, fire_valid, fire_row,
                             purge_mask):
        carry0 = (state, count, outs, count_out)
        if phases:
            carry0 = carry0 + (jnp.zeros((PHASE_COUNTS,), jnp.int32),)
        carry, _ = jax.lax.scan(
            step, carry0,
            (idx, vals, smin_pos, fire_pos, fire_valid, fire_row,
             purge_mask),
        )
        return carry

    return run_global_superscan


# ---------------------------------------------------------------------------
# fused session superscan: T ingest steps + in-scan segmented gap-merges
# in ONE device program (runtime/tpu_session_operator.py drives this)
# ---------------------------------------------------------------------------

def session_gap_merge_scan(c, fmn, fmx, fl, vfields, idents, g, wm_rel, est):
    """The [K, n]-wide touching-fragment gap-merge scan — ONE copy of the
    join/break/close semantics shared by the per-watermark merge program
    (runtime/tpu_session_operator._build_merge_scan) and the fused
    superspan's in-carry merges (make_session_superscan below). The
    overflow-recovery contract ("placement never changes a result")
    requires the two paths to be bit-identical; single-sourcing the scan
    body makes a one-sided edit to the join condition (min - cmax <= g)
    or the close condition (cmax + g - 1 <= wm_rel) impossible.

    c/fmn/fmx [K, n] and fl ([K, n] per value field) are the gathered
    span: per-cell fragment counts and min/max rel-ms (columns with c == 0
    are gaps — callers zero invalid columns); `est` is the emission-slot
    carry (slots, e_start, e_end, e_cnt, e_s0, e_s1, e_flds, overflow)
    with [K, M] slot arrays — fresh for a standalone merge, carried across
    merges for a superspan. Returns the updated est: sessions closed by
    this scan (a following fragment breaks the gap, or end <= wm_rel)
    appended at each key's next slot, e_s0/e_s1 holding the session's
    column range in THIS scan's coordinates for the caller's purge."""
    import jax.numpy as jnp

    from flink_tpu.ops.aggregators import combine_binary

    combine = {sc: combine_binary(sc) for _n, _dt, sc in vfields}
    i32 = jnp.int32
    K, n = c.shape
    M = est[1].shape[1]
    mslots = jnp.arange(M, dtype=i32)[None, :]

    open_ = jnp.zeros((K,), bool)
    cmin = jnp.zeros((K,), i32)
    cmax = jnp.full((K,), -(1 << 30), i32)
    ccnt = jnp.zeros((K,), i32)
    cstart = jnp.zeros((K,), i32)
    clast = jnp.zeros((K,), i32)
    cflds = [jnp.full((K,), ident, f.dtype) for f, ident in zip(fl, idents)]

    def do_emit(mask, est):
        (slots, e_start, e_end, e_cnt, e_s0, e_s1, e_flds, overflow) = est
        can = mask & (slots < M)
        oh = (mslots == slots[:, None]) & can[:, None]        # [K, M]
        e_start = jnp.where(oh, cmin[:, None], e_start)
        e_end = jnp.where(oh, cmax[:, None], e_end)
        e_cnt = jnp.where(oh, ccnt[:, None], e_cnt)
        e_s0 = jnp.where(oh, cstart[:, None], e_s0)
        e_s1 = jnp.where(oh, clast[:, None], e_s1)
        e_flds = [jnp.where(oh, cf[:, None], ef)
                  for cf, ef in zip(cflds, e_flds)]
        overflow = overflow | jnp.any(mask & (slots >= M))
        slots = slots + can.astype(i32)
        return (slots, e_start, e_end, e_cnt, e_s0, e_s1, e_flds, overflow)

    for i in range(n):
        ci = c[:, i]
        frag = ci > 0
        mni = fmn[:, i]
        mxi = fmx[:, i]
        joins = open_ & frag & (mni - cmax <= g)
        breaks = open_ & frag & ~joins
        est = do_emit(breaks, est)
        starts = frag & ~joins
        cmin = jnp.where(starts, mni, cmin)
        ccnt = jnp.where(starts, 0, ccnt)
        cstart = jnp.where(starts, i, cstart)
        cflds = [jnp.where(starts, jnp.asarray(ident, cf.dtype), cf)
                 for cf, ident in zip(cflds, idents)]
        open_ = open_ | frag
        cmax = jnp.where(frag, mxi, cmax)
        ccnt = jnp.where(frag, ccnt + ci, ccnt)
        clast = jnp.where(frag, i, clast)
        cflds = [
            jnp.where(frag, combine[sc](cf, fi[:, i]), cf)
            for cf, fi, (_n, _dt, sc) in zip(cflds, fl, vfields)
        ]
    return do_emit(open_ & (cmax + g - 1 <= wm_rel), est)


def session_ingest_scatter(K, S, vfields):
    """The per-batch session ingest scatter — ONE copy of the [K, S] ring
    update (count/min-ts/max-ts/value fields, kid < 0 dropped via the
    sentinel row) shared by the per-step program
    (runtime/tpu_session_operator._build_ingest) and the fused superspan's
    in-scan ingest (make_session_superscan below). The overflow-recovery
    contract ("placement never changes a result") requires the two paths
    to be bit-identical; single-sourcing the body makes a one-sided edit
    to the scatter semantics impossible, like session_gap_merge_scan for
    the merge side."""
    import jax.numpy as jnp

    def ingest(cnt, mn, mx, fields, kid, spos, rel, vals):
        flat = jnp.where(kid >= 0, kid * S + spos, K * S)
        cnt = cnt.reshape(-1).at[flat].add(1, mode="drop").reshape(K, S)
        mn = mn.reshape(-1).at[flat].min(rel, mode="drop").reshape(K, S)
        mx = mx.reshape(-1).at[flat].max(rel, mode="drop").reshape(K, S)
        new_fields = []
        for (name, dt, scatter), f in zip(vfields, fields):
            upd = getattr(f.reshape(-1).at[flat], scatter)
            new_fields.append(
                upd(vals.astype(jnp.dtype(dt)), mode="drop").reshape(K, S))
        return cnt, mn, mx, tuple(new_fields)

    return ingest


@_functools.lru_cache(maxsize=None)
def make_session_superscan(K, S, M, g, vfields, idents, T, B):
    """Compile the fused session dispatch: T staged ingest steps with the
    gap-merge scan RUNNING INSIDE THE PROGRAM at watermark steps — sessions
    coalesce in the scan carry (the touching-session merge semantics of
    api/windowing/assigners.py EventTimeSessionWindows.merge_windows:
    fragments at consecutive slices join iff min_ts(frag) - max_ts(cur)
    <= gap) and never round-trip to host per merge. Closed sessions
    accumulate into M fixed emission slots per key across the whole
    dispatch; ONE packed int32 array comes back per dispatch, in the exact
    layout of the per-watermark `_build_merge_scan` (so the operator's
    `_resolve_entry` parses both).

    vfields: ((name, dtype_str, scatter), ...); idents aligned identities.

    run(cnt [K,S] i32, mn [K,S] i32, mx [K,S] i32, fields ([K,S] dt, ...),
        kid [T,B] i32, spos [T,B] i32, rel [T,B] i32, vals [T,B] f32,
        merge_flag [T] i32, lo_pos [T] i32, lo_rel [T] i32, wm_rel [T] i32)
      -> (cnt, mn, mx, fields, packed [K+1, (3+nf)*M + 1] i32)

    Coordinates: everything slice-relative to ONE dispatch base `lo0`
    (lo_rel[t] = merge-span base slice − lo0; rel-ms fit int32 — the
    caller guards (span + 2) * g < 2^31). The caller guarantees the whole
    dispatch's resident span stays inside the ring (< S slices), so every
    merge scans the full ring from lo_pos — empty columns are no-ops.
    Emission overflow (a key closing more than M sessions in one
    dispatch) sets the packed overflow flag; the caller discards the
    fused result and replays the dispatch on the exact per-watermark
    path from its retained pre-dispatch state."""
    import jax
    import jax.numpy as jnp

    nf = len(vfields)
    i32 = jnp.int32

    ingest = session_ingest_scatter(K, S, vfields)

    def merge(state, lo_pos, lo_rel, wm_rel):
        (cnt, mn, mx, fields, est) = state
        idx_p = jnp.arange(S, dtype=i32)
        pos = (lo_pos + idx_p) % S              # full-ring span, bijective
        abs_rel = lo_rel + idx_p                # absolute slice − lo0
        c = cnt[:, pos]                                        # [K, S]
        fmn = mn[:, pos] + abs_rel[None, :] * g
        fmx = mx[:, pos] + abs_rel[None, :] * g
        fl = [f[:, pos] for f in fields]
        mslots = jnp.arange(M, dtype=i32)[None, :]
        slots_in = est[0]

        est = session_gap_merge_scan(c, fmn, fmx, fl, vfields, idents, g,
                                     wm_rel, est)
        (slots, e_start, e_end, e_cnt, e_s0, e_s1, e_flds, overflow) = est

        # purge exactly the cells of sessions emitted by THIS merge (the
        # slot-range mask excludes entries from earlier merge steps of the
        # same dispatch, whose span coordinates were a different base)
        this = (mslots >= slots_in[:, None]) & (mslots < slots[:, None])
        cover = (idx_p[None, None, :] >= e_s0[:, :, None]) & \
                (idx_p[None, None, :] <= e_s1[:, :, None]) & \
                this[:, :, None]
        purge = jnp.any(cover, axis=1)                         # [K, S]
        c_new = jnp.where(purge, 0, c)
        # full-ring span: pos is a permutation, so column set-back is exact
        cnt = cnt.at[:, pos].set(c_new)
        mn = mn.at[:, pos].set(jnp.where(purge, g, mn[:, pos]))
        mx = mx.at[:, pos].set(jnp.where(purge, -1, mx[:, pos]))
        fields = tuple(
            f.at[:, pos].set(
                jnp.where(purge, jnp.asarray(ident, f.dtype), f[:, pos]))
            for f, ident in zip(fields, idents)
        )
        return (cnt, mn, mx, fields,
                (slots, e_start, e_end, e_cnt, e_s0, e_s1, e_flds, overflow))

    def step(carry, args):
        kid, spos, rel, vals, merge_flag, lo_pos, lo_rel, wm_rel = args
        (cnt, mn, mx, fields, est) = carry
        cnt, mn, mx, fields = ingest(cnt, mn, mx, fields, kid, spos, rel,
                                     vals)
        cnt, mn, mx, fields, est = jax.lax.cond(
            merge_flag > 0,
            lambda s: merge(s, lo_pos, lo_rel, wm_rel),
            lambda s: s,
            (cnt, mn, mx, fields, est))
        return (cnt, mn, mx, fields, est), None

    def run(cnt, mn, mx, fields, kid, spos, rel, vals,
            merge_flag, lo_pos, lo_rel, wm_rel):
        slots = jnp.zeros((K,), i32)
        e_start = jnp.zeros((K, M), i32)
        e_end = jnp.zeros((K, M), i32)
        e_cnt = jnp.zeros((K, M), i32)
        e_s0 = jnp.zeros((K, M), i32)
        e_s1 = jnp.full((K, M), -1, i32)
        e_flds = [jnp.full((K, M), ident, jnp.dtype(dt))
                  for (_n, dt, _s), ident in zip(vfields, idents)]
        overflow = jnp.zeros((), bool)
        est0 = (slots, e_start, e_end, e_cnt, e_s0, e_s1, e_flds, overflow)
        carry0 = (cnt, mn, mx, tuple(fields), est0)
        (cnt, mn, mx, fields, est), _ = jax.lax.scan(
            step, carry0,
            (kid, spos, rel, vals, merge_flag, lo_pos, lo_rel, wm_rel))
        (slots, e_start, e_end, e_cnt, _s0, _s1, e_flds, overflow) = est

        # live span of the surviving fragments, in dispatch-base slice
        # coordinates: the ring is bijective from position p -> slice
        # base_lo_rel + ((p - base_lo_pos) % S); the host passes the
        # dispatch-final base via the LAST step's lo_pos/lo_rel
        idx_p = jnp.arange(S, dtype=i32)
        pos = (lo_pos[-1] + idx_p) % S
        abs_rel = lo_rel[-1] + idx_p
        live = jnp.any(cnt[:, pos] > 0, axis=0)
        lo_live = jnp.min(jnp.where(live, abs_rel, 1 << 30))
        hi_live = jnp.max(jnp.where(live, abs_rel, -1))

        blocks = [e_start, e_end, e_cnt]
        for ef in e_flds:
            blocks.append(jax.lax.bitcast_convert_type(
                ef, i32) if ef.dtype != i32 else ef)
        packed = jnp.concatenate(blocks + [slots[:, None]], axis=1)
        scal = jnp.zeros((1, packed.shape[1]), i32)
        scal = scal.at[0, 0].set(
            jnp.where(hi_live >= 0, lo_live, 0).astype(i32))
        scal = scal.at[0, 1].set(hi_live.astype(i32))
        scal = scal.at[0, 2].set(overflow.astype(i32))
        packed = jnp.concatenate([packed, scal], axis=0)
        return cnt, mn, mx, fields, packed

    return jax.jit(run)
