"""Pallas superscan: the whole T-step window dispatch as ONE TPU kernel.

The XLA superscan (`fused_window_pipeline._build_superscan`) expresses each
step as a chain of HLO ops inside `lax.scan`: every step pays its op
sequence and the HBM round trip of every intermediate (one-hot matrices,
partial histograms). This kernel removes both by fusing the full dispatch
— ingest, fire, purge, T steps — into a single `pallas_call`:

- the slice-ring count state lives in VMEM for the whole dispatch, laid out
  `[S * K/128, 128]` (slice-major blocks of 64x128 key tiles), so ingest
  and fire touch on-chip memory only;
- ingest is the same MXU one-hot trick as `ops/matmul_hist` (reference
  semantics: per-record HeapAggregatingState.add, WindowOperator.java:293),
  but the one-hot factors are built in VMEM per chunk and consumed by the
  MXU immediately — nothing spills to HBM;
- fire/purge control (slice positions, output rows, purge masks) is
  precomputed by the host planner and prefetched to SMEM
  (PrefetchScalarGridSpec), so the kernel's control flow is branch-cheap
  `@pl.when` predication, XLA-style static shapes throughout.

Its rate, alone and against the XLA superscan, is not measured on the
current code and installation (jax 0.9.0, libtpu 0.0.34).

Segment encoding matches the host planner (`FusedWindowPipeline.stage`):
`idx = key_id * NSB + rel_slice`, negative = dropped. In-kernel it is
re-factored to `seg = rel_slice * K + key_id` so a segment's histogram
lands at rows `rel_slice * K/128 + key_id/128`, lane `key_id % 128` —
directly addressable as 64x128 blocks of the slice-ring state.

Supported aggregates: the count field, any number of add-combining VALUE
fields (sum/mean), and bounded-domain max fields
(`max_agg(domain_bits<=8)`). Weighted sums use the same three-term bf16
split-float trick as `matmul_hist.weighted_hist` (t0+t1+t2 == v bit-exactly
for |v| >= ~2**-110), so each record's f32 value enters the accumulator
unquantized. Bounded max runs on the MXU via two conditional nibble
histograms (pass 1 finds each segment's max high nibble, an MXU matvec
gathers it per record, pass 2 counts low nibbles among records matching it)
plus a dense elementwise maximum into the ring state. Unbounded min/max
have no matmul form and stay on the XLA superscan.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from flink_tpu.ops.aggregators import VALUE

LANE = 128
# 1D int32 inputs are tiled T(1024) by XLA; chunk blocks must align to it
MIN_CHUNK = 1024
# Scoped VMEM both kernels ask Mosaic for (pltpu.CompilerParams) and the
# budget `supports()` sizes its gate from. The compiler's own default is
# 16 MiB; the kernels keep their whole state resident, so they state their
# need: 32 MiB, a quarter of a v5e core's 128 MiB.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _field_kind(f) -> str:
    """'add' | 'max8' | None (unsupported)."""
    if f.scatter == "add":
        return "add"
    if f.scatter == "max" and getattr(f, "domain_bits", None) is not None \
            and f.domain_bits <= 8:
        return "max8"
    return None


def vmem_bytes(agg, K: int, R: int, S: int, NSB: int, chunk: int) -> int:
    """Upper bound on the scoped VMEM Mosaic allocates for the keyed
    kernel at this geometry.

    Blocks are exact: the ring state is resident TWICE per field (the
    input block and the aliased-in-spirit output block both stay in VMEM
    for the whole grid), the fire rows once, and the idx/vals chunk blocks
    are double-buffered. The per-chunk transients are not what the source
    suggests — Mosaic tiles the [NSB*K/128, CH] row factor instead of
    materializing it — so their per-element weights are fitted upper
    bounds from compiling the kernel against a v5e topology (libtpu
    0.0.34; K 1024..32768 x CH 1024..32768: compiler's need / this bound
    <= 0.97). tests/test_tpu_compile.py holds the gate to the compiler."""
    value_fields = [f for f in agg.fields if f.source == VALUE]
    nf = len(value_fields)
    n_add = sum(1 for f in value_fields if _field_kind(f) == "add")
    HI = NSB * K // LANE
    blocks = (1 + nf) * (2 * S + R) * K * 4 \
        + 2 * chunk * 4 * (2 if nf else 1)
    if n_add:
        # bf16 lane factor + the three split-float products + the f32
        # value column; bf16 row factor + its compare mask
        lane_b, row_b = 10, 4
    else:
        lane_b, row_b = 2, 1          # int8 factors
    transients = chunk * LANE * lane_b + HI * chunk * row_b \
        + 3 * HI * LANE * 4
    if nf - n_add:
        # nibble passes, counted as if fully materialized: two
        # [16*NSB*K/128, CH] int8 factor sets, their int32 histograms and
        # the f32 gather matmul
        hi16 = 16 * HI
        transients += 2 * hi16 * chunk + 2 * hi16 * LANE * 4 \
            + chunk * LANE * 4
    return blocks + transients


def supports(agg, K: int, R: int, S: int, NSB: int, chunk: int) -> bool:
    """Whether this aggregate/geometry can run on the pallas superscan:
    every geometry admitted here must compile, because the `auto` backend
    does not catch a Mosaic refusal and fall back."""
    if K % LANE != 0 or chunk % MIN_CHUNK != 0:
        return False
    value_fields = [f for f in agg.fields if f.source == VALUE]
    if any(_field_kind(f) is None for f in value_fields):
        return False
    return vmem_bytes(agg, K, R, S, NSB, chunk) <= VMEM_LIMIT_BYTES


@functools.lru_cache(maxsize=None)
def build_superscan(
    agg,
    K: int,
    S: int,
    NSB: int,
    F: int,
    SPW: int,
    R: int,
    T: int,
    B: int,
    CH: int,
    exact: bool,
    interpret: bool,
    fire_spws: Tuple[int, ...] = None,
):
    """Compile the fused T-step dispatch.

    `fire_spws` (shared partials): per-fire-slot window lengths in slices,
    length F — one gcd-granule ring serves several correlated window
    shapes, each slot combining its own slice-run length (Factor Windows);
    None keeps the uniform-SPW program unchanged.

    Returns run(smin, fire_pos, fire_valid, fire_row, purge_mask,
                count_in [S*KB,128] i32, field_states... , idx [T*B] i32,
                vals [T*B] f32 | None)
        -> (count_state, field_states..., count_out [R*KB,128],
            field_outs...)
    """
    assert B % CH == 0 and CH % MIN_CHUNK == 0
    spws = tuple(fire_spws) if fire_spws is not None else (SPW,) * F
    assert len(spws) == F, f"fire_spws has {len(spws)} slots, expected {F}"
    KB = K // LANE
    HI = NSB * KB
    C = B // CH
    vfields = [
        (f.name, jnp.dtype(f.dtype), _field_kind(f),
         getattr(f, "domain_bits", None))
        for f in agg.fields if f.source == VALUE
    ]
    nf = len(vfields)
    has_add = any(kind == "add" for _n, _d, kind, _b in vfields)
    has_max = any(kind == "max8" for _n, _d, kind, _b in vfields)

    def kernel(smin_ref, fpos_ref, fvalid_ref, frow_ref, purge_ref,
               count_in_ref, *rest):
        state_in = rest[:nf]
        idx_ref = rest[nf]
        off = nf + 1
        vals_ref = rest[off] if nf else None
        off += 1 if nf else 0
        count_ref = rest[off]
        states = rest[off + 1:off + 1 + nf]
        out_ref = rest[off + 1 + nf]
        outs = rest[off + 2 + nf:]

        t = pl.program_id(0)
        c = pl.program_id(1)

        @pl.when(jnp.logical_and(t == 0, c == 0))
        def _():
            count_ref[:] = count_in_ref[:]
            out_ref[:] = jnp.zeros_like(out_ref)
            for sref, sin in zip(states, state_in):
                sref[:] = sin[:]
            for o in outs:
                o[:] = jnp.zeros_like(o)

        # ---- ingest one chunk: one-hot factors in VMEM, MXU contraction ----
        # count-only dispatches use int8 factors with an int32 MXU
        # accumulator (exact, half the VMEM of the bf16 form);
        # weighted dispatches need bf16 for the split-float value terms
        oh_dt = jnp.int8 if not has_add else jnp.bfloat16
        acc_dt = jnp.int32 if not has_add else jnp.float32
        ii = idx_ref[:]                                   # [CH] i32
        kid = ii // NSB
        srel = ii % NSB
        seg = jnp.where(ii >= 0, srel * K + kid, -1)
        hi = seg // LANE
        lo = seg % LANE
        oh_hiT = (hi[None, :] == jax.lax.broadcasted_iota(
            jnp.int32, (HI, CH), 0)).astype(oh_dt)
        oh_lo = (lo[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (CH, LANE), 1)).astype(oh_dt)
        part = jax.lax.dot_general(
            oh_hiT, oh_lo, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_dt).astype(jnp.int32)

        smin = smin_ref[t]
        for sr in range(NSB):
            col = (smin + sr) % S
            base = pl.multiple_of(col * KB, KB)
            count_ref[pl.ds(base, KB), :] += part[sr * KB:(sr + 1) * KB, :]

        if has_add:
            # [CH, 1] while still f32: Mosaic has no 1-D -> 2-D shape cast
            # for packed bf16 vectors, so the split below runs on columns
            v = vals_ref[:].astype(jnp.float32)[:, None]
            terms = []
            t0 = v.astype(jnp.bfloat16)
            terms.append(t0)
            if exact:
                r1 = v - t0.astype(jnp.float32)
                t1 = r1.astype(jnp.bfloat16)
                r2 = r1 - t1.astype(jnp.float32)
                terms.append(t1)
                terms.append(r2.astype(jnp.bfloat16))
            wacc = None
            for tm in terms:
                d = jax.lax.dot_general(
                    oh_hiT, oh_lo * tm, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                wacc = d if wacc is None else wacc + d
            for sref, (_name, dt, kind, _b) in zip(states, vfields):
                if kind != "add":
                    continue
                w = wacc.astype(dt)
                for sr in range(NSB):
                    col = (smin + sr) % S
                    base = pl.multiple_of(col * KB, KB)
                    sref[pl.ds(base, KB), :] += w[sr * KB:(sr + 1) * KB, :]

        if has_max:
            # bounded-domain max on the MXU (no scatter): values are ints in
            # [0, 2^bits). Two conditional nibble histograms find each
            # segment's batch max; a dense elementwise maximum folds it into
            # the ring state.
            #   pass 1: h1[v_hi, seg] = count  -> maxhi[seg]
            #   gather: g_r = maxhi[seg_r] via one MXU matvec (no scatter/
            #           gather unit: M = ohT @ maxhi, then lane-select)
            #   pass 2: h2[v_lo, seg | v_hi==maxhi] = count -> maxlo[seg]
            mv = jnp.clip(vals_ref[:].astype(jnp.int32), 0, 255)
            vhi = mv >> 4
            vlo = mv & 15
            valid = ii >= 0
            i8 = jnp.int8
            # reuse the count path's lane factor (already int8 unless an
            # add-field forced bf16 factors)
            oh_lo8 = oh_lo if oh_dt == i8 else oh_lo.astype(i8)
            row1 = jnp.where(valid, vhi * HI + hi, -1)
            ohm1 = (row1[None, :] == jax.lax.broadcasted_iota(
                jnp.int32, (16 * HI, CH), 0)).astype(i8)
            h1 = jax.lax.dot_general(
                ohm1, oh_lo8, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            maxhi = jnp.full((HI, LANE), -1, jnp.int32)
            for h in range(16):               # ascending: last hit wins
                maxhi = jnp.where(h1[h * HI:(h + 1) * HI, :] > 0, h, maxhi)
            # per-record gather of maxhi[seg_r] as an MXU matvec (reusing
            # the count path's row factor)
            M = jax.lax.dot_general(
                oh_hiT.astype(jnp.bfloat16), maxhi.astype(jnp.bfloat16),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [CH, LANE]
            g = jnp.sum(oh_lo8.astype(jnp.float32) * M, axis=1)
            cond = valid & (vhi == g.astype(jnp.int32))
            row2 = jnp.where(cond, vlo * HI + hi, -1)
            ohm2 = (row2[None, :] == jax.lax.broadcasted_iota(
                jnp.int32, (16 * HI, CH), 0)).astype(i8)
            h2 = jax.lax.dot_general(
                ohm2, oh_lo8, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            maxlo = jnp.full((HI, LANE), -1, jnp.int32)
            for h in range(16):
                maxlo = jnp.where(h2[h * HI:(h + 1) * HI, :] > 0, h, maxlo)
            chunkmax = jnp.where(maxhi >= 0, maxhi * 16 + maxlo, -1)
            for sref, (_name, _dt, kind, _b) in zip(states, vfields):
                if kind != "max8":
                    continue
                for sr in range(NSB):
                    col = (smin + sr) % S
                    base = pl.multiple_of(col * KB, KB)
                    sref[pl.ds(base, KB), :] = jnp.maximum(
                        sref[pl.ds(base, KB), :],
                        chunkmax[sr * KB:(sr + 1) * KB, :])

        # ---- fire + purge once the step's last chunk is ingested ----
        @pl.when(c == C - 1)
        def _():
            for f in range(F):
                @pl.when(fvalid_ref[t, f] > 0)
                def _(f=f):
                    fp = fpos_ref[t, f]
                    row = frow_ref[t, f]
                    acc = jnp.zeros((KB, LANE), jnp.int32)
                    for w in range(spws[f]):
                        col = (fp + w) % S
                        acc += count_ref[
                            pl.ds(pl.multiple_of(col * KB, KB), KB), :]
                    out_ref[pl.ds(row * KB, KB), :] = acc
                    for sref, oref, (_n, dt, kind, _b) in zip(
                            states, outs, vfields):
                        if kind == "max8":
                            sacc = jnp.full((KB, LANE), -1, dt)
                            for w in range(spws[f]):
                                col = (fp + w) % S
                                sacc = jnp.maximum(sacc, sref[
                                    pl.ds(pl.multiple_of(col * KB, KB), KB),
                                    :])
                        else:
                            sacc = jnp.zeros((KB, LANE), dt)
                            for w in range(spws[f]):
                                col = (fp + w) % S
                                sacc += sref[
                                    pl.ds(pl.multiple_of(col * KB, KB), KB),
                                    :]
                        oref[pl.ds(row * KB, KB), :] = sacc
            for s in range(S):
                @pl.when(purge_ref[t, s] == 0)
                def _(s=s):
                    base = pl.multiple_of(s * KB, KB)
                    count_ref[pl.ds(base, KB), :] = jnp.zeros(
                        (KB, LANE), jnp.int32)
                    for sref, (_n, dt, kind, _b) in zip(states, vfields):
                        ident = -1 if kind == "max8" else 0
                        sref[pl.ds(base, KB), :] = jnp.full(
                            (KB, LANE), ident, dt)

    state_spec = pl.BlockSpec((S * KB, LANE), lambda t, c, *_: (0, 0))
    out_spec = pl.BlockSpec((R * KB, LANE), lambda t, c, *_: (0, 0))
    chunk_spec = pl.BlockSpec((CH,), lambda t, c, *_: (t * C + c,))

    in_specs = [state_spec]                      # count_in
    in_specs += [state_spec] * nf                # field states in
    in_specs += [chunk_spec]                     # idx
    if nf:
        in_specs += [chunk_spec]                 # vals
    out_specs = [state_spec] + [state_spec] * nf + [out_spec] + [out_spec] * nf

    out_shape = [jax.ShapeDtypeStruct((S * KB, LANE), jnp.int32)]
    out_shape += [jax.ShapeDtypeStruct((S * KB, LANE), dt)
                  for _n, dt, _k, _b in vfields]
    out_shape += [jax.ShapeDtypeStruct((R * KB, LANE), jnp.int32)]
    out_shape += [jax.ShapeDtypeStruct((R * KB, LANE), dt)
                  for _n, dt, _k, _b in vfields]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T, C),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )

    @jax.jit
    def run_pallas_superscan(smin, fpos, fvalid, frow, purge, count_in,
                             states, idx, vals):
        args = [count_in, *states, idx]
        if nf:
            args.append(vals)
        res = fn(smin, fpos, fvalid, frow, purge, *args)
        count_state = res[0]
        field_states = tuple(res[1:1 + nf])
        count_out = res[1 + nf]
        field_outs = tuple(res[2 + nf:])
        return count_state, field_states, count_out, field_outs

    return run_pallas_superscan


# ------------------------------------------------------------------
# layout converters between the canonical [K, S] state (XLA superscan,
# snapshots) and the kernel's slice-major [S*KB, LANE] layout
# ------------------------------------------------------------------

def to_kernel_layout(arr, K: int, S: int):
    """[K, S] -> [S*K/128, 128] (numpy or jax array)."""
    xp = jnp if isinstance(arr, jax.Array) else np
    return xp.transpose(arr, (1, 0)).reshape(S * (K // LANE), LANE)


def from_kernel_layout(arr, K: int, S: int):
    """[S*K/128, 128] -> [K, S]."""
    xp = jnp if isinstance(arr, jax.Array) else np
    return xp.transpose(arr.reshape(S, K), (1, 0))


def rows_to_keys(out, R: int, K: int):
    """Compact fire buffer [R*K/128, 128] -> [R, K]."""
    return out.reshape(R, K)


# ------------------------------------------------------------------
# global-window superscan: keyed-partial -> cross-segment fold as ONE
# T-step kernel (the Nexmark-Q7 shape: per-window GLOBAL max/min/sum)
# ------------------------------------------------------------------

#: largest chunk the global kernel is cleared for against the compiler: its
#: VMEM need is ~28 B per chunk element (3.5 MiB here, v5e, libtpu 0.0.34)
#: and Mosaic's compile time grows with the chunk's vreg count
MAX_GLOBAL_CHUNK = 1 << 17


def supports_global(agg, S: int, R: int, NSB: int, chunk: int) -> bool:
    """Whether an aggregate/geometry can run on the fused global scan
    kernel: the [S] slice ring and the [R] out rows each live in one
    128-lane vector row, the purge mask unrolls over S scalar reads, and
    every field folds elementwise (any add/min/max, bounded or not — the
    fold needs no scatter unit and no one-hot matrices). Like
    `supports()`, what it admits must compile."""
    if S > 32 or R > LANE or NSB > 8 or chunk % MIN_CHUNK != 0 \
            or chunk > MAX_GLOBAL_CHUNK:
        return False
    return all(f.scatter in ("add", "min", "max")
               for f in agg.fields if f.source == VALUE)


@functools.lru_cache(maxsize=None)
def build_global_superscan(
    agg,
    S: int,
    NSB: int,
    F: int,
    SPW: int,
    R: int,
    T: int,
    B: int,
    CH: int,
    interpret: bool,
    fire_spws: Tuple[int, ...] = None,
):
    """Compile the fused T-step GLOBAL-window dispatch as one kernel.

    The XLA global scan (ops/superscan.make_global_scan_step) already
    removes the [K, S] ring; this kernel additionally removes the
    per-step lax.scan overhead: ingest partials, slice-ring folds, fires
    and purges for all T steps run as one pallas_call with the [S] ring
    resident in a single VMEM vector row. Each chunk costs NSB masked
    whole-chunk reductions — no scatter unit, no one-hot factors, no HBM
    round trips. Out rows are scalars packed into one [1, 128] row.

    Returns run(smin, fpos, fvalid, frow, purge,
                count_in [1,128] i32, states ([1,128] dt, ...),
                idx [T*B] i32, vals [T*B] f32 | None)
        -> (count_state, field_states, count_out [1,128], field_outs)"""
    assert B % CH == 0 and CH % MIN_CHUNK == 0
    assert S <= 32 and R <= LANE
    spws = tuple(fire_spws) if fire_spws is not None else (SPW,) * F
    assert len(spws) == F
    C = B // CH
    vfields = [
        (f.name, jnp.dtype(f.dtype), f.scatter, f.identity)
        for f in agg.fields if f.source == VALUE
    ]
    nf = len(vfields)

    def _ident(dt, scatter):
        from flink_tpu.ops.aggregators import scan_identity

        return scan_identity(dt, scatter)

    def kernel(smin_ref, fpos_ref, fvalid_ref, frow_ref, purge_ref,
               count_in_ref, *rest):
        state_in = rest[:nf]
        idx_ref = rest[nf]
        off = nf + 1
        vals_ref = rest[off] if nf else None
        off += 1 if nf else 0
        count_ref = rest[off]
        states = rest[off + 1:off + 1 + nf]
        out_ref = rest[off + 1 + nf]
        outs = rest[off + 2 + nf:]

        t = pl.program_id(0)
        c = pl.program_id(1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)

        @pl.when(jnp.logical_and(t == 0, c == 0))
        def _():
            count_ref[:] = count_in_ref[:]
            out_ref[:] = jnp.zeros_like(out_ref)
            for sref, sin in zip(states, state_in):
                sref[:] = sin[:]
            for oref, (_n, dt, scatter, _i) in zip(outs, vfields):
                oref[:] = jnp.full_like(oref, _ident(dt, scatter))

        # ---- ingest one chunk: NSB masked whole-chunk folds ----
        ii = idx_ref[:]
        srel = jnp.where(ii >= 0, ii % NSB, -1)
        smin = smin_ref[t]
        for sr in range(NSB):
            col = (smin + sr) % S
            sel = lane == col
            cpart = jnp.sum((srel == sr).astype(jnp.int32))
            count_ref[:] = jnp.where(sel, count_ref[:] + cpart, count_ref[:])
            if nf:
                v = vals_ref[:]
                for sref, (_n, dt, scatter, _i) in zip(states, vfields):
                    ident = jnp.asarray(_ident(dt, scatter), dt)
                    lanev = jnp.where(srel == sr, v.astype(dt), ident)
                    if scatter == "add":
                        part = lanev.sum()
                        sref[:] = jnp.where(sel, sref[:] + part, sref[:])
                    elif scatter == "min":
                        part = lanev.min()
                        sref[:] = jnp.where(
                            sel, jnp.minimum(sref[:], part), sref[:])
                    else:
                        part = lanev.max()
                        sref[:] = jnp.where(
                            sel, jnp.maximum(sref[:], part), sref[:])

        # ---- fire + purge once the step's last chunk is ingested ----
        @pl.when(c == C - 1)
        def _():
            for f in range(F):
                @pl.when(fvalid_ref[t, f] > 0)
                def _(f=f):
                    fp = fpos_ref[t, f]
                    row = frow_ref[t, f]
                    inwin = (jnp.remainder(lane - fp, S) < spws[f]) & \
                        (lane < S)
                    rowsel = lane == row
                    cnt = jnp.sum(jnp.where(inwin, count_ref[:], 0))
                    out_ref[:] = jnp.where(rowsel, cnt, out_ref[:])
                    for sref, oref, (_n, dt, scatter, _i) in zip(
                            states, outs, vfields):
                        ident = jnp.asarray(_ident(dt, scatter), dt)
                        masked = jnp.where(inwin, sref[:], ident)
                        if scatter == "add":
                            folded = masked.sum()
                        elif scatter == "min":
                            folded = masked.min()
                        else:
                            folded = masked.max()
                        oref[:] = jnp.where(rowsel, folded, oref[:])
            # purge: S scalar reads build the expired-lane mask
            keep = jnp.ones((1, LANE), jnp.bool_)
            for s in range(S):
                keep = keep & ~((lane == s) & (purge_ref[t, s] == 0))
            count_ref[:] = jnp.where(keep, count_ref[:], 0)
            for sref, (_n, dt, scatter, _i) in zip(states, vfields):
                sref[:] = jnp.where(
                    keep, sref[:], jnp.asarray(_ident(dt, scatter), dt))

    row_spec = pl.BlockSpec((1, LANE), lambda t, c, *_: (0, 0))
    chunk_spec = pl.BlockSpec((CH,), lambda t, c, *_: (t * C + c,))

    in_specs = [row_spec] + [row_spec] * nf + [chunk_spec]
    if nf:
        in_specs += [chunk_spec]
    out_specs = [row_spec] * (1 + nf) + [row_spec] * (1 + nf)
    out_shape = [jax.ShapeDtypeStruct((1, LANE), jnp.int32)]
    out_shape += [jax.ShapeDtypeStruct((1, LANE), dt)
                  for _n, dt, _s, _i in vfields]
    out_shape += [jax.ShapeDtypeStruct((1, LANE), jnp.int32)]
    out_shape += [jax.ShapeDtypeStruct((1, LANE), dt)
                  for _n, dt, _s, _i in vfields]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(T, C),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    fn = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )

    @jax.jit
    def run_pallas_global_superscan(smin, fpos, fvalid, frow, purge,
                                    count_in, states, idx, vals):
        args = [count_in, *states, idx]
        if nf:
            args.append(vals)
        res = fn(smin, fpos, fvalid, frow, purge, *args)
        count_state = res[0]
        field_states = tuple(res[1:1 + nf])
        count_out = res[1 + nf]
        field_outs = tuple(res[2 + nf:])
        return count_state, field_states, count_out, field_outs

    return run_pallas_global_superscan
