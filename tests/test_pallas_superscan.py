"""Parity tests for the fused pallas superscan (interpret mode on CPU).

The kernel itself targets TPU; CI validates its semantics through the pallas
interpreter at tiny geometry, against (a) a direct numpy model of the
ingest/fire/purge contract and (b) the XLA superscan driven through the same
FusedWindowPipeline planner on identical streams.
"""

import numpy as np
import pytest

from flink_tpu.api.windowing.assigners import SlidingEventTimeWindows
from flink_tpu.ops import pallas_superscan as ps
from flink_tpu.ops.aggregators import count_agg, max_agg, sum_agg
from flink_tpu.runtime.fused_window_pipeline import FusedWindowPipeline

K, S, NSB, F, SPW, R = 256, 8, 2, 2, 3, 8
T, B, CH = 4, 2048, 1024
KB = K // 128


def _numpy_model(idx, vals, smin, fpos, fvalid, frow, purge, mode):
    """mode: 'count' | 'sum' | 'max8' — field semantics of the kernel."""
    cnt = np.zeros((S, KB, 128), np.int64)
    sm = np.zeros((S, KB, 128), np.float64)
    mx = np.full((S, KB, 128), -1, np.int64)
    out_c = np.zeros((R, KB, 128), np.int64)
    out_s = np.zeros((R, KB, 128), np.float64)
    out_m = np.zeros((R, KB, 128), np.int64)
    for t in range(T):
        for b in range(B):
            ii = idx[t * B + b]
            if ii < 0:
                continue
            kid, sr = ii // NSB, ii % NSB
            col = (smin[t] + sr) % S
            cnt[col, kid // 128, kid % 128] += 1
            if mode == "sum":
                sm[col, kid // 128, kid % 128] += vals[t * B + b]
            elif mode == "max8":
                cell = (col, kid // 128, kid % 128)
                mx[cell] = max(mx[cell], int(vals[t * B + b]))
        for f in range(F):
            if fvalid[t, f]:
                acc_c = np.zeros((KB, 128), np.int64)
                acc_s = np.zeros((KB, 128), np.float64)
                acc_m = np.full((KB, 128), -1, np.int64)
                for w in range(SPW):
                    acc_c += cnt[(fpos[t, f] + w) % S]
                    acc_s += sm[(fpos[t, f] + w) % S]
                    acc_m = np.maximum(acc_m, mx[(fpos[t, f] + w) % S])
                out_c[frow[t, f]] = acc_c
                out_s[frow[t, f]] = acc_s
                out_m[frow[t, f]] = acc_m
        for s in range(S):
            if purge[t, s] == 0:
                cnt[s] = 0
                sm[s] = 0
                mx[s] = -1
    return cnt, {"sum": sm, "max8": mx}, out_c, {"sum": out_s, "max8": out_m}


@pytest.mark.parametrize("mode", ["count", "sum", "max8"])
def test_kernel_parity_vs_numpy(mode):
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    idx = rng.integers(-1, K * NSB, size=(T * B,)).astype(np.int32)
    vals = rng.integers(0, 50, size=(T * B,)).astype(np.float32)
    smin = rng.integers(0, S, size=T).astype(np.int32)
    fpos = rng.integers(0, S, size=(T, F)).astype(np.int32)
    fvalid = rng.integers(0, 2, size=(T, F)).astype(np.int32)
    frow = (np.arange(T * F, dtype=np.int32).reshape(T, F)) % R
    purge = (rng.random((T, S)) > 0.2).astype(np.int32)

    agg = {"count": count_agg, "sum": sum_agg,
           "max8": lambda: max_agg(domain_bits=8)}[mode]()
    run = ps.build_superscan(
        agg, K, S, NSB, F, SPW, R, T, B, CH, True, True  # interpret=True
    )
    with_field = mode != "count"
    field_dt = jnp.float32 if mode == "sum" else jnp.int32
    ident = 0 if mode == "sum" else -1
    states = (jnp.full((S * KB, 128), ident, field_dt),) if with_field else ()
    count_state, field_states, count_out, field_outs = run(
        smin, fpos, fvalid, frow, purge,
        jnp.zeros((S * KB, 128), jnp.int32), states,
        jnp.asarray(idx), jnp.asarray(vals) if with_field else None,
    )
    cnt, sm, out_c, out_s = _numpy_model(
        idx, vals, smin, fpos, fvalid, frow, purge, mode
    )
    assert np.array_equal(
        np.asarray(count_state).reshape(S, KB, 128).astype(np.int64), cnt
    )
    assert np.array_equal(
        np.asarray(count_out).reshape(R, KB, 128).astype(np.int64), out_c
    )
    if with_field:
        np.testing.assert_allclose(
            np.asarray(field_states[0]).reshape(S, KB, 128).astype(np.float64),
            sm[mode], rtol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(field_outs[0]).reshape(R, KB, 128).astype(np.float64),
            out_s[mode], rtol=1e-6,
        )


def _ysb_stream(steps, batch, num_keys, seed=11):
    rng = np.random.default_rng(seed)
    batches, wms = [], []
    ms_per_batch = 400.0
    t_cursor = 0.0
    for _ in range(steps):
        keys = rng.integers(0, num_keys, size=batch).astype(np.int32)
        base = t_cursor + np.sort(rng.random(batch)) * ms_per_batch
        ts = np.maximum(base.astype(np.int64) - rng.integers(0, 120, batch), 0)
        vals = rng.integers(0, 9, size=batch).astype(np.float32)
        batches.append((keys, vals, ts))
        wms.append(int(base[-1]) - 150)
        t_cursor += ms_per_batch
    return batches, wms


@pytest.mark.parametrize("aggregate", ["count", "sum", "max8"])
def test_pipeline_pallas_matches_xla(aggregate):
    steps, batch, num_keys = 6, 700, 128
    batches, wms = _ysb_stream(steps, batch, num_keys)
    agg = max_agg(domain_bits=8) if aggregate == "max8" else aggregate

    def mk(backend):
        return FusedWindowPipeline(
            SlidingEventTimeWindows.of(2000, 500), agg,
            key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
            out_rows=16, chunk=1024, backend=backend,
            pallas_interpret=(backend == "pallas"),
        )

    ref_pipe, dev_pipe = mk("xla"), mk("pallas")
    half = steps // 2
    ref1 = ref_pipe.process_superbatch(batches[:half], wms[:half])
    dev1 = dev_pipe.process_superbatch(batches[:half], wms[:half])
    ref2 = ref_pipe.process_superbatch(batches[half:], wms[half:])
    dev2 = dev_pipe.process_superbatch(batches[half:], wms[half:])

    for ref, dev in ((ref1, dev1), (ref2, dev2)):
        assert len(ref) == len(dev) and len(ref) > 0
        for (rw, rc, rf), (dw, dc, df) in zip(ref, dev):
            assert rw == dw
            assert np.array_equal(np.asarray(rc), np.asarray(dc))
            for name in rf:
                np.testing.assert_allclose(
                    np.asarray(rf[name]), np.asarray(df[name]), rtol=1e-6
                )


def test_pipeline_snapshot_crosses_backends():
    steps, batch, num_keys = 6, 500, 128
    batches, wms = _ysb_stream(steps, batch, num_keys, seed=5)
    half = steps // 2

    dev_pipe = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024, backend="pallas", pallas_interpret=True,
    )
    ref_pipe = FusedWindowPipeline(
        SlidingEventTimeWindows.of(2000, 500), "count",
        key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
        out_rows=16, chunk=1024, backend="xla",
    )
    dev1 = dev_pipe.process_superbatch(batches[:half], wms[:half])
    snap = dev_pipe.snapshot()  # canonical [K, S] layout regardless of backend
    assert snap["count"].shape == (num_keys, 16)

    ref_pipe.restore(snap)
    ref_pipe.backend = "xla"
    dev2 = dev_pipe.process_superbatch(batches[half:], wms[half:])
    ref2 = ref_pipe.process_superbatch(batches[half:], wms[half:])
    assert len(dev2) == len(ref2) and len(dev2) > 0
    for (rw, rc, _), (dw, dc, _) in zip(ref2, dev2):
        assert rw == dw
        assert np.array_equal(np.asarray(rc), np.asarray(dc))


def test_plan_superbatch_matches_staged():
    """The analytic planner + caller-staged idx produce the same emissions as
    the data-driven `stage` on an identical stream."""
    import jax
    import jax.numpy as jnp

    steps, batch, num_keys = 6, 1024, 128
    M, SLIDE, OOO = 400, 500, 120
    rng = np.random.default_rng(9)
    batches, wms, bounds = [], [], []
    for t in range(steps):
        keys = rng.integers(0, num_keys, size=batch).astype(np.int32)
        base = t * M + ((np.arange(1, batch + 1) * M) // batch)
        ts = np.maximum(base - rng.integers(0, OOO + 1, batch), 0).astype(np.int64)
        batches.append((keys, None, ts))
        wms.append((t + 1) * M - 150)
        s = ts // SLIDE
        bounds.append((max((t * M + M // batch - OOO) // SLIDE, 0),
                       ((t + 1) * M) // SLIDE))
        assert bounds[-1][0] <= s.min() and s.max() <= bounds[-1][1]

    def mk():
        return FusedWindowPipeline(
            SlidingEventTimeWindows.of(2000, 500), "count",
            key_capacity=num_keys, num_slices=16, nsb=4, fires_per_step=4,
            out_rows=16, chunk=1024, backend="pallas", pallas_interpret=True,
        )

    ref_pipe, gen_pipe = mk(), mk()
    ref = ref_pipe.process_superbatch(batches, wms)

    staged, smin_abs = gen_pipe.plan_superbatch(bounds, wms)
    idx_rows = []
    for t, (keys, _v, ts) in enumerate(batches):
        srel = (ts // SLIDE - smin_abs[t]).astype(np.int32)
        assert (srel >= 0).all() and (srel < 4).all()
        idx_rows.append(keys.astype(np.int32) * 4 + srel)
    idx_flat = jax.device_put(np.concatenate(idx_rows))
    vals_d = jnp.zeros((steps, 1), jnp.float32)
    got = gen_pipe.dispatch(staged._replace(xs=(idx_flat, vals_d)))

    assert len(ref) == len(got) and len(ref) > 0
    for (rw, rc, _), (gw, gc, _) in zip(ref, got):
        assert rw == gw
        assert np.array_equal(np.asarray(rc), np.asarray(gc))
