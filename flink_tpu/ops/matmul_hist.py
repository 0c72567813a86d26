"""MXU histogram: segment aggregation as one-hot matmuls.

The scatter that the reference performs per record
(WindowOperator.processElement -> HeapAggregatingState.add, per-(key,window)
hash-map mutation) is re-expressed as dense linear algebra so it lands on the
TPU's systolic array instead of the (slow, serialized) scatter unit:

    count[seg]   = sum_b  1[idx_b == seg]
    sum[seg]     = sum_b  v_b * 1[idx_b == seg]

with the segment id factored two-level, ``idx = hi * LANES + lo``:

    H[hi, lo] = one_hot(hi_b)^T  @  one_hot(lo_b)        # [B,HI]x[B,LO] matmul

One [B, HI] x [B, LO] contraction over the batch axis replaces B random
scatters; HI*LO = num_segments. Counts run as int8 one-hots accumulating into
int32 (exact); weighted sums run as bf16 with an optional THREE-term
split-float pass (8+8+8 mantissa bits cover f32's 24) so each record's value
enters the f32 accumulator without quantization — see weighted_hist for the
precise exactness contract.

Out-of-range segment ids (idx < 0 or >= num_segments) contribute nothing:
their `hi` row matches no column of the iota, so they vanish from the
product — this is the INVALID_INDEX drop semantics of segment_ops without
any masking cost.

The batch is processed in static chunks via lax.scan so the one-hot
intermediates stay VMEM-sized.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128  # TPU lane width: the `lo` one-hot dimension


def plan_segments(num_segments: int) -> Tuple[int, int]:
    """Factor num_segments as HI * LANES (rounded up)."""
    hi = -(-num_segments // LANES)
    return hi, LANES


def _one_hots(idx: jnp.ndarray, hi_n: int, dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    hi = (idx // LANES).astype(jnp.int32)
    lo = (idx % LANES).astype(jnp.int32)
    oh_hi = (hi[:, None] == jnp.arange(hi_n, dtype=jnp.int32)[None, :]).astype(dtype)
    oh_lo = (lo[:, None] == jnp.arange(LANES, dtype=jnp.int32)[None, :]).astype(dtype)
    return oh_hi, oh_lo


def _dot(a: jnp.ndarray, b: jnp.ndarray, out_dtype) -> jnp.ndarray:
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=out_dtype
    )


def count_hist(idx: jnp.ndarray, num_segments: int, *, chunk: int = 8192) -> jnp.ndarray:
    """int32[num_segments] counts of idx values; out-of-range ids dropped.

    idx length must be a multiple of `chunk` (pad with -1).
    """
    hi_n, _ = plan_segments(num_segments)

    def body(acc, ii):
        oh_hi, oh_lo = _one_hots(ii, hi_n, jnp.int8)
        return acc + _dot(oh_hi, oh_lo, jnp.int32), None

    n = idx.shape[0] // chunk
    acc, _ = jax.lax.scan(body, jnp.zeros((hi_n, LANES), jnp.int32), idx.reshape(n, chunk))
    return acc.reshape(-1)[:num_segments]


def bf16_terms(vals: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """f32 values as THREE bf16 terms with v == t0 + t1 + t2 bit for bit
    (8 + 8 + 8 mantissa bits cover f32's 24; the precise contract is
    weighted_hist's).

    Each term is rounded by `lax.reduce_precision`, never by a convert to
    bf16 and back: XLA:TPU takes `f32 -> bf16 -> f32` for an identity it may
    skip (excess precision is allowed by default), the residual `v - t0`
    then reads 0, and the "exact" sum is the one-term sum. That is what the
    first chip run of a value column found (PERF.md section 6, PR 37): 99.6 %
    of 44 M window sums of 14-bit prices wrong on the chip with every CPU
    test passing. `reduce_precision` exists to be honoured, and the converts
    left below act on values bf16 holds exactly, so skipping them changes
    nothing."""
    def rounded(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    t0 = rounded(vals)
    r1 = vals - t0
    t1 = rounded(r1)
    r2 = r1 - t1        # what is left has 8 significant bits or fewer
    return tuple(t.astype(jnp.bfloat16) for t in (t0, t1, r2))


def weighted_hist(
    idx: jnp.ndarray,
    vals: jnp.ndarray,
    num_segments: int,
    *,
    chunk: int = 8192,
    exact: bool = True,
) -> jnp.ndarray:
    """f32[num_segments] per-segment sums of vals; out-of-range ids dropped.

    Exactness contract (honest version):
    - exact=True splits each f32 value into THREE bf16 terms (bf16_terms),
      v == t0+t1+t2 bit-exactly for every finite f32 whose twice-reduced
      residual does not underflow bf16's subnormal floor (all values with
      |v| >= ~2**-110, and 0). Each bf16 x {0,1} one-hot product is exact,
      so every record's
      value enters the f32 accumulator unquantized; the per-segment SUM is
      then an f32 accumulation, equal to a per-record f32 sum up to
      addition order. It is NOT f64 accumulation (the reference's
      per-record path sums in double): results are bit-equal to the oracle
      for integer-valued / short-mantissa payloads and f32-rounded
      otherwise — the parity tests compare under f32 tolerance.
    - exact=False uses a single bf16 term: ~8 mantissa bits per value,
      3x less matmul work; for count-like payloads (small integers) it is
      still exact.
    """
    hi_n, _ = plan_segments(num_segments)

    def body(acc, args):
        ii, vv = args
        oh_hi, oh_lo = _one_hots(ii, hi_n, jnp.bfloat16)
        for t in (bf16_terms(vv) if exact else (vv.astype(jnp.bfloat16),)):
            acc = acc + _dot(oh_hi * t[:, None], oh_lo, jnp.float32)
        return acc, None

    n = idx.shape[0] // chunk
    acc, _ = jax.lax.scan(
        body, jnp.zeros((hi_n, LANES), jnp.float32), (idx.reshape(n, chunk), vals.reshape(n, chunk))
    )
    return acc.reshape(-1)[:num_segments]


def pad_batch(arrs, n: int, chunk: int, fill_idx: int = -1):
    """Host-side: pad (idx, *value arrays) up to a chunk multiple."""
    padded = -(-max(n, 1) // chunk) * chunk
    if padded == n:
        return arrs, n
    out = []
    for i, a in enumerate(arrs):
        fill = fill_idx if i == 0 else 0
        pad = np.full(padded - n, fill, dtype=a.dtype)
        out.append(np.concatenate([a, pad]))
    return out, padded
