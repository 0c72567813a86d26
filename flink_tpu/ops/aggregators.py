"""Aggregator specs usable on both the device path and the Python oracle.

The reference folds window contents into a single accumulator per
(key, window) via ReducingState/AggregatingState
(HeapAggregatingState.add:94) — state per key×window is one ACC. The device
path makes the ACC *columnar*: each accumulator field is one [keys, slices]
array in HBM, updated by scatter-combine and merged across slices by a
segment reduce at fire time.

A `DeviceAggregator` therefore restricts accumulators to a flat dict of
numeric fields, each with a scatter combiner in {add, min, max} — enough for
sum/count/min/max/mean/sum-of-squares-style analytics (the YSB/Nexmark
baseline set). Arbitrary Python `AggregateFunction`s run on the oracle
operator instead (same split as the reference, where only
Reducing/AggregatingState windows pre-aggregate).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import operator
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from flink_tpu.core.functions import AggregateFunction

# scatter sources
VALUE = "value"   # scatter the record's value column
ONE = "one"       # scatter constant 1 (count)


@dataclasses.dataclass(frozen=True)
class AccField:
    """One columnar accumulator field: a [keys, slices] device array."""

    name: str
    dtype: Any            # numpy dtype of the field
    identity: float       # padding / empty-slice value
    scatter: str          # 'add' | 'min' | 'max'
    source: str = VALUE   # which input column feeds the scatter
    # declared value domain: non-negative ints < 2**domain_bits. Unlocks the
    # MXU fast path for order statistics (pallas nibble-histogram max, ~5x
    # the scatter unit); None = unbounded, order statistics scatter-combine
    domain_bits: Any = None


@dataclasses.dataclass(frozen=True, eq=False)
class DeviceAggregator:
    """Columnar aggregator: fields + an extract over the combined fields.

    `extract` maps {field_name: array} -> result array (any backend: works
    with both numpy and jnp inputs since it must use only ufunc-style ops).

    eq=False ⇒ identity hashing: instances are cache keys for compiled
    kernels (segment_ops builders are lru_cached on them), so builtin
    factories below memoize and return singletons per dtype.
    """

    name: str
    fields: Tuple[AccField, ...]
    extract: Callable[[Dict[str, Any]], Any]
    result_dtype: Any = np.float32
    # pre-aggregation contract: True means per-(key, slice) partials of the
    # fields, merged by each field's own scatter combiner, reconstruct the
    # exact ring state — the property the mesh map-side combiner
    # (parallel.mesh.local-combine) relies on. Every builtin holds it by
    # construction (add/min/max are associative + commutative); closure-tier
    # aggregates (e.g. the q5 top-K post-processing) never resolve to a
    # DeviceAggregator at all, and a custom spec whose extract depends on
    # more than the combined fields can opt out here.
    combinable: bool = True

    def field(self, name: str) -> AccField:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(name)

    def python_equivalent(self) -> AggregateFunction:
        """Scalar AggregateFunction with identical math, for the oracle."""
        return _ColumnarAsPython(self)


_SCATTER_NP = {
    "add": lambda a, b: a + b,
    "min": np.minimum,
    "max": np.maximum,
}


def combine_binary(op: str):
    """Elementwise jnp combine for a scatter kind — the single dispatch
    table the in-scan session merge carry and the global-fold kernels
    share (numpy ufuncs do NOT dispatch on jit tracers, so the host
    oracle's `_SCATTER_NP` table above cannot serve the kernels; the jax
    import is deferred to kernel-build time)."""
    import jax.numpy as jnp

    table = {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
    if op not in table:
        raise ValueError(op)
    return table[op]


def combine_reduce(op: str):
    """Axis reduction for a scatter kind (works on numpy and jnp arrays):
    the fire-time segment fold over a window's slice columns."""
    if op == "add":
        return lambda a, axis: a.sum(axis=axis)
    if op == "min":
        return lambda a, axis: a.min(axis=axis)
    if op == "max":
        return lambda a, axis: a.max(axis=axis)
    raise ValueError(op)


def scan_identity(dtype, scatter: str):
    """The neutral element of a scatter kind at a dtype — what purged ring
    cells and empty fold lanes must hold so combining them is a no-op."""
    if scatter == "add":
        return 0
    if scatter == "min":
        return _max_of(dtype)
    if scatter == "max":
        return _min_of(dtype)
    raise ValueError(scatter)


class _ColumnarAsPython(AggregateFunction):
    """Scalar-dict interpretation of a DeviceAggregator (oracle parity)."""

    def __init__(self, spec: DeviceAggregator):
        self.spec = spec

    def create_accumulator(self):
        return {f.name: f.identity for f in self.spec.fields}

    def add(self, value, acc):
        out = dict(acc)
        for f in self.spec.fields:
            v = 1 if f.source == ONE else value
            out[f.name] = _SCATTER_NP[f.scatter](acc[f.name], v)
        return out

    def get_result(self, acc):
        res = self.spec.extract({k: np.asarray(v) for k, v in acc.items()})
        arr = np.asarray(res)
        return arr.item() if arr.ndim == 0 else arr

    def merge(self, a, b):
        return {
            f.name: _SCATTER_NP[f.scatter](a[f.name], b[f.name])
            for f in self.spec.fields
        }


# ---------------------------------------------------------------------------
# Built-ins
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def sum_agg(dtype=np.float32) -> DeviceAggregator:
    return DeviceAggregator(
        "sum",
        (AccField("sum", dtype, 0, "add"),),
        lambda f: f["sum"],
        result_dtype=dtype,
    )


@functools.lru_cache(maxsize=None)
def count_agg() -> DeviceAggregator:
    return DeviceAggregator(
        "count",
        (AccField("count", np.int32, 0, "add", source=ONE),),
        lambda f: f["count"],
        result_dtype=np.int32,
    )


@functools.lru_cache(maxsize=None)
def min_agg(dtype=np.float32) -> DeviceAggregator:
    ident = _max_of(dtype)
    return DeviceAggregator(
        "min", (AccField("min", dtype, ident, "min"),), lambda f: f["min"], result_dtype=dtype
    )


@functools.lru_cache(maxsize=None)
def max_agg(dtype=np.float32, domain_bits=None) -> DeviceAggregator:
    """Windowed max. With `domain_bits` set, values are declared to be
    non-negative ints < 2**domain_bits: the accumulator becomes int32 with
    identity -1 ("absent") and the pallas superscan runs max on the MXU via
    two conditional nibble histograms instead of the serial scatter unit."""
    if domain_bits is not None:
        if domain_bits > 8:
            raise ValueError("bounded max supports domain_bits <= 8")
        return DeviceAggregator(
            "max8",
            (AccField("max", np.int32, -1, "max", domain_bits=domain_bits),),
            lambda f: f["max"],
            result_dtype=np.int32,
        )
    ident = _min_of(dtype)
    return DeviceAggregator(
        "max", (AccField("max", dtype, ident, "max"),), lambda f: f["max"], result_dtype=dtype
    )


@functools.lru_cache(maxsize=None)
def mean_agg(dtype=np.float32) -> DeviceAggregator:
    return DeviceAggregator(
        "mean",
        (
            AccField("sum", dtype, 0, "add"),
            AccField("count", np.int32, 0, "add", source=ONE),
        ),
        lambda f: f["sum"] / _maximum(f["count"], 1),
        result_dtype=dtype,
    )


def _maximum(a, b):
    # dispatches correctly for both numpy and jax array inputs
    if isinstance(a, np.ndarray) or np.isscalar(a):
        return np.maximum(a, b)
    import jax.numpy as jnp
    return jnp.maximum(a, b)


def _max_of(dtype) -> float:
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return float(np.finfo(dt).max)
    return int(np.iinfo(dt).max)


def _min_of(dtype) -> float:
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return float(np.finfo(dt).min)
    return int(np.iinfo(dt).min)


BUILTINS = {
    "sum": sum_agg,
    "count": count_agg,
    "min": min_agg,
    "max": max_agg,
    "mean": mean_agg,
}


def decomposable(agg: DeviceAggregator) -> bool:
    """True when the mesh map-side combiner may pre-reduce this aggregate:
    every field's scatter kind is one of the associative+commutative
    combiners and the spec has not opted out. The combine path sends one
    partial per (key, rel-slice) per source shard — merged by the SAME
    scatter ops the ring ingest applies, so pre-reduction is exact by
    construction. Non-decomposable aggregates route raw records instead."""
    return bool(getattr(agg, "combinable", True)) and all(
        f.scatter in _SCATTER_NP for f in agg.fields
    )


class PositionalAggregate(AggregateFunction):
    """`max_by(position)` / `min_by(position)` (Flink's `maxBy(int)` /
    `minBy(int)`): keeps the WHOLE row whose field at `position` is the
    window's extreme. Tie rule, stated: **the first row to arrive among
    equals stays** (Flink's `first = true`). A fused fire hands its rows
    over in ascending key id, so among equal counts of one fire the lowest
    id wins.

    Not a `DeviceAggregator` (its result is a row, not a numeric field):
    as an `AggregateFunction` it runs on the oracle window operator row by
    row, and `pick` is the same rule over a whole column, which is how a
    null-key window reduces a `FireBlock` without building its rows
    (runtime/fire_block.reduce_block)."""

    def __init__(self, name: str, position: int, better: Callable,
                 arg_best: Callable):
        self.name = name
        self.position = int(position)
        self._better = better         # strict: an equal row never replaces
        self._arg_best = arg_best     # first occurrence of the extreme

    def create_accumulator(self):
        return None                   # no row yet (a row is never None)

    def add(self, value, acc):
        if acc is None or self._better(value[self.position],
                                       acc[self.position]):
            return value
        return acc

    def get_result(self, acc):
        return acc

    def merge(self, a, b):
        """`a` holds the earlier rows."""
        if a is None:
            return b
        return a if b is None else self.add(b, a)

    def pick(self, column: np.ndarray) -> int:
        """Index of the row `add` would keep, rows taken in column order."""
        return int(self._arg_best(column))


def max_by_agg(position: int) -> PositionalAggregate:
    return PositionalAggregate("max_by", position, operator.gt, np.argmax)


def min_by_agg(position: int) -> PositionalAggregate:
    return PositionalAggregate("min_by", position, operator.lt, np.argmin)


def resolve(agg) -> Optional[DeviceAggregator]:
    """Resolve a user-provided aggregate spec to a DeviceAggregator if it can
    run on the device path; None means fall back to the oracle operator
    (a `PositionalAggregate` among them: no device program holds rows)."""
    if isinstance(agg, DeviceAggregator):
        return agg
    if isinstance(agg, str) and agg in BUILTINS:
        return BUILTINS[agg]()
    return None
