"""Lookups in a small constant integer table as exact one-hot contractions.

A traced chain that enriches each record from static reference data — the
ad -> campaign join of the Yahoo streaming benchmark, a dimension table —
writes `jnp.take(table, idx)`. On the TPU that is a gather, and the gather
path fetches one element a lane at ~5.3 ns: 2 M lookups in a 4 KB table cost
11.2 ms a dispatch, 68 % of `ysb_catchup`'s program (PERF.md section 5, PR 36).

The same lookup as linear algebra, with the row split two-level as
`idx = h * 128 + l` and the table laid out `[H, 128]`:

    T[idx] = sum_h 1[h == idx >> 7] * ( P[h, :] @ one_hot(idx & 127) )

One `[b * H, 128] x [128, B]` dot on the MXU, a select over H rows: 128 + H
compares a lane, H <= 128 (so at most MAX_ROWS rows). Exactness: the table
minus its minimum is held as `b` byte planes, each a bf16 constant whose
entries are integers 0-255, which bf16 holds exactly; every output of the
dot is one product 1 x byte accumulated in f32, exact; the bytes recombine
in uint32. No traced value is ever converted f32 -> bf16 -> f32 (XLA:TPU may
skip such a pair, PERF.md section 6, PR 37): the only bf16 values are the
0/1 one-hot and the host-made planes.

`call(fn, *args)` is the pass that puts it to use: it reads `fn`'s jaxpr
(through nested `jit` / `closed_call`), and where a `gather` reads a
trace-time constant that `refusal` accepts, evaluates the jaxpr with that
equation replaced by `lookup`. Where none qualifies, `fn` is called as it
is, so its trace is what it was without the pass. A nested jaxpr is shared
between the call sites that trace alike (`jnp.take` on two tables of one
shape reaches one `_take` jaxpr), so what is replaced is kept per path:
each call site holds its own sites for the jaxpr it calls.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
#: rows of the largest table lowered: ceil(N / 128) <= 128 keeps the select
#: over the table's row blocks no wider than the one-hot over its lanes
MAX_ROWS = LANES * LANES

#: why a gather is kept (`Lowering.kept`; the prologue's
#: `prologueGathersKept`, docs/observability.md)
NOT_CONSTANT = "not constant"
NOT_INTEGER = "not integer"
TOO_MANY_ROWS = "too many rows"
NOT_SCALAR_ROW = "not the scalar-row pattern"

#: the nested scope a lowered lookup runs under (`t1.map/lookup/...`)
SCOPE = "lookup"

_MODES = (jax.lax.GatherScatterMode.CLIP,
          jax.lax.GatherScatterMode.FILL_OR_DROP,
          jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)


class TablePlanes(NamedTuple):
    """A table as `lookup` reads it, made once on the host."""

    planes: np.ndarray   # bf16 [b * H, 128]: byte k of T[h*128 + l] - base at [k*H + h, l]
    base: int            # the table's minimum, as the uint32 of its bits
    rows: int            # N
    dtype: np.dtype      # the table's own dtype: the lookup's result


def refusal(shape, dtype) -> Optional[str]:
    """Why a constant gather operand of `shape` / `dtype` is not lowered
    (None: it is) — read before its values are (`_read_gather`)."""
    dtype = np.dtype(dtype)
    if len(shape) != 1 or shape[0] == 0:
        return NOT_SCALAR_ROW
    if dtype != np.bool_ and not (
            np.issubdtype(dtype, np.integer) and dtype.itemsize <= 4):
        return NOT_INTEGER
    if shape[0] > MAX_ROWS:
        return TOO_MANY_ROWS
    return None


def planes_of(table: np.ndarray) -> TablePlanes:
    """`table` minus its minimum as byte planes, as few as its range needs."""
    vals = np.asarray(table).astype(np.int64)
    base = int(vals.min())
    span = (vals - base).astype(np.uint64)          # < 2**32
    nbytes = max(1, -(-int(span.max()).bit_length() // 8))
    H = -(-len(vals) // LANES)
    padded = np.zeros(H * LANES, np.uint64)
    padded[:len(vals)] = span
    planes = np.stack([(padded >> np.uint64(8 * k)) & np.uint64(255)
                       for k in range(nbytes)])
    return TablePlanes(planes.reshape(nbytes * H, LANES).astype(jnp.bfloat16),
                       base % (1 << 32), len(vals), np.dtype(table.dtype))


def lookup(tp: TablePlanes, idx, *, mode, fill_value=None):
    """`tp`'s table at each of `idx` ([B], any integer dtype), as the gather
    of `mode` reads it: an index outside [0, N) reads `fill_value` under
    FILL_OR_DROP, the nearest row otherwise."""
    B = idx.shape[0]
    if idx.dtype.itemsize < 4:      # so that N and N - 1 fit its dtype
        idx = idx.astype(jnp.int32)
    H = -(-tp.rows // LANES)
    nbytes = tp.planes.shape[0] // H
    inside = (idx >= 0) & (idx < tp.rows)
    if mode == jax.lax.GatherScatterMode.FILL_OR_DROP:
        row = jnp.where(inside, idx, 0)
    else:
        row = jnp.clip(idx, 0, tp.rows - 1)
    row = row.astype(jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (LANES, B), 0)
    one_hot = (lane == (row & (LANES - 1))[None, :]).astype(jnp.bfloat16)
    got = jax.lax.dot(jnp.asarray(tp.planes), one_hot,
                      preferred_element_type=jnp.float32)   # [b * H, B]
    block = jax.lax.broadcasted_iota(jnp.int32, (H, B), 0) == (row >> 7)[None, :]
    span = jnp.zeros((B,), jnp.uint32)
    for k in range(nbytes):
        byte = jnp.sum(jnp.where(block, got[k * H:(k + 1) * H], 0.0), axis=0)
        span = span | (byte.astype(jnp.uint32) << (8 * k))
    bits = span + jnp.uint32(tp.base)       # wraps: the value's own 32 bits
    if tp.dtype == np.bool_:
        out = bits != 0
    elif tp.dtype == np.uint32:
        out = bits
    else:
        out = jax.lax.bitcast_convert_type(bits, jnp.int32).astype(tp.dtype)
    if mode == jax.lax.GatherScatterMode.FILL_OR_DROP:
        out = jnp.where(inside, out, jnp.asarray(fill_value, tp.dtype))
    return out


# -- the pass: a callable's constant-table gathers, found and replaced ------

class Lowering(NamedTuple):
    """What `call` does with one callable at one argument shape."""

    jaxpr: Any                    # its ClosedJaxpr (None: call it as it is)
    out_tree: Any
    sites: Dict[int, Any]         # id(eqn) -> TablePlanes for a gather, or
                                  # for a call that holds one the sites of
                                  # the jaxpr it calls (from this call site)
    lowered: int                  # gathers replaced
    kept: Tuple[str, ...]         # one reason a gather left as it is


_CALLS = {"jit": "jaxpr", "closed_call": "call_jaxpr"}


def _const(atom, known):
    """The constant `atom` is bound to, as traced (a device or numpy array:
    never copied here), or None."""
    from jax._src import core
    if isinstance(atom, core.Literal):
        return np.asarray(atom.val)     # a scalar
    return known.get(atom)


def _read_gather(eqn, known) -> Tuple[Optional[str], Optional[TablePlanes]]:
    operand, indices = eqn.invars
    table = _const(operand, known)
    if table is None:
        return NOT_CONSTANT, None
    dn, p = eqn.params["dimension_numbers"], eqn.params
    if (dn.offset_dims != () or dn.collapsed_slice_dims != (0,)
            or dn.start_index_map != (0,) or dn.operand_batching_dims
            or dn.start_indices_batching_dims
            or tuple(p["slice_sizes"]) != (1,) or p["mode"] not in _MODES
            or indices.aval.ndim != 2 or indices.aval.shape[1] != 1):
        return NOT_SCALAR_ROW, None
    # shape and dtype first: only a table that qualifies is read to the host
    why = refusal(operand.aval.shape, operand.aval.dtype)
    return why, (None if why else planes_of(table))


def _read(jaxpr, known, sites, kept) -> int:
    """Gathers of `jaxpr` lowered, entered in `sites` (this path's: a
    nested call's gathers go in a dict of its own, under the call);
    `known`: its variables bound to trace-time constants."""
    lowered = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "gather":
            why, tp = _read_gather(eqn, known)
            if why:
                kept.append(why)
            else:
                sites[id(eqn)] = tp
                lowered += 1
        elif name in _CALLS:
            inner = eqn.params[_CALLS[name]]
            inner_known = dict(zip(inner.jaxpr.constvars, inner.consts))
            for var, atom in zip(inner.jaxpr.invars, eqn.invars):
                c = _const(atom, known)
                if c is not None:
                    inner_known[var] = c
            inner_sites: Dict[int, Any] = {}
            n = _read(inner.jaxpr, inner_known, inner_sites, kept)
            if n:
                sites[id(eqn)] = inner_sites
                lowered += n
    return lowered


@functools.lru_cache(maxsize=256)
def lowering(fn, avals: Tuple[jax.ShapeDtypeStruct, ...]) -> Lowering:
    """`fn`'s constant-table gathers at argument shapes `avals`, read once
    (the entry holds `fn`: identity-keyed, as the program caches are)."""
    closed, shapes = jax.make_jaxpr(fn, return_shape=True)(*avals)
    sites: Dict[int, Any] = {}
    kept: List[str] = []
    known = dict(zip(closed.jaxpr.constvars, closed.consts))
    lowered = _read(closed.jaxpr, known, sites, kept)
    return Lowering(closed if lowered else None,
                    jax.tree_util.tree_structure(shapes), sites, lowered,
                    tuple(kept))


def aval_of(x) -> jax.ShapeDtypeStruct:
    aval = jax.typeof(x)
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype,
                                weak_type=getattr(aval, "weak_type", False))


def call(fn, *args) -> Tuple[Any, Lowering]:
    """(`fn(*args)`, with its constant-table gathers done by `lookup` under
    a `lookup` scope — exactly `fn(*args)` where it has none; the
    `Lowering` that decided it)."""
    low = lowering(fn, tuple(aval_of(a) for a in args))
    if low.jaxpr is None:
        return fn(*args), low
    outs = _eval(low.jaxpr.jaxpr, low.jaxpr.consts, args, low.sites)
    return jax.tree_util.tree_unflatten(low.out_tree, outs), low


def _eval(jaxpr, consts, args, sites):
    """`jax.core.eval_jaxpr`, with the equations of `sites` replaced."""
    from jax._src import core, source_info_util

    env: Dict[Any, Any] = dict(zip(jaxpr.constvars, consts))
    env.update(zip(jaxpr.invars, args))

    def read(atom):
        return atom.val if isinstance(atom, core.Literal) else env[atom]

    for eqn in jaxpr.eqns:
        vals = [read(v) for v in eqn.invars]
        site = sites.get(id(eqn))
        name_stack = (source_info_util.current_name_stack()
                      + eqn.source_info.name_stack)
        with source_info_util.user_context(eqn.source_info.traceback,
                                           name_stack=name_stack), \
                eqn.ctx.manager:
            if isinstance(site, dict):
                inner = eqn.params[_CALLS[eqn.primitive.name]]
                outs = _eval(inner.jaxpr, inner.consts, vals, site)
            elif site is not None:
                with jax.named_scope(SCOPE):
                    outs = [lookup(site, vals[1][:, 0],
                                   mode=eqn.params["mode"],
                                   fill_value=eqn.params["fill_value"])]
            else:
                subfuns, params = eqn.primitive.get_bind_params(eqn.params)
                outs = eqn.primitive.bind(*subfuns, *vals, **params)
                if not eqn.primitive.multiple_results:
                    outs = [outs]
        env.update(zip(eqn.outvars, outs))
    return [read(v) for v in jaxpr.outvars]
