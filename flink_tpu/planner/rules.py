"""Rewrite rules over the logical plan.

Each rule either annotates the tree (the lowering reads the annotations)
or raises `Unsupported` with a catalogued reason — the table layer keeps
such statements on the interpreted path with the reason attributed.

The sequence mirrors the reference planner's group-window rewrite set at
the scale this dialect needs:

  normalize_window          TUMBLE/HOP -> the sliceable assigner form
                            (slice granule = gcd(size, slide); session is
                            not sliceable and was rejected at build)
  map_aggregates            agg call -> builtin DeviceAggregator name
                            (COUNT->count, SUM->sum, MIN->min, MAX->max,
                            AVG->mean — mean's two add-scatter fields pass
                            the fused classifier's add/min/max bar)
  push_predicate_below_window
                            WHERE mask proven columnar-traceable over the
                            scanned numeric fields -> marked for the
                            traced device prologue (below the window
                            ingest, above nothing: the filter IS part of
                            the compiled superscan)
  prune_projection          the scan's required field set = group col +
                            agg arg + predicate columns; row-mode tables
                            columnarize exactly these (physical pruning),
                            columnar sources keep their layout and the
                            traced extractors simply never touch pruned
                            columns

A join of two derived tables (WindowMaximaJoin) takes
`rewrite_window_maxima` first: a windowed aggregate joined on its window
bounds with the per-window MAX of the same aggregate over the same input,
under `agg >= max`, keeps each window's maxima, every tied key. Proven
from the statement's structure alone, it leaves the windowed aggregate's
plan with a maxima output stage, and the four rules above run on that.

Join plans (JoinLogicalPlan — windowed INNER equi-joins) take their own
single rewrite, `rewrite_join_window`: the shared window normalizes onto
the sliceable form whose gcd granule seeds the device join ring's bucket
geometry, which is what turns `SELECT ... FROM a JOIN b ... WINDOW ...`
into a fused-runner selection instead of the old blanket 'join' fallback.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from flink_tpu.planner.logical import (
    JoinLogicalPlan,
    LogicalPlan,
    Unsupported,
    WindowMaximaJoin,
    predicate_is_columnar,
    render_predicate,
    window_slice_ms,
)
#: single-sourced with the interpreted translation (table_env) — the two
#: front doors must never disagree about which aggregates have a device
#: form; the runtime and the fusion classifier resolve these strings via
#: ops.aggregators.resolve
from flink_tpu.table.sql import (
    DEVICE_AGG_OF,
    SelectItem,
    conjuncts,
    predicate_columns,
)


def optimize(plan):
    """Run the full rule sequence in order; mutates and returns `plan`
    (a WindowMaximaJoin comes back as the LogicalPlan it rewrites to)."""
    if isinstance(plan, JoinLogicalPlan):
        rewrite_join_window(plan)
        return plan
    if isinstance(plan, WindowMaximaJoin):
        plan = rewrite_window_maxima(plan)
    normalize_window(plan)
    map_aggregates(plan)
    push_predicate_below_window(plan)
    prune_projection(plan)
    return plan


def rewrite_join_window(plan: JoinLogicalPlan) -> None:
    """The join lowering rewrite: normalize the shared window onto the
    sliceable form the device join ring consumes. The ring's bucket
    granule is gcd(size, slide) — the same slice decomposition the
    windowed-aggregate path uses — so a SQL TUMBLE/HOP join lands on the
    fused `DeviceJoinRunner` with NO host re-bucketing: the logical
    window spec IS the ring geometry's seed (joins/spec.py
    plan_join_geometry starts from exactly these numbers)."""
    w = plan.window
    if w.size_ms <= 0 or w.slide_ms <= 0:
        raise Unsupported("bad-window-geometry",
                          f"size={w.size_ms} slide={w.slide_ms}")
    w.slice_ms = window_slice_ms(w.size_ms, w.slide_ms)


def normalize_window(plan: LogicalPlan) -> None:
    w = plan.window_agg.window
    table = plan.scan.table
    if w.size_ms <= 0 or w.slide_ms <= 0:
        raise Unsupported(
            "bad-window-geometry",
            f"size={w.size_ms} slide={w.slide_ms}")
    if table.rowtime is None or w.time_col != table.rowtime:
        raise Unsupported(
            "window-not-on-rowtime",
            f"window over {w.time_col!r}, table rowtime is "
            f"{table.rowtime!r}")
    w.slice_ms = window_slice_ms(w.size_ms, w.slide_ms)


def map_aggregates(plan: LogicalPlan) -> None:
    agg = plan.window_agg.agg
    table = plan.scan.table
    agg.device_agg = DEVICE_AGG_OF.get(agg.func)
    if agg.device_agg is None:   # parser only emits the five; belt+braces
        raise Unsupported("multi-aggregate",
                          f"unmapped aggregate {agg.func}")
    if agg.arg is not None:
        if agg.arg == table.rowtime:
            raise Unsupported("rowtime-in-expression",
                              f"{agg.func}({agg.arg})")
        if agg.arg not in table.fields:
            raise Unsupported("unknown-column",
                              f"{agg.func} over unknown column "
                              f"{agg.arg!r}")
        if table.field_types is None and not table.columnar:
            raise Unsupported("untyped-schema",
                              f"{agg.func}({agg.arg}) over an untyped "
                              f"row-mode table")
        if not table.is_numeric(agg.arg):
            raise Unsupported("non-numeric-field",
                              f"{agg.func}({agg.arg})")


def push_predicate_below_window(plan: LogicalPlan) -> None:
    if plan.filter is None:
        return
    table = plan.scan.table
    if table.field_types is None and not table.columnar:
        raise Unsupported("untyped-schema",
                          "WHERE over an untyped row-mode table")
    code, why = predicate_is_columnar(plan.filter.pred, table)
    if code is not None:
        raise Unsupported(code, why)
    plan.filter.below_window = True


def prune_projection(plan: LogicalPlan) -> None:
    table = plan.scan.table
    wa = plan.window_agg
    key = wa.group_col
    if key == table.rowtime:
        raise Unsupported("rowtime-in-expression", f"GROUP BY {key}")
    if key not in table.fields:
        raise Unsupported("unknown-column",
                          f"unknown GROUP BY column {key!r}")
    if table.field_types is None and not table.columnar:
        raise Unsupported("untyped-schema", f"GROUP BY {key} over an "
                                            "untyped row-mode table")
    if table.type_of(key) != "int":
        if table.field_types is None:
            # columnar registration without declared types: nothing was
            # "declared 'float'" — the user just needs to declare the key
            raise Unsupported(
                "untyped-schema",
                f"GROUP BY {key!r} on a columnar table without "
                "field_types (the group key must be a declared int — "
                "dense device keys, and the row view must emit the same "
                "Python ints the fused path does)")
        raise Unsupported(
            "non-integer-group-key",
            f"GROUP BY {key!r} is declared {table.type_of(key)!r}")
    required = [key]
    if wa.agg.arg is not None and wa.agg.arg not in required:
        required.append(wa.agg.arg)
    if plan.filter is not None:
        for c in predicate_columns(plan.filter.pred):
            if c not in required:
                required.append(c)
    plan.scan.required = required


#: comparison op with its sides swapped
_FLIP = {"=": "=", "!=": "!=", "<>": "<>", "<": ">", "<=": ">=", ">": "<",
         ">=": "<="}


def _roles(select: List[SelectItem], key: str) -> Dict[str, str]:
    """Output column -> what it holds, of a windowed aggregate's SELECT:
    'key', 'agg', 'start' (window_start), 'end' (window_end)."""
    kind_role = {"agg": "agg", "window_start": "start", "window_end": "end"}
    return {i.output_name: ("key" if i.kind == "column" and i.name == key
                            else kind_role.get(i.kind, "?"))
            for i in select}


def _column_of(name: str, alias: str) -> Optional[str]:
    """`alias.col` -> col, a bare `col` -> col, another alias -> None."""
    head, dot, tail = name.partition(".")
    if not dot:
        return name
    return tail if head == alias else None


def _structure(plan: LogicalPlan) -> Tuple:
    """What makes two windowed aggregates the same relation."""
    wa, w = plan.window_agg, plan.window_agg.window
    return (plan.scan.table.name,
            None if plan.filter is None else plan.filter.pred,
            (w.kind, w.time_col, w.size_ms, w.slide_ms), wa.group_col,
            (wa.agg.func, wa.agg.arg))


def rewrite_window_maxima(join: WindowMaximaJoin) -> LogicalPlan:
    """Keep each window's maxima. Applies where (1) the per-key side A and
    the input C of the MAX side B are structurally equal windowed
    aggregates (table or view, filter, window, group key, aggregate); (2) B
    is `SELECT MAX(c.<C's aggregate>), <C's window bounds>` grouped by C's
    window bounds alone; (3) the condition is AND-ed `=` of a window bound
    of A with the same bound of B (one at least) and one `a.<aggregate> >=
    b.<max>` (or `=`, the same rows). The result is A's plan whose output
    stage keeps, of each window, every key whose aggregate equals the
    window's maximum; anything else raises Unsupported('window-maxima')."""
    a, c, b = join.per_key, join.per_window, join.max_query
    why = "window-maxima"
    for side in (a.query, c.query):
        if side.having is not None or side.order_by or side.limit is not None:
            raise Unsupported(why, "a derived table with HAVING, ORDER BY or "
                                   "LIMIT")
    sa, sc = _structure(a), _structure(c)
    parts = ("table", "filter", "window", "group key", "aggregate")
    for part, x, y in zip(parts, sa, sc):
        if x != y:
            show = (render_predicate(x) if part == "filter" and x is not None
                    else x)
            other = (render_predicate(y) if part == "filter"
                     and y is not None else y)
            raise Unsupported(why, f"the two derived tables differ in their "
                                   f"{part}: {show} vs {other}")
    inner_alias = b.table
    c_roles = _roles(c.query.select, c.window_agg.group_col)
    b_roles: Dict[str, str] = {}
    for item in b.select:
        col = _column_of(item.name, inner_alias)
        role = c_roles.get(col)
        if item.kind == "agg" and item.func == "MAX" and role == "agg":
            b_roles[item.output_name] = "max"
        elif item.kind == "column" and role in ("start", "end"):
            b_roles[item.output_name] = role
        else:
            raise Unsupported(why, f"{join.max_alias} selects {item.name} "
                                   "where it keeps MAX of the aggregate and "
                                   "the window bounds alone")
    grouped = {c_roles.get(_column_of(g, inner_alias)) for g in b.group_by}
    if (not grouped or not grouped <= {"start", "end"}
            or b.window is not None or b.where_ast is not None
            or b.having is not None or b.order_by or b.limit is not None
            or "max" not in b_roles.values()):
        raise Unsupported(why, f"{join.max_alias} is not the per-window MAX "
                               "of the other side's aggregate")
    a_roles = _roles(a.query.select, a.window_agg.group_col)
    sides = {join.per_key_alias: a_roles, join.max_alias: b_roles}
    bounds, keeps = set(), 0
    on = join.query.derived_join.on
    terms_of_on = conjuncts(on)
    if terms_of_on is None:
        raise Unsupported(why, f"OR in the join condition: "
                               f"{render_predicate(on)}")
    for cmp in terms_of_on:
        terms = [(_alias_of(op), op) for op in (cmp.left, cmp.right)]
        op = cmp.op
        if terms[0][0] == join.max_alias:
            terms.reverse()
            op = _FLIP[op]
        (la, lhs), (ra, rhs) = terms
        if la != join.per_key_alias or ra != join.max_alias:
            raise Unsupported(why, f"{render_predicate(cmp)} does not compare "
                                   f"{join.per_key_alias} with "
                                   f"{join.max_alias}")
        lrole = a_roles.get(lhs.value.split(".", 1)[1])
        rrole = b_roles.get(rhs.value.split(".", 1)[1])
        if op == "=" and lrole in ("start", "end") and lrole == rrole:
            bounds.add(lrole)
        elif op in (">=", "=") and (lrole, rrole) == ("agg", "max"):
            keeps += 1
        else:
            raise Unsupported(why, f"{render_predicate(cmp)} is neither a "
                                   "window bound equated nor `aggregate >= "
                                   "max`")
    if not bounds or keeps != 1:
        raise Unsupported(why, "the condition must equate the window bounds "
                               "and hold the aggregate >= the maximum once")
    roles: List[Tuple[str, str]] = []
    for item in join.query.select:
        alias, _dot, col = item.name.partition(".")
        role = sides.get(alias, {}).get(col)
        if item.kind != "column" or role is None or role == "?":
            raise Unsupported(why, f"SELECT {item.name}: only columns of "
                                   "the two sides are selected")
        roles.append((item.output_name, "agg" if role == "max" else role))
    a.output = dataclasses.replace(
        a.output, columns=[n for n, _r in roles], maxima=True, roles=roles,
        having_text=None, order_by=[], limit=None)
    return a


def _alias_of(op) -> Optional[str]:
    if op.kind != "column" or "." not in op.value:
        return None
    return op.value.split(".", 1)[0]
