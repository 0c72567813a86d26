"""SQL planner: table plans → StepGraph, onto the fused device path.

The production front door for "millions of users" is SQL, not hand-built
operator chains. This package translates parsed `Query` objects
(table/sql.py) into logical relational plans (planner/logical.py),
optimizes them (planner/rules.py: predicate pushdown below the window,
projection pruning, window-spec normalization onto the sliceable
assigners, agg-call → DeviceAggregator mapping), and lowers them
(planner/lowering.py) into the same transformation chain the DataStream
API records — so `graph.plan()` + `graph/fusion.py` classify SQL windowed
aggregates as device-fusable and `DeviceChainRunner` (plus the sharded
mesh path and the tiered state plane) run them as one compiled superscan.

Statements outside the fused core fall back to the interpreted
TableEnvironment path with a catalogued reason (`FALLBACK_CATALOG`),
never an error. `TableEnvironment.execute_sql*` routes through here
behind `table.device-fusion` (default on); `explain_sql` returns the
report this module produces.

Layering (ARCH001): may import table/graph/core/config — never runtime,
api, or scheduler; assigner construction is a function-scoped lazy import.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from flink_tpu.planner.logical import (  # noqa: F401 — public surface
    FALLBACK_CATALOG,
    JoinLogicalPlan,
    LogicalPlan,
    TableInfo,
    Unsupported,
    ViewInfo,
    WindowMaximaJoin,
    build_logical_plan,
)
from flink_tpu.planner.lowering import LoweredQuery, lower
from flink_tpu.planner.rules import optimize
from flink_tpu.table.sql import Query


@dataclasses.dataclass
class SqlPlanReport:
    """Per-statement planning outcome: which path was selected and why.

    `path` is 'fused' or 'interpreted'; on fallback, `reason` is a
    FALLBACK_CATALOG code and `detail` the specific trigger. `plan` holds
    the optimized logical tree for fused statements (golden-test /
    EXPLAIN surface); `lowered` the emitted chain when a source
    transformation was provided."""

    path: str
    reason: Optional[str] = None
    detail: Optional[str] = None
    plan: Optional[LogicalPlan] = None
    lowered: Optional[LoweredQuery] = None

    @property
    def fused(self) -> bool:
        return self.path == "fused"

    def describe(self) -> str:
        if self.fused and self.plan is not None:
            return self.plan.describe()
        return f"interpreted[{self.reason}]: {self.detail}"

    def summary(self) -> Dict[str, Optional[str]]:
        """Plain data for a job's metrics: the path, the fallback's reason
        and detail (None on the fused path), the plan as text."""
        return {"path": self.path, "reason": self.reason,
                "detail": self.detail, "plan": self.describe()}


def plan_query(
    q: Query,
    catalog: Dict[str, TableInfo],
    sources: Optional[Dict[str, object]] = None,
) -> SqlPlanReport:
    """Plan one parsed statement against the catalog.

    `sources` maps table name -> source Transformation; when provided and
    the statement is fused-lowerable, the report carries the emitted
    LoweredQuery ready for execution. Without sources the report is
    plan-only (EXPLAIN / golden tests)."""
    try:
        plan = optimize(build_logical_plan(q, catalog))
    except Unsupported as u:
        return SqlPlanReport(path="interpreted", reason=u.reason,
                             detail=u.detail)
    lowered = None
    if sources is not None:
        if isinstance(plan, JoinLogicalPlan):
            # fused windowed join: the planner validated the shape; the
            # two-input stream construction happens in the table layer
            # (row streams are an api-layer concern), which stamps the
            # window_join transformation sql_origin so the runtime's
            # DeviceJoinRunner counts as the SQL-fused selection. The
            # report stays `lowered=None` by design.
            for name in (q.table, q.join.table2):
                if sources.get(name) is None:
                    return SqlPlanReport(
                        path="interpreted", reason="unknown-table",
                        detail=f"no source for {name!r}")
        else:
            # a view's statement reads the table under it
            name = plan.scan.table.name
            src = sources.get(name)
            if src is None:
                return SqlPlanReport(
                    path="interpreted", reason="unknown-table",
                    detail=f"no source for {name!r}")
            lowered = lower(plan, src)
    return SqlPlanReport(path="fused", plan=plan, lowered=lowered)
