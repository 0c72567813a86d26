"""NEXMark Query 5, Hot Items, as its SQL statement: `q5.sql` run through
`StreamTableEnvironment.sql_query` and `env.execute()`.

The benchmark's source is registered as the columnar table the
configuration's `sql` block names (`nexmark`, every column an integer, its
event-time column the rowtime); the `bid` view is that block's `WHERE` over
it; the statement's text is handed to `sql_query` as it is. The planner
rewrites the statement's self-join with MAX onto the fused hop window with an
output stage that keeps every auction at the window's maximum; the build
stops if it plans anything else, so a cell never runs the interpreted path.
The sink receives `(auction, num)` pairs stamped `window_end - 1`.
"""

from __future__ import annotations

from typing import Dict

from benchmarks.jobs.common import from_source


def build(env, source, sink, cfg: Dict, tables: Dict) -> None:
    from flink_tpu.table import StreamTableEnvironment, TableSchema

    sql = cfg["sql"]
    t_env = StreamTableEnvironment.create(env)
    t_env.register_table(
        sql["table"], from_source(env, source, cfg),
        TableSchema(list(sql["columns"]), rowtime=sql["rowtime"],
                    field_types=["int"] * len(sql["columns"])),
        columnar=True)
    t_env.create_temporary_view(sql["view"]["name"], sql["view"]["statement"])
    rows = t_env.sql_query(sql["statement"])
    report = t_env.last_plan_report
    if report.path != "fused":
        raise RuntimeError(f"q5.sql planned {report.path!r}, not fused: "
                           f"{report.describe()}")
    rows.map(lambda row: (row["auction"], row["num"]),
             name="q5_sink_row").sink_to(sink)
