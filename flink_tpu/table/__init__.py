"""Table/SQL layer (reference: flink-table — Calcite parser T1, planner T2,
runtime T3). A compact dialect covering the streaming-aggregation core:
windowed GROUP BY over TUMBLE/HOP/SESSION, WHERE filters, and the standard
aggregate functions, translated onto the DataStream plan (and therefore onto
the device window operator — the same sliced-window execution the reference
SQL runtime uses via tvf/slicing)."""

from flink_tpu.table.table_env import (
    StreamTableEnvironment,
    TableEnvironment,
    TableSchema,
)
from flink_tpu.table.sql import SqlParseError, parse_query
from flink_tpu.table.changelog import (
    DELETE,
    INSERT,
    ROW_KIND_FIELD,
    UPDATE_AFTER,
    UPDATE_BEFORE,
    materialize,
    row_kind,
    with_kind,
)
