"""User function interfaces — re-exported from flink_tpu.core.functions.

The definitions live in core (matching the reference, which places these
in flink-core .../api/common/functions/, not in the streaming API layer);
this module keeps the API-namespace import path working.
"""

from flink_tpu.core.functions import (  # noqa: F401
    ACC,
    IN,
    KEY,
    LATE_DATA_TAG,
    OUT,
    AggregateFunction,
    FilterFunction,
    FlatMapFunction,
    KeySelector,
    MapFunction,
    OutputTag,
    PassThroughWindowFunction,
    ProcessFunction,
    ProcessWindowFunction,
    ReduceAggregate,
    ReduceFunction,
    as_key_selector,
    as_reduce_function,
    null_key,
)
