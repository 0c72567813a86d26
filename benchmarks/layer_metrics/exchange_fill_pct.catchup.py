"""Share of the lanes the fullest device ingests that carry a record:
100 x the largest `routed / lanes` over the devices, from the program's
per-device exchange counters (`ctx["counters"]["per_device"]`: records the
keyBy exchange delivered to the device since the job started, over lanes its
ingest read for them). The exchange is positional, so every device reads the
same lanes whatever the keys are; what skew changes is how many of them are
live on the device that owns the hot range. A description of the traffic
more than a goal: `better` is `higher` because the schema wants a direction.
`program_span` in `BENCHMARK.json`, as `shard_skew` has it. A job on one
chip, or a program without the counters, gives nothing to read."""


def read(ctx):
    fills = [e["routed"] / e["lanes"]
             for e in ctx["counters"].get("per_device") or []
             if e.get("lanes", 0) > 0 and "routed" in e]
    if len(fills) < 2:
        return None
    return 100.0 * max(fills)
