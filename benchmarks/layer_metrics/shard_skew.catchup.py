"""Most records resident on one device over the mean per device, from the
program's per-device key statistics (its newest fold; 1.0 = the key ranges
hold equal shares). `program_span` in `BENCHMARK.json`: the nearest source
the schema has to a program counter. A job on one chip reports no
`per_device` block: nothing to read."""


def read(ctx):
    records = [e.get("records", 0)
               for e in ctx["counters"].get("per_device") or []]
    if len(records) < 2 or sum(records) <= 0:
        return None
    return max(records) * len(records) / sum(records)
