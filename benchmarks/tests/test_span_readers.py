"""The span readers on a hand-made trace: every number below is worked out
by hand from fixtures/synthetic_trace_spans.json (ns, window [0, 1000)).

The job's thread (the line that holds `benchmark.poll_batch`), self times:

  source.poll [0,50) holds benchmark.poll_batch [0,40)        10 / 40
  chain.host [60,100) and [980,1030), clipped at 1000          40 + 20
  normalize [100,150)   keys.lookup [150,170)                  50 / 20
  stage.fill [200,300)                                         100
  stage.put [300,400), the DevicePut inside it stays with it   100
  dispatch [400,450), the PjitFunction inside stays with it    50
  resolve [450,700) holds np.asarray (its own) and two emits   250 - 70
  emit [620,660) [660,690)                                     70
  drain [700,800) holds sink.write [720,780) holds
      benchmark.sink_write [730,770)                           40 / 20 / 40
  under no span: [50,60) [170,200) [800,980)                   220

The other host line holds a `flink_tpu.emit` of another thread: not counted.
"""

import json
import os

import pytest

from benchmarks import harness, span_lib
from benchmarks import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "fixtures", "synthetic_trace_spans.json")
SPAN_METRICS = {
    "host_chain_pct.catchup": 6.0,
    "key_lookup_pct.catchup": 2.0,
    "normalize_pct.catchup": 5.0,
    "stage_fill_pct.catchup": 10.0,
    "stage_put_pct.catchup": 10.0,
    "resolve_pct.catchup": 18.0,
    "emit_pct.catchup": 7.0 + 4.0,          # emit + drain
    "host_dark_pct.catchup": 22.0,
}

def ctx_of(trace):
    return {"trace": trace, "trace_window": tr.window_of(trace)}


def read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


def test_self_times_partition_the_window():
    ctx = ctx_of(tr.load_json(FIXTURE))
    assert ctx["trace_window"] == (0, 1000)
    times = span_lib.self_times(ctx)
    assert times == {
        "flink_tpu.source.poll": 10, "benchmark.poll_batch": 40,
        "flink_tpu.chain.host": 60, "flink_tpu.normalize": 50,
        "flink_tpu.keys.lookup": 20, "flink_tpu.stage.fill": 100,
        "flink_tpu.stage.put": 100, "flink_tpu.dispatch": 50,
        "flink_tpu.resolve": 180, "flink_tpu.emit": 70,
        "flink_tpu.drain": 40, "flink_tpu.sink.write": 20,
        "benchmark.sink_write": 40}
    shares = sum(100.0 * v / 1000 for v in times.values())
    assert shares + span_lib.dark_pct(ctx) == pytest.approx(100.0)


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_reader(name):
    ctx = ctx_of(tr.load_json(FIXTURE))
    assert read(name, ctx) == pytest.approx(SPAN_METRICS[name])


def test_metrics_without_a_reader_are_read_from_the_gaps_by_name():
    # dispatch, source.poll and sink.write have no metric of their own: the
    # ledger's breakdown.idle_gaps names them (innermost event wins, so the
    # profiler's own events keep their names there)
    trace = tr.load_json(FIXTURE)
    by_name, idle = tr.attribute_gaps(trace, "/device:TPU:0", 0, 1000)
    assert idle == 1000 - 200 - 100           # busy [420,620) and [700,800)
    assert by_name["flink_tpu.source.poll"] == 10          # [40,50)
    assert by_name["flink_tpu.dispatch"] == 5              # [400,405)
    assert "flink_tpu.sink.write" not in by_name           # device busy then
    assert by_name[tr.UNATTRIBUTED] == 220                 # the dark time
    assert sum(by_name.values()) == idle


def test_a_program_without_the_stage_clock_gives_nothing_to_read():
    """The parent of the PR that brought the spans: no `flink_tpu.*` event.
    Every span reader returns None and none raises."""
    trace = tr.load_json(FIXTURE)
    host = trace.planes[tr.HOST_PLANE]
    for line in host:
        host[line] = [e for e in host[line]
                      if not e[0].startswith(span_lib.PROGRAM)]
    ctx = ctx_of(trace)
    for name in SPAN_METRICS:
        assert read(name, ctx) is None


def test_every_new_metric_is_declared_with_its_cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in SPAN_METRICS:
        m = declared[name]
        assert (m["source"], m["moves"], m["unit"], m["better"]) == \
            ("program_span", "events_per_s", "%", "lower")
        want = (["ysb_hostkeyed_catchup"] if name.startswith("key_lookup")
                else cells)
        assert m["workloads"] == want
