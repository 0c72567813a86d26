"""The benchmark's source and sink, and the phases of one run.

One thread: the job's own. `env.execute()` polls the reader and pushes into
the sink synchronously, so the reader is where the run's phases turn over:

  WARM     batches handed over as fast as the job takes them until the sink
           has seen `min_windows` window fires and `min_batches` batches have
           gone in: every program of the window is compiled and dispatched.
  MEASURE  the timed window. `closed` traffic hands the next batch at once
           (a backlog replay); `open` traffic hands a batch only when its last
           event is due on the fixed schedule `t0 + i / rate`, and records
           how late it was.
  DRAIN    after `--seconds`: the stream goes on (uncounted) until the sink
           holds every window a counted event belongs to; that arrival ends
           the timed window. A window that never arrives ends it at the
           time-out, and is reported missing.
  END      the reader returns None, the job fires what is left and ends;
           those rows are compared with the reference like all others.

The reader and the sink keep their own clocks (`time.perf_counter`) and write
`jax.profiler.TraceAnnotation`s so the device trace can attribute idle gaps
to them; they touch nothing of the program but its Source / Sink interfaces.
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.stream import T0_MS, Cycle

WARM, MEASURE, DRAIN, END = "warm", "measure", "drain", "end"


class RunState:
    """What reader and sink share: the phase, the clocks, the tallies."""

    def __init__(self, cycle: Cycle, traffic: Dict, window: Dict,
                 seconds: float, batch: int, annotate=None,
                 on_measure_start: Optional[Callable] = None,
                 on_tick: Optional[Callable] = None,
                 on_window_end: Optional[Callable] = None):
        self.cycle, self.traffic, self.window = cycle, traffic, window
        self.seconds, self.batch = float(seconds), int(batch)
        self.annotate = annotate or (lambda _name: contextlib.nullcontext())
        self.on_measure_start = on_measure_start
        self.on_tick = on_tick            # called each poll of MEASURE/DRAIN
        self.on_window_end = on_window_end
        self.handed_at: List[float] = []  # perf_counter of each timed hand-over
        self.phase = WARM
        self.open_loop = traffic["loop"] == "open"
        self.rate = float(traffic.get("rate_events_per_s") or 0.0)
        self.drain_timeout_s = float(traffic.get("drain_timeout_s", 60.0))
        # stream position
        self.batches = 0                  # batches handed over, all phases
        self.events = 0                   # events handed over, all phases
        self.pos, self.lap = 0, 0
        # measured phase
        self.t_start = self.t_end = None  # perf_counter
        self.events_at_start = 0
        self.counted_events = 0           # handed over within --seconds
        self.target_end_ms = None         # last window end they belong to
        self.missing_target = False
        self.poll_s = 0.0                 # time inside poll_batch, MEASURE+DRAIN
        self.lag_s: List[float] = []      # open loop: hand-over minus due, per batch
        # sink side
        self.sink_s = 0.0                 # time inside write_batch, MEASURE+DRAIN
        self.arrivals: List[tuple] = []   # (perf_counter, phase, n_rows, ends_ms)
        self.rows: List[tuple] = []       # (values as written, ts i64)
        self.max_end_ms = -1              # newest window end at the sink
        self.windows_seen = 0
        self.rows_in_window = 0
        self.closed_at = None
        # per-second log (perf_counter second, events, rows)
        self.per_second: List[tuple] = []
        self._next_log = None

    # -- schedule -------------------------------------------------------
    def creation_ms(self, events_handed: int) -> float:
        """Event time at which the `events_handed`-th event of the run is
        created (lap-aware, before jitter)."""
        return T0_MS + events_handed * 1000.0 / self.cycle.events_per_s

    def due_s(self, end_ms: float) -> float:
        """Open loop: the wall time (perf_counter) at which event time
        `end_ms` is reached on the generator's schedule."""
        ms0 = self.creation_ms(self.events_at_start)
        return self.t_start + (end_ms - ms0) / 1000.0 * (
            self.cycle.events_per_s / self.rate)

    def log_tick(self, now: float) -> None:
        if self._next_log is None:
            self._next_log = now + 1.0
        while now >= self._next_log:
            self.per_second.append(
                (round(self._next_log - self.t_start, 3),
                 self.events - self.events_at_start, self.rows_in_window))
            self._next_log += 1.0

    def emission_latencies_ms(self) -> np.ndarray:
        """One latency per result row of the window: sink arrival minus the
        creation time of the last event that could contribute (the window's end
        on the generator's schedule). The sample is every window that ends inside
        the measured schedule; rows of a window that never arrived are missing
        any limit: they get the time the run gave up waiting."""
        ms0 = self.creation_ms(self.events_at_start)
        ms1 = ms0 + self.seconds * 1000.0 * (self.rate / self.cycle.events_per_s)
        slide = self.window["slide_ms"]
        lat: List[np.ndarray] = []
        seen = set()
        for (t, _phase, _n, ends), (_values, ts) in zip(self.arrivals, self.rows):
            for end in ends:
                if ms0 < end <= ms1:
                    n_rows = int((ts == end - 1).sum())
                    lat.append(np.full(n_rows, (t - self.due_s(float(end))) * 1000.0))
                    seen.add(int(end))
        first = (int(ms0) // slide + 1) * slide
        rows_per_fire = max((len(a) for a in lat), default=1)
        for end in range(first, int(ms1) + 1, slide):
            if end not in seen:
                lat.append(np.full(
                    rows_per_fire, (self.t_end - self.due_s(float(end))) * 1000.0))
        return np.concatenate(lat) if lat else np.empty(0)


def unpack_rows(rows: List[tuple]) -> List[tuple]:
    """The sink's batches of (key, count) tuples as (keys i64, counts i64, ts)
    columns, for the comparison with the reference. Lets each batch of
    tuples go as soon as it is unpacked; the timestamps stay."""
    out = []
    for i, (values, ts) in enumerate(rows):
        kv = np.fromiter(itertools.chain.from_iterable(values.tolist()),
                         dtype=np.int64, count=2 * len(values)).reshape(-1, 2)
        out.append((kv[:, 0], kv[:, 1], ts))
        rows[i] = (None, ts)
    return out


def make_source(state: RunState):
    from flink_tpu.connectors.source import (
        Batch, Source, SourceReader, SourceSplit, SplitEnumerator)

    class Reader(SourceReader):
        def add_split(self, split) -> None:
            pass

        def poll_batch(self, max_records: int) -> Optional[Batch]:
            st = state
            if max_records != st.batch:
                raise RuntimeError(
                    f"the job polls {max_records} records, the cycle was "
                    f"built for batches of {st.batch}")
            if st.phase == WARM:
                return self._warm()
            if st.on_tick is not None:
                st.on_tick(time.perf_counter() - st.t_start)
            t_in = time.perf_counter()
            with st.annotate("benchmark.poll_batch"):
                out = self._timed(t_in)
            st.poll_s += time.perf_counter() - t_in
            if out is not None:
                st.handed_at.append(t_in)
            return out

        def _warm(self) -> Batch:
            st = state
            warm = st.traffic["warm"]
            if (st.batches >= warm["min_batches"]
                    and st.windows_seen >= warm["min_windows"]):
                # the measured phase starts here: set-up garbage is not
                # walked inside it; the collector stays on for the job's own
                gc.collect()
                gc.freeze()
                if st.on_measure_start is not None:
                    st.on_measure_start()
                st.phase = MEASURE
                st.events_at_start = st.events
                st.t_start = time.perf_counter()
                return self.poll_batch(st.batch)
            return self._hand_over()

        def _timed(self, now: float) -> Optional[Batch]:
            st = state
            st.log_tick(now)
            if st.phase == MEASURE and now - st.t_start >= st.seconds:
                st.phase = DRAIN
                st.counted_events = st.events - st.events_at_start
                w = st.window
                last_ms = int(st.creation_ms(st.events)) - 1
                j_last = last_ms // w["slide_ms"]
                if st.open_loop:
                    # latency is sampled over the windows that END inside
                    # the measured schedule; wait for the last of those
                    st.target_end_ms = j_last * w["slide_ms"]
                else:
                    # the last window a counted event belongs to
                    st.target_end_ms = j_last * w["slide_ms"] + w["size_ms"]
            if st.phase == DRAIN:
                if st.max_end_ms >= st.target_end_ms:
                    return self._finish()
                if now - st.t_start - st.seconds > st.drain_timeout_s:
                    st.missing_target = True
                    st.t_end = now
                    return self._finish()
            if st.phase == END:
                return None
            if st.open_loop:
                # the batch is due when its LAST event is created
                n_after = st.events - st.events_at_start + st.batch
                due = st.t_start + n_after / st.rate
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                st.lag_s.append(time.perf_counter() - due)
            return self._hand_over()

        def _finish(self) -> None:
            st = state
            if st.t_end is None:
                # the arrival that completed the target ended the window
                st.t_end = next(
                    (t for t, _p, _n, ends in st.arrivals
                     if t >= st.t_start and len(ends)
                     and ends.max() >= st.target_end_ms),
                    time.perf_counter())
            st.phase = END
            if st.on_window_end is not None:
                st.on_window_end()
            return None

        def _hand_over(self) -> Batch:
            st = state
            c = st.cycle
            p, b = st.pos, st.batch
            ts = c.ts[p:p + b] + st.lap * c.cycle_ms
            vals = c.values[p:p + b]
            p += b
            if p >= c.events:
                p -= c.events
                st.lap += 1
            st.pos = p
            st.batches += 1
            st.events += b
            return Batch(vals, ts)

    class ReplaySource(Source):
        boundedness = "BOUNDED"

        def create_enumerator(self):
            return SplitEnumerator([SourceSplit("replay-0", {})])

        def create_reader(self):
            return Reader()

    return ReplaySource()


def make_sink(state: RunState):
    from flink_tpu.connectors.sink import Sink, SinkWriter

    class Writer(SinkWriter):
        def write_batch(self, values, timestamps=None) -> None:
            st = state
            t_in = time.perf_counter()
            timed = st.phase in (MEASURE, DRAIN)
            with st.annotate("benchmark.sink_write"):
                ts = np.asarray(timestamps, np.int64)
                # the cheapest sink there is: keep the batch, look only at
                # which windows it closes (rows of one fire are contiguous);
                # the (key, value) tuples are unpacked after the job
                st.rows.append((values, ts))
                if len(ts):
                    last = np.flatnonzero(ts[1:] != ts[:-1])
                    ends = np.unique(np.append(ts[last], ts[-1])) + 1
                    st.windows_seen += len(ends)
                    st.max_end_ms = max(st.max_end_ms, int(ends[-1]))
                else:
                    ends = ts
                if timed:
                    st.rows_in_window += len(ts)
            t_out = time.perf_counter()
            # a row has arrived when the sink has taken it: the end of the write
            st.arrivals.append((t_out, st.phase, len(ts), ends))
            if timed:
                st.sink_s += t_out - t_in

        def close(self) -> None:
            state.closed_at = time.perf_counter()

    class WindowSink(Sink):
        def create_writer(self):
            return Writer()

    return WindowSink()
