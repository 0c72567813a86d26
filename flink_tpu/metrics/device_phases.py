"""The device program's phases, and a capture's time under each of them.

The window programs mark their phases with `jax.named_scope`: metadata of the
compiled program that costs a dispatch nothing and that a `jax.profiler`
capture hands back with every device op. `PHASES` spells the five top-level
names once; the programs take their scope names from here, and `phase_table`
reads a capture back into ms per phase on the device's own clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: top-level scopes of a window program's scan step
PHASES = ("prologue", "exchange", "ingest", "fire", "purge")
PROLOGUE, EXCHANGE, INGEST, FIRE, PURGE = PHASES
#: nested in `prologue` (`TracedPrologue.apply`): one `t<i>.<kind>` per
#: transform of the chain, then the key selector with the lane index built
#: from it, the value function, and the key-range reduction the scan carries
KEY, VALUE, BOUNDS = "key", "value", "bounds"
#: nested in `ingest` (`ops/superscan.make_superscan_step`): the step's
#: [K, NSB] partial histogram, that partial folded into the ring's columns,
#: a per-record scatter into the ring: the count's
HIST, FOLD, SCATTER = "hist", "fold", "scatter"
#: the same three pieces of work done for the aggregate's VALUE fields (a
#: sum's weighted histogram and its fold, a min's scatter), each under a
#: name of its own, so that a table tells what a value column costs from
#: what counting costs; absent from a count-only program
_OF_VALUES = ".value"
HIST_VALUE, FOLD_VALUE, SCATTER_VALUE = (
    name + _OF_VALUES for name in (HIST, FOLD, SCATTER))
_NESTED = {
    PROLOGUE: re.compile(r"t\d+\.(map|filter|map_ts)|key|value|bounds"),
    INGEST: re.compile(r"(hist|fold|scatter)(\.value)?"),
}


def transform_scope(i: int, kind: str) -> str:
    """The nested scope of the chain's `i`-th transform: `t0.filter`."""
    return f"t{i}.{kind}"


def phase_of(op_name: str) -> Tuple[Optional[str], Optional[str]]:
    """(phase, nested name) of an op from the `op_name` the compiler keeps
    for it (`jit(run)/while/body/closed_call/ingest/hist/dot_general`): the
    first path component that is one of `PHASES`, and the component after it
    where that is one of the phase's nested names. The VALUE fields' share of
    a piece whose one op serves both kinds of field (the histogram's
    conditional: `ingest/hist/cond/branch_1_fun/hist.value/...`) is named
    deeper, and goes by that name."""
    parts = (op_name or "").split("/")
    for i, part in enumerate(parts):
        if part in PHASES:
            nested = _NESTED.get(part)
            sub = parts[i + 1] if i + 1 < len(parts) else ""
            if not (nested and nested.fullmatch(sub)):
                return part, None
            deeper = sub + _OF_VALUES
            if nested.fullmatch(deeper) and deeper in parts[i + 2:]:
                sub = deeper
            return part, sub
    return None, None


# -- a capture's phase table -------------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NO_OP = "(no op)"       # time of a module execution during which no op ran


def _cut(modules, ops, scopes, top: int) -> Dict[str, Dict]:
    """One device plane. `modules`: (name, start, end) of the executions to
    cut; `ops`: (start, end, event name) of the plane's op events, both in ns
    on one clock; `scopes`: {module name: {instruction: op_name}}."""
    ops = sorted(ops, key=lambda e: (e[0], -e[1]))
    starts = [e[0] for e in ops]
    out: Dict[str, Dict] = {}
    named: Dict[str, Dict[str, tuple]] = {}
    for name, m_lo, m_hi in modules:
        op_names = scopes.get(name, {})
        # event name -> (phase, nested, op, op_name), once per program
        known = named.setdefault(name, {})
        acc = out.setdefault(name, {"executions": 0, "ns": 0, "self": {}})
        acc["executions"] += 1
        acc["ns"] += m_hi - m_lo
        self_ns = acc["self"]       # (phase, nested, op, op_name) -> ns
        stack: List[list] = []      # [end, (phase, nested, op, op_name), self]
        covered = 0

        def close(upto):
            while stack and stack[-1][0] <= upto:
                _end, key, own = stack.pop()
                self_ns[key] = self_ns.get(key, 0) + own

        for a, b, event in ops[bisect.bisect_left(starts, m_lo):
                               bisect.bisect_left(starts, m_hi)]:
            close(a)
            key = known.get(event)
            if key is None:
                # an event's name is its instruction's text: `%copy.11 = ...`
                op = event.split(" = ")[0].lstrip("%")
                op_name = op_names.get(op, "")
                key = known[event] = (*phase_of(op_name), op, op_name)
            # an op ends with what holds it: its module, its enclosing op
            b = min(b, stack[-1][0] if stack else m_hi)
            if stack:
                stack[-1][2] -= b - a
                if key[0] is None:  # a compiler-made op inside a scoped one
                    key = (*stack[-1][1][:2], *key[2:])
            else:
                covered += b - a
            stack.append([b, key, b - a])
        close(m_hi)
        key = (None, None, NO_OP, "")
        self_ns[key] = self_ns.get(key, 0) + (m_hi - m_lo) - covered
    return {name: _module_table(acc, top) for name, acc in out.items()}


def _module_table(acc: Dict, top: int) -> Dict:
    phases: Dict[str, float] = {}
    sub: Dict[str, float] = {}
    by_op: Dict[Optional[str], Dict[Tuple[str, str], float]] = {}
    for (phase, nested, op, op_name), ns in acc["self"].items():
        if phase is not None:
            phases[phase] = phases.get(phase, 0.0) + ns / 1e6
        if nested is not None:
            row = f"{phase}/{nested}"
            sub[row] = sub.get(row, 0.0) + ns / 1e6
        ops = by_op.setdefault(phase, {})
        ops[(op, op_name)] = ops.get((op, op_name), 0.0) + ns / 1e6

    def longest(phase):
        ranked = sorted(by_op.get(phase, {}).items(), key=lambda kv: -kv[1])
        return [[op, ms, op_name] for (op, op_name), ms in ranked[:top] if ms]

    return {
        "executions": acc["executions"], "ms": acc["ns"] / 1e6,
        "phases": {p: phases[p] for p in PHASES if p in phases},
        "sub": dict(sorted(sub.items())),
        "other": sum(by_op.get(None, {}).values()),
        "other_ops": [row[:2] for row in longest(None)],
        "phase_ops": {p: longest(p) for p in PHASES if p in phases},
    }


# -- the scopes in a capture -------------------------------------------------
# A TPU capture's op events carry no scope that `jax.profiler.ProfileData`
# shows: an event's own stats are its device offset and duration, its name is
# the instruction's text without metadata, and `tf_op`, a stat of the op's
# XEventMetadata, is not handed out (and the `while` ops have none). The
# compiled module holds it: the profiler stores one per program in the
# `/host:metadata` plane, as the `Hlo Proto` stat of an event metadata named
# like the program's `XLA Modules` events (`jit_run_x(<program id>)`), every
# instruction with its `op_name`. Read off the protobuf wire by field number:
#   XSpace.planes 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5 (maps:
#   key 1, value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
#   XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
#   HloModuleProto.computations 3; HloComputationProto.instructions 2;
#   HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2.

METADATA_PLANE, HLO_PROTO = "/host:metadata", "Hlo Proto"


def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        else:
            size = {1: 8, 5: 4}.get(kind)
            if size is None:            # 2: length-delimited
                size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        yield tag >> 3, value


def _sub(buf, number: int):
    """The values of one message's fields numbered `number`."""
    return (value for field, value in _fields(buf) if field == number)


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def op_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """{program: {instruction: op_name}} of every program a `.xplane.pb`
    stores the compiled module of, for the instructions that have one."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):
        if METADATA_PLANE not in map(_text, _sub(plane, 2)):
            continue
        stat_names = {}
        for entry in map(dict, map(_fields, _sub(plane, 5))):
            stat_names[entry.get(1)] = "".join(
                map(_text, _sub(entry.get(2, b""), 2)))
        for entry in map(dict, map(_fields, _sub(plane, 4))):
            program = "".join(map(_text, _sub(entry.get(2, b""), 2)))
            scopes = out.setdefault(program, {})
            for stat in map(dict, map(_fields, _sub(entry.get(2, b""), 5))):
                if stat_names.get(stat.get(1)) != HLO_PROTO or 6 not in stat:
                    continue
                for module in _sub(stat[6], 1):
                    for computation in _sub(module, 3):
                        for fields in map(dict, map(
                                _fields, _sub(computation, 2))):
                            op_name = "".join(
                                map(_text, _sub(fields.get(7, b""), 2)))
                            if op_name:
                                scopes[_text(fields.get(1, b""))] = op_name
    return out


def capture_file(capture: str) -> str:
    """A `.xplane.pb`, or the newest one under the directory a capture was
    written to (`observability.profiler.dir`, the REST capture)."""
    found = [capture] if os.path.isfile(capture) else glob.glob(
        os.path.join(capture, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb at {capture}")
    return max(found, key=os.path.getmtime)


def phase_table(capture: str, programs: Optional[Sequence[str]] = None,
                planes: Optional[Sequence[str]] = None,
                window: Optional[Tuple[float, float]] = None,
                top: int = 5) -> Dict[str, Dict[str, Dict]]:
    """{device plane: {module: {"executions", "ms", "phases": {phase: ms},
    "sub": {"prologue/t1.map": ms}, "other": ms, "other_ops": [[op, ms]],
    "phase_ops": {phase: [[op, ms, op_name]]}}}} of a capture, totals in ms.

    Per device plane the `XLA Ops` events inside an execution of a module
    are cut into innermost segments: an op's SELF time is what the ops
    nested in it do not cover. A segment goes to its op's phase; an op
    without a scope inherits the nearest enclosing op's (a compiler-made
    copy inside ingest's loop is ingest's); time under no scoped op and time
    of the module under no op at all is `other`, whose longest ops
    `other_ops` names. So sum(phases) + other == ms.

    `programs`: keep modules whose name contains one of these; `planes`:
    keep these device planes; `window`: keep executions that start inside
    [lo, hi) ns of the capture's clock."""
    import jax

    path = capture_file(capture)
    data = jax.profiler.ProfileData.from_file(path)
    scopes = None
    out: Dict[str, Dict[str, Dict]] = {}
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name) or (
                planes is not None and plane.name not in planes):
            continue
        lines = {line.name: line for line in plane.lines}
        if MODULES_LINE not in lines or OPS_LINE not in lines:
            continue
        modules = [
            (e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in lines[MODULES_LINE].events
            if (programs is None or any(p in e.name for p in programs))
            and (window is None or window[0] <= e.start_ns < window[1])]
        if not modules:
            continue
        scopes = op_scopes(path) if scopes is None else scopes
        lo = min(m[1] for m in modules)
        hi = max(m[2] for m in modules)
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
               for e in lines[OPS_LINE].events if lo <= e.start_ns < hi]
        out[plane.name] = _cut(modules, ops, scopes, top)
    return out


def _per(value, n: int):
    """`value` with every float in it divided by `n`."""
    if isinstance(value, dict):
        return {k: _per(v, n) for k, v in value.items()}
    if isinstance(value, list):
        return [_per(v, n) for v in value]
    return value / n if isinstance(value, float) else value


def per_execution(table: Dict[str, Dict[str, Dict]]) -> Dict[str, Any]:
    """The busiest device plane of a `phase_table` in ms per execution of
    each module: {"plane": name, "programs": {module: {...}}}, the form the
    command prints and `/jobs/:id/device` reports as `phaseMs`."""
    if not table:
        return {}
    plane = max(table, key=lambda p: sum(m["ms"] for m in table[p].values()))
    return {"plane": plane, "programs": {
        name: _per(m, m["executions"]) for name, m in table[plane].items()}}


def render(report: Dict[str, Any], ops: bool = False) -> str:
    """`per_execution`'s report as lines of text, a program after another."""
    lines = []
    for name, m in report.get("programs", {}).items():
        lines.append(f"{name} on {report['plane']}: {m['executions']} "
                     f"executions, {m['ms']:.3f} ms each")
        for phase, ms in m["phases"].items():
            lines.append(f"  {phase:<10}{ms:9.3f} ms {100 * ms / m['ms']:5.1f} %")
            lines += [f"    {row.split('/', 1)[1]:<12}{sub_ms:9.3f}"
                      for row, sub_ms in m["sub"].items()
                      if row.startswith(phase + "/")]
            if ops:
                lines += [f"    . {op:<36}{op_ms:9.3f}  {op_name}"
                          for op, op_ms, op_name in m["phase_ops"][phase]]
        lines.append(f"  {'other':<10}{m['other']:9.3f} ms "
                     f"{100 * m['other'] / m['ms']:5.1f} %")
        lines += [f"    . {op:<36}{op_ms:9.3f}" for op, op_ms in m["other_ops"]]
    return "\n".join(lines) or "no window program on a device plane"


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="ms per execution of each device program of a "
                    "jax.profiler capture under each of its phases")
    ap.add_argument("capture", help="a .xplane.pb, or a capture's directory")
    ap.add_argument("--programs", nargs="*", default=None,
                    help="keep modules whose name contains one of these")
    ap.add_argument("--ops", action="store_true",
                    help="each phase's longest ops with their op_name")
    args = ap.parse_args(argv)
    print(render(per_execution(phase_table(args.capture, args.programs)),
                 ops=args.ops))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
