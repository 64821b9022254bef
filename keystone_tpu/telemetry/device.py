"""Reading a `jax.profiler` trace by the program's own names.

    python -m keystone_tpu.telemetry device <trace dir or .xplane.pb>

Under a profiler session every live `telemetry.span` is an event
``ks:<layer>:<name>`` on a thread line of the host plane, and every op
traced under a `jax.named_scope("ks.<label>")` carries the scope in its
name stack, on the device's clock. On a TPU v5e (jax 0.9) the name stack
is the stat ``tf_op`` of the op's *event metadata*, which
`jax.profiler.ProfileData` does not hand out (it gives an event's own
stats: offset and duration), so `op_scopes` reads that one table from
the file's protobuf wire format itself. From those this prints:

    spans    per ``ks:`` span name (and per name under any further
             ``--prefix``, such as the benchmark's ``bench:``): runs, host
             seconds, the device busy seconds and the XLA module launches
             that began under it
    scopes   device seconds (self time) per chain of ``ks.`` scopes, with
             the ops that took most of each
    modules  launches and device seconds per XLA module (jit name)
    idle     the device's idle seconds (every gap between device ops, a
             mean over the chips) summed by the innermost ``ks:`` span
             open at each gap's middle, or ``no span``: the table
             ``idle_by_span_s``, which sums to ``device_idle_s``
    gaps     the longest idle gaps, each named the same way; a gap that
             every chip of a mesh sees is listed once, with the number
             of planes that saw it

`reduce_planes` works on plain data, so a test hands it planes made by
hand. The benchmark's own reduction is `benchmark/trace_reduce.py`,
which reads ``bench:`` annotations only; this reader is the operator's.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import sys
from typing import Dict, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "ks:"
SCOPE = re.compile(r"ks\.[^/:]+")
#: the stat of a device op's event metadata that holds its name stack
SCOPE_STAT = "tf_op"
NO_SPAN = "no span"
NO_SCOPE = "no scope"


class Event(NamedTuple):
    name: str
    start: float  # ns
    end: float    # ns
    scope: str    # the op's name stack on a device line, else ""


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane name: {line name: [Event]}} of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        scopes = op_scopes(f.read())
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        by_op = scopes.get(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            of_op = by_op if line.name == OPS_LINE else {}
            for ev in line.events:
                start = float(ev.start_ns)
                events.append(Event(
                    ev.name, start, start + float(ev.duration_ns),
                    of_op.get(ev.name, "")))
    return planes


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            length, i = _varint(buf, i)
            value, i = buf[i:i + length], i + length
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value, i = buf[i:i + width], i + width
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane file")
        yield key >> 3, value


def op_scopes(data: bytes) -> Dict[str, Dict[str, str]]:
    """{device plane name: {op event name: its `SCOPE_STAT`}} from the
    bytes of an ``.xplane.pb``. Field numbers of `xplane.proto`
    (tsl/profiler): XSpace.planes 1; XPlane.name 2, .event_metadata 4,
    .stat_metadata 5 (maps: key 1, value 2); XEventMetadata.name 2,
    .stats 5; XStatMetadata.name 2; XStat.metadata_id 1, .str_value 5,
    .ref_value 7 (the id of a stat metadata whose name is the value)."""
    out: Dict[str, Dict[str, str]] = {}
    for number, plane in _fields(memoryview(data)):
        if number != 1:
            continue
        name, events, stat_names = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(dict(_fields(value))[2])
            elif number == 5:
                entry = dict(_fields(value))
                stat = dict(_fields(entry[2]))
                stat_names[entry[1]] = bytes(stat.get(2, b"")).decode()
        if not DEVICE_PLANE.match(name):
            continue
        scope_ids = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        by_op = out.setdefault(name, {})
        for metadata in events:
            op, scope = "", ""
            for number, value in _fields(metadata):
                if number == 2:
                    op = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in scope_ids:
                        scope = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if op and scope:
                by_op.setdefault(op, scope)
    return out


def find_xplane(path: str) -> str:
    """``path`` itself, or the one ``.xplane.pb`` under a trace directory."""
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise ValueError(f"{path}: expected one .xplane.pb, found {found}")
    return found[0]


def _union(intervals) -> List[List[float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _overlap(merged, lo: float, hi: float) -> float:
    return sum(min(e, hi) - max(s, lo) for s, e in merged
               if min(e, hi) > max(s, lo))


def _self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """(event, self ns): an op's time less the ops nested in it (a
    ``while`` holds its body's ops)."""
    events = sorted(events, key=lambda ev: (ev.start, -ev.end))
    own = [ev.end - ev.start for ev in events]
    open_: List[int] = []
    for i, ev in enumerate(events):
        while open_ and events[open_[-1]].end <= ev.start:
            open_.pop()
        if open_:
            own[open_[-1]] -= ev.end - ev.start
        open_.append(i)
    return list(zip(events, own))


def _op_name(text: str) -> str:
    """An op event's name is its HLO text: keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def scope_chain(stack: str) -> str:
    """``jit(f)/ks.A/ks.b/mul`` -> ``ks.A/ks.b``."""
    return "/".join(SCOPE.findall(stack)) or NO_SCOPE


def reduce_planes(planes, prefixes: Sequence[str] = (SPAN_PREFIX,),
                  gaps: int = 8, ops_per_scope: int = 3) -> dict:
    """The tables of the module docstring, as plain data (seconds)."""
    spans = [ev for name, lines in planes.items()
             if not DEVICE_PLANE.match(name)
             for events in lines.values() for ev in events
             if ev.name.startswith(tuple(prefixes))]
    devices = {name: lines for name, lines in planes.items()
               if DEVICE_PLANE.match(name) and lines.get(OPS_LINE)}
    n_dev = max(len(devices), 1)
    per_span: Dict[str, dict] = collections.defaultdict(
        lambda: {"runs": 0, "host_s": 0.0, "device_busy_s": 0.0,
                 "launches": 0})
    for ev in spans:
        per_span[ev.name]["runs"] += 1
        per_span[ev.name]["host_s"] += (ev.end - ev.start) * 1e-9
    scopes: Dict[str, dict] = collections.defaultdict(
        lambda: {"device_s": 0.0, "ops": collections.Counter()})
    modules: Dict[str, dict] = collections.defaultdict(
        lambda: {"launches": 0, "device_s": 0.0})
    gap_list: List[Tuple[float, float]] = []  # (start, end), every plane
    busy = 0.0
    for lines in devices.values():
        merged = _union((ev.start, ev.end) for ev in lines[OPS_LINE])
        busy += sum(e - s for s, e in merged)
        launched = sorted(lines.get(MODULES_LINE, []),
                          key=lambda ev: ev.start)
        for ev in spans:
            entry = per_span[ev.name]
            entry["device_busy_s"] += _overlap(
                merged, ev.start, ev.end) * 1e-9 / n_dev
            entry["launches"] += sum(
                1 for m in launched if ev.start <= m.start < ev.end)
        for m in launched:
            name = re.sub(r"\(\d+\)$", "", m.name)
            modules[name]["launches"] += 1
            modules[name]["device_s"] += (m.end - m.start) * 1e-9 / n_dev
        for ev, own in _self_times(lines[OPS_LINE]):
            entry = scopes[scope_chain(ev.scope)]
            entry["device_s"] += own * 1e-9 / n_dev
            entry["ops"][_op_name(ev.name)] += own * 1e-9 / n_dev
        for (_, end), (start, _) in zip(merged, merged[1:]):
            gap_list.append((end, start))

    ks_spans = [ev for ev in spans if ev.name.startswith(SPAN_PREFIX)]
    gap_list.sort(key=lambda gap: gap[0] + gap[1])
    names = _spans_at(ks_spans, [(s + e) / 2 for s, e in gap_list])
    idle_by_span: Dict[str, float] = collections.defaultdict(float)
    for (start, end), name in zip(gap_list, names):
        idle_by_span[name] += (end - start) * 1e-9 / n_dev

    return {
        "devices": len(devices),
        "device_busy_s": busy * 1e-9 / n_dev,
        "device_idle_s": sum(e - s for s, e in gap_list) * 1e-9 / n_dev,
        "spans": dict(sorted(per_span.items(),
                             key=lambda kv: -kv[1]["host_s"])),
        "scopes": {
            name: {"device_s": entry["device_s"],
                   "ops": dict(entry["ops"].most_common(ops_per_scope))}
            for name, entry in sorted(scopes.items(),
                                      key=lambda kv: -kv[1]["device_s"])},
        "modules": dict(sorted(modules.items(),
                               key=lambda kv: -kv[1]["device_s"])),
        "idle_by_span_s": dict(sorted(idle_by_span.items(),
                                      key=lambda kv: -kv[1])),
        "gaps": [
            {"seconds": seconds, "span": name, "planes": planes,
             "under": _spans_over(ks_spans, start, end)}
            for seconds, name, planes, (start, end) in sorted(
                _gaps_seen_once(gap_list, names), reverse=True)[:gaps]],
    }


def _spans_at(ks_spans: Sequence[Event], times: Sequence[float]) -> List[str]:
    """For each of ``times`` (ascending) the innermost of ``ks_spans``
    open at it (the shortest; ties by name), or `NO_SPAN`: one sweep,
    since a trace has a gap between every two ops."""
    pending = sorted(ks_spans, key=lambda ev: ev.start, reverse=True)
    open_: List[Event] = []
    out = []
    for t in times:
        while pending and pending[-1].start <= t:
            open_.append(pending.pop())
        open_ = [ev for ev in open_ if t < ev.end]
        out.append(min((ev.end - ev.start, ev.name) for ev in open_)[1]
                   if open_ else NO_SPAN)
    return out


def _spans_over(ks_spans: Sequence[Event], start: float,
                end: float) -> Dict[str, float]:
    """The seconds of [start, end) under each innermost span, in the
    order the spans come: a long gap split by what the host was doing."""
    inside = [ev for ev in ks_spans if ev.start < end and start < ev.end]
    cuts = sorted({start, end, *(t for ev in inside
                                 for t in (ev.start, ev.end)
                                 if start < t < end)})
    out: Dict[str, float] = {}
    for lo, hi in zip(cuts, cuts[1:]):
        (name,) = _spans_at(inside, [(lo + hi) / 2])
        out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
    return out


def _gaps_seen_once(gap_list, names, recent: int = 8):
    """``gap_list`` (sorted by middle) with the gaps of different planes
    that hold each other's middles made one: the chips of a mesh wait
    for the host together. Gives (seconds, name, planes, the first
    plane's interval) a gap: the seconds are the mean of what its planes
    saw, the name the first's. Two gaps of one plane never hold each
    other's middles, so a group has a plane once."""
    groups: List[Tuple[str, List[Tuple[float, float]]]] = []
    for (start, end), name in zip(gap_list, names):
        middle = (start + end) / 2
        for _, members in reversed(groups[-recent:]):
            first_start, first_end = members[0]
            if first_start <= middle < first_end \
                    and start <= (first_start + first_end) / 2 < end:
                members.append((start, end))
                break
        else:
            groups.append((name, [(start, end)]))
    return [(sum(e - s for s, e in members) * 1e-9 / len(members), name,
             len(members), members[0]) for name, members in groups]


def render(table: dict, top: int = 20) -> str:
    """The tables as text: numbers first, so no name is cut short."""
    out = [f"devices {table['devices']}, busy "
           f"{table['device_busy_s']:.6f} s", "",
           f"{'runs':>6}{'host s':>12}{'device s':>12}{'launches':>9}  span"]
    for name, e in list(table["spans"].items())[:top]:
        out.append(f"{e['runs']:>6}{e['host_s']:>12.6f}"
                   f"{e['device_busy_s']:>12.6f}{e['launches']:>9}  {name}")
    out += ["", f"{'device s':>12}  scope (its ops)"]
    for name, e in list(table["scopes"].items())[:top]:
        ops = ", ".join(f"{op} {s:.6f}" for op, s in e["ops"].items())
        out.append(f"{e['device_s']:>12.6f}  {name} ({ops})")
    out += ["", f"{'launches':>9}{'device s':>12}  module"]
    for name, e in list(table["modules"].items())[:top]:
        out.append(f"{e['launches']:>9}{e['device_s']:>12.6f}  {name}")
    out += ["", f"device idle {table['device_idle_s']:.6f} s, by the "
            "innermost span at each gap's middle (idle_by_span_s)"]
    for name, seconds in list(table["idle_by_span_s"].items())[:top]:
        out.append(f"{seconds:>12.6f}  {name}")
    out += ["", "longest idle gaps (planes that saw each)"]
    for gap in table["gaps"]:
        out.append(f"  {gap['seconds'] * 1e3:10.3f} ms  {gap['planes']:>2}  "
                   f"{gap['span']}")
        if len(gap["under"]) > 1:
            out += [f"  {seconds * 1e3:18.3f} ms  under {name}"
                    for name, seconds in gap["under"].items()]
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m keystone_tpu.telemetry device",
        description=__doc__.split("\n\n")[0])
    p.add_argument("trace", help="a jax.profiler trace directory or an "
                                 ".xplane.pb file")
    p.add_argument("--prefix", action="append", default=[],
                   help="also tabulate host annotations whose names start "
                        "so (the benchmark's are 'bench:')")
    p.add_argument("--top", type=int, default=20, help="rows per table")
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)
    try:
        table = reduce_planes(read_planes(find_xplane(args.trace)),
                              prefixes=[SPAN_PREFIX, *args.prefix])
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(table) if args.as_json else render(table, args.top))
    return 0
