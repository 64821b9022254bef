"""From a profiler trace (`.xplane.pb`, read with `jax.profiler.ProfileData`)
to the numbers the per-layer metrics read. One reduction for every PR.

What the trace of a v5e looks like (jax 0.9.0, looked at by hand in PR 24):
each chip is a plane `/device:TPU:<n>` with the lines `XLA Modules` (one event
per executed program, named `<jit name>(<fingerprint>)`) and `XLA Ops` (one
event per HLO op, named by the op's whole HLO text; a `while` holds its body's
ops nested inside it), besides `Async XLA Ops` (copies in flight, which overlap
the ops and are not counted). The harness's `TraceAnnotation`s are events named
`bench:<phase>` on a thread line of the plane `/host:CPU`, on the same clock.

    window_s        the harness annotation `bench:traced_window`
    busy_s          union of the op intervals inside the window, averaged over
                    the device planes that ran anything
    modules, ops    device seconds per module (jit name) and per op (self time:
                    an op's time less the ops nested in it), in all and by the
                    harness phase the host was in when the event began
    phases          per `bench:<phase>`: runs, host seconds, device busy seconds
    top_ops         the ten ops with most self time
    idle_gaps       the longest gaps between device ops, each named by the
                    harness phase the host was in at the gap's middle
"""

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION = "bench:"
WINDOW_PHASE = "traced_window"  # the annotation that spans the traced window
WINDOW = ANNOTATION + WINDOW_PHASE
OUTSIDE = "outside_annotations"


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _total(intervals):
    return sum(e - s for s, e in intervals)


def module_name(event_name):
    """`jit__bcd_epoch(1234)` -> `jit__bcd_epoch`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(hlo_text):
    """An op's HLO text -> its name, with the target of a custom call:
    `%closed_call.9 = f32[..] custom-call(..), custom_call_target="tpu_custom_call"`
    -> `closed_call.9 custom-call:tpu_custom_call` (a Mosaic kernel)."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', hlo_text)
    return f"{name} custom-call:{target.group(1)}" if target else name


def self_times(events):
    """[(name, start, end, self)] for events that may nest: an event's
    self time is its length less its direct children's."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    own = [e - s for _, s, e in events]
    open_ = []
    for i, (_, s, e) in enumerate(events):
        while open_ and events[open_[-1]][2] <= s:
            open_.pop()
        if open_:
            own[open_[-1]] -= e - s
        open_.append(i)
    return [(n, s, e, t) for (n, s, e), t in zip(events, own)]


def read_planes(path):
    """The trace as plain data: {plane name: {line name: [(event name,
    start ns, end ns), ...]}}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for event in line.events:
                start = float(event.start_ns)
                events.append((event.name, start,
                               start + float(event.duration_ns)))
    return planes


def reduce_file(path):
    return reduce_planes(read_planes(path))


def reduce_planes(planes, gaps=5, top=10):
    annotations = [
        ev for name, lines in planes.items() if not DEVICE_PLANE.match(name)
        for events in lines.values() for ev in events
        if ev[0].startswith(ANNOTATION)]
    windows = [ev for ev in annotations if ev[0] == WINDOW]
    if len(windows) != 1:
        raise ValueError(
            f"the trace holds {len(windows)} {WINDOW!r} annotations, not one")
    _, lo, hi = windows[0]
    phase_spans = collections.defaultdict(list)
    for name, start, end in annotations:
        if name != WINDOW:
            phase_spans[name[len(ANNOTATION):]].extend(
                _clip([(start, end)], lo, hi))

    def phase_at(t):
        inside = [(e - s, phase) for phase, spans in phase_spans.items()
                  for s, e in spans if s <= t < e]
        return min(inside)[1] if inside else OUTSIDE

    devices = {name: lines for name, lines in planes.items()
               if DEVICE_PLANE.match(name) and lines.get(OPS_LINE)}
    n_dev = max(len(devices), 1)
    ns = 1e-9 / n_dev
    busy = 0.0
    phase_busy = collections.Counter()
    by_phase = collections.defaultdict(
        lambda: {"modules": collections.Counter(), "ops": collections.Counter()})
    gap_list = []
    for lines in devices.values():
        merged = _union(_clip([(s, e) for _, s, e in lines[OPS_LINE]], lo, hi))
        busy += _total(merged)
        for phase, spans in phase_spans.items():
            for s, e in spans:
                phase_busy[phase] += _total(_clip(merged, s, e))
        modules = sorted((s, e, module_name(n))
                         for n, s, e in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in modules]
        for s, e, name in modules:
            if lo <= s < hi:
                by_phase[phase_at(s)]["modules"][name] += e - s
        for text, s, e, own in self_times(lines[OPS_LINE]):
            if not lo <= s < hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            module = modules[i][2] if i >= 0 and s < modules[i][1] else "?"
            by_phase[phase_at(s)]["ops"][f"{module}/{op_name(text)}"] += own
        edges = [lo] + [t for s, e in merged for t in (s, e)] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gap_list.append((e - s, (s + e) / 2))

    def summed(kind):
        total = collections.Counter()
        for entry in by_phase.values():
            total.update(entry[kind])
        return {k: v * ns for k, v in total.most_common()}

    idle_by_phase = collections.Counter()
    for length, middle in gap_list:
        idle_by_phase[phase_at(middle)] += length
    ops = summed("ops")
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * ns,
        "devices": len(devices),
        "modules": summed("modules"),
        "ops": ops,
        "by_phase": {
            phase: {kind: {k: v * ns for k, v in entry[kind].most_common()}
                    for kind in ("modules", "ops")}
            for phase, entry in by_phase.items()},
        "phases": {
            phase: {"count": len(spans), "host_s": _total(spans) * 1e-9,
                    "device_busy_s": phase_busy[phase] * ns}
            for phase, spans in phase_spans.items()},
        "top_ops": [[k, v] for k, v in list(ops.items())[:top]],
        "idle_gaps": [[phase_at(m), g * 1e-9]
                      for g, m in sorted(gap_list, reverse=True)[:gaps]],
        "idle_by_phase_s": {k: v * ns for k, v in idle_by_phase.most_common()},
    }
