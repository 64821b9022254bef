"""Summed device time, in milliseconds, of the trace's events of one kind
(`modules`: XLA modules by jit name; `ops`: single ops as
`<module>/<op name>`, self time) whose name matches a regular expression
and which began while the host was in the harness annotation ``phase``,
divided by how often that annotation ran inside the traced window."""

import re


def matching_seconds(trace, kind, pattern, phase):
    rx = re.compile(pattern)
    events = trace["by_phase"].get(phase, {}).get(kind, {})
    return sum(s for name, s in events.items() if rx.search(name))


def read(context, kind, pattern, phase):
    trace = context["trace"]
    if not trace or not trace["devices"]:
        return None
    n = trace["phases"].get(phase, {}).get("count", 0)
    seconds = matching_seconds(trace, kind, pattern, phase)
    if not n or not seconds:
        return None
    return 1e3 * seconds / n
