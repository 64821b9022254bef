"""A kernel's or a solver's share of its roofline, in percent: the least
time the chip could take for the work of one run of the harness
annotation ``phase`` (the larger of operations over peak FLOP/s and
bytes over peak bytes/s, both from `benchmark/costs/<cost>.py` and the
configuration's sizes) over the device time the trace shows for the
events that do that work (`device_ms_matching`). The run's `reader`
line says which of the two peaks bounds it."""

from .. import files
from . import device_ms_matching


def read(context, cost, kind, pattern, phase):
    ms = device_ms_matching.read(context, kind, pattern, phase)
    if ms is None:
        return None
    work = files.module("costs", cost).cost(context["stats"]["sizes"])
    peaks = context["peaks"]
    by_flops = work["flops"] / peaks["flops"]
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    context.setdefault("notes", {})[cost] = {
        "bound_by": "flops" if by_flops >= by_bytes else "bytes",
        "least_ms": 1e3 * max(by_flops, by_bytes), "device_ms": ms, **work}
    return 100.0 * 1e3 * max(by_flops, by_bytes) / ms
