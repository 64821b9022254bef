"""What the benchmark reads from the running program: its counters (by
delta), the peak device memory, and a phase annotation that the device
trace carries. Nothing here changes the program."""

import collections

import jax

from .trace_reduce import ANNOTATION


def counters_now():
    """Every counter of the program's registry, as name -> value."""
    from keystone_tpu.telemetry.metrics import registry

    return {name: c.value for name, c in list(registry().counters.items())}


class PhaseCounters:
    """Counter deltas summed per phase of the measured loop."""

    def __init__(self):
        self.by_phase = collections.defaultdict(collections.Counter)
        self._mark = counters_now()

    def mark(self):
        self._mark = counters_now()

    def close(self, phase):
        """Charge what moved since the last mark to ``phase``."""
        now = counters_now()
        for name, value in now.items():
            delta = value - self._mark.get(name, 0.0)
            if delta:
                self.by_phase[phase][name] += delta
        self._mark = now

    def total(self, name):
        return sum(c.get(name, 0.0) for c in self.by_phase.values())

    def as_dict(self):
        return {phase: dict(c) for phase, c in self.by_phase.items()}


def annotate(phase):
    """A host span named ``bench:<phase>`` in the profiler's trace, so
    the reduction can attribute device time and idle gaps to phases."""
    return jax.profiler.TraceAnnotation(ANNOTATION + phase)


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest of ``devices``; None where the
    backend does not report it (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
