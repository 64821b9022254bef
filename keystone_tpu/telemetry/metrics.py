"""Process-wide metrics registry: counters, gauges, histograms.

One trustworthy measurement substrate (KeystoneML's profile-guided
optimizer premise, PAPER.md §5): the executor, the overlap engine, and
the solver loops all report into the same named-metric namespace, so the
auto-cacher, user-facing profiler reports, and trace exports can never
disagree about what was observed.

Metric names are dotted and stable — they are part of the telemetry
contract documented in OBSERVABILITY.md:

  executor.node_forces                      (counter)
  host.<layer>.seconds / host.<layer>.spans (counters; the layer clock
                                             of `spans.span`: self time
                                             and count of each layer's
                                             spans, always on; with
                                             ``.<part>`` after the layer
                                             for a span that names one)
  host.gc.seconds / collections / full_collections
                                            (counters; `gc_events`)
  executor.live_bytes                       (gauge; .max = observed peak)
  prefetch.queue_depth                      (gauge)
  prefetch.producer_stall_s / consumer_wait_s   (histograms, seconds)
  overlap.inflight_results / resident_chunks    (gauges)
  overlap.bytes_pulled / chunks_dispatched      (counters)
  solver.steps                              (counter)
  dispatch.programs_executed                (counter; one per jitted
                                             call boundary — see
                                             instrument.dispatch)
  dispatch.scheduler_runs / scheduled_tasks (counters; concurrent DAG
                                             scheduler activity)
  dispatch.programs_compiled                (counter; one per COLD XLA
                                             backend compile — see
                                             compile_events)
  dispatch.compile_cache_hits               (counter; persistent-cache
                                             retrievals, i.e. warm
                                             compiles)
  compile.cold_secs / warm_secs             (histograms, seconds of
                                             compile / retrieval wall)

Thread-safety: one process lock guards mutation — producer threads
(overlap engine) and the main thread share these. Updates are
chunk/force granular (hundreds per run, not millions), so contention is
irrelevant next to the work being measured.
"""

from __future__ import annotations

import random
import threading
import zlib
from typing import Dict, Optional

_LOCK = threading.Lock()


class Counter:
    """Monotonic accumulator."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with _LOCK:
            self.value += n

    def snapshot(self) -> Dict[str, float]:
        return {"value": self.value}


class Gauge:
    """Point-in-time level with a high-water mark. ``set``/``add`` also
    emit a counter sample into the active tracer (when one is installed)
    so the level is a time series in the Chrome trace, not just a max."""

    __slots__ = ("name", "value", "max")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self.max = 0.0

    def set(self, v: float) -> None:
        with _LOCK:
            self.value = v
            if v > self.max:
                self.max = v
        from .spans import current_tracer

        t = current_tracer()
        if t is not None:
            t.counter_sample(self.name, v)

    def add(self, d: float) -> float:
        with _LOCK:
            self.value += d
            v = self.value
            if v > self.max:
                self.max = v
        from .spans import current_tracer

        t = current_tracer()
        if t is not None:
            t.counter_sample(self.name, v)
        return v

    def snapshot(self) -> Dict[str, float]:
        return {"value": self.value, "max": self.max}


#: Histogram reservoir capacity. 512 float samples ≈ 4 KiB per metric —
#: a long-lived serving process holds a fixed few KiB per histogram no
#: matter how many observations arrive, yet p50/p99 stay readable
#: (standard error of a reservoir quantile at n=512 is ~2% at p50).
RESERVOIR_SIZE = 512


class Histogram:
    """Streaming count/sum/min/max plus a FIXED-SIZE uniform reservoir
    (Vitter's Algorithm R) so percentiles are readable without retaining
    samples unboundedly. The exact aggregates (count/total/min/max) are
    what reports and tests assert on; `percentile` answers from the
    reservoir — an unbiased uniform sample of everything observed —
    while memory stays O(RESERVOIR_SIZE) forever."""

    __slots__ = ("name", "count", "total", "min", "max",
                 "_reservoir", "_rng")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max = 0.0
        self._reservoir: list = []
        # deterministic per-name seed: reproducible snapshots in tests
        # without coupling separate histograms' sampling decisions
        self._rng = random.Random(zlib.crc32(name.encode()))

    def observe(self, v: float) -> None:
        with _LOCK:
            self.count += 1
            self.total += v
            if self.min is None or v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            if len(self._reservoir) < RESERVOIR_SIZE:
                self._reservoir.append(v)
            else:
                j = self._rng.randrange(self.count)
                if j < RESERVOIR_SIZE:
                    self._reservoir[j] = v

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, q: float) -> float:
        """Reservoir-estimated q-quantile (q in [0, 1]); 0.0 when empty.
        Linear interpolation between order statistics."""
        with _LOCK:
            data = sorted(self._reservoir)
        if not data:
            return 0.0
        if len(data) == 1:
            return data[0]
        pos = max(0.0, min(1.0, q)) * (len(data) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(data) - 1)
        frac = pos - lo
        return data[lo] * (1.0 - frac) + data[hi] * frac

    def snapshot(self) -> Dict[str, float]:
        return {
            "count": self.count,
            "total": self.total,
            "min": self.min if self.min is not None else 0.0,
            "max": self.max,
            "mean": self.mean,
            "p50": self.percentile(0.50),
            "p99": self.percentile(0.99),
        }


class MetricsRegistry:
    """Name→metric table. ``counter``/``gauge``/``histogram`` create on
    first use; a name is one kind forever (a config bug, not a race —
    raise loudly)."""

    def __init__(self):
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}

    def _get(self, table: Dict, name: str, cls):
        m = table.get(name)
        if m is None:
            for other in (self.counters, self.gauges, self.histograms):
                if other is not table and name in other:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{type(other[name]).__name__}"
                    )
            with _LOCK:
                m = table.setdefault(name, cls(name))
        return m

    def counter(self, name: str) -> Counter:
        return self._get(self.counters, name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(self.gauges, name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(self.histograms, name, Histogram)

    def snapshot(self) -> Dict[str, Dict[str, Dict[str, float]]]:
        """JSON-ready view: {counters: {...}, gauges: {...},
        histograms: {...}} — embedded verbatim in trace exports."""
        return {
            "counters": {k: v.snapshot() for k, v in sorted(self.counters.items())},
            "gauges": {k: v.snapshot() for k, v in sorted(self.gauges.items())},
            "histograms": {
                k: v.snapshot() for k, v in sorted(self.histograms.items())
            },
        }

    def reset(self) -> None:
        """Drop all metric state (tests; a fresh bench tier)."""
        with _LOCK:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()


_registry = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _registry


class MetricsDelta:
    """Counter deltas over one measured window, against the
    process-cumulative registry.

    Every per-example measurement used to hand-roll
    ``before = c.value; ...; c.value - before`` against the cumulative
    counters; this is that idiom, once::

        with metrics_delta() as d:
            predictor(test).get()
        programs = d.counter("dispatch.programs_executed")

    ``counter(name)`` is the window's increment (0.0 for a counter that
    did not exist or did not move); ``counters()`` is every nonzero
    delta. Gauges and histograms are cumulative-by-design (high-water
    marks, streaming totals) and are deliberately not delta'd here —
    read their snapshots directly. Reentrant and thread-compatible: the
    baseline is captured once at ``__enter__`` and never mutated."""

    def __init__(self, reg: Optional[MetricsRegistry] = None):
        self._registry = reg or _registry
        self._base: Dict[str, float] = {}

    def __enter__(self) -> "MetricsDelta":
        with _LOCK:
            self._base = {
                name: c.value for name, c in self._registry.counters.items()
            }
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def counter(self, name: str) -> float:
        c = self._registry.counters.get(name)
        current = c.value if c is not None else 0.0
        return current - self._base.get(name, 0.0)

    def counters(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        with _LOCK:
            for name, c in self._registry.counters.items():
                d = c.value - self._base.get(name, 0.0)
                if d:
                    out[name] = d
        return out


def metrics_delta(reg: Optional[MetricsRegistry] = None) -> MetricsDelta:
    """Snapshot-delta context over the process-cumulative counter
    registry (see `MetricsDelta`)."""
    return MetricsDelta(reg)


def counter(name: str) -> Counter:
    return _registry.counter(name)


def gauge(name: str) -> Gauge:
    return _registry.gauge(name)


def histogram(name: str) -> Histogram:
    return _registry.histogram(name)
