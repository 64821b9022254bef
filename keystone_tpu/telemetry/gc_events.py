"""Collections of Python's garbage collector, counted where they happen.

One `gc.callbacks` hook, installed with the package as the compile
listeners are (`compile_events`):

  host.gc.seconds           (counter) — seconds between the callback's
                            ``start`` and ``stop``, every generation
  host.gc.collections       (counter) — one a collection
  host.gc.full_collections  (counter) — one a collection of generation 2

A collection stops the thread it runs on wherever that thread is, so its
seconds are taken out of the self time of the layer span open around it
(`spans.pause_layer_span`): no layer is charged for a pause. A full
collection is also the annotation ``ks:gc:full`` on the profiler's host
plane, so a device gap that a collection made is named by it
(`device.reduce_planes`).

The hook takes no lock: the collector may run between any two bytecodes
of a thread that holds the registry's lock, and one collection runs at a
time, so nothing else writes these three counters. It is appended behind
jax's own callback, which frees device buffers at ``stop``: that time is
inside the measured seconds.
"""

from __future__ import annotations

import gc
import time

from jax.profiler import TraceAnnotation

from .metrics import Counter, registry
from .spans import pause_layer_span

_t0 = 0.0
_annotation = None


def _add(name: str, n: float) -> None:
    table = registry().counters
    c = table.get(name)
    if c is None:  # first collection, or the first after a reset
        c = table.setdefault(name, Counter(name))
    c.value += n


def _on_gc(phase: str, info: dict) -> None:
    global _t0, _annotation
    if phase == "start":
        if info["generation"] == 2:
            _annotation = TraceAnnotation("ks:gc:full")
            _annotation.__enter__()
        _t0 = time.perf_counter()
        return
    if not _t0:
        return  # installed while a collection ran: no start to measure from
    seconds, _t0 = time.perf_counter() - _t0, 0.0
    _add("host.gc.seconds", seconds)
    _add("host.gc.collections", 1.0)
    if info["generation"] == 2:
        _add("host.gc.full_collections", 1.0)
        if _annotation is not None:
            _annotation.__exit__(None, None, None)
            _annotation = None
    pause_layer_span(seconds)


def install_gc_hook() -> None:
    """Append the hook to `gc.callbacks` (idempotent)."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
