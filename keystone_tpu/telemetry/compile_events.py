"""Compile accounting — programs *compiled* as a first-class metric.

PR 4 made programs *executed* per run a measured, minimized quantity;
this module does the same for programs compiled. Every XLA backend
compile the process performs is observed through `jax.monitoring`'s
event stream (no wrapping of jit call sites — the events fire inside
jax's own compile path, so nothing can dispatch a compile without being
counted):

  dispatch.programs_compiled   (counter) — COLD compiles: real XLA
                               backend work. THE quantity the
                               compile-bounded execution work minimizes;
                               a warm process/run holds this at 0.
  dispatch.compile_cache_hits  (counter) — persistent-cache retrievals
                               (`jax_compilation_cache_dir`, wired via
                               `ExecutionConfig.compile_cache_dir`): the
                               executable was deserialized, not rebuilt.
  compile.cold_secs            (histogram) — cold backend-compile wall
                               time.
  compile.warm_secs            (histogram) — warm retrieval wall time
                               (typically ~ms against multi-second
                               compiles — the win the persistent cache
                               and AOT warmup buy).

Every compile's seconds also go to the ``compile`` layer's clock
(``host.compile.seconds``, `spans.record_layer_complete`), out of the
self time of the dispatch span the compile ran inside. With a tracer
active every compile additionally records a closed ``cat="compile"``
span (``cold``/``warm`` in args), so traces show
exactly WHERE compile time lands — including the AOT warmup pool's
background compiles, which appear on their own thread lane.

Event pairing: jax records ``/jax/compilation_cache/cache_hits`` (and a
retrieval-time duration) *before* the enclosing
``/jax/core/compile/backend_compile_duration`` event of the same
compile, on the same thread. A thread-local flag set by the hit event
and consumed by the next backend-compile event classifies that compile
as warm; compiles with no intervening hit are cold. Listener
registration is process-global and permanent (jax.monitoring has no
per-listener deregistration), installed once on first telemetry import.
"""

from __future__ import annotations

import threading

from .metrics import counter, histogram
from .spans import current_tracer, record_layer_complete

#: duration-event suffix jax records around every backend compile
#: (cache hit or miss) — jax 0.4.x name: /jax/core/compile/...
_BACKEND_COMPILE = "backend_compile_duration"
#: event recorded on a persistent-compilation-cache retrieval
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

_local = threading.local()
_installed = False
_install_lock = threading.Lock()


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT:
        _local.pending_hit = True
        counter("dispatch.compile_cache_hits").inc()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if not event.endswith(_BACKEND_COMPILE):
        return
    warm = getattr(_local, "pending_hit", False)
    _local.pending_hit = False
    if warm:
        histogram("compile.warm_secs").observe(duration)
    else:
        counter("dispatch.programs_compiled").inc()
        from .instrument import process_dim

        dim = process_dim()
        if dim is not None:
            # multi-host: every process compiles its own executables, so
            # pod-level compile accounting carries a per-process axis
            counter(f"dispatch.programs_compiled.{dim}").inc()
        histogram("compile.cold_secs").observe(duration)
    # the event arrives once the compile (or the cache load) is over, so
    # it is counted as a closed span of the `compile` layer and leaves
    # the self time of the dispatch or warmup span it ran inside
    record_layer_complete("compile", duration)
    tracer = current_tracer()
    if tracer is not None:
        now = tracer.now()
        tracer.record_complete(
            "xla_compile", "compile", max(0.0, now - duration), duration,
            cold=not warm, seconds=round(duration, 6))


def install_compile_listeners() -> bool:
    """Register the monitoring listeners (idempotent). Returns whether
    the hooks are live — False only when jax.monitoring is absent, in
    which case compile counters simply stay at zero."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            import jax.monitoring as monitoring

            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            return False
        # pre-register the compile metrics so they appear in every
        # snapshot/trace from the moment the hooks are live — a fully
        # warm run's "0 cold compiles" is a headline number, and it must
        # be distinguishable from a pre-accounting trace (where the
        # counters are absent entirely)
        counter("dispatch.programs_compiled")
        counter("dispatch.compile_cache_hits")
        histogram("compile.cold_secs")
        histogram("compile.warm_secs")
        _installed = True
        return True


def compiles_snapshot() -> dict:
    """Point-in-time compile accounting (the compile bench's delta
    primitive): cold compiles, cache hits, and their wall-clock totals."""
    cold = histogram("compile.cold_secs").snapshot()
    warm = histogram("compile.warm_secs").snapshot()
    return {
        "programs_compiled": int(
            counter("dispatch.programs_compiled").value),
        "compile_cache_hits": int(
            counter("dispatch.compile_cache_hits").value),
        "cold_compile_secs": round(cold["total"], 4),
        "warm_retrieval_secs": round(warm["total"], 4),
    }
