"""The one span primitive, and the host tracer behind it.

`span(name, cat, layer=..., part=..., rid=..., **args)` is the only way
the program opens a span. A live span feeds three sinks:

  - the profiler: a `jax.profiler.TraceAnnotation` named
    ``ks:<layer or cat>:<name>``. The annotation checks the profiler's
    own flag, so it costs under a microsecond with no session and lands
    on the host plane of the session's ``.xplane.pb``, on the device's
    clock, whenever one runs (`jax.profiler.trace`, the benchmark's
    ``--trace 1``). There is no switch of the program's own;
  - the layer clock (``layer=`` one of `LAYERS`, always on): the span's
    self time, its length less the layer spans opened inside it on the
    same thread, goes to the counter ``host.<layer>.seconds`` and 1 to
    ``host.<layer>.spans``; a layer span that names a ``part`` adds the
    same self time to ``host.<layer>.<part>.seconds`` as well (and 1 to
    ``host.<layer>.<part>.spans``), so where every span of a layer names
    a part the parts sum to the layer;
  - the host tracer (`Tracer`, on under `trace_run` / ``KEYSTONE_TRACE``):
    a closed `SpanRecord` whose parent is the span that was open on the
    thread, written as Chrome trace JSON by `export.write_trace`.

A span with no ``layer`` and no tracer installed is the shared no-op
(one global read, no allocation), so ``cat="chunk"`` and per-row spans
cost nothing in an untraced run.

    pipeline run (trace_run)          cat="pipeline"
      optimizer phase                 cat="phase"   layer optimize
        node force (executor)         cat="node"    layer force
          program call                cat="dispatch" layer dispatch
          stream chunk (batching)     cat="chunk"
          solver fit, iteration       cat="solver"/"step" layer solver
          blocking pull               cat="sync"    layer sync

Nesting is structural, not declared: each thread keeps a span stack, so
a node force that pulls its dependency inside its own thunk is that
dependency's parent, and the overlap engine's producer thread has a
root lane of its own.

Host tracer timestamps use `time.perf_counter()` relative to the
tracer's epoch (KJ004 discipline); the wall-clock epoch is recorded once
in metadata for cross-run alignment.
"""

from __future__ import annotations

import atexit
import itertools
import re
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import counter

_capabilities: Dict[str, Dict[str, Any]] = {}


def record_capability(name: str, available: bool, reason: str = "") -> None:
    """Record an environment capability probe outcome (e.g. a skipped
    test's reason). Exported in every trace's metadata so bench/trace
    artifacts carry which capabilities were absent for the run."""
    _capabilities[name] = {"available": bool(available), "reason": reason}


def capabilities() -> Dict[str, Dict[str, Any]]:
    return dict(_capabilities)


class SpanRecord:
    """One closed span. ``t0``/``dur`` are seconds relative to the
    tracer epoch; ``sid``/``parent`` link the hierarchy."""

    __slots__ = ("name", "cat", "t0", "dur", "tid", "sid", "parent",
                 "args", "error")

    def __init__(self, name: str, cat: str, t0: float, tid: int, sid: int,
                 parent: Optional[int], args: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.dur = 0.0
        self.tid = tid
        self.sid = sid
        self.parent = parent
        self.args = args
        self.error = False


class Tracer:
    """Span + counter-sample collector. Append-only lists mutated under
    the GIL (list.append is atomic); per-thread span stacks live in a
    `threading.local` so producer threads nest independently."""

    def __init__(self):
        self.epoch = time.perf_counter()
        self.wall_epoch = time.time()  # keystone: ignore[KJ004] — wall-clock anchor, not a duration
        self.spans: List[SpanRecord] = []
        self.counter_samples: List[tuple] = []  # (name, t, value, tid)
        self.metadata: Dict[str, Any] = {}
        self._ids = itertools.count(1)
        self._local = threading.local()
        # sid → still-open SpanRecord, so a dump/export racing an open
        # span can emit it as incomplete-but-parseable instead of
        # dropping it (dict add/pop are atomic under the GIL)
        self._open: Dict[int, SpanRecord] = {}

    # ------------------------------------------------------------ spans

    def _stack(self) -> List[SpanRecord]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def start(self, name: str, cat: str = "span", **args) -> SpanRecord:
        st = self._stack()
        rec = SpanRecord(
            name,
            cat,
            time.perf_counter() - self.epoch,
            threading.get_ident(),
            next(self._ids),
            st[-1].sid if st else None,
            args,
        )
        st.append(rec)
        self._open[rec.sid] = rec
        return rec

    def end(self, rec: SpanRecord, error: bool = False, **args) -> None:
        rec.dur = time.perf_counter() - self.epoch - rec.t0
        rec.error = error
        if args:
            rec.args.update(args)
        st = self._stack()
        # tolerate exception-path unwinding that skipped inner ends
        while st and st[-1] is not rec:
            st.pop()
        if st:
            st.pop()
        self._open.pop(rec.sid, None)
        self.spans.append(rec)
        if _TEES:
            _tee_span(self, rec)

    def record_complete(self, name: str, cat: str, t0: float, dur: float,
                        error: bool = False, **args) -> SpanRecord:
        """Append an already-closed span without touching the stack —
        for measurements whose lifetime does not nest cleanly (a
        streamed stage's drain interleaves with its consumer). Parent is
        whatever span is open on this thread right now. ``t0`` is
        seconds relative to this tracer's epoch."""
        st = self._stack()
        rec = SpanRecord(
            name, cat, t0, threading.get_ident(), next(self._ids),
            st[-1].sid if st else None, args,
        )
        rec.dur = dur
        rec.error = error
        self.spans.append(rec)
        if _TEES:
            _tee_span(self, rec)
        return rec

    def now(self) -> float:
        """Seconds since this tracer's epoch (for `record_complete`)."""
        return time.perf_counter() - self.epoch

    def open_spans(self) -> List[SpanRecord]:
        """Snapshot of the spans still open right now (dump/export use:
        each is emitted as an incomplete-but-parseable event). The list
        is a copy; the records themselves are live."""
        return list(self._open.values())

    def counter_sample(self, name: str, value: float) -> None:
        t = time.perf_counter() - self.epoch
        tid = threading.get_ident()
        self.counter_samples.append((name, t, value, tid))
        if _TEES:
            _tee_counter(self, name, t, value, tid)

    # ------------------------------------------------- live-set tracking

    def add_live_bytes(self, nbytes: float) -> None:
        """Per-run observed live-set accounting: node outputs are
        memoized for their executor's lifetime, so the running sum's
        high-water mark is THIS run's observed peak (the process-global
        `executor.live_bytes` gauge is cumulative across runs)."""
        live = self.metadata.get("observed_live_bytes", 0.0) + nbytes
        self.metadata["observed_live_bytes"] = live
        if live > self.metadata.get("observed_live_peak_bytes", 0.0):
            self.metadata["observed_live_peak_bytes"] = live


#: the layers whose host seconds are always counted, in the order a
#: request meets them (OBSERVABILITY.md lists each one's site)
LAYERS = ("optimize", "force", "dispatch", "sync", "solver", "compile",
          "serve")

_layer_local = threading.local()
#: guards `_Span._inner`: a worker thread's spans add to the span of the
#: thread that waits for it (`adopt_layer_parent`)
_inner_lock = threading.Lock()


def _layer_stack() -> list:
    st = getattr(_layer_local, "stack", None)
    if st is None:
        st = _layer_local.stack = []
    return st


def layer_parent() -> Optional["_Span"]:
    """The innermost layer span open on this thread, to hand to the
    threads it is about to wait for (`adopt_layer_parent`)."""
    st = _layer_stack()
    return st[-1] if st else None


def adopt_layer_parent(parent: Optional["_Span"]) -> None:
    """Called first thing on a worker thread whose spawner waits for it
    inside ``parent``: the worker's layer spans then count as opened
    inside ``parent``, so the seconds the spawner spends waiting are
    charged to what the workers did and not to the spawner's layer as
    well. Workers that run side by side still add up to more than the
    wall time: these are thread seconds."""
    _layer_local.stack = [parent] if parent is not None else []


def _add_inner(parent: "_Span", seconds: float) -> None:
    with _inner_lock:
        parent._inner += seconds


def pause_layer_span(seconds: float) -> None:
    """Take ``seconds`` that this thread has just spent in a pause of
    the interpreter (a collection: `gc_events`) out of the self time of
    the innermost layer span open on it. No lock is taken: one
    collection runs at a time and nothing else writes ``_paused``, and
    the collector may stop a thread that holds `_inner_lock`."""
    st = getattr(_layer_local, "stack", None)
    if st:
        st[-1]._paused += seconds


#: layer -> the names of its two counters (looked up at every close:
#: the registry can be reset under a span)
_LAYER_COUNTERS = {layer: (f"host.{layer}.seconds", f"host.{layer}.spans")
                   for layer in LAYERS}


def _count_layer(layer: str, seconds: float,
                 part: Optional[str] = None) -> None:
    seconds_name, spans_name = _LAYER_COUNTERS[layer]
    counter(seconds_name).inc(seconds)
    counter(spans_name).inc()
    if part is not None:
        counter(f"host.{layer}.{part}.seconds").inc(seconds)
        counter(f"host.{layer}.{part}.spans").inc()


def record_layer_complete(layer: str, seconds: float) -> None:
    """Count ``seconds`` that have already passed on this thread as one
    closed span of ``layer``, and take them out of the self time of the
    layer span that is open around them. For measurements that arrive
    after the fact (jax's compile events)."""
    _count_layer(layer, seconds)
    st = _layer_stack()
    if st:
        _add_inner(st[-1], seconds)


def scope_name(label: str) -> str:
    """``ks.<label>`` for `jax.named_scope`: the label without blanks,
    without the slash that separates scopes in an op's name, and without
    a private name's leading underscores."""
    return "ks." + re.sub(r"[\s/]+", "", label).lstrip("_")


class _Span:
    """One live span and its three sinks (module docstring). Exceptions
    close the span (marked ``error`` in the host tracer) and propagate."""

    __slots__ = ("_tracer", "_name", "_cat", "_layer", "_part", "_args",
                 "_rec", "_annotation", "_t0", "_inner", "_paused")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 layer: Optional[str], part: Optional[str], args: Dict):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._layer = layer
        self._part = part
        self._args = args
        self._rec = None

    def __enter__(self) -> Optional[SpanRecord]:
        self._annotation = TraceAnnotation(
            f"ks:{self._layer or self._cat}:{self._name}", **self._args)
        self._annotation.__enter__()
        if self._tracer is not None:
            self._rec = self._tracer.start(
                self._name, self._cat, **self._args)
        if self._layer is not None:
            self._inner = self._paused = 0.0
            _layer_stack().append(self)
            self._t0 = time.perf_counter()
        return self._rec

    def end(self, error: bool = False, **args) -> None:
        """Close the span; ``args`` are added to the tracer's record."""
        if self._layer is not None:
            length = time.perf_counter() - self._t0
            st = _layer_stack()
            # tolerate exception-path unwinding that skipped inner ends
            while st and st.pop() is not self:
                pass
            _count_layer(
                self._layer,
                max(0.0, length - self._inner - self._paused), self._part)
            if st:
                _add_inner(st[-1], length)
        if self._rec is not None:
            self._tracer.end(self._rec, error=error, **args)
        self._annotation.__exit__(None, None, None)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end(error=exc_type is not None)
        return False


class _NoopSpan:
    """Shared do-nothing context manager for the untraced hot path."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def __bool__(self) -> bool:  # `if span_ctx:` idiom in instrumentation
        return False


_NOOP = _NoopSpan()

# ------------------------------------------------------------------ tees
#
# A tee is a passive sink (the flight recorder) that receives a copy of
# every CLOSED span and counter sample any tracer records — so the
# always-on ring stays populated even while a scoped `trace_run` tracer
# owns the active slot. The registry is an immutable tuple swapped
# whole-sale (read is one global load; the hot path pays a falsy check
# when no tee is installed). A tee that is itself a Tracer never
# receives its own records.

_TEES: tuple = ()


def add_tee(sink) -> None:
    """Register ``sink`` (needs ``tee_span(src, rec)`` and
    ``tee_counter(src, name, t, value, tid)``) to receive copies of all
    closed spans / counter samples from every tracer. Idempotent."""
    global _TEES
    if sink not in _TEES:
        _TEES = _TEES + (sink,)


def remove_tee(sink) -> None:
    global _TEES
    _TEES = tuple(s for s in _TEES if s is not sink)


def _tee_span(src: Tracer, rec: SpanRecord) -> None:
    for sink in _TEES:
        if sink is src:
            continue
        try:
            sink.tee_span(src, rec)
        except Exception:
            pass  # telemetry must never take down the measured run


def _tee_counter(src: Tracer, name: str, t: float, value: float,
                 tid: int) -> None:
    for sink in _TEES:
        if sink is src:
            continue
        try:
            sink.tee_counter(src, name, t, value, tid)
        except Exception:
            pass


# ---------------------------------------------------------------- active

_active: Optional[Tracer] = None
_ambient_checked = False


def _env_trace_path() -> Optional[str]:
    from ..workflow.env import execution_config

    return execution_config().trace_path


def _flush_ambient(path: str) -> None:
    global _active
    t = _active
    if t is not None:
        from .export import write_trace

        try:
            write_trace(t, path)
        except OSError:
            pass


def current_tracer() -> Optional[Tracer]:
    """The installed tracer, or None. On first call, honors
    ``KEYSTONE_TRACE``/`ExecutionConfig.trace_path` by installing an
    ambient tracer flushed at process exit."""
    global _active, _ambient_checked
    if _active is None and not _ambient_checked:
        _ambient_checked = True
        try:
            path = _env_trace_path()
        except Exception:
            path = None
        if path:
            _active = Tracer()
            atexit.register(_flush_ambient, path)
    return _active


def telemetry_active() -> bool:
    return current_tracer() is not None


def span(name: str, cat: str = "span", *, layer: Optional[str] = None,
         part: Optional[str] = None, rid: Optional[str] = None, **args):
    """Open a span (module docstring). ``layer`` names the layer whose
    clock the span's self time is charged to, ``part`` the part of that
    layer whose clock gets the same seconds; ``rid`` is an identifier
    that the spans of one request share. With neither a layer nor a
    tracer this is the shared no-op."""
    t = current_tracer()
    if layer is None:
        if part is not None:
            raise ValueError(f"span part {part!r} needs a layer")
        if t is None:
            return _NOOP
    elif layer not in LAYERS:
        raise ValueError(f"span layer {layer!r} is not one of {LAYERS}")
    if rid is not None:
        args["rid"] = rid
    return _Span(t, name, cat, layer, part, args)


class trace_run:
    """Scope a tracer (and optionally write its Chrome trace on exit):

        with trace_run("run.json") as tracer:
            pipeline(data).get()

    ``path=None`` falls back to `ExecutionConfig.trace_path` (the
    ``KEYSTONE_TRACE`` env var); with neither, the trace is only held in
    memory on the yielded tracer. Nests: the previous tracer is restored
    on exit. Opens a root ``cat="pipeline"`` span so every run has a
    top-level interval."""

    def __init__(self, path: Optional[str] = None, name: str = "pipeline_run"):
        self._path = path
        self._name = name
        self._prev: Optional[Tracer] = None
        self._root = None
        self.tracer = Tracer()

    def __enter__(self) -> Tracer:
        global _active
        self._prev = _active
        _active = self.tracer
        self._root = self.tracer.start(self._name, cat="pipeline")
        return self.tracer

    def __exit__(self, exc_type, exc, tb) -> bool:
        global _active
        self.tracer.end(self._root, error=exc_type is not None)
        _active = self._prev
        path = self._path
        if path is None:
            try:
                path = _env_trace_path()
            except Exception:
                path = None
        if path:
            from .export import write_trace

            write_trace(self.tracer, path)
        return False


def set_tracer(tracer: Optional[Tracer]) -> None:
    """Install ``tracer`` process-wide (None uninstalls). `trace_run` is
    the structured form; this exists for hosts that manage lifecycle
    themselves (bench child processes)."""
    global _active
    _active = tracer
