"""Node-force instrumentation — the one wrapper every profile consumer
shares.

`GraphExecutor` wraps each node's lazy Expression through
`instrument_node_force`; the wrapper times the real force (try/finally,
so a thunk that raises still reports its elapsed time, and its span is
marked ``error``), estimates output bytes ONCE per force with the
module-level `estimate_bytes` (no per-force import — the old
`ExecutionProfiler.wrap` re-imported it inside the thunk on every
force), opens a ``cat="node"`` span of the ``force`` layer, feeds the
observed live-set accounting, and notifies the attached profiler.
Streaming expressions — which downstream consumers drain through
``iter_chunks()`` without ever running the memoized thunk — are
instrumented at the chunk generator instead (`_instrument_stream`), so
they too appear in spans, profiles, and reconciliation. Because
`utils.profiling.ExecutionProfiler` and `workflow.autocache.profile_nodes`
both consume these span completions, cache decisions and user-facing
profile reports can never disagree about a measurement.

Timing semantics: with a profiler attached the forced value is
``.sync()``-ed (scalar pull) so device compute is honestly attributed to
the producing node — the contract `profile_nodes` and
`profile_execution` always had. Under pure tracing no sync is injected:
a trace must observe the overlap engine, not serialize it, so node spans
measure dispatch+materialization and the *stall* time shows up where it
is actually paid (chunk drains, consumer waits).
"""

from __future__ import annotations

from time import perf_counter
from typing import Optional

from .metrics import counter, gauge
from .spans import _Span, current_tracer, span


#: cached per-process metric dimension: "" on single-process jobs (no
#: extra counter), "p<index>" under a multi-host mesh, None = unresolved.
#: Tests reset this to None to re-probe after monkeypatching.
_proc_dim_cache: Optional[str] = None


def process_dim() -> Optional[str]:
    """The per-process dispatch/compile accounting dimension: ``p<i>``
    when this is process ``i`` of a multi-host job, None on single-host
    jobs (where a second counter would just duplicate the total).
    Resolved once — `jax.process_index()` is constant for the life of a
    process — and never initializes a backend that isn't already the
    caller's problem (dispatch implies an initialized backend)."""
    global _proc_dim_cache
    if _proc_dim_cache is None:
        try:
            import jax

            _proc_dim_cache = (
                f"p{jax.process_index()}" if jax.process_count() > 1
                else "")
        except Exception:
            _proc_dim_cache = ""
    return _proc_dim_cache or None


def record_dispatch(n: int = 1) -> None:
    """Count ``n`` executed XLA programs against
    ``dispatch.programs_executed`` — the per-run dispatch budget: every
    executed program pays a fixed launch cost whatever its size, so
    trivial stages are bounded by their count, not their bytes. The
    counting half of `dispatch`, which is what the library's call sites
    use. Always on (not gated on tracing): the benchmark's
    `programs_per_fit` and the scheduler tests read the counter
    directly.

    Under a multi-host mesh each count also lands on
    ``dispatch.programs_executed.p<i>`` — every host dispatches its own
    SPMD program launches, so a pod-level trace must say which process
    executed what (the telemetry CLI's dispatch summary and
    ``perf_table.py --trace`` render the per-process breakdown)."""
    counter("dispatch.programs_executed").inc(n)
    dim = process_dim()
    if dim is not None:
        counter(f"dispatch.programs_executed.{dim}").inc(n)


def fn_label(fn) -> str:
    """The label of a dispatch that has no node behind it: the name of
    the function it calls."""
    return getattr(fn, "__name__", None) or type(fn).__name__


class _Dispatch(_Span):
    """The span `dispatch` returns: a ``dispatch`` layer span that counts
    its programs when the call has returned."""

    __slots__ = ("_n",)

    def __init__(self, label: str, n: int, args: dict):
        super().__init__(current_tracer(), label, "dispatch", "dispatch",
                         None, args)
        self._n = n

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            record_dispatch(self._n)
        return super().__exit__(exc_type, exc, tb)


def dispatch(label: str, n: int = 1, **args) -> _Dispatch:
    """The span around one call of a jitted program (``n`` where one
    call launches several): times from the call to its return under the
    ``dispatch`` layer, so the enqueue shows on the profiler's host
    plane as ``ks:dispatch:<label>`` and its seconds in
    ``host.dispatch.seconds``, and counts the call as `record_dispatch`
    does once it has returned. A call that raises launched nothing and
    is not counted, so a fallback that dispatches again counts once.
    ``label`` is the node's label or the program's name.

        with dispatch(self.label):
            out = program(flat, data.array, data.mask)

    Call sites are the library's jitted call boundaries: every
    `Dataset.map_batches`, every fused-chain program launch
    (`FusedBatchTransformer.apply_batch`), every solver step
    (`_bcd_epoch` / `_krr_step` / `_lbfgs_step`), every overlap-engine
    chunk dispatch, and the node-level module jits that bypass
    `map_batches` (scalers, label indicators, random features, normal
    equations, the evaluator's confusion matrix)."""
    return _Dispatch(label, n, args)


def estimate_bytes(value) -> float:
    """Estimated host/device bytes of a forced value: array leaves by
    ``nbytes``, strings/bytes by length, opaque leaves at a nominal 64.
    Canonical home of the estimator previously private to
    `workflow.autocache` (which still re-exports it). Dataset-likes
    unwrap to their payload: ``.data`` (device `Dataset`) or ``.items``
    (`HostDataset` — summed per item, so a host stage's output is its
    real residency, not one opaque-leaf placeholder)."""
    import jax

    payload = getattr(value, "data", None)
    if payload is None:
        payload = getattr(value, "items", None)
    if payload is None:
        payload = value
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(payload):
        if hasattr(leaf, "nbytes"):
            total += float(leaf.nbytes)
        elif isinstance(leaf, (bytes, str)):
            total += len(leaf)
        else:
            total += 64.0
    return total


def _record_node(label, vertex, profiler, dt, nbytes, failed,
                 t0_rel=None, streamed=False):
    """Shared completion bookkeeping for both force paths."""
    counter("executor.node_forces").inc()
    if nbytes and not failed:
        # memoized outputs stay live for the executor's lifetime: the
        # running sum's high-water mark is the observed live-set peak
        # the static KP2xx model reconciles against (per-run copy on the
        # tracer; the registry gauge is cumulative across runs)
        gauge("executor.live_bytes").add(nbytes)
        tracer = current_tracer()
        if tracer is not None:
            tracer.add_live_bytes(nbytes)
    if streamed:
        tracer = current_tracer()
        if tracer is not None and t0_rel is not None:
            # ts is the FIRST-pull timestamp (the drain window's start,
            # not the completion time the record is written at) and dur
            # stays the cumulative pull time — the consumer's
            # between-chunk work is excluded from the stage's cost, so
            # self-time math holds; drain_window_s carries the real
            # first-pull→exhaustion extent for timeline readers
            tracer.record_complete(
                f"force {label}", "node", t0_rel, dt, error=failed,
                vertex=vertex, out_bytes=nbytes, seconds=round(dt, 6),
                drain_window_s=round(max(0.0, tracer.now() - t0_rel), 6),
                streamed=True)
    if profiler is not None:
        profiler.on_force(label, dt, nbytes, failed=failed, vertex=vertex)


def _instrument_stream(label, expr, vertex, profiler):
    """Streamed stages are drained through ``iter_chunks()`` — the
    memoized ``_thunk`` never runs on that path, so wrap the chunk
    generator instead. Per-pull timing keeps the consumer's
    between-chunk work OUT of this stage's duration (drains interleave
    with downstream compute by design); on exhaustion one closed
    ``cat="node"`` span is recorded via `Tracer.record_complete`
    (``streamed=True``, ``dur`` = cumulative pull time) and the profiler
    is notified — so streamed stages appear in profiles, reconciliation,
    and live-set accounting instead of silently folding into their
    consumer. Early close (`GeneratorExit`) records nothing: the stream
    is resumable and will complete (and report) later."""
    orig_chunks = expr._chunks_thunk

    def chunks():
        it = orig_chunks()
        total = 0.0
        nbytes = 0.0
        t0_rel = None
        while True:
            t0 = perf_counter()
            if t0_rel is None:
                tracer = current_tracer()
                t0_rel = tracer.now() if tracer is not None else 0.0
            try:
                item = next(it)
            except StopIteration:
                total += perf_counter() - t0
                _record_node(label, vertex, profiler, total, nbytes,
                             failed=False, t0_rel=t0_rel, streamed=True)
                return
            except GeneratorExit:
                raise  # early close: resumable, not a completion
            except BaseException:
                total += perf_counter() - t0
                _record_node(label, vertex, profiler, total, 0.0,
                             failed=True, t0_rel=t0_rel, streamed=True)
                raise
            total += perf_counter() - t0
            try:
                nbytes += estimate_bytes(item[1])
            except Exception:
                pass
            yield item

    expr._chunks_thunk = chunks
    return expr


def instrument_node_force(
    label: str,
    expr,
    vertex: Optional[int] = None,
    profiler=None,
):
    """Wrap ``expr`` so its force reports spans + metrics + profiler
    completions. Streaming expressions get their chunk generator wrapped
    (see `_instrument_stream`); plain expressions get their thunk
    wrapped. Already-forced expressions pass through untouched. Safe to
    call with neither tracer nor profiler active — but the executor
    guards the call, so the untraced hot path never even reaches here."""
    if getattr(expr, "_chunks_thunk", None) is not None \
            and not expr.is_forced:
        return _instrument_stream(label, expr, vertex, profiler)
    orig_thunk = expr._thunk
    if orig_thunk is None:  # already forced; nothing to time
        return expr

    def forced():
        ctx = span(f"force {label}", cat="node", layer="force",
                   vertex=vertex)
        ctx.__enter__()
        t0 = perf_counter()
        value = None
        failed = False
        try:
            value = orig_thunk()
            if profiler is not None and hasattr(value, "sync"):
                value.sync()  # scalar-pull sync so device time lands on
                # this node; tracing alone never injects a sync — it
                # must observe the overlap engine, not serialize it
            return value
        except BaseException:
            failed = True
            raise
        finally:
            dt = perf_counter() - t0
            nbytes = 0.0
            if not failed and value is not None:
                try:
                    nbytes = estimate_bytes(value)
                except Exception:
                    nbytes = 0.0
            ctx.end(error=failed, out_bytes=nbytes, seconds=round(dt, 6))
            _record_node(label, vertex, profiler, dt, nbytes, failed)

    expr._thunk = forced
    return expr
