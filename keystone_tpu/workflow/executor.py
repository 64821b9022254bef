"""Lazy memoized graph execution with a concurrent DAG scheduler.

Mirrors reference workflow/GraphExecutor.scala:14-81: execution of a graph
up to a `GraphId` optimizes the graph once (lazily, via the globally
configured optimizer), then recursively evaluates dependencies with
per-vertex memoization. Results of nodes whose prefixes were marked
saveable are written into the global prefix table so later executors can
reuse them (fit-once guarantee, GraphExecutor.scala:65-71).

Dispatch-bounded execution: the serial recursive force dispatches one
node at a time, and every program boundary pays a fixed launch cost, so
a pipeline of small stages is bounded by its *program count*. When `ExecutionConfig.concurrent_dispatch` is on (the
default; ``KEYSTONE_CONCURRENT_DISPATCH=0`` reverts), forcing a sink
first runs `_force_concurrent`: the root's ancestor sub-DAG is forced in
topological order by a bounded worker pool, so independent subgraphs
(gather branches, train-vs-test applies, estimator fits) keep multiple
programs in flight concurrently. Guarantees:

  - **single force** — each vertex is claimed by exactly one worker, in
    a deterministic (topo-index) order; the memo/prefix tables are only
    mutated during single-threaded wiring, never from the pool;
  - **deterministic results** — values are pure functions of already-
    forced dependencies, so worker count cannot change any output;
  - **serial-identical exceptions** — on failure the scheduler stops
    issuing work, drains in-flight tasks, and re-raises the failure of
    the earliest vertex in topo order (what the depth-first serial
    force would have hit); the failing expression stays unforced, so a
    retry re-runs exactly as the serial path would;
  - **streaming stays lazy** — a single-consumer streaming stage is
    never forced by the pool; its chunks keep flowing into the consumer
    (the PR-1 overlap engine still applies inside fused chains), while
    fan-out streaming stages are materialized *before* their consumers
    can race on `iter_chunks`.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional, Tuple

from .env import PipelineEnv, Prefix, execution_config
from .expressions import Expression, StreamingDatasetExpression
from .graph import Graph, GraphId, NodeId, SinkId, SourceId

# A worker thread re-entering `execute` (e.g. a fit forcing a nested
# sample executor) must not spawn a nested pool: the flag makes inner
# schedules run serially on the worker itself.
_sched_local = threading.local()


# Live AOT-warmup threads (the per-executor scan + per-program
# compiles), so measurement code can quiesce them: an un-joined
# straggler compile from run N would otherwise land its
# `dispatch.programs_compiled` increment inside run N+1's snapshot
# window and flakily break the warm-run == 0-compiles gates.
_warm_threads: List[threading.Thread] = []
_warm_threads_lock = threading.Lock()

# Warm-scan memo per `warm_scope` (see GraphExecutor.__init__): the set
# of serving-ladder signatures already scanned for a given long-lived
# owner. Weak keys so a dropped FittedPipeline releases its entry.
_warm_scope_seen: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_warm_scope_lock = threading.Lock()


_exit_drain_registered = False


def _spawn_warm_thread(target, name: str) -> None:
    global _exit_drain_registered
    t = threading.Thread(target=target, name=name, daemon=True)
    with _warm_threads_lock:
        _warm_threads[:] = [x for x in _warm_threads if x.is_alive()]
        _warm_threads.append(t)
        if not _exit_drain_registered:
            # a daemon thread still inside an XLA compile while CPython
            # finalizes segfaults the interpreter (seen with the
            # serving envelope armed, where a short-lived process can
            # exit right after an apply spawned its ladder warmup);
            # quiesce in-flight warmups at exit, briefly — a compile
            # that never returns still cannot block exit past the
            # timeout
            import atexit

            atexit.register(drain_warmups, timeout=10.0)
            _exit_drain_registered = True
    t.start()


def drain_warmups(timeout: float = 60.0) -> None:
    """Join every in-flight AOT warmup thread (best effort, bounded by
    ``timeout`` total). The compile bench and the lint-gate compile
    smoke call this before reading compile counters, so background
    warmup compiles are attributed to the run that started them."""
    import time as _time

    deadline = _time.monotonic() + timeout
    while True:
        with _warm_threads_lock:
            live = [t for t in _warm_threads if t.is_alive()]
            _warm_threads[:] = live
        if not live:
            return
        for t in live:
            t.join(timeout=max(0.0, deadline - _time.monotonic()))
        if _time.monotonic() >= deadline:
            return


def warm_fitted_manifest(fitted, manifest, sample) -> int:
    """The serving runtime's pre-traffic warm hook: bind ``sample`` (a
    host batch of the declared ingress element, or a `Dataset`) into a
    throwaway executor over the fitted apply graph and feed ``manifest``
    (an `analysis.serving.warmup_manifest()` enumeration) to
    `warm_manifest`. Program caches are global and structure-keyed, so
    the programs compiled here are exactly the ones every later
    `FittedPipeline.apply` — and a hot-swapped successor warming on a
    background thread — will hit warm. Returns the number of program
    sites submitted; call `drain_warmups()` to block on the compiles."""
    from ..data.dataset import Dataset
    from .operators import DatasetOperator

    data = (sample if getattr(sample, "is_dataset", False)
            else Dataset.from_numpy(sample))
    g, nid = fitted.graph.add_node(DatasetOperator(data), [])
    g = g.replace_dependency(fitted.source, nid).remove_source(fitted.source)
    return GraphExecutor(g, optimize=False).warm_manifest(manifest)


def _submit_warmup(op, element, counts) -> None:
    """Run one fused-program AOT warmup on a daemon thread. ``counts``
    is one example count or a sequence of them (the serving ladder): the
    shapes compile sequentially on one thread, so a plan warms a whole
    envelope without a thread per shape. Plans carry at most a handful
    of fused programs, so a thread per program site is the bound; daemon
    so a compile that never returns can never block process exit.
    Failures are
    logged at debug and otherwise dropped — the force path compiles
    inline exactly as it would have without warmup (it also clears the
    pending-future entry, so nothing waits on a dead warmup; see
    `nodes.util.fusion._WARMUP_PENDING`)."""
    if isinstance(counts, int):
        counts = (counts,)
    counts = tuple(dict.fromkeys(int(c) for c in counts if c))

    def run():
        for count in counts:
            try:
                op.warmup(element, count)
            except Exception as e:
                import logging

                logging.getLogger(__name__).debug(
                    "AOT warmup of %s at count %d failed: %s: %s",
                    getattr(op, "label", op), count, type(e).__name__, e)

    _spawn_warm_thread(run, "keystone-aot-warmup")


def _serving_warm_counts() -> List[int]:
    """The extra AOT warm counts a declared serving envelope demands:
    every pad-ladder shape `analysis.serving.ladder_shapes` enumerates —
    the SAME (element × count) expansion `serving.warmup_manifest`
    exports, so the KP902 coverage claim ("with KEYSTONE_SLO_MS armed,
    warm serving at any in-envelope shape performs 0 cold compiles") is
    enforced here, not just stated. Deliberately widens EVERY warm
    target — fit-graph sites included, which serving never dispatches
    at ladder shapes: the fit/apply chains share structural program
    keys more often than not, the compiles run on background daemon
    threads overlapped with fit compute, and a path-scoped filter here
    would duplicate the certificate's apply-path walk in the executor.
    Empty when no envelope is armed; a serving.py bug must never break
    warmup."""
    try:
        from ..analysis.serving import envelope_from_env, ladder_shapes

        envelope = envelope_from_env()
        if envelope is None:
            return []
        return ladder_shapes(envelope)
    except Exception:
        return []


def _spec_dtype_name(spec) -> Optional[str]:
    """The boundary dtype of a propagated DataSpec ("float32",
    "uint8", ...; mixed pytrees join with "+"), or None when unknown —
    the trace/reconcile tables' dtype column. Delegates to the
    precision module's formatter so this column and the
    ``--explain-precision`` table can never disagree on a boundary."""
    try:
        from ..analysis.precision import _elem_dtype_name
        from ..analysis.specs import DataSpec, is_known

        if not isinstance(spec, DataSpec) or not is_known(spec.element):
            return None
        name = _elem_dtype_name(spec)
        return None if name == "?" else name
    except Exception:
        return None


def concurrent_relation(graph: Graph):
    """The scheduler's concurrently-schedulable relation, exposed for
    static analysis (the KP511 interference pass): a predicate
    ``unordered(u, v)`` that is True when the concurrent DAG scheduler
    could force ``u`` and ``v`` simultaneously.

    This is the static projection of `_schedule_plan`'s effective-
    dependency DAG: two vertices are ordered only when one is an
    ancestor of the other. Deferral (absorbing an already-forced or
    single-consumer streaming vertex into its consumer's task) only
    merges a vertex INTO a dependent's task — it never adds ordering
    between otherwise-independent vertices — so DAG-unordered is a
    faithful, conservative answer to "could the pool run these at the
    same time"."""
    from .analysis import ancestors

    anc: Dict[GraphId, frozenset] = {}

    def _anc(v: GraphId) -> frozenset:
        got = anc.get(v)
        if got is None:
            got = anc[v] = frozenset(ancestors(graph, v))
        return got

    def unordered(u: GraphId, v: GraphId) -> bool:
        return u != v and u not in _anc(v) and v not in _anc(u)

    return unordered


class GraphExecutor:
    def __init__(
        self,
        graph: Graph,
        optimize: bool = True,
        plan: Optional[Tuple[Graph, Dict[NodeId, Prefix]]] = None,
        warm_scope: Optional[object] = None,
    ):
        """``plan`` supplies an already-optimized (graph, prefixes) pair,
        bypassing the optimizer (used by `Pipeline.fit`). ``warm_scope``
        names a long-lived owner (a `FittedPipeline`) whose program set
        this executor's graph is derived from: the AOT warm scan runs
        ONCE per scope instead of once per bound executor — the serving
        request loop builds an executor per dispatch, and re-scanning an
        already-warm plan costs a thread spawn plus spec_pass traces on
        every request (milliseconds that dominate a warm apply)."""
        self._raw_graph = graph
        self._optimize = optimize
        self._warm_scope = warm_scope
        self._optimized: Optional[Tuple[Graph, Dict[NodeId, Prefix]]] = plan
        self._memo: Dict[GraphId, Expression] = {}
        self._structure_checked = False
        self._static_recorded = False
        self._warmed = False
        self._concurrent_wrapped: set = set()
        # AOT warmup re-arm state: fused-chain programs whose estimator
        # slots had not resolved when the warm scan ran (see
        # `_rearm_warmup`). Appended from the scan thread, drained from
        # whichever thread notices the fits resolved.
        self._warm_pending: List[dict] = []
        self._warm_est_watch: set = set()
        self._warm_lock = threading.Lock()

    @property
    def graph(self) -> Graph:
        """The unoptimized graph (used for graph splicing)."""
        return self._raw_graph

    @property
    def optimized_graph(self) -> Graph:
        return self._optimized_plan()[0]

    def _optimized_plan(self) -> Tuple[Graph, Dict[NodeId, Prefix]]:
        if self._optimized is None:
            if self._optimize:
                optimizer = PipelineEnv.get().get_optimizer()
                self._optimized = optimizer.execute(self._raw_graph)
            else:
                self._optimized = (self._raw_graph, {})
        return self._optimized

    def _check_structure(self, graph: Graph) -> None:
        """Run the analyzer's structural tier once per executor before the
        first force: cycles, arity, fit-before-use, inverted delegate
        wiring (see `keystone_tpu.analysis`). O(V+E) and data-free, so a
        malformed plan fails in microseconds here instead of deep inside
        a run. ERROR findings raise `PipelineValidationError` (a
        ValueError, matching the old runtime checks' contract)."""
        if self._structure_checked:
            return
        from ..analysis import structural_report

        # mark checked only on success: a caller that catches the
        # validation error and retries gets the same error again, not a
        # silent unvalidated run
        structural_report(graph).raise_for_errors()
        self._structure_checked = True

    def _record_static_estimates(self, graph: Graph, tracer) -> None:
        """Embed the analyzer's per-node byte estimates (the KP2xx memory
        model, `analysis.memory`) in the trace metadata so
        `analysis.reconcile` can diff them against this run's observed
        bytes. Runs once per executor, only while tracing, and never
        fails a run: the data graph is already bound (DatasetOperators
        carry real specs), so `spec_pass` needs no placeholder sources."""
        if self._static_recorded:
            return
        self._static_recorded = True
        try:
            from ..analysis.memory import memory_pass
            from ..analysis.propagate import spec_pass
            from ..analysis.reconcile import node_key
            from ..analysis.sharding import (
                per_device_bytes,
                per_device_pass,
                sharding_pass,
                spec_str,
            )
            from ..parallel import mesh as meshlib

            specs, _ = spec_pass(graph, {})
            est, _ = memory_pass(graph, specs)
            # per-device side: propagate partition specs over the bound
            # graph and divide each node's full bytes by its shard
            # counts — the static analog of one shard's observed bytes,
            # so reconcile.py can diff per-device estimates against a
            # real mesh run
            mesh = meshlib.current_mesh()
            try:
                shardings, _, _ = sharding_pass(graph, specs, mesh=mesh)
            except Exception:
                shardings = {}
            try:
                # peak only; a failure here must not discard the specs
                # sharding_pass already propagated
                per_device_pass(graph, specs, shardings, est, mesh=mesh)
            except Exception:
                pass
            meta = tracer.metadata.setdefault(
                "static_memory",
                {"per_node": {}, "peak_bytes": 0,
                 "per_device_peak_bytes": 0})
            for vid, nbytes in est.per_node.items():
                if nbytes is None:
                    continue
                label = graph.get_operator(vid).label
                key = node_key(vid.id, label)
                prev = meta["per_node"].get(key)
                # structurally identical graphs (train/test applies)
                # collide on id:label — keep the larger estimate, matching
                # the observed side's max-over-forces semantics
                if prev is None or prev["bytes"] < int(nbytes):
                    entry = {
                        "label": label,
                        "vertex": vid.id,
                        "bytes": int(nbytes),
                    }
                    dt = _spec_dtype_name(specs.get(vid))
                    if dt is not None:
                        # the propagated boundary dtype: the precision
                        # planner's decisions (and the uint8/int32
                        # loader stages) show up in the reconcile table
                        entry["dtype"] = dt
                    sv = shardings.get(vid)
                    if sv is not None:
                        entry["spec"] = spec_str(sv)
                        pd = per_device_bytes(specs.get(vid), sv, mesh)
                        if pd is not None:
                            entry["per_device_bytes"] = int(pd)
                    meta["per_node"][key] = entry
            # several executors (fit graph, apply graph) contribute to one
            # trace; keep the largest static peak — the model's watermark
            meta["peak_bytes"] = max(meta["peak_bytes"], int(est.peak_bytes))
            meta["per_device_peak_bytes"] = max(
                meta.get("per_device_peak_bytes", 0),
                int(getattr(est, "per_device_peak_bytes", 0) or 0))
            # roofline side (KP803's trace half): per-stage flops /
            # bytes / predicted seconds, so analysis.reconcile can join
            # the time model against this run's observed span timings
            # (the flops-residual column of the drift report)
            roof = None
            try:
                from ..analysis.roofline import roofline_pass

                roof, _ = roofline_pass(graph, specs)
                rmeta = tracer.metadata.setdefault(
                    "roofline",
                    {"per_node": {}, "plan_predicted_seconds": 0.0,
                     "peak_flops": roof.machine.peak_flops,
                     "peak_bw": roof.machine.peak_bw})
                for vid, st in roof.stages.items():
                    key = node_key(vid.id, st.label)
                    prev = rmeta["per_node"].get(key)
                    # fit/apply graph id:label collisions keep the
                    # larger prediction, matching static_memory above
                    if prev is None or prev["predicted_seconds"] \
                            < st.predicted_seconds:
                        rmeta["per_node"][key] = {
                            "label": st.label,
                            "vertex": vid.id,
                            "flops": float(st.flops),
                            "hbm_bytes": int(st.hbm_bytes),
                            "intensity": float(st.intensity),
                            "bound": st.bound,
                            "predicted_seconds": float(
                                st.predicted_seconds),
                        }
                rmeta["plan_predicted_seconds"] = max(
                    rmeta["plan_predicted_seconds"],
                    float(roof.plan_seconds))
            except Exception:
                pass  # the byte estimates above must still land
            # serving side (KP903's trace half): with an envelope armed
            # (KEYSTONE_SLO_MS), embed the per-shape certified latency
            # bounds so `reconcile.reconcile_serving` can join observed
            # serving percentiles against them. Later executors
            # overwrite earlier ones: in a fit-then-serve trace the
            # apply-path executor runs last, and its certificate is the
            # one a serving run's percentiles must sit under.
            try:
                from ..analysis.serving import envelope_from_env, serving_pass

                envelope = envelope_from_env()
                if envelope is not None:
                    cert, _ = serving_pass(
                        graph, specs, envelope, memory=est,
                        roofline=roof, record=False)
                    record = cert.as_record()
                    tracer.metadata["serving"] = record
                    # live half: arm the conformance watchdog against
                    # the certificate just embedded, so every later
                    # apply in this process is checked online against
                    # its padded-shape KP903 bound (no-op when
                    # KEYSTONE_LIVE_TELEMETRY=0)
                    from ..telemetry.watchdog import (
                        maybe_arm_from_certificate,
                    )

                    maybe_arm_from_certificate(
                        record,
                        pipeline=cert.dominating_stage or "pipeline")
            except Exception:
                pass
        except Exception:  # estimation must never break execution
            pass

    def _warm_plan(self, graph: Graph) -> None:
        """AOT plan warmup: compile the optimized plan's fused programs
        on background daemon threads, overlapped with whatever the
        caller does before (and while) forcing — loader prefetch, host
        stacking — so the first chunk dispatches into a warm executable
        (`FusedBatchTransformer.warmup`; `ExecutionConfig.aot_warmup`).

        Input avals come from the static analyzer's propagated specs
        (`analysis.propagate.spec_pass` — the data graph is bound, so
        DatasetOperators carry real shapes). Covered: fused transformer
        chains whose input spec is a known on-device dataset, and
        `FusedChainOperator`s / `MegafusedPlanOperator`s whose estimator
        slots already resolved to forced saved state (the re-apply /
        serving path). A chain whose fits have NOT run yet is parked in
        ``_warm_pending`` and re-armed by `_rearm_warmup` the moment fit
        substitution completes, so the serving path is warm on its first
        force instead of being skipped for the executor's lifetime.
        Warmup must never break execution: every failure is swallowed
        (the force would just compile inline, exactly as without it)."""
        if self._warmed:
            return
        self._warmed = True
        if not execution_config().aot_warmup:
            return
        if self._warm_scope is not None:
            # one scan per scope × ladder signature: program caches are
            # global and structure-keyed, so the first scan's warmups
            # cover every later executor bound from the same fitted
            # graph. A scope applying at a count outside the first
            # scan's targets compiles that program inline exactly once —
            # the same end state, minus a background thread per request.
            sig = tuple(_serving_warm_counts())
            try:
                with _warm_scope_lock:
                    seen = _warm_scope_seen.setdefault(
                        self._warm_scope, set())
                    if sig in seen:
                        return
                    seen.add(sig)
            except TypeError:
                pass  # unweakrefable scope: fall through and scan

        def scan_and_warm():
            # the whole scan — including the spec_pass eval_shape traces
            # — runs off the caller's thread; the graph is immutable and
            # warmup compiles rendezvous with any concurrent force via
            # the pending-future registry
            try:
                from ..analysis.propagate import spec_pass
                from ..analysis.specs import DataSpec, is_known
                from ..nodes.util.fusion import FusedBatchTransformer
                from .fusion_rule import FusedChainOperator
                from .operators import ExpressionOperator

                _PENDING = "pending"

                def warm_target(op, deps):
                    """('ready', transformer, data dep) |
                    ('pending', chain op, est deps, data dep) | None."""
                    if isinstance(op, FusedChainOperator) and deps:
                        fitted = []
                        for est_dep in deps[:-1]:
                            if not isinstance(est_dep, NodeId):
                                return None
                            eop = graph.get_operator(est_dep)
                            if not (isinstance(eop, ExpressionOperator)
                                    and eop.expression.is_forced):
                                # fits unresolved at scan time: parked,
                                # re-armed once the fits force
                                return (_PENDING, op,
                                        tuple(deps[:-1]), deps[-1])
                            fitted.append(eop.expression.get)
                        mat = op.materialize(fitted)
                        if isinstance(mat, FusedBatchTransformer):
                            return ("ready", mat, deps[-1])
                        return None
                    if isinstance(op, FusedBatchTransformer):
                        return ("ready", op, deps[0]) \
                            if len(deps) == 1 else None
                    return None

                targets, parked = [], []
                for vid in graph.operators:
                    t = warm_target(graph.get_operator(vid),
                                    graph.get_dependencies(vid))
                    if t is None:
                        continue
                    (targets if t[0] == "ready" else parked).append(t[1:])
                if not targets and not parked:
                    return
                specs, _ = spec_pass(graph, {})
                # serving-manifest expansion: an armed envelope
                # (KEYSTONE_SLO_MS) widens every program site's warm
                # count to the whole pad ladder, so ANY in-envelope
                # request shape dispatches into a warm executable
                serving_counts = _serving_warm_counts()

                def data_spec(data_dep):
                    s = specs.get(data_dep)
                    if (isinstance(s, DataSpec) and s.kind == "dataset"
                            and s.on_device and is_known(s.element)
                            and s.count):
                        return s
                    return None

                for op, data_dep in targets:
                    s = data_spec(data_dep)
                    if s is not None:
                        _submit_warmup(op, s.element,
                                       [s.count, *serving_counts])
                for op, est_deps, data_dep in parked:
                    s = data_spec(data_dep)
                    if s is None:
                        continue
                    with self._warm_lock:
                        self._warm_pending.append({
                            "op": op, "est_deps": est_deps,
                            "element": s.element, "count": s.count,
                        })
                        self._warm_est_watch.update(est_deps)
            except Exception:
                pass

        _spawn_warm_thread(scan_and_warm, "keystone-aot-warmup-scan")

    def _rearm_warmup(self) -> None:
        """Re-arm AOT warmup for fused-chain programs whose estimator
        slots resolved AFTER the warm scan ran: once every watched fit
        expression is forced, materialize the chain against the fitted
        transformers and submit its compile — so a re-apply through this
        executor (and the first force after concurrent fits complete)
        dispatches into a warm executable. Cheap when nothing is
        pending; never raises."""
        if not self._warm_pending:
            return
        if not execution_config().aot_warmup:
            return
        from ..nodes.util.fusion import FusedBatchTransformer
        from .expressions import TransformerExpression

        with self._warm_lock:
            pending, self._warm_pending = self._warm_pending, []
        still: List[dict] = []
        serving_counts = _serving_warm_counts()
        for ent in pending:
            exprs = [self._memo.get(d) for d in ent["est_deps"]]
            if all(isinstance(e, TransformerExpression) and e.is_forced
                   for e in exprs):
                try:
                    mat = ent["op"].materialize([e.get for e in exprs])
                    if isinstance(mat, FusedBatchTransformer):
                        _submit_warmup(mat, ent["element"],
                                       [ent["count"], *serving_counts])
                except Exception:
                    pass
            else:
                still.append(ent)
        if still:
            with self._warm_lock:
                self._warm_pending.extend(still)

    def warm_manifest(self, manifest) -> int:
        """Feed an explicit `analysis.serving.warmup_manifest()`
        enumeration to the AOT warmer: each entry names a fused program
        site (vertex id + label), the element spec its programs trace
        from, and every pad-ladder count the envelope can produce — the
        serving runtime's pre-traffic warm step. Entries are resolved
        against this executor's optimized plan by vertex id, falling
        back to operator label (the manifest may have been computed on
        the raw graph whose fused projection renumbered vertices).
        Returns the number of program sites submitted; never raises."""
        graph, _ = self._optimized_plan()
        from ..nodes.util.fusion import FusedBatchTransformer
        from .expressions import TransformerExpression
        from .fusion_rule import FusedChainOperator
        from .operators import ExpressionOperator

        def resolve(entry):
            by_label = None
            for vid in graph.operators:
                op = graph.get_operator(vid)
                if not isinstance(op, (FusedBatchTransformer,
                                       FusedChainOperator)):
                    continue
                if vid.id == entry.get("vertex"):
                    return vid, op
                if by_label is None and op.label == entry.get("label"):
                    by_label = (vid, op)
            return by_label

        submitted = 0
        for entry in manifest or ():
            try:
                hit = resolve(entry)
                if hit is None:
                    continue
                vid, op = hit
                if isinstance(op, FusedChainOperator):
                    fitted = []
                    for dep in graph.get_dependencies(vid)[:-1]:
                        # a fitted plan carries its fits as forced
                        # ExpressionOperators; a live executor may hold
                        # them in the memo instead
                        eop = (graph.get_operator(dep)
                               if isinstance(dep, NodeId) else None)
                        expr = (eop.expression
                                if isinstance(eop, ExpressionOperator)
                                else self._memo.get(dep))
                        if not (isinstance(expr, TransformerExpression)
                                and expr.is_forced):
                            fitted = None
                            break
                        fitted.append(expr.get)
                    if fitted is None:
                        continue
                    op = op.materialize(fitted)
                    if not isinstance(op, FusedBatchTransformer):
                        continue
                _submit_warmup(op, entry["element"], entry["counts"])
                submitted += 1
            except Exception:
                continue
        return submitted

    def execute(self, graph_id: GraphId) -> Expression:
        """Execute up to ``graph_id``, returning its lazy Expression
        (GraphExecutor.scala:53-80). The plan, the warm-up scan and the
        walk are the ``force`` layer's, with the root's force
        (`_arm_concurrent`); the optimizer inside is its own layer. The
        layer's parts: ``prepare`` (the structure check and the warm-up
        scan) and ``walk`` (the graph walk and arming the root)."""
        from ..telemetry import span

        with span("execute", cat="phase", layer="force"):
            return self._execute(graph_id)

    def _execute(self, graph_id: GraphId) -> Expression:
        from ..telemetry import span

        graph, prefixes = self._optimized_plan()
        with span("prepare", cat="phase", layer="force", part="prepare"):
            self._check_structure(graph)
            self._warm_plan(graph)
            self._rearm_warmup()  # fits may have resolved since the scan
        env = PipelineEnv.get()
        profiler = getattr(env, "profiler", None)
        from ..telemetry import current_tracer
        from ..telemetry.instrument import instrument_node_force

        tracer = current_tracer()
        if tracer is not None:
            self._record_static_estimates(graph, tracer)
        observing = tracer is not None or profiler is not None

        def go(vid: GraphId) -> Expression:
            if vid in self._memo:
                return self._memo[vid]
            if isinstance(vid, SourceId):
                raise ValueError(
                    f"{vid} is an unbound source; bind data by applying the pipeline"
                )
            if isinstance(vid, SinkId):
                expr = go(graph.get_sink_dependency(vid))
            else:
                dep_exprs = [go(d) for d in graph.get_dependencies(vid)]
                op = graph.get_operator(vid)
                expr = op.execute(dep_exprs)
                if observing:
                    expr = instrument_node_force(
                        op.label, expr, vertex=vid.id, profiler=profiler)
                prefix = prefixes.get(vid)
                if prefix is not None and prefix not in env.state:
                    env.state[prefix] = expr
            self._memo[vid] = expr
            return expr

        with span("walk", cat="phase", layer="force", part="walk"):
            root = go(graph_id)
            self._arm_concurrent(graph_id, root, graph)
        return root

    # ---------------------------------------------------- concurrent force

    def _arm_concurrent(self, root_id: GraphId, root: Expression,
                        graph: Graph) -> None:
        """Hook the concurrent scheduler into ``root``'s force (or first
        chunk drain), preserving laziness: nothing runs until the caller
        forces the result, exactly as on the serial path. Wrapping is
        idempotent per root; the on/off decision is read from the live
        `ExecutionConfig` at force time so scoped overrides
        (`dispatch_override`) behave."""
        if root_id in self._concurrent_wrapped or root.is_forced:
            return
        self._concurrent_wrapped.add(root_id)
        from ..telemetry import span

        node = (graph.get_sink_dependency(root_id)
                if isinstance(root_id, SinkId) else root_id)
        name = "root " + (graph.get_operator(node).label
                          if isinstance(node, NodeId) else str(node))

        def prefetch():
            if getattr(_sched_local, "active", False):
                return  # a pool worker forcing this root: its ancestors
                # are already ordered by the schedule that claimed it
            cfg = execution_config()
            if cfg.concurrent_dispatch and cfg.dispatch_workers > 1:
                self._force_concurrent(root_id, graph, cfg.dispatch_workers)

        # the root's force is the `force` layer's span, always on: what
        # the executor and the nodes' own host code take, less the
        # dispatch, sync, solver and optimize spans opened inside it
        chunks_thunk = getattr(root, "_chunks_thunk", None)
        if chunks_thunk is not None:
            def chunks(orig=chunks_thunk):
                # a stream's chunks are pulled between the consumer's own
                # work, so only the schedule and the stream's start are
                # under the span; each chunk's dispatch has its own
                with span(name, cat="phase", layer="force", streamed=True):
                    prefetch()
                    return orig()

            root._chunks_thunk = chunks
        elif root._thunk is not None:
            def thunk(orig=root._thunk):
                with span(name, cat="phase", layer="force"):
                    prefetch()
                    return orig()

            root._thunk = thunk

    def _schedule_plan(self, root_id: GraphId, graph: Graph):
        """Partition the root's ancestor sub-DAG into worker tasks.

        Returns ``(tasks, eff_deps)`` where ``tasks`` is the topo-ordered
        list of vertices the pool must force and ``eff_deps[v]`` the set
        of *tasks* that must complete first. Vertices are *deferred*
        (absorbed into their consumer's task) when forcing them eagerly
        would change semantics or defeat the overlap engine:

          - already-forced expressions (nothing to do),
          - a non-forced streaming expression with exactly one consumer
            in scope — its chunks must keep draining lazily into that
            consumer (fan-out streams ARE forced here, so two racing
            consumers can never interleave `iter_chunks`),
          - the root itself (the caller's force runs it).
        """
        from .analysis import linearize

        order = [v for v in linearize(graph, root_id)
                 if not isinstance(v, SourceId)]
        scope = set(order)

        def vertex_deps(v) -> List[GraphId]:
            if isinstance(v, SinkId):
                deps = [graph.get_sink_dependency(v)]
            else:
                deps = list(graph.get_dependencies(v))
            return [d for d in dict.fromkeys(deps) if d in scope]

        users: Dict[GraphId, int] = {}
        for v in order:
            for d in vertex_deps(v):
                users[d] = users.get(d, 0) + 1

        # Which vertices can yield a genuine multi-chunk stream? Most
        # device stages are wrapped in StreamingDatasetExpression but
        # materialize as ONE whole-value chunk — forcing those on the
        # pool is free concurrency. Only a stage that may actually
        # produce chunks (a stream origin: bucketed host dispatchers) or
        # pass them through (chunkable, fed by a may-stream dep) must
        # stay lazy so the overlap engine keeps draining it into its
        # consumer chunk-by-chunk.
        from ..analysis.hazards import _is_stream_origin

        may_stream: Dict[GraphId, bool] = {}
        for v in order:  # topo: deps resolved before dependents
            if isinstance(v, SinkId):
                may_stream[v] = any(
                    may_stream.get(d, False) for d in vertex_deps(v))
                continue
            op = graph.get_operator(v)
            cap = getattr(op, "may_consume_chunks",
                          getattr(op, "chunkable", False))
            may_stream[v] = _is_stream_origin(op) or (
                bool(cap)
                and any(may_stream.get(d, False) for d in vertex_deps(v))
            )

        deferred = set()
        root_expr = self._memo.get(root_id)
        for v in order:
            expr = self._memo.get(v)
            if expr is None or expr.is_forced:
                deferred.add(v)
            elif v == root_id or expr is root_expr:
                # the caller forces the root (a sink shares its dep
                # node's Expression object — both ARE the root); keeping
                # it off the pool also keeps its span nesting serial
                deferred.add(v)
            elif isinstance(expr, StreamingDatasetExpression) \
                    and users.get(v, 0) <= 1 and may_stream.get(v, False):
                deferred.add(v)

        eff_memo: Dict[GraphId, frozenset] = {}

        def eff_deps(v) -> frozenset:
            got = eff_memo.get(v)
            if got is None:
                out = set()
                for d in vertex_deps(v):
                    if d in deferred:
                        out |= eff_deps(d)
                    else:
                        out.add(d)
                got = eff_memo[v] = frozenset(out)
            return got

        tasks = [v for v in order if v not in deferred]
        return tasks, {v: eff_deps(v) for v in tasks}

    def _force_concurrent(self, root_id: GraphId, graph: Graph,
                          workers: int) -> None:
        """Force the root's ancestor tasks with a bounded worker pool in
        topological order (see module docstring for the guarantees)."""
        tasks, eff_deps = self._schedule_plan(root_id, graph)
        if len(tasks) < 2:
            return
        # nested schedules never reach here: a pool worker re-entering a
        # wrapped root skips its prefetch() (the _sched_local.active
        # guard in _arm_concurrent), so forcing proceeds depth-first on
        # that worker — concurrency already exists one level up.

        from ..telemetry import counter, span
        from ..telemetry.spans import adopt_layer_parent, layer_parent

        topo_index = {v: i for i, v in enumerate(tasks)}
        indeg = {v: len(eff_deps[v]) for v in tasks}
        dependents: Dict[GraphId, List[GraphId]] = {v: [] for v in tasks}
        for v in tasks:
            for d in eff_deps[v]:
                dependents[d].append(v)

        cond = threading.Condition()
        ready = sorted((v for v in tasks if indeg[v] == 0),
                       key=topo_index.__getitem__)
        outstanding = len(tasks)
        failures: List[Tuple[int, BaseException]] = []
        stop = False

        # the caller waits for the pool inside its own span: what the
        # workers do is charged to their layers, not to the wait as well
        waiting_in = layer_parent()

        def worker():
            nonlocal outstanding, stop
            _sched_local.active = True
            adopt_layer_parent(waiting_in)
            try:
                while True:
                    with cond:
                        while not ready and outstanding and not stop:
                            cond.wait()
                        if not ready or stop:
                            return
                        v = ready.pop(0)
                    err = None
                    try:
                        self._memo[v].get
                    except BaseException as e:  # recorded, raised in order
                        err = e
                    if err is None and v in self._warm_est_watch:
                        # a watched fit just resolved: re-arm the parked
                        # chain warmup so its compile overlaps the rest
                        # of the schedule instead of the first force
                        self._rearm_warmup()
                    with cond:
                        outstanding -= 1
                        if err is not None:
                            failures.append((topo_index[v], err))
                            stop = True  # serial would not run past here
                        else:
                            for u in dependents[v]:
                                indeg[u] -= 1
                                if indeg[u] == 0:
                                    ready.append(u)
                            ready.sort(key=topo_index.__getitem__)
                        cond.notify_all()
            finally:
                _sched_local.active = False

        counter("dispatch.scheduler_runs").inc()
        counter("dispatch.scheduled_tasks").inc(len(tasks))
        n = min(workers, len(tasks))
        with span("dispatch.schedule", cat="phase", tasks=len(tasks),
                  workers=n):
            threads = [
                threading.Thread(target=worker,
                                 name=f"keystone-dispatch-{i}", daemon=True)
                for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        if failures:
            # deterministic across worker counts for a single failing
            # vertex; with several, the earliest scheduled failure wins —
            # the vertex a depth-first serial force reaches first
            raise min(failures, key=lambda f: f[0])[1]

    def execute_stream(self, graph_id: GraphId):
        """Execute up to ``graph_id``, yielding ``(indices, payload)``
        chunks as the terminal stage drains (overlap engine) instead of
        materializing the full stage. Non-streaming terminals yield one
        ``(None, value)`` whole-value chunk, so consumers can treat every
        pipeline uniformly."""
        expr = self.execute(graph_id)
        if isinstance(expr, StreamingDatasetExpression):
            yield from expr.iter_chunks()
        else:
            yield None, expr.get
