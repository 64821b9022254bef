"""Process-global pipeline environment and structural prefixes.

`Prefix` (reference workflow/Prefix.scala:4-30) is a structural hash of a
node's full ancestry — operator identity plus the prefixes of its
dependencies. It is the key for cross-pipeline fitted-state reuse: every
Cacher/Estimator output is memoized in `PipelineEnv.state` under its prefix
and swapped back in by `SavedStateLoadRule` on later optimizations, so
re-applying or extending a pipeline never refits
(reference PipelineEnv.scala:7-45, ExtractSaveablePrefixes.scala:9-22).

Graphs are immutable and the prefix table is only *mutated* on the
thread that wires a pipeline's expressions (Pipeline.scala:14,
PipelineEnv.scala:11); the concurrent DAG scheduler (executor.py) only
ever *forces* already-wired expressions from its worker pool, each
vertex by exactly one worker, so the tables never see a cross-thread
read-modify-write.
"""

from __future__ import annotations

import os
from contextlib import contextmanager
from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple

from .expressions import Expression
from .graph import Graph, NodeId, SourceId


# --------------------------------------------------------------------------
# Execution configuration (overlapped execution engine)


@dataclass(frozen=True)
class ExecutionConfig:
    """Knobs for the overlapped execution engine (utils/batching.py).

    ``overlap`` (default on; env ``KEYSTONE_OVERLAP=0`` disables) turns on
    the async double-buffered host→device dispatcher: a background thread
    stacks/uploads chunk k+1 while the device runs chunk k, result pulls
    are deferred and drained in order, loaders prefetch decode work
    through a bounded queue, and forced Expressions stream per-chunk
    results to chunk-capable consumers. Single-chunk inputs always take
    the serial path, so the flag only changes *when* work happens, never
    what is computed.

    ``prefetch_depth`` bounds every background queue and the in-flight
    result window (which holds up to depth + 1 dispatched results),
    capping peak host memory at O(depth × chunk) items — at most
    2·depth + 2 chunks resident per stage (env
    ``KEYSTONE_PREFETCH_DEPTH``).

    ``hbm_budget_bytes`` is the per-host accelerator memory budget the
    static analyzer lints against (KP201/KP202, see
    `keystone_tpu.analysis`); env ``KEYSTONE_HBM_BUDGET_GB`` (float,
    GiB). None disables budget warnings.

    ``trace_path`` (env ``KEYSTONE_TRACE``) arms the telemetry layer's
    ambient tracer: the process collects hierarchical spans + metrics
    and writes Chrome trace-event JSON to this path at exit (see
    `keystone_tpu.telemetry` and OBSERVABILITY.md). None disables
    tracing (the instrumented hot paths reduce to one global read).

    ``concurrent_dispatch`` (default on; env
    ``KEYSTONE_CONCURRENT_DISPATCH=0`` reverts to the serial recursive
    force) turns on the executor's concurrent DAG scheduler: independent
    subgraphs of a forced pipeline are forced by a bounded worker pool
    in topological order, so multiple XLA programs stay in flight
    instead of dispatching strictly one node at a time.
    Results are deterministic (each vertex is forced exactly once, by
    exactly one worker, after all of its dependencies) and single-user
    streaming stages keep their lazy chunk flow (see
    `GraphExecutor._force_concurrent`).

    ``dispatch_workers`` bounds the scheduler's pool (env
    ``KEYSTONE_DISPATCH_WORKERS``, default 4; values <= 1 force the
    serial path).

    ``chunk_size`` is the library-wide host-batching chunk row count
    (`utils.batching.map_host_batched`'s dispatch granularity AND the
    static memory model's streaming-chunk assumption — one number, read
    by both, so the analyzer can never model a different chunking than
    the runtime executes). Env ``KEYSTONE_CHUNK_SIZE``, default 256.

    ``pad_chunks`` (default on; env ``KEYSTONE_PAD_CHUNKS=0`` disables)
    turns on shape-stable chunk dispatch: each shape bucket's ragged
    tail chunk is zero-padded up to the chunk size (tiny buckets round
    up a power-of-two ladder instead), so a stage compiles ONE program
    per bucket shape regardless of item count — without it every
    distinct ``bucket_size % chunk`` residue compiles its own XLA
    program. Padded rows are sliced off before any consumer sees them,
    so outputs are identical either way.

    ``aot_warmup`` (default on; env ``KEYSTONE_AOT_WARMUP=0`` disables)
    compiles the optimized plan's fused programs ahead of time: at
    execute time the static analyzer's propagated specs are lowered via
    ``jit(...).lower(abstract).compile()`` on a background pool, so the
    first chunk dispatches into a warm executable instead of blocking on
    a cold compile while the loaders sit idle.

    ``compile_cache_dir`` arms jax's persistent compilation cache
    (``jax_compilation_cache_dir``) so repeated *processes* skip XLA
    compilation entirely. The default is the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else the fixed repo-local
    ``<repo>/.keystone_compile_cache``; env
    ``KEYSTONE_COMPILE_CACHE=0``/``off``/``false`` disables the cache
    and names no directory. Compile activity is
    measured either way (``dispatch.programs_compiled``, see
    `keystone_tpu.telemetry.compile_events`).

    ``megafusion`` (default on; env ``KEYSTONE_MEGAFUSION=0`` reverts to
    the PR-4/5 plan) turns on whole-plan megafusion: when a fitted
    pipeline's apply plan is a fan-out-free chain of fusable stages
    whose chunks are shape-stable (the ``pad_chunks`` contract), the
    optimizer's `MegafusionRule` collapses the ENTIRE apply path —
    featurize → scale → linear → argmax, *including the chunk loop as an
    in-program ``lax.scan``* — into one donated XLA program
    (`MegafusedPlanOperator`), and the host batcher hands a bucket's
    whole padded chunk stack to one scan-bodied program instead of
    dispatching per chunk. Ineligible plans (streaming single-consumer
    stages, host-code stages, fan-out) keep the per-program dispatch
    path and `validate()` says why (KP401).

    ``sharding_planner`` (default on; env ``KEYSTONE_SHARDING_PLANNER=0``
    reverts to the PR-8 plan bit-for-bit) turns on the sharding-aware
    plan optimizer: after fusion/megafusion, `ShardingPlannerRule`
    enumerates legal per-stage placements (data-sharded, model-sharded,
    2-D data×model, replicated), prices each assignment with the KP6xx
    boundary-collective cost model under the KP600 per-device budget
    (`analysis.planner`), and — only when the chosen assignment
    strictly beats the default placement's priced boundary bytes —
    enforces it: ``with_sharding_constraint`` on fused/megafused
    program outputs, explicit `collectives.reshard` of plan-input
    datasets. A 1-device mesh, an unimproved plan, or a planner failure
    all leave the plan untouched.

    ``precision_planner`` (default on; env ``KEYSTONE_PRECISION_PLANNER=0``
    reverts to the PR-9 plan bit-for-bit) turns on the mixed-precision
    policy pass: after the sharding planner, `PrecisionPlannerRule`
    assigns each fused/megafused program's internal stage boundaries a
    storage dtype from the legal menu (bf16 where every adjacent stage
    declares/probes tolerance, f32 everywhere a solver, moments stage,
    or label stage pins exactness — `analysis.precision`), prices each
    assignment by the bytes the boundary moves, and bakes winning
    policies into the compiled program as ``convert_element_type``
    casts (cache-keyed, AOT-warmable, jaxpr-visible). A no-win plan, a
    planner failure, or the kill switch leave the program untouched.

    ``precision_min_savings_bytes`` (env
    ``KEYSTONE_PRECISION_MIN_SAVINGS_BYTES``, default 1 MiB) is the
    enforcement floor: a policy is only baked into a program when its
    priced savings clear it. Tiny pipelines (tests, smoke runs) stay
    bit-identical to the PR-9 programs by construction; real featurize
    workloads clear the floor trivially. 0 enforces every strict win.

    ``ledger_path`` (env ``KEYSTONE_LEDGER``) arms the decision ledger's
    JSONL artifact: every optimizer decision (fusion chain, megafusion,
    placement, precision policy) is appended as one structured record —
    kind, affected vertices, the chosen entry AND its priced
    alternatives, predicted cost in the shared units — after a run
    header that snapshots the optimizer config (the ``--diff``
    kill-switch channel). None defers to the default: a traced run
    writes ``<trace_path>.ledger.jsonl`` alongside the trace artifact;
    an untraced, unarmed run keeps records in memory only (see
    `keystone_tpu.telemetry.ledger` and OBSERVABILITY.md).

    ``unified_planner`` (default on; env ``KEYSTONE_UNIFIED_PLANNER=0``
    reverts to the PR-13 sequential passes bit-for-bit) turns on the
    unified plan optimizer: after fusion/megafusion, `UnifiedPlannerRule`
    solves ONE decision IR spanning {placement family × storage dtype ×
    chunk size × cache point} per stage boundary (`analysis.plan_ir`),
    priced in seconds by the calibrated roofline time model
    (`roofline.stage_cost` + `collective_cost` seconds at family flips)
    under the declared HBM budget as a hard per-device constraint. When
    the joint optimum strictly beats the sequential composition it owns
    enforcement (placement/precision tags, the chunk override below,
    `CacheMarker` insertion) and the sequential planner rules stand
    down; otherwise the sequential rules run unchanged.

    ``unified_min_savings_seconds`` (env
    ``KEYSTONE_UNIFIED_MIN_SAVINGS_S``, default 5 ms) is the unified
    planner's enforcement floor: a joint win is only enforced when its
    predicted seconds saved clear it, so tiny pipelines (tests, smoke
    runs) stay bit-identical to the sequential plan by construction.
    0 enforces every strict win.

    ``pallas_kernels`` (default on; env ``KEYSTONE_CHAIN_KERNELS=0``
    kills, ledger-header recorded so ``--diff`` can name the flip) is
    the ONE master switch for every Pallas kernel the library owns:
    the single-op kernels in ``ops/pallas_kernels.py`` (their
    per-kernel env knobs remain as documented overrides UNDER this
    switch) and the planned chain megakernels in
    ``ops/chain_kernels.py``. Off-TPU the chain kernels are
    interpret-validated only — the planner still prices and records the
    kernel-vs-XLA decision, but built programs keep the XLA body unless
    ``KEYSTONE_CHAIN_KERNELS=interpret`` forces the interpret-mode swap
    (the e2e test hook). ``=0`` is bit-for-bit: programs are exactly
    the XLA form.

    ``live_telemetry`` (``KEYSTONE_LIVE_TELEMETRY``) arms the live
    telemetry plane: the bounded flight recorder, streaming latency
    sketches, per-apply request spans, and the KP9xx conformance
    watchdog (``telemetry/flight.py`` / ``streaming.py`` /
    ``watchdog.py``). ``=0`` is bit-for-bit the post-hoc-only behavior:
    no request spans, no sketch updates, no watchdog checks.

    ``serving_coalesce`` (default on; env ``KEYSTONE_SERVING_COALESCE=0``
    kills, ledger-header recorded) turns on the serving runtime's
    continuous micro-batching: concurrent single-item requests coalesce
    through the bounded ingress queue into batches padded onto the
    certificate's pow-2 pad ladder, so a warm server dispatches ONE
    pre-compiled program per coalesced batch instead of one per
    request. ``=0`` is bit-for-bit: every request dispatches alone on
    its caller thread, exactly a direct ``FittedPipeline.apply``.

    ``serving_queue_depth`` (env ``KEYSTONE_SERVING_QUEUE_DEPTH``,
    default 256) bounds the serving ingress queue — the load-shed
    discipline (jaxlint KJ019): a full queue REFUSES the request
    (``serving.shed_total`` counted, flight ring dumped) instead of
    growing host memory until latency collapses.

    ``serving_window_ms`` (env ``KEYSTONE_SERVING_WINDOW_MS``, default
    2.0) is the coalescing window: after the first queued request, the
    batcher waits at most this long for followers before dispatching.
    0 dispatches whatever is queued immediately (lowest latency, least
    coalescing).

    ``ooc_spill`` (default on; env ``KEYSTONE_OOC_SPILL=0`` kills,
    ledger-header recorded so ``--diff`` can name the flip) turns on the
    out-of-core spill tier of the unified plan optimizer: cache points
    may be placed on the HOST (`CacheMarker(placement="host")`), priced
    by the calibrated host↔device bandwidth (reload bytes / host_bw +
    one dispatch floor per window trip) and charged at window-residency
    instead of full-residency by the KP2xx/KP600 live-set model — so a
    plan whose pinned caches bust ``hbm_budget_bytes`` can become
    *feasible* by spilling instead of being rejected. ``=0`` is
    bit-for-bit the device-only menu: no spill entries are priced, no
    host placements are enforced, and the chosen plan is exactly what
    the PR-19 optimizer produced.
    """

    overlap: bool = True
    prefetch_depth: int = 2
    hbm_budget_bytes: Optional[int] = None
    trace_path: Optional[str] = None
    concurrent_dispatch: bool = True
    dispatch_workers: int = 4
    chunk_size: int = 256
    pad_chunks: bool = True
    aot_warmup: bool = True
    compile_cache_dir: Optional[str] = None
    megafusion: bool = True
    sharding_planner: bool = True
    precision_planner: bool = True
    precision_min_savings_bytes: int = 1 << 20
    ledger_path: Optional[str] = None
    unified_planner: bool = True
    unified_min_savings_seconds: float = 5e-3
    pallas_kernels: bool = True
    live_telemetry: bool = True
    serving_coalesce: bool = True
    serving_queue_depth: int = 256
    serving_window_ms: float = 2.0
    ooc_spill: bool = True


_exec_config: Optional[ExecutionConfig] = None

_OFF = ("0", "false", "off")


def _default_compile_cache_dir() -> str:
    """Repo-local persistent-cache default: next to the package, so the
    cache survives across runs of the same checkout without polluting
    the user's home directory."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        ".keystone_compile_cache",
    )


def _env_compile_cache_dir() -> Optional[str]:
    """Where the persistent cache lives: the directory the caller gave
    jax itself (``JAX_COMPILATION_CACHE_DIR``), else the fixed
    repo-local default — the path is part of a cache entry's key, so a
    directory that moves never hits. None when
    ``KEYSTONE_COMPILE_CACHE`` switches the cache off."""
    if os.environ.get("KEYSTONE_COMPILE_CACHE", "").lower() in _OFF:
        return None
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _default_compile_cache_dir())


#: what `_sync_compile_cache` last applied; the sentinel means "nothing
#: yet", which differs from None ("switched off")
_NOT_APPLIED = object()
_compile_cache_applied: object = _NOT_APPLIED


def _sync_compile_cache(cfg: ExecutionConfig) -> None:
    """Bring jax's persistent compilation cache in line with
    ``cfg.compile_cache_dir`` (idempotent; None switches it off). A
    directory jax already has — the one ``JAX_COMPILATION_CACHE_DIR``
    gave it — is never set again from here.
    The min-compile-time / min-entry-size floors are zeroed so the
    sub-second CPU programs this library dispatches get cached too;
    without that only multi-second TPU compiles would persist and the
    warm-run == 0-compiles contract would silently not hold on the CPU
    tier-1 path."""
    global _compile_cache_applied
    path = cfg.compile_cache_dir
    if path == _compile_cache_applied:
        return
    _compile_cache_applied = path
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", path is not None)
    # a Mosaic call's key holds its kernel's body, each op located by the
    # stack that traced it: past the kernel's frame, a warm-up thread's or
    # the dispatch's. One frame keeps the key whoever traces the kernel
    jax.config.update("jax_traceback_in_locations_limit", 1)
    if path is not None:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    # jax's cache object binds its directory and its on/off verdict at
    # first use; after a change it must be reset or writes keep landing
    # in the old (possibly deleted) directory
    compilation_cache.reset_cache()


def execution_config() -> ExecutionConfig:
    global _exec_config
    if _exec_config is None:
        _exec_config = ExecutionConfig(
            overlap=os.environ.get("KEYSTONE_OVERLAP", "1").lower()
            not in _OFF,
            prefetch_depth=max(
                1, int(os.environ.get("KEYSTONE_PREFETCH_DEPTH", "2"))
            ),
            hbm_budget_bytes=(
                int(float(os.environ["KEYSTONE_HBM_BUDGET_GB"]) * (1 << 30))
                if os.environ.get("KEYSTONE_HBM_BUDGET_GB")
                else None
            ),
            trace_path=os.environ.get("KEYSTONE_TRACE") or None,
            concurrent_dispatch=os.environ.get(
                "KEYSTONE_CONCURRENT_DISPATCH", "1").lower()
            not in _OFF,
            dispatch_workers=max(
                1, int(os.environ.get("KEYSTONE_DISPATCH_WORKERS", "4"))
            ),
            chunk_size=max(
                1, int(os.environ.get("KEYSTONE_CHUNK_SIZE", "256"))
            ),
            pad_chunks=os.environ.get("KEYSTONE_PAD_CHUNKS", "1").lower()
            not in _OFF,
            aot_warmup=os.environ.get("KEYSTONE_AOT_WARMUP", "1").lower()
            not in _OFF,
            compile_cache_dir=_env_compile_cache_dir(),
            megafusion=os.environ.get("KEYSTONE_MEGAFUSION", "1").lower()
            not in _OFF,
            sharding_planner=os.environ.get(
                "KEYSTONE_SHARDING_PLANNER", "1").lower() not in _OFF,
            precision_planner=os.environ.get(
                "KEYSTONE_PRECISION_PLANNER", "1").lower() not in _OFF,
            precision_min_savings_bytes=max(0, int(os.environ.get(
                "KEYSTONE_PRECISION_MIN_SAVINGS_BYTES", str(1 << 20)))),
            ledger_path=os.environ.get("KEYSTONE_LEDGER") or None,
            unified_planner=os.environ.get(
                "KEYSTONE_UNIFIED_PLANNER", "1").lower() not in _OFF,
            unified_min_savings_seconds=max(0.0, float(os.environ.get(
                "KEYSTONE_UNIFIED_MIN_SAVINGS_S", "5e-3"))),
            pallas_kernels=os.environ.get(
                "KEYSTONE_CHAIN_KERNELS", "1").lower() not in _OFF,
            live_telemetry=os.environ.get(
                "KEYSTONE_LIVE_TELEMETRY", "1").lower() not in _OFF,
            serving_coalesce=os.environ.get(
                "KEYSTONE_SERVING_COALESCE", "1").lower() not in _OFF,
            serving_queue_depth=max(1, int(os.environ.get(
                "KEYSTONE_SERVING_QUEUE_DEPTH", "256"))),
            serving_window_ms=max(0.0, float(os.environ.get(
                "KEYSTONE_SERVING_WINDOW_MS", "2.0"))),
            ooc_spill=os.environ.get(
                "KEYSTONE_OOC_SPILL", "1").lower() not in _OFF,
        )
        _sync_compile_cache(_exec_config)
    return _exec_config


def set_execution_config(config: Optional[ExecutionConfig]) -> None:
    """Install ``config`` process-wide; None re-derives from the env."""
    global _exec_config
    _exec_config = config
    if config is not None:
        _sync_compile_cache(config)


# --------------------------------------------------------------------------
# Planned chunk size (the unified plan optimizer's chunk decision)

#: the chunk size the most recently enforced unified plan chose, or
#: None when no plan owns the knob. Process-global like the optimizer
#: itself: the LAST optimized plan's decision is the live one, so
#: optimizing a second pipeline re-decides (or clears) the knob for
#: everything that dispatches afterwards — interleave two live
#: pipelines and the later optimize wins, exactly like the process-
#: global `PipelineEnv` optimizer. In-flight streams are safe either
#: way: `utils.batching` resolves the chunk ONCE when a stream's plan
#: is built, so a mid-run flip only affects new dispatches.
_planned_chunk: Optional[int] = None


def set_planned_chunk_size(chunk: Optional[int]) -> None:
    """Install (or clear, with None) the unified planner's chunk
    decision. Only `workflow.optimizer.UnifiedPlannerRule` should call
    this at enforcement time — everything else reads the resolved value
    through `resolved_chunk_size` (the KJ015 contract)."""
    global _planned_chunk
    _planned_chunk = max(1, int(chunk)) if chunk is not None else None


def planned_chunk_size() -> Optional[int]:
    """The unified planner's live chunk decision — None when no plan
    owns the knob or the unified planner is switched off
    (``KEYSTONE_UNIFIED_PLANNER=0`` must restore the config knob
    bit-for-bit, stale overrides included)."""
    if _planned_chunk is not None and execution_config().unified_planner:
        return _planned_chunk
    return None


def resolved_chunk_size() -> int:
    """THE chunk-size resolution: the unified planner's enforced
    decision when one is live, else ``ExecutionConfig.chunk_size``
    (env ``KEYSTONE_CHUNK_SIZE``). The host batcher
    (`utils.batching`), the KP2xx memory model
    (`analysis.memory.resolve_chunk_rows`), and the roofline's trip
    accounting all read this one function, so the analyzer can never
    model a different chunking than the runtime executes and the
    planner's decision reaches both from one place (jaxlint KJ015
    keeps ad-hoc readers out of ``nodes/``/``workflow/``)."""
    planned = planned_chunk_size()
    if planned is not None:
        return planned
    return execution_config().chunk_size


@contextmanager
def overlap_override(enabled: bool, prefetch_depth: Optional[int] = None):
    """Scoped overlap toggle — the serial-vs-overlapped bench tier and
    tests flip the engine without touching process env state."""
    global _exec_config
    prev = _exec_config
    cfg = replace(execution_config(), overlap=enabled)
    if prefetch_depth is not None:
        cfg = replace(cfg, prefetch_depth=max(1, prefetch_depth))
    _exec_config = cfg
    try:
        yield cfg
    finally:
        _exec_config = prev


@contextmanager
def dispatch_override(enabled: bool, workers: Optional[int] = None):
    """Scoped concurrent-dispatch toggle — the dispatch-count bench tier
    and the scheduler test matrix flip the scheduler (and its worker
    count) without touching process env state."""
    global _exec_config
    prev = _exec_config
    cfg = replace(execution_config(), concurrent_dispatch=enabled)
    if workers is not None:
        cfg = replace(cfg, dispatch_workers=max(1, workers))
    _exec_config = cfg
    try:
        yield cfg
    finally:
        _exec_config = prev


@contextmanager
def config_override(**fields):
    """Scoped override of arbitrary `ExecutionConfig` fields — the
    compile bench and tests flip chunk padding / AOT warmup / the cache
    dir without touching process env state. The persistent-cache config
    is re-synced on entry AND exit so a scoped ``compile_cache_dir``
    never leaks into later runs."""
    global _exec_config
    prev = _exec_config
    cfg = replace(execution_config(), **fields)
    _exec_config = cfg
    _sync_compile_cache(cfg)
    try:
        yield cfg
    finally:
        _exec_config = prev
        _sync_compile_cache(execution_config())


@dataclass(frozen=True)
class Prefix:
    """Structural identity of a node's ancestry (Prefix.scala:4-30)."""

    operator_key: Tuple
    dep_prefixes: Tuple["Prefix", ...]


def compute_prefix(graph: Graph, node: NodeId, _memo=None) -> Optional[Prefix]:
    """Prefix of ``node``, or None if any ancestor is an unbound source
    (unbound ancestry has no stable identity — Prefix.scala:13-27)."""
    if _memo is None:
        _memo = {}
    if node in _memo:
        return _memo[node]
    dep_prefixes = []
    for d in graph.get_dependencies(node):
        if isinstance(d, SourceId):
            _memo[node] = None
            return None
        dp = compute_prefix(graph, d, _memo)
        if dp is None:
            _memo[node] = None
            return None
        dep_prefixes.append(dp)
    p = Prefix(graph.get_operator(node).prefix_key(), tuple(dep_prefixes))
    _memo[node] = p
    return p


class PipelineEnv:
    """Process-global state: prefix→Expression memo table + current
    optimizer (PipelineEnv.scala:7-45). ``reset()`` exists for tests."""

    _instance: Optional["PipelineEnv"] = None

    def __init__(self):
        self.state: Dict[Prefix, Expression] = {}
        self._optimizer = None
        self.profiler = None  # set by utils.profiling.profile_execution

    @classmethod
    def get(cls) -> "PipelineEnv":
        if cls._instance is None:
            cls._instance = PipelineEnv()
        return cls._instance

    def get_optimizer(self):
        if self._optimizer is None:
            from .optimizer import DefaultOptimizer

            self._optimizer = DefaultOptimizer()
        return self._optimizer

    def set_optimizer(self, optimizer) -> None:
        self._optimizer = optimizer

    @classmethod
    def reset(cls) -> None:
        cls._instance = None
        # a fresh env must not inherit a previous pipeline's enforced
        # chunk decision (tests and benches reset between plans)
        set_planned_chunk_size(None)


class IdentityKey:
    """Hashable wrapper keying on *object identity* while holding a strong
    reference, so a garbage-collected object's address can never be reused
    by a different object and silently collide in the prefix table."""

    __slots__ = ("obj",)

    def __init__(self, obj):
        self.obj = obj

    def __hash__(self) -> int:
        return id(self.obj)

    def __eq__(self, other) -> bool:
        return isinstance(other, IdentityKey) and other.obj is self.obj

    def __repr__(self) -> str:
        return f"IdentityKey({type(self.obj).__name__}@{id(self.obj):#x})"


def _operator_prefix_key(self) -> Tuple:
    """Default operator identity for prefix/CSE purposes: object identity.

    The reference relies on Scala case-class equality of operators; here
    operators carrying fitted state or closures are only equal to
    themselves, which is exactly the sharing pattern the reference exploits
    (the same node object reused across pipeline graphs). Operators with
    meaningful structural identity (e.g. DatasetOperator keyed on its
    dataset) override this.
    """
    return (type(self).__qualname__, IdentityKey(self))


# Attach default prefix_key to Operator without circular imports.
from .operators import DatasetOperator, DatumOperator, Operator  # noqa: E402

Operator.prefix_key = _operator_prefix_key
DatasetOperator.prefix_key = lambda self: ("Dataset", IdentityKey(self.dataset))
DatumOperator.prefix_key = lambda self: ("Datum", IdentityKey(self.datum))
