"""Typed combinator API: Transformer / Estimator / Pipeline.

This is the user-facing layer. Typed combinators (`and_then`, `gather`,
`with_data`) build the untyped operator `Graph`; execution is lazy and
memoized through `GraphExecutor`. Mirrors the reference's
workflow/{Pipeline,Chainable,Transformer,Estimator,LabelEstimator,
FittedPipeline,PipelineResult}.scala.

Key semantic properties preserved from the reference:
  - **Laziness**: applying a pipeline returns a `PipelineDataset` /
    `PipelineDatum` handle; nothing runs until `.get()`
    (PipelineResult.scala:13-21).
  - **Fit-once**: estimator fits are memoized globally by structural
    prefix, so re-applying or extending a pipeline never refits
    (PipelineSuite.scala:28-52 is the behavioural contract).
  - **Single/batch duality**: the same graph serves one datum or a whole
    dataset (Operator.scala:77-100).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

from .env import PipelineEnv
from .executor import GraphExecutor
from .expressions import DatasetExpression, DatumExpression
from .graph import Graph, NodeId, NodeOrSourceId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    DatumOperator,
    DelegatingOperator,
    EstimatorOperator,
    GatherTransformerOperator,
    TransformerOperator,
)


# --------------------------------------------------------------------------
# Results


class PipelineResult:
    """Lazy handle on (executor, sink); `.get()` triggers execution
    (PipelineResult.scala:13-21)."""

    def __init__(self, executor: GraphExecutor, sink: SinkId):
        self.executor = executor
        self.sink = sink

    @property
    def graph(self) -> Graph:
        return self.executor.graph

    def get(self):
        return self.executor.execute(self.sink).get

    def stream(self):
        """Consume the result chunk-by-chunk (overlap engine): yields
        ``(indices, items)`` as the terminal stage drains, or a single
        ``(None, value)`` chunk when the pipeline doesn't stream.
        Drained chunks are memoized: after a full drain a later
        ``.get()`` is free, and after an early exit it resumes the
        remaining chunks without re-running the ones already seen."""
        return self.executor.execute_stream(self.sink)

    def validate(self, **kwargs):
        """Statically validate this applied pipeline's graph (all
        sources are already bound to data, so specs derive from the
        bound datasets). See `Pipeline.validate`."""
        return _validate(self.graph, {}, **kwargs)


class PipelineDataset(PipelineResult):
    """Lazy distributed dataset result (PipelineDataset.scala:10-23)."""


class PipelineDatum(PipelineResult):
    """Lazy single-datum result (PipelineDatum.scala:8-21)."""


def _splice_result(g: Graph, result: PipelineResult) -> Tuple[Graph, NodeOrSourceId]:
    """Merge a lazy result's (unoptimized) graph into ``g`` and return the
    vertex producing its value. Used by `with_data` so estimators can train
    on other pipelines' lazy outputs with full state sharing."""
    if result.graph.sources:
        raise ValueError("cannot splice a pipeline result with unbound sources")
    g2, _, kmap = g.add_graph(result.graph)
    vid = g2.get_sink_dependency(kmap[result.sink])
    for k in kmap.values():
        g2 = g2.remove_sink(k)
    return g2, vid


def _add_data_vertex(g: Graph, data: Any) -> Tuple[Graph, NodeOrSourceId]:
    """Bind a data argument into the graph: lazy results are spliced,
    anything else is wrapped in a DatasetOperator."""
    if isinstance(data, PipelineResult):
        return _splice_result(g, data)
    g2, nid = g.add_node(DatasetOperator(data), [])
    return g2, nid


def _validate(graph, source_specs, *, level: str = "full", ignore=(),
              hbm_budget_bytes=None, chunk_rows=None, partition_rules=(),
              serving=None, raise_on_error=True):
    """Shared implementation of `Pipeline.validate` and friends."""
    from ..analysis import validate_graph

    report = validate_graph(
        graph,
        source_specs,
        level=level,
        ignore=ignore,
        hbm_budget_bytes=hbm_budget_bytes,
        # None → ExecutionConfig.chunk_size, resolved inside memory_pass
        chunk_rows=chunk_rows,
        partition_rules=partition_rules,
        serving=serving,
    )
    if raise_on_error:
        report.raise_for_errors()
    return report


# --------------------------------------------------------------------------
# Chainable


class Chainable:
    """`and_then` combinators shared by Pipeline and Transformer
    (Chainable.scala:13-126)."""

    def to_pipeline(self) -> "Pipeline":
        raise NotImplementedError

    def and_then(self, nxt, *fit_args) -> "Pipeline":
        """Compose with a Transformer/Pipeline, or fit-and-append an
        (Label)Estimator:

          p.and_then(transformer)
          p.and_then(estimator, data)
          p.and_then(label_estimator, data, labels)

        (Chainable.scala:26-126). Estimator training inputs are this
        pipeline applied to ``data`` — featurization is shared with the
        final pipeline via CSE + prefix reuse.
        """
        me = self.to_pipeline()
        if isinstance(nxt, Estimator) and len(fit_args) == 1:
            return me.and_then(nxt.with_data(me.apply(fit_args[0])))
        if isinstance(nxt, LabelEstimator) and len(fit_args) == 2:
            return me.and_then(nxt.with_data(me.apply(fit_args[0]), fit_args[1]))
        if fit_args:
            raise TypeError("and_then: unexpected fit arguments")
        other = nxt.to_pipeline()
        g, kmap = me.graph.connect_graph(
            other.graph, {other.source: me.graph.get_sink_dependency(me.sink)}
        )
        g = g.remove_sink(me.sink)
        return Pipeline(g, me.source, kmap[other.sink])

    def __rshift__(self, nxt) -> "Pipeline":
        return self.and_then(nxt)


# --------------------------------------------------------------------------
# Pipeline


class Pipeline(Chainable):
    """Typed facade over (graph, source, sink) (Pipeline.scala:22-155)."""

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        self.graph = graph
        self.source = source
        self.sink = sink

    def to_pipeline(self) -> "Pipeline":
        return self

    # ----------------------------------------------------------- validate

    def validate(self, source_spec=None, *, level: str = "full", ignore=(),
                 hbm_budget_bytes=None, chunk_rows=None, partition_rules=(),
                 serving=None, raise_on_error: bool = True):
        """Statically validate this pipeline before any data loads.

        Walks the lowered graph propagating abstract specs
        (`jax.eval_shape` — zero data movement, zero device allocation),
        estimates per-node and peak live memory against
        ``hbm_budget_bytes``, and lints donation/streaming hazards. See
        ANALYSIS.md for the rule catalog and suppression
        (``ignore=["KP302", ...]`` or per-line ``# keystone:
        ignore[...]`` for the AST lints).

        ``source_spec`` describes the pipeline input: a
        `analysis.SpecDataset`, a `jax.ShapeDtypeStruct`, a
        ``(shape, dtype)`` pair, or a bare shape tuple (float32). None
        leaves the input unknown — structural lints still run, shape
        propagation starts at the first node with intrinsic specs.

        ``level``: "structure" ⊂ "specs" ⊂ "memory" ⊂ "full".
        ``partition_rules``: declarative ``(regex, PartitionSpec)``
        placement overrides for the sharding tier (see
        `analysis.sharding.PartitionRule`).
        ``serving``: a `analysis.ServingEnvelope` arming the KP9xx
        serving-readiness certifier (batch range + SLO + tenancy); the
        certificate lands on ``report.serving``. None falls back to the
        env-declared envelope (``KEYSTONE_SLO_MS``); with neither the
        serving tier is skipped. An armed envelope makes KP9xx errors
        raise like any other tier's — a fit-only script validating a
        known-host pipeline under an inherited ``KEYSTONE_SLO_MS``
        acknowledges the boundary with ``ignore=("KP901",)`` (the
        example registry's named suppressions are a CLI-layer concept).
        Raises `analysis.PipelineValidationError` on ERROR-severity
        findings unless ``raise_on_error=False``; always returns the
        `ValidationReport`."""
        from ..analysis import as_source_spec

        return _validate(
            self.graph,
            {self.source: as_source_spec(source_spec)},
            level=level, ignore=ignore, hbm_budget_bytes=hbm_budget_bytes,
            chunk_rows=chunk_rows, partition_rules=partition_rules,
            serving=serving, raise_on_error=raise_on_error)

    # -------------------------------------------------------------- apply

    def apply(self, data: Any):
        """Bind data and return a lazy result. Dispatch: lazy results are
        graph-spliced; `Dataset`s (or any object flagged `is_dataset`)
        follow the batch path; everything else is a single datum
        (Pipeline.scala:67-96)."""
        if isinstance(data, PipelineResult):
            g, smap, kmap = data.graph.add_graph(self.graph)
            # kmap maps *self*'s sinks; data's sink ids are unchanged.
            tgt = data.graph.get_sink_dependency(data.sink)
            src = smap[self.source]
            g = g.replace_dependency(src, tgt).remove_source(src)
            executor = GraphExecutor(g)
            cls = (
                PipelineDataset if isinstance(data, PipelineDataset) else PipelineDatum
            )
            return cls(executor, kmap[self.sink])

        if getattr(data, "is_dataset", False):
            g, nid = self.graph.add_node(DatasetOperator(data), [])
            g = g.replace_dependency(self.source, nid).remove_source(self.source)
            return PipelineDataset(GraphExecutor(g), self.sink)

        g, nid = self.graph.add_node(DatumOperator(data), [])
        g = g.replace_dependency(self.source, nid).remove_source(self.source)
        return PipelineDatum(GraphExecutor(g), self.sink)

    def __call__(self, data: Any):
        return self.apply(data)

    # ---------------------------------------------------------------- fit

    def fit(self) -> "FittedPipeline":
        """Eagerly fit every estimator, substitute the fitted transformers,
        prune the training branches, and return a serializable
        `FittedPipeline` (Pipeline.scala:38-65)."""
        from .fusion_rule import FusedChainOperator

        plan = PipelineEnv.get().get_optimizer().execute(self.graph)
        g, prefixes = plan
        fit_exec = GraphExecutor(g, plan=plan)
        for node in sorted(g.operators, key=lambda n: n.id):
            op = g.get_operator(node)
            if isinstance(op, DelegatingOperator):
                deps = g.get_dependencies(node)
                est_dep = deps[0]
                fitted = fit_exec.execute(est_dep).get  # forces the fit NOW
                if not isinstance(fitted, TransformerOperator):
                    raise TypeError(
                        f"estimator produced {type(fitted).__name__}, expected a Transformer"
                    )
                g = g.set_operator(node, fitted).set_dependencies(node, deps[1:])
            elif isinstance(op, FusedChainOperator):
                # a fused chain crossing estimator apply boundaries:
                # force each estimator dependency, bake the fitted
                # transformers into the chain, keep only the data dep
                deps = g.get_dependencies(node)
                fitted_ops = []
                for est_dep in deps[:-1]:
                    fitted = fit_exec.execute(est_dep).get
                    if not isinstance(fitted, TransformerOperator):
                        raise TypeError(
                            f"estimator produced {type(fitted).__name__}, "
                            "expected a Transformer")
                    fitted_ops.append(fitted)
                g = g.set_operator(node, op.materialize(fitted_ops))
                g = g.set_dependencies(node, deps[-1:])
        from .optimizer import UnusedBranchRemovalRule

        g, _ = UnusedBranchRemovalRule().apply((g, {}))
        return FittedPipeline(g, self.source, self.sink)

    # ------------------------------------------------------------- gather

    @staticmethod
    def gather(branches: Sequence[Chainable]) -> "Pipeline":
        """Merge N branches that consume the same input into one pipeline
        producing a list of branch outputs per item
        (Pipeline.scala:119-154)."""
        g = Graph()
        g, source = g.add_source()
        outs: List[NodeOrSourceId] = []
        for b in branches:
            bp = b.to_pipeline()
            g, kmap = g.connect_graph(bp.graph, {bp.source: source})
            out = g.get_sink_dependency(kmap[bp.sink])
            g = g.remove_sink(kmap[bp.sink])
            outs.append(out)
        g, gid = g.add_node(GatherTransformerOperator(), outs)
        g, sink = g.add_sink(gid)
        return Pipeline(g, source, sink)

    @staticmethod
    def identity() -> "Pipeline":
        g = Graph()
        g, source = g.add_source()
        g, sink = g.add_sink(source)
        return Pipeline(g, source, sink)


# --------------------------------------------------------------------------
# FittedPipeline


class FittedPipeline(Chainable):
    """A fit-free, serializable pipeline: transformers only
    (FittedPipeline.scala:18-48, TransformerGraph.scala:12-29). Applies
    without re-optimization."""

    def __init__(self, graph: Graph, source: SourceId, sink: SinkId):
        for n, op in graph.operators.items():
            if isinstance(op, (EstimatorOperator, DelegatingOperator)):
                raise ValueError(f"FittedPipeline may not contain {op.label}")
        self.graph = graph
        self.source = source
        self.sink = sink

    def to_pipeline(self) -> Pipeline:
        return Pipeline(self.graph, self.source, self.sink)

    def validate(self, source_spec=None, **kwargs):
        """Statically validate the fitted graph (see `Pipeline.validate`)."""
        return self.to_pipeline().validate(source_spec, **kwargs)

    def apply(self, data: Any):
        # every apply is one live serving request: request_scope tags it
        # with its padded ladder shape, feeds the streaming latency
        # sketches, and runs the conformance watchdog when armed (a
        # no-op context when KEYSTONE_LIVE_TELEMETRY=0)
        from ..telemetry.watchdog import request_scope

        if getattr(data, "is_dataset", False):
            try:
                batch = len(data)
            except TypeError:
                batch = 1
            with request_scope(batch, pipeline="fitted_pipeline"):
                g, nid = self.graph.add_node(DatasetOperator(data), [])
                g = g.replace_dependency(self.source, nid).remove_source(self.source)
                return PipelineDataset(
                    GraphExecutor(g, optimize=False, warm_scope=self),
                    self.sink).get()
        with request_scope(1, pipeline="fitted_pipeline"):
            g, nid = self.graph.add_node(DatumOperator(data), [])
            g = g.replace_dependency(self.source, nid).remove_source(self.source)
            return PipelineDatum(
                GraphExecutor(g, optimize=False, warm_scope=self),
                self.sink).get()

    def __call__(self, data: Any):
        return self.apply(data)

    # -------------------------------------------------------- persistence

    def save(self, path: str, format: str = "pickle") -> None:
        """Serialize to disk (FittedPipeline.scala:10 'may be written to
        and from disk').

        format="pickle" (default): one file; device arrays are gathered
        to host numpy so the artifact is portable across topologies.
        format="orbax": a directory; arrays are checkpointed with orbax
        so each host writes only its addressable shards — the multi-host
        path for pod-sharded models (call collectively from every
        process in a multi-process job)."""
        if format == "orbax":
            from ..utils.serialization import save_pytree_orbax

            save_pytree_orbax(self, path)
        elif format == "pickle":
            from ..utils.serialization import save_pytree_pickle

            save_pytree_pickle(self, path)
        else:
            raise ValueError(f"unknown save format {format!r}")

    @staticmethod
    def load(path: str) -> "FittedPipeline":
        """Load either artifact format (auto-detected: an orbax artifact
        is a directory with a skeleton)."""
        from ..utils.serialization import (
            is_orbax_artifact,
            load_pytree_orbax,
            load_pytree_pickle,
        )

        obj = (load_pytree_orbax(path) if is_orbax_artifact(path)
               else load_pytree_pickle(path))
        if not isinstance(obj, FittedPipeline):
            raise TypeError(f"{path} does not contain a FittedPipeline")
        return obj


# --------------------------------------------------------------------------
# Transformer


class Transformer(TransformerOperator, Chainable):
    """Per-item function with a default vectorized bulk path
    (Transformer.scala:18-70). Subclasses implement `apply(x)`; override
    `apply_batch` when a fused whole-batch implementation exists (e.g. a
    single GEMM for a linear model).

    Overlap-engine hooks: ``chunkable = True`` declares that the host
    batch path distributes over chunks (f(concat(chunks)) ==
    concat(f(chunks)) with host items in, host items out), letting this
    stage consume an upstream chunk stream as it drains;
    ``apply_batch_stream`` (return an iterator of ``(indices, results)``
    chunks, or None) makes the stage a stream *producer* — the bucketed
    host-batch dispatchers (SIFT, grid descriptors) yield each chunk as
    it comes off the device instead of materializing the whole stage.

    Precision hooks (`analysis.precision`): ``precision_tolerance``
    declares what the mixed-precision policy pass may do to this
    stage's boundaries — ``"tolerant"`` (bf16 storage and compute are
    fine: elementwise/featurize stages), ``"compute"`` (f32 storage
    required, bf16 matmul acceptable), ``"exact"`` (f32/HIGHEST,
    non-negotiable: solvers, moments, label/index stages), or None
    (undeclared — the analyzer probes the stage with an eval_shape
    sensitivity check and pins anything it cannot prove tolerant).
    ``precision_passthrough = True`` marks value-preserving plumbing
    (caches, combiners, identity): the analyzer looks *through* such
    stages, so the consumers behind them decide tolerance.
    """

    chunkable = False
    precision_tolerance = None
    precision_passthrough = False

    def apply(self, x: Any) -> Any:
        raise NotImplementedError

    def absorb(self, followers: Sequence[Any]) -> Optional[Tuple]:
        """Fusion hook (`nodes.util.fusion.fused_stages`): the one stage
        this stage and the first of ``followers`` make in a fused program
        and how many of them it takes in; None (the default) for none."""
        return None

    def moves_ahead_of(self, stage: Any) -> bool:
        """Fusion hook: whether this stage, a choice of rows, gives the
        same rows run ahead of ``stage``, the one before it."""
        return False

    def apply_batch_stream(self, data: Any):
        """Optional streaming batch path over a HostDataset; None means
        'no streaming implementation' (the operator layer falls back to
        one whole-value chunk)."""
        return None

    def batch_transform_stream(self, inputs: List[Any]):
        from ..data.dataset import HostDataset

        if isinstance(inputs[0], HostDataset):
            return self.apply_batch_stream(inputs[0])
        if self.chunkable and (getattr(inputs[0], "is_out_of_core", False)
                               or getattr(inputs[0], "is_spilled", False)):
            # Out-of-core tier: a host-resident source (planner-spilled
            # cache or on-demand sharded loader) re-enters the device in
            # bounded windows instead of materializing — residency stays
            # O(window) through every chunkable stage downstream.
            return self._windowed_batch_stream(inputs[0])
        return None

    def _windowed_batch_stream(self, source):
        """Per-window batch path over an out-of-core source: stage each
        pow-2 row window (reload overlapped with compute by
        `stream_spill_windows`), run this stage's fused batch path on
        it, and yield the standard ``(indices, results)`` chunk contract
        with phantom padded rows sliced off."""
        from ..data.dataset import Dataset
        from ..utils.batching import _split_result, stream_spill_windows

        for idxs, win in stream_spill_windows(source.row_loader,
                                              source.count):
            import jax

            n = jax.tree_util.tree_leaves(win)[0].shape[0]
            ds = Dataset(win, count=n, mesh=source.mesh, _placed=True)
            out = self.apply_batch(ds)
            yield _split_result(getattr(out, "data", out), idxs)

    def apply_batch(self, data: Any) -> Any:
        from ..data.dataset import Dataset, HostDataset

        if getattr(data, "is_spilled", False):
            # whole-batch consumer of a spilled value: the sanctioned
            # full re-entry (chunk-capable consumers never land here —
            # they stream windows via batch_transform_stream)
            data = data.rehydrate()
        elif getattr(data, "is_out_of_core", False):
            data = data.materialize()
        if isinstance(data, Dataset):
            # One stable jitted vmap per transformer instance: repeated
            # batch applies hit the jit cache instead of retracing (the
            # cache is keyed on function identity, so a fresh
            # jit(vmap(bound_method)) per call would always miss).
            fn = self.__dict__.get("_jitted_batch_apply")
            if fn is None:
                import jax

                fn = jax.jit(jax.vmap(self.apply))
                self.__dict__["_jitted_batch_apply"] = fn
            return data.map_batches(fn, jitted=False)
        if isinstance(data, HostDataset):
            return data.map(self.apply)
        return [self.apply(x) for x in data]

    # TransformerOperator plumbing
    def single_transform(self, inputs: List[Any]) -> Any:
        return self.apply(inputs[0])

    def batch_transform(self, inputs: List[Any]) -> Any:
        return self.apply_batch(inputs[0])

    def to_pipeline(self) -> Pipeline:
        g = Graph()
        g, source = g.add_source()
        g, nid = g.add_node(self, [source])
        g, sink = g.add_sink(nid)
        return Pipeline(g, source, sink)

    def __call__(self, data: Any):
        """Lazy application through the pipeline machinery."""
        return self.to_pipeline().apply(data)

    @staticmethod
    def from_function(fn: Callable[[Any], Any], name: str = None) -> "Transformer":
        """Lift a lambda into a Transformer node (Transformer.scala:58-70)."""
        t = _FunctionTransformer(fn)
        if name:
            t._label = name
        return t


class _FunctionTransformer(Transformer):
    chunkable = True  # pure per-item fn: distributes over chunks

    def __init__(self, fn: Callable[[Any], Any]):
        self.fn = fn
        self._label = None

    @property
    def label(self) -> str:
        return self._label or f"Fn[{getattr(self.fn, '__name__', 'lambda')}]"

    def apply(self, x: Any) -> Any:
        return self.fn(x)


# --------------------------------------------------------------------------
# Estimators


class Estimator(EstimatorOperator, Chainable):
    """Unsupervised estimator: `fit(data) -> Transformer`
    (Estimator.scala:10-62)."""

    saveable = True  # fit results are memoized by prefix

    def fit(self, data: Any) -> Transformer:
        raise NotImplementedError

    def fit_datasets(self, inputs: List[Any]) -> TransformerOperator:
        return self.fit(inputs[0])

    def with_data(self, data: Any) -> Pipeline:
        """Build the fit-then-apply pipeline graph: estimator node feeding a
        DelegatingOperator over a fresh source (Estimator.scala:18-46)."""
        g = Graph()
        g, data_id = _add_data_vertex(g, data)
        g, est_id = g.add_node(self, [data_id])
        g, source = g.add_source()
        g, delegate = g.add_node(DelegatingOperator(), [est_id, source])
        g, sink = g.add_sink(delegate)
        return Pipeline(g, source, sink)

    def to_pipeline(self):
        raise TypeError("an Estimator needs data: use .with_data(data)")


class LabelEstimator(EstimatorOperator, Chainable):
    """Supervised estimator: `fit(data, labels) -> Transformer`
    (LabelEstimator.scala:13-100)."""

    saveable = True

    def fit(self, data: Any, labels: Any) -> Transformer:
        raise NotImplementedError

    def fit_datasets(self, inputs: List[Any]) -> TransformerOperator:
        return self.fit(inputs[0], inputs[1])

    def with_data(self, data: Any, labels: Any) -> Pipeline:
        g = Graph()
        g, data_id = _add_data_vertex(g, data)
        g, labels_id = _add_data_vertex(g, labels)
        g, est_id = g.add_node(self, [data_id, labels_id])
        g, source = g.add_source()
        g, delegate = g.add_node(DelegatingOperator(), [est_id, source])
        g, sink = g.add_sink(delegate)
        return Pipeline(g, source, sink)

    def to_pipeline(self):
        raise TypeError("a LabelEstimator needs data: use .with_data(data, labels)")


# --------------------------------------------------------------------------
# Chains (reference workflow/ChainUtils.scala:12-41) — used by cost-model
# solver auto-selection to fuse a prep transformer into an estimator.


class TransformerChain(Transformer):
    def __init__(self, stages: Sequence[Transformer]):
        self.stages = list(stages)

    @property
    def chunkable(self) -> bool:  # a chain distributes iff every stage does
        return all(getattr(s, "chunkable", False) for s in self.stages)

    @property
    def label(self) -> str:
        return " >> ".join(s.label for s in self.stages)

    def apply(self, x):
        for s in self.stages:
            x = s.apply(x)
        return x

    def apply_batch(self, data):
        for s in self.stages:
            data = s.apply_batch(data)
        return data


class EstimatorChain(Estimator):
    """prep >> estimator, fused as one Estimator (ChainUtils.scala:12-24)."""

    def __init__(self, prep: Transformer, est: Estimator):
        self.prep = prep
        self.est = est

    @property
    def label(self) -> str:
        return f"{self.prep.label} >> {self.est.label}"

    def fit(self, data):
        return TransformerChain([self.prep, self.est.fit(self.prep.apply_batch(data))])


class LabelEstimatorChain(LabelEstimator):
    """prep >> label-estimator, fused (ChainUtils.scala:26-41)."""

    def __init__(self, prep: Transformer, est: LabelEstimator):
        self.prep = prep
        self.est = est

    @property
    def label(self) -> str:
        return f"{self.prep.label} >> {self.est.label}"

    def fit(self, data, labels):
        return TransformerChain(
            [self.prep, self.est.fit(self.prep.apply_batch(data), labels)]
        )


# --------------------------------------------------------------------------
# Optimizable nodes (reference workflow/OptimizableNodes.scala:12-50)


class OptimizableTransformer(Transformer):
    """A transformer with a default impl plus a sample-driven `optimize`
    hook consulted by NodeOptimizationRule."""

    @property
    def default(self) -> Transformer:
        raise NotImplementedError

    def optimize(self, sample: Any, num_per_shard: int) -> Transformer:
        raise NotImplementedError

    def apply(self, x):
        return self.default.apply(x)

    def apply_batch(self, data):
        return self.default.apply_batch(data)

    def optimize_from_sample(self, sample_inputs, scale):
        return self.optimize(sample_inputs[0], scale)


class OptimizableEstimator(Estimator):
    @property
    def default(self) -> Estimator:
        raise NotImplementedError

    def optimize(self, sample: Any, num_per_shard: int) -> Estimator:
        raise NotImplementedError

    def fit(self, data):
        return self.default.fit(data)

    def optimize_from_sample(self, sample_inputs, scale):
        return self.optimize(sample_inputs[0], scale)


class OptimizableLabelEstimator(LabelEstimator):
    @property
    def default(self) -> LabelEstimator:
        raise NotImplementedError

    def optimize(self, sample: Any, sample_labels: Any, num_per_shard: int) -> LabelEstimator:
        raise NotImplementedError

    def fit(self, data, labels):
        return self.default.fit(data, labels)

    def optimize_from_sample(self, sample_inputs, scale):
        return self.optimize(sample_inputs[0], sample_inputs[1], scale)
