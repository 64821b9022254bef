"""Automatic stage-fusion rule — a TPU-native optimizer pass with no
reference analog (Spark streams partition iterators, so per-node
materialization is free there; on TPU every node boundary is an HBM
round-trip AND one more program launch).

`NodeFusionRule` finds maximal linear chains of adjacent nodes that can
compile into one XLA program and replaces each chain with a single
operator:

  - transformer nodes that declare themselves XLA-traceable
    (``fusable = True``) fuse into one `FusedBatchTransformer`
    (nodes/util/fusion.py) exactly as before;
  - with ``fuse_apply`` (default on), chains additionally extend through
    *fan-out-free estimator apply boundaries*: a `DelegatingOperator`
    whose estimator declares ``fusable_fit = True`` (its fit always
    yields a traceable transformer — scalers, least-squares mappers)
    joins the chain as a `_FitSlot`. The chain lowers to a
    `FusedChainOperator` whose extra dependencies are the estimator
    expressions; at force time the fitted transformers are captured as
    fused closure *params* and the whole chain runs as one program;
  - also with ``fuse_apply``, fusable ``Pipeline.gather`` diamonds
    (N traceable branches over one source + VectorCombiner) collapse
    into one `GatherConcatStage` program (`_fuse_gathers`).

A node with two children terminates the chain (fusing across fan-out
would duplicate work for one consumer and starve the other's memo), and
chain discovery walks up to the chain head from ANY member, so the result
is independent of node-id iteration order.
"""

from __future__ import annotations

import logging
import math
from typing import Dict, List, Sequence, Tuple

from .analysis import children
from .expressions import (
    DatasetExpression,
    DatumExpression,
    Expression,
    StreamingDatasetExpression,
    TransformerExpression,
)
from .graph import Graph, NodeId
from .operators import (
    DelegatingOperator,
    EstimatorOperator,
    ExpressionOperator,
    Operator,
    _overlap_enabled,
    _streamed_batch,
)
from .optimizer import Plan, Rule

logger = logging.getLogger(__name__)


def _record_fusion_decision(kind: str, rule: str, chain, labels,
                            chosen_entry: str, programs_before: int,
                            graph: Graph = None) -> None:
    """One ledger record per enforced fusion rewrite: the chain's
    vertices/labels, the chosen program shape, the per-stage dispatch
    alternative it beat, and the predicted program arithmetic in the
    shared units (programs-per-apply; one cold compile upper-bounds the
    fresh program — the persistent cache may serve it warm). With a
    durable ledger destination armed, the record additionally carries
    the chain's roofline ``predicted_seconds``
    (`analysis.roofline.chain_predicted_seconds` over the bound graph's
    propagated specs) — the time-domain prediction `reconcile` joins
    against the run's observed spans. Never raises: a ledger bug must
    not break the rewrite it records."""
    try:
        from ..telemetry import ledger

        predicted = {"programs_per_apply": 1,
                     "programs_eliminated": max(0, programs_before - 1),
                     "cold_compiles_max": 1}
        # roofline pricing traces stage jaxprs — worth it only when the
        # record reaches a durable destination (trace/JSONL), not on
        # every optimizer run's session-only bookkeeping
        if graph is not None and ledger.ledger_active():
            from ..analysis.roofline import chain_predicted_seconds

            seconds = chain_predicted_seconds(graph, list(chain))
            if seconds is not None:
                predicted["predicted_seconds"] = seconds
        ledger.record_decision(
            kind=kind,
            rule=rule,
            vertices=[n.id for n in chain],
            labels=list(labels),
            chosen={"entry": chosen_entry, "programs": 1,
                    "members": len(chain)},
            alternatives=[{"entry": "per_stage_dispatch",
                           "programs": programs_before,
                           "cost_programs": programs_before}],
            predicted=predicted,
        )
    except Exception:
        pass


class _FitSlot:
    """Placeholder in a fused chain's stage list: 'the transformer fitted
    by estimator dependency ``index``' (resolved at force time)."""

    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index

    def __repr__(self) -> str:
        return f"fit:{self.index}"


class FusedChainOperator(Operator):
    """A fused linear chain that crosses estimator `apply` boundaries.

    Dependencies: ``(est_0, ..., est_{k-1}, data)`` — the estimator
    expressions whose fitted transformers fill the chain's `_FitSlot`s,
    then the single data input. Forcing the output forces the fits
    (fit-once still holds: the shared TransformerExpressions memoize) and
    composes the fully-fitted stage list into one microbatched XLA
    program via `FusedBatchTransformer`; if a fit unexpectedly yields a
    non-traceable transformer the chain degrades to a sequential
    `TransformerChain` — same values, per-stage dispatch.

    The data input keeps PR-1 overlap semantics: under the overlap engine
    the output is a `StreamingDatasetExpression` whose thunk routes
    through `_streamed_batch`, so a chunk-streaming upstream keeps
    draining chunk-by-chunk through the fused chain when every fitted
    stage is ``chunkable``.
    """

    may_consume_chunks = True

    def __init__(self, stage_specs: Sequence, microbatch=None):
        self.stage_specs = list(stage_specs)
        self.microbatch = microbatch

    @property
    def n_fits(self) -> int:
        return sum(1 for s in self.stage_specs if isinstance(s, _FitSlot))

    @property
    def estimator_positions(self) -> tuple:
        """Dependency indices that consume estimator outputs (KP003)."""
        return tuple(range(self.n_fits))

    #: display prefix + runnable class hook, overridden by
    #: `MegafusedPlanOperator` (same fit-slot resolution and fallback,
    #: different compiled form)
    _label_prefix = "Fused"

    #: the sharding planner's chosen output placement (set by
    #: `ShardingPlannerRule` on a tagged copy); `materialize` hands it
    #: to the built fused transformer, whose program builder lowers it
    #: into a with_sharding_constraint on the program output
    planned_out_spec = None

    #: the precision planner's chosen per-stage storage dtypes (set by
    #: `PrecisionPlannerRule` on a tagged copy: one dtype name or None
    #: per `fused_stages` output) and its matmul-precision scope;
    #: `materialize` hands both to the built fused transformer, whose
    #: program builder bakes the casts (and the
    #: jax.default_matmul_precision scope) into the traced program
    planned_precision = None
    planned_matmul_precision = None

    #: the unified planner's chain-megakernel tag ``(start, stop,
    #: family)`` over the `fused_stages` list (set by
    #: `UnifiedPlannerRule` on a tagged copy) plus its predicted
    #: seconds; `materialize` hands both to the built fused transformer,
    #: whose program builder swaps the tagged sub-trail for ONE
    #: pallas_call (ops/chain_kernels.py)
    planned_kernel = None
    planned_kernel_seconds = None
    planned_kernel_statically_verified = None

    def _fused_cls(self):
        from ..nodes.util.fusion import FusedBatchTransformer

        return FusedBatchTransformer

    @property
    def label(self) -> str:
        return self._label_prefix + "[" + " >> ".join(
            repr(s) if isinstance(s, _FitSlot) else s.label
            for s in self.stage_specs) + "]"

    def materialize(self, fitted: Sequence):
        """Resolve `_FitSlot`s against ``fitted`` (one TransformerOperator
        per estimator dependency, in order) and build the runnable fused
        transformer; if a fit unexpectedly yielded a non-traceable
        transformer, degrade to sequential per-stage dispatch — same
        values. Shared by force-time execution and `Pipeline.fit`'s
        estimator substitution."""
        from .pipeline import TransformerChain

        stages = [fitted[s.index] if isinstance(s, _FitSlot) else s
                  for s in self.stage_specs]
        if all(getattr(s, "fusable", False) for s in stages):
            fused = self._fused_cls()(stages, microbatch=self.microbatch)
            if self.planned_out_spec is not None:
                fused.planned_out_spec = self.planned_out_spec
            if self.planned_precision is not None:
                fused.planned_precision = self.planned_precision
            if self.planned_matmul_precision is not None:
                fused.planned_matmul_precision = \
                    self.planned_matmul_precision
            if self.planned_kernel is not None:
                fused.planned_kernel = self.planned_kernel
                fused.planned_kernel_seconds = self.planned_kernel_seconds
                fused.planned_kernel_statically_verified = \
                    self.planned_kernel_statically_verified
            return fused
        return TransformerChain(stages)

    def abstract_eval(self, in_specs: List) -> object:
        from ..analysis.specs import (
            UNKNOWN,
            DataSpec,
            SpecMismatchError,
            TransformerSpec,
            is_known,
            trace_element,
        )

        if len(in_specs) != self.n_fits + 1:
            raise SpecMismatchError(
                f"fused chain expects {self.n_fits} estimator "
                f"dependency(ies) plus data, got {len(in_specs)}",
                rule="KP002")
        t_specs, data_spec = in_specs[:-1], in_specs[-1]
        for i, ts in enumerate(t_specs):
            if isinstance(ts, DataSpec):
                raise SpecMismatchError(
                    f"fused-chain dependency {i} produces data, not a "
                    "transformer", rule="KP004")
        if isinstance(data_spec, TransformerSpec):
            raise SpecMismatchError(
                "a transformer output is consumed as the fused chain's "
                "data input (fit-before-use)", rule="KP003")
        if not isinstance(data_spec, DataSpec):
            return UNKNOWN

        elem = data_spec.element
        for s in self.stage_specs:
            if not is_known(elem):
                elem = UNKNOWN
                break
            if isinstance(s, _FitSlot):
                ts = t_specs[s.index]
                elem = (ts.apply_element(elem)  # may raise mismatch
                        if isinstance(ts, TransformerSpec) else UNKNOWN)
            else:
                elem = trace_element(
                    lambda x, s=s: s.single_transform([x]), (elem,))

        # chunk capability of the fitted slots is only provable when the
        # estimator's spec declares it — conservative otherwise
        chunk_ok = all(
            getattr(s, "chunkable", False) if not isinstance(s, _FitSlot)
            else (isinstance(t_specs[s.index], TransformerSpec)
                  and t_specs[s.index].chunkable)
            for s in self.stage_specs)
        return DataSpec(
            element=elem,
            count=data_spec.count if data_spec.kind == "dataset" else None,
            kind=data_spec.kind,
            on_device=data_spec.on_device,
            streaming=(data_spec.kind == "dataset" and data_spec.streaming
                       and chunk_ok),
        )

    def execute(self, deps: Sequence[Expression]) -> Expression:
        deps = list(deps)
        if len(deps) != self.n_fits + 1:
            raise ValueError(
                f"{self.label} expects {self.n_fits} estimator "
                f"dependency(ies) plus one data dependency, got {len(deps)}")
        t_exprs, data = deps[:-1], deps[-1]
        for t in t_exprs:
            if not isinstance(t, TransformerExpression):
                raise ValueError(
                    f"{self.label}: estimator dependency did not produce a "
                    "transformer expression")

        def make():
            # forcing the fits happens HERE, inside the chain's own force
            # — identical laziness to the DelegatingOperator path
            return self.materialize([t.get for t in t_exprs])

        if isinstance(data, DatumExpression):
            return DatumExpression(lambda: make().single_transform([data.get]))
        if _overlap_enabled():
            return StreamingDatasetExpression(
                lambda: _streamed_batch(make(), data))
        return DatasetExpression(lambda: make().batch_transform([data.get]))


class MegafusedPlanOperator(FusedChainOperator):
    """A whole plan collapsed to ONE donated XLA program.

    Produced by `MegafusionRule` when the apply plan is a fan-out-free
    chain of fusable members — `FusedBatchTransformer` stages,
    `FusedChainOperator`s (their fit slots re-indexed into this
    operator's combined estimator dependency list), bare fusable
    transformers, and `Cacher` passthroughs (absorbed: inside one
    program there is no intermediate to pin). Forcing materializes a
    `MegafusedBatchTransformer`, whose chunk loop is an in-program
    ``lax.scan`` over the shape-stable padded chunks (PR 5's contract)
    with fit state as scan-invariant closure params — so the entire
    apply run, *including the chunk loop*, is one executed program.
    """

    _label_prefix = "Megafused"

    def _fused_cls(self):
        from ..nodes.util.fusion import MegafusedBatchTransformer

        return MegafusedBatchTransformer

    def scan_live_nbytes(self, dep_specs: Sequence, chunk_rows: int):
        """Static size of the scan's in-program live set: one chunk's
        input plus its largest stage boundary — the carry-side residency
        the KP2xx memory model prices INSTEAD of materialized
        intermediates (which never exist inside the program). Returns
        None when any boundary element is unknown."""
        from ..analysis.specs import (
            DataSpec,
            TransformerSpec,
            element_nbytes,
            is_known,
            trace_element,
        )

        if not dep_specs:
            return None
        t_specs, data_spec = dep_specs[:-1], dep_specs[-1]
        if not isinstance(data_spec, DataSpec):
            return None
        elem = data_spec.element
        boundary_nbytes = []
        for s in self.stage_specs:
            if not is_known(elem):
                return None
            per_item = element_nbytes(elem)
            if per_item is None:
                return None
            boundary_nbytes.append(per_item)
            try:
                if isinstance(s, _FitSlot):
                    ts = t_specs[s.index]
                    if not isinstance(ts, TransformerSpec):
                        return None
                    elem = ts.apply_element(elem)
                else:
                    elem = trace_element(
                        lambda x, s=s: s.single_transform([x]), (elem,))
            except Exception:
                return None
        out_nbytes = element_nbytes(elem)
        if out_nbytes is None:
            return None
        boundary_nbytes.append(out_nbytes)
        # per trip: a chunk's input boundary + output boundary live at
        # once; the largest adjacent pair bounds the in-scan live set
        worst = max(
            boundary_nbytes[i] + boundary_nbytes[i + 1]
            for i in range(len(boundary_nbytes) - 1))
        return int(worst * chunk_rows)


class MegafusionRule(Rule):
    """Whole-plan megafusion: collapse a fan-out-free chain of fused
    members into one `MegafusedPlanOperator` (ONE executed program per
    apply run — the whole-program-offload endpoint of arXiv 1810.09868).

    Runs after `NodeFusionRule`, whose output plan is already maximally
    node-fused: what remains are the chain of fused super-nodes the
    earlier pass cannot merge (a `FusedBatchTransformer` followed by a
    `FusedChainOperator`, optionally with `Cacher` passthroughs between
    them). Members must consume each other as their single DATA input;
    a fan-out, a host-code (non-fusable) stage, or a stream-producing
    stage terminates the chain — those plans keep the PR-4/5 per-program
    dispatch path, and `validate()`'s KP401 diagnostics say why.

    `ExecutionConfig.megafusion` (env ``KEYSTONE_MEGAFUSION``, default
    on) is read at optimization time; off reverts to the PR-4/5 plan
    exactly.
    """

    def __init__(self, microbatch=None):
        self.microbatch = microbatch

    # ---------------------------------------------------- member predicate

    @staticmethod
    def _member_kind(graph: Graph, node: NodeId):
        """'chain' (fit-slot carrier), 'stage' (plain fusable), 'cache'
        (identity passthrough), or None (terminates megafusion)."""
        from ..nodes.util.basic import Cacher
        from .operators import TransformerOperator

        op = graph.get_operator(node)
        deps = graph.get_dependencies(node)
        if isinstance(op, FusedChainOperator):
            return "chain"
        if isinstance(op, Cacher) and len(deps) == 1:
            return "cache"
        if isinstance(op, TransformerOperator) \
                and getattr(op, "fusable", False) and len(deps) == 1:
            return "stage"
        return None

    @staticmethod
    def _data_dep(graph: Graph, node: NodeId):
        deps = graph.get_dependencies(node)
        if isinstance(graph.get_operator(node), FusedChainOperator):
            return deps[-1]
        return deps[0]

    @staticmethod
    def _is_plan_input(graph: Graph, dep) -> bool:
        """True when ``dep`` is the plan's own input — an unbound
        source, bound data, or spliced saved state — rather than a
        mid-plan producer node. A single fused chain consuming the plan
        input IS the whole apply path, so it is promoted to the
        scan-bodied megafused form even with nothing left to merge."""
        from .graph import SourceId
        from .operators import DatasetOperator, DatumOperator

        if isinstance(dep, SourceId):
            return True
        if not isinstance(dep, NodeId):
            return False
        op = graph.get_operator(dep)
        return isinstance(
            op, (DatasetOperator, DatumOperator, ExpressionOperator))

    # ------------------------------------------------------------ rewrite

    def apply(self, plan: Plan) -> Plan:
        from .env import execution_config

        if not execution_config().megafusion:
            return plan  # kill switch: the PR-4/5 plan, bit for bit
        graph, prefixes = plan
        visited: set = set()
        chains: List[List[NodeId]] = []
        for node in sorted(graph.operators, key=lambda n: n.id):
            if node in visited or self._member_kind(graph, node) is None:
                continue
            head = node
            while True:
                dep = self._data_dep(graph, head)
                if (isinstance(dep, NodeId)
                        and self._member_kind(graph, dep) is not None
                        and len(children(graph, dep)) == 1):
                    head = dep
                else:
                    break
            chain = [head]
            cur = head
            while True:
                kids = children(graph, cur)
                if len(kids) != 1:
                    break
                (kid,) = kids
                if (isinstance(kid, NodeId)
                        and self._member_kind(graph, kid) is not None
                        and self._data_dep(graph, kid) == cur):
                    chain.append(kid)
                    cur = kid
                else:
                    break
            visited.update(chain)
            # a merge of >= 2 PROGRAM-bearing members removes a
            # dispatch; a [stage, Cacher] pair would only forfeit the
            # cache point. A single fitted chain consuming the plan
            # input is ALSO rewritten — it is the whole apply path, and
            # promotion moves its chunk loop in-program (scan body).
            kinds = [self._member_kind(graph, n) for n in chain]
            programs = sum(1 for k in kinds if k != "cache")
            whole_plan_single = (
                len(chain) == 1 and kinds[0] == "chain"
                and self._is_plan_input(
                    graph, self._data_dep(graph, chain[0])))
            if (len(chain) >= 2 and programs >= 2) or whole_plan_single:
                chains.append(chain)

        for chain in chains:
            if any(n not in graph.operators for n in chain):
                continue
            _record_fusion_decision(
                "megafusion", type(self).__name__, chain,
                [graph.get_operator(n).label for n in chain],
                "megafused_scan_program",
                max(1, sum(1 for n in chain
                           if self._member_kind(graph, n) != "cache")),
                graph=graph)
            head_data_dep = self._data_dep(graph, chain[0])
            est_deps: List = []
            stage_specs: List = []
            for n in chain:
                kind = self._member_kind(graph, n)
                op = graph.get_operator(n)
                if kind == "chain":
                    base = len(est_deps)
                    est_deps.extend(graph.get_dependencies(n)[:-1])
                    for s in op.stage_specs:
                        stage_specs.append(
                            _FitSlot(base + s.index)
                            if isinstance(s, _FitSlot) else s)
                elif kind == "cache":
                    continue  # identity inside one program: nothing to pin
                else:
                    stage_specs.append(op)
            fused = MegafusedPlanOperator(
                stage_specs, microbatch=self.microbatch)
            graph = graph.set_operator(chain[0], fused)
            graph = graph.replace_dependency(chain[-1], chain[0])
            graph = graph.set_dependencies(
                chain[0], tuple(est_deps) + (head_data_dep,))
            for n in reversed(chain[1:]):
                graph = graph.set_dependencies(n, ())
                graph = graph.remove_node(n)
            # EVERY member's saveable prefix goes, the head's included:
            # the head node now holds the megafused operator, and saving
            # the whole-chain output under the original head's prefix
            # (e.g. an absorbed Cacher's) would hand later pipelines the
            # wrong value through SavedStateLoadRule
            for n in chain:
                prefixes.pop(n, None)
        return graph, prefixes


def megafusion_blockers(graph: Graph) -> List[Tuple[NodeId, str, str]]:
    """Why a plan cannot collapse to one program: ``(vertex, label,
    reason)`` triples over the node-fused plan, reported only for
    blockers ADJACENT to an otherwise-fusable member (the informative
    fallbacks — a host-only pipeline is not megafusion's business).
    Consumed by the analyzer's KP401 diagnostics so `validate()`
    explains fallbacks."""
    from ..analysis.hazards import _is_stream_origin
    from ..telemetry import ledger
    from .operators import TransformerOperator

    # this is an ANALYSIS re-run on a throwaway graph: no executor will
    # enforce these rewrites, so they must not reach the run's ledger
    with ledger.suppressed():
        fused_graph = NodeFusionRule().apply((graph, {}))[0]
    kinds = {
        n: MegafusionRule._member_kind(fused_graph, n)
        for n in fused_graph.operators
    }

    def neighbors(node):
        out = [d for d in fused_graph.get_dependencies(node)
               if isinstance(d, NodeId)]
        out.extend(u for u in children(fused_graph, node)
                   if isinstance(u, NodeId))
        return out

    blockers: List[Tuple[NodeId, str, str]] = []
    for node in sorted(fused_graph.operators, key=lambda n: n.id):
        op = fused_graph.get_operator(node)
        if kinds.get(node) is not None:
            kids = [k for k in children(fused_graph, node)
                    if isinstance(k, NodeId) and kinds.get(k) is not None]
            all_kids = children(fused_graph, node)
            if len(all_kids) > 1 and kids:
                blockers.append((node, op.label, (
                    f"fan-out ({len(all_kids)} consumers) terminates the "
                    "megafused chain here; each branch dispatches its own "
                    "program")))
            continue
        if not any(kinds.get(nb) is not None for nb in neighbors(node)):
            continue  # not interrupting a fusable chain: not informative
        if _is_stream_origin(op):
            blockers.append((node, op.label, (
                "stream-producing host stage stays on the overlapped "
                "host-staging path; the single-program plan can only "
                "start downstream of it")))
        elif isinstance(op, DelegatingOperator):
            deps = fused_graph.get_dependencies(node)
            if deps and NodeFusionRule._est_fusable(fused_graph, deps[0]):
                continue  # fusable fit, just nothing adjacent to merge
            blockers.append((node, op.label, (
                "estimator apply boundary is not provably fusable (the "
                "estimator does not declare fusable_fit); the fitted "
                "stage dispatches its own program")))
        elif isinstance(op, TransformerOperator) \
                and not getattr(op, "fusable", False):
            blockers.append((node, op.label, (
                "host-code stage (fusable=False) cannot enter a single "
                "XLA program; the chain splits around it")))
    return blockers


def _declared_output(graph: Graph, vid, memo: Dict):
    """(element, count) of what ``vid`` puts out, where every stage from
    the dataset down to it says its output's shape without a trace (an
    ``abstract_apply`` hook on a transformer, ``abstract_fit`` on the
    estimator behind an apply boundary), else None. Cheap by design:
    this is asked of every plan, and a plan with a stage that declares
    nothing is left as it is."""
    if vid in memo:
        return memo[vid]
    memo[vid] = out = None
    if not isinstance(vid, NodeId):
        return None
    op = graph.get_operator(vid)
    deps = graph.get_dependencies(vid)
    try:
        from .operators import DatasetOperator

        if isinstance(op, DatasetOperator):
            array = getattr(op.dataset, "array", None)
            if hasattr(array, "shape") and hasattr(op.dataset, "count"):
                import jax

                out = (jax.ShapeDtypeStruct(array.shape[1:], array.dtype),
                       int(op.dataset.count))
        elif isinstance(op, DelegatingOperator) and len(deps) == 2:
            src = _declared_output(graph, deps[1], memo)
            est = (graph.get_operator(deps[0])
                   if isinstance(deps[0], NodeId) else None)
            fit = getattr(est, "abstract_fit", None)
            if src is not None and fit is not None:
                elem = fit([]).apply_element(src[0])
                if hasattr(elem, "shape"):
                    out = (elem, src[1])
        elif len(deps) == 1 and hasattr(op, "abstract_apply"):
            src = _declared_output(graph, deps[0], memo)
            if src is not None:
                out = (op.abstract_apply(src[0]), src[1])
    except Exception:
        out = None
    memo[vid] = out
    return out


def _refuse_oversized(rule: "NodeFusionRule", plan: Plan) -> Plan:
    """Keep off the device what cannot lie on it. A dataset a plan would
    hold whole is priced from the shapes its stages declare
    (`_declared_output`) against the planner's HBM budget
    (`workflow.budget.resident_fits`):

      - a `Cacher` whose output does not fit is refused: it is taken out
        of the plan and its consumers read what fed it
        (``planner.caches_refused``);
      - a fusable stage with several consumers, whose output would be
        held whole between them and does not fit, is planted once a
        consumer (``planner.recomputes_planted``): each consumer's chain
        then fuses down to it and makes the stage's rows again, a
        microbatch at a time, instead of reading 100 GB that no chip
        holds.

    From the sinks up, so a stage that feeds an oversized stage sees its
    new consumers."""
    from .budget import resident_fits
    from ..analysis.propagate import toposort
    from ..nodes.util.basic import Cacher
    from ..telemetry import counter

    graph, prefixes = plan
    memo: Dict = {}

    def oversized(node) -> bool:
        out = _declared_output(graph, node, memo)
        if out is None:
            return False
        elem, count = out
        nbytes = count * math.prod(elem.shape) * elem.dtype.itemsize
        return not resident_fits(nbytes)

    # most plans hold nothing of the kind: ask the few vertices that could
    # (a cache point, a stage with several consumers) before any rewrite
    if not any(oversized(n) for n, op in graph.operators.items()
               if isinstance(op, Cacher) or len(children(graph, n)) > 1):
        return plan
    order, _ = toposort(graph)
    for node in reversed([v for v in order if isinstance(v, NodeId)]):
        if node not in graph.operators:
            continue
        op = graph.get_operator(node)
        deps = graph.get_dependencies(node)
        if isinstance(op, Cacher) and len(deps) == 1:
            if oversized(node):
                logger.info("refusing %s: its output does not fit the "
                            "HBM budget", op.label)
                graph = graph.replace_dependency(node, deps[0])
                graph = graph.remove_node(node)
                prefixes.pop(node, None)
                counter("planner.caches_refused").inc()
            continue
        kids = sorted((k for k in children(graph, node)),
                      key=lambda k: (isinstance(k, NodeId), k.id))
        if len(kids) < 2 or not rule._fusable(graph, node) \
                or not oversized(node):
            continue
        for kid in kids[1:]:
            graph, twin = graph.add_node(op, deps)
            if isinstance(kid, NodeId):
                graph = graph.set_dependencies(kid, tuple(
                    twin if d == node else d
                    for d in graph.get_dependencies(kid)))
            else:
                graph = graph.set_sink_dependency(kid, twin)
            counter("planner.recomputes_planted").inc()
    return graph, prefixes


class NodeFusionRule(Rule):
    def __init__(self, microbatch=None, fuse_apply: bool = True):
        self.microbatch = microbatch
        #: PR-4 expanded coverage: fuse through fan-out-free estimator
        #: apply boundaries AND collapse fusable gather/combiner
        #: diamonds; the dispatch-count bench's "legacy" plan turns this
        #: off to reproduce the PR-3 optimizer exactly
        self.fuse_apply = fuse_apply

    # ------------------------------------------------------ chain predicate

    @staticmethod
    def _est_fusable(graph: Graph, dep) -> bool:
        """Will this delegate's estimator dependency produce a traceable
        (fusable) transformer? Provable for estimators that declare
        ``fusable_fit`` and for already-forced saved state."""
        if not isinstance(dep, NodeId):
            return False
        op = graph.get_operator(dep)
        if isinstance(op, EstimatorOperator):
            return bool(getattr(op, "fusable_fit", False))
        if isinstance(op, ExpressionOperator):
            e = op.expression
            return (isinstance(e, TransformerExpression) and e.is_forced
                    and bool(getattr(e.get, "fusable", False)))
        return False

    def _fusable(self, graph: Graph, node: NodeId) -> bool:
        op = graph.get_operator(node)
        deps = graph.get_dependencies(node)
        if getattr(op, "fusable", False) and len(deps) == 1:
            return True
        return (
            self.fuse_apply
            and isinstance(op, DelegatingOperator)
            and len(deps) == 2
            and self._est_fusable(graph, deps[0])
        )

    @staticmethod
    def _data_dep(graph: Graph, node: NodeId):
        """The chain-forming (data) dependency of a fusable node."""
        deps = graph.get_dependencies(node)
        if isinstance(graph.get_operator(node), DelegatingOperator):
            return deps[1]
        return deps[0]

    # ------------------------------------------------------------ rewrite

    def _fuse_gathers(self, plan: Plan) -> Plan:
        """Collapse a fusable ``Pipeline.gather`` diamond — N single-dep
        fusable branches over ONE source, zipped by a
        GatherTransformerOperator whose sole consumer is a
        VectorCombiner — into one `FusedBatchTransformer` wrapping a
        `GatherConcatStage`. The branch fan-out, the zip, and the
        concat all become one XLA program; the linear pass below can
        then chain it with whatever follows (MnistRandomFFT's whole
        apply path collapses to a single program)."""
        from ..nodes.util.basic import VectorCombiner
        from ..nodes.util.fusion import FusedBatchTransformer, GatherConcatStage
        from .operators import GatherTransformerOperator

        graph, prefixes = plan
        gathers = [n for n in sorted(graph.operators, key=lambda n: n.id)
                   if isinstance(graph.get_operator(n),
                                 GatherTransformerOperator)]
        for g in gathers:
            if g not in graph.operators:
                continue
            deps = graph.get_dependencies(g)
            if not deps or not all(isinstance(d, NodeId) for d in deps):
                continue
            srcs = set()
            ok = True
            for b in deps:
                op = graph.get_operator(b)
                bdeps = graph.get_dependencies(b)
                if not (getattr(op, "fusable", False) and len(bdeps) == 1
                        and set(children(graph, b)) == {g}):
                    ok = False
                    break
                srcs.add(bdeps[0])
            if not ok or len(srcs) != 1:
                continue
            kids = children(graph, g)
            if len(kids) != 1:
                continue
            (kid,) = kids
            if not isinstance(kid, NodeId) or not isinstance(
                    graph.get_operator(kid), VectorCombiner):
                continue
            if graph.get_dependencies(kid) != (g,):
                continue
            (src,) = srcs
            _record_fusion_decision(
                "fusion", type(self).__name__, list(deps) + [g, kid],
                [graph.get_operator(b).label for b in deps]
                + [graph.get_operator(g).label,
                   graph.get_operator(kid).label],
                "gather_concat_program", len(deps) + 1, graph=graph)
            stage = GatherConcatStage([graph.get_operator(b) for b in deps])
            graph = graph.set_operator(
                kid, FusedBatchTransformer([stage], microbatch=self.microbatch))
            graph = graph.set_dependencies(kid, (src,))
            graph = graph.remove_node(g)
            prefixes.pop(g, None)
            for b in dict.fromkeys(deps):
                graph = graph.remove_node(b)
                prefixes.pop(b, None)
        return graph, prefixes

    def apply(self, plan: Plan) -> Plan:
        plan = _refuse_oversized(self, plan)
        plan = self._fuse_linear(plan)
        if self.fuse_apply:
            # gather diamonds need the linear pass FIRST (each branch
            # collapses to one node over the shared source), and another
            # linear pass AFTER so the collapsed combiner chains with
            # its downstream neighbors (delegates, argmax)
            plan = self._fuse_gathers(plan)
            plan = self._fuse_linear(plan)
        return plan

    def _fuse_linear(self, plan: Plan) -> Plan:
        from ..nodes.util.fusion import FusedBatchTransformer

        graph, prefixes = plan
        visited: set = set()
        chains: List[List[NodeId]] = []
        for node in sorted(graph.operators, key=lambda n: n.id):
            if node in visited or not self._fusable(graph, node):
                continue
            # walk up to the chain head (any member finds the same head,
            # so discovery is independent of iteration order)
            head = node
            while True:
                dep = self._data_dep(graph, head)
                if (
                    isinstance(dep, NodeId)
                    and self._fusable(graph, dep)
                    and len(children(graph, dep)) == 1
                ):
                    head = dep
                else:
                    break
            # walk down collecting the chain; a fan-out terminates it
            chain = [head]
            cur = head
            while True:
                kids = children(graph, cur)
                if len(kids) != 1:
                    break
                (kid,) = kids
                if (
                    isinstance(kid, NodeId)
                    and self._fusable(graph, kid)
                    # the child must consume cur as its DATA input — a
                    # delegate whose *estimator* feeds from cur is a fit
                    # boundary, not a chain link
                    and self._data_dep(graph, kid) == cur
                ):
                    chain.append(kid)
                    cur = kid
                else:
                    break
            visited.update(chain)
            if len(chain) >= 2:
                chains.append(chain)

        for chain in chains:
            if any(n not in graph.operators for n in chain):
                continue  # already rewritten by an overlapping chain
            _record_fusion_decision(
                "fusion", type(self).__name__, chain,
                [graph.get_operator(n).label for n in chain],
                "fused_chain_program", len(chain), graph=graph)
            head_data_dep = self._data_dep(graph, chain[0])
            est_deps: List = []
            stage_specs: List = []
            for n in chain:
                op = graph.get_operator(n)
                if isinstance(op, DelegatingOperator):
                    stage_specs.append(_FitSlot(len(est_deps)))
                    est_deps.append(graph.get_dependencies(n)[0])
                else:
                    stage_specs.append(op)
            if est_deps:
                fused: Operator = FusedChainOperator(
                    stage_specs, microbatch=self.microbatch)
                new_deps = tuple(est_deps) + (head_data_dep,)
            else:
                fused = FusedBatchTransformer(
                    stage_specs, microbatch=self.microbatch)
                new_deps = (head_data_dep,)
            graph = graph.set_operator(chain[0], fused)
            # rewire users of the tail to the head, then drop the rest
            graph = graph.replace_dependency(chain[-1], chain[0])
            # the head now (wrongly) depends on itself via the rewire if
            # the chain's second node pointed at head — restore true deps
            graph = graph.set_dependencies(chain[0], new_deps)
            for n in reversed(chain[1:]):
                graph = graph.set_dependencies(n, ())
                graph = graph.remove_node(n)
            for n in chain[1:]:
                prefixes.pop(n, None)
        return graph, prefixes
