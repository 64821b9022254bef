"""Catalyst-style rule engine + the standard optimization rules.

Mirrors reference workflow/{Rule,RuleExecutor,DefaultOptimizer}.scala and
the individual rules:
  - ExtractSaveablePrefixes + SavedStateLoadRule — fitted-state reuse
    (ExtractSaveablePrefixes.scala:9-22, SavedStateLoadRule.scala:7-20)
  - UnusedBranchRemovalRule — dead-branch elimination
    (UnusedBranchRemovalRule.scala:7-24)
  - EquivalentNodeMergeRule — common-subexpression elimination
    (EquivalentNodeMergeRule.scala:13-48)
  - NodeOptimizationRule — sample-driven node-level implementation choice
    (NodeOptimizationRule.scala:14-198)

A *plan* is ``(Graph, dict[NodeId, Prefix])`` where the prefix map carries
only the saveable nodes' structural prefixes.
"""

from __future__ import annotations

import hashlib
import logging
import re
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from .analysis import ancestors, linearize
from .env import PipelineEnv, Prefix, compute_prefix
from .expressions import DatasetExpression
from .graph import Graph, NodeId, SinkId, SourceId
from .operators import (
    DatasetOperator,
    EstimatorOperator,
    ExpressionOperator,
    Operator,
)

logger = logging.getLogger(__name__)

Plan = Tuple[Graph, Dict[NodeId, Prefix]]

#: an address in a repr: never part of a digest
_ADDRESS = re.compile(r" at 0x[0-9a-f]+")
#: tags that hold the planner's prediction and not its decision
_PREDICTION_TAGS = ("planned_kernel_seconds",
                    "planned_kernel_statically_verified")
#: digest of a graph's labels -> digest of the last plan this process
#: made for a graph of those labels (`_note_plan`)
_LAST_PLAN: Dict[str, str] = {}
_LAST_PLAN_MAX = 256


def _hexdigest(text: str) -> str:
    return hashlib.sha1(text.encode()).hexdigest()[:16]


def plan_digest(graph: Graph) -> str:
    """What the optimizer decided for ``graph``, as sixteen hex digits:
    the operators' labels in topological order (cache and spill markers
    among them), each with its ``planned_*`` tags, a fused program's
    microbatch and a dataset's placement, and the planned chunk size.
    Shapes, labels and tags only: no array is read, and nothing that
    holds an object's address is hashed, so two processes that decide
    alike read alike."""
    from .env import planned_chunk_size

    lines = [f"chunk={planned_chunk_size()}"]
    operators = graph.operators
    for vid in linearize(graph):
        op = operators.get(vid)
        if op is None:
            continue  # a source or a sink
        line = op.label
        microbatch = getattr(op, "microbatch", None)
        if microbatch is not None:
            line += f" microbatch={microbatch}"
        tags = [tag for tag in getattr(op, "__dict__", ())
                if tag.startswith("planned_") and tag not in _PREDICTION_TAGS]
        for tag in sorted(tags):
            line += f" {tag}={getattr(op, tag)!r}"
        if isinstance(op, DatasetOperator):
            import jax

            for leaf in jax.tree_util.tree_leaves(
                    getattr(op.dataset, "data", None)):
                spec = getattr(getattr(leaf, "sharding", None), "spec", None)
                if spec is not None:
                    line += f" placed={spec!r}"
        lines.append(line)
    return _hexdigest(_ADDRESS.sub("", "\n".join(lines)))


def _note_plan(raw: Graph, planned: Graph) -> str:
    """The plan's digest; adds 1 to ``planner.plan_changes`` when it
    differs from the last plan made for a graph of ``raw``'s labels (a
    first plan has nothing to differ from)."""
    from ..telemetry import counter

    digest = plan_digest(planned)
    operators = raw.operators
    key = _hexdigest("\n".join(
        operators[vid].label
        for vid in sorted(operators, key=lambda n: n.id)))
    last = _LAST_PLAN.get(key)
    if last is not None and last != digest:
        counter("planner.plan_changes").inc()
    if last is None and len(_LAST_PLAN) >= _LAST_PLAN_MAX:
        _LAST_PLAN.clear()
    _LAST_PLAN[key] = digest
    return digest


def _spec_pass(graph: Graph):
    """`spec_pass(graph, {})`, which traces every stage under
    `eval_shape`, as the `specs` part of the optimize layer."""
    from ..analysis.propagate import spec_pass
    from ..telemetry import span

    with span("specs", cat="phase", layer="optimize", part="specs"):
        return spec_pass(graph, {})


class Rule:
    """A plan→plan rewrite (Rule.scala:11-19)."""

    @property
    def name(self) -> str:
        return type(self).__name__

    def apply(self, plan: Plan) -> Plan:
        raise NotImplementedError


@dataclass
class Batch:
    """A named group of rules with an iteration strategy
    (RuleExecutor.scala:5-27). ``max_iterations=1`` is Once; more is
    FixedPoint."""

    name: str
    rules: List[Rule]
    max_iterations: int = 1


class RuleExecutor:
    """Runs batches of rules, iterating each batch to fixpoint or its
    iteration cap (RuleExecutor.scala:29-84)."""

    @property
    def batches(self) -> List[Batch]:
        raise NotImplementedError

    def execute(self, graph: Graph) -> Plan:
        from ..telemetry import span

        plan: Plan = (graph, {})
        # the self time of this span and of the batches' is the `rules`
        # part of the layer: fusion, CSE, saved state; the planner rules
        # name parts of their own
        with span("optimize", cat="phase", layer="optimize", part="rules",
                  batches=len(self.batches)) as record:
            for batch in self.batches:
                with span(f"optimizer:{batch.name}", cat="phase",
                          layer="optimize", part="rules"):
                    for iteration in range(batch.max_iterations):
                        new_plan = plan
                        for rule in batch.rules:
                            new_plan = rule.apply(new_plan)
                        if self._plans_equal(new_plan, plan):
                            break
                        plan = new_plan
                        if logger.isEnabledFor(logging.DEBUG):
                            logger.debug(
                                "after batch %s iter %d:\n%s",
                                batch.name,
                                iteration,
                                plan[0].to_dot(),
                            )
            digest = _note_plan(graph, plan[0])
            if record is not None:
                record.args["plan"] = digest
        return plan

    @staticmethod
    def _plans_equal(a: Plan, b: Plan) -> bool:
        ga, gb = a[0], b[0]
        return (
            ga.sources == gb.sources
            and ga.operators == gb.operators
            and ga.dependencies == gb.dependencies
            and ga.sink_dependencies == gb.sink_dependencies
            and a[1] == b[1]
        )


class ExtractSaveablePrefixes(Rule):
    """Record the structural prefix of every saveable node — estimators and
    cache markers (ExtractSaveablePrefixes.scala:9-22)."""

    def apply(self, plan: Plan) -> Plan:
        graph, prefixes = plan
        memo: dict = {}
        new_prefixes = dict(prefixes)
        for node, op in graph.operators.items():
            if getattr(op, "saveable", False):
                p = compute_prefix(graph, node, memo)
                if p is not None:
                    new_prefixes[node] = p
        return graph, new_prefixes


class SavedStateLoadRule(Rule):
    """Swap in memoized expressions for nodes whose prefix was already
    executed by an earlier pipeline (SavedStateLoadRule.scala:7-20)."""

    def apply(self, plan: Plan) -> Plan:
        graph, prefixes = plan
        env = PipelineEnv.get()
        for node, prefix in list(prefixes.items()):
            expr = env.state.get(prefix)
            if expr is not None and not isinstance(
                graph.get_operator(node), ExpressionOperator
            ):
                graph = graph.set_operator(
                    node, ExpressionOperator(expr, name=str(prefix.operator_key[0]))
                ).set_dependencies(node, ())
        return graph, prefixes


class UnusedBranchRemovalRule(Rule):
    """Remove nodes that no sink transitively depends on
    (UnusedBranchRemovalRule.scala:7-24). Sources are kept — they are the
    pipeline's input contract."""

    def apply(self, plan: Plan) -> Plan:
        graph, prefixes = plan
        live: set = set()
        for sink in graph.sink_dependencies:
            live |= ancestors(graph, sink)
        dead = [n for n in graph.operators if n not in live]
        # Remove in reverse topological order so users go first.
        order = {v: i for i, v in enumerate(linearize(graph))}
        for n in sorted(dead, key=lambda n: -order.get(n, 0)):
            graph = graph.remove_node(n)
        prefixes = {n: p for n, p in prefixes.items() if n in graph.operators}
        return graph, prefixes


class EquivalentNodeMergeRule(Rule):
    """CSE: merge nodes with identical (operator, dependencies)
    (EquivalentNodeMergeRule.scala:13-48). Run to fixpoint so chains of
    equivalent nodes collapse bottom-up."""

    def apply(self, plan: Plan) -> Plan:
        graph, prefixes = plan
        groups: Dict[tuple, List[NodeId]] = {}
        for node in sorted(graph.operators, key=lambda n: n.id):
            key = (graph.get_operator(node).prefix_key(), graph.get_dependencies(node))
            groups.setdefault(key, []).append(node)
        for nodes in groups.values():
            if len(nodes) < 2:
                continue
            keep, drop = nodes[0], nodes[1:]
            for d in drop:
                graph = graph.replace_dependency(d, keep)
                graph = graph.remove_node(d)
                prefixes.pop(d, None)
        return graph, prefixes


class NodeOptimizationRule(Rule):
    """Execute the DAG on per-shard samples and let each `Optimizable*`
    node choose its concrete implementation from the sample statistics
    (NodeOptimizationRule.scala:14-198).

    A node opts in by exposing ``optimize_from_sample(sample_inputs,
    num_per_shard) -> Operator``. The sample execution replaces every
    DatasetOperator's dataset with a per-shard sample of
    ``samples_per_shard`` items (SampleCollector, default 3/partition in
    the reference).
    """

    def __init__(self, samples_per_shard: int = 3):
        self.samples_per_shard = samples_per_shard

    def apply(self, plan: Plan) -> Plan:
        graph, prefixes = plan
        targets = [
            n
            for n in sorted(graph.operators, key=lambda n: n.id)
            if hasattr(graph.get_operator(n), "optimize_from_sample")
        ]
        if not targets:
            return plan

        # Build the sampled graph: swap each dataset (device or host) for a
        # small sample and record the true per-shard counts so nodes can
        # extrapolate.
        sampled = graph
        num_per_shard: Dict[int, int] = {}
        for node in graph.operators:
            op = graph.get_operator(node)
            if isinstance(op, DatasetOperator) and hasattr(
                op.dataset, "sample_per_shard"
            ):
                num_per_shard[node.id] = op.dataset.per_shard_count
                sampled = sampled.set_operator(
                    node,
                    DatasetOperator(
                        op.dataset.sample_per_shard(self.samples_per_shard),
                        name=f"sample[{op.name}]",
                    ),
                )
        scale = max(num_per_shard.values(), default=self.samples_per_shard)

        from .executor import GraphExecutor

        sample_exec = GraphExecutor(sampled, optimize=False)
        for node in targets:
            op = graph.get_operator(node)
            try:
                sample_inputs = [
                    sample_exec.execute(d).get for d in sampled.get_dependencies(node)
                ]
            except ValueError:
                continue  # depends on an unbound source; cannot sample
            chosen = op.optimize_from_sample(sample_inputs, scale)
            if chosen is not None and chosen is not op:
                logger.info("NodeOptimizationRule: %s -> %s", op.label, chosen.label)
                graph = graph.set_operator(node, chosen)
        return graph, prefixes


#: bytes of resident device-dataset data below which the unified
#: planner's priced solve cannot clear a nonzero enforcement floor on
#: any calibrated machine (64 KiB over even the slowest modeled
#: bandwidth, recomputed tens of times across tens of stages, stays
#: under a millisecond) — the cheap pre-filter that keeps tiny test
#: pipelines from paying the jaxpr-priced solve on every optimize.
UNIFIED_SOLVE_MIN_BYTES = 64 << 10


#: graphs whose placement/precision axes an enforced unified plan
#: OWNS — registered by `UnifiedPlannerRule._enforce` whenever the
#: joint optimum deviates on a tagged axis, whether or not the
#: deviation produced tagged operator copies (a joint plan can win by
#: REVERTING the sequential placement to the defaults, by turning a
#: sequential precision trail OFF, or by re-seeding only dataset
#: placements — all tag-free shapes that must still stand the
#: sequential rules down). Weak references: a dropped plan releases
#: its entry.
_UNIFIED_OWNED: "weakref.WeakSet" = weakref.WeakSet()


def unified_enforced(graph: Graph) -> bool:
    """Whether this plan's placement/precision axes are owned by an
    enforced unified plan — the signal for the sequential planner
    rules to stand down instead of re-deciding an axis the joint
    optimizer already decided. The ownership registry covers the
    current optimization; the ``planned_by_unified`` tag scan
    additionally covers re-optimizations of an already-enforced
    graph."""
    return graph in _UNIFIED_OWNED or any(
        getattr(op, "planned_by_unified", False)
        for op in graph.operators.values())


class UnifiedPlannerRule(Rule):
    """Unified plan optimizer: ONE decision IR over {placement family ×
    storage dtype × chunk size × cache point × chain megakernel} per
    stage boundary, priced in seconds by the calibrated roofline time
    model and solved jointly under the HBM budget as a hard constraint
    (`analysis.plan_ir` is the pure decision core; this rule is the
    enforcement shell).

    Runs after fusion/megafusion (the program boundaries that will
    actually execute) and before the sequential planner rules. Reads
    ``ExecutionConfig.unified_planner`` (env
    ``KEYSTONE_UNIFIED_PLANNER``, default on) at optimization time and
    is a strict no-op — the sequential PR-13 passes then run unchanged
    — on host-only plans, on any planner failure, when the joint
    optimum cannot STRICTLY beat the sequential composition scored by
    the same function, and when the win is below the
    ``unified_min_savings_seconds`` enforcement floor.

    Enforcement of a winning joint plan reuses the existing machinery:

      - placement deviations become ``planned_out_spec`` tagged copies
        / `Dataset.reshard` re-seeds exactly like `ShardingPlannerRule`
        (and precision trail wins become ``planned_precision`` tagged
        copies exactly like `PrecisionPlannerRule`); when the joint
        plan deviates on EITHER axis it enforces BOTH itself and marks
        the copies ``planned_by_unified`` so the sequential rules stand
        down — one owner per axis, never two;
      - the chunk decision flows through
        `workflow.env.set_planned_chunk_size`, which
        `utils.batching` and the KP2xx/KP8xx models all read back via
        the one `resolved_chunk_size` resolution;
      - chosen cache points insert `autocache.CacheMarker` nodes where
        the profile-guided greedy used to;
      - chosen chain megakernels become ``planned_kernel`` tagged
        copies of the fused program: `_build_program` swaps the tagged
        stage sub-trail for ONE `pl.pallas_call`
        (`ops.chain_kernels`), with the ``KEYSTONE_CHAIN_KERNELS``
        gate folded into the program cache key so the kill switch is
        bit-for-bit and ledger-attributable.

    Every enforced decision kind emits a ledger record
    (rule=``UnifiedPlannerRule``) whose alternatives are the product
    menu the solver actually scored, so ``--ledger``/``--diff`` and
    `reconcile_decisions` cover the joint plan from day one.
    """

    def apply(self, plan: Plan) -> Plan:
        from .env import execution_config, set_planned_chunk_size

        cfg = execution_config()
        if not cfg.unified_planner:
            return plan  # kill switch: the PR-13 sequential passes
        # every path through this rule re-decides the chunk knob: clear
        # a previous plan's override up front so no bail-out below can
        # leak it into an unrelated pipeline; enforcement re-sets it
        set_planned_chunk_size(None)
        graph, prefixes = plan
        if not ShardingPlannerRule._has_device_dataset(graph):
            return plan
        if not self._worth_solving(graph, cfg):
            return plan
        from ..telemetry import counter, span

        # the span's own self time is the `solve` part: the pre-filter,
        # the sequential point, the chain DP and the descent
        with span("unified_planner", cat="phase", layer="optimize",
                  part="solve"):
            try:
                from ..analysis.plan_ir import plan_unified

                specs, _ = _spec_pass(graph)
                uplan = plan_unified(
                    graph, specs,
                    hbm_budget_bytes=cfg.hbm_budget_bytes,
                    chunk_default=cfg.chunk_size,  # keystone: ignore[KJ015] — the planner IS the decision site: it scores the raw knob as the sequential baseline
                    include_boundary_policies=False,
                    precision_floor_bytes=cfg.precision_min_savings_bytes)
            except Exception:
                logger.debug("unified planner failed; plan unchanged",
                             exc_info=True)
                return plan
            if uplan is None or not uplan.improved or \
                    uplan.savings_seconds < cfg.unified_min_savings_seconds:
                # strict no-op: the sequential rules (place, precision)
                # run next and reproduce the PR-13 plan exactly
                return plan
            counter("planner.unified_plans_enforced").inc()
            counter("planner.unified_seconds_saved").inc(
                uplan.savings_seconds)
            logger.info(
                "UnifiedPlannerRule: enforcing joint plan, predicted "
                "%.3es -> %.3es (%s)", uplan.sequential_seconds,
                uplan.joint_seconds, ", ".join(uplan.changed_kinds()))
            with span("enforce", cat="phase", layer="optimize",
                      part="enforce"):
                graph = self._enforce(graph, uplan, cfg)
        return graph, prefixes

    @staticmethod
    def _worth_solving(graph: Graph, cfg) -> bool:
        """Cheap pre-filter: with a nonzero enforcement floor, skip the
        jaxpr-priced solve when the plan's resident device data is so
        small no modeled win could clear the floor and the chunk axis
        has no trips to save. Floor 0 (tests, explicit opt-in) always
        solves."""
        if cfg.unified_min_savings_seconds <= 0:
            return True
        device_bytes = 0
        max_rows = 0
        for op in graph.operators.values():
            if isinstance(op, DatasetOperator):
                data = getattr(op.dataset, "data", None)
                if data is not None:
                    import jax

                    for leaf in jax.tree_util.tree_leaves(data):
                        device_bytes += int(getattr(leaf, "nbytes", 0))
                        shape = getattr(leaf, "shape", ())
                        if shape:
                            max_rows = max(max_rows, int(shape[0]))
        return (device_bytes >= UNIFIED_SOLVE_MIN_BYTES
                or max_rows > 4 * cfg.chunk_size)  # keystone: ignore[KJ015] — the planner's own pre-filter compares against the undecided knob

    def _enforce(self, graph: Graph, uplan, cfg) -> Graph:
        from .env import set_planned_chunk_size

        kinds = uplan.changed_kinds()
        own_tags = "placement" in kinds or "precision" in kinds
        if own_tags:
            # the joint plan deviates on a tagged axis: enforce BOTH
            # tagged axes itself (sequential rules stand down via the
            # planned_by_unified marks)
            if uplan.sharding is not None:
                self._record(uplan, "placement",
                             uplan.sharding.changed_vertices(), graph)
                graph = ShardingPlannerRule._enforce(
                    graph, uplan.sharding, uplan.mesh, mark_unified=True)
            for vid, decided in sorted(
                    uplan.program_precision.items(),
                    key=lambda kv: getattr(kv[0], "id", -1)):
                if vid not in graph.operators:
                    continue
                storage, saved, menu = decided
                op = graph.get_operator(vid)
                import copy

                new_op = copy.copy(op)
                new_op.planned_precision = storage
                new_op.planned_by_unified = True
                if PrecisionPlannerRule._all_compute_tolerant(
                        graph, vid, op):
                    new_op.planned_matmul_precision = "bfloat16"
                graph = graph.set_operator(vid, new_op)
                PrecisionPlannerRule._record_decision(
                    graph, vid, op, storage, saved, menu,
                    rule="UnifiedPlannerRule")
        if "kernel" in kinds and getattr(cfg, "pallas_kernels", True):
            # the kernel-vs-XLA axis: tag each chosen fused program
            # with its chain-megakernel slice. The tag is latent off
            # the gate (`_kernel_plan` folds in `use_chain_kernels()`),
            # so `KEYSTONE_CHAIN_KERNELS=0` still builds the bit-for-bit
            # XLA program — the ledger record names the flip.
            import copy

            self._record(uplan, "kernel",
                         sorted(uplan.kernel_choices,
                                key=lambda v: getattr(v, "id", -1)), graph)
            for vid, cand in sorted(
                    uplan.kernel_choices.items(),
                    key=lambda kv: getattr(kv[0], "id", -1)):
                if vid not in graph.operators:
                    continue
                start, stop = cand["stage_slice"]
                family = (cand.get("lowerable") or {}).get("family")
                new_op = copy.copy(graph.get_operator(vid))
                new_op.planned_kernel = (int(start), int(stop), family)
                new_op.planned_kernel_seconds = float(
                    cand["kernel_seconds"])
                # the KP10xx static verdict rides with the tag so the
                # chain_kernel span (and reconcile_roofline) can report
                # whether the dispatched geometry was proven safe
                # before any TPU time (analysis/kernels.py)
                new_op.planned_kernel_statically_verified = cand.get(
                    "statically_verified")
                new_op.planned_by_unified = True
                graph = graph.set_operator(vid, new_op)
        if "chunk" in kinds:
            self._record(uplan, "chunk", [], graph)
            set_planned_chunk_size(uplan.chunk_size)
        spilled = set(getattr(uplan.chosen, "spills", frozenset()))
        if "cache" in kinds:
            from .autocache import AutoCacheRule

            # spilled vids live in `caches` too — they are enforced by
            # the spill branch below as host-placed markers, never
            # double-inserted here as device caches
            device_caches = [v for v in uplan.cache_vertices
                             if v not in spilled]
            if device_caches:
                self._record(uplan, "cache", device_caches, graph)
            for vid in sorted(device_caches,
                              key=lambda v: -getattr(v, "id", -1)):
                if vid in graph.operators:
                    graph = AutoCacheRule._insert_cache(graph, vid)
        if "spill" in kinds and getattr(cfg, "ooc_spill", True):
            # the spill tier: a host-placed CacheMarker materializes the
            # value as numpy on host and re-enters the device through
            # the windowed prefetcher. KEYSTONE_OOC_SPILL=0 never gets
            # here (plan_unified scores no spill toggles), but the gate
            # is belt-and-braces against a hand-built plan.
            from .autocache import AutoCacheRule

            self._record(uplan, "spill", uplan.spill_vertices, graph)
            for vid in sorted(uplan.spill_vertices,
                              key=lambda v: -getattr(v, "id", -1)):
                if vid in graph.operators:
                    graph = AutoCacheRule._insert_cache(
                        graph, vid, placement="host")
        if own_tags:
            # ownership survives tag-free deviations (a reverted
            # sequential placement, a trail turned off, dataset-only
            # re-seeds): the sequential rules stand down on THIS graph
            _UNIFIED_OWNED.add(graph)
        return graph

    @staticmethod
    def _record(uplan, kind: str, vertices, graph: Graph) -> None:
        """One ledger record per enforced joint decision kind: the
        chosen entry, the product menu the solver actually scored as
        the alternatives, and the predicted seconds in the shared time
        model's units. Never raises."""
        try:
            from ..analysis.propagate import _label
            from ..telemetry import ledger

            # one (vertex, label) pair per vertex still present in the
            # enforced graph — consumers zip the two lists
            present = [v for v in vertices
                       if v in getattr(graph, "operators", {})]
            chosen = {
                "entry": "joint_optimum",
                "predicted_seconds": float(uplan.joint_seconds),
                "chunk_size": int(uplan.chunk_size),
            }
            if kind == "chunk":
                chosen["sequential_chunk_size"] = int(
                    uplan.default_chunk_size)
            if kind == "cache":
                chosen["cache_points"] = [getattr(v, "id", -1)
                                          for v in present]
            if kind == "spill":
                chosen["spill_points"] = [getattr(v, "id", -1)
                                          for v in present]
                chosen["placement"] = "host"
                preds = getattr(uplan, "spill_predictions", {}) or {}
                chosen["spills"] = [
                    dict(preds.get(v, {}), vertex=getattr(v, "id", -1))
                    for v in present
                ]
            if kind == "kernel":
                chosen["kernels"] = [
                    {
                        "vertex": getattr(v, "id", -1),
                        "family": (c.get("lowerable") or {}).get("family"),
                        "stage_slice": list(c.get("stage_slice") or ()),
                        "kernel_seconds": c.get("kernel_seconds"),
                        "chain_seconds": c.get("chain_seconds"),
                        "boundary_bytes": c.get("boundary_bytes"),
                        "statically_verified": c.get(
                            "statically_verified"),
                    }
                    for v in present
                    for c in [uplan.kernel_choices[v]]
                ]
            # each kind's record carries ITS axis's slice of the
            # product menu (chunk records the ladder, cache records
            # the cache toggles, precision the trail toggles) plus the
            # cross-axis baselines — not the full menu duplicated per
            # kind with other axes' entries posing as alternatives
            prefixes = {"chunk": ("chunk_",), "cache": ("cache_",),
                        "precision": ("trail_",),
                        "kernel": ("kernel_",),
                        "spill": ("spill_", "cache_"),
                        "placement": ()}.get(kind, ())
            alternatives = [
                c for c in uplan.scored_candidates
                if c.get("entry") in ("sequential", "chain_dp_product")
                or (prefixes
                    and str(c.get("entry", "")).startswith(prefixes))
            ]
            predicted = {
                "predicted_seconds": float(uplan.joint_seconds),
                "sequential_seconds": float(uplan.sequential_seconds),
                "seconds_saved": float(uplan.savings_seconds),
            }
            if kind == "spill":
                preds = getattr(uplan, "spill_predictions", {}) or {}
                reload_s = sum(
                    float(p.get("reload_seconds") or 0.0)
                    for v, p in preds.items() if v in present)
                if reload_s:
                    predicted["reload_seconds"] = reload_s
            ledger.record_decision(
                kind=kind,
                rule="UnifiedPlannerRule",
                vertices=[getattr(v, "id", -1) for v in present],
                labels=[_label(graph, v) for v in present],
                chosen=chosen,
                alternatives=alternatives,
                predicted=predicted,
            )
        except Exception:
            logger.debug("unified decision not recorded", exc_info=True)


class _ClearPlannedChunkRule(Rule):
    """Built in place of `UnifiedPlannerRule` when the constructor opts
    out (`DefaultOptimizer(unified_planner=False)`): a pre-unified
    optimizer must not execute — or statically model — under a
    PREVIOUS plan's enforced chunk decision, so the process-global
    override is cleared at the same point in the batch order where the
    unified rule would have re-decided it. The graph is untouched
    (bit-for-bit PR-13)."""

    def apply(self, plan: Plan) -> Plan:
        from .env import set_planned_chunk_size

        set_planned_chunk_size(None)
        return plan


class ShardingPlannerRule(Rule):
    """Sharding-aware plan optimizer: choose, price, and ENFORCE
    per-stage placement as an optimizer decision (`analysis.planner` is
    the pure decision core; this rule is the enforcement shell).

    Runs after fusion/megafusion so the placement decision sees the
    program boundaries that will actually execute. Reads
    `ExecutionConfig.sharding_planner` (env ``KEYSTONE_SHARDING_PLANNER``,
    default on) at optimization time and is a strict no-op on 1-device
    meshes, on unbound/abstract graphs, when the planner cannot beat the
    PR-8 default placement's priced boundary bytes, and on any planner
    failure — so the kill switch (and every no-win case) reproduces the
    PR-8 plan bit-for-bit.

    Enforcement of a winning assignment:

      - fused / megafused program operators (`FusedChainOperator`,
        `FusedBatchTransformer`) whose chosen output placement deviates
        from the default are replaced with tagged copies carrying
        ``planned_out_spec``; the program builder lowers that into a
        ``jax.lax.with_sharding_constraint`` on the program output (and
        keys the program cache on it), so the chosen layout is baked
        into the compiled XLA program;
      - plan-input `DatasetOperator`s are re-seeded: the dataset is
        moved to the chosen placement through `collectives.reshard`
        (identity short-circuit — an unchanged placement moves
        nothing), so execution starts from the planned layout instead
        of the static default.

    Operators are copied, never mutated in place: shared instances
    reused across pipelines must not carry one plan's placement into
    another's.
    """

    def apply(self, plan: Plan) -> Plan:
        from .env import execution_config

        cfg = execution_config()
        if not cfg.sharding_planner:
            return plan  # kill switch: the PR-8 plan, bit for bit
        if cfg.unified_planner and unified_enforced(plan[0]):
            return plan  # the unified planner enforced placement jointly
        from ..parallel import mesh as meshlib

        mesh = meshlib.current_mesh()
        if int(mesh.devices.size) <= 1:
            return plan
        from ..telemetry import counter, span

        graph, prefixes = plan
        if not self._has_device_dataset(graph):
            # nothing to place: the planner decides DATASET placement,
            # and a datum/host-only plan has no device data boundary.
            # Skipping also keeps the single-datum serving path free of
            # the planner's abstract traces (spec_pass runs user apply
            # bodies under eval_shape).
            return plan
        with span("sharding_planner", cat="phase", layer="optimize",
                  part="sequential", devices=int(mesh.devices.size)):
            try:
                from ..analysis.planner import plan_sharding

                specs, _ = _spec_pass(graph)
                splan = plan_sharding(
                    graph, specs, mesh=mesh,
                    hbm_budget_bytes=cfg.hbm_budget_bytes)
            except Exception:
                logger.debug("sharding planner failed; plan unchanged",
                             exc_info=True)
                return plan
            if splan is None or not splan.improved:
                return plan
            counter("planner.boundary_bytes_saved").inc(splan.savings_bytes)
            counter("planner.plans_enforced").inc()
            logger.info(
                "ShardingPlannerRule: enforcing plan, boundary bytes "
                "%d -> %d (%d saved)", int(splan.default_cost_bytes),
                int(splan.planned_cost_bytes), splan.savings_bytes)
            self._record_decision(graph, splan)
            graph = self._enforce(graph, splan, mesh)
        return graph, prefixes

    @staticmethod
    def _record_decision(graph: Graph, splan) -> None:
        """One ledger record per enforced placement plan: the changed
        stages, the chosen family assignment, the planner's own scored
        candidate menu as the priced alternatives (the decision cores
        already score these — expose them instead of discarding), and
        the predicted boundary-byte arithmetic in the shared
        `collective_cost` units. Never raises: a ledger bug must not
        break the enforcement it records."""
        try:
            from ..analysis.propagate import _label
            from ..telemetry import ledger

            changed = splan.changed_vertices()
            chosen_cost = float(splan.planned_cost_bytes)
            alternatives = [c for c in splan.scored_candidates
                            if c.get("cost_bytes") != chosen_cost]
            if not alternatives:
                alternatives = [
                    {"entry": "default",
                     "cost_bytes": float(splan.default_cost_bytes)}]
            ledger.record_decision(
                kind="placement",
                rule="ShardingPlannerRule",
                vertices=[getattr(v, "id", -1) for v in changed],
                labels=[_label(graph, v) for v in changed],
                chosen={
                    "entry": "planned_assignment",
                    "families": {str(v): splan.families.get(v)
                                 for v in changed},
                    "cost_bytes": chosen_cost,
                },
                alternatives=alternatives,
                predicted={
                    "boundary_bytes": chosen_cost,
                    "boundary_bytes_saved": int(splan.savings_bytes),
                },
            )
        except Exception:
            logger.debug("placement decision not recorded", exc_info=True)

    @staticmethod
    def _has_device_dataset(graph: Graph) -> bool:
        for vid in graph.operators:
            op = graph.get_operator(vid)
            if isinstance(op, DatasetOperator) \
                    and getattr(op.dataset, "data", None) is not None:
                return True
        return False

    @staticmethod
    def _enforce(graph: Graph, splan, mesh,
                 mark_unified: bool = False) -> Graph:
        import copy

        from ..nodes.util.fusion import FusedBatchTransformer
        from .fusion_rule import FusedChainOperator

        for vid in splan.changed_vertices():
            if vid not in getattr(graph, "operators", {}):
                continue
            op = graph.get_operator(vid)
            spec = splan.spec_for(vid)
            if spec is None:
                continue
            if isinstance(op, (FusedChainOperator, FusedBatchTransformer)):
                tagged = copy.copy(op)
                tagged.planned_out_spec = spec
                if mark_unified:
                    tagged.planned_by_unified = True
                graph = graph.set_operator(vid, tagged)
            elif isinstance(op, DatasetOperator) \
                    and hasattr(op.dataset, "reshard"):
                try:
                    reseeded = op.dataset.reshard(spec)
                except Exception:
                    continue  # placement stays default; the plan's
                    # other enforcement points still apply
                graph = graph.set_operator(
                    vid, DatasetOperator(reseeded, name=op.name))
        return graph


class PrecisionPlannerRule(Rule):
    """Mixed-precision policy pass: choose, price, and ENFORCE per-stage
    storage dtypes as an optimizer decision (`analysis.precision` is the
    pure decision core; this rule is the enforcement shell — the PR-9
    placement pattern applied to precision).

    Runs after `ShardingPlannerRule` so the dtype decision sees the
    program boundaries (and placements) that will actually execute.
    Reads `ExecutionConfig.precision_planner` (env
    ``KEYSTONE_PRECISION_PLANNER``, default on) at optimization time and
    is a strict no-op on plans with no fused program, on unbound or
    abstract graphs, when no policy clears the
    ``precision_min_savings_bytes`` enforcement floor, and on any
    planner failure — so the kill switch (and every no-win case)
    reproduces the PR-9 plan bit-for-bit.

    Enforcement of a winning policy: each fused/megafused program
    operator whose internal stage trail admits a priced bf16 win is
    replaced with a tagged copy carrying ``planned_precision`` (one
    storage dtype per `fused_stages` output); `_build_program`
    lowers that into ``convert_element_type`` casts between stages —
    cache-keyed like ``planned_out_spec``, AOT-warmable, and visible in
    the compiled jaxpr. When every stage of the program tolerates
    reduced compute the tagged copy additionally carries
    ``planned_matmul_precision="bfloat16"``, baking a
    `jax.default_matmul_precision` scope into the traced program. The
    program's FINAL output dtype is never changed, so downstream
    consumers (and the pipeline's visible output) see exactly the PR-9
    dtypes.

    Operators are copied, never mutated in place: shared instances
    reused across pipelines must not carry one plan's policy into
    another's.
    """

    def apply(self, plan: Plan) -> Plan:
        from .env import execution_config

        cfg = execution_config()
        if not cfg.precision_planner:
            return plan  # kill switch: the PR-9 plan, bit for bit
        if cfg.unified_planner and unified_enforced(plan[0]):
            return plan  # the unified planner enforced precision jointly
        graph, prefixes = plan
        from .fusion_rule import FusedChainOperator

        from ..nodes.util.fusion import FusedBatchTransformer

        targets = [
            vid for vid in sorted(graph.operators, key=lambda n: n.id)
            if isinstance(graph.get_operator(vid),
                          (FusedChainOperator, FusedBatchTransformer))
        ]
        if not targets:
            return plan
        if not ShardingPlannerRule._has_device_dataset(graph):
            # the policy prices DATASET boundaries (plan_stage_precision
            # requires a device dataset data dep), so a datum/host-only
            # serving plan can never enforce anything — skip it before
            # spec_pass runs user apply bodies under eval_shape (the
            # same guard the sharding planner carries)
            return plan
        from ..telemetry import counter, span

        with span("precision_planner", cat="phase", layer="optimize",
                  part="sequential", programs=len(targets)):
            try:
                from ..analysis.precision import plan_stage_precision

                specs, _ = _spec_pass(graph)
                total_saved = 0
                tagged = 0
                for vid in targets:
                    op = graph.get_operator(vid)
                    if getattr(op, "planned_precision", None) is not None:
                        continue  # already planned (re-optimization)
                    decided = plan_stage_precision(graph, vid, op, specs)
                    if decided is None:
                        continue
                    storage, saved, menu = decided
                    if saved < cfg.precision_min_savings_bytes:
                        continue  # below the enforcement floor: the
                        # program stays bit-identical to PR 9
                    import copy

                    new_op = copy.copy(op)
                    new_op.planned_precision = storage
                    if self._all_compute_tolerant(graph, vid, op):
                        new_op.planned_matmul_precision = "bfloat16"
                    graph = graph.set_operator(vid, new_op)
                    self._record_decision(graph, vid, op, storage, saved,
                                          menu)
                    total_saved += saved
                    tagged += 1
            except Exception:
                logger.debug("precision planner failed; plan unchanged",
                             exc_info=True)
                return plan
            if not tagged:
                return plan
            counter("planner.bytes_halved").inc(total_saved)
            counter("planner.precision_policies_enforced").inc(tagged)
            logger.info(
                "PrecisionPlannerRule: enforcing bf16 storage on %d "
                "program(s), %d boundary bytes saved", tagged, total_saved)
        return graph, prefixes

    @staticmethod
    def _record_decision(graph: Graph, vid, op, storage, saved: int,
                         menu=None, rule: str = "PrecisionPlannerRule"
                         ) -> None:
        """One ledger record per program operator that received a baked
        storage policy: the chosen per-stage dtype trail, the priced
        alternatives it beat — the all-f32 reference (priced by the
        same `policy_nbytes` arithmetic: keeping f32 forgoes exactly
        ``saved`` bytes) plus the decision core's own candidate-run
        menu (`analysis.precision.stage_policy_menu`: every maximal
        legal bf16 run the chain DP scored, kept or rejected) — and
        the predicted cast count (the casts the program builder will
        bake — `precision.casts_baked` observes the real number).
        Never raises: a ledger bug must not break the enforcement it
        records."""
        try:
            from ..telemetry import ledger

            casts = sum(1 for s in storage if s is not None)
            alternatives = [{
                "entry": "f32_reference",
                "bytes_saved": 0,
                "cost_bytes_extra": int(saved),
            }]
            for cand in menu or []:
                if cand.get("kept"):
                    continue  # part of (or superseded by) the chosen trail
                alternatives.append({
                    "entry": cand["entry"],
                    "bytes_saved": int(cand.get("bytes_saved", 0)),
                    "cast_penalty_bytes": int(
                        cand.get("cast_penalty_bytes", 0)),
                    "rejected": cand.get("dropped", "below_cast_penalty"),
                })
            ledger.record_decision(
                kind="precision",
                rule=rule,
                vertices=[getattr(vid, "id", -1)],
                labels=[op.label],
                chosen={
                    "entry": "bf16_storage",
                    "storage": [s for s in storage],
                    "bytes_saved": int(saved),
                    "cost_bytes_extra": 0,
                },
                alternatives=alternatives,
                predicted={
                    "policy_bytes_saved": int(saved),
                    "casts_baked": casts,
                },
            )
        except Exception:
            logger.debug("precision decision not recorded", exc_info=True)

    @staticmethod
    def _all_compute_tolerant(graph: Graph, vid, op) -> bool:
        from ..analysis.precision import TOLERANT, stage_tolerance

        stage_specs = getattr(op, "stage_specs", None)
        if stage_specs is None:
            stage_specs = list(getattr(op, "stages", []))
        return bool(stage_specs) and all(
            stage_tolerance(s, graph, vid) == TOLERANT
            for s in stage_specs)


class Optimizer(RuleExecutor):
    pass


class DefaultOptimizer(Optimizer):
    """Batches mirror DefaultOptimizer.scala:8-31 (saved-state reuse and
    dead-branch removal once; CSE to fixpoint; node-level optimization
    once) plus the TPU-native stage-fusion pass (see fusion_rule.py).
    ``fusion_microbatch`` None (the default) leaves every fused program's
    microbatch to the bytes a row makes in it under the HBM budget
    (`workflow.budget.microbatch_rows`); a number overrides that."""

    def __init__(self, samples_per_shard: int = 3, fuse: bool = True,
                 fusion_microbatch: Optional[int] = None,
                 fuse_apply: bool = True,
                 megafuse: bool = True, sharding_planner: bool = True,
                 precision_planner: bool = True,
                 unified_planner: bool = True):
        from .fusion_rule import MegafusionRule, NodeFusionRule

        self._batches = [
            Batch(
                "state",
                [ExtractSaveablePrefixes(), SavedStateLoadRule(), UnusedBranchRemovalRule()],
            ),
            Batch("cse", [EquivalentNodeMergeRule()], max_iterations=64),
        ]
        if fuse:
            # fuse_apply=False reproduces the PR-3 plan (transformer
            # chains only, no fusion through estimator apply boundaries)
            # — the dispatch-count bench's "legacy" baseline
            fuse_rules: List[Rule] = [
                NodeFusionRule(fusion_microbatch, fuse_apply=fuse_apply)]
            if fuse_apply and megafuse:
                # whole-plan megafusion rides AFTER node fusion: it
                # merges the fused super-nodes the linear pass leaves
                # behind into ONE scan-bodied program. Gated twice: the
                # constructor flag builds the PR-4/5 optimizer exactly,
                # and the rule itself reads `ExecutionConfig.megafusion`
                # (KEYSTONE_MEGAFUSION) at optimization time.
                fuse_rules.append(MegafusionRule(fusion_microbatch))
            self._batches.append(Batch("fuse", fuse_rules))
        if unified_planner:
            # the unified plan optimizer rides AFTER megafusion (it
            # must see the program boundaries that will execute) and
            # BEFORE the sequential planner rules: when its joint
            # optimum strictly beats the sequential composition it
            # enforces all tagged axes itself and the sequential rules
            # stand down; otherwise it is a strict no-op and the PR-13
            # passes run unchanged. Gated twice like its siblings: the
            # constructor flag builds the PR-13 optimizer exactly, and
            # the rule reads `ExecutionConfig.unified_planner`
            # (KEYSTONE_UNIFIED_PLANNER) at optimization time.
            self._batches.append(Batch("unified", [UnifiedPlannerRule()]))
        else:
            # the constructor opt-out still clears a previous plan's
            # enforced chunk override (the env kill switch hides it by
            # itself; the constructor channel must too, or a stale
            # decision would leak into this PR-13-exact plan)
            self._batches.append(Batch("unified",
                                       [_ClearPlannedChunkRule()]))
        if sharding_planner:
            # placement rides AFTER megafusion: the planner must see the
            # program boundaries that will actually execute. Gated twice
            # like megafusion: the constructor flag builds the PR-8
            # optimizer exactly, and the rule reads
            # `ExecutionConfig.sharding_planner`
            # (KEYSTONE_SHARDING_PLANNER) at optimization time.
            self._batches.append(Batch("place", [ShardingPlannerRule()]))
        if precision_planner:
            # precision rides AFTER placement: the dtype decision must
            # see the fused program boundaries (and their placements)
            # that will actually execute. Gated twice like the sharding
            # planner: the constructor flag builds the PR-9 optimizer
            # exactly, and the rule reads
            # `ExecutionConfig.precision_planner`
            # (KEYSTONE_PRECISION_PLANNER) at optimization time.
            self._batches.append(Batch("precision",
                                       [PrecisionPlannerRule()]))
        self._batches.append(Batch("node-opt", [NodeOptimizationRule(samples_per_shard)]))

    @property
    def batches(self) -> List[Batch]:
        return self._batches


class AutoCachingOptimizer(Optimizer):
    """DefaultOptimizer plus profile-guided automatic caching
    (DefaultOptimizer.scala:8-31 with AutoCacheRule appended)."""

    def __init__(self, strategy: str = "greedy", mem_budget_bytes: int = None):
        from .autocache import AutoCacheRule

        self._batches = DefaultOptimizer().batches + [
            Batch("auto-cache", [AutoCacheRule(strategy, mem_budget_bytes)])
        ]

    @property
    def batches(self) -> List[Batch]:
        return self._batches
