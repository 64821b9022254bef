"""Unified plan optimizer: ONE decision IR over the whole choice space.

PRs 4–10 built five *sequential greedy* passes — fuse, megafuse, place
(`analysis.planner`), retype (`analysis.precision`) — while
``chunk_size``, streaming-vs-materialization, and autocache placement
stayed manual knobs outside the optimizer entirely. Each pass wins its
axis locally and can still lose jointly: a bf16 policy halves the very
boundary bytes whose all-to-all price drove the placement choice, and a
chunk size that fixes KP804 underfilled scans can bust the KP600
per-device budget. This module is the ROADMAP's refactor-that-unlocks:
KeystoneML's cost-based whole-pipeline optimizer thesis (arXiv
1610.09451) fused with the memory-safe-XLA discipline of treating the
HBM budget as a hard constraint, not an afterthought (arXiv 2206.14148).

The IR: per choosable stage boundary a product menu

    {placement family (PR 9's MENU, legality = the `leaf_sharding`
     divisibility contract)
     × storage dtype (PR 10's policies, legality = `precision_tolerance`
       flowed through passthrough stages; inside fused programs the
       per-trail `plan_stage_precision` decision)
     × cache point (legality = `AutoCacheRule._candidates`: demanded
       more than once, not already cached)}

plus one plan-level axis, the chunk size from the PR-5 pow-2 ladder,
plus a per-fused-program kernel axis: lower a KP801 candidate's stage
sub-trail to ONE double-buffered Pallas chain megakernel
(`ops.chain_kernels`) or keep XLA's stage-at-a-time lowering. The
kernel side prices ONE HBM pass of in+out bytes (the chain's traffic
minus its 2× boundary round-trips); non-lowerable statics or a
VMEM-infeasible block geometry price INF and demote cleanly — a scored
demotion record, never a compile crash.

Every assignment is priced by ONE calibrated time model, in seconds:

  - per stage, ``roofline.stage_cost(flops, policy_nbytes)`` — the
    KP8xx jaxpr-walk FLOPs against the boundary bytes the chosen dtypes
    actually move (`precision.policy_nbytes`), on the calibrated
    machine (`calibrate.machine_rates`, or the
    `reconcile.drift_cost_weights`-recalibrated peaks when a trace
    artifact is supplied);
  - plus ``collective_cost`` seconds at placement-family flips, unmet
    `abstract_sharding` demands, and host gathers — literally the same
    `CollectiveCost` objects the KP601/KP603 lints and the byte planner
    read (`planner.transition_cost` / `demand_cost` / `gather_cost`);
  - plus a per-dispatch floor (`roofline.DISPATCH_OVERHEAD_S`) per
    chunk trip, which is what makes the chunk axis a real decision
    (KP804's underfilled-scan economics, priced instead of linted);
  - plus the cast seconds every storage flip costs
    (`precision.CAST_PENALTY_BYTES` over the machine's bandwidth);
  - each stage weighted by its recomputation count under the chosen
    cache points (`autocache.get_runs` — the reference's lazy
    re-execution semantics, the same model `AutoCacheRule` prices),
    which is what makes cache placement a priced decision instead of a
    profile-then-guess pass.

The KP600 per-device budget is a hard constraint: a family whose
per-device residency, a chunk whose in-flight rows, or a cache set
whose pinned bytes bust it price INFEASIBLE and are pruned — never
linted after the fact.

Solver: the existing chain-DP + frontier-merge shape generalized to the
product menu (states are (family, policy) pairs along fan-out-free
chains, greedy freeze at fan-in), then bounded local descent ACROSS
decision kinds — family/policy sweeps, program-trail toggles, the chunk
ladder, greedy cache additions — every candidate re-scored by the one
shared scorer. The sequential PR-13 composition (plan_sharding's
placement, the per-program precision trails, the config chunk, no
caches) is always scored as a candidate by the SAME function, so the
joint plan can never lose to it: ``improved`` is a strict win or the
plan IS the sequential assignment and nothing deviates.

Everything here is pure spec arithmetic — no data moves, no device
allocates. Enforcement lives in `workflow.optimizer.UnifiedPlannerRule`
(placement/precision tags, the `workflow.env.set_planned_chunk_size`
chunk override, `CacheMarker` insertion).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Any, Dict, FrozenSet, List, Optional, Tuple

from ..parallel import mesh as meshlib
from ..workflow.graph import Graph, GraphId, NodeId, SinkId
from .planner import (
    FAMILY_REPLICATED,
    ShardingPlan,
    _CostModel,
    demand_cost,
    family_shards,
    gather_cost,
    plan_sharding,
    transition_cost,
)
from .precision import (
    CAST_PENALTY_BYTES,
    POLICY_F32,
    _STORAGE,
    _PrecisionModel,
    plan_precision,
    plan_stage_precision,
    policy_nbytes,
)
from .sharding import DEFAULT_REPLICATED_THRESHOLD
from .propagate import _label, toposort
from .roofline import (
    DISPATCH_OVERHEAD_S,
    Machine,
    default_machine,
    roofline_pass,
    stage_cost,
)
from .specs import DataSpec

_INF = float("inf")

#: the PR-5 pow-2 chunk ladder the chunk axis chooses from (the same
#: shape family `utils.batching._pad_target` pads into, so every chosen
#: chunk is a shape the pad-stable dispatcher already compiles).
CHUNK_LADDER: Tuple[int, ...] = (32, 64, 128, 256, 512, 1024, 2048, 4096)


def machine_from_weights(weights) -> Machine:
    """The roofline `Machine` a `calibrate.CostWeights` implies — the
    recalibration seam: `reconcile.drift_cost_weights(trace)` feeds the
    trace-implied peaks straight into the unified scorer."""
    return Machine(float(weights.peak_flops), float(weights.peak_bw))


# ------------------------------------------------------------ assignment


@dataclass(frozen=True)
class Assignment:
    """One point in the joint decision space. ``families`` and
    ``policies`` are per-vertex; ``trails`` holds the per-fused-program
    bf16-trail on/off decisions; ``chunk`` is the plan-level chunk
    size; ``caches`` the chosen cache points."""

    families: Tuple[Tuple[Any, str], ...] = ()
    policies: Tuple[Tuple[Any, str], ...] = ()
    trails: Tuple[Tuple[Any, bool], ...] = ()
    chunk: int = 256
    caches: FrozenSet = frozenset()
    #: per-fused-program chain-megakernel on/off (the kernel-vs-XLA
    #: axis over the KP801 fused-trail candidates)
    kernels: Tuple[Tuple[Any, bool], ...] = ()
    #: cache points placed on the HOST (⊆ caches): the spill tier.
    #: A spilled cache pins window-residency on device instead of its
    #: full bytes and pays reload seconds (bytes over the calibrated
    #: host↔device bandwidth + the dispatch floor per window trip) —
    #: how a tight KP600 budget becomes satisfiable instead of pruning
    #: every cache entry to INF.
    spills: FrozenSet = frozenset()

    def fam(self) -> Dict[Any, str]:
        return dict(self.families)

    def pol(self) -> Dict[Any, str]:
        return dict(self.policies)

    def trl(self) -> Dict[Any, bool]:
        return dict(self.trails)

    def krn(self) -> Dict[Any, bool]:
        return dict(self.kernels)


def _assign(families: Dict, policies: Dict, trails: Dict, chunk: int,
            caches, kernels: Optional[Dict] = None,
            spills=frozenset()) -> Assignment:
    return Assignment(
        families=tuple(sorted(families.items(),
                              key=lambda kv: getattr(kv[0], "id", -1))),
        policies=tuple(sorted(policies.items(),
                              key=lambda kv: getattr(kv[0], "id", -1))),
        trails=tuple(sorted(trails.items(),
                            key=lambda kv: getattr(kv[0], "id", -1))),
        chunk=int(chunk),
        caches=frozenset(caches),
        kernels=tuple(sorted((kernels or {}).items(),
                             key=lambda kv: getattr(kv[0], "id", -1))),
        spills=frozenset(spills),
    )


# ------------------------------------------------------------- the model


class _UnifiedModel:
    """The priced joint view of one graph: the placement menus and
    collective formulas of `analysis.planner`, the dtype menus and byte
    model of `analysis.precision`, the roofline's per-stage FLOPs, the
    autocache candidate set — and ONE scorer that prices any complete
    assignment in seconds. The sequential composition and the joint
    optimum are scored by literally the same function."""

    def __init__(self, graph: Graph, specs: Dict[GraphId, Any], mesh,
                 hbm_budget_bytes: Optional[int], chunk_default: int,
                 machine: Machine,
                 include_boundary_policies: bool = True,
                 precision_floor_bytes: int = 0,
                 allow_spill: bool = False):
        from ..workflow.autocache import AutoCacheRule, get_runs

        self.graph = graph
        self.specs = specs
        self.mesh = mesh
        self.budget = hbm_budget_bytes
        self.chunk_default = int(chunk_default)
        self.machine = machine
        self.precision_floor_bytes = int(precision_floor_bytes)
        #: spill axis gate (KEYSTONE_OOC_SPILL): when False no spill
        #: toggle is ever scored, Assignment.spills stays empty, and the
        #: scorer's spill branches are dead — bit-for-bit the PR-19 plan
        self.allow_spill = bool(allow_spill)
        self._host_bw: Optional[float] = None
        #: calls of `score`, added to ``planner.candidates_scored`` once
        #: a solve (`plan_unified`): no span or counter inside the loop
        self.candidates_scored = 0
        self._get_runs = get_runs
        order, _ = toposort(graph)
        self.order = [v for v in order if not isinstance(v, SinkId)]

        # --- compute axis: the roofline's chunk-independent FLOPs and
        # reference bytes per stage (the time model's numerators)
        self.roof, _ = roofline_pass(graph, specs, machine=machine,
                                     chunk_rows=chunk_default)
        self.unpriced_stages = self.roof.unknown_stages

        # --- placement axis (multi-device meshes only)
        self.pmodel: Optional[_CostModel] = None
        self.splan: Optional[ShardingPlan] = None
        if int(mesh.devices.size) > 1:
            self.splan = plan_sharding(
                graph, specs, mesh=mesh,
                hbm_budget_bytes=hbm_budget_bytes)
            if self.splan is not None:
                self.pmodel = _CostModel(
                    graph, specs, mesh, hbm_budget_bytes,
                    replicated_threshold_bytes=DEFAULT_REPLICATED_THRESHOLD)
                # the choice set is exactly the sequential planner's —
                # vertices it dropped as unclassifiable stay dropped
                for vid in list(self.pmodel.menus):
                    if vid not in self.splan.families:
                        del self.pmodel.menus[vid]

        # --- dtype axis: graph-level boundary policies (CLI surfaces,
        # unenforced — mirroring --explain-precision) and per-program
        # trails (the enforced PR-10 mechanism)
        self.prmodel: Optional[_PrecisionModel] = None
        self.pplan = None
        if include_boundary_policies:
            self.pplan = plan_precision(graph, specs)
            if self.pplan is not None:
                self.prmodel = _PrecisionModel(
                    graph, specs, tolerances=self.pplan.tolerances)
        self.program_trails: Dict[Any, Tuple] = {}
        from ..nodes.util.fusion import FusedBatchTransformer
        from ..workflow.fusion_rule import FusedChainOperator

        for vid in self.order:
            if not isinstance(vid, NodeId):
                continue
            op = graph.get_operator(vid)
            if isinstance(op, (FusedChainOperator, FusedBatchTransformer)) \
                    and getattr(op, "planned_precision", None) is None:
                try:
                    decided = plan_stage_precision(graph, vid, op, specs)
                except Exception:
                    decided = None
                if decided is not None:
                    self.program_trails[vid] = decided

        # --- kernel axis: KP801 fused-trail candidates — the
        # chain-megakernel-vs-XLA choice per fused program. Every
        # candidate joins the menu (one per vertex, highest boundary
        # savings wins); non-lowerable statics or a VMEM-infeasible
        # block geometry price INF in the scorer, so the toggle is
        # scored-and-demoted with a ledger record instead of crashing
        # or silently vanishing.
        self.kernel_candidates: Dict[Any, Dict[str, Any]] = {}
        for cand in self.roof.candidates:
            if cand.get("kind") != "fused_trail" \
                    or not cand.get("stage_slice"):
                continue
            kvid = cand["vertices"][0]
            prev = self.kernel_candidates.get(kvid)
            if prev is None or cand["seconds_saved"] > prev["seconds_saved"]:
                self.kernel_candidates[kvid] = cand
        for kvid, cand in self.kernel_candidates.items():
            cand["vmem_feasible"] = self._kernel_feasible(kvid, cand)
            cand["statically_verified"] = self._kernel_verified(
                kvid, cand)

        # --- cache axis: the autocache candidate set, restricted to
        # boundaries whose residency the model can price
        self.cache_candidates: List[Any] = []
        self._cache_bytes: Dict[Any, int] = {}
        try:
            candidates = AutoCacheRule._candidates(graph)
        except Exception:
            candidates = []
        nominal = 1024
        counts = [s.count for s in specs.values()
                  if isinstance(s, DataSpec) and s.kind == "dataset"
                  and s.count]
        if counts:
            nominal = max(counts)
        self.nominal_count = nominal
        for vid in candidates:
            spec = specs.get(vid)
            nb = policy_nbytes(spec, POLICY_F32, nominal) \
                if isinstance(spec, DataSpec) else None
            if nb is not None and vid in self.roof.stages:
                self.cache_candidates.append(vid)
                self._cache_bytes[vid] = nb
        self._nbytes_cache: Dict[Tuple[Any, str], Optional[int]] = {}

    # ------------------------------------------------------------ pieces

    def host_bandwidth(self) -> float:
        """Calibrated host↔device bytes/second — the spill tier's
        reload price denominator. Resolved lazily (only when a spilled
        assignment is actually scored) so the KEYSTONE_OOC_SPILL=0
        path never touches the calibration machinery."""
        if self._host_bw is None:
            bw = 0.0
            try:
                from ..nodes.learning.calibrate import host_bandwidth
                bw = float(host_bandwidth())
            except Exception:
                bw = 0.0
            self._host_bw = bw if bw > 0 else 1.0e10
        return self._host_bw

    def vbytes(self, vid, policy: str) -> Optional[int]:
        key = (vid, policy)
        if key not in self._nbytes_cache:
            self._nbytes_cache[key] = policy_nbytes(
                self.specs.get(vid), policy, self.nominal_count)
        return self._nbytes_cache[key]

    def _count(self, vid) -> int:
        st = self.roof.stages.get(vid)
        if st is not None and st.count:
            return int(st.count)
        spec = self.specs.get(vid)
        if isinstance(spec, DataSpec) and spec.count:
            return int(spec.count)
        return self.nominal_count

    def _data_dep(self, vid):
        if not isinstance(vid, NodeId):
            return None
        for d in self.graph.get_dependencies(vid):
            if isinstance(self.specs.get(d), DataSpec):
                return d
        return None

    def _kernel_slice(self, vid, cand):
        """(slice stage objects, element aval entering the slice) for a
        fused-trail kernel candidate — the one walk both the VMEM
        feasibility probe and the KP10xx static verifier consume."""
        import jax

        from ..nodes.util.fusion import fused_stages
        from ..workflow.fusion_rule import FusedChainOperator

        op = self.graph.get_operator(vid)
        stage_list = (op.stage_specs if isinstance(op, FusedChainOperator)
                      else op.stages)
        stages = fused_stages(stage_list)
        i, j = cand["stage_slice"]
        dep = self._data_dep(vid)
        spec = self.specs.get(dep)
        elem = spec.element
        # walk the element to the slice's input shape
        for s in stages[:i]:
            elem = jax.eval_shape(
                lambda x, s=s: s.single_transform([x]), elem)
        return stages[i:j], elem

    def _kernel_feasible(self, vid, cand) -> Tuple[bool, str]:
        """Probe the candidate slice's block geometry against the VMEM
        budget at the ACTUAL propagated element shapes — the
        memory-safety side of the kernel axis (arXiv 2206.14148
        discipline): an infeasible geometry prices INF downstream, it
        never reaches a compiler."""
        try:
            from ..ops.chain_kernels import chain_feasible

            if not (cand.get("lowerable") or {}).get("lowerable"):
                return False, (cand.get("lowerable") or {}).get(
                    "reason", "not lowerable")
            stages, elem = self._kernel_slice(vid, cand)
            return chain_feasible(stages, tuple(elem.shape), elem.dtype)
        except Exception as e:
            return False, f"feasibility probe failed: {e}"

    def _kernel_verified(self, vid, cand):
        """The KP10xx static proof for the candidate slice
        (analysis/kernels.statically_verified): False prices the kernel
        toggle INF — a lowering the verifier refuted must never reach
        the runtime canary, let alone a chip. None (verifier could not
        run) keeps the pre-verifier behavior: the canary decides."""
        try:
            from .kernels import statically_verified

            if not (cand.get("lowerable") or {}).get("lowerable"):
                return None
            stages, elem = self._kernel_slice(vid, cand)
            return statically_verified(stages, tuple(elem.shape),
                                       elem.dtype)
        except Exception:
            return None

    # ------------------------------------------------------------ scorer

    def score(self, a: Assignment) -> float:
        """Predicted seconds of one complete assignment — the ONE
        objective every candidate (sequential composition included) is
        measured by. INF means a hard KP600 infeasibility (the
        assignment is pruned, never enforced-then-linted)."""
        self.candidates_scored += 1
        families = a.fam()
        policies = a.pol()
        trails = a.trl()
        kernels = a.krn()
        chunk = max(1, a.chunk)
        runs = self._get_runs(self.graph, set(a.caches))
        total = 0.0
        bw = self.machine.peak_bw

        # cache residency is pinned for the whole run: it must fit the
        # per-device budget alongside the plan (hard constraint). A
        # HOST-placed cache (the spill tier) pins only its windowed
        # double-buffer residency — full bytes live in host RAM and
        # re-enter through the PR-1 overlap prefetcher — which is what
        # turns a busted budget into a satisfiable constraint.
        if self.budget:
            pinned = 0
            for vid in a.caches:
                shards = family_shards(families.get(vid), self.mesh)
                nb = (self.vbytes(vid, policies.get(vid, POLICY_F32))
                      or 0)
                if vid in a.spills:
                    count = max(1, self._count(vid))
                    nb = int(2 * (nb / count) * chunk)
                pinned += nb // max(1, shards)
            if pinned > self.budget:
                return _INF

        # spill reload seconds: each spilled cache pays one eviction
        # (device→host) plus, per consuming re-run, one full windowed
        # reload (host→device) over the calibrated host bandwidth and
        # the dispatch floor per window trip — the priced disadvantage
        # that keeps device placement winning whenever it fits.
        if a.spills:
            host_bw = self.host_bandwidth()
            for vid in a.spills:
                if vid not in a.caches:
                    continue
                nb = (self.vbytes(vid, policies.get(vid, POLICY_F32))
                      or 0)
                count = max(1, self._count(vid))
                trips = max(1, math.ceil(count / chunk))
                reruns = max(1, runs.get(vid, 1))
                total += nb / host_bw  # evict once
                total += reruns * (nb / host_bw
                                   + trips * DISPATCH_OVERHEAD_S)

        for vid, st in self.roof.stages.items():
            pol_v = policies.get(vid, POLICY_F32)
            dep = self._data_dep(vid)
            pol_u = policies.get(dep, POLICY_F32) if dep is not None \
                else POLICY_F32
            out_b = self.vbytes(vid, pol_v)
            in_b = self.vbytes(dep, pol_u) if dep is not None else None
            if out_b is not None and in_b is not None:
                nbytes = in_b + out_b
            elif out_b is not None:
                nbytes = 2 * out_b
            else:
                nbytes = st.hbm_bytes
            trail = self.program_trails.get(vid)
            if trail is not None and trails.get(vid):
                # the baked bf16 trail halves the program's INTERNAL
                # boundaries (each internal boundary is one write + one
                # read in the stage-at-a-time model) and costs its casts
                _, saved, _ = trail
                nbytes = max(0, nbytes - 2 * saved)
                casts = sum(1 for s in trail[0] if s is not None)
                total += casts * CAST_PENALTY_BYTES / bw
            kc = self.kernel_candidates.get(vid)
            if kc is not None and kernels.get(vid):
                # the chain megakernel: the slice's internal boundaries
                # never round-trip HBM (one streamed pass of in+out
                # bytes). Non-lowerable statics or a VMEM-infeasible
                # geometry make the WHOLE assignment infeasible — the
                # toggle demotes with a priced-INF record, it is never
                # enforced.
                if not kc["vmem_feasible"][0]:
                    return _INF
                if kc.get("statically_verified") is False:
                    # the KP10xx verifier refuted the lowering: the
                    # kernel toggle is pruned statically instead of
                    # relying on the runtime canary to demote it
                    return _INF
                nbytes = max(0, nbytes - 2 * kc["boundary_bytes"])
            count = self._count(vid)
            trips = max(1, math.ceil(count / chunk))
            if self.budget and count:
                # in-flight chunk residency (the scan/dispatch window's
                # live rows) must fit the per-device budget: the KP600
                # constraint that couples the chunk axis to placement
                shards = family_shards(families.get(vid), self.mesh)
                per_row = nbytes / count
                if per_row * chunk / max(1, shards) > self.budget:
                    return _INF
            sec = stage_cost(st.flops, nbytes, self.machine)
            sec += trips * DISPATCH_OVERHEAD_S
            total += sec * max(1, runs.get(vid, 1))

        # boundary-policy cast seconds (graph-level dtype flips)
        if self.prmodel is not None:
            for vid in self.order:
                if not isinstance(vid, NodeId):
                    continue
                sv = _STORAGE[policies.get(vid, POLICY_F32)]
                for d in self.graph.get_dependencies(vid):
                    if not isinstance(self.specs.get(d), DataSpec):
                        continue
                    if _STORAGE[policies.get(d, POLICY_F32)] != sv:
                        total += CAST_PENALTY_BYTES / bw

        # placement collective seconds — the planner's own formulas,
        # with the boundary bytes the chosen DTYPES actually move (the
        # interaction the sequential passes cannot see)
        pm = self.pmodel
        if pm is not None:
            for vid in pm.order:
                fam_v = families.get(vid)
                if fam_v is not None and vid in pm.menus:
                    if pm.node_cost(vid, fam_v) == _INF:
                        return _INF  # KP600: per-device residency
                    spec = self.specs.get(vid)
                    if fam_v == FAMILY_REPLICATED and spec.nbytes \
                            and spec.nbytes >= pm.threshold:
                        cost = meshlib.collective_cost(
                            "broadcast", spec.nbytes,
                            shards=int(self.mesh.devices.size),
                            mesh=self.mesh)
                        total += float(cost.seconds)
                deps = pm.data_deps(vid)
                demands = pm.demands(vid, {})
                all_deps = (list(self.graph.get_dependencies(vid))
                            if isinstance(vid, NodeId) else [])
                for d in deps:
                    fam_u = families.get(d)
                    u_spec = self.specs.get(d)
                    nbytes = self.vbytes(d, policies.get(d, POLICY_F32))
                    if nbytes is None:
                        nbytes = pm.vbytes(u_spec)
                    cost = None
                    if pm.is_host(vid):
                        cost = gather_cost(fam_u, nbytes, self.mesh)
                    else:
                        demand = None
                        if demands:
                            try:
                                i = all_deps.index(d)
                            except ValueError:
                                i = -1
                            if 0 <= i < len(demands):
                                demand = demands[i]
                        if demand is not None:
                            cost = demand_cost(demand, fam_u, nbytes,
                                               self.mesh)
                        elif fam_v is not None:
                            cost = transition_cost(fam_u, fam_v, nbytes,
                                                   self.mesh, u_spec=u_spec)
                    if cost is not None:
                        # every reshard is also one more launched
                        # program: the dispatch floor doubles as the
                        # byte planner's per-move penalty, in seconds
                        total += float(cost.seconds) + DISPATCH_OVERHEAD_S
        return total

    # ----------------------------------------------------- the sequential

    def sequential(self) -> Assignment:
        """The PR-13 composition as a point in the joint space: the
        sharding planner's enforced families, the per-program precision
        trails the sequential rule would bake (its enforcement floor
        included), `plan_precision`'s own clamped graph-level policies
        (the --explain-precision surface), the config chunk, and no
        cache points (autocache is a separate opt-in optimizer in the
        sequential world)."""
        families = dict(self.splan.families) if self.splan else {}
        policies = dict(self.pplan.policies) if self.pplan else {}
        trails = {
            vid: bool(saved >= self.precision_floor_bytes)
            for vid, (_, saved, _) in self.program_trails.items()
        }
        return _assign(families, policies, trails, self.chunk_default,
                       frozenset())

    # ------------------------------------------------------------ solver

    def chain_dp(self, seed: Assignment) -> Assignment:
        """The chain-DP + frontier merge generalized to the product
        menu: along each maximal fan-out-free chain of choosable
        vertices the state is a (family, policy) PAIR, transitions
        price the placement collective (at the producer's policy-scaled
        bytes) plus the cast flip, and fan-in freezes greedily at the
        best table entry — the planner's solver shape, one product
        state space."""
        families = seed.fam()
        policies = seed.pol()
        fam_menu = dict(self.pmodel.menus) if self.pmodel else {}
        pol_menu = dict(self.prmodel.menus) if self.prmodel else {}
        choosable = set(fam_menu) | set(pol_menu)
        if not choosable:
            return seed
        users = {vid: [u for u in self.graph.users_of(vid)
                       if not isinstance(u, SinkId)]
                 for vid in self.order}

        def states(vid) -> List[Tuple[Optional[str], str]]:
            fams = list(fam_menu.get(vid, (families.get(vid),)))
            pols = list(pol_menu.get(vid, (policies.get(vid, POLICY_F32),)))
            return [(f, p) for f in fams for p in pols]

        def edge_cost(u, us, v, vs) -> float:
            fam_u, pol_u = us
            fam_v, pol_v = vs
            sec = 0.0
            u_spec = self.specs.get(u)
            nbytes = self.vbytes(u, pol_u)
            cost = transition_cost(fam_u, fam_v, nbytes, self.mesh,
                                   u_spec=u_spec)
            if cost is not None:
                sec += float(cost.seconds) + DISPATCH_OVERHEAD_S
            if _STORAGE[pol_u] != _STORAGE[pol_v]:
                sec += CAST_PENALTY_BYTES / self.machine.peak_bw
            return sec

        def node_cost(v, vs) -> float:
            fam_v, pol_v = vs
            if self.pmodel and v in fam_menu and fam_v is not None:
                if self.pmodel.node_cost(v, fam_v) == _INF:
                    return _INF
            st = self.roof.stages.get(v)
            if st is None:
                return 0.0
            out_b = self.vbytes(v, pol_v)
            nbytes = 2 * out_b if out_b is not None else st.hbm_bytes
            return stage_cost(st.flops, nbytes, self.machine)

        visited: set = set()
        for vid in self.order:
            if vid not in choosable or vid in visited:
                continue
            head = vid
            while isinstance(head, NodeId):
                deps = [d for d in self.graph.get_dependencies(head)
                        if d in choosable]
                if len(deps) == 1 and len(users.get(deps[0], ())) == 1 \
                        and deps[0] not in visited:
                    head = deps[0]
                else:
                    break
            chain = [head]
            cur = head
            while True:
                kids = [u for u in users.get(cur, ())
                        if isinstance(u, NodeId) and u in choosable]
                if len(users.get(cur, ())) == 1 and len(kids) == 1 \
                        and kids[0] not in visited:
                    chain.append(kids[0])
                    cur = kids[0]
                else:
                    break
            visited.update(chain)
            # exact DP along the chain over product states
            table: Dict[Tuple, float] = {s: node_cost(chain[0], s)
                                         for s in states(chain[0])}
            back: List[Dict[Tuple, Tuple]] = []
            for prev, v in zip(chain, chain[1:]):
                nxt: Dict[Tuple, float] = {}
                bp: Dict[Tuple, Tuple] = {}
                for s in states(v):
                    best, best_c = None, _INF
                    for ps, pc in table.items():
                        c = pc + edge_cost(prev, ps, v, s)
                        if c < best_c:
                            best, best_c = ps, c
                    nxt[s] = best_c + node_cost(v, s)
                    bp[s] = best
                back.append(bp)
                table = nxt
            # greedy freeze at the tail, walk backpointers up the chain
            tail_state = min(table, key=lambda s: (table[s],
                                                   str(s)))
            if table[tail_state] == _INF:
                continue  # every product entry infeasible: keep seed
            assign = [tail_state]
            for bp in reversed(back):
                assign.append(bp[assign[-1]])
            assign.reverse()
            for v, (f, p) in zip(chain, assign):
                if v in fam_menu and f is not None:
                    families[v] = f
                if v in pol_menu:
                    policies[v] = p
        return replace(seed,
                       families=_assign(families, {}, {}, 0, ()).families,
                       policies=_assign({}, policies, {}, 0, ()).policies)

    def descend(self, seed: Assignment, obj: float,
                ladder: Tuple[int, ...],
                sweeps: int = 2) -> Tuple[Assignment, float,
                                          List[Dict[str, Any]]]:
        """Bounded local descent ACROSS decision kinds: per-vertex
        family/policy sweeps, per-program trail toggles, the chunk
        ladder, and greedy cache additions — each trial re-scored by
        the one shared scorer, strict improvements kept. Returns the
        best assignment, its objective, and the priced entries it
        actually scored (the ledger's product menu)."""
        scored: List[Dict[str, Any]] = []
        seen_entries: set = set()
        best, best_obj = seed, obj

        def try_(label: str, cand: Assignment) -> None:
            nonlocal best, best_obj
            c = self.score(cand)
            if label not in seen_entries:
                # one priced entry per menu label: later rounds re-score
                # the same toggle against a different intermediate
                # assignment, and duplicate labels with conflicting
                # prices would make the ledger's alternatives ambiguous
                seen_entries.add(label)
                scored.append({"entry": label, "predicted_seconds":
                               (None if c == _INF else float(c)),
                               "feasible": c != _INF})
            if c < best_obj:
                best, best_obj = cand, c

        # chunk ladder (the plan-level axis: cheap, solve it first)
        for chunk in ladder:
            if chunk != best.chunk:
                try_(f"chunk_{chunk}", replace(best, chunk=chunk))
        # program-trail toggles
        for vid in self.program_trails:
            trails = best.trl()
            trails[vid] = not trails.get(vid, False)
            try_(f"trail_{getattr(vid, 'id', vid)}_"
                 f"{'on' if trails[vid] else 'off'}",
                 replace(best, trails=_assign({}, {}, trails, 0,
                                              ()).trails))
        # chain-megakernel toggles (the kernel-vs-XLA axis): an
        # infeasible kernel scores INF here — the scored entry IS the
        # demotion record
        for vid in self.kernel_candidates:
            kernels = best.krn()
            kernels[vid] = not kernels.get(vid, False)
            try_(f"kernel_{getattr(vid, 'id', vid)}_"
                 f"{'on' if kernels[vid] else 'off'}",
                 replace(best, kernels=_assign({}, {}, {}, 0, (),
                                               kernels).kernels))
        # greedy cache additions (the autocache greedy shape, priced
        # statically): add the best strict improvement until none
        while True:
            gain_best, gain_cand = 0.0, None
            for vid in self.cache_candidates:
                if vid in best.caches:
                    continue
                cand = replace(best, caches=best.caches | {vid})
                c = self.score(cand)
                label = f"cache_{getattr(vid, 'id', vid)}"
                if label not in seen_entries:
                    seen_entries.add(label)
                    scored.append({"entry": label, "predicted_seconds":
                                   (None if c == _INF else float(c)),
                                   "feasible": c != _INF})
                if best_obj - c > gain_best:
                    gain_best, gain_cand = best_obj - c, cand
            if gain_cand is None:
                break
            best, best_obj = gain_cand, best_obj - gain_best
        # spill-placement toggles (the out-of-core axis): per cache
        # candidate, flip device↔host placement. Where a device cache
        # busts the KP600 budget (scored INF in the greedy loop above),
        # the host-placed variant prices window residency + reload
        # seconds instead — a tight budget becomes satisfiable, and the
        # INF/feasible pair IS the ledger's priced alternative set.
        if self.allow_spill:
            for vid in self.cache_candidates:
                caches = set(best.caches)
                spills = set(best.spills)
                if vid in spills:
                    spills.discard(vid)  # back to device placement
                else:
                    caches.add(vid)
                    spills.add(vid)
                flipped = replace(best, caches=frozenset(caches),
                                  spills=frozenset(spills))
                # the spill and window decisions are coupled: a spilled
                # cache pins O(window) residency, so the toggle is
                # priced at its best rung — scoring it only at the
                # incumbent chunk would report INF for spills a smaller
                # window makes feasible
                cands = [flipped] + [replace(flipped, chunk=c)
                                     for c in ladder
                                     if c != flipped.chunk]
                try_(f"spill_{getattr(vid, 'id', vid)}",
                     min(cands, key=self.score))
            if best.spills:
                # a spilled cache changes the chunk economics (reload
                # trips vs window residency): re-walk the ladder once
                for chunk in ladder:
                    if chunk != best.chunk:
                        try_(f"chunk_{chunk}",
                             replace(best, chunk=chunk))
        # family/policy coordinate sweeps
        fam_menu = dict(self.pmodel.menus) if self.pmodel else {}
        pol_menu = dict(self.prmodel.menus) if self.prmodel else {}
        for _sweep in range(sweeps):
            changed = False
            for vid in self.order:
                for fam in fam_menu.get(vid, ()):
                    if fam == best.fam().get(vid):
                        continue
                    fams = best.fam()
                    fams[vid] = fam
                    cand = replace(best, families=_assign(
                        fams, {}, {}, 0, ()).families)
                    c = self.score(cand)
                    if c < best_obj:
                        best, best_obj, changed = cand, c, True
                for pol in pol_menu.get(vid, ()):
                    if pol == best.pol().get(vid, POLICY_F32):
                        continue
                    pols = best.pol()
                    pols[vid] = pol
                    cand = replace(best, policies=_assign(
                        {}, pols, {}, 0, ()).policies)
                    c = self.score(cand)
                    if c < best_obj:
                        best, best_obj, changed = cand, c, True
            if not changed:
                break
        return best, best_obj, scored


# --------------------------------------------------------------- the plan


@dataclass
class UnifiedPlan:
    """The joint decision: the chosen assignment, the sequential PR-13
    composition it was scored against (same scorer), and the priced
    menu. When ``improved`` is False the assignment IS the sequential
    composition and nothing deviates."""

    mesh: Any
    chosen: Assignment
    sequential_assignment: Assignment
    joint_seconds: float
    sequential_seconds: float
    #: the product-menu entries the solver actually scored — the
    #: decision ledger's alternatives
    scored_candidates: List[Dict[str, Any]] = field(default_factory=list)
    #: a `ShardingPlan` whose families are the JOINT choice (spec_for /
    #: changed_vertices drive enforcement exactly like PR 9)
    sharding: Optional[ShardingPlan] = None
    #: vid -> (storage, saved_bytes, menu) for every program trail the
    #: joint plan turns ON (the PR-10 enforcement payload)
    program_precision: Dict[Any, Tuple] = field(default_factory=dict)
    #: a `PrecisionPlan` whose policies are the JOINT graph-level
    #: choice — the KP7xx lint surface (`precision_pass(plan=...)`),
    #: None when the dtype axis had nothing to decide
    boundary_precision: Optional[Any] = None
    #: vid -> the KP801 candidate dict (stage_slice, lowerable verdict,
    #: kernel_seconds vs chain_seconds, boundary_bytes) for every
    #: fused program the joint plan lowers to a chain megakernel — the
    #: `UnifiedPlannerRule` kernel-enforcement payload
    kernel_choices: Dict[Any, Dict[str, Any]] = field(default_factory=dict)
    #: vid -> {bytes, window_trips, reload_seconds} for every spilled
    #: cache point — the ledger's predicted side of the spill decision
    #: (`reconcile_decisions` joins it against the observed
    #: spill.reload_stall_s histogram and spill_window spans)
    spill_predictions: Dict[Any, Dict[str, Any]] = field(
        default_factory=dict)
    unpriced_stages: int = 0

    @property
    def improved(self) -> bool:
        return self.joint_seconds < self.sequential_seconds

    @property
    def savings_seconds(self) -> float:
        return max(0.0, self.sequential_seconds - self.joint_seconds)

    @property
    def chunk_size(self) -> int:
        return self.chosen.chunk

    @property
    def default_chunk_size(self) -> int:
        return self.sequential_assignment.chunk

    @property
    def cache_vertices(self) -> List:
        return sorted(self.chosen.caches,
                      key=lambda v: getattr(v, "id", -1))

    @property
    def spill_vertices(self) -> List:
        """Cache points the joint plan places on the HOST (⊆
        cache_vertices) — the `UnifiedPlannerRule` spill-enforcement
        payload (`CacheMarker(placement="host")`)."""
        return sorted(self.chosen.spills,
                      key=lambda v: getattr(v, "id", -1))

    def changed_kinds(self) -> List[str]:
        """Which decision kinds deviate from the sequential
        composition — what `UnifiedPlannerRule` must enforce (and
        record) itself."""
        out = []
        if self.chosen.families != self.sequential_assignment.families:
            out.append("placement")
        if (self.chosen.trails != self.sequential_assignment.trails
                or self.chosen.policies
                != self.sequential_assignment.policies):
            out.append("precision")
        if self.chosen.chunk != self.sequential_assignment.chunk:
            out.append("chunk")
        if self.chosen.caches != self.sequential_assignment.caches:
            out.append("cache")
        if self.chosen.kernels != self.sequential_assignment.kernels:
            out.append("kernel")
        if self.chosen.spills != self.sequential_assignment.spills:
            out.append("spill")
        return out

    def rows(self, graph: Graph) -> List[Dict[str, Any]]:
        """Per-stage chosen-vs-sequential table (topo order),
        JSON-ready — the ``--explain-unified`` payload."""
        order, _ = toposort(graph)
        fams, seq_fams = self.chosen.fam(), self.sequential_assignment.fam()
        pols, seq_pols = self.chosen.pol(), self.sequential_assignment.pol()
        trails = self.chosen.trl()
        seq_trails = self.sequential_assignment.trl()
        caches = set(self.chosen.caches)
        spills = set(self.chosen.spills)
        kernels = self.chosen.krn()
        rows = []
        for vid in order:
            if not isinstance(vid, NodeId):
                continue
            if vid not in fams and vid not in pols \
                    and vid not in trails and vid not in caches \
                    and vid not in kernels:
                continue
            rows.append({
                "vertex": vid.id,
                "label": _label(graph, vid),
                "family": fams.get(vid),
                "sequential_family": seq_fams.get(vid),
                "policy": pols.get(vid, POLICY_F32),
                "sequential_policy": seq_pols.get(vid, POLICY_F32),
                "trail": trails.get(vid),
                "sequential_trail": seq_trails.get(vid),
                "cached": vid in caches,
                "spilled": vid in spills,
                "kernel": bool(kernels.get(vid)),
                "changed": (fams.get(vid) != seq_fams.get(vid)
                            or pols.get(vid) != seq_pols.get(vid)
                            or trails.get(vid) != seq_trails.get(vid)
                            or vid in caches
                            or bool(kernels.get(vid))),
            })
        return rows


def format_plan(plan: UnifiedPlan, graph: Graph) -> str:
    lines = [
        f"joint ≈{plan.joint_seconds:.3e}s vs sequential "
        f"≈{plan.sequential_seconds:.3e}s "
        f"({'strict win' if plan.improved else 'no win: sequential plan'}"
        f", chunk {plan.default_chunk_size} → {plan.chunk_size}, "
        f"{len(plan.cache_vertices)} cache point(s), "
        f"{len(plan.spill_vertices)} spilled to host)"
    ]
    header = (f"{'stage':<36} {'family':<22} {'policy':<14} "
              f"{'cache':>5} {'kern':>5}")
    body = [header]
    for r in plan.rows(graph):
        mark = "*" if r["changed"] else " "
        fam = (f"{r['sequential_family'] or '—'}"
               + (f"→{r['family']}" if r["family"]
                  != r["sequential_family"] else ""))
        pol = (f"{r['sequential_policy']}"
               + (f"→{r['policy']}" if r["policy"]
                  != r["sequential_policy"] else ""))
        body.append(
            f"{mark}{(r['label'] + '@' + str(r['vertex']))[:35]:<35} "
            f"{fam[:22]:<22} {pol[:14]:<14} "
            f"{('host' if r.get('spilled') else 'yes') if r['cached'] else '':>5} "
            f"{'yes' if r.get('kernel') else '':>5}")
    if len(body) > 1:
        lines.extend(body)
    return "\n".join(lines)


# ------------------------------------------------------------ entry point


def plan_unified(
    graph: Graph,
    specs: Dict[GraphId, Any],
    *,
    mesh=None,
    hbm_budget_bytes: Optional[int] = None,
    chunk_default: Optional[int] = None,
    machine: Optional[Machine] = None,
    weights=None,
    include_boundary_policies: bool = True,
    precision_floor_bytes: int = 0,
    ladder: Tuple[int, ...] = CHUNK_LADDER,
    allow_spill: Optional[bool] = None,
) -> Optional[UnifiedPlan]:
    """Solve the joint decision IR for one graph.

    ``weights`` (a `calibrate.CostWeights`, e.g. from
    `reconcile.drift_cost_weights(trace)`) recalibrates the time
    model's peaks from a live trace; ``machine`` pins them directly;
    neither falls back to `calibrate.machine_rates()`. Returns None
    when there is nothing to decide (no priceable stage and no axis
    with more than one entry). ``improved`` is a STRICT win over the
    sequential composition scored by the same function — otherwise the
    plan is the sequential assignment and nothing deviates."""
    mesh = mesh or meshlib.current_mesh()
    if weights is not None and machine is None:
        machine = machine_from_weights(weights)
    machine = machine or default_machine()
    from ..workflow.env import execution_config

    cfg = execution_config()
    chunk_default = int(chunk_default or cfg.chunk_size)
    if allow_spill is None:
        # KEYSTONE_OOC_SPILL=0 is the bit-for-bit kill switch: no spill
        # toggle is scored and the chosen plan matches PR 19 exactly
        allow_spill = bool(getattr(cfg, "ooc_spill", False))
    from ..telemetry import counter, span

    with span("price", cat="phase", layer="optimize", part="price"):
        model = _UnifiedModel(
            graph, specs, mesh, hbm_budget_bytes, chunk_default, machine,
            include_boundary_policies=include_boundary_policies,
            precision_floor_bytes=precision_floor_bytes,
            allow_spill=allow_spill)
    if not model.roof.stages:
        return None
    has_axis = bool(model.cache_candidates or model.program_trails
                    or model.kernel_candidates
                    or (model.pmodel and model.pmodel.menus)
                    or (model.prmodel and model.prmodel.menus)
                    or any(model._count(v) > min(ladder)
                           for v in model.roof.stages))
    if not has_axis:
        return None

    # the chunk ladder never exceeds the largest known count's padded
    # shape (bigger chunks change nothing but the pad waste)
    max_count = max((model._count(v) for v in model.roof.stages),
                    default=chunk_default)
    ladder = tuple(sorted({c for c in ladder
                           if c <= max(max_count, chunk_default)}
                          | {chunk_default}))

    seq = model.sequential()
    seq_obj = model.score(seq)
    scored: List[Dict[str, Any]] = [
        {"entry": "sequential", "predicted_seconds": float(seq_obj),
         "feasible": seq_obj != _INF},
    ]

    # the product chain-DP seed, then descent across decision kinds
    dp_seed = model.chain_dp(seq)
    dp_obj = model.score(dp_seed)
    scored.append({"entry": "chain_dp_product",
                   "predicted_seconds":
                   (None if dp_obj == _INF else float(dp_obj)),
                   "feasible": dp_obj != _INF})
    best, best_obj = (dp_seed, dp_obj) if dp_obj < seq_obj \
        else (seq, seq_obj)
    best, best_obj, descent_scored = model.descend(best, best_obj, ladder)
    scored.extend(descent_scored)
    scored.append({"entry": "joint_optimum",
                   "predicted_seconds":
                   (None if best_obj == _INF else float(best_obj)),
                   "feasible": best_obj != _INF})

    counter("planner.candidates_scored").inc(model.candidates_scored)

    if not best_obj < seq_obj:
        best, best_obj = seq, seq_obj  # the plan IS the sequential one

    # the enforcement payloads: a ShardingPlan over the JOINT families
    # (PR-9 machinery) and the ON program trails (PR-10 machinery)
    sharding = None
    if model.splan is not None and model.pmodel is not None:
        fams = best.fam()
        choices = {vid: model.pmodel.menus[vid][fam]
                   for vid, fam in fams.items()
                   if vid in model.pmodel.menus
                   and fam in model.pmodel.menus[vid]}
        _, planned_bytes, planned_boundary = model.pmodel.score(fams)
        sharding = ShardingPlan(
            mesh=mesh,
            families=fams,
            default_families=model.splan.default_families,
            choices=choices,
            default_shardings=model.splan.default_shardings,
            planned_cost_bytes=planned_bytes,
            default_cost_bytes=model.splan.default_cost_bytes,
            planned_boundary=planned_boundary,
            default_boundary=model.splan.default_boundary,
            scored_candidates=model.splan.scored_candidates,
        )
    program_precision = {
        vid: model.program_trails[vid]
        for vid, on in best.trl().items()
        if on and vid in model.program_trails
    }
    kernel_choices = {
        vid: model.kernel_candidates[vid]
        for vid, on in best.krn().items()
        if on and vid in model.kernel_candidates
    }
    spill_predictions: Dict[Any, Dict[str, Any]] = {}
    if best.spills:
        host_bw = model.host_bandwidth()
        pols = best.pol()
        for vid in best.spills:
            nb = model.vbytes(vid, pols.get(vid, POLICY_F32)) or 0
            count = max(1, model._count(vid))
            trips = max(1, math.ceil(count / max(1, best.chunk)))
            spill_predictions[vid] = {
                "bytes": int(nb),
                "window_trips": int(trips),
                "reload_seconds": float(
                    2 * nb / host_bw + trips * DISPATCH_OVERHEAD_S),
            }
    boundary_precision = None
    if model.pplan is not None and model.prmodel is not None:
        from .precision import PrecisionPlan

        policies = dict(model.pplan.default_policies)
        policies.update(best.pol())
        cost, boundary = model.prmodel.score(policies)
        boundary_precision = PrecisionPlan(
            policies=policies,
            default_policies=model.pplan.default_policies,
            planned_cost_bytes=cost,
            default_cost_bytes=model.pplan.default_cost_bytes,
            planned_boundary=boundary,
            default_boundary=model.pplan.default_boundary,
            tolerances=model.pplan.tolerances,
        )
    return UnifiedPlan(
        mesh=mesh,
        chosen=best,
        sequential_assignment=seq,
        joint_seconds=float(best_obj),
        sequential_seconds=float(seq_obj),
        scored_candidates=scored,
        sharding=sharding,
        program_precision=program_precision,
        boundary_precision=boundary_precision,
        kernel_choices=kernel_choices,
        spill_predictions=spill_predictions,
        unpriced_stages=model.unpriced_stages,
    )
