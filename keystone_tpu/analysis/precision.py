"""Mixed-precision policy pass: per-stage dtype as an optimizer decision.

The featurize hot path is bandwidth-bound while the MXU already ingests
bf16 (the fused conv kernel's numerics story, PERF.md; the bf16x3
precision discipline of arXiv 2112.09017). KeystoneML's thesis is that
pipeline-level choices should be made by cost models over the lowered
DAG (arXiv 1610.09451) — PR 9 made *placement* such a decision; this
module makes *precision* one: per stage boundary a legal dtype menu,
priced by the bytes the boundary actually moves, solved with the same
chain-DP + frontier-merge shape as `analysis.planner`, and enforced by
baking casts and matmul-precision scopes into fused/megafused programs
(`workflow.optimizer.PrecisionPlannerRule` is the enforcement shell).

The model:

  - **menu** — per stage boundary, the legal storage policies:
    ``bf16`` (bf16 storage, DEFAULT compute — halves every float32 byte
    the boundary moves), ``f32_bf16`` (f32 storage, bf16 matmul compute
    — a compute-only concession, byte-neutral, never chosen by the byte
    objective but available to explicit policies), and ``f32`` (f32
    storage, HIGHEST-fidelity compute — the reference policy, always
    legal, and exactly what runs today).
  - **legality** — flowed from per-operator ``precision_tolerance``
    declarations: solvers, moments/stats estimators, and label/index
    stages pin ``exact`` (their boundaries stay f32); elementwise and
    featurize stages declare ``tolerant``. Undeclared stages get an
    `jax.eval_shape`-based sensitivity probe: the stage is traced on a
    bf16 element — a trace that dies, or a non-floating output, pins
    the stage. Passthrough stages (`precision_passthrough` — Cacher,
    Identity, VectorCombiner) are *transparent*: the consumers behind
    them decide, so a cached feature matrix feeding an exact solver is
    pinned even though the cache itself tolerates anything. A boundary
    feeding a sink is the pipeline's visible output and stays f32.
  - **cost** — a boundary priced at the bytes its storage dtype
    implies: `policy_nbytes` halves float32 leaves under bf16 (ints and
    bools never change — the dtype-aware KP2xx story). Every storage
    flip along an edge carries a fixed cast penalty so a downcast that
    is immediately undone (KP702 cast-thrash) never wins on byte ties.
  - **solver** — min-cost DP over fan-out-free chains of choosable
    boundaries (each maximal run of bf16 boundaries pays two casts and
    saves its halved bytes), greedy freeze at fan-out/fan-in, one
    bounded descent sweep; chosen and default assignments are scored by
    the SAME function, and the plan degrades to the all-f32 default
    whenever it cannot strictly beat it — the kill switch
    (``KEYSTONE_PRECISION_PLANNER=0``) and every no-win case reproduce
    the PR-9 plan bit-for-bit.

Everything here is pure spec arithmetic — no data moves, no device
allocates.  Numeric safety is gated by the existing
allclose-vs-serial-unfused machinery (tests/test_precision.py, the
bench accuracy band): `shrink_to_band` discards a policy stage-by-stage
when an evaluation busts the declared tolerance band, so a policy that
cannot hold the band is never shipped.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..workflow.graph import Graph, GraphId, NodeId, SinkId, SourceId
from .diagnostics import Diagnostic, Severity
from .memory import _fmt_bytes, memory_pass
from .propagate import _label, toposort
from .specs import (
    UNKNOWN,
    DataSpec,
    TransformerSpec,
    is_known,
    trace_element,
)

# ------------------------------------------------------------------ policies

#: f32 storage + HIGHEST-fidelity compute — the reference policy; what
#: every boundary runs today, and what the kill switch reproduces.
POLICY_F32 = "f32"
#: f32 storage + bf16 matmul compute — byte-neutral, compute-only.
POLICY_F32_BF16 = "f32_bf16"
#: bf16 storage + DEFAULT compute — halves every f32 byte the boundary
#: moves; the policy the byte objective actually fights for.
POLICY_BF16 = "bf16"
POLICIES: Tuple[str, ...] = (POLICY_F32, POLICY_F32_BF16, POLICY_BF16)

#: `precision_tolerance` declaration values.
TOLERANT = "tolerant"   # bf16 storage AND bf16 compute acceptable
COMPUTE = "compute"     # f32 storage required; bf16 matmul acceptable
EXACT = "exact"         # f32 storage + HIGHEST compute, non-negotiable

#: the default per-pipeline tolerance band for policy-on outputs vs the
#: serial unfused f32 reference: ~2 bf16 roundings of relative error
#: plus an absolute floor for near-zero rectified values. Tests and the
#: bench accuracy gate both read these; `shrink_to_band` discards
#: policy stages until an evaluation fits inside them.
DEFAULT_BAND_RTOL = 2e-2
DEFAULT_BAND_ATOL = 5e-2

#: fixed per-cast penalty (bytes): every storage flip on an edge is a
#: convert_element_type the program would not otherwise contain, so a
#: single halved boundary sandwiched between f32 neighbours must save
#: more than two casts' worth of churn to win (the KP702 discipline,
#: priced into the objective instead of only linted after the fact).
CAST_PENALTY_BYTES = 2 << 10

_STORAGE = {POLICY_F32: "float32", POLICY_F32_BF16: "float32",
            POLICY_BF16: "bfloat16"}


def storage_dtype(policy: str) -> Optional[str]:
    """Boundary storage dtype name a policy implies for float32 leaves;
    None means 'leave the propagated dtype alone'."""
    name = _STORAGE[policy]
    return None if name == "float32" else name


def compute_precision(policy: str) -> Optional[str]:
    """`jax.default_matmul_precision` scope a policy implies, or None
    for the ambient default."""
    return "bfloat16" if policy == POLICY_F32_BF16 else None


# ----------------------------------------------------------------- tolerance


def declared_tolerance(op) -> Optional[str]:
    tol = getattr(op, "precision_tolerance", None)
    if tol in (TOLERANT, COMPUTE, EXACT):
        return tol
    return None


def _float32_leaves(element) -> List:
    if not is_known(element):
        return []
    return [l for l in jax.tree_util.tree_leaves(element)
            if getattr(l, "dtype", None) is not None
            and np.dtype(l.dtype) == np.float32]


def _bf16_element(element):
    """The element with every float32 leaf re-typed bf16 — the probe
    input for the sensitivity check and the storage spec under
    POLICY_BF16."""
    return cast_element(element, "bfloat16")


def cast_element(element, dtype_name: str):
    """Re-type every float32 leaf of an element pytree to ``dtype_name``
    (non-float leaves — labels, indices, masks — are never touched)."""
    if not is_known(element):
        return element

    def one(l):
        if getattr(l, "dtype", None) is not None \
                and np.dtype(l.dtype) == np.float32:
            return jax.ShapeDtypeStruct(tuple(l.shape), np.dtype(dtype_name))
        return l

    return jax.tree_util.tree_map(one, element)


def probe_tolerance(op, element) -> Tuple[str, str]:
    """``(tolerance, source)`` for one operator: the declared contract
    when present, else the eval_shape sensitivity probe — trace the
    stage's per-item transform on a bf16 element; a trace that dies or
    a non-floating output pins the stage. Conservative: anything the
    probe cannot prove tolerant is EXACT."""
    tol = declared_tolerance(op)
    if tol is not None:
        return tol, "declared"
    fn = getattr(op, "single_transform", None)
    if fn is None or not is_known(element) or not _float32_leaves(element):
        return EXACT, "pinned"
    try:
        out = trace_element(lambda x: fn([x]), (_bf16_element(element),))
    except Exception:
        return EXACT, "probe-pinned"
    if not is_known(out):
        return EXACT, "probe-pinned"
    leaves = jax.tree_util.tree_leaves(out)
    # jnp.issubdtype, not np: the probe input is bf16 so floating
    # outputs come back bf16, and numpy does not count ml_dtypes'
    # bfloat16 as np.floating — np.issubdtype here would pin every
    # undeclared stage and make the probe useless
    if leaves and all(
            jax.numpy.issubdtype(np.dtype(l.dtype), jax.numpy.floating)
            for l in leaves if getattr(l, "dtype", None) is not None):
        return TOLERANT, "probed"
    return EXACT, "probe-pinned"


# -------------------------------------------------------------- byte pricing


def policy_nbytes(spec: Any, policy: str,
                  nominal_count: int = 1024) -> Optional[int]:
    """Bytes one boundary materializes under ``policy`` — the
    dtype-aware KP2xx arithmetic: bf16 storage halves float32 leaves,
    every other dtype (uint8 loaders, int32 labels) keeps its real
    itemsize. Falls back to a nominal count when the spec carries
    none (apply-path boundaries)."""
    if not isinstance(spec, DataSpec) or not is_known(spec.element):
        return None
    sd = storage_dtype(policy)
    element = spec.element if sd is None else cast_element(spec.element, sd)
    total = 0
    for leaf in jax.tree_util.tree_leaves(element):
        if not (hasattr(leaf, "shape") and hasattr(leaf, "dtype")):
            return None
        total += int(np.prod(leaf.shape, dtype=np.int64)) \
            * np.dtype(leaf.dtype).itemsize
    if spec.kind == "datum":
        return total
    count = spec.count if spec.count else nominal_count
    return total * int(count)


# ------------------------------------------------------------------ the plan


@dataclass
class PrecisionPlan:
    """The decision: per-stage boundary policies, the all-f32 default
    they were scored against, and both priced byte totals. When
    ``improved`` is False the policies ARE the default and nothing is
    enforced."""

    policies: Dict[GraphId, str]
    default_policies: Dict[GraphId, str]
    planned_cost_bytes: float
    default_cost_bytes: float
    planned_boundary: Dict[NodeId, int] = field(default_factory=dict)
    default_boundary: Dict[NodeId, int] = field(default_factory=dict)
    #: vid -> (tolerance, source) for every inspected stage
    tolerances: Dict[GraphId, Tuple[str, str]] = field(default_factory=dict)

    @property
    def improved(self) -> bool:
        return self.planned_cost_bytes < self.default_cost_bytes

    @property
    def savings_bytes(self) -> int:
        return max(0, int(self.default_cost_bytes - self.planned_cost_bytes))

    def changed_vertices(self) -> List[GraphId]:
        return [vid for vid, pol in sorted(
                    self.policies.items(),
                    key=lambda kv: getattr(kv[0], "id", -1))
                if self.default_policies.get(vid) != pol]

    def storage_for(self, vid) -> Optional[str]:
        """Chosen storage dtype name for a vertex's boundary, or None
        when it keeps its propagated dtype."""
        pol = self.policies.get(vid)
        return storage_dtype(pol) if pol else None

    def retyped_specs(self, specs: Dict[GraphId, Any]) -> Dict[GraphId, Any]:
        """The propagated specs with chosen storage dtypes baked into
        the elements — what the KP2xx/KP600 models price under this
        policy (bf16 halves residency exactly where chosen)."""
        out = dict(specs)
        for vid, pol in self.policies.items():
            sd = storage_dtype(pol)
            spec = specs.get(vid)
            if sd is None or not isinstance(spec, DataSpec):
                continue
            out[vid] = spec.with_element(cast_element(spec.element, sd))
        return out

    def rows(self, graph: Graph, specs: Dict[GraphId, Any]
             ) -> List[Dict[str, Any]]:
        """Per-stage chosen-dtype table (topo order), JSON-ready — the
        ``--explain-precision`` payload."""
        order, _ = toposort(graph)
        rows = []
        for vid in order:
            if not isinstance(vid, NodeId):
                continue
            spec = specs.get(vid)
            if not isinstance(spec, DataSpec):
                continue
            pol = self.policies.get(vid, POLICY_F32)
            tol, source = self.tolerances.get(vid, (EXACT, "pinned"))
            default_b = self.default_boundary.get(vid)
            planned_b = self.planned_boundary.get(vid)
            rows.append({
                "vertex": vid.id,
                "label": _label(graph, vid),
                "policy": pol,
                "dtype": storage_dtype(pol) or _elem_dtype_name(spec),
                "tolerance": tol,
                "tolerance_source": source,
                "default_bytes": default_b,
                "planned_bytes": planned_b,
                "bytes_saved": (default_b - planned_b)
                if default_b is not None and planned_b is not None else 0,
                "changed": pol != self.default_policies.get(vid, POLICY_F32),
            })
        return rows


def _elem_dtype_name(spec: DataSpec) -> str:
    leaves = jax.tree_util.tree_leaves(spec.element) if is_known(
        spec.element) else []
    names = sorted({np.dtype(l.dtype).name for l in leaves
                    if getattr(l, "dtype", None) is not None})
    if not names:
        return "?"
    return names[0] if len(names) == 1 else "+".join(names)


def format_plan(rows: List[Dict[str, Any]]) -> str:
    lines = [f"{'stage':<40} {'dtype':<10} {'tolerance':<18} {'Δbytes':>12}"]
    for r in rows:
        mark = "*" if r["changed"] else " "
        name = f"{r['label']}@{r['vertex']}"
        delta = r["bytes_saved"]
        col = f"-{delta:,d}" if delta else "—"
        lines.append(
            f"{name[:40]:<40} {mark}{r['dtype'][:9]:<9} "
            f"{(r['tolerance'] + '/' + r['tolerance_source'])[:18]:<18} "
            f"{col:>12}")
    return "\n".join(lines)


# ------------------------------------------------------------------- solver


class _PrecisionModel:
    """The priced view of one graph: per-vertex menus (legality flowed
    from tolerances through passthrough stages), dtype-aware boundary
    bytes, and a shared scorer — the DP's choice and the default's
    score come from literally the same arithmetic (the planner's
    `_CostModel` discipline)."""

    def __init__(self, graph: Graph, specs: Dict[GraphId, Any],
                 tolerances: Optional[Dict[GraphId, Tuple[str, str]]] = None):
        self.graph = graph
        self.specs = specs
        order, _ = toposort(graph)
        self.order = [v for v in order if not isinstance(v, SinkId)]
        known_counts = [
            s.count for s in specs.values()
            if isinstance(s, DataSpec) and s.kind == "dataset" and s.count
        ]
        self.nominal_count = max(known_counts, default=1024)
        # `tolerances` lets a caller holding an already-resolved map (a
        # PrecisionPlan's) skip the eval_shape sensitivity probe for
        # undeclared stages; only vertices it misses are probed fresh
        self.tolerances: Dict[GraphId, Tuple[str, str]] = {}
        for vid in self.order:
            if isinstance(vid, NodeId):
                if tolerances is not None and vid in tolerances:
                    self.tolerances[vid] = tolerances[vid]
                else:
                    self.tolerances[vid] = self._tolerance(vid)
        #: vid -> set of legal policies (only vertices with a real menu)
        self.menus: Dict[GraphId, Tuple[str, ...]] = {}
        for vid in self.order:
            menu = self._menu(vid)
            if len(menu) > 1:
                self.menus[vid] = menu

    # ---------------------------------------------------------- legality

    def _tolerance(self, vid: NodeId) -> Tuple[str, str]:
        op = self.graph.get_operator(vid)
        deps = self.graph.get_dependencies(vid)
        in_spec = next(
            (self.specs.get(d) for d in deps
             if isinstance(self.specs.get(d), DataSpec)), None)
        element = in_spec.element if isinstance(in_spec, DataSpec) \
            else UNKNOWN
        return probe_tolerance(op, element)

    def _effective_consumers(self, vid, _seen=None) -> List[GraphId]:
        """Users of ``vid`` with passthrough stages (Cacher, Identity,
        combiners) looked *through*: the stage that actually computes on
        the bytes decides whether reduced precision is tolerable."""
        _seen = _seen if _seen is not None else set()
        out: List[GraphId] = []
        for u in self.graph.users_of(vid):
            if u in _seen:
                continue
            _seen.add(u)
            if isinstance(u, NodeId) and getattr(
                    self.graph.get_operator(u),
                    "precision_passthrough", False):
                out.extend(self._effective_consumers(u, _seen))
            else:
                out.append(u)
        return out

    def _menu(self, vid) -> Tuple[str, ...]:
        if not isinstance(vid, NodeId):
            return (POLICY_F32,)
        spec = self.specs.get(vid)
        if not isinstance(spec, DataSpec) or spec.kind != "dataset" \
                or not spec.on_device or not is_known(spec.element) \
                or not _float32_leaves(spec.element):
            return (POLICY_F32,)
        tol, _ = self.tolerances.get(vid, (EXACT, "pinned"))
        if tol != TOLERANT:
            return (POLICY_F32,)
        for u in self._effective_consumers(vid):
            if isinstance(u, SinkId):
                return (POLICY_F32,)  # the pipeline's visible output
            if not isinstance(u, NodeId):
                return (POLICY_F32,)
            u_tol, _ = self.tolerances.get(u, (EXACT, "pinned"))
            if u_tol != TOLERANT:
                return (POLICY_F32,)
        return (POLICY_F32, POLICY_BF16)

    # ------------------------------------------------------------ pricing

    def vbytes(self, vid, policy: str) -> Optional[int]:
        return policy_nbytes(self.specs.get(vid), policy,
                             self.nominal_count)

    def score(self, policies: Dict[GraphId, str]) -> Tuple[
            float, Dict[NodeId, int]]:
        """``(objective, boundary)``: boundary bytes per vertex under
        the assignment plus a fixed cast penalty per storage flip edge.
        The SAME function scores the chosen plan and the all-f32
        default, so "planner ≤ default" is a property of the
        arithmetic, not of two models agreeing."""
        objective = 0.0
        boundary: Dict[NodeId, int] = {}

        def stor(v) -> str:
            return _STORAGE[policies.get(v, POLICY_F32)]

        for vid in self.order:
            if not isinstance(vid, NodeId):
                continue
            nbytes = self.vbytes(vid, policies.get(vid, POLICY_F32))
            if nbytes is not None and isinstance(
                    self.specs.get(vid), DataSpec):
                spec = self.specs.get(vid)
                if spec.kind == "dataset" and spec.on_device \
                        and is_known(spec.element):
                    objective += nbytes
                    boundary[vid] = int(nbytes)
            for d in self.graph.get_dependencies(vid):
                if isinstance(self.specs.get(d), DataSpec) \
                        and stor(d) != stor(vid) \
                        and (d in self.menus or vid in self.menus):
                    objective += CAST_PENALTY_BYTES
        return objective, boundary


def _plan_path(saved: List[Optional[int]], legal: List[bool]
               ) -> List[bool]:
    """Chain DP over one fan-out-free path of boundaries: choose bf16
    per boundary so that every maximal bf16 run's saved bytes exceed
    its two cast penalties (one down-cast entering the run, one up-cast
    leaving it). Returns the keep/drop decision per boundary. This is
    the exact chain solution — runs are independent, and a run is
    worth keeping iff sum(saved) > 2·CAST_PENALTY_BYTES."""
    out = [False] * len(saved)
    i = 0
    while i < len(saved):
        if not legal[i] or not saved[i]:
            i += 1
            continue
        j = i
        total = 0
        while j < len(saved) and legal[j] and saved[j]:
            total += saved[j]
            j += 1
        if total > 2 * CAST_PENALTY_BYTES:
            for k in range(i, j):
                out[k] = True
        i = j
    return out


def plan_precision(graph: Graph, specs: Dict[GraphId, Any]
                   ) -> Optional[PrecisionPlan]:
    """Choose a per-stage-boundary precision policy minimizing priced
    boundary bytes. Returns None when there is nothing to decide (no
    tolerant float boundary anywhere); otherwise the chain DP runs and
    the better of {optimum, all-f32 default} is returned — ``improved``
    says whether the policy actually beat the reference."""
    model = _PrecisionModel(graph, specs)
    if not model.menus:
        return None
    default = {vid: POLICY_F32 for vid in model.menus}
    default_obj, default_boundary = model.score(default)

    # chain decomposition: maximal fan-out-free runs of choosable
    # vertices (single choosable dep, single user), solved exactly by
    # the run DP; everything else freezes greedily at its own best
    users = {vid: [u for u in graph.users_of(vid)
                   if not isinstance(u, SinkId)]
             for vid in model.order}
    chosen: Dict[GraphId, str] = dict(default)
    visited: set = set()
    for vid in model.order:
        if vid not in model.menus or vid in visited:
            continue
        # walk up to the chain head
        head = vid
        while True:
            deps = [d for d in graph.get_dependencies(head)
                    if d in model.menus]
            if len(deps) == 1 and len(users.get(deps[0], ())) == 1 \
                    and deps[0] not in visited:
                head = deps[0]
            else:
                break
        chain = [head]
        cur = head
        while True:
            kids = [u for u in users.get(cur, ())
                    if isinstance(u, NodeId) and u in model.menus]
            if len(users.get(cur, ())) == 1 and len(kids) == 1 \
                    and kids[0] not in visited:
                chain.append(kids[0])
                cur = kids[0]
            else:
                break
        visited.update(chain)
        saved = []
        legal = []
        for v in chain:
            f32_b = model.vbytes(v, POLICY_F32)
            bf16_b = model.vbytes(v, POLICY_BF16)
            saved.append((f32_b - bf16_b)
                         if f32_b is not None and bf16_b is not None
                         else None)
            legal.append(POLICY_BF16 in model.menus[v])
        for v, keep in zip(chain, _plan_path(saved, legal)):
            if keep:
                chosen[v] = POLICY_BF16

    # bounded local descent: the frontier-merge repair sweep — try the
    # other policy at each vertex, keep strict improvements (scored by
    # the same function both sides use)
    best_obj, _ = model.score(chosen)
    for _sweep in range(2):
        changed = False
        for vid in model.menus:
            for pol in model.menus[vid]:
                if pol == chosen[vid]:
                    continue
                trial = dict(chosen)
                trial[vid] = pol
                trial_obj, _ = model.score(trial)
                if trial_obj < best_obj:
                    chosen, best_obj = trial, trial_obj
                    changed = True
        if not changed:
            break

    planned_obj, planned_boundary = model.score(chosen)
    if not planned_obj < default_obj:
        chosen = dict(default)  # no strict win: the plan IS the default
        planned_obj, planned_boundary = default_obj, default_boundary
    return PrecisionPlan(
        policies=chosen,
        default_policies=default,
        planned_cost_bytes=planned_obj,
        default_cost_bytes=default_obj,
        planned_boundary=planned_boundary,
        default_boundary=default_boundary,
        tolerances=dict(model.tolerances),
    )


# ----------------------------------------------- fused-program stage trails


def stage_tolerance(stage, graph: Graph = None, vid: NodeId = None,
                    slot_index: int = None) -> str:
    """Tolerance of one fused-program stage: a `_FitSlot` reads the
    declared tolerance of the estimator operator that fills it (solvers
    pin EXACT; an undeclared estimator is conservatively EXACT — a fit
    is a whole-dataset reduction), a plain stage its own declaration
    (undeclared fused members are EXACT: inside a program there is no
    probe spec to check against)."""
    from ..workflow.fusion_rule import _FitSlot

    if isinstance(stage, _FitSlot):
        if graph is None or vid is None:
            return EXACT
        deps = graph.get_dependencies(vid)
        if stage.index >= len(deps) or not isinstance(
                deps[stage.index], NodeId):
            return EXACT
        est_op = graph.get_operator(deps[stage.index])
        return declared_tolerance(est_op) or EXACT
    return declared_tolerance(stage) or EXACT


def stage_policy_menu(saved: List[Optional[int]],
                      legal: List[bool]) -> List[Dict[str, Any]]:
    """The priced candidate menu `_plan_path` decides over: one entry
    per maximal legal bf16 run of boundaries, carrying the bytes the
    run would save, the cast penalty it must clear, and whether the DP
    kept it. This is the decision core's own scoring made visible —
    the decision ledger records it as the alternatives the chosen
    policy beat (a rejected run IS a priced alternative: enabling it
    would cost ``2·CAST_PENALTY_BYTES − saved`` net bytes)."""
    menu: List[Dict[str, Any]] = []
    i = 0
    while i < len(saved):
        if not legal[i] or not saved[i]:
            i += 1
            continue
        j = i
        total = 0
        while j < len(saved) and legal[j] and saved[j]:
            total += saved[j]
            j += 1
        menu.append({
            "entry": f"bf16_boundaries_{i}..{j - 1}",
            "bytes_saved": int(total),
            "cast_penalty_bytes": 2 * CAST_PENALTY_BYTES,
            "kept": total > 2 * CAST_PENALTY_BYTES,
        })
        i = j
    return menu


def plan_stage_precision(
    graph: Graph,
    vid: NodeId,
    op,
    specs: Dict[GraphId, Any],
) -> Optional[Tuple[Tuple[Optional[str], ...], int, List[Dict[str, Any]]]]:
    """Per-internal-boundary storage policy for one fused/megafused
    program operator: ``(storage_names, savings_bytes, menu)`` where
    ``storage_names[i]`` is the dtype name stage ``i``'s output is cast
    to inside the program (None = untouched), aligned with the
    operator's `fused_stages` list (the list `_build_program`
    executes), and ``menu`` is the `stage_policy_menu` of priced
    candidate runs the chain DP scored (kept and rejected — the
    decision ledger's alternatives). The program's final output
    boundary always stays untouched so downstream consumers see
    exactly the PR-9 dtypes. Returns None when the trail cannot be
    priced (unknown elements)."""
    from ..nodes.util.fusion import fused_stages
    from ..workflow.fusion_rule import _FitSlot

    stages = fused_stages(getattr(op, "stage_specs", None)
                          or getattr(op, "stages", []))
    deps = graph.get_dependencies(vid)
    if not deps:
        return None
    # a chain's data input is its LAST dependency (est_0..est_k, data);
    # a plain fused transformer's its only one — deps[-1] serves both
    data_spec = specs.get(deps[-1])
    if not isinstance(data_spec, DataSpec) or not is_known(
            data_spec.element) or data_spec.kind != "dataset":
        return None
    count = data_spec.count or 1024
    t_specs = [specs.get(d) for d in deps[:-1]]

    elem = data_spec.element
    # saved_bytes[i]: bytes halving stage i's OUTPUT boundary saves
    # across the whole dataset (2 bytes per float32 element), None when
    # the boundary has no float32 leaves to halve. restore_names[i]: the
    # boundary's OWN single-leaf floating dtype name — the cast that
    # re-asserts the unplanned trail's dtype at that point — None when
    # the boundary is multi-leaf or non-float (unrestorable).
    saved_bytes: List[Optional[int]] = []
    restore_names: List[Optional[str]] = []
    tols: List[str] = []
    for s in stages:
        tols.append(stage_tolerance(s, graph, vid))
        if not is_known(elem):
            return None
        try:
            if isinstance(s, _FitSlot):
                ts = t_specs[s.index] if s.index < len(t_specs) else None
                elem = (ts.apply_element(elem)
                        if isinstance(ts, TransformerSpec) else UNKNOWN)
            else:
                elem = trace_element(
                    lambda x, s=s: s.single_transform([x]), (elem,))
        except Exception:
            return None
        if not is_known(elem):
            return None
        f32_leaves = _float32_leaves(elem)
        saved = sum(
            int(np.prod(l.shape, dtype=np.int64)) * 2 for l in f32_leaves)
        saved_bytes.append(saved * count if f32_leaves else None)
        leaves = jax.tree_util.tree_leaves(elem)
        restore_names.append(
            np.dtype(leaves[0].dtype).name
            if len(leaves) == 1 and np.issubdtype(
                np.dtype(leaves[0].dtype), np.floating) else None)

    # boundary i sits between stage i and stage i+1: it may be bf16
    # only when both sides tolerate it; the final boundary (the program
    # output) is never reduced
    n = len(stages)
    legal = [
        tols[i] == TOLERANT and tols[i + 1] == TOLERANT
        and saved_bytes[i] is not None
        for i in range(n - 1)
    ] + [False]
    keep = _plan_path(saved_bytes, legal)
    menu = stage_policy_menu(saved_bytes, legal)

    # Every kept bf16 run must be RESTORED at its exit boundary: the
    # fused stage bodies deliberately follow their input dtype (the
    # KJ011 discipline), so without an explicit up-cast the bf16 would
    # silently flow past the first f32 boundary into exact stages —
    # producing the very KP701 failure the menu legality priced out.
    # The exit entry re-asserts the trail's own dtype (the program
    # output entry serves as the exit for a run reaching the last
    # internal boundary); a run whose exit boundary is unrestorable
    # (multi-leaf / non-float) is dropped entirely.
    storage: List[Optional[str]] = [None] * n
    savings = 0
    i = 0
    while i < n - 1:
        if not keep[i]:
            i += 1
            continue
        j = i
        while j < n - 1 and keep[j]:
            j += 1
        exit_restore = restore_names[j]
        if exit_restore is not None:
            for k in range(i, j):
                storage[k] = "bfloat16"
                savings += saved_bytes[k] or 0
            storage[j] = exit_restore
        else:
            # the DP kept the run but the exit boundary cannot re-assert
            # its dtype: the run is dropped — the menu must say so, or
            # the ledger would record an alternative as chosen
            for entry in menu:
                if entry["entry"] == f"bf16_boundaries_{i}..{j - 1}":
                    entry["kept"] = False
                    entry["dropped"] = "unrestorable_exit_boundary"
        i = j
    # defensive: always re-assert the program's visible output dtype
    # when it is known (a same-dtype astype is an identity, so an
    # untouched trail compiles to exactly the PR-9 program)
    if storage[n - 1] is None:
        storage[n - 1] = restore_names[n - 1]
    if not savings:
        return None
    return tuple(storage), int(savings), menu


# ------------------------------------------------------------------- lints


def precision_pass(
    graph: Graph,
    specs: Dict[GraphId, Any],
    plan: Optional[PrecisionPlan] = None,
) -> List[Diagnostic]:
    """Lint a chosen (or externally supplied) precision policy:

      - KP701 (ERROR): a reduced-precision policy on a boundary whose
        producer or an effective consumer declares/probes EXACT — the
        legality contract the planner enforces, checked independently
        so a hand-written policy fails loudly;
      - KP702 (WARNING): cast-thrash — a bf16 boundary whose every
        consumer's own boundary is f32 and whose saved bytes do not
        cover the two casts the flip pair costs: the downcast is undone
        immediately downstream for nothing;
      - KP703 (INFO): dtype-dependent memory re-pricing — the stages
        whose KP2xx residency the chosen policy halves, old → new, so
        the static memory numbers visibly track the decided dtypes.
    """
    if plan is None:
        return []
    diags: List[Diagnostic] = []
    model = _PrecisionModel(graph, specs, tolerances=plan.tolerances)
    for vid, pol in sorted(plan.policies.items(),
                           key=lambda kv: getattr(kv[0], "id", -1)):
        if pol in (None, POLICY_F32) or not isinstance(vid, NodeId):
            continue
        label = _label(graph, vid)
        tol, source = model.tolerances.get(vid, (EXACT, "pinned"))
        bad = tol != TOLERANT
        bad_consumer = None
        # a compute-only policy (f32_bf16) leaves the boundary storage
        # f32, so consumers still see full precision — only the stage
        # computing under it must tolerate; a storage policy degrades
        # what every effective consumer RECEIVES, so both sides must
        if storage_dtype(pol) is not None:
            for u in model._effective_consumers(vid):
                if isinstance(u, SinkId) or not isinstance(u, NodeId):
                    bad_consumer = u
                    break
                u_tol, _ = model.tolerances.get(u, (EXACT, "pinned"))
                if u_tol != TOLERANT:
                    bad_consumer = u
                    break
        if bad or bad_consumer is not None:
            who = ("this stage declares/probes "
                   f"{tol!r} ({source})" if bad else
                   f"consumer {_label(graph, bad_consumer)}@{bad_consumer} "
                   "does not tolerate reduced precision")
            diags.append(Diagnostic(
                "KP701", Severity.ERROR,
                f"precision policy {pol!r} on an intolerant boundary: "
                f"{who}; the policy would silently degrade an exact "
                "stage's inputs",
                vertex=vid, label=label))
            continue
        if storage_dtype(pol) is None:
            continue  # compute-only policy: no boundary bytes to thrash
        f32_b = model.vbytes(vid, POLICY_F32)
        bf16_b = model.vbytes(vid, POLICY_BF16)
        saved = (f32_b - bf16_b) if f32_b and bf16_b else 0
        consumers = [u for u in model._effective_consumers(vid)
                     if isinstance(u, NodeId)]
        undone = consumers and all(
            storage_dtype(plan.policies.get(u, POLICY_F32)) is None
            for u in consumers)
        if undone and saved <= 2 * CAST_PENALTY_BYTES:
            diags.append(Diagnostic(
                "KP702", Severity.WARNING,
                f"cast-thrash: this boundary stores bf16 but every "
                f"consumer's boundary is f32 and the halving saves only "
                f"{_fmt_bytes(int(saved))} — less than the two "
                "convert_element_type casts the flip pair costs; drop "
                "the policy here",
                vertex=vid, label=label))
    return diags


def reprice_memory(
    graph: Graph,
    specs: Dict[GraphId, Any],
    plan: PrecisionPlan,
    **memory_kwargs,
) -> Tuple[Any, Any, List[Diagnostic]]:
    """Re-run the KP2xx memory model under the chosen policy's storage
    dtypes: ``(default_estimate, planned_estimate, diags)`` where the
    KP703 INFO diagnostics name each stage whose residency the policy
    changed (bf16 halves exactly the chosen float boundaries)."""
    est0, _ = memory_pass(graph, specs, **memory_kwargs)
    est1, _ = memory_pass(graph, plan.retyped_specs(specs),
                          **memory_kwargs)
    diags: List[Diagnostic] = []
    for vid in sorted(est0.resident, key=lambda v: v.id):
        a, b = est0.resident.get(vid), est1.resident.get(vid)
        if a and b and a != b:
            diags.append(Diagnostic(
                "KP703", Severity.INFO,
                f"dtype-aware re-pricing: residency {_fmt_bytes(a)} → "
                f"{_fmt_bytes(b)} under the chosen precision policy",
                vertex=vid, label=_label(graph, vid)))
    return est0, est1, diags


# ------------------------------------------------------------------ banding


def shrink_to_band(
    plan: PrecisionPlan,
    evaluate: Callable[[PrecisionPlan], bool],
    rescore: Optional[Callable[[Dict[GraphId, str]],
                               Tuple[float, Dict[NodeId, int]]]] = None,
) -> PrecisionPlan:
    """Discard a policy stage-by-stage until ``evaluate`` (the
    allclose-vs-serial-unfused band check) passes: the largest-savings
    reduced boundary is reverted first, so the policy sheds the most
    numerically aggressive halvings before giving up entirely. The
    all-f32 default always evaluates in band by construction, so this
    terminates with a shippable plan.

    ``rescore`` (a ``_PrecisionModel.score`` bound method) keeps the
    shrunk plan's cost EXACT — a revert can split a bf16 run and change
    the number of cast-penalty edges, which the byte-only fallback
    cannot see. Without it the adjustment restores boundary bytes only
    (an upper bound on the true objective), and a fully-reverted plan
    is clamped to the default's own cost."""
    current = plan
    while not evaluate(current):
        changed = current.changed_vertices()
        if not changed:
            return current  # already the default; the band check is
            # measuring something other than this policy
        worst = max(
            changed,
            key=lambda v: current.default_boundary.get(v, 0)
            - current.planned_boundary.get(v, 0))
        policies = dict(current.policies)
        policies[worst] = current.default_policies.get(worst, POLICY_F32)
        if rescore is not None:
            cost, planned_boundary = rescore(policies)
        else:
            cost = current.planned_cost_bytes + (
                current.default_boundary.get(worst, 0)
                - current.planned_boundary.get(worst, 0))
            planned_boundary = dict(current.planned_boundary)
            planned_boundary[worst] = current.default_boundary.get(worst, 0)
            if all(policies.get(v) == current.default_policies.get(v)
                   for v in policies):
                cost = current.default_cost_bytes
        current = PrecisionPlan(
            policies=policies,
            default_policies=current.default_policies,
            planned_cost_bytes=cost,
            default_cost_bytes=current.default_cost_bytes,
            planned_boundary=planned_boundary,
            default_boundary=current.default_boundary,
            tolerances=current.tolerances,
        )
    return current
