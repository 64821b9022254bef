"""Static roofline analyzer — jaxpr-level FLOP/byte pricing, a
time-domain cost model, and Pallas-candidate lints (the KP8xx tier).

KeystoneML's solver cost model already prices ``cpuWeight·flops +
memWeight·bytes`` (nodes/learning/cost_model.py, after
LeastSquaresEstimator.scala), but until this tier the FLOP term existed
only as hand-written per-solver formulas: every static tier (KP2xx
memory, KP6xx collectives, KP7xx precision) priced bytes alone, so the
optimizer literally could not see compute. This module closes that gap
with the same static-resource discipline arXiv 2206.14148 applies to
memory: walk the jaxpr of every stage body — traced from the analyzer's
already-propagated element specs via `jax.make_jaxpr`, zero data
movement — count FLOPs and HBM bytes moved, derive arithmetic
intensity, and classify each stage compute-bound vs bandwidth-bound
against the calibrated machine balance
(`nodes.learning.calibrate.machine_rates`, the same weights
`reconcile.drift_cost_weights` recalibrates from live spans).

The model:

  - **flops** — a per-primitive jaxpr walk (`jaxpr_counts`):
    `dot_general` 2·out·contraction, `conv_general_dilated`
    2·out·kernel·in_ch, FFT 5·n·log2 n, reductions/pool windows at
    input size, elementwise at one FLOP per output element,
    transcendentals deliberately flattened to the same (the MXU/VPU
    issue rate, not the op latency, is what the roofline prices).
    `lax.scan` bodies multiply by trip count; `while` counts one trip
    (an honest floor); `cond` takes the worst branch. Where the backend
    provides `Lowered.cost_analysis()`, `xla_cost_analysis` is the
    cross-check (tests pin 2× agreement on a GEMM stage) — the jaxpr
    walk stays the source of truth because the CPU backend's analysis
    is absent or partial for many ops.
  - **bytes** — the stage-at-a-time HBM model: under XLA's per-stage
    lowering every stage boundary round-trips through HBM, so a stage's
    traffic is its input element bytes plus its output element bytes
    (× the propagated example count). Pure data-movement primitives
    (transpose/reshape/gather/...) additionally accumulate
    ``movement_bytes`` — traffic that produces no FLOPs — which is what
    KP802 compares against compute.
  - **time** — ``stage_cost(flops, bytes) = max(flops/peak_flops,
    bytes/peak_bw)``: the roofline's time denominator, exported for the
    future unified plan optimizer (ROADMAP: ONE calibrated cost model).
  - **fitted applies** — a `_FitSlot` / `DelegatingOperator` body does
    not exist before the fit runs; it is *modeled* as a dense map
    (2·in·out FLOPs per item, ``flop_source="modeled"``) — exactly the
    y=xW family every `fusable_fit` estimator produces.

Lints (all advisory — the roofline informs, placement/precision decide):

  - **KP801** (INFO): a bandwidth-bound fan-out-free fused chain of ≥2
    stages is a Pallas megakernel candidate, priced with the boundary
    bytes the chain would stop round-tripping through HBM (each
    internal boundary is one write + one read at peak bandwidth) — the
    static selector for the ROADMAP's Pallas megakernel backend.
  - **KP802** (WARNING): a stage dominated by pure data movement —
    transpose/reshape/gather traffic at least the larger of its compute
    and its unavoidable boundary traffic — is paying for layout, not
    math (the file-level twin is jaxlint KJ013).
  - **KP803** (INFO): the whole plan re-priced in seconds; the per-stage
    ``predicted_seconds`` are embedded in trace metadata
    (``keystone.roofline``) so `analysis.reconcile` joins them against
    observed span timings (the flops-residual column of the drift
    report).
  - **KP804** (INFO): a megafused scan body whose per-trip compute is
    below the dispatch/loop overhead floor cannot amortize its trips —
    raise ``chunk_size``.
  - **KP805** (INFO): a KP801 candidate that actually LOWERS — its
    `stage_fuse` statics match a chain-kernel family in
    `ops/chain_kernels.py` — and whose one-HBM-pass kernel pricing
    beats the XLA chain's predicted seconds; the unified planner's
    kernel axis prices the scored pair and records the decision.

Everything here is pure spec arithmetic over abstract values — no data
moves, no device allocates, no program compiles.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..workflow.graph import Graph, GraphId, NodeId, SinkId
from .diagnostics import Diagnostic, Severity
from .memory import _fmt_bytes, resolve_chunk_rows
from .propagate import _label, toposort
from .specs import (
    UNKNOWN,
    DataSpec,
    TransformerSpec,
    element_nbytes,
    is_known,
)

#: per-program dispatch / scan-trip bookkeeping floor the KP804 lint
#: amortizes against (~50 µs, an order of magnitude for a host-attached
#: chip, not measured on the machine builders reach now; in-program scan trips are cheaper but
#: the same order once loop bookkeeping and donation checks are paid).
DISPATCH_OVERHEAD_S = 5e-5

# ------------------------------------------------------------ jaxpr walk

#: primitives that MOVE bytes but perform no arithmetic — the traffic
#: KP802 weighs against compute. `convert_element_type` belongs here:
#: a cast re-materializes every byte it touches for zero FLOPs.
_MOVEMENT_PRIMS = frozenset({
    "transpose", "reshape", "rev", "broadcast_in_dim", "squeeze",
    "expand_dims", "slice", "dynamic_slice", "dynamic_update_slice",
    "concatenate", "pad", "gather", "scatter", "select_and_scatter_add",
    "convert_element_type", "bitcast_convert_type", "copy",
    "device_put", "split",
})

#: primitives that neither compute nor read (generators, annotations).
_FREE_PRIMS = frozenset({
    "iota", "stop_gradient", "broadcast", "create_token",
    "sharding_constraint", "constant",
})

#: reductions priced at INPUT size (every input element is touched once).
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "reduce_precision", "cumsum", "cumprod", "cummax", "cummin",
    "cumlogsumexp",
})


def _aval_elems(v) -> int:
    shape = getattr(getattr(v, "aval", None), "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape, dtype=np.int64))


def _aval_nbytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def _eqn_cost(eqn) -> Tuple[float, float]:
    """``(flops, movement_bytes)`` of one first-order equation."""
    name = eqn.primitive.name
    out_elems = sum(_aval_elems(v) for v in eqn.outvars)
    if name in _FREE_PRIMS:
        return 0.0, 0.0
    if name in _MOVEMENT_PRIMS:
        nbytes = (sum(_aval_nbytes(v) for v in eqn.invars)
                  + sum(_aval_nbytes(v) for v in eqn.outvars))
        return 0.0, float(nbytes)
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs_shape = getattr(eqn.invars[0].aval, "shape", ())
        contraction = int(np.prod(
            [lhs_shape[d] for d in lhs_contract], dtype=np.int64)) or 1
        return 2.0 * out_elems * contraction, 0.0
    if name == "conv_general_dilated":
        dnums = eqn.params["dimension_numbers"]
        kshape = getattr(eqn.invars[1].aval, "shape", ())
        rhs_spec = dnums.rhs_spec  # (out_ch, in_ch, *spatial)
        in_ch = kshape[rhs_spec[1]] if len(kshape) > rhs_spec[1] else 1
        spatial = int(np.prod(
            [kshape[d] for d in rhs_spec[2:]], dtype=np.int64)) or 1
        return 2.0 * out_elems * spatial * in_ch, 0.0
    if name == "fft":
        lengths = eqn.params.get("fft_lengths", ())
        n = int(np.prod(lengths, dtype=np.int64)) or 1
        in_elems = _aval_elems(eqn.invars[0]) or n
        batches = max(1, in_elems // n)
        return 5.0 * n * math.log2(max(2, n)) * batches, 0.0
    if name in _REDUCE_PRIMS:
        return float(sum(_aval_elems(v) for v in eqn.invars)), 0.0
    if name.startswith("reduce_window") or name == "select_and_scatter":
        window = eqn.params.get("window_dimensions", ())
        wsize = int(np.prod(window, dtype=np.int64)) or 1
        return float(out_elems * wsize), 0.0
    if name == "sort":
        in_elems = sum(_aval_elems(v) for v in eqn.invars)
        dim_shape = getattr(eqn.invars[0].aval, "shape", (2,))
        axis = eqn.params.get("dimension", len(dim_shape) - 1)
        n = dim_shape[axis] if dim_shape else 2
        return float(in_elems * math.log2(max(2, n))), 0.0
    if name.startswith("scatter"):
        # scatter-add and friends: one op per update element, plus the
        # operand copy counts as movement
        updates = _aval_elems(eqn.invars[-1])
        nbytes = _aval_nbytes(eqn.invars[0]) + sum(
            _aval_nbytes(v) for v in eqn.outvars)
        return float(updates), float(nbytes)
    # default: elementwise — one FLOP per output element (transcendental
    # flattening is deliberate; see module docstring)
    return float(out_elems), 0.0


def jaxpr_counts(jaxpr) -> Tuple[float, float]:
    """``(flops, movement_bytes)`` of a (Closed)Jaxpr, sub-jaxprs
    (pjit, scan × trip count, while ≥1 trip, cond worst-branch)
    included."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    flops = 0.0
    movement = 0.0
    for eqn in inner.eqns:
        name = eqn.primitive.name
        if name == "scan":
            f, m = jaxpr_counts(eqn.params["jaxpr"])
            trips = int(eqn.params.get("length", 1) or 1)
            flops += f * trips
            movement += m * trips
            continue
        if name == "while":
            fc, mc = jaxpr_counts(eqn.params["cond_jaxpr"])
            fb, mb = jaxpr_counts(eqn.params["body_jaxpr"])
            flops += fc + fb  # one trip: an honest floor, documented
            movement += mc + mb
            continue
        if name == "cond":
            branches = eqn.params.get("branches", ())
            if branches:
                sub = [jaxpr_counts(b) for b in branches]
                flops += max(s[0] for s in sub)
                movement += max(s[1] for s in sub)
            continue
        recursed = False
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = eqn.params.get(key) if eqn.params else None
            if sub is not None and hasattr(
                    getattr(sub, "jaxpr", sub), "eqns"):
                f, m = jaxpr_counts(sub)
                flops += f
                movement += m
                recursed = True
                break
        if recursed:
            continue
        f, m = _eqn_cost(eqn)
        flops += f
        movement += m
    return flops, movement


def body_counts(fn, elem) -> Optional[Tuple[float, float]]:
    """Per-item ``(flops, movement_bytes)`` of one stage body, traced
    abstractly over the propagated element spec (`jax.make_jaxpr` on a
    `ShapeDtypeStruct` pytree — zero data movement). None when the body
    is host code the tracer cannot enter."""
    if not is_known(elem):
        return None
    try:
        jx = jax.make_jaxpr(fn)(elem)
    except Exception:
        return None
    return jaxpr_counts(jx)


def xla_cost_analysis(fn, elem) -> Optional[Dict[str, Optional[float]]]:
    """Backend-reported ``{"flops", "bytes"}`` of one stage body via
    `Lowered.cost_analysis()` — the cross-check, NOT the source of
    truth: the CPU backend's analysis is absent or partial for many
    ops, so callers must treat None (or a non-positive flop count) as
    'backend cannot tell' and fall back to the jaxpr walk."""
    try:
        ca = jax.jit(fn).lower(elem).cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    if flops is None or not np.isfinite(flops) or flops <= 0:
        return None
    nbytes = ca.get("bytes accessed")
    return {"flops": float(flops),
            "bytes": float(nbytes) if nbytes is not None else None}


# --------------------------------------------------------------- machine


@dataclass(frozen=True)
class Machine:
    """The roofline's two peak rates. ``balance`` (FLOP per byte) is
    the ridge point: a stage whose arithmetic intensity sits below it
    is bandwidth-bound."""

    peak_flops: float  # FLOP/s
    peak_bw: float     # HBM B/s

    @property
    def balance(self) -> float:
        return self.peak_flops / self.peak_bw


def default_machine() -> Machine:
    """Machine balance from the calibrated cost weights — the SAME
    numbers the solver cost model and every optimizer decision price
    with (`calibrate.machine_rates`: measured calibration when the
    platform matches, honest CPU-backend analytic peaks otherwise)."""
    from ..nodes.learning.calibrate import machine_rates

    peak_flops, peak_bw = machine_rates()
    return Machine(peak_flops, peak_bw)


def stage_cost(flops: Optional[float], nbytes: Optional[float],
               machine: Optional[Machine] = None) -> float:
    """``predicted_seconds = max(flops/peak_flops, bytes/peak_bw)`` —
    the roofline time model, exported for the future unified plan
    optimizer (each decision menu entry prices in these seconds)."""
    machine = machine or default_machine()
    return max(float(flops or 0.0) / machine.peak_flops,
               float(nbytes or 0.0) / machine.peak_bw)


# ------------------------------------------------------------ stage model


@dataclass
class StageRoofline:
    """One priced stage: FLOPs, stage-at-a-time HBM traffic, derived
    intensity/bound, and the predicted seconds. ``trail`` carries the
    per-internal-stage rows of a fused/megafused program body."""

    vertex: NodeId
    label: str
    flops: float
    hbm_bytes: int
    movement_bytes: float
    count: int
    flop_source: str  # "traced" | "modeled" | "mixed"
    intensity: float
    bound: str  # "compute" | "bandwidth"
    predicted_seconds: float
    trail: List[Dict[str, Any]] = field(default_factory=list)
    #: bytes of the stage's internal boundaries (fused trails only):
    #: what a Pallas megakernel would keep in VMEM
    internal_boundary_bytes: int = 0

    def as_row(self) -> Dict[str, Any]:
        return {
            "vertex": self.vertex.id,
            "label": self.label,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "movement_bytes": self.movement_bytes,
            "count": self.count,
            "flop_source": self.flop_source,
            "intensity": self.intensity,
            "bound": self.bound,
            "predicted_seconds": self.predicted_seconds,
            "stages": list(self.trail),
        }


@dataclass
class RooflineEstimate:
    """The roofline picture of one graph: per-stage costs, the machine
    they were classified against, the plan total in seconds, and the
    KP801 Pallas-candidate chains."""

    stages: Dict[NodeId, StageRoofline] = field(default_factory=dict)
    machine: Machine = None
    plan_seconds: float = 0.0
    candidates: List[Dict[str, Any]] = field(default_factory=list)
    unknown_stages: int = 0

    def rows(self, graph: Graph) -> List[Dict[str, Any]]:
        order, _ = toposort(graph)
        return [self.stages[v].as_row() for v in order
                if isinstance(v, NodeId) and v in self.stages]

    def __repr__(self) -> str:
        return (f"RooflineEstimate({len(self.stages)} stage(s), "
                f"≈{self.plan_seconds:.3e}s predicted, "
                f"{len(self.candidates)} pallas candidate(s))")


def _fmt_rate(x: float) -> str:
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(x) < 1000 or unit == "P":
            return f"{x:.1f}{unit}"
        x /= 1000.0
    return str(x)


def format_roofline(rows: List[Dict[str, Any]]) -> str:
    """Text table of `RooflineEstimate.rows` (the --explain-roofline
    rendering)."""
    lines = [f"{'stage':<40} {'flops':>10} {'bytes':>10} {'flop/B':>8} "
             f"{'bound':<10} {'pred s':>10}"]
    for r in rows:
        name = f"{r['label']}@{r['vertex']}"
        lines.append(
            f"{name[:40]:<40} {_fmt_rate(r['flops']):>10} "
            f"{_fmt_bytes(int(r['hbm_bytes'])):>10} "
            f"{r['intensity']:>8.2f} {r['bound']:<10} "
            f"{r['predicted_seconds']:>10.3e}")
    return "\n".join(lines)


# --------------------------------------------------------- trail walking


def _elem_count(spec: Any, nominal: int) -> int:
    if isinstance(spec, DataSpec) and spec.kind == "dataset":
        return int(spec.count) if spec.count else nominal
    return 1


def _modeled_dense_flops(in_elem, out_elem) -> Optional[float]:
    """Per-item FLOPs of a fitted apply modeled as a dense map in→out
    (2·in·out — the y = xW family every `fusable_fit` estimator
    produces). Refinement: when both sides are single-leaf 2-D arrays
    sharing a leading dim, the map is row-wise (each row independently
    projected — the PCA/whitening family) and prices 2·rows·d_in·d_out;
    the full in×out product would charge the rows against each other,
    a quadratic overprice the serving latency bound cannot afford."""
    in_leaves = jax.tree_util.tree_leaves(in_elem)
    out_leaves = jax.tree_util.tree_leaves(out_elem)
    if len(in_leaves) == 1 and len(out_leaves) == 1:
        a, b = in_leaves[0], out_leaves[0]
        if getattr(a, "ndim", 0) == 2 and getattr(b, "ndim", 0) == 2 \
                and a.shape[0] == b.shape[0]:
            return 2.0 * float(a.shape[0]) * float(a.shape[1]) \
                * float(b.shape[1])

    def elems(e) -> Optional[int]:
        total = 0
        for leaf in jax.tree_util.tree_leaves(e):
            shape = getattr(leaf, "shape", None)
            if shape is None:
                return None
            total += int(np.prod(shape, dtype=np.int64))
        return total

    in_elems = elems(in_elem)
    out_elems = elems(out_elem)
    if in_elems is None or out_elems is None:
        return None
    return 2.0 * in_elems * out_elems


def _stage_trail(graph: Graph, vid: NodeId, op, specs: Dict[GraphId, Any]):
    """The per-internal-stage cost trail of one vertex:
    ``[(label, in_elem, out_elem, flops_per_item, movement_per_item,
    source)]``, or None when nothing can be priced.

    A `FusedChainOperator`/`MegafusedPlanOperator` walks its
    `fused_stages` list (the list `_build_program` executes) with `_FitSlot`s
    modeled as dense maps; a `FusedBatchTransformer` walks its fitted
    ``stages`` the same way; a `DelegatingOperator` is one modeled
    dense map; a plain transformer with a traceable per-item body is
    one traced stage."""
    from ..nodes.util.fusion import FusedBatchTransformer
    from ..workflow.fusion_rule import FusedChainOperator, _FitSlot
    from ..workflow.operators import DelegatingOperator

    deps = graph.get_dependencies(vid)
    if not deps:
        return None

    if isinstance(op, (FusedChainOperator, FusedBatchTransformer)):
        from ..nodes.util.fusion import fused_stages

        data_spec = specs.get(deps[-1])
        if not isinstance(data_spec, DataSpec) or not is_known(
                data_spec.element):
            return None
        t_specs = [specs.get(d) for d in deps[:-1]]
        elem = data_spec.element
        trail = []
        stage_list = (op.stage_specs if isinstance(op, FusedChainOperator)
                      else op.stages)
        # any unpriceable internal stage makes the WHOLE vertex
        # unpriced: a partial prefix silently recorded as the full
        # stage would undercount KP803 plan seconds, corrupt KP801
        # boundary bytes, and hand reconcile a prediction covering
        # less work than the span it joins (spurious residual)
        for s in fused_stages(stage_list):
            if not is_known(elem):
                return None
            if isinstance(s, _FitSlot):
                ts = t_specs[s.index] if s.index < len(t_specs) else None
                out = (ts.apply_element(elem)
                       if isinstance(ts, TransformerSpec) else UNKNOWN)
                if not is_known(out):
                    return None
                flops = _modeled_dense_flops(elem, out)
                if flops is None:
                    return None
                trail.append((repr(s), elem, out, flops, 0.0, "modeled"))
            else:
                counts = body_counts(
                    lambda x, s=s: s.single_transform([x]), elem)
                try:
                    out = jax.eval_shape(
                        lambda x, s=s: s.single_transform([x]), elem)
                except Exception:
                    return None
                if counts is None or not is_known(out):
                    return None
                trail.append((s.label, elem, out, counts[0], counts[1],
                              "traced"))
            elem = trail[-1][2]
        return trail or None

    if isinstance(op, DelegatingOperator):
        if len(deps) < 2:
            return None
        data_spec = specs.get(deps[1])
        out_spec = specs.get(vid)
        if not isinstance(data_spec, DataSpec) \
                or not isinstance(out_spec, DataSpec) \
                or not is_known(data_spec.element) \
                or not is_known(out_spec.element):
            return None
        # the estimator may declare its encoder's honest flop order
        # (`abstract_apply_flops` — the FV family prices ~40× under
        # the generic dense map); the dense model is the fallback
        flops = None
        est_dep = deps[0]
        if isinstance(est_dep, NodeId):
            hook = getattr(graph.get_operator(est_dep),
                           "abstract_apply_flops", None)
            if hook is not None:
                try:
                    flops = hook(data_spec.element, out_spec.element)
                except Exception:
                    flops = None
        if flops is None:
            flops = _modeled_dense_flops(data_spec.element,
                                         out_spec.element)
        if flops is None:
            return None
        return [(_label(graph, vid), data_spec.element, out_spec.element,
                 float(flops), 0.0, "modeled")]

    fn = getattr(op, "single_transform", None)
    if fn is None:
        return None
    data_spec = specs.get(deps[0])
    if not isinstance(data_spec, DataSpec) or not is_known(
            data_spec.element):
        return None
    counts = body_counts(lambda x: fn([x]), data_spec.element)
    out_spec = specs.get(vid)
    out_elem = out_spec.element if isinstance(out_spec, DataSpec) else UNKNOWN
    if counts is None or not is_known(out_elem):
        return None
    return [(_label(graph, vid), data_spec.element, out_elem,
             counts[0], counts[1], "traced")]


# ------------------------------------------------------------------ pass


def roofline_pass(
    graph: Graph,
    specs: Dict[GraphId, Any],
    *,
    machine: Optional[Machine] = None,
    chunk_rows: Optional[int] = None,
    only: Optional[Sequence[NodeId]] = None,
) -> Tuple[RooflineEstimate, List[Diagnostic]]:
    """Price every priceable stage of one graph on the roofline and
    emit the KP8xx lints. Pure spec arithmetic — never touches data or
    devices.

    ``only`` restricts pricing to the given vertices (the per-chain
    ledger path: jaxpr-tracing every stage of the graph to price one
    chain would be O(stages) per decision record). A restricted
    estimate skips the lints — KP801/KP803 are whole-plan statements."""
    from ..workflow.fusion_rule import MegafusedPlanOperator

    machine = machine or default_machine()
    chunk_rows = resolve_chunk_rows(chunk_rows)
    order, _ = toposort(graph)
    restrict = set(only) if only is not None else None
    est = RooflineEstimate(machine=machine)
    diags: List[Diagnostic] = []

    known_counts = [
        s.count for s in specs.values()
        if isinstance(s, DataSpec) and s.kind == "dataset" and s.count
    ]
    nominal = max(known_counts, default=1024)

    for vid in order:
        if not isinstance(vid, NodeId):
            continue
        if restrict is not None and vid not in restrict:
            continue
        op = graph.get_operator(vid)
        out_spec = specs.get(vid)
        if not isinstance(out_spec, DataSpec):
            continue  # estimators/transformer outputs: not a data stage
        trail = None
        try:
            trail = _stage_trail(graph, vid, op, specs)
        except Exception:
            trail = None
        if not trail:
            if graph.get_dependencies(vid):
                est.unknown_stages += 1
            continue
        count = _elem_count(out_spec, nominal)

        flops = 0.0
        movement = 0.0
        hbm = 0
        internal = 0
        trail_rows: List[Dict[str, Any]] = []
        sources = set()
        priced = True
        for i, (label, in_elem, out_elem, f_item, m_item, source) in \
                enumerate(trail):
            in_b = element_nbytes(in_elem)
            out_b = element_nbytes(out_elem)
            if in_b is None or out_b is None:
                priced = False
                break
            s_flops = f_item * count
            s_bytes = (in_b + out_b) * count
            s_move = m_item * count
            s_int = s_flops / s_bytes if s_bytes else 0.0
            s_bound = ("compute" if s_int >= machine.balance
                       else "bandwidth")
            trail_rows.append({
                "stage": label,
                "flops": s_flops,
                "hbm_bytes": s_bytes,
                "movement_bytes": s_move,
                "intensity": s_int,
                "bound": s_bound,
                "predicted_seconds": stage_cost(s_flops, s_bytes, machine),
                "flop_source": source,
            })
            flops += s_flops
            movement += s_move
            hbm += s_bytes
            if i < len(trail) - 1:
                internal += out_b * count
            sources.add(source)
        if not priced or not hbm:
            est.unknown_stages += 1
            continue

        intensity = flops / hbm
        bound = "compute" if intensity >= machine.balance else "bandwidth"
        seconds = stage_cost(flops, hbm, machine)
        est.stages[vid] = StageRoofline(
            vertex=vid,
            label=_label(graph, vid),
            flops=flops,
            hbm_bytes=hbm,
            movement_bytes=movement,
            count=count,
            flop_source=(sources.pop() if len(sources) == 1 else "mixed"),
            intensity=intensity,
            bound=bound,
            predicted_seconds=seconds,
            trail=trail_rows if len(trail_rows) > 1 else [],
            internal_boundary_bytes=internal,
        )

        # KP802: movement-dominated stage — pure layout traffic at least
        # the larger of its compute and its unavoidable boundary bytes
        st = est.stages[vid]
        if restrict is not None:
            continue  # restricted pricing: no lints
        if st.movement_bytes > max(st.flops, float(st.hbm_bytes)):
            diags.append(Diagnostic(
                "KP802", Severity.WARNING,
                f"data-movement-dominated stage: "
                f"{_fmt_bytes(int(st.movement_bytes))} of pure "
                f"transpose/reshape/gather traffic vs {_fmt_rate(st.flops)}"
                f" FLOPs over {_fmt_bytes(st.hbm_bytes)} of boundary "
                "bytes — the stage pays for layout, not math "
                "(see jaxlint KJ013 for the in-body pattern)",
                vertex=vid, label=st.label))

        # KP804: megafused scan body too small per trip
        if isinstance(op, MegafusedPlanOperator) and count:
            trip_cost = stage_cost(flops / count * chunk_rows,
                                   hbm / count * chunk_rows, machine)
            if trip_cost < DISPATCH_OVERHEAD_S:
                diags.append(Diagnostic(
                    "KP804", Severity.INFO,
                    f"megafused scan body predicts ≈{trip_cost:.1e}s per "
                    f"trip (chunk_rows={chunk_rows}) — below the "
                    f"≈{DISPATCH_OVERHEAD_S:.0e}s dispatch/loop overhead "
                    "floor; raise chunk_size so each trip amortizes its "
                    "bookkeeping",
                    vertex=vid, label=st.label))

    est.plan_seconds = sum(
        s.predicted_seconds for s in est.stages.values())
    if restrict is not None:
        return est, diags

    # ----------------------------------------------------------- KP801
    est.candidates = _pallas_candidates(graph, est, machine)
    for cand in est.candidates:
        head = cand["vertices"][0]
        diags.append(Diagnostic(
            "KP801", Severity.INFO,
            f"pallas-candidate: bandwidth-bound fan-out-free chain of "
            f"{cand['n_stages']} stage(s) "
            f"[{' >> '.join(cand['stages'])}]; one double-buffered "
            f"HBM→VMEM kernel stops "
            f"{_fmt_bytes(cand['boundary_bytes'])} of boundary "
            f"round-trips (≈{cand['seconds_saved']:.2e}s at "
            f"{_fmt_rate(machine.peak_bw)}B/s)",
            vertex=head, label=_label(graph, head)))
        # KP805: the candidate actually lowers, and the kernel's one
        # HBM pass beats the XLA chain's predicted seconds
        verdict = cand.get("lowerable") or {}
        if verdict.get("lowerable") \
                and cand["kernel_seconds"] < cand["chain_seconds"]:
            diags.append(Diagnostic(
                "KP805", Severity.INFO,
                f"chain-kernel-wins: lowers to ONE "
                f"{verdict['family']} Pallas kernel "
                f"(ops/chain_kernels) — predicted "
                f"≈{cand['kernel_seconds']:.2e}s vs the XLA chain's "
                f"≈{cand['chain_seconds']:.2e}s; the unified planner's "
                "kernel axis prices this pair",
                vertex=head, label=_label(graph, head)))

    if est.stages:
        diags.append(Diagnostic(
            "KP803", Severity.INFO,
            f"plan roofline: ≈{est.plan_seconds:.3e}s predicted over "
            f"{len(est.stages)} priced stage(s) (machine balance "
            f"{machine.balance:.1f} FLOP/B; peaks "
            f"{_fmt_rate(machine.peak_flops)}FLOP/s, "
            f"{_fmt_rate(machine.peak_bw)}B/s)"
            + (f"; {est.unknown_stages} stage(s) unpriced"
               if est.unknown_stages else ""),
            vertex=None, label="<plan>"))
    return est, diags


def _fusable_member(graph: Graph, vid: NodeId) -> bool:
    from ..workflow.fusion_rule import FusedChainOperator

    op = graph.get_operator(vid)
    return bool(getattr(op, "fusable", False)) \
        or isinstance(op, FusedChainOperator)


def _pallas_candidates(graph: Graph, est: RooflineEstimate,
                       machine: Machine) -> List[Dict[str, Any]]:
    """KP801 chains, two sources merged:

      - graph-level: maximal fan-out-free runs of ≥2 adjacent priced
        bandwidth-bound fusable stages (each member the sole consumer
        of its producer's data output) — what the fusion rules WILL
        collapse and a Pallas kernel could then swallow whole;
      - within one fused/megafused operator: a run of ≥2 consecutive
        bandwidth-bound trail stages — the already-fused chain whose
        internal boundaries still round-trip HBM under XLA's
        stage-at-a-time lowering.

    Each candidate is priced with the boundary bytes the kernel would
    keep in VMEM: every internal boundary is one write plus one read
    at peak bandwidth."""
    out: List[Dict[str, Any]] = []
    order, _ = toposort(graph)

    def bandwidth_bound(v) -> bool:
        s = est.stages.get(v)
        return s is not None and s.bound == "bandwidth"

    # graph-level chains
    visited: set = set()
    for vid in order:
        if not isinstance(vid, NodeId) or vid in visited:
            continue
        if not (bandwidth_bound(vid) and _fusable_member(graph, vid)):
            continue
        chain = [vid]
        cur = vid
        while True:
            users = [u for u in graph.users_of(cur)
                     if not isinstance(u, SinkId)]
            if len(users) != 1 or not isinstance(users[0], NodeId):
                break
            nxt = users[0]
            if nxt in visited or not (
                    bandwidth_bound(nxt) and _fusable_member(graph, nxt)):
                break
            chain.append(nxt)
            cur = nxt
        visited.update(chain)
        if len(chain) < 2:
            continue
        boundary = sum(_chain_boundary_bytes(est, v) for v in chain[:-1])
        chain_seconds = sum(est.stages[v].predicted_seconds for v in chain)
        cand = {
            "vertices": [v for v in chain],
            "stages": [est.stages[v].label for v in chain],
            "n_stages": len(chain),
            "boundary_bytes": int(boundary),
            "seconds_saved": 2.0 * boundary / machine.peak_bw,
            "chain_seconds": chain_seconds,
            "chain_flops": sum(est.stages[v].flops for v in chain),
            "chain_hbm_bytes": int(
                sum(est.stages[v].hbm_bytes for v in chain)),
            "stage_slice": None,
            "kind": "graph_chain",
        }
        _annotate_kernel_lowering(graph, cand, machine)
        out.append(cand)

    # fused-trail runs
    for vid, st in est.stages.items():
        if len(st.trail) < 2:
            continue
        i = 0
        while i < len(st.trail):
            if st.trail[i]["bound"] != "bandwidth":
                i += 1
                continue
            j = i
            while j < len(st.trail) and st.trail[j]["bound"] == "bandwidth":
                j += 1
            if j - i >= 2:
                # boundary between trail stages k and k+1 is stage k's
                # output: half of (in+out) is not recoverable from the
                # row, so re-derive from hbm − in: use the row's own
                # out-boundary share (hbm_bytes = (in+out)·count)
                boundary = 0
                for k in range(i, j - 1):
                    row = st.trail[k]
                    nxt = st.trail[k + 1]
                    # stage k's out bytes == stage k+1's in bytes ==
                    # (row_k.hbm + row_{k+1}.hbm − ends) /2 … simplest
                    # exact form: shared boundary = overlap of the two
                    # stage traffics
                    boundary += int(min(row["hbm_bytes"],
                                        nxt["hbm_bytes"]) // 2)
                seconds = sum(st.trail[k]["predicted_seconds"]
                              for k in range(i, j))
                cand = {
                    "vertices": [vid],
                    "stages": [st.trail[k]["stage"] for k in range(i, j)],
                    "n_stages": j - i,
                    "boundary_bytes": int(boundary),
                    "seconds_saved": 2.0 * boundary / machine.peak_bw,
                    "chain_seconds": seconds,
                    "chain_flops": sum(st.trail[k]["flops"]
                                       for k in range(i, j)),
                    "chain_hbm_bytes": int(
                        sum(st.trail[k]["hbm_bytes"] for k in range(i, j))),
                    "stage_slice": (i, j),
                    "kind": "fused_trail",
                }
                _annotate_kernel_lowering(graph, cand, machine)
                out.append(cand)
            i = j
    return out


def _candidate_stage_objects(graph: Graph, cand: Dict[str, Any]):
    """The actual stage objects a KP801 candidate's kernel would
    replace, or None when the chain has no static fuse bodies
    (`_FitSlot`s — the decomposition depends on a fit that has not
    happened). A fused_trail candidate slices the operator's
    `fused_stages` list (the list `_build_program` executes, which the trail
    indices address); a graph_chain candidate concatenates its member
    stages — the list the fusion rules WILL collapse."""
    from ..nodes.util.fusion import FusedBatchTransformer, fused_stages
    from ..workflow.fusion_rule import FusedChainOperator, _FitSlot

    stages: List[Any] = []
    if cand["kind"] == "fused_trail":
        op = graph.get_operator(cand["vertices"][0])
        stage_list = (op.stage_specs if isinstance(op, FusedChainOperator)
                      else op.stages)
        i, j = cand["stage_slice"]
        stages = fused_stages(stage_list)[i:j]
    else:
        for vid in cand["vertices"]:
            op = graph.get_operator(vid)
            if isinstance(op, (FusedChainOperator, FusedBatchTransformer)):
                stages.extend(op.stage_specs
                              if isinstance(op, FusedChainOperator)
                              else op.stages)
            else:
                stages.append(op)
    if any(isinstance(s, _FitSlot) for s in stages) \
            or not all(hasattr(s, "fuse") for s in stages):
        return None
    return stages


def _annotate_kernel_lowering(graph: Graph, cand: Dict[str, Any],
                              machine: Machine) -> None:
    """Attach the chain-kernel verdict to one KP801 candidate:

    - ``lowerable``: the `ops.chain_kernels.lowerability` verdict on
      the candidate's `stage_fuse` statics — family when it lowers,
      the blocking stages (and any NAMED suppression) when it doesn't;
    - ``kernel_seconds``: the kernel side of the planner's
      kernel-vs-XLA axis — ONE HBM pass of in+out bytes (the chain's
      traffic minus the 2× boundary round-trips the kernel keeps in
      VMEM) at the same calibrated roofline; INF when not lowerable,
      so the planner demotes cleanly instead of picking a kernel that
      cannot compile.
    """
    try:
        from ..ops.chain_kernels import lowerability, stage_statics

        stages = _candidate_stage_objects(graph, cand)
        if stages is None:
            verdict = {"lowerable": False, "family": None,
                       "reason": "fit-dependent stage: no static fuse "
                                 "body to lower"}
        else:
            verdict = lowerability(stage_statics(stages))
    except Exception as e:  # never let the verdict break the pass
        verdict = {"lowerable": False, "family": None,
                   "reason": f"fuse decomposition failed: {e}"}
    cand["lowerable"] = verdict
    if verdict.get("lowerable"):
        kernel_bytes = max(
            float(cand["chain_hbm_bytes"] - 2 * cand["boundary_bytes"]),
            0.0)
        cand["kernel_seconds"] = stage_cost(
            cand["chain_flops"], kernel_bytes, machine)
    else:
        cand["kernel_seconds"] = float("inf")


def _chain_boundary_bytes(est: RooflineEstimate, vid: NodeId) -> int:
    """The boundary a graph-chain member hands its consumer: its output
    element bytes × count — half its stage traffic minus the input
    side. Derived from the trail when present, else out = hbm − in is
    unavailable, so approximate with hbm/2 (exact for in == out)."""
    st = est.stages[vid]
    if st.trail:
        return int(st.trail[-1]["hbm_bytes"] // 2)
    return int(st.hbm_bytes // 2)


# --------------------------------------------------- optimizer plumbing


def chain_predicted_seconds(graph: Graph,
                            vertices: Sequence[NodeId]) -> Optional[float]:
    """Roofline seconds of one chain of vertices on a bound graph —
    the `predicted_seconds` a fusion/megafusion ledger record carries.
    None when nothing in the chain can be priced (unbound sources,
    host bodies). Never raises."""
    try:
        from .propagate import spec_pass

        specs, _ = spec_pass(graph, {})
        # price ONLY the chain's vertices: tracing every stage of the
        # graph per decision record would be O(stages) jaxpr walks per
        # fused chain
        est, _ = roofline_pass(graph, specs, only=list(vertices))
        vals = [est.stages[v].predicted_seconds for v in vertices
                if v in est.stages]
        return float(sum(vals)) if vals else None
    except Exception:
        return None
