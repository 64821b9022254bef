"""Mixed-precision policy pass tests (keystone_tpu/analysis/precision.py
+ workflow.optimizer.PrecisionPlannerRule).

The acceptance contract (ISSUE 10): the planner's priced boundary bytes
never exceed the all-f32 default on any example and strictly beat it on
≥ 2; policy-on outputs are allclose to the serial unfused f32 reference
within the declared tolerance band at multiple AND ragged counts;
``KEYSTONE_PRECISION_PLANNER=0`` reproduces the PR-9 plan bit-for-bit;
chosen casts are present in the fused/megafused program jaxpr with the
program's visible output dtype unchanged; intolerant solver boundaries
stay f32; the KP2xx/KP600 memory models re-price under the decided
dtypes (bf16 halves exactly the chosen float boundaries — and the
static model reads REAL leaf dtypes, pinned by the uint8
static-vs-observed reconciliation test); and warm runs stay 0-cold
under an enforced policy.
"""

import numpy as np
import pytest
import jax

from keystone_tpu.analysis import SpecDataset, as_source_spec
from keystone_tpu.analysis.diagnostics import Severity
from keystone_tpu.analysis.examples import EXAMPLES, build_example
from keystone_tpu.analysis.memory import memory_pass
from keystone_tpu.analysis.precision import (
    CAST_PENALTY_BYTES,
    DEFAULT_BAND_ATOL,
    DEFAULT_BAND_RTOL,
    EXACT,
    POLICY_BF16,
    POLICY_F32,
    POLICY_F32_BF16,
    TOLERANT,
    PrecisionPlan,
    _PrecisionModel,
    _plan_path,
    plan_precision,
    plan_stage_precision,
    policy_nbytes,
    precision_pass,
    probe_tolerance,
    reprice_memory,
    shrink_to_band,
)
from keystone_tpu.analysis.propagate import spec_pass
from keystone_tpu.analysis.specs import DataSpec, shape_struct
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.nodes.stats import LinearRectifier, RandomSignNode
from keystone_tpu.nodes.stats.normalization import (
    NormalizeRows,
    SignedHellingerMapper,
)
from keystone_tpu.nodes.util import (
    Cacher,
    ClassLabelIndicatorsFromInt,
    MaxClassifier,
)
from keystone_tpu.nodes.util.fusion import FusedBatchTransformer
from keystone_tpu.parallel import mesh as meshlib
from keystone_tpu.workflow import PipelineEnv
from keystone_tpu.workflow.env import config_override
from keystone_tpu.workflow.fusion_rule import (
    FusedChainOperator,
    MegafusedPlanOperator,
)
from keystone_tpu.workflow.graph import NodeId
from keystone_tpu.workflow.optimizer import DefaultOptimizer


def _source(shape, dtype, count):
    return as_source_spec(SpecDataset(shape, dtype, count=count).spec)


def _raw_graph(name):
    pipeline, source_spec = build_example(name)
    graph = pipeline.graph
    specs, _ = spec_pass(graph, {pipeline.source: as_source_spec(source_spec)})
    return graph, specs


def _tolerant_chain_pipeline(count=4, dim=8):
    from keystone_tpu.nodes.stats import LinearRectifier

    pipe = (SignedHellingerMapper().to_pipeline() >> NormalizeRows()
            >> LinearRectifier(0.0))
    graph = pipe.graph
    specs, _ = spec_pass(
        graph, {pipe.source: _source((dim,), np.float32, count)})
    return graph, specs


# -------------------------------------------------------- decision core


def test_policy_nbytes_is_dtype_aware():
    """bf16 storage halves float32 leaves ONLY; uint8 loader stages and
    int32 label stages keep their real 1/4-byte itemsize — the
    dtype-aware KP2xx arithmetic."""
    f32 = DataSpec(element=shape_struct((16,), np.float32), count=10)
    u8 = DataSpec(element=shape_struct((16,), np.uint8), count=10)
    i32 = DataSpec(element=shape_struct((16,), np.int32), count=10)
    assert policy_nbytes(f32, POLICY_F32) == 16 * 4 * 10
    assert policy_nbytes(f32, POLICY_BF16) == 16 * 2 * 10
    assert policy_nbytes(u8, POLICY_F32) == 16 * 1 * 10
    assert policy_nbytes(u8, POLICY_BF16) == 16 * 1 * 10  # never touched
    assert policy_nbytes(i32, POLICY_BF16) == 16 * 4 * 10
    # f32_bf16 is byte-neutral (compute-only concession)
    assert policy_nbytes(f32, "f32_bf16") == policy_nbytes(f32, POLICY_F32)


def test_plan_path_run_economics():
    """The chain DP keeps a maximal bf16 run iff its saved bytes exceed
    the TWO casts the run costs (one down entering, one up leaving)."""
    big = 3 * CAST_PENALTY_BYTES
    # a run worth keeping
    assert _plan_path([big, big], [True, True]) == [True, True]
    # a run not worth two casts
    assert _plan_path([CAST_PENALTY_BYTES], [True]) == [False]
    # an illegal boundary splits runs: each side judged independently
    assert _plan_path([big, None, big], [True, False, True]) == \
        [True, False, True]
    assert _plan_path([CAST_PENALTY_BYTES, None, big],
                      [True, False, True]) == [False, False, True]


def test_probe_tolerance_declared_beats_probe():
    """A declared contract wins outright; an undeclared floating
    elementwise stage probes tolerant; a stage whose trace dies (or
    yields non-float) pins EXACT."""
    elem = shape_struct((8,), np.float32)
    tol, src = probe_tolerance(NormalizeRows(), elem)
    assert (tol, src) == (TOLERANT, "declared")
    tol, src = probe_tolerance(MaxClassifier(), elem)
    assert (tol, src) == (EXACT, "declared")

    from keystone_tpu.workflow import Transformer

    undeclared = Transformer.from_function(lambda x: x * 2.0)
    tol, src = probe_tolerance(undeclared, elem)
    assert (tol, src) == (TOLERANT, "probed")
    to_int = Transformer.from_function(
        lambda x: jax.numpy.argmax(x, axis=-1))
    tol, src = probe_tolerance(to_int, elem)
    assert (tol, src) == (EXACT, "probe-pinned")


def test_small_boundaries_never_beat_the_cast_penalty():
    """A tolerant chain whose total halving is below two casts' worth
    degrades to the all-f32 default (improved=False) — the KP702
    discipline priced into the objective."""
    graph, specs = _tolerant_chain_pipeline(count=4, dim=8)
    plan = plan_precision(graph, specs)
    assert plan is not None and not plan.improved
    assert plan.policies == plan.default_policies
    assert plan.savings_bytes == 0


def test_big_boundaries_choose_bf16_and_strictly_win():
    """The same chain at a real count halves every eligible boundary
    and strictly beats the default's priced bytes."""
    graph, specs = _tolerant_chain_pipeline(count=100_000, dim=64)
    plan = plan_precision(graph, specs)
    assert plan is not None and plan.improved
    changed = plan.changed_vertices()
    assert changed, "no boundary chosen despite clear savings"
    for vid in changed:
        assert plan.policies[vid] == POLICY_BF16
        tol, _ = plan.tolerances[vid]
        assert tol == TOLERANT
    assert plan.planned_cost_bytes < plan.default_cost_bytes
    # chosen policies are KP7xx-clean under the independent lint
    diags = precision_pass(graph, specs, plan)
    assert [d for d in diags if d.severity >= Severity.WARNING] == []


def test_exact_consumer_through_passthrough_pins_producer():
    """A tolerant featurize stage whose bytes flow through a Cacher into
    an exact solver keeps its f32 boundary: the analyzer looks through
    value-preserving plumbing and lets the REAL consumer decide."""
    graph, specs = _raw_graph("RandomPatchCifar")
    plan = plan_precision(graph, specs)
    assert plan is not None and plan.improved
    # ImageVectorizer (tolerant) feeds Cacher -> StandardScaler (exact):
    # its boundary must stay f32 even though the stage itself tolerates
    from keystone_tpu.nodes.images.core import ImageVectorizer

    vec_vids = [v for v in graph.operators
                if isinstance(graph.get_operator(v), ImageVectorizer)]
    assert vec_vids
    for v in vec_vids:
        assert plan.policies.get(v, POLICY_F32) == POLICY_F32
    # while upstream boundaries between tolerant stages went bf16
    assert any(plan.policies[v] == POLICY_BF16
               for v in plan.changed_vertices())


def test_planner_beats_default_on_at_least_two_examples():
    """The static acceptance gate, in tier-1: planner bytes ≤ default on
    every analyzable example, strictly less on ≥ 2, and every chosen
    policy KP7xx-clean (mirrors scripts/lint.sh's precision audit)."""
    strict = 0
    for name in sorted(EXAMPLES):
        graph, specs = _raw_graph(name)
        plan = plan_precision(graph, specs)
        if plan is None:
            continue  # nothing to decide: no tolerant float boundary
        assert plan.planned_cost_bytes <= plan.default_cost_bytes, name
        if plan.planned_cost_bytes < plan.default_cost_bytes:
            strict += 1
        diags = precision_pass(graph, specs, plan)
        gate = [d for d in diags if d.severity >= Severity.WARNING]
        assert gate == [], (name, gate)
    assert strict >= 2, f"strict wins on only {strict} example(s)"


# ------------------------------------------------------------- the lints


def test_kp701_flags_policy_on_intolerant_stage():
    """A hand-written bf16 policy on an exact boundary fails loudly."""
    graph, specs = _raw_graph("RandomPatchCifar")
    from keystone_tpu.nodes.stats.scalers import StandardScalerModel

    exact_vids = [
        v for v in graph.operators
        if getattr(graph.get_operator(v), "precision_tolerance", None)
        == EXACT and isinstance(specs.get(v), DataSpec)
    ]
    assert exact_vids
    vid = exact_vids[0]
    plan = PrecisionPlan(
        policies={vid: POLICY_BF16},
        default_policies={vid: POLICY_F32},
        planned_cost_bytes=0, default_cost_bytes=0)
    diags = precision_pass(graph, specs, plan)
    kp701 = [d for d in diags if d.rule == "KP701"]
    assert kp701 and kp701[0].severity == Severity.ERROR
    assert kp701[0].vertex == vid


def test_kp702_flags_cast_thrash():
    """A bf16 boundary whose every consumer is f32 and whose halving
    does not cover the two casts is cast-thrash: the downcast is undone
    immediately downstream for nothing."""
    graph, specs = _tolerant_chain_pipeline(count=4, dim=8)
    order = sorted((v for v in graph.operators), key=lambda v: v.id)
    first = order[0]  # tiny tolerant boundary, tolerant f32 consumer
    plan = PrecisionPlan(
        policies={first: POLICY_BF16},
        default_policies={first: POLICY_F32},
        planned_cost_bytes=0, default_cost_bytes=0)
    diags = precision_pass(graph, specs, plan)
    kp702 = [d for d in diags if d.rule == "KP702"]
    assert kp702 and kp702[0].severity == Severity.WARNING
    assert kp702[0].vertex == first


def test_kp703_reprices_memory_under_chosen_dtypes():
    """`reprice_memory` re-runs the KP2xx model with the decided storage
    dtypes: every changed f32 stage's residency halves exactly, KP703
    INFO rows name each one, and untouched stages keep their numbers."""
    graph, specs = _raw_graph("RandomPatchCifar")
    plan = plan_precision(graph, specs)
    assert plan is not None and plan.improved
    est0, est1, diags = reprice_memory(graph, specs, plan)
    assert est1.peak_bytes < est0.peak_bytes
    kp703 = {d.vertex for d in diags if d.rule == "KP703"}
    assert kp703
    halved = 0
    for vid in plan.changed_vertices():
        spec = specs.get(vid)
        leaves = jax.tree_util.tree_leaves(spec.element)
        a, b = est0.resident.get(vid), est1.resident.get(vid)
        if a is None or b is None:
            continue
        if all(np.dtype(l.dtype) == np.float32 for l in leaves):
            assert b * 2 == a, (vid, a, b)
            assert vid in kp703
            halved += 1
    assert halved, "no changed f32 stage had a priceable residency pair"
    changed = set(plan.changed_vertices())
    for vid in est0.resident:
        if vid not in changed:
            assert est0.resident[vid] == est1.resident[vid]


def test_kp600_per_device_numbers_halve_under_policy():
    """The dtype-aware KP600 pin: per-device residency (the sharded
    KP2xx picture) halves on a chosen f32 boundary when the per-device
    pass prices the plan's retyped specs."""
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from keystone_tpu.analysis.sharding import per_device_pass, sharding_pass

    graph, specs = _raw_graph("RandomPatchCifar")
    plan = plan_precision(graph, specs)
    assert plan is not None and plan.improved
    retyped = plan.retyped_specs(specs)

    def per_dev(sp):
        shardings, _, _ = sharding_pass(graph, sp)
        est, _ = memory_pass(graph, sp)
        pd, _ = per_device_pass(graph, sp, shardings, est)
        return pd

    pd0, pd1 = per_dev(specs), per_dev(retyped)
    halved = [
        v for v in plan.changed_vertices()
        if pd0.get(v) and pd1.get(v) and pd1[v] * 2 == pd0[v]
    ]
    assert halved, "no per-device number halved under the chosen policy"


def test_shrink_to_band_reverts_largest_savings_first():
    """The band repair loop discards the most aggressive halving first
    and terminates at the all-f32 default when nothing satisfies."""
    graph, specs = _raw_graph("RandomPatchCifar")
    plan = plan_precision(graph, specs)
    assert plan is not None and len(plan.changed_vertices()) >= 2
    biggest = max(
        plan.changed_vertices(),
        key=lambda v: plan.default_boundary.get(v, 0)
        - plan.planned_boundary.get(v, 0))

    seen = []

    def eval_once(p):
        seen.append(list(p.changed_vertices()))
        return len(p.changed_vertices()) <= len(
            plan.changed_vertices()) - 2

    fixed = shrink_to_band(plan, eval_once)
    assert len(fixed.changed_vertices()) == len(plan.changed_vertices()) - 2
    assert biggest not in fixed.changed_vertices()  # reverted first

    # an evaluator that never passes terminates at the default
    allf32 = shrink_to_band(plan, lambda p: False)
    assert allf32.changed_vertices() == []
    # ...whose cost is the default's own (no orphaned cast penalties)
    assert allf32.planned_cost_bytes == allf32.default_cost_bytes


def test_shrink_to_band_rescore_keeps_cost_exact():
    """With the model's scorer supplied, every partially shrunk plan's
    cost is EXACTLY what scoring its policies yields — cast-penalty
    edges created by splitting a run are accounted, not approximated."""
    graph, specs = _raw_graph("RandomPatchCifar")
    plan = plan_precision(graph, specs)
    assert plan is not None and len(plan.changed_vertices()) >= 2
    model = _PrecisionModel(graph, specs, tolerances=plan.tolerances)

    def eval_once(p):
        return len(p.changed_vertices()) <= len(plan.changed_vertices()) - 2

    fixed = shrink_to_band(plan, eval_once, rescore=model.score)
    obj, _ = model.score(fixed.policies)
    assert fixed.planned_cost_bytes == obj
    assert fixed.default_cost_bytes == plan.default_cost_bytes


def test_kp701_compute_policy_checked_and_consumer_exempt():
    """A hand-written compute-reduced policy (f32_bf16) on an EXACT
    stage fires KP701 — reduced matmul precision degrades the solver
    even though the boundary storage stays f32. On a TOLERANT stage it
    passes even when the downstream consumer is exact: consumers still
    receive full-precision bytes under a compute-only policy."""
    graph, specs = _raw_graph("RandomPatchCifar")
    exact_vids = [
        v for v in graph.operators
        if getattr(graph.get_operator(v), "precision_tolerance", None)
        == EXACT and isinstance(specs.get(v), DataSpec)
    ]
    assert exact_vids
    plan = PrecisionPlan(
        policies={exact_vids[0]: POLICY_F32_BF16},
        default_policies={exact_vids[0]: POLICY_F32},
        planned_cost_bytes=0, default_cost_bytes=0)
    kp701 = [d for d in precision_pass(graph, specs, plan)
             if d.rule == "KP701"]
    assert kp701 and kp701[0].vertex == exact_vids[0]

    # tolerant producer feeding an exact consumer: storage bf16 would
    # flag (the existing KP701 contract), compute-only must not
    from keystone_tpu.nodes.images.core import ImageVectorizer

    vec = next(v for v in graph.operators
               if isinstance(graph.get_operator(v), ImageVectorizer))
    plan2 = PrecisionPlan(
        policies={vec: POLICY_F32_BF16},
        default_policies={vec: POLICY_F32},
        planned_cost_bytes=0, default_cost_bytes=0)
    assert [d for d in precision_pass(graph, specs, plan2)
            if d.rule == "KP701"] == []


# --------------------------------------------- satellite 1: dtype reconcile


def test_uint8_pipeline_static_vs_observed_bytes_exact(tmp_path):
    """The static KP2xx model prices a uint8 source at ONE byte per
    element (a float32-itemsize assumption would read 4x), the fused
    f32 featurize output matches the runtime-observed bytes exactly,
    and the reconcile table carries the propagated dtype column."""
    import json

    from keystone_tpu.analysis.reconcile import (
        format_reconciliation,
        reconcile_trace,
    )
    from keystone_tpu.nodes.images.core import ImageVectorizer, PixelScaler
    from keystone_tpu.telemetry import trace_run

    n, h, w, c = 64, 8, 8, 3
    imgs = np.random.default_rng(0).integers(
        0, 256, size=(n, h, w, c), dtype=np.uint8)
    path = tmp_path / "uint8_trace.json"
    PipelineEnv.reset()
    try:
        with trace_run(str(path)):
            pipe = PixelScaler().to_pipeline() >> ImageVectorizer()
            pipe(Dataset.from_numpy(imgs)).get()
    finally:
        PipelineEnv.reset()
    rec = reconcile_trace(json.load(open(path)))
    rows = {r["label"]: r for r in rec["rows"]}
    src = next(r for label, r in rows.items() if "Dataset" in label)
    assert src["static_bytes"] == n * h * w * c  # 1 byte/elem, not 4
    assert src["dtype"] == "uint8"
    fused = next(r for label, r in rows.items() if "PixelScaler" in label)
    assert fused["dtype"] == "float32"
    assert fused["observed_bytes"] == n * h * w * c * 4
    assert fused["static_bytes"] == fused["observed_bytes"]  # exact
    assert "uint8" in format_reconciliation(rec)


# ------------------------------------------------------------ enforcement


def _enforcement_stages(dim=64):
    return [RandomSignNode(dim), SignedHellingerMapper(), NormalizeRows(),
            LinearRectifier(0.0)]


def test_casts_present_in_fused_jaxpr_output_dtype_restored():
    """A tagged fused program carries the chosen convert_element_type
    casts in its jaxpr, the bare program does not, their cache keys
    differ, the visible output dtype is unchanged, and the bf16 values
    sit inside the declared band."""
    ft = FusedBatchTransformer(_enforcement_stages())
    ft.planned_precision = (None, "bfloat16", "bfloat16", "float32")
    statics, flat, treedef, fns = ft._decompose()
    mesh = meshlib.current_mesh()
    n = 64
    prog = ft._build_program(mesh, 1, n, treedef, fns)
    ds = Dataset.from_numpy(
        np.random.default_rng(0).normal(size=(n, 64)).astype(np.float32))
    jaxpr = str(jax.make_jaxpr(prog)(flat, ds.array, ds.mask))
    assert "convert_element_type" in jaxpr and "bf16" in jaxpr
    out = np.asarray(prog(flat, ds.array, ds.mask))
    assert out.dtype == np.float32  # the program's output dtype never changes

    bare = FusedBatchTransformer(_enforcement_stages())
    bare_prog = bare._build_program(mesh, 1, n, treedef, fns)
    assert "bf16" not in str(jax.make_jaxpr(bare_prog)(flat, ds.array,
                                                       ds.mask))
    ref = np.asarray(bare_prog(flat, ds.array, ds.mask))
    np.testing.assert_allclose(out, ref, rtol=DEFAULT_BAND_RTOL,
                               atol=DEFAULT_BAND_ATOL)
    key_tagged = ft._program_key(statics, flat, treedef, (n, 64),
                                 "float32", n, 1, mesh)
    key_bare = bare._program_key(statics, flat, treedef, (n, 64),
                                 "float32", n, 1, mesh)
    assert key_tagged != key_bare  # planned/unplanned never collide


def test_megafused_jaxpr_carries_casts():
    """materialize() propagates the precision tag from the plan operator
    to the runnable megafused transformer, and the scan-bodied program's
    jaxpr contains the chosen bf16 casts."""
    plan_op = MegafusedPlanOperator(_enforcement_stages())
    plan_op.planned_precision = (None, "bfloat16", "bfloat16", "float32")
    plan_op.planned_matmul_precision = "bfloat16"
    mat = plan_op.materialize([])
    assert mat.planned_precision == plan_op.planned_precision
    assert mat.planned_matmul_precision == "bfloat16"

    statics, flat, treedef, fns = mat._decompose()
    mesh = meshlib.current_mesh()
    n = 64
    prog = mat._build_program(mesh, 1, n, treedef, fns)
    ds = Dataset.from_numpy(
        np.random.default_rng(1).normal(size=(n, 64)).astype(np.float32))
    jaxpr = str(jax.make_jaxpr(prog)(flat, ds.array, ds.mask))
    assert "convert_element_type" in jaxpr and "bf16" in jaxpr
    out = np.asarray(prog(flat, ds.array, ds.mask))
    assert out.dtype == np.float32


def _predictor(classes=4, dim=64):
    featurizer = (RandomSignNode(dim).to_pipeline()
                  >> SignedHellingerMapper() >> NormalizeRows()
                  >> LinearRectifier(0.0) >> Cacher("feat"))

    def build(data, labels_ds):
        labels = ClassLabelIndicatorsFromInt(classes)(labels_ds)
        return featurizer.and_then(
            BlockLeastSquaresEstimator(32, num_iter=1, lam=1e-3),
            data, labels) >> MaxClassifier()

    return build


def _data(n, dim=64, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, dim).astype(np.float32),
            rng.randint(0, classes, size=n).astype(np.int32))


def _run_predictor(n, optimizer=None, **overrides):
    X, y = _data(n)
    PipelineEnv.reset()
    try:
        if optimizer is not None:
            PipelineEnv.get().set_optimizer(optimizer)
        with config_override(**overrides):
            data = Dataset.from_numpy(X)
            labels = Dataset.from_numpy(y)
            applied = _predictor()(data, labels)(data)
            out = np.asarray(applied.get().numpy())
            graph = applied.executor.optimized_graph
        return out, graph
    finally:
        PipelineEnv.reset()


def _tagged_ops(graph):
    return [graph.get_operator(v) for v in graph.operators
            if getattr(graph.get_operator(v), "planned_precision", None)
            is not None]


def test_kill_switch_reproduces_pr9_plan_bit_for_bit():
    """KEYSTONE_PRECISION_PLANNER=0 (config channel) and
    DefaultOptimizer(precision_planner=False) (constructor channel)
    agree exactly: same vertices, same operator classes, same
    dependencies, and no planned_precision tag anywhere — while the
    planner-on run DOES tag (so parity is not vacuous)."""
    _, g_off = _run_predictor(64, precision_planner=False)
    _, g_ctor = _run_predictor(
        64, DefaultOptimizer(precision_planner=False),
        precision_planner=True)
    _, g_on = _run_predictor(64, precision_planner=True,
                             precision_min_savings_bytes=0)

    def shape(g):
        return [
            (vid.id, type(g.get_operator(vid)).__name__,
             tuple(d.id if hasattr(d, "id") else d
                   for d in g.get_dependencies(vid)),
             getattr(g.get_operator(vid), "planned_precision", None))
            for vid in sorted(g.operators, key=lambda v: v.id)
        ]

    off, ctor, on = shape(g_off), shape(g_ctor), shape(g_on)
    assert off == ctor
    assert all(t[3] is None for t in off)
    assert any(t[3] is not None for t in on), \
        "planner-on run enforced nothing; parity check is vacuous"
    # topology identical either way — the policy rides on tagged copies
    assert [t[:3] for t in on] == [t[:3] for t in off]


@pytest.mark.parametrize("n", [64, 43])
def test_policy_on_outputs_in_band_at_multiple_and_ragged_counts(n):
    """Planner-on predictions match the serial unfused f32 reference
    within the declared band at a shard-multiple AND a ragged count,
    with enforcement asserted present (not a vacuous no-op run)."""
    planned, g_on = _run_predictor(n, precision_planner=True,
                                   precision_min_savings_bytes=0)
    serial, _ = _run_predictor(
        n, DefaultOptimizer(fuse=False, sharding_planner=False,
                            precision_planner=False),
        precision_planner=False)
    assert _tagged_ops(g_on), "no policy enforced at count %d" % n
    # argmax outputs: the band degenerates to (near-)equality
    assert planned.shape == serial.shape
    assert np.mean(planned == serial) >= 0.95


def test_intolerant_solver_boundary_stays_f32():
    """In the enforced storage trail, boundaries adjacent to an exact
    stage (the solver's fit slot, the argmax) are never reduced, and
    the final entry restores the PR-9 output dtype."""
    _, g_on = _run_predictor(64, precision_planner=True,
                             precision_min_savings_bytes=0)
    tagged = _tagged_ops(g_on)
    assert tagged
    from keystone_tpu.analysis.precision import stage_tolerance
    from keystone_tpu.nodes.util.fusion import fused_stages

    for op in tagged:
        stage_specs = getattr(op, "stage_specs", None)
        stages = fused_stages(stage_specs if stage_specs is not None
                              else list(op.stages))
        storage = op.planned_precision
        assert len(storage) == len(stages)
        vid = next(v for v in g_on.operators if g_on.get_operator(v) is op)
        tols = [stage_tolerance(s, g_on, vid) for s in stages]
        for i, st in enumerate(storage[:-1]):
            if st == "bfloat16":
                assert tols[i] == TOLERANT and tols[i + 1] == TOLERANT, (
                    f"bf16 boundary {i} adjacent to an intolerant stage")
                # every kept bf16 run must END in an explicit up-cast:
                # the fused bodies are dtype-following, so a None exit
                # would let bf16 flow into the exact stages downstream
                assert storage[i + 1] is not None, (
                    f"bf16 run through boundary {i} has no restore cast "
                    "at its exit")
        assert storage[-1] in (None, "float32")  # output dtype restored
        assert any(st == "bfloat16" for st in storage[:-1])


def test_warm_run_zero_cold_compiles_under_policy():
    """A rebuilt-from-scratch run under the enforced policy against a
    warm persistent cache performs 0 cold compiles — the planned
    program is cache-keyed and AOT-warmable like any other."""
    from keystone_tpu.compile_bench import measure_example_compiles

    rep = measure_example_compiles("RandomPatchCifar", plan="precision")
    assert rep["plan"] == "precision"
    assert rep["warm_programs_compiled"] == 0, rep
    assert rep["outputs_match_cold"]


def test_dispatch_bench_precision_plan_in_band():
    """The bench surface: the `precision` plan keeps the megafused
    1-program apply shape, its outputs sit inside the declared band
    (the `precision_in_band` verdict), and the
    per-plan breakdown row carries the precision column."""
    from keystone_tpu.dispatch_bench import PLANS, dispatch_count_report

    rep = dispatch_count_report(examples=("RandomPatchCifar",))
    assert "precision" in rep["plans"]
    e = rep["examples"]["RandomPatchCifar"]
    assert e["apply_run_programs"]["precision"] == \
        e["apply_run_programs"]["megafused"] == 1
    assert e["precision_in_band"] and rep["precision_in_band"]
    (row,) = rep["plan_breakdown"]
    assert all(p in row for p in PLANS)
