"""The optimize layer in parts, and what the planning decided (PR 38):
one tiny fit on the CPU's eight virtual devices moves each of the six
``host.optimize.<part>.seconds``, which sum to the layer; the solver's
candidates are counted once a solve; the plan's digest is the `plan` arg
of the `optimize` span, equal for two fits of one pipeline and different
under ``KEYSTONE_UNIFIED_PLANNER=0`` (its config field) or another
microbatch, and `planner.plan_changes` counts the differences. Counts
only: nothing here is a time of the chip."""

import numpy as np
import pytest

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
from keystone_tpu.nodes.util import ClassLabelIndicatorsFromInt, MaxClassifier
from keystone_tpu.telemetry import metrics_delta, trace_run
from keystone_tpu.workflow import PipelineEnv
from keystone_tpu.workflow import optimizer as optimizer_mod
from keystone_tpu.workflow.env import config_override
from keystone_tpu.workflow.optimizer import DefaultOptimizer, plan_digest

PARTS = ("rules", "specs", "price", "solve", "enforce", "sequential")
# the floor dropped, so that the joint plan of a tiny pipeline is enforced
ENFORCING = {"unified_min_savings_seconds": 0.0}


@pytest.fixture(autouse=True)
def no_plan_remembered():
    optimizer_mod._LAST_PLAN.clear()
    yield
    optimizer_mod._LAST_PLAN.clear()


def fit(optimizer=None, n=256, dim=64, classes=4, stages=1, **config):
    """One tiny fit through the default optimizer: what moved of the
    optimize layer's and the planners' counters, the `plan` args of its
    `optimize` spans, and the digest of its optimized graph."""
    rng = np.random.RandomState(0)
    X = rng.randn(n, dim).astype(np.float32)
    y = rng.randint(0, classes, size=n).astype(np.int32)
    PipelineEnv.reset()
    if optimizer is not None:
        PipelineEnv.get().set_optimizer(optimizer)
    with config_override(**config), metrics_delta() as delta, \
            trace_run() as tracer:
        data, labels = Dataset.from_numpy(X), Dataset.from_numpy(y)
        featurizer = RandomSignNode(dim).to_pipeline() >> PaddedFFT()
        for _ in range(stages):
            featurizer = featurizer >> LinearRectifier(0.0)
        applied = (featurizer.and_then(
            BlockLeastSquaresEstimator(32, num_iter=1, lam=1e-3), data,
            ClassLabelIndicatorsFromInt(classes)(labels))
            >> MaxClassifier())(data)
        applied.get()
        moved = {k: v for k, v in delta.counters().items()
                 if k.startswith(("host.optimize", "planner."))}
    plans = [s.args.get("plan") for s in tracer.spans if s.name == "optimize"]
    return moved, plans, plan_digest(applied.executor.optimized_graph)


@pytest.fixture(scope="module")
def enforced_fit():
    optimizer_mod._LAST_PLAN.clear()
    return fit(**ENFORCING)


@pytest.mark.parametrize("part", PARTS)
def test_a_tiny_fit_moves_the_part(enforced_fit, part):
    moved, _, _ = enforced_fit
    assert moved[f"host.optimize.{part}.seconds"] > 0.0
    assert moved[f"host.optimize.{part}.spans"] >= 1


def test_the_six_parts_sum_to_the_layer(enforced_fit):
    moved, _, _ = enforced_fit
    named = {k for k in moved if k.startswith("host.optimize.")
             and k.endswith(".seconds") and k != "host.optimize.seconds"}
    assert named == {f"host.optimize.{part}.seconds" for part in PARTS}
    assert sum(moved[k] for k in named) == pytest.approx(
        moved["host.optimize.seconds"], rel=1e-9)
    assert sum(moved[f"host.optimize.{part}.spans"] for part in PARTS) \
        == moved["host.optimize.spans"]


def test_every_planner_traces_the_stages_once(enforced_fit):
    moved, _, _ = enforced_fit
    # the unified planner's pass, and on a mesh the two sequential rules'
    assert moved["host.optimize.specs.spans"] == 3
    assert moved["host.optimize.sequential.spans"] == 2
    assert moved["host.optimize.price.spans"] == 1
    assert moved["host.optimize.solve.spans"] == 1
    assert moved["host.optimize.enforce.spans"] == 1
    assert moved["planner.unified_plans_enforced"] == 1


def test_the_candidates_are_counted_once_a_solve(enforced_fit):
    moved, _, _ = enforced_fit
    scored = moved["planner.candidates_scored"]
    # the sequential point, the chain DP's seed and the descent's trials
    assert scored >= 3 and scored == int(scored)
    again, _, _ = fit(**ENFORCING)
    assert again["planner.candidates_scored"] == scored


def test_the_digest_is_the_plan_arg_of_the_optimize_span(enforced_fit):
    _, plans, digest = enforced_fit
    assert plans[-1] == digest
    assert len(digest) == 16 and int(digest, 16) >= 0


def test_the_second_of_two_fits_changes_no_plan():
    first, _, digest = fit(**ENFORCING)
    second, _, again = fit(**ENFORCING)
    # a first plan has nothing to differ from
    assert first.get("planner.plan_changes", 0) == 0
    assert second.get("planner.plan_changes", 0) == 0
    assert again == digest


def test_the_kill_switch_reads_a_different_digest_and_is_counted():
    _, _, enforced = fit(**ENFORCING)
    moved, _, sequential = fit(unified_planner=False, **ENFORCING)
    assert sequential != enforced
    assert moved["planner.plan_changes"] == 1
    assert "host.optimize.price.seconds" not in moved
    # and back again is a change too
    moved, _, back = fit(**ENFORCING)
    assert back == enforced and moved["planner.plan_changes"] == 1


def test_another_microbatch_reads_a_different_digest():
    _, _, default = fit()
    moved, _, narrow = fit(DefaultOptimizer(fusion_microbatch=64))
    assert narrow != default
    assert moved["planner.plan_changes"] == 1


def test_a_graph_of_other_labels_is_compared_with_its_own_last_plan():
    _, _, one = fit(**ENFORCING)
    other, _, two = fit(stages=2, **ENFORCING)  # its own first plan
    assert two != one and other.get("planner.plan_changes", 0) == 0
    same, _, _ = fit(**ENFORCING)
    assert same.get("planner.plan_changes", 0) == 0


def test_the_digest_holds_no_address():
    _, _, one = fit(**ENFORCING)
    PipelineEnv.reset()
    junk = [object() for _ in range(1000)]  # move the allocator on
    _, _, two = fit(**ENFORCING)
    del junk
    assert one == two
