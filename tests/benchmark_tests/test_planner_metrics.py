"""The per-layer metrics that read the planner's parts, what the planners
enforced, the executor's `prepare` part and the collector (PR 38): each
is a manifest entry with a JSON file over the accepted reader
`counter_delta`, and the tiny rehearsed cells report each; the six
`plan_*_host_s_per_fit` of tiny `cifar_fit` sum to its
`optimize_host_s_per_fit`. Shares and counts only: none of these is a
time of the chip."""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, run  # noqa: E402

SEED = 2**31 + 38  # the driver's seeds are larger than 32 signed bits hold
PLANNER = "planner, optimizer (workflow/optimizer.py)"
EXECUTOR = "executor, dispatch (workflow/executor.py)"
RUNTIME = "host runtime (the interpreter's collector)"
# `cifar_kernel_fit` is not listed: the accepted
# `test_cifar_kernel_cell.py::test_the_new_metrics_are_the_cell_s_alone`
# holds that cell's per-layer metrics to an exact set (PERF.md 7)
CELLS = ["cifar_fit", "timit_fit", "timit_fit_4chip"]
# name: (counter, unit, better, source, layer, cells, phase)
NEW = {
    "plan_rules_host_s_per_fit": (
        "host.optimize.rules.seconds", "s", "lower", "program_span",
        PLANNER, CELLS, "fit"),
    "plan_specs_host_s_per_fit": (
        "host.optimize.specs.seconds", "s", "lower", "program_span",
        PLANNER, CELLS, "fit"),
    "plan_spec_passes_per_fit": (
        "host.optimize.specs.spans", "passes", "lower", "program_counter",
        PLANNER, CELLS, "fit"),
    "plan_price_host_s_per_fit": (
        "host.optimize.price.seconds", "s", "lower", "program_span",
        PLANNER, CELLS, "fit"),
    "plan_solve_host_s_per_fit": (
        "host.optimize.solve.seconds", "s", "lower", "program_span",
        PLANNER, CELLS, "fit"),
    "plan_enforce_host_s_per_fit": (
        "host.optimize.enforce.seconds", "s", "lower", "program_span",
        PLANNER, CELLS, "fit"),
    "plan_sequential_host_s_per_fit": (
        "host.optimize.sequential.seconds", "s", "lower", "program_span",
        PLANNER, CELLS, "fit"),
    "plan_candidates_scored_per_fit": (
        "planner.candidates_scored", "candidates", "lower",
        "program_counter", PLANNER, CELLS, "fit"),
    "plan_changes_per_fit": (
        "planner.plan_changes", "plans", "lower", "program_counter",
        PLANNER, CELLS, "fit"),
    "unified_plans_enforced_per_fit": (
        "planner.unified_plans_enforced", "plans", "higher",
        "program_counter", PLANNER, CELLS, "fit"),
    "plan_seconds_saved_per_fit": (
        "planner.unified_seconds_saved", "s", "higher", "program_counter",
        PLANNER, CELLS, "fit"),
    "sharding_plans_enforced_per_fit": (
        "planner.plans_enforced", "plans", "higher", "program_counter",
        PLANNER, ["timit_fit_4chip"], "fit"),
    "precision_policies_enforced_per_fit": (
        "planner.precision_policies_enforced", "policies", "higher",
        "program_counter", PLANNER, CELLS, "fit"),
    "executor_prepare_host_s_per_fit": (
        "host.force.prepare.seconds", "s", "lower", "program_span",
        EXECUTOR, CELLS, "fit"),
    "optimize_host_s_per_apply": (
        "host.optimize.seconds", "s", "lower", "program_span", PLANNER,
        ["cifar_fit"], "apply"),
    "executor_host_s_per_apply": (
        "host.force.seconds", "s", "lower", "program_span", EXECUTOR,
        ["cifar_fit"], "apply"),
    "gc_host_s_per_fit": (
        "host.gc.seconds", "s", "lower", "program_span", RUNTIME, CELLS,
        "fit"),
    "gc_full_collections_per_fit": (
        "host.gc.full_collections", "collections", "lower",
        "program_counter", RUNTIME, CELLS, "fit"),
}
PARTS = ("plan_rules_host_s_per_fit", "plan_specs_host_s_per_fit",
         "plan_price_host_s_per_fit", "plan_solve_host_s_per_fit",
         "plan_enforce_host_s_per_fit", "plan_sequential_host_s_per_fit")
TINY = {
    "cifar_fit": ("random_patch_cifar", 1, {
        "num_filters": 16, "block_size": 64, "num_train": 256,
        "num_test": 64, "sample_patches": 10000, "feature_dim": 128,
        "lam": 10.0,
        "assumed": {"noise": 1.2, "confusion": 0.6, "microbatch": 32},
        "accuracy_band": [0.5, 1.0], "reference_agreement": 0.9}),
    "timit_fit": ("timit_cosine", 1, {
        "input_dim": 32, "num_cosines": 3, "num_cosine_features": 64,
        "feature_dim": 192, "block_size": 64, "num_classes": 12,
        "num_train": 2048, "num_test": 512, "gamma": 0.2,
        "assumed": {"signal": 0.6}, "default_matmul_operands": "float32",
        "accuracy_band": [0.5, 1.0], "reference_agreement": 0.97}),
    "timit_fit_4chip": ("timit_cosine_mesh4", 4, {
        "input_dim": 32, "num_cosines": 4, "num_cosine_features": 64,
        "feature_dim": 256, "block_size": 64, "num_classes": 12,
        "num_train": 2048, "num_test": 512, "gamma": 0.2,
        "assumed": {"signal": 0.6}, "default_matmul_operands": "float32",
        "accuracy_band": [0.5, 1.0], "reference_agreement": 0.97}),
}


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


@pytest.fixture(scope="module")
def readings(bench):
    """{cell: (record, {metric: value})}: each tiny cell run once,
    untraced (every new metric reads a counter), and its new metrics read
    as `run.layer_metrics` reads them."""
    out = {}
    for cell, (config, chips, tiny) in TINY.items():
        sizes = {**bench.sizes(config), **tiny}
        record = run.measure(bench, cell, SEED, 0.01, 0,
                             jax.devices()[:chips], sizes=sizes,
                             log=lambda r: None)
        context = {"counters": record["counters"], "stats": record["stats"],
                   "trace": None, "peaks": None}
        values = {}
        for metric in bench.metrics("per_layer", cell):
            spec = bench.reader_spec(metric["name"])
            if spec["reader"] == "counter_delta":
                values[metric["name"]] = files.module(
                    "readers", "counter_delta").read(context, **spec["args"])
        out[cell] = (record, values)
    return out


def test_the_new_entries_stand_behind_the_accepted_ones(bench):
    """Appended behind PR 37's, in the table's order (a later PR appends
    behind them in turn)."""
    names = [m["name"] for m in bench.manifest["per_layer"]]
    first = names.index("residual_addbacks_skipped_per_fit") + 1
    assert names[first:first + len(NEW)] == list(NEW)


@pytest.mark.parametrize("name", NEW)
def test_the_manifest_entry_and_its_file(bench, name):
    counter, unit, better, source, layer, cells, phase = NEW[name]
    (entry,) = [m for m in bench.manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": phase + "_throughput", "workloads": cells}
    per = "applies" if phase == "apply" else "fits"
    assert bench.reader_spec(name) == {
        "reader": "counter_delta",
        "args": {"counter": counter, "phase": phase, "per": per}}


def test_the_layers_are_the_manifest_s_own(bench):
    accepted = {m["layer"] for m in bench.manifest["per_layer"]
                if m["name"] not in NEW}
    assert PLANNER in accepted and EXECUTOR in accepted
    assert RUNTIME not in accepted  # the one new layer string


@pytest.mark.parametrize("cell,name", [
    (cell, name) for name, spec in NEW.items() for cell in spec[5]])
def test_the_tiny_cell_reports_the_metric(readings, cell, name):
    record, values = readings[cell]
    assert record["correct"]
    assert values[name] is not None and values[name] >= 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_what_a_tiny_fit_always_moves_is_above_zero(readings, cell):
    _, values = readings[cell]
    for name in ("plan_rules_host_s_per_fit", "plan_specs_host_s_per_fit",
                 "plan_price_host_s_per_fit", "plan_solve_host_s_per_fit",
                 "executor_prepare_host_s_per_fit"):
        assert values[name] > 0.0, name
    assert values["plan_candidates_scored_per_fit"] >= 2
    assert values["plan_spec_passes_per_fit"] >= 1
    # warm fits of one pipeline on one data: the plan never changes
    assert values["plan_changes_per_fit"] == 0.0


def test_a_mesh_adds_the_sharding_planner_s_passes(readings):
    (one_chip, one), (mesh, four) = (readings["timit_fit"],
                                     readings["timit_fit_4chip"])
    spans = [r["counters"]["fit"]["host.optimize.sequential.spans"]
             / r["stats"]["fits"] for r in (one_chip, mesh)]
    # one chip runs the precision planner alone of the sequential two
    assert spans[1] > spans[0] >= 1
    assert four["plan_spec_passes_per_fit"] > one["plan_spec_passes_per_fit"]
    assert four["plan_sequential_host_s_per_fit"] > 0.0


def test_the_parts_of_tiny_cifar_fit_sum_to_its_optimize_layer(readings):
    _, values = readings["cifar_fit"]
    whole = values["optimize_host_s_per_fit"]
    assert whole > 0.0
    assert sum(values[name] for name in PARTS) == pytest.approx(
        whole, rel=0.01)


def test_an_apply_of_tiny_cifar_fit_plans_nothing_and_prepares(readings):
    _, values = readings["cifar_fit"]
    # the fitted pipeline was planned by `fit()`, between the two phases
    assert values["optimize_host_s_per_apply"] == 0.0
    assert values["executor_host_s_per_apply"] > 0.0
