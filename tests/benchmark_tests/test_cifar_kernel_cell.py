"""The `cifar_kernel_fit` cell rehearsed without the chip: the
configuration file is of the source's widths, mode `fit` runs
`random_patch_cifar_kernel` tiny on the CPU through the normal path and
tells a right model from one fitted to shuffled labels, the program's
alpha and test scores are the plain reference's, the counters read what
the cached solver does, and the cost functions give the numbers worked
out by hand. Nothing here is a time or a rate."""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, run  # noqa: E402

SEED = 2**31 + 36  # the driver's seeds are larger than 32 signed bits hold
# 8 filters (64 features), six blocks of 32 rows, the source's three
# epochs; gamma and lam raised for 192 rows of 64 features
TINY = {
    "num_filters": 8, "feature_dim": 64, "sample_patches": 2000,
    "kernel_block": 32, "num_train": 192, "num_test": 64,
    "gamma": 2e-3, "lam": 0.1,
    "default_matmul_operands": "float32",  # the CPU's default rounds nothing
    "accuracy_band": [0.5, 1.0], "reference_agreement": 0.97}
CELL, CONFIG = "cifar_kernel_fit", "random_patch_cifar_kernel"
NEW_METRICS = ("krr_ms_per_fit", "krr_roofline", "kernel_apply_ms_per_fit",
               "kernel_apply_roofline", "kernel_blocks_reused_per_fit")


def quiet(record):
    pass


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


@pytest.fixture(scope="module")
def sizes(bench):
    return {**bench.sizes(CONFIG), **TINY}


@pytest.fixture(scope="module")
def kernel_fit(bench, sizes):
    return run.measure(bench, CELL, SEED, 0.01, 1, jax.devices()[:1],
                       sizes=sizes, log=quiet)


def test_the_cell_is_of_the_source_s_widths(bench):
    full = bench.sizes(CONFIG)
    assert (full["num_filters"], full["feature_dim"], full["gamma"],
            full["kernel_block"], full["cache_kernel"]) == (
                100, 800, 2e-4, 5000, True)
    assert (full["num_train"], full["num_test"], full["num_classes"]) == (
        50000, 10000, 10)
    featurizer = ("image_height", "image_width", "image_channels",
                  "patch_size", "patch_steps", "pool_size", "pool_stride",
                  "alpha", "whitening_epsilon", "sample_patches")
    linear = bench.sizes("random_patch_cifar")
    assert [full[k] for k in featurizer] == [linear[k] for k in featurizer]
    assert full["num_epochs"] == 3
    assert {"num_epochs", "lam", "data", "source"} <= set(full["assumed"])
    assert "microbatch" not in full["assumed"]  # the optimizer's default
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "warm_fit_apply", 1)
    assert bench._named("configs", CONFIG)["reduced"] == []
    reported = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert reported == {"fit_throughput", "setup_s"}


def test_the_new_metrics_are_the_cell_s_alone(bench):
    for name in NEW_METRICS:
        entry = bench._named("per_layer", name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "fit_throughput"
    shared = {m["name"] for m in bench.metrics("per_layer", CELL)}
    assert shared == set(NEW_METRICS) | {
        "device_idle.fit", "featurize_ms_per_fit", "fused_conv_ms_per_fit",
        "conv_rectify_pool_roofline", "programs_per_fit",
        "solver_steps_per_fit"}


def test_cifar_kernel_fit_tiny_is_correct_and_counts_its_blocks(
        bench, sizes, kernel_fit):
    assert kernel_fit["correct"] and kernel_fit["failed"] == 0
    fits = kernel_fit["stats"]["fits"]
    assert kernel_fit["attempted"] == fits >= 1
    metrics = run.layer_metrics(
        bench, CELL, kernel_fit, {"flops": 1.0, "bytes_per_s": 1.0},
        log=quiet)
    blocks = sizes["num_train"] // sizes["kernel_block"]
    epochs = sizes["num_epochs"]
    assert metrics["solver_steps_per_fit"]["value"] == epochs * blocks == 18
    assert metrics["kernel_blocks_reused_per_fit"]["value"] == (
        (epochs - 1) * blocks)
    counters = kernel_fit["counters"]["fit"]
    assert counters["solver.kernel_blocks_formed"] == fits * blocks
    assert counters["solver.kernel_cache_bytes"] == (
        fits * 4 * sizes["num_train"] ** 2)
    # the filters, their fold, the indicators, the featurizer, the scaler's
    # two, the mask, the solver's zeros and 18 steps, the scaler and the
    # kernel apply over the training features, the evaluator's three
    assert metrics["programs_per_fit"]["value"] == 18 + 12.0
    # the apply makes no block and leaves the fit's counters alone
    assert "solver.steps" not in kernel_fit["counters"]["apply"]
    # off the chip no device reader finds anything to read
    device_metrics = {m["name"] for m in bench.metrics("per_layer", CELL)
                      if m["source"] == "device_trace"}
    assert {"krr_roofline", "kernel_apply_roofline"} <= device_metrics
    assert not device_metrics & set(metrics)


def test_a_model_fitted_to_shuffled_labels_is_called_incorrect(bench, sizes):
    from benchmark.modes import fit
    from keystone_tpu.parallel.mesh import make_mesh

    adapter = files.module("configs", CONFIG)

    class ShuffledLabels:
        """The same pipeline fitted to labels that say nothing."""
        make_data = staticmethod(adapter.make_data)

        @staticmethod
        def build(train, sizes, seed):
            from keystone_tpu.data.dataset import Dataset
            from keystone_tpu.loaders.csv_loader import LabeledData

            labels = np.random.default_rng(0).permutation(
                np.asarray(train.labels.numpy()))
            return adapter.build(
                LabeledData(labels=Dataset(labels, mesh=train.data.mesh),
                            data=train.data), sizes, seed)

    record = fit.run(
        ShuffledLabels, files.module("reference", CONFIG), sizes,
        bench.traffic("warm_fit_apply"), SEED, 0.1,
        make_mesh(jax.devices()[:1]), log=quiet)
    assert not record["correct"]
    assert record["failed"] == record["attempted"] >= 1


def test_the_program_s_alpha_and_scores_are_the_reference_s(sizes):
    """Float32 both, the same filters: the fitted model's alpha and the
    class scores of the test set (not only their argmax) agree with the
    plain reference. The tolerance is float32 rounding: the features
    come out of two differently ordered float32 featurizers (1e-6
    relative), and three epochs of 32-wide Cholesky solves at lam 0.1
    carry that into alphas of order 1 as differences of up to 4e-5
    (seen); 2e-4 absolute and relative leaves five times that."""
    from keystone_tpu.nodes.learning.kernels import KernelBlockLinearMapper
    from keystone_tpu.parallel.mesh import make_mesh, use_mesh
    from keystone_tpu.workflow import PipelineEnv

    adapter = files.module("configs", CONFIG)
    reference = files.module("reference", CONFIG)
    mesh = make_mesh(jax.devices()[:1])
    with use_mesh(mesh):
        PipelineEnv.reset()
        train, test = adapter.make_data(sizes, SEED, mesh)
        fitted = adapter.build(train, sizes, SEED).fit()
        stages = list(fitted.graph.operators.values())
        (model,) = [s for s in stages if isinstance(s, KernelBlockLinearMapper)]
        out = test.data
        for stage in stages[:stages.index(model) + 1]:
            out = stage.apply_batch(out)
        with jax.default_matmul_precision("highest"):
            *_, alpha = reference.fit(train, sizes, SEED)
        want = reference.scores(train, test, sizes, SEED)
    np.testing.assert_allclose(np.asarray(model.alpha), np.asarray(alpha),
                               rtol=2e-4, atol=2e-4)
    scores = np.asarray(out.numpy())
    assert scores.shape == want.shape == (sizes["num_test"], 10)
    np.testing.assert_allclose(scores, want, rtol=2e-4, atol=2e-4)


def test_the_reference_visits_the_program_s_blocks_in_the_program_s_order():
    """The reference writes its own shuffle; it is the program's."""
    from keystone_tpu.nodes.learning.kernels import block_order

    for seed, epoch in [(0, 0), (5, 2), (2**31 - 2, 1)]:
        np.testing.assert_array_equal(
            block_order(seed, epoch, 10),
            np.random.default_rng(seed + epoch).permutation(10))


@pytest.mark.parametrize("cache_kernel,formed,cache_bytes", [
    (True, 4, 4 * 3 * 100 * 100), (False, 12, 0)], ids=["cached", "uncached"])
def test_the_solver_s_cost_counts_what_the_shapes_say(
        cache_kernel, formed, cache_bytes):
    cost = files.module("costs", "krr").cost(
        {"num_train": 100, "feature_dim": 7, "num_classes": 2,
         "kernel_block": 25, "num_epochs": 3, "cache_kernel": cache_kernel})
    # a block formed: 2 x 100 x 25 x 7 = 35,000; a step: 25^3 / 3 +
    # 2 x 25^2 x 2 + 2 x 100 x 25 x 2 = 5,208.33 + 2,500 + 10,000
    assert cost["flops"] == pytest.approx(
        formed * 35000 + 12 * (25**3 / 3 + 2500 + 10000))
    # a block formed reads X and its own rows (700 + 175 floats); K alpha
    # read and written a step (400 floats)
    assert cost["bytes"] == 4 * formed * 875 + cache_bytes + 4 * 12 * 400


def test_the_kernel_apply_s_cost_counts_what_the_shapes_say():
    cost = files.module("costs", "kernel_apply").cost(
        {"num_train": 100, "feature_dim": 7, "num_classes": 2})
    assert cost["flops"] == 2 * 100 * 100 * 7 + 2 * 100 * 100 * 2 == 180000
    # rows 700, anchors 700, alpha 200 read, scores 200 written, 4 B each
    assert cost["bytes"] == 4 * (700 + 700 + 200 + 200) == 7200
