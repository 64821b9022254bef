"""The `timit_fit` cell rehearsed without the chip: mode `fit` runs the
`timit_cosine` configuration tiny on the CPU through the normal path,
tells a right model from one fitted to shuffled labels, counts five
solver steps a fit and the bytes the gather stage writes, the cost
function gives the numbers worked out by hand, and the frames are a
function of the seed. Nothing here is a time or a rate."""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, run, timit_frames  # noqa: E402

SEED = 2**31 + 28  # the driver's seeds are larger than 32 signed bits hold
# three branches of 64 over 32-dimensional frames, a solver block each;
# the signal is raised so that 2,048 rows are enough to learn 12 classes
TINY_TIMIT = {
    "input_dim": 32, "num_cosines": 3, "num_cosine_features": 64,
    "feature_dim": 192, "block_size": 64, "num_classes": 12,
    "num_train": 2048, "num_test": 512, "gamma": 0.2,
    "assumed": {"signal": 0.6},
    "default_matmul_operands": "float32",  # the CPU's default rounds nothing
    "accuracy_band": [0.5, 1.0], "reference_agreement": 0.97}


def quiet(record):
    pass


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


@pytest.fixture(scope="module")
def sizes(bench):
    return {**bench.sizes("timit_cosine"), **TINY_TIMIT}


@pytest.fixture(scope="module")
def timit_fit(bench, sizes):
    return run.measure(bench, "timit_fit", SEED, 0.01, 1, jax.devices()[:1],
                       sizes=sizes, log=quiet)


def test_the_cell_is_of_the_source_s_widths(bench):
    full = bench.sizes("timit_cosine")
    assert (full["input_dim"], full["num_cosine_features"],
            full["block_size"], full["num_classes"]) == (440, 4096, 4096, 147)
    assert (full["bcd_iters"], full["lam"], full["gamma"]) == (5, 0.0, 0.05555)
    assert full["feature_dim"] == full["num_cosines"] * 4096
    cell = bench.cell("timit_fit")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "timit_cosine", "warm_fit_apply", 1)
    entry = bench._named("configs", "timit_cosine")
    assert set(entry["reduced"]) == {"num_cosines", "num_train", "num_test"}


def test_timit_fit_tiny_is_correct_and_counts_its_steps(bench, sizes,
                                                        timit_fit):
    assert timit_fit["correct"] and timit_fit["failed"] == 0
    fits = timit_fit["stats"]["fits"]
    assert timit_fit["attempted"] == fits >= 1
    metrics = run.layer_metrics(
        bench, "timit_fit", timit_fit, {"flops": 1.0, "bytes_per_s": 1.0},
        log=quiet)
    assert metrics["solver_steps_per_fit"]["value"] == sizes["bcd_iters"] == 5
    # a draw of W and b for each of the three branches, one program for the
    # gathered branches, one for the model over the cached training
    # features, the solver's seven, the indicators and the evaluator's three
    assert metrics["programs_per_fit"]["value"] == 3 + 13.0
    # off the chip no device reader finds anything to read
    device_metrics = {m["name"] for m in bench.metrics("per_layer", "timit_fit")
                      if m["source"] == "device_trace"}
    assert "cosine_features_roofline" in device_metrics
    assert not device_metrics & set(metrics)
    # the gather stage writes the combined training features once a fit
    # and the test features once an apply
    counters = timit_fit["counters"]
    row = 4 * sizes["feature_dim"]
    assert counters["fit"]["gather.concat_bytes"] == fits * row * sizes["num_train"]
    assert counters["apply"]["gather.concat_bytes"] == fits * row * sizes["num_test"]


def test_a_model_fitted_to_shuffled_labels_is_called_incorrect(bench, sizes):
    from benchmark.modes import fit
    from keystone_tpu.parallel.mesh import make_mesh

    adapter = files.module("configs", "timit_cosine")

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
        ShuffledLabels,
        files.module("reference", "timit_cosine"), sizes,
        bench.traffic("warm_fit_apply"), SEED, 0.1,
        make_mesh(jax.devices()[:1]), log=quiet)
    assert not record["correct"]
    assert record["failed"] == record["attempted"] >= 1


def test_the_cost_function_counts_what_the_shapes_say():
    cost = files.module("costs", "cosine_features").cost(
        {"num_train": 100, "input_dim": 7, "feature_dim": 12})
    assert cost["flops"] == 2 * 100 * 7 * 12 == 16800
    # frames 700, W 84, b 12 read, features 1,200 written, four bytes each
    assert cost["bytes"] == 4 * (700 + 84 + 12 + 1200) == 7984


def test_the_frames_are_a_function_of_the_seed_and_have_full_rank():
    def frames(seed):
        (train, labels), (test, _) = timit_frames.timit_like(
            512, 128, seed, num_classes=12, dim=32, signal=0.4)
        return np.asarray(train), np.asarray(labels), np.asarray(test)

    first, again, other = frames(SEED), frames(SEED), frames(SEED + 1)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(first[0], other[0])
    assert first[0].shape == (512, 32) and first[2].shape == (128, 32)
    assert first[1].min() >= 0 and first[1].max() < 12
    assert not np.array_equal(first[0][:128], first[2])  # test is not train
    X = first[0]
    assert abs(X.var() - 1.0) < 0.1  # about unit variance
    assert np.linalg.matrix_rank(X) == 32
    # every class has mean zero (the random sign), so a linear model has
    # nothing to separate; the class shows in the second moment along mu_c
    (X, y), _ = timit_frames.timit_like(
        8192, 8, SEED, num_classes=12, dim=32, signal=0.4)
    X, y = np.asarray(X), np.asarray(y)
    means = np.stack([X[y == c].mean(axis=0) for c in range(12)])
    assert np.sqrt((means ** 2).mean()) < 0.06  # 1 / sqrt(683 rows a class)
    along = [np.linalg.eigvalsh(np.cov(X[y == c].T))[-1] for c in range(12)]
    assert min(along) > 5.0  # 0.6 + 0.4 |mu_c|^2, and |mu_c|^2 is about 32
