"""The `voc_fit` cell rehearsed without the chip: the configuration's
file is held to the source's defaults key by key, the manifest names the
cell and its metrics, mode `fit_multilabel` runs `voc_sift_fisher` tiny
on the CPU through the normal path, calls it correct and tells a right
model from one fitted to shuffled labels, the counters read what the
program does (three passes of SIFT, no descriptor bytes on the host),
and the cost functions give the numbers worked out by hand. Nothing
here is a time or a rate."""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, run  # noqa: E402

SEED = 2**31 + 40  # the driver's seeds are larger than 32 signed bits hold
CELL, CONFIG = "voc_fit", "voc_sift_fisher"
# images of 48 x 64 (410 descriptors), PCA to 8, 4 centres, 30 rows an
# image in each sample, two solver blocks of 32; little clutter and
# noise, since a texture of 6 to 18 pixels' wavelength has little room
EASY = {"texture": 1.0, "clutter": 0.1, "noise": 0.1}
TINY = {
    "image_height": 48, "image_width": 64, "descriptors_per_image": 410,
    "pca_dims": 8, "gmm_k": 4, "gmm_iters": 8, "feature_dim": 64,
    "solver_block": 32, "block_size": 32, "num_classes": 4,
    "num_train": 96, "num_test": 64, "num_pca_samples": 96 * 30,
    "num_gmm_samples": 96 * 30, "map_band": [0.7, 1.0],
    "default_matmul_operands": "float32",  # the CPU's default rounds nothing
    "pca_angle_limit": 1e-2, "gmm_loglik_gap_limit": 1e-2,
    "scores_rel_error_limit": 1e-2, "top_class_agreement": 0.95}
# VOC 2007's counts: a cut of either is a cut of rows, listed in `reduced`
ROWS = {"num_train": 5011, "num_test": 4952}
# SIFTFisherConfig's defaults, key by key: no width may differ
SOURCE = {
    "descriptor_dim": 128, "sift_step": 3, "sift_bin": 4, "num_scales": 4,
    "scale_step": 0, "pca_dims": 80, "gmm_k": 256, "feature_dim": 40960,
    "solver_block": 4096, "bcd_iters": 1, "lam": 0.5, "num_classes": 20,
    "num_pca_samples": 1000000, "num_gmm_samples": 1000000,
    "image_height": 375,
    "image_width": 500, "image_channels": 3, "descriptors_per_image": 73866}
NEW_METRICS = {
    "gmm_em_ms_per_fit": "fit_throughput",
    "gmm_em_roofline": "fit_throughput",
    "pca_fit_ms_per_fit": "fit_throughput",
    "sift_fisher_roofline": "fit_throughput",
    "sift_fisher_roofline_apply": "apply_throughput",
    "sift_passes_per_fit": "fit_throughput",
    "descriptors_per_fit": "fit_throughput",
    "descriptor_host_bytes_per_fit": "fit_throughput",
    "gmm_em_iterations_per_fit": "fit_throughput"}
SHARED_METRICS = {
    "device_idle.fit", "featurize_ms_per_fit", "solver_ms_per_fit",
    "bcd_roofline", "apply_device_ms", "programs_per_fit",
    "programs_per_apply", "solver_steps_per_fit"}


def quiet(record):
    pass


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


@pytest.fixture(scope="module")
def sizes(bench):
    full = bench.sizes(CONFIG)
    return {**full, **TINY, "assumed": {**full["assumed"], **EASY}}


@pytest.fixture(scope="module")
def voc_fit(bench, sizes):
    window = []
    record = run.measure(bench, CELL, SEED, 0.01, 0, jax.devices()[:1],
                         sizes=sizes, log=window.append)
    return record, window[-1]


@pytest.mark.parametrize("key", sorted(SOURCE))
def test_the_file_holds_the_source_s_default(bench, key):
    assert bench.sizes(CONFIG)[key] == SOURCE[key]


def test_the_file_s_widths_agree_with_each_other_and_with_the_program(bench):
    from keystone_tpu.nodes.images.sift import SIFTExtractor
    from keystone_tpu.pipelines.voc_sift_fisher import VOCSIFTFisherConfig

    full = bench.sizes(CONFIG)
    assert full["feature_dim"] == 2 * full["pca_dims"] * full["gmm_k"]
    assert full["feature_dim"] % full["solver_block"] == 0
    assert full["block_size"] == full["solver_block"]  # `costs/bcd.py`'s name
    assert SIFTExtractor(
        full["sift_step"], full["sift_bin"], full["num_scales"],
        full["scale_step"]).num_descriptors(
            full["image_height"], full["image_width"]) == 73866
    program = files.module("configs", CONFIG).program_config(full, SEED)
    defaults = VOCSIFTFisherConfig()
    for field in ("num_classes", "pca_dims", "gmm_k", "sift_step", "sift_bin",
                  "num_scales", "scale_step", "num_pca_samples",
                  "num_gmm_samples", "lam", "block_size", "bcd_iters"):
        assert getattr(program, field) == getattr(defaults, field), field
    assert {"source", "data", "image_size", "labels", "gmm_iters",
            "gmm_init"} <= set(full["assumed"])
    assert "microbatch" not in full["assumed"]  # no knob is handed over
    lo, hi = full["map_band"]
    assert 0.05 < lo < hi < 1.0


def test_the_manifest_names_the_cell_and_its_metrics(bench):
    config = bench._named("configs", CONFIG)
    assert config["file"] == "benchmark/configs/voc_sift_fisher.json"
    # `reduced` lists exactly the rows the file cuts, and nothing else is cut
    full = bench.sizes(CONFIG)
    cut = {key for key, rows in ROWS.items() if full[key] != rows}
    assert set(config["reduced"]) == cut
    assert all(full[key] <= rows for key, rows in ROWS.items())
    assert full.get("published", ROWS) == ROWS
    assert ("published" in full) == bool(cut)
    for word in ("pipelines.images.voc.VOCSIFTFisher", "SIFTFisherConfig",
                 "PASCAL VOC 2007"):
        assert word in config["source"]
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "warm_fit_apply_multilabel", 1)
    assert bench.traffic(cell["traffic"])["mode"] == "fit_multilabel"
    reported = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert reported == {"fit_throughput", "apply_throughput", "setup_s"}
    names = [m["name"] for m in bench.manifest["per_layer"]]
    assert names[-len(NEW_METRICS):] == list(NEW_METRICS)  # appended
    for name, moves in NEW_METRICS.items():
        entry = bench._named("per_layer", name)
        assert entry["workloads"] == [CELL] and entry["moves"] == moves
        if name.endswith("_roofline") or "_roofline_" in name:
            assert entry["unit"] == "%" and entry["source"] == "device_trace"
    assert {m["name"] for m in bench.metrics("per_layer", CELL)} == (
        set(NEW_METRICS) | SHARED_METRICS)


def test_voc_fit_tiny_is_correct_and_agrees_with_the_reference(voc_fit):
    record, window = voc_fit
    assert record["correct"] and record["failed"] == 0, window["checks"]
    assert record["attempted"] == record["stats"]["fits"] >= 1
    assert all(window["checks"].values())
    # float32 both: the comparisons read rounding, far inside the limits
    assert window["pca_angle"] < 1e-3
    assert window["gmm_loglik_gap"] < 1e-3
    assert window["scores_rel_error"] < 1e-3
    assert window["top_class_agreement"] == 1.0
    assert window["test_map"]["min"] > 0.8 and window["reference_map"] > 0.8


def test_the_counters_read_three_passes_and_no_bytes_on_the_host(
        bench, sizes, voc_fit):
    record, _ = voc_fit
    metrics = run.layer_metrics(
        bench, CELL, record, {"flops": 1.0, "bytes_per_s": 1.0}, log=quiet)
    n, nd = sizes["num_train"], sizes["descriptors_per_image"]
    # at this size every stage's output fits, so the plan keeps the
    # descriptors once; the optimizer's samples of three images are the rest
    passes = metrics["sift_passes_per_fit"]["value"]
    assert 1.0 <= passes <= 1.0 + 6.0 / n
    assert metrics["descriptors_per_fit"]["value"] == passes * n * nd
    assert metrics["descriptor_host_bytes_per_fit"]["value"] == 0.0
    assert metrics["gmm_em_iterations_per_fit"]["value"] == sizes["gmm_iters"]
    assert metrics["solver_steps_per_fit"]["value"] == sizes["bcd_iters"]
    # the featurizer's program, and the model's behind the features' cache
    assert metrics["programs_per_apply"]["value"] == 2.0
    fit = record["counters"]["fit"]
    assert fit["fisher.images"] == record["stats"]["fits"] * n
    assert record["counters"]["apply"]["sift.images"] == (
        record["stats"]["applies"] * sizes["num_test"])
    # off the chip no device reader finds anything to read
    device = {m["name"] for m in bench.metrics("per_layer", CELL)
              if m["source"] == "device_trace"}
    assert {"gmm_em_roofline", "sift_fisher_roofline",
            "sift_fisher_roofline_apply"} <= device
    assert not device & set(metrics)


def test_a_model_fitted_to_shuffled_labels_is_called_incorrect(bench, sizes):
    from benchmark.modes import fit_multilabel
    from keystone_tpu.parallel.mesh import make_mesh

    adapter = files.module("configs", CONFIG)

    class ShuffledLabels:
        """The same pipeline fitted to labels that say nothing."""
        make_data = staticmethod(adapter.make_data)
        fitted_parts = staticmethod(adapter.fitted_parts)

        @staticmethod
        def build(train, sizes, seed):
            from keystone_tpu.data.dataset import Dataset
            from keystone_tpu.loaders.csv_loader import LabeledData

            labels = np.random.default_rng(0).permutation(
                np.asarray(train.labels.numpy()))
            return adapter.build(
                LabeledData(labels=Dataset(labels, mesh=train.data.mesh),
                            data=train.data), sizes, seed)

    window = []
    record = fit_multilabel.run(
        ShuffledLabels, files.module("reference", CONFIG), sizes,
        bench.traffic("warm_fit_apply_multilabel"), SEED, 0.01,
        make_mesh(jax.devices()[:1]), log=window.append)
    assert not record["correct"]
    assert record["failed"] == record["attempted"] >= 1
    assert not window[-1]["checks"]["every_fit_in_band"]


@pytest.mark.parametrize("limit,reading", [
    ("pca_angle_limit", "pca_angle"),
    ("gmm_loglik_gap_limit", "gmm_loglik_gap"),
    ("scores_rel_error_limit", "scores_rel_error")])
def test_each_limit_alone_calls_the_cell_incorrect(bench, sizes, voc_fit,
                                                   limit, reading):
    """A limit under the tiny run's own reading refuses it, by that
    check and by no other."""
    _, window = voc_fit
    checks = _checks(window, {**sizes, limit: window[reading] / 2 - 1e-12})
    wrong = [name for name, ok in checks.items() if not ok]
    assert len(wrong) == 1


def _checks(window, sizes):
    lo, hi = sizes["map_band"]
    return {
        "reference_in_band": lo <= window["reference_map"] <= hi,
        "pca": window["pca_angle"] <= sizes["pca_angle_limit"],
        "gmm": window["gmm_loglik_gap"] <= sizes["gmm_loglik_gap_limit"],
        "scores": window["scores_rel_error"]
        <= sizes["scores_rel_error_limit"],
        "top": window["top_class_agreement"] >= sizes["top_class_agreement"]}


def test_the_costs_count_what_the_shapes_say():
    shapes = {"num_train": 10, "num_test": 4, "num_gmm_samples": 70,
              "descriptors_per_image": 100, "pca_dims": 3, "gmm_k": 5,
              "gmm_iters": 2, "image_height": 20, "image_width": 30,
              "descriptor_dim": 128, "num_scales": 1, "sift_bin": 4}
    em = files.module("costs", "gmm_em").cost(shapes)
    rows = 10 * 7
    assert em == {"flops": 2 * 8 * rows * 3 * 5, "bytes": 2 * 4 * rows * 3}
    one = files.module("costs", "sift_fisher").image_cost(shapes)
    # one scale, bin 4: a Gaussian of 2 * ceil(4 * 4 / 6) + 1 = 7 taps on
    # one map, a triangle of 7 taps on eight, two directions, two
    # operations a tap a pixel
    stencil = 2 * 2 * 20 * 30 * (7 + 8 * 7)
    assert one["flops"] == stencil + 2 * 100 * 128 * 3 + 8 * 100 * 3 * 5
    assert one["bytes"] == 4 * (20 * 30 + 2 * 100 * 128 + 2 * 100 * 3
                                + 2 * 3 * 5)
    fit = files.module("costs", "sift_fisher").cost(shapes)
    apply = files.module("costs", "sift_fisher_apply").cost(shapes)
    assert fit == {k: 10 * v for k, v in one.items()}
    assert apply == {k: 4 * v for k, v in one.items()}


def test_the_images_are_seeded_and_hold_one_to_three_labels():
    from benchmark import voc_images

    (train, labels), (test, _) = voc_images.voc_like(
        40, 8, SEED, num_classes=6, height=24, width=32)
    (again, same), _ = voc_images.voc_like(
        40, 8, SEED, num_classes=6, height=24, width=32)
    (other, _), _ = voc_images.voc_like(
        40, 8, SEED + 1, num_classes=6, height=24, width=32)
    assert train.shape == (40, 24, 32, 3) and train.dtype == np.uint8
    assert test.shape == (8, 24, 32, 3) and labels.shape == (40, 6)
    np.testing.assert_array_equal(np.asarray(train), np.asarray(again))
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(same))
    assert (np.asarray(train) != np.asarray(other)).mean() > 0.5
    counts = np.asarray(labels).sum(axis=1)
    assert counts.min() >= 1 and counts.max() <= 3
    assert set(np.unique(np.asarray(labels))) <= {0.0, 1.0}
