"""The six per-layer metrics that read the program's layer clock (PR 25),
rehearsed in the tiny `cifar_fit` on the CPU: each is reported and above
0, and a fit's five layers together take no more than the fit's seconds
on the host. Shares and counts only: none of these is a time of the
chip."""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, run  # noqa: E402

SEED = 2**31 + 25
TINY_CIFAR = {
    "num_filters": 16, "block_size": 64, "num_train": 256, "num_test": 64,
    "sample_patches": 10000, "feature_dim": 128, "lam": 10.0,
    "assumed": {"noise": 1.2, "confusion": 0.6, "microbatch": 32},
    "accuracy_band": [0.5, 1.0], "reference_agreement": 0.9}
FIT_LAYERS = ("optimize_host_s_per_fit", "executor_host_s_per_fit",
              "dispatch_host_s_per_fit", "solver_host_s_per_fit",
              "sync_host_s_per_fit")
NEW = FIT_LAYERS + ("dispatch_host_s_per_apply",)


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


@pytest.fixture(scope="module")
def traced(bench):
    sizes = {**bench.sizes("random_patch_cifar"), **TINY_CIFAR}
    record = run.measure(bench, "cifar_fit", SEED, 0.01, 1,
                         jax.devices()[:1], sizes=sizes, log=lambda r: None)
    metrics = run.layer_metrics(
        bench, "cifar_fit", record, {"flops": 1.0, "bytes_per_s": 1.0},
        log=lambda r: None)
    return record, metrics


@pytest.mark.parametrize("name", NEW)
def test_the_manifest_names_the_metric_as_a_program_span(bench, name):
    (entry,) = [m for m in bench.manifest["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span" and entry["unit"] == "s"
    assert entry["better"] == "lower" and entry["workloads"] == ["cifar_fit"]
    spec = bench.reader_spec(name)
    assert spec["reader"] == "counter_delta"
    assert spec["args"]["counter"].startswith("host.")
    assert spec["args"]["counter"].endswith(".seconds")
    phase = "apply" if name.endswith("_apply") else "fit"
    assert spec["args"]["phase"] == phase
    assert entry["moves"] == phase + "_throughput"


@pytest.mark.parametrize("name", NEW)
def test_tiny_cifar_fit_reports_the_metric_above_zero(traced, name):
    _, metrics = traced
    assert metrics[name]["unit"] == "s"
    assert metrics[name]["value"] > 0.0


def test_a_fits_layers_take_no_more_than_the_fit(traced):
    record, metrics = traced
    assert record["correct"]
    sizes_train = record["stats"]["sizes"]["num_train"]
    fit_host_s = sizes_train / record["end_to_end"]["fit_throughput"]
    layers = sum(metrics[name]["value"] for name in FIT_LAYERS)
    assert 0.0 < layers <= fit_host_s


def test_the_counted_programs_are_the_dispatch_spans(traced):
    record, _ = traced
    for phase in ("fit", "apply"):
        moved = record["counters"][phase]
        assert (moved["dispatch.programs_executed"]
                == moved["host.dispatch.spans"])
