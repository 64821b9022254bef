"""`residual_addbacks_skipped_per_fit`: the manifest entry and its reader
file agree, and the reader, fed the counters of small fits, reads one
product a block step (the add-back of the block's contribution into the
residual, which the step no longer runs), per fit, and 0 for a program
without the counter (the parent). A count from the CPU: nothing here is a
time of the chip. (Kept outside `tests/benchmark_tests/`, so the
benchmark's own `paths` gain one JSON file and no code.)"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, probes  # noqa: E402

METRIC = "residual_addbacks_skipped_per_fit"
CELLS = ["cifar_fit", "timit_fit", "timit_fit_4chip"]
BLOCKS = 3  # 24 features in blocks of 8


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


def test_manifest_entry_and_reader_file_agree(bench):
    entry = bench._named("per_layer", METRIC)
    assert entry == {
        "name": METRIC, "unit": "products", "better": "higher",
        "source": "program_counter", "layer": "solvers (nodes/learning/)",
        "moves": "fit_throughput", "workloads": CELLS}
    # appended behind PR 36's last: one put elsewhere reads as a change
    # (later PRs append behind it in turn)
    names = [m["name"] for m in bench.manifest["per_layer"]]
    assert names.index(METRIC) == names.index(
        "kernel_blocks_reused_per_fit") + 1
    assert bench.reader_spec(METRIC) == {
        "reader": "counter_delta",
        "args": {"counter": "solver.residual_addbacks_skipped",
                 "phase": "fit", "per": "fits"}}
    # `moves` is an end-to-end metric that every listed cell reports
    for cell in CELLS:
        reported = {m["name"] for m in bench.metrics("end_to_end", cell)}
        assert entry["moves"] in reported, cell
        assert entry in bench.metrics("per_layer", cell)
    # the layer's name as the accepted solver metrics spell it
    assert entry["layer"] == bench._named("per_layer", "solver_ms_per_fit")["layer"]


def _read(bench, counters, fits):
    context = {"counters": counters, "stats": {"fits": fits}}
    spec = bench.reader_spec(METRIC)
    return files.module("readers", spec["reader"]).read(context, **spec["args"])


@pytest.mark.parametrize("iters", [1, 3, 5])
def test_reader_reads_one_product_a_block_step(bench, iters):
    """`num_blocks x num_iter` a fit: the forming sweep skips the add-back
    as the sweeps on kept factors do."""
    from keystone_tpu import Dataset
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    rng = np.random.default_rng(37)
    X = rng.normal(size=(96, 8 * BLOCKS)).astype(np.float32)
    Y = rng.normal(size=(96, 2)).astype(np.float32)
    fits = 2
    counters = probes.PhaseCounters()
    for _ in range(fits):
        BlockLeastSquaresEstimator(8, iters, lam=1.0).fit(Dataset(X), Dataset(Y))
    counters.close("fit")
    assert _read(bench, counters.as_dict(), fits) == BLOCKS * iters


def test_reader_reads_zero_for_a_program_without_the_counter(bench):
    """The parent under this PR's benchmark files: its fits move
    `solver.steps` and know no `solver.residual_addbacks_skipped`."""
    counters = {"fit": {"solver.gram_blocks_formed": 8.0, "solver.steps": 10.0},
                "apply": {}}
    assert _read(bench, counters, 2) == 0
