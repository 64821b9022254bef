"""`gram_blocks_reused_per_fit`: the manifest entry and its reader file
agree, and the reader, fed the counters of a small fit, reads four epochs'
worth of blocks for a five-epoch fit and 0 for a one-epoch fit. A count
from the CPU: nothing here is a time of the chip. (Kept outside
`tests/benchmark_tests/`, so the benchmark's own `paths` gain one JSON
file and no code.)"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, probes  # noqa: E402

METRIC = "gram_blocks_reused_per_fit"
CELLS = ["cifar_fit", "timit_fit", "timit_fit_4chip"]  # the last since PR 33
BLOCKS = 3  # 24 features in blocks of 8


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


def test_manifest_entry_and_reader_file_agree(bench):
    entry = bench._named("per_layer", METRIC)
    assert entry == {
        "name": METRIC, "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "solvers (nodes/learning/)",
        "moves": "fit_throughput", "workloads": CELLS}
    # appended behind what the manifest had (PR 28's last metric): one put
    # elsewhere reads as a change
    names = [m["name"] for m in bench.manifest["per_layer"]]
    assert names[names.index(METRIC) - 1] == "solver_steps_per_fit"
    assert bench.reader_spec(METRIC) == {
        "reader": "counter_delta",
        "args": {"counter": "solver.gram_blocks_reused", "phase": "fit",
                 "per": "fits"}}
    # `moves` is an end-to-end metric that every listed cell reports
    for cell in CELLS:
        reported = {m["name"] for m in bench.metrics("end_to_end", cell)}
        assert entry["moves"] in reported, cell
        assert entry in bench.metrics("per_layer", cell)
    # the layer's name as the accepted solver metrics spell it
    assert entry["layer"] == bench._named("per_layer", "solver_ms_per_fit")["layer"]


@pytest.mark.parametrize("iters,expected", [(5, 4 * BLOCKS), (1, 0)])
def test_reader_reads_the_blocks_a_fit_reused(bench, iters, expected):
    from keystone_tpu import Dataset
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    rng = np.random.default_rng(29)
    X = rng.normal(size=(96, 8 * BLOCKS)).astype(np.float32)
    Y = rng.normal(size=(96, 2)).astype(np.float32)
    fits = 2
    counters = probes.PhaseCounters()
    for _ in range(fits):
        BlockLeastSquaresEstimator(8, iters, lam=1.0).fit(Dataset(X), Dataset(Y))
    counters.close("fit")
    context = {"counters": counters.as_dict(), "stats": {"fits": fits}}
    spec = bench.reader_spec(METRIC)
    reader = files.module("readers", spec["reader"])
    assert reader.read(context, **spec["args"]) == expected
    formed = dict(spec["args"], counter="solver.gram_blocks_formed")
    assert reader.read(context, **formed) == BLOCKS
