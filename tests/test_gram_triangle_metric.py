"""`gram_tiles_skipped_per_fit`: the manifest entry and its reader file
agree, and the reader, fed the counters of small fits, reads the tiles
below the diagonal that a forming sweep did not compute, per fit, and 0 for
a program without the counter (the parent) or one that ran the full
product. A count from the CPU: nothing here is a time of the chip. (Kept
outside `tests/benchmark_tests/`, so the benchmark's own `paths` gain one
JSON file and no code.)"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, probes  # noqa: E402

METRIC = "gram_tiles_skipped_per_fit"
CELLS = ["cifar_fit", "timit_fit", "timit_fit_4chip"]  # the last since PR 33
BLOCKS = 3  # 24 features in blocks of 8


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


def test_manifest_entry_and_reader_file_agree(bench):
    entry = bench._named("per_layer", METRIC)
    assert entry == {
        "name": METRIC, "unit": "tiles", "better": "higher",
        "source": "program_counter", "layer": "solvers (nodes/learning/)",
        "moves": "fit_throughput", "workloads": CELLS}
    # appended behind PR 29's metric: one put elsewhere reads as a change
    names = [m["name"] for m in bench.manifest["per_layer"]]
    assert names[names.index(METRIC) - 1] == "gram_blocks_reused_per_fit"
    assert bench.reader_spec(METRIC) == {
        "reader": "counter_delta",
        "args": {"counter": "solver.gram_tiles_skipped", "phase": "fit",
                 "per": "fits"}}
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


# (tile or None for the shape rule's own choice at B = 8, epochs, expected
# a fit): a tile of 4 makes t = 2 and skips one tile a block, a tile of 2
# makes t = 4 and skips six; only the forming sweep skips any
@pytest.mark.parametrize("tile,iters,expected", [
    (4, 1, BLOCKS * 1), (2, 1, BLOCKS * 6), (2, 5, BLOCKS * 6), (None, 5, 0)])
def test_reader_reads_the_tiles_a_fit_skipped(bench, monkeypatch, tile, iters,
                                              expected):
    from keystone_tpu import Dataset
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator, block_ls

    if tile is not None:
        monkeypatch.setattr(block_ls, "_gram_tile", lambda block_size: tile)
    rng = np.random.default_rng(32)
    X = rng.normal(size=(96, 8 * BLOCKS)).astype(np.float32)
    Y = rng.normal(size=(96, 2)).astype(np.float32)
    fits = 2
    counters = probes.PhaseCounters()
    for _ in range(fits):
        BlockLeastSquaresEstimator(8, iters, lam=1.0).fit(Dataset(X), Dataset(Y))
    counters.close("fit")
    assert _read(bench, counters.as_dict(), fits) == expected


def test_reader_reads_zero_for_a_program_without_the_counter(bench):
    """The parent under this PR's benchmark files: its fits move
    `solver.gram_blocks_formed` and know no `solver.gram_tiles_skipped`."""
    counters = {"fit": {"solver.gram_blocks_formed": 8.0, "solver.steps": 10.0},
                "apply": {}}
    assert _read(bench, counters, 2) == 0
