"""The `timit_fit_4chip` cell rehearsed without the chip, on four of the
CPU's eight virtual devices: mode `fit` runs the `timit_cosine_mesh4`
configuration tiny through the normal path on a `(4,)` `data` mesh, the
fit counts its steps, its kept factors and the bytes it hands to
all-reduces (none on one device), the mesh fit is the one-device fit, a
model fitted to shuffled labels is called incorrect, the one-device
blockwise reference gives `reference/timit_cosine.py`'s scores while it
holds one block of features at a time, and the per-chip roofline reader
is `roofline_share` over the device count. Nothing here is a time or a
rate."""

import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import files, run, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 33  # the driver's seeds are larger than 32 signed bits hold
# four branches of 64 over 32-dimensional frames, a solver block each, 512
# rows a device; the signal is raised so that 2,048 rows learn 12 classes
TINY_TIMIT = {
    "input_dim": 32, "num_cosines": 4, "num_cosine_features": 64,
    "feature_dim": 256, "block_size": 64, "num_classes": 12,
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
    return {**bench.sizes("timit_cosine_mesh4"), **TINY_TIMIT}


@pytest.fixture(scope="module")
def records(bench, sizes):
    """The cell traced off on four devices and on one, run once each."""
    return {chips: run.measure(bench, "timit_fit_4chip", SEED, 0.01, 0,
                               jax.devices()[:chips], sizes=sizes, log=quiet)
            for chips in (4, 1)}


def allreduce_bytes_by_the_shapes(sizes):
    """What one device hands to all-reduces in a fit: the centring sums
    (d column sums, the row count, k label sums), each forming step's
    Gram and correlation, each later step's correlation. At a block of 64
    the Gram is the one full product (under two tiles of 256)."""
    d, B, k = sizes["feature_dim"], sizes["block_size"], sizes["num_classes"]
    blocks, epochs = d // B, sizes["bcd_iters"]
    return 4 * ((d + 1 + k) + blocks * (B * B + B * k)
                + (epochs - 1) * blocks * B * k)


def test_the_cell_is_of_the_source_s_widths_on_four_chips(bench):
    full, one_chip = (bench.sizes(c) for c in ("timit_cosine_mesh4",
                                               "timit_cosine"))
    changed = {k for k in one_chip if one_chip[k] != full.get(k)}
    assert {"num_train", "num_test"} <= changed
    # everything else that differs is prose, the published numbers or a note
    assert changed <= {"num_train", "num_test", "source_detail", "published",
                       "reduced_why", "assumed", "accuracy_band",
                       "accuracy_band_why", "reference_agreement",
                       "reference_agreement_why"}
    assert full["assumed"]["signal"] == one_chip["assumed"]["signal"] == 0.065
    assert (full["input_dim"], full["num_cosine_features"],
            full["block_size"], full["num_classes"]) == (440, 4096, 4096, 147)
    assert (full["num_train"], full["num_test"]) == (262144, 262144)
    assert full["mesh"] == {"data": 4}
    assert full["num_train"] // full["mesh"]["data"] == one_chip["num_train"]
    assert (full["published"]["num_cosines"], full["published"]["num_train"],
            full["published"]["machines"]) == (50, 2200000, 16)
    cell = bench.cell("timit_fit_4chip")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "timit_cosine_mesh4", "warm_fit_apply", 4)
    entry = bench._named("configs", "timit_cosine_mesh4")
    assert set(entry["reduced"]) == {"num_cosines", "num_train", "num_test"}
    reported = {m["name"] for section in ("end_to_end", "per_layer")
                for m in bench.metrics(section, "timit_fit_4chip")}
    assert {"fit_throughput", "setup_s", "collective_ms_per_fit",
            "allreduce_bytes_per_fit", "bcd_roofline_per_chip"} <= reported
    # one chip's peak under four chips' work would read four times too high
    assert not reported & {"apply_throughput", "bcd_roofline",
                           "cosine_features_roofline"}


@pytest.mark.parametrize("chips,metric,want", [
    (4, "solver_steps_per_fit", lambda sizes: sizes["bcd_iters"]),
    (4, "gram_blocks_reused_per_fit", lambda sizes: 16),
    (4, "allreduce_bytes_per_fit", allreduce_bytes_by_the_shapes),
    (1, "solver_steps_per_fit", lambda sizes: sizes["bcd_iters"]),
    (1, "gram_blocks_reused_per_fit", lambda sizes: 16),
    (1, "allreduce_bytes_per_fit", lambda sizes: 0),
])
def test_a_tiny_run_is_correct_and_counts(bench, sizes, records, chips,
                                          metric, want):
    record = records[chips]
    assert record["correct"] and record["failed"] == 0
    assert record["attempted"] == record["stats"]["fits"] >= 1
    metrics = run.layer_metrics(bench, "timit_fit_4chip", record, {},
                                log=quiet)
    assert metrics[metric]["value"] == want(sizes)
    assert sizes["bcd_iters"] == 5
    # the programs of a fit do not depend on the mesh
    assert metrics["programs_per_fit"]["value"] == 4 + 13.0
    assert set(record["end_to_end"]) >= {"fit_throughput"}
    reported = run.end_to_end_metrics(bench, "timit_fit_4chip", record, 1.0)
    assert set(reported) == {"fit_throughput", "setup_s"}


def test_a_model_fitted_to_shuffled_labels_is_called_incorrect(bench, sizes):
    from benchmark.modes import fit
    from keystone_tpu.parallel.mesh import make_mesh

    adapter = files.module("configs", "timit_cosine_mesh4")

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
        ShuffledLabels, files.module("reference", "timit_cosine_mesh4"),
        sizes, bench.traffic("warm_fit_apply"), SEED, 0.1,
        make_mesh(jax.devices()[:4]), log=quiet)
    assert not record["correct"]
    assert record["failed"] == record["attempted"] >= 1


def _fitted(sizes, devices):
    """(W of the fitted model, test predictions) of the cell's pipeline on
    a mesh of ``devices``."""
    from keystone_tpu.nodes.learning import BlockLinearMapper
    from keystone_tpu.parallel.mesh import make_mesh, use_mesh
    from keystone_tpu.workflow import PipelineEnv

    adapter = files.module("configs", "timit_cosine_mesh4")
    mesh = make_mesh(devices)
    with use_mesh(mesh):
        PipelineEnv.reset()
        train, test = adapter.make_data(sizes, SEED, mesh)
        fitted = adapter.build(train, sizes, SEED).fit()
        preds = np.asarray(fitted.apply(test.data).numpy())
    (model,) = [m for op in fitted.graph.operators.values()
                for m in _stages_of(op, BlockLinearMapper)]
    return np.asarray(model.W), preds


def _stages_of(op, kind):
    """The transformers of ``kind`` in a fitted operator, through the
    fused operators' nested `stages`."""
    if isinstance(op, kind):
        yield op
    for stage in getattr(op, "stages", []):
        yield from _stages_of(stage, kind)


def test_the_mesh_fit_is_the_one_device_fit(sizes):
    W4, preds4 = _fitted(sizes, jax.devices()[:4])
    W1, preds1 = _fitted(sizes, jax.devices()[:1])
    # the sums over rows are taken in another order (four partial sums,
    # then the all-reduce): float32 rounding through five epochs of
    # solves on Grams of condition number about 1e4, nothing more
    np.testing.assert_allclose(W4, W1, rtol=1e-3, atol=1e-4)
    np.testing.assert_array_equal(preds4, preds1)


def test_the_blockwise_reference_is_the_one_chip_reference(sizes, monkeypatch):
    from keystone_tpu.parallel.mesh import make_mesh, use_mesh

    adapter = files.module("configs", "timit_cosine_mesh4")
    blockwise = files.module("reference", "timit_cosine_mesh4")
    whole = files.module("reference", "timit_cosine")
    n, block = sizes["num_train"], sizes["block_size"]

    def feature_arrays():
        """Live arrays as large as a block of the training features."""
        return sum(1 for a in jax.live_arrays()
                   if a.ndim == 2 and a.shape[0] == n and a.shape[1] >= block)

    made = []
    make_block = blockwise._centred_block

    def watched(frames, W, b, *, operands):
        # the last block's features are gone before the next are made
        assert feature_arrays() == held_before
        Xb, xm = make_block(frames, W, b, operands=operands)
        made.append(Xb.shape)
        return Xb, xm

    monkeypatch.setattr(blockwise, "_centred_block", watched)
    mesh = make_mesh(jax.devices()[:4])
    with use_mesh(mesh):
        train, test = adapter.make_data(sizes, SEED, mesh)
        held_before = feature_arrays()  # other tests' leavings, if any
        got = blockwise.scores(train, test, sizes, SEED)
        assert feature_arrays() == held_before
        want = whole.scores(train, test, sizes, SEED)
    blocks = sizes["feature_dim"] // block
    assert made == [(n, block)] * (sizes["bcd_iters"] * blocks)
    # the same arithmetic on the same operands, but for the Gram, which the
    # whole reference forms anew in every epoch and this one factors once:
    # equal to float32 rounding on scores of order 1
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(got.argmax(axis=-1), want.argmax(axis=-1))


def test_the_per_chip_roofline_is_the_roofline_over_the_device_count():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    trace = trace_reduce.reduce_planes(recorded["planes"])
    sizes = {"num_train": 100, "num_classes": 2, "feature_dim": 12,
             "block_size": 8, "bcd_iters": 3}
    spec = files.BenchFiles().reader_spec("bcd_roofline_per_chip")
    assert spec["reader"] == "roofline_share_per_chip"
    assert spec["args"] == files.BenchFiles().reader_spec("bcd_roofline")["args"]
    # the recorded excerpt's one module inside `bench:fit` stands in for
    # the solver's: the readers differ by the device count and nothing else
    args = dict(spec["args"], pattern="^jit__learn_")

    def read(reader, devices):
        context = {"trace": dict(trace, devices=devices), "counters": {},
                   "stats": {"sizes": sizes},
                   "peaks": {"flops": 1e9, "bytes_per_s": 1e9}}
        return files.module("readers", reader).read(context, **args), context

    whole, _ = read("roofline_share", 4)
    per_chip, context = read("roofline_share_per_chip", 4)
    assert whole > 0 and per_chip == pytest.approx(whole / 4)
    assert context["notes"]["bcd"]["chips"] == 4
    assert read("roofline_share_per_chip", 1)[0] == pytest.approx(whole)
    assert read("roofline_share_per_chip", 0)[0] is None  # off the chip
