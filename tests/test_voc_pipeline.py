"""VOCSIFTFisher on the normal path at a small size, and the pieces it
forced: the strided-slice SIFT against the gather form and the scalar
oracle, sampling on the device, the mixture fitted on every row from a
start made on the device, the microbatch from the bytes a row makes, the
chunk loop that neither pads nor copies, and the plan that keeps off the
device what cannot lie on it."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import descriptor_reference_impls as oracle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from keystone_tpu import telemetry  # noqa: E402
from keystone_tpu.data.dataset import Dataset, HostDataset  # noqa: E402
from keystone_tpu.workflow import PipelineEnv  # noqa: E402
from keystone_tpu.workflow.env import (  # noqa: E402
    ExecutionConfig,
    set_execution_config,
)

SEED = 2**31 + 40
# images of 48 x 64, PCA to 8, 4 centres, seeded
SIZES = {
    "image_height": 48, "image_width": 64, "num_classes": 4, "pca_dims": 8,
    "gmm_k": 4, "gmm_iters": 8, "sift_step": 3, "sift_bin": 4,
    "num_scales": 4, "scale_step": 0, "num_pca_samples": 96 * 30,
    "num_gmm_samples": 96 * 30, "lam": 0.5, "solver_block": 32,
    "bcd_iters": 1, "num_train": 96, "num_test": 64,
    "assumed": {"texture": 1.0, "clutter": 0.1, "noise": 0.1}}


def _counters(*names):
    return {name: telemetry.counter(name).value for name in names}


@pytest.fixture(scope="module")
def adapter():
    from benchmark import files

    return files.module("configs", "voc_sift_fisher")


@pytest.fixture(scope="module")
def data(adapter):
    from keystone_tpu.parallel.mesh import make_mesh

    return adapter.make_data(SIZES, SEED, make_mesh(jax.devices()[:1]))


@pytest.fixture(scope="module")
def program_scores(adapter, data):
    train, test = data
    PipelineEnv.reset()
    names = ("sift.images", "sampler.host_bytes", "gmm.em_iterations")
    before = _counters(*names)
    scores = np.asarray(adapter.build(train, SIZES, SEED)(test.data)
                        .get().numpy())
    after = _counters(*names)
    return scores, {n: after[n] - before[n] for n in names}


def test_the_pipeline_is_the_plain_reference_on_scores_and_map(
        data, program_scores):
    """Both fit their own PCA and their own mixture from the same seeded
    choices, in float32: the test scores agree to rounding carried
    through eight EM iterations, and the mAP is the same."""
    from benchmark.reference import voc_sift_fisher as reference
    from keystone_tpu.evaluation import MeanAveragePrecisionEvaluator

    train, test = data
    scores, counted = program_scores
    want = reference.fit_and_score(train, test, SIZES, SEED)["scores"]
    assert scores.shape == want.shape == (64, 4)
    assert np.linalg.norm(scores - want) / np.linalg.norm(want) < 2e-3
    evaluate = MeanAveragePrecisionEvaluator(4, multi_hot=True)
    labels = np.asarray(test.labels.numpy())
    got, ref = evaluate(scores, labels).mean(), evaluate(want, labels).mean()
    assert abs(got - ref) < 1e-3 and got > 0.8
    assert counted["sampler.host_bytes"] == 0
    assert counted["gmm.em_iterations"] == SIZES["gmm_iters"]


def _gather_form(gray, extractor):
    """Descriptors by a general gather of the aggregated maps' elements,
    as `sift.py` took them before it cut sixteen strided slices: the
    stencils are the module's, the sampling is written out in indices."""
    from keystone_tpu.nodes.images import sift

    parts = []
    for bs, step, off in extractor._scales():
        sm = sift._sep_conv_edge(gray[None], sift._gaussian_taps(bs / 6.0))[0]
        dy, dx = jnp.gradient(sm, axis=0), jnp.gradient(sm, axis=1)
        mag, ang = jnp.sqrt(dx * dx + dy * dy), jnp.arctan2(dy, dx)
        t = jnp.mod(ang / (2.0 * jnp.pi) * 8, 8)
        lo = jnp.floor(t)
        frac = t - lo
        lo = lo.astype(jnp.int32) % 8
        hi = (lo + 1) % 8
        maps = jnp.stack(
            [jnp.where(lo == o, mag * (1.0 - frac), 0.0)
             + jnp.where(hi == o, mag * frac, 0.0) for o in range(8)])
        agg = sift._sep_conv_edge(maps, sift._triangle(bs) / (bs * bs))
        n_r, n_c = sift.frame_grid(*gray.shape, bs, step, off)
        rr = (off + jnp.arange(n_r) * step)[:, None] + jnp.arange(4) * bs
        cc = (off + jnp.arange(n_c) * step)[:, None] + jnp.arange(4) * bs
        desc = agg[jnp.arange(8)[None, None, None, None, :],
                   rr[None, :, :, None, None], cc[:, None, None, :, None]]
        wm = jnp.asarray([sift._bin_window_mean(bs, b) for b in range(4)])
        desc = desc * wm[None, None, :, None, None] * wm[None, None, None, :, None]
        desc = desc.reshape(n_c * n_r, 128)
        norm = jnp.sqrt(jnp.sum(desc * desc, axis=1, keepdims=True)) + sift.VL_EPSILON_F
        desc = jnp.minimum(desc / norm, 0.2)
        desc = desc / (jnp.sqrt(jnp.sum(desc * desc, axis=1, keepdims=True))
                       + sift.VL_EPSILON_F)
        desc = jnp.where(norm < sift.CONTRAST_THRESHOLD, 0.0, desc)
        parts.append(jnp.minimum(jnp.floor(512.0 * desc), 255.0))
    return jnp.concatenate(parts, axis=0)


@pytest.mark.parametrize("shape,scales,step", [
    ((48, 64), 4, 3), ((60, 41), 2, 3), ((37, 52), 1, 4)])
def test_strided_slices_are_the_gather_and_the_scalar_oracle(
        shape, scales, step):
    from keystone_tpu.nodes.images.sift import SIFTExtractor

    rng = np.random.default_rng(3)
    yy, xx = np.meshgrid(np.arange(shape[0]), np.arange(shape[1]),
                         indexing="ij")
    gray = (0.5 + 0.2 * np.sin(xx / 3.0 + yy / 5.0)
            + 0.1 * rng.normal(size=shape)).astype(np.float32)
    extractor = SIFTExtractor(step, 4, scales, 0)
    got = np.asarray(extractor.apply(gray))
    assert got.shape == (extractor.num_descriptors(*shape), 128)
    # the same elements by another route: equal but where a float32 sum
    # in another order tips floor(512 v) over an edge
    gathered = np.asarray(_gather_form(jnp.asarray(gray), extractor))
    assert np.abs(got - gathered).max() <= 1.0
    assert np.mean(got != gathered) < 1e-4
    want = oracle.vl_dsift_multiscale(gray, step=step, bin_size=4,
                                      num_scales=scales, scale_step=0)
    diff = np.abs(got - want)
    assert np.mean(diff > 1.0) < 0.005 and diff.max() <= 2.0


def test_a_batch_of_images_is_the_images_one_by_one():
    from keystone_tpu.nodes.images.sift import SIFTExtractor

    extractor = SIFTExtractor(3, 4, 2, 0)
    images = np.random.default_rng(0).uniform(size=(3, 40, 44, 1)).astype(
        np.float32)
    batch = np.asarray(extractor.batch_fn()(jnp.asarray(images)))
    for image, row in zip(images, batch):
        alone = np.asarray(extractor.apply(image))
        assert np.abs(alone - row).max() <= 1.0
        assert np.mean(alone != row) < 1e-3


def test_sampling_on_the_device_keeps_the_reference_s_rows(adapter):
    """Inside a fused program the sampler takes the rows `sample_rows`
    names (the plain reference asks the same function), counts them, and
    moves no descriptor byte to the host; the host path counts what it
    pulls across."""
    from benchmark.reference import voc_sift_fisher as reference
    from keystone_tpu.nodes.images.sift import SIFTExtractor
    from keystone_tpu.nodes.stats import ColumnSampler
    from keystone_tpu.nodes.stats.normalization import sample_rows
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    images = np.random.default_rng(1).uniform(size=(5, 48, 64)).astype(
        np.float32)
    extractor = SIFTExtractor(3, 4, 4, 0)
    program_seed = adapter.program_config(SIZES, SEED).seed
    rows = reference.sampler_rows(SIZES, SEED, "pca", 96, 410)
    np.testing.assert_array_equal(rows, sample_rows(410, 30, program_seed))
    assert len(set(rows.tolist())) == 30 and (np.diff(rows) > 0).all()
    before = _counters("sampler.rows_kept", "sampler.host_bytes")
    out = FusedBatchTransformer(
        [extractor, ColumnSampler(30, program_seed)]).apply_batch(
            Dataset(images))
    after = _counters("sampler.rows_kept", "sampler.host_bytes")
    full = np.asarray(extractor.batch_fn()(jnp.asarray(images)))
    np.testing.assert_array_equal(np.asarray(out.numpy()), full[:, rows])
    assert after["sampler.rows_kept"] - before["sampler.rows_kept"] == 5 * 30
    assert after["sampler.host_bytes"] == before["sampler.host_bytes"]
    ColumnSampler(30, program_seed).apply(full[0])  # numpy: the host's way
    assert (telemetry.counter("sampler.host_bytes").value
            - after["sampler.host_bytes"]) == full[0].nbytes


def test_the_mixture_is_textbook_em_on_every_row_from_a_device_start():
    from benchmark.reference import voc_sift_fisher as reference
    from keystone_tpu.nodes.learning.gmm import (
        EM_BLOCK_ROWS,
        GaussianMixtureModelEstimator,
        gmm_start,
    )

    rng = np.random.default_rng(0)
    centres = rng.normal(size=(5, 6)) * 4.0
    n = EM_BLOCK_ROWS + 4321  # more than a block of rows, and no whole number
    X = (centres[rng.integers(0, 5, n)]
         + rng.normal(size=(n, 6))).astype(np.float32)
    items = 23  # (items, rows, d) as the samplers hand it over, zero rows behind
    per = -(-n // items)
    padded = np.concatenate([X, np.zeros((items * per - n, 6), np.float32)])
    before = _counters("gmm.em_iterations", "sampler.host_bytes")
    model = GaussianMixtureModelEstimator(5, num_iters=12, seed=7).fit(
        Dataset(X))
    after = _counters("gmm.em_iterations", "sampler.host_bytes")
    assert after["gmm.em_iterations"] - before["gmm.em_iterations"] == 12
    assert after["sampler.host_bytes"] == before["sampler.host_bytes"]
    means0, variances0, weights0, spread = gmm_start(
        jnp.asarray(X), n, 5, 7)
    # every start is one of the rows
    assert all((np.asarray(X) == np.asarray(m)).all(axis=1).any()
               for m in means0)
    with jax.default_matmul_precision("highest"):
        want = reference.em(jnp.asarray(X), means0, variances0, weights0,
                            0.01 * spread, iters=12)
    for got, ref in zip((model.means, model.variances, model.weights), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)
    # the likelihood climbs, and rows of padding behind the real ones
    # (a sharded dataset's) change nothing
    trace = np.asarray(model.log_likelihood_trace)
    assert (np.diff(trace) > -1e-4).all()
    dataset = Dataset(padded.reshape(items, per, 6))
    assert dataset.count == items
    ragged = GaussianMixtureModelEstimator(5, num_iters=2, seed=7)
    # a dataset of matrices counts whole items: fit its rows, all of them
    model2 = ragged.fit(dataset)
    assert np.isfinite(np.asarray(model2.means)).all()


@pytest.mark.parametrize("row_bytes,budget,want", [
    (64 * 1024, 16 << 30, 2048),      # a small row: the ceiling
    (75_638_784, 16 << 30, 8),        # 73,866 x 256 posteriors an image
    (75_638_784, 8 << 30, 4),
    (29_160_000, 16 << 30, 32),       # a 27 x 27 x 10,000 conv output
    (1 << 40, 16 << 30, 1)])          # never under one
def test_microbatch_rows_from_bytes_a_row_under_a_budget(
        row_bytes, budget, want):
    from keystone_tpu.workflow.budget import microbatch_rows

    assert microbatch_rows(row_bytes, budget) == want


def test_the_rule_gives_timit_2048_and_the_descriptor_chain_8():
    """What the fused programs of two cells derive under a 16 GiB chip:
    `timit_cosine`'s gather of four 4,096-wide branches keeps the 2,048
    it always ran at; SIFT, PCA and the Fisher encoding at VOC's shape
    take 8 images, whose posteriors are 605 MB. Traced abstractly: no
    array of that size exists here."""
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.images.sift import SIFTExtractor
    from keystone_tpu.nodes.learning.gmm import GaussianMixtureModel
    from keystone_tpu.nodes.learning.pca import PCATransformer
    from keystone_tpu.nodes.stats import CosineRandomFeatures
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        GatherConcatStage,
        chain_row_bytes,
    )

    set_execution_config(ExecutionConfig(hbm_budget_bytes=16 << 30))
    try:
        branches = [CosineRandomFeatures(440, 4096, 0.05555, seed=i)
                    for i in range(4)]
        gather = FusedBatchTransformer([GatherConcatStage(branches)])
        assert gather._chunk_rows(
            gather._decompose(), (65536, 440), "float32", 65536) == 2048

        def shapes(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float32)

        pca = PCATransformer.__new__(PCATransformer)
        pca.components = shapes(128, 80)
        gmm = GaussianMixtureModel.__new__(GaussianMixtureModel)
        gmm.means, gmm.variances, gmm.weights = (
            shapes(256, 80), shapes(256, 80), shapes(256))
        chain = FusedBatchTransformer(
            [SIFTExtractor(3, 4, 4, 0), pca, FisherVector(gmm)])
        decomposition = statics, flat, treedef, fns = chain._decompose()
        params = jax.tree_util.tree_unflatten(treedef, flat)
        assert chain_row_bytes(fns, params, (375, 500), "float32") == (
            73866 * 256 * 4)
        assert chain._chunk_rows(
            decomposition, (5011, 375, 500), "float32", 5011) == 8
        # a number handed over is taken as given
        assert FusedBatchTransformer(chain.stages, microbatch=32)._chunk_rows(
            decomposition, (5011, 375, 500), "float32", 5011) == 32
    finally:
        set_execution_config(None)


@pytest.mark.parametrize("rows,microbatch", [(300, 8), (131, 32), (50, 16)])
def test_the_chunk_loop_over_a_ragged_count_pads_and_copies_nothing(
        rows, microbatch):
    """Rows that are no whole number of microbatches go through in
    overlapping chunks (in slabs of 128 where there are that many): the
    values are the whole-batch function's, and the traced program holds
    no pad of the input."""
    from keystone_tpu.nodes.stats import NormalizeRows, SignedHellingerMapper
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    X = np.random.default_rng(rows).normal(size=(rows, 24)).astype(np.float32)
    chain = FusedBatchTransformer(
        [SignedHellingerMapper(), NormalizeRows()], microbatch=microbatch)
    got = np.asarray(chain.apply_batch(Dataset(X)).numpy())
    want = np.sign(X) * np.sqrt(np.abs(X))
    want = want / np.linalg.norm(want, axis=1, keepdims=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    statics, flat, treedef, fns = chain._decompose()
    from keystone_tpu.parallel import mesh as meshlib

    program = chain._build_program(
        meshlib.current_mesh(), 1, rows, treedef, fns, statics=statics)
    jaxpr = str(jax.make_jaxpr(program)(
        flat, jnp.asarray(X), jnp.ones((rows,), bool)))
    assert " pad" not in jaxpr and "dynamic_slice" in jaxpr


def _plan_counters():
    return _counters("planner.caches_refused", "planner.recomputes_planted",
                     "sift.images", "sampler.host_bytes")


def test_what_cannot_lie_on_the_device_is_recomputed_a_microbatch_at_a_time(
        adapter, data, program_scores):
    """Under a budget that holds the grayscale images and neither the
    descriptors nor their projections, the plan refuses the cache of the
    reduced descriptors, plants SIFT and the projection once a consumer
    (three passes over the images), and the scores are those of the plan
    that held everything."""
    train, test = data
    scores, counted = program_scores
    roomy_passes = counted["sift.images"]
    gray = 96 * 48 * 64 * 4
    reduced = 96 * 410 * 8 * 4
    assert gray < reduced  # so one budget parts them
    sizes = {**SIZES, "pca_dims": 8}
    set_execution_config(ExecutionConfig(hbm_budget_bytes=2 * gray + 4096))
    try:
        PipelineEnv.reset()
        before = _plan_counters()
        tight = np.asarray(adapter.build(train, sizes, SEED)(test.data)
                           .get().numpy())
        after = _plan_counters()
    finally:
        set_execution_config(None)
        PipelineEnv.reset()
    delta = {k: after[k] - before[k] for k in after}
    assert delta["planner.caches_refused"] >= 1
    assert delta["planner.recomputes_planted"] >= 3
    assert delta["sampler.host_bytes"] == 0
    # three passes over the training images, the optimizer's samples of
    # three images and the test set, where the roomy plan made one pass
    assert delta["sift.images"] >= 3 * 96 + 64 > roomy_passes
    np.testing.assert_allclose(tight, scores, rtol=1e-3, atol=1e-4)


def test_host_images_of_mixed_sizes_go_the_host_way():
    from keystone_tpu.evaluation import MeanAveragePrecisionEvaluator
    from keystone_tpu.loaders.csv_loader import LabeledData
    from keystone_tpu.pipelines.voc_sift_fisher import (
        VOCSIFTFisherConfig,
        build_pipeline,
        multi_hot,
    )

    rng = np.random.default_rng(0)
    templates = rng.uniform(0, 255, size=(3, 52, 52, 3)).astype(np.float32)

    def split(n, seed):
        r = np.random.default_rng(seed)
        images, labels = [], []
        for i in range(n):
            c = int(r.integers(0, 3))
            h, w = ((48, 52), (52, 44))[i % 2]  # two sizes, as a loader gives
            images.append(np.clip(
                templates[c, :h, :w] + 15.0 * r.normal(size=(h, w, 3)),
                0, 255).astype(np.float32))
            labels.append([c])
        return LabeledData(labels=Dataset(multi_hot(labels, 3)),
                           data=HostDataset(images))

    PipelineEnv.reset()
    train, test = split(24, 1), split(10, 2)
    config = VOCSIFTFisherConfig(num_classes=3, pca_dims=8, gmm_k=3,
                                 gmm_iters=5, block_size=16)
    scores = build_pipeline(train, config)(test.data).get()
    aps = MeanAveragePrecisionEvaluator(3, multi_hot=True)(
        scores, test.labels)
    assert np.isfinite(aps).all() and aps.mean() > 0.5
