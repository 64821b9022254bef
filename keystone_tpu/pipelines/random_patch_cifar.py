"""RandomPatchCifar — the north-star pipeline.

Reference: pipelines/images/cifar/RandomPatchCifar.scala:21-86. Filters
are whitened random patches from the training set (Coates & Ng style):

  driver-side filter learning (:45-57):
    Windower(1, patch) → vectorize → sample 100k patches
    → normalizeRows(sample, 10) → ZCAWhitenerEstimator.fitSingle
    → whiten sample → normalize → take numFilters rows as filters
  prediction pipeline (:59-69):
    Convolver(filters, whitener) → SymmetricRectifier(α=0.25)
    → Pooler(stride, size, sum) → ImageVectorizer → Cacher
    → StandardScaler → BlockLeastSquares(4096, 1, λ) → MaxClassifier

The TPU featurization path is one fused XLA program per batch: conv with
whitening folded into the kernel, two-sided ReLU, reduce_window pooling.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import jax
import numpy as np

from ..data.dataset import Dataset
from ..evaluation import MulticlassClassifierEvaluator
from ..loaders.cifar_loader import cifar_loader, synthetic_cifar
from ..nodes.images.core import (
    Convolver,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    SymmetricRectifier,
)
from ..nodes.learning import BlockLeastSquaresEstimator
from ..nodes.learning.zca import ZCAWhitener
from ..nodes.stats import StandardScaler
from ..nodes.util import Cacher, ClassLabelIndicatorsFromInt, MaxClassifier
from ..nodes.util.fusion import FusedBatchTransformer
from ..workflow import Pipeline


def analyzable(config: Optional["RandomPatchCifarConfig"] = None):
    """Abstract predictor graph for static validation: the prediction
    path (conv → rectify → pool → vectorize → scale → solve → argmax)
    with random filters standing in for the data-learned ones — filter
    *learning* is driver-side and data-dependent, but the pipeline
    shapes it must produce are not. Returns ``(pipeline, source_spec)``."""
    from ..analysis import SpecDataset
    from ..nodes.learning import BlockLeastSquaresEstimator

    config = config or RandomPatchCifarConfig(num_filters=32)
    h = w = 32
    c = 3
    n = 256
    rng = np.random.default_rng(config.seed)
    d = config.patch_size * config.patch_size * c
    filters = rng.normal(size=(config.num_filters, d)).astype(np.float32)
    featurizer = (
        PixelScaler().to_pipeline()
        >> Convolver(filters, h, w, c, whitener=None)
        >> SymmetricRectifier(alpha=config.alpha)
        >> Pooler(config.pool_stride, config.pool_size, pool_fn="sum")
        >> ImageVectorizer()
        >> Cacher("features")
    )
    data = SpecDataset((h, w, c), np.float32, count=n, name="cifar-images")
    raw_labels = SpecDataset((), np.int32, count=n, name="cifar-labels")
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(raw_labels)
    predictor = (
        featurizer.and_then(StandardScaler(), data)
        .and_then(
            BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
            data, labels,
        )
        >> MaxClassifier()
    )
    return predictor, (h, w, c)


@dataclass
class RandomPatchCifarConfig:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    num_filters: int = 256
    patch_size: int = 6
    patch_steps: int = 1
    pool_size: int = 14
    pool_stride: int = 13
    alpha: float = 0.25
    lam: float = 10.0
    sample_patches: int = 100_000
    block_size: int = 4096
    bcd_iters: int = 1
    num_classes: int = 10
    microbatch: int = 2048
    seed: int = 0
    # synthetic fallback sizes (used when no train_path)
    synth_train: int = 2000
    synth_test: int = 500


@jax.named_scope("ks.learn_filters")
def _learn_filters_device(images, key, eps, patch: int, step: int,
                          n_valid: int, n_sample: int, m: int,
                          num_filters: int):
    """The WHOLE filter-learning computation in one XLA program: sampled
    patch extraction + normalization, covariance, ZCA eigendecomposition,
    whitening, and filter selection. One dispatch, one packed transfer —
    per-call latency (not FLOPs) dominates this phase, so fusing the
    reference's driver-side LAPACK step (ZCAWhitener.scala:53-60) into
    the device program is the win. Sample indices are drawn ON DEVICE
    from ``key``: image and filter draws use the top-k trick (without
    replacement, matching the replaced host rng.choice semantics); only
    the patch subsample is with replacement — statistically equivalent
    for sampling 100k of ~360k patches, and no fresh host-side index
    array has to be shipped per call."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    k_img, k_patch, k_filt = jax.random.split(key, 3)

    def draw_without_replacement(k, pop: int, size: int):
        # top-k over per-element uniforms ≡ a uniform no-replacement
        # draw; compiles to a cheap partial selection (jax.random.choice
        # with replace=False lowers to a full sort of the population)
        _, picked = jax.lax.top_k(jax.random.uniform(k, (pop,)), size)
        return picked

    # without-replacement draws where duplicates would hurt (matching
    # the replaced host-side rng.choice semantics — with-replacement
    # filter selection would duplicate ~28% of runs' filters at
    # 256-of-100k)
    idx = draw_without_replacement(k_img, n_valid, n_sample)
    sel = jnp.take(images, idx, axis=0) / 255.0
    c = sel.shape[-1]
    # shared exact-extraction helper (HIGHEST precision, (ph, pw, C)
    # flat layout matching utils.images.extract_patches)
    from ..utils.images import extract_patches_device

    flat = extract_patches_device(sel, patch, step).reshape(
        -1, patch * patch * c
    )
    # patch subsample WITH replacement: collisions among 100k-of-364k
    # only reweight a few patches of a covariance estimate (immaterial),
    # and it avoids a full 364k selection in the program
    sub_idx = jax.random.randint(k_patch, (m,), 0, flat.shape[0])
    flat = jnp.take(flat, sub_idx, axis=0)
    # normalizeRows(_, 10.0): subtract patch mean, divide by max(norm, 10/255)
    flat = flat - flat.mean(axis=1, keepdims=True)
    norms = jnp.linalg.norm(flat, axis=1, keepdims=True)
    flat = flat / jnp.maximum(norms, 10.0 / 255.0)
    # true-f32 Gram: TPU default matmul precision is bf16-based, which
    # would corrupt the small eigenvalues the ZCA whitener depends on
    gram = jnp.matmul(flat.T, flat, precision=lax.Precision.HIGHEST)
    m = flat.shape[0]
    mu = flat.sum(axis=0) / m
    cov = (gram - m * jnp.outer(mu, mu)) / max(m - 1.0, 1.0)
    # ZCA: V diag((λ+ε)^-½) Vᵀ — f32 eigh is safe because eps floors the
    # spectrum far above f32 eigensolver error (zca.zca_from_covariance
    # is the host/f64 twin used by ZCAWhitenerEstimator)
    lams, V = jnp.linalg.eigh(cov)
    scale = 1.0 / jnp.sqrt(jnp.maximum(lams, 0.0) + eps)
    W = jnp.matmul(V * scale, V.T, precision=lax.Precision.HIGHEST)
    whitened = jnp.matmul(flat - mu, W, precision=lax.Precision.HIGHEST)
    wnorms = jnp.linalg.norm(whitened, axis=1, keepdims=True)
    whitened = whitened / jnp.maximum(wnorms, 1e-8)
    filter_idx = draw_without_replacement(k_filt, m, num_filters)
    filters = jnp.take(whitened, filter_idx, axis=0)
    # pack: one host transfer instead of three
    return jnp.concatenate([filters.ravel(), W.ravel(), mu])


def _learn_filters_parts(images, seed, eps, patch: int, step: int,
                         n_valid: int, n_sample: int, m: int,
                         num_filters: int):
    """`_learn_filters_device` as `learn_filters` launches it: the PRNG
    key made from ``seed`` and the packed result taken apart inside the
    program, so filter learning is one launch and not nine (the key's
    two, this one, and six slices and reshapes: the device trace of
    PR 25)."""
    import jax.numpy as jnp

    packed = _learn_filters_device(
        images, jax.random.PRNGKey(seed), eps, patch, step, n_valid,
        n_sample, m, num_filters)
    D = patch * patch * images.shape[-1]
    K = num_filters
    return (packed[: K * D].reshape(K, D),
            packed[K * D : K * D + D * D].reshape(D, D),
            packed[K * D + D * D :])


_learn_filters_jit = jax.jit(
    _learn_filters_parts,
    static_argnames=("patch", "step", "n_valid", "n_sample", "m",
                     "num_filters"))


def learn_filters(train_data: Dataset, config) -> tuple:
    """Whitened random-patch filter learning (reference :45-57), fully
    on-device: one program, whose three results stay on the device (the
    Convolver folds the whitener into its kernel there too), so pipeline
    construction never blocks on a host round trip."""
    from ..telemetry import dispatch

    n = train_data.count
    n_sample = min(n, max(config.sample_patches // 100, 64))
    h, w, c = train_data.array.shape[1:]
    gy = (h - config.patch_size) // config.patch_steps + 1
    gx = (w - config.patch_size) // config.patch_steps + 1
    total = n_sample * gy * gx
    m = min(total, config.sample_patches)

    # only the seed crosses host->device: the key and the index draws
    # are made inside the program
    with dispatch("_learn_filters_parts"):
        filters, W, mu = _learn_filters_jit(
            train_data.array, np.int32(config.seed), np.float32(0.1),
            patch=config.patch_size, step=config.patch_steps,
            n_valid=n, n_sample=n_sample, m=m,
            num_filters=config.num_filters,
        )
    return filters, ZCAWhitener(W, mu)


def make_featurizer(filters, whitener, h, w, c, config,
                    microbatch: Optional[int] = None) -> FusedBatchTransformer:
    """THE fused featurization stack (scale → folded-whitening conv →
    two-sided ReLU → sum-pool → flatten), one microbatched XLA program.
    Single source of truth for `build_pipeline` and `run_staged`."""
    return FusedBatchTransformer(
        [
            PixelScaler(),
            Convolver(filters, h, w, c, whitener=whitener, normalize_patches=True),
            SymmetricRectifier(alpha=config.alpha),
            Pooler(config.pool_stride, config.pool_size, pool_fn="sum"),
            ImageVectorizer(),
        ],
        microbatch=microbatch if microbatch is not None else config.microbatch,
    )


def build_pipeline(train, config):
    """Build + fit the full prediction pipeline; returns (pipeline, labels)."""
    filters, whitener = learn_filters(train.data, config)

    leaves = train.data.array
    h, w, c = leaves.shape[1:]
    featurizer = (
        make_featurizer(filters, whitener, h, w, c, config).to_pipeline()
        >> Cacher("features")
    )
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(train.labels).get()
    predictor = (
        featurizer
        .and_then(StandardScaler(), train.data)
        .and_then(
            BlockLeastSquaresEstimator(config.block_size, num_iter=config.bcd_iters, lam=config.lam),
            train.data,
            labels,
        )
        >> MaxClassifier()
    )
    return predictor


def _sync_leaf(x):
    """Scalar-pull host sync for RAW arrays (Dataset values use
    `Dataset.sync()`; both route through data.dataset.sync_pull, the
    single encoding of the timing fence)."""
    from ..data.dataset import sync_pull

    sync_pull(x)
    return x


def run_staged(train, config, evaluator):
    """Stage-resolved timed run of the SAME components `build_pipeline`
    assembles, with a scalar-pull host sync closing every stage so the
    per-stage wall-clocks are honest and sum to the staged end-to-end by
    construction (each stage's async dispatch cannot leak into the
    next). Returns (stage_seconds, train_metrics, predictor_parts).

    Stages mirror the reference app's phases (RandomPatchCifar.scala:
    21-86): filter learning (:45-57), featurization conv/rectify/pool
    (:59-64), scaler fit+apply (:67), BCD solve (:68), predict+eval
    (:70-80)."""
    stages = {}
    t = time.perf_counter

    t0 = t()
    filters, whitener = learn_filters(train.data, config)
    _sync_leaf(filters)
    stages["filter_learning"] = t() - t0

    leaves = train.data.array
    h, w, c = leaves.shape[1:]
    t0 = t()
    featurizer = make_featurizer(filters, whitener, h, w, c, config)
    feats = featurizer.apply_batch(train.data).sync()
    stages["featurize"] = t() - t0

    t0 = t()
    scaler = StandardScaler().fit(feats)
    scaled = scaler.apply_batch(feats).sync()
    stages["scaler"] = t() - t0

    t0 = t()
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(train.labels).get()
    model = BlockLeastSquaresEstimator(
        config.block_size, num_iter=config.bcd_iters, lam=config.lam
    ).fit(scaled, labels)
    _sync_leaf(model.W)
    stages["bcd_solve"] = t() - t0

    t0 = t()
    preds = MaxClassifier().apply_batch(model.apply_batch(scaled))
    train_metrics = evaluator(preds, train.labels)
    stages["predict_eval"] = t() - t0

    parts = {
        "featurizer": featurizer, "scaler": scaler, "model": model,
        "filters": filters, "whitener": whitener,
    }
    return stages, train_metrics, parts


def run(config: RandomPatchCifarConfig):
    if config.train_path:
        train = cifar_loader(config.train_path)
        test = cifar_loader(config.test_path or config.train_path)
    else:
        train, test = synthetic_cifar(
            config.synth_train, config.synth_test, config.num_classes, config.seed
        )

    t0 = time.perf_counter()
    predictor = build_pipeline(train, config)
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    train_metrics = evaluator(predictor(train.data), train.labels)
    t_train = time.perf_counter() - t0
    test_metrics = evaluator(predictor(test.data), test.labels)
    return {
        "train_error": train_metrics.error,
        "test_error": test_metrics.error,
        "test_accuracy": test_metrics.accuracy,
        "train_seconds": t_train,
        "images_per_sec": train.data.count / t_train,
        "summary": test_metrics.summary(),
        "predictor": predictor,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--train-path", dest="train_path")
    p.add_argument("--test-path", dest="test_path")
    p.add_argument("--num-filters", dest="num_filters", type=int, default=256)
    p.add_argument("--patch-size", dest="patch_size", type=int, default=6)
    p.add_argument("--pool-size", dest="pool_size", type=int, default=14)
    p.add_argument("--pool-stride", dest="pool_stride", type=int, default=13)
    p.add_argument("--alpha", type=float, default=0.25)
    p.add_argument("--lam", type=float, default=10.0)
    p.add_argument("--block-size", dest="block_size", type=int, default=4096)
    p.add_argument("--synth-train", dest="synth_train", type=int, default=2000)
    p.add_argument("--synth-test", dest="synth_test", type=int, default=500)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    config = RandomPatchCifarConfig(
        **{k: v for k, v in vars(args).items() if v is not None}
    )
    result = run(config)
    print(result["summary"])
    print(
        f"train_error={result['train_error']:.4f} "
        f"test_error={result['test_error']:.4f} "
        f"train_time={result['train_seconds']:.2f}s "
        f"({result['images_per_sec']:.0f} img/s)"
    )
    return result


if __name__ == "__main__":
    main()
