"""Remaining CIFAR example apps.

- LinearPixels (reference pipelines/images/cifar/LinearPixels.scala):
  GrayScaler→ImageVectorizer→LinearMapEstimator→MaxClassifier.
- RandomCifar (RandomCifar.scala): random (unwhitened) conv filters.
- RandomPatchCifarKernel (RandomPatchCifarKernel.scala:62-75): the
  RandomPatchCifar featurization with KernelRidgeRegression as solver.
- RandomPatchCifarAugmented (RandomPatchCifarAugmented.scala): random
  patch + flip augmentation at train, center/corner patches at test,
  AugmentedExamplesEvaluator.
- RandomPatchCifarAugmentedKernel
  (RandomPatchCifarAugmentedKernel.scala:1-190): the augmented
  featurization with random horizontal flips and a shuffle at train,
  KernelRidgeRegression as the solver (with `--checkpoint-dir` block-loop
  checkpointing, :176), center/corner/flip crops + score averaging at
  test.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..data.dataset import Dataset
from ..evaluation import AugmentedExamplesEvaluator, MulticlassClassifierEvaluator
from ..loaders.cifar_loader import cifar_loader, synthetic_cifar
from ..nodes.images.core import (
    CenterCornerPatcher,
    Convolver,
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    Pooler,
    RandomPatcher,
    SymmetricRectifier,
)
from ..nodes.learning import KernelRidgeRegression, LinearMapEstimator
from ..nodes.stats import StandardScaler
from ..nodes.util import Cacher, ClassLabelIndicatorsFromInt, MaxClassifier
from ..nodes.util.fusion import FusedBatchTransformer
from ..workflow import Pipeline
from .random_patch_cifar import (
    RandomPatchCifarConfig,
    learn_filters,
    make_featurizer,
)


def _load(config):
    if getattr(config, "train_path", None):
        return cifar_loader(config.train_path), cifar_loader(
            config.test_path or config.train_path
        )
    return synthetic_cifar(config.synth_train, config.synth_test, config.num_classes,
                           config.seed)


@dataclass
class LinearPixelsConfig:
    train_path: Optional[str] = None
    test_path: Optional[str] = None
    lam: float = 1.0
    num_classes: int = 10
    synth_train: int = 1000
    synth_test: int = 250
    seed: int = 0


def analyzable(config: Optional[LinearPixelsConfig] = None):
    """Abstract LinearPixels predictor graph for static validation.
    Returns ``(pipeline, source_spec)``."""
    from ..analysis import SpecDataset

    config = config or LinearPixelsConfig()
    h = w = 32
    c = 3
    n = 256
    featurizer = (
        FusedBatchTransformer(
            [PixelScaler(), GrayScaler(), ImageVectorizer()], microbatch=4096
        ).to_pipeline()
        >> Cacher("pixels")
    )
    data = SpecDataset((h, w, c), np.float32, count=n, name="cifar-images")
    raw_labels = SpecDataset((), np.int32, count=n, name="cifar-labels")
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(raw_labels)
    predictor = featurizer.and_then(
        LinearMapEstimator(config.lam), data, labels
    ) >> MaxClassifier()
    return predictor, (h, w, c)


def run_linear_pixels(config: LinearPixelsConfig):
    train, test = _load(config)
    t0 = time.perf_counter()
    featurizer = (
        FusedBatchTransformer(
            [PixelScaler(), GrayScaler(), ImageVectorizer()], microbatch=4096
        ).to_pipeline()
        >> Cacher("pixels")
    )
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(train.labels).get()
    predictor = featurizer.and_then(
        LinearMapEstimator(config.lam), train.data, labels
    ) >> MaxClassifier()
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    train_eval = evaluator(predictor(train.data), train.labels)
    test_eval = evaluator(predictor(test.data), test.labels)
    return {
        "train_error": train_eval.error,
        "test_error": test_eval.error,
        "test_accuracy": test_eval.accuracy,
        "seconds": time.perf_counter() - t0,
    }


@dataclass
class RandomCifarConfig(RandomPatchCifarConfig):
    pass


def run_random_cifar(config: RandomCifarConfig):
    """Random Gaussian filters, no whitening (RandomCifar.scala)."""
    train, test = _load(config)
    t0 = time.perf_counter()
    rng = np.random.default_rng(config.seed)
    d = config.patch_size * config.patch_size * 3
    filters = rng.normal(size=(config.num_filters, d)).astype(np.float32)
    filters /= np.linalg.norm(filters, axis=1, keepdims=True)
    h, w, c = train.data.array.shape[1:]
    featurizer = (
        FusedBatchTransformer(
            [
                PixelScaler(),
                Convolver(filters, h, w, c, whitener=None, normalize_patches=True),
                SymmetricRectifier(alpha=config.alpha),
                Pooler(config.pool_stride, config.pool_size, pool_fn="sum"),
                ImageVectorizer(),
            ],
            microbatch=config.microbatch,
        ).to_pipeline()
        >> Cacher("features")
    )
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(train.labels).get()
    from ..nodes.learning import BlockLeastSquaresEstimator

    predictor = (
        featurizer.and_then(StandardScaler(), train.data)
        .and_then(
            BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
            train.data, labels,
        )
        >> MaxClassifier()
    )
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    test_eval = evaluator(predictor(test.data), test.labels)
    return {
        "test_error": test_eval.error,
        "test_accuracy": test_eval.accuracy,
        "seconds": time.perf_counter() - t0,
    }


@dataclass
class RandomPatchCifarKernelConfig(RandomPatchCifarConfig):
    """`RandomPatchCifarKernelConfig`'s defaults in
    RandomPatchCifarKernel.scala: numFilters 100, gamma 2e-4, blockSize
    5000, cacheKernel true, numEpochs 1. ``lam`` has no default there
    (the command line gives it); 10.0 is this port's own."""
    num_filters: int = 100
    gamma: float = 2e-4
    kernel_block: int = 5000
    kernel_epochs: int = 1
    cache_kernel: bool = True


def build_kernel_pipeline(train, config: RandomPatchCifarKernelConfig):
    """The lazy predictor `Pipeline` of RandomPatchCifarKernel
    (RandomPatchCifarKernel.scala:62-75), its estimators bound to
    ``train``: RandomPatchCifar's featurizer and scaler, then
    `KernelRidgeRegression` and `MaxClassifier`."""
    filters, whitener = learn_filters(train.data, config)
    h, w, c = train.data.array.shape[1:]
    featurizer = (
        make_featurizer(filters, whitener, h, w, c, config).to_pipeline()
        >> Cacher("features")
    )
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(train.labels).get()
    return (
        featurizer.and_then(StandardScaler(), train.data)
        .and_then(
            KernelRidgeRegression(
                config.gamma, config.lam, config.kernel_block,
                config.kernel_epochs, seed=config.seed,
                cache_kernel=config.cache_kernel,
            ),
            train.data, labels,
        )
        >> MaxClassifier()
    )


def run_random_patch_cifar_kernel(config: RandomPatchCifarKernelConfig):
    """RandomPatchCifar featurization + kernel ridge regression solver
    (RandomPatchCifarKernel.scala:62-75)."""
    train, test = _load(config)
    t0 = time.perf_counter()
    predictor = build_kernel_pipeline(train, config)
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    train_eval = evaluator(predictor(train.data), train.labels)
    test_eval = evaluator(predictor(test.data), test.labels)
    return {
        "train_error": train_eval.error,
        "test_error": test_eval.error,
        "test_accuracy": test_eval.accuracy,
        "seconds": time.perf_counter() - t0,
    }


@dataclass
class RandomPatchCifarAugmentedConfig(RandomPatchCifarConfig):
    patches_per_image: int = 4
    aug_patch: int = 24


def run_random_patch_cifar_augmented(config: RandomPatchCifarAugmentedConfig):
    """Train on random crops (+id-tracked center/corner crops at test),
    average augmented scores per original image
    (RandomPatchCifarAugmented.scala)."""
    train, test = _load(config)
    t0 = time.perf_counter()
    ap = config.aug_patch

    # augment train: random crops; labels repeat per crop
    patcher = RandomPatcher(config.patches_per_image, ap, ap, seed=config.seed)
    aug_train = patcher.apply_batch(train.data)
    aug_labels = np.repeat(np.asarray(train.labels.numpy()), config.patches_per_image)

    filters, whitener = learn_filters(aug_train, config)
    h = w = ap
    featurizer = (
        FusedBatchTransformer(
            [
                PixelScaler(),
                Convolver(filters, h, w, 3, whitener=whitener),
                SymmetricRectifier(alpha=config.alpha),
                Pooler(max(ap // 2 - 1, 1), ap // 2, pool_fn="sum"),
                ImageVectorizer(),
            ],
            microbatch=config.microbatch,
        ).to_pipeline()
        >> Cacher("features")
    )
    label_ind = ClassLabelIndicatorsFromInt(config.num_classes)(
        Dataset(aug_labels.astype(np.int32))
    ).get()
    from ..nodes.learning import BlockLeastSquaresEstimator

    scorer = featurizer.and_then(StandardScaler(), aug_train).and_then(
        BlockLeastSquaresEstimator(config.block_size, 1, config.lam),
        aug_train, label_ind,
    )
    # test: center+corner crops, ids track the source image
    cc = CenterCornerPatcher(ap, ap, with_flips=False)
    aug_test = cc.apply_batch(test.data)
    n_aug = 5
    ids = np.repeat(np.arange(test.data.count), n_aug)
    actuals = np.repeat(np.asarray(test.labels.numpy()), n_aug)
    scores = scorer(aug_test).get()
    m = AugmentedExamplesEvaluator(config.num_classes)(ids, scores, actuals)
    return {
        "test_error": m.error,
        "test_accuracy": m.accuracy,
        "seconds": time.perf_counter() - t0,
    }


@dataclass
class RandomPatchCifarAugmentedKernelConfig(RandomPatchCifarConfig):
    patches_per_image: int = 4
    aug_patch: int = 24
    flip_chance: float = 0.5
    gamma: float = 2e-4
    kernel_block: int = 2048
    kernel_epochs: int = 1
    checkpoint_dir: Optional[str] = None
    blocks_before_checkpoint: int = 25


def run_random_patch_cifar_augmented_kernel(
    config: RandomPatchCifarAugmentedKernelConfig,
):
    """The 13th reference app (RandomPatchCifarAugmentedKernel.scala:
    1-190): random 24x24 crops + p=0.5 horizontal flips at train,
    shuffled; whitened-random-patch featurization; KernelRidgeRegression
    with optional block-loop checkpointing (`--checkpoint-dir`, :176);
    center/corner crops WITH flips (10 augmentations) at test, scores
    averaged per source image by AugmentedExamplesEvaluator."""
    from ..nodes.images.core import RandomImageTransformer
    from ..utils.images import flip_horizontal

    train, test = _load(config)
    t0 = time.perf_counter()
    ap = config.aug_patch

    # augment train: random crops, then horizontal flips with p=0.5.
    # Per-stage seed offsets keep the crop / flip / shuffle streams
    # independent (one shared PCG64 state would correlate the draws).
    patcher = RandomPatcher(config.patches_per_image, ap, ap, seed=config.seed)
    aug_train = RandomImageTransformer(
        config.flip_chance, flip_horizontal, seed=config.seed + 1
    ).apply_batch(patcher.apply_batch(train.data))
    aug_labels = np.repeat(
        np.asarray(train.labels.numpy()), config.patches_per_image
    )
    # shuffle images and labels with ONE permutation (the reference zips,
    # shuffles, and unzips — Shuffler over (Image, label) pairs); the
    # image gather stays on device, only the permutation crosses over
    import jax.numpy as jnp

    perm = np.random.default_rng(config.seed + 2).permutation(len(aug_labels))
    perm_dev = jnp.asarray(perm)
    aug_train = aug_train.map_batches(lambda a: jnp.take(a, perm_dev, axis=0))
    aug_labels = aug_labels[perm]

    filters, whitener = learn_filters(aug_train, config)
    featurizer = (
        FusedBatchTransformer(
            [
                PixelScaler(),
                Convolver(filters, ap, ap, 3, whitener=whitener),
                SymmetricRectifier(alpha=config.alpha),
                Pooler(max(ap // 2 - 1, 1), ap // 2, pool_fn="sum"),
                ImageVectorizer(),
            ],
            microbatch=config.microbatch,
        ).to_pipeline()
        >> Cacher("features")
    )
    label_ind = ClassLabelIndicatorsFromInt(config.num_classes)(
        Dataset(aug_labels.astype(np.int32))
    ).get()
    predictor = featurizer.and_then(StandardScaler(), aug_train).and_then(
        KernelRidgeRegression(
            config.gamma, config.lam, config.kernel_block,
            config.kernel_epochs, seed=config.seed,
            checkpoint_dir=config.checkpoint_dir,
            blocks_before_checkpoint=config.blocks_before_checkpoint,
        ),
        aug_train, label_ind,
    )
    # test: center + corner crops AND their flips -> 10 augmented views
    cc = CenterCornerPatcher(ap, ap, with_flips=True)
    aug_test = cc.apply_batch(test.data)
    n_aug = 10
    ids = np.repeat(np.arange(test.data.count), n_aug)
    actuals = np.repeat(np.asarray(test.labels.numpy()), n_aug)
    scores = predictor(aug_test).get()
    m = AugmentedExamplesEvaluator(config.num_classes)(ids, scores, actuals)
    return {
        "test_error": m.error,
        "test_accuracy": m.accuracy,
        "seconds": time.perf_counter() - t0,
    }
