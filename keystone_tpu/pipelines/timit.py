"""TimitPipeline (reference pipelines/speech/TimitPipeline.scala:1-148):
pre-featurized TIMIT frames → `Pipeline.gather` of ``numCosines``
branches `CosineRandomFeatures(440, 4096, gamma, Gaussian or Cauchy)` →
`VectorCombiner` → `BlockLeastSquaresEstimator(4096, numEpochs, lambda)`
→ `MaxClassifier`, evaluated multiclass.

`TimitConfig`'s defaults are the source's own (`TimitConfig` in
TimitPipeline.scala: numCosines 50, gamma 0.05555, rfType gaussian,
lambda 0.0, numEpochs 5; `numCosineFeatures` 4,096 is a constant of the
file and is the solver's block size too, one block a branch). The frames
have 440 dimensions and the labels 147 classes (`timitDimension` and
`numClasses` of TimitFeaturesDataLoader.scala). This checkout has no
copy of the Scala sources: the numbers are those `PERF.md` section 7
recorded from them (row 0b), and the benchmark's configuration lists
them under `assumed`.

Without data paths the pipeline runs on a synthetic stand-in: a small
CPU default (8,192 frames of 12 classes, rows enough for a 4,096-wide
block's Gram to have full rank at lambda 0), not TIMIT's shape."""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, replace
from typing import Optional

import numpy as np

from ..evaluation import MulticlassClassifierEvaluator
from ..loaders.csv_loader import LabeledData
from ..loaders.text_loaders import timit_loader
from ..nodes.learning import BlockLeastSquaresEstimator
from ..nodes.stats import CosineRandomFeatures
from ..nodes.util import (
    Cacher,
    ClassLabelIndicatorsFromInt,
    MaxClassifier,
    VectorCombiner,
)
from ..workflow import Pipeline

#: the stand-in's class count: the real loader gives `num_classes`
SYNTH_CLASSES = 12


@dataclass
class TimitConfig:
    train_features: Optional[str] = None
    train_labels: Optional[str] = None
    test_features: Optional[str] = None
    test_labels: Optional[str] = None
    num_cosines: int = 50  # branches, the source's --numCosines
    num_cosine_features: int = 4096  # a branch, and a solver block
    gamma: float = 0.05555
    distribution: str = "gaussian"
    num_epochs: int = 5
    lam: float = 0.0
    num_classes: int = 147
    seed: int = 0
    # the synthetic stand-in's sizes (used when no train_features)
    n_synth: int = 8192
    synth_dim: int = 440


def _synthetic_timit(n, dim, num_classes, noise_seed, class_seed=1234):
    """Class-dependent frames — learnable stand-in. Class structure comes
    from `class_seed` so train/test splits share the same classes; only
    the noise/labels vary with `noise_seed`. The noise is isotropic, so
    the frames have full rank."""
    crng = np.random.default_rng(class_seed)
    latent = crng.normal(size=(num_classes, 16)).astype(np.float32) * 3.0
    embed = crng.normal(size=(16, dim)).astype(np.float32) / 4.0
    rng = np.random.default_rng(noise_seed)
    y = rng.integers(0, num_classes, n).astype(np.int32)
    X = latent[y] @ embed + 1.0 * rng.normal(size=(n, dim)).astype(np.float32)
    return LabeledData.from_arrays(y, X)


def cosine_branches(config: TimitConfig, dim: int):
    """The ``num_cosines`` random-feature branches, each with a seed of
    its own drawn from ``config.seed``."""
    seeds = np.random.SeedSequence(config.seed).generate_state(
        config.num_cosines)
    return [
        CosineRandomFeatures(
            dim, config.num_cosine_features, config.gamma,
            distribution=config.distribution, seed=int(s))
        for s in seeds
    ]


def _featurizer(config: TimitConfig, dim: int) -> Pipeline:
    return (
        Pipeline.gather(cosine_branches(config, dim))
        >> VectorCombiner()
        >> Cacher("timit-features")
    )


def _solver(config: TimitConfig) -> BlockLeastSquaresEstimator:
    return BlockLeastSquaresEstimator(
        config.num_cosine_features, config.num_epochs, config.lam)


def build_scorer(train: LabeledData, config: TimitConfig) -> Pipeline:
    """The lazy pipeline up to the class scores (n, k): what
    `build_pipeline` puts in front of `MaxClassifier`."""
    dim = train.data.array.shape[1]
    labels = ClassLabelIndicatorsFromInt(config.num_classes)(train.labels).get()
    return _featurizer(config, dim).and_then(
        _solver(config), train.data, labels)


def build_pipeline(train: LabeledData, config: TimitConfig) -> Pipeline:
    """The lazy predictor, its estimator bound to ``train``."""
    return build_scorer(train, config) >> MaxClassifier()


def analyzable(config: Optional[TimitConfig] = None):
    """Abstract predictor graph for static validation — see
    `keystone_tpu.analysis`. Returns ``(pipeline, source_spec)``."""
    from ..analysis import SpecDataset

    config = config or TimitConfig(num_cosines=2, num_cosine_features=64)
    dim, n = config.synth_dim, 256
    num_classes = min(config.num_classes, SYNTH_CLASSES)
    data = SpecDataset((dim,), np.float32, count=n, name="timit-data")
    raw_labels = SpecDataset((), np.int32, count=n, name="timit-labels")
    labels = ClassLabelIndicatorsFromInt(num_classes)(raw_labels)
    predictor = _featurizer(config, dim).and_then(
        _solver(config), data, labels) >> MaxClassifier()
    return predictor, (dim,)


def run(config: TimitConfig):
    if config.train_features:
        train = timit_loader(config.train_features, config.train_labels)
        test = timit_loader(
            config.test_features or config.train_features,
            config.test_labels or config.train_labels,
        )
    else:
        config = replace(
            config, num_classes=min(config.num_classes, SYNTH_CLASSES))
        train = _synthetic_timit(config.n_synth, config.synth_dim, config.num_classes, config.seed)
        test = _synthetic_timit(config.n_synth // 4, config.synth_dim, config.num_classes, config.seed + 1)

    predictor = build_pipeline(train, config)

    t0 = time.perf_counter()
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    train_eval = evaluator(predictor(train.data), train.labels)
    elapsed = time.perf_counter() - t0
    test_eval = evaluator(predictor(test.data), test.labels)
    return {
        "train_error": train_eval.error,
        "test_error": test_eval.error,
        "test_accuracy": test_eval.accuracy,
        "train_seconds": elapsed,
        "summary": test_eval.summary(),
    }


def main(argv=None):
    defaults = TimitConfig()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--train-features")
    p.add_argument("--train-labels")
    p.add_argument("--test-features")
    p.add_argument("--test-labels")
    p.add_argument("--num-cosines", type=int, default=defaults.num_cosines)
    p.add_argument("--num-cosine-features", type=int,
                   default=defaults.num_cosine_features)
    p.add_argument("--gamma", type=float, default=defaults.gamma)
    p.add_argument("--distribution", default=defaults.distribution,
                   choices=["gaussian", "cauchy"])
    p.add_argument("--num-epochs", type=int, default=defaults.num_epochs)
    p.add_argument("--lam", type=float, default=defaults.lam)
    p.add_argument("--n-synth", type=int, default=defaults.n_synth)
    p.add_argument("--seed", type=int, default=defaults.seed)
    args = p.parse_args(argv)
    config = TimitConfig(**{k: v for k, v in vars(args).items() if v is not None})
    result = run(config)
    print(result["summary"])
    print(
        f"train_error={result['train_error']:.4f} test_error={result['test_error']:.4f} "
        f"train_time={result['train_seconds']:.2f}s"
    )
    return result


if __name__ == "__main__":
    main()
