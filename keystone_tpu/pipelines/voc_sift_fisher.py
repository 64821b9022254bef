"""VOCSIFTFisher (reference pipelines/images/voc/VOCSIFTFisher.scala:
23-157): `PixelScaler → GrayScaler → Cacher → SIFTExtractor(scaleStep 0)`
→ `ColumnSampler` → `ColumnPCAEstimator(descDim)` → `Cacher` →
`ColumnSampler` → `GMMFisherVectorEstimator(vocabSize)` → `FloatToDouble
→ MatrixVectorizer → NormalizeRows → SignedHellingerMapper →
NormalizeRows → Cacher` → `BlockLeastSquaresEstimator(4096, 1, lambda,
numFeatures 2·descDim·vocabSize)` → `MeanAveragePrecisionEvaluator`. The
reference's JNI VLFeat/enceval calls are the XLA SIFT/GMM/FV programs.

`VOCSIFTFisherConfig`'s defaults are the source's (`SIFTFisherConfig`:
descDim 80, vocabSize 256, scaleStep 0, lambda 0.5, numPcaSamples and
numGmmSamples 1e6; SIFT at `SIFTExtractor`'s step 3, bin 4, 4 scales).
This checkout has no copy of the Scala sources: the numbers are issue
40's reading of them, agreeing with `SURVEY.md` 3.5, and the benchmark's
configuration lists them under `assumed`. `FloatToDouble` has no place
on a TPU (float32 throughout) and is left out.

`build_pipeline(train, config)` is the lazy predictor over a device
`Dataset` of equal-sized images (n, H, W, 3) with multi-hot labels
(n, classes): under `PipelineEnv`'s default optimizer the chain runs as
fused programs a microbatch of images at a time (three passes of SIFT
over the training set: the PCA's sample, the mixture's sample, the
Fisher vectors), the samplers take their rows on the device, and the
only things held whole are the grayscale images, the two samples and
the features. A `HostDataset` of images of mixed sizes (what the
loaders give) goes the host way: bucketed SIFT dispatches, the samples
collected on the host.

Without data paths the app runs on a synthetic stand-in at a small CPU
size (48 x 48 images), not VOC's shape."""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np

from ..data.dataset import Dataset, HostDataset
from ..evaluation import MeanAveragePrecisionEvaluator
from ..loaders.csv_loader import LabeledData
from ..loaders.image_loaders import voc_loader
from ..nodes.images import GMMFisherVectorEstimator, SIFTExtractor
from ..nodes.images.core import GrayScaler, PixelScaler
from ..nodes.learning import BlockLeastSquaresEstimator, ColumnPCAEstimator
from ..nodes.stats import ColumnSampler, NormalizeRows, SignedHellingerMapper
from ..nodes.util import Cacher, MatrixVectorizer
from ..workflow import Pipeline, Transformer


@dataclass
class VOCSIFTFisherConfig:
    train_tar: Optional[str] = None
    train_labels: Optional[str] = None
    test_tar: Optional[str] = None
    test_labels: Optional[str] = None
    num_classes: int = 20
    pca_dims: int = 80  # descDim
    gmm_k: int = 256  # vocabSize
    gmm_iters: int = 30  # a fixed count where the source stops by tolerance
    sift_step: int = 3
    sift_bin: int = 4
    num_scales: int = 4
    scale_step: int = 0
    num_pca_samples: int = 1_000_000
    num_gmm_samples: int = 1_000_000
    lam: float = 0.5
    block_size: int = 4096
    bcd_iters: int = 1
    seed: int = 0
    # the synthetic stand-in's sizes (used when no train_tar)
    n_synth: int = 60
    synth_side: int = 48
    # sideband model files (reference --pcaFile / --gmmMeanFile /
    # --gmmVarFile / --gmmWtsFile, VOCSIFTFisher.scala:49-67): when set,
    # the corresponding fit is skipped and the model loaded from CSV
    pca_file: Optional[str] = None
    gmm_mean_file: Optional[str] = None
    gmm_var_file: Optional[str] = None
    gmm_wts_file: Optional[str] = None


def multi_hot(label_lists, num_classes: int) -> np.ndarray:
    """(n, classes) float32 indicators of per-image label lists."""
    out = np.zeros((len(label_lists), num_classes), np.float32)
    for i, labels in enumerate(label_lists):
        out[i, list(labels)] = 1.0
    return out


def _synthetic_voc(n, num_classes, noise_seed, side=48, class_seed=1234):
    """Equal-sized images with one or two of ``num_classes`` labels each,
    as `LabeledData` on the device. Class templates come from
    ``class_seed``, so train and test share the classes."""
    crng = np.random.default_rng(class_seed)
    templates = crng.uniform(0, 255, size=(num_classes, side, side, 3)).astype(np.float32)
    rng = np.random.default_rng(noise_seed)
    images = np.zeros((n, side, side, 3), np.float32)
    label_lists = []
    for i in range(n):
        labs = sorted(set(rng.integers(0, num_classes, size=rng.integers(1, 3)).tolist()))
        for l in labs:
            images[i] += templates[l] / len(labs)
        images[i] += 20.0 * rng.normal(size=images[i].shape).astype(np.float32)
        label_lists.append(labs)
    return LabeledData(labels=Dataset(multi_hot(label_lists, num_classes)),
                       data=Dataset(np.clip(images, 0, 255)))


def _sift(config: VOCSIFTFisherConfig) -> Pipeline:
    return (
        PixelScaler().to_pipeline()
        >> GrayScaler(channel=False)  # (H, W): see GrayScaler
        >> Cacher("voc-gray")
        >> SIFTExtractor(config.sift_step, config.sift_bin,
                         config.num_scales, config.scale_step)
    )


def build_featurizer(images, config: VOCSIFTFisherConfig) -> Pipeline:
    """The lazy featurizer, images to normalized Fisher vectors, its PCA
    and its mixture fitted on samples of ``images``' descriptors
    (``num_pca_samples // n`` and ``num_gmm_samples // n`` an image), or
    loaded from the sideband files."""
    n = len(images) if isinstance(images, HostDataset) else images.count
    sift = _sift(config)
    # PCA fit on subsampled descriptors (reference :53-55 uses withData on
    # the already-featurized sample, not and_then) — or loaded from the
    # sideband file (reference :49-56)
    if config.pca_file:
        from ..nodes.learning.pca import BatchPCATransformer

        # reference sideband layout is (k × d): csvread(fname).t
        # (VOCSIFTFisher.scala:52); PCATransformer wants (d, k)
        pca_featurizer = sift >> BatchPCATransformer(
            np.loadtxt(config.pca_file, delimiter=",", ndmin=2).T
            .astype(np.float32)
        )
    else:
        sampled = (sift >> ColumnSampler(
            max(1, config.num_pca_samples // n), config.seed)).apply(images)
        pca_featurizer = sift.and_then(
            ColumnPCAEstimator(config.pca_dims).with_data(sampled)
        )
    # the source caches every image's reduced descriptors here; a plan
    # holds them only where they fit (workflow/fusion_rule.py)
    pca_featurizer = pca_featurizer >> Cacher("voc-pca-descriptors")
    if config.gmm_mean_file:
        from ..nodes.images import FisherVector
        from ..nodes.learning import GaussianMixtureModel

        if not (config.gmm_var_file and config.gmm_wts_file):
            raise ValueError(
                "--gmm-mean-file requires --gmm-var-file and --gmm-wts-file"
            )

        fisher = FisherVector(
            GaussianMixtureModel.load_csv(
                config.gmm_mean_file, config.gmm_var_file, config.gmm_wts_file
            )
        ).to_pipeline()
    else:
        fisher_sample = (pca_featurizer >> ColumnSampler(
            max(1, config.num_gmm_samples // n), config.seed + 1)
        ).apply(images)
        fisher = GMMFisherVectorEstimator(
            config.gmm_k, num_iters=config.gmm_iters, seed=config.seed
        ).with_data(fisher_sample)
    featurizer = (
        pca_featurizer.and_then(fisher)
        >> MatrixVectorizer()
        >> NormalizeRows()
        >> SignedHellingerMapper()
        >> NormalizeRows()
    )
    if isinstance(images, HostDataset):
        featurizer = featurizer >> _Stack()
    return featurizer >> Cacher("voc-features")


def build_pipeline(train: LabeledData, config: VOCSIFTFisherConfig) -> Pipeline:
    """The lazy predictor (images to class scores), its estimators bound
    to ``train``: images ``train.data`` and multi-hot labels
    ``train.labels`` (n, classes)."""
    return build_featurizer(train.data, config).and_then(
        BlockLeastSquaresEstimator(
            config.block_size, config.bcd_iters, config.lam),
        train.data,
        train.labels,
    )


def analyzable(config: Optional[VOCSIFTFisherConfig] = None):
    """Abstract VOC predictor graph for static validation:
    `build_pipeline`'s DAG wired over placeholder data. Returns
    ``(pipeline, source_spec)``."""
    from ..analysis import SpecDataset

    config = config or VOCSIFTFisherConfig(pca_dims=16, gmm_k=8)
    n, shape = 64, (64, 64, 3)
    train = LabeledData(
        labels=SpecDataset((config.num_classes,), np.float32, count=n,
                           name="voc-labels"),
        data=SpecDataset(shape, np.float32, count=n, name="voc-images"))
    return build_pipeline(train, config), shape


def _host_data(ds: HostDataset, num_classes: int) -> LabeledData:
    """What a loader gives (`MultiLabeledImage`s of mixed sizes) as
    images on the host and multi-hot labels on the device."""
    return LabeledData(
        labels=Dataset(multi_hot([x.labels for x in ds.items], num_classes)),
        data=HostDataset([x.image for x in ds.items]))


def run(config: VOCSIFTFisherConfig):
    if config.train_tar:
        train = _host_data(
            voc_loader(config.train_tar, config.train_labels),
            config.num_classes)
        test = _host_data(
            voc_loader(config.test_tar or config.train_tar,
                       config.test_labels or config.train_labels),
            config.num_classes)
    else:
        train = _synthetic_voc(config.n_synth, config.num_classes,
                               config.seed, config.synth_side)
        test = _synthetic_voc(config.n_synth // 3, config.num_classes,
                              config.seed + 1, config.synth_side)

    t0 = time.perf_counter()
    predictor = build_pipeline(train, config)
    scores = predictor(test.data).get()
    elapsed = time.perf_counter() - t0
    aps = MeanAveragePrecisionEvaluator(config.num_classes, multi_hot=True)(
        scores, test.labels)
    return {"map": float(aps.mean()), "aps": aps.tolist(), "seconds": elapsed}


class _Stack(Transformer):
    """HostDataset of equal-length vectors → device Dataset."""

    def apply(self, x):
        return x

    def apply_batch(self, data):
        if isinstance(data, HostDataset):
            return data.stack(dtype=np.float32)
        return data


def main(argv=None):
    defaults = VOCSIFTFisherConfig()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--train-tar")
    p.add_argument("--train-labels")
    p.add_argument("--test-tar")
    p.add_argument("--test-labels")
    p.add_argument("--num-classes", type=int, default=defaults.num_classes)
    p.add_argument("--pca-dims", type=int, default=defaults.pca_dims)
    p.add_argument("--gmm-k", type=int, default=defaults.gmm_k)
    p.add_argument("--gmm-iters", type=int, default=defaults.gmm_iters)
    p.add_argument("--scale-step", type=int, default=defaults.scale_step)
    p.add_argument("--num-pca-samples", type=int,
                   default=defaults.num_pca_samples)
    p.add_argument("--num-gmm-samples", type=int,
                   default=defaults.num_gmm_samples)
    p.add_argument("--lam", type=float, default=defaults.lam)
    p.add_argument("--n-synth", type=int, default=defaults.n_synth)
    p.add_argument("--seed", type=int, default=defaults.seed)
    p.add_argument("--pca-file")
    p.add_argument("--gmm-mean-file")
    p.add_argument("--gmm-var-file")
    p.add_argument("--gmm-wts-file")
    args = p.parse_args(argv)
    config = VOCSIFTFisherConfig(
        **{k: v for k, v in vars(args).items() if v is not None}
    )
    result = run(config)
    print(f"mAP={result['map']:.4f} time={result['seconds']:.1f}s")
    return result


if __name__ == "__main__":
    main()
