"""CIFAR-10 binary loader (reference loaders/CifarLoader.scala:13-52:
1 label byte + 3072 channel-planar bytes per record) plus a learnable
synthetic CIFAR-like generator for environments without the dataset.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

from ..data.dataset import Dataset
from .csv_loader import LabeledData

RECORD_BYTES = 1 + 3072


def cifar_loader(path: str, mesh=None) -> LabeledData:
    """Read CIFAR-10 binary batches (a file or a directory of *.bin)."""
    files = (
        [os.path.join(path, f) for f in sorted(os.listdir(path)) if f.endswith(".bin")]
        if os.path.isdir(path)
        else [path]
    )
    # native multithreaded parse (channel-planar -> HWC); numpy fallback.
    # The disk read of batch k+1 runs in a bounded background queue while
    # batch k parses (prefetch_iterator is a no-op for a single file or
    # with the overlap engine disabled); per-file parse + concatenate is
    # record-wise identical to parsing the concatenated records.
    from ..utils.batching import prefetch_iterator
    from ..utils.native_io import parse_cifar

    def read(f):
        raw = np.fromfile(f, dtype=np.uint8)
        if raw.size % RECORD_BYTES:
            raise ValueError(
                f"{f}: size {raw.size} is not a multiple of {RECORD_BYTES}")
        return raw.reshape(-1, RECORD_BYTES)

    parsed = [
        parse_cifar(records)
        for records in prefetch_iterator(read(f) for f in files)
    ]
    if len(parsed) == 1:
        images, labels = parsed[0]
    else:
        images = np.concatenate([p[0] for p in parsed])
        labels = np.concatenate([p[1] for p in parsed])
    return LabeledData(
        labels=Dataset(labels, mesh=mesh), data=Dataset(images, mesh=mesh)
    )


def synthetic_cifar(
    n_train: int = 2000,
    n_test: int = 500,
    num_classes: int = 10,
    seed: int = 0,
    mesh=None,
    noise: float = 0.6,
    confusion: float = 0.0,
) -> Tuple[LabeledData, LabeledData]:
    """A learnable CIFAR-shaped task: each class is a smooth random
    template warped by random shifts + noise. Pipelines that work on real
    CIFAR separate these classes; broken featurization drops to chance.

    `noise` scales the per-pixel Gaussian noise; `confusion` > 0 mixes
    each sample's template toward a random OTHER class's template by a
    per-sample weight ~ Uniform(0, confusion), creating genuinely
    ambiguous examples (irreducible class overlap). Together they place
    the best attainable accuracy in a nontrivial, calibratable band —
    the benchmark and `chip_smoke.py` assert that band so
    solver-quality regressions (broken centering, BCD convergence,
    precision) fail loudly instead of hiding behind a trivially
    separable task."""
    rng = np.random.default_rng(seed)
    # smooth class templates (low-frequency patterns)
    freqs = rng.normal(size=(num_classes, 4, 2))
    phases = rng.uniform(0, 2 * np.pi, size=(num_classes, 4))
    amps = rng.uniform(0.5, 1.0, size=(num_classes, 4, 3))
    yy, xx = np.meshgrid(np.arange(32), np.arange(32), indexing="ij")

    def template(c):
        img = np.zeros((32, 32, 3), np.float32)
        for i in range(4):
            wave = np.sin(
                freqs[c, i, 0] * yy / 5.0 + freqs[c, i, 1] * xx / 5.0 + phases[c, i]
            )
            img += wave[:, :, None] * amps[c, i][None, None, :]
        return img

    templates = np.stack([template(c) for c in range(num_classes)])

    def make(n, seed2):
        r = np.random.default_rng(seed2)
        labels = r.integers(0, num_classes, size=n).astype(np.int32)
        images = templates[labels].copy()
        if confusion > 0.0:
            other = (labels + r.integers(1, num_classes, size=n)) % num_classes
            mix = r.uniform(0.0, confusion, size=n).astype(np.float32)
            images = (1.0 - mix[:, None, None, None]) * images + mix[
                :, None, None, None
            ] * templates[other]
        # random circular shifts + noise
        for i in range(n):
            sy, sx = r.integers(-4, 5, size=2)
            images[i] = np.roll(images[i], (sy, sx), axis=(0, 1))
        images += noise * r.normal(size=images.shape).astype(np.float32)
        images = (images - images.min()) / (images.max() - images.min()) * 255.0
        return LabeledData(
            labels=Dataset(labels, mesh=mesh),
            data=Dataset(images.astype(np.float32), mesh=mesh),
        )

    return make(n_train, seed + 1), make(n_test, seed + 2)
