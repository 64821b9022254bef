"""End-to-end RandomPatchCifar on the synthetic learnable task (north-star
pipeline, SURVEY.md §3.4), small config for the CPU mesh."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `benchmark` lives at the root of the checkout
    sys.path.insert(0, REPO)

from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifarConfig, run


def test_random_patch_cifar_end_to_end():
    result = run(
        RandomPatchCifarConfig(
            num_filters=64,
            sample_patches=10_000,
            synth_train=320,
            synth_test=80,
            microbatch=64,
            block_size=512,
        )
    )
    # the synthetic task is fully separable for a working pipeline
    assert result["test_accuracy"] > 0.9, result["summary"]


def test_cifar_binary_loader_roundtrip(tmp_path):
    import numpy as np

    from keystone_tpu.loaders.cifar_loader import cifar_loader

    rng = np.random.default_rng(0)
    n = 20
    labels = rng.integers(0, 10, n, dtype=np.uint8)
    images = rng.integers(0, 256, size=(n, 3, 32, 32), dtype=np.uint8)
    records = np.concatenate(
        [labels[:, None], images.reshape(n, -1)], axis=1
    )
    path = tmp_path / "data_batch_1.bin"
    records.tofile(path)
    data = cifar_loader(str(path))
    assert data.data.count == n
    np.testing.assert_array_equal(data.labels.numpy(), labels)
    # HWC conversion: channel-planar source
    np.testing.assert_allclose(
        data.data.numpy()[0][:, :, 0], images[0, 0].astype(np.float32)
    )


def test_cifar_loader_on_checked_in_real_format_fixture():
    """100-record fixture in the EXACT CIFAR-10 binary layout (1 label
    byte + 3072 channel-planar bytes — CifarLoader.scala:21-51): record i
    has label i%10 and pixel value row*2 + label*10 + channel*5, so the
    loader's record framing, label extraction, and planar→HWC transpose
    are each pinned to known bytes (VERDICT r3 #6)."""
    import os

    import numpy as np

    from keystone_tpu.loaders.cifar_loader import cifar_loader

    path = os.path.join(os.path.dirname(__file__), "resources", "cifar_mini.bin")
    data = cifar_loader(path)
    assert data.data.count == 100
    labels = np.asarray(data.labels.numpy())
    np.testing.assert_array_equal(labels, np.arange(100) % 10)
    imgs = np.asarray(data.data.numpy())
    assert imgs.shape == (100, 32, 32, 3)
    # record 17 (label 7): channel c pixel at row r = r*2 + 70 + c*5
    r = np.arange(32)
    for c in range(3):
        want = np.clip(r * 2 + 7 * 10 + c * 5, 0, 255).astype(np.float32)
        np.testing.assert_array_equal(imgs[17, :, 5, c], want)


def test_random_patch_pipeline_on_real_images():
    """Fixture-scale REAL-image regression (VERDICT r1 item 2: real CIFAR
    binaries are unobtainable in this zero-egress env, so the full
    featurize+solve pipeline is exercised on natural-image statistics
    instead: 32x32 crops of two checked-in photographs, classified by
    source photo)."""
    import os

    import numpy as np
    from PIL import Image

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
    )

    res = os.path.join(os.path.dirname(__file__), "resources")

    def crops(name):
        img = np.asarray(Image.open(os.path.join(res, name)).convert("RGB"),
                         np.float32)
        h, w = img.shape[:2]
        out = [
            img[y : y + 32, x : x + 32]
            for y in range(0, h - 32, 32)
            for x in range(0, w - 32, 32)
        ]
        return np.stack(out)

    a, b = crops("gantrycrane.png"), crops("000012.jpg")
    X = np.concatenate([a, b])
    y = np.concatenate([np.zeros(len(a), np.int32), np.ones(len(b), np.int32)])
    rng = np.random.default_rng(0)
    order = rng.permutation(len(X))
    X, y = X[order], y[order]
    cut = int(len(X) * 0.8)

    class _Split:
        def __init__(self, X, y):
            self.data = Dataset(X)
            self.labels = Dataset(y)

    train, test = _Split(X[:cut], y[:cut]), _Split(X[cut:], y[cut:])
    config = RandomPatchCifarConfig(
        num_filters=32, num_classes=2, sample_patches=5_000, microbatch=64,
        block_size=256,
    )
    predictor = build_pipeline(train, config)
    ev = MulticlassClassifierEvaluator(2)
    acc = ev(predictor(test.data), test.labels).accuracy
    assert acc > 0.85, f"real-image crop classification accuracy {acc}"


def test_calibrated_difficulty_accuracy_band():
    """VERDICT r2 #2: the synthetic task at the benchmark's calibrated
    difficulty (noise=1.2, confusion=0.6) must land test accuracy in a
    nontrivial band — a solver-quality regression (broken centering, BCD
    convergence, precision) drops below it; an accidentally-trivialized
    generator saturates above it. Calibration measured 0.797 at this
    exact config (n=2000, 128 filters, seed 0; chance = 0.10)."""
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.loaders.cifar_loader import synthetic_cifar
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
    )
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.reset()
    train, test = synthetic_cifar(2000, 1000, seed=0, noise=1.2, confusion=0.6)
    pred = build_pipeline(train, RandomPatchCifarConfig(num_filters=128))
    acc = MulticlassClassifierEvaluator(10)(pred(test.data), test.labels).accuracy
    assert 0.68 <= acc <= 0.92, f"accuracy {acc} left the calibrated band"


# The solver regimes of `test_pipeline_fit_agrees_with_the_plain_reference`,
# as changes to `benchmark/configs/random_patch_cifar.json`: d = 2*2*(2*K)
# features at K filters. The reference solves an unpadded last block
# where the program pads it with zero columns, which at lambda 0 make the
# block's Gram singular, so the lambda-0 regime has three whole blocks.
REFERENCE_REGIMES = {
    "one_block_one_epoch":
        {"num_filters": 16, "block_size": 128, "bcd_iters": 1, "lam": 10.0},
    "padded_last_block_one_epoch":
        {"num_filters": 16, "block_size": 48, "bcd_iters": 1, "lam": 10.0},
    "padded_last_block_two_epochs":
        {"num_filters": 16, "block_size": 48, "bcd_iters": 2, "lam": 10.0},
    "three_blocks_two_epochs_lam_0":
        {"num_filters": 24, "block_size": 64, "bcd_iters": 2, "lam": 0.0},
}


@pytest.mark.parametrize("regime", sorted(REFERENCE_REGIMES))
def test_pipeline_fit_agrees_with_the_plain_reference(regime):
    """The pipeline as the benchmark's cell builds it (the adapter's
    `program_config`, then `build_pipeline`, fitted and applied under
    `PipelineEnv`'s default optimizer) against the benchmark's plain
    reference, which learns the same filters from the same seed and
    shares no featurizer or solver code with the program: the same
    prediction on every test row (float32 on the CPU; five seeds gave no
    differing row in any regime). One device, as in the cell: the
    reference's eager loop over blocks is not written for a mesh."""
    import jax
    import numpy as np

    from benchmark import files
    from benchmark.configs import random_patch_cifar as adapter
    from benchmark.reference import random_patch_cifar as reference
    from keystone_tpu.parallel.mesh import make_mesh, use_mesh
    from keystone_tpu.pipelines.random_patch_cifar import build_pipeline

    seed = 2**31 + 30
    changes = REFERENCE_REGIMES[regime]
    base = files.BenchFiles().sizes("random_patch_cifar")
    sizes = {**base, **changes, "num_train": 256, "num_test": 128,
             "sample_patches": 10_000,
             "feature_dim": 8 * changes["num_filters"],
             "assumed": {**base["assumed"], "microbatch": 32}}
    mesh = make_mesh(jax.devices()[:1])
    with use_mesh(mesh):
        train, test = adapter.make_data(sizes, seed, mesh)
        predictor = build_pipeline(train, adapter.program_config(sizes, seed))
        got = np.asarray(predictor(test.data).get().numpy())
        want = reference.predict(train, test, sizes, seed)
    (solver,) = [op for op in predictor.graph.operators.values()
                 if type(op).__name__ == "BlockLeastSquaresEstimator"]
    assert (solver.block_size, solver.num_iter, solver.lam) == (
        changes["block_size"], changes["bcd_iters"], changes["lam"])
    np.testing.assert_array_equal(got, want)
    labels = np.asarray(test.labels.numpy())
    assert np.mean(got == labels) > 0.5  # chance is 0.10


def test_fused_conv_vmem_accounting_lane_padding():
    """The fused conv kernel's VMEM block chooser must lane-pad k to 128
    (Mosaic pads the minor dim): ignoring it produced a real scoped-vmem
    OOM at k=16 on v5e (21.5 MB actual vs 8.9 MB estimated)."""
    from keystone_tpu.ops.pallas_kernels import _fused_conv_block_images

    # CIFAR geometry: 27x27 valid conv, pool 14 stride 13 -> 784 class-
    # ordered patch rows an image, 72 of partial sums to the pool dot
    # (`_pool_layout`), dp=128, cells=4
    b16 = _fused_conv_block_images(784, 72, 128, 16, 4)
    b256 = _fused_conv_block_images(784, 72, 128, 256, 4)
    # k=16 must be budgeted like k=64 (lane padding: kp=128 and k2p=128
    # for both — the pre-fix unpadded budget OOM'd live at k=16: 21.5 MB
    # actual vs 8.9 MB estimated). With the per-group sequential loop
    # the z transient does not scale with the block, and since PR 34
    # the rectified halves are never whole, so the block is what the
    # double-buffered patches leave room for: 401,408 bytes an image.
    b64 = _fused_conv_block_images(784, 72, 128, 64, 4)
    assert b16 == b64 == 22, (b16, b64)
    assert b256 == 20, b256


def test_fused_conv_is_chosen_from_the_backend_and_the_master_switch(
        monkeypatch):
    """On a TPU the fused conv kernel runs; anywhere else XLA's path
    does (the ledger judged the two paths in PR 27). Nothing stands
    between them but `pallas_kernels`, the master switch over every
    kernel."""
    import jax

    from keystone_tpu.ops import pallas_kernels as pk
    from keystone_tpu.workflow.env import config_override

    assert not pk.use_fused_conv()  # the tests' backend is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk.use_fused_conv()
    # the variable PR 27 left, deleted in PR 31, is read nowhere
    monkeypatch.setenv("KEYSTONE_DISABLE_FUSED_CONV", "1")
    assert pk.use_fused_conv()
    with config_override(pallas_kernels=False):
        assert not pk.use_fused_conv()
