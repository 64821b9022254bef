"""End-to-end smoke tests for every example app on synthetic data
(small configs; the CLI registry is exercised too)."""

import numpy as np
import pytest


def test_timit_pipeline():
    from keystone_tpu.pipelines.timit import TimitConfig, run

    # two branches of 256 cosine features, a solver block each; the
    # defaults are the source's 50 x 4,096, 5 epochs, lambda 0
    r = run(TimitConfig(num_cosines=2, num_cosine_features=256, n_synth=1500,
                        synth_dim=128, num_classes=8))
    assert r["test_accuracy"] > 0.9, r["summary"]


def test_newsgroups_pipeline():
    from keystone_tpu.pipelines.text_pipelines import NewsgroupsConfig, run_newsgroups

    r = run_newsgroups(NewsgroupsConfig(n_synth=200))
    assert r["test_accuracy"] > 0.9, r["summary"]


def test_amazon_pipeline():
    from keystone_tpu.pipelines.text_pipelines import AmazonReviewsConfig, run_amazon

    r = run_amazon(AmazonReviewsConfig(n_synth=200))
    assert r["test_accuracy"] > 0.9


def test_stupid_backoff_pipeline():
    from keystone_tpu.pipelines.text_pipelines import (
        StupidBackoffConfig,
        run_stupid_backoff,
    )

    r = run_stupid_backoff(StupidBackoffConfig(n_synth=50))
    assert np.isfinite(r["mean_log_score"])
    assert r["num_trigrams"] > 0


def test_linear_pixels():
    from keystone_tpu.pipelines.cifar_variants import (
        LinearPixelsConfig,
        run_linear_pixels,
    )

    r = run_linear_pixels(LinearPixelsConfig(synth_train=300, synth_test=80))
    assert r["test_accuracy"] > 0.8


def test_random_cifar_kernel():
    from keystone_tpu.pipelines.cifar_variants import (
        RandomPatchCifarKernelConfig,
        run_random_patch_cifar_kernel,
    )

    r = run_random_patch_cifar_kernel(
        RandomPatchCifarKernelConfig(
            synth_train=240, synth_test=60, num_filters=48, sample_patches=5000,
            microbatch=64, kernel_block=128,
        )
    )
    assert r["test_accuracy"] > 0.9


def test_random_patch_cifar_augmented():
    from keystone_tpu.pipelines.cifar_variants import (
        RandomPatchCifarAugmentedConfig,
        run_random_patch_cifar_augmented,
    )

    r = run_random_patch_cifar_augmented(
        RandomPatchCifarAugmentedConfig(
            synth_train=200, synth_test=50, num_filters=48, sample_patches=5000,
            microbatch=64, block_size=512,
        )
    )
    assert r["test_accuracy"] > 0.85


def test_random_patch_cifar_augmented_kernel(tmp_path, monkeypatch):
    """The 13th app (RandomPatchCifarAugmentedKernel.scala:1-190):
    augmented featurization + flips + shuffle + KRR with checkpoint dir
    + flip-augmented test eval."""
    import os

    from keystone_tpu.pipelines.cifar_variants import (
        RandomPatchCifarAugmentedKernelConfig,
        run_random_patch_cifar_augmented_kernel,
    )

    # the solver removes its checkpoint on successful completion, so
    # observe the atomic os.replace publishes to prove --checkpoint-dir
    # was threaded through to the KRR block loop
    writes = []
    real_replace = os.replace
    monkeypatch.setattr(
        os, "replace",
        lambda src, dst: (writes.append(dst), real_replace(src, dst))[1],
    )
    r = run_random_patch_cifar_augmented_kernel(
        RandomPatchCifarAugmentedKernelConfig(
            synth_train=200, synth_test=50, num_filters=48, sample_patches=5000,
            microbatch=64, kernel_block=128, gamma=2e-3, lam=0.1,
            checkpoint_dir=str(tmp_path), blocks_before_checkpoint=2,
        )
    )
    assert r["test_accuracy"] > 0.85
    ckpt_writes = [d for d in writes if str(tmp_path) in str(d)]
    assert ckpt_writes, "KRR wrote no checkpoints under --checkpoint-dir"
    # and the completed fit cleaned its checkpoint up
    assert not any(f.startswith("krr_") for f in os.listdir(tmp_path))


def test_voc_sift_fisher():
    from keystone_tpu.pipelines.voc_sift_fisher import VOCSIFTFisherConfig, run

    r = run(VOCSIFTFisherConfig(n_synth=30, num_classes=4, gmm_k=4, pca_dims=16))
    assert r["map"] > 0.6


def test_imagenet_sift_lcs_fv():
    from keystone_tpu.pipelines.imagenet_sift_lcs_fv import (
        ImageNetSiftLcsFVConfig,
        run,
    )

    r = run(ImageNetSiftLcsFVConfig(n_synth=40, num_classes=5, gmm_k=4, pca_dims=16))
    assert r["test_accuracy"] > 0.6


def test_cli_registry_lists_and_dispatches(capsys):
    from keystone_tpu.__main__ import main

    assert main(["--help"]) == 0
    out = capsys.readouterr().out
    assert "pipelines.images.cifar.RandomPatchCifar" in out
    assert main(["NoSuchPipeline"]) == 2


def test_cifar_kernel_app_runs_from_the_launcher(capsys):
    """`python -m keystone_tpu RandomPatchCifarKernel` at a tiny size:
    the source's flag names reach `build_kernel_pipeline`, two epochs
    over three cached blocks."""
    from keystone_tpu import telemetry
    from keystone_tpu.__main__ import main

    formed = telemetry.counter("solver.kernel_blocks_formed")
    reused = telemetry.counter("solver.kernel_blocks_reused")
    before = formed.value, reused.value
    assert main([
        "pipelines.images.cifar.RandomPatchCifarKernel",
        "--synth-train", "240", "--synth-test", "60", "--num-filters", "16",
        "--gamma", "2e-3", "--lam", "0.1", "--kernel-block", "80",
        "--kernel-epochs", "2", "--cache-kernel"]) == 0
    out = capsys.readouterr().out
    assert "test_error=" in out
    assert float(out.split("test_error=")[1].split()[0]) < 0.1
    # the train error's and the test's pipelines share one fit
    assert (formed.value - before[0], reused.value - before[1]) == (3, 3)


def test_voc_sideband_model_files(tmp_path):
    """Reference --pcaFile/--gmm*File flags (VOCSIFTFisher.scala:49-67):
    precomputed PCA + GMM load from CSV and skip fitting."""
    import numpy as np

    from keystone_tpu.pipelines.voc_sift_fisher import VOCSIFTFisherConfig, run

    d, p, k = 128, 8, 4  # SIFT dim, PCA dims, GMM components
    rng = np.random.default_rng(0)
    # reference on-disk layouts: PCA is (k x d) (csvread(...).t at
    # VOCSIFTFisher.scala:52), GMM means/vars are dims x clusters
    pca = rng.normal(size=(p, d)).astype(np.float32)
    np.savetxt(tmp_path / "pca.csv", pca, delimiter=",")
    np.savetxt(tmp_path / "m.csv", rng.normal(size=(p, k)), delimiter=",")
    np.savetxt(tmp_path / "v.csv", rng.uniform(0.5, 1.5, size=(p, k)), delimiter=",")
    np.savetxt(tmp_path / "w.csv", np.full(k, 1.0 / k), delimiter=",")

    cfg = VOCSIFTFisherConfig(
        num_classes=3, n_synth=9, gmm_k=k, pca_dims=p,
        pca_file=str(tmp_path / "pca.csv"),
        gmm_mean_file=str(tmp_path / "m.csv"),
        gmm_var_file=str(tmp_path / "v.csv"),
        gmm_wts_file=str(tmp_path / "w.csv"),
    )
    result = run(cfg)
    assert np.isfinite(result["map"])
    assert len(result["aps"]) == 3
