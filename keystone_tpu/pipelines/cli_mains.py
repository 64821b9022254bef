"""argparse mains for the smaller example apps (each mirrors the
reference app's scopt flags)."""

from __future__ import annotations

import argparse


def _cifar_parser(desc):
    p = argparse.ArgumentParser(description=desc)
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--num-filters", type=int, default=256)
    p.add_argument("--lam", type=float, default=10.0)
    p.add_argument("--synth-train", type=int, default=1000)
    p.add_argument("--synth-test", type=int, default=250)
    p.add_argument("--seed", type=int, default=0)
    return p


def linear_pixels_main(argv=None):
    from .cifar_variants import LinearPixelsConfig, run_linear_pixels

    p = argparse.ArgumentParser(description="LinearPixels")
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--lam", type=float, default=1.0)
    p.add_argument("--synth-train", type=int, default=1000)
    p.add_argument("--synth-test", type=int, default=250)
    args = p.parse_args(argv)
    r = run_linear_pixels(
        LinearPixelsConfig(**{k: v for k, v in vars(args).items() if v is not None})
    )
    print(f"test_error={r['test_error']:.4f} time={r['seconds']:.1f}s")
    return r


def random_cifar_main(argv=None):
    from .cifar_variants import RandomCifarConfig, run_random_cifar

    args = _cifar_parser("RandomCifar").parse_args(argv)
    r = run_random_cifar(
        RandomCifarConfig(**{k: v for k, v in vars(args).items() if v is not None})
    )
    print(f"test_error={r['test_error']:.4f} time={r['seconds']:.1f}s")
    return r


def cifar_kernel_main(argv=None):
    from .cifar_variants import (
        RandomPatchCifarKernelConfig,
        run_random_patch_cifar_kernel,
    )

    # the source's flags and RandomPatchCifarKernelConfig's defaults for
    # this app (numFilters 100, gamma 2e-4, blockSize 5000, numEpochs 1,
    # cacheKernel true)
    p = _cifar_parser("RandomPatchCifarKernel")
    p.set_defaults(num_filters=100)
    p.add_argument("--gamma", type=float, default=2e-4)
    p.add_argument("--kernel-block", type=int, default=5000)
    p.add_argument("--kernel-epochs", type=int, default=1)
    p.add_argument("--cache-kernel", default=True,
                   action=argparse.BooleanOptionalAction)
    args = p.parse_args(argv)
    r = run_random_patch_cifar_kernel(
        RandomPatchCifarKernelConfig(
            **{k: v for k, v in vars(args).items() if v is not None}
        )
    )
    print(f"test_error={r['test_error']:.4f} time={r['seconds']:.1f}s")
    return r


def cifar_augmented_main(argv=None):
    from .cifar_variants import (
        RandomPatchCifarAugmentedConfig,
        run_random_patch_cifar_augmented,
    )

    p = _cifar_parser("RandomPatchCifarAugmented")
    p.add_argument("--patches-per-image", type=int, default=4)
    p.add_argument("--aug-patch", type=int, default=24)
    args = p.parse_args(argv)
    r = run_random_patch_cifar_augmented(
        RandomPatchCifarAugmentedConfig(
            **{k: v for k, v in vars(args).items() if v is not None}
        )
    )
    print(f"test_error={r['test_error']:.4f} time={r['seconds']:.1f}s")
    return r


def cifar_augmented_kernel_main(argv=None):
    from .cifar_variants import (
        RandomPatchCifarAugmentedKernelConfig,
        run_random_patch_cifar_augmented_kernel,
    )

    p = _cifar_parser("RandomPatchCifarAugmentedKernel")
    p.add_argument("--patches-per-image", type=int, default=4)
    p.add_argument("--aug-patch", type=int, default=24)
    p.add_argument("--flip-chance", type=float, default=0.5)
    p.add_argument("--gamma", type=float, default=2e-4)
    p.add_argument("--kernel-block", type=int, default=2048)
    p.add_argument("--kernel-epochs", type=int, default=1)
    p.add_argument("--checkpoint-dir")
    p.add_argument("--blocks-before-checkpoint", type=int, default=25)
    args = p.parse_args(argv)
    r = run_random_patch_cifar_augmented_kernel(
        RandomPatchCifarAugmentedKernelConfig(
            **{k: v for k, v in vars(args).items() if v is not None}
        )
    )
    print(f"test_error={r['test_error']:.4f} time={r['seconds']:.1f}s")
    return r


def newsgroups_main(argv=None):
    from .text_pipelines import NewsgroupsConfig, run_newsgroups

    p = argparse.ArgumentParser(description="NewsgroupsPipeline")
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--common-features", type=int, default=100_000)
    p.add_argument("--n-synth", type=int, default=400)
    args = p.parse_args(argv)
    r = run_newsgroups(
        NewsgroupsConfig(**{k: v for k, v in vars(args).items() if v is not None})
    )
    print(r["summary"])
    print(f"test_error={r['test_error']:.4f} time={r['seconds']:.1f}s")
    return r


def amazon_main(argv=None):
    from .text_pipelines import AmazonReviewsConfig, run_amazon

    p = argparse.ArgumentParser(description="AmazonReviewsPipeline")
    p.add_argument("--data-path")
    p.add_argument("--common-features", type=int, default=100_000)
    p.add_argument("--lam", type=float, default=1e-3)
    p.add_argument("--n-synth", type=int, default=400)
    args = p.parse_args(argv)
    r = run_amazon(
        AmazonReviewsConfig(**{k: v for k, v in vars(args).items() if v is not None})
    )
    print(f"accuracy={r['test_accuracy']:.4f} f1={r['f1']:.4f}")
    return r


def stupid_backoff_main(argv=None):
    from .text_pipelines import StupidBackoffConfig, run_stupid_backoff

    p = argparse.ArgumentParser(description="StupidBackoffPipeline")
    p.add_argument("--data-path")
    p.add_argument("--n-synth", type=int, default=200)
    args = p.parse_args(argv)
    r = run_stupid_backoff(
        StupidBackoffConfig(**{k: v for k, v in vars(args).items() if v is not None})
    )
    print(
        f"mean_log_score={r['mean_log_score']:.4f} vocab={r['vocab']} "
        f"trigrams={r['num_trigrams']}"
    )
    return r
