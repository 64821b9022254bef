"""RandomPatchCifarKernel as a user builds it: `build_kernel_pipeline` of
`keystone_tpu.pipelines.cifar_variants`, at the sizes of
`random_patch_cifar_kernel.json`, under `PipelineEnv`'s default optimizer
(no knob handed over). The images are `random_patch_cifar`'s."""

from keystone_tpu.pipelines.cifar_variants import (
    RandomPatchCifarKernelConfig,
    build_kernel_pipeline,
)

from .. import datagen
from .random_patch_cifar import make_data  # noqa: F401


def program_config(sizes, seed):
    return RandomPatchCifarKernelConfig(
        num_filters=sizes["num_filters"], patch_size=sizes["patch_size"],
        patch_steps=sizes["patch_steps"], pool_size=sizes["pool_size"],
        pool_stride=sizes["pool_stride"], alpha=sizes["alpha"],
        lam=sizes["lam"], sample_patches=sizes["sample_patches"],
        gamma=sizes["gamma"], kernel_block=sizes["kernel_block"],
        kernel_epochs=sizes["num_epochs"], cache_kernel=sizes["cache_kernel"],
        num_classes=sizes["num_classes"], seed=datagen.program_seed(seed))


def build(train, sizes, seed):
    """The lazy predictor `Pipeline`, its estimators bound to ``train``."""
    return build_kernel_pipeline(train, program_config(sizes, seed))
