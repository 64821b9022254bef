"""RandomPatchCifar as a user builds it: `build_pipeline` of
`keystone_tpu.pipelines.random_patch_cifar`, at the sizes of
`random_patch_cifar.json`."""

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.pipelines.random_patch_cifar import (
    RandomPatchCifarConfig,
    build_pipeline,
)

from keystone_tpu.workflow import PipelineEnv
from keystone_tpu.workflow.optimizer import DefaultOptimizer

from .. import datagen


def program_config(sizes, seed):
    return RandomPatchCifarConfig(
        num_filters=sizes["num_filters"], patch_size=sizes["patch_size"],
        patch_steps=sizes["patch_steps"], pool_size=sizes["pool_size"],
        pool_stride=sizes["pool_stride"], alpha=sizes["alpha"],
        lam=sizes["lam"], sample_patches=sizes["sample_patches"],
        block_size=sizes["block_size"], bcd_iters=sizes["bcd_iters"],
        num_classes=sizes["num_classes"],
        microbatch=sizes["assumed"]["microbatch"],
        seed=datagen.program_seed(seed))


def make_data(sizes, seed, mesh):
    """(train, test) as `LabeledData` on ``mesh``, from the seed."""
    splits = datagen.cifar_like(
        sizes["num_train"], sizes["num_test"], seed,
        num_classes=sizes["num_classes"], side=sizes["image_height"],
        noise=sizes["assumed"]["noise"],
        confusion=sizes["assumed"]["confusion"])
    return tuple(
        LabeledData(labels=Dataset(labels, mesh=mesh),
                    data=Dataset(images, mesh=mesh))
        for images, labels in splits)


def build(train, sizes, seed):
    """The lazy predictor `Pipeline`, its estimators bound to ``train``.
    The optimizer fuses the featurizer with its neighbours into programs
    of its own, at its own microbatch (2,048 images by default) and not
    the pipeline's, so a user at this width hands it the same number
    through `PipelineEnv.set_optimizer`; the harness resets the
    environment before every fit."""
    config = program_config(sizes, seed)
    PipelineEnv.get().set_optimizer(
        DefaultOptimizer(fusion_microbatch=config.microbatch))
    return build_pipeline(train, config)
