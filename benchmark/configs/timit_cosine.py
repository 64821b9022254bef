"""TimitPipeline as a user builds it: `build_pipeline` of
`keystone_tpu.pipelines.timit`, at the sizes of `timit_cosine.json`,
under `PipelineEnv`'s default optimizer."""

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.pipelines.timit import TimitConfig, build_pipeline

from .. import datagen, timit_frames


def program_config(sizes, seed):
    return TimitConfig(
        num_cosines=sizes["num_cosines"],
        num_cosine_features=sizes["num_cosine_features"],
        gamma=sizes["gamma"], distribution=sizes["distribution"],
        num_epochs=sizes["bcd_iters"], lam=sizes["lam"],
        num_classes=sizes["num_classes"], seed=datagen.program_seed(seed))


def make_data(sizes, seed, mesh):
    """(train, test) as `LabeledData` on ``mesh``, from the seed."""
    splits = timit_frames.timit_like(
        sizes["num_train"], sizes["num_test"], seed,
        num_classes=sizes["num_classes"], dim=sizes["input_dim"],
        signal=sizes["assumed"]["signal"])
    return tuple(
        LabeledData(labels=Dataset(labels, mesh=mesh),
                    data=Dataset(frames, mesh=mesh))
        for frames, labels in splits)


def build(train, sizes, seed):
    """The lazy predictor `Pipeline`, its estimator bound to ``train``."""
    return build_pipeline(train, program_config(sizes, seed))
