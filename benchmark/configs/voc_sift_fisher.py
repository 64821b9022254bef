"""VOCSIFTFisher as a user builds it: `build_pipeline` of
`keystone_tpu.pipelines.voc_sift_fisher`, at the sizes of
`voc_sift_fisher.json`, under `PipelineEnv`'s default optimizer (no knob
handed over: every fused program's microbatch follows from the bytes a
row makes in it). The images are `benchmark.voc_images`'."""

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.loaders.csv_loader import LabeledData
from keystone_tpu.pipelines.voc_sift_fisher import (
    VOCSIFTFisherConfig,
    build_pipeline,
)

from .. import datagen, voc_images


def program_config(sizes, seed):
    return VOCSIFTFisherConfig(
        num_classes=sizes["num_classes"], pca_dims=sizes["pca_dims"],
        gmm_k=sizes["gmm_k"], gmm_iters=sizes["gmm_iters"],
        sift_step=sizes["sift_step"], sift_bin=sizes["sift_bin"],
        num_scales=sizes["num_scales"], scale_step=sizes["scale_step"],
        num_pca_samples=sizes["num_pca_samples"],
        num_gmm_samples=sizes["num_gmm_samples"], lam=sizes["lam"],
        block_size=sizes["solver_block"], bcd_iters=sizes["bcd_iters"],
        seed=datagen.program_seed(seed))


def make_data(sizes, seed, mesh):
    """(train, test) as `LabeledData` on ``mesh``, from the seed: uint8
    images (n, height, width, 3) and multi-hot labels (n, classes)."""
    assumed = sizes["assumed"]
    splits = voc_images.voc_like(
        sizes["num_train"], sizes["num_test"], seed,
        num_classes=sizes["num_classes"], height=sizes["image_height"],
        width=sizes["image_width"], texture=assumed["texture"],
        clutter=assumed["clutter"], noise=assumed["noise"])
    return tuple(
        LabeledData(labels=Dataset(labels, mesh=mesh),
                    data=Dataset(images, mesh=mesh))
        for images, labels in splits)


def build(train, sizes, seed):
    """The lazy predictor `Pipeline`, its estimators bound to ``train``."""
    return build_pipeline(train, program_config(sizes, seed))


def fitted_parts(fitted):
    """What a fit learned, taken out of the `FittedPipeline` the timed
    path made: (PCA components (128, pca_dims), the mixture, the linear
    model). The plain reference is handed the first two for the
    comparison of scores."""
    from keystone_tpu.nodes.images.fisher_vector import FisherVector
    from keystone_tpu.nodes.learning.block_ls import BlockLinearMapper
    from keystone_tpu.nodes.learning.pca import PCATransformer

    found = {}
    for op in fitted.graph.operators.values():
        stages = op._flat_stages() if hasattr(op, "_flat_stages") else [op]
        for stage in stages:
            for kind in (PCATransformer, FisherVector, BlockLinearMapper):
                if isinstance(stage, kind):
                    found[kind] = stage
    return (found[PCATransformer].components, found[FisherVector].gmm,
            found[BlockLinearMapper])
