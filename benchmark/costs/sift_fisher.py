"""Operations and bytes of featurizing the training set once, images to
normalized Fisher vectors (`nodes/images/sift.py`, `nodes/learning/pca.py`,
`nodes/images/fisher_vector.py`), from the shapes, whatever implements
it and however often the program goes over the images (a fit makes three
passes of SIFT today: the share reads the lower for it).

An image (h x w) once through the stencils: per scale a separable
Gaussian of 2 ceil(4 sigma) + 1 taps on one map and a separable triangle
of 2 binSize - 1 taps on eight orientation maps, two operations a tap a
pixel a direction (gradients, the arctangent and the binning's weights
are not counted). Then the projection of its nd descriptors onto the
PCA's components, 2 nd 128 d, and the Fisher encoding, 8 nd d k (two
products for the posteriors, two for the moments). Bytes: the image read
once in float32, the descriptors written and read once, the reduced
descriptors written and read once, the Fisher vector written.

PCA and the encoding run at `highest` matmul precision and the stencils
on the vector unit, so the honest ceiling is a fraction of the bf16 peak
the share is taken of; PERF.md says so beside the number."""

import math


def image_cost(sizes):
    h, w = sizes["image_height"], sizes["image_width"]
    nd, D = sizes["descriptors_per_image"], sizes["descriptor_dim"]
    d, k = sizes["pca_dims"], sizes["gmm_k"]
    stencil = 0
    for s in range(sizes["num_scales"]):
        bs = sizes["sift_bin"] + 2 * s
        gauss = 2 * max(math.ceil(4.0 * bs / 6.0), 1) + 1
        stencil += 2 * 2 * h * w * (gauss + 8 * (2 * bs - 1))
    return {"flops": stencil + 2 * nd * D * d + 8 * nd * d * k,
            "bytes": 4 * (h * w + 2 * nd * D + 2 * nd * d + 2 * d * k)}


def cost(sizes, rows="num_train"):
    one = image_cost(sizes)
    return {key: sizes[rows] * value for key, value in one.items()}
