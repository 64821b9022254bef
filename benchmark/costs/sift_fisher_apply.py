"""`costs/sift_fisher.py` at the test set's rows: what one batch apply
of the fitted featurizer needs, images to normalized Fisher vectors (the
model's scoring product, 2 n d_feature k_classes, is a thousandth of it
and is left out)."""

from . import sift_fisher


def cost(sizes):
    return sift_fisher.cost(sizes, rows="num_test")
