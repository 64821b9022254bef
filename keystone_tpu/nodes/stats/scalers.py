"""Feature standardization (reference nodes/stats/StandardScaler.scala:36-60).

The reference computes per-feature mean/std with a
`treeAggregate(MultivariateOnlineSummarizer)` over partitions; here the
moments are one jitted reduction over the data-sharded array — XLA GSPMD
lowers the sums to an all-reduce over the mesh's ``data`` axis. Padded
rows are zero so raw sums are exact; only ``count`` matters for
normalization.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.pipeline import Estimator, Transformer


@partial(jax.jit, static_argnames=("normalize_std",))
@jax.named_scope("ks.StandardScaler.moments")
def _moments(X, count, normalize_std: bool):
    s = jnp.sum(X, axis=0)
    s2 = jnp.sum(X * X, axis=0)
    mean = s / count
    if normalize_std:
        # unbiased variance, matching MLlib's summarizer
        var = (s2 - count * mean * mean) / jnp.maximum(count - 1.0, 1.0)
        std = jnp.sqrt(jnp.maximum(var, 0.0))
        std = jnp.where(std == 0.0, 1.0, std)
    else:
        std = jnp.ones_like(mean)
    return mean, std


@jax.jit
@jax.named_scope("ks.StandardScaler.scale")
def _scale(X, mean, std, mask):
    return (X - mean) / std * mask[:, None]


class StandardScalerModel(Transformer):
    """(x - mean) / std. Masked so padded rows stay zero."""

    fusable = True   # pure elementwise apply — joins fused chains
    chunkable = True  # distributes over host chunks (KP302)
    #: the unfused batch path re-zeros padded rows (`_scale`'s mask);
    #: fused programs must keep that invariant — mask-less reductions
    #: downstream (`_moments`, `_normal_equations`) rely on it
    fuse_masks_output = True
    #: moments stage: standardized features feed solvers; a bf16
    #: boundary here would round exactly the values the normal
    #: equations accumulate — pinned f32 (the precision planner's
    #: EXACT class)
    precision_tolerance = "exact"

    def __init__(self, mean, std=None):
        self.mean = mean
        self.std = std

    def apply(self, x):
        if self.std is None:
            return x - self.mean
        return (x - self.mean) / self.std

    def fuse(self):
        """Fused-chain decomposition: mean/std are traced params, so
        structurally identical pipelines share one compiled program.
        The fusion builder re-applies the padded-row mask after this
        stage (``fuse_masks_output``), exactly like `_scale` does."""
        if self.std is None:
            return (("StandardScaler", "center"), (self.mean,),
                    lambda p, X: X - p[0])
        return (("StandardScaler", "scale"), (self.mean, self.std),
                lambda p, X: (X - p[0]) / p[1])

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)  # host chunks: per-item path
        std = self.std if self.std is not None else jnp.ones_like(self.mean)
        from ...telemetry import dispatch

        with dispatch(self.label):
            return data.with_data(
                _scale(data.array, self.mean, std, data.mask))


class StandardScaler(Estimator):
    """Fit per-feature mean/std (StandardScaler.scala:36-60)."""

    #: the fit always yields a traceable StandardScalerModel, so the
    #: optimizer may fuse through this estimator's apply boundary
    fusable_fit = True
    precision_tolerance = "exact"  # `_moments` is an exact reduction

    def __init__(self, normalize_std_dev: bool = True, eps: float = 1e-12):
        self.normalize_std_dev = normalize_std_dev
        self.eps = eps

    def abstract_fit(self, in_specs):
        """Static fit: the scaler is shape-preserving, but the fitted
        mean/std pin the feature dim — applying to a different width is
        a static error."""
        from ...analysis.specs import (
            SpecMismatchError,
            TransformerSpec,
            leaf_vector_dim,
        )

        d = leaf_vector_dim(in_specs[0] if in_specs else None)

        def elem_fn(elem):
            if d is not None and getattr(elem, "ndim", None) == 1 \
                    and elem.shape[0] != d:
                raise SpecMismatchError(
                    f"StandardScaler was fit on {d}-dim features but is "
                    f"applied to a {elem.shape[0]}-dim element")
            return elem

        return TransformerSpec(elem_fn, label=self.label, chunkable=True)

    def fit(self, data: Dataset) -> StandardScalerModel:
        from ...telemetry import dispatch

        with dispatch(self.label):
            # a host scalar: `jnp.float32(...)` would launch a program
            # of its own to convert it
            mean, std = _moments(
                data.array, np.float32(data.count), self.normalize_std_dev
            )
        return StandardScalerModel(mean, std if self.normalize_std_dev else None)
