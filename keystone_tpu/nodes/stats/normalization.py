"""Row-wise normalization nodes (reference nodes/stats/*).

- `NormalizeRows` — L2 row normalization (NormalizeRows.scala:10).
- `SignedHellingerMapper` — sign(x)·sqrt(|x|) (SignedHellingerMapper.scala:12-22).
- `Sampler` / `ColumnSampler` — deterministic down-sampling
  (Sampling.scala:12-32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset, HostDataset
from ...workflow.pipeline import Transformer


class NormalizeRows(Transformer):

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    precision_tolerance = "tolerant"  # per-item norm: featurize scale

    def __init__(self, eps: float = 2.2e-16):
        self.eps = eps

    def apply(self, x):
        norm = jnp.linalg.norm(x)
        return x / jnp.maximum(norm, self.eps)

    def fuse(self):
        # eps rides as a traced scalar matched to the input dtype in
        # the body; the batch form normalizes each ITEM (all axes but
        # the leading) — identical to vmap(apply)
        def fn(p, xb):
            axes = tuple(range(1, xb.ndim))
            norms = jnp.sqrt(jnp.sum(xb * xb, axis=axes, keepdims=True))
            return xb / jnp.maximum(norms, jnp.asarray(p[0], xb.dtype))

        return (("NormalizeRows",), (np.float64(self.eps),), fn)


class SignedHellingerMapper(Transformer):

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    precision_tolerance = "tolerant"  # elementwise sign·sqrt

    def apply(self, x):
        return jnp.sign(x) * jnp.sqrt(jnp.abs(x))

    def fuse(self):
        return (("SignedHellingerMapper",), (),
                lambda p, x: jnp.sign(x) * jnp.sqrt(jnp.abs(x)))


class Sampler(Transformer):
    """Deterministic dataset down-sample to ≤ size items (a FunctionNode in
    the reference: takes the whole dataset, returns a smaller one)."""

    def __init__(self, size: int, seed: int = 0):
        self.size = size
        self.seed = seed

    def apply(self, x):
        return x  # single items pass through

    def apply_batch(self, data):
        if isinstance(data, HostDataset):
            n = len(data)
            if n <= self.size:
                return data
            idx = np.random.default_rng(self.seed).choice(n, self.size, replace=False)
            idx.sort()
            return HostDataset([data.items[i] for i in idx])
        n = data.count
        if n <= self.size:
            return data
        idx = np.random.default_rng(self.seed).choice(n, self.size, replace=False)
        idx.sort()
        # gather on device — never pull the full dataset to host
        jidx = jnp.asarray(idx)
        picked = jax.tree_util.tree_map(
            lambda x: jnp.take(x, jidx, axis=0), data.array
        )
        return Dataset(picked, count=self.size, mesh=data.mesh)


def sample_rows(n: int, num: int, seed: int) -> np.ndarray:
    """The sorted rows a `ColumnSampler(num, seed)` keeps of an
    ``n``-row matrix: a seeded choice without replacement, the same for
    every matrix of that height. A handful of integers made on the host;
    the matrices themselves never leave the device."""
    idx = np.random.default_rng(seed).choice(n, num, replace=False)
    idx.sort()
    return idx.astype(np.int32)


class ColumnSampler(Transformer):
    """Sample ≤ num_cols columns from each item's (cols × dim) matrix —
    used to subsample descriptors per image (Sampling.scala:12-25).

    Traceable: inside a fused program the kept rows are taken on the
    device from the descriptors the program has just made, so what
    leaves the program is the sample and never the descriptor matrix
    (`sampler.rows_kept`; `sampler.host_bytes` counts what the host
    path pulls across)."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    precision_tolerance = "tolerant"  # a choice of rows: values untouched

    def __init__(self, num_cols: int, seed: int = 0):
        self.num_cols = num_cols
        self.seed = seed

    def abstract_apply(self, elem):
        from ...analysis.specs import shape_struct

        shape = tuple(elem.shape)
        return shape_struct((min(shape[0], self.num_cols),) + shape[1:],
                            elem.dtype)

    def apply(self, x):
        n = x.shape[0]
        if n <= self.num_cols:
            return x
        idx = sample_rows(n, self.num_cols, self.seed)
        if isinstance(x, jax.Array):
            return jnp.take(x, idx, axis=0)
        from ...telemetry import counter

        x = np.asarray(x)
        counter("sampler.host_bytes").inc(x.nbytes)
        return x[idx]

    def apply_batch(self, data):
        if isinstance(data, Dataset):
            # the structurally cached program, not a jit of this instance
            from ..util.fusion import FusedBatchTransformer

            return FusedBatchTransformer([self]).apply_batch(data)
        return super().apply_batch(data)

    def fuse(self):
        num, seed = self.num_cols, self.seed

        def fn(p, xb):
            n = xb.shape[1]
            if n <= num:
                return xb
            with jax.named_scope("ks.sift.sample"):
                return jnp.take(xb, sample_rows(n, num, seed), axis=1)

        return (("ColumnSampler", num, seed), (), fn)

    def moves_ahead_of(self, stage) -> bool:
        """Ahead of the PCA projection, a map of each row."""
        from ..learning.pca import PCATransformer

        return isinstance(stage, PCATransformer)

    def count_rows(self, elem, rows: int):
        from ...telemetry import counter

        counter("sampler.rows_kept").inc(
            rows * min(elem.shape[0], self.num_cols))
