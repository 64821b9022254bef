"""Small utility nodes (reference nodes/util/*.scala).

- `ClassLabelIndicatorsFromInt[Array]` — label(s) → ±1 one-hot
  (ClassLabelIndicators.scala:14-55). Batch path masks padded rows to
  zero so label sums stay exact under padding.
- `MaxClassifier` — argmax (MaxClassifier.scala).
- `TopKClassifier` — indices of the k largest scores.
- `VectorCombiner` — concatenate gathered branch outputs.
- `Cacher` — materialize + prefix-memoize (Cacher.scala:15-25).
- `FloatToDouble`, `MatrixVectorizer`, `Identity`, `Shuffler`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset, HostDataset, zip_datasets
from ...workflow.pipeline import Transformer


# Module-level jits (shape/static-keyed): per-instance jits recompile on
# every pipeline rebuild, which costs far more than these tiny kernels.
@partial(jax.jit, static_argnames=("k",))
def _int_indicators(y, mask, k: int):
    return (2.0 * jax.nn.one_hot(y, k) - 1.0) * mask[:, None]


@partial(jax.jit, static_argnames=("k",))
def _int_array_indicators(Y, mask, k: int):
    onehots = jax.nn.one_hot(Y, k)  # (n, L, k); -1 rows are 0
    ind = 2.0 * jnp.clip(jnp.sum(onehots, axis=1), 0.0, 1.0) - 1.0
    return ind * mask[:, None]


@jax.jit
def _argmax_last(x):
    return jnp.argmax(x, axis=-1)


@jax.jit
@jax.named_scope("ks.VectorCombiner")
def _concat_last(parts):
    return jnp.concatenate(parts, axis=-1)


class ClassLabelIndicatorsFromInt(Transformer):
    """int label → length-k vector of -1/+1."""

    fusable = True   # one_hot is traceable; joins fused chains
    chunkable = True  # pure per-item fn: distributes over chunks
    #: unfused batch path masks padded rows to zero (`_int_indicators`);
    #: the fusion builder re-applies the mask so label sums stay exact
    fuse_masks_output = True
    precision_tolerance = "exact"  # label stage: ±1 targets feed solvers

    def __init__(self, num_classes: int):
        if num_classes < 2:
            raise ValueError("num_classes must be >= 2")
        self.num_classes = num_classes

    def abstract_apply(self, elem):
        from ...analysis.specs import shape_struct

        # one_hot appends the class axis; scalar int labels → (k,)
        return shape_struct(
            tuple(getattr(elem, "shape", ())) + (self.num_classes,),
            np.float32)

    def apply(self, y):
        return 2.0 * jax.nn.one_hot(y, self.num_classes) - 1.0

    def fuse(self):
        k = self.num_classes
        return (("ClassLabelIndicators", k), (),
                lambda p, y: 2.0 * jax.nn.one_hot(y, k) - 1.0)

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)
        from ...telemetry import dispatch

        with dispatch(self.label):
            return data.with_data(_int_indicators(
                data.array, data.mask, k=self.num_classes))


class ClassLabelIndicatorsFromIntArray(Transformer):
    """multi-label int array → ±1 indicator (ClassLabelIndicators.scala:38-55).
    Expects per-item fixed-size padded label arrays with -1 as padding."""

    fusable = True
    chunkable = True
    fuse_masks_output = True  # see ClassLabelIndicatorsFromInt
    precision_tolerance = "exact"  # label stage: ±1 targets feed solvers

    def __init__(self, num_classes: int):
        self.num_classes = num_classes

    def apply(self, ys):
        onehots = jax.nn.one_hot(ys, self.num_classes)  # (L, k); -1 rows are 0
        return 2.0 * jnp.clip(jnp.sum(onehots, axis=0), 0.0, 1.0) - 1.0

    def fuse(self):
        k = self.num_classes

        def fn(p, Y):
            onehots = jax.nn.one_hot(Y, k)  # (n, L, k); -1 rows are 0
            return 2.0 * jnp.clip(jnp.sum(onehots, axis=1), 0.0, 1.0) - 1.0

        return (("ClassLabelIndicatorsArray", k), (), fn)

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)
        from ...telemetry import dispatch

        with dispatch(self.label):
            return data.with_data(_int_array_indicators(
                data.array, data.mask, k=self.num_classes))


class MaxClassifier(Transformer):
    """argmax over scores → int label (MaxClassifier.scala)."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks
    #: index stage: a bf16 score vector can flip near-tie argmaxes, so
    #: the boundary INTO the classifier stays f32
    precision_tolerance = "exact"

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        if getattr(elem, "ndim", 0) < 1:
            raise SpecMismatchError(
                "MaxClassifier needs a score vector, got a scalar element")
        return shape_struct(tuple(elem.shape[:-1]), np.int32)

    def apply(self, x):
        return jnp.argmax(x, axis=-1)

    def fuse(self):
        return (("MaxClassifier",), (), lambda p, x: jnp.argmax(x, axis=-1))

    def apply_batch(self, data):
        if isinstance(data, Dataset):
            from ...telemetry import dispatch

            with dispatch(self.label):
                return data.with_data(_argmax_last(data.array))
        return super().apply_batch(data)


class TopKClassifier(Transformer):
    def __init__(self, k: int):
        self.k = k

    def apply(self, x):
        return jnp.argsort(-x)[: self.k]


class VectorCombiner(Transformer):
    """Concatenate the tuple of branch outputs produced by gather
    (VectorCombiner.scala)."""

    #: value-preserving plumbing: the consumers behind the concat decide
    #: precision tolerance (analysis.precision looks through this stage)
    precision_passthrough = True

    def apply(self, xs):
        return jnp.concatenate([jnp.asarray(x) for x in xs], axis=-1)

    def apply_batch(self, data):
        if isinstance(data, Dataset) and isinstance(data.data, tuple):
            from ...telemetry import counter, dispatch

            with dispatch(self.label):
                out = _concat_last(data.data)
            # the branches' buffers copied into a combined one
            counter("gather.concat_bytes").inc(out.nbytes)
            return data.with_data(out)
        return super().apply_batch(data)


class Cacher(Transformer):
    """Materialize the dataset and mark the prefix saveable, enabling
    cross-pipeline reuse (Cacher.scala:15-25 + ExtractSaveablePrefixes)."""

    saveable = True
    #: value-preserving plumbing: the consumers behind the cache decide
    #: precision tolerance — a cached feature matrix feeding an exact
    #: solver must stay f32 even though the cache tolerates anything
    precision_passthrough = True

    def __init__(self, name: str = ""):
        self.name = name

    @property
    def label(self) -> str:
        return f"Cacher[{self.name}]"

    def abstract_apply(self, elem):
        return elem

    def apply(self, x):
        return x

    def apply_batch(self, data):
        return data.cache() if hasattr(data, "cache") else data


class Densify(Transformer):
    """SparseDataset → device Dataset (reference nodes/util/Densify.scala)."""

    def apply(self, x):
        import numpy as np

        return np.asarray(x.todense()).ravel() if hasattr(x, "todense") else x

    def apply_batch(self, data):
        from ...data.sparse import SparseDataset

        return data.densify() if isinstance(data, SparseDataset) else data


class Sparsify(Transformer):
    """Device Dataset → host SparseDataset (reference nodes/util/Sparsify.scala)."""

    def apply(self, x):
        import scipy.sparse as sp

        return sp.csr_matrix(x)

    def apply_batch(self, data):
        import scipy.sparse as sp

        from ...data.sparse import SparseDataset

        if isinstance(data, SparseDataset):
            return data
        return SparseDataset(sp.csr_matrix(data.numpy()), mesh=getattr(data, "mesh", None))


class FloatToDouble(Transformer):
    def apply(self, x):
        return jnp.asarray(x, dtype=jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)


class MatrixVectorizer(Transformer):
    """Flatten a per-item matrix to a vector (MatrixVectorizer.scala)."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks
    precision_tolerance = "tolerant"  # reshape: values untouched

    def apply(self, x):
        return jnp.ravel(x)

    def fuse(self):
        # shape-only: one static key for every instance (KP501)
        return (("MatrixVectorizer",), (),
                lambda p, x: x.reshape(x.shape[0], -1))


class Identity(Transformer):
    precision_passthrough = True  # see Cacher

    def apply(self, x):
        return x


class Shuffler(Transformer):
    """Random permutation of the dataset (Shuffler.scala:16-19 —
    a repartition+shuffle in the reference; here a host-side gather)."""

    def __init__(self, seed: int = 0):
        self.seed = seed

    def apply(self, x):
        return x

    def apply_batch(self, data):
        import numpy as np

        if isinstance(data, HostDataset):
            idx = np.random.default_rng(self.seed).permutation(len(data))
            return HostDataset([data.items[i] for i in idx])
        idx = np.random.default_rng(self.seed).permutation(data.count)
        # device gather (indices only touch valid rows)
        jidx = jnp.asarray(idx)
        picked = jax.tree_util.tree_map(
            lambda x: jnp.take(x, jidx, axis=0), data.array
        )
        return Dataset(picked, count=data.count, mesh=data.mesh)
