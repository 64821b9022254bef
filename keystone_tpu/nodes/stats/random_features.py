"""Randomized featurization nodes.

- `CosineRandomFeatures` — random Fourier features cos(xWᵀ + b)
  (reference nodes/stats/CosineRandomFeatures.scala:20-61: broadcast W,
  per-partition GEMM → here one sharded GEMM on the MXU with W
  replicated over the mesh).
- `RandomSignNode` — x ∘ random ±1 (RandomSignNode.scala:11-24).
- `PaddedFFT` — zero-pad to a power of two, FFT, return the real half
  (PaddedFFT.scala:13-21).
- `LinearRectifier` — max(maxVal, x − α) (LinearRectifier.scala:12-17).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.pipeline import Transformer


@jax.jit
@jax.named_scope("ks.CosineRandomFeatures")
def _cosine_rf(X, W, b):
    return jnp.cos(X @ W + b)


@partial(jax.jit,
         static_argnames=("input_dim", "num_features", "distribution"))
@jax.named_scope("ks.CosineRandomFeatures.draw")
def _draw_cosine_rf(seed, gamma, *, input_dim, num_features, distribution):
    """W ~ gamma * N(0, 1) or gamma * Cauchy, b ~ U[0, 2 pi), float32."""
    kw, kb = jax.random.split(jax.random.PRNGKey(seed))
    draw = jax.random.normal if distribution == "gaussian" else jax.random.cauchy
    W = gamma * draw(kw, (input_dim, num_features), jnp.float32)
    b = jax.random.uniform(kb, (num_features,), jnp.float32, 0.0, 2 * np.pi)
    return W, b


@lru_cache(maxsize=None)
def _draw_cosine_rf_on(mesh):
    """`_draw_cosine_rf` as one program over the chips of ``mesh`` that
    leaves W and b replicated: every chip draws the same numbers from the
    same key. Drawn on one chip, each of them was copied to the others
    again by every program that took it (`DevicePutWithSharding`, three
    device-to-device copies an array and about 1 ms of host each: 8 ms in
    front of the gather's program and as much in front of an apply's with
    four branches on four chips; my chip run, PR 33)."""
    from ...parallel.mesh import replicated_sharding

    return jax.jit(
        _draw_cosine_rf.__wrapped__,
        static_argnames=("input_dim", "num_features", "distribution"),
        out_shardings=replicated_sharding(mesh))


class CosineRandomFeatures(Transformer):
    """cos(x Wᵀ + b) with W ~ gamma·N(0,1) (gaussian) or gamma·Cauchy,
    b ~ U[0, 2π]."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    #: random-projection featurize: bf16 storage/compute tolerated (the
    #: bandwidth-bound hot path the precision planner halves)
    precision_tolerance = "tolerant"

    def __init__(
        self,
        input_dim: int,
        num_features: int,
        gamma: float = 1.0,
        distribution: str = "gaussian",
        seed: int = 0,
    ):
        if distribution not in ("gaussian", "cauchy"):
            raise ValueError(f"unknown distribution {distribution!r}")
        from ...parallel.mesh import current_mesh
        from ...telemetry import dispatch

        # drawn on the device by one program: numpy took 26 ms a branch
        # of 440 x 4,096 with the device idle, and its float64 arrays
        # became two uncounted convert programs a branch (my chip run,
        # PR 28; PERF.md section 6). Across chips, by one program on all
        # of them: the data the node will meet is sharded over that mesh
        mesh = current_mesh()
        draw = (_draw_cosine_rf if mesh.devices.size == 1
                else _draw_cosine_rf_on(mesh))
        with dispatch("CosineRandomFeatures.draw"):
            self.W, self.b = draw(
                np.uint32(seed % 2**32), np.float32(gamma),
                input_dim=input_dim, num_features=num_features,
                distribution=distribution)

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        d, m = self.W.shape
        if getattr(elem, "ndim", 0) >= 1 and elem.shape[-1] != d:
            raise SpecMismatchError(
                f"CosineRandomFeatures expects {d}-dim inputs "
                f"(input_dim={d}) but the element's last axis is "
                f"{elem.shape[-1]}")
        return shape_struct(tuple(elem.shape[:-1]) + (m,), self.W.dtype)

    def apply(self, x):
        return jnp.cos(x @ self.W + self.b)

    def fuse(self):
        return (("CosineRandomFeatures",), (self.W, self.b),
                lambda p, X: jnp.cos(X @ p[0] + p[1]))

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)  # host chunks: per-item path
        from ...telemetry import dispatch

        # module-level jit: W/b are traced args, so rebuilding a pipeline
        # (fresh weights, same shapes) reuses the compiled program
        with dispatch(self.label):
            return data.with_data(_cosine_rf(data.array, self.W, self.b))


class RandomSignNode(Transformer):
    """Elementwise multiply by a fixed random ±1 vector."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    precision_tolerance = "tolerant"  # elementwise ±1 flip

    def __init__(self, dim: int, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.signs = jnp.asarray(
            rng.integers(0, 2, size=(dim,)) * 2 - 1, dtype=jnp.float32
        )

    def apply(self, x):
        return x * self.signs

    def fuse(self):
        # signs ride as a traced param: every RandomSignNode of one dim
        # shares ONE compiled program (and fused programs containing
        # this stage keep a structural — not id-keyed — cache key)
        return (("RandomSignNode",), (self.signs,),
                lambda p, x: x * p[0])


class PaddedFFT(Transformer):
    """Zero-pad to the next power of two and return the real part of the
    positive-frequency half of the FFT."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    precision_tolerance = "tolerant"  # featurize transform

    def apply(self, x):
        n = x.shape[-1]
        padded = 1 << max(int(np.ceil(np.log2(n))), 0)
        return jnp.fft.rfft(self._widen(x), n=padded).real[..., : padded // 2]

    @staticmethod
    def _widen(x):
        """RFFT only accepts f32/f64: a bf16-stored boundary (the
        precision planner's halving) upcasts at entry — bf16 storage,
        f32 compute. The widened value never leaves the program."""
        if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != jnp.float64:
            return x.astype(jnp.float32)  # keystone: ignore[KJ011]
        return x

    def fuse(self):
        # shape-only state: the pad width derives from the traced input
        # shape, so one static key serves every instance
        def fn(p, x):
            n = x.shape[-1]
            padded = 1 << max(int(np.ceil(np.log2(n))), 0)
            x = PaddedFFT._widen(x)
            return jnp.fft.rfft(x, n=padded).real[..., : padded // 2]

        return (("PaddedFFT",), (), fn)


class LinearRectifier(Transformer):
    """max(maxVal, x - alpha)."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks (KP302)
    precision_tolerance = "tolerant"  # elementwise max/sub

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def apply(self, x):
        return jnp.maximum(self.max_val, x - self.alpha)

    def fuse(self):
        # thresholds ride as traced scalars matched to the INPUT dtype
        # inside the program: a pinned-f32 scalar would silently promote
        # a bf16 boundary back to f32 and defeat any precision policy
        # (the KJ011 class of bug)
        return (("LinearRectifier",),
                (np.float64(self.max_val), np.float64(self.alpha)),
                lambda p, x: jnp.maximum(
                    jnp.asarray(p[0], x.dtype), x - jnp.asarray(p[1], x.dtype)))
