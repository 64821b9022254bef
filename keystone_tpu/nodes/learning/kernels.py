"""Kernel methods: RBF kernel blocks, kernel ridge regression via
Gauss-Seidel block coordinate descent, and blocked kernel model apply.

Reference: nodes/learning/KernelGenerator.scala:18-206 (RBF via the
dot-product trick, broadcast column block), KernelMatrix.scala:17-90
(lazy column-block view with optional caching),
KernelRidgeRegression.scala:37-275 (arXiv:1602.05310 — per block:
kernel col-block gen → treeReduce residual → local (B×B) solve →
distributed model update; lineage truncation via checkpoint every 25
blocks), KernelBlockLinearMapper.scala:28-90.

TPU-native: the n×n kernel never materializes. One jitted `krr_step`
(kernel block GEMM + replicated solve + residual update) is compiled
once and reused for every block and epoch — the host loop only permutes
block order. The reference's RDD checkpointing maps to the natural
materialization of each step's outputs (no lineage to truncate).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...ops import use_pallas as _use_pallas_now
from ...workflow.pipeline import Estimator, LabelEstimator, Transformer


@partial(jax.jit, static_argnames=("gamma", "use_pal"))
def _rbf_block_jit(X, Xb, gamma: float, use_pal: bool):
    from ...ops import rbf_block_pallas, rbf_block_reference

    if use_pal:
        return rbf_block_pallas(X, Xb, gamma)
    return rbf_block_reference(X, Xb, gamma)


def _rbf_block(X, Xb, gamma: float):
    """K(X, Xb) = exp(-γ‖x−y‖²) via the dot-product trick
    (KernelGenerator.scala:18-206). gamma is static: the Pallas kernel
    fuses the distance/exp epilogue into the Gram GEMM (ops/), and one
    estimator has one gamma, so this costs no extra compiles. The
    backend choice is part of the jit key so toggling
    KEYSTONE_ENABLE_PALLAS mid-process cannot reuse the other path's
    compiled program."""
    from ...ops import use_pallas

    return _rbf_block_jit(X, Xb, gamma, use_pallas())


class GaussianKernelTransformer(Transformer):
    """x → K(x, anchors) (KernelGenerator.scala)."""

    def __init__(self, anchors, gamma: float):
        self.anchors = jnp.asarray(anchors)
        self.gamma = gamma

    def apply(self, x):
        return _rbf_block(
            jnp.atleast_2d(jnp.asarray(x)), self.anchors, float(self.gamma)
        )[0]

    def apply_batch(self, data: Dataset):
        return data.map_batches(
            lambda X: _rbf_block(X, self.anchors, float(self.gamma)),
            jitted=False,
        )


class GaussianKernelGenerator(Estimator):
    def __init__(self, gamma: float):
        self.gamma = gamma

    def fit(self, data: Dataset) -> GaussianKernelTransformer:
        # anchors stay on device: slice off the padding rows, no host
        # round trip of the training matrix
        return GaussianKernelTransformer(
            data.array[: data.count], self.gamma
        )


class BlockKernelMatrix:
    """Lazy column-block view of K(X, X) with optional block caching
    (KernelMatrix.scala:17-90)."""

    def __init__(self, X, gamma: float, cache_blocks: bool = False):
        self.X = X  # (n_pad, d) sharded
        self.gamma = float(gamma)
        self.cache_blocks = cache_blocks
        self._cache = {}

    def block(self, idx, block_size: int):
        key = (int(idx), block_size)
        if key in self._cache:
            return self._cache[key]
        Xb = jax.lax.dynamic_slice_in_dim(self.X, int(idx) * block_size, block_size, 0)
        Kb = _rbf_block(self.X, Xb, self.gamma)
        if self.cache_blocks:
            self._cache[key] = Kb
        return Kb


@partial(
    jax.jit, static_argnames=("gamma", "use_pal"), donate_argnums=(3, 4)
)
def _krr_step(X, Y, mask, alpha, KA, lam, gamma, block_ids, use_pal):
    """One Gauss-Seidel block update of dual KRR (K + λI)α = Y.

    KA tracks K @ alpha. For block b: solve
      (K_bb + λI + eps) Δ = (Y_b − KA_b − λ α_b)
    then α_b += Δ, KA += K[:, b] Δ.

    alpha and KA are DONATED: the solver state is updated in place
    across the block loop instead of allocating two fresh (n, k) buffers
    per step — at the flagship shapes (n≈100k) that is ~2·n·k·4 bytes of
    HBM churn per block removed. Callers must not reuse a passed-in
    alpha/KA after the call (the fit loop rebinds both every step).
    """
    with jax.default_matmul_precision("highest"):
        B = block_ids.shape[0]
        Xb = jnp.take(X, block_ids, axis=0)
        Kb = _rbf_block_jit(X, Xb, gamma, use_pal) * mask[:, None]  # (n, B) masked rows
        Kbb = jnp.take(Kb, block_ids, axis=0)  # (B, B)
        alpha_b = jnp.take(alpha, block_ids, axis=0)
        resid_b = (
            jnp.take(Y, block_ids, axis=0)
            - jnp.take(KA, block_ids, axis=0)
            - lam * alpha_b
        )
        delta = jax.scipy.linalg.solve(
            Kbb + lam * jnp.eye(B, dtype=X.dtype), resid_b, assume_a="pos"
        )
        alpha = alpha.at[block_ids].add(delta)
        KA = KA + Kb @ delta
        return alpha, KA


@partial(jax.jit, static_argnames=("gamma", "block_size", "n_blocks", "use_pal"))
def _kernel_apply_scan(X, train_X, alpha, gamma, block_size, n_blocks, use_pal):
    """K(X, train) @ alpha as ONE program: a `lax.scan` over train blocks
    (the reference streams blocks for memory, KernelBlockLinearMapper.
    scala:28-90 — on TPU the scan gives the same memory bound without
    paying one host dispatch per block, which on a ~69 ms-RTT link
    dominates the apply)."""
    from ...ops import rbf_block_pallas, rbf_block_reference

    rbf = rbf_block_pallas if use_pal else rbf_block_reference

    def body(acc, i):
        Xb = jax.lax.dynamic_slice_in_dim(train_X, i * block_size, block_size, 0)
        ab = jax.lax.dynamic_slice_in_dim(alpha, i * block_size, block_size, 0)
        Kb = rbf(X, Xb, gamma)
        return acc + Kb @ ab, None

    acc0 = jnp.zeros((X.shape[0], alpha.shape[1]), X.dtype)
    out, _ = jax.lax.scan(body, acc0, jnp.arange(n_blocks))
    return out


class KernelBlockLinearMapper(Transformer):
    """Apply a kernel model to test data block-by-block with incremental
    accumulation (KernelBlockLinearMapper.scala:28-90)."""

    precision_tolerance = "exact"  # kernel solve apply: f32 inputs

    def __init__(self, train_X, alpha, gamma: float, block_size: int = 4096):
        self.train_X = jnp.asarray(train_X)
        self.alpha = jnp.asarray(alpha)
        self.gamma = gamma
        self.block_size = block_size

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        d = self.train_X.shape[1]
        if getattr(elem, "ndim", None) == 1 and elem.shape[0] != d:
            raise SpecMismatchError(
                f"kernel model was trained on {d}-dim features but the "
                f"input element has {elem.shape[0]}")
        return shape_struct((self.alpha.shape[1],), self.alpha.dtype)

    def apply(self, x):
        K = _rbf_block(
            jnp.atleast_2d(jnp.asarray(x)), self.train_X, float(self.gamma)
        )
        return (K @ self.alpha)[0]

    def apply_batch(self, data: Dataset):
        X = data.array
        n_train = self.train_X.shape[0]
        bs = min(self.block_size, n_train)
        n_blocks = -(-n_train // bs)
        train_X, alpha = self.train_X, self.alpha
        pad = n_blocks * bs - n_train
        if pad:
            # zero-padded anchor rows have alpha = 0, so their (nonzero!)
            # kernel values contribute nothing to K @ alpha
            train_X = jnp.pad(train_X, [(0, pad), (0, 0)])
            alpha = jnp.pad(alpha, [(0, pad), (0, 0)])
        out = _kernel_apply_scan(
            X, train_X, alpha, float(self.gamma), bs, n_blocks,
            _use_pallas_now(),
        )
        return data.with_data(out)


class KernelRidgeRegression(LabelEstimator):
    """Dual KRR via Gauss-Seidel BCD over permuted sample blocks
    (KernelRidgeRegression.scala:37-275)."""

    precision_tolerance = "exact"  # solver: f32/HIGHEST inputs

    def __init__(self, gamma: float, lam: float, block_size: int = 2048,
                 num_epochs: int = 1, seed: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 blocks_before_checkpoint: int = 25):
        self.gamma = gamma
        self.lam = lam
        self.block_size = block_size
        self.num_epochs = num_epochs
        self.seed = seed
        # block-loop checkpoint/resume — the analog of the reference's RDD
        # lineage truncation + checkpointDir (KernelRidgeRegression.scala:
        # 35,199-205): solver state (alpha, KA) is persisted every
        # `blocks_before_checkpoint` blocks and restored on restart.
        self.checkpoint_dir = checkpoint_dir
        self.blocks_before_checkpoint = blocks_before_checkpoint

    def abstract_fit(self, in_specs):
        from ...analysis.specs import supervised_fit_spec

        return supervised_fit_spec(in_specs, self.label)

    def abstract_sharding(self, in_shardings, in_specs):
        """`_krr_step`'s kernel blocks are computed against row-sharded
        training data (K(X_block, X) distributes over X's row shards):
        both training inputs must arrive data-sharded or the dual solve
        implicitly reshards the full training set (KP601)."""
        from ...analysis.sharding import fit_sharding_demands

        return fit_sharding_demands(2)

    @property
    def weight(self):
        return 3 * self.num_epochs + 1

    def _ckpt_path(self, data, labels) -> Optional[str]:
        if not self.checkpoint_dir:
            return None
        import hashlib
        import os

        import jax

        if jax.process_count() > 1:
            # single-host-only: the save path host-fetches alpha/KA
            # (non-addressable in a multi-process job) and every process
            # would race the same file. The reference's equivalent was
            # driver-side RDD checkpointing — also a single coordinator.
            import logging

            logging.getLogger(__name__).warning(
                "KernelRidgeRegression checkpointing is single-host only; "
                "disabling for this %d-process job", jax.process_count())
            return None

        os.makedirs(self.checkpoint_dir, exist_ok=True)
        # fingerprint the data, not just shapes: a stale checkpoint from a
        # different dataset with identical shape must not resume
        h = hashlib.sha1()
        h.update(np.asarray(data.take(4)).tobytes())
        h.update(np.asarray(labels.take(4)).tobytes())
        h.update(str((data.count, data.array.shape)).encode())
        tag = (
            f"krr_{h.hexdigest()[:12]}_B{self.block_size}"
            f"_g{self.gamma}_l{self.lam}_s{self.seed}"
        )
        return os.path.join(self.checkpoint_dir, tag + ".npz")

    def fit(self, data: Dataset, labels: Dataset) -> KernelBlockLinearMapper:
        from ...telemetry import span

        with span(self.label, cat="solver", layer="solver"):
            return self._fit(data, labels)

    def _fit(self, data: Dataset, labels: Dataset) -> KernelBlockLinearMapper:
        import os

        X = data.array
        Y = labels.array * data.mask[:, None]
        n_pad = X.shape[0]
        mask = data.mask_as(X.dtype)
        B = min(self.block_size, n_pad)
        # permutable blocks over VALID rows only; padded rows keep alpha=0
        n_blocks = -(-data.count // B)
        alpha = jnp.zeros((n_pad, Y.shape[1]), X.dtype)
        KA = jnp.zeros_like(alpha)
        start_epoch, start_block = 0, 0
        ckpt = self._ckpt_path(data, labels)
        if ckpt and os.path.exists(ckpt):
            state = np.load(ckpt)
            alpha = jnp.asarray(state["alpha"])
            KA = jnp.asarray(state["KA"])
            start_epoch, start_block = int(state["epoch"]), int(state["block"])
        lam = jnp.asarray(self.lam, X.dtype)
        gamma = float(self.gamma)
        done = 0
        from ...telemetry import counter, dispatch, span
        for epoch in range(start_epoch, self.num_epochs):
            # per-epoch seed so a resumed run replays identical block orders
            perm = np.random.default_rng(self.seed + epoch).permutation(data.count)
            pad = (-len(perm)) % (n_blocks * B)
            ids = np.concatenate([perm, perm[: pad]]) if pad else perm
            first = start_block if epoch == start_epoch else 0
            for b in range(first, n_blocks):
                block_ids = jnp.asarray(ids[b * B : (b + 1) * B], jnp.int32)
                with span("krr_step", cat="step", layer="solver",
                          epoch=epoch, block=b), dispatch("_krr_step"):
                    alpha, KA = _krr_step(
                        X, Y, mask, alpha, KA, lam, gamma, block_ids,
                        use_pal=_use_pallas_now(),
                    )
                counter("solver.steps").inc()
                done += 1
                if ckpt and done % self.blocks_before_checkpoint == 0:
                    # atomic write: a crash mid-save must not corrupt the
                    # checkpoint the next run resumes from
                    tmp = ckpt + ".tmp.npz"
                    np.savez(
                        tmp, alpha=np.asarray(alpha), KA=np.asarray(KA),
                        epoch=epoch, block=b + 1,
                    )
                    os.replace(tmp, ckpt)
        if ckpt and os.path.exists(ckpt):
            os.unlink(ckpt)  # fit completed; stale state must not resume
        # keep the anchors on device: np.asarray here would fetch a
        # global array spanning non-addressable devices in a multihost
        # job (and costs a pointless round trip on one host)
        return KernelBlockLinearMapper(X, alpha, self.gamma, self.block_size)
