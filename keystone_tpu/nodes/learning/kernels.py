"""Kernel methods: RBF kernel blocks, kernel ridge regression via
Gauss-Seidel block coordinate descent, and blocked kernel model apply.

Reference: nodes/learning/KernelGenerator.scala:18-206 (RBF via the
dot-product trick, broadcast column block), KernelMatrix.scala:17-90
(lazy column-block view with optional caching: here the blocks a fit
keeps, `cache_kernel`),
KernelRidgeRegression.scala:37-275 (arXiv:1602.05310 — per block:
kernel col-block gen → treeReduce residual → local (B×B) solve →
distributed model update; lineage truncation via checkpoint every 25
blocks), KernelBlockLinearMapper.scala:28-90.

TPU-native: one jitted `_krr_step` (kernel block GEMM + replicated solve
+ residual update) takes its contiguous column block by a block index,
and the host loop only shuffles the order of the blocks each epoch. A
fit of one epoch never holds more than one (n, B) block of the kernel
matrix; a fit of several keeps the blocks its first epoch forms
(`cache_kernel`, the reference's `cacheKernel`), each with the Cholesky
factor of its `K_bb + lam I` (which depends on nothing an epoch changes),
and its later epochs run the same step without the kernel generation
and without the factorization. The reference's RDD
checkpointing maps to the natural materialization of each step's
outputs (no lineage to truncate).
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


def block_order(seed: int, epoch: int, n_blocks: int):
    """The order in which epoch ``epoch`` visits the column blocks: a
    permutation of ``range(n_blocks)`` that depends on the seed and the
    epoch alone (the reference's `blockPermuter`), so a resumed fit
    replays it. Which rows a block holds never changes."""
    return np.random.default_rng(seed + epoch).permutation(n_blocks)


@partial(jax.jit, static_argnames=("rows",))
def _krr_rows(X, Y, mask, *, rows: int):
    """X, Y and the mask on ``rows`` rows, a whole number of blocks:
    zero rows added (their mask is 0) or padding rows dropped."""
    def resized(a):
        extra = rows - a.shape[0]
        if extra <= 0:
            return a[:rows]
        return jnp.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1))

    return resized(X), resized(Y), resized(mask)


@jax.jit
def _krr_init(Y):
    return jnp.zeros_like(Y), jnp.zeros_like(Y)


@partial(
    jax.jit,
    static_argnames=("gamma", "block_size", "use_pal", "keep_kernel"),
    donate_argnums=(3, 4),
)
def _krr_step(X, Y, mask, alpha, KA, lam, block, kept=None, *, gamma,
              block_size: int, use_pal: bool = False,
              keep_kernel: bool = False):
    """One Gauss-Seidel block update of dual KRR (K + λI)α = Y.

    KA tracks K @ alpha. Block ``block`` is the contiguous rows
    ``[block * block_size, (block + 1) * block_size)``: solve
      (K_bb + λI) Δ = (Y_b − KA_b − λ α_b)
    then α_b += Δ, KA += K[:, b] Δ.

    One function, two programs, both the XLA module `jit__krr_step`:

    - ``kept=None``: forms the (n, B) kernel column block from X (rows
      and columns of padding zeroed by the mask) and solves by
      ``solve(assume_a="pos")``. With ``keep_kernel`` the block and the
      (B, B) upper Cholesky factor of ``K_bb + λI`` are also outputs:
      returns ``(alpha, KA, (Kb, Ub))``. The factor is what ``solve``
      takes inside (``cho_factor(a, lower=False)``, then ``cho_solve``),
      on the same operand, so keeping it changes no arithmetic.
    - ``kept=(Kb, Ub)`` (what an earlier epoch kept): no kernel is formed
      and nothing is factored: ``cho_solve`` on ``Ub`` alone. Neither is
      donated: every later epoch reads both again.

    alpha and KA are DONATED: the solver state is updated in place
    across the block loop. Callers must not reuse a passed-in alpha/KA
    after the call (the fit loop rebinds both every step).
    """
    with jax.default_matmul_precision("highest"):
        B = block_size
        start = block * B

        def rows_of(a):
            return jax.lax.dynamic_slice_in_dim(a, start, B, 0)

        mask_b = rows_of(mask)
        if kept is None:
            with jax.named_scope("ks.krr.kernel"):
                Kb = (_rbf_block_jit(X, rows_of(X), gamma, use_pal)
                      * mask[:, None] * mask_b[None, :])
        else:
            Kb, Ub = kept
        with jax.named_scope("ks.krr.solve"):
            alpha_b = rows_of(alpha)
            resid_b = ((rows_of(Y) - rows_of(KA) - lam * alpha_b)
                       * mask_b[:, None])
            if kept is None:
                # a padding row's row and column of K_bb are zero: a one
                # on its diagonal keeps the system definite at lam = 0
                system = rows_of(Kb) + jnp.diag(lam + 1.0 - mask_b)
                Ub = (jax.scipy.linalg.cho_factor(system, lower=False)[0]
                      if keep_kernel else None)
            if Ub is None:
                delta = jax.scipy.linalg.solve(system, resid_b,
                                               assume_a="pos")
            else:
                delta = jax.scipy.linalg.cho_solve((Ub, False), resid_b)
        with jax.named_scope("ks.krr.update"):
            alpha = jax.lax.dynamic_update_slice_in_dim(
                alpha, alpha_b + delta, start, 0)
            KA = KA + Kb @ delta
        return (alpha, KA, (Kb, Ub)) if keep_kernel else (alpha, KA)


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
        # float32 at `highest`, as the mapper declares: at the TPU's
        # default the scoring product would round Kb and alpha to bfloat16
        with jax.named_scope("ks.krr.apply"), \
                jax.default_matmul_precision("highest"):
            Xb = jax.lax.dynamic_slice_in_dim(
                train_X, i * block_size, block_size, 0)
            ab = jax.lax.dynamic_slice_in_dim(
                alpha, i * block_size, block_size, 0)
            return acc + rbf(X, Xb, gamma) @ ab, None

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
        return jnp.matmul(K, self.alpha, precision="highest")[0]

    def apply_batch(self, data: Dataset):
        from ...telemetry import dispatch

        X = data.array
        n_train = self.train_X.shape[0]
        bs = min(self.block_size, n_train)
        n_blocks = -(-n_train // bs)
        train_X, alpha = self.train_X, self.alpha
        pad = n_blocks * bs - n_train
        if pad:
            # zero-padded anchor rows have alpha = 0, so their (nonzero!)
            # kernel values contribute nothing to K @ alpha
            with dispatch("pad", n=2):  # each `jnp.pad` is a program
                train_X = jnp.pad(train_X, [(0, pad), (0, 0)])
                alpha = jnp.pad(alpha, [(0, pad), (0, 0)])
        with dispatch("_kernel_apply_scan"):
            out = _kernel_apply_scan(
                X, train_X, alpha, float(self.gamma), bs, n_blocks,
                _use_pallas_now(),
            )
        return data.with_data(out)


class KernelRidgeRegression(LabelEstimator):
    """Dual KRR via Gauss-Seidel BCD over contiguous column blocks of
    the kernel matrix, visited in a seeded shuffled order each epoch
    (KernelRidgeRegression.scala:37-275). With ``cache_kernel`` (the
    reference's `cacheKernel`, KernelMatrix.scala:17-90) a fit of several
    epochs keeps each (n, B) block it forms on the device and the later
    epochs read it: n x n floats in all, held by the fit and by nothing
    after it. Beside each kept block the fit keeps the (B, B) upper
    Cholesky factor of its ``K_bb + lam I``, formed in the step that
    forms the block (features, ``gamma`` and ``lam`` set it; no epoch
    changes it), so a step on a kept block runs two triangular solves and
    no factorization: n x B floats more over a fit (a block's size, 1 /
    n_blocks of the cache), replicated on a mesh, kept exactly as long
    as the blocks and under the same condition. A fit of one epoch, or
    without ``cache_kernel``, keeps neither. Counted by
    ``solver.kernel_factors_formed`` / ``_reused`` / ``kernel_factor_bytes``
    (`OBSERVABILITY.md`)."""

    precision_tolerance = "exact"  # solver: f32/HIGHEST inputs

    def __init__(self, gamma: float, lam: float, block_size: int = 2048,
                 num_epochs: int = 1, seed: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 blocks_before_checkpoint: int = 25,
                 cache_kernel: bool = True):
        self.gamma = gamma
        self.lam = lam
        self.block_size = block_size
        self.num_epochs = num_epochs
        self.seed = seed
        self.cache_kernel = cache_kernel
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

        from ...telemetry import counter, dispatch, span

        X, Y = data.array, labels.array
        mask = data.mask_as(X.dtype)
        B = min(self.block_size, X.shape[0])
        # contiguous blocks over the valid rows; padded rows keep alpha=0
        n_blocks = -(-data.count // B)
        if n_blocks * B != X.shape[0]:
            with dispatch("_krr_rows"):
                X, Y, mask = _krr_rows(X, Y, mask, rows=n_blocks * B)
        start_epoch, start_block = 0, 0
        ckpt = self._ckpt_path(data, labels)
        if ckpt and os.path.exists(ckpt):
            state = np.load(ckpt)
            alpha = jnp.asarray(state["alpha"])
            KA = jnp.asarray(state["KA"])
            start_epoch, start_block = int(state["epoch"]), int(state["block"])
        else:
            with dispatch("_krr_init"):
                alpha, KA = _krr_init(Y)
        # host scalars: `jnp.asarray` would launch a convert program each
        lam = np.asarray(self.lam, X.dtype)
        gamma = float(self.gamma)
        use_pal = _use_pallas_now()
        # the blocks this fit has formed and kept, by block index, each
        # with the factor of its K_bb + lam I: (Kb, Ub). Freed when the
        # fit returns (the model holds the anchors and alpha)
        kept = {}
        done = 0
        for epoch in range(start_epoch, self.num_epochs):
            order = block_order(self.seed, epoch, n_blocks)
            keep = self.cache_kernel and epoch + 1 < self.num_epochs
            first = start_block if epoch == start_epoch else 0
            for pos in range(first, n_blocks):
                b = int(order[pos])
                reusing = b in kept
                keeping = keep and not reusing
                with span("krr_step", cat="step", layer="solver",
                          epoch=epoch, block=b,
                          kernel="reused" if reusing else "formed",
                          factor=("reused" if reusing else
                                  "formed" if keeping else "unkept")), \
                        dispatch("_krr_step"):
                    alpha, KA, *formed = _krr_step(
                        X, Y, mask, alpha, KA, lam, np.int32(b),
                        kept.get(b), gamma=gamma, block_size=B,
                        use_pal=use_pal, keep_kernel=keeping)
                if formed:
                    (kept[b],) = formed
                counter("solver.steps").inc()
                if reusing:
                    counter("solver.kernel_blocks_reused").inc()
                    counter("solver.kernel_factors_reused").inc()
                else:
                    counter("solver.kernel_blocks_formed").inc()
                    if keeping:
                        counter("solver.kernel_factors_formed").inc()
                done += 1
                if ckpt and done % self.blocks_before_checkpoint == 0:
                    # atomic write: a crash mid-save must not corrupt the
                    # checkpoint the next run resumes from
                    tmp = ckpt + ".tmp.npz"
                    np.savez(
                        tmp, alpha=np.asarray(alpha), KA=np.asarray(KA),
                        epoch=epoch, block=pos + 1,
                    )
                    os.replace(tmp, ckpt)
            if epoch == start_epoch:
                counter("solver.kernel_cache_bytes").inc(
                    sum(Kb.nbytes for Kb, _ in kept.values()))
                counter("solver.kernel_factor_bytes").inc(
                    sum(Ub.nbytes for _, Ub in kept.values()))
        if ckpt and os.path.exists(ckpt):
            os.unlink(ckpt)  # fit completed; stale state must not resume
        # keep the anchors on device: np.asarray here would fetch a
        # global array spanning non-addressable devices in a multihost
        # job (and costs a pointless round trip on one host)
        return KernelBlockLinearMapper(X, alpha, self.gamma, self.block_size)
