"""Block coordinate descent least squares — the workhorse solver.

Reference: nodes/learning/BlockLinearMapper.scala:22-283 (estimator at
:199-283) + mlmatrix `BlockCoordinateDescent.solveLeastSquaresWithL2`.

The reference splits the d-dim feature space into blocks
(`VectorSplitter` → Seq[RDD]), then per block: broadcast the model,
per-partition GEMMs, treeReduce of the block Gram/correlation to the
driver, local (B×B) solve, and a distributed residual update.

TPU-native redesign: the entire BCD sweep is ONE jitted program. X stays
a single (n, d_padded) array sharded over the mesh ``data`` axis, the
model W lives as (num_blocks, B, k) replicated, and the residual R is a
persistent data-sharded (n, k) array. A `lax.scan` over block indices
does `dynamic_slice` on the feature axis (static block size → one
compile reused for every block, the reference's 'pad the last block'
trick), with XLA inserting the Gram all-reduce where the reference had
treeReduce. Epochs are an outer `lax.scan`. Mean-centering (the
reference's per-block StandardScaler) is applied once up front with
masking so padded rows stay zero.

`BlockLeastSquaresEstimator.fit` runs the same sweep as a host loop of
donated `_bcd_epoch` programs, one an epoch. A block's Gram ``Xb'Xb +
lam I`` and its Cholesky factor depend on nothing an epoch changes (only
the residual moves), so a fit of several epochs forms and factors each
block's Gram in its first sweep, keeps the stacked upper factors
``(num_blocks, B, B)`` (B/n of one copy of X), and every later sweep is,
a block, the correlation ``Xb'R``, two triangular solves on the kept
factor and one residual update. A one-epoch fit keeps nothing.

A block step solves for the block's change, ``(Xb'Xb + lam I) delta =
Xb'R - lam Wb``, and subtracts ``Xb delta`` from the residual: two
products over the block's slice of X, where adding the block's
contribution back, solving the block again and subtracting it (the
textbook step, `_bcd_fit`'s) takes three.

The sweep that forms the Grams (the only one of a one-epoch fit, the
first of a longer one) computes each as the row panels of its upper
triangle and copies the tiles below the diagonal from those above
(`_gram_upper_panels`): the Gram is symmetric, and at a tile of B/16 its
sixteen panels are 17/32 of the full product's work. The tile is a
function of the block's width (`_gram_tile`), and a block too narrow for
two tiles keeps the one full product.

`_bcd_fit` (the one-program scan form, which forms every Gram in every
epoch, each as one full product, and runs the textbook step) is called
by the tests alone: the independent statement that all three traces are
held to.

The estimator declares optimizer weight 3·numIter+1 — the number of
passes over the input — feeding auto-caching (BlockLinearMapper.scala:205-210).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...data.dataset import Dataset
from ...workflow.pipeline import LabelEstimator, Transformer


@partial(
    jax.jit,
    static_argnames=("block_size", "num_blocks", "num_iter", "center", "x_sharding"),
)
def _bcd_fit(
    X, Y, mask, lam, block_size: int, num_blocks: int, num_iter: int, center: bool,
    x_sharding=None,
):
    # Solver numerics need true f32 Gram matrices: on TPU the default
    # matmul precision is bf16, which caps BCD's convergence floor.
    with jax.default_matmul_precision("highest"):
        return _bcd_fit_impl(
            X, Y, mask, lam, block_size, num_blocks, num_iter, center, x_sharding
        )


def _bcd_fit_impl(X, Y, mask, lam, block_size, num_blocks, num_iter, center,
                  x_sharding=None):
    n_pad, d_pad = X.shape
    k = Y.shape[1]
    dtype = X.dtype
    count = jnp.sum(mask)

    if center:
        xm = jnp.sum(X, axis=0) / count
        ym = jnp.sum(Y, axis=0) / count
        Xc = (X - xm) * mask[:, None]
        Yc = (Y - ym) * mask[:, None]
    else:
        xm = jnp.zeros((d_pad,), dtype)
        ym = jnp.zeros((k,), dtype)
        Xc = X * mask[:, None]
        Yc = Y * mask[:, None]

    if x_sharding is not None:
        # dp × tp layout on a ('data', 'model') mesh: the feature axis of
        # X is model-sharded (reference VectorSplitter → SURVEY §2.7);
        # per-block Grams then all-reduce over 'data' while block slices
        # move over 'model' via XLA-inserted collectives.
        Xc = jax.lax.with_sharding_constraint(Xc, x_sharding)

    eye = lam * jnp.eye(block_size, dtype=dtype)

    def block_step(carry, b_idx):
        W, R = carry
        Xb = jax.lax.dynamic_slice_in_dim(Xc, b_idx * block_size, block_size, axis=1)
        Wb = W[b_idx]
        # add back this block's contribution, then re-solve it exactly
        R1 = R + Xb @ Wb
        G = Xb.T @ Xb + eye          # all-reduce over the data axis
        C = Xb.T @ R1                # all-reduce over the data axis
        Wb_new = jax.scipy.linalg.solve(G, C, assume_a="pos")
        R2 = R1 - Xb @ Wb_new
        return (W.at[b_idx].set(Wb_new), R2), None

    def epoch(carry, _):
        carry, _ = jax.lax.scan(block_step, carry, jnp.arange(num_blocks))
        return carry, None

    W0 = jnp.zeros((num_blocks, block_size, k), dtype)
    R0 = Yc
    (W, _), _ = jax.lax.scan(epoch, (W0, R0), None, length=num_iter)

    W_full = W.reshape(d_pad, k)  # block b occupies rows [b*B, (b+1)*B)
    b = ym - xm @ W_full
    return W_full, b


@partial(
    jax.jit,
    static_argnames=("block_size", "num_blocks", "center", "x_sharding"),
)
def _bcd_prepare(X, Y, mask, block_size: int, num_blocks: int, center: bool,
                 x_sharding=None):
    """Centering/masking pass + zero-initialized model and residual
    buffers for the donated epoch loop. Identical arithmetic to the
    prologue of `_bcd_fit_impl`."""
    with jax.default_matmul_precision("highest"):
        d_pad = X.shape[1]
        k = Y.shape[1]
        dtype = X.dtype
        with jax.named_scope("ks.bcd.centre"):
            count = jnp.sum(mask)
            if center:
                xm = jnp.sum(X, axis=0) / count
                ym = jnp.sum(Y, axis=0) / count
                Xc = (X - xm) * mask[:, None]
                Yc = (Y - ym) * mask[:, None]
            else:
                xm = jnp.zeros((d_pad,), dtype)
                ym = jnp.zeros((k,), dtype)
                Xc = X * mask[:, None]
                Yc = Y * mask[:, None]
        if x_sharding is not None:
            Xc = jax.lax.with_sharding_constraint(Xc, x_sharding)
        W0 = jnp.zeros((num_blocks, block_size, k), dtype)
        return Xc, Yc, xm, ym, W0


#: The triangular Gram's tile: the narrowest the sweep on the chip tried was
#: the fastest at every shape (PERF.md 6, PR 32), and at most this many
#: panels keep the forming sweep's program and its compile bounded.
_GRAM_TILE_MIN = 256
_GRAM_PANELS_MAX = 16


def _gram_tile(block_size: int) -> Optional[int]:
    """Tile width T of the triangular Gram for a block of ``block_size``
    columns, or None for the one full product where the block has fewer
    than two tiles. A pure function of the shape, so every fit of one shape
    runs one program; no option overrides it. The sweep found no dependence
    on the rows (8,192 to 65,536)."""
    if block_size < 2 * _GRAM_TILE_MIN:
        return None
    widest = -(-block_size // _GRAM_PANELS_MAX)
    return -(-widest // _GRAM_TILE_MIN) * _GRAM_TILE_MIN


def _gram_tiles_skipped(block_size: int, tile: Optional[int]) -> int:
    """Tiles of a block's Gram that `_gram_upper_panels` does not compute:
    the t(t-1)/2 below the diagonal, 0 for the full product."""
    if tile is None:
        return 0
    t = -(-block_size // tile)
    return t * (t - 1) // 2


def _allreduce_bytes(block_size: int, k: int, tile: Optional[int],
                     forming: bool) -> int:
    """Bytes one chip hands to the all-reduces over ``data`` in one block
    step of `_bcd_epoch`, from the shapes and from what the partitioned
    program reduces (PERF.md 5, the v5e compiler's listing): the (B, k)
    correlation in every step, and in a forming step the Gram's partial
    sums as the program forms them, the row panels of the upper triangle
    before they are mirrored (or the one full product where ``tile`` is
    None)."""
    elems = block_size * k
    if forming:
        T = tile or block_size
        elems += sum(min(T, block_size - s) * (block_size - s)
                     for s in range(0, block_size, T))
    return 4 * elems


def _gram_upper_panels(Xb, tile: int):
    """``Xb.T @ Xb`` from the row panels of its upper triangle. Panel i is
    ``Xb[:, iT:(i+1)T].T @ Xb[:, iT:]``, a (T, B - iT) product: t =
    ceil(B/T) products of falling width (the last panel narrower where T
    does not divide B), (t+1)/(2t) of the full product's work. The
    tiles below the diagonal are the transposes of those above, copied and
    not computed, so the result is a full (B, B) matrix equal in both
    triangles. The caller sets the matmul precision."""
    B = Xb.shape[1]
    starts = range(0, B, tile)
    panels = [Xb[:, s:s + tile].T @ Xb[:, s:] for s in starts]
    rows = [
        jnp.concatenate(
            [panels[j][:, s - sj:s - sj + tile].T
             for j, sj in enumerate(starts[:i])] + [panels[i]], axis=1)
        for i, s in enumerate(starts)]
    return jnp.concatenate(rows, axis=0)


@partial(
    jax.jit,
    static_argnames=("block_size", "num_blocks", "keep_factors", "gram_tile"),
    donate_argnums=(0, 1),
)
def _bcd_epoch(W, R, Xc, lam, block_size: int, num_blocks: int, *,
               factors=None, keep_factors: bool = False,
               gram_tile: Optional[int] = None):
    """One BCD sweep over all feature blocks with the model W and
    residual R DONATED: XLA reuses their buffers for the outputs, so the
    per-epoch host loop updates solver state in place instead of
    re-allocating (num_blocks, B, k) + (n, k) of HBM every epoch.

    A block step is `_bcd_fit_impl`'s in another form. That one adds the
    block's contribution back (``R1 = R + Xb Wb``), solves ``(G + lam I)
    Wb_new = Xb'R1`` with ``G = Xb'Xb`` and subtracts ``Xb Wb_new``. Since
    ``Xb'R1 = Xb'R + G Wb`` the same system reads ``(G + lam I)(Wb_new -
    Wb) = Xb'R - lam Wb``, so this one solves for the change and updates
    the residual once: the same mathematics at the same precision with
    other rounding (fits allclose to `_bcd_fit`'s, and no farther from a
    float64 run of the textbook step: tests/test_solvers.py), and one
    product over the (n, B) slice fewer.

    A block's Gram ``Xb'Xb + lam I`` depends on nothing an epoch changes
    (only R moves), so a fit of several epochs forms and factors it once.
    One function, three traces, all the XLA module `jit__bcd_epoch`:

    - ``factors=None, keep_factors=False``: the sweep of a one-epoch
      fit. Forms, factors and solves each block; returns ``(W, R)``.
    - ``factors=None, keep_factors=True``: the first sweep of a longer
      fit. The same arithmetic, and the scan also emits each block's
      upper Cholesky factor (what ``solve(assume_a="pos")`` computes
      inside): returns ``(W, R, factors)``, factors
      ``(num_blocks, B, B)``.
    - ``factors`` given: every later sweep. The factors are a scanned
      input, NOT donated (each later epoch reads them again); a block
      step is the correlation ``Xb'R``, ``cho_solve`` on the kept factor
      and the residual update. No Gram and no factorization; returns
      ``(W, R)``.

    ``cho_factor`` + ``cho_solve`` is what ``solve(assume_a="pos")``
    runs, on the same operands in the same order, so the kept-factor
    epochs give the W of the factor-free ones.

    ``gram_tile`` (static; `_gram_tile` of the block's width, handed in by
    the fit) makes the two forming traces compute ``Xb'Xb`` as the row
    panels of its upper triangle (`_gram_upper_panels`) in place of the one
    full product: the same operands at the same precision, G still a full
    (B, B) matrix equal in both triangles. None, and every trace that is
    handed factors, forms what it formed before."""
    with jax.default_matmul_precision("highest"):
        eye = lam * jnp.eye(block_size, dtype=Xc.dtype)

        def block_step(carry, xs):
            W, R = carry
            b_idx, factor = xs  # factor is None where none was handed in
            Xb = jax.lax.dynamic_slice_in_dim(
                Xc, b_idx * block_size, block_size, axis=1)
            Wb = W[b_idx]
            with jax.named_scope("ks.bcd.gram"):
                if factor is None:  # all-reduce over the data axis
                    XtX = (Xb.T @ Xb if gram_tile is None
                           else _gram_upper_panels(Xb, gram_tile))
                    G = XtX + eye
                C = Xb.T @ R             # all-reduce over the data axis
            formed = None
            if factor is None and keep_factors:
                with jax.named_scope("ks.bcd.factor"):
                    formed = factor = jax.scipy.linalg.cho_factor(G)[0]
            # the block's change: (Xb'Xb + lam I) delta = Xb'R - lam Wb
            with jax.named_scope("ks.bcd.solve"):
                rhs = C - lam * Wb
                if factor is None:
                    delta = jax.scipy.linalg.solve(G, rhs, assume_a="pos")
                else:
                    delta = jax.scipy.linalg.cho_solve((factor, False), rhs)
            with jax.named_scope("ks.bcd.residual"):
                R = R - Xb @ delta
            return (W.at[b_idx].set(Wb + delta), R), formed

        (W, R), formed = jax.lax.scan(
            block_step, (W, R), (jnp.arange(num_blocks), factors))
        return (W, R) if formed is None else (W, R, formed)


@jax.jit
def _bcd_finalize(W, xm, ym):
    with jax.default_matmul_precision("highest"), \
            jax.named_scope("ks.bcd.intercept"):
        W_full = W.reshape(-1, ym.shape[0])
        return W_full, ym - xm @ W_full


@partial(jax.jit, static_argnames=("block_size", "n_chunk"))
def _partial_preds_scan(X, W, b, acc0, start, block_size: int, n_chunk: int):
    """Cumulative partial predictions for ``n_chunk`` consecutive feature
    blocks beginning at block ``start``: one dispatch per chunk, stacked
    (n_chunk, n, k) + the carried accumulator (BlockLinearMapper.
    scala:96-137)."""

    def body(acc, i):
        Xb = jax.lax.dynamic_slice_in_dim(X, i * block_size, block_size, axis=1)
        Wb = jax.lax.dynamic_slice_in_dim(W, i * block_size, block_size, axis=0)
        acc = acc + Xb @ Wb
        return acc, acc + b

    acc, stacked = jax.lax.scan(body, acc0, start + jnp.arange(n_chunk))
    return stacked, acc


class BlockLinearMapper(Transformer):
    """Apply a blocked linear model. The model is stored full-width; for
    very large d the apply GEMM itself can be sharded over the ``model``
    mesh axis by XLA (BlockLinearMapper.scala:22-137)."""

    fusable = True   # pad + GEMM: traceable, joins fused chains
    chunkable = True  # per-row GEMM: distributes over host chunks
    precision_tolerance = "exact"  # solver apply: f32/HIGHEST inputs

    def __init__(self, W, b=None, block_size: Optional[int] = None):
        self.W = W
        self.b = b if b is not None else jnp.zeros(W.shape[1], dtype=W.dtype)
        self.block_size = block_size

    def fuse(self):
        d = int(self.W.shape[0])

        def fn(p, X):
            W_, b_ = p
            if X.shape[1] < d:
                X = jnp.pad(X, [(0, 0), (0, d - X.shape[1])])
            return X @ W_ + b_

        return (("BlockLinearMapper", d), (self.W, self.b), fn)

    def abstract_apply(self, elem):
        from ...analysis.specs import SpecMismatchError, shape_struct

        d, k = self.W.shape
        if getattr(elem, "ndim", None) == 1 and elem.shape[0] > d:
            raise SpecMismatchError(
                f"BlockLinearMapper holds a {d}-row model but the input "
                f"element has {elem.shape[0]} features")
        return shape_struct((k,), self.W.dtype)

    def apply(self, x):
        x = jnp.asarray(x)
        d = self.W.shape[0]
        if x.shape[-1] < d:  # pad features like training did
            x = jnp.pad(x, [(0, d - x.shape[-1])])
        return x @ self.W + self.b

    def apply_batch(self, data):
        if not isinstance(data, Dataset):
            return super().apply_batch(data)  # host chunks: per-item path
        from .linear import _gemm_bias

        def fn(X):
            d = self.W.shape[0]
            if X.shape[1] < d:
                X = jnp.pad(X, [(0, 0), (0, d - X.shape[1])])
            return _gemm_bias(X, self.W, self.b)

        return data.map_batches(fn, jitted=False)

    def apply_and_evaluate(self, data: Dataset, eval_fn,
                           blocks_per_dispatch: Optional[int] = None):
        """Incremental per-block evaluation (BlockLinearMapper.scala:96-137):
        yields eval_fn(partial prediction) after each feature block.
        Blocks are scanned in chunks — one dispatch per chunk instead of
        one per block, while the
        stacked (chunk, n, k) partials stay memory-bounded and a consumer
        that stops early skips the remaining chunks entirely."""
        d = self.W.shape[0]
        bs = min(self.block_size or d, d)
        n_blocks = -(-d // bs)
        X, W = data.array, self.W
        pad = n_blocks * bs - d
        if pad:  # zero feature/weight padding leaves partial sums exact
            X = jnp.pad(X, [(0, 0), (0, pad)])
            W = jnp.pad(W, [(0, pad), (0, 0)])
        n, k = X.shape[0], W.shape[1]
        if blocks_per_dispatch is None:  # bound stacked partials to ~64 MB
            budget = 64 << 20
            blocks_per_dispatch = max(1, min(n_blocks, budget // max(4 * n * k, 1)))
        acc = jnp.zeros((n, k), W.dtype)
        for c0 in range(0, n_blocks, blocks_per_dispatch):
            m = min(blocks_per_dispatch, n_blocks - c0)
            stacked, acc = _partial_preds_scan(
                X, W, self.b, acc, jnp.int32(c0), bs, m
            )
            for i in range(m):
                yield eval_fn(data.with_data(stacked[i]))


class BlockLeastSquaresEstimator(LabelEstimator):
    """BCD least squares with L2 (BlockLinearMapper.scala:199-283)."""

    #: solver: normal-equation accumulation pins f32/HIGHEST inputs
    #: (`_normal_equations` runs under default_matmul_precision highest)
    precision_tolerance = "exact"

    def __init__(
        self,
        block_size: int,
        num_iter: int,
        lam: float = 0.0,
        fit_intercept: bool = True,
    ):
        self.block_size = block_size
        self.num_iter = num_iter
        self.lam = lam
        self.fit_intercept = fit_intercept
        # passes over the input: weight for auto-caching
        self.weight = 3 * num_iter + 1

    #: always fits a traceable BlockLinearMapper — the optimizer may
    #: fuse through this estimator's apply boundary
    fusable_fit = True

    def abstract_fit(self, in_specs):
        """Static fit: (d,) features + (k,) labels → model mapping (d,)
        to (k,). The solver zero-pads features to a block multiple, so
        apply accepts any dim ≤ ceil(d/bs)·bs."""
        from ...analysis.specs import leaf_vector_dim, supervised_fit_spec

        d = leaf_vector_dim(in_specs[0] if in_specs else None)
        d_pad = None
        if d is not None:
            bs = min(self.block_size, d)
            d_pad = -(-d // bs) * bs
        return supervised_fit_spec(
            in_specs, self.label, max_in_dim=d_pad)

    def abstract_sharding(self, in_shardings, in_specs):
        """The BCD sweep's per-block Grams are per-shard partial sums
        all-reduced over ``data`` (`_bcd_epoch`'s XᵀX layout): both
        training inputs must arrive row-sharded, or the solve implicitly
        reshards its whole training set (KP601)."""
        from ...analysis.sharding import fit_sharding_demands

        return fit_sharding_demands(2)

    def fit(self, data: Dataset, labels: Dataset) -> BlockLinearMapper:
        from ...parallel import mesh as meshlib

        from ...telemetry import counter, dispatch, span

        X, Y = data.array, labels.array
        d = X.shape[1]
        bs = min(self.block_size, d)
        num_blocks = -(-d // bs)
        d_pad = num_blocks * bs
        # Donated-buffer epoch loop: prepare once, then each sweep
        # updates (W, R) IN PLACE via donate_argnums — no fresh
        # model/residual allocation per epoch, and the host loop's
        # dispatches pipeline through jax's async queue (no sync until
        # the caller pulls the model). `_bcd_fit`/_bcd_fit_impl (the
        # single-program scan form of the textbook step) is what the
        # tests hold these steps to; nothing else calls it.
        with span(self.label, cat="solver", layer="solver",
                  blocks=num_blocks):
            if d_pad != d:
                with dispatch("pad"):  # `jnp.pad` is a program of its own
                    X = jnp.pad(X, [(0, 0), (0, d_pad - d)])
            mask = data.mask_as(X.dtype)
            x_sharding = meshlib.feature_sharding(data.mesh, d_pad)
            with dispatch("_bcd_prepare"):
                Xc, R, xm, ym, W = _bcd_prepare(
                    X,
                    Y,
                    mask,
                    bs,
                    num_blocks,
                    self.fit_intercept,
                    x_sharding=x_sharding,
                )
            # what a chip hands to all-reduces over `data`, from the shapes:
            # nothing where the rows live on one chip
            reduced = counter("solver.allreduce_bytes")
            sharded = meshlib.n_data_shards(data.mesh) > 1
            if sharded and self.fit_intercept:  # the sums behind xm, count, ym
                reduced.inc(4 * (d_pad + 1 + Y.shape[1]))
            # a host scalar: `jnp.asarray` would launch a convert program
            lam = np.asarray(self.lam, X.dtype)
            # A block's Gram and its Cholesky factor do not change between
            # epochs, so a fit of several forms them in its first sweep
            # and every later sweep solves on the kept factors
            # (num_blocks x B x B: B/n of one copy of X). A one-epoch fit
            # keeps nothing and runs the factor-free program.
            factors = None
            # The forming sweep computes the upper triangle of each Gram in
            # row panels where the block is wide enough for it; the tile
            # comes from the shapes alone. Where the feature axis is
            # sharded over `model`, every panel's column slices would be
            # gathered anew (n/p x B arrays): the one full product there.
            gram_tile = None if x_sharding is not None else _gram_tile(bs)
            for i in range(self.num_iter):
                # the spans measure the host-side dispatch of one
                # donated-buffer sweep; device time pipelines
                # asynchronously and lands on whoever pulls the model
                # (see OBSERVABILITY.md)
                reusing = factors is not None
                tile = None if reusing else gram_tile
                with span("bcd_epoch", cat="step", layer="solver", iter=i,
                          blocks=num_blocks,
                          gram="reused" if reusing else "formed",
                          gram_tile=tile or 0), \
                        dispatch("_bcd_epoch"):
                    W, R, *kept = _bcd_epoch(
                        W, R, Xc, lam, bs, num_blocks, factors=factors,
                        keep_factors=i == 0 and self.num_iter > 1,
                        gram_tile=tile)
                if kept:
                    (factors,) = kept
                counter("solver.steps").inc()
                # one product a block step: the add-back the step leaves out
                counter("solver.residual_addbacks_skipped").inc(num_blocks)
                if reusing:
                    counter("solver.gram_blocks_reused").inc(num_blocks)
                else:
                    counter("solver.gram_blocks_formed").inc(num_blocks)
                    counter("solver.gram_tiles_skipped").inc(
                        num_blocks * _gram_tiles_skipped(bs, tile))
                if sharded:
                    reduced.inc(num_blocks * _allreduce_bytes(
                        bs, Y.shape[1], tile, forming=not reusing))
            with dispatch("_bcd_finalize"):
                W, b = _bcd_finalize(W, xm, ym)
        return BlockLinearMapper(W, b if self.fit_intercept else None, self.block_size)
