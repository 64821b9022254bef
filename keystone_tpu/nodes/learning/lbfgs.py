"""Distributed L-BFGS least-squares solvers.

Reference: nodes/learning/LBFGS.scala:14-281 + Gradient.scala:10-119.

The reference computes per-partition loss/gradient GEMMs
(`zipPartitions` of features×labels), treeReduces the sums to the
master, and runs Breeze's LBFGS driver there. Here the loss over the
data-sharded X/Y is a jitted function whose gradient XLA all-reduces
over the mesh; the optax L-BFGS driver (two-loop recursion +
zoom linesearch) runs replicated inside the same jit via `lax.scan` —
no host round-trips per iteration.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax

from ...data.dataset import Dataset
from ...workflow.pipeline import LabelEstimator
from .linear import LinearMapper, SparseLinearMapper


@partial(
    jax.jit,
    static_argnames=("num_iters", "memory_size", "fit_intercept", "x_sharding"),
)
def _lbfgs_fit(
    X, Y, mask, lam, count, num_iters: int, memory_size: int, fit_intercept: bool,
    x_sharding=None,
):
    with jax.default_matmul_precision("highest"):
        return _lbfgs_fit_impl(
            X, Y, mask, lam, count, num_iters, memory_size, fit_intercept, x_sharding
        )


def _lbfgs_fit_impl(X, Y, mask, lam, count, num_iters, memory_size, fit_intercept,
                    x_sharding=None):
    d, k = X.shape[1], Y.shape[1]
    dtype = X.dtype

    if x_sharding is not None:  # dp × tp layout on a ('data','model') mesh
        X = jax.lax.with_sharding_constraint(X, x_sharding)

    if fit_intercept:
        xm = jnp.sum(X, axis=0) / count
        ym = jnp.sum(Y, axis=0) / count
        Xc = (X - xm) * mask[:, None]
        Yc = (Y - ym) * mask[:, None]
    else:
        Xc = X * mask[:, None]
        Yc = Y * mask[:, None]

    def loss(W):
        # Unnormalized objective: matches the exact/block solvers'
        # (XᵀX + λI) convention so cost-model routing never silently
        # changes the effective regularization strength.
        resid = Xc @ W - Yc
        return 0.5 * jnp.sum(resid * resid) + 0.5 * lam * jnp.sum(W * W)

    opt = optax.lbfgs(memory_size=memory_size)
    W0 = jnp.zeros((d, k), dtype)
    state0 = opt.init(W0)
    value_and_grad = optax.value_and_grad_from_state(loss)

    def step(carry, _):
        W, state = carry
        value, grad = value_and_grad(W, state=state)
        updates, state = opt.update(
            grad, state, W, value=value, grad=grad, value_fn=loss
        )
        W = optax.apply_updates(W, updates)
        return (W, state), value

    (W, _), values = jax.lax.scan(step, (W0, state0), None, length=num_iters)
    if fit_intercept:
        b = ym - xm @ W
    else:
        b = jnp.zeros((k,), dtype)
    return W, b, values


@partial(jax.jit, static_argnames=("fit_intercept", "x_sharding"))
def _lbfgs_prepare(X, Y, mask, count, fit_intercept: bool, x_sharding=None):
    """Centering pass + zero model and initial optimizer state for the
    donated step loop. Same prologue arithmetic as `_lbfgs_fit_impl`."""
    with jax.default_matmul_precision("highest"):
        d, k = X.shape[1], Y.shape[1]
        dtype = X.dtype
        if x_sharding is not None:
            X = jax.lax.with_sharding_constraint(X, x_sharding)
        if fit_intercept:
            xm = jnp.sum(X, axis=0) / count
            ym = jnp.sum(Y, axis=0) / count
            Xc = (X - xm) * mask[:, None]
            Yc = (Y - ym) * mask[:, None]
        else:
            xm = jnp.zeros((d,), dtype)
            ym = jnp.zeros((k,), dtype)
            Xc = X * mask[:, None]
            Yc = Y * mask[:, None]
        return Xc, Yc, xm, ym


@partial(jax.jit, static_argnames=("memory_size",))
def _lbfgs_init(Xc, Yc, memory_size: int):
    W0 = jnp.zeros((Xc.shape[1], Yc.shape[1]), Xc.dtype)
    return W0, optax.lbfgs(memory_size=memory_size).init(W0)


@partial(jax.jit, static_argnames=("memory_size",), donate_argnums=(0, 1))
def _lbfgs_step(W, state, Xc, Yc, lam, memory_size: int):
    """One L-BFGS update with the model W and optimizer state (history
    ring buffers, cached value/grad) DONATED: every iteration writes
    into the previous iteration's buffers instead of allocating a fresh
    (2m+1)·d·k of history. Identical step arithmetic to `_lbfgs_fit`'s
    scan body, hence allclose-identical fits (tests/test_solvers.py).
    Callers must rebind (W, state) every call and never touch the old
    values."""
    with jax.default_matmul_precision("highest"):

        def loss(W):
            resid = Xc @ W - Yc
            return 0.5 * jnp.sum(resid * resid) + 0.5 * lam * jnp.sum(W * W)

        opt = optax.lbfgs(memory_size=memory_size)
        value, grad = optax.value_and_grad_from_state(loss)(W, state=state)
        updates, state = opt.update(
            grad, state, W, value=value, grad=grad, value_fn=loss
        )
        W = optax.apply_updates(W, updates)
        return W, state, value


class DenseLBFGSwithL2(LabelEstimator):
    """Least-squares + L2 via L-BFGS on dense features
    (LBFGS.scala `DenseLBFGSwithL2`)."""

    precision_tolerance = "exact"  # solver: f32/HIGHEST inputs

    def __init__(
        self,
        lam: float = 0.0,
        num_iters: int = 20,
        memory_size: int = 10,
        fit_intercept: bool = True,
    ):
        self.lam = lam
        self.num_iters = num_iters
        self.memory_size = memory_size
        self.fit_intercept = fit_intercept
        self.weight = num_iters  # passes over the input

    def abstract_fit(self, in_specs):
        from ...analysis.specs import supervised_fit_spec

        return supervised_fit_spec(in_specs, self.label)

    def abstract_sharding(self, in_shardings, in_specs):
        """`_lbfgs_step`'s gradient is a per-shard partial sum all-reduced
        over ``data`` (the treeReduce analog): training inputs must
        arrive row-sharded or every iteration pays an implicit reshard
        (KP601)."""
        from ...analysis.sharding import fit_sharding_demands

        return fit_sharding_demands(2)

    def fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        from ...telemetry import span

        with span(self.label, cat="solver", layer="solver"):
            return self._fit(data, labels)

    def _fit(self, data: Dataset, labels: Dataset) -> LinearMapper:
        from ...parallel import mesh as meshlib

        X, Y = data.array, labels.array
        # Donated-buffer iteration loop: model + L-BFGS history are
        # updated in place each step (donate_argnums), and the host
        # loop dispatches one step ahead of the device. `_lbfgs_fit`
        # (the one-program scan form) remains as the numerics reference
        # for these steps.
        Xc, Yc, xm, ym = _lbfgs_prepare(
            X,
            Y,
            data.mask_as(X.dtype),
            jnp.asarray(data.count, X.dtype),
            self.fit_intercept,
            x_sharding=meshlib.feature_sharding(data.mesh, X.shape[1]),
        )
        lam = jnp.asarray(self.lam, X.dtype)
        W, state = _lbfgs_init(Xc, Yc, self.memory_size)
        values = []
        from ...telemetry import counter, dispatch, span

        for i in range(self.num_iters):
            with span("lbfgs_step", cat="step", layer="solver", iter=i), \
                    dispatch("_lbfgs_step"):
                W, state, value = _lbfgs_step(
                    W, state, Xc, Yc, lam, self.memory_size)
            counter("solver.steps").inc()
            if values:
                # Wait for the step before this one, so one step runs
                # while the next is queued and no more. XLA:CPU's
                # in-process collectives deadlock once several launches
                # of a program whose all-reduce spans the virtual devices
                # are queued ahead (certain past its 32 per device, rare
                # from a handful, on a loaded host), and XLA aborts the
                # process 40 s later. A deeper queue buys nothing on any
                # backend: the device already has its next step.
                with span("lbfgs_fence", cat="sync", layer="sync"):
                    jax.block_until_ready(values[-1])  # keystone: ignore[KJ005]
            values.append(value)
        self.loss_history = jnp.stack(values) if values else jnp.zeros((0,))
        if not self.fit_intercept:
            return LinearMapper(W, None)
        with jax.default_matmul_precision("highest"):
            b = ym - xm @ W
        return LinearMapper(W, b)


@partial(jax.jit, static_argnames=("num_iters", "memory_size"))
def _lbfgs_gram_fit(G, C, lam, num_iters: int, memory_size: int):
    """L-BFGS on the Gram form: 0.5‖XW−Y‖² = 0.5 tr(WᵀGW) − tr(WᵀC) + const.
    The data size n has dropped out entirely — every iteration is a d×d
    GEMM on device."""
    with jax.default_matmul_precision("highest"):
        d, k = G.shape[0], C.shape[1]

        def loss(W):
            return (
                0.5 * jnp.sum(W * (G @ W)) - jnp.sum(W * C) + 0.5 * lam * jnp.sum(W * W)
            )

        opt = optax.lbfgs(memory_size=memory_size)
        W0 = jnp.zeros((d, k), G.dtype)
        state0 = opt.init(W0)
        value_and_grad = optax.value_and_grad_from_state(loss)

        def step(carry, _):
            W, state = carry
            value, grad = value_and_grad(W, state=state)
            updates, state = opt.update(
                grad, state, W, value=value, grad=grad, value_fn=loss
            )
            W = optax.apply_updates(W, updates)
            return (W, state), value

        (W, _), values = jax.lax.scan(step, (W0, state0), None, length=num_iters)
        return W, values


def _sparse_matvec_fit_impl(
    idx, val, Y, mask, lam, count, cidx, cval, d: int,
    num_iters: int, memory_size: int, fit_intercept: bool, row_block: int,
    col_block: int = 1, use_col: bool = False, axis_name=None,
):
    """L-BFGS over width-padded sparse rows with per-iteration sparse
    matvecs — the direct analog of the reference's iteration structure
    (LBFGS.scala:14-103 + Gradient.scala `LeastSquaresSparseGradient`:
    per-partition sparse gradient, treeReduce to master, Breeze L-BFGS
    driver), with the whole optimization ONE scanned XLA program and the
    data resident on device across iterations.

    For k ≪ d this does O(num_iters · nnz · k) work where the Gram path
    does O(n · d²). In raw FLOPs that is a ~10⁴× saving on the
    reference's Amazon shapes (k=2, sparsity .005) — but each of those
    nnz·k "flops" is a table GATHER, which the TPU issues at scalar
    rate (~5 ns each, no gather hardware; scripts/sparse_microbench.py),
    so `_route` only picks this path when d is too large to densify
    (hashing-trick feature spaces). It is also the dp-sharded
    multi-host path, where per-shard gather streams divide by the mesh.

    The objective is quadratic, so the Wolfe line search the reference
    delegates to Breeze collapses to its closed form: for direction D,
    t* = −(⟨R, XcD⟩ + λ⟨W, D⟩) / (‖XcD‖² + λ‖D‖²) — one extra matvec
    per iteration, no search loop. Centering (fit_intercept) is
    algebraic: Xc@W = X@W − 1(x̄ᵀW); centered data is never materialized.

    ALL row-space arrays are SLOT-MAJOR (long axis minor) so the TPU's
    (8, 128) tiled layout pads the narrow axis to 8 sublanes instead of
    padding it to 128 lanes (a 25× HBM blow-up at Amazon's w=5, k=2 —
    at the reference's n=65e6 the row-major layout cannot even be
    allocated). The model space is likewise (k, d) so d sits in lanes.

    idx: (w, n) int32 column ids with sentinel `d` in padding slots.
    val: (w, n) f32 (0.0 in padding slots). Y: (k, n) f32 (zero columns
    where ~mask). mask: (n,) f32 marks true rows (n is block-padded).
    count: true row count (scalar f32). cidx/cval: optional (wc, d)
    column-oriented padding (see PaddedSparseDataset) — when use_col,
    Xᵀv is a gather over cidx instead of a scatter-add into the (k, d)
    gradient (whose massive index collisions serialize on TPU).

    With `axis_name` set this body runs inside shard_map with the row
    arrays dp-sharded along their n axis: every row-space reduction
    (gradient, colsum, line-search inner products, loss) all-reduces
    over the mesh — the psum standing exactly where the reference
    treeReduces per-partition gradients to the master
    (LBFGS.scala:97-103); W and the L-BFGS history stay replicated like
    the reference's broadcast model.
    """
    w, n = idx.shape
    k = Y.shape[0]
    assert n % row_block == 0
    n_blocks = n // row_block
    m = memory_size
    dtype = val.dtype

    def dsum(x):
        """Sum a row-space reduction over the data axis (identity when
        running unsharded)."""
        return jax.lax.psum(x, axis_name) if axis_name else x

    def matvec(W):
        """X @ W → (k, n); W is (k, d), padded to a zero sentinel col."""
        table = jnp.concatenate([W, jnp.zeros((k, 1), W.dtype)], axis=1)

        def body(i, R):
            ib = jax.lax.dynamic_slice_in_dim(idx, i * row_block, row_block, 1)
            vb = jax.lax.dynamic_slice_in_dim(val, i * row_block, row_block, 1)
            g = jnp.take(table, ib, axis=1)  # (k, w, b)
            rb = jnp.einsum("wb,kwb->kb", vb, g,
                            precision=jax.lax.Precision.HIGHEST)
            return jax.lax.dynamic_update_slice(R, rb, (0, i * row_block))

        return jax.lax.fori_loop(
            0, n_blocks, body, jnp.zeros((k, n), W.dtype))

    if use_col:
        dc = cidx.shape[1]  # d padded to a col_block multiple
        assert dc % col_block == 0
        nbc = dc // col_block

        def tmatvec(R):
            """Xᵀ @ R → (k, d) as a pure gather over the column form:
            columns of R indexed by cidx; sentinel ids hit the appended
            zero column."""
            Rp = jnp.concatenate([R, jnp.zeros((k, 1), R.dtype)], axis=1)

            def body(i, G):
                cb = jax.lax.dynamic_slice_in_dim(cidx, i * col_block,
                                                  col_block, 1)
                vb = jax.lax.dynamic_slice_in_dim(cval, i * col_block,
                                                  col_block, 1)
                g = jnp.take(Rp, cb, axis=1)  # (k, wc, cblk)
                gb = jnp.einsum("wc,kwc->kc", vb, g,
                                precision=jax.lax.Precision.HIGHEST)
                return jax.lax.dynamic_update_slice(G, gb, (0, i * col_block))

            out = jax.lax.fori_loop(
                0, nbc, body, jnp.zeros((k, dc), R.dtype))
            return out[:, :d]
    else:

        def tmatvec(R):
            """Xᵀ @ R → (k, d); padding slots scatter into the dropped
            sentinel column."""
            def body(i, acc):
                ib = jax.lax.dynamic_slice_in_dim(idx, i * row_block,
                                                  row_block, 1)
                vb = jax.lax.dynamic_slice_in_dim(val, i * row_block,
                                                  row_block, 1)
                Rb = jax.lax.dynamic_slice_in_dim(R, i * row_block,
                                                  row_block, 1)
                contrib = vb[None, :, :] * Rb[:, None, :]  # (k, w, b)
                return acc.at[:, ib.reshape(-1)].add(
                    contrib.reshape(k, -1))

            out = jax.lax.fori_loop(
                0, n_blocks, body, jnp.zeros((k, d + 1), R.dtype))
            return dsum(out[:, :d])

    if fit_intercept:
        if use_col:
            colsum = jnp.sum(cval, axis=0)[:d]
        else:
            colsum = dsum(
                jnp.zeros((d + 1,), dtype)
                .at[idx.reshape(-1)]
                .add(val.reshape(-1))[:d]
            )
        xm = colsum / count          # (d,)
        ym = dsum(jnp.sum(Y, axis=1)) / count  # (k,)
    else:
        xm = jnp.zeros((d,), dtype)
        ym = jnp.zeros((k,), dtype)

    def centered_matvec(V):
        """Xc @ V for true rows, 0 for padding: mask ∘ (XV − 1 x̄ᵀV)."""
        return (matvec(V) - (V @ xm)[:, None]) * mask[None, :]

    def centered_tmatvec(R):
        """Xcᵀ R (R already masked): XᵀR − (1ᵀR) x̄; 1ᵀR is a row-space
        reduction so it all-reduces like the matvec itself."""
        return tmatvec(R) - jnp.outer(dsum(jnp.sum(R, axis=1)), xm)

    def grad_of(W, R):
        return centered_tmatvec(R) + lam * W

    W0 = jnp.zeros((k, d), dtype)
    R0 = (-(Y - ym[:, None])) * mask[None, :]  # Xc@0 − Yc
    g0 = grad_of(W0, R0)

    S0 = jnp.zeros((m, k, d), dtype)
    YH0 = jnp.zeros((m, k, d), dtype)
    rho0 = jnp.zeros((m,), dtype)

    def step(carry, _):
        W, R, g, S, YH, rho, ptr = carry

        # two-loop recursion over the ring buffer (static unroll, m≤16)
        q = g
        alphas = []
        for j in range(m):
            i = (ptr - 1 - j) % m
            a = rho[i] * jnp.sum(S[i] * q)
            q = q - a * YH[i]
            alphas.append((i, a))
        i_last = (ptr - 1) % m
        yy = jnp.sum(YH[i_last] * YH[i_last])
        sy = jnp.sum(S[i_last] * YH[i_last])
        gamma = jnp.where(yy > 0, sy / jnp.maximum(yy, 1e-30), 1.0)
        r = gamma * q
        for i, a in reversed(alphas):
            b = rho[i] * jnp.sum(YH[i] * r)
            r = r + S[i] * (a - b)
        D = -r

        # exact line search on the quadratic; ⟨u,u⟩ and ⟨R,u⟩ live in
        # row space (sharded), the λ terms in replicated model space
        u = centered_matvec(D)
        den = dsum(jnp.sum(u * u)) + lam * jnp.sum(D * D)
        num = -(dsum(jnp.sum(R * u)) + lam * jnp.sum(W * D))
        t = jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)

        W_new = W + t * D
        R_new = R + t * u
        g_new = grad_of(W_new, R_new)

        s_vec = t * D
        y_vec = g_new - g
        sy_new = jnp.sum(s_vec * y_vec)
        ok = sy_new > 1e-10
        S = S.at[ptr].set(jnp.where(ok, s_vec, 0.0))
        YH = YH.at[ptr].set(jnp.where(ok, y_vec, 0.0))
        rho = rho.at[ptr].set(jnp.where(ok, 1.0 / jnp.where(ok, sy_new, 1.0), 0.0))
        ptr = (ptr + 1) % m

        value = (0.5 * dsum(jnp.sum(R_new * R_new))
                 + 0.5 * lam * jnp.sum(W_new * W_new))
        return (W_new, R_new, g_new, S, YH, rho, ptr), value

    (W, _, _, _, _, _, _), values = jax.lax.scan(
        step, (W0, R0, g0, S0, YH0, rho0, jnp.int32(0)), None,
        length=num_iters)
    b = ym - W @ xm if fit_intercept else jnp.zeros((k,), dtype)
    # external contract stays (d, k) — only the iteration space is
    # transposed; the final transpose is a tiny (k, d) copy
    return W.T, b, values


@partial(
    jax.jit,
    static_argnames=("d", "num_iters", "memory_size", "fit_intercept",
                     "row_block", "col_block", "use_col"),
)
def _lbfgs_sparse_matvec_fit(
    idx, val, Y, mask, lam, count, cidx, cval, d: int,
    num_iters: int, memory_size: int, fit_intercept: bool, row_block: int,
    col_block: int = 1, use_col: bool = False,
):
    """Single-device entry for `_sparse_matvec_fit_impl`."""
    return _sparse_matvec_fit_impl(
        idx, val, Y, mask, lam, count, cidx, cval, d,
        num_iters, memory_size, fit_intercept, row_block, col_block, use_col)


@partial(
    jax.jit,
    static_argnames=("d", "num_iters", "memory_size", "fit_intercept",
                     "row_block", "mesh"),
)
def _lbfgs_sparse_matvec_fit_sharded(
    idx, val, Y, mask, lam, count, d: int,
    num_iters: int, memory_size: int, fit_intercept: bool, row_block: int,
    mesh=None,
):
    """dp-sharded entry: rows split over the mesh 'data' axis under
    shard_map; W and the L-BFGS history replicate, row-space reductions
    psum (the reference's treeReduce-to-master, LBFGS.scala:97-103)."""
    from jax.sharding import PartitionSpec as P

    from ...parallel import mesh as meshlib

    def body(idx_s, val_s, Y_s, mask_s, lam_s, count_s):
        dummy = jnp.zeros((1, 1), jnp.float32)
        return _sparse_matvec_fit_impl(
            idx_s, val_s, Y_s, mask_s, lam_s, count_s,
            dummy.astype(jnp.int32), dummy, d,
            num_iters, memory_size, fit_intercept, row_block,
            col_block=1, use_col=False, axis_name=meshlib.DATA_AXIS)

    # slot-major arrays shard along their MINOR n axis; mask is 1-D
    row = P(None, meshlib.DATA_AXIS)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(row, row, row, P(meshlib.DATA_AXIS), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )(idx, val, Y, mask, lam, count)


class SparseLBFGSwithL2(LabelEstimator):
    """Sparse-input least squares (LBFGS.scala `SparseLBFGSwithL2`).

    TPU-native treatment of sparsity, two routes picked by estimated
    device cost (`_route`): **gram** — the host CSR matrix is reduced ONCE
    to Gram statistics G = XᵀX (d×d) and C = XᵀY (d×k) — accumulated in
    row blocks so no dense (n, d) matrix ever materializes — and the
    L-BFGS iterations then run entirely on-device with n dropped out.
    This replaces the reference's per-iteration sparse gradient passes
    (Gradient.scala `LeastSquaresSparseGradient`) with a single sparse
    pass + dense MXU iterations. **iterative** —
    `_lbfgs_sparse_matvec_fit`: device-resident width-padded rows,
    per-iteration gather matvecs, the reference's own iteration
    structure; O(num_iters·nnz·k) total work. Counter-intuitively the
    measured chip rates (scripts/sparse_microbench.py) send even the
    k ≪ d Amazon shapes to gram: the TPU has no gather hardware, so
    the iterative route's per-nonzero cost is ~5 ns of scalar-issue
    gathers, while the Gram's d²-FLOP "blow-up" runs on the MXU at
    ~10⁵ flops per gather-equivalent — iterative wins only when d is
    hashing-trick huge (d ≳ 1e5). Intercept is fit by mean-correction
    in both routes (the reference appends a ones column,
    LBFGS.scala:223-247).
    """

    precision_tolerance = "exact"  # solver: f32/HIGHEST inputs

    def __init__(
        self,
        lam: float = 0.0,
        num_iters: int = 20,
        memory_size: int = 10,
        fit_intercept: bool = True,
        block_rows: int = 65536,
        method: "str | None" = None,
        gram_precision: str = "highest",
    ):
        self.lam = lam
        self.num_iters = num_iters
        self.memory_size = memory_size
        self.fit_intercept = fit_intercept
        self.block_rows = block_rows
        if method not in (None, "gram", "iterative"):
            raise ValueError(f"method must be gram|iterative, got {method!r}")
        self.method = method
        if gram_precision not in ("default", "high", "highest"):
            raise ValueError(
                f"gram_precision must be default|high|highest, "
                f"got {gram_precision!r}")
        # MXU passes for the Gram GEMMs: "highest" = 6-pass bf16x6
        # (f32-grade), "high" = 3-pass bf16x3 (measured ~1e-5 max
        # relative W delta vs highest at amazon shapes — PERF.md),
        # "default" = single bf16 pass. The L-BFGS iterations on G
        # stay at highest regardless.
        self.gram_precision = gram_precision
        # both routes consume the pipeline input ONCE (the iterative
        # route keeps the padded rows device-resident across iterations),
        # unlike the reference whose num_iters weight models Spark
        # recomputing the input RDD every gradient pass
        self.weight = 1

    def _route(self, n: int, d: int, k: int, w: int) -> str:
        """Pick Gram-form vs iterative-matvec by estimated device cost —
        the same decision the reference delegates to its CostModel
        (LBFGS.scala CostModel: per-iteration nnz flops), re-derived for
        one chip from MEASURED rates (scripts/sparse_microbench.py, live
        v5e): Gram = one-hot densify (a fused compare pass, ~nnz·d ops
        at ~2e12/s) + 2·n·d² MXU flops at ~2.5e13 f32-HIGHEST flop/s,
        paid ONCE. Iterative = per iteration ~3 sparse passes whose
        table gathers cost ~5 ns/element — the TPU has no gather
        hardware, so per-nonzero cost is flat in d but never below the
        scalar-issue rate. The MXU's densified brute force wins whenever
        d ≲ num_iters · (gather_ns · mxu_rate) / 2 ≈ 1e4·num_iters/2 —
        i.e. essentially always for k ≪ d workloads. Overridable via
        method=."""
        if self.method is not None:
            return self.method
        nnz = n * w
        gram_sec = nnz * d / 2.0e12 + 2.0 * n * d * d / 2.5e13
        iter_sec = self.num_iters * 3.0 * nnz * (3.0 + 1.5 * k) * 1e-9
        return "iterative" if iter_sec < gram_sec else "gram"

    def _fit_gram_device(self, idx, val, d: int, Y, n_true: int,
                         sparse_in: bool):
        """Reduce slot-major device-resident padded rows (idx/val
        (w, n), labels Y (k, n)) to Gram statistics with the one-hot
        densify + MXU accumulator, then run the L-BFGS iterations with
        n dropped out. The TPU answer to the reference's per-iteration
        sparse gradient passes for k ≪ d: one densified streaming pass
        at MXU rate beats num_iters × gather passes at the ~5 ns/element
        scalar-gather rate (no gather hardware on TPU; measured in
        scripts/sparse_microbench.py)."""
        w, n = idx.shape
        k = Y.shape[0]
        # dense block ≤ ~512 MB of HBM, multiple of 8 sublanes
        row_block = max(8, min(n, int(512e6 / (4 * (d + 1)))) // 8 * 8)
        n_pad = -(-n // row_block) * row_block
        if n_pad != n:
            idx = jnp.pad(idx, ((0, 0), (0, n_pad - n)), constant_values=d)
            val = jnp.pad(val, ((0, 0), (0, n_pad - n)))
            Y = jnp.pad(Y, ((0, 0), (0, n_pad - n)))
        G, C, col_sum = _sparse_gram_accumulate(
            jnp.asarray(idx), jnp.asarray(val),
            jnp.asarray(Y, jnp.float32), row_block, d,
            precision=self.gram_precision)
        if self.fit_intercept:
            xm = col_sum / n_true
            ym = jnp.sum(Y, axis=1) / n_true
            G = G - n_true * jnp.outer(xm, xm)
            C = C - n_true * jnp.outer(xm, ym)
        W, self.loss_history = _lbfgs_gram_fit(
            G, C, jnp.float32(self.lam), self.num_iters, self.memory_size)
        if self.fit_intercept:
            b = ym - xm @ W
            return SparseLinearMapper(W, b) if sparse_in else LinearMapper(W, b)
        return SparseLinearMapper(W) if sparse_in else LinearMapper(W)

    def _fit_iterative(self, idx, val, d: int, Y, n_true: int, sparse_in: bool,
                       cidx=None, cval=None):
        """Run the matvec L-BFGS on slot-major width-padded rows
        (idx/val (w, n), labels Y (k, n)) already shaped for the
        device; blocks the row (and column-form) dimension so per-block
        gather transients stay ≤ ~256 MB of HBM."""
        from ...data.sparse import sublane_pad8
        from ...parallel import mesh as meshlib

        w, n = idx.shape
        k = Y.shape[0]
        w8 = sublane_pad8(w)  # HBM slot count of a (w, n) tile
        mesh = meshlib.current_mesh()
        data_shards = (int(mesh.shape.get(meshlib.DATA_AXIS, 1))
                       if mesh is not None else 1)
        # dp-sharded: TRUE rows must spread across shards (shard_map
        # splits the n axis into contiguous per-device chunks), so size
        # the block within the PER-SHARD row count, then pad the global
        # count to shards × (a block multiple of that local size)
        n_per = -(-n // data_shards)
        budget = max(256, int(256e6 / (4.0 * w8 * max(k, 1))))
        row_block = min(n_per, budget, 1 << 20)
        if row_block >= 512:  # keep dynamic slices lane-aligned
            row_block = row_block // 512 * 512
        local = -(-n_per // row_block) * row_block
        n_pad = local * data_shards
        sharded = data_shards > 1
        if sharded:
            # the sharded inputs must be HOST-fetchable: jit places each
            # process's addressable shards itself, which also works for
            # a multi-host mesh (a jnp.pad/arange here would pin a
            # process-local single-device array and break placement)
            for name, arr in (("idx", idx), ("val", val), ("labels", Y)):
                if not getattr(arr, "is_fully_addressable", True):
                    raise ValueError(
                        f"sparse fit on a multi-host mesh needs "
                        f"host-side inputs, but {name} is a cross-host "
                        "global array; pass host numpy/CSR data (each "
                        "process supplies the full problem)")
        import numpy as _np

        xp = _np if sharded else jnp
        idx = xp.asarray(idx)
        val = xp.asarray(val)
        Y = xp.asarray(Y, _np.float32 if sharded else jnp.float32)
        if n_pad != n:
            idx = xp.pad(idx, ((0, 0), (0, n_pad - n)), constant_values=d)
            val = xp.pad(val, ((0, 0), (0, n_pad - n)))
            Y = xp.pad(Y, ((0, 0), (0, n_pad - n)))
        mask = (xp.arange(n_pad) < n_true).astype(xp.float32)
        if sharded:
            W, b, self.loss_history = _lbfgs_sparse_matvec_fit_sharded(
                idx, val, Y, mask,
                jnp.float32(self.lam), jnp.float32(n_true), d,
                self.num_iters, self.memory_size, self.fit_intercept,
                row_block, mesh=mesh,
            )
            bias = b if self.fit_intercept else None
            return (SparseLinearMapper(W, bias) if sparse_in
                    else LinearMapper(W, bias))
        use_col = cidx is not None
        if use_col:
            cidx = jnp.asarray(cidx)
            cval = jnp.asarray(cval)
            wc = cidx.shape[0]
            wc8 = sublane_pad8(wc)
            col_block = max(8, min(d, int(256e6 / (4.0 * wc8 * max(k, 1)))))
            d_pad = -(-d // col_block) * col_block
            if d_pad != cidx.shape[1]:
                pad = d_pad - cidx.shape[1]
                # sentinel row id: anything ≥ R's column count would be
                # out of range for take; use the appended zero col (= n_pad)
                cidx = jnp.pad(cidx, ((0, 0), (0, pad)),
                               constant_values=n_pad)
                cval = jnp.pad(cval, ((0, 0), (0, pad)))
        else:
            cidx = jnp.zeros((1, 1), jnp.int32)
            cval = jnp.zeros((1, 1), jnp.float32)
            col_block = 1
        W, b, self.loss_history = _lbfgs_sparse_matvec_fit(
            idx, val, Y, mask,
            jnp.float32(self.lam), jnp.float32(n_true), cidx, cval, d,
            self.num_iters, self.memory_size, self.fit_intercept, row_block,
            col_block, use_col,
        )
        bias = b if self.fit_intercept else None
        return SparseLinearMapper(W, bias) if sparse_in else LinearMapper(W, bias)

    def fit(self, data, labels) -> "LinearMapper | SparseLinearMapper":
        import numpy as np

        from ...data.sparse import PaddedSparseDataset, SparseDataset

        if isinstance(data, PaddedSparseDataset):
            is_ds = isinstance(labels, Dataset)
            Y = labels.array if is_ds else jnp.asarray(labels, jnp.float32)
            # Dataset labels are always row-major (n, k). A raw array
            # may instead be label-major (k, n) — huge-n callers pass
            # label-major so the (n, k) layout (narrow minor dim →
            # 128-lane tile padding) never materializes on device;
            # row-major wins the k == n ambiguity for API continuity
            label_major = (not is_ds and Y.shape[0] != data.count
                           and Y.shape[1] == data.count)
            if not label_major:
                if Y.shape[0] != data.count:  # Dataset shard-pads rows
                    Y = Y[: data.count]
                Y = Y.T
            from ...parallel import mesh as meshlib

            m = meshlib.current_mesh()
            sharded = (m is not None
                       and int(m.shape.get(meshlib.DATA_AXIS, 1)) > 1)
            # under a dp mesh keep the sharded iterative route: the
            # device-gram reduction is a single-device program
            if not sharded and self._route(
                    data.count, data.dim, Y.shape[0], data.width) == "gram":
                return self._fit_gram_device(
                    data.idx, data.val, data.dim, Y, data.count,
                    sparse_in=False)
            return self._fit_iterative(
                data.idx, data.val, data.dim, Y, data.count, sparse_in=False,
                cidx=data.cidx, cval=data.cval)

        sparse_in = isinstance(data, SparseDataset)
        if sparse_in:
            X = data.matrix
        else:
            X = data.numpy() if isinstance(data, Dataset) else np.asarray(data)
        Y = labels.numpy() if hasattr(labels, "numpy") else np.asarray(labels)
        n, d = X.shape
        k = Y.shape[1]
        if sparse_in:
            import scipy.sparse as sp

            lens = np.diff(sp.csr_matrix(X).indptr)
            w = max(1, int(lens.max()) if n else 1)
            # width-padding is shared by both device paths; bail to the
            # host-scipy Gram when an outlier-dense row blows it up
            from ...data.sparse import padded_form_ok

            if padded_form_ok(n, w, X.nnz) and (
                    self._route(n, d, k, w) == "iterative"):
                from ...parallel import mesh as meshlib

                m = meshlib.current_mesh()
                sharded = (m is not None
                           and int(m.shape.get(meshlib.DATA_AXIS, 1)) > 1)
                if sharded:
                    # host padding straight into the sharded fit: no
                    # column form (the sharded route scatters per shard)
                    # and no intermediate device round-trip
                    from ...data.sparse import pad_csr

                    idx_pad, val_pad = pad_csr(X)
                    return self._fit_iterative(
                        idx_pad, val_pad, d,
                        np.ascontiguousarray(np.asarray(Y, np.float32).T), n,
                        sparse_in=True)
                from ...data.sparse import PaddedSparseDataset as _PSD

                padded = _PSD.from_csr(X)
                return self._fit_iterative(
                    padded.idx, padded.val, d,
                    np.ascontiguousarray(np.asarray(Y, np.float32).T), n,
                    sparse_in=True, cidx=padded.cidx, cval=padded.cval)
        device_gram = None
        if sparse_in:
            # G/C/col_sum stay device arrays: a (d, d) Gram at d=16384 is
            # 1 GB — pulling it to host for the intercept correction and
            # pushing it back would reintroduce the O(d²) host traffic
            # this path exists to avoid. Returns None when width-padding
            # would blow up (outlier dense row) — host path below.
            device_gram = _sparse_gram_on_device(
                X, Y, self.block_rows, precision=self.gram_precision)
        if device_gram is not None:
            G, C, col_sum = device_gram
        else:
            G = np.zeros((d, d), np.float32)
            C = np.zeros((d, k), np.float32)
            col_sum = np.zeros((d,), np.float64)
            for start in range(0, n, self.block_rows):
                Xb = X[start : start + self.block_rows]
                Yb = Y[start : start + self.block_rows]
                Gb = Xb.T @ Xb
                G += np.asarray(
                    Gb.todense() if hasattr(Gb, "todense") else Gb, np.float32
                )
                C += np.asarray(Xb.T @ Yb, np.float32)
                col_sum += np.asarray(Xb.sum(axis=0)).ravel()
        if self.fit_intercept:
            xm = jnp.asarray(col_sum, jnp.float32) / n
            ym = jnp.asarray(Y.mean(axis=0), jnp.float32)
            G = jnp.asarray(G) - n * jnp.outer(xm, xm)
            C = jnp.asarray(C) - n * jnp.outer(xm, ym)
        W, self.loss_history = _lbfgs_gram_fit(
            jnp.asarray(G), jnp.asarray(C), jnp.float32(self.lam),
            self.num_iters, self.memory_size,
        )
        if self.fit_intercept:
            b = ym - xm @ W
            return SparseLinearMapper(W, b) if sparse_in else LinearMapper(W, b)
        return SparseLinearMapper(W) if sparse_in else LinearMapper(W)


@partial(jax.jit,
         static_argnames=("row_block", "d", "precision"))
def _sparse_gram_accumulate_chunk(idx_pad, val_pad, Y, row_block: int,
                                  d: int, n_blocks, start, carry,
                                  precision: str = "highest"):
    """Accumulate G = XᵀX, C = XᵀY, colsum(X) over `n_blocks` row
    blocks beginning at block `start`, continuing a device-resident
    carry. Each row block is densified by a fused one-hot pass
    (column d is the padding sentinel) and the Gram update runs on the
    MXU — no per-block host round trips, no (n, d) dense array in HBM.
    Chunked because one monolithic accumulation over ~10⁹ rows is a
    multi-minute single XLA execution that nothing can observe or
    interrupt until it ends; the carry stays on device so
    chunking costs only dispatch latency. `n_blocks` and `start` are
    traced (fori_loop takes a dynamic trip count), so the trailing
    partial chunk reuses the same compiled program."""
    w, n_pad = idx_pad.shape
    iota = jnp.arange(d + 1, dtype=idx_pad.dtype)

    with jax.default_matmul_precision(precision):

        def body(i, carry):
            G, C, s = carry
            i = start + i
            ib = jax.lax.dynamic_slice_in_dim(
                idx_pad, i * row_block, row_block, 1)
            vb = jax.lax.dynamic_slice_in_dim(
                val_pad, i * row_block, row_block, 1)
            Ybt = jax.lax.dynamic_slice_in_dim(Y, i * row_block, row_block, 1)
            # one-hot densify: a static sum of w compare-selects that
            # XLA fuses into ONE elementwise pass writing the dense
            # block. Measured 9x faster than scatter-add densify on TPU
            # (scripts/sparse_microbench.py: TPU scatter serializes,
            # ~10 ns/element; the fused compare pass streams at VPU
            # rate). Duplicate ids within a row accumulate, matching
            # scatter-add semantics.
            dense = sum(
                jnp.where(ib[j][:, None] == iota[None, :],
                          vb[j][:, None], 0.0)
                for j in range(w)
            )[:, :d]
            return (
                G + dense.T @ dense,
                C + dense.T @ Ybt.T,
                # f32 carry is safe here: the sequential adds happen once
                # per BLOCK (tens of iterations; within-block sums are
                # XLA tree reductions), not once per row — relative error
                # ~n_blocks·eps, far below the f32 storage of the result
                s + dense.sum(axis=0),
            )

        return jax.lax.fori_loop(0, n_blocks, body, carry)


def _sparse_gram_accumulate(idx_pad, val_pad, Y, row_block: int, d: int,
                            precision: str = "highest"):
    """Drive `_sparse_gram_accumulate_chunk` over all row blocks in
    executions bounded to a few seconds of device time each (the carry
    never leaves the device)."""
    w, n_pad = idx_pad.shape
    k = Y.shape[0]
    total_blocks = n_pad // row_block
    # per-block cost ~ 2·b·d² MXU passes + b·d·w one-hot ops; bound a
    # chunk at ~2e13 of the former + ~2e12-rate of the latter ≈ a few s
    mxu_passes = {"default": 1.0, "high": 3.0, "highest": 6.0}.get(
        str(precision), 6.0)
    per_block = mxu_passes * 2.0 * row_block * d * d / 2.0e13 \
        + row_block * (d + 1) * w / 2.0e12
    blocks_per_chunk = max(1, int(4.0 / max(per_block, 1e-9)))
    carry = (
        jnp.zeros((d, d), jnp.float32),
        jnp.zeros((d, k), jnp.float32),
        jnp.zeros((d,), jnp.float32),
    )
    start = 0
    while start < total_blocks:
        nb = min(blocks_per_chunk, total_blocks - start)
        carry = _sparse_gram_accumulate_chunk(
            idx_pad, val_pad, Y, row_block, d, jnp.int32(nb),
            jnp.int32(start), carry, precision)
        start += nb
    return carry


def _sparse_gram_on_device(X, Y, block_rows: int,
                           precision: str = "highest"):
    """Host CSR → width-padded (n, w) index/value arrays (one transfer)
    → on-device blockwise densify + MXU Gram. This is the TPU-native
    sparse reduction: the previous host-scipy Gram was d²-bound on CPU
    (209 s at d=16384, n=500k vs ~seconds of MXU work). Returns None
    when the width-padded form would be pathologically large (outlier
    dense rows) — the caller falls back to the host path."""
    import numpy as np
    import scipy.sparse as sp

    from ...data.sparse import pad_csr, padded_form_ok

    X = sp.csr_matrix(X)
    n, d = X.shape
    lens = np.diff(X.indptr)
    w = max(1, int(lens.max()) if n else 1)
    # a row cannot be split across padded slots (the Gram needs each
    # row's full outer product; splitting drops the cross terms), so
    # bail to the caller's host-scipy path on pathological padding
    if not padded_form_ok(n, w, X.nnz):
        return None
    idx_pad, val_pad = pad_csr(X)  # slot-major (w, n)
    Yt = np.ascontiguousarray(np.asarray(Y, np.float32).T)
    # bound the densified block at ~512 MB of HBM, honoring a smaller
    # caller-specified block_rows (tests use tiny blocks to exercise the
    # multi-block accumulation path)
    hbm_cap = max(8, int(512e6 / (4 * (d + 1))) // 8 * 8)
    row_block = max(8, min(block_rows, hbm_cap))
    n_pad = -(-n // row_block) * row_block
    if n_pad != n:
        idx_pad = np.pad(idx_pad, ((0, 0), (0, n_pad - n)),
                         constant_values=d)
        val_pad = np.pad(val_pad, ((0, 0), (0, n_pad - n)))
        Yt = np.pad(Yt, ((0, 0), (0, n_pad - n)))
    return _sparse_gram_accumulate(
        jnp.asarray(idx_pad), jnp.asarray(val_pad),
        jnp.asarray(Yt), row_block, d, precision=precision,
    )
