"""Pallas kernel correctness (interpret mode on CPU) and fusion peephole.

The reference implementations (`*_reference`) are the XLA paths the
dispatchers use off-TPU; the Pallas kernels must match them bit-for-bit
in structure and numerically to f32 tolerance. The peephole test mirrors
the reference's single-vs-batch parity style (PipelineSuite): the fused
RectifyPool stage must equal running SymmetricRectifier then Pooler
stage-by-stage.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu.ops import (
    rbf_block,
    rbf_block_pallas,
    rbf_block_reference,
    rectify_pool,
    rectify_pool_pallas,
    rectify_pool_reference,
)


@pytest.mark.parametrize(
    "n,h,w,k,pool,stride,alpha,max_val",
    [
        (3, 27, 27, 16, 14, 13, 0.25, 0.0),  # CIFAR north-star geometry
        (5, 12, 12, 8, 4, 4, 0.0, 0.0),  # non-overlapping windows
        (2, 10, 14, 4, 5, 3, 0.1, 0.05),  # rectangular, overlap, floor
    ],
)
def test_rectify_pool_pallas_matches_reference(n, h, w, k, pool, stride, alpha, max_val):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, h, w, k)).astype(np.float32))
    want = rectify_pool_reference(x, alpha, max_val, pool, stride)
    got = rectify_pool_pallas(
        x, alpha, max_val, pool, stride, block_n=2, interpret=True
    )
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize(
    "m,n,d",
    [
        (70, 33, 50),  # forces padding on every axis
        (128, 128, 128),  # exactly tiled
        (9, 200, 513),  # k-loop with ragged last step
    ],
)
def test_rbf_block_pallas_matches_reference(m, n, d):
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    gamma = 0.07
    want = rbf_block_reference(X, Y, gamma)
    got = rbf_block_pallas(X, Y, gamma, bm=64, bn=128, bk=256, interpret=True)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_dispatchers_fall_back_off_tpu():
    # on the CPU test mesh the dispatcher must route to the XLA path and
    # agree with it exactly
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 4)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(rectify_pool(x, 0.1, 0.0, 4, 2)),
        np.asarray(rectify_pool_reference(x, 0.1, 0.0, 4, 2)),
    )
    X = jnp.asarray(rng.normal(size=(6, 5)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(3, 5)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(rbf_block(X, Y, 0.3)), np.asarray(rbf_block_reference(X, Y, 0.3))
    )


def test_fusion_peephole_matches_stagewise():
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.images.core import Pooler, SymmetricRectifier
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer, _peephole

    rng = np.random.default_rng(3)
    imgs = rng.normal(size=(16, 27, 27, 8)).astype(np.float32)
    rect = SymmetricRectifier(alpha=0.25)
    pool = Pooler(13, 14, pool_fn="sum")

    stages = _peephole([rect, pool])
    assert len(stages) == 1 and type(stages[0]).__name__ == "_RectifyPoolStage"
    # max-pool / pixel_fn poolers must NOT be fused
    assert len(_peephole([rect, Pooler(13, 14, pool_fn="max")])) == 2

    data = Dataset(imgs)
    fused_out = FusedBatchTransformer([rect, pool], microbatch=8).apply_batch(data)
    want = pool.apply_batch(rect.apply_batch(data))
    np.testing.assert_allclose(
        fused_out.numpy(), want.numpy(), rtol=1e-5, atol=1e-5
    )


def test_krr_still_learns_with_static_gamma():
    # XOR learnability, mirroring the reference KernelModelSuite.scala:13-39
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning.kernels import KernelRidgeRegression

    rng = np.random.default_rng(4)
    X = rng.uniform(-1, 1, size=(256, 2)).astype(np.float32)
    y = np.where(X[:, 0] * X[:, 1] > 0, 1.0, -1.0).astype(np.float32)[:, None]
    model = KernelRidgeRegression(gamma=4.0, lam=1e-3, block_size=64).fit(
        Dataset(X), Dataset(y)
    )
    preds = np.sign(model.apply_batch(Dataset(X)).numpy()[:, 0])
    assert (preds == y[:, 0]).mean() > 0.95


@pytest.mark.parametrize(
    "n,h,w,c,patch,k,pool,stride,normalize",
    [
        (5, 32, 32, 3, 6, 32, 14, 13, True),   # CIFAR north-star geometry
        (3, 16, 16, 1, 5, 16, 6, 6, False),    # gray, no normalization
        (2, 20, 14, 2, 3, 8, 5, 4, True),      # rectangular
        (3, 16, 16, 1, 2, 8, 5, 5, False),     # npos=225: 16-alignment
        # padding of the patch rows; cells=9 > 8: padded output groups
        (5, 12, 12, 1, 3, 8, 10, 10, True),    # cells=1: g=8 grouping
        (3, 12, 10, 2, 3, 8, 8, 2, False),     # cells=2 (1x2): g=4
    ],
)
def test_conv_rectify_pool_pallas_matches_reference(
    n, h, w, c, patch, k, pool, stride, normalize
):
    """Fused conv+rectify+pool kernel vs the exact XLA path. The kernel
    feeds the MXU bf16 patches (what DEFAULT-precision f32 matmuls
    truncate to anyway); on CPU interpret mode the dot is genuinely
    bf16, so the tolerance covers bf16 product rounding."""
    from keystone_tpu.ops import (
        conv_rectify_pool_pallas,
        conv_rectify_pool_reference,
    )

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random(size=(n, h, w, c)).astype(np.float32))
    kern = jnp.asarray(
        rng.normal(size=(patch, patch, c, k)).astype(np.float32)
    )
    colsum = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    alpha, max_val = 0.25, 0.0

    want = conv_rectify_pool_reference(
        x, kern, colsum, bias, alpha, max_val, pool, stride, normalize
    )
    g_cmajor = jnp.asarray(
        np.asarray(kern).transpose(2, 0, 1, 3).reshape(-1, k)
    )
    got = conv_rectify_pool_pallas(
        x, g_cmajor, colsum, bias, alpha, max_val, pool, stride,
        normalize, patch, interpret=True,
    )
    assert got.shape == want.shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-2 * scale
    )


@pytest.mark.parametrize(
    "n,h,w,c,patch,k,pool,stride,normalize,budget,tk,k_blocks",
    [
        # the 10 MB budget itself splits these banks at a small posp
        (3, 12, 12, 1, 3, 12288, 4, 3, True, None, 4096, 3),    # cells=9
        (2, 12, 12, 1, 3, 8200, 10, 10, False, None, 768, 11),  # K % 128
        # smaller budgets at the CIFAR geometry, over more than one
        # image block: tight groups of two images, then one image a loop
        # iteration (padded output groups)
        (5, 32, 32, 3, 6, 1100, 14, 13, True, 6 << 20, 256, 5),  # K % tile
        (5, 32, 32, 3, 6, 300, 14, 13, True, 3 << 20, 128, 3),
        (7, 32, 32, 3, 6, 200, 14, 13, True, 2 << 20, 128, 2),  # K % 128
        (3, 16, 16, 1, 2, 136, 5, 5, False, 700_000, 128, 2),   # cells=9
    ],
)
def test_conv_rectify_pool_pallas_tiles_over_filter_blocks(
    monkeypatch, n, h, w, c, patch, k, pool, stride, normalize, budget,
    tk, k_blocks,
):
    """A bank too wide for the VMEM budget runs as filter blocks of tk
    lanes, padded with zero filters to whole tiles, and still returns
    (N, gy, gx, 2K) with the positive half first."""
    import keystone_tpu.ops.pallas_kernels as pk

    if budget is not None:
        monkeypatch.setattr(pk, "_FUSED_CONV_VMEM_BUDGET", budget)
    pos_h, pos_w = h - patch + 1, w - patch + 1
    cells = ((pos_h - pool) // stride + 1) * ((pos_w - pool) // stride + 1)
    geometry = pk._fused_conv_geometry(
        -(-(pos_h * pos_w) // 16) * 16, -(-(c * patch * patch) // 128) * 128,
        k, cells)
    assert geometry[0] > 0 and geometry[3] == tk, geometry
    assert -(-k // tk) == k_blocks

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random(size=(n, h, w, c)).astype(np.float32))
    kern = jnp.asarray(
        rng.normal(size=(patch, patch, c, k)).astype(np.float32))
    colsum = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    alpha, max_val = 0.25, 0.0

    want = np.asarray(pk.conv_rectify_pool_reference(
        x, kern, colsum, bias, alpha, max_val, pool, stride, normalize))
    got = np.asarray(pk.conv_rectify_pool_pallas(
        x, pk.hwio_to_cmajor(kern), colsum, bias, alpha, max_val, pool,
        stride, normalize, patch, interpret=True))
    assert got.shape == want.shape == (n,) + want.shape[1:3] + (2 * k,)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-2 * scale)
    # the halves are where the contract puts them: the positive half of
    # filter f at column f, its negative half at column K + f. Where the
    # conv output is far from zero exactly one half is above max_val.
    conv = np.asarray(pk.folded_conv_reference(
        x, kern, colsum, bias, normalize))
    strongly_pos = (conv > alpha + 0.5).all(axis=(1, 2))      # (n, k)
    strongly_neg = (conv < -alpha - 0.5).all(axis=(1, 2))
    assert strongly_pos.any() and strongly_neg.any()
    pos_half, neg_half = got[..., :k], got[..., k:]

    def cell_of(mask):  # (n, k) -> every pooled cell of those filters
        return np.broadcast_to(mask[:, None, None, :], pos_half.shape)

    assert (pos_half[cell_of(strongly_pos)] > 0).all()
    assert (neg_half[cell_of(strongly_pos)] == 0).all()
    assert (neg_half[cell_of(strongly_neg)] > 0).all()
    assert (pos_half[cell_of(strongly_neg)] == 0).all()


def test_fused_conv_geometry_tiles_the_documented_width():
    """RandomPatchCifar's documented 10,000 filters (posp 736, dp 128,
    cells 4): eligible, as filter tiles inside the budget; the widths
    that fit whole keep their single block."""
    import keystone_tpu.ops.pallas_kernels as pk

    b, g, rows, tk = pk._fused_conv_geometry(736, 128, 10000, 4)
    assert b > 0 and b % g == 0 and rows % 8 == 0
    assert tk % 128 == 0 and tk < 10000
    assert pk._fused_conv_vmem_bytes(
        736, 128, b, g, rows, tk, 2 * tk, 2) <= 10 * (1 << 20)
    assert pk._fused_conv_block_images(736, 128, 10000, 4) == b
    for k, want in ((16, 22), (64, 22), (256, 14)):
        assert pk._fused_conv_geometry(736, 128, k, 4) == (want, 2, 8, k)
    # the tiles are evened out: three of 384 cover 1,100 filters
    assert pk._fused_conv_geometry(736, 128, 1100, 4)[3] == 384
    # nothing fits: not one image at the narrowest tile; no pooled cell
    assert pk._fused_conv_geometry(1 << 16, 128, 10000, 4)[0] == 0
    assert pk._fused_conv_geometry(736, 128, 10000, 0)[0] == 0


def test_conv_fusion_peephole_matches_stagewise():
    """The _ConvRectifyPoolStage peephole (off-TPU: reference path) must
    equal running Convolver, SymmetricRectifier, Pooler stage-by-stage
    through a FusedBatchTransformer."""
    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.images.core import (
        Convolver,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer, _peephole

    rng = np.random.default_rng(2)
    imgs = rng.random(size=(6, 16, 16, 3)).astype(np.float32)
    filters = rng.normal(size=(8, 5 * 5 * 3)).astype(np.float32)
    conv = Convolver(filters, 16, 16, 3, normalize_patches=True)
    rect = SymmetricRectifier(alpha=0.1)
    pool = Pooler(4, 5, pool_fn="sum")  # distinct stride/size: catches transposition

    stages = [conv, rect, pool]
    merged = _peephole(stages)
    assert len(merged) == 1, [type(s).__name__ for s in merged]

    fused = FusedBatchTransformer(stages, microbatch=4)
    got = fused.apply_batch(Dataset(imgs)).numpy()

    want = imgs
    want = np.asarray(conv.batch_fn()(jnp.asarray(want)))
    want = np.asarray(rect.batch_fn()(jnp.asarray(want)))
    want = np.asarray(pool.batch_fn()(jnp.asarray(want)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_conv_fused_stage_ineligible_fallback_reconstructs_hwio(monkeypatch):
    """When the Pallas block geometry can't fit VMEM the fused stage must
    fall back to the reference conv with a correctly reconstructed HWIO
    kernel (inverse of the channel-major packing)."""
    from keystone_tpu.nodes.images.core import Convolver, Pooler, SymmetricRectifier
    from keystone_tpu.nodes.util.fusion import _ConvRectifyPoolStage

    rng = np.random.default_rng(3)
    imgs = jnp.asarray(rng.random(size=(4, 16, 16, 3)).astype(np.float32))
    filters = rng.normal(size=(8, 5 * 5 * 3)).astype(np.float32)
    conv = Convolver(filters, 16, 16, 3, normalize_patches=True)
    stage = _ConvRectifyPoolStage(conv, 0.1, 0.0, 5, 4)

    # force the fused path on and make the geometry ineligible
    monkeypatch.setattr("keystone_tpu.ops.use_fused_conv", lambda: True)
    monkeypatch.setattr(
        "keystone_tpu.ops.pallas_kernels.use_fused_conv", lambda: True
    )
    monkeypatch.setattr(
        "keystone_tpu.ops.pallas_kernels._fused_conv_geometry",
        lambda *a, **k: (0, 1, 8, 8),
    )
    key, params, fn = stage.fuse()
    assert key[-1] is True  # fused flag baked into the program key
    got = np.asarray(fn(params, imgs))

    from keystone_tpu.ops import conv_rectify_pool_reference

    want = np.asarray(
        conv_rectify_pool_reference(
            imgs, conv.kernel, conv.colsum, conv.bias, 0.1, 0.0, 5, 4, True
        )
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _canary_case(seed, n):
    rng = np.random.default_rng(seed)
    imgs = jnp.asarray(rng.random(size=(n, 16, 16, 3)).astype(np.float32))
    kern = jnp.asarray(rng.normal(size=(5, 5, 3, 8)).astype(np.float32))
    colsum = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    return imgs, kern, colsum, bias


def test_fused_conv_canary_raises_what_it_did_not_design(monkeypatch):
    """A kernel geometry whose COMPILE fails for a reason nobody
    designed (a scoped-vmem OOM, a Mosaic reject, a backend that is not
    there) must fail the caller, not become the XLA path in silence:
    the eager per-geometry canary lets the exception through and keeps
    no verdict and no retry marker, so every later call asks again."""
    import keystone_tpu.ops.pallas_kernels as pk

    imgs, kern, colsum, bias = _canary_case(5, 3)
    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("Mosaic scoped-vmem OOM (simulated)")

    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    monkeypatch.setattr(pk, "conv_rectify_pool_pallas", boom)
    monkeypatch.setattr(pk, "_fused_conv_canary", {})

    for _ in range(3):
        with pytest.raises(RuntimeError, match="scoped-vmem OOM"):
            pk.conv_rectify_pool(
                imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True)
    assert calls["n"] == 3, calls["n"]
    assert pk._fused_conv_canary == {}

    # a failure, then a kernel that works: nothing was remembered
    # against the geometry, so the next call records a pass
    want = np.asarray(pk.conv_rectify_pool_reference(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    monkeypatch.setattr(pk, "conv_rectify_pool_pallas",
                        lambda *a, **kw: jnp.asarray(want))
    got = np.asarray(pk.conv_rectify_pool(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert list(pk._fused_conv_canary.values()) == [True]

    # a canary that runs and returns garbage is a failure too
    pk._fused_conv_canary.clear()
    monkeypatch.setattr(pk, "conv_rectify_pool_pallas",
                        lambda *a, **kw: jnp.full((1, 3, 3, 16), jnp.nan))
    with pytest.raises(FloatingPointError):
        pk.conv_rectify_pool(imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True)
    assert pk._fused_conv_canary == {}


def test_fused_conv_canary_records_the_designed_demotion(monkeypatch):
    """The one designed demotion — a block geometry that cannot fit VMEM
    (`FusedConvIneligibleError`) — takes the XLA path, is tried once,
    and stays readable as False in the verdict dict."""
    import keystone_tpu.ops.pallas_kernels as pk

    imgs, kern, colsum, bias = _canary_case(5, 3)
    calls = {"n": 0}

    def ineligible(*a, **k):
        calls["n"] += 1
        raise pk.FusedConvIneligibleError("no block fits VMEM (simulated)")

    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    monkeypatch.setattr(pk, "conv_rectify_pool_pallas", ineligible)
    monkeypatch.setattr(pk, "_fused_conv_canary", {})

    want = np.asarray(pk.conv_rectify_pool_reference(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    for _ in range(3):
        got = np.asarray(pk.conv_rectify_pool(
            imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert calls["n"] == 1, calls["n"]
    assert list(pk._fused_conv_canary.items()) == [
        ((16, 16, 3, 8, 5, 4, True, 5), False)]


@pytest.mark.parametrize("eligible", [True, False],
                         ids=["traced", "demoted"])
def test_fused_conv_counts_each_program_traced(monkeypatch, eligible):
    """`pallas.fused_conv.traced` counts the programs that staged the
    Mosaic call and `pallas.fused_conv.demoted` those the canary's one
    designed demotion sent to XLA: once per program traced, not per
    run, and neither moves where the fused path is off."""
    import jax

    import keystone_tpu.ops.pallas_kernels as pk
    from keystone_tpu.telemetry import metrics_delta

    imgs, kern, colsum, bias = _canary_case(8, 3)
    want = np.asarray(pk.conv_rectify_pool_reference(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))

    def kernel(*a, **kw):
        if not eligible:
            raise pk.FusedConvIneligibleError("no block fits (simulated)")
        return jnp.asarray(want)

    monkeypatch.setattr(pk, "conv_rectify_pool_pallas", kernel)
    monkeypatch.setattr(pk, "_fused_conv_canary", {})
    program = jax.jit(lambda x: pk.conv_rectify_pool(
        x, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    with metrics_delta() as off:
        program(imgs)
    assert not any(k.startswith("pallas.fused_conv") for k in off.counters())

    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    program = jax.jit(lambda x: pk.conv_rectify_pool(
        x, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    with metrics_delta() as on:
        for _ in range(3):  # one trace, three runs
            np.testing.assert_allclose(
                np.asarray(program(imgs)), want, rtol=1e-6, atol=1e-6)
    assert on.counter("pallas.fused_conv.traced") == (1 if eligible else 0)
    assert on.counter("pallas.fused_conv.demoted") == (0 if eligible else 1)


def test_fused_conv_canary_multihost_verdict_is_broadcast(monkeypatch):
    """In a multi-process job processes with different local canary
    verdicts would compile divergent programs for a collective launch.
    With process_count > 1 every process must adopt process 0's verdict
    (broadcast); a failure nobody designed still raises, before any
    broadcast. (The single-process rules are covered by the two tests
    above.)"""
    import jax
    from jax.experimental import multihost_utils

    import keystone_tpu.ops.pallas_kernels as pk

    imgs, kern, colsum, bias = _canary_case(6, 2)
    calls = {"n": 0}
    broadcasts = []

    def ineligible(*a, **k):
        calls["n"] += 1
        raise pk.FusedConvIneligibleError("no block fits VMEM (simulated)")

    def fake_broadcast(x):
        # this process plays the non-0 host: process 0's verdict (False
        # here — it demoted too) comes back regardless of local state
        broadcasts.append(bool(np.asarray(x)))
        return np.asarray(False)

    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    monkeypatch.setattr(pk, "conv_rectify_pool_pallas", ineligible)
    monkeypatch.setattr(pk, "_fused_conv_canary", {})
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        multihost_utils, "broadcast_one_to_all", fake_broadcast)

    want = np.asarray(pk.conv_rectify_pool_reference(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    for _ in range(3):
        got = np.asarray(pk.conv_rectify_pool(
            imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # ONE local attempt, ONE broadcast, then a permanent cached verdict
    assert calls["n"] == 1, calls["n"]
    assert broadcasts == [False]
    assert list(pk._fused_conv_canary.values()) == [False]

    # a host whose local canary PASSES must still adopt process 0's
    # demoting verdict (the divergence the broadcast exists to close)
    pk._fused_conv_canary.clear()
    monkeypatch.setattr(pk, "conv_rectify_pool_pallas",
                        lambda *a, **k: jnp.zeros((2, 2, 2, 8)))
    got = np.asarray(pk.conv_rectify_pool(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert broadcasts[-1] is True  # local verdict was pass...
    assert list(pk._fused_conv_canary.values()) == [False]  # ...p0 wins

    # a failure nobody designed raises here as it does on one host:
    # nothing is broadcast and nothing is remembered
    pk._fused_conv_canary.clear()
    n_broadcasts = len(broadcasts)

    def boom(*a, **k):
        raise RuntimeError("Mosaic reject (simulated)")

    monkeypatch.setattr(pk, "conv_rectify_pool_pallas", boom)
    with pytest.raises(RuntimeError, match="Mosaic reject"):
        pk.conv_rectify_pool(imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True)
    assert len(broadcasts) == n_broadcasts
    assert pk._fused_conv_canary == {}


def test_chain_canary_raises_or_records(monkeypatch):
    """The chain kernels' canary follows the same rule as the fused
    conv's: the designed `ChainKernelIneligibleError` demotes once and
    is recorded; anything else propagates and leaves no verdict."""
    import keystone_tpu.ops.chain_kernels as ck

    monkeypatch.setattr(ck, "_chain_canary", {})
    calls = {"n": 0}

    def ineligible():
        calls["n"] += 1
        raise ck.ChainKernelIneligibleError("no block fits VMEM (simulated)")

    assert ck._canary_ok("geo-a", ineligible) is False
    assert ck._canary_ok("geo-a", ineligible) is False
    assert calls["n"] == 1 and ck._chain_canary == {"geo-a": False}

    def boom():
        raise RuntimeError("Mosaic reject (simulated)")

    for _ in range(2):
        with pytest.raises(RuntimeError, match="Mosaic reject"):
            ck._canary_ok("geo-b", boom)
    assert "geo-b" not in ck._chain_canary

    with pytest.raises(FloatingPointError):
        ck._canary_ok("geo-b", lambda: jnp.full((1, 4), jnp.inf))
    assert ck._canary_ok("geo-b", lambda: jnp.ones((1, 4))) is True
    assert ck._chain_canary == {"geo-a": False, "geo-b": True}




def test_canaries_run_inside_an_enclosing_trace(monkeypatch):
    """The dispatchers consult their canary at TRACE time, inside the
    enclosing program's `jit`. An eager canary there is staged into the
    enclosing program and reading its result raises
    (TracerArrayConversionError), which the old catch-all turned into
    the XLA path for every geometry; the canary now compiles and runs
    outside the caller's trace."""
    import jax

    import keystone_tpu.ops.chain_kernels as ck
    import keystone_tpu.ops.pallas_kernels as pk

    imgs, kern, colsum, bias = _canary_case(7, 3)
    real = pk.conv_rectify_pool_pallas
    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    monkeypatch.setattr(
        pk, "conv_rectify_pool_pallas",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    monkeypatch.setattr(pk, "_fused_conv_canary", {})

    got = jax.jit(lambda x, g, cs, b: pk.conv_rectify_pool(
        x, g, cs, b, 0.1, 0.0, 5, 4, True))(imgs, kern, colsum, bias)
    want = pk.conv_rectify_pool_reference(
        imgs, kern, colsum, bias, 0.1, 0.0, 5, 4, True)
    assert list(pk._fused_conv_canary.values()) == [True]
    # the kernel's answer (bf16 patch feed), not the reference's own
    want = np.asarray(want)
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert 0.0 < err < 5e-3, err

    monkeypatch.setattr(ck, "_chain_canary", {})

    @jax.jit
    def traced(x):
        assert ck._canary_ok("geo", lambda: ck.run_outside_trace(
            lambda xc: ck.rectify_pool_vectorize_pallas(
                xc, 0.1, 0.0, 4, 4, interpret=True),
            np.zeros((3, 8, 8, 4), np.float32)))
        return x + 1.0

    traced(jnp.ones(3))
    assert ck._chain_canary == {"geo": True}
