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
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        fused_stages,
    )

    rng = np.random.default_rng(3)
    imgs = rng.normal(size=(16, 27, 27, 8)).astype(np.float32)
    rect = SymmetricRectifier(alpha=0.25)
    pool = Pooler(13, 14, pool_fn="sum")

    stages = fused_stages([rect, pool])
    assert len(stages) == 1 and type(stages[0]).__name__ == "_RectifyPoolStage"
    # max-pool / pixel_fn poolers must NOT be fused
    assert len(fused_stages([rect, Pooler(13, 14, pool_fn="max")])) == 2

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
    model = KernelRidgeRegression(gamma=4.0, lam=1e-3, block_size=64,
                                  num_epochs=2).fit(
        Dataset(X), Dataset(y)
    )
    preds = np.sign(model.apply_batch(Dataset(X)).numpy()[:, 0])
    assert (preds == y[:, 0]).mean() > 0.95


# (h, w, patch, pool, stride) of every geometry the kernel's tests run,
# and what `_pool_layout` makes of each
_POOL_GEOMETRIES = [
    (32, 32, 6, 14, 13),   # CIFAR: 27x27, 9 classes, 784 rows, 72 to the dot
    (16, 16, 5, 6, 6),     # 12x12, 4 disjoint windows: 4 classes of 36
    (20, 14, 3, 5, 4),     # 18x12 rectangular, 21 small classes: identity
    (16, 16, 2, 5, 5),     # 15x15, 9 disjoint windows: 9 classes of 25
    (12, 12, 3, 10, 10),   # 10x10, one window: one class of 100
    (12, 10, 3, 8, 2),     # 10x8, overlapping windows: classes 16, 48, 16
    (12, 12, 3, 4, 3),     # 10x10, pool 4 stride 3, 25 classes: identity
    (29, 29, 3, 3, 1),     # 27x27, pool 3 stride 1: every position its own
]


@pytest.mark.parametrize("h,w,patch,pool,stride", _POOL_GEOMETRIES)
def test_pool_layout_orders_the_positions_by_class(h, w, patch, pool, stride):
    """The layout alone: the classes partition the covered positions,
    the rows of a class share one column of the pool matrix, uncovered
    positions are in no class, and the reduced matrix over the class
    sums is the pool matrix over the rows in the old order."""
    import keystone_tpu.ops.pallas_kernels as pk

    pos_h, pos_w = h - patch + 1, w - patch + 1
    gy, gx = (pos_h - pool) // stride + 1, (pos_w - pool) // stride + 1
    layout = pk._pool_layout(pos_h, pos_w, pool, stride)
    # the pool matrix over row-major positions, as the kernel had it
    full = np.zeros((gy * gx, pos_h, pos_w), np.float32)
    for iy in range(gy):
        for ix in range(gx):
            full[iy * gx + ix, iy * stride:iy * stride + pool,
                 ix * stride:ix * stride + pool] = 1.0
    covered = full.any(axis=0)

    assert layout.posp % 16 == 0
    assert layout.weights.shape == (gy * gx, layout.dot_rows)
    assert all(offset % 8 == 0 for offset, *_ in layout.pieces)
    rng = np.random.default_rng(0)
    act = rng.normal(size=(pos_h, pos_w, 3)).astype(np.float32)
    want = np.einsum("cyx,yxk->ck", full, act)
    if not layout.presummed_rows:
        # ordering would not halve the contraction: row-major positions,
        # the whole grid one rectangle, every row to the dot
        assert layout.rects == ((0, pos_h, 0, pos_w),)
        assert layout.pieces == (
            (0, layout.posp // 8, pos_h * pos_w, False),)
        assert layout.presummed_rows == 0
        np.testing.assert_array_equal(
            layout.weights[:, :pos_h * pos_w].reshape(full.shape), full)
        assert not layout.weights[:, pos_h * pos_w:].any()
        return
    assert 2 * layout.dot_rows <= -(-pos_h * pos_w // 16) * 16
    seen = np.zeros((pos_h, pos_w), int)
    sums, end = [], 0
    for (y0, y1, x0, x1), (offset, tiles, valid, summed) in zip(
            layout.rects, layout.pieces, strict=True):
        seen[y0:y1, x0:x1] += 1
        columns = full[:, y0:y1, x0:x1].reshape(gy * gx, -1)
        assert (columns == columns[:, :1]).all() and columns.any()
        assert offset == end and valid == columns.shape[1]
        assert tiles == -(-valid // 8) and summed == (tiles > 1)
        end = offset + 8 * tiles
        # what the kernel hands the dot of this class: its tiles added
        # up, the padded rows kept out
        rows = np.zeros((8 * tiles, 3), np.float32)
        rows[:valid] = act[y0:y1, x0:x1].reshape(valid, 3)
        sums.append(rows.reshape(tiles, 8, 3).sum(axis=0))
    assert end <= layout.posp < end + 16
    np.testing.assert_array_equal(seen, covered.astype(int))
    assert layout.presummed_rows == sum(
        8 * t for _, t, _, summed in layout.pieces if summed)
    np.testing.assert_allclose(
        layout.weights @ np.concatenate(sums), want, rtol=1e-5, atol=1e-5)
    # two images a group: block-diagonal, rows padded to whole tiles
    M = pk._pool_matrix(layout, 2)
    assert M.shape == (-(-2 * gy * gx // 8) * 8, 2 * layout.dot_rows)
    np.testing.assert_array_equal(
        M[gy * gx:2 * gy * gx, layout.dot_rows:], layout.weights)
    assert not M[:gy * gx, layout.dot_rows:].any()


def test_pool_layout_at_the_cifar_geometry():
    """27 x 27 positions, pool 14 stride 13: four blocks of 13 x 13 (176
    rows padded), four edges of 13 (16), the centre (8): 776, 784 an
    image; 8 rows of partial sums a class go to the dot."""
    import keystone_tpu.ops.pallas_kernels as pk

    layout = pk._pool_layout(27, 27, 14, 13)
    assert layout.posp == 784 and layout.dot_rows == 72
    assert [8 * tiles for _, tiles, _, _ in layout.pieces] == [
        176, 16, 176, 16, 8, 16, 176, 16, 176]
    assert [valid for _, _, valid, _ in layout.pieces] == [
        169, 13, 169, 13, 1, 13, 169, 13, 169]
    assert layout.presummed_rows == 776 - 8  # all but the centre's tile
    # the centre feeds all four cells, an edge two, a block one
    assert layout.weights.sum(axis=0).reshape(9, 8)[:, 0].tolist() == [
        1, 2, 1, 2, 4, 2, 1, 2, 1]


@pytest.mark.parametrize(
    "n,h,w,c,patch,k,pool,stride,normalize,bias0,tol",
    [
        (5, 32, 32, 3, 6, 32, 14, 13, True, 0.0, 2e-2),   # CIFAR north-star
        # geometry
        (3, 16, 16, 1, 5, 16, 6, 6, False, 0.0, 2e-2),    # gray, no
        # normalization
        (2, 20, 14, 2, 3, 8, 5, 4, True, 0.0, 2e-2),      # rectangular
        (3, 16, 16, 1, 2, 8, 5, 5, False, 0.0, 2e-2),     # npos=225:
        # 16-alignment padding of the patch rows; cells=9 > 8: padded
        # output groups
        (5, 12, 12, 1, 3, 8, 10, 10, True, 0.0, 2e-2),    # cells=1: g=8
        # grouping
        (3, 12, 10, 2, 3, 8, 8, 2, False, 0.0, 2e-2),     # cells=2 (1x2): g=4
        # a padded row is not a zero: a zero patch rectifies to
        # max(max_val, bias - alpha) = 4.75, and the 7 padded rows of a
        # 13 x 13 class would add 33 to a sum of about 1,000. Pixels and
        # filters that bf16 holds exactly leave the two paths float32
        # sums of the same terms, so 1e-4 of the scale sees one such row
        (5, 32, 32, 3, 6, 32, 14, 13, True, 5.0, 1e-4),
        (3, 12, 12, 1, 3, 8, 4, 3, True, 5.0, 1e-4),      # identity layout
    ],
)
def test_conv_rectify_pool_pallas_matches_reference(
    n, h, w, c, patch, k, pool, stride, normalize, bias0, tol
):
    """Fused conv+rectify+pool kernel vs the exact XLA path. The kernel
    feeds the MXU bf16 patches (what DEFAULT-precision f32 matmuls
    truncate to anyway); on CPU interpret mode the dot is genuinely
    bf16, so the tolerance covers bf16 product rounding, except where
    the inputs are rounded to bf16 beforehand (tol under 1e-2)."""
    from keystone_tpu.ops import (
        conv_rectify_pool_pallas,
        conv_rectify_pool_reference,
    )

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.random(size=(n, h, w, c)).astype(np.float32))
    kern = jnp.asarray(
        rng.normal(size=(patch, patch, c, k)).astype(np.float32)
    )
    if tol < 1e-2:
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
        kern = kern.astype(jnp.bfloat16).astype(jnp.float32)
    colsum = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    bias = jnp.asarray(
        (bias0 + rng.normal(size=(k,))).astype(np.float32))
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
        np.asarray(got), np.asarray(want), atol=tol * scale
    )


@pytest.mark.parametrize(
    "n,h,w,c,patch,k,pool,stride,normalize,budget,tk,k_blocks",
    [
        # Budgets from `_fused_conv_vmem_bytes` as PR 34 left it (the
        # rectified halves are never whole: 8 rows of partial sums a
        # class go to the pool dot, 72 rows an image at the CIFAR
        # geometry), each between what its tile takes and what the next
        # wider choice would.
        # 10x10 positions, pool 4 stride 3: identity layout (112 rows,
        # all of them to the dot), cells=9 so one image a group, R=16.
        # The 10 MB budget itself splits this bank: a tile tk takes
        # 64,512 + 2,112 tk, the widest that fits is 4,864 (10,337,280),
        # and the three blocks it needs are evened out to 4,096
        (3, 12, 12, 1, 3, 12288, 4, 3, True, None, 4096, 3),    # cells=9
        # 10x10 positions, one window: one class, 112 rows, 8 to the
        # dot, groups of 8 images. The whole bank takes 7,500,032 bytes
        # at one image a group and fits 10 MB; under 4 MB a group of 8
        # takes 460,800 + 4,736 tk: 768 fits (4,098,048), not 896, and
        # whole rounds of the four matrix units are 512: 17 blocks
        (2, 12, 12, 1, 3, 8200, 10, 10, False, 4 << 20, 512, 17),  # K % 128
        # smaller budgets at the CIFAR geometry, over more than one
        # image block. Tight groups of two images take 807,424 +
        # 8,064 tk at a tile tk: 256 fits 3 MB (2,871,808; 384 does not,
        # nor the whole bank at one image a group, 5,122,304), 128 fits
        # 1,900,000 (1,839,616; the whole 300 filters at one image a
        # group take 1,931,520)
        (5, 32, 32, 3, 6, 1100, 14, 13, True, 3 << 20, 256, 5),  # K % tile
        (5, 32, 32, 3, 6, 300, 14, 13, True, 1_900_000, 128, 3),
        # then one image a loop iteration (padded output groups):
        # 403,712 + 4,352 tk, 960,768 at 128, under 1 MB where two
        # images a group take 1,839,616 and the whole 200 filters at one
        # image a group 1,452,288
        (7, 32, 32, 3, 6, 200, 14, 13, True, 1 << 20, 128, 2),  # K % 128
        # 15x15 positions, 9 disjoint windows: 288 rows, 72 to the dot,
        # R=16: a tile takes 152,064 + 2,496 tk (471,552 at 128), the
        # whole 136 filters 672,256
        (3, 16, 16, 1, 2, 136, 5, 5, False, 600_000, 128, 2),   # cells=9
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
    _, geometry = pk._fused_conv_plan(h, w, c, k, pool, stride, patch)
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
    """RandomPatchCifar's documented 10,000 filters (784 class-ordered
    patch rows an image, 72 rows of partial sums to the pool dot, dp 128,
    cells 4): eligible, as filter tiles inside the budget; the widths
    that fit whole keep their single block."""
    import keystone_tpu.ops.pallas_kernels as pk

    layout, (b, g, rows, tk) = pk._fused_conv_plan(32, 32, 3, 10000, 14, 13, 6)
    assert (layout.posp, layout.dot_rows) == (784, 72)
    assert (b, g, rows, tk) == pk._fused_conv_geometry(784, 72, 128, 10000, 4)
    assert b > 0 and b % g == 0 and rows % 8 == 0
    assert tk % 128 == 0 and tk < 10000
    assert pk._fused_conv_vmem_bytes(
        784, 72, 128, b, g, rows, tk, 2 * tk, 2) <= 10 * (1 << 20)
    assert pk._fused_conv_block_images(784, 72, 128, 10000, 4) == b
    # two images a group at a tile tk: patches 802,816, z 6,272 tk, the
    # partial sums of both signs 1,152 tk, the output tile twice 128 tk,
    # the filter tile twice 512 tk, the pool matrix 4,608: 807,424 +
    # 8,064 tk. 1,152 takes 10,097,152 of the 10,485,760; 1,280 would
    # take 11,129,344. Nine lane columns are two rounds of the four
    # matrix units and a third with one, so the tile is the 1,024 of two
    # whole rounds: ten tiles where the stored halves held it to twenty
    # of 512 (0.636 ms a microbatch of 32 against 0.689 at 1,152 and
    # 0.702 at 512, PERF.md section 6, PR 34)
    assert (b, g, rows, tk) == (2, 2, 8, 1024)
    # whole banks: an image of the block is 401,408 bytes of patches
    # (784 x 128 bf16, twice); beside them one group's z, partial sums
    # and the output: 22 images at 128 lanes are 9,835,008 (24:
    # 10,646,016), 20 at 256 are 10,326,528 (22: 11,162,112)
    for k, want in ((16, 22), (64, 22), (256, 20)):
        assert pk._fused_conv_geometry(784, 72, 128, k, 4) == (want, 2, 8, k)
    # a bank that fits whole at one image a group is one block: 2,432
    # lanes take 10,365,184
    assert pk._fused_conv_geometry(784, 72, 128, 2400, 4) == (1, 1, 8, 2400)
    # the tiles are evened out: three of 896 cover 2,500 filters, not
    # two of 1,024 and a third of 452
    assert pk._fused_conv_geometry(784, 72, 128, 2500, 4)[3] == 896
    # nothing fits: not one image at the narrowest tile; no pooled cell
    assert pk._fused_conv_geometry(1 << 16, 72, 128, 10000, 4)[0] == 0
    assert pk._fused_conv_geometry(784, 72, 128, 10000, 0)[0] == 0


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
    from keystone_tpu.nodes.util.fusion import (
        FusedBatchTransformer,
        fused_stages,
    )

    rng = np.random.default_rng(2)
    imgs = rng.random(size=(6, 16, 16, 3)).astype(np.float32)
    filters = rng.normal(size=(8, 5 * 5 * 3)).astype(np.float32)
    conv = Convolver(filters, 16, 16, 3, normalize_patches=True)
    rect = SymmetricRectifier(alpha=0.1)
    pool = Pooler(4, 5, pool_fn="sum")  # distinct stride/size: catches transposition

    stages = [conv, rect, pool]
    merged = fused_stages(stages)
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
    from keystone_tpu.nodes.images.core import _ConvRectifyPoolStage

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
    # 12x12 positions, pool 5 stride 4: 9 classes, two images a loop
    # iteration; 64 rows an image are summed on the vector unit and 72
    # go to the pool dot
    assert on.counter("pallas.fused_conv.pool_rows_presummed") == (
        128 if eligible else 0)
    assert on.counter("pallas.fused_conv.pool_dot_rows") == (
        144 if eligible else 0)


@pytest.mark.parametrize("k,pool,stride,presummed,dot_rows", [
    (10000, 14, 13, 1536, 144),  # the cell: 2 images x (768 summed, 72 left)
    (256, 14, 13, 1536, 144),    # the same rows at any width
    (256, 5, 4, 0, 736),         # identity layout, one image a loop iteration
], ids=["cifar_10000", "cifar_256", "identity"])
def test_fused_conv_counts_the_rows_it_sums_before_the_pool_dot(
        monkeypatch, k, pool, stride, presummed, dot_rows):
    """`pallas.fused_conv.pool_rows_presummed`: the rows one loop
    iteration adds up on the vector unit; `.pool_dot_rows`: the rows it
    leaves the pool dot to contract over. From shapes, once a program
    traced, beside `.traced`."""
    import jax

    import keystone_tpu.ops.pallas_kernels as pk
    from keystone_tpu.telemetry import metrics_delta

    monkeypatch.setattr(pk, "use_fused_conv", lambda: True)
    monkeypatch.setattr(pk, "_fused_conv_canary", {})
    gy = (27 - pool) // stride + 1
    monkeypatch.setattr(
        pk, "conv_rectify_pool_pallas",
        lambda images, *a, **kw: jnp.zeros(
            (images.shape[0], gy, gy, 2 * k), jnp.float32))
    program = jax.jit(lambda x, g: pk.conv_rectify_pool(
        x, g, jnp.zeros((k,)), jnp.zeros((k,)), 0.25, 0.0, pool, stride,
        True))
    with metrics_delta() as on:
        program.lower(jax.ShapeDtypeStruct((32, 32, 32, 3), jnp.float32),
                      jax.ShapeDtypeStruct((6, 6, 3, k), jnp.float32))
    assert on.counter("pallas.fused_conv.traced") == 1
    assert on.counter("pallas.fused_conv.pool_rows_presummed") == presummed
    assert on.counter("pallas.fused_conv.pool_dot_rows") == dot_rows


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
