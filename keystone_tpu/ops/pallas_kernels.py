"""Pallas TPU kernels for the hot ops, with XLA fallbacks.

Four ops dominate HBM traffic in the flagship pipelines:

1. **Two-sided rectify + sum-pool** (RandomPatchCifar serving path,
   reference SymmetricRectifier.scala:7-32 then Pooler.scala:21-69).
   The XLA lowering materializes the channel-doubled rectified tensor
   (N·H·W·2K floats) in HBM before `reduce_window` shrinks it ~100×.
   The Pallas kernel reads the conv output once per batch block and
   writes only the pooled grid — one HBM pass instead of three.

2. **RBF kernel block** K(X, Yb) = exp(-γ‖x−y‖²) (reference
   KernelGenerator.scala:18-206), the inner op of kernel ridge
   regression. The Pallas kernel tiles the Gram GEMM onto the MXU with
   an f32 VMEM accumulator and applies the distance/exp epilogue before
   the (m, b) block ever leaves VMEM, instead of round-tripping the
   GEMM output through HBM for a separate elementwise kernel.

3. **SIFT's descriptor normalization** (L2, clamp, L2, contrast
   zeroing, quantization; reference VLFeat.cxx via SIFTExtractor.scala).
   XLA's path concatenates the scales' descriptors and takes each row
   sum as a product with ones that returns it in all 128 places: passes
   through HBM as large as the descriptors. The Pallas kernel reads each
   scale's raw rows once and writes the quantized rows once, at their
   place in the concatenation.

4. **The Fisher encoding's posteriors and moments** (reference
   FisherVector.scala:33-53). XLA's path writes each image's (nd, k)
   Mahalanobis form to HBM and reads it back for the softmax's sum, the
   moments product and the posteriors' sum. The Pallas kernel takes a
   tile of an image's reduced descriptors through the posteriors and
   both moment products in VMEM; only the (2d, k) moments leave it.

Every op has `*_reference` (pure jnp — the XLA path, also the CPU/test
oracle) and a dispatcher. Kernels are runnable in interpret mode on CPU
for unit tests.

**Measured on a v5e (1 chip, round 4, 2026-07-30, an earlier machine;
fresh-valued chained timing; not comparable with the machine builders
reach now, to be re-measured):**

- rectify+pool: Pallas wins at EVERY measured shape —
  (2048,27,27,256): 23.2 vs 25.4 ms; (512,27,27,512): 8.3 vs 12.8 ms
  (1.54×); (4096,13,13,128): 6.3 vs 7.9 ms; (1024,54,54,64): 11.2 vs
  12.4 ms. → **default-ON on TPU** (`KEYSTONE_DISABLE_PALLAS_RECTIFY=1`
  reverts). Round 2's parity readings repeated the same values and
  were unreliable.
- RBF block: parity across shapes — (8192×2048,d=1024): 5.36 vs
  5.13 ms; (32768×1024,d=256): 4.85 vs 4.75; (4096×4096,d=2048): 10.4
  vs 11.0; (16384×512,d=64): 2.10 vs 2.12. → stays opt-in
  (`KEYSTONE_ENABLE_PALLAS=1`), kept because the VMEM-epilogue
  structure is the right shape for pods/toolchains where XLA's fusion
  regresses, with parity documented here.
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _kernels_enabled() -> bool:
    """The ONE master switch over every Pallas kernel this library
    owns: `ExecutionConfig.pallas_kernels` (env
    ``KEYSTONE_CHAIN_KERNELS``, ledger-header recorded so ``--diff``
    names a kernel flip as the suspect kill switch). The per-kernel env
    knobs below remain as documented overrides UNDER this switch —
    their opt-in/opt-out defaults reflect each kernel's measured
    verdict, the master switch reflects trust in Pallas at all."""
    from ..workflow.env import execution_config

    return execution_config().pallas_kernels


def use_pallas() -> bool:
    """Trace-time gate for the RBF kernel: opt-in (measured XLA parity,
    module docstring) and TPU-only."""
    if not _kernels_enabled():
        return False
    if os.environ.get("KEYSTONE_ENABLE_PALLAS") != "1":
        return False
    return jax.default_backend() == "tpu"


def use_rectify_pallas() -> bool:
    """Trace-time gate for the standalone rectify+pool kernel:
    default-ON on TPU (measured 1.1-1.54× over XLA's fusion at every
    shape point, module docstring); KEYSTONE_DISABLE_PALLAS_RECTIFY=1
    reverts to the XLA path."""
    if not _kernels_enabled():
        return False
    if os.environ.get("KEYSTONE_DISABLE_PALLAS_RECTIFY") == "1":
        return False
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Fused two-sided rectify + sum pool
# ---------------------------------------------------------------------------


def rectify_pool_reference(x, alpha, max_val, pool: int, stride: int):
    """XLA path: SymmetricRectifier >> Pooler(sum) exactly as the
    unfused stages compute it. x: (N, H, W, K) → (N, GY, GX, 2K)."""
    with jax.named_scope("ks.rectify"):
        cat = jnp.concatenate(
            [jnp.maximum(max_val, x - alpha),
             jnp.maximum(max_val, -x - alpha)],
            axis=-1,
        )
    with jax.named_scope("ks.pool"):
        return lax.reduce_window(
            cat, 0.0, lax.add,
            window_dimensions=(1, pool, pool, 1),
            window_strides=(1, stride, stride, 1),
            padding="VALID",
        )


def _rectify_pool_kernel(x_ref, o_ref, *, alpha, max_val, pool, stride, gy, gx, k):
    # windows overlap by at most pool−stride columns; recomputing the
    # rectification per window keeps VMEM at one input block + one
    # window slice instead of 3× the input block
    for iy in range(gy):
        for ix in range(gx):
            xw = x_ref[:, iy * stride : iy * stride + pool,
                       ix * stride : ix * stride + pool, :]
            pos = jnp.maximum(max_val, xw - alpha).sum(axis=(1, 2))
            neg = jnp.maximum(max_val, -xw - alpha).sum(axis=(1, 2))
            o_ref[:, iy, ix, 0:k] = pos
            o_ref[:, iy, ix, k : 2 * k] = neg


def _rectify_pool_block(h: int, w: int, k: int) -> int:
    """Images per block of the standalone rectify+pool kernel. VMEM
    budget: the pipelined input block is double-buffered, and tiling
    pads the sublane dim (W) to 8 and the lane dim (K) to 128 — keep the
    nominal input block under ~3 MB of the 16 MB VMEM. The working set
    is input-only (the pooled output is negligible), so the
    2x-double-buffer chain formula would over-reserve; the chain path's
    chooser covers the fused RectifyPool>>Vectorizer form instead. A
    fixed block of 8 is refused by the v5e's compiler at the CIFAR
    conv-output geometry (27x27x256: 16.19M of scoped VMEM against a
    16.00M limit), so there is no fixed default."""
    per_img = h * _round_up(w, 8) * _round_up(k, 128) * 4
    return max(1, min(8, (3 << 20) // max(per_img, 1)))  # keystone: ignore[KJ017]


@jax.named_scope("ks.rectify_pool_pallas")
def rectify_pool_pallas(
    x, alpha: float, max_val: float, pool: int, stride: int,
    *, block_n: "int | None" = None, interpret: bool = False,
):
    n, h, w, k = x.shape
    gy = (h - pool) // stride + 1
    gx = (w - pool) // stride + 1
    bn = min(block_n or _rectify_pool_block(h, w, k), n)
    n_pad = _round_up(n, bn)
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0), (0, 0), (0, 0)))
    out = pl.pallas_call(
        partial(
            _rectify_pool_kernel,
            alpha=float(alpha), max_val=float(max_val),
            pool=pool, stride=stride, gy=gy, gx=gx, k=k,
        ),
        grid=(n_pad // bn,),
        in_specs=[
            pl.BlockSpec((bn, h, w, k), lambda i: (i, 0, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bn, gy, gx, 2 * k), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, gy, gx, 2 * k), x.dtype),
        interpret=interpret,
        name="ks_rectify_pool",
    )(x)
    return out[:n]


def rectify_pool(x, alpha: float, max_val: float, pool: int, stride: int):
    """Dispatcher: Pallas on TPU (default-on), XLA elsewhere."""
    if use_rectify_pallas():
        return rectify_pool_pallas(x, alpha, max_val, pool, stride)
    return rectify_pool_reference(x, alpha, max_val, pool, stride)


# ---------------------------------------------------------------------------
# RBF kernel block: exp(-γ‖x−y‖²) with fused GEMM epilogue
# ---------------------------------------------------------------------------


@jax.named_scope("ks.rbf_block")
def rbf_block_reference(X, Yb, gamma):
    """XLA path — the dot-product trick at full f32 precision."""
    with jax.default_matmul_precision("highest"):
        d2 = (
            jnp.sum(X * X, axis=1, keepdims=True)
            - 2.0 * X @ Yb.T
            + jnp.sum(Yb * Yb, axis=1)
        )
        return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def _rbf_kernel(x_ref, y_ref, x2_ref, y2_ref, o_ref, acc_ref, *, gamma, k_steps):
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += lax.dot_general(
        x_ref[:], y_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _():
        d2 = x2_ref[:] + y2_ref[:] - 2.0 * acc_ref[:]
        o_ref[:] = jnp.exp(-gamma * jnp.maximum(d2, 0.0)).astype(o_ref.dtype)


@jax.named_scope("ks.rbf_block_pallas")
def rbf_block_pallas(
    X, Yb, gamma, *, bm: int = 512, bn: int = 512, bk: int = 512,
    interpret: bool = False,
):
    m, d = X.shape
    n = Yb.shape[0]
    bm, bn = min(bm, _round_up(m, 8)), min(bn, _round_up(n, 128))
    bk = min(bk, _round_up(d, 128))
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(d, bk)
    # f32 squared norms computed on the un-padded inputs (padding rows
    # are zero; their outputs are sliced off)
    with jax.default_matmul_precision("highest"):
        x2 = jnp.sum(X.astype(jnp.float32) ** 2, axis=1)
        y2 = jnp.sum(Yb.astype(jnp.float32) ** 2, axis=1)
    Xp = jnp.pad(X, ((0, mp - m), (0, kp - d)))
    Yp = jnp.pad(Yb, ((0, np_ - n), (0, kp - d)))
    x2p = jnp.pad(x2, (0, mp - m)).reshape(mp, 1)
    y2p = jnp.pad(y2, (0, np_ - n)).reshape(1, np_)
    k_steps = kp // bk
    out = pl.pallas_call(
        partial(_rbf_kernel, gamma=float(gamma), k_steps=k_steps),
        grid=(mp // bm, np_ // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk), memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((mp, np_), X.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="ks_rbf_block",
    )(Xp, Yp, x2p, y2p)
    return out[:m, :n]


def rbf_block(X, Yb, gamma):
    """Dispatcher: Pallas on TPU, XLA elsewhere."""
    if use_pallas():
        return rbf_block_pallas(X, Yb, gamma)
    return rbf_block_reference(X, Yb, gamma)


# ---------------------------------------------------------------------------
# Fused conv + mean-correction + two-sided rectify + sum pool
# ---------------------------------------------------------------------------
#
# The featurizer's true bottleneck is not the conv FLOPs but the HBM
# round trips between conv, rectify, and pool: XLA's path writes both
# halves of the rectified conv output and the pool reads them back, at
# the documented 10,000 filters 2 x f32[32,27,27,10000] a microbatch of
# 32 and 958 GB a fit, at 85% of the HBM bandwidth (PERF.md section 5,
# PR 25). This kernel keeps everything after the im2col in VMEM: one
# GEMM against the folded filter bank, the rank-1 patch-mean correction,
# the two-sided rectification and the sum pool — only the (n, gy, gx,
# 2K) pooled grid is written back. A bank too wide for VMEM runs as
# filter blocks (grid axis 1): the patches stay resident while the
# filter tiles stream past.
#
# How it pools (PR 34). The patch rows of an image are laid out CLASS by
# class, a class being the positions that feed the same set of pooled
# cells (`_pool_layout`: a rectangle of the position grid, so slices and
# one concatenation put them so). The kernel rectifies a class's 8-row
# tiles of the conv output and adds them to each other on the vector
# unit, one add a vreg and nothing stored; what is left of a class, 8
# rows of partial sums a sign, goes through a 0/1 matrix at HIGHEST
# (`_pool_matrix`) to the output tile. At the CIFAR geometry that dot
# contracts over 144 rows a group of two images. Before PR 34 the rows
# were row-major, the rectified (1,472 x 1,024) f32 tile was stored
# whole (6 MB of the 10 MB budget) and the dot contracted over all of
# it: Mosaic split it into bf16 parts and pushed them through the
# matrix unit six times against 8 rows, which was two thirds of a loop
# iteration's vector operations and half its stores. Where ordering by
# class would not halve the dot (a stride so small that nearly every
# position is a class of its own) the layout is the identity and every
# row goes to the dot, as then.
#
# Patches are fed to the MXU in bfloat16: at DEFAULT matmul precision
# the MXU truncates f32 operands to bf16 anyway, so this halves patch
# traffic with bit-for-bit-equivalent results vs the XLA conv path
# (max rel. disagreement 1.8e-4 at 10,000 filters on the chip — the
# same class as two DEFAULT-precision XLA convs of the same values).
# Everything behind the GEMM is float32.
#
# Measured on one TPU v5 lite (2026-10-02, PR 34; PERF.md section 6 has
# the runs): the kernel alone, patch extraction included, in a loop
# over microbatches of 32 images at 10,000 filters: 1.472 ms a
# microbatch before PR 34 (PR 27: XLA's path 5.53), 0.702 at the same
# tile of 512, 0.636 at the tile of 1,024 the freed VMEM lets in; the
# outputs equal to 2.2e-7 of their largest value. The compiler's static
# schedule says why: a grid step of 2 images x 512 filters was 6,240
# bundles with 15,523 vector-ALU operations and 4,394 stores in them,
# and is 2,707 with 8,333 and 1,646. Unlike the standalone rectify_pool
# kernel above, this one is ON by default on TPU; the XLA path runs on
# every other backend and where the chooser raises
# `FusedConvIneligibleError`.


def use_fused_conv() -> bool:
    if not _kernels_enabled():
        return False
    return jax.default_backend() == "tpu"


class FusedConvIneligibleError(ValueError):
    """The fused conv kernel's block geometry cannot fit VMEM."""


@jax.named_scope("ks.conv")
def folded_conv_reference(images, kernel_hwio, colsum, bias, normalize: bool):
    """The folded conv: filter bank with ZCA pre-applied, patch-mean
    subtraction as a rank-1 correction via a uniform conv, plus bias.
    Single source of truth — nodes/images/core.py's Convolver and the
    fused stage's fallback both call this.

    Mixed-precision contract: `lax.conv_general_dilated` requires both
    operands to share a dtype, so when the precision planner stores the
    activation boundary in bf16 the filter bank follows the activation
    dtype (bf16 inputs, f32 accumulation via `preferred_element_type` —
    the MXU discipline); the conv output is always f32."""
    if jnp.issubdtype(images.dtype, jnp.floating) \
            and kernel_hwio.dtype != images.dtype:
        kernel_hwio = kernel_hwio.astype(images.dtype)
    dn = lax.conv_dimension_numbers(
        images.shape, kernel_hwio.shape, ("NHWC", "HWIO", "NHWC")
    )
    out = lax.conv_general_dilated(
        images, kernel_hwio, (1, 1), "VALID", dimension_numbers=dn,
        preferred_element_type=jnp.float32,
    )
    if normalize:
        p, c = kernel_hwio.shape[0], kernel_hwio.shape[2]
        ones = jnp.ones((p, p, c, 1), images.dtype) / (p * p * c)
        means = lax.conv_general_dilated(
            images, ones, (1, 1), "VALID",
            dimension_numbers=lax.conv_dimension_numbers(
                images.shape, ones.shape, ("NHWC", "HWIO", "NHWC")
            ),
            preferred_element_type=jnp.float32,
        )
        out = out - means * colsum
    return out + bias


def conv_rectify_pool_reference(
    images, kernel_hwio, colsum, bias, alpha, max_val,
    pool: int, stride: int, normalize: bool,
):
    """XLA path: exactly the unfused Convolver >> SymmetricRectifier >>
    Pooler(sum) computation (see nodes/images/core.py)."""
    out = folded_conv_reference(images, kernel_hwio, colsum, bias, normalize)
    return rectify_pool_reference(out, alpha, max_val, pool, stride)


def hwio_to_cmajor(kernel_hwio):
    """(P,P,C,K) → the channel-major (C·P·P, K) feature layout the Pallas
    kernel consumes (conv_general_dilated_patches order)."""
    return kernel_hwio.transpose(2, 0, 1, 3).reshape(-1, kernel_hwio.shape[3])


def run_outside_trace(fn, *args):
    """Compile ``fn`` for the numpy ``args`` (arrays or pytrees of them)
    and run it once, whatever trace the caller is inside; returns the
    result as numpy. The dispatchers consult their canary at trace time,
    inside the enclosing program's trace, where an eager call is only
    staged into that program: its result is a tracer, and reading it
    raises. Lowering ahead of time from shapes starts a trace of its own
    and leaves the caller's alone, and the compiled executable takes
    numpy arrays as they are."""
    import numpy as np

    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), args)
    compiled = jax.jit(fn).lower(*avals).compile()
    return np.asarray(compiled(*args))


def canary_verdict(verdicts: dict, key, thunk, ineligible, what: str) -> bool:
    """THE canary rule, shared by the fused conv and the chain kernels:
    ``thunk`` compiles and runs a kernel once for geometry ``key``
    (through `run_outside_trace`) and the verdict stays in ``verdicts``
    for anyone to read. True when it ran and gave finite values; False
    for the one designed demotion, ``ineligible`` (a block geometry
    that cannot fit VMEM, deterministic in the geometry). Anything else
    the compile or the run raises (a scoped-vmem OOM, a Mosaic lowering
    reject, a backend that is not there) propagates and leaves no
    verdict: it would hit the enclosing program anyway, and a kernel
    that silently became the XLA path is a measurement of something
    else."""
    import numpy as np

    if key in verdicts:
        return verdicts[key]
    try:
        got = thunk()
    except ineligible:
        ok = False
    else:
        if not np.isfinite(got).all():
            raise FloatingPointError(
                f"{what} canary at geometry {key} returned non-finite "
                "values")
        ok = True
    if jax.process_count() > 1:
        # Every process must compile the SAME program for the collective
        # launch (fused on one, XLA on the rest → a wedged collective).
        # Adopt process 0's verdict everywhere: the canary runs at the
        # same SPMD program point on every process (same geometry key,
        # same call site), so this broadcast lines up like
        # parallel.multihost.barrier() does.
        from jax.experimental import multihost_utils

        ok = bool(multihost_utils.broadcast_one_to_all(np.asarray(ok)))
    verdicts[key] = ok
    return ok


_fused_conv_canary: dict = {}


def _fused_conv_canary_ok(h: int, w: int, c: int, k: int, pool: int,
                          stride: int, normalize: bool, patch: int) -> bool:
    """The fused kernel's canary (`canary_verdict`): one n=1 call, which
    pads to one full image block, on all-zero inputs."""
    import numpy as np

    return canary_verdict(
        _fused_conv_canary,
        (h, w, c, k, pool, stride, bool(normalize), patch),
        lambda: run_outside_trace(
            lambda images, g, colsum, bias: conv_rectify_pool_pallas(
                images, g, colsum, bias,
                0.1, 0.0, pool, stride, normalize, patch),
            np.zeros((1, h, w, c), np.float32),
            np.zeros((c * patch * patch, k), np.float32),
            np.zeros((k,), np.float32),
            np.zeros((k,), np.float32),
        ),
        FusedConvIneligibleError, "fused conv")


def conv_rectify_pool(
    images, kernel_hwio, colsum, bias, alpha, max_val,
    pool: int, stride: int, normalize: bool,
):
    """Dispatcher: fused Pallas kernel on TPU (default on), XLA
    elsewhere or when the block geometry cannot fit VMEM (the canary's
    one designed demotion). The single entry point for
    Convolver>>Rectifier>>Pooler semantics — the fused stage and
    the driver graft entry both route through it."""
    # precision-planner boundaries may hand bf16 activations to an f32
    # filter bank: the kernel follows the activation dtype here so BOTH
    # paths (Pallas GEMM, XLA conv) see matching operand dtypes; the
    # accumulator stays f32 in each.
    if jnp.issubdtype(images.dtype, jnp.floating) \
            and kernel_hwio.dtype != images.dtype:
        kernel_hwio = kernel_hwio.astype(images.dtype)
    if use_fused_conv():
        # once per program traced: how often the mechanism engages
        # (`pallas.fused_conv.traced`) against how often the canary's
        # one designed demotion sends a program to XLA (`.demoted`)
        from ..telemetry import counter

        if _fused_conv_canary_ok(
            images.shape[1], images.shape[2], images.shape[3],
            kernel_hwio.shape[3], pool, stride, normalize,
            kernel_hwio.shape[0],
        ):
            try:
                out = conv_rectify_pool_pallas(
                    images, hwio_to_cmajor(kernel_hwio), colsum, bias,
                    alpha, max_val, pool, stride, normalize,
                    kernel_hwio.shape[0],
                )
                counter("pallas.fused_conv.traced").inc()
                # what one loop iteration of that program does with the
                # rectified rows: added up on the vector unit, and left
                # for the pool dot to contract over
                layout, (_, g_img, _, _) = _fused_conv_plan(
                    images.shape[1], images.shape[2], images.shape[3],
                    kernel_hwio.shape[3], pool, stride,
                    kernel_hwio.shape[0])
                counter("pallas.fused_conv.pool_rows_presummed").inc(
                    g_img * layout.presummed_rows)
                counter("pallas.fused_conv.pool_dot_rows").inc(
                    g_img * layout.dot_rows)
                return out
            except FusedConvIneligibleError:
                pass
        counter("pallas.fused_conv.demoted").inc()
    return conv_rectify_pool_reference(
        images, kernel_hwio, colsum, bias, alpha, max_val, pool, stride,
        normalize,
    )


def _pool_axis_runs(pos: int, pool: int, stride: int) -> list:
    """The runs of one axis of the conv-position grid: maximal intervals
    [lo, hi) whose positions lie in the same, non-empty set of pooling
    windows, as (lo, hi, windows). Positions no window covers are in no
    run. Window i covers [i*stride, i*stride + pool), so a set recurs
    only in adjacent positions and the runs are what partitions the
    covered part of the axis."""
    n = (pos - pool) // stride + 1
    runs = []
    for x in range(pos):
        wins = tuple(i for i in range(n)
                     if i * stride <= x < i * stride + pool)
        if not wins:
            continue
        if runs and runs[-1][2] == wins:
            runs[-1][1] = x + 1
        else:
            runs.append([x, x + 1, wins])
    return [tuple(r) for r in runs]


class _PoolLayout(NamedTuple):
    """How one image's conv positions are laid out as patch rows, and
    what the kernel does with each stretch of them.

    posp: patch rows an image, a multiple of 16 (the bf16 tile: the
        kernel slices the patches per group at dynamic offsets).
    rects: the stretches as rectangles (y0, y1, x0, x1) of the position
        grid, row-major inside each, each padded with zero rows to whole
        8-row tiles (the last to `posp`); the identity layout is the
        whole grid as one.
    pieces: the same stretches for the kernel, (offset, tiles, valid,
        summed): `tiles` 8-row tiles from row `offset`, the first
        `valid` rows real positions. A summed piece is added up over its
        tiles on the vector unit and hands 8 rows of partial sums to the
        pool dot; any other goes to the dot as it is.
    weights: (cells, reduced rows) 0/1, the pool matrix of one image
        over the rows its pieces hand to the dot.
    """
    posp: int
    rects: tuple
    pieces: tuple
    weights: "np.ndarray"

    @property
    def dot_rows(self) -> int:
        """Rows an image hands to the pool dot (its contraction)."""
        return self.weights.shape[1]

    @property
    def presummed_rows(self) -> int:
        """Rows an image adds up on the vector unit before the dot."""
        return sum(8 * t for _, t, _, summed in self.pieces if summed)


def _pool_layout(pos_h: int, pos_w: int, pool: int,
                 stride: int) -> _PoolLayout:
    """The layout of the patch rows for a sum pool of `pool` at `stride`
    over a (pos_h, pos_w) grid of conv positions; a function of these
    four alone.

    Two positions are of one CLASS when they feed the same set of pooled
    cells (the same column of the 0/1 pool matrix). A class is a
    rectangle, a run of rows times a run of columns (`_pool_axis_runs`),
    so the rows can be put class by class with slices and one
    concatenation, and the sum over a class needs no matrix: its tiles
    are added to each other, 8 rows of partial sums a class are left,
    and the pool dot contracts over those. At the CIFAR geometry (27 x
    27, pool 14 stride 13) there are 9 classes: four blocks of 13 x 13
    (176 rows padded), four edges of 13 (16) and the centre (8): 784
    rows an image where row-major order has 736, 72 rows to the dot
    where it had 736. Positions under no window are in no class and
    leave the GEMM.

    Where that does not at least halve the dot's contraction (a stride
    so small that nearly every position is a class of its own) the
    layout is the identity: one piece, every row to the dot."""
    import numpy as np

    gy = (pos_h - pool) // stride + 1
    gx = (pos_w - pool) // stride + 1
    npos = pos_h * pos_w
    identity_posp = _round_up(npos, 16)
    classes = [
        ((y0, y1, x0, x1), [iy * gx + ix for iy in wy for ix in wx])
        for y0, y1, wy in _pool_axis_runs(pos_h, pool, stride)
        for x0, x1, wx in _pool_axis_runs(pos_w, pool, stride)
    ] if gy > 0 and gx > 0 else []
    if not classes or 2 * 8 * len(classes) > identity_posp:
        weights = np.zeros((max(gy, 0) * max(gx, 0), identity_posp),
                           np.float32)
        for iy in range(gy):
            for ix in range(gx):
                for i in range(iy * stride, iy * stride + pool):
                    weights[iy * gx + ix,
                            i * pos_w + ix * stride:
                            i * pos_w + ix * stride + pool] = 1.0
        return _PoolLayout(identity_posp, ((0, pos_h, 0, pos_w),),
                           ((0, identity_posp // 8, npos, False),), weights)
    pieces, columns, offset = [], [], 0
    for (y0, y1, x0, x1), cells in classes:
        valid = (y1 - y0) * (x1 - x0)
        tiles = -(-valid // 8)
        pieces.append((offset, tiles, valid, tiles > 1))
        offset += 8 * tiles
        # partial-sum row r holds rows r, r + 8, ... of the class; in a
        # class of one tile it is row r itself, real only below `valid`
        column = np.zeros((gy * gx, 8), np.float32)
        column[cells, :min(valid, 8)] = 1.0
        columns.append(column)
    return _PoolLayout(_round_up(offset, 16),
                       tuple(rect for rect, _ in classes), tuple(pieces),
                       np.concatenate(columns, axis=1))


def _pool_matrix(layout: _PoolLayout, g: int) -> "np.ndarray":
    """(R, g * layout.dot_rows) 0/1 sum-pool weights for ONE kernel loop
    iteration (g images, R = round_up(g * cells, 8)): block-diagonal over
    the g images, each block the layout's (cells, dot_rows) weights over
    the rows that image's pieces hand to the dot: 8 rows of partial sums
    a summed class, the rows themselves elsewhere (`_pool_layout`). Per
    group and not per image block, because the block-diagonal form's
    FLOPs grow with the square of the images it spans; per 8 output rows,
    because a 4-row dot and store measured slower than that (history in
    git). Since PR 34 the contraction at the CIFAR geometry is 144 rows
    where it was 1,472, so the `highest` dot splits and multiplies a
    tenth of what it did."""
    import numpy as np

    cells, rows = layout.weights.shape
    M = np.zeros((_round_up(g * cells, 8), g * rows), np.float32)
    for im in range(g):
        M[im * cells:(im + 1) * cells,
          im * rows:(im + 1) * rows] = layout.weights
    return M


def _class_ordered_patches(pat, layout: _PoolLayout):
    """(n, pos_h, pos_w, d) patches -> (n, layout.posp, d): each
    rectangle's rows together, padded with zero rows to whole tiles.
    Slices and one concatenation, no gather: the compiler makes a copy a
    rectangle of it (it re-tiles the extraction's output either way)
    and one concatenate."""
    n, _, _, d = pat.shape
    ends = [offset for offset, *_ in layout.pieces[1:]] + [layout.posp]
    parts = []
    for (y0, y1, x0, x1), (offset, _, valid, _), end in zip(
            layout.rects, layout.pieces, ends):
        parts.append(lax.reshape(
            lax.slice(pat, (0, y0, x0, 0), (n, y1, x1, d)), (n, valid, d)))
        if end > offset + valid:
            parts.append(lax.full((n, end - offset - valid, d), 0, pat.dtype))
    return lax.concatenate(parts, 1)


def _conv_rect_pool_kernel(
    pat_ref, g_ref, pmat_ref, colsum_ref, bias_ref, o_ref,
    *, alpha, max_val, d_real, normalize, b, posp, grp, rows, pieces,
):
    g = g_ref[:]                                       # (dp, tk) bf16
    pm = pmat_ref[:]                                   # (rows, grp·dot_rows)
    cs = colsum_ref[:]
    bs = bias_ref[:]

    def body(i, carry):
        # one iteration = one group of `grp` images (one 8-row output
        # tile when cells divides 8 — see _fused_conv_geometry)
        pat = pat_ref[pl.ds(i * grp * posp, grp * posp), :]  # bf16
        # precision pinned DEFAULT: bf16 operands under an ambient
        # default_matmul_precision("highest") context would ask Mosaic
        # for an fp32-contract bf16 matmul, which it rejects ("Bad lhs
        # type")
        z = jnp.dot(pat, g, preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT)
        if normalize:
            means = jnp.sum(pat.astype(jnp.float32), axis=1,
                            keepdims=True) * (1.0 / d_real)
            z = z - means * cs
        out = z + bs
        # (-alpha) - out is -out - alpha to the bit, in one vector
        # subtraction a vreg where the negation made it two. Written for
        # the whole group at once, but never whole anywhere: each vreg
        # of `act` is made where a sum below consumes it
        act = jnp.concatenate(
            [jnp.maximum(max_val, out - alpha),
             jnp.maximum(max_val, (-alpha) - out)],
            axis=1,
        )
        # lax and not jnp below: a jnp call is a jit of its own to trace,
        # and some 150 of them a trace of this body were 2 s of every
        # process's set-up (PERF.md section 6, PR 34)
        zero_tile = jnp.zeros((8, act.shape[1]), act.dtype)
        real_rows = {}  # an 8-row tile's rows below n, by n
        parts = []
        for im in range(grp):
            for offset, tiles, valid, summed in pieces:
                lo = im * posp + offset
                if not summed:
                    # every row to the dot: the padded ones meet zero
                    # weights there
                    parts.append(lax.slice_in_dim(act, lo, lo + 8 * tiles))
                    continue
                # the class's tiles added to each other. A padded row is
                # not a zero (a zero patch rectifies to max(max_val,
                # ±bias − alpha)), so the last tile goes through a mask
                end = lo + 8 * (tiles - 1)
                last = lax.slice_in_dim(act, end, end + 8)
                n_real = valid - 8 * (tiles - 1)
                if n_real < 8:
                    if n_real not in real_rows:
                        real_rows[n_real] = lax.broadcasted_iota(
                            jnp.int32, zero_tile.shape, 0) < n_real
                    last = lax.select(real_rows[n_real], last, zero_tile)
                head = lax.reshape(lax.slice_in_dim(act, lo, end),
                                   (tiles - 1, 8, act.shape[1]))
                parts.append(lax.add(lax.reduce_sum(head, (0,)), last))
        # HIGHEST: the partial sums would otherwise be truncated to bf16
        # by the pool GEMM, a second rounding on top of the documented
        # bf16 patch feed; the 0/1 pm operand is exact either way. The
        # loads, the pieces and the store are tile-aligned: posp % 16 ==
        # 0, every piece whole 8-row tiles, rows % 8 == 0.
        o_ref[pl.ds(i * rows, rows), :] = jnp.dot(
            pm, jnp.concatenate(parts, axis=0),
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST)
        return carry

    # a SEQUENTIAL loop on purpose: per-group z transients are the VMEM
    # hog, and fori_loop guarantees only one iteration's worth is live —
    # the block chooser's budget is structural, not a scheduling guess
    # (a Python-unrolled loop would let Mosaic keep several groups'
    # transients in flight)
    lax.fori_loop(0, b // grp, body, 0)


# the 10 MB cap of the 16 MB VMEM absorbs scheduling slop
_FUSED_CONV_VMEM_BUDGET = 10 * (1 << 20)  # keystone: ignore[KJ017]
# four 128 x 128 matrix units a core (v4, v5e, v5p; two of 256 on v6e)
_MXU_ROUND_LANES = 512


def _fused_conv_vmem_bytes(posp: int, dot_rows: int, dp: int, b: int, g: int,
                           R: int, kp: int, k2p: int,
                           filter_bufs: int) -> int:
    """VMEM the kernel is accounted for at an image block of b, groups
    of g images (R output rows each, `posp` patch rows and `dot_rows`
    rows to the pool dot an image: `_pool_layout`) and one filter block
    of kp lanes (k2p for both signs) held in `filter_bufs` buffers: one
    when it is the whole bank, two when it changes with the grid step.

    Mosaic pads the lane (minor) dimension to 128: every (rows, k) f32
    buffer really occupies (rows, round_up(k, 128)) of VMEM — ignoring
    it produced a real scoped-vmem OOM at k=16 (21.5 MB actual vs 8.9 MB
    estimated). The conv output z and what is rectified of it are ONE
    group's worth by construction (sequential fori_loop in the kernel),
    so they don't scale with the block. Of the rectified halves only
    what goes to the pool dot is ever whole: 8 rows of partial sums a
    summed class (72 rows an image at the CIFAR geometry where the
    halves had 736), every row in the identity layout."""
    return (
        2 * b * posp * dp * 2            # patches, dbl-buf bf16
        + g * posp * kp * 4              # z (one group, f32)
        + g * dot_rows * k2p * 4         # the pool dot's operand, both signs
        + 2 * (b // g) * R * k2p * 4     # pooled out, dbl-buf
        + R * g * dot_rows * 4           # group pool matrix
        + filter_bufs * dp * kp * 2      # filter block, bf16
    )


def _fused_conv_largest_block(posp: int, dot_rows: int, dp: int, g: int,
                              R: int, kp: int, k2p: int,
                              filter_bufs: int) -> int:
    """The largest image block, a multiple of g up to 32, whose working
    set fits the budget; 0 when not even one group does."""
    # grouped conv working set (patches + per-group z and partial sums +
    # pooled out + pool matrix + filters) has no chain-formula
    # equivalent; its own live-chip canary gates it
    b = 0
    while b + g <= 32 and _fused_conv_vmem_bytes(
            posp, dot_rows, dp, b + g, g, R, kp, k2p, filter_bufs
    ) <= _FUSED_CONV_VMEM_BUDGET:
        b += g
    return b


def _fused_conv_geometry(posp: int, dot_rows: int, dp: int, k: int,
                         cells: int) -> "tuple[int, int, int, int]":
    """(b, g, R, tk): image block, images per kernel loop iteration,
    output rows per iteration and filter tile, from shapes alone, so
    that the working set fits the VMEM budget.

    Groups are tried largest-first — g images per iteration share one
    pool dot/store whose 8-row tiles are fully used when g·cells is a
    multiple of 8 — and halved when a group's z and partial sums (which
    scale with g) blow the budget, down to one image per iteration. b is
    always a multiple of g so the kernel's loop covers the block
    exactly; R is a multiple of 8 so stores stay tile-aligned.

    A bank that fits whole at some group size is ONE filter block,
    tk = k. Only a bank that does not is tiled over filter blocks (the
    pool is a sum per filter, so they are independent): tk is then a
    multiple of 128, the bank is padded with zero filters to a multiple
    of it, and no (position x filter) tensor wider than tk exists in
    VMEM or in HBM. The widest tile that fits a tight group wins, in
    whole rounds of the four matrix units (512 lanes) once it is that
    wide, then the blocks are evened out (K = 2,500 is three tiles of
    896, not two of 1,024 and a third of 452). Wide, because a grid
    step has a part that grows with the tile and a part of its own, and
    the image block hardly matters; whole rounds, because the conv GEMM
    gives each 128-lane column to one unit: 0.894, 0.702, 0.790, 0.636
    and 0.689 ms a microbatch of 32 at K = 10,000 at tiles of 256, 512,
    768, 1,024 and 1,152, and 0.692 at 512 with the microbatch one image
    block (PERF.md section 6, PR 34). b = 0: ineligible (one image at
    128 filters does not fit, or there are no pooled cells)."""
    if cells <= 0:  # pool window larger than the conv-position grid:
        # no pooled output exists; plainly ineligible, not a crash
        return 0, 1, 8, k
    groups = []
    g = 8 // cells if 8 % cells == 0 else 1
    while g >= 1:
        # only TIGHT multi-image groups (or g=1): a padded group of
        # several images would interleave zero rows between groups,
        # breaking the per-image output reshape below
        if g == 1 or (g * cells) % 8 == 0:
            groups.append((g, _round_up(g * cells, 8)))
        g //= 2
    kp = _round_up(k, 128)
    for g, R in groups:
        b = _fused_conv_largest_block(
            posp, dot_rows, dp, g, R, kp, _round_up(2 * k, 128), 1)
        if b > 0:
            return b, g, R, k
    for g, R in groups:
        for tk in range(kp - 128, 0, -128):
            b = _fused_conv_largest_block(
                posp, dot_rows, dp, g, R, tk, 2 * tk, 2)
            if b > 0:
                # whole rounds of the matrix units, once the tile is
                # that wide: the conv GEMM gives each 128-lane column of
                # the tile to one unit, so 9 columns are three rounds
                # with three units idle in the last
                tk -= tk % _MXU_ROUND_LANES if tk > _MXU_ROUND_LANES else 0
                k_blocks = -(-k // tk)  # as many as the widest tile
                # takes, evenly sized
                return b, g, R, _round_up(-(-k // k_blocks), 128)
    return 0, 1, _round_up(cells, 8), k


def _fused_conv_block_images(posp: int, dot_rows: int, dp: int, k: int,
                             cells: int) -> int:
    """Largest eligible image block (0 = the geometry cannot fit VMEM);
    see `_fused_conv_geometry`."""
    return _fused_conv_geometry(posp, dot_rows, dp, k, cells)[0]


def _fused_conv_plan(h: int, w: int, c: int, k: int, pool: int, stride: int,
                     patch: int) -> "tuple[_PoolLayout, tuple]":
    """The layout of the patch rows and the block geometry (b, g, R, tk)
    the kernel runs (h, w, c) images against k filters of `patch` at;
    from shapes alone."""
    pos_h, pos_w = h - patch + 1, w - patch + 1
    cells = ((pos_h - pool) // stride + 1) * ((pos_w - pool) // stride + 1)
    layout = _pool_layout(pos_h, pos_w, pool, stride)
    return layout, _fused_conv_geometry(
        layout.posp, layout.dot_rows, _round_up(c * patch * patch, 128), k,
        cells)


@jax.named_scope("ks.conv_rectify_pool_pallas")
def conv_rectify_pool_pallas(
    images, G_cmajor, colsum, bias, alpha, max_val,
    pool: int, stride: int, normalize: bool, patch: int,
    *, interpret: bool = False,
):
    """images (N,H,W,C) f32 → pooled (N,gy,gx,2K) f32.

    G_cmajor: (C·P·P, K) folded filter bank in the channel-major feature
    order of `conv_general_dilated_patches`.
    """
    n, h, w, c = images.shape
    d = c * patch * patch
    k = G_cmajor.shape[1]
    dp = _round_up(d, 128)
    gy = (h - patch + 1 - pool) // stride + 1
    gx = (w - patch + 1 - pool) // stride + 1
    cells = gy * gx
    layout, (b, g_img, rows, tk) = _fused_conv_plan(
        h, w, c, k, pool, stride, patch)
    posp = layout.posp
    if b == 0:
        raise FusedConvIneligibleError("fused conv block does not fit VMEM")
    n_pad = _round_up(n, b)
    k_pad = _round_up(k, tk)
    k_blocks = k_pad // tk

    pat = lax.conv_general_dilated_patches(
        jnp.moveaxis(images, -1, 1), (patch, patch), (1, 1), "VALID"
    )  # (N, C·P·P, pos_h, pos_w), channel-major features
    pat = jnp.pad(jnp.moveaxis(pat, 1, -1).astype(jnp.bfloat16),
                  ((0, n_pad - n), (0, 0), (0, 0), (0, dp - d)))
    pat = _class_ordered_patches(pat, layout).reshape(n_pad * posp, dp)

    r_img = rows // g_img  # output rows per image (== cells when tight;
    # padded groups are g=1 only, so this stays exact)
    # zero filters (zero colsum, zero bias) pad the bank to whole tiles;
    # their columns are sliced off below
    Gp = jnp.pad(G_cmajor, ((0, dp - d), (0, k_pad - k))).astype(jnp.bfloat16)
    pmat = jnp.asarray(_pool_matrix(layout, g_img))
    cs = jnp.pad(jnp.asarray(colsum, jnp.float32), (0, k_pad - k))
    bs = jnp.pad(jnp.asarray(bias, jnp.float32), (0, k_pad - k))

    # filter blocks innermost: the patch block's index ignores j, so it
    # is fetched once per image block and stays resident while the
    # (dp, tk) filter tiles stream past
    out = pl.pallas_call(
        partial(
            _conv_rect_pool_kernel,
            alpha=float(alpha), max_val=float(max_val),
            d_real=d, normalize=normalize, b=b, posp=posp,
            grp=g_img, rows=rows, pieces=layout.pieces,
        ),
        grid=(n_pad // b, k_blocks),
        in_specs=[
            pl.BlockSpec((b * posp, dp), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((dp, tk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(pmat.shape, lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tk), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b * r_img, 2 * tk), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad * r_img, k_blocks * 2 * tk),
                                       jnp.float32),
        interpret=interpret,
        name="ks_conv_rectify_pool",
    )(pat, Gp, pmat, cs.reshape(1, k_pad), bs.reshape(1, k_pad))
    # a filter block's columns are its positive half, then its negative
    # half: gather the halves over the blocks. With one block (tk == k)
    # and tight grouping (r_img == cells) all of this is a reshape.
    out = out.reshape(n_pad, r_img, k_blocks, 2, tk)[:n, :cells]
    out = out.transpose(0, 1, 3, 2, 4).reshape(n, cells, 2, k_pad)[..., :k]
    return out.reshape(n, gy, gx, 2 * k)


# ---------------------------------------------------------------------------
# SIFT's descriptor normalization and quantization in one pass
# ---------------------------------------------------------------------------

# Measured on one TPU v5 lite (voc_fit's traced runs, 375 x 500 images,
# 73,866 descriptors an image, microbatches of 8): the kernel 0.134 ms
# an image, where XLA's passes and the scales' concatenate took 0.549;
# 0.092 ms is the raw rows' read and the quantized rows' write at
# 819 GB/s. The compiler's schedule of a grid step of 2,048 rows in
# slabs of 256 is about 4,200 bundles, so the kernel is about as bound
# by the vector unit as by HBM. Quantized values agree with the
# reference's on all but 1.8e-6 of the entries, by one unit.
SIFT_NORMALIZE_TILE = 2048  # descriptors a grid step
_SIFT_NORMALIZE_SLAB = 256  # descriptors a step of the loop inside one


def use_sift_normalize(rows: int) -> bool:
    """Trace-time gate of `sift_normalize_pallas`: on a TPU, for ``rows``
    descriptors an image of at least one tile (SIFT's full pass). A
    sampling pass's few hundred an image, and every other backend, take
    the jnp form (`nodes/images/sift.py`, `_normalize_quantize_reference`)."""
    if not _kernels_enabled():
        return False
    return jax.default_backend() == "tpu" and rows >= SIFT_NORMALIZE_TILE


def _normalize_slabs(x_ref, o_ref, *, eps, clamp, contrast, slab):
    """``x_ref``'s rows normalized and quantized into ``o_ref``, a slab
    of rows at a time, so that the row, its two sums and the clamped row
    stay in vector registers; nothing but the quantized row is stored.
    Unrolled, so that one slab's loads and lane sums overlap the last
    one's arithmetic."""
    def step(i, carry):
        rows = pl.ds(pl.multiple_of(i * slab, slab), slab)
        x = x_ref[rows, :]
        norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)) + eps
        y = jnp.minimum(x / norm, clamp)
        y = y / (jnp.sqrt(jnp.sum(y * y, axis=-1, keepdims=True)) + eps)
        y = jnp.where(jnp.broadcast_to(norm, y.shape) < contrast, 0.0, y)
        o_ref[rows, :] = jnp.minimum(jnp.floor(512.0 * y), 255.0)
        return carry

    lax.fori_loop(0, x_ref.shape[0] // slab, step, 0, unroll=True)


class _PartTiles(NamedTuple):
    """Where each part's tiles lie among an image's grid steps and in
    the output's rows: part p is steps ``first[p]`` to ``first[p] +
    count[p] - 1``, its tile t the output's rows ``offset[p] + t *
    tile`` on, ``tile`` of them but ``last[p]`` in its last tile."""
    tile: int
    first: tuple
    count: tuple
    offset: tuple
    last: tuple

    @property
    def steps(self) -> int:
        return self.first[-1] + self.count[-1]


def _part_tiles(sizes, tile: int) -> _PartTiles:
    count = [-(-n // tile) for n in sizes]
    first = [0]
    offset = [0]
    for n, c in zip(sizes[:-1], count[:-1]):
        first.append(first[-1] + c)
        offset.append(offset[-1] + n)
    last = [n - (c - 1) * tile for n, c in zip(sizes, count)]
    return _PartTiles(tile, tuple(first), tuple(count), tuple(offset),
                      tuple(last))


def _sift_normalize_kernel(*refs, plan: _PartTiles, **math):
    # refs: one (tile, d) block of each part, the output in HBM, two
    # VMEM tiles, a DMA semaphore for each. Grid step (i, j) normalizes
    # the tile of whichever part step j falls in into one of the two
    # tiles, and copies it to the output rows that the part's place in
    # the concatenation gives them, while the next step computes into
    # the other: no concatenated copy of the raw rows is ever made.
    *x_refs, o_hbm, buf, sem = refs
    i, j = pl.program_id(0), pl.program_id(1)
    steps = pl.num_programs(1)
    g = i * steps + j
    slot = lax.rem(g, 2)
    for p, x_ref in enumerate(x_refs):
        @pl.when((j >= plan.first[p]) & (j < plan.first[p] + plan.count[p]))
        def _(x_ref=x_ref):
            _normalize_slabs(x_ref, buf.at[slot], **math)

    def copies(i_, j_, slot_, act):
        # the copy of step (i_, j_)'s tile: its length is static in each
        # branch, where the part and the tile are known
        for p in range(len(x_refs)):
            lo, hi = plan.first[p], plan.first[p] + plan.count[p] - 1
            branches = [(plan.last[p], j_ == hi)]
            if hi > lo:
                branches.append((plan.tile, (j_ >= lo) & (j_ < hi)))
            for rows, when in branches:
                @pl.when(when)
                def _(p=p, rows=rows):
                    start = plan.offset[p] + (j_ - lo) * plan.tile
                    act(pltpu.make_async_copy(
                        buf.at[slot_, pl.ds(0, rows)],
                        o_hbm.at[i_, pl.ds(start, rows)], sem.at[slot_]))

    @pl.when(g > 0)
    def _():
        first = j == 0
        copies(jnp.where(first, i - 1, i), jnp.where(first, steps - 1, j - 1),
               1 - slot, lambda c: c.wait())

    copies(i, j, slot, lambda c: c.start())

    @pl.when(g == pl.num_programs(0) * steps - 1)
    def _():
        copies(i, j, slot, lambda c: c.wait())


@partial(jax.jit,
         static_argnames=("eps", "clamp", "contrast", "tile", "interpret"))
@jax.named_scope("ks.sift.normalize")
def sift_normalize_pallas(parts, *, eps: float, clamp: float,
                          contrast: float, tile: int = SIFT_NORMALIZE_TILE,
                          interpret: bool = False):
    """Raw descriptors in parts (b, n_p, d) float32 -> their
    concatenation along the rows (b, sum n_p, d), each row L2-normalized
    (+eps), clamped at ``clamp``, normalized again, zeroed where the
    first norm is under ``contrast``, and quantized to
    min(floor(512 v), 255), in float32: vl_dsift's normalization and the
    JNI's short quantization. Each tile of raw rows is read from HBM
    once and its quantized rows written once, at their place in the
    concatenation; both row sums are lane sums in float32 and never
    leave the core. A part's last tile computes on what lies past its
    end, row by row, and copies out its own rows alone. Jitted, so that
    the planner's repeated abstract passes over a chain find the kernel
    traced."""
    parts = [p.astype(jnp.float32) for p in parts]
    b, _, d = parts[0].shape
    sizes = [p.shape[1] for p in parts]
    slab = min(_SIFT_NORMALIZE_SLAB, _round_up(max(sizes), 8))
    tile = min(_round_up(tile, slab), _round_up(max(sizes), slab))
    plan = _part_tiles(sizes, tile)
    # a block may not be longer than its array: a part under one tile
    # is padded to one (what lies past its rows is never copied out)
    parts = [jnp.pad(p, ((0, 0), (0, tile - n), (0, 0))) if n < tile else p
             for p, n in zip(parts, sizes)]

    def block(p):
        lo, hi = plan.first[p], plan.first[p] + plan.count[p] - 1
        # outside its own steps a part's block stays where it was or
        # where it will start, so it is fetched once an image
        return pl.BlockSpec(
            (None, tile, d),
            lambda i, j: (i, jnp.clip(j, lo, hi) - lo, 0),
            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        partial(_sift_normalize_kernel, plan=plan, eps=float(eps),
                clamp=float(clamp), contrast=float(contrast), slab=slab),
        grid=(b, plan.steps),
        in_specs=[block(p) for p in range(len(parts))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((b, sum(sizes), d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2, tile, d), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ks_sift_normalize",
    )(*parts)


# ---------------------------------------------------------------------------
# Fisher vector encoding: the posteriors and both moments in one pass
# ---------------------------------------------------------------------------

# XLA's form (`fisher_vector._fisher_moments_reference`) writes an
# image's (nd, k) log-densities to HBM beside their max and reads them
# back three times, for the softmax's sum, the moments product and S0:
# four passes of 605 MB a microbatch of 8 VOC images (73,866
# descriptors, k = 256). This kernel takes a tile of an image's
# descriptors, laid out (d, tile) with the descriptors on the lanes,
# through the posteriors and both moments in VMEM; only the (2d, k)
# moments and the (k, 128) partial sums of the posteriors leave it.
# The v5e compiler's schedule of a grid step of 2,048 descriptors at
# d = 80 and k = 256 is 18,457 bundles with the MXU slots 97% full: six
# bf16 passes of each product at HIGHEST, the Mahalanobis form's
# contraction over d padded to 128 twice (x and x squared), the moments
# with k = 256 on the stationary side and the 2d = 160 rows of
# [x; x squared] streamed (the other way round streams 256 rows a weight
# tile, and a ones row for S0 costs 2% more than adding q on the vector
# unit). Measured on one TPU v5 lite over microbatches of 8 VOC images
# (73,866 descriptors): 0.52 ms an image at tiles of 1,024, 1,536 and
# 2,048 alike, where XLA's four fusions take 0.89; the Fisher vectors
# 14 times nearer a float64 encoding than XLA's (relative Frobenius
# error 1.2e-4 against 1.7e-3), since S0 and S1 sum the same posteriors.
FISHER_TILE = 2048  # descriptors a grid step, at most
# A grid step's (k, tile) log-densities and posteriors and (2d, tile)
# [x; x^2], with Mosaic's bf16 splits of each, fit the v5e's scoped VMEM
# at a whole tile up to d = 128 and k = 256; a wider mixture takes
# proportionally fewer descriptors a step. Each width of d up to 128
# and k up to 1,024 compiles so for a v5e; at d = 256 and k = 128 a
# tile of 1,536 does not fit, so d stops at 128.
_FISHER_STEP_SIZE = FISHER_TILE * (256 + 2 * 128)  # tile x (k + 2d)
_FISHER_MAX_D = 128
_FISHER_MAX_K = 1024


def fisher_tile(d: int, k: int) -> int:
    """Descriptors a grid step of `fisher_moments_pallas` takes at width
    ``d`` and ``k`` components: the largest multiple of 128 up to
    `FISHER_TILE` whose step fits VMEM; 0 for a width the kernel does
    not take (d over 128, k over 1,024 or not whole lanes)."""
    if d > _FISHER_MAX_D or k > _FISHER_MAX_K or k % 128:
        return 0
    return min(FISHER_TILE, _FISHER_STEP_SIZE // (k + 2 * d) // 128 * 128)


def use_fisher_kernel(nd: int, d: int, k: int) -> bool:
    """Trace-time gate of `fisher_moments_pallas`: on a TPU, for a width
    it takes (`fisher_tile`) and at least one tile of ``nd`` descriptors
    an image. Every other shape, and every other backend, takes the jnp
    form (`fisher_vector._fisher_moments_reference`)."""
    if not _kernels_enabled():
        return False
    tile = fisher_tile(d, k)
    return jax.default_backend() == "tpu" and 0 < tile <= nd


def _fisher_moments_kernel(x_ref, a_ref, b_ref, c_ref, s_ref, s0_ref, *,
                           nd: int, tile: int):
    # Grid step (i, j): tile j of image i's descriptors, (d, tile).
    # Columns past nd (the last tile's) are zeroed in x, and their
    # posteriors too: a zero descriptor has a finite, non-zero posterior,
    # and what lies past the end of the array may not even be finite.
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        s0_ref[...] = jnp.zeros_like(s0_ref)

    valid = lax.broadcasted_iota(jnp.int32, (1, tile), 1) < nd - j * tile
    x = jnp.where(valid, x_ref[...], 0.0)
    xx = x * x

    def dot(a, b, dims):
        return lax.dot_general(a, b, (dims, ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)

    # log w_k + log N(x | mu_k, var_k), (k, tile): x . mu/var and
    # x^2 . -1/(2 var) against the descriptors, plus the constant
    logp = (dot(a_ref[...], x, ((1,), (0,)))
            + dot(b_ref[...], xx, ((1,), (0,))) + c_ref[...])
    e = jnp.exp(logp - jnp.max(logp, axis=0, keepdims=True))
    q = jnp.where(valid, e / jnp.sum(e, axis=0, keepdims=True), 0.0)
    # [S1; S2]^T += [x; x^2] q^T, (2d, k)
    s_ref[...] += dot(jnp.concatenate([x, xx], axis=0), q, ((1,), (1,)))
    s0 = s0_ref[...]
    for lo in range(0, tile, 128):
        s0 = s0 + q[:, lo:lo + 128]
    s0_ref[...] = s0


@partial(jax.jit, static_argnames=("tile", "interpret"))
def fisher_moments_pallas(X, means, variances, weights, *,
                          tile: "int | None" = None, interpret: bool = False):
    """The posterior-weighted moments of descriptor matrices X (b, nd, d)
    under a diagonal GMM (means and variances (k, d), weights (k,)):
    S0 (b, k), and S1 and S2 transposed, (b, d, k), the sums over each
    image's descriptors of q, q x and q x^2. The posteriors q are each
    descriptor's softmax over the k components in float32, the max
    subtracted, and every product is float32 at HIGHEST, as in the jnp
    form; only the order of the sums differs (tile by tile). No (nd, k)
    array reaches HBM. ``tile`` descriptors a grid step, `fisher_tile`'s
    by default. Jitted, so that the planner's repeated abstract passes
    over a chain find the kernel traced."""
    b, nd, d = X.shape
    k = means.shape[0]
    tile = tile or fisher_tile(d, k)
    inv = 1.0 / variances
    a = means * inv
    c = jnp.log(weights) - 0.5 * (
        jnp.sum(means * means * inv, axis=1)
        + jnp.sum(jnp.log(variances), axis=1) + d * jnp.log(2.0 * jnp.pi))
    XT = jnp.swapaxes(X.astype(jnp.float32), 1, 2)  # (b, d, nd)
    if nd < tile:  # a block may not be longer than its array
        XT = jnp.pad(XT, ((0, 0), (0, 0), (0, tile - nd)))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, j: (0, 0),
                            memory_space=pltpu.VMEM)

    def per_image(rows, cols):
        return pl.BlockSpec((None, rows, cols), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.VMEM)

    S, s0 = pl.pallas_call(
        partial(_fisher_moments_kernel, nd=nd, tile=tile),
        grid=(b, pl.cdiv(nd, tile)),
        in_specs=[
            pl.BlockSpec((None, d, tile), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            whole((k, d)), whole((k, d)), whole((k, 1)),
        ],
        out_specs=[per_image(2 * d, k), per_image(k, 128)],
        out_shape=[jax.ShapeDtypeStruct((b, 2 * d, k), jnp.float32),
                   jax.ShapeDtypeStruct((b, k, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ks_fisher",
    )(XT, a, -0.5 * inv, c[:, None])
    return jnp.sum(s0, axis=2), S[:, :d], S[:, d:]
