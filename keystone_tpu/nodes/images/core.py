"""Core image featurization nodes.

TPU-first redesign of the reference's convolution path: the reference
hand-packs im2col patch matrices per image and GEMMs them against the
filter bank with per-partition buffer reuse (nodes/images/
Convolver.scala:20-221). On TPU that entire dance is
`lax.conv_general_dilated` over the NHWC batch — XLA does the im2col
tiling onto the MXU itself. Patch-mean normalization and ZCA whitening
are *folded into the conv algebraically* instead of materializing
normalized patches:

    out[p, k] = (patch_p − mean(patch_p)·1 − zca_mean) · (W_zca f_k)
              = conv(img, G)[p, k] − mean_p · colsum(G_k) − zca_mean·G_k

with G = W_zca @ F, and mean_p itself a uniform conv. One big conv + a
cheap rank-1 correction, fully fused by XLA.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ...data.dataset import Dataset
from ...workflow.pipeline import Transformer


@partial(jax.jit, static_argnames=("normalize",))
def _convolve(images, kernel, colsum, bias, normalize: bool):
    """Folded conv: one module-level jit keyed on shapes, shared by every
    Convolver instance (rebuilding a pipeline must not recompile). The
    math lives in ops.folded_conv_reference — the fused conv+rectify+pool
    stage's fallback path must stay in lockstep with it."""
    from ...ops import folded_conv_reference

    return folded_conv_reference(images, kernel, colsum, bias, normalize)


@partial(jax.jit, static_argnames=("patch", "channels"))
@jax.named_scope("ks.Convolver.fold")
def _fold_filters(filters, whitener, means, patch: int, channels: int):
    """The folded conv kernel (HWIO), its column sums and its bias from
    a filter bank (K, D) or (K, patch, patch, C) and an optional ZCA
    whitener: one program for every Convolver of a shape. HIGHEST
    precision: the fold feeds every downstream conv, and bf16
    default-precision folding would corrupt the whitened kernel."""
    filters = jnp.asarray(filters, jnp.float32)
    K = filters.shape[0]
    F = filters.reshape(K, patch * patch * channels).T  # (D, K)
    if whitener is not None:
        G = jnp.matmul(jnp.asarray(whitener, jnp.float32), F,
                       precision=lax.Precision.HIGHEST)  # (D, K)
        bias = -jnp.matmul(jnp.asarray(means, jnp.float32), G,
                           precision=lax.Precision.HIGHEST)
    else:
        G = F
        bias = jnp.zeros(K, jnp.float32)
    kernel = G.T.reshape(K, patch, patch, channels).transpose(1, 2, 3, 0)
    return kernel, G.sum(axis=0), bias


class Convolver(Transformer):
    """Valid-mode convolution of a filter bank over image batches
    (Convolver.scala:20-221), with optional folded patch-mean
    normalization and ZCA whitening.

    filters: (K, D) with D = patch·patch·C (the reference's packed
    layout, Convolver.scala:99-125) or (K, patch, patch, C).
    """

    fusable = True
    #: featurize conv: the fused kernel's numerics story (PERF.md) —
    #: bf16 boundary storage tolerated; the FOLD below stays HIGHEST
    precision_tolerance = "tolerant"

    def __init__(
        self,
        filters,
        img_height: int,
        img_width: int,
        img_channels: int,
        whitener=None,
        normalize_patches: bool = True,
        patch_size: Optional[int] = None,
    ):
        # The fold is one jitted program (`_fold_filters`): when
        # filters/whitener live on device (the filter-learning program
        # returns device arrays) it is an async dispatch, with no
        # blocking host round trip per Convolver construction.
        shape = np.shape(filters)
        if len(shape) == 2 and patch_size is None:
            patch_size = int(round((shape[1] / img_channels) ** 0.5))
        self.patch = patch_size if len(shape) == 2 else shape[1]
        self.num_filters = shape[0]
        self.img_shape = (img_height, img_width, img_channels)
        self.whitener = whitener
        self.normalize_patches = normalize_patches

        from ...telemetry import dispatch

        with dispatch("Convolver.fold"):
            self.kernel, self.colsum, self.bias = _fold_filters(
                filters,
                None if whitener is None else whitener.whitener,
                None if whitener is None else whitener.means,
                patch=self.patch, channels=img_channels)

    def apply(self, image):
        return _convolve(
            jnp.asarray(image)[None], self.kernel, self.colsum, self.bias,
            self.normalize_patches,
        )[0]

    def batch_fn(self):
        return lambda imgs: _convolve(
            imgs, self.kernel, self.colsum, self.bias, self.normalize_patches
        )

    def fuse(self):
        normalize = self.normalize_patches
        return (
            ("Convolver", normalize),
            (self.kernel, self.colsum, self.bias),
            lambda p, xb: _convolve.__wrapped__(xb, p[0], p[1], p[2], normalize),
        )

    def apply_batch(self, data: Dataset):
        return data.map_batches(self.batch_fn(), jitted=False)

    def absorb(self, followers):
        """A rectifier and a sum `Pooler` behind it: the fused stage."""
        if (len(followers) >= 2
                and isinstance(followers[0], SymmetricRectifier)
                and _sums_pixels(followers[1])):
            r, p = followers[:2]
            return _ConvRectifyPoolStage(
                self, r.alpha, r.max_val, p.pool_size, p.stride), 2
        return None


class SymmetricRectifier(Transformer):
    """Two-sided ReLU: channels double to [max(0, x−α), max(0, −x−α)]
    (SymmetricRectifier.scala:7-32)."""

    fusable = True
    precision_tolerance = "tolerant"  # elementwise two-sided ReLU

    def __init__(self, max_val: float = 0.0, alpha: float = 0.0):
        self.max_val = max_val
        self.alpha = alpha

    def apply(self, x):
        return jnp.concatenate(
            [
                jnp.maximum(self.max_val, x - self.alpha),
                jnp.maximum(self.max_val, -x - self.alpha),
            ],
            axis=-1,
        )

    def batch_fn(self):
        return self.apply  # elementwise: batched arrays work directly

    def fuse(self):
        max_val, alpha = self.max_val, self.alpha
        return (
            ("SymmetricRectifier", max_val, alpha),
            (),
            lambda p, x: jnp.concatenate(
                [jnp.maximum(max_val, x - alpha), jnp.maximum(max_val, -x - alpha)],
                axis=-1,
            ),
        )

    def absorb(self, followers):
        """A sum `Pooler` behind it: the fused rectify+pool stage."""
        if followers and _sums_pixels(followers[0]):
            p = followers[0]
            return _RectifyPoolStage(
                self.alpha, self.max_val, p.pool_size, p.stride), 1
        return None


class Pooler(Transformer):
    """Strided sum-pooling with an elementwise pre-map
    (Pooler.scala:21-69) — `lax.reduce_window` on TPU."""

    fusable = True
    precision_tolerance = "tolerant"  # windowed sum/max over featurize

    def __init__(self, stride: int, pool_size: int, pixel_fn=None, pool_fn="sum"):
        self.stride = stride
        self.pool_size = pool_size
        self.pixel_fn = pixel_fn
        if pool_fn not in ("sum", "max"):
            raise ValueError("pool_fn must be 'sum' or 'max'")
        self.pool_fn = pool_fn

    def apply(self, x):  # (H, W, C)
        if self.pixel_fn is not None:
            x = self.pixel_fn(x)
        init, op = (0.0, lax.add) if self.pool_fn == "sum" else (-jnp.inf, lax.max)
        return lax.reduce_window(
            x,
            init,
            op,
            window_dimensions=(self.pool_size, self.pool_size, 1),
            window_strides=(self.stride, self.stride, 1),
            padding="VALID",
        )

    def batch_fn(self):
        def fn(x):  # (N, H, W, C)
            y = x if self.pixel_fn is None else self.pixel_fn(x)
            init, op = (0.0, lax.add) if self.pool_fn == "sum" else (-jnp.inf, lax.max)
            return lax.reduce_window(
                y, init, op,
                window_dimensions=(1, self.pool_size, self.pool_size, 1),
                window_strides=(1, self.stride, self.stride, 1),
                padding="VALID",
            )

        return fn

    def fuse(self):
        # arbitrary pixel_fn callables get no shared key (instance-cached)
        key = (
            ("opaque", id(self))
            if self.pixel_fn is not None
            else ("Pooler", self.stride, self.pool_size, self.pool_fn)
        )
        fn = self.batch_fn()
        return (key, (), lambda p, x: fn(x))


def _sums_pixels(stage) -> bool:
    """A `Pooler` the fused kernels take in: a plain sum, no pre-map."""
    return (isinstance(stage, Pooler) and stage.pool_fn == "sum"
            and stage.pixel_fn is None)


class _RectifyPoolStage(Transformer):
    """SymmetricRectifier >> Pooler(sum) as one stage
    (`SymmetricRectifier.absorb`): lowers to the Pallas one-pass kernel
    on TPU (ops/pallas_kernels.py), XLA elsewhere."""

    fusable = True
    precision_tolerance = "tolerant"  # both fused members are tolerant

    def __init__(self, alpha: float, max_val: float, pool: int, stride: int):
        self.alpha = alpha
        self.max_val = max_val
        self.pool = pool
        self.stride = stride

    def apply(self, x):
        from ...ops import rectify_pool_reference

        return rectify_pool_reference(
            x[None], self.alpha, self.max_val, self.pool, self.stride
        )[0]

    def fuse(self):
        from ...ops import use_rectify_pallas

        a, mv, p, s = self.alpha, self.max_val, self.pool, self.stride
        pal = use_rectify_pallas()  # part of the key: flag flips must
        # not reuse the other path's cached program

        def fn(params, x):
            # the dispatcher picks the VMEM-safe block size
            from ...ops import rectify_pool, rectify_pool_reference

            if pal:
                return rectify_pool(x, a, mv, p, s)
            return rectify_pool_reference(x, a, mv, p, s)

        return (("RectifyPool", a, mv, p, s, pal), (), fn)


class _ConvRectifyPoolStage(Transformer):
    """Convolver >> SymmetricRectifier >> Pooler(sum) as one stage
    (`Convolver.absorb`): the Pallas one-pass kernel keeps the conv
    output and the channel-doubled rectified tensor in VMEM, writing
    only the pooled grid, at any filter count (a bank too wide for VMEM
    runs as filter blocks). ops/pallas_kernels.py; measured 3.7x the XLA
    path at 10,000 filters on a TPU v5 lite, kernel alone (PERF.md).
    Default-on for TPU; XLA elsewhere."""

    fusable = True
    precision_tolerance = "tolerant"  # all three fused members are

    def __init__(self, conv, alpha: float, max_val: float, pool: int, stride: int):
        self.alpha = alpha
        self.max_val = max_val
        self.pool = pool
        self.stride = stride
        self.patch = conv.patch
        self.normalize = conv.normalize_patches
        self.kernel_hwio = conv.kernel
        self.colsum = conv.colsum
        self.bias = conv.bias

    def apply(self, x):
        from ...ops import conv_rectify_pool_reference

        return conv_rectify_pool_reference(
            x[None], self.kernel_hwio, self.colsum, self.bias,
            self.alpha, self.max_val, self.pool, self.stride, self.normalize,
        )[0]

    def fuse(self):
        from ...ops import use_fused_conv

        a, mv, p, s = self.alpha, self.max_val, self.pool, self.stride
        normalize = self.normalize
        fused = use_fused_conv()  # part of the key (see _RectifyPoolStage)

        def fn(params, x):
            (kern, cs, bs) = params
            from ...ops import conv_rectify_pool

            return conv_rectify_pool(
                x, kern, cs, bs, a, mv, p, s, normalize
            )

        return (
            ("ConvRectifyPool", a, mv, p, s, self.patch, normalize, fused),
            (self.kernel_hwio, self.colsum, self.bias),
            fn,
        )


class ImageVectorizer(Transformer):
    """(H, W, C) → flat vector (ImageVectorizer.scala:12)."""

    fusable = True
    chunkable = True  # pure per-item fn: distributes over chunks
    precision_tolerance = "tolerant"  # reshape: values untouched

    def apply(self, x):
        return jnp.ravel(x)

    def batch_fn(self):
        return lambda x: x.reshape(x.shape[0], -1)

    def fuse(self):
        return (("ImageVectorizer",), (), lambda p, x: x.reshape(x.shape[0], -1))


class PixelScaler(Transformer):
    """x / 255 (PixelScaler.scala:9)."""

    fusable = True
    chunkable = True  # per-item host map: distributes over chunks
    precision_tolerance = "tolerant"  # uint8 decode: 8 significant bits

    def abstract_apply(self, elem):
        return jax.ShapeDtypeStruct(tuple(elem.shape), jnp.float32)

    def apply(self, x):
        return jnp.asarray(x, jnp.float32) / 255.0

    def apply_batch(self, data):
        from ...data.dataset import HostDataset

        if isinstance(data, HostDataset):
            # stay host-resident: variable-size images reach the device
            # only at the bucketed extractor dispatch, not one round
            # trip per item here
            import numpy as np

            return data.map(lambda x: np.asarray(x, np.float32) / 255.0)
        return super().apply_batch(data)

    def batch_fn(self):
        return self.apply

    def fuse(self):
        # uint8 pixel decode: the f32 widening IS this stage's job (the
        # input has 8 significant bits; downstream boundaries may still
        # be halved by the precision planner)
        return (
            ("PixelScaler",),
            (),
            lambda p, x: jnp.asarray(x, jnp.float32) / 255.0,  # keystone: ignore[KJ011]
        )


class GrayScaler(Transformer):
    """NTSC grayscale (GrayScaler.scala:9): (H, W, C) to (H, W, 1), or
    with ``channel=False`` to (H, W). A set of one-channel images held
    on a TPU wants the latter: the chip's tiled layout of (n, H, W, 1)
    puts the images' index on the lanes, and a program that then takes
    them a few at a time first copies the whole set."""

    fusable = True
    chunkable = True  # per-item host map: distributes over chunks

    def __init__(self, channel: bool = True):
        self.channel = channel

    def abstract_apply(self, elem):
        return jax.ShapeDtypeStruct(
            tuple(elem.shape[:-1]) + ((1,) if self.channel else ()),
            jnp.float32)

    def apply(self, x):
        from ...utils.images import grayscale

        gray = grayscale(x)
        return gray if self.channel else gray[..., 0]

    def fuse(self):
        # shape-only state: one static key serves every instance, so
        # fused programs containing this stage stay structurally cached
        # (KP501 — the PR-6 silent-retrace class)
        channel = self.channel

        def fn(p, x):
            if x.shape[-1] == 1:
                return x if channel else x[..., 0]
            # uint8 pixel decode (see PixelScaler.fuse): widening to f32
            # is the stage's contract, not a policy leak
            w = jnp.asarray([0.299, 0.587, 0.114], jnp.float32)  # keystone: ignore[KJ011]
            return jnp.sum(
                jnp.asarray(x, jnp.float32) * w, axis=-1, keepdims=channel)  # keystone: ignore[KJ011]

        return (("GrayScaler", channel), (), fn)

    def apply_batch(self, data):
        from ...data.dataset import HostDataset

        if isinstance(data, HostDataset):  # host-resident (see PixelScaler)
            import numpy as np

            w = np.asarray([0.299, 0.587, 0.114], np.float32)
            keep = self.channel
            return data.map(
                lambda x: (x if keep else x[..., 0]) if x.shape[-1] == 1
                else np.sum(np.asarray(x, np.float32) * w, -1, keepdims=keep)
            )
        return super().apply_batch(data)


class Cropper(Transformer):
    """(Cropper.scala:19)"""

    fusable = True
    chunkable = True  # pure per-item slice: distributes over chunks

    def __init__(self, y0: int, x0: int, y1: int, x1: int):
        self.box = (y0, x0, y1, x1)

    def apply(self, x):
        y0, x0, y1, x1 = self.box
        return x[y0:y1, x0:x1, :]

    def fuse(self):
        # the box is static (it changes output shapes), so it keys the
        # program; same-box Croppers share one compiled program (KP501)
        y0, x0, y1, x1 = self.box
        return (("Cropper", y0, x0, y1, x1), (),
                lambda p, x: x[:, y0:y1, x0:x1, :])


class Windower(Transformer):
    """All strided patches of each image; the batch path flattens
    (N, …) → (N·patches, p, p, C), changing the dataset count
    (Windower.scala:13-56 — a FunctionNode/flatMap in the reference)."""

    def __init__(self, stride: int, window_size: int):
        self.stride = stride
        self.window_size = window_size

    def apply(self, image):
        from ...utils.images import extract_patches

        flat = extract_patches(np.asarray(image)[None], self.window_size, self.stride)
        return flat.reshape(-1, self.window_size, self.window_size, image.shape[-1])

    def apply_batch(self, data: Dataset):
        from ...telemetry import dispatch
        from ...utils.images import extract_patches_device

        h, w = data.array.shape[1], data.array.shape[2]
        gy = (h - self.window_size) // self.stride + 1
        gx = (w - self.window_size) // self.stride + 1
        with dispatch(self.label):
            patches = extract_patches_device(
                data.array, self.window_size, self.stride)
        # padding rows' windows land at the tail (image-major order), so
        # an explicit count keeps exactly the valid windows
        return Dataset(patches, count=data.count * gy * gx, mesh=data.mesh)


class RandomPatcher(Transformer):
    """Random crops for augmentation (RandomPatcher.scala:16-47). The
    batch path emits `patches_per_image` crops per image (count grows)."""

    def __init__(self, patches_per_image: int, patch_h: int, patch_w: int, seed: int = 0):
        self.patches_per_image = patches_per_image
        self.patch_h = patch_h
        self.patch_w = patch_w
        self.seed = seed
        self._rng = np.random.default_rng(seed)  # stateful: varies per call

    def apply_batch(self, data: Dataset):
        # crop offsets drawn on host (tiny); the gather runs on device —
        # no round trip of the image tensor
        n = data.count
        h, w = data.array.shape[1], data.array.shape[2]
        rng = np.random.default_rng(self.seed)
        ys = rng.integers(0, h - self.patch_h + 1, size=(n, self.patches_per_image))
        xs = rng.integers(0, w - self.patch_w + 1, size=(n, self.patches_per_image))
        ppi = self.patches_per_image
        img_idx = jnp.asarray(np.repeat(np.arange(n), ppi))        # (n·ppi,)
        row0 = jnp.asarray(ys.reshape(-1))                          # (n·ppi,)
        col0 = jnp.asarray(xs.reshape(-1))
        rows = row0[:, None, None] + jnp.arange(self.patch_h)[None, :, None]
        cols = col0[:, None, None] + jnp.arange(self.patch_w)[None, None, :]
        from ...telemetry import dispatch

        with dispatch(self.label):
            out = data.array[img_idx[:, None, None], rows, cols, :]  # one gather
        return Dataset(out, count=n * ppi, mesh=data.mesh)

    def apply(self, image):
        y = self._rng.integers(0, image.shape[0] - self.patch_h + 1)
        x = self._rng.integers(0, image.shape[1] - self.patch_w + 1)
        return image[y : y + self.patch_h, x : x + self.patch_w]


class CenterCornerPatcher(Transformer):
    """Center + 4 corner crops, optionally h-flipped variants
    (CenterCornerPatcher.scala:19-48)."""

    def __init__(self, patch_h: int, patch_w: int, with_flips: bool = False):
        self.patch_h = patch_h
        self.patch_w = patch_w
        self.with_flips = with_flips

    def _starts(self, h: int, w: int):
        """Shared crop geometry — the single-item and batch paths must
        emit identical crop order (cifar_variants relies on it)."""
        ph, pw = self.patch_h, self.patch_w
        return [
            (0, 0), (0, w - pw), (h - ph, 0), (h - ph, w - pw),
            ((h - ph) // 2, (w - pw) // 2),
        ]

    def _crops(self, image):
        ph, pw = self.patch_h, self.patch_w
        starts = self._starts(image.shape[0], image.shape[1])
        crops = [image[y : y + ph, x : x + pw] for y, x in starts]
        if self.with_flips:
            crops += [c[:, ::-1] for c in crops]
        return crops

    def apply(self, image):
        return np.stack(self._crops(np.asarray(image)))

    def apply_batch(self, data: Dataset):
        # five static slices (+flips) on device, image-major output order
        from ...telemetry import dispatch

        imgs = data.array
        ph, pw = self.patch_h, self.patch_w
        starts = self._starts(imgs.shape[1], imgs.shape[2])
        with dispatch(self.label):
            crops = [imgs[:, y : y + ph, x : x + pw] for y, x in starts]
            if self.with_flips:
                crops += [c[:, :, ::-1] for c in crops]
            k = len(crops)
            out = jnp.stack(crops, axis=1).reshape(
                -1, ph, pw, imgs.shape[-1])
        return Dataset(out, count=data.count * k, mesh=data.mesh)


class RandomImageTransformer(Transformer):
    """Apply a transform with probability p (RandomImageTransformer.scala:15-31)."""

    def __init__(self, prob: float, transform, seed: int = 0):
        self.prob = prob
        self.transform = transform
        self.seed = seed
        self._rng = np.random.default_rng(seed)  # stateful: varies per call

    def apply_batch(self, data):
        rng = np.random.default_rng(self.seed)
        flips = rng.random(data.count) < self.prob
        # Device path ONLY for transforms that declare themselves pure
        # and traceable (`jax_traceable = True`, e.g. utils.images.
        # flip_horizontal). vmap traces the function ONCE, so a
        # transform with host-side randomness/state would silently get
        # constant-folded — the per-image host loop is the only correct
        # general path.
        if (
            isinstance(data, Dataset)
            and getattr(self.transform, "jax_traceable", False)
        ):
            imgs = data.array
            # shape/dtype eligibility without computing anything
            spec = jax.eval_shape(jax.vmap(self.transform), imgs)
            if spec.shape == imgs.shape and spec.dtype == imgs.dtype:
                mask = jnp.asarray(
                    np.pad(flips, (0, imgs.shape[0] - data.count))
                ).reshape((-1,) + (1,) * (imgs.ndim - 1))
                transformed = jax.vmap(self.transform)(imgs)
                return data.with_data(jnp.where(mask, transformed, imgs))
        # host path; also reached by HostDataset input (fixed-shape items
        # stack — HostDataset.numpy() returns the item list, and it has
        # no .mesh, hence the getattr)
        imgs = np.array(data.numpy(), copy=True)
        for i in np.nonzero(flips)[0]:
            imgs[i] = self.transform(imgs[i])
        return Dataset(imgs, mesh=getattr(data, "mesh", None))

    def apply(self, image):
        return self.transform(image) if self._rng.random() < self.prob else image
