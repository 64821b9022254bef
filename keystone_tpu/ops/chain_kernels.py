"""Chain megakernels: lower a KP801 candidate's fused-stage trail to
ONE double-buffered Pallas kernel.

The fusion builder (nodes/util/fusion.py) composes stage bodies into a
single XLA program, but XLA still lowers the chain stage-at-a-time:
every boundary round-trips HBM (KP801 prices these — RandomPatchCifar's
rectify→pool→vectorize alone round-trips ~60 MB per sharded branch).
This module lowers an eligible sub-trail to one `pl.pallas_call` whose
grid streams batch blocks HBM→VMEM (the grid pipeline double-buffers
blocked operands), applies every stage body in VMEM, and writes only
the chain's final output — one HBM pass of in+out bytes instead of a
round-trip per boundary.

Two candidate families, matched on the same `stage_fuse` static keys
the fusion builder and the KP501 auditor use:

- ``rectify_pool_vectorize``: the merged ``RectifyPool >>
  ImageVectorizer`` trail of the conv pipelines. Reuses the proven
  rectify+pool kernel body (ops/pallas_kernels.py, 1.1-1.54x live) and
  appends the vectorize as a free contiguous reshape of the pooled
  block — the channel-doubled rectified tensor never leaves VMEM.
- ``elementwise_chain``: runs of shape-preserving-or-reshaping per-row
  stages (PixelScaler, GrayScaler, LinearRectifier, NormalizeRows,
  SignedHellingerMapper, RandomSign, StandardScaler, the vectorizers)
  on the FFT/patch paths. Each stage body executes on the VMEM block;
  ``fuse_masks_output`` stages keep re-zeroing padded rows at their
  original chain position via a streamed (block, 1) mask operand.

Every lowering has a pure-jnp ``*_reference`` oracle (the XLA path and
the CPU/test oracle — the SAME body functions applied outside Pallas),
a VMEM geometry chooser that returns 0 / raises
`ChainKernelIneligibleError` instead of compiling an OOM, and a canary
(the fused-conv discipline) so a Mosaic reject demotes to XLA instead
of crashing the enclosing program.

Gate: `use_chain_kernels()` — `ExecutionConfig.pallas_kernels` is the
master kill switch (env ``KEYSTONE_CHAIN_KERNELS``, ledger-header
recorded). Off-TPU the kernels are interpret-validated only: the
planner still prices and records the decision, but programs keep the
XLA body unless ``KEYSTONE_CHAIN_KERNELS=interpret`` forces the
interpret-mode swap (the e2e test hook). ``=0`` is the bit-for-bit
kill: the built program is exactly the pre-kernel XLA form.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import (
    _rectify_pool_kernel,
    _round_up,
    canary_verdict,
    rectify_pool_reference,
    run_outside_trace,
)

#: the fused-conv budget discipline: leave ~6 MB of the 16 MB VMEM for
#: scheduling slop and double-buffer headroom
_VMEM_BUDGET = 10 * (1 << 20)

#: block-row ladders each family's chooser descends (largest first).
#: Shared with the KP1003 static proof (analysis/kernels.py) so the
#: prover walks the exact candidate set the runtime chooser walks.
_RECTIFY_BLOCK_LADDER = tuple(range(8, 0, -1))
_ELEMENTWISE_BLOCK_LADDER = (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)


def chain_vmem_bytes(bn: int, io_bytes: int, inter_bytes: int = 0,
                     param_bytes: int = 0) -> int:
    """THE chain-kernel VMEM working-set formula — the one shared
    arithmetic behind both families' block choosers AND the KP1003
    static proof (the `collective_cost`/`live_set_walk` precedent: one
    function, so the static verdict and the runtime demotion can never
    diverge). At batch block ``bn``: the grid pipeline double-buffers
    every streamed block (2× the in+out bytes), intermediates are
    single-buffered transients, closure params are resident once."""
    return 2 * bn * io_bytes + bn * inter_bytes + param_bytes


def chain_block_rows(io_bytes: int, inter_bytes: int = 0,
                     param_bytes: int = 0, *,
                     ladder=_ELEMENTWISE_BLOCK_LADDER,
                     budget=None) -> int:
    """Largest ladder block whose `chain_vmem_bytes` working set fits
    the budget (0 = the geometry cannot fit VMEM at any block)."""
    budget = _VMEM_BUDGET if budget is None else budget
    for bn in ladder:
        if chain_vmem_bytes(bn, io_bytes, inter_bytes, param_bytes) <= budget:
            return bn
    return 0


class ChainKernelIneligibleError(ValueError):
    """The chain kernel's block geometry cannot fit VMEM."""


def use_chain_kernels() -> bool:
    """Master gate for the planned chain megakernels:
    `ExecutionConfig.pallas_kernels` (env ``KEYSTONE_CHAIN_KERNELS``)
    AND a TPU backend — except ``KEYSTONE_CHAIN_KERNELS=interpret``,
    which enables the interpret-mode swap everywhere (tests, off-TPU
    validation)."""
    from ..workflow.env import execution_config

    if not execution_config().pallas_kernels:
        return False
    if chain_interpret_forced():
        return True
    return jax.default_backend() == "tpu"


def chain_interpret_forced() -> bool:
    """``KEYSTONE_CHAIN_KERNELS=interpret``: run the kernels in
    interpret mode regardless of backend (the e2e swap-path hook)."""
    return os.environ.get("KEYSTONE_CHAIN_KERNELS", "").lower() == "interpret"


def chain_interpret() -> bool:
    """Interpret off-TPU (validated emulation), native on TPU."""
    if chain_interpret_forced():
        return True
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Static-key matcher: which fused sub-trails lower, and why not
# ---------------------------------------------------------------------------

#: stages a chain kernel cannot absorb, with the NAMED reason the
#: lint.sh chain-kernel audit renders: a KP801 candidate containing
#: only these is suppressed (stays on XLA deliberately), anything else
#: unsupported is an open lowering gap the audit fails on.
SUPPRESSED_STAGES = {
    "ConvRectifyPool": "already ONE fused Pallas kernel "
                       "(ops.conv_rectify_pool, PR 11)",
    "PaddedFFT": "rfft has no Mosaic lowering; stays on the XLA path",
    "Pooler": "non-sum/pixel_fn pooling (the sum form merges into "
              "RectifyPool) stays on lax.reduce_window",
    "opaque": "id-keyed opaque stage: no static body to lower",
}

#: per-stage VMEM body builders for the elementwise family, keyed on
#: the `stage_fuse` static-key head. Each entry:
#: ``prep(params) -> tuple of >=2-D operand arrays`` and
#: ``body(x, ops) -> y`` — pure jnp, used verbatim inside the kernel
#: and by the reference oracle (bit-identical bodies by construction).
_ELEMENTWISE = {}


def _register(head):
    def deco(builder):
        _ELEMENTWISE[head] = builder
        return builder
    return deco


def _scalar_ops(*vals):
    return tuple(jnp.asarray(v, jnp.float32).reshape(1, 1) for v in vals)


@_register("PixelScaler")
def _px(key, params):
    return (lambda p: (),
            lambda x, ops: jnp.asarray(x, jnp.float32) / 255.0)  # keystone: ignore[KJ011]


@_register("GrayScaler")
def _gray(key, params):
    # the NTSC weights ride as a kernel operand — Pallas kernels cannot
    # capture array constants
    def prep(p):
        return (jnp.asarray([0.299, 0.587, 0.114],  # keystone: ignore[KJ011]
                            jnp.float32).reshape(1, 3),)

    # the key's second entry: whether the one channel stays an axis
    channel = key[1] if isinstance(key, tuple) and len(key) > 1 else True

    def body(x, ops):
        if x.shape[-1] == 1:
            return x if channel else x[..., 0]
        return jnp.sum(jnp.asarray(x, jnp.float32) * ops[0],  # keystone: ignore[KJ011]
                       axis=-1, keepdims=channel)

    return prep, body


@_register("ImageVectorizer")
@_register("MatrixVectorizer")
def _vec(key, params):
    return (lambda p: ()), (lambda x, ops: x.reshape(x.shape[0], -1))


@_register("LinearRectifier")
def _rect(key, params):
    def body(x, ops):
        mv, a = ops
        return jnp.maximum(mv[0, 0].astype(x.dtype),
                           x - a[0, 0].astype(x.dtype))

    return (lambda p: _scalar_ops(p[0], p[1])), body


@_register("NormalizeRows")
def _norm(key, params):
    def body(x, ops):
        (eps,) = ops
        axes = tuple(range(1, x.ndim))
        norms = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True))
        return x / jnp.maximum(norms, eps[0, 0].astype(x.dtype))

    return (lambda p: _scalar_ops(p[0])), body


@_register("SignedHellingerMapper")
def _hell(key, params):
    return (lambda p: ()), (lambda x, ops: jnp.sign(x) * jnp.sqrt(jnp.abs(x)))


@_register("RandomSignNode")
def _sign(key, params):
    def body(x, ops):
        (s,) = ops
        return x * s.astype(x.dtype)

    return (lambda p: (jnp.asarray(p[0]).reshape(1, -1),)), body


@_register("StandardScaler")
def _std(key, params):
    mode = key[1] if isinstance(key, tuple) and len(key) > 1 else "scale"
    if mode == "center":
        def body(x, ops):
            (m,) = ops
            return x - m.astype(x.dtype)

        return (lambda p: (jnp.asarray(p[0]).reshape(1, -1),)), body

    def body(x, ops):
        m, s = ops
        return (x - m.astype(x.dtype)) / s.astype(x.dtype)

    return (lambda p: (jnp.asarray(p[0]).reshape(1, -1),
                       jnp.asarray(p[1]).reshape(1, -1))), body


def _unwrap(key):
    """Strip `stage_fuse`'s ``(key, "masked")`` wrapping; returns
    (inner_key, masked)."""
    masked = False
    while (isinstance(key, tuple) and len(key) == 2 and key[1] == "masked"):
        key, masked = key[0], True
    return key, masked


def _head(key):
    key, _ = _unwrap(key)
    if isinstance(key, tuple) and key:
        return key[0]
    return key


def stage_statics(stages):
    """The fuse static keys of the chain's `fused_stages` — the
    matcher's input. Same decomposition a fused program derives its
    key from; never builds or compiles a program."""
    from ..nodes.util.fusion import fused_stages, stage_fuse

    return tuple(stage_fuse(s)[0] for s in fused_stages(stages))


def lowerability(statics) -> dict:
    """Verdict for a candidate chain's fuse statics: ``lowerable``
    (bool), ``family`` (str or None), ``reason`` (always rendered — why
    it lowers or why not), and ``suppressed`` (dict of stage → named
    reason, present only when EVERY blocker is a deliberate
    SUPPRESSED_STAGES entry — the lint.sh audit's escape hatch)."""
    statics = tuple(statics)
    heads = [_head(k) for k in statics]
    if len(statics) < 2:
        return {"lowerable": False, "family": None,
                "reason": "chain shorter than 2 fused stages"}
    if (len(statics) == 2 and heads[0] == "RectifyPool"
            and heads[1] in ("ImageVectorizer", "MatrixVectorizer")):
        return {"lowerable": True, "family": "rectify_pool_vectorize",
                "reason": "RectifyPool >> Vectorizer: one double-buffered "
                          "kernel writes only the pooled-flat output"}
    if all(h in _ELEMENTWISE for h in heads):
        return {"lowerable": True, "family": "elementwise_chain",
                "reason": "all stage bodies execute on the VMEM block: "
                          + " >> ".join(str(h) for h in heads)}
    blockers = sorted({str(h) for h in heads if h not in _ELEMENTWISE
                       and h != "RectifyPool"})
    out = {"lowerable": False, "family": None,
           "reason": "unsupported stage(s): " + ", ".join(blockers)}
    named = {b: SUPPRESSED_STAGES[b] for b in blockers
             if b in SUPPRESSED_STAGES}
    if blockers and len(named) == len(blockers):
        out["suppressed"] = named
    return out


# ---------------------------------------------------------------------------
# Family 1: rectify -> pool -> vectorize
# ---------------------------------------------------------------------------


def rectify_pool_vectorize_reference(x, alpha, max_val, pool, stride):
    """XLA oracle: SymmetricRectifier >> Pooler(sum) >> ImageVectorizer
    exactly as the unfused stages compute it. (N,H,W,K) → (N, gy·gx·2K)."""
    y = rectify_pool_reference(x, alpha, max_val, pool, stride)
    return y.reshape(y.shape[0], -1)


def _rectify_pool_vectorize_parts(h, w, k, pool, stride):
    """(io_bytes, inter_bytes, param_bytes, ladder) — the exact inputs
    this family's chooser feeds `chain_block_rows`, or None when the
    pool grid is empty. Input and pooled-output blocks both stream
    (double-buffered), with Mosaic's (8, 128) f32 tile padding on the
    two minor dims of each; no intermediates or closure params."""
    gy = (h - pool) // stride + 1
    gx = (w - pool) // stride + 1
    if gy <= 0 or gx <= 0:
        return None
    in_per = h * _round_up(w, 8) * _round_up(k, 128) * 4
    out_per = gy * _round_up(gx, 8) * _round_up(2 * k, 128) * 4
    return in_per + out_per, 0, 0, _RECTIFY_BLOCK_LADDER


def _rectify_pool_vectorize_block(h, w, k, pool, stride) -> int:
    """Largest eligible batch block (0 = the geometry cannot fit VMEM),
    chosen by the shared `chain_vmem_bytes` working-set formula."""
    parts = _rectify_pool_vectorize_parts(h, w, k, pool, stride)
    if parts is None:
        return 0
    io_bytes, inter, param_bytes, ladder = parts
    return chain_block_rows(io_bytes, inter, param_bytes, ladder=ladder)


@jax.named_scope("ks.rectify_pool_vectorize_pallas")
def rectify_pool_vectorize_pallas(
    x, alpha, max_val, pool, stride, *, block_n=None, interpret=False,
):
    """One double-buffered kernel for the whole chain: the grid streams
    (bn, H, W, K) blocks into VMEM, the rectify+pool body writes the
    pooled grid per block, and the trailing vectorize is a contiguous
    row-major reshape of the kernel output (a bitcast, not a pass)."""
    n, h, w, k = x.shape
    bn = block_n or _rectify_pool_vectorize_block(h, w, k, pool, stride)
    if bn <= 0:
        raise ChainKernelIneligibleError(
            f"rectify_pool_vectorize block does not fit VMEM at "
            f"(h={h}, w={w}, k={k})")
    gy = (h - pool) // stride + 1
    gx = (w - pool) // stride + 1
    bn = min(bn, n)
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
        name="ks_rectify_pool_vectorize",
    )(x)
    return out[:n].reshape(n, gy * gx * 2 * k)


def rectify_pool_vectorize(x, alpha, max_val, pool, stride, *,
                           interpret=None):
    """Dispatcher: the chain kernel when the gate and geometry allow,
    the XLA oracle otherwise. A canary (the fused-conv discipline)
    compiles and runs the kernel once per geometry, eagerly, at the
    block the real program will use (one row more than a block, so a
    padded tail too): what Mosaic refuses fails there, by name, and not
    in the middle of the enclosing program's compile."""
    if use_chain_kernels():
        n, h, w, k = x.shape
        interp = chain_interpret() if interpret is None else interpret
        bn = _rectify_pool_vectorize_block(h, w, k, pool, stride)
        if bn > 0 and (interp or _canary_ok(
            ("rectify_pool_vectorize", h, w, k, pool, stride),
            lambda: run_outside_trace(
                lambda xc: rectify_pool_vectorize_pallas(
                    xc, 0.1, 0.0, pool, stride),
                np.zeros((bn + 1, h, w, k), np.float32)),
        )):
            try:
                return rectify_pool_vectorize_pallas(
                    x, alpha, max_val, pool, stride, interpret=interp)
            except ChainKernelIneligibleError:
                pass
    return rectify_pool_vectorize_reference(x, alpha, max_val, pool, stride)


# ---------------------------------------------------------------------------
# Family 2: elementwise chains
# ---------------------------------------------------------------------------


def _compile_bodies(statics):
    """[(masked, prep, body)] per stage, or None when any stage's head
    has no registered VMEM body."""
    out = []
    for key in statics:
        inner, masked = _unwrap(key)
        head = inner[0] if isinstance(inner, tuple) and inner else inner
        builder = _ELEMENTWISE.get(head)
        if builder is None:
            return None
        prep, body = builder(inner, None)
        out.append((masked, prep, body))
    return out


def _run_bodies(bodies, ops, x, mask):
    """Apply the chain's bodies in order (pure jnp — shared by the
    reference oracle and shape/geometry probes). ``mask``: f32 (n, 1)
    valid-row column or None; masked stages re-zero padded rows at
    their original chain position (the `fuse_masks_output` contract)."""
    for (masked, _, body), o in zip(bodies, ops):
        x = body(x, o)
        if masked and mask is not None:
            x = x * mask.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)
    return x


@jax.named_scope("ks.elementwise_chain")
def elementwise_chain_reference(statics, params, x, mask=None):
    """Pure-jnp oracle: the SAME stage bodies the kernel traces,
    applied outside Pallas. ``params``: one pytree per stage (the
    `stage_fuse` params slice); ``mask``: bool (n,) or None."""
    bodies = _compile_bodies(statics)
    if bodies is None:
        raise ChainKernelIneligibleError(
            f"no elementwise lowering for {statics!r}")
    ops = [prep(p) for (_, prep, _), p in zip(bodies, params)]
    m = None
    if mask is not None:
        m = jnp.asarray(mask, jnp.float32).reshape(-1, 1)
    return _run_bodies(bodies, ops, x, m)


def _padded_item_bytes(shape, dtype) -> int:
    """Per-item VMEM bytes of one (block, *shape) buffer under Mosaic
    tile padding: lane (minor) dim to 128, sublane to 8."""
    itemsize = max(jnp.dtype(dtype).itemsize, 1)
    dims = list(shape)
    if not dims:
        return 128 * itemsize
    dims[-1] = _round_up(dims[-1], 128)
    if len(dims) >= 2:
        dims[-2] = _round_up(dims[-2], 8)
    total = 1
    for d in dims:
        total *= d
    return total * itemsize


def _elementwise_avals(bodies, ops, x):
    """Per-boundary avals of the chain at batch probe ``x`` (index 0 =
    the input, index i = after stage i) — `jax.eval_shape` only, shared
    by the geometry chooser and the KP1005 boundary check."""
    avals = [jax.eval_shape(lambda xx: xx, x)]
    cur = avals[0]
    for (_, _, body), o in zip(bodies, ops):
        cur = jax.eval_shape(lambda xx, oo: body(xx, oo), cur, o)
        avals.append(cur)
    return avals


def _elementwise_parts(bodies, ops, x):
    """(io_bytes, inter_bytes, param_bytes, ladder) — the exact inputs
    this family's chooser feeds `chain_block_rows`: in+out blocks
    stream (double-buffered), every internal boundary's transient is
    single-buffered, closure params are resident once."""
    avals = _elementwise_avals(bodies, ops, x)
    per_item = [_padded_item_bytes(a.shape[1:], a.dtype) for a in avals]
    io_bytes = per_item[0] + per_item[-1]
    inter = sum(per_item[1:-1])
    param_bytes = sum(_padded_item_bytes(a.shape, a.dtype)
                      for stage in ops for a in stage)
    return io_bytes, inter, param_bytes, _ELEMENTWISE_BLOCK_LADDER


def _elementwise_geometry(bodies, ops, x) -> int:
    """Largest batch block (0 = infeasible), chosen by the shared
    `chain_vmem_bytes` working-set formula."""
    io_bytes, inter, param_bytes, ladder = _elementwise_parts(bodies, ops, x)
    return chain_block_rows(io_bytes, inter, param_bytes, ladder=ladder)


@jax.named_scope("ks.elementwise_chain_pallas")
def elementwise_chain_pallas(
    statics, params, x, mask=None, *, block_n=None, interpret=False,
):
    """ONE kernel for the whole elementwise run: the grid streams batch
    blocks HBM→VMEM double-buffered, applies every stage body on the
    block, and writes only the final output. Masked stages consume a
    streamed (bn, 1) valid-row column so padded rows stay exactly what
    the node-by-node path produces."""
    bodies = _compile_bodies(statics)
    if bodies is None:
        raise ChainKernelIneligibleError(
            f"no elementwise lowering for {statics!r}")
    ops = [prep(p) for (_, prep, _), p in zip(bodies, params)]
    n = x.shape[0]
    bn = block_n or _elementwise_geometry(bodies, ops, x)
    if bn <= 0:
        raise ChainKernelIneligibleError(
            f"elementwise chain block does not fit VMEM at {x.shape}")
    bn = min(bn, n)
    n_pad = _round_up(n, bn)
    needs_mask = any(masked for masked, _, _ in bodies)
    m = None
    if needs_mask:
        m = (jnp.ones((n,), jnp.float32) if mask is None
             else jnp.asarray(mask, jnp.float32)).reshape(-1, 1)
    if n_pad != n:
        x = jnp.pad(x, [(0, n_pad - n)] + [(0, 0)] * (x.ndim - 1))
        if m is not None:
            m = jnp.pad(m, ((0, n_pad - n), (0, 0)))
    out_aval = jax.eval_shape(
        lambda xx, oo: _run_bodies(bodies, oo, xx, None), x, ops)
    flat_ops = [a for stage in ops for a in stage]

    def kernel(*refs):
        x_refs = refs[: 2 if needs_mask else 1]
        p_refs = refs[len(x_refs):-1]
        o_ref = refs[-1]
        xb = x_refs[0][...].reshape((bn,) + x_item)
        mb = x_refs[1][...] if needs_mask else None
        idx = 0
        for (masked, _, body), stage in zip(bodies, ops):
            loaded = tuple(p_refs[idx + t][...] for t in range(len(stage)))
            idx += len(stage)
            xb = body(xb, loaded)
            if masked:
                xb = xb * mb.reshape(
                    (-1,) + (1,) * (xb.ndim - 1)).astype(xb.dtype)
        o_ref[...] = xb.astype(o_ref.dtype).reshape(o_ref.shape)

    def _block(shape, ndim=None):
        nd = len(shape) if ndim is None else ndim
        return pl.BlockSpec(shape, lambda i, nd=nd: (i,) + (0,) * (nd - 1),
                            memory_space=pltpu.VMEM)

    def streamed(item):
        """Item shape under which Mosaic takes a streamed (bn, *item)
        block. A (bn, width) block has the batch as its sublane
        dimension and is taken only in whole 8-row tiles or covering
        the array: the v5e's compiler refused (4, 1024) of (2052, 1024),
        the LinearPixels trail's output. A unit dimension in between
        makes the last two dimensions the array's own; the reshape
        outside the kernel is a bitcast."""
        if len(item) == 1 and bn % 8 and bn != n_pad:
            return (1,) + item
        return item

    x_item = tuple(x.shape[1:])
    in_specs = [_block((bn,) + streamed(x_item))]
    operands = [x.reshape((n_pad,) + streamed(x_item))]
    if needs_mask:
        in_specs.append(_block((bn,) + streamed((1,))))
        operands.append(m.reshape((n_pad,) + streamed((1,))))
    for a in flat_ops:
        in_specs.append(pl.BlockSpec(
            a.shape, lambda i, nd=a.ndim: (0,) * nd,
            memory_space=pltpu.VMEM))
        operands.append(a)
    out_item = tuple(out_aval.shape[1:])
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=in_specs,
        out_specs=_block((bn,) + streamed(out_item)),
        out_shape=jax.ShapeDtypeStruct((n_pad,) + streamed(out_item),
                                       out_aval.dtype),
        interpret=interpret,
        name="ks_elementwise_chain",
    )(*operands)
    return out.reshape((n_pad,) + out_item)[:n]


def elementwise_chain(statics, params, x, mask=None, *, interpret=None):
    """Dispatcher: the chain kernel when the gate and geometry allow,
    the pure-jnp oracle otherwise (same bodies either way)."""
    if use_chain_kernels():
        interp = chain_interpret() if interpret is None else interpret
        bodies = _compile_bodies(statics)
        if bodies is not None:
            ops = [prep(p) for (_, prep, _), p in zip(bodies, params)]
            bn = _elementwise_geometry(bodies, ops, x)
            geo = ("elementwise_chain", tuple(str(_head(k)) for k in statics),
                   tuple(x.shape[1:]), jnp.dtype(x.dtype).name)
            # canary operands are rebuilt from STATIC shapes (params may
            # be tracers inside the enclosing program trace) and filled
            # with ones, not zeros — a zero std/eps would NaN the probe
            # and fail a working geometry
            canary_params = [
                jax.tree_util.tree_map(
                    lambda a: np.ones(jnp.shape(a), jnp.result_type(a)), p)
                for p in params
            ]
            if bn > 0 and (interp or _canary_ok(
                geo,
                # one row more than a block: the block geometry of the
                # real program and a padded tail, not the whole-array
                # block a single row would get
                lambda: run_outside_trace(
                    lambda ps, xc: elementwise_chain_pallas(statics, ps, xc),
                    canary_params,
                    np.zeros((bn + 1,) + tuple(x.shape[1:]), x.dtype)),
            )):
                try:
                    return elementwise_chain_pallas(
                        statics, params, x, mask, interpret=interp)
                except ChainKernelIneligibleError:
                    pass
    return elementwise_chain_reference(statics, params, x, mask)


# ---------------------------------------------------------------------------
# Canary + chain builder (the fusion swap's entry point)
# ---------------------------------------------------------------------------

_chain_canary: dict = {}


def _canary_ok(key, thunk) -> bool:
    """The chain kernels' canary: `pallas_kernels.canary_verdict` over
    `_chain_canary`, demoting only on `ChainKernelIneligibleError`."""
    return canary_verdict(_chain_canary, key, thunk,
                          ChainKernelIneligibleError, "chain kernel")


def build_chain_fn(statics, family=None, interpret=None):
    """The fusion swap's entry point: a ``fn(params_slice, xb, mb)``
    lowering the sub-trail to one kernel dispatch, or None when the
    slice doesn't match a family (a stale `planned_kernel` tag is
    ignored, never mis-lowered — the `planned_precision` discipline).
    ``family`` (from the plan tag) must agree with the matcher."""
    statics = tuple(statics)
    verdict = lowerability(statics)
    if not verdict["lowerable"]:
        return None
    if family is not None and family != verdict["family"]:
        return None
    if verdict["family"] == "rectify_pool_vectorize":
        inner, _ = _unwrap(statics[0])
        _, alpha, max_val, pool, stride = inner[:5]

        def fn(ps, xb, mb):
            return rectify_pool_vectorize(
                xb, alpha, max_val, pool, stride, interpret=interpret)

        return fn

    def fn(ps, xb, mb):
        return elementwise_chain(statics, ps, xb, mb, interpret=interpret)

    return fn


#: what a stage or a shape that no chain kernel takes can raise from
#: `chain_feasible`'s static probes (nothing there compiles or runs);
#: an error from the backend is none of these and propagates
_STATIC_PROBE_ERRORS = (TypeError, ValueError, AttributeError,
                        NotImplementedError, IndexError, KeyError)


def chain_feasible(stages, item_shape, dtype=jnp.float32):
    """(ok, reason): probe the chain kernel's VMEM geometry at the
    per-item input shape without compiling anything. Used by the
    planner to price VMEM-infeasible tile geometries INF (clean
    demotion, never a crash). ``stages``: the raw (unmerged) stage
    objects of the candidate chain."""
    from ..nodes.util.fusion import fused_stages, stage_fuse

    try:
        fused = [stage_fuse(s) for s in fused_stages(stages)]
    except _STATIC_PROBE_ERRORS as e:
        return False, f"stage decomposition failed: {type(e).__name__}"
    statics = tuple(f[0] for f in fused)
    params = [f[1] for f in fused]
    verdict = lowerability(statics)
    if not verdict["lowerable"]:
        return False, verdict["reason"]
    if verdict["family"] == "rectify_pool_vectorize":
        if len(item_shape) != 3:
            return False, f"expected (H, W, K) input, got {item_shape}"
        inner, _ = _unwrap(statics[0])
        _, _, _, pool, stride = inner[:5]
        h, w, k = item_shape
        bn = _rectify_pool_vectorize_block(h, w, k, pool, stride)
        if bn <= 0:
            return False, (f"VMEM: no feasible block at "
                           f"(h={h}, w={w}, k={k})")
        return True, f"block={bn}"
    bodies = _compile_bodies(statics)
    if bodies is None:
        return False, verdict["reason"]
    try:
        x = jax.ShapeDtypeStruct((8,) + tuple(item_shape), dtype)
        ops = [prep(p) for (_, prep, _), p in zip(bodies, params)]
        bn = _elementwise_geometry(bodies, ops, x)
    except _STATIC_PROBE_ERRORS as e:
        return False, f"geometry probe failed: {type(e).__name__}"
    if bn <= 0:
        return False, f"VMEM: no feasible block at item shape {item_shape}"
    return True, f"block={bn}"
