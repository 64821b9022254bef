"""Live (on-chip) validation + timing of the Pallas kernels after a
geometry/structure change: the fused conv+rectify+pool kernel and the
two chain-megakernel families (`ops/chain_kernels.py` — the
elementwise chain and rectify→pool→vectorize, the KP801 lowerings the
unified planner's kernel axis prices).

Three gates per kernel, in order (each is a prerequisite for trusting
the next):

1. COMPILE: the kernel at the flagship geometry (conv: CIFAR k=256 at
   the largest VMEM block, the benchmark cell's 10,000 filters as
   filter tiles, and one pool geometry that takes the identity layout;
   chains: the bench-tier item shapes) must compile at a ragged batch
   (2·block+3, forcing a padded tail block) — a scoped-vmem OOM or
   Mosaic reject here is the failure class interpret-mode tests cannot
   see.
2. NUMERICS: on-chip agreement vs the XLA reference path at the same
   geometry (conv tolerance: the documented bf16-patch-feed class,
   ~5e-4 relative pooled over 196-element windows; chains: the same
   2e-3 gate — they are pure f32 so the observed error should sit at
   float roundoff).
3. TIMING: chained fresh-valued reps inside one program, R vs R/2
   differenced so launch and dispatch costs cancel —
   prints per-rep seconds and kernel-only images/sec for the Pallas
   path and the XLA reference path at the bench tier's batch; for the
   cell's geometry a rep is one microbatch of 32 images, the
   kernel-alone number PERF.md quotes.

Run from the repo root on the live chip: python scripts/kernel_live_check.py
``--interpret`` runs the chain-kernel gates 1+2 in Pallas interpret
mode (CPU smoke of this script's own harness; not a chip verdict).
"""

import sys
import time

sys.path.insert(0, ".")

import numpy as np


def _static_refutation(stages, item_shape):
    """KP10xx pre-flight: the static kernel verifier's refuting rule
    code (and message) when it proves this geometry unsafe/infeasible —
    the live check skips such geometries rather than burning TPU time
    on a lowering the unified planner prices to INF anyway. Returns
    None when the lowering verifies (or the verifier can't run)."""
    from keystone_tpu.analysis.kernels import verify_lowering

    try:
        proof, _ = verify_lowering(stages, item_shape)
    except Exception:
        return None  # verifier unavailable: the live gates decide
    code = proof.get("refuted_by")
    if code is None:
        code = next((r for r, v in (proof.get("rules") or {}).items()
                     if str(v).startswith("REFUTED")), None)
    if code is None:
        return None
    return code, (proof.get("rules") or {}).get(code, "")


def _timing_gate(name, fn_one, xb, reps=120):
    """Gate 3: differenced chained-rep timing (R vs R/2 inside one
    program so launch and dispatch costs cancel) — shared by the conv
    canary and both chain families."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def chained(r):
        @jax.jit
        def run(x, seed):
            def body(i, acc):
                key = jax.random.fold_in(seed, i)
                xp = x * (1.0 + 1e-6 * jax.random.uniform(key))
                y = fn_one(xp)
                return acc + y.reshape(x.shape[0], -1)[:, :8].sum()

            return lax.fori_loop(0, r, body, jnp.float32(0.0))

        return run

    seconds = {}
    for r in (reps // 2, reps):
        run = chained(r)
        float(run(xb, jax.random.PRNGKey(0)))  # compile+warm
        t0 = time.perf_counter()
        s = float(run(xb, jax.random.PRNGKey(1)))
        seconds[r] = time.perf_counter() - t0
        assert np.isfinite(s)
    per_rep = (seconds[reps] - seconds[reps // 2]) / (reps - reps // 2)
    print(f"{name}: full={seconds[reps]:.3f}s half={seconds[reps//2]:.3f}s "
          f"per_rep={per_rep*1e3:.2f}ms "
          f"kernel_only={xb.shape[0]/per_rep:,.0f} img/s", flush=True)


def check_chain_elementwise(interpret=False, timing=True):
    """Chain family 1: the elementwise megakernel at the LinearPixels
    geometry (PixelScaler >> GrayScaler >> ImageVectorizer on 32×32×3)
    — the exact stage trail the unified planner tags `planned_kernel`
    on that example's fused operator."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.images import (
        GrayScaler,
        ImageVectorizer,
        PixelScaler,
    )
    from keystone_tpu.nodes.util.fusion import fused_stages, stage_fuse
    from keystone_tpu.ops.chain_kernels import (
        _compile_bodies,
        _elementwise_geometry,
        elementwise_chain_pallas,
        elementwise_chain_reference,
    )

    stages = [PixelScaler(), GrayScaler(), ImageVectorizer()]
    item = (32, 32, 3)
    refuted = _static_refutation(stages, item)
    if refuted:
        code, msg = refuted
        print(f"elementwise_chain SKIPPED (statically refuted {code}): "
              f"{msg}", flush=True)
        return
    fused = [stage_fuse(s) for s in fused_stages(stages)]
    statics = tuple(f[0] for f in fused)
    params = [f[1] for f in fused]

    rng = np.random.default_rng(1)
    bodies = _compile_bodies(statics)
    assert bodies is not None, "elementwise trail no longer lowers"
    ops = [prep(p) for (_, prep, _), p in zip(bodies, params)]
    probe = jnp.zeros((8,) + item, jnp.float32)
    b = _elementwise_geometry(bodies, ops, probe)
    assert b > 0, f"gate 1 FAILED: no VMEM block at item {item}"
    print(f"elementwise_chain block chooser at item={item}: b={b}",
          flush=True)

    # gates 1+2: compile at a ragged batch (padded tail block) + numerics
    n_small = 2 * b + 3
    x = jnp.asarray(rng.random((n_small,) + item).astype(np.float32))
    got = np.asarray(elementwise_chain_pallas(
        statics, params, x, interpret=interpret))
    want = np.asarray(elementwise_chain_reference(statics, params, x))
    scale = max(np.abs(want).max(), 1e-12)
    err = np.abs(got - want).max() / scale
    assert err < 2e-3, f"gate 2 FAILED: max rel err {err:.2e}"
    print(f"elementwise_chain gate 1+2 ok: compiled at b={b}, n={n_small}; "
          f"max rel err vs XLA = {err:.2e}", flush=True)

    if timing:
        # 16,384 (32, 32, 3) images are 8.6 GB an array on the chip (3
        # channels padded to 128 lanes): two of them do not fit
        batch = 4096
        xb = jnp.asarray(rng.random((batch,) + item).astype(np.float32))
        _timing_gate("elementwise_chain pallas",
                     lambda xp: elementwise_chain_pallas(statics, params, xp),
                     xb)
        _timing_gate("elementwise_chain xla",
                     lambda xp: elementwise_chain_reference(
                         statics, params, xp),
                     xb)


def check_chain_rectify_pool(interpret=False, timing=True):
    """Chain family 2: rectify→pool→vectorize at the RandomPatchCifar
    conv-output geometry (27×27 positions, k=256 filters, 14/13
    pooling) — the highest-priced KP801 family on that example."""
    import jax.numpy as jnp

    from keystone_tpu.ops.chain_kernels import (
        _rectify_pool_vectorize_block,
        rectify_pool_vectorize_pallas,
        rectify_pool_vectorize_reference,
    )

    h = w = 27
    k, pool, stride, alpha = 256, 14, 13, 0.25
    from keystone_tpu.nodes.images import (
        ImageVectorizer,
        Pooler,
        SymmetricRectifier,
    )

    # the verifier's stage walk merges the rectifier and the pooler
    refuted = _static_refutation(
        [SymmetricRectifier(alpha=alpha), Pooler(stride, pool),
         ImageVectorizer()], (h, w, k))
    if refuted:
        code, msg = refuted
        print(f"rectify_pool_vectorize SKIPPED (statically refuted "
              f"{code}): {msg}", flush=True)
        return
    b = _rectify_pool_vectorize_block(h, w, k, pool, stride)
    assert b > 0, f"gate 1 FAILED: no VMEM block at (h={h}, w={w}, k={k})"
    print(f"rectify_pool_vectorize block chooser at (h={h}, w={w}, k={k}): "
          f"b={b}", flush=True)

    rng = np.random.default_rng(2)
    n_small = 2 * b + 3
    x = jnp.asarray(rng.standard_normal((n_small, h, w, k)).astype(np.float32))
    got = np.asarray(rectify_pool_vectorize_pallas(
        x, alpha, 0.0, pool, stride, interpret=interpret))
    want = np.asarray(rectify_pool_vectorize_reference(
        x, alpha, 0.0, pool, stride))
    scale = max(np.abs(want).max(), 1e-12)
    err = np.abs(got - want).max() / scale
    assert err < 2e-3, f"gate 2 FAILED: max rel err {err:.2e}"
    print(f"rectify_pool_vectorize gate 1+2 ok: compiled at b={b}, "
          f"n={n_small}; max rel err vs XLA = {err:.2e}", flush=True)

    if timing:
        batch = 2048
        xb = jnp.asarray(
            rng.standard_normal((batch, h, w, k)).astype(np.float32))
        _timing_gate("rectify_pool_vectorize pallas",
                     lambda xp: rectify_pool_vectorize_pallas(
                         xp, alpha, 0.0, pool, stride),
                     xb)
        _timing_gate("rectify_pool_vectorize xla",
                     lambda xp: rectify_pool_vectorize_reference(
                         xp, alpha, 0.0, pool, stride),
                     xb)


def check_fused_conv(name, k, pool, stride, batch, reps=120):
    """The fused conv+rectify+pool kernel on 32x32x3 images, 6x6 patches,
    at `k` filters and one pool geometry: the three gates, the timing at
    `batch` images a call against the XLA reference path."""
    import jax.numpy as jnp

    from keystone_tpu.ops import (
        conv_rectify_pool_pallas,
        conv_rectify_pool_reference,
        hwio_to_cmajor,
    )
    from keystone_tpu.ops.pallas_kernels import _fused_conv_plan

    patch, c, h, w, alpha = 6, 3, 32, 32, 0.25
    # the kernel's own layout of the patch rows and its block geometry
    layout, (b, g_img, rows, tk) = _fused_conv_plan(
        h, w, c, k, pool, stride, patch)
    assert b > 0, f"gate 1 FAILED: no VMEM block at k={k}"
    print(f"{name}: k={k} pool {pool} stride {stride}: {layout.posp} patch "
          f"rows an image, "
          + (f"{len(layout.rects)} classes, {layout.presummed_rows} rows "
             f"summed on the vector unit" if layout.presummed_rows
             else "identity layout")
          + f", {layout.dot_rows} rows an image to the pool dot; b={b}, "
          f"{g_img} images and {rows} output rows a loop iteration, "
          f"filter tile {tk}" + (" (the whole bank)" if tk == k else ""),
          flush=True)

    rng = np.random.default_rng(0)
    kern = jnp.asarray(rng.normal(size=(patch, patch, c, k)).astype(np.float32))
    g = hwio_to_cmajor(kern)
    colsum = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))

    # --- gate 1+2: compile at the chosen block and check numerics ------
    n_small = 2 * b + 3  # forces a padded tail block too
    x = jnp.asarray(rng.random((n_small, h, w, c)).astype(np.float32))
    got = np.asarray(conv_rectify_pool_pallas(
        x, g, colsum, bias, alpha, 0.0, pool, stride, True, patch))
    want = np.asarray(conv_rectify_pool_reference(
        x, kern, colsum, bias, alpha, 0.0, pool, stride, True))
    scale = np.abs(want).max()
    err = np.abs(got - want).max() / scale
    assert err < 2e-3, f"gate 2 FAILED: max rel err {err:.2e}"
    print(f"{name} gate 1+2 ok: compiled at b={b}, n={n_small}; "
          f"max rel err vs XLA on-chip = {err:.2e}", flush=True)

    # --- gate 3: differenced chained-rep timing ------------------------
    xb = jnp.asarray(rng.random((batch, h, w, c)).astype(np.float32))
    _timing_gate(f"{name} pallas",
                 lambda xp: conv_rectify_pool_pallas(
                     xp, g, colsum, bias, alpha, 0.0, pool, stride, True,
                     patch),
                 xb, reps)
    _timing_gate(f"{name} xla",
                 lambda xp: conv_rectify_pool_reference(
                     xp, kern, colsum, bias, alpha, 0.0, pool, stride, True),
                 xb, reps)


def main():
    import jax

    interpret = "--interpret" in sys.argv[1:]
    if interpret:
        # CPU smoke of the chain-kernel harness only — not a chip verdict
        check_chain_elementwise(interpret=True, timing=False)
        check_chain_rectify_pool(interpret=True, timing=False)
        print("interpret-mode chain smoke ok (no chip verdict)", flush=True)
        return

    dev = jax.devices()[0]
    print(f"device: {dev} ({dev.platform})", flush=True)

    # RandomPatchCifar's pool (14 stride 13: class-ordered rows) at the
    # port's 256 filters, the whole bank one block. 2,048 images a call:
    # XLA's path passes 2 x f32[n,27,27,256] through HBM, 34 GB at the
    # 16,384 this script once asked for
    check_fused_conv("fused_conv", 256, 14, 13, batch=2048)
    # the benchmark's cell (benchmark/configs/random_patch_cifar.json):
    # the documented 10,000 filters at a microbatch of 32, as filter
    # tiles; per_rep is the kernel alone a microbatch
    check_fused_conv("fused_conv_cell", 10000, 14, 13, batch=32, reps=400)
    # overlapping windows (36 cells an image): ordering would not halve
    # the pool dot, so the rows stay row-major and all go to the dot
    check_fused_conv("fused_conv_identity", 256, 5, 4, batch=2048)

    # --- chain megakernels (ops/chain_kernels.py) ----------------------
    check_chain_elementwise()
    check_chain_rectify_pool()


if __name__ == "__main__":
    main()
