"""Benchmark: RandomPatchCifar featurize+solve throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Baseline: the driver-defined north star is RandomPatchCifar over 50 000
CIFAR images reaching >=84% accuracy in <60 s on a v5e-16 pod, i.e.
833 images/sec across 16 chips (BASELINE.md). vs_baseline compares this
single-chip warm throughput against the full-pod 833 img/s target, so
vs_baseline > 1.0 means one chip alone already beats the whole-pod
reference rate.

One process: the workload runs here, on whatever device jax finds, and
that device must be in `DEVICE_PEAKS`. A run that raises prints its
traceback and exits non-zero with no record; a run whose record carries
an "error" (accuracy out of band, a failed tier) prints it and exits 1.

Uses the learnable synthetic CIFAR task (no dataset egress in this
environment — see BENCH notes); pass --train-path for real CIFAR binaries.
"""

import argparse
import json
import os
import sys
import time

BASELINE_IMGS_PER_SEC = 833.0  # north-star pod rate: 50k imgs / 60 s on v5e-16


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def emit(record):
    print(json.dumps(record), flush=True)


# Checkpoints ranked by completeness: a later-tier partial must never
# lose to an earlier-tier one.
PROGRESS_RANK = {"headline": 1, "staged": 2, "flagship": 3,
                 "featurize_tier": 4, "krr_tier": 5, "overlap_tier": 6,
                 "ooc_tier": 7, "dispatch_tier": 8, "telemetry_tier": 9,
                 "serving_tier": 10, "compile_tier": 11, "complete": 12}

# The tier payload keys a detail may carry. finalize_record's
# error scan is restricted to exactly these: a future informational
# payload that happens to contain an "error" field (e.g. a north_star
# sub-dict) must not silently block persistence.
TIER_KEYS = ("flagship_bcd_d8192", "flagship_featurize", "flagship_krr",
             "featurize_overlap", "out_of_core", "dispatch_count",
             "telemetry_overhead", "serving_qps", "compile_count",
             "fused")


def progress_rank(detail) -> int:
    return PROGRESS_RANK.get(detail.get("progress", "complete"), 0)


def pick_better_partial(best, detail):
    """The detail to keep across attempts: the latest of the
    highest-ranked checkpoints (ties go to the newer attempt)."""
    if best is None or progress_rank(detail) >= progress_rank(best):
        return detail
    return best


def result_record(detail, extra=None):
    imgs_per_sec = detail["images_per_sec"]
    rec = {
        "metric": "cifar_randompatch_train_images_per_sec",
        "value": round(imgs_per_sec, 2),
        "unit": "images/sec (1 chip, warm)",
        "vs_baseline": round(imgs_per_sec / BASELINE_IMGS_PER_SEC, 4),
        "detail": detail,
    }
    if extra:
        rec.update(extra)
    return rec


def finalize_record(detail):
    """Gate a measurement: returns (record, clean).

    An out-of-band accuracy (solver-quality regression on the calibrated
    task) is emitted loudly marked with "error" and is never clean; nor
    is a CPU run. A record whose tier payloads carry {"error": ...}
    (failure-isolated tiers, run_workload) surfaces them top-level — a
    deterministically broken tier must not hide behind a clean exit."""
    rec = result_record(detail)
    if not detail.get("accuracy_in_band", True):
        band = detail.get("accuracy_band") or [None]
        bound = (band[0] if detail.get("synthetic", True)
                 else (detail.get("north_star") or {}).get("target_accuracy"))
        rec["error"] = (
            f"test_accuracy {detail.get('test_accuracy')} below "
            f"{'calibrated lower bound' if detail.get('synthetic', True) else 'north-star target'} "
            f"{bound}")
        return rec, False
    tier_errors = {k: detail[k]["error"] for k in TIER_KEYS
                   if isinstance(detail.get(k), dict)
                   and "error" in detail[k]}
    if tier_errors:
        rec["error"] = "tier failures: " + "; ".join(
            f"{k}: {e}" for k, e in sorted(tier_errors.items()))
        return rec, False
    # precision accuracy band: the mixed-precision policy's outputs must
    # sit inside the declared tolerance band vs the serial unfused f32
    # reference (dispatch_bench's `precision` plan verdict). A policy
    # that busts the band is an accuracy regression, not a perf win —
    # loud error, never a clean record.
    dispatch_tier = detail.get("dispatch_count")
    if isinstance(dispatch_tier, dict) \
            and dispatch_tier.get("precision_in_band") is False:
        rec["error"] = (
            "precision policy busted the declared tolerance band vs the "
            "serial unfused f32 reference (dispatch_count tier "
            "precision_in_band=false)")
        return rec, False
    # decision-ledger verdict: every enforced optimizer decision the
    # measured plans made must appear in the ledger with a prediction
    # the observed program counts agree with (dispatch_count tier's
    # `decisions_reconciled`). A plan the ledger cannot account for is
    # an observability regression, not a perf win.
    if isinstance(dispatch_tier, dict) \
            and dispatch_tier.get("decisions_reconciled") is False:
        rec["error"] = (
            "optimizer decisions and the decision ledger disagree: a "
            "megafused 1-program apply run lacks a matching megafusion "
            "decision record (dispatch_count tier "
            "decisions_reconciled=false)")
        return rec, False
    return rec, detail.get("platform") != "cpu"


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cifar-dir",
                   help="directory with real CIFAR-10 binaries "
                        "(data_batch_*.bin + test_batch.bin); when present "
                        "the bench consumes them and asserts the north star "
                        "(>=84%% accuracy, <60 s train); otherwise it falls "
                        "back to the calibrated synthetic task")
    p.add_argument("--train-path")
    p.add_argument("--test-path")
    p.add_argument("--n-train", type=int, default=50_000)
    p.add_argument("--n-test", type=int, default=10_000)
    p.add_argument("--num-filters", type=int, default=256)
    p.add_argument("--flagship-n", type=int, default=120_000)
    p.add_argument("--flagship-d", type=int, default=8192)
    p.add_argument("--flagship-k", type=int, default=138)
    p.add_argument("--skip-flagship", action="store_true")
    p.add_argument("--featurize-batch", type=int, default=16384)
    p.add_argument("--featurize-reps", type=int, default=120)
    p.add_argument("--skip-featurize-tier", action="store_true")
    p.add_argument("--krr-n", type=int, default=98_304)
    p.add_argument("--krr-d", type=int, default=440)
    p.add_argument("--krr-k", type=int, default=138)
    p.add_argument("--skip-krr", action="store_true")
    p.add_argument("--overlap-n", type=int, default=16_384)
    p.add_argument("--overlap-chunk", type=int, default=2048)
    p.add_argument("--skip-overlap-tier", action="store_true")
    p.add_argument("--skip-ooc-tier", action="store_true")
    p.add_argument("--skip-dispatch-tier", action="store_true")
    p.add_argument("--skip-telemetry-tier", action="store_true")
    p.add_argument("--skip-serving-tier", action="store_true")
    p.add_argument("--skip-compile-tier", action="store_true")
    args = p.parse_args()

    detail = run_workload(args)
    if detail is None:  # unusable --cifar-dir, already reported
        return 2
    rec, _ = finalize_record(detail)
    emit(rec)
    return 1 if "error" in rec else 0


def error_record(error):
    """Zero-value record in the headline metric's shape, for failures."""
    return {
        "metric": "cifar_randompatch_train_images_per_sec",
        "value": 0.0,
        "unit": "images/sec (1 chip, warm)",
        "vs_baseline": 0.0,
        "error": error,
    }


def phase(name, **kw):
    log("phase " + json.dumps({"phase": name, **kw}))


# Calibrated synthetic-task difficulty (see loaders.cifar_loader.
# synthetic_cifar): class templates partially mixed toward confusers +
# heavy pixel noise place the best attainable accuracy in a nontrivial
# band, so solver-quality regressions (centering, BCD convergence,
# precision) FAIL the bench instead of hiding behind a separable task.
# Calibration (CPU mesh, 2026-07): noise=1.2/confusion=0.6 → test acc
# 0.745-0.797 at n=2-3k, rising with n; chance = 0.10. The regression
# gate is ONE-SIDED (accuracy >= lower bound): the upper edge was
# calibrated only at n=2-3k and accuracy legitimately rises with n, so a
# good large-n run must not be stamped an error (ADVICE r3). The upper
# bound stays informational in the record as acc_above_calibrated_band.
BENCH_NOISE = 1.2
BENCH_CONFUSION = 0.6
ACC_BAND = (0.72, 0.96)

# Published peaks of one chip, keyed by jax's `device_kind`: bf16 MXU
# FLOP/s and HBM bytes/s. Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s). A device that is not here is an error,
# never a default: a share of some other chip's peak means nothing.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 1.97e14, "bytes_per_s": 8.19e11},
}


def device_peaks():
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise RuntimeError(
            f"no published peaks for device kind {kind!r} in "
            f"bench.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); this "
            "benchmark measures a chip and does not fall back")
    return DEVICE_PEAKS[kind]


def _ledger_artifact():
    """The decision-ledger JSONL path this run appends to: explicit
    ``KEYSTONE_LEDGER``, else the traced run's default
    ``<trace>.ledger.jsonl`` companion, else None (untraced, unarmed
    runs keep decisions in memory only)."""
    try:
        from keystone_tpu.telemetry import ledger

        return ledger.resolve_ledger_path()
    except Exception:
        return None


def _roofline(flops, bytes_, seconds):
    peaks = device_peaks()
    return {
        "gflops": round(flops / 1e9, 1),
        "gbytes": round(bytes_ / 1e9, 2),
        "attained_tflops": round(flops / seconds / 1e12, 2),
        "attained_gbs": round(bytes_ / seconds / 1e9, 1),
        "pct_peak_flops": round(100 * flops / seconds / peaks["flops"], 1),
        "pct_peak_bw": round(
            100 * bytes_ / seconds / peaks["bytes_per_s"], 1),
        "seconds": round(seconds, 4),
    }


def _flagship_bcd(n, d, k, block, iters):
    """Reference-scale solver metric: multi-block,
    multi-iter BCD at d≥8192 exercising the block loop + tp sharding at
    scale. Mirrors the TIMIT-shaped row of the reference's solver sweep
    (scripts/solver-comparisons-final.csv; BASELINE.md: TIMIT Block
    d=8192 = 580 555 ms on 16x r3.4xlarge at n=2.2e6)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator

    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel import mesh as meshlib

    rng = np.random.default_rng(0)
    # Generate ON DEVICE, directly into the Dataset's sharding: random
    # data (the solve's arithmetic profile is label-independent) via
    # jitted PRNG instead of a ~4 GB host device_put, which is set-up
    # time and nothing else. out_shardings matters: without it the
    # full array would materialize unsharded on one chip before the
    # Dataset reshard (OOM at reference scale on a pod).
    m = meshlib.current_mesh()
    shards = meshlib.n_data_shards(m)
    n = -(-n // shards) * shards  # pad to whole rows per shard
    row_sh = NamedSharding(m, P(meshlib.DATA_AXIS))

    def gen(key, rows, cols):
        sh = meshlib.feature_sharding(m, cols) or row_sh
        f = jax.jit(
            lambda kk: jax.random.normal(kk, (rows, cols), jnp.float32),
            out_shardings=sh,
        )
        return f(key)

    X = gen(jax.random.PRNGKey(0), n, d)
    Y = gen(jax.random.PRNGKey(1), n, k)
    data, labels = Dataset(X), Dataset(Y)
    del X, Y
    est = BlockLeastSquaresEstimator(block_size=block, num_iter=iters, lam=1e-2)

    def fit_once():
        # fresh values each call, so no layer can answer from an earlier
        # execution; the scalar pull fences the perturbation out of the
        # timed window and the post-fit pull is the true sync
        eps = float(rng.random()) * 1e-6
        d2 = data.map_batches(lambda x: x * (1.0 + eps)).sync()
        t0 = time.perf_counter()
        model = est.fit(d2, labels)
        np.asarray(model.W[:1, :1])  # raw array: scalar pull is the sync
        return time.perf_counter() - t0

    fit_once()  # warm/compile
    secs = fit_once()
    B = min(block, d)  # effective block width (solver clamps to d)
    nb = -(-d // B)
    flops = iters * nb * (2.0 * n * B * (B + 2 * k) + (2 / 3) * B**3)
    bytes_ = iters * nb * 4.0 * n * (B + k)
    ref_ms = 580_555.0  # TIMIT Block d=8192 (csv:25), n=2.2e6
    n_scale = n / 2_200_000.0
    return {
        "n": n, "d": d, "k": k, "block_size": block,
        "effective_block": B, "num_iter": iters,
        "fit_seconds": round(secs, 3),
        "scaled_fit_seconds_at_ref_n": round(secs / n_scale, 2),
        "reference_ms_16xr3.4xlarge": ref_ms,
        "speedup_vs_reference_n_scaled": round(
            ref_ms / 1e3 / (secs / n_scale), 1),
        "roofline": _roofline(flops, bytes_, secs),
    }


def _flagship_featurize(batch, reps, num_filters, patch=6):
    """Compute-bound featurize tier: the fused conv+rectify+pool kernel
    chained `reps` times inside ONE XLA program, timed at `reps` and
    `reps//2` and DIFFERENCED — per-execution launch, dispatch and sync
    costs cancel exactly, leaving pure kernel throughput, to set beside
    the headline featurize stage. Matches Convolver.scala:20-221
    economics at the same 32×32×3 shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.ops import conv_rectify_pool

    rng = np.random.default_rng(2)
    kernel = jnp.asarray(
        rng.normal(size=(patch, patch, 3, num_filters)).astype(np.float32) * 0.1)
    colsum = kernel.reshape(-1, num_filters).sum(axis=0)
    bias = jnp.zeros((num_filters,), jnp.float32)
    images = jax.jit(
        lambda k: jax.random.uniform(k, (batch, 32, 32, 3), jnp.float32, 0, 255)
    )(jax.random.PRNGKey(0))

    def chained(r):
        @jax.jit
        def run(x, seed):
            def body(i, acc):
                # acc-dependent input defeats CSE across reps; the
                # perturbation is one fused elementwise op
                xi = x * (1.0 + (seed + acc * 1e-30) * 1e-12)
                pooled = conv_rectify_pool(
                    xi, kernel, colsum, bias, 0.25, 0.0, 14, 13, True)
                return acc + jnp.sum(pooled) * 1e-12

            return jax.lax.fori_loop(0, r, body, jnp.float32(0.0))

        # fresh seed per call: no two executions are byte-identical
        def timed():
            t0 = time.perf_counter()
            out = run(images, float(np.random.default_rng().random()))
            float(out)  # scalar pull = sync
            return time.perf_counter() - t0

        timed()  # warm/compile at this rep count
        return min(timed(), timed())

    t_full = chained(reps)
    t_half = chained(reps // 2)
    per_rep = (t_full - t_half) / (reps - reps // 2)
    pos = (32 - patch + 1) ** 2
    d_patch = patch * patch * 3
    posp, dp = -(-pos // 8) * 8, -(-d_patch // 128) * 128
    flops = 2.0 * batch * pos * d_patch * (num_filters + 1)
    bytes_ = batch * (2.0 * posp * dp * 2 + 32 * 32 * 3 * 4
                      + 8 * num_filters * 4)
    return {
        "batch": batch, "num_filters": num_filters, "reps": reps,
        "seconds_full_chain": round(t_full, 3),
        "seconds_half_chain": round(t_half, 3),
        "per_rep_seconds": round(per_rep, 5),
        "images_per_sec_kernel_only": round(batch / per_rep, 1),
        "method": "differenced chained reps (launch/dispatch cancel)",
        "roofline": _roofline(flops, bytes_, per_rep),
    }


def _flagship_krr(n, d, k, block, epochs=2, gamma=0.01, lam=0.1):
    """KRR flagship row: RBF column-block generation +
    Gauss-Seidel dual BCD at n ≈ 100k — the reference's flagship kernel
    solver (KernelRidgeRegression.scala:37-275, arXiv:1602.05310). The
    per-block structure matches the reference loop exactly: kernel
    col-block gen → residual → local (B×B) solve → model + K·α update;
    here each block is one jitted `_krr_step` whose async dispatches
    pipeline through the host loop (no per-block host sync), where the
    reference paid a treeReduce + driver solve per block."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning import KernelRidgeRegression

    n = -(-n // block) * block

    @jax.jit
    def gen(key):
        kx, ky = jax.random.split(key)
        X = jax.random.normal(kx, (n, d), jnp.float32)
        Y = jax.random.normal(ky, (n, k), jnp.float32)
        return X, Y

    X, Y = gen(jax.random.PRNGKey(3))
    data, labels = Dataset(X), Dataset(Y)
    est = KernelRidgeRegression(
        gamma=gamma, lam=lam, block_size=block, num_epochs=epochs)
    rng = np.random.default_rng()

    def fit_once():
        eps = float(rng.random()) * 1e-6
        d2 = data.map_batches(lambda x: x * (1.0 + eps)).sync()
        t0 = time.perf_counter()
        model = est.fit(d2, labels)
        np.asarray(model.alpha[:1, :1])  # scalar pull = sync
        return time.perf_counter() - t0

    fit_once()  # warm/compile
    secs = min(fit_once(), fit_once())
    blocks = n // block
    # per block: K col-block GEMM (2nBd) + exp epilogue, residual+update
    # GEMM (2nBk), local solve (B³/3), K_bb gather
    flops = epochs * blocks * (
        2.0 * n * block * d + 2.0 * n * block * k + block**3 / 3.0)
    bytes_ = epochs * blocks * (
        2.0 * n * block * 4 + n * d * 4 + n * k * 4 * 2)
    return {
        "n": n, "d": d, "k": k, "block_size": block, "epochs": epochs,
        "blocks_per_epoch": blocks,
        "fit_seconds": round(secs, 3),
        "samples_per_sec": round(n * epochs / secs, 1),
        "roofline": _roofline(flops, bytes_, secs),
        "structure": ("per block: RBF col-block gen -> residual -> "
                      "(BxB) solve -> alpha & K.alpha update "
                      "(KernelRidgeRegression.scala:37-275)"),
    }


def _flagship_overlap(n, chunk, num_filters, patch=6, block=512, iters=2,
                      num_classes=10):
    """Serial-vs-overlapped featurize→solve tier (overlap engine PR):
    the SAME chunked host workload — n host-resident images featurized
    through the fused conv kernel via `map_host_batched`, stacked, then
    BCD-solved — timed once with the overlap engine disabled (stack →
    dispatch → blocking pull per chunk, the pre-change behavior) and
    once enabled (background thread stages/uploads chunk k+1 while the
    device runs chunk k; result pulls deferred and drained in order).
    The paths are numerically identical (asserted in
    tests/test_overlap.py); the delta is pure pipelining of host stack,
    host→device upload, device compute, and device→host pull."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.ops import conv_rectify_pool
    from keystone_tpu.utils import batching
    from keystone_tpu.workflow.env import execution_config, overlap_override

    rng = np.random.default_rng(5)
    items = [rng.uniform(0, 255, size=(32, 32, 3)).astype(np.float32)
             for _ in range(n)]
    labels = Dataset(
        (2.0 * np.eye(num_classes, dtype=np.float32)[
            rng.integers(0, num_classes, size=n)] - 1.0))
    kernel = jnp.asarray(
        rng.normal(size=(patch, patch, 3, num_filters)).astype(np.float32)
        * 0.1)
    colsum = kernel.reshape(-1, num_filters).sum(axis=0)
    bias = jnp.zeros((num_filters,), jnp.float32)

    @jax.jit
    def feat(xb):
        pooled = conv_rectify_pool(
            xb / 255.0, kernel, colsum, bias, 0.25, 0.0, 14, 13, True)
        return pooled.reshape(xb.shape[0], -1)

    est = BlockLeastSquaresEstimator(block_size=block, num_iter=iters,
                                     lam=1e-2)

    class _Fresh:
        """Lazy per-item perturbation: fresh values keep any two
        executions from being byte-identical, and the multiply is
        paid at chunk-STACK time — on the producer thread in the
        overlapped path, inline in the serial path — so it is part of
        the chunked host work the engine must hide, not a constant
        added to both timings outside the dispatcher."""

        __slots__ = ("x", "eps")

        def __init__(self, x, eps):
            self.x = x
            self.eps = eps

        @property
        def shape(self):
            return self.x.shape

        def __array__(self, dtype=None):
            return np.asarray(self.x * self.eps, dtype or np.float32)

    def run_once():
        eps = 1.0 + float(np.random.default_rng().random()) * 1e-6
        t0 = time.perf_counter()
        feats = batching.map_host_batched(
            [_Fresh(x, eps) for x in items], feat, chunk=chunk)
        model = est.fit(Dataset(np.stack(feats)), labels)
        np.asarray(model.W[:1, :1])  # scalar pull = sync
        return time.perf_counter() - t0

    with overlap_override(False):
        run_once()  # warm/compile
        t_serial = min(run_once(), run_once())
    with overlap_override(True):
        run_once()  # warm the producer-thread path
        t_overlap = min(run_once(), run_once())
    return {
        "n": n, "chunk": chunk, "n_chunks": -(-n // chunk),
        "num_filters": num_filters,
        "prefetch_depth": execution_config().prefetch_depth,
        "serial_seconds": round(t_serial, 4),
        "overlapped_seconds": round(t_overlap, 4),
        "speedup": round(t_serial / t_overlap, 3),
        "images_per_sec_serial": round(n / t_serial, 1),
        "images_per_sec_overlapped": round(n / t_overlap, 1),
        "structure": ("map_host_batched(featurize) -> stack -> BCD "
                      "solve; serial = blocking pull per chunk, "
                      "overlapped = double-buffered dispatch + deferred "
                      "in-order drains"),
    }


def _out_of_core_bench(n=81_920, dim=128, k=8, shard_rows=8192,
                       window=1024, lam=1e-3):
    """Out-of-core featurize→solve tier (planner-governed host spill
    PR): a synthetic dataset 8× a synthetic HBM budget streams through
    the windowed spill prefetcher — shards load on demand, each window
    pads onto the PR-5 pow-2 ladder, normal-equation accumulators
    (AᵀA, Aᵀb — tiny) stay device-resident, and the full design matrix
    is NEVER materialized on device. Gates: observed peak device
    residency ≤ the budget during the windowed pass; the windowed
    solution is allclose to the unconstrained (fully materialized) arm
    at window-multiple AND ragged counts with exact index coverage;
    the warm re-run performs 0 cold compiles (every window shape is a
    ladder shape already compiled); and the unified planner, asked to
    plan under a budget the device cache busts, prices the spill
    alternative (feasible) against the device cache (INF) — the
    KEYSTONE_OOC_SPILL=0 arm scores no spill entry and keeps an empty
    spill set. Overlapped-vs-serial reload wall-clock is recorded
    (`overlap_beats_serial`); host-only meshes report it without
    gating — the pipelining win is a device-transfer property."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from keystone_tpu.loaders import synthetic_out_of_core
    from keystone_tpu.telemetry import compiles_snapshot
    from keystone_tpu.telemetry.compile_events import (
        install_compile_listeners,
    )
    from keystone_tpu.utils.batching import stream_spill_windows
    from keystone_tpu.workflow.env import overlap_override
    from keystone_tpu.workflow.executor import drain_warmups

    install_compile_listeners()
    dataset_bytes = n * dim * 4
    budget = dataset_bytes // 8

    rng = np.random.default_rng(17)
    W = jnp.asarray(
        rng.standard_normal((dim, dim)).astype(np.float32) * 0.05)
    theta = jnp.asarray(rng.standard_normal((dim, k)).astype(np.float32))
    eye = jnp.eye(dim, dtype=jnp.float32)

    @jax.jit
    def accum(ata, atb, xb):
        f = jnp.maximum(xb @ W, 0.0)
        # zero pad rows featurize to zero rows: they add nothing to
        # either accumulator, so padded windows need no masking
        return ata + f.T @ f, atb + f.T @ (xb @ theta)

    @jax.jit
    def solve(ata, atb):
        return jnp.linalg.solve(ata + lam * eye, atb)

    def solve_windowed(source, count, track_peak=False):
        ata = jnp.zeros((dim, dim), jnp.float32)
        atb = jnp.zeros((dim, k), jnp.float32)
        seen = []
        peak = 0
        for idxs, win in stream_spill_windows(source.row_loader, count,
                                              window=window):
            ata, atb = accum(ata, atb, win)
            seen.extend(idxs)
            if track_peak:
                ata.block_until_ready()
                live = sum(int(a.nbytes) for a in jax.live_arrays())
                peak = max(peak, live)
        out = solve(ata, atb)
        return np.asarray(out), seen, peak

    def solve_resident(source, count):
        x = jnp.asarray(source.numpy())
        f = jnp.maximum(x @ W, 0.0)
        out = jnp.linalg.solve(f.T @ f + lam * eye, f.T @ (x @ theta))
        return np.asarray(out)

    # --- the big out-of-core pass: 8× the budget, windowed, gated
    big = synthetic_out_of_core(n, dim, shard_rows=shard_rows, seed=17)
    with overlap_override(True):
        theta_big, seen, _ = solve_windowed(big, n)  # cold/compile
        drain_warmups()
        before = compiles_snapshot()
        t0 = time.perf_counter()
        theta_big, seen, peak = solve_windowed(big, n, track_peak=True)
        t_warm = time.perf_counter() - t0
        drain_warmups()
        after = compiles_snapshot()
    warm_cold_compiles = (after["programs_compiled"]
                          - before["programs_compiled"])
    coverage_ok = (sorted(seen) == list(range(n)))

    # --- serial vs overlapped reload wall-clock (same windowed pass)
    with overlap_override(False):
        solve_windowed(big, n)  # warm the serial path
        t_serial = min(
            _timed(lambda: solve_windowed(big, n)) for _ in range(2))
    with overlap_override(True):
        t_overlap = min(
            _timed(lambda: solve_windowed(big, n)) for _ in range(2))

    # --- allclose vs the unconstrained arm at multiple AND ragged
    # counts (small enough to materialize honestly)
    allclose = {}
    for count in (4 * window, 4 * window + 1, 3 * window - 413):
        src = synthetic_out_of_core(count, dim, shard_rows=4096,
                                    seed=29 + count)
        got, idxs, _ = solve_windowed(src, count)
        want = solve_resident(src, count)
        allclose[str(count)] = bool(
            sorted(idxs) == list(range(count))
            and np.allclose(got, want, rtol=2e-4, atol=2e-4))

    # --- the planner's spill axis: under a budget the device cache
    # busts, the spill placement prices feasible where device prices
    # INF; with the axis off nothing spills (the kill-switch shape)
    planner = _ooc_planner_probe()

    problems = []
    if peak > budget:
        problems.append(
            f"windowed pass peak device residency {peak} bytes exceeds "
            f"the {budget}-byte budget (dataset {dataset_bytes} bytes)")
    if not coverage_ok:
        problems.append("windowed index coverage != range(n)")
    if warm_cold_compiles:
        problems.append(
            f"warm windowed re-run performed {warm_cold_compiles} cold "
            "compile(s)")
    if not all(allclose.values()):
        problems.append(f"windowed vs resident allclose failed: "
                        f"{allclose}")
    if planner.get("error"):
        problems.append(planner["error"])
    res = {
        "n": n, "dim": dim, "k": k, "window": window,
        "shard_rows": shard_rows,
        "dataset_bytes": dataset_bytes,
        "hbm_budget_bytes": budget,
        "dataset_over_budget": round(dataset_bytes / budget, 2),
        "peak_device_bytes": int(peak),
        "peak_under_budget": bool(peak <= budget),
        "warm_seconds": round(t_warm, 4),
        "warm_cold_compiles": int(warm_cold_compiles),
        "rows_per_sec_warm": round(n / t_warm, 1),
        "serial_seconds": round(t_serial, 4),
        "overlapped_seconds": round(t_overlap, 4),
        "overlap_speedup": round(t_serial / t_overlap, 3),
        "overlap_beats_serial": bool(t_overlap < t_serial),
        "allclose_vs_resident": allclose,
        "planner": planner,
        "structure": ("synthetic_out_of_core shards -> "
                      "stream_spill_windows (pad ladder, double-buffered"
                      " host->device reload) -> jit normal-equation "
                      "accumulate -> device solve; design matrix never "
                      "device-materialized"),
    }
    if problems:
        res["error"] = "; ".join(problems)
    return res


def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _ooc_planner_probe():
    """Pure spec arithmetic: ask the unified planner for a plan whose
    only way to keep a demanded-twice value is the host spill tier, and
    check the ledger-bound menu prices BOTH placements — device cache
    INF (busts the budget), host spill feasible — while the
    KEYSTONE_OOC_SPILL=0 arm scores no spill entry at all."""
    from keystone_tpu.analysis import as_source_spec
    from keystone_tpu.analysis.examples import build_example
    from keystone_tpu.analysis.plan_ir import plan_unified
    from keystone_tpu.analysis.propagate import spec_pass

    pipeline, source_spec = build_example("MnistRandomFFT")
    specs, _ = spec_pass(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
    budget = 32 << 10
    on = plan_unified(pipeline.graph, specs, hbm_budget_bytes=budget,
                      allow_spill=True, include_boundary_policies=False)
    off = plan_unified(pipeline.graph, specs, hbm_budget_bytes=budget,
                       allow_spill=False, include_boundary_policies=False)
    spill_entries = [c for c in (on.scored_candidates if on else [])
                     if str(c.get("entry", "")).startswith("spill_")]
    off_spill_entries = [c for c in (off.scored_candidates if off else [])
                        if str(c.get("entry", "")).startswith("spill_")]
    out = {
        "budget_bytes": budget,
        "spill_alternatives_scored": len(spill_entries),
        "spill_alternatives_feasible": sum(
            1 for c in spill_entries if c.get("feasible")),
        "chosen_spills": len(getattr(on.chosen, "spills", ()) if on
                             else ()),
        "kill_switch_spill_entries": len(off_spill_entries),
        "kill_switch_chosen_spills": len(
            getattr(off.chosen, "spills", ()) if off else ()),
    }
    if not spill_entries:
        out["error"] = ("planner scored no spill alternatives under a "
                        "cache-busting budget")
    elif off_spill_entries or out["kill_switch_chosen_spills"]:
        out["error"] = ("KEYSTONE_OOC_SPILL=0 arm still scored or chose "
                        "spill placements")
    return out


def _telemetry_overhead(name="MnistRandomFFT", batch=64, reps=30):
    """Live-telemetry-plane overhead tier (ISSUE 18): warm
    `FittedPipeline.apply` wall with the plane ARMED — flight-ring span
    tee + streaming latency sketches + a conformance watchdog holding a
    generous bound (the tier prices instrumentation, not breach
    handling) — vs DISARMED (``live_telemetry=False``, the kill-switch
    fast path), median of ``reps`` warm applies per side at a serving
    batch size. The plane's standing budget is <5% of the warm serving
    path; ``overhead_in_budget`` is the verdict finalize_record can
    gate on. The two sides are interleaved request-by-request so host
    load/thermal drift cancels out of the comparison."""
    import statistics

    import numpy as np

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.dispatch_bench import EXAMPLES
    from keystone_tpu.telemetry.flight import (
        ensure_flight,
        flight_recorder,
        reset_flight,
    )
    from keystone_tpu.telemetry.streaming import reset_live
    from keystone_tpu.telemetry.watchdog import (
        arm_watchdog,
        disarm_watchdog,
    )
    from keystone_tpu.workflow import PipelineEnv
    from keystone_tpu.workflow.env import config_override

    PipelineEnv.reset()
    predictor, train, test = EXAMPLES[name]()
    fitted = predictor.fit()
    X = np.concatenate([np.asarray(test.numpy()),
                        np.asarray(train.numpy())])

    def make_batch(i):
        off = (i * batch) % max(1, len(X) - batch)
        return Dataset.from_numpy(np.ascontiguousarray(X[off:off + batch]))

    def apply_once(i):
        t0 = time.perf_counter()
        np.asarray(fitted.apply(make_batch(i)).numpy())
        return time.perf_counter() - t0

    disarm_watchdog()
    reset_live()
    reset_flight()
    ensure_flight()
    # a bound no warm apply can breach: every request is checked and
    # teed, none takes the breach slow path (dump + ledger write)
    arm_watchdog({
        "slo_seconds": 3600.0,
        "certified": True,
        "shapes": [{"batch": 1 << 20, "predicted_seconds": 3600.0}],
    }, pipeline=name)
    try:
        # warm both paths, then INTERLEAVE the sides: back-to-back
        # pairs share whatever load/thermal drift the host is under, so
        # the medians difference out everything except the plane itself
        with config_override(live_telemetry=False):
            apply_once(0)
        apply_once(1)
        off_s, on_s = [], []
        for i in range(reps):
            with config_override(live_telemetry=False):
                off_s.append(apply_once(2 + 2 * i))
            on_s.append(apply_once(3 + 2 * i))
        t_disarmed = statistics.median(off_s)
        t_armed = statistics.median(on_s)
        # the plane's true cost is microseconds against a noisy
        # multi-ms apply wall (per-apply warm-thread spawn, lock
        # scheduling): the median of PAIRWISE deltas differences that
        # noise out pair by pair, where a ratio of independent medians
        # would flap by far more than the 5% budget
        delta = statistics.median(b - a for a, b in zip(off_s, on_s))
        rec = flight_recorder()
        spans_held = len(rec.spans) if rec is not None else 0
    finally:
        disarm_watchdog()
        reset_live()
        reset_flight()
    overhead = delta / t_disarmed if t_disarmed > 0 else 0.0
    return {
        "example": name, "batch": batch, "reps": reps,
        "disarmed_seconds": round(t_disarmed, 5),
        "armed_seconds": round(t_armed, 5),
        "seconds": round(t_armed, 5),
        "overhead_seconds": round(delta, 6),
        "overhead_pct": round(100.0 * overhead, 2),
        "overhead_in_budget": bool(overhead < 0.05),
        "flight_spans_held": spans_held,
        "method": ("interleaved warm applies, disarmed "
                   "(live_telemetry=False) vs armed (flight tee + "
                   "sketches + non-breaching watchdog); overhead = "
                   "median pairwise delta"),
    }


def _serving_qps_example(name, build, reps, clients, offered_qps,
                         max_batch, slo_ms, speedup_floor):
    """One example through the serving_qps tier: sustained concurrent
    load at a fixed offered QPS through the REAL certified runtime
    (`serving.ServingRuntime`), coalesced vs kill-switch
    (``serving_coalesce=False`` — per-request dispatch) in the SAME
    process, same payloads, same offered load. The SLO gate IS the
    certificate: every ladder shape the coalesced run dispatches must
    hold observed p99 ≤ its certified KP903 bound, with 0 cold compiles
    and 0 watchdog breaches inside the measured window; the kill-switch
    side must reproduce per-request dispatch bit-for-bit against direct
    `FittedPipeline.apply`, and coalescing must sustain ≥
    ``speedup_floor``× its attained throughput."""
    import threading

    import numpy as np

    from keystone_tpu.analysis.serving import ServingEnvelope
    from keystone_tpu.telemetry.metrics import (
        histogram,
        metrics_delta,
        registry,
    )
    from keystone_tpu.telemetry.streaming import latency_sketch, reset_live
    from keystone_tpu.telemetry.watchdog import (
        active_watchdog,
        arm_watchdog,
        disarm_watchdog,
    )
    from keystone_tpu.workflow import PipelineEnv
    from keystone_tpu.workflow.env import config_override

    PipelineEnv.reset()
    disarm_watchdog()
    reset_live()
    registry().histograms.pop("serving.coalesced_batch", None)
    envelope = ServingEnvelope(max_batch=max_batch,
                               slo_seconds=slo_ms / 1e3)
    make_runtime, payloads, reference = build(envelope)
    total = clients * reps

    def fire(rt, results):
        """Open-loop paced load: request k is scheduled at t0 +
        k/offered_qps; a client behind schedule fires immediately
        (offered load never degrades to the server's pace). Returns
        (wall_seconds, errors)."""
        errors = []
        t0 = time.perf_counter()

        def client(cid):
            for i in range(reps):
                k = cid + clients * i
                due = t0 + k / offered_qps
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                try:
                    results[k] = rt.submit(payloads[k % len(payloads)])
                except Exception as e:
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, errors

    problems = []
    res = {"example": name, "clients": clients, "requests": total,
           "offered_qps": offered_qps, "max_batch": max_batch,
           "slo_ms": slo_ms}

    # ---- coalesced side: certified runtime, micro-batching on
    rt = make_runtime().start()
    try:
        res["ladder"] = rt.stats()["ladder"]
        bounds = {int(s["batch"]): float(s["predicted_seconds"])
                  for s in rt.certificate.shapes}
        # prime every ladder-adjacent code path, then open a FRESH
        # measured window: zeroed sketches and watchdog counters, so
        # the gates judge steady-state serving, not ramp-up
        prime: dict = {}
        fire(rt, prime)
        reset_live()
        arm_watchdog(rt.certificate.as_record(), pipeline="fitted_pipeline")
        registry().histograms.pop("serving.coalesced_batch", None)
        rt._batcher._coalesced = histogram("serving.coalesced_batch")
        coalesced: dict = {}
        with metrics_delta() as delta:
            wall, errors = fire(rt, coalesced)
        if errors:
            problems.append(f"coalesced run errors: {errors[:3]}")
        cold = delta.counter("dispatch.programs_compiled")
        if cold:
            problems.append(
                f"{int(cold)} cold compile(s) inside the warm measured "
                "window (the certificate promises 0)")
        wd = active_watchdog()
        digest = wd.describe() if wd is not None else {}
        if digest.get("breaches", 0):
            problems.append(
                f"{digest['breaches']} conformance breach(es) in the "
                "measured window")
        stats = rt.stats()
        if stats["dispatched_outside_ladder"]:
            problems.append("dispatched shapes outside the certified "
                            f"ladder: {stats['dispatched_outside_ladder']}")
        shapes = []
        for shape in stats["dispatched_shapes"]:
            sk = latency_sketch("fitted_pipeline", int(shape))
            if sk is None or sk.count == 0:
                continue
            bound = bounds.get(int(shape))
            if bound is None:
                covering = [b for b in bounds if b >= int(shape)]
                bound = bounds[min(covering)] if covering else None
            p99 = sk.quantile(0.99)
            holds = bound is not None and p99 <= bound
            if not holds:
                problems.append(
                    f"shape {int(shape)}: observed p99 "
                    f"{p99 * 1e3:.2f}ms over the certified KP903 bound "
                    f"{(bound or 0) * 1e3:.2f}ms")
            shapes.append({
                "chunk_shape": int(shape),
                "p50_ms": round(sk.quantile(0.50) * 1e3, 3),
                "p99_ms": round(p99 * 1e3, 3),
                "reps": int(sk.count),
                "bound_ms": (round(bound * 1e3, 3)
                             if bound is not None else None),
                "holds": bool(holds),
            })
        hist = registry().histograms.get("serving.coalesced_batch")
        res.update({
            "coalesced_wall_seconds": round(wall, 3),
            "coalesced_rps": round(total / wall, 1),
            "dispatches": int(delta.counter("serving.dispatches")),
            "shed": int(delta.counter("serving.shed_total")),
            "cold_compiles": int(cold),
            "watchdog": {"checked": digest.get("checked", 0),
                         "breaches": digest.get("breaches", 0)},
            "shapes": shapes,
            "coalesced_batch": hist.snapshot() if hist else None,
        })
    finally:
        rt.stop()

    # ---- kill-switch side: per-request dispatch, same offered load
    reset_live()
    with config_override(serving_coalesce=False):
        rt2 = make_runtime().start()
        try:
            perreq: dict = {}
            wall2, errors2 = fire(rt2, perreq)
            if errors2:
                problems.append(f"kill-switch run errors: {errors2[:3]}")
            if rt2._batcher._thread is not None:
                problems.append("kill switch did not disable the "
                                "dispatcher thread")
        finally:
            rt2.stop()
    res.update({
        "killswitch_wall_seconds": round(wall2, 3),
        "killswitch_rps": round(total / wall2, 1),
    })

    # bit-for-bit: the kill switch IS per-request dispatch — its rows
    # must equal direct FittedPipeline.apply on the same payloads
    mismatched = sum(
        1 for k in sorted(perreq)[:64]
        if not np.array_equal(np.asarray(perreq[k]),
                              np.asarray(reference(payloads[k % len(payloads)]))))
    if mismatched:
        problems.append(f"kill-switch output diverged from direct "
                        f"per-request apply on {mismatched} request(s)")
    res["killswitch_bit_for_bit"] = mismatched == 0
    # coalesced rows must agree with the per-request rows numerically
    drifted = sum(
        1 for k in sorted(coalesced)[:256]
        if k in perreq and not np.allclose(
            np.asarray(coalesced[k]), np.asarray(perreq[k]),
            rtol=1e-5, atol=1e-5))
    if drifted:
        problems.append(f"coalesced rows drifted from per-request rows "
                        f"on {drifted} request(s)")

    speedup = (res["coalesced_rps"] / res["killswitch_rps"]
               if res["killswitch_rps"] else 0.0)
    res["speedup"] = round(speedup, 2)
    res["speedup_floor"] = speedup_floor
    if speedup < speedup_floor:
        problems.append(
            f"coalesced throughput {res['coalesced_rps']} rps is only "
            f"{speedup:.2f}x the per-request baseline "
            f"{res['killswitch_rps']} rps (floor {speedup_floor}x)")
    if problems:
        res["error"] = "; ".join(problems)
    reset_live()
    disarm_watchdog()
    PipelineEnv.reset()
    return res


def _serving_qps(clients=16, reps=50, slo_ms=1000.0):
    """The serving_qps tier: the certified serving runtime under
    sustained concurrent load, coalesced vs kill-switch, for the two
    covered modalities — MnistRandomFFT (ndarray ingress, pure device
    tail) and Newsgroups (text ingress: fitted host front-end runs per
    request on the client thread, the device tail serves behind the
    certificate)."""
    import numpy as np

    from keystone_tpu.data.dataset import Dataset

    def mnist_build(envelope):
        from keystone_tpu.dispatch_bench import EXAMPLES
        from keystone_tpu.serving import NdarrayIngress, ServingRuntime

        predictor, train, test = EXAMPLES["MnistRandomFFT"]()
        fitted = predictor.fit()
        X = np.concatenate([np.asarray(test.numpy()),
                            np.asarray(train.numpy())])
        payloads = [np.ascontiguousarray(X[i]) for i in range(len(X))]

        def make_runtime():
            return ServingRuntime(fitted, NdarrayIngress(X.shape[1:]),
                                  envelope=envelope, name="MnistRandomFFT")

        def reference(p):
            out = fitted.apply(Dataset.from_numpy(p[np.newaxis]))
            return np.asarray(out.numpy())[0]

        return make_runtime, payloads, reference

    def newsgroups_build(envelope):
        from keystone_tpu.pipelines.text_pipelines import (
            build_newsgroups_predictor,
            synthetic_corpus,
        )
        from keystone_tpu.serving import (
            NdarrayIngress,
            ServingRuntime,
            TextIngress,
            split_fitted_at,
        )

        labels, docs = synthetic_corpus(600, 4, seed=0)
        fitted = build_newsgroups_predictor(docs, labels, 4).fit()
        host_ops, tail = split_fitted_at(fitted, "NaiveBayesModel")
        ingress = TextIngress(host_ops)
        # Pre-featurize the payload pool: the host text front-end runs
        # per-request on the caller's thread IDENTICALLY in both modes,
        # so leaving it in the measured loop only dilutes the
        # coalescing delta this gate exists to measure. The live
        # TextIngress request path is covered by test_serving_runtime
        # and `scripts/serving_latency.py --runtime`; here the tier
        # drives the certified device tail directly.
        payloads = [ingress.accept(d) for d in list(docs.items)[:256]]
        element = payloads[0].shape

        def make_runtime():
            return ServingRuntime(tail, NdarrayIngress(element),
                                  envelope=envelope,
                                  name="NewsgroupsPipeline")

        def reference(row):
            out = tail.apply(Dataset.from_numpy(row[np.newaxis]))
            return np.asarray(out.numpy()
                              if hasattr(out, "numpy") else out)[0]

        return make_runtime, payloads, reference

    t0 = time.perf_counter()
    examples = {
        "MnistRandomFFT": _serving_qps_example(
            "MnistRandomFFT", mnist_build, reps=reps, clients=clients,
            offered_qps=4000.0, max_batch=16, slo_ms=slo_ms,
            speedup_floor=4.0),
        "NewsgroupsPipeline": _serving_qps_example(
            "NewsgroupsPipeline", newsgroups_build, reps=reps,
            clients=clients, offered_qps=4000.0, max_batch=16,
            slo_ms=slo_ms, speedup_floor=4.0),
    }
    rec = {"examples": examples,
           "seconds": round(time.perf_counter() - t0, 2)}
    errors = [f"{n}: {e['error']}" for n, e in examples.items()
              if e.get("error")]
    if errors:
        rec["error"] = "; ".join(errors)
    return rec


def run_workload(args):
    """The measured workload, in this process. Logs phase markers to
    stderr and returns the complete detail dict (None for an unusable
    --cifar-dir)."""
    phase("import")
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
        run_fused,
        run_staged,
    )
    from keystone_tpu.loaders.cifar_loader import cifar_loader, synthetic_cifar
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.workflow import PipelineEnv
    import jax

    phase("devices", platform=jax.devices()[0].platform,
          kind=jax.devices()[0].device_kind, n=len(jax.devices()))
    device_peaks()  # an unknown device fails here, before any work

    config = RandomPatchCifarConfig(num_filters=args.num_filters)
    train_path, test_path = args.train_path, args.test_path
    north_star_gate = False  # the >=84% gate is calibrated for FULL
    # CIFAR-10 via --cifar-dir; arbitrary --train-path data keeps the
    # old always-pass behavior (no calibrated target exists for it)
    if args.cifar_dir:
        cdir = os.path.abspath(args.cifar_dir)
        batches = sorted(
            f for f in os.listdir(cdir)
            if f.startswith("data_batch") and f.endswith(".bin")
        ) if os.path.isdir(cdir) else []
        tb = os.path.join(cdir, "test_batch.bin")
        if batches and os.path.exists(tb):
            # standard CIFAR-10 binary layout (CifarLoader.scala:13-52):
            # the loader handles a directory of *.bin; point train at
            # the data batches and test at the held-out batch
            train_path = (os.path.join(cdir, batches[0])
                          if len(batches) == 1 else cdir)
            if len(batches) > 1:
                # directory mode globs every .bin incl. test_batch; stage
                # train batches alone via a temp dir of symlinks
                import atexit
                import shutil
                import tempfile

                tdir = tempfile.mkdtemp(prefix="cifar_train_")
                atexit.register(shutil.rmtree, tdir, ignore_errors=True)
                for f in batches:
                    os.symlink(os.path.join(cdir, f), os.path.join(tdir, f))
                train_path = tdir
            test_path = tb
            north_star_gate = True
        else:
            # LOUD: a typo'd/empty --cifar-dir must not silently report
            # calibrated-band success on the synthetic task
            print(f"BENCH ERROR: --cifar-dir {args.cifar_dir!r} has no "
                  "data_batch_*.bin + test_batch.bin; refusing to fall "
                  "back silently", file=sys.stderr, flush=True)
            emit(error_record(f"--cifar-dir {args.cifar_dir!r} unusable: "
                              "no data_batch_*.bin + test_batch.bin"))
            return None
    if train_path:
        train = cifar_loader(train_path)
        test = cifar_loader(test_path or train_path)
        synthetic = False
    else:
        train, test = synthetic_cifar(
            args.n_train, args.n_test,
            noise=BENCH_NOISE, confusion=BENCH_CONFUSION,
        )
        synthetic = True
    phase("data", n_train=train.data.count, n_test=test.data.count,
          synthetic=synthetic)

    # Warm-up at the SAME shapes (jit caches are shape-keyed, and the
    # fused-program cache is global/structural): one untimed staged pass
    # + one untimed pipeline pass compile every program both timed paths
    # use, so the measurements reflect steady-state TPU throughput.
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    run_staged(train, config, evaluator)
    PipelineEnv.reset()
    warm_pipe = build_pipeline(train, config)
    evaluator(warm_pipe(train.data), train.labels)
    phase("warm_done")

    # Headline: the real pipeline path end-to-end, async dispatch free to
    # overlap stages (what a user's run costs).
    PipelineEnv.reset()
    t0 = time.perf_counter()
    predictor = build_pipeline(train, config)
    train_metrics = evaluator(predictor(train.data), train.labels)
    elapsed = time.perf_counter() - t0
    phase("timed_done", seconds=round(elapsed, 3))
    test_metrics = evaluator(predictor(test.data), test.labels)

    acc = test_metrics.accuracy
    north_star = None
    if synthetic:
        in_band = acc >= ACC_BAND[0]
    elif not north_star_gate:
        in_band = True  # ad-hoc --train-path data: no calibrated target
    else:
        # real CIFAR present: the driver-defined north star becomes the
        # gate — >=84% test accuracy, <60 s train (BASELINE.md; the 60 s
        # target is the v5e-16 pod budget, so single-chip time is
        # recorded against it but only accuracy fails the record)
        north_star = {
            "target_accuracy": 0.84,
            "target_seconds_v5e16": 60.0,
            "accuracy_ok": bool(acc >= 0.84),
            "train_seconds_single_chip": round(elapsed, 3),
            "time_ok_single_chip": bool(elapsed < 60.0),
        }
        in_band = north_star["accuracy_ok"]
    detail = {
        "progress": "headline",
        "n_train": train.data.count,
        "train_seconds": round(elapsed, 3),
        "images_per_sec": round(train.data.count / elapsed, 2),
        "train_error": round(train_metrics.error, 4),
        "test_accuracy": round(acc, 4),
        "accuracy_band": list(ACC_BAND) if synthetic else None,
        "north_star": north_star,
        "accuracy_in_band": in_band,
        "acc_above_calibrated_band": bool(synthetic and acc > ACC_BAND[1]),
        "task_difficulty": {"noise": BENCH_NOISE, "confusion": BENCH_CONFUSION},
        "num_filters": config.num_filters,
        "synthetic": synthetic,
        "platform": jax.devices()[0].platform,
        "data_note": (None if not synthetic else
                      "real CIFAR-10 binaries are not obtainable in this "
                      "zero-egress environment; synthetic learnable task at "
                      "identical shapes/scale with CALIBRATED difficulty "
                      "(see BENCH notes in README)"),
        # With KEYSTONE_TRACE set the process's ambient tracer writes a
        # Chrome trace (all tiers' spans: node forces, stream chunks,
        # solver iterations, queue stalls) at exit; the record carries
        # the path so BENCH rounds keep span-level detail
        # (`scripts/perf_table.py --trace <path>` to render).
        "trace_artifact": os.environ.get("KEYSTONE_TRACE") or None,
        # The decision ledger the same run appends (KEYSTONE_LEDGER, or
        # derived alongside the trace artifact): every optimizer
        # decision the tiers enforced, with predicted costs —
        # `python -m keystone_tpu.telemetry --ledger <path>` renders it,
        # `--diff` compares two rounds' ledgers.
        "ledger_artifact": _ledger_artifact(),
    }
    log(f"checkpoint: {detail['progress']}")

    # Stage breakdown: same components, scalar-pull sync after each
    # stage, so the stages SUM to the staged end-to-end by construction
    # (no unaccounted time).
    PipelineEnv.reset()
    stages, _, _ = run_staged(train, config, evaluator)
    staged_total = sum(stages.values())
    phase("staged_done", seconds=round(staged_total, 3))

    # Per-stage roofline vs v5e peaks (featurize/solve dominate; the
    # fused conv kernel's HBM traffic is patches bf16 write+read +
    # images read + pooled write).
    n = train.data.count
    F, p = config.num_filters, config.patch_size
    pos = (32 - p + 1) ** 2
    d_patch = p * p * 3
    posp, dp = -(-pos // 8) * 8, -(-d_patch // 128) * 128
    d = 8 * F
    k = config.num_classes
    B = min(config.block_size, d)
    conv_flops = 2.0 * n * pos * d_patch * (F + 1)
    conv_bytes = n * (2.0 * posp * dp * 2 + 32 * 32 * 3 * 4 + 8 * F * 4)
    scaler_bytes = 3.0 * n * d * 4
    solve_flops = 2.0 * n * d * B + (2.0 / 3.0) * B**3 + 6.0 * n * d * k
    solve_bytes = 3.0 * n * d * 4
    pred_flops = 2.0 * n * d * k
    rooflines = {
        "featurize": _roofline(conv_flops, conv_bytes, stages["featurize"]),
        "scaler": _roofline(n * d * 4.0, scaler_bytes, stages["scaler"]),
        "bcd_solve": _roofline(solve_flops, solve_bytes, stages["bcd_solve"]),
        "predict_eval": _roofline(pred_flops, n * d * 4.0,
                                  stages["predict_eval"]),
    }

    total_flops = conv_flops + solve_flops
    detail.update({
        "progress": "staged",
        "stages_seconds": {kk: round(vv, 4) for kk, vv in stages.items()},
        "stages_sum_seconds": round(staged_total, 3),
        "rooflines": rooflines,
        "analytic_tflops": round(total_flops / 1e12, 2),
        "mfu_vs_v5e_peak": round(
            total_flops / elapsed / device_peaks()["flops"], 4),
    })
    log(f"checkpoint: {detail['progress']}")

    def run_tier(key, start_phase, done_phase, seconds_key, fn):
        """Failure-isolated tier: a tier that raises records
        {"error": ...} instead of ending the run and losing every
        later tier's measurement (finalize_record surfaces tier errors
        top-level, and main() then exits non-zero). ``key`` is the
        detail key the caller will store the result under; it MUST be
        registered in TIER_KEYS or the error gate would silently skip
        it — fail loudly here instead of persisting a broken record."""
        assert key in TIER_KEYS, (
            f"tier detail key {key!r} is not in bench.TIER_KEYS; "
            "finalize_record would ignore its errors — register it")
        phase(start_phase)
        try:
            res = fn()
        except Exception as e:
            res = {"error": f"{type(e).__name__}: {e}"}
        phase(done_phase, seconds=res.get(seconds_key, "error"))
        return res

    def flagship_fn():
        res = _flagship_bcd(
            n=args.flagship_n, d=args.flagship_d, k=args.flagship_k,
            block=4096, iters=3,
        )
        # honest f32 ceiling: the solver pins HIGHEST matmul precision
        # (6-pass bf16x3 on the MXU, ≈ peak/6), so percent-of-bf16-peak
        # understates MXU occupancy by that factor for the Gram GEMMs
        r = res["roofline"]
        r["pct_peak_flops_f32_highest"] = round(
            100 * r["attained_tflops"] * 1e12
            / (device_peaks()["flops"] / 6.0), 1)
        return res

    flagship = None
    if not args.skip_flagship:
        flagship = run_tier("flagship_bcd_d8192", "flagship_solver",
                            "flagship_done", "fit_seconds", flagship_fn)
    detail.update({"progress": "flagship", "flagship_bcd_d8192": flagship})
    log(f"checkpoint: {detail['progress']}")

    feat_tier = None
    if not args.skip_featurize_tier:
        feat_tier = run_tier(
            "flagship_featurize", "featurize_tier",
            "featurize_tier_done", "per_rep_seconds",
            lambda: _flagship_featurize(
                batch=args.featurize_batch, reps=args.featurize_reps,
                num_filters=config.num_filters))
    detail.update({"progress": "featurize_tier",
                   "flagship_featurize": feat_tier})
    log(f"checkpoint: {detail['progress']}")

    krr = None
    if not args.skip_krr:
        krr = run_tier(
            "flagship_krr", "krr_solver", "krr_done", "fit_seconds",
            lambda: _flagship_krr(
                n=args.krr_n, d=args.krr_d, k=args.krr_k, block=4096))
    detail.update({"progress": "krr_tier", "flagship_krr": krr})
    log(f"checkpoint: {detail['progress']}")

    overlap = None
    if not args.skip_overlap_tier:
        overlap = run_tier(
            "featurize_overlap", "overlap_tier", "overlap_done",
            "overlapped_seconds",
            lambda: _flagship_overlap(
                n=args.overlap_n, chunk=args.overlap_chunk,
                num_filters=config.num_filters))
    detail.update({"progress": "overlap_tier",
                   "featurize_overlap": overlap})
    log(f"checkpoint: {detail['progress']}")

    # Out-of-core tier: featurize→solve over a synthetic dataset 8× a
    # synthetic HBM budget through the windowed spill prefetcher —
    # peak device residency gated under the budget, windowed solution
    # allclose to the materialized arm at multiple AND ragged counts,
    # warm re-run at 0 cold compiles, and the unified planner pricing
    # the host-spill placement against the INF device cache.
    ooc_tier = None
    if not args.skip_ooc_tier:
        ooc_tier = run_tier(
            "out_of_core", "ooc_tier", "ooc_tier_done", "warm_seconds",
            _out_of_core_bench)
    detail.update({"progress": "ooc_tier", "out_of_core": ooc_tier})
    log(f"checkpoint: {detail['progress']}")

    # Dispatch-count tier: programs-per-run for the example pipelines
    # under serial-unfused / PR-3-legacy / optimized plans.
    # Platform-independent — the counts are a property of the
    # optimizer plan, so CPU and TPU runs record the same numbers.
    def dispatch_fn():
        import time as _t

        from keystone_tpu.dispatch_bench import dispatch_count_report

        t0 = _t.perf_counter()
        rep = dispatch_count_report()
        rep["seconds"] = round(_t.perf_counter() - t0, 2)
        problems = []
        if not rep["all_outputs_match"]:
            problems.append("optimized/legacy/megafused plan predictions "
                            "diverged from the serial unfused path")
        if rep.get("examples_at_one_program", 0) < 2:
            problems.append("megafusion did not reach 1 program/apply run "
                            "on at least two example pipelines")
        if problems:
            rep["error"] = "; ".join(problems)
        return rep

    dispatch_tier = None
    if not args.skip_dispatch_tier:
        dispatch_tier = run_tier(
            "dispatch_count", "dispatch_tier", "dispatch_tier_done",
            "seconds", dispatch_fn)
    detail.update({"progress": "dispatch_tier",
                   "dispatch_count": dispatch_tier})
    log(f"checkpoint: {detail['progress']}")

    # Telemetry-overhead tier: the live plane's warm-serving cost,
    # armed vs disarmed (ISSUE 18's <5% standing budget). Platform
    # independent in spirit — the measured delta is host-side Python
    # (ring tee, sketch insert, conformance compare), not device work.
    telemetry_tier = None
    if not args.skip_telemetry_tier:
        telemetry_tier = run_tier(
            "telemetry_overhead", "telemetry_tier", "telemetry_tier_done",
            "seconds", _telemetry_overhead)
    detail.update({"progress": "telemetry_tier",
                   "telemetry_overhead": telemetry_tier})
    log(f"checkpoint: {detail['progress']}")

    # Serving-QPS tier: the certified serving runtime under sustained
    # concurrent load at fixed offered QPS, coalesced vs the
    # KEYSTONE_SERVING_COALESCE=0 kill switch in the same run. The SLO
    # gate IS the certificate: per-shape observed p99 must sit under
    # the KP903 bound, with 0 cold compiles and 0 conformance breaches
    # inside the measured window, and coalescing must sustain >=4x the
    # per-request-dispatch throughput at equal offered load.
    serving_tier = None
    if not args.skip_serving_tier:
        serving_tier = run_tier(
            "serving_qps", "serving_tier", "serving_tier_done",
            "seconds", _serving_qps)
    detail.update({"progress": "serving_tier",
                   "serving_qps": serving_tier})
    log(f"checkpoint: {detail['progress']}")

    # Compile-count tier: cold-vs-warm compiles + wall clock for the
    # example pipelines against a fresh persistent-cache dir, plus the
    # host ragged-tail microbench. The warm run must perform 0 cold
    # compiles and beat the cold run end-to-end, with outputs identical
    # at multiple AND ragged counts (ISSUE 5 acceptance).
    def compile_fn():
        import time as _t

        from keystone_tpu.compile_bench import compile_count_report

        t0 = _t.perf_counter()
        rep = compile_count_report()
        rep["seconds"] = round(_t.perf_counter() - t0, 2)
        problems = []
        if not rep["all_warm_runs_zero_compiles"]:
            problems.append("a warm run performed cold compiles")
        if not rep["all_warm_beats_cold"]:
            problems.append("a warm run did not beat the cold wall clock")
        if not rep["all_apply_compiles_bounded"]:
            problems.append("apply-run compiles exceed plan programs")
        if not rep["host_tail_padding_saves_programs"]:
            problems.append("chunk padding failed to remove the "
                            "ragged-tail program")
        if problems:
            rep["error"] = "; ".join(problems)
        return rep

    compile_tier = None
    if not args.skip_compile_tier:
        compile_tier = run_tier(
            "compile_count", "compile_tier", "compile_tier_done",
            "seconds", compile_fn)
    detail.update({"progress": "compile_tier",
                   "compile_count": compile_tier})
    log(f"checkpoint: {detail['progress']}")

    # Fused tier LAST: the SAME training run as one XLA program (the
    # `--fused` CLI path, run_fused) — filter learning, featurize,
    # scaler, the pipeline's own BCD solve, and train/test confusion in
    # a single device execution, so per-dispatch latency is paid once.
    # Solver-identical to the pipeline path (it jits the same
    # _bcd_fit_impl), hence reported as a tier of the same record.
    def fused_fn():
        run_fused(train, test, config)  # compile + warm
        # fresh-valued timed run; perturbation dispatched and fenced
        # BEFORE the timed window
        import random as _random

        from keystone_tpu.loaders.csv_loader import LabeledData

        eps = _random.random() * 1e-6
        train_f = LabeledData(
            labels=train.labels,
            data=train.data.map_batches(lambda x: x * (1.0 + eps)).sync())
        t0 = time.perf_counter()
        fused_res = run_fused(train_f, test, config)
        fused_s = time.perf_counter() - t0
        return {
            "train_seconds": round(fused_s, 3),
            "images_per_sec": round(train.data.count / fused_s, 2),
            "test_accuracy": round(fused_res["test_accuracy"], 4),
            "note": "one-execution training run (run_fused, the --fused "
                    "CLI path); includes train+test featurize and both "
                    "confusion matrices",
        }

    fused_detail = run_tier("fused", "fused_tier", "fused_done",
                            "train_seconds", fused_fn)
    detail.update({"progress": "complete", "fused": fused_detail})
    log(f"checkpoint: {detail['progress']}")
    return detail


if __name__ == "__main__":
    sys.exit(main())
