"""End-to-end RandomPatchCifar on the synthetic learnable task (north-star
pipeline, SURVEY.md §3.4), small config for the CPU mesh."""

from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifarConfig, run


def test_random_patch_cifar_end_to_end():
    result = run(
        RandomPatchCifarConfig(
            num_filters=64,
            sample_patches=10_000,
            synth_train=320,
            synth_test=80,
            microbatch=64,
            block_size=512,
        )
    )
    # the synthetic task is fully separable for a working pipeline
    assert result["test_accuracy"] > 0.9, result["summary"]


def test_cifar_binary_loader_roundtrip(tmp_path):
    import numpy as np

    from keystone_tpu.loaders.cifar_loader import cifar_loader

    rng = np.random.default_rng(0)
    n = 20
    labels = rng.integers(0, 10, n, dtype=np.uint8)
    images = rng.integers(0, 256, size=(n, 3, 32, 32), dtype=np.uint8)
    records = np.concatenate(
        [labels[:, None], images.reshape(n, -1)], axis=1
    )
    path = tmp_path / "data_batch_1.bin"
    records.tofile(path)
    data = cifar_loader(str(path))
    assert data.data.count == n
    np.testing.assert_array_equal(data.labels.numpy(), labels)
    # HWC conversion: channel-planar source
    np.testing.assert_allclose(
        data.data.numpy()[0][:, :, 0], images[0, 0].astype(np.float32)
    )


def test_cifar_loader_on_checked_in_real_format_fixture():
    """100-record fixture in the EXACT CIFAR-10 binary layout (1 label
    byte + 3072 channel-planar bytes — CifarLoader.scala:21-51): record i
    has label i%10 and pixel value row*2 + label*10 + channel*5, so the
    loader's record framing, label extraction, and planar→HWC transpose
    are each pinned to known bytes (VERDICT r3 #6)."""
    import os

    import numpy as np

    from keystone_tpu.loaders.cifar_loader import cifar_loader

    path = os.path.join(os.path.dirname(__file__), "resources", "cifar_mini.bin")
    data = cifar_loader(path)
    assert data.data.count == 100
    labels = np.asarray(data.labels.numpy())
    np.testing.assert_array_equal(labels, np.arange(100) % 10)
    imgs = np.asarray(data.data.numpy())
    assert imgs.shape == (100, 32, 32, 3)
    # record 17 (label 7): channel c pixel at row r = r*2 + 70 + c*5
    r = np.arange(32)
    for c in range(3):
        want = np.clip(r * 2 + 7 * 10 + c * 5, 0, 255).astype(np.float32)
        np.testing.assert_array_equal(imgs[17, :, 5, c], want)


def test_random_patch_pipeline_on_real_images():
    """Fixture-scale REAL-image regression (VERDICT r1 item 2: real CIFAR
    binaries are unobtainable in this zero-egress env, so the full
    featurize+solve pipeline is exercised on natural-image statistics
    instead: 32x32 crops of two checked-in photographs, classified by
    source photo)."""
    import os

    import numpy as np
    from PIL import Image

    from keystone_tpu.data.dataset import Dataset
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
    )

    res = os.path.join(os.path.dirname(__file__), "resources")

    def crops(name):
        img = np.asarray(Image.open(os.path.join(res, name)).convert("RGB"),
                         np.float32)
        h, w = img.shape[:2]
        out = [
            img[y : y + 32, x : x + 32]
            for y in range(0, h - 32, 32)
            for x in range(0, w - 32, 32)
        ]
        return np.stack(out)

    a, b = crops("gantrycrane.png"), crops("000012.jpg")
    X = np.concatenate([a, b])
    y = np.concatenate([np.zeros(len(a), np.int32), np.ones(len(b), np.int32)])
    rng = np.random.default_rng(0)
    order = rng.permutation(len(X))
    X, y = X[order], y[order]
    cut = int(len(X) * 0.8)

    class _Split:
        def __init__(self, X, y):
            self.data = Dataset(X)
            self.labels = Dataset(y)

    train, test = _Split(X[:cut], y[:cut]), _Split(X[cut:], y[cut:])
    config = RandomPatchCifarConfig(
        num_filters=32, num_classes=2, sample_patches=5_000, microbatch=64,
        block_size=256,
    )
    predictor = build_pipeline(train, config)
    ev = MulticlassClassifierEvaluator(2)
    acc = ev(predictor(test.data), test.labels).accuracy
    assert acc > 0.85, f"real-image crop classification accuracy {acc}"


def test_calibrated_difficulty_accuracy_band():
    """VERDICT r2 #2: the synthetic task at the bench's calibrated
    difficulty (noise=1.2, confusion=0.6) must land test accuracy in a
    nontrivial band — a solver-quality regression (broken centering, BCD
    convergence, precision) drops below it; an accidentally-trivialized
    generator saturates above it. Calibration measured 0.797 at this
    exact config (n=2000, 128 filters, seed 0; chance = 0.10)."""
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.loaders.cifar_loader import synthetic_cifar
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
    )
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.reset()
    train, test = synthetic_cifar(2000, 1000, seed=0, noise=1.2, confusion=0.6)
    pred = build_pipeline(train, RandomPatchCifarConfig(num_filters=128))
    acc = MulticlassClassifierEvaluator(10)(pred(test.data), test.labels).accuracy
    assert 0.68 <= acc <= 0.92, f"accuracy {acc} left the calibrated band"


def test_run_fused_matches_pipeline_path():
    """`run_fused` collapses the whole fit (filters → featurize → scaler
    → single-block ridge → eval) into ONE traced program; with
    block_size ≥ d and num_iter=1 it must reproduce the pipeline path's
    accuracy exactly (the scaler fold is a linear reparameterization,
    not an approximation)."""
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.loaders.cifar_loader import synthetic_cifar
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
        run_fused,
    )
    from keystone_tpu.workflow import PipelineEnv

    train, test = synthetic_cifar(1000, 500, seed=0, noise=1.2, confusion=0.6)
    config = RandomPatchCifarConfig(num_filters=64)
    res = run_fused(train, test, config)

    PipelineEnv.reset()
    ev = MulticlassClassifierEvaluator(10)
    predictor = build_pipeline(train, config)
    acc = ev(predictor(test.data), test.labels).accuracy
    assert abs(res["test_accuracy"] - acc) < 0.02, (res["test_accuracy"], acc)
    assert res["train_error"] < 0.2


def test_run_fused_multiblock_matches_pipeline():
    """The fused path calls the SAME _bcd_fit_impl as the pipeline's
    BlockLeastSquaresEstimator, so it must agree even when block_size <
    d (multi-block coordinate descent, not a single ridge solve)."""
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.loaders.cifar_loader import synthetic_cifar
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        build_pipeline,
        run_fused,
    )
    from keystone_tpu.workflow import PipelineEnv

    train, test = synthetic_cifar(600, 300, seed=1, noise=1.2, confusion=0.6)
    # d = 2·2·2·32 = 256 features; block_size=64 -> 4 BCD blocks
    config = RandomPatchCifarConfig(num_filters=32, block_size=64)
    res = run_fused(train, test, config)

    PipelineEnv.reset()
    ev = MulticlassClassifierEvaluator(10)
    predictor = build_pipeline(train, config)
    acc = ev(predictor(test.data), test.labels).accuracy
    assert abs(res["test_accuracy"] - acc) < 0.02, (res["test_accuracy"], acc)


def test_fused_conv_vmem_accounting_lane_padding():
    """The fused conv kernel's VMEM block chooser must lane-pad k to 128
    (Mosaic pads the minor dim): ignoring it produced a real scoped-vmem
    OOM at k=16 on v5e (21.5 MB actual vs 8.9 MB estimated)."""
    from keystone_tpu.ops.pallas_kernels import _fused_conv_block_images

    # CIFAR geometry: 27x27 valid conv -> posp=736, dp=128, cells=4
    b16 = _fused_conv_block_images(736, 128, 16, 4)
    b256 = _fused_conv_block_images(736, 128, 256, 4)
    # k=16 must be budgeted like k=64 (lane padding: kp=128 and k2p=128
    # for both — the pre-fix unpadded budget OOM'd live at k=16: 21.5 MB
    # actual vs 8.9 MB estimated). With the per-image sequential pool
    # loop the z/act transients no longer scale with the block, so the
    # block is much larger than the block-diagonal design's 8/4.
    b64 = _fused_conv_block_images(736, 128, 64, 4)
    assert b16 == b64 == 22, (b16, b64)
    assert b256 == 14, b256


def _load_bench():
    """Import bench.py as a module (it lives at the repo root, outside
    the package)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_mod",
        os.path.join(os.path.dirname(__file__), "..", "bench.py"),
    )
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


def test_bench_band_gate():
    """bench.py's record gate: out-of-band accuracy is marked as an
    error and is never a clean record; in-band TPU runs are clean; CPU
    runs never are."""
    bench = _load_bench()

    base = {"images_per_sec": 1000.0, "test_accuracy": 0.85,
            "accuracy_band": [0.72, 0.96], "platform": "tpu"}
    rec, persist = bench.finalize_record(dict(base, accuracy_in_band=True))
    assert persist and "error" not in rec

    rec, persist = bench.finalize_record(
        dict(base, test_accuracy=0.3, accuracy_in_band=False))
    assert not persist and "below calibrated lower bound" in rec["error"]

    rec, persist = bench.finalize_record(
        dict(base, platform="cpu", accuracy_in_band=True))
    assert not persist

    # legacy records (no band fields) still pass through and persist
    rec, persist = bench.finalize_record(
        {"images_per_sec": 500.0, "platform": "tpu"})
    assert persist and "error" not in rec

    # real-data records gate on the north star, not the synthetic band
    real = {"images_per_sec": 1000.0, "test_accuracy": 0.80,
            "accuracy_band": None, "synthetic": False, "platform": "tpu",
            "north_star": {"target_accuracy": 0.84, "accuracy_ok": False},
            "accuracy_in_band": False}
    rec, persist = bench.finalize_record(real)
    assert not persist and "north-star target 0.84" in rec["error"]

    rec, persist = bench.finalize_record(
        dict(real, test_accuracy=0.9, accuracy_in_band=True,
             north_star={"target_accuracy": 0.84, "accuracy_ok": True}))
    assert persist and "error" not in rec


def test_bench_partial_record_ranking():
    """Best-partial selection across checkpoints: a
    later-tier checkpoint (e.g. krr_tier, everything measured except the
    fused tier) must beat an earlier-tier one from another attempt, ties
    go to the newer attempt, and unknown progress values rank lowest."""
    bench = _load_bench()

    d_head = {"progress": "headline", "attempt": 1}
    d_krr = {"progress": "krr_tier", "attempt": 2}
    d_head2 = {"progress": "headline", "attempt": 3}
    d_unknown = {"progress": "someday_tier", "attempt": 4}

    best = bench.pick_better_partial(None, d_head)
    assert best is d_head
    best = bench.pick_better_partial(best, d_krr)
    assert best is d_krr
    # an earlier-tier checkpoint from a later attempt must NOT displace it
    best = bench.pick_better_partial(best, d_head2)
    assert best is d_krr
    # unknown progress ranks 0 and never displaces a ranked one
    best = bench.pick_better_partial(best, d_unknown)
    assert best is d_krr
    # same-tier tie goes to the newer attempt
    d_krr2 = {"progress": "krr_tier", "attempt": 5}
    assert bench.pick_better_partial(d_krr, d_krr2) is d_krr2
    # every tier the child emits is ranked (completeness ordering)
    emitted = ["headline", "staged", "flagship", "featurize_tier",
               "krr_tier", "overlap_tier", "complete"]
    ranks = [bench.PROGRESS_RANK[p] for p in emitted]
    assert ranks == sorted(ranks) and len(set(ranks)) == len(ranks)


def test_bench_tier_errors_surface_and_never_persist():
    """A record whose tier payload carries {"error": ...} (the
    failure-isolated tiers) must surface the failure top-level and never
    count as clean, even in-band on TPU."""
    bench = _load_bench()

    base = {"images_per_sec": 1000.0, "test_accuracy": 0.85,
            "accuracy_band": [0.72, 0.96], "platform": "tpu",
            "accuracy_in_band": True,
            "flagship_bcd_d8192": {"error": "RuntimeError: boom"},
            "flagship_krr": {"fit_seconds": 1.0}}
    rec, persist = bench.finalize_record(base)
    assert not persist
    assert "flagship_bcd_d8192" in rec["error"] and "boom" in rec["error"]
    # healthy tiers still persist
    ok = dict(base, flagship_bcd_d8192={"fit_seconds": 1.0})
    rec, persist = bench.finalize_record(ok)
    assert persist and "error" not in rec


def test_bench_tier_error_scan_ignores_informational_payloads():
    """The error scan is restricted to the known tier keys: a future
    informational dict that happens to carry an "error" field (e.g. a
    diagnostics payload) must NOT block persistence — only real tier
    payloads gate the record."""
    bench = _load_bench()

    base = {"images_per_sec": 1000.0, "test_accuracy": 0.85,
            "accuracy_band": [0.72, 0.96], "platform": "tpu",
            "accuracy_in_band": True,
            # informational payloads with an embedded "error" field
            "link_diagnostics": {"error": "transient stall at 03:12"},
            "north_star": {"target_accuracy": 0.84, "accuracy_ok": True,
                           "error": "informational only"},
            # healthy real tiers
            "flagship_krr": {"fit_seconds": 1.0},
            "featurize_overlap": {"serial_seconds": 2.0,
                                  "overlapped_seconds": 1.0}}
    rec, persist = bench.finalize_record(base)
    assert persist and "error" not in rec
    # a real tier key carrying an error still gates
    bad = dict(base, featurize_overlap={"error": "ValueError: nope"})
    rec, persist = bench.finalize_record(bad)
    assert not persist and "featurize_overlap" in rec["error"]
    # every gating key the child can emit is covered by the scan list
    assert set(bench.TIER_KEYS) == {
        "flagship_bcd_d8192", "flagship_featurize", "flagship_krr",
        "featurize_overlap", "dispatch_count", "telemetry_overhead",
        "serving_qps", "out_of_core", "compile_count", "fused"}
