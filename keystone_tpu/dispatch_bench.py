"""Dispatch accounting for the example pipelines: programs per run.

Every executed program pays a fixed launch cost whatever its size, so
the optimizer's fusion coverage is a first-class perf quantity. This module measures ``dispatch.programs_executed`` for small
CPU-runnable instances of the example pipelines under three optimizer
plans and checks the outputs are identical:

  - ``serial_unfused`` — no fusion, no overlap, no concurrent dispatch:
    one program per node, the dispatch-per-node regime every unfused
    boundary degenerates to;
  - ``legacy`` — the PR-3 optimizer exactly (transformer-chain fusion
    only, ``NodeFusionRule(fuse_apply=False)``, serial dispatch);
  - ``optimized`` — the PR-4/5 plan: expanded fusable coverage, fusion
    through fan-out-free estimator apply boundaries
    (`FusedChainOperator`), concurrent DAG dispatch, megafusion OFF;
  - ``megafused`` — the PR-9 default plan: ``optimized`` plus
    whole-plan megafusion (`MegafusionRule`): the entire apply path,
    chunk loop included, collapses into ONE scan-bodied program;
  - ``precision`` — ``megafused`` plus the mixed-precision policy pass
    (`PrecisionPlannerRule`, enforcement floor dropped to 0 so the
    small bench instances actually bake their policies): same program
    count, halved tolerant stage boundaries. Its outputs are gated
    against the serial unfused f32 reference with the declared
    tolerance band (`analysis.precision.DEFAULT_BAND_*`), not exact
    equality — the ``precision_in_band`` verdict of
    `dispatch_count_report`.
  - ``kernel`` — the PR-16 plan: ``megafused`` plus the unified
    planner (enforcement floor dropped to 0 so the small bench
    instances actually plan) with its chain-megakernel axis live:
    eligible fused stage sub-trails dispatch as ONE Pallas kernel
    (`ops/chain_kernels.py`; interpret mode off-TPU, forced via the
    ``KEYSTONE_CHAIN_KERNELS=interpret`` hook so the swap path — not
    just the pricing — is what this column measures). Outputs stay on
    the exact-equality gate: interpret-mode kernels are the same f32
    jnp bodies XLA runs.

Each measurement reports the *fit run* (first application: estimator
fits + train apply) and the *apply run* (re-applying the fitted
pipeline to held-out data — the serving path) separately; the apply run
is the headline programs-per-run number, and the report carries a
per-plan breakdown row per example so the 2→1 reduction shows up in
``perf_table.py --trace`` directly. Used by tests/test_scheduler.py +
tests/test_megafusion.py (the acceptance gates + allclose identity
against the serial unfused path) and by `scripts/lint.sh`.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

PLANS = ("serial_unfused", "legacy", "optimized", "megafused",
         "precision", "kernel")


# ---------------------------------------------------------------- examples
#
# Small, data-identical instances of example pipelines from the
# `python -m keystone_tpu.analysis` set. Builders return
# (predictor, train_data, test_data): applying `predictor` to train_data
# is the fit run, to test_data the apply run. Sizes are chosen so a full
# three-plan sweep stays in tier-1 time on the 8-device CPU mesh.


def _build_mnist_random_fft():
    """MnistRandomFFT (pipelines/mnist_random_fft.py): gather of
    RandomSign → PaddedFFT → LinearRectifier branches → VectorCombiner →
    BlockLeastSquares → MaxClassifier."""
    from .data.dataset import Dataset
    from .nodes.learning import BlockLeastSquaresEstimator
    from .nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
    from .nodes.util import (
        ClassLabelIndicatorsFromInt,
        MaxClassifier,
        VectorCombiner,
    )
    from .workflow import Pipeline

    rng = np.random.default_rng(0)
    dim, n_train, n_test, k = 32, 64, 32, 6
    X = rng.normal(size=(n_train, dim)).astype(np.float32)
    Xt = rng.normal(size=(n_test, dim)).astype(np.float32)
    y = rng.integers(0, k, n_train).astype(np.int32)

    branches = [
        RandomSignNode(dim, seed=i) >> PaddedFFT() >> LinearRectifier(0.0)
        for i in range(3)
    ]
    featurizer = Pipeline.gather(branches) >> VectorCombiner()
    train = Dataset.from_numpy(X)
    labels = ClassLabelIndicatorsFromInt(k)(Dataset.from_numpy(y)).get()
    predictor = featurizer.and_then(
        BlockLeastSquaresEstimator(dim, num_iter=1, lam=1e-2), train, labels
    ) >> MaxClassifier()
    return predictor, train, Dataset.from_numpy(Xt)


def _build_random_patch_cifar():
    """RandomPatchCifar's prediction path (the `analyzable()` graph,
    pipelines/random_patch_cifar.py): per-node conv → rectify → pool →
    vectorize → Cacher → StandardScaler → BlockLeastSquares → argmax,
    with random filters standing in for the data-learned ones."""
    from .data.dataset import Dataset
    from .nodes.images.core import (
        Convolver,
        ImageVectorizer,
        PixelScaler,
        Pooler,
        SymmetricRectifier,
    )
    from .nodes.learning import BlockLeastSquaresEstimator
    from .nodes.stats import StandardScaler
    from .nodes.util import Cacher, ClassLabelIndicatorsFromInt, MaxClassifier

    rng = np.random.default_rng(1)
    h = w = 16
    c, nf, k = 3, 8, 4
    X = rng.uniform(0, 255, size=(48, h, w, c)).astype(np.float32)
    Xt = rng.uniform(0, 255, size=(24, h, w, c)).astype(np.float32)
    y = rng.integers(0, k, 48).astype(np.int32)
    filters = rng.normal(size=(nf, 4 * 4 * c)).astype(np.float32)

    featurizer = (
        PixelScaler().to_pipeline()
        >> Convolver(filters, h, w, c, whitener=None)
        >> SymmetricRectifier(alpha=0.25)
        >> Pooler(6, 7, pool_fn="sum")
        >> ImageVectorizer()
        >> Cacher("features")
    )
    train = Dataset.from_numpy(X)
    labels = ClassLabelIndicatorsFromInt(k)(Dataset.from_numpy(y)).get()
    predictor = (
        featurizer.and_then(StandardScaler(), train)
        .and_then(BlockLeastSquaresEstimator(64, 1, 1.0), train, labels)
        >> MaxClassifier()
    )
    return predictor, train, Dataset.from_numpy(Xt)


def _build_timit():
    """TimitPipeline (pipelines/timit.py): gather of CosineRandomFeatures
    branches → VectorCombiner → Cacher → BlockLeastSquares (a block a
    branch) → MaxClassifier over pre-featurized frames."""
    from .data.dataset import Dataset
    from .loaders.csv_loader import LabeledData
    from .pipelines.timit import TimitConfig, build_pipeline

    rng = np.random.default_rng(2)
    dim, k = 24, 6
    X = rng.normal(size=(64, dim)).astype(np.float32)
    Xt = rng.normal(size=(32, dim)).astype(np.float32)
    y = rng.integers(0, k, 64).astype(np.int32)

    train = LabeledData.from_arrays(y, X)
    predictor = build_pipeline(train, TimitConfig(
        num_cosines=2, num_cosine_features=24, gamma=0.05, num_epochs=1,
        lam=1e-3, num_classes=k))
    return predictor, train.data, Dataset.from_numpy(Xt)


def _build_linear_pixels():
    """LinearPixels (pipelines/linear_pixels.py): PixelScaler →
    GrayScaler → ImageVectorizer → BlockLeastSquares → argmax. The
    featurizer trail is exactly the elementwise chain-megakernel
    family, so this is the bench instance where the ``kernel`` plan's
    swap actually fires (the report's default set keeps the historical
    three; tests and ad-hoc sweeps pass it explicitly)."""
    from .data.dataset import Dataset
    from .nodes.images.core import GrayScaler, ImageVectorizer, PixelScaler
    from .nodes.learning import BlockLeastSquaresEstimator
    from .nodes.util import ClassLabelIndicatorsFromInt, MaxClassifier

    rng = np.random.default_rng(3)
    h = w = 8
    c, k = 3, 4
    X = rng.uniform(0, 255, size=(48, h, w, c)).astype(np.float32)
    Xt = rng.uniform(0, 255, size=(24, h, w, c)).astype(np.float32)
    y = rng.integers(0, k, 48).astype(np.int32)

    featurizer = (PixelScaler().to_pipeline() >> GrayScaler()
                  >> ImageVectorizer())
    train = Dataset.from_numpy(X)
    labels = ClassLabelIndicatorsFromInt(k)(Dataset.from_numpy(y)).get()
    predictor = featurizer.and_then(
        BlockLeastSquaresEstimator(h * w, num_iter=1, lam=1e-2), train,
        labels) >> MaxClassifier()
    return predictor, train, Dataset.from_numpy(Xt)


#: name (matching the analysis-set registry) -> builder
EXAMPLES: Dict[str, Callable] = {
    "MnistRandomFFT": _build_mnist_random_fft,
    "RandomPatchCifar": _build_random_patch_cifar,
    "TimitPipeline": _build_timit,
    "LinearPixels": _build_linear_pixels,
}


# ------------------------------------------------------------- measurement


def _plan_context(plan: str):
    """(optimizer, overlap_on, concurrent_on, config_overrides) for a
    named plan. ``optimized`` pins megafusion OFF so it remains the
    PR-4/5 plan bit for bit; the historical baselines also pin the
    sharding planner OFF (it post-dates them — PR 9), every plan up
    to ``megafused`` pins the precision planner OFF (it post-dates them
    — PR 10), and EVERY named plan pins the unified planner OFF (it
    post-dates all of them — PR 15 — and the named plans are exact
    historical reproductions; the unified planner's bench story is the
    static joint-vs-sequential audit); ``precision`` is the full PR-13
    sequential stack with the enforcement floor dropped so the small
    bench instances bake their policies."""
    from .workflow.optimizer import DefaultOptimizer

    if plan == "serial_unfused":
        return DefaultOptimizer(fuse=False, sharding_planner=False,
                                precision_planner=False,
                                unified_planner=False), \
            False, False, dict(megafusion=False, precision_planner=False,
                               unified_planner=False)
    if plan == "legacy":
        return DefaultOptimizer(fuse_apply=False, sharding_planner=False,
                                precision_planner=False,
                                unified_planner=False), \
            True, False, dict(megafusion=False, precision_planner=False,
                              unified_planner=False)
    if plan == "optimized":
        return DefaultOptimizer(megafuse=False, sharding_planner=False,
                                precision_planner=False,
                                unified_planner=False), \
            True, True, dict(megafusion=False, precision_planner=False,
                             unified_planner=False)
    if plan == "megafused":
        return DefaultOptimizer(precision_planner=False,
                                unified_planner=False), True, True, \
            dict(megafusion=True, precision_planner=False,
                 unified_planner=False)
    if plan == "precision":
        return DefaultOptimizer(unified_planner=False), True, True, \
            dict(megafusion=True, precision_planner=True,
                 precision_min_savings_bytes=0, unified_planner=False)
    if plan == "kernel":
        # the PR-16 plan: megafused + the unified planner (floor 0 so
        # the small instances actually plan) with the chain-megakernel
        # axis live; precision stays off so the column isolates the
        # kernel decision against ``megafused`` exactly
        return DefaultOptimizer(precision_planner=False), True, True, \
            dict(megafusion=True, precision_planner=False,
                 unified_planner=True, unified_min_savings_seconds=0.0,
                 pallas_kernels=True)
    raise ValueError(f"unknown plan {plan!r}; expected one of {PLANS}")


import contextlib
import os


@contextlib.contextmanager
def _chain_kernel_interpret():
    """Force `KEYSTONE_CHAIN_KERNELS=interpret` for the ``kernel`` plan
    off-TPU, so the bench measures the actual swap path (one chain
    dispatch per planned sub-trail), not just the planner's pricing.
    On a TPU backend the default gate already dispatches native
    kernels — the env is left alone."""
    import jax

    try:
        native = jax.default_backend() == "tpu"
    except Exception:
        native = False
    if native:
        yield
        return
    prev = os.environ.get("KEYSTONE_CHAIN_KERNELS")
    os.environ["KEYSTONE_CHAIN_KERNELS"] = "interpret"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("KEYSTONE_CHAIN_KERNELS", None)
        else:
            os.environ["KEYSTONE_CHAIN_KERNELS"] = prev


def measure_example(name: str, plan: str) -> Dict:
    """Run one example under one plan from a clean `PipelineEnv`,
    returning program counts, the (host) predictions of both runs, and
    the optimizer decisions the window recorded (the decision-ledger
    slice the `decisions_reconciled` bench verdict audits)."""
    from .telemetry import ledger, metrics_delta
    from .workflow.env import (
        PipelineEnv,
        config_override,
        dispatch_override,
        overlap_override,
    )

    optimizer, overlap_on, concurrent_on, overrides = _plan_context(plan)
    PipelineEnv.reset()
    mark = ledger.session_mark()
    kernel_env = (_chain_kernel_interpret() if plan == "kernel"
                  else contextlib.nullcontext())
    try:
        PipelineEnv.get().set_optimizer(optimizer)
        with kernel_env, overlap_override(overlap_on), \
                dispatch_override(concurrent_on), \
                config_override(**overrides):
            predictor, train, test = EXAMPLES[name]()
            with metrics_delta() as d:
                train_pred = np.asarray(predictor(train).get().numpy())
            fit_programs = d.counter("dispatch.programs_executed")
            with metrics_delta() as d:
                test_pred = np.asarray(predictor(test).get().numpy())
            apply_programs = d.counter("dispatch.programs_executed")
    finally:
        PipelineEnv.reset()
    decisions = ledger.session_since(mark)
    from .telemetry import current_tracer

    tracer = current_tracer()
    if tracer is not None:
        # per-plan breakdown in the trace metadata: perf_table.py
        # --trace and the telemetry CLI render the 2→1 reduction from
        # here (rows accumulate across measure_example calls)
        meta = tracer.metadata.setdefault(
            "dispatch_plans",
            {"plans": list(PLANS), "apply_run_programs": {}})
        meta["apply_run_programs"].setdefault(name, {})[plan] = int(
            apply_programs)
    return {
        "plan": plan,
        "fit_run_programs": int(fit_programs),
        "apply_run_programs": int(apply_programs),
        "train_pred": train_pred,
        "test_pred": test_pred,
        "decisions": decisions,
    }


def dispatch_count_report(
    examples: Tuple[str, ...] = ("MnistRandomFFT", "RandomPatchCifar",
                                 "TimitPipeline"),
    check_outputs: bool = True,
) -> Dict:
    """Per-example programs per
    run under each plan (an explicit per-plan breakdown row per
    example), reduction ratios (apply run, the serving path — headline
    plan is ``megafused``), and an output-identity verdict against the
    serial unfused path. When a tracer is active the breakdown is also
    embedded in the trace metadata, so ``perf_table.py --trace`` and the
    telemetry CLI render the 2→1 reduction without spelunking the raw
    trace."""
    from .analysis.precision import DEFAULT_BAND_ATOL, DEFAULT_BAND_RTOL

    from .telemetry.ledger import decision_key

    out: Dict = {"examples": {}, "plans": list(PLANS),
                 "plan_breakdown": []}
    reductions: List[float] = []
    mega_one = 0
    precision_in_band = True
    decisions_reconciled = True
    for name in examples:
        runs = {plan: measure_example(name, plan) for plan in PLANS}
        base = runs["serial_unfused"]
        mega = runs["megafused"]
        outputs_match = True
        in_band = True
        if check_outputs:
            for r in (runs["legacy"], runs["optimized"], mega,
                      runs["kernel"]):
                try:
                    np.testing.assert_allclose(
                        r["train_pred"], base["train_pred"],
                        rtol=1e-5, atol=1e-5)
                    np.testing.assert_allclose(
                        r["test_pred"], base["test_pred"],
                        rtol=1e-5, atol=1e-5)
                except AssertionError:
                    outputs_match = False
            # the precision plan is gated with the DECLARED band, not
            # exact equality: bf16 boundaries legitimately round, and
            # the policy is only shippable inside the band (argmax
            # outputs are int — the band degenerates to equality there,
            # with a small tie-flip allowance)
            for side in ("train_pred", "test_pred"):
                a, b = runs["precision"][side], base[side]
                if np.issubdtype(a.dtype, np.integer):
                    if np.mean(a == b) < 0.95:
                        in_band = False
                else:
                    try:
                        np.testing.assert_allclose(
                            a, b, rtol=DEFAULT_BAND_RTOL,
                            atol=DEFAULT_BAND_ATOL)
                    except AssertionError:
                        in_band = False
            precision_in_band &= in_band
        apply_ratio = (base["apply_run_programs"] / mega["apply_run_programs"]
                       if mega["apply_run_programs"] else float("inf"))
        reductions.append(apply_ratio)
        mega_one += int(mega["apply_run_programs"] == 1)
        # the decision-ledger verdict: a megafused plan that executed its
        # apply run as ONE program must have RECORDED that decision, and
        # the record's prediction must say exactly that — the enforced
        # plan and the ledger cannot disagree (`decisions_reconciled`)
        mega_uniq: Dict = {}
        for d in mega.get("decisions") or []:
            if d.get("kind") == "megafusion":
                mega_uniq.setdefault(decision_key(d), d)
        ex_reconciled = bool(
            mega["apply_run_programs"] != 1 or (
                mega_uniq and all(
                    (d.get("predicted") or {}).get("programs_per_apply") == 1
                    for d in mega_uniq.values())))
        decisions_reconciled &= ex_reconciled
        out["examples"][name] = {
            "apply_run_programs": {
                p: runs[p]["apply_run_programs"] for p in PLANS},
            "fit_run_programs": {
                p: runs[p]["fit_run_programs"] for p in PLANS},
            "reduction_vs_serial_unfused": round(apply_ratio, 2),
            "reduction_vs_legacy": round(
                runs["legacy"]["apply_run_programs"]
                / max(1, mega["apply_run_programs"]), 2),
            "reduction_vs_optimized": round(
                runs["optimized"]["apply_run_programs"]
                / max(1, mega["apply_run_programs"]), 2),
            "outputs_match_serial_unfused": bool(outputs_match),
            "precision_in_band": bool(in_band),
            "decisions_reconciled": ex_reconciled,
            "decision_counts": {
                p: _kind_counts(runs[p].get("decisions") or [])
                for p in PLANS},
        }
        # the per-plan breakdown row: one flat record per example, the
        # shape perf_table.py / the trace CLI print verbatim (the
        # `precision` column is the policy-on apply-run program count —
        # same 1-program shape as megafused, halved boundaries inside)
        out["plan_breakdown"].append({
            "example": name,
            **{p: runs[p]["apply_run_programs"] for p in PLANS},
        })
    reductions.sort(reverse=True)
    # the acceptance gates: at least two example pipelines drop >= 2x,
    # and (megafusion) at least two run their apply in ONE program
    out["examples_at_or_above_2x"] = int(sum(1 for r in reductions if r >= 2.0))
    out["examples_at_one_program"] = int(mega_one)
    out["top2_min_reduction"] = round(min(reductions[:2]), 2) if len(
        reductions) >= 2 else None
    out["all_outputs_match"] = all(
        e["outputs_match_serial_unfused"] for e in out["examples"].values())
    out["precision_in_band"] = bool(precision_in_band)
    out["decisions_reconciled"] = bool(decisions_reconciled)
    return out


def _kind_counts(decisions: List[Dict]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for d in decisions:
        k = str(d.get("kind"))
        out[k] = out.get(k, 0) + 1
    return out
