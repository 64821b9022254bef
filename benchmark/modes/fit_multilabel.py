"""Mode `fit_multilabel`: `modes/fit.py`'s traffic for a pipeline whose
labels are several an image and whose score is mean average precision:
repeated warm fits, each followed by one batch apply of the test set.
Timing, phases, counters, warm-ups, the peak-memory read and the
compile check are `fit.py`'s, and so is how the two rates are computed.

Set-up: the data from the seed, one cold iteration (it compiles, or
loads from the persistent cache) and one warm one. Window: iterations
until the seconds have passed, at least one. An iteration is one fit,
timed from `build` to the training set's mean average precision on the
host (11-point, `MeanAveragePrecisionEvaluator`), then one
`FittedPipeline.apply` of the test set timed behind
`jax.block_until_ready`, then the test mAP, outside both timers.

After the window, outside every timer and after the peak memory has
been read, `correct`: every fit's test mAP and the plain reference's
inside `map_band`; nothing compiled in the window; and three
comparisons of what the timed path itself made, at the timed sizes,
with the plain reference (`reference/<config>.py`):

  (a) the PCA basis: the largest principal angle between the program's
      subspace and the one the reference finds from its own descriptors
      (`pca_angle_limit`, radians);
  (b) the mixture: the mean log-likelihood of the reference's samples
      under the program's mixture against the reference's own EM from
      the same start (`gmm_loglik_gap_limit`, nats a sample, either
      way);
  (c) the scores: the reference's featurizer handed the program's PCA
      basis and mixture (so that EM's sensitivity to rounding does not
      set the limit), its own solver: the relative Frobenius error of
      the test score matrix (`scores_rel_error_limit`) and the share of
      test images whose top-scoring class agrees
      (`top_class_agreement`)."""

import time

import jax
import numpy as np

from .. import probes
from .fit import _drop_the_last_fit, _spread


def _iteration(config, sizes, seed, train, test, evaluate, counters):
    """One fit and its apply. Returns (fit seconds, apply seconds, train
    mAP, test mAP, the fitted pipeline, test scores as numpy)."""
    _drop_the_last_fit()
    counters.mark()
    with probes.annotate("fit"):
        t0 = time.perf_counter()
        predictor = config.build(train, sizes, seed)
        train_map = evaluate(predictor(train.data), "train")
        fit_s = time.perf_counter() - t0
    counters.close("fit")
    fitted = predictor.fit()
    # the fit's own arrays (the cached grayscale images, the samples, the
    # features) go before the apply starts: both do not fit side by side
    del predictor
    _drop_the_last_fit()
    counters.close("between")
    with probes.annotate("apply"):
        t0 = time.perf_counter()
        out = fitted.apply(test.data)
        jax.block_until_ready(out.array)
        apply_s = time.perf_counter() - t0
    counters.close("apply")
    with probes.annotate("evaluate"):
        scores = np.asarray(out.numpy())
        test_map = evaluate(scores, "test")
    counters.close("evaluate")
    return fit_s, apply_s, train_map, test_map, fitted, scores


def compare(reference, sizes, seed, train, test, basis, mixture, scores):
    """The three comparisons of a fit (its PCA ``basis``, its
    ``mixture`` and its test ``scores``) with the plain reference: a
    dict of readings (`pca_angle`, `gmm_loglik_program`,
    `gmm_loglik_reference`, `scores_rel_error`, `top_class_agreement`,
    `reference_scores`)."""
    ref = reference.fit_and_score(train, test, sizes, seed, basis=basis,
                                  mixture=mixture)
    with jax.default_matmul_precision("highest"):
        own_basis = reference.pca_basis(ref.pop("pca_samples"),
                                        sizes["pca_dims"])
    samples = ref["gmm_samples"]
    own_mixture = reference.own_mixture(samples, sizes, seed)
    want = ref["scores"]
    return {
        "pca_angle": reference.largest_principal_angle(basis, own_basis),
        "gmm_loglik_program": float(
            reference.mean_log_likelihood(samples, *mixture)),
        "gmm_loglik_reference": float(
            reference.mean_log_likelihood(samples, *own_mixture)),
        "scores_rel_error": float(
            np.linalg.norm(scores - want) / np.linalg.norm(want)),
        "top_class_agreement": float(
            np.mean(scores.argmax(axis=1) == want.argmax(axis=1))),
        "reference_scores": want,
    }


def run(config, reference, sizes, traffic, seed, seconds, mesh, tracer=None,
        log=print):
    """Run the cell; returns the harness's record (see `benchmark.run`)."""
    from keystone_tpu.evaluation import MeanAveragePrecisionEvaluator
    from keystone_tpu.parallel.mesh import use_mesh

    evaluator = MeanAveragePrecisionEvaluator(
        sizes["num_classes"], multi_hot=True)
    counters = probes.PhaseCounters()
    with use_mesh(mesh):
        t0 = time.perf_counter()
        train, test = config.make_data(sizes, seed, mesh)
        jax.block_until_ready((train.data.array, test.data.array))
        labels = {"train": np.asarray(train.labels.numpy()),
                  "test": np.asarray(test.labels.numpy())}
        data_s = time.perf_counter() - t0

        def evaluate(scores, split):
            return float(evaluator(scores, labels[split]).mean())

        warmups = []
        for _ in range(2):  # cold (compiles or loads), then warm
            t0 = time.perf_counter()
            _iteration(config, sizes, seed, train, test, evaluate, counters)
            warmups.append(time.perf_counter() - t0)
        log({"phase": "setup", "data_s": data_s,
             "warmup_iteration_s": warmups})

        counters = probes.PhaseCounters()
        fit_s, apply_s, train_maps, test_maps = [], [], [], []
        fitted = scores = None
        window_start = time.perf_counter()
        deadline = window_start + seconds
        while True:
            if tracer is not None:
                tracer.at_boundary(len(fit_s))
            if fit_s and time.perf_counter() >= deadline and (
                    tracer is None or tracer.done):
                break
            fitted = scores = None  # two fits do not have to fit side by side
            f, a, train_map, test_map, fitted, scores = _iteration(
                config, sizes, seed, train, test, evaluate, counters)
            fit_s.append(f)
            apply_s.append(a)
            train_maps.append(train_map)
            test_maps.append(test_map)
        window_s = time.perf_counter() - window_start
        # the peak of the program's own fits and applies (and of the data):
        # read before the plain reference, which is the benchmark's, runs
        memory_peak_bytes = probes.memory_peak_bytes(mesh.devices.flat)

        # what the last fit learned, then the fit itself given up: the
        # reference needs the room
        basis, gmm, _ = config.fitted_parts(fitted)
        mixture = (gmm.means, gmm.variances, gmm.weights)
        # the samples' mean log-likelihood before each EM iteration
        em_trace = np.asarray(gmm.log_likelihood_trace).tolist()
        fitted = None
        _drop_the_last_fit()
        t0 = time.perf_counter()
        readings = compare(reference, sizes, seed, train, test, basis,
                           mixture, scores)
        reference_s = time.perf_counter() - t0
        reference_map = evaluate(readings.pop("reference_scores"), "test")

    lo, hi = sizes["map_band"]
    out_of_band = sum(1 for m in test_maps if not lo <= m <= hi)
    compiled = counters.total("dispatch.programs_compiled")
    cache_hits = counters.total("dispatch.compile_cache_hits")
    gap = abs(readings["gmm_loglik_program"]
              - readings["gmm_loglik_reference"])
    checks = {
        "every_fit_in_band": out_of_band == 0,
        "reference_in_band": lo <= reference_map <= hi,
        "nothing_compiled_in_window": compiled == 0 and cache_hits == 0,
        "pca_subspace_agrees":
            readings["pca_angle"] <= sizes["pca_angle_limit"],
        "gmm_loglik_agrees": gap <= sizes["gmm_loglik_gap_limit"],
        "scores_agree": readings["scores_rel_error"]
            <= sizes["scores_rel_error_limit"],
        "top_class_agrees": readings["top_class_agreement"]
            >= sizes["top_class_agreement"],
    }
    fits = len(fit_s)
    log({"phase": "window", "fits": fits, "window_s": window_s,
         "fit_s": _spread(fit_s), "apply_s": _spread(apply_s),
         "test_map": _spread(test_maps), "train_map": _spread(train_maps),
         "reference_s": reference_s, "reference_map": reference_map,
         **readings, "gmm_loglik_gap": gap, "gmm_em_trace": em_trace,
         "limits": {k: sizes[k] for k in (
             "map_band", "pca_angle_limit", "gmm_loglik_gap_limit",
             "scores_rel_error_limit", "top_class_agreement")},
         "checks": checks,
         "compiled_in_window": compiled, "cache_hits_in_window": cache_hits,
         "memory_peak_bytes": memory_peak_bytes})
    return {
        "correct": all(checks.values()),
        "attempted": fits,
        "failed": out_of_band,
        "window_start": window_start,
        "memory_peak_bytes": memory_peak_bytes,
        "end_to_end": {
            "fit_throughput": train.data.count * fits / sum(fit_s),
            "apply_throughput": test.data.count * fits / sum(apply_s),
        },
        "stats": {"fits": fits, "applies": fits, "window_s": window_s,
                  "train_rows_fitted": fits * train.data.count,
                  "sizes": sizes},
        "counters": counters.as_dict(),
    }
