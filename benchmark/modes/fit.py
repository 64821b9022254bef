"""Mode `fit`: repeated warm fits, each followed by one batch apply of
the test set, as `chip_smoke.fit_once` makes one (the lazy `Pipeline`
built on the train set, the evaluator on the train predictions, `fit()`
to a `FittedPipeline`, `apply`).

Set-up: the data from the seed, one cold iteration (it compiles, or
loads from the persistent cache) and one warm one. Window: iterations
until the seconds have passed. An iteration is one fit, timed from
`build` to the train error on the host, then one
`FittedPipeline.apply` of the test set timed behind
`jax.block_until_ready`, then the test accuracy, outside both timers.
After the window, outside every timer and after the peak memory has
been read: the plain reference's test predictions, for `correct`."""

import gc
import time

import jax
import numpy as np

from .. import probes


def _iteration(config, sizes, seed, train, test, evaluator, counters):
    """One fit and its apply. Returns (fit seconds, apply seconds,
    train error, test accuracy, test predictions as numpy)."""
    _drop_the_last_fit()
    counters.mark()
    with probes.annotate("fit"):
        t0 = time.perf_counter()
        predictor = config.build(train, sizes, seed)
        train_metrics = evaluator(predictor(train.data), train.labels)
        fit_s = time.perf_counter() - t0
    counters.close("fit")
    fitted = predictor.fit()
    counters.close("between")
    with probes.annotate("apply"):
        t0 = time.perf_counter()
        out = fitted.apply(test.data)
        jax.block_until_ready(out.array)
        apply_s = time.perf_counter() - t0
    counters.close("apply")
    with probes.annotate("evaluate"):
        accuracy = float(evaluator(out, test.labels).accuracy)
        preds = np.asarray(out.numpy())
    counters.close("evaluate")
    return fit_s, apply_s, float(train_metrics.error), accuracy, preds


def _drop_the_last_fit():
    """The last fit's pipeline objects refer to each other, so its device
    buffers wait for the cycle collector: without this the third fit in
    a row found no room for its centred copy (my chip run, PR 24)."""
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.reset()
    gc.collect()


def run(config, reference, sizes, traffic, seed, seconds, mesh, tracer=None,
        log=print):
    """Run the cell; returns the harness's record (see `benchmark.run`)."""
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.parallel.mesh import use_mesh

    evaluator = MulticlassClassifierEvaluator(sizes["num_classes"])
    counters = probes.PhaseCounters()
    with use_mesh(mesh):
        t0 = time.perf_counter()
        train, test = config.make_data(sizes, seed, mesh)
        jax.block_until_ready((train.data.array, test.data.array))
        data_s = time.perf_counter() - t0
        warmups = []
        for _ in range(2):  # cold (compiles or loads), then warm
            t0 = time.perf_counter()
            _iteration(config, sizes, seed, train, test, evaluator, counters)
            warmups.append(time.perf_counter() - t0)
        log({"phase": "setup", "data_s": data_s,
             "warmup_iteration_s": warmups})

        counters = probes.PhaseCounters()
        fit_s, apply_s, train_errors, accuracies = [], [], [], []
        preds = None
        window_start = time.perf_counter()
        deadline = window_start + seconds
        while True:
            if tracer is not None:
                tracer.at_boundary(len(fit_s))
            if fit_s and time.perf_counter() >= deadline and (
                    tracer is None or tracer.done):
                break
            preds = None  # two fits' outputs do not have to fit side by side
            f, a, err, acc, preds = _iteration(
                config, sizes, seed, train, test, evaluator, counters)
            fit_s.append(f)
            apply_s.append(a)
            train_errors.append(err)
            accuracies.append(acc)
        window_s = time.perf_counter() - window_start
        # the peak of the program's own fits and applies (and of the data):
        # read before the plain reference, which is the benchmark's, runs
        memory_peak_bytes = probes.memory_peak_bytes(mesh.devices.flat)

        _drop_the_last_fit()
        t0 = time.perf_counter()
        want = reference.predict(train, test, sizes, seed)
        reference_s = time.perf_counter() - t0
        labels = np.asarray(test.labels.numpy())
        reference_accuracy = float(np.mean(want == labels))

    lo, hi = sizes["accuracy_band"]
    out_of_band = sum(1 for acc in accuracies if not lo <= acc <= hi)
    agreement = float(np.mean(preds == want))
    compiled = counters.total("dispatch.programs_compiled")
    cache_hits = counters.total("dispatch.compile_cache_hits")
    checks = {
        "every_fit_in_band": out_of_band == 0,
        "agrees_with_reference": agreement >= sizes["reference_agreement"],
        "reference_in_band": lo <= reference_accuracy <= hi,
        "nothing_compiled_in_window": compiled == 0 and cache_hits == 0,
    }
    fits = len(fit_s)
    log({"phase": "window", "fits": fits, "window_s": window_s,
         "fit_s": _spread(fit_s), "apply_s": _spread(apply_s),
         "test_accuracy": _spread(accuracies),
         "train_error": _spread(train_errors),
         "reference_s": reference_s,
         "reference_accuracy": reference_accuracy,
         "reference_agreement": agreement, "checks": checks,
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
                  "sizes": sizes},
        "counters": counters.as_dict(),
    }


def _spread(values):
    v = np.asarray(values, float)
    return {"n": int(v.size), "median": float(np.median(v)),
            "p95": float(np.percentile(v, 95)), "min": float(v.min()),
            "max": float(v.max())}
