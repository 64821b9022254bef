"""Chip smoke: RandomPatchCifar fit, apply and serve on the TPU, through
the entry points a user calls, in one process.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the mesh path, and only that

The model is RandomPatchCifar at its defaults (256 filters of 6x6, pool
14 stride 13, block 4096: d = 2048 features) on the synthetic CIFAR
task of the benchmark's cell `cifar_fit`, 50,000 train and 10,000 test
images made from ``--seed``. Phases run in order and the first thing
that fails ends the run: nothing here catches an exception and carries
on. Every phase prints one JSON line; the last line of standard output is
``{"ok": true, "device": {...}}`` with the device as jax reports it, and
it is printed only when every phase passed.

The phases are functions of sizes and a mesh, so a test can rehearse
them tiny on the CPU (`tests/test_chip_smoke.py`). Only `main()` holds
the platform check and the exit code: the script itself passes nowhere
but on a TPU.
"""

import argparse
import contextlib
import json
import sys
import threading
import time

import numpy as np

#: agreement gate of one microbatch through the kernel against the XLA
#: reference on the same chip: `scripts/kernel_live_check.py`'s
KERNEL_REL_TOL = 2e-3
#: share of test predictions a mesh fit must share with the one-device fit
MESH_AGREEMENT = 0.99
#: the synthetic task: `synthetic_cifar`'s pixel noise and the share of a
#: confusable class mixed into each image, the values that
#: `benchmark/configs/random_patch_cifar.json` states under ``assumed``
#: (`tests/test_chip_smoke.py` holds the two files to each other)
TASK_NOISE = 1.2
TASK_CONFUSION = 0.6
#: least test accuracy a fit may read: the lower end of the cell's
#: ``accuracy_band``, which 256 filters on 50,000 images clear as 10,000
#: filters on 8,192 do (0.8293 on the chip, `PERF.md` section 6, PR 22)
MIN_ACCURACY = 0.72


def emit(record):
    print(json.dumps(record), flush=True)


def _fence(x):
    import jax

    return jax.block_until_ready(x)


def _compiles():
    from keystone_tpu.telemetry.compile_events import compiles_snapshot
    from keystone_tpu.telemetry.metrics import counter

    snap = compiles_snapshot()
    return {
        "compiled": snap["programs_compiled"],
        "cache_hits": snap["compile_cache_hits"],
        "executed": int(counter("dispatch.programs_executed").value),
    }


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


def make_data(n_train, n_test, seed, mesh):
    """The synthetic task (`TASK_NOISE`, `TASK_CONFUSION`), placed on
    ``mesh``."""
    from keystone_tpu.loaders.cifar_loader import synthetic_cifar

    return synthetic_cifar(
        n_train, n_test, seed=seed, mesh=mesh,
        noise=TASK_NOISE, confusion=TASK_CONFUSION)


def fit_once(train, test, config, mesh):
    """One fit as `python -m keystone_tpu
    pipelines.images.cifar.RandomPatchCifar` makes it
    (`random_patch_cifar.run`): `build_pipeline`, then the lazy
    `Pipeline` applied to train and test under the evaluator. Returns
    (seconds to the train metrics, pipeline, train metrics, test
    metrics, test predictions)."""
    from keystone_tpu.evaluation import MulticlassClassifierEvaluator
    from keystone_tpu.parallel.mesh import use_mesh
    from keystone_tpu.pipelines.random_patch_cifar import build_pipeline
    from keystone_tpu.workflow import PipelineEnv

    PipelineEnv.reset()
    evaluator = MulticlassClassifierEvaluator(config.num_classes)
    with use_mesh(mesh):
        t0 = time.perf_counter()
        predictor = build_pipeline(train, config)
        train_metrics = evaluator(predictor(train.data), train.labels)
        seconds = time.perf_counter() - t0
        test_out = predictor(test.data).get()
        test_metrics = evaluator(test_out, test.labels)
    return (seconds, predictor, train_metrics, test_metrics,
            np.asarray(test_out.numpy()))


def phase_fit(train, test, config, mesh, min_accuracy):
    """Fit twice, cold then warm (a reset `PipelineEnv` refits every
    estimator; the programs are compiled by then). Returns the record
    and the second fit's pipeline and test predictions."""
    before = _compiles()
    cold_s, _, _, cold_test, _ = fit_once(train, test, config, mesh)
    mid = _compiles()
    warm_s, predictor, train_m, test_m, preds = fit_once(
        train, test, config, mesh)
    after = _compiles()
    for name, acc in (("cold", cold_test.accuracy), ("warm", test_m.accuracy)):
        if not np.isfinite(acc) or acc < min_accuracy:
            raise AssertionError(
                f"{name} fit: test accuracy {acc:.4f} is below "
                f"{min_accuracy}")
    record = {
        "phase": "fit", "n_train": train.data.count,
        "n_test": test.data.count, "cold_seconds": cold_s,
        "warm_seconds": warm_s, "train_error": float(train_m.error),
        "test_accuracy": float(test_m.accuracy),
        "cold": _delta(before, mid), "warm": _delta(mid, after),
    }
    return record, predictor, preds


def phase_apply(predictor, test, mesh, reps=5):
    """`Pipeline.fit()` to a `FittedPipeline`, then apply it to the test
    set: a first pass, then a second that must compile nothing. The
    warm apply is then timed ``reps`` times behind each of the two
    fences (`jax.block_until_ready` on the result, and
    `data.dataset.sync_pull`'s one-element pull), alternating."""
    from keystone_tpu.data.dataset import sync_pull
    from keystone_tpu.parallel.mesh import use_mesh

    def timed(fence):
        t0 = time.perf_counter()
        out = fitted.apply(test.data)
        fence(out.array)
        return time.perf_counter() - t0, out

    with use_mesh(mesh):
        fitted = predictor.fit()
        before = _compiles()
        cold_s, first = timed(_fence)
        mid = _compiles()
        warm_s, second = timed(_fence)
        after = _compiles()
        warm = _delta(mid, after)
        if warm["compiled"] or warm["cache_hits"]:
            raise AssertionError(
                f"the second apply asked for a compile: {warm}")
        if warm["executed"] < 1:
            raise AssertionError("the second apply executed no program")
        batch_preds = np.asarray(second.numpy())
        if not np.array_equal(batch_preds, np.asarray(first.numpy())):
            raise AssertionError("two applies of one input disagree")
        fences = {"block_until_ready": [], "sync_pull": []}
        for _ in range(reps):
            fences["block_until_ready"].append(timed(_fence)[0])
            fences["sync_pull"].append(timed(sync_pull)[0])
    record = {
        "phase": "apply", "n": test.data.count, "cold_seconds": cold_s,
        "warm_seconds": warm_s, "cold": _delta(before, mid), "warm": warm,
        "warm_seconds_by_fence": fences,
    }
    return record, fitted, batch_preds


def phase_kernel(config, seed, n=2048):
    """One ``n``-image microbatch through the `conv_rectify_pool`
    dispatcher against `conv_rectify_pool_reference`, both compiled and
    run here, and the chain family of the same geometry
    (`rectify_pool_vectorize`) against its reference. On a TPU the
    dispatchers must have taken their kernels: the canary verdicts are
    True and the compiled programs hold a ``tpu_custom_call``. Anywhere
    else the dispatchers are the reference path, and the record says
    so."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.ops import (
        chain_kernels,
        conv_rectify_pool,
        conv_rectify_pool_reference,
        pallas_kernels,
    )

    on_tpu = jax.default_backend() == "tpu"
    h = w = 32
    c, k, p = 3, config.num_filters, config.patch_size
    pool, stride, alpha = config.pool_size, config.pool_stride, config.alpha
    rng = np.random.default_rng(seed)
    images = jnp.asarray(rng.random((n, h, w, c)).astype(np.float32))
    kern = jnp.asarray(rng.normal(size=(p, p, c, k)).astype(np.float32))
    colsum = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))

    def run(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        t0 = time.perf_counter()
        out = np.asarray(compiled(*args))
        return out, time.perf_counter() - t0, "tpu_custom_call" in compiled.as_text()

    def rel_err(got, want):
        if not np.isfinite(got).all():
            raise AssertionError("kernel output is not finite")
        return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-12))

    conv_args = (images, kern, colsum, bias)
    got, conv_s, conv_custom = run(
        lambda x, g, cs, b: conv_rectify_pool(
            x, g, cs, b, alpha, 0.0, pool, stride, True), *conv_args)
    want, ref_s, _ = run(
        lambda x, g, cs, b: conv_rectify_pool_reference(
            x, g, cs, b, alpha, 0.0, pool, stride, True), *conv_args)
    conv_err = rel_err(got, want)

    pos = h - p + 1
    acts = jnp.asarray(rng.standard_normal((n, pos, pos, k)).astype(np.float32))
    chain_got, chain_s, chain_custom = run(
        lambda x: chain_kernels.rectify_pool_vectorize(
            x, alpha, 0.0, pool, stride), acts)
    chain_want, _, _ = run(
        lambda x: chain_kernels.rectify_pool_vectorize_reference(
            x, alpha, 0.0, pool, stride), acts)
    chain_err = rel_err(chain_got, chain_want)

    conv_key = (h, w, c, k, pool, stride, True, p)
    chain_key = ("rectify_pool_vectorize", pos, pos, k, pool, stride)
    verdicts = {
        "fused_conv": pallas_kernels._fused_conv_canary.get(conv_key),
        "rectify_pool_vectorize": chain_kernels._chain_canary.get(chain_key),
    }
    for name, err in (("fused_conv", conv_err),
                      ("rectify_pool_vectorize", chain_err)):
        if err >= KERNEL_REL_TOL:
            raise AssertionError(
                f"{name}: max relative error {err:.2e} against the "
                f"reference is not under {KERNEL_REL_TOL}")
    if on_tpu:
        if verdicts != {"fused_conv": True, "rectify_pool_vectorize": True}:
            raise AssertionError(f"canary verdicts on a TPU: {verdicts}")
        if not (conv_custom and chain_custom):
            raise AssertionError(
                "a dispatcher's compiled program holds no tpu_custom_call: "
                f"fused_conv={conv_custom}, "
                f"rectify_pool_vectorize={chain_custom}")
    return {
        "phase": "kernel", "n": n, "backend": jax.default_backend(),
        "verdicts": verdicts,
        "all_verdicts": {
            "fused_conv": {
                repr(key): v
                for key, v in pallas_kernels._fused_conv_canary.items()},
            "chain": {
                repr(key): v
                for key, v in chain_kernels._chain_canary.items()},
        },
        "tpu_custom_call": {"fused_conv": conv_custom,
                            "rectify_pool_vectorize": chain_custom},
        "max_rel_err": {"fused_conv": conv_err,
                        "rectify_pool_vectorize": chain_err},
        "run_seconds": {"fused_conv": conv_s, "fused_conv_reference": ref_s,
                        "rectify_pool_vectorize": chain_s},
    }


def phase_serve(fitted, test, batch_preds, mesh, n_requests=64, n_clients=8,
                max_batch=8, slo_seconds=1.0):
    """`ServingRuntime.start()` (certify, arm, warm, hand off), then
    ``n_requests`` single-image requests from ``n_clients`` threads
    through `submit()`. Every answer must equal the batch apply's row,
    and nothing may compile after `start()` returns."""
    from keystone_tpu.analysis import ServingEnvelope
    from keystone_tpu.parallel.mesh import use_mesh
    from keystone_tpu.serving import NdarrayIngress, ServingRuntime
    from keystone_tpu.telemetry.watchdog import active_watchdog

    images = np.asarray(test.data.numpy()[:n_requests], np.float32)
    answers = [None] * n_requests
    latencies = [None] * n_requests

    def client(first):
        for i in range(first, n_requests, n_clients):
            t0 = time.perf_counter()
            answers[i] = rt.submit(images[i], timeout=120.0)
            latencies[i] = time.perf_counter() - t0

    with use_mesh(mesh):
        rt = ServingRuntime(
            fitted, NdarrayIngress(images.shape[1:]),
            envelope=ServingEnvelope(max_batch=max_batch,
                                     slo_seconds=slo_seconds),
            name="chip-smoke")
        t0 = time.perf_counter()
        rt.start()
        try:
            start_s = time.perf_counter() - t0
            before = _compiles()
            threads = [threading.Thread(target=client, args=(j,))
                       for j in range(n_clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
            serve_s = time.perf_counter() - t0
            if any(t.is_alive() for t in threads):
                raise AssertionError("a serving client did not finish")
            after = _compiles()
            stats = rt.stats()
            watchdog = active_watchdog()
            digest = watchdog.describe() if watchdog is not None else None
        finally:
            rt.stop()
    missing = [i for i, a in enumerate(answers) if a is None]
    if missing:
        raise AssertionError(f"requests without an answer: {missing[:8]}")
    wrong = [i for i, a in enumerate(answers)
             if not np.array_equal(np.asarray(a), batch_preds[i])]
    if wrong:
        raise AssertionError(
            f"{len(wrong)} served answers differ from the batch apply's "
            f"rows, first at request {wrong[0]}")
    served = _delta(before, after)
    if served["compiled"] or served["cache_hits"]:
        raise AssertionError(
            f"serving compiled after start(): {served}")
    if stats["dispatched_outside_ladder"]:
        raise AssertionError(
            "dispatched outside the certified ladder: "
            f"{stats['dispatched_outside_ladder']}")
    return {
        "phase": "serve", "requests": n_requests, "clients": n_clients,
        "start_seconds": start_s, "serve_seconds": serve_s,
        "request_seconds_median": float(np.median(latencies)),
        "request_seconds_max": float(np.max(latencies)),
        "after_start": served, "certified": stats["certified"],
        "warmed_sites": stats["warmed_sites"], "ladder": stats["ladder"],
        "dispatched_shapes": stats["dispatched_shapes"],
        "watchdog": digest,
    }


def _check_shards(array, mesh, what):
    """Every device of ``mesh`` holds its own share of ``array``: code
    that has only seen virtual devices may put everything on the
    first."""
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    if devices != set(mesh.devices.flat):
        raise AssertionError(
            f"{what}: shards on {len(devices)} devices, mesh has "
            f"{mesh.devices.size}")
    spec = array.sharding.spec
    parts = 1
    for axis in spec:
        for name in ((axis,) if isinstance(axis, str) else (axis or ())):
            parts *= mesh.shape[name]
    want = int(np.prod(array.shape)) // parts
    sizes = {int(np.prod(s.data.shape)) for s in shards}
    if parts < 2 or sizes != {want}:
        raise AssertionError(
            f"{what}: spec {spec} splits the array {parts} ways; shard "
            f"sizes {sorted(sizes)}, expected {want} each")
    return {"spec": str(spec), "parts": parts,
            "shard_shape": list(shards[0].data.shape)}


def phase_mesh(n_train, n_test, config, seed, devices, min_accuracy):
    """The mesh path on ``devices`` (four chips, or four virtual ones in
    the rehearsal): the same fit on a ``(n,)`` ``data`` mesh and on an
    ``(n/2, 2)`` ``data`` x ``model`` mesh against the same fit on the
    first device alone, the solver matrix of `__graft_entry__`, and a
    check that every device holds its share of the images and of the
    features. Each fit prints its line as it ends, so a later failure
    loses nothing; the record returned holds them all."""
    import __graft_entry__
    from keystone_tpu.parallel.mesh import make_mesh, use_mesh
    from keystone_tpu.pipelines.random_patch_cifar import (
        learn_filters,
        make_featurizer,
    )

    n = len(devices)
    meshes = {
        "one_device": make_mesh(devices[:1]),
        "data": make_mesh(devices, shape=(n,), axis_names=("data",)),
        "data_model": make_mesh(devices, shape=(n // 2, 2),
                                axis_names=("data", "model")),
    }
    fits, one_preds = {}, None
    for name, mesh in meshes.items():
        train, test = make_data(n_train, n_test, seed, mesh)
        seconds, _, _, test_m, preds = fit_once(train, test, config, mesh)
        acc = float(test_m.accuracy)
        if not np.isfinite(acc) or acc < min_accuracy:
            raise AssertionError(
                f"fit on mesh {dict(mesh.shape)}: test accuracy "
                f"{acc:.4f} is below {min_accuracy}")
        fits[name] = {"mesh": dict(mesh.shape), "seconds": seconds,
                      "test_accuracy": acc}
        if name == "one_device":
            one_preds = preds
        else:
            agreement = float(np.mean(preds == one_preds))
            if agreement < MESH_AGREEMENT:
                raise AssertionError(
                    f"fit on mesh {dict(mesh.shape)} agrees with the "
                    f"one-device fit on {agreement:.2%} of test predictions")
            with use_mesh(mesh):
                filters, whitener = learn_filters(train.data, config)
                h, w, c = train.data.array.shape[1:]
                feats = make_featurizer(
                    filters, whitener, h, w, c, config).apply_batch(train.data)
                shards = {
                    "images": _check_shards(train.data.array, mesh, "images"),
                    "features": _check_shards(feats.array, mesh, "features"),
                }
            fits[name].update(agreement=agreement, shards=shards)
        emit({"phase": "mesh_fit", "name": name, **fits[name]})

    with contextlib.redirect_stdout(sys.stderr):  # it prints as it goes
        cells = __graft_entry__._solver_matrix(devices)
    return {"phase": "mesh", "devices": n, "n_train": n_train,
            "n_test": n_test, "fits": fits,
            "solver_matrix": cells}


def report(device):
    """What the process holds at the end: the compile cache in effect
    with its hits and misses, device memory, the native loader."""
    from keystone_tpu.utils import native_io
    from keystone_tpu.workflow.env import execution_config

    total = _compiles()
    stats = device.memory_stats() or {}
    return {
        "phase": "report",
        "compile_cache_dir": execution_config().compile_cache_dir,
        "compile_cache_hits": total["cache_hits"],
        "compile_cache_misses": total["compiled"],
        "programs_executed": total["executed"],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "native_io_available": native_io.available(),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs the mesh phase on four chips and no other")
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(f"chip_smoke: jax found platform {first.platform!r}, not a "
              "TPU; this script passes nowhere else", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from keystone_tpu.parallel.mesh import make_mesh
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
    )

    config = RandomPatchCifarConfig(seed=args.seed)
    n_train, n_test = 50_000, 10_000
    emit({"phase": "start", "seed": args.seed, "chips": args.chips,
          "platform": first.platform, "kind": first.device_kind,
          "count": len(devices), "jax": jax.__version__})

    if args.chips == 4:
        emit(phase_mesh(n_train, n_test, config, args.seed, devices[:4],
                        MIN_ACCURACY))
    else:
        mesh = make_mesh(devices[:1])
        t0 = time.perf_counter()
        train, test = make_data(n_train, n_test, args.seed, mesh)
        _fence((train.data.array, test.data.array))
        emit({"phase": "data", "seconds": time.perf_counter() - t0})
        record, predictor, _ = phase_fit(train, test, config, mesh,
                                         MIN_ACCURACY)
        emit(record)
        record, fitted, batch_preds = phase_apply(predictor, test, mesh)
        emit(record)
        emit(phase_kernel(config, args.seed))
        emit(phase_serve(fitted, test, batch_preds, mesh))
    emit(report(first))
    emit({"ok": True, "device": {"platform": first.platform,
                                 "kind": first.device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
