"""Featurizer microbatch sweep: time the fused featurization (the
dominant pipeline stage) across microbatch sizes to pick the default.

One JSON line per point; fresh-valued inputs and a scalar-pull fence
(see data.dataset.sync_pull).

Usage: python scripts/featurize_sweep.py [--n 50000] [--filters 256]
       [--quick]  # tiny CPU smoke
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=50_000)
    p.add_argument("--filters", type=int, default=256)
    p.add_argument("--microbatches", type=int, nargs="+",
                   default=[1024, 2048, 4096, 8192])
    p.add_argument("--quick", action="store_true")
    args = p.parse_args()
    if os.environ.get("KEYSTONE_BACKEND") == "cpu" or args.quick:
        import jax

        jax.config.update("jax_platforms", "cpu")
        if args.quick:
            args.n, args.filters = 1024, 64
            args.microbatches = [256, 512]

    from bench import BENCH_CONFUSION, BENCH_NOISE
    from keystone_tpu.data.dataset import sync_pull
    from keystone_tpu.loaders.cifar_loader import synthetic_cifar
    from keystone_tpu.pipelines.random_patch_cifar import (
        RandomPatchCifarConfig,
        learn_filters,
        make_featurizer,
    )

    train, _ = synthetic_cifar(args.n, 64, noise=BENCH_NOISE,
                               confusion=BENCH_CONFUSION)
    config = RandomPatchCifarConfig(num_filters=args.filters)
    filters, whitener = learn_filters(train.data, config)
    h, w, c = train.data.array.shape[1:]
    rng = np.random.default_rng()
    best = None
    for mb in args.microbatches:
        feat = make_featurizer(filters, whitener, h, w, c, config,
                               microbatch=mb)

        def run_once():
            eps = float(rng.random()) * 1e-6
            d2 = train.data.map_batches(lambda x: x * (1.0 + eps)).sync()
            t0 = time.perf_counter()
            out = feat.apply_batch(d2)
            sync_pull(out.array)
            return time.perf_counter() - t0

        run_once()  # compile
        secs = min(run_once() for _ in range(3))
        row = {
            "microbatch": mb, "n": args.n, "filters": args.filters,
            "featurize_seconds": round(secs, 4),
            "images_per_sec": round(args.n / secs, 1),
        }
        print(json.dumps(row), flush=True)
        if best is None or secs < best[1]:
            best = (mb, secs)
    print(json.dumps({"best_microbatch": best[0],
                      "best_seconds": round(best[1], 4)}), flush=True)


if __name__ == "__main__":
    main()
