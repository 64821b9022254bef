#!/usr/bin/env bash
# Pipeline launcher (mirrors the reference bin/run-pipeline.sh: class
# name + flags -> JVM/spark-submit; here -> python -m keystone_tpu).
#
#   ./bin/run-pipeline.sh pipelines.images.cifar.RandomPatchCifar --num-filters 256
#
#   ./bin/run-pipeline.sh --backend=tpu pipelines.speech.TimitPipeline ...
#
# Flags:
#   --backend tpu|cpu          (anywhere on the line; also via env
#                               KEYSTONE_BACKEND)
# Env:
#   KEYSTONE_CPU_DEVICES=N     (virtual device count when backend=cpu)
set -euo pipefail
REPO_DIR="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="${REPO_DIR}${PYTHONPATH:+:$PYTHONPATH}"

# KEYSTONE_BACKEND and KEYSTONE_CPU_DEVICES are read inside
# keystone_tpu.__main__: cpu is applied through jax.config, and tpu
# fails the run unless the device jax finds is a TPU.

exec python -m keystone_tpu "$@"
