"""Pipeline launcher (the reference's bin/run-pipeline.sh: class name +
flags → spark-submit; here: pipeline name + flags → the app's argparse
main, reference bin/run-pipeline.sh:1-55).

    python -m keystone_tpu pipelines.images.cifar.RandomPatchCifar --num-filters 256
    python -m keystone_tpu MnistRandomFFT --num-ffts 4

Names accept the reference's fully-qualified form or the bare class name.
"""

from __future__ import annotations

import importlib
import sys

#: reference class name -> (module, main callable name)
REGISTRY = {
    "pipelines.images.mnist.MnistRandomFFT": ("keystone_tpu.pipelines.mnist_random_fft", "main"),
    "pipelines.images.cifar.RandomPatchCifar": ("keystone_tpu.pipelines.random_patch_cifar", "main"),
    "pipelines.images.cifar.LinearPixels": ("keystone_tpu.pipelines.cli_mains", "linear_pixels_main"),
    "pipelines.images.cifar.RandomCifar": ("keystone_tpu.pipelines.cli_mains", "random_cifar_main"),
    "pipelines.images.cifar.RandomPatchCifarKernel": ("keystone_tpu.pipelines.cli_mains", "cifar_kernel_main"),
    "pipelines.images.cifar.RandomPatchCifarAugmented": ("keystone_tpu.pipelines.cli_mains", "cifar_augmented_main"),
    "pipelines.images.cifar.RandomPatchCifarAugmentedKernel": ("keystone_tpu.pipelines.cli_mains", "cifar_augmented_kernel_main"),
    "pipelines.images.voc.VOCSIFTFisher": ("keystone_tpu.pipelines.voc_sift_fisher", "main"),
    "pipelines.images.imagenet.ImageNetSiftLcsFV": ("keystone_tpu.pipelines.imagenet_sift_lcs_fv", "main"),
    "pipelines.speech.TimitPipeline": ("keystone_tpu.pipelines.timit", "main"),
    "pipelines.text.NewsgroupsPipeline": ("keystone_tpu.pipelines.cli_mains", "newsgroups_main"),
    "pipelines.text.AmazonReviewsPipeline": ("keystone_tpu.pipelines.cli_mains", "amazon_main"),
    "pipelines.nlp.StupidBackoffPipeline": ("keystone_tpu.pipelines.cli_mains", "stupid_backoff_main"),
}

_SHORT = {name.rsplit(".", 1)[-1]: v for name, v in REGISTRY.items()}


def _pop_multihost_flags(argv):
    """Launcher-level multi-host flags (≈ the reference launcher's
    cluster args living outside the app's own scopt flags):

        python -m keystone_tpu --coordinator host:port --num-processes 4 \\
            --process-id $I pipelines.images.cifar.RandomPatchCifar ...
    """
    names = ("--coordinator", "--num-processes", "--process-id")
    opts, rest = {}, []
    it = iter(argv)
    for a in it:
        flag, eq, inline = a.partition("=")
        if flag in names:
            val = inline if eq else next(it, None)
            if not val:
                raise SystemExit(f"{flag} requires a value")
            opts[flag.lstrip("-").replace("-", "_")] = val
        else:
            rest.append(a)
    if opts:
        if "coordinator" not in opts:
            raise SystemExit(
                "--num-processes/--process-id require --coordinator "
                "(single-host runs need none of these flags)"
            )
        from .parallel import init_multihost

        init_multihost(
            coordinator_address=opts["coordinator"],
            num_processes=(
                int(opts["num_processes"]) if "num_processes" in opts else None
            ),
            process_id=int(opts["process_id"]) if "process_id" in opts else None,
        )
    return rest


def _normalize_flags(argv):
    """Accept the reference apps' scopt camelCase flags verbatim:
    `--numFFTs 4 --blockSize 2048` → `--num-ffts 4 --block-size 2048`
    (the reference CLI contract, e.g. MnistRandomFFT.scala:80-97)."""
    import re

    out = []
    for a in argv:
        if a.startswith("--"):
            flag, eq, val = a.partition("=")
            flag = re.sub(r"(?<=[a-z0-9])(?=[A-Z])", "-", flag).lower()
            a = flag + eq + val
        out.append(a)
    return out


def _pop_backend_flag(argv):
    """`--backend tpu|cpu` anywhere on the command line (the north-star
    launcher contract: run-pipeline.sh --backend=tpu) → KEYSTONE_BACKEND."""
    import os

    out = []
    it = iter(argv)
    for a in it:
        flag, eq, inline = a.partition("=")
        if flag == "--backend":
            val = inline if eq else next(it, None)
            if not val:
                raise SystemExit("--backend requires a value (tpu|cpu)")
            os.environ["KEYSTONE_BACKEND"] = val
        else:
            out.append(a)
    return out


def _apply_backend_env():
    """Honor KEYSTONE_BACKEND/KEYSTONE_CPU_DEVICES.

    ``cpu`` is applied through jax.config before any backend initializes
    (the conftest uses the same pattern for the test mesh). ``tpu``
    changes nothing and checks: the run fails unless the device jax
    finds is a TPU, so a run that asked for the chip never carries on
    somewhere else."""
    import os

    backend = os.environ.get("KEYSTONE_BACKEND")
    if backend is None:
        return
    import jax

    if backend == "cpu":
        jax.config.update("jax_platforms", "cpu")
        n = os.environ.get("KEYSTONE_CPU_DEVICES")
        if n:
            jax.config.update("jax_num_cpu_devices", int(n))
    elif backend == "tpu":
        found = jax.devices()[0].platform
        if found != "tpu":
            raise SystemExit(
                f"--backend tpu: jax found platform {found!r}, not a TPU")
    else:
        raise SystemExit(
            f"--backend must be tpu or cpu, got {backend!r}")


def main(argv=None):
    argv = _pop_backend_flag(list(sys.argv[1:] if argv is None else argv))
    _apply_backend_env()
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print("Available pipelines:")
        for name in sorted(REGISTRY):
            print(f"  {name}")
        return 0
    argv = _pop_multihost_flags(argv)
    name, rest = argv[0], _normalize_flags(argv[1:])
    entry = REGISTRY.get(name) or _SHORT.get(name)
    if entry is None:
        print(f"unknown pipeline {name!r}; run with --help to list", file=sys.stderr)
        return 2
    module, fn_name = entry
    fn = getattr(importlib.import_module(module), fn_name)
    fn(rest)
    return 0


if __name__ == "__main__":
    sys.exit(main())
