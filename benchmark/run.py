"""The benchmark's command: one process, one cell, one last line of JSON.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of the checkout. The cell's configuration, traffic and
per-layer metrics are found by name through `BENCHMARK.json`
(`benchmark.files`). Earlier lines of standard output are JSON records
of the run (set-up, window, readers); the last line is the result:
`correct`, `attempted`, `failed`, `metrics`, `device`, and `breakdown`
in a traced run. With `--trace 0` the metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics, read from counters and
from the profiler's trace of a few iterations of the window.

Off a TPU, with fewer chips than the cell asks for, or on a device whose
peaks are not in `benchmark.peaks`, it prints no result and exits 1."""

import time

_T0 = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from . import files  # noqa: E402


def emit(record):
    print(json.dumps(record, default=float), flush=True)


def measure(bench, cell_name, seed, seconds, trace, devices, sizes=None,
            log=emit):
    """Run one cell on ``devices`` and return the mode's record, with
    the trace's reduction under `trace` when ``trace`` is set. ``sizes``
    replaces the configuration file's (the tests rehearse tiny)."""
    from keystone_tpu.parallel.mesh import make_mesh

    from .tracing import WindowTracer

    cell = bench.cell(cell_name)
    traffic = bench.traffic(cell["traffic"])
    sizes = sizes if sizes is not None else bench.sizes(cell["config"])
    mode = files.module("modes", traffic["mode"])
    config = files.module("configs", cell["config"])
    reference = files.module("reference", cell["config"])
    mesh = make_mesh(list(devices)[:cell["chips"]])
    tracer = WindowTracer() if trace else None
    record = mode.run(config, reference, sizes, traffic, seed, seconds, mesh,
                      tracer=tracer, log=log)
    record["trace"] = tracer.reduction if tracer is not None else None
    return record


def layer_metrics(bench, cell_name, record, peaks, log=emit):
    """The cell's per-layer metrics, each by the reader its file names.
    A reader that finds nothing to read returns None and the metric is
    left out."""
    context = {"counters": record["counters"], "stats": record["stats"],
               "trace": record["trace"], "peaks": peaks}
    out = {}
    for metric in bench.metrics("per_layer", cell_name):
        spec = bench.reader_spec(metric["name"])
        reader = files.module("readers", spec["reader"])
        value = reader.read(context, **spec.get("args", {}))
        log({"phase": "reader", "metric": metric["name"],
             "reader": spec["reader"], "value": value,
             **context.pop("notes", {})})
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def end_to_end_metrics(bench, cell_name, record, setup_s):
    values = dict(record["end_to_end"], setup_s=setup_s)
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench.metrics("end_to_end", cell_name)}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench = files.BenchFiles()
    cell = bench.cell(args.workload)

    import jax

    from .peaks import peaks_for

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(f"benchmark.run: jax found platform {first.platform!r}, not a "
              "TPU; the benchmark measures the chip and nothing else",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"benchmark.run: cell {args.workload!r} needs {cell['chips']} "
              f"chip(s), jax found {len(devices)}", file=sys.stderr)
        return 1
    peaks = peaks_for(first.device_kind)

    record = measure(bench, args.workload, args.seed, args.seconds,
                     args.trace, devices)
    setup_s = record["window_start"] - _T0
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": bool(record["correct"]),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"])}
    if args.trace:
        trace = record["trace"]
        result["metrics"] = layer_metrics(bench, args.workload, record, peaks)
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["top_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        emit({"phase": "trace", "setup_s": setup_s,
              **{k: trace[k] for k in ("devices", "modules", "phases",
                                       "idle_by_phase_s")},
              "top_ops_by_phase": {
                  phase: list(entry["ops"].items())[:5]
                  for phase, entry in trace["by_phase"].items()}})
    else:
        result["metrics"] = end_to_end_metrics(
            bench, args.workload, record, setup_s)
    result["device"] = device
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
