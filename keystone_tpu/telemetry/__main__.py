"""Trace summary + decision ledger CLI.

    python -m keystone_tpu.telemetry run.json [--top N] [--json]
    python -m keystone_tpu.telemetry --ledger <run> [--json]
    python -m keystone_tpu.telemetry --ledger <run> --emit-calibration <path>
    python -m keystone_tpu.telemetry --diff <run_a> <run_b> [--json]
    python -m keystone_tpu.telemetry --flight <dump> [--top N] [--json]
    python -m keystone_tpu.telemetry --live [--json]
    python -m keystone_tpu.telemetry device <trace dir or .xplane.pb>

The trace form prints the span digest (top nodes by self-time, solver
iteration and stream-chunk totals), overlap queue-stall totals, bytes
moved, and — when the trace carries the static analyzer's estimates —
the static-vs-observed memory reconciliation table that calibrates the
KP2xx model.

``--ledger`` renders a run's decision ledger (a ``KEYSTONE_LEDGER``
JSONL file or a trace whose metadata embeds the decisions): one row per
optimizer decision — chosen entry, best-priced runner-up, predicted
cost — joined, when the run's trace is reachable, with the observed
values and residuals (`analysis.reconcile.reconcile_decisions`) plus
the cost-model drift report (`cost_model_drift`).

``--emit-calibration`` (with ``--ledger``) closes the
trace-bytes-in/plan-out loop: the run's cost-model drift report is
persisted as a ``tpu_calibration.json``-schema file
(`reconcile.drift_cost_weights` → `calibrate.write_calibration`), and
pointing ``KEYSTONE_COST_CALIBRATION`` at it makes
`calibrate.machine_rates()` — hence every roofline classification and
every unified-planner menu price — prefer the trace-implied rates
whenever the recorded platform matches the live backend.

``--flight`` renders a flight-recorder dump (`flight.flight_snapshot`
/ SIGUSR2 / a watchdog breach artifact): the ring-window header
(capacity, spans held, evictions, in-flight-at-dump count) followed by
the ordinary trace digest — a dump IS a Chrome trace, so every other
consumer (``--ledger``, reconcile, ``perf_table.py --trace``) accepts
it unchanged.

``--live`` renders this process's live-health view
(`streaming.health`): per-(pipeline, padded-shape) apply-latency
percentiles from the streaming sketches, throughput, in-flight depth,
conformance check/breach counters, and the armed watchdog's
certificate digest. (Meaningful in-process — e.g. from a serving
wrapper's debug hook; a fresh CLI process reports an empty table.)

``device`` reads a `jax.profiler` trace (not a Chrome trace of the host
tracer) by the program's own names: ``ks:`` spans with the device time
under each, device seconds per ``ks.`` scope, and the longest idle gaps
named by the span open in them (`telemetry.device`).

``--diff`` is run-over-run regression detection between two runs'
ledgers: config kill-switch flips are named by env var (an injected
``KEYSTONE_MEGAFUSION=0`` reads as exactly that), removed/added
decisions, prediction drift, and observed regressions from the two
reconciliations. Exit code 1 when any regression is reported — the
lint-gate contract (a run diffed against itself exits 0).

See OBSERVABILITY.md; rule catalog in ANALYSIS.md.
"""

from __future__ import annotations

import argparse
import json
import sys

from .export import aggregate_spans, load_trace, summarize


def _read_run(path: str):
    from .ledger import read_ledger

    try:
        return read_ledger(path)
    except (OSError, ValueError) as e:
        print(f"error: {path}: {e}", file=sys.stderr)
        return None


def _reconcile(run):
    if not run.get("trace"):
        return None
    try:
        from ..analysis.reconcile import reconcile_decisions

        return reconcile_decisions(run)
    except Exception:
        return None


def _emit_calibration(run, out_path: str, ledger_path: str) -> int:
    """Persist the run's drift-implied `CostWeights` in the
    ``tpu_calibration.json`` schema (the `machine_rates` round-trip)."""
    if not run.get("trace"):
        print("error: --emit-calibration needs a run whose trace "
              "artifact is reachable (the drift report is computed "
              "from observed span timings)", file=sys.stderr)
        return 2
    from ..analysis.reconcile import drift_cost_weights
    from ..nodes.learning.calibrate import write_calibration

    weights = drift_cost_weights(run["trace"])
    provenance = {"source": "drift_cost_weights", "ledger": ledger_path}
    # the weights are implied by the TRACED run's measurements: its
    # recorded platform owns the provenance — emitting from a
    # different host must not relabel TPU-implied weights as CPU ones
    run_platform = (run.get("header") or {}).get("platform")
    assumed = ""
    if run_platform:
        provenance["platform"] = run_platform
    else:
        assumed = (" [platform assumed from THIS host — the run's "
                   "ledger predates the header platform field]")
    payload = write_calibration(out_path, weights, provenance=provenance)
    print(f"wrote {out_path}: cpu_weight={payload['cpu_weight']:.3e} "
          f"mem_weight={payload['mem_weight']:.3e} "
          f"(platform={payload['provenance'].get('platform')}{assumed}); "
          "point KEYSTONE_COST_CALIBRATION at it to recalibrate "
          "machine_rates()")
    return 0


def _ledger_main(path: str, as_json: bool,
                 emit_calibration: str = None) -> int:
    from .ledger import render_ledger

    run = _read_run(path)
    if run is None:
        return 2
    if emit_calibration:
        return _emit_calibration(run, emit_calibration, path)
    rec = _reconcile(run)
    drift = None
    if run.get("trace"):
        try:
            from ..analysis.reconcile import cost_model_drift

            drift = cost_model_drift(run["trace"])
        except Exception:
            drift = None
    if as_json:
        json.dump({
            "header": run["header"],
            "decisions": run["decisions"],
            "reconciliation": rec,
            "cost_model_drift": drift,
        }, sys.stdout, indent=1, default=str)
        print()
        return 0
    print(render_ledger(run, reconciliation=rec))
    if rec is not None:
        from ..analysis.reconcile import format_decision_reconciliation

        print()
        print(format_decision_reconciliation(rec))
    if drift is not None:
        from ..analysis.reconcile import format_drift

        print()
        print(format_drift(drift))
    return 0


def _diff_main(path_a: str, path_b: str, as_json: bool) -> int:
    from .ledger import diff_runs, format_diff

    run_a = _read_run(path_a)
    run_b = _read_run(path_b)
    if run_a is None or run_b is None:
        return 2
    diff = diff_runs(run_a, run_b,
                     reconciliation_a=_reconcile(run_a),
                     reconciliation_b=_reconcile(run_b))
    if as_json:
        json.dump(diff, sys.stdout, indent=1, default=str)
        print()
    else:
        print(format_diff(diff))
    return 1 if diff["regressions"] else 0


def _flight_main(path: str, top: int, as_json: bool) -> int:
    try:
        trace = load_trace(path)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    meta = trace.get("keystone", {}).get("flight") or {}
    incomplete = sum(
        1 for e in trace.get("traceEvents", [])
        if e.get("ph") == "X" and e.get("args", {}).get("incomplete"))
    if as_json:
        json.dump({
            "flight": meta,
            "incomplete_spans": incomplete,
            "metrics": trace.get("keystone", {}).get("metrics", {}),
            "spans": aggregate_spans(trace),
        }, sys.stdout, indent=1)
        print()
        return 0
    if meta:
        dropped = int(meta.get("dropped_spans", 0))
        print(f"flight dump: {int(meta.get('spans_held', 0))}/"
              f"{int(meta.get('capacity', 0))} span(s) in ring, "
              f"{dropped} evicted before dump, "
              f"{incomplete} in-flight at dump")
        print()
    print(summarize(trace, top=top))
    return 0


def _live_main(as_json: bool) -> int:
    from .streaming import format_health, health

    h = health()
    if as_json:
        json.dump(h, sys.stdout, indent=1, default=str)
        print()
    else:
        print(format_health(h))
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] == ["device"]:
        from .device import main as device_main

        return device_main(argv[1:])
    p = argparse.ArgumentParser(
        prog="python -m keystone_tpu.telemetry",
        description=__doc__.splitlines()[0],
    )
    p.add_argument("trace", nargs="?",
                   help="Chrome trace JSON written by trace_run / "
                        "KEYSTONE_TRACE")
    p.add_argument("--top", type=int, default=15,
                   help="rows per section (default 15)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable digest (perf_table.py input)")
    p.add_argument("--ledger", metavar="RUN",
                   help="render a run's decision ledger (JSONL file or "
                        "decision-carrying trace) with the "
                        "predicted-vs-observed reconciliation")
    p.add_argument("--diff", nargs=2, metavar=("RUN_A", "RUN_B"),
                   help="run-over-run regression detection between two "
                        "runs' ledgers (exit 1 on any regression)")
    p.add_argument("--flight", metavar="DUMP",
                   help="render a flight-recorder dump: ring-window "
                        "header (capacity / evictions / in-flight "
                        "spans) followed by the trace digest")
    p.add_argument("--live", action="store_true",
                   help="render this process's live health view "
                        "(streaming latency percentiles, throughput, "
                        "conformance counters, armed watchdog)")
    p.add_argument("--emit-calibration", metavar="PATH",
                   help="with --ledger: persist the run's drift-implied "
                        "cost weights as a tpu_calibration.json-schema "
                        "file; KEYSTONE_COST_CALIBRATION=<PATH> then "
                        "recalibrates machine_rates() when the platform "
                        "matches")
    args = p.parse_args(argv)
    if args.emit_calibration and not args.ledger:
        p.error("--emit-calibration requires --ledger")
    if args.diff:
        return _diff_main(args.diff[0], args.diff[1], args.as_json)
    if args.ledger:
        return _ledger_main(args.ledger, args.as_json,
                            emit_calibration=args.emit_calibration)
    if args.live:
        return _live_main(args.as_json)
    if args.flight:
        return _flight_main(args.flight, args.top, args.as_json)
    if not args.trace:
        p.error("a trace path, --ledger, --diff, --flight, or --live "
                "is required")
    try:
        trace = load_trace(args.trace)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.as_json:
        digest = {
            "nodes": aggregate_spans(trace, "node"),
            "steps": aggregate_spans(trace, "step"),
            "chunks": aggregate_spans(trace, "chunk"),
            "metrics": trace.get("keystone", {}).get("metrics", {}),
        }
        try:
            from ..analysis.reconcile import reconcile_trace

            digest["memory_reconciliation"] = reconcile_trace(trace)
        except Exception:
            pass
        json.dump(digest, sys.stdout, indent=1)
        print()
    else:
        print(summarize(trace, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
