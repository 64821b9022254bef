"""Render a run's host trace, its decision ledger, the static roofline
or the static serving verdicts as markdown tables.

Usage: python scripts/perf_table.py --trace run.json [--top N]
       python scripts/perf_table.py --ledger run.ledger.jsonl
       python scripts/perf_table.py --roofline [EXAMPLE ...]
       python scripts/perf_table.py --serving [EXAMPLE ...]

``--roofline`` runs the STATIC roofline analyzer
(keystone_tpu/analysis/roofline.py) over the named analyzable()
examples (default: `_ROOFLINE_DEFAULT_EXAMPLES`) and renders the
per-stage markdown table: flops, stage-at-a-time HBM bytes, arithmetic
intensity, the compute/bandwidth classification against the calibrated
machine balance, predicted seconds, and the KP801 Pallas-candidate
chains.

``--trace`` renders a Chrome trace (written via KEYSTONE_TRACE /
`trace_run`) as a markdown per-node self-time table (see
OBSERVABILITY.md). When the trace embeds optimizer decisions, the
decision tables are appended automatically.

``--ledger`` renders a run's decision ledger (a ``KEYSTONE_LEDGER``
file, or a decision-carrying trace) as markdown predicted-vs-observed
tables.

``--serving`` runs the STATIC serving-readiness certifier
(keystone_tpu/analysis/serving.py — the KP9xx tier) over the named
analyzable() examples (default: every registered example) and renders
the per-example markdown verdict table: certified / uncertified (with
the NAMED suppressions for examples that genuinely cannot certify
yet), the worst-shape certified latency bound vs the SLO, and the
dominating stage. ``KEYSTONE_SLO_MS`` / ``KEYSTONE_SERVING_MAX_BATCH``
refine the envelope.
"""

import sys


def trace_table(path, top=15):
    """Markdown per-node self-time table from a Chrome trace."""
    sys.path.insert(0, ".")
    from keystone_tpu.telemetry import aggregate_spans, load_trace

    trace = load_trace(path)
    print(f"Trace `{path}`:\n")
    for cat, title in (("node", "Node forces"), ("step", "Solver steps"),
                       ("chunk", "Stream chunks")):
        agg = aggregate_spans(trace, cat)
        if not agg:
            continue
        print(f"**{title}** (top {top} by self-time)\n")
        print("| Span | Self s | Total s | Count | MB |")
        print("|---|---|---|---|---|")
        for name, a in sorted(agg.items(), key=lambda kv: -kv[1]["self_s"])[:top]:
            print(f"| {name} | {a['self_s']:.4f} | {a['total_s']:.4f} | "
                  f"{int(a['count'])} | {a['bytes'] / 1e6:.1f} |")
        print()
    from keystone_tpu.telemetry import compile_summary, dispatch_summary

    dispatch = dispatch_summary(trace)
    if dispatch:
        print(f"**Dispatch**: {dispatch} — serial-vs-concurrent runs "
              "diff on this line\n")
    # the per-plan breakdown the dispatch bench embeds: the 2→1
    # megafusion reduction per example, readable without opening the
    # raw trace (same metadata dispatch_plan_breakdown renders — the
    # table form tolerates partial rows/plans the same way)
    meta = trace.get("keystone", {}).get("dispatch_plans") or {}
    per = meta.get("apply_run_programs") or {}
    if per:
        plans = meta.get("plans") or sorted(
            {p for row in per.values() for p in row})
        print("| Example | " + " | ".join(plans) + " |")
        print("|---" * (1 + len(plans)) + "|")
        for example in sorted(per):
            row = per[example]
            cells = " | ".join(
                str(row[p]) if p in row else "—" for p in plans)
            print(f"| {example} | {cells} |")
        print()
    compiles = compile_summary(trace)
    if compiles:
        print(f"**Compiles**: {compiles} — a warm (persistent-cache / "
              "AOT-warmed) run holds the cold count at 0\n")
    hist = trace.get("keystone", {}).get("metrics", {}).get("histograms", {})
    stall = hist.get("prefetch.producer_stall_s")
    wait = hist.get("prefetch.consumer_wait_s")
    if stall or wait:
        print("**Overlap queue stalls**: "
              + "; ".join(
                  f"{label} {h['total']:.4f}s/{int(h['count'])}"
                  for label, h in (("producer", stall), ("consumer", wait))
                  if h))
    apply_h = hist.get("serving.apply_seconds")
    if apply_h and apply_h.get("count"):
        print("**Live serving latency**: "
              f"{int(apply_h['count'])} request(s), "
              f"p50 {apply_h.get('p50', 0.0) * 1e3:.1f} ms / "
              f"p99 {apply_h.get('p99', 0.0) * 1e3:.1f} ms "
              "(reservoir percentiles, `serving.apply_seconds`)")
    try:
        from keystone_tpu.analysis.reconcile import (
            format_reconciliation,
            reconcile_trace,
        )

        rec = reconcile_trace(trace)
        if rec["rows"]:
            print()
            print("```\n" + format_reconciliation(rec) + "\n```")
    except Exception:
        pass
    try:
        from keystone_tpu.analysis.reconcile import reconcile_roofline

        roof = reconcile_roofline(trace)
        if roof["stages_joined"]:
            print("\n**Roofline** (static predicted vs observed span "
                  "seconds)\n")
            print("| Stage | FLOPs | Bound | Predicted s | Observed s | "
                  "Residual s |")
            print("|---|---|---|---|---|---|")
            for r in roof["rows"]:
                if r["residual"] is None:
                    continue
                print(f"| {r['label'][:40]} | {r['flops']:.3g} | "
                      f"{r['bound'] or '—'} | "
                      f"{r['predicted_seconds']:.3e} | "
                      f"{r['observed_seconds']:.3e} | "
                      f"{r['residual']:+.3e} |")
            print(f"\nflops residual: predicted "
                  f"{roof['predicted_seconds']:.4f}s vs observed "
                  f"{roof['observed_seconds']:.4f}s over "
                  f"{roof['stages_joined']} joined stage(s)\n")
    except Exception:
        pass
    if trace.get("keystone", {}).get("decisions"):
        print()
        ledger_table(path)


def _fmt_kv(d):
    return "; ".join(
        f"{k}={int(v) if isinstance(v, float) and v == int(v) else v}"
        for k, v in sorted(d.items())
        if not isinstance(v, (dict, list))) or "—"


def ledger_table(path):
    """Markdown predicted-vs-observed tables from a run's decision
    ledger (a ``KEYSTONE_LEDGER`` JSONL file or a decision-carrying
    trace) — the PERF.md round-table source: one run-level row per
    reconciled quantity (programs executed/compiled, megafused
    programs, baked casts) and one row per decision with the chosen
    entry, the best-priced runner-up, and the observed/residual join
    when the run's trace is reachable."""
    sys.path.insert(0, ".")
    from keystone_tpu.telemetry.ledger import read_ledger, runner_up

    run = read_ledger(path)
    rec = None
    if run.get("trace") is not None:
        try:
            from keystone_tpu.analysis.reconcile import reconcile_decisions

            rec = reconcile_decisions(run)
        except Exception:
            rec = None
    print(f"**Optimizer decisions** ({len(run['decisions'])} recorded, "
          f"`{path}`):\n")
    if rec and (rec["run_predicted"] or rec["run_observed"]):
        print("| Run quantity | Predicted | Observed | Residual |")
        print("|---|---|---|---|")
        keys = sorted(set(rec["run_predicted"]) | set(rec["run_observed"]))
        for k in keys:
            p = rec["run_predicted"].get(k, "—")
            o = rec["run_observed"].get(k, "—")
            r = rec["residuals"].get(k, "—")
            print(f"| {k} | {p} | {o} | {r} |")
        print()
    obs_by_seq = {}
    if rec:
        obs_by_seq = {row["seq"]: row for row in rec["rows"]}
    print("| Kind | Decision | Chosen | Runner-up | Predicted | "
          "Observed | Residual |")
    print("|---|---|---|---|---|---|---|")
    for d in run["decisions"]:
        labels = d.get("labels") or ["?"]
        name = labels[0][:40] + (f" (+{len(labels) - 1})"
                                 if len(labels) > 1 else "")
        ru = runner_up(d)
        row = obs_by_seq.get(d.get("seq")) or {}
        print(f"| {d.get('kind')} | {name} "
              f"| {(d.get('chosen') or {}).get('entry', '—')} "
              f"| {(ru or {}).get('entry', '—')} "
              f"| {_fmt_kv(d.get('predicted') or {})} "
              f"| {_fmt_kv(row.get('observed') or {})} "
              f"| {_fmt_kv(row.get('residuals') or {})} |")
    print()


#: the examples `--roofline` renders when none is named
_ROOFLINE_DEFAULT_EXAMPLES = (
    "MnistRandomFFT", "RandomPatchCifar", "TimitPipeline")


def roofline_table(examples=None):
    """Markdown per-stage roofline table from the STATIC analyzer (no
    run needed): the PERF.md round-table source for per-stage
    arithmetic intensity."""
    sys.path.insert(0, ".")
    from keystone_tpu.analysis import as_source_spec
    from keystone_tpu.analysis.examples import build_example
    from keystone_tpu.analysis.propagate import spec_pass
    from keystone_tpu.analysis.roofline import roofline_pass

    machine = None
    for name in examples or _ROOFLINE_DEFAULT_EXAMPLES:
        pipeline, source_spec = build_example(name)
        specs, _ = spec_pass(
            pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
        est, _ = roofline_pass(pipeline.graph, specs)
        machine = est.machine
        print(f"**{name}** — ≈{est.plan_seconds:.3e}s predicted over "
              f"{len(est.stages)} priced stage(s), "
              f"{len(est.candidates)} pallas candidate(s)\n")
        rows = est.rows(pipeline.graph)
        if rows:
            print("| Stage | FLOPs | HBM bytes | FLOP/B | Bound | "
                  "Predicted s |")
            print("|---|---|---|---|---|---|")
            for r in rows:
                print(f"| {r['label'][:44]} | {r['flops']:.3g} | "
                      f"{int(r['hbm_bytes']):,} | {r['intensity']:.2f} | "
                      f"{r['bound']} | {r['predicted_seconds']:.3e} |")
            print()
        for c in est.candidates:
            print(f"- KP801 candidate ({c['kind']}): "
                  f"{' >> '.join(c['stages'])} — "
                  f"{c['boundary_bytes']:,} boundary bytes, "
                  f"≈{c['seconds_saved']:.2e}s saved")
        if est.candidates:
            print()
    if machine is not None:
        print(f"(machine balance {machine.balance:.1f} FLOP/B — peaks "
              f"{machine.peak_flops:.3g} FLOP/s, "
              f"{machine.peak_bw:.3g} B/s)")


def serving_table(examples=None):
    """Markdown per-example serving-certification table from the STATIC
    KP9xx certifier (no run needed): the ROADMAP serving runtime's
    pre-traffic readiness board."""
    sys.path.insert(0, ".")
    from keystone_tpu.analysis.examples import EXAMPLES
    from keystone_tpu.analysis.serving import (
        SERVING_SUPPRESSIONS,
        ServingEnvelope,
        certify_example,
        envelope_from_env,
    )

    envelope = envelope_from_env(require_slo=False)
    print(f"**Serving readiness** — envelope: batch "
          f"[{envelope.min_batch}, {envelope.max_batch}], SLO "
          f"{envelope.slo_seconds * 1e3:.0f} ms, "
          f"{envelope.tenants} tenant(s)\n")
    print("| Example | Verdict | Worst shape | Bound | SLO | "
          "Dominating stage | Notes |")
    print("|---|---|---|---|---|---|---|")
    for name in examples or sorted(EXAMPLES):
        try:
            cert, diags = certify_example(name, envelope)
        except Exception as e:
            print(f"| {name} | build error | — | — | — | — | "
                  f"{type(e).__name__}: {e} |")
            continue
        suppressed = sorted(
            {d.rule for d in diags if d.severity.name == "ERROR"
             and d.rule in SERVING_SUPPRESSIONS.get(name, {})})
        verdict = ("certified" if cert.certified else
                   f"uncertified (suppressed: {', '.join(suppressed)})"
                   if suppressed else "**UNCERTIFIED**")
        worst = cert.worst_shape
        notes = []
        if cert.ingress:
            notes.append(f"ingress at {cert.ingress['stage']}")
        if cert.unpriced_stages:
            notes.append(f"{cert.unpriced_stages} unpriced host stage(s)")
        if cert.exposed_stages:
            notes.append(f"{len(cert.exposed_stages)} recompile-exposed")
        print(f"| {name} | {verdict} "
              f"| {worst['batch'] if worst else '—'} "
              f"| {worst['predicted_seconds'] * 1e3:.1f} ms "
              f"| {envelope.slo_seconds * 1e3:.0f} ms "
              f"| {(cert.dominating_stage or '—')[:44]} "
              f"| {'; '.join(notes) or '—'} |")
    print()


def main():
    if "--serving" in sys.argv:
        names = [a for a in sys.argv[sys.argv.index("--serving") + 1:]
                 if not a.startswith("-")]
        return serving_table(names or None)
    if "--roofline" in sys.argv:
        names = [a for a in sys.argv[sys.argv.index("--roofline") + 1:]
                 if not a.startswith("-")]
        return roofline_table(names or None)
    if "--ledger" in sys.argv:
        return ledger_table(sys.argv[sys.argv.index("--ledger") + 1])
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        path = sys.argv[i + 1]
        top = (int(sys.argv[sys.argv.index("--top") + 1])
               if "--top" in sys.argv else 15)
        return trace_table(path, top)
    raise SystemExit(__doc__)


if __name__ == "__main__":
    main()
