#!/usr/bin/env bash
# Fast pre-test lint gate: AST-level JAX lints + static validation of
# every example pipeline. Runs in seconds with no data and no devices
# beyond the CPU backend (the pipeline validator traces with
# jax.eval_shape only). Mirrored in tier-1 by the `lint` pytest marker
# (tests/test_jaxlint.py, tests/test_analysis.py).
#
#   scripts/lint.sh              # whole gate
#   scripts/lint.sh --list-rules # rule catalog
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--list-rules" ]]; then
    python scripts/jaxlint.py --list-rules
    JAX_PLATFORMS=cpu python -m keystone_tpu.analysis --list-rules
    exit 0
fi

echo "== jaxlint (AST rules) =="
python scripts/jaxlint.py keystone_tpu

echo "== pipeline validation (abstract specs) =="
JAX_PLATFORMS=cpu python -m keystone_tpu.analysis "$@"

echo "== operator contract audit (registry-wide KP5xx) =="
JAX_PLATFORMS=cpu python -m keystone_tpu.analysis --audit-operators

echo "== sharding audit (per-stage placement over every example, 8-device mesh) =="
# Every analyzable() example's propagated partition table on a forced
# 8-device CPU mesh: the CLI exits 1 on ANY unsuppressed KP6xx finding
# (implicit reshard, oversized replication, host all-gather,
# mesh-indivisible counts) — placement regressions fail here in seconds.
SHARDING_JSON="$(mktemp /tmp/keystone_sharding_audit.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON"' EXIT
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m keystone_tpu.analysis --explain-sharding --json > "$SHARDING_JSON"
python - "$SHARDING_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
assert payload["devices"] == 8, payload["devices"]
examples = payload["examples"]
assert len(examples) >= 7, [e["example"] for e in examples]
for e in examples:
    assert "build_error" not in e, e
    assert e["findings"] == [], e["findings"]
    assert e["stages"], e["example"]
stages = sum(len(e["stages"]) for e in examples)
print(f"sharding audit: {len(examples)} example(s), {stages} stage rows, "
      "0 KP6xx findings OK")
PY

echo "== planner audit (chosen vs default placement over every example, 2x4 mesh) =="
# The sharding planner's decision gate: on an 8-device CPU mesh arranged
# 2 (data) x 4 (model), run the planner over every analyzable() example
# and assert (1) the chosen placement's priced boundary bytes never
# exceed the default placement's, (2) the planner strictly wins on at
# least 2 examples, and (3) zero unsuppressed KP6xx findings UNDER the
# chosen plan — the decided placement is clean, not just the default.
PLANNER_JSON="$(mktemp /tmp/keystone_planner_audit.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON"' EXIT
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m keystone_tpu.analysis --explain-sharding --plan --mesh-shape 2x4 \
    --json > "$PLANNER_JSON"
python - "$PLANNER_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
assert payload["devices"] == 8, payload["devices"]
examples = payload["examples"]
assert len(examples) >= 7, [e["example"] for e in examples]
strict = 0
for e in examples:
    assert "build_error" not in e, e
    assert e["findings"] == [], (e["example"], e["findings"])
    planner = e.get("planner")
    if planner is None:
        continue  # nothing to decide (host-only pipeline)
    assert planner["planned_cost_bytes"] <= planner["default_cost_bytes"], e
    if planner["planned_cost_bytes"] < planner["default_cost_bytes"]:
        strict += 1
assert strict >= 2, f"planner strictly beat the default on only {strict} example(s)"
saved = sum((e.get("planner") or {}).get("savings_bytes", 0) for e in examples)
print(f"planner audit: {len(examples)} example(s), strict wins on {strict}, "
      f"{saved:,} boundary bytes saved, 0 KP6xx under chosen plans OK")
PY

echo "== precision audit (chosen per-stage dtypes over every example) =="
# The mixed-precision policy planner's decision gate: run the planner
# over every analyzable() example and assert (1) the chosen policy's
# priced boundary bytes never exceed the all-f32 default's, (2) the
# planner strictly wins on at least 2 examples, and (3) zero
# unsuppressed WARNING/ERROR KP7xx findings under the chosen policies —
# the decided dtypes are clean, not just the f32 reference.
PRECISION_JSON="$(mktemp /tmp/keystone_precision_audit.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON"' EXIT
JAX_PLATFORMS=cpu python -m keystone_tpu.analysis --explain-precision \
    --json > "$PRECISION_JSON"
python - "$PRECISION_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
examples = payload["examples"]
assert len(examples) >= 7, [e["example"] for e in examples]
strict = 0
for e in examples:
    assert "build_error" not in e, e
    gate = [f for f in e["findings"] if f["severity"] != "INFO"]
    assert gate == [], (e["example"], gate)
    planner = e.get("planner")
    if planner is None:
        continue  # nothing to decide (no tolerant float boundary)
    assert planner["planned_cost_bytes"] <= planner["default_cost_bytes"], e
    if planner["planned_cost_bytes"] < planner["default_cost_bytes"]:
        strict += 1
assert strict >= 2, f"precision planner strictly won on only {strict} example(s)"
saved = sum((e.get("planner") or {}).get("savings_bytes", 0) for e in examples)
print(f"precision audit: {len(examples)} example(s), strict wins on {strict}, "
      f"{saved:,} boundary bytes saved, 0 KP7xx under chosen policies OK")
PY

echo "== roofline audit (per-stage flops/bytes/intensity over every example) =="
# The static roofline analyzer's gate: price every analyzable() example
# on the calibrated machine balance and assert (1) zero unsuppressed
# ERROR-severity KP8xx findings (the tier is advisory — KP801/KP803
# candidates and re-pricings are INFO), (2) the device-featurize
# examples actually price (stage rows with flops/bytes/intensity/
# predicted-seconds present), and (3) the KP801 Pallas-candidate list
# is non-empty — the Pallas megakernel backend (ROADMAP) needs a
# statically identified bandwidth-bound chain to target.
ROOFLINE_JSON="$(mktemp /tmp/keystone_roofline_audit.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON"' EXIT
JAX_PLATFORMS=cpu python -m keystone_tpu.analysis --explain-roofline \
    --json > "$ROOFLINE_JSON"
python - "$ROOFLINE_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
machine = payload["machine"]
assert machine and machine["peak_flops"] > 0 and machine["peak_bw"] > 0
examples = payload["examples"]
assert len(examples) >= 7, [e["example"] for e in examples]
candidates = 0
priced = 0
for e in examples:
    assert "build_error" not in e, e
    errors = [f for f in e["findings"] if f["severity"] == "ERROR"]
    assert errors == [], (e["example"], errors)
    for s in e["stages"]:
        assert s["flops"] >= 0 and s["hbm_bytes"] > 0, (e["example"], s)
        assert s["bound"] in ("compute", "bandwidth"), s
        assert s["predicted_seconds"] > 0, s
    priced += len(e["stages"])
    candidates += len(e["candidates"])
assert priced > 0, "no example priced a single stage"
assert candidates >= 1, "KP801 found no Pallas-candidate chain anywhere"
print(f"roofline audit: {len(examples)} example(s), {priced} priced stage "
      f"rows, {candidates} KP801 pallas candidate(s), 0 KP8xx errors OK")
PY

echo "== chain-kernel audit (every KP801 candidate lowers, prices worse, or is suppressed) =="
# The chain-megakernel backend's gate (ops/chain_kernels.py): every
# KP801 Pallas candidate the roofline finds must resolve one of three
# ways — (1) it LOWERS (a lowerable verdict naming the kernel family,
# with a finite kernel-seconds price), (2) it prices WORSE than the XLA
# chain with the reason rendered, or (3) it carries a NAMED suppression
# (chain_kernels.SUPPRESSED_STAGES — each blocker states why it stays
# on XLA deliberately). An unlowerable candidate with no named
# suppression is an open lowering gap: exit 1. At least 2 candidates
# must lower with a winning price (the PR-16 acceptance floor).
python - "$ROOFLINE_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
total = wins = worse = suppressed = 0
gaps = []
for e in payload["examples"]:
    for c in e.get("candidates", []):
        total += 1
        v = c.get("lowerable")
        anchor = f"{e['example']}:{c['vertices']}"
        assert v is not None and v.get("reason"), (
            f"{anchor}: KP801 candidate carries no lowerability verdict")
        ks, cs = c.get("kernel_seconds"), c.get("chain_seconds")
        if v.get("lowerable"):
            assert ks is not None and ks == ks and ks != float("inf"), (
                f"{anchor}: lowerable but kernel price is not finite")
            if ks < cs:
                wins += 1
            else:
                worse += 1  # priced worse, reason rendered in the verdict
        elif v.get("suppressed"):
            suppressed += 1
        else:
            gaps.append(f"{anchor}: {v.get('reason')}")
if gaps:
    print("chain-kernel audit: open lowering gap(s) with no named "
          "suppression:", file=sys.stderr)
    for g in gaps:
        print(f"  {g}", file=sys.stderr)
    sys.exit(1)
assert wins >= 2, f"only {wins} candidate(s) lower with a winning price"
print(f"chain-kernel audit: {total} KP801 candidate(s) — {wins} lower and "
      f"win, {worse} price worse (reason rendered), {suppressed} carry "
      "named suppressions, 0 open gaps OK")
PY

echo "== kernel-verifier audit (KP10xx: every registered lowering statically proved) =="
# The static Pallas kernel verifier (analysis/kernels.py): every
# lowerable KP801 candidate must carry a full KP1001-KP1005 proof —
# grid coverage, ragged-tail bounds, VMEM working set (the SAME
# arithmetic as chain_feasible's runtime chooser), mask discipline,
# and abstract oracle equivalence — or a named
# `# keystone: ignore[KP100x]` suppression. An unsuppressed KP10xx
# finding means a lowering could dispatch without a static safety
# proof: exit 1.
KERNELS_JSON="$(mktemp /tmp/keystone_kernels_audit.XXXXXX.json)"
JAX_PLATFORMS=cpu python -m keystone_tpu.analysis --audit-kernels \
    --json > "$KERNELS_JSON"
python - "$KERNELS_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
assert not payload["build_errors"], payload["build_errors"]
findings = payload["findings"]
if findings:
    print("kernel-verifier audit: unsuppressed KP10xx finding(s):",
          file=sys.stderr)
    for f in findings:
        print(f"  {f['example']}:{f['lowering']}: {f['rule']} "
              f"{f['message']}", file=sys.stderr)
    sys.exit(1)
verified, total = payload["verified_lowerings"], payload["total_lowerings"]
assert total >= 6, f"only {total} registered lowering(s) audited"
assert verified == total, (
    f"only {verified}/{total} lowerings statically verified")
print(f"kernel-verifier audit: {payload['audited_examples']} example(s) "
      f"swept, {verified}/{total} lowerings statically verified, "
      f"{len(payload['suppressed'])} suppression(s), "
      "0 unsuppressed KP10xx OK")
PY
rm -f "$KERNELS_JSON"

echo "== unified-planner audit (joint decision IR vs sequential passes, 2x4 mesh) =="
# The unified plan optimizer's decision gate: on an 8-device CPU mesh
# arranged 2 (data) x 4 (model), solve the joint {placement x dtype x
# chunk x cache} IR over every analyzable() example and assert (1) the
# joint plan's predicted seconds never exceed the sequential PR-13
# composition's (both scored by the same time model), (2) the joint
# plan strictly wins on at least 2 examples, and (3) zero unsuppressed
# WARNING/ERROR KP6xx/KP7xx/KP8xx findings UNDER the chosen plans —
# the jointly decided placement/dtypes/chunk are clean, not just the
# sequential reference.
UNIFIED_JSON="$(mktemp /tmp/keystone_unified_audit.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON"' EXIT
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python -m keystone_tpu.analysis --explain-unified --mesh-shape 2x4 \
    --json > "$UNIFIED_JSON"
python - "$UNIFIED_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
assert payload["devices"] == 8, payload["devices"]
examples = payload["examples"]
assert len(examples) >= 7, [e["example"] for e in examples]
strict = 0
for e in examples:
    assert "build_error" not in e, e
    gate = [f for f in e["findings"] if f["severity"] != "INFO"]
    assert gate == [], (e["example"], gate)
    planner = e.get("planner")
    if planner is None:
        continue  # nothing to decide (host-only pipeline)
    assert planner["joint_seconds"] <= planner["sequential_seconds"], e
    if planner["joint_seconds"] < planner["sequential_seconds"]:
        strict += 1
assert strict >= 2, f"joint plan strictly won on only {strict} example(s)"
saved = sum((e.get("planner") or {}).get("savings_seconds", 0.0)
            for e in examples)
print(f"unified audit: {len(examples)} example(s), strict wins on {strict}, "
      f"{saved:.3e} predicted seconds saved, 0 KP6xx/KP7xx/KP8xx under "
      "chosen plans OK")
PY

echo "== serving audit (KP9xx readiness certificate over every example) =="
# The serving-readiness certifier's gate: certify every analyzable()
# example against the default envelope (batch [1,64], 1s SLO) and
# assert (1) the CLI exits 0 — zero UNSUPPRESSED ERROR-severity KP9xx
# findings anywhere, (2) at least 5 examples certify clean, and (3)
# every example that cannot certify carries NAMED suppressions
# (serving.SERVING_SUPPRESSIONS — each states the stage and the fix),
# so the audit says exactly what is uncertified and why instead of
# silently passing.
SERVING_JSON="$(mktemp /tmp/keystone_serving_audit.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON"' EXIT
JAX_PLATFORMS=cpu python -m keystone_tpu.analysis --certify-serving \
    --json > "$SERVING_JSON"
python - "$SERVING_JSON" <<'PY'
import json, sys
payload = json.load(open(sys.argv[1]))
examples = payload["examples"]
assert len(examples) >= 7, [e.get("example") for e in examples]
certified = 0
for e in examples:
    assert "build_error" not in e, e
    assert e["unsuppressed_errors"] == 0, (e["example"], e["findings"])
    if e["certified"]:
        certified += 1
        assert e["certificate"]["shapes"], e["example"]
        assert all(s["predicted_seconds"] > 0
                   for s in e["certificate"]["shapes"]), e["example"]
    else:
        assert e["suppressions"], (
            f"{e['example']} is uncertified with NO named suppression")
assert certified >= 5, f"only {certified} example(s) certified clean"
suppressed = sum(1 for e in examples if e["suppressions"])
print(f"serving audit: {len(examples)} example(s), {certified} certified "
      f"clean, {suppressed} carrying named suppressions, 0 unsuppressed "
      "KP9xx errors OK")
PY

echo "== telemetry smoke (trace a tiny pipeline, validate the JSON) =="
TRACE_TMP="$(mktemp /tmp/keystone_trace_smoke.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON" "$TRACE_TMP"' EXIT
JAX_PLATFORMS=cpu KEYSTONE_SMOKE_TRACE="$TRACE_TMP" python - <<'PY'
import json, os
import numpy as np
from keystone_tpu import Dataset, Transformer
from keystone_tpu.telemetry import trace_run

path = os.environ["KEYSTONE_SMOKE_TRACE"]
with trace_run(path):
    pipe = Transformer.from_function(lambda x: x * 2.0).to_pipeline()
    pipe(Dataset.from_numpy(np.ones((8, 4), np.float32))).get()
trace = json.load(open(path))
events = trace["traceEvents"]
assert isinstance(events, list) and events, "empty traceEvents"
for e in events:
    assert "ph" in e and "name" in e and "pid" in e, e
assert any(e.get("cat") == "node" for e in events), "no node-force spans"
assert "keystone" in trace and "metrics" in trace["keystone"]
print(f"telemetry smoke: {len(events)} events OK")
PY
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry "$TRACE_TMP" >/dev/null

echo "== dispatch smoke (example pipeline under the concurrent scheduler) =="
DISPATCH_TRACE="$(mktemp /tmp/keystone_dispatch_smoke.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON" "$TRACE_TMP" "$DISPATCH_TRACE"' EXIT
JAX_PLATFORMS=cpu KEYSTONE_TRACE="$DISPATCH_TRACE" KEYSTONE_CONCURRENT_DISPATCH=1 \
python - <<'PY'
# One example pipeline (the dispatch-bench MnistRandomFFT instance) run
# end-to-end under the concurrent DAG scheduler with tracing armed: the
# trace must parse and the run must have executed (and counted) real
# XLA programs through dispatch.programs_executed.
import json, os
from keystone_tpu.dispatch_bench import measure_example

res = measure_example("MnistRandomFFT", "optimized")
assert res["fit_run_programs"] > 0 and res["apply_run_programs"] > 0, res

import keystone_tpu.telemetry.spans as spans
from keystone_tpu.telemetry.export import write_trace
tracer = spans.current_tracer()
assert tracer is not None, "KEYSTONE_TRACE did not arm the ambient tracer"
write_trace(tracer, os.environ["KEYSTONE_TRACE"])

trace = json.load(open(os.environ["KEYSTONE_TRACE"]))
assert trace["traceEvents"], "empty traceEvents"
programs = (trace["keystone"]["metrics"]["counters"]
            .get("dispatch.programs_executed", {}).get("value", 0))
assert programs > 0, "programs_executed not counted"
print(f"dispatch smoke: {int(programs)} program(s), "
      f"{res['apply_run_programs']} on the apply run OK")
PY
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry "$DISPATCH_TRACE" >/dev/null

echo "== compile smoke (warm second run performs 0 cold compiles) =="
COMPILE_CACHE="$(mktemp -d /tmp/keystone_compile_smoke.XXXXXX)"
COMPILE_TRACE="$(mktemp /tmp/keystone_compile_smoke.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON" "$TRACE_TMP" "$DISPATCH_TRACE" "$COMPILE_TRACE"; rm -rf "$COMPILE_CACHE"' EXIT
JAX_PLATFORMS=cpu JAX_COMPILATION_CACHE_DIR="$COMPILE_CACHE" \
KEYSTONE_TRACE="$COMPILE_TRACE" python - <<'PY'
# One example pipeline run TWICE against a fresh persistent-cache dir
# with tracing armed: the second (rebuilt-from-scratch) run must perform
# zero cold compiles — everything served warm from the persistent cache
# or the in-process program caches — and the trace must parse and carry
# the compile accounting.
import json, os
from keystone_tpu.dispatch_bench import measure_example
from keystone_tpu.telemetry import compiles_snapshot
from keystone_tpu.workflow.executor import drain_warmups

measure_example("MnistRandomFFT", "optimized")
drain_warmups()  # background AOT compiles count against THIS run
first = compiles_snapshot()
measure_example("MnistRandomFFT", "optimized")
drain_warmups()
second = compiles_snapshot()
new_cold = second["programs_compiled"] - first["programs_compiled"]
assert new_cold == 0, (
    f"second identical run performed {new_cold} cold compile(s): "
    f"{first} -> {second}")

import keystone_tpu.telemetry.spans as spans
from keystone_tpu.telemetry.export import compile_summary, write_trace
tracer = spans.current_tracer()
assert tracer is not None, "KEYSTONE_TRACE did not arm the ambient tracer"
write_trace(tracer, os.environ["KEYSTONE_TRACE"])

trace = json.load(open(os.environ["KEYSTONE_TRACE"]))
assert trace["traceEvents"], "empty traceEvents"
counters = trace["keystone"]["metrics"]["counters"]
assert "dispatch.programs_compiled" in counters, sorted(counters)
line = compile_summary(trace)
assert line is not None, "trace carries no compile digest"
print(f"compile smoke: run1 {first['programs_compiled']} cold / "
      f"{first['compile_cache_hits']} hits; run2 +0 cold — {line} OK")
PY
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry "$COMPILE_TRACE" >/dev/null

echo "== megafusion smoke (1-program apply run; warm repeat stays 0-cold) =="
MEGA_CACHE="$(mktemp -d /tmp/keystone_mega_smoke.XXXXXX)"
MEGA_TRACE="$(mktemp /tmp/keystone_mega_smoke.XXXXXX.json)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON" "$TRACE_TMP" "$DISPATCH_TRACE" "$COMPILE_TRACE" "$MEGA_TRACE"; rm -rf "$COMPILE_CACHE" "$MEGA_CACHE"' EXIT
JAX_PLATFORMS=cpu KEYSTONE_MEGAFUSION=1 JAX_COMPILATION_CACHE_DIR="$MEGA_CACHE" \
KEYSTONE_TRACE="$MEGA_TRACE" python - <<'PY'
# One example apply run TWICE under megafusion against a fresh
# persistent-cache dir with tracing armed: each apply run must execute
# exactly ONE program (the whole-plan scan-bodied megafused program),
# the warm second run must perform zero cold compiles, and the trace's
# dispatch digest must carry the per-plan breakdown row showing it.
import json, os
from keystone_tpu.dispatch_bench import measure_example
from keystone_tpu.telemetry import compiles_snapshot
from keystone_tpu.workflow.executor import drain_warmups

r1 = measure_example("MnistRandomFFT", "megafused")
assert r1["apply_run_programs"] == 1, r1["apply_run_programs"]
drain_warmups()  # background AOT compiles count against run 1
first = compiles_snapshot()
r2 = measure_example("MnistRandomFFT", "megafused")
assert r2["apply_run_programs"] == 1, r2["apply_run_programs"]
drain_warmups()
second = compiles_snapshot()
new_cold = second["programs_compiled"] - first["programs_compiled"]
assert new_cold == 0, (
    f"warm megafused run performed {new_cold} cold compile(s)")

import keystone_tpu.telemetry.spans as spans
from keystone_tpu.telemetry.export import (
    dispatch_plan_breakdown, dispatch_summary, write_trace)
tracer = spans.current_tracer()
assert tracer is not None, "KEYSTONE_TRACE did not arm the ambient tracer"
write_trace(tracer, os.environ["KEYSTONE_TRACE"])

trace = json.load(open(os.environ["KEYSTONE_TRACE"]))
rows = dispatch_plan_breakdown(trace)
assert rows and "megafused=1" in rows[0], rows
summary = dispatch_summary(trace)
assert summary is not None and "megafused" in summary, summary
print(f"megafusion smoke: {rows[0]}; run2 +0 cold OK")
PY
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry "$MEGA_TRACE" >/dev/null

echo "== ledger smoke (decision records match enforced plan tags; self-diff clean) =="
LEDGER_TRACE="$(mktemp /tmp/keystone_ledger_smoke.XXXXXX.json)"
LEDGER_FILE="$(mktemp /tmp/keystone_ledger_smoke.XXXXXX.jsonl)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON" "$TRACE_TMP" "$DISPATCH_TRACE" "$COMPILE_TRACE" "$MEGA_TRACE" "$LEDGER_TRACE" "$LEDGER_FILE"; rm -rf "$COMPILE_CACHE" "$MEGA_CACHE"' EXIT
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
KEYSTONE_TRACE="$LEDGER_TRACE" KEYSTONE_LEDGER="$LEDGER_FILE" python - <<'PY'
# One example pipeline (the dispatch-bench MnistRandomFFT instance,
# full default stack: megafusion + sharding planner + precision with
# the floor dropped) run end-to-end with the trace AND the decision
# ledger armed. The gate: the JSONL ledger parses, EVERY enforced plan
# tag in the executed graphs (fused/megafused program operators,
# planned_out_spec placements, planned_precision policies) has a
# matching decision record of the right kind covering its vertex, and
# every record carries chosen + >=1 priced alternative + predicted cost.
import os
import numpy as np
from keystone_tpu import PipelineEnv
from keystone_tpu.dispatch_bench import EXAMPLES, _plan_context
from keystone_tpu.telemetry import ledger
from keystone_tpu.workflow.env import (
    config_override, dispatch_override, overlap_override)

optimizer, overlap_on, concurrent_on, overrides = _plan_context("precision")
PipelineEnv.reset()
PipelineEnv.get().set_optimizer(optimizer)
with overlap_override(overlap_on), dispatch_override(concurrent_on), \
        config_override(**overrides):
    predictor, train, test = EXAMPLES["MnistRandomFFT"]()
    fit_res = predictor(train)
    fit_res.get()
    apply_res = predictor(test)
    apply_res.get()

    run = ledger.read_ledger(os.environ["KEYSTONE_LEDGER"])
    assert run["header"]["ledger_version"] == ledger.LEDGER_VERSION
    assert run["header"]["config"]["megafusion"] is True, run["header"]
    decisions = run["decisions"]
    assert decisions, "armed run recorded no decisions"
    for d in decisions:
        assert d["enforced"], d
        assert d["chosen"] and len(d["alternatives"]) >= 1, d
        assert d["predicted"], d

    # every enforced plan tag has a matching decision record
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer
    from keystone_tpu.workflow.fusion_rule import (
        FusedChainOperator, MegafusedPlanOperator)
    by_kind = {}
    for d in decisions:
        for v in d["vertices"]:
            by_kind.setdefault(d["kind"], set()).add(int(v))
    checked = {"fusion": 0, "megafusion": 0, "placement": 0,
               "precision": 0}
    for res in (fit_res, apply_res):
        graph = res.executor.optimized_graph
        for vid, op in graph.operators.items():
            tags = []
            if isinstance(op, MegafusedPlanOperator):
                tags.append("megafusion")
            elif isinstance(op, (FusedChainOperator, FusedBatchTransformer)):
                tags.append("fusion")
            if getattr(op, "planned_out_spec", None) is not None:
                tags.append("placement")
            if getattr(op, "planned_precision", None) is not None:
                tags.append("precision")
            for kind in tags:
                vertices = by_kind.get(kind, set())
                assert vid.id in vertices, (
                    f"enforced {kind} tag on vertex {vid.id} "
                    f"({op.label}) has no matching decision record "
                    f"(recorded vertices: {sorted(vertices)})")
                checked[kind] += 1
    assert checked["fusion"] or checked["megafusion"], checked

    # flush the ambient trace so the CLI can join decisions with
    # observations on this same artifact
    import keystone_tpu.telemetry.spans as spans
    from keystone_tpu.telemetry.export import write_trace
    tracer = spans.current_tracer()
    assert tracer is not None, "KEYSTONE_TRACE did not arm the tracer"
    write_trace(tracer, os.environ["KEYSTONE_TRACE"])
PipelineEnv.reset()
print("ledger smoke: " + ", ".join(
    f"{k}={v}" for k, v in sorted(checked.items())) + " plan tags matched")
PY
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry --ledger "$LEDGER_FILE" >/dev/null
# a run diffed against itself must report zero regressions (exit 0)
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry --diff "$LEDGER_FILE" "$LEDGER_FILE"

echo "== live-telemetry smoke (tight SLO breaches on a real apply; flight dump + conformance record) =="
LIVE_LEDGER="$(mktemp /tmp/keystone_live_smoke.XXXXXX.jsonl)"
LIVE_FLIGHT="$(mktemp -d /tmp/keystone_live_smoke.XXXXXX)"
trap 'rm -f "$SHARDING_JSON" "$PLANNER_JSON" "$PRECISION_JSON" "$ROOFLINE_JSON" "$UNIFIED_JSON" "$SERVING_JSON" "$TRACE_TMP" "$DISPATCH_TRACE" "$COMPILE_TRACE" "$MEGA_TRACE" "$LEDGER_TRACE" "$LEDGER_FILE" "$LIVE_LEDGER"; rm -rf "$COMPILE_CACHE" "$MEGA_CACHE" "$LIVE_FLIGHT"' EXIT
JAX_PLATFORMS=cpu KEYSTONE_LEDGER="$LIVE_LEDGER" \
KEYSTONE_FLIGHT_DIR="$LIVE_FLIGHT" python - <<'PY'
# Arm the conformance watchdog with an artificially tight certificate
# (1 ns bound at every ladder shape), run a real warm apply through
# `request_scope`, and assert the breach path end-to-end: the breach
# counter fires, the flight-ring dump the breach triggered parses as a
# Chrome trace, and the conformance ledger record names the certified
# bound the observed latency was compared against.
import numpy as np
from keystone_tpu import PipelineEnv
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.dispatch_bench import EXAMPLES
from keystone_tpu.telemetry import ledger, registry
from keystone_tpu.telemetry.export import load_trace
from keystone_tpu.telemetry.flight import ensure_flight, reset_flight
from keystone_tpu.telemetry.streaming import health, reset_live
from keystone_tpu.telemetry.watchdog import arm_watchdog, disarm_watchdog

TIGHT = 1e-9
PipelineEnv.reset()
predictor, train, test = EXAMPLES["MnistRandomFFT"]()
fitted = predictor.fit()
X = np.asarray(test.numpy())[:64]
np.asarray(fitted.apply(Dataset.from_numpy(X)).numpy())  # warm the shape

ensure_flight()
wd = arm_watchdog({
    "slo_seconds": TIGHT, "certified": True,
    "shapes": [{"batch": b, "predicted_seconds": TIGHT}
               for b in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                         1024, 2048, 4096)],
}, pipeline="MnistRandomFFT")
assert wd is not None, "watchdog did not arm from the tight certificate"
mark = ledger.session_mark()
np.asarray(fitted.apply(Dataset.from_numpy(X)).numpy())

assert wd.breaches >= 1, f"no breach under a {TIGHT}s bound: {wd.describe()}"
reg = registry()
assert reg.counter("serving.slo_breaches").value >= 1
assert reg.counter("serving.conformance_checks").value >= 1
recs = [d for d in ledger.session_since(mark) if d["kind"] == "conformance"]
assert recs, "breach emitted no conformance ledger record"
rec = recs[0]
assert rec["predicted"]["bound_seconds"] == TIGHT, rec["predicted"]
assert rec["chosen"]["observed_seconds"] > TIGHT
assert rec["alternatives"][0]["cost_seconds"] == TIGHT
dump = rec["chosen"]["flight_dump"]
assert dump, "breach did not dump the flight ring"
trace = load_trace(dump)  # the dump is a valid Chrome trace
assert trace.get("keystone", {}).get("flight", {}).get("capacity", 0) > 0
h = health()
assert h["counters"]["serving.slo_breaches"]["value"] >= 1, h["counters"]
assert any(r["count"] >= 1 for r in h["latency"]), h["latency"]
disarm_watchdog()
reset_live()
reset_flight()
PipelineEnv.reset()
print(f"live-telemetry smoke: {len(recs)} breach record(s), "
      f"dump {int(trace['keystone']['flight']['spans_held'])} span(s) OK")
PY
# the JSONL ledger the breach appended renders through the --ledger CLI,
# and the breach dump renders through the --flight CLI
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry --ledger "$LIVE_LEDGER" >/dev/null
LIVE_DUMP="$(ls "$LIVE_FLIGHT"/keystone_flight_*.json | head -1)"
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry --flight "$LIVE_DUMP" >/dev/null

echo "== serving-runtime smoke (certified micro-batching: ladder-only dispatch, 0 cold compiles, handoff record) =="
SERVING_SMOKE_LEDGER="$(mktemp /tmp/keystone_serving_rt_smoke.XXXXXX.jsonl)"
JAX_PLATFORMS=cpu KEYSTONE_LEDGER="$SERVING_SMOKE_LEDGER" python - <<'PY'
# Start the real certified serving runtime on MnistRandomFFT, fire
# concurrent requests through the coalescing path, and assert the
# start-sequence contract end-to-end: every dispatched batch shape sits
# on the certificate's warmed pad ladder (ragged coalesced counts pad
# onto a rung, never compile their own program), the warm window
# performs 0 cold compiles, the conformance watchdog records 0
# breaches, results equal direct FittedPipeline.apply, and the ledger
# carries the serving_handoff record binding certificate to runtime.
import threading

import numpy as np

from keystone_tpu import PipelineEnv
from keystone_tpu.analysis import ServingEnvelope
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.dispatch_bench import EXAMPLES
from keystone_tpu.serving import NdarrayIngress, ServingRuntime
from keystone_tpu.telemetry import ledger
from keystone_tpu.telemetry.streaming import reset_live
from keystone_tpu.telemetry.watchdog import active_watchdog, disarm_watchdog

PipelineEnv.reset()
reset_live()
predictor, train, test = EXAMPLES["MnistRandomFFT"]()
fitted = predictor.fit()
X = np.asarray(test.numpy())
ref = np.asarray(fitted.apply(Dataset.from_numpy(X)).numpy())

mark = ledger.session_mark()
rt = ServingRuntime(
    fitted, NdarrayIngress(X.shape[1:]),
    envelope=ServingEnvelope(max_batch=8, slo_seconds=1.0),
    name="MnistRandomFFT").start()
try:
    from jax._src import monitoring

    compiles = []

    def listener(name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            compiles.append(name)

    monitoring.register_event_listener(listener)
    try:
        results, errors = {}, []

        def client(i):
            try:
                results[i] = rt.submit(X[i])
            except Exception as e:  # noqa: BLE001 - asserted below
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        try:
            monitoring._event_listeners.remove(listener)
        except ValueError:
            monitoring.clear_event_listeners()

    assert not errors, errors[:3]
    assert len(results) == 32
    for i, out in results.items():
        assert np.allclose(out, ref[i]), i
    stats = rt.stats()
    assert stats["dispatched_shapes"], "nothing dispatched"
    assert stats["dispatched_outside_ladder"] == [], (
        "a dispatch left the certified ladder: "
        f"{stats['dispatched_shapes']} vs {stats['ladder']}")
    assert not compiles, (
        f"{len(compiles)} cold compile(s) while serving on a warm "
        "runtime — the warmed-manifest claim is broken")
    wd = active_watchdog()
    assert wd is not None and wd.describe()["breaches"] == 0, (
        wd and wd.describe())
    checked = wd.describe()["checked"]
    handoffs = [d for d in ledger.session_since(mark)
                if d["kind"] == "serving_handoff"]
    assert handoffs, "runtime start emitted no serving_handoff record"
    h = handoffs[0]
    assert h["chosen"]["entry"] == "coalesced micro-batching", h["chosen"]
    assert h["chosen"]["ladder_shapes"] == stats["ladder"], h["chosen"]
    assert h["chosen"]["warmed_sites"] == rt.warmed_sites
finally:
    rt.stop()
disarm_watchdog()
reset_live()
PipelineEnv.reset()
print(f"serving-runtime smoke: 32 requests, shapes "
      f"{stats['dispatched_shapes']} on ladder {stats['ladder']}, "
      f"0 cold compiles, {checked} watchdog checks / 0 breaches, "
      f"{len(handoffs)} handoff record(s) OK")
PY
# the handoff record the start appended renders through the --ledger CLI
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry --ledger "$SERVING_SMOKE_LEDGER" >/dev/null
rm -f "$SERVING_SMOKE_LEDGER"

echo "== out-of-core smoke (dataset 8x budget: windowed peak under budget, warm 0-cold, spill decision in ledger) =="
OOC_LEDGER="$(mktemp /tmp/keystone_ooc_smoke.XXXXXX.jsonl)"
OOC_CACHE="$(mktemp -d /tmp/keystone_ooc_cache.XXXXXX)"
JAX_PLATFORMS=cpu KEYSTONE_LEDGER="$OOC_LEDGER" \
JAX_COMPILATION_CACHE_DIR="$OOC_CACHE" python - <<'PY'
# Two halves of the out-of-core contract. (1) Streaming: a synthetic
# dataset 8x a synthetic HBM budget streams through the windowed spill
# prefetcher into normal-equation accumulators — the warm second pass
# performs 0 cold compiles (every window pads onto an already-compiled
# ladder rung), observed live device bytes stay under the budget, and
# index coverage is exact. (2) Planning: the unified planner, given a
# budget every device cache busts, enforces a HOST-placed CacheMarker
# end-to-end and appends a kind="spill" ledger record whose
# alternatives price the infeasible device cache (INF) against the
# feasible host spill; the kill-switch arm enforces no host placement
# and keeps an empty spill set.
import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu import PipelineEnv
from keystone_tpu.data.dataset import Dataset
from keystone_tpu.loaders import synthetic_out_of_core
from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT, RandomSignNode
from keystone_tpu.nodes.util import ClassLabelIndicatorsFromInt, MaxClassifier
from keystone_tpu.telemetry import compiles_snapshot, ledger
from keystone_tpu.telemetry.compile_events import install_compile_listeners
from keystone_tpu.utils.batching import stream_spill_windows
from keystone_tpu.workflow.autocache import CacheMarker
from keystone_tpu.workflow.env import config_override
from keystone_tpu.workflow.executor import drain_warmups

PipelineEnv.reset()
install_compile_listeners()

# -- (1) windowed streaming under an 8x-too-small budget -----------------
n, dim, window = 32768, 64, 512
budget = n * dim * 4 // 8
source = synthetic_out_of_core(n, dim, shard_rows=4096)
W = jnp.asarray(np.random.default_rng(7)
                .standard_normal((dim, dim)).astype(np.float32) * 0.05)

@jax.jit
def accum(ata, xb):
    f = jnp.maximum(xb @ W, 0.0)
    return ata + f.T @ f

def windowed_pass(track_peak=False):
    ata = jnp.zeros((dim, dim), jnp.float32)
    seen, peak = [], 0
    for idxs, win in stream_spill_windows(source.row_loader, n,
                                          window=window):
        ata = accum(ata, win)
        seen.extend(int(i) for i in idxs)
        if track_peak:
            jax.block_until_ready(ata)
            peak = max(peak, sum(int(a.nbytes) for a in jax.live_arrays()))
    return ata, seen, peak

windowed_pass()          # cold pass: compiles the ladder rungs
drain_warmups()
first = compiles_snapshot()
ata, seen, peak = windowed_pass(track_peak=True)
drain_warmups()
second = compiles_snapshot()
new_cold = second["programs_compiled"] - first["programs_compiled"]
assert new_cold == 0, (
    f"warm windowed pass performed {new_cold} cold compile(s): "
    f"{first} -> {second}")
assert sorted(seen) == list(range(n)), (
    f"window index coverage broken: {len(seen)} indices for {n} rows")
assert peak <= budget, (
    f"windowed pass peaked at {peak} device bytes against a "
    f"{budget}-byte budget (dataset is {n * dim * 4})")

# -- (2) planner-enforced host spill + ledger record ---------------------
def predictor(data, labels_ds, fdim=64, classes=4):
    featurizer = (RandomSignNode(fdim).to_pipeline()
                  >> PaddedFFT() >> LinearRectifier(0.0))
    labels = ClassLabelIndicatorsFromInt(classes)(labels_ds)
    return featurizer.and_then(
        BlockLeastSquaresEstimator(32, num_iter=1, lam=1e-3),
        data, labels) >> MaxClassifier()

rng = np.random.default_rng(11)
X = rng.standard_normal((16384, 64)).astype(np.float32)
y = rng.integers(0, 4, size=16384).astype(np.int32)

def markers_under(spill_budget, **cfg):
    PipelineEnv.reset()
    with config_override(unified_min_savings_seconds=0.0,
                         hbm_budget_bytes=spill_budget, **cfg):
        applied = predictor(Dataset.from_numpy(X),
                            Dataset.from_numpy(y))(Dataset.from_numpy(X))
        g = applied.executor.optimized_graph
        return [(v.id, g.get_operator(v).placement) for v in g.operators
                if isinstance(g.get_operator(v), CacheMarker)]

mark = ledger.session_mark()
spill_markers = markers_under(64 << 10)
assert any(p == "host" for _, p in spill_markers), (
    f"64KiB budget enforced no host placement: {spill_markers}")
spills = [d for d in ledger.session_since(mark) if d["kind"] == "spill"]
assert spills, "spill enforcement appended no kind='spill' ledger record"
rec = spills[0]
assert rec["chosen"]["placement"] == "host", rec["chosen"]
assert rec["chosen"]["spills"][0]["reload_seconds"] > 0, rec["chosen"]
alts = rec["alternatives"]
assert any(a["entry"].startswith("cache_") and not a["feasible"]
           for a in alts), (
    "spill record prices no infeasible device-cache alternative", alts)
assert any(a["entry"].startswith("spill_") and a["feasible"]
           for a in alts), (
    "spill record prices no feasible spill alternative", alts)

kill_markers = markers_under(64 << 10, ooc_spill=False)
assert not any(p == "host" for _, p in kill_markers), (
    f"KEYSTONE_OOC_SPILL=0 arm still placed a host cache: {kill_markers}")

PipelineEnv.reset()
print(f"out-of-core smoke: {n * dim * 4 >> 20}MiB dataset / "
      f"{budget >> 10}KiB budget, peak {peak >> 10}KiB, warm +0 cold, "
      f"host marker {spill_markers} with {len(alts)} priced "
      f"alternative(s); kill switch clean OK")
PY
# the spill record the enforcement appended renders through --ledger
JAX_PLATFORMS=cpu python -m keystone_tpu.telemetry --ledger "$OOC_LEDGER" >/dev/null
rm -f "$OOC_LEDGER"; rm -rf "$OOC_CACHE"

echo "lint: OK"
