"""Unified runtime telemetry (keystone_tpu/telemetry/).

Covers the telemetry contract documented in OBSERVABILITY.md: span
nesting/parent attribution, Chrome-trace schema validity, the overlap
engine's documented residency bound surfacing as gauge high-water marks,
exception-path span closure (including the profiler's
elapsed-time-on-failure fix), autocache greedy decisions being stable on
telemetry-derived profiles, and the static-vs-observed memory
reconciliation loop end-to-end.
"""

import json
import time as _time

import numpy as np
import pytest

from keystone_tpu import Dataset, HostDataset, Pipeline, PipelineEnv, Transformer
from keystone_tpu.telemetry import (
    load_trace,
    registry,
    span,
    summarize,
    trace_run,
)
from keystone_tpu.utils.batching import map_host_batched
from keystone_tpu.workflow.env import overlap_override


@pytest.fixture(autouse=True)
def fresh_metrics():
    registry().reset()
    yield
    registry().reset()


# ----------------------------------------------------------- span basics


def test_span_nesting_and_parent_attribution():
    with trace_run() as tr:
        with span("outer", cat="phase", k=1):
            with span("inner_a", cat="step"):
                pass
            with span("inner_b", cat="step"):
                pass
    by_name = {s.name: s for s in tr.spans}
    root = by_name["pipeline_run"]
    outer = by_name["outer"]
    assert outer.parent == root.sid
    assert by_name["inner_a"].parent == outer.sid
    assert by_name["inner_b"].parent == outer.sid
    assert by_name["inner_a"].sid != by_name["inner_b"].sid
    assert outer.args["k"] == 1
    # children close before parents, so their intervals nest
    assert outer.t0 <= by_name["inner_a"].t0
    assert outer.t0 + outer.dur >= by_name["inner_b"].t0 + by_name["inner_b"].dur


def test_span_noop_without_tracer():
    # no tracer installed: the context manager is the shared no-op
    ctx = span("nothing", cat="node")
    with ctx as rec:
        assert rec is None


def test_exception_path_closes_spans():
    with pytest.raises(ValueError, match="boom"):
        with trace_run() as tr:
            with span("will_fail", cat="step"):
                raise ValueError("boom")
    failed = next(s for s in tr.spans if s.name == "will_fail")
    assert failed.error and failed.dur >= 0.0
    root = next(s for s in tr.spans if s.name == "pipeline_run")
    assert root.error  # the run itself is marked failed
    # the tracer's thread stack fully unwound: a new span is a root again
    with trace_run() as tr2:
        with span("fresh"):
            pass
    fresh = next(s for s in tr2.spans if s.name == "fresh")
    assert fresh.parent == next(
        s for s in tr2.spans if s.name == "pipeline_run").sid


def test_profiler_failure_keeps_elapsed_time_and_counts():
    """Satellite fix: a thunk that raises must not lose its elapsed time
    or force count (try/finally), and bumps a failure counter."""
    from keystone_tpu.utils.profiling import ExecutionProfiler
    from keystone_tpu.workflow.expressions import Expression

    prof = ExecutionProfiler()

    def bad_thunk():
        _time.sleep(0.05)
        raise RuntimeError("solver died")

    expr = prof.wrap("exploding", Expression(bad_thunk))
    with pytest.raises(RuntimeError, match="solver died"):
        expr.get
    p = prof.profiles["exploding"]
    assert p.forced == 1 and p.failures == 1
    assert p.seconds >= 0.04  # elapsed time survived the raise
    assert p.bytes == 0.0


# ----------------------------------------------------- trace JSON schema


def _run_traced_pipeline(tmp_path, n=48, dim=12):
    """A pipeline exercising all three runtime layers: a streaming
    host-batched stage (chunk spans), node forces, and a BCD solver fit
    (step spans)."""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import (
        ClassLabelIndicatorsFromInt,
        MaxClassifier,
    )

    class StreamScale(Transformer):
        chunkable = True

        def apply(self, x):
            return x * 2.0  # eval_shape-traceable: the analyzer's
            # spec_pass resolves this stage statically

        def apply_batch_stream(self, data):
            from keystone_tpu.utils import batching

            return batching.map_host_batched_stream(
                data.items, lambda X: X * 2.0, chunk=8)

    class ToDevice(Transformer):
        def apply(self, x):
            return x

        def batch_transform(self, inputs):
            items = inputs[0].items if isinstance(inputs[0], HostDataset) \
                else list(inputs[0])
            return Dataset.from_numpy(np.stack(
                [np.asarray(x, np.float32) for x in items]))

    rng = np.random.default_rng(7)
    X = [rng.normal(size=(dim,)).astype(np.float32) for _ in range(n)]
    y = rng.integers(0, 3, size=n).astype(np.int32)
    labels = ClassLabelIndicatorsFromInt(3)(Dataset.from_numpy(y)).get()

    path = str(tmp_path / "trace.json")
    with overlap_override(True, prefetch_depth=2):
        with trace_run(path):
            featurizer = StreamScale().to_pipeline() >> ToDevice()
            predictor = featurizer.and_then(
                BlockLeastSquaresEstimator(8, num_iter=2, lam=0.1),
                HostDataset(X),
                labels,
            ) >> MaxClassifier()
            predictor(HostDataset(X)).get()
    return path


def test_trace_json_is_valid_chrome_trace(tmp_path):
    path = _run_traced_pipeline(tmp_path)
    trace = load_trace(path)  # raises on a non-trace object
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    for e in events:
        assert isinstance(e, dict)
        assert "name" in e and "ph" in e and "pid" in e
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
    # round-trips through json
    json.loads(json.dumps(trace))
    # the three runtime hierarchy levels are all present
    cats = {e.get("cat") for e in events if e.get("ph") == "X"}
    assert {"node", "chunk", "step"} <= cats, cats
    # and they nest: every step/chunk span links to a parent
    linked = [e for e in events
              if e.get("ph") == "X" and e.get("cat") in ("step", "chunk")]
    assert linked and all(
        "parent_id" in e.get("args", {}) or e.get("cat") == "chunk"
        for e in linked)
    # prefetch/queue metrics made it into the export
    metrics = trace["keystone"]["metrics"]
    assert "prefetch.consumer_wait_s" in metrics["histograms"]
    assert metrics["counters"]["executor.node_forces"]["value"] > 0


def test_cli_summary_includes_memory_reconciliation(tmp_path):
    path = _run_traced_pipeline(tmp_path)
    out = summarize(load_trace(path))
    assert "top node forces by self-time" in out
    assert "solver iterations" in out and "bcd_epoch" in out
    assert "static vs observed memory" in out
    # the solver-side nodes appear in the reconciliation table
    assert "BlockLeastSquaresEstimator" in out or "DelegatingOperator" in out
    # and the module runs as a CLI
    from keystone_tpu.telemetry.__main__ import main as cli_main

    assert cli_main([path]) == 0


def test_reconciliation_static_matches_observed_for_solver_output(tmp_path):
    """The static KP2xx model and the observed bytes agree exactly for
    dense fixed-shape outputs (the solver-adjacent nodes) — the
    reconciliation loop's base case."""
    from keystone_tpu.analysis.reconcile import reconcile_trace

    path = _run_traced_pipeline(tmp_path)
    rec = reconcile_trace(load_trace(path))
    both = [r for r in rec["rows"] if r["rel_error"] is not None]
    assert both, "no node had both static and observed bytes"
    exact = [r for r in both if abs(r["rel_error"]) < 1e-6]
    assert exact, f"no exact reconciliation rows: {both}"
    assert rec["observed_peak_bytes"] and rec["observed_peak_bytes"] > 0


def test_streamed_stage_gets_node_span_and_bytes():
    """Review regression: a chunkable chain drains the upstream stage
    through iter_chunks() — the memoized thunk never runs — yet the
    stage must still appear in spans, bytes, and live-set accounting
    (instrumented at the chunk generator, marked ``streamed``)."""

    class StreamDouble(Transformer):
        chunkable = True

        def apply(self, x):
            return x * 2.0

        def apply_batch_stream(self, data):
            from keystone_tpu.utils import batching

            return batching.map_host_batched_stream(
                data.items, lambda X: X * 2.0, chunk=8)

    X = [np.ones((4,), np.float32) * i for i in range(32)]
    with overlap_override(True, prefetch_depth=2):
        with trace_run() as tr:
            pipe = StreamDouble().to_pipeline() >> Transformer.from_function(
                lambda x: x + 1.0, name="inc")
            out = pipe(HostDataset(X)).get()
    np.testing.assert_allclose(np.stack(out.items), np.stack(X) * 2.0 + 1.0)
    node_spans = {s.name: s for s in tr.spans if s.cat == "node"}
    assert "force StreamDouble" in node_spans, sorted(node_spans)
    assert "force Fn[inc]" in node_spans or "force inc" in node_spans \
        or any("inc" in n for n in node_spans)
    up = node_spans["force StreamDouble"]
    assert up.args.get("streamed") is True
    assert up.args.get("out_bytes") == 32 * 4 * 4  # real bytes, not 64B
    # the span covers the actual drain window: ts is the FIRST-pull
    # timestamp (not the completion time the record is written at),
    # dur stays the cumulative pull time, and drain_window_s carries
    # the full first-pull→exhaustion extent (≥ dur: the consumer's
    # between-chunk work is excluded from dur but inside the window)
    window = up.args.get("drain_window_s")
    assert window is not None
    assert window + 2e-6 >= up.dur
    assert 0.0 <= up.t0 <= up.t0 + window <= tr.now() + 2e-6


def test_observed_live_peak_is_per_run():
    """Review regression: the reconciliation's observed peak must be
    scoped to the traced run, not the process-cumulative gauge."""
    data = Dataset.from_numpy(np.ones((16, 8), np.float32))

    def one_run():
        PipelineEnv.reset()
        with trace_run() as tr:
            Transformer.from_function(lambda x: x * 2.0)(data).get()
        return tr.metadata.get("observed_live_peak_bytes", 0.0)

    first = one_run()
    second = one_run()
    assert first > 0
    assert second == pytest.approx(first)  # no carry-over between runs


# ------------------------------------------------- overlap engine bounds


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_queue_depth_gauge_obeys_documented_bound(depth):
    """utils/batching.py documents ≤ 2·prefetch_depth + 2 chunks resident
    per stage; the gauges' high-water marks must respect it."""
    items = [np.full((4,), i, np.float32) for i in range(64)]
    with overlap_override(True, prefetch_depth=depth):
        out = map_host_batched(items, lambda X: X * 2.0, chunk=4)
    np.testing.assert_allclose(
        np.stack(out), np.stack(items) * 2.0)
    reg = registry()
    assert reg.gauge("prefetch.queue_depth").max <= depth + 1
    assert reg.gauge("overlap.inflight_results").max <= depth + 1
    assert reg.gauge("overlap.resident_chunks").max <= 2 * depth + 2
    assert reg.counter("overlap.chunks_dispatched").value == 16
    assert reg.counter("overlap.bytes_pulled").value > 0


def test_producer_exception_still_records_metrics_and_raises():
    items = [np.ones((4,), np.float32)] * 32

    def exploding(X):
        raise RuntimeError("device fell over")

    with overlap_override(True, prefetch_depth=2):
        with pytest.raises(RuntimeError, match="device fell over"):
            map_host_batched(items, exploding, chunk=4)
    # gauges exist and the failure did not leave accounting below zero
    assert registry().gauge("prefetch.queue_depth").max >= 0


# -------------------------------------------------- autocache consistency


class _SlowShared(Transformer):
    def apply(self, x):
        _time.sleep(0.12)
        return x * 2.0

    def apply_batch(self, data):
        _time.sleep(0.12)
        return data.map_batches(lambda a: a * 2.0)


class _Cheap(Transformer):
    def apply(self, x):
        return x + 1.0

    def apply_batch(self, data):
        return data.map_batches(lambda a: a + 1.0)


def _shared_slow_graph():
    """data -> slow -> {a, b}: the slow node is demanded twice, the
    classic cache-me shape (reference AutocCacheRuleSuite)."""
    from keystone_tpu.workflow.graph import Graph
    from keystone_tpu.workflow.operators import DatasetOperator

    g = Graph()
    g, data = g.add_node(
        DatasetOperator(Dataset.from_numpy(np.ones((64, 4), np.float32))), [])
    g, slow = g.add_node(_SlowShared(), [data])
    g, a = g.add_node(_Cheap(), [slow])
    g, b = g.add_node(_Cheap(), [slow])
    g, _ = g.add_sink(a)
    g, _ = g.add_sink(b)
    return g, slow


def test_autocache_greedy_identical_on_telemetry_profiles(monkeypatch):
    """Greedy decisions fed by telemetry-derived profiles: the shared
    slow node is cached, and replaying the rule on the captured profiles
    makes the identical decision (cache choices and user-facing reports
    draw from the same span data, so they cannot disagree)."""
    import keystone_tpu.workflow.autocache as ac
    from keystone_tpu.workflow.autocache import AutoCacheRule, CacheMarker

    PipelineEnv.reset()
    g, slow = _shared_slow_graph()
    candidates = AutoCacheRule._candidates(g)
    assert slow in candidates
    profiles = ac.profile_nodes(g, candidates, scales=(2, 4))
    # telemetry attribution: the 120 ms sleep lands on the slow node
    assert profiles[slow].ns > 100e6
    assert profiles[slow].mem_bytes > 0

    def cached_parents(graph):
        return {
            graph.get_operator(graph.get_dependencies(n)[0]).label
            for n in graph.nodes
            if isinstance(graph.get_operator(n), CacheMarker)
        }

    live_rule = AutoCacheRule(strategy="greedy", mem_budget_bytes=1 << 20)
    g_live, _ = live_rule.apply((g, {}))
    decisions_live = cached_parents(g_live)
    assert "_SlowShared" in decisions_live

    # identical decisions when the rule replays the SAME telemetry-derived
    # profiles without re-measuring
    monkeypatch.setattr(ac, "profile_nodes", lambda *a, **k: profiles)
    replay_rule = AutoCacheRule(strategy="greedy", mem_budget_bytes=1 << 20)
    g_replay, _ = replay_rule.apply((g, {}))
    assert cached_parents(g_replay) == decisions_live


def test_profile_execution_report_still_works():
    """Public API preserved: profile_execution + report() rows."""
    from keystone_tpu.utils.profiling import profile_execution

    PipelineEnv.reset()
    data = Dataset.from_numpy(np.ones((16, 4), np.float32))
    pipe = Transformer.from_function(lambda x: x * 3.0, name="tripler").to_pipeline()
    with profile_execution() as prof:
        pipe(data).get()
    report = prof.report()
    assert "tripler" in report and "seconds" in report
    assert any(p.forced for p in prof.profiles.values())


# ------------------------------------------------------- executor counters


def test_memo_and_prefix_counters_count_reuse():
    from keystone_tpu.utils.profiling import profile_execution

    PipelineEnv.reset()
    rng = np.random.default_rng(0)
    data = Dataset.from_numpy(rng.normal(size=(32, 4)).astype(np.float32))
    with profile_execution():
        p = Pipeline.gather([
            Transformer.from_function(lambda x: x * 2.0),
            Transformer.from_function(lambda x: x + 1.0),
        ])
        p(data).get()
    assert registry().counter("executor.node_forces").value > 0


# --------------------------------------------------- per-process dimension


def test_per_process_dispatch_dimension(monkeypatch):
    """Under a multi-host mesh every dispatch also lands on a
    per-process counter (dispatch.programs_executed.p<i>), and the
    shared dispatch/compile summaries render the breakdown; single-host
    jobs get no duplicate counter."""
    from keystone_tpu.telemetry import instrument

    # single-host: no per-process counter
    monkeypatch.setattr(instrument, "_proc_dim_cache", "")
    before = registry().counter("dispatch.programs_executed").value
    instrument.record_dispatch()
    assert registry().counter("dispatch.programs_executed").value == before + 1
    assert not any(k.startswith("dispatch.programs_executed.p")
                   for k in registry().counters)

    # simulated process 1 of a multi-host job
    monkeypatch.setattr(instrument, "_proc_dim_cache", "p1")
    instrument.record_dispatch(3)
    assert registry().counter("dispatch.programs_executed.p1").value == 3

    from keystone_tpu.telemetry.export import dispatch_summary

    trace = {"traceEvents": [], "keystone": {"metrics": registry().snapshot()}}
    line = dispatch_summary(trace)
    assert line is not None and "per-process: p1=3" in line


def test_per_process_compile_summary_breakdown():
    from keystone_tpu.telemetry.export import compile_summary

    trace = {"traceEvents": [], "keystone": {"metrics": {
        "counters": {
            "dispatch.programs_compiled": {"value": 5},
            "dispatch.programs_compiled.p0": {"value": 3},
            "dispatch.programs_compiled.p1": {"value": 2},
            "dispatch.compile_cache_hits": {"value": 0},
        },
        "histograms": {},
    }}}
    line = compile_summary(trace)
    assert "5 cold" in line and "per-process: p0=3 p1=2" in line
