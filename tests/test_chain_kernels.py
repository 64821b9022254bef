"""Chain-megakernel backend acceptance suite — PR 16.

The contract (`keystone_tpu/ops/chain_kernels.py` + the fusion swap +
the unified planner's kernel axis):

  - both candidate families lower: the elementwise chain (the
    LinearPixels PixelScaler >> GrayScaler >> ImageVectorizer trail)
    and rectify→pool→vectorize, each matching its pure-jnp
    ``*_reference`` oracle in interpret mode at multiple AND ragged
    counts;
  - `fuse_masks_output` stages keep padded rows EXACT inside the
    kernel (the masked-stage column is streamed into VMEM);
  - a VMEM-overbudget geometry demotes cleanly: the dispatcher falls
    back to the oracle, and the planner prices the kernel assignment
    INF (`vmem_feasible` False → never chosen, never crashes);
  - the kill switch: `pallas_kernels=False` (env
    ``KEYSTONE_CHAIN_KERNELS=0``) reproduces the XLA-only program
    bit for bit and dispatches zero chain kernels;
  - the unified planner records the kernel decision in the ledger
    with the scored kernel/XLA alternative pair, and the enforced
    `planned_kernel` tag rides the fused program key (AOT-warmable:
    a warm second run performs zero cold compiles);
  - the bench tier: the ``kernel`` plan column exists and the
    LinearPixels bench instance actually swaps.
"""

import os

import numpy as np
import pytest

import jax.numpy as jnp

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.images.core import (
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
)
from keystone_tpu.nodes.stats.scalers import StandardScalerModel
from keystone_tpu.nodes.util.fusion import (
    FusedBatchTransformer,
    fused_stages,
    stage_fuse,
)
from keystone_tpu.ops import chain_kernels as ck
from keystone_tpu.telemetry import ledger
from keystone_tpu.workflow import PipelineEnv
from keystone_tpu.workflow.env import config_override
from keystone_tpu.workflow.optimizer import DefaultOptimizer


def _elementwise_trail():
    """The LinearPixels featurizer trail — the planner's flagship
    elementwise-chain candidate."""
    stages = [PixelScaler(), GrayScaler(), ImageVectorizer()]
    fused = [stage_fuse(s) for s in fused_stages(stages)]
    return tuple(f[0] for f in fused), [f[1] for f in fused]


def _pipeline():
    return (PixelScaler().to_pipeline() >> GrayScaler()
            >> ImageVectorizer())


def _run(pipe, X, optimizer=None, **overrides):
    """One clean-env run; returns (host outputs, optimized graph)."""
    PipelineEnv.reset()
    try:
        if optimizer is not None:
            PipelineEnv.get().set_optimizer(optimizer)
        with config_override(**overrides):
            applied = pipe(Dataset.from_numpy(X))
            out = np.asarray(applied.get().numpy())
            return out, applied.executor.optimized_graph
    finally:
        PipelineEnv.reset()


def _serial_unfused(pipe, X):
    out, _ = _run(
        pipe, X,
        optimizer=DefaultOptimizer(fuse=False, sharding_planner=False,
                                   precision_planner=False,
                                   unified_planner=False),
        megafusion=False, overlap=False, concurrent_dispatch=False)
    return out


# ------------------------------------------------------------ lowerability


def test_lowerability_families():
    statics, _ = _elementwise_trail()
    v = ck.lowerability(statics)
    assert v["lowerable"] and v["family"] == "elementwise_chain", v

    from keystone_tpu.nodes.images.core import Pooler, SymmetricRectifier
    trail = [SymmetricRectifier(alpha=0.25), Pooler(6, 7, pool_fn="sum"),
             ImageVectorizer()]
    v = ck.lowerability(ck.stage_statics(trail))
    assert v["lowerable"] and v["family"] == "rectify_pool_vectorize", v


def test_unsupported_stage_is_a_named_suppression():
    """A chain blocked ONLY by deliberate non-lowerings (PaddedFFT)
    carries the named suppression the lint.sh audit accepts."""
    from keystone_tpu.nodes.stats import LinearRectifier, PaddedFFT
    trail = [PaddedFFT(), LinearRectifier(0.0)]
    v = ck.lowerability(ck.stage_statics(trail))
    assert not v["lowerable"]
    assert "PaddedFFT" in (v.get("suppressed") or {}), v


# ------------------------------------- interpret-mode numerics vs oracle


@pytest.mark.parametrize("n", [3, 11, 37, 64])
def test_elementwise_chain_interpret_matches_reference(n):
    """Multiple AND ragged counts: block_n=4 forces a padded tail
    block on every non-multiple count."""
    statics, params = _elementwise_trail()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(n, 8, 8, 3).astype(np.float32))
    got = np.asarray(ck.elementwise_chain_pallas(
        statics, params, x, block_n=4, interpret=True))
    want = np.asarray(ck.elementwise_chain_reference(statics, params, x))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n", [2, 7, 16])
def test_rectify_pool_vectorize_interpret_matches_reference(n):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(n, 12, 12, 8).astype(np.float32))
    got = np.asarray(ck.rectify_pool_vectorize_pallas(
        x, 0.25, 0.0, 6, 5, interpret=True))
    want = np.asarray(ck.rectify_pool_vectorize_reference(
        x, 0.25, 0.0, 6, 5))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_masked_stage_padded_rows_exact():
    """`fuse_masks_output` inside the kernel: a chain containing a
    StandardScalerModel re-zeros padded rows at its chain position —
    bit-identical to the oracle's masking, including rows where the
    scaler would otherwise write (0 - mean) / std."""
    stages = [PixelScaler(), ImageVectorizer(),
              StandardScalerModel(np.full((192,), 0.5, np.float32),
                                  np.full((192,), 2.0, np.float32))]
    fused = [stage_fuse(s) for s in fused_stages(stages)]
    statics = tuple(f[0] for f in fused)
    params = [f[1] for f in fused]
    rng = np.random.RandomState(2)
    n, valid = 10, 6
    x = jnp.asarray(rng.rand(n, 8, 8, 3).astype(np.float32))
    mask = jnp.asarray(np.arange(n) < valid)
    got = np.asarray(ck.elementwise_chain_pallas(
        statics, params, x, mask, block_n=4, interpret=True))
    want = np.asarray(ck.elementwise_chain_reference(
        statics, params, x, mask))
    np.testing.assert_array_equal(got[valid:], want[valid:])
    assert np.all(got[valid:] == 0.0), "padded rows must stay zero"
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ------------------------------------------------- VMEM-overbudget demotion


def test_vmem_overbudget_demotes_to_reference(monkeypatch):
    """An overbudget geometry never crashes: the dispatcher falls back
    to the oracle, and `chain_feasible` reports the named reason the
    planner prices INF."""
    monkeypatch.setenv("KEYSTONE_CHAIN_KERNELS", "interpret")
    monkeypatch.setattr(ck, "_VMEM_BUDGET", 1)
    statics, params = _elementwise_trail()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.rand(9, 8, 8, 3).astype(np.float32))
    got = np.asarray(ck.elementwise_chain(statics, params, x))
    want = np.asarray(ck.elementwise_chain_reference(statics, params, x))
    np.testing.assert_array_equal(got, want)

    ok, reason = ck.chain_feasible(
        [PixelScaler(), GrayScaler(), ImageVectorizer()], (8, 8, 3))
    assert not ok and "VMEM" in reason, (ok, reason)


def test_vmem_overbudget_planner_never_chooses_kernel(monkeypatch):
    """The planner's side of the demotion: with the budget floored the
    kernel assignment prices INF, so `kernel_choices` stays empty and
    the joint plan remains feasible."""
    from keystone_tpu.analysis import as_source_spec
    from keystone_tpu.analysis.examples import build_example
    from keystone_tpu.analysis.plan_ir import plan_unified
    from keystone_tpu.analysis.propagate import spec_pass

    monkeypatch.setattr(ck, "_VMEM_BUDGET", 1)
    pipeline, source_spec = build_example("LinearPixels")
    specs, _ = spec_pass(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
    uplan = plan_unified(pipeline.graph, specs)
    assert uplan is not None
    assert uplan.kernel_choices == {}, uplan.kernel_choices
    assert uplan.joint_seconds <= uplan.sequential_seconds
    infeasible = [c for c in uplan.scored_candidates
                  if c["entry"].startswith("kernel_") and
                  c["entry"].endswith("_on")]
    assert all(not c["feasible"] for c in infeasible), infeasible


def test_planner_prices_kernel_axis_on_linear_pixels():
    """The healthy-budget twin: the kernel axis joins the product menu,
    the chosen plan turns it on, and the scored entries carry the
    kernel/XLA pair the ledger records."""
    from keystone_tpu.analysis import as_source_spec
    from keystone_tpu.analysis.examples import build_example
    from keystone_tpu.analysis.plan_ir import plan_unified
    from keystone_tpu.analysis.propagate import spec_pass

    pipeline, source_spec = build_example("LinearPixels")
    specs, _ = spec_pass(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
    uplan = plan_unified(pipeline.graph, specs)
    assert uplan is not None and uplan.kernel_choices, uplan
    assert "kernel" in uplan.changed_kinds()
    for cand in uplan.kernel_choices.values():
        assert cand["kernel_seconds"] < cand["chain_seconds"], cand
        assert (cand.get("lowerable") or {}).get("family"), cand
    assert any(c["entry"].startswith("kernel_") and c["feasible"]
               for c in uplan.scored_candidates), uplan.scored_candidates


# ------------------------------------------------------- e2e swap + parity


@pytest.mark.parametrize("n", [37, 64])
def test_e2e_kernel_swap_matches_serial_unfused(monkeypatch, n):
    """The full optimizer path at multiple AND ragged counts: the plan
    tags `planned_kernel`, the fused program dispatches the interpret
    kernel, outputs stay allclose to the serial unfused path."""
    monkeypatch.setenv("KEYSTONE_CHAIN_KERNELS", "interpret")
    pipe = _pipeline()
    rng = np.random.RandomState(4)
    X = rng.rand(n, 8, 8, 3).astype(np.float32)
    out, g = _run(pipe, X, unified_min_savings_seconds=0.0)
    tagged = [op for vid in g.operators
              for op in [g.get_operator(vid)]
              if getattr(op, "planned_kernel", None) is not None]
    assert tagged, "no operator carries a planned_kernel tag"
    start, stop, family = tagged[0].planned_kernel
    assert family == "elementwise_chain" and stop - start >= 2
    base = _serial_unfused(pipe, X)
    np.testing.assert_allclose(out, base, rtol=1e-5, atol=1e-6)


def test_kill_switch_bit_for_bit(monkeypatch):
    """`pallas_kernels=False` reproduces the XLA-only fused program bit
    for bit (same outputs as a run that never heard of kernels) and
    plans no kernel."""
    pipe = _pipeline()
    rng = np.random.RandomState(5)
    X = rng.rand(37, 8, 8, 3).astype(np.float32)
    # reference: the pre-PR16 program (no kernel gate consulted at all
    # off-TPU — use_chain_kernels() is False without the interpret hook)
    want, _ = _run(pipe, X, unified_min_savings_seconds=0.0)
    # killed: planner enforcement off, swap gated off
    got, g = _run(pipe, X, unified_min_savings_seconds=0.0,
                  pallas_kernels=False)
    np.testing.assert_array_equal(got, want)
    assert not [op for vid in g.operators
                for op in [g.get_operator(vid)]
                if getattr(op, "planned_kernel", None) is not None]


def test_stale_kernel_tag_is_ignored(monkeypatch):
    """A `planned_kernel` tag that no longer matches the stage trail
    (the `planned_precision` stale-tag discipline) is silently ignored,
    never mis-lowered."""
    monkeypatch.setenv("KEYSTONE_CHAIN_KERNELS", "interpret")
    rng = np.random.RandomState(6)
    X = rng.rand(13, 8, 8, 3).astype(np.float32)
    stages = [PixelScaler(), GrayScaler(), ImageVectorizer()]
    op = FusedBatchTransformer(stages)
    op.planned_kernel = (0, 9, "elementwise_chain")  # out of range
    PipelineEnv.reset()
    try:
        got = np.asarray(op.apply_batch(
            Dataset.from_numpy(X)).numpy())
        ref = FusedBatchTransformer(
            [PixelScaler(), GrayScaler(), ImageVectorizer()])
        want = np.asarray(ref.apply_batch(
            Dataset.from_numpy(X)).numpy())
    finally:
        PipelineEnv.reset()
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- ledger + warm compiles


def test_kernel_decision_ledger_record(monkeypatch):
    """The enforced kernel axis is ledger-recorded: kind="kernel",
    chosen kernels naming family/slice/prices, and the scored
    alternatives carry the sequential (XLA) and kernel entries."""
    monkeypatch.setenv("KEYSTONE_CHAIN_KERNELS", "interpret")
    pipe = _pipeline()
    rng = np.random.RandomState(7)
    X = rng.rand(37, 8, 8, 3).astype(np.float32)
    mark = ledger.session_mark()
    _run(pipe, X, unified_min_savings_seconds=0.0)
    recs = [r for r in ledger.session_since(mark)
            if r.get("kind") == "kernel"]
    assert recs, "no kernel decision recorded"
    rec = recs[0]
    assert rec["enforced"] and rec["rule"] == "UnifiedPlannerRule", rec
    kernels = (rec.get("chosen") or {}).get("kernels")
    assert kernels, rec
    assert kernels[0]["family"] == "elementwise_chain"
    assert kernels[0]["kernel_seconds"] < kernels[0]["chain_seconds"]
    entries = [a.get("entry") for a in rec.get("alternatives") or []]
    assert "sequential" in entries, entries
    assert any(str(e).startswith("kernel_") for e in entries), entries


def test_ledger_header_names_the_kill_switch():
    """`--diff` can name a kernel flip as the suspect: the header
    snapshots `pallas_kernels` with its env knob."""
    assert ledger.CONFIG_ENV["pallas_kernels"] == "KEYSTONE_CHAIN_KERNELS"
    assert "kernel" in ledger.KINDS


def test_warm_kernel_run_zero_cold_compiles():
    """A rebuilt-from-scratch second run with a planned kernel serves
    everything warm: `planned_kernel` is part of the fused program key,
    so the swapped program caches like any other."""
    from keystone_tpu.dispatch_bench import measure_example
    from keystone_tpu.telemetry import compiles_snapshot
    from keystone_tpu.workflow.executor import drain_warmups

    r1 = measure_example("LinearPixels", "kernel")
    assert r1["apply_run_programs"] >= 1
    drain_warmups()
    first = compiles_snapshot()
    r2 = measure_example("LinearPixels", "kernel")
    drain_warmups()
    second = compiles_snapshot()
    new_cold = second["programs_compiled"] - first["programs_compiled"]
    assert new_cold == 0, (
        f"warm kernel-plan run performed {new_cold} cold compile(s)")
    assert any(d.get("kind") == "kernel" for d in r2["decisions"] or []), (
        "warm run lost the kernel decision")


def test_bench_kernel_plan_column():
    """The dispatch-bench tier gained the `kernel` plan: listed in
    PLANS, and its context turns the unified planner + kernels on."""
    from keystone_tpu.dispatch_bench import PLANS, _plan_context

    assert "kernel" in PLANS
    _, _, _, overrides = _plan_context("kernel")
    assert overrides["unified_planner"] is True
    assert overrides["pallas_kernels"] is True
    assert overrides["unified_min_savings_seconds"] == 0.0
