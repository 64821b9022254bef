"""KP10xx static chain-kernel verifier — `analysis/kernels.py`.

Marked ``lint``: data-free, device-free (`eval_shape` traces only),
mirroring `scripts/lint.sh`'s --audit-kernels stage so CI and pytest
cannot drift.

The acceptance contract:

  - both registered lowering families verify clean on every rule at
    their flagship geometries (the --audit-kernels 6/6 gate);
  - every seeded mutation — an off-by-one grid, a constant index map,
    an out-of-range grid, a floor-instead-of-ceil pad recipe, an
    inflated VMEM block, a dropped/shifted mask stream, a corrupted
    boundary aval — is caught by the rule that owns it;
  - the static KP1003 verdict agrees with `chain_feasible`'s runtime
    chooser on every geometry in the matrix, under the default AND
    floored VMEM budgets (the shared-formula identity);
  - the unified planner prices statically refuted kernel entries INF
    (`kernel_choices` stays empty) and annotates verified candidates;
  - the --audit-kernels CLI emits the CI-annotation JSON schema with
    zero unsuppressed findings.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from keystone_tpu.analysis import Severity, as_source_spec, validate_graph
from keystone_tpu.analysis.examples import build_example
from keystone_tpu.analysis.kernels import (
    audit_kernels,
    batcher_pad_targets,
    check_grid_coverage,
    check_mask_discipline,
    check_oracle_boundaries,
    check_ragged_bounds,
    check_read_bounds,
    check_vmem_budget,
    statically_verified,
    verify_lowering,
)
from keystone_tpu.analysis.propagate import spec_pass
from keystone_tpu.nodes.images.core import (
    GrayScaler,
    ImageVectorizer,
    PixelScaler,
    _RectifyPoolStage,
)
from keystone_tpu.nodes.stats.scalers import StandardScalerModel
from keystone_tpu.ops import chain_kernels as ck

pytestmark = pytest.mark.lint

REPO = Path(__file__).resolve().parent.parent


def _elem_stages():
    """The LinearPixels flagship elementwise-chain trail."""
    return [PixelScaler(), GrayScaler(), ImageVectorizer()]


def _rect_stages(pool=14, stride=13):
    """The RandomPatchCifar rectify→pool→vectorize trail."""
    return [_RectifyPoolStage(0.25, 0.0, pool, stride), ImageVectorizer()]


def _masked_stages():
    """An elementwise trail with a `fuse_masks_output` stage (the
    StandardScalerModel padded-row re-zeroing contract)."""
    return [PixelScaler(), ImageVectorizer(),
            StandardScalerModel(np.zeros(192, np.float32),
                                np.ones(192, np.float32))]


# ------------------------------------------------------ clean lowerings


def test_elementwise_lowering_verifies_clean():
    proof, diags = verify_lowering(_elem_stages(), (32, 32, 3))
    assert proof["family"] == "elementwise_chain"
    assert proof["verified"] and proof["refuted_by"] is None
    assert diags == []
    for rule in ("KP1001", "KP1002", "KP1003", "KP1004", "KP1005"):
        assert proof["rules"][rule].startswith("proved"), (
            rule, proof["rules"][rule])


def test_rectify_lowering_verifies_clean():
    proof, diags = verify_lowering(_rect_stages(), (27, 27, 256))
    assert proof["family"] == "rectify_pool_vectorize"
    assert proof["verified"] and proof["refuted_by"] is None
    assert diags == []
    for rule in ("KP1001", "KP1002", "KP1003", "KP1004", "KP1005"):
        assert proof["rules"][rule].startswith("proved"), rule


def test_masked_trail_proves_mask_discipline():
    proof, diags = verify_lowering(_masked_stages(), (8, 8, 3))
    assert proof["verified"] and diags == []
    assert "position(s) [2]" in proof["rules"]["KP1004"]


def test_unlowerable_trail_returns_no_verdict():
    """A chain no family expresses gets no proof and no diagnostics —
    the pre-kernel XLA path needs no kernel safety story."""
    from keystone_tpu.nodes.stats import PaddedFFT

    proof, diags = verify_lowering([PaddedFFT()], (48,))
    assert not proof["verified"] and diags == []
    assert statically_verified([PaddedFFT()], (48,)) is None


# --------------------------------------- KP1001 seeded grid mutations


def test_kp1001_off_by_one_grid_is_a_gap():
    imap = lambda i: (i, 0)  # noqa: E731
    assert check_grid_coverage((4,), (4, 8), imap, (16, 8)) == []
    problems = check_grid_coverage((3,), (4, 8), imap, (16, 8))
    assert problems and "coverage gap" in problems[0]


def test_kp1001_constant_index_map_is_a_double_write():
    problems = check_grid_coverage((4,), (4, 8), lambda i: (0, 0),
                                   (16, 8))
    assert problems and all("double-write" in p for p in problems)


def test_kp1001_overrun_grid_writes_out_of_bounds():
    problems = check_grid_coverage((5,), (4, 8), lambda i: (i, 0),
                                   (16, 8))
    assert problems and any("outside output dim 0" in p
                            for p in problems)


# ----------------------------------- KP1002 seeded pad/read mutations


def test_kp1002_floor_pad_recipe_drops_rows():
    counts = batcher_pad_targets(256)
    assert check_ragged_bounds(4, counts) == []
    floor = lambda n, b: (n // b) * b  # noqa: E731
    problems = check_ragged_bounds(4, [6], pad=floor)
    assert problems and "drops" in problems[0]


def test_kp1002_read_past_padded_operand():
    imap = lambda i: (i, 0)  # noqa: E731
    assert check_read_bounds((3,), (4, 8), imap, (12, 8), name="x") == []
    problems = check_read_bounds((4,), (4, 8), imap, (12, 8), name="x")
    assert problems and "x: grid point (3,) reads [12, 16)" in problems[0]


# -------------------------------------- KP1003 seeded block mutations


def test_kp1003_inflated_block_busts_budget_and_chooser():
    """A block one ladder rung above the chooser's pick fails BOTH
    halves: the working set exceeds the budget and the chooser-identity
    check names the divergence."""
    ladder = (8, 4, 2, 1)
    io = 1 << 20
    assert check_vmem_budget(4, io, 0, 0, ladder) == []
    problems = check_vmem_budget(8, io, 0, 0, ladder)
    assert any("exceeds the VMEM budget" in p for p in problems)
    assert any("chooser divergence" in p for p in problems)


def test_kp1003_deflated_block_is_chooser_divergence_only():
    problems = check_vmem_budget(2, 1 << 20, 0, 0, (8, 4, 2, 1))
    assert problems == [p for p in problems if "chooser divergence" in p]
    assert problems


def test_kp1003_shared_formula_is_the_chain_formula():
    """The one working-set arithmetic, pinned: 2× double-buffered
    streamed blocks + bn× transients + params."""
    assert ck.chain_vmem_bytes(3, 10, 4, 7) == 2 * 3 * 10 + 3 * 4 + 7
    assert ck.chain_block_rows(1 << 20, ladder=(8, 4, 2, 1)) == 4
    assert ck.chain_block_rows(1 << 30, ladder=(8, 4, 2, 1)) == 0


# --------------------------------------- KP1004 seeded mask mutations


def test_kp1004_dropped_mask_stream():
    problems = check_mask_discipline([1], [], False)
    assert problems and "streams no mask operand" in problems[0]


def test_kp1004_mask_consumed_at_wrong_position():
    problems = check_mask_discipline([1], [2], True)
    assert any("stage 1 declares fuse_masks_output" in p
               for p in problems)
    assert any("position 2 where no stage declares" in p
               for p in problems)


def test_kp1004_clean_positions_stay_silent():
    assert check_mask_discipline([], [], False) == []
    assert check_mask_discipline([0, 2], [0, 2], True) == []


# ------------------------------------- KP1005 seeded oracle mutations


def _avals(*shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype) for s in shapes]


def test_kp1005_boundary_count_mismatch():
    problems = check_oracle_boundaries(
        _avals((4, 8), (4, 16)), _avals((4, 8), (4, 16), (4, 32)), 4)
    assert problems and "boundary count mismatch" in problems[0]


def test_kp1005_dtype_and_tail_mismatch():
    kern = _avals((4, 8), (4, 16))
    oracle = _avals((4, 8)) + _avals((4, 24), dtype=jnp.bfloat16)
    problems = check_oracle_boundaries(kern, oracle, 4)
    assert any("dtype" in p for p in problems)
    assert any("tail" in p for p in problems)


def test_kp1005_batch_axis_not_preserved():
    problems = check_oracle_boundaries(
        _avals((4, 8), (1, 8)), _avals((4, 8), (1, 8)), 4)
    assert problems and "does not preserve the batch axis" in problems[0]


# ------------------------------- end-to-end refutation + the identity


def test_floored_budget_refutes_without_error(monkeypatch):
    """A VMEM-infeasible geometry the runtime chooser also refuses is a
    refutation FACT (refuted_by KP1003), not a safety ERROR — the
    planner prices it INF, nothing is broken."""
    monkeypatch.setattr(ck, "_VMEM_BUDGET", 1)
    proof, diags = verify_lowering(_elem_stages(), (32, 32, 3))
    assert proof["refuted_by"] == "KP1003"
    assert not proof["verified"]
    assert not [d for d in diags if d.severity >= Severity.ERROR]
    assert statically_verified(_elem_stages(), (32, 32, 3)) is False


@pytest.mark.parametrize("budget", [None, 1, 200_000, 3 << 20])
def test_static_verdict_agrees_with_chain_feasible(monkeypatch, budget):
    """The shared-formula identity, test-pinned: on every geometry in
    the matrix — both families, feasible and infeasible, default and
    floored budgets — `statically_verified` and `chain_feasible` reach
    the same verdict, because both sit on `chain_vmem_bytes`."""
    if budget is not None:
        monkeypatch.setattr(ck, "_VMEM_BUDGET", budget)
    matrix = [
        (_elem_stages(), (32, 32, 3)),
        (_elem_stages(), (8, 8, 3)),
        (_elem_stages(), (128, 128, 3)),
        (_elem_stages(), (2048, 2048, 3)),
        (_masked_stages(), (8, 8, 3)),
        (_rect_stages(), (27, 27, 256)),
        (_rect_stages(), (27, 27, 32)),
        (_rect_stages(), (5, 5, 8)),  # empty pool grid
    ]
    for stages, item in matrix:
        feasible, reason = ck.chain_feasible(stages, item, jnp.float32)
        verdict = statically_verified(stages, item)
        assert verdict is not None, (item, reason)
        assert verdict == bool(feasible), (item, reason, verdict)


def test_chooser_decisions_pinned():
    """Satellite regression pin: deduplicating the inline VMEM formulas
    into `chain_vmem_bytes`/`chain_block_rows` changed NO chooser
    decision."""
    assert ck._rectify_pool_vectorize_block(27, 27, 256, 14, 13) == 5
    assert ck.chain_feasible(_elem_stages(), (32, 32, 3),
                             jnp.float32) == (True, "block=4")
    assert ck.chain_feasible(_rect_stages(), (27, 27, 256),
                             jnp.float32) == (True, "block=5")


def test_batcher_pad_targets_enumerates_the_pr5_ladder():
    assert batcher_pad_targets(256) == [1, 2, 4, 8, 16, 32, 64, 128, 256]
    assert batcher_pad_targets(None) == [1] or batcher_pad_targets(None)


# --------------------------------------------- analyzer + planner wiring


def test_validate_full_runs_kernel_tier_clean():
    pipeline, source_spec = build_example("LinearPixels")
    report = validate_graph(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)},
        level="full")
    kern = [d for d in report.diagnostics
            if d.rule.startswith("KP100") and len(d.rule) == 6
            and d.severity >= Severity.WARNING]
    assert kern == [], kern


def test_audit_kernels_all_registered_lowerings_verify():
    findings, stats = audit_kernels()
    assert findings == [], findings
    assert not stats["build_errors"], stats["build_errors"]
    assert stats["lowerings"] >= 6
    assert stats["verified"] == stats["lowerings"]
    families = {p["family"] for p in stats["proofs"]}
    assert families == {"elementwise_chain", "rectify_pool_vectorize"}


def test_planner_annotates_verified_candidates():
    from keystone_tpu.analysis.plan_ir import plan_unified

    pipeline, source_spec = build_example("LinearPixels")
    specs, _ = spec_pass(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
    uplan = plan_unified(pipeline.graph, specs)
    assert uplan is not None and uplan.kernel_choices
    for cand in uplan.kernel_choices.values():
        assert cand["statically_verified"] is True, cand


def test_planner_prices_refuted_kernels_inf(monkeypatch):
    """A statically refuted lowering never joins the chosen plan even
    when its VMEM probe would pass — the verifier's verdict is its own
    gate, not an alias of `chain_feasible`."""
    import keystone_tpu.analysis.kernels as kmod
    from keystone_tpu.analysis.plan_ir import plan_unified

    monkeypatch.setattr(kmod, "statically_verified",
                        lambda *a, **k: False)
    pipeline, source_spec = build_example("LinearPixels")
    specs, _ = spec_pass(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
    uplan = plan_unified(pipeline.graph, specs)
    assert uplan is not None
    assert uplan.kernel_choices == {}, uplan.kernel_choices
    assert uplan.joint_seconds <= uplan.sequential_seconds


def test_kernel_pass_annotates_candidates_in_place():
    from keystone_tpu.analysis.kernels import kernel_pass
    from keystone_tpu.analysis.roofline import roofline_pass

    pipeline, source_spec = build_example("LinearPixels")
    specs, _ = spec_pass(
        pipeline.graph, {pipeline.source: as_source_spec(source_spec)})
    est, _ = roofline_pass(pipeline.graph, specs)
    proofs, diags = kernel_pass(pipeline.graph, specs, est)
    assert proofs and all(p["verified"] for p in proofs)
    lowerable = [c for c in est.candidates
                 if (c.get("lowerable") or {}).get("lowerable")]
    assert lowerable
    assert all(c.get("statically_verified") is True for c in lowerable)


# ------------------------------------------------------------------ CLI


def test_audit_kernels_cli_json_schema():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "keystone_tpu.analysis",
         "--audit-kernels", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert not payload["build_errors"]
    assert payload["audited_examples"] >= 7
    assert payload["total_lowerings"] >= 6
    assert payload["verified_lowerings"] == payload["total_lowerings"]
    for p in payload["proofs"]:
        assert p["verified"] is True, p
        assert set(p["rules"]) >= {"KP1001", "KP1002", "KP1003",
                                   "KP1004", "KP1005"}, p
