"""KP10xx static chain-kernel verification tier: prove every registered
chain-kernel lowering (`ops/chain_kernels.py` — the KP801 candidates
the unified planner's kernel axis prices) safe BEFORE any TPU time.

PR 16 lowered the KP801 candidates to hand-rolled Pallas megakernels,
but every safety property they rest on was validated only by
interpret-mode CPU tests — while live TPU windows are scarce and must
not be burned on avoidable Mosaic rejects or silent padded-row
corruption. This tier makes those runtime disciplines *checked static
properties* (the KP2xx/KP5xx/KP6xx/KP9xx pattern; the memory-safe-XLA
discipline of arXiv 2206.14148 applied to kernel geometry), from the
analyzer's propagated element specs, with no device and no tracing
beyond `jax.eval_shape` / `jax.make_jaxpr`:

- **KP1001** grid/index-map coverage: grid × block shape tiles the
  padded output exactly — every output element written exactly once
  (double-writes AND gaps both flagged).
- **KP1002** ragged-tail bounds: block reads stay inside the padded
  operand shapes for EVERY batch count the host batcher's PR-5 pad
  ladder can emit (checked against `utils/batching._pad_target`'s
  actual pad targets, not a convention).
- **KP1003** VMEM working-set proof: 2× double-buffered streamed
  blocks + single-buffered intermediates + closure params ≤ the
  budget, computed by the SAME arithmetic as `chain_feasible`'s
  runtime chooser (`ops.chain_kernels.chain_vmem_bytes` /
  `chain_block_rows` — one shared function, so the static proof and
  the runtime demotion can never diverge; the
  `collective_cost`/`live_set_walk` precedent).
- **KP1004** mask discipline: a `fuse_masks_output` stage inside a
  kernel body that does not consume the streamed mask operand at its
  original chain position is the padded-row corruption class —
  detected structurally from `stage_statics`, not by convention.
- **KP1005** abstract oracle equivalence: the per-block kernel body vs
  the pure-jnp reference oracle — shape/dtype agreement on every stage
  boundary, with the block's leading (batch) dim preserved end to end
  (a body that reduces or grows the batch axis inside a block cannot
  equal the batch oracle).

Surfaced in `validate(level="full")` (after the roofline pass — the
verifier consumes its KP801 candidate list), `python -m
keystone_tpu.analysis --audit-kernels [--json]` (gated in
scripts/lint.sh: every registered lowering verifies clean or carries a
named suppression), the unified planner (statically-refuted kernel
menu entries price INF instead of relying on the runtime canary), the
ledger's kernel records (`statically_verified`, reconciled by
`reconcile.reconcile_roofline`), and `scripts/kernel_live_check.py`
(statically-refuted geometries are skipped with the KP code printed,
so live TPU minutes only test what static analysis cannot prove).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

from .diagnostics import Diagnostic, Severity

#: named suppressions for the --audit-kernels gate: (example, rule) or
#: (example, rule, stage-label-substring) → reason. Each entry states
#: WHY the lowering stays unproven and what would discharge it — the
#: `SUPPRESSED_STAGES`/`SERVING_SUPPRESSIONS` escape-hatch discipline.
#: Empty today: all registered lowerings verify clean.
KERNEL_SUPPRESSIONS: Dict[Tuple[str, str], str] = {}

#: smallest ragged probe the coverage proof re-runs at (exercises the
#: bn_e = min(bn, n) clamp the full-chunk probe cannot see)
_MIN_PROBE = 1


# ---------------------------------------------------------------------------
# Rule checkers — pure functions over explicit geometry, so the
# seeded-mutant tests can feed broken grids/blocks/recipes directly
# ---------------------------------------------------------------------------


def check_grid_coverage(grid, block_shape, index_map, out_shape) -> List[str]:
    """KP1001: prove ``grid`` × ``block_shape`` under ``index_map``
    tiles ``out_shape`` exactly — every output element written exactly
    once. Block origins are index-map outputs scaled by the block shape
    (Pallas `BlockSpec` semantics), so in-bounds distinct origins are
    disjoint by construction; a repeated origin is a double-write, a
    short union is a gap, an origin past the padded extent is an
    out-of-bounds write."""
    import itertools
    import math

    problems: List[str] = []
    origins = set()
    for idx in itertools.product(*(range(int(g)) for g in grid)):
        bi = tuple(index_map(*idx))
        if len(bi) != len(block_shape):
            return [f"index map returns rank {len(bi)} for block rank "
                    f"{len(block_shape)}"]
        origin = tuple(int(b) * int(s) for b, s in zip(bi, block_shape))
        for d, (o, s, full) in enumerate(
                zip(origin, block_shape, out_shape)):
            if o < 0 or o + s > full:
                problems.append(
                    f"grid point {idx}: writes [{o}, {o + s}) outside "
                    f"output dim {d} of size {full}")
        if origin in origins:
            problems.append(
                f"grid point {idx}: double-write — origin {origin} "
                f"already written by an earlier grid step")
        origins.add(origin)
    if problems:
        return problems
    covered = len(origins) * math.prod(int(s) for s in block_shape)
    total = math.prod(int(s) for s in out_shape)
    if covered != total:
        problems.append(
            f"coverage gap: {len(origins)} block(s) of "
            f"{tuple(block_shape)} write {covered} of {total} padded "
            f"output elements")
    return problems


def check_read_bounds(grid, block_shape, index_map, operand_shape,
                      name="operand") -> List[str]:
    """KP1002 (structural half): every block READ stays inside the
    padded operand — repeated reads (broadcast params) are legal, reads
    past the padded extent are not."""
    import itertools

    problems: List[str] = []
    for idx in itertools.product(*(range(int(g)) for g in grid)):
        bi = tuple(index_map(*idx))
        if len(bi) != len(block_shape):
            return [f"{name}: index map returns rank {len(bi)} for "
                    f"block rank {len(block_shape)}"]
        origin = tuple(int(b) * int(s) for b, s in zip(bi, block_shape))
        for d, (o, s, full) in enumerate(
                zip(origin, block_shape, operand_shape)):
            if o < 0 or o + s > full:
                problems.append(
                    f"{name}: grid point {idx} reads [{o}, {o + s}) "
                    f"outside padded dim {d} of size {full}")
                break
    return problems


def check_ragged_bounds(bn, counts, *, pad=None) -> List[str]:
    """KP1002 (pad-ladder half): for every batch count the host batcher
    can emit, the lowering's own padding recipe (``bn_e = min(bn, n)``,
    ``n_pad = round_up(n, bn_e)``, ``grid = n_pad // bn_e``) must cover
    every valid row and end the final block exactly at the padded row
    count. ``pad`` is injectable so the seeded-mutant tests can feed a
    floor-instead-of-ceil recipe."""
    if pad is None:
        from ..ops.pallas_kernels import _round_up as pad
    problems: List[str] = []
    for n_b in counts:
        n_b = int(n_b)
        if n_b <= 0:
            continue
        bn_e = min(int(bn), n_b)
        if bn_e <= 0:
            problems.append(f"count {n_b}: non-positive block {bn_e}")
            continue
        n_pad = int(pad(n_b, bn_e))
        if n_pad < n_b:
            problems.append(
                f"count {n_b}: padded row count {n_pad} drops "
                f"{n_b - n_pad} valid row(s)")
            continue
        grid = n_pad // bn_e
        if grid * bn_e != n_pad:
            problems.append(
                f"count {n_b}: grid {grid} × block {bn_e} covers "
                f"{grid * bn_e} of {n_pad} padded rows")
    return problems


def check_vmem_budget(bn, io_bytes, inter_bytes, param_bytes, ladder, *,
                      budget=None) -> List[str]:
    """KP1003: the chosen block's working set fits the VMEM budget AND
    the static choice is identical to the runtime chooser's — both
    computed by the ONE shared formula (`chain_vmem_bytes` /
    `chain_block_rows`), so a divergence here means the shared-function
    contract itself was broken."""
    from ..ops import chain_kernels as ck

    budget = ck._VMEM_BUDGET if budget is None else budget
    problems: List[str] = []
    if bn <= 0:
        problems.append("no feasible VMEM block at this geometry")
        return problems
    used = ck.chain_vmem_bytes(int(bn), io_bytes, inter_bytes, param_bytes)
    if used > budget:
        problems.append(
            f"block {bn}: working set {used} B (2×{io_bytes} streamed "
            f"+ {bn}×{inter_bytes} transient + {param_bytes} params) "
            f"exceeds the VMEM budget {budget} B")
    chooser = ck.chain_block_rows(io_bytes, inter_bytes, param_bytes,
                                  ladder=ladder, budget=budget)
    if chooser != bn:
        problems.append(
            f"chooser divergence: static proof holds block {bn} but "
            f"the runtime chooser picks {chooser} from the same parts")
    return problems


def check_mask_discipline(declared_positions, consumed_positions,
                          streams_mask) -> List[str]:
    """KP1004: every `fuse_masks_output` stage (declared via its
    `stage_fuse` static's ``(key, "masked")`` wrapping) must re-zero
    padded rows at its ORIGINAL chain position inside the kernel body,
    from a streamed mask operand — a mask applied late, early, or not
    at all lets padded garbage flow through downstream reductions."""
    declared = [int(p) for p in declared_positions]
    consumed = [int(p) for p in consumed_positions]
    problems: List[str] = []
    if declared and not streams_mask:
        problems.append(
            f"stage position(s) {declared} declare fuse_masks_output "
            f"but the kernel streams no mask operand — padded rows are "
            f"never re-zeroed")
        return problems
    for p in declared:
        if p not in consumed:
            problems.append(
                f"stage {p} declares fuse_masks_output but the kernel "
                f"body does not consume the mask at position {p} — the "
                f"padded-row corruption class")
    for p in consumed:
        if p not in declared:
            problems.append(
                f"kernel body masks at position {p} where no stage "
                f"declares fuse_masks_output — the body diverges from "
                f"the node-by-node semantics")
    return problems


def check_oracle_boundaries(kernel_avals, oracle_avals, bn) -> List[str]:
    """KP1005: per-block kernel body vs pure-jnp reference oracle —
    shape/dtype agreement at every stage boundary, with the block's
    leading (batch) dim preserved: a body that reduces or concatenates
    over the batch axis inside a block cannot agree with the batch
    oracle even when per-boundary tails match."""
    problems: List[str] = []
    if len(kernel_avals) != len(oracle_avals):
        return [f"boundary count mismatch: kernel body traces "
                f"{len(kernel_avals)} boundaries, the oracle "
                f"{len(oracle_avals)}"]
    for i, (ka, oa) in enumerate(zip(kernel_avals, oracle_avals)):
        if str(ka.dtype) != str(oa.dtype):
            problems.append(
                f"boundary {i}: kernel dtype {ka.dtype} != oracle "
                f"dtype {oa.dtype}")
        if tuple(ka.shape[1:]) != tuple(oa.shape[1:]):
            problems.append(
                f"boundary {i}: kernel block tail {tuple(ka.shape[1:])} "
                f"!= oracle tail {tuple(oa.shape[1:])}")
        if ka.shape and int(ka.shape[0]) != int(bn):
            problems.append(
                f"boundary {i}: kernel block leading dim "
                f"{ka.shape[0]} != block rows {bn} — the body does not "
                f"preserve the batch axis within a block")
    return problems


# ---------------------------------------------------------------------------
# Pad-ladder enumeration (the KP1002 bucket set)
# ---------------------------------------------------------------------------


_PAD_TARGET_CACHE: Dict[int, List[int]] = {}


def batcher_pad_targets(chunk: Optional[int] = None) -> List[int]:
    """Every padded batch count `utils/batching`'s PR-5 pad ladder can
    emit at the resolved chunk size: full chunks, the pow-2 ladder for
    small buckets, and the tail counts of chunk-straddling buckets —
    enumerated from `_pad_target` itself, never re-derived."""
    from ..utils.batching import _pad_target
    from ..workflow.env import resolved_chunk_size

    if chunk is None:
        try:
            chunk = resolved_chunk_size()
        except Exception:
            chunk = None
    if not chunk:
        return [1]
    chunk = int(chunk)
    if chunk in _PAD_TARGET_CACHE:
        return _PAD_TARGET_CACHE[chunk]
    targets = {chunk}
    for n in range(1, chunk + 1):
        for bucket_n in (n, chunk + n):
            t = _pad_target(n, chunk, bucket_n)
            if t:
                targets.add(int(t))
    _PAD_TARGET_CACHE[chunk] = sorted(targets)
    return _PAD_TARGET_CACHE[chunk]


# ---------------------------------------------------------------------------
# Per-family abstract geometry (mirrors the pallas_call construction)
# ---------------------------------------------------------------------------


def _abstract_geometry(family, statics, params, item_shape, dtype, n):
    """The lowering's abstract launch geometry at batch count ``n``:
    grid, write spec, read specs, per-boundary avals (kernel block and
    batch oracle), the shared VMEM parts, and the mask positions —
    everything the KP1001–KP1005 checkers consume, built from the SAME
    published chooser/body helpers `ops/chain_kernels.py` dispatches
    through (`eval_shape` only, nothing compiles)."""
    import jax
    import jax.numpy as jnp

    from ..ops import chain_kernels as ck
    from ..ops.pallas_kernels import _round_up

    item_shape = tuple(int(d) for d in item_shape)
    geom: Dict[str, Any] = {"family": family, "item_shape": item_shape,
                            "dtype": jnp.dtype(dtype).name}
    if family == "rectify_pool_vectorize":
        if len(item_shape) != 3:
            geom["error"] = (f"expected (H, W, K) input, got "
                             f"{item_shape}")
            return geom
        inner, _ = ck._unwrap(statics[0])
        _, _, _, pool, stride = inner[:5]
        h, w, k = item_shape
        parts = ck._rectify_pool_vectorize_parts(h, w, k, pool, stride)
        if parts is None:
            geom["error"] = (f"empty pool grid at (h={h}, w={w}) with "
                             f"pool={pool}, stride={stride}")
            return geom
        geom["parts"] = parts
        bn = ck.chain_block_rows(parts[0], parts[1], parts[2],
                                 ladder=parts[3])
        geom["bn"] = bn
        if bn <= 0:
            return geom
        gy = (h - pool) // stride + 1
        gx = (w - pool) // stride + 1
        bn_e = min(bn, int(n))
        n_pad = _round_up(int(n), bn_e)
        geom["grid"] = (n_pad // bn_e,)
        geom["out_block"] = ((bn_e, gy, gx, 2 * k),
                             lambda i: (i, 0, 0, 0))
        geom["out_shape"] = (n_pad, gy, gx, 2 * k)
        geom["reads"] = [("x", (bn_e, h, w, k), lambda i: (i, 0, 0, 0),
                          (n_pad, h, w, k))]
        geom["streams_mask"] = False
        geom["mask_declared"] = [i for i, key in enumerate(statics)
                                 if ck._unwrap(key)[1]]
        geom["mask_consumed"] = []
        # kernel-side boundary avals come from the DECLARED launch
        # geometry (the BlockSpec shapes the kernel writes); the oracle
        # side re-derives them by eval_shape of the pure-jnp reference
        # — a gy/gx arithmetic bug shows up as a boundary mismatch
        x = jax.ShapeDtypeStruct((bn_e, h, w, k), jnp.dtype(dtype))
        geom["kernel_avals"] = [
            x,
            jax.ShapeDtypeStruct((bn_e, gy, gx, 2 * k), x.dtype),
            jax.ShapeDtypeStruct((bn_e, gy * gx * 2 * k), x.dtype)]
        pooled = jax.eval_shape(
            lambda xx: ck.rectify_pool_reference(xx, 0.25, 0.0, pool,
                                                 stride), x)
        flat = jax.eval_shape(
            lambda xx: ck.rectify_pool_vectorize_reference(
                xx, 0.25, 0.0, pool, stride), x)
        geom["oracle_avals"] = [x, pooled, flat]
        return geom

    # elementwise_chain
    bodies = ck._compile_bodies(statics)
    if bodies is None:
        geom["error"] = f"no elementwise lowering for {statics!r}"
        return geom
    ops = [prep(p) for (_, prep, _), p in zip(bodies, params)]
    probe = jax.ShapeDtypeStruct((8,) + item_shape, jnp.dtype(dtype))
    parts = ck._elementwise_parts(bodies, ops, probe)
    geom["parts"] = parts
    bn = ck.chain_block_rows(parts[0], parts[1], parts[2],
                             ladder=parts[3])
    geom["bn"] = bn
    if bn <= 0:
        return geom
    bn_e = min(bn, int(n))
    n_pad = _round_up(int(n), bn_e)
    block_probe = jax.ShapeDtypeStruct((bn_e,) + item_shape,
                                       jnp.dtype(dtype))
    avals = ck._elementwise_avals(bodies, ops, block_probe)
    out_tail = tuple(int(d) for d in avals[-1].shape[1:])
    geom["grid"] = (n_pad // bn_e,)
    geom["out_block"] = ((bn_e,) + out_tail,
                         lambda i, nd=len(out_tail) + 1:
                         (i,) + (0,) * (nd - 1))
    geom["out_shape"] = (n_pad,) + out_tail
    reads = [("x", (bn_e,) + item_shape,
              lambda i, nd=len(item_shape) + 1: (i,) + (0,) * (nd - 1),
              (n_pad,) + item_shape)]
    needs_mask = any(masked for masked, _, _ in bodies)
    if needs_mask:
        reads.append(("mask", (bn_e, 1), lambda i: (i, 0), (n_pad, 1)))
    for t, a in enumerate(x for stage in ops for x in stage):
        shape = tuple(int(d) for d in a.shape)
        reads.append((f"param{t}", shape,
                      lambda i, nd=len(shape): (0,) * nd, shape))
    geom["reads"] = reads
    geom["streams_mask"] = needs_mask
    geom["mask_declared"] = [i for i, key in enumerate(statics)
                             if ck._unwrap(key)[1]]
    geom["mask_consumed"] = [i for i, (masked, _, _) in enumerate(bodies)
                             if masked]
    geom["kernel_avals"] = avals
    # the batch oracle at a distinct probe count: tails must agree with
    # the block trace at EVERY boundary (a batch-axis reduce would not)
    oracle_probe = jax.ShapeDtypeStruct((max(2 * bn_e, 2),) + item_shape,
                                        jnp.dtype(dtype))
    oracle = ck._elementwise_avals(bodies, ops, oracle_probe)
    geom["oracle_avals"] = [
        jax.ShapeDtypeStruct((bn_e,) + tuple(a.shape[1:]), a.dtype)
        for a in oracle]
    return geom


# ---------------------------------------------------------------------------
# The per-lowering verifier
# ---------------------------------------------------------------------------


def verify_lowering(stages, item_shape, dtype=None, *, vertex=None,
                    label="", chunk=None) -> Tuple[Dict[str, Any],
                                                   List[Diagnostic]]:
    """Run every KP10xx rule over one candidate chain at its propagated
    element shape. Returns ``(proof, diagnostics)``:

    - ``proof["verified"]`` — True when every rule proved;
    - ``proof["refuted_by"]`` — the rule that refuted a geometry that
      can NEVER dispatch (VMEM-infeasible, chooser-agreeing) — an INFO
      fact, not an error: the planner prices it INF and the live check
      skips it;
    - ERROR diagnostics — genuine safety violations (a lowering the
      runtime WOULD dispatch whose geometry/mask/oracle proof failed).
    """
    import jax.numpy as jnp

    from ..nodes.util.fusion import fused_stages, stage_fuse
    from ..ops import chain_kernels as ck

    dtype = jnp.float32 if dtype is None else dtype
    proof: Dict[str, Any] = {
        "label": label, "vertex": vertex,
        "item_shape": tuple(int(d) for d in item_shape),
        "dtype": jnp.dtype(dtype).name, "family": None,
        "rules": {}, "verified": False, "refuted_by": None,
    }
    diags: List[Diagnostic] = []

    def err(rule, msg):
        diags.append(Diagnostic(rule, Severity.ERROR, msg,
                                vertex=vertex, label=label))
        proof["rules"][rule] = f"REFUTED: {msg}"

    try:
        fused = [stage_fuse(s) for s in fused_stages(stages)]
    except Exception as e:
        err("KP1005", f"stage decomposition failed: "
                      f"{type(e).__name__}: {e}")
        return proof, diags
    statics = tuple(f[0] for f in fused)
    params = [f[1] for f in fused]
    verdict = ck.lowerability(statics)
    proof["family"] = verdict.get("family")
    if not verdict["lowerable"]:
        proof["rules"]["lowerability"] = verdict["reason"]
        return proof, diags

    counts = batcher_pad_targets(chunk)
    try:
        geom = _abstract_geometry(verdict["family"], statics, params,
                                  item_shape, dtype, max(counts))
    except Exception as e:
        err("KP1005", f"abstract geometry probe failed: "
                      f"{type(e).__name__}: {e}")
        return proof, diags
    if geom.get("error"):
        # a geometry the family cannot express — the runtime chooser
        # refuses it identically (chain_feasible), so it never runs
        proof["rules"]["KP1003"] = f"refuted: {geom['error']}"
        proof["refuted_by"] = "KP1003"
        _assert_chooser_agreement(stages, item_shape, dtype, False,
                                  err)
        return proof, diags
    bn = geom["bn"]
    if bn <= 0:
        proof["rules"]["KP1003"] = (
            "refuted: no feasible VMEM block at item shape "
            f"{proof['item_shape']} (runtime chooser agrees — the "
            "planner prices this lowering INF, it never dispatches)")
        proof["refuted_by"] = "KP1003"
        _assert_chooser_agreement(stages, item_shape, dtype, False,
                                  err)
        return proof, diags

    # KP1001 — output write coverage at the flagship AND a ragged probe
    problems = check_grid_coverage(geom["grid"], geom["out_block"][0],
                                   geom["out_block"][1],
                                   geom["out_shape"])
    small = _abstract_geometry(verdict["family"], statics, params,
                               item_shape, dtype, _MIN_PROBE)
    if not small.get("error") and small.get("bn", 0) > 0:
        problems += check_grid_coverage(
            small["grid"], small["out_block"][0], small["out_block"][1],
            small["out_shape"])
    if problems:
        err("KP1001", "; ".join(sorted(set(problems))))
    else:
        proof["rules"]["KP1001"] = (
            f"proved: grid {geom['grid']} × block "
            f"{geom['out_block'][0]} tiles {geom['out_shape']} "
            f"exactly, every element written once")

    # KP1002 — read bounds + the full pad-ladder sweep
    problems = []
    for name, block, imap, oshape in geom["reads"]:
        problems += check_read_bounds(geom["grid"], block, imap, oshape,
                                      name=name)
    problems += check_ragged_bounds(bn, counts)
    if problems:
        err("KP1002", "; ".join(sorted(set(problems))))
    else:
        proof["rules"]["KP1002"] = (
            f"proved: all block reads in bounds; padding covers every "
            f"pad-ladder count in {counts}")

    # KP1003 — the shared-formula VMEM proof + chooser identity
    io_b, inter_b, param_b, ladder = geom["parts"]
    problems = check_vmem_budget(bn, io_b, inter_b, param_b, ladder)
    if problems:
        err("KP1003", "; ".join(problems))
    else:
        used = ck.chain_vmem_bytes(bn, io_b, inter_b, param_b)
        proof["rules"]["KP1003"] = (
            f"proved: block {bn} working set {used} B ≤ budget "
            f"{ck._VMEM_BUDGET} B (shared chain_vmem_bytes formula; "
            f"runtime chooser identical)")
        _assert_chooser_agreement(stages, item_shape, dtype, True, err)

    # KP1004 — mask discipline
    problems = check_mask_discipline(geom["mask_declared"],
                                     geom["mask_consumed"],
                                     geom["streams_mask"])
    if problems:
        err("KP1004", "; ".join(problems))
    else:
        proof["rules"]["KP1004"] = (
            f"proved: fuse_masks_output position(s) "
            f"{geom['mask_declared']} re-zero from the streamed mask "
            f"at their original chain position"
            if geom["mask_declared"] else
            "proved: no fuse_masks_output stage in the chain")

    # KP1005 — abstract oracle equivalence per boundary
    problems = check_oracle_boundaries(geom["kernel_avals"],
                                       geom["oracle_avals"],
                                       geom["kernel_avals"][0].shape[0])
    if problems:
        err("KP1005", "; ".join(problems))
    else:
        proof["rules"]["KP1005"] = (
            f"proved: kernel block trace agrees with the pure-jnp "
            f"oracle on shape/dtype at all "
            f"{len(geom['kernel_avals'])} stage boundaries")

    proof["verified"] = not any(d.severity >= Severity.ERROR
                                for d in diags)
    return proof, diags


def _assert_chooser_agreement(stages, item_shape, dtype, expect_ok, err):
    """The KP1003 identity half: `chain_feasible` (the runtime chooser
    the planner and dispatcher consult) must reach the same verdict as
    the static proof — both sit on `chain_vmem_bytes`, so a mismatch
    means the shared-function contract was broken."""
    from ..ops.chain_kernels import chain_feasible

    try:
        ok, reason = chain_feasible(list(stages), tuple(item_shape),
                                    dtype)
    except Exception as e:
        ok, reason = None, f"chain_feasible raised {type(e).__name__}"
    if ok is not None and bool(ok) != bool(expect_ok):
        err("KP1003",
            f"static proof says feasible={expect_ok} but "
            f"chain_feasible says feasible={ok} ({reason}) — the "
            f"shared VMEM formula diverged")


def statically_verified(stages, item_shape, dtype=None, *,
                        chunk=None) -> Optional[bool]:
    """Tri-state verdict for one candidate slice: True (every KP10xx
    rule proved), False (a rule refuted the lowering — the planner must
    price it INF), None (verification could not run — the runtime
    canary remains the only gate, as before this tier existed)."""
    try:
        proof, diags = verify_lowering(stages, item_shape, dtype,
                                       chunk=chunk)
    except Exception:
        return None
    if proof.get("family") is None:
        return None
    if any(d.severity >= Severity.ERROR for d in diags):
        return False
    if proof.get("refuted_by"):
        return False
    return bool(proof.get("verified"))


# ---------------------------------------------------------------------------
# Graph-level pass (validate(level="full")) and the registry-wide audit
# ---------------------------------------------------------------------------


def _element_at_slice(graph, specs, cand):
    """The propagated element aval entering a KP801 candidate's slice —
    the same data-dep + `eval_shape` stage walk
    `plan_ir._UnifiedModel._kernel_feasible` uses."""
    import jax

    from .specs import DataSpec

    vid = cand["vertices"][0]
    dep = None
    try:
        for d in graph.get_dependencies(vid):
            if isinstance(specs.get(d), DataSpec):
                dep = d
                break
    except Exception:
        return None
    spec = specs.get(dep)
    if spec is None or getattr(spec, "element", None) is None:
        return None
    elem = spec.element
    if cand.get("kind") == "fused_trail" and cand.get("stage_slice"):
        from ..nodes.util.fusion import fused_stages
        from ..workflow.fusion_rule import FusedChainOperator

        op = graph.get_operator(vid)
        stage_list = (op.stage_specs if isinstance(op, FusedChainOperator)
                      else op.stages)
        stages = fused_stages(stage_list)
        i, _ = cand["stage_slice"]
        for s in stages[:i]:
            elem = jax.eval_shape(
                lambda x, s=s: s.single_transform([x]), elem)
    return elem


def kernel_pass(graph, specs, roofline) -> Tuple[List[Dict[str, Any]],
                                                 List[Diagnostic]]:
    """Verify every lowerable KP801 candidate of one graph's roofline
    estimate. Returns (proofs, diagnostics); annotates each candidate
    dict with ``statically_verified`` in place (the ledger/planner
    thread). Never breaks validation — an internal failure downgrades
    to a WARNING naming the candidate (the `contract_pass` discipline:
    the audit must never break the analyzer that hosts it)."""
    proofs: List[Dict[str, Any]] = []
    diags: List[Diagnostic] = []
    if roofline is None:
        return proofs, diags
    from .roofline import _candidate_stage_objects

    for cand in getattr(roofline, "candidates", None) or []:
        verdict = cand.get("lowerable") or {}
        if not verdict.get("lowerable"):
            continue
        head = cand["vertices"][0]
        label = " >> ".join(str(s) for s in cand.get("stages", []))
        try:
            stages = _candidate_stage_objects(graph, cand)
            elem = _element_at_slice(graph, specs, cand)
            if stages is None or elem is None:
                continue
            proof, pdiags = verify_lowering(
                stages, tuple(elem.shape), elem.dtype, vertex=head,
                label=label)
        except Exception as e:
            diags.append(Diagnostic(
                "KP1005", Severity.WARNING,
                f"kernel verification could not run: "
                f"{type(e).__name__}: {e}", vertex=head, label=label))
            cand["statically_verified"] = None
            continue
        proof["vertices"] = list(cand["vertices"])
        proof["kind"] = cand.get("kind")
        cand["statically_verified"] = (
            False if (proof["refuted_by"] or not proof["verified"])
            else True)
        proofs.append(proof)
        diags.extend(pdiags)
        if proof["refuted_by"]:
            diags.append(Diagnostic(
                proof["refuted_by"], Severity.INFO,
                f"statically refuted: "
                f"{proof['rules'].get(proof['refuted_by'], '')} — the "
                f"unified planner prices this kernel INF and the live "
                f"check skips the geometry", vertex=head, label=label))
    return proofs, diags


def audit_kernels(names: Optional[Iterable[str]] = None,
                  chunk: Optional[int] = None):
    """Registry-wide chain-kernel verification sweep — the KP10xx twin
    of `contracts.audit_registry`: build every example pipeline,
    propagate specs, price the roofline, and verify every lowerable
    KP801 candidate. Returns ``(findings, stats)`` where findings is
    ``[(example, proof, Diagnostic)]`` (ERROR/WARNING only — named
    `KERNEL_SUPPRESSIONS` entries are dropped with their reason
    recorded) and stats carries the per-example proof records the
    --audit-kernels CLI renders."""
    from . import as_source_spec
    from .examples import EXAMPLES, build_example
    from .propagate import spec_pass
    from .roofline import roofline_pass

    names = sorted(EXAMPLES) if names is None else list(names)
    findings: List[Tuple[str, Dict[str, Any], Diagnostic]] = []
    stats: Dict[str, Any] = {"examples": 0, "lowerings": 0,
                             "verified": 0, "proofs": [],
                             "suppressed": [], "build_errors": {}}
    for name in names:
        try:
            pipeline, source_spec = build_example(name)
            graph = pipeline.graph
            specs, _ = spec_pass(
                graph, {pipeline.source: as_source_spec(source_spec)})
            est, _ = roofline_pass(graph, specs)
            proofs, diags = kernel_pass(graph, specs, est)
        except Exception as e:
            stats["build_errors"][name] = f"{type(e).__name__}: {e}"
            continue
        stats["examples"] += 1
        stats["lowerings"] += len(proofs)
        stats["verified"] += sum(1 for p in proofs if p["verified"])
        for p in proofs:
            stats["proofs"].append({"example": name, **{
                k: v for k, v in p.items() if k != "vertex"}})
        for d in diags:
            if d.severity < Severity.WARNING:
                continue
            reason = KERNEL_SUPPRESSIONS.get((name, d.rule))
            if reason is not None:
                stats["suppressed"].append(
                    {"example": name, "rule": d.rule, "reason": reason})
                continue
            proof = next((p for p in proofs
                          if p.get("label") == d.label), {})
            findings.append((name, proof, d))
    return findings, stats
