"""Solver cost models for node-level auto-selection.

Reference: nodes/learning/CostModel.scala:6-16 and the per-solver models
embedded in LeastSquaresEstimator.scala / LinearMapper.scala / LBFGS.scala
/ BlockLinearMapper.scala. The reference's cost is
cpuWeight·flops + memWeight·bytes + networkWeight·bytes-moved, with
weights fit on a 16× r3.4xlarge cluster (cpu 3.8e-4, mem 2.9e-1, net
1.32 — LeastSquaresEstimator.scala:190-192).

TPU translation: "machines" becomes mesh chips; compute cost is MXU
FLOPs, memory cost is HBM-resident bytes, and network cost is ICI
collective bytes (Gram all-reduces, model replication). The default
weights below are normalized per-chip rates for a v5e-class chip
(~2e14 bf16 FLOP/s MXU, ~8e11 B/s HBM, ~1e11 B/s ICI all-reduce
effective) so costs come out in seconds — or measure them on the
attached mesh with `calibrate.calibrate_cost_weights()` /
`LeastSquaresEstimator.calibrated(...)`.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class CostProfile:
    """Workload statistics measured from a sample (n, d, k, sparsity) plus
    the mesh size (≈ numMachines, a plain parameter so tests can simulate
    a 16-chip pod without one — LeastSquaresEstimatorSuite.scala:18-37)."""

    n: int
    d: int
    k: int
    sparsity: float
    num_chips: int


# Analytic v5e-ish fallbacks (peak-rate reciprocals), used when no
# measured calibration file is present.
ANALYTIC_CPU_WEIGHT = 1.0 / 2.0e14   # per FLOP (MXU bf16)
ANALYTIC_MEM_WEIGHT = 1.0 / 8.0e11   # per HBM byte touched
ANALYTIC_NETWORK_WEIGHT = 1.0 / 1.0e11  # per ICI all-reduced byte


_ANALYTIC = (ANALYTIC_CPU_WEIGHT, ANALYTIC_MEM_WEIGHT, ANALYTIC_NETWORK_WEIGHT)
_weights_cache = None


def live_platform() -> str:
    """The platform of the backend jax runs on ("tpu", "cpu", ...)."""
    import jax

    return jax.default_backend()


def _resolve_weights():
    """Measured weights from tpu_calibration.json (committed with
    provenance; produced by calibrate.calibrate_cost_weights() on real
    hardware), used only when its recorded platform matches the live JAX
    backend — a v5e-measured file must not silently override the analytic
    model on CPU dev boxes or other TPU generations.

    KEYSTONE_COST_CALIBRATION=analytic ignores the file entirely;
    KEYSTONE_COST_CALIBRATION=force applies it regardless of platform;
    Any other KEYSTONE_COST_CALIBRATION value is a calibration file
    PATH read instead of the committed one (same schema, platform
    check still applies; a missing path warns and falls back to
    analytic) — the round-trip seam for trace-recalibrated weights
    emitted by ``python -m keystone_tpu.telemetry --ledger <run>
    --emit-calibration <path>``.
    Resolution is lazy (first weight access). The cache is keyed on
    (mode, live platform).
    """
    global _weights_cache
    import json
    import logging
    import os

    mode = os.environ.get("KEYSTONE_COST_CALIBRATION", "")
    live = None if mode in ("analytic", "force") else live_platform()
    cache_key = (mode, live)
    if _weights_cache is not None and _weights_cache[0] == cache_key:
        return _weights_cache[1]
    if mode == "analytic":
        _weights_cache = (cache_key, _ANALYTIC)
        return _ANALYTIC
    if mode not in ("", "force"):
        # any value other than the keywords ("analytic" returned above,
        # "force", empty) IS a calibration file path — a bare filename
        # must not silently fall back to the committed file while the
        # user believes recalibration is active (a missing path warns
        # in the FileNotFoundError branch below)
        path = mode
    else:
        path = os.path.join(os.path.dirname(__file__),
                            "tpu_calibration.json")
    log = logging.getLogger(__name__)
    try:
        with open(path) as f:
            cal = json.load(f)
        weights = (
            float(cal["cpu_weight"]),
            float(cal["mem_weight"]),
            float(cal["network_weight"]),
        )
        prov = cal.get("provenance")
        cal_platform = prov.get("platform") if isinstance(prov, dict) else None
    except FileNotFoundError:
        if path == mode:
            # an explicitly pointed-at calibration file that does not
            # exist is a user error, not the quiet no-committed-file
            # default — say so instead of silently going analytic
            log.warning(
                "KEYSTONE_COST_CALIBRATION=%s does not exist; "
                "falling back to analytic weights", path)
        _weights_cache = (cache_key, _ANALYTIC)
        return _ANALYTIC
    except (OSError, KeyError, ValueError, TypeError, AttributeError) as e:
        log.warning(
            "cost-model calibration file %s exists but failed to parse "
            "(%s); falling back to analytic weights", path, e)
        _weights_cache = (cache_key, _ANALYTIC)
        return _ANALYTIC
    if mode != "force" and (live is None or cal_platform is None
                            or live != cal_platform):
        log.info(
            "cost-model calibration was measured on platform=%r but "
            "the live/configured platform is %r; using analytic weights "
            "(KEYSTONE_COST_CALIBRATION=force to override)",
            cal_platform, live)
        _weights_cache = (cache_key, _ANALYTIC)
        return _ANALYTIC
    _weights_cache = (cache_key, weights)
    return weights


def __getattr__(name):
    # Lazy module attributes (PEP 562): CPU_WEIGHT / MEM_WEIGHT /
    # NETWORK_WEIGHT resolve the calibration on first access.
    idx = {"CPU_WEIGHT": 0, "MEM_WEIGHT": 1, "NETWORK_WEIGHT": 2}.get(name)
    if idx is None:
        raise AttributeError(name)
    return _resolve_weights()[idx]


class CostModel:
    """cost(profile) -> estimated seconds (CostModel.scala:6-16)."""

    def cost(
        self,
        p: CostProfile,
        cpu_weight: float = None,
        mem_weight: float = None,
        network_weight: float = None,
    ) -> float:
        raise NotImplementedError

    @staticmethod
    def _weights(cpu_weight, mem_weight, network_weight):
        if None not in (cpu_weight, mem_weight, network_weight):
            return cpu_weight, mem_weight, network_weight
        cw, mw, nw = _resolve_weights()
        return (
            cw if cpu_weight is None else cpu_weight,
            mw if mem_weight is None else mem_weight,
            nw if network_weight is None else network_weight,
        )


class ExactSolverCostModel(CostModel):
    """Normal equations: XᵀX flops n·d²/chips + d³ solve (replicated) +
    d² all-reduce (LinearMapper.scala cost model)."""

    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cpu_weight, mem_weight, network_weight = self._weights(
            cpu_weight, mem_weight, network_weight)
        flops = 2.0 * p.n * p.d * p.d / p.num_chips + 2.0 * p.d**3
        mem = 4.0 * (p.n * p.d / p.num_chips + p.d * p.d)
        net = 4.0 * p.d * p.d
        return cpu_weight * flops + mem_weight * mem + network_weight * net


class BlockSolverCostModel(CostModel):
    """BCD: numIter sweeps of per-block Gram (n·B·(B+k)/chips) + B³ solves
    + B·(B+k) all-reduces (BlockLinearMapper.scala cost model)."""

    def __init__(self, block_size: int = 4096, num_iter: int = 1):
        self.block_size = block_size
        self.num_iter = num_iter

    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cpu_weight, mem_weight, network_weight = self._weights(
            cpu_weight, mem_weight, network_weight)
        B = min(self.block_size, p.d)
        nb = -(-p.d // B)
        per_sweep_flops = nb * (
            2.0 * p.n * B * (B + 2 * p.k) / p.num_chips + (2.0 / 3.0) * B**3
        )
        mem = 4.0 * self.num_iter * nb * (p.n * (B + p.k) / p.num_chips)
        net = 4.0 * self.num_iter * nb * B * (B + p.k)
        return cpu_weight * self.num_iter * per_sweep_flops + mem_weight * mem + network_weight * net


class LBFGSCostModel(CostModel):
    """numIters gradient passes: 2·n·d·k flops each /chips + d·k model
    all-reduce per iter (LBFGS.scala cost model). Sparse variant scales
    flops by sparsity."""

    def __init__(self, num_iters: int = 20, sparse: bool = False):
        self.num_iters = num_iters
        self.sparse = sparse

    def cost(self, p, cpu_weight=None, mem_weight=None, network_weight=None):
        cpu_weight, mem_weight, network_weight = self._weights(
            cpu_weight, mem_weight, network_weight)
        density = p.sparsity if self.sparse else 1.0
        flops = self.num_iters * 4.0 * p.n * p.d * p.k * density / p.num_chips
        mem = 4.0 * self.num_iters * (p.n * p.d * density / p.num_chips + p.d * p.k)
        net = 4.0 * self.num_iters * p.d * p.k
        return cpu_weight * flops + mem_weight * mem + network_weight * net
