"""On-device cost-model calibration.

The reference's cost weights were "determined empirically via results
run on a 16 r3.4xlarge node cluster" (LeastSquaresEstimator.scala:17,
:190-192) — constants baked into the source. Here the measurement is a
library call: time the three resources a solver consumes (MXU FLOPs,
HBM bytes, ICI all-reduced bytes) on the attached mesh and return
weights in seconds-per-unit for `CostModel.cost(...)`.

Each probe runs K dependency-chained iterations inside one jitted
program and is keyed on a fresh scalar, so no cache between the call
and the device can short-circuit the measured work.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ...parallel import mesh as meshlib
from . import cost_model


@dataclass
class CostWeights:
    cpu_weight: float  # seconds per FLOP
    mem_weight: float  # seconds per HBM byte touched
    network_weight: float  # seconds per all-reduced byte
    #: peak rates implied by the SAME microbenchmarks (the GEMM probe's
    #: sustained MXU rate, the elementwise probe's HBM stream
    #: bandwidth): the roofline analyzer's machine-balance inputs
    #: (analysis/roofline.py). Default 0.0 resolves to the weight
    #: reciprocals in ``__post_init__`` so every existing constructor —
    #: including `reconcile.drift_cost_weights` — keeps working and the
    #: two views (seconds-per-unit, units-per-second) can never
    #: disagree.
    peak_flops: float = 0.0  # FLOP/s
    peak_bw: float = 0.0     # HBM B/s
    #: sustained host↔device transfer bandwidth (B/s) — the out-of-core
    #: spill tier's reload price (`analysis.plan_ir`: reload bytes /
    #: host_bw + one dispatch floor per window trip). 0.0 means
    #: "unmeasured": `host_bandwidth()` resolves it to the platform
    #: analytic default, so every existing constructor keeps working.
    host_bw: float = 0.0     # host↔device B/s

    def __post_init__(self):
        if not self.peak_flops and self.cpu_weight > 0:
            self.peak_flops = 1.0 / self.cpu_weight
        if not self.peak_bw and self.mem_weight > 0:
            self.peak_bw = 1.0 / self.mem_weight


def _time_chained(build_step, x0, iters: int) -> float:
    """Per-iteration wall time of `step` applied to its own output.

    Data dependence defeats dead-code elimination and caching; timing at
    `iters` and `2·iters` and differencing cancels the fixed per-call
    cost (dispatch + transfer), which otherwise dwarfs a short probe."""

    from functools import partial

    @partial(jax.jit, static_argnames=("n",))
    def prog(x, s, n):
        def body(i, acc):
            return build_step(acc) * (1.0 + s * 0.0)
        return lax.fori_loop(0, n, body, x * (1.0 + s * 1e-20))

    rng = np.random.default_rng()  # entropy-seeded: no two replays
    # issue byte-identical executions

    def run(n):
        s = jnp.float32(rng.random())
        t0 = time.perf_counter()
        np.asarray(jnp.ravel(prog(x0, s, n))[0])  # keystone: ignore[KJ005] — one-element transfer IS the timing fence (the sync_pull idiom, inlined)
        return time.perf_counter() - t0

    run(iters), run(2 * iters)  # warm both compiles
    t1 = np.median([run(iters) for _ in range(3)])
    t2 = np.median([run(2 * iters) for _ in range(3)])
    return float(t2 - t1) / iters


def _probe(build_step, x0, iters: int, fallback: float, name: str) -> float:
    """Differenced timing with a noise guard: a ~0 or negative difference
    (fast probes, shared hosts) means the measurement is noise — clamping
    it would produce an absurdly small per-unit weight that silently
    skews solver routing. Retry once with 4× the work; if still not
    cleanly positive, keep the baked default and warn."""
    import logging

    t = _time_chained(build_step, x0, iters)
    if t <= 0.0:
        t = _time_chained(build_step, x0, 4 * iters)
    if t <= 0.0:
        logging.getLogger(__name__).warning(
            "cost-model %s probe was noise (differenced time <= 0); "
            "keeping default weight", name,
        )
        return fallback
    return t


def calibrate_cost_weights(
    mesh=None, gemm_dim: int = 2048, mem_mb: int = 64, iters: int = 8
) -> CostWeights:
    """Measure (cpu, mem, network) weights on the current mesh.

    On a single-device mesh the network probe has nothing to measure and
    the reference ICI default is returned for it.
    """
    mesh = mesh or meshlib.current_mesh()

    # --- MXU: square GEMM, 2·D³ flops/iter ----------------------------
    a = jnp.ones((gemm_dim, gemm_dim), jnp.float32)
    flops = 2.0 * gemm_dim**3
    t = _probe(lambda x: x @ a / jnp.float32(gemm_dim), a, iters,
               fallback=cost_model.CPU_WEIGHT * flops, name="cpu")
    cpu_weight = t / flops

    # --- HBM: elementwise pass over a large buffer (read + write) -----
    n = mem_mb * (1 << 20) // 4
    v = jnp.ones((n,), jnp.float32)
    hbm_bytes = 2.0 * 4.0 * n
    t = _probe(lambda x: x * 1.000001 + 1e-9, v, iters,
               fallback=cost_model.MEM_WEIGHT * hbm_bytes, name="mem")
    mem_weight = t / hbm_bytes

    # --- ICI: psum of a sharded buffer over the data axis -------------
    rows = meshlib.n_data_shards(mesh)
    if rows <= 1:
        network_weight = cost_model.NETWORK_WEIGHT
    else:
        axis = meshlib.DATA_AXIS
        m = (4 << 20) // 4  # 4 MB per shard
        xs = jax.device_put(
            np.ones((rows, m), np.float32),
            jax.sharding.NamedSharding(mesh, P(axis)),
        )

        def step(x):
            def local(xl):
                return lax.psum(xl, axis) / rows
            return jax.shard_map(local, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(axis), check_vma=False)(x)

        ici_bytes = 4.0 * m * 2.0 * (rows - 1) / rows
        # ring all-reduce moves ~2·(p−1)/p of the buffer per chip
        t = _probe(step, xs, iters, fallback=cost_model.NETWORK_WEIGHT * ici_bytes,
                   name="network")
        network_weight = t / ici_bytes

    return CostWeights(cpu_weight, mem_weight, network_weight,
                       host_bw=_probe_host_bw(mem_mb))


def _probe_host_bw(mem_mb: int = 64, reps: int = 3) -> float:
    """Sustained host→device transfer bandwidth (B/s): min-of-reps
    `device_put` of a fresh host buffer, fenced by `block_until_ready`.
    Min (not median) because page faults and allocator warmup only ever
    slow a transfer down — the best rep is the sustainable rate the
    spill tier's reload price should use. Returns 0.0 (= "unmeasured",
    resolved analytically by `host_bandwidth()`) if the probe fails."""
    try:
        n = mem_mb * (1 << 20) // 4
        src = np.ones((n,), np.float32)
        nbytes = 4.0 * n
        best = float("inf")
        for _ in range(reps + 1):  # first rep warms the transfer path
            src += 1.0  # fresh values each rep
            t0 = time.perf_counter()
            jax.device_put(src).block_until_ready()  # keystone: ignore[KJ005] — the transfer IS the measured work
            best = min(best, time.perf_counter() - t0)
        return nbytes / best if best > 0 else 0.0
    except Exception:
        return 0.0


def default_weights() -> CostWeights:
    return CostWeights(cost_model.CPU_WEIGHT, cost_model.MEM_WEIGHT,
                       cost_model.NETWORK_WEIGHT)


def write_calibration(path: str, weights: CostWeights,
                      provenance: "dict | None" = None) -> dict:
    """Persist a `CostWeights` in the ``tpu_calibration.json`` schema —
    the same file format `cost_model._resolve_weights` loads, so a
    trace-recalibrated suggestion (`reconcile.drift_cost_weights`)
    round-trips: emit it here, point ``KEYSTONE_COST_CALIBRATION`` at
    the file, and `machine_rates()` prefers it whenever the recorded
    platform matches the live backend. Returns the written payload."""
    import json

    prov = {"platform": cost_model.live_platform()}
    prov.update(provenance or {})
    payload = {
        "cpu_weight": float(weights.cpu_weight),
        "mem_weight": float(weights.mem_weight),
        "network_weight": float(weights.network_weight),
        "peak_flops": float(weights.peak_flops),
        "peak_bw": float(weights.peak_bw),
        "host_bw": float(weights.host_bw),
        "provenance": prov,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload


#: Honest CPU-backend analytic peaks, used when no measured calibration
#: applies and the live platform is the CPU backend: an order-of-
#: magnitude model of a few-core AVX host (~50 GFLOP/s sustained,
#: ~20 GB/s DDR stream). Claiming the v5e analytic peaks (2e14 FLOP/s,
#: 8e11 B/s) on a dev box would misclassify every stage's roofline
#: bound — the machine balance would be ~100× too high.
CPU_PEAK_FLOPS = 5.0e10
CPU_PEAK_BW = 2.0e10


def machine_rates() -> "tuple[float, float]":
    """``(peak_flops, peak_bw)`` — the roofline's machine balance from
    ONE place, the same resolution the solver cost model reads:

      - a measured calibration file whose platform matches the live
        backend wins (its weight reciprocals ARE the sustained peaks
        the probes measured);
      - otherwise, on a CPU backend, the honest CPU analytic peaks
        above (the v5e analytic model would be off by ~1000×);
      - otherwise the analytic v5e-class peaks
        (`cost_model.ANALYTIC_*` reciprocals)."""
    cw, mw, _ = cost_model._resolve_weights()
    analytic = (cw == cost_model.ANALYTIC_CPU_WEIGHT
                and mw == cost_model.ANALYTIC_MEM_WEIGHT)
    if analytic and cost_model.live_platform() == "cpu":
        return CPU_PEAK_FLOPS, CPU_PEAK_BW
    return 1.0 / cw, 1.0 / mw


#: Analytic host↔device transfer bandwidths (B/s) for the spill tier's
#: reload price when no measured calibration applies. CPU backend: a
#: "transfer" is a host memcpy (~8 GB/s, same order as the DDR stream
#: above but cheaper than a full read+write pass). TPU: PCIe-class
#: pageable host→device (~10 GB/s) — deliberately ~80× below the v5e
#: HBM stream rate, which is exactly why spilling must be PRICED, not
#: free: a reload trip costs real seconds the planner has to win back
#: in residency.
CPU_HOST_BW = 8.0e9
ANALYTIC_HOST_BW = 1.0e10


def host_bandwidth() -> float:
    """Sustained host↔device bandwidth (B/s) — the `machine_rates()`
    companion the out-of-core spill tier prices reloads with, resolved
    the same way: a measured calibration file whose platform matches
    the live backend wins (its ``host_bw`` entry, when the probe
    recorded one); otherwise the platform analytic constant above.
    Kept a separate accessor (not a third `machine_rates()` element)
    because that tuple's arity is a published contract of the roofline
    layer."""
    import json
    import os

    mode = os.environ.get("KEYSTONE_COST_CALIBRATION", "")
    if mode != "analytic":
        path = mode if mode not in ("", "force") else os.path.join(
            os.path.dirname(cost_model.__file__), "tpu_calibration.json")
        try:
            with open(path) as f:
                cal = json.load(f)
            prov = cal.get("provenance")
            cal_platform = (prov.get("platform")
                            if isinstance(prov, dict) else None)
            live = cost_model.live_platform()
            if float(cal.get("host_bw", 0.0)) > 0 and (
                    mode == "force"
                    or (live is not None and live == cal_platform)):
                return float(cal["host_bw"])
        except Exception:
            pass  # unreadable/absent file: analytic, like machine_rates
    if cost_model.live_platform() == "cpu":
        return CPU_HOST_BW
    return ANALYTIC_HOST_BW
