"""The runtime's HBM budget rule, asked by the fused programs
(`nodes.util.fusion`) and the fusion rules (`workflow.fusion_rule`)."""

from __future__ import annotations

from typing import Optional

from .env import execution_config

#: what a chip holds where neither the configuration nor the device says
DEFAULT_HBM_BYTES = 16 << 30
#: the share of the budget one fused program's working set may take: the
#: rest holds what a fit keeps (the data, cached features, the solver's
#: copies)
PROGRAM_WORKING_SHARE = 0.25
#: a row's largest value is live beside what it is made from and what is
#: made of it, and the loop's output slot
LIVE_COPIES = 4


def hbm_budget_bytes() -> int:
    """The per-device HBM budget: `ExecutionConfig.hbm_budget_bytes`
    when set, else what the first local device reports as its limit,
    else `DEFAULT_HBM_BYTES` (a CPU backend reports none)."""
    budget = execution_config().hbm_budget_bytes
    if budget:
        return int(budget)
    try:
        import jax

        limit = (jax.local_devices()[0].memory_stats() or {}).get(
            "bytes_limit", 0)
        if limit:
            return int(limit)
    except Exception:
        pass
    return DEFAULT_HBM_BYTES


#: the share of the budget one dataset held whole may take
RESIDENT_SHARE = 0.5


def resident_fits(nbytes: int, budget_bytes: Optional[int] = None) -> bool:
    """Whether a dataset of ``nbytes`` may lie whole on a device: under
    `RESIDENT_SHARE` of the HBM budget. The fusion rules refuse a cache
    point, and recompute a shared stage, whose output does not."""
    return nbytes <= RESIDENT_SHARE * (budget_bytes or hbm_budget_bytes())


def microbatch_rows(row_bytes: int, budget_bytes: Optional[int] = None,
                    ceiling: int = 2048) -> int:
    """Rows a fused program takes a step: the largest power of two, at
    most ``ceiling``, whose `LIVE_COPIES` copies of the largest value a
    row makes (``row_bytes``) fit `PROGRAM_WORKING_SHARE` of the HBM
    budget. At least one."""
    budget = budget_bytes or hbm_budget_bytes()
    room = int(PROGRAM_WORKING_SHARE * budget) // max(
        1, LIVE_COPIES * int(row_bytes))
    rows = 1
    while rows * 2 <= min(room, ceiling):
        rows *= 2
    return rows
