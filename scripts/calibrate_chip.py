"""Measure the cost model's weights on the chip jax finds and write them
in the `tpu_calibration.json` schema (`calibrate.write_calibration`).

    python scripts/calibrate_chip.py <out.json>

Fails unless the device is a TPU. The file it writes is brought back
from the chip machine and committed as
`keystone_tpu/nodes/learning/tpu_calibration.json`; nothing here writes
into the package.
"""

import datetime
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from keystone_tpu.nodes.learning import calibrate

#: larger than `calibrate_cost_weights`' defaults, so that the
#: differenced window of each probe is tens of milliseconds on a v5e and
#: the host clock's jitter is a small share of it
GEMM_DIM, MEM_MB, ITERS = 4096, 256, 32


def main(argv):
    (out,) = argv
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"jax found platform {device.platform!r}, not a TPU")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    weights = calibrate.calibrate_cost_weights(
        gemm_dim=GEMM_DIM, mem_mb=MEM_MB, iters=ITERS)
    payload = calibrate.write_calibration(out, weights, {
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "date": datetime.date.today().isoformat(),
        "jax": jax.__version__,
        "method": (
            "scripts/calibrate_chip.py: keystone_tpu.nodes.learning."
            f"calibrate.calibrate_cost_weights(gemm_dim={GEMM_DIM}, "
            f"mem_mb={MEM_MB}, iters={ITERS}): dependency-chained "
            "fori_loop probes timed at N and 2N iterations, differenced "
            "to cancel dispatch and transfer, median of 3"),
        "notes": (
            "Effective rates, not peaks: the GEMM probe is f32 at default "
            "matmul precision including loop-carried HBM traffic; the "
            "memory probe is one elementwise read+write pass. "
            "network_weight is the analytic ICI default: a 1-chip mesh "
            "has no collective to measure."),
        "network_weight_measured": len(jax.devices()) > 1,
    })
    print(json.dumps(payload))


if __name__ == "__main__":
    main(sys.argv[1:])
