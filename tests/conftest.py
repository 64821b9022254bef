"""Test harness.

Mirrors the reference's `PipelineContext` trait
(src/test/scala/workflow/PipelineContext.scala:9-26): where the reference
runs every "distributed" test on local-mode Spark, we run on a virtual
8-device CPU mesh, exercising the full shard/collective code path in one
process. Each test resets the process-global `PipelineEnv` so
prefix-memoized fitted state cannot leak between tests.

Platform forcing uses `jax.config` (not env vars): pytest plugins may
import jax before this conftest runs, at which point XLA_FLAGS /
JAX_PLATFORMS are ignored — config updates still work until a backend is
actually initialized.
"""

import os

# Harmless belt-and-braces for subprocesses spawned by tests.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def assert_cpu_mesh():
    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) == 8, (
        f"tests require the 8-device CPU mesh, got {devs}; "
        "a plugin initialized a jax backend before conftest could configure it"
    )
    yield


def pytest_runtest_logreport(report):
    """Record environment-probe skips (jax_num_cpu_devices config knob,
    orbax presence, 2d-mesh L-BFGS numerics — and any future probe) as
    telemetry capability metadata, so a trace/bench artifact produced
    from this process states WHICH capabilities were absent for the run
    instead of silently carrying fewer measurements."""
    if report.when in ("setup", "call") and report.skipped:
        try:
            from keystone_tpu.telemetry import record_capability

            reason = ""
            if isinstance(report.longrepr, tuple) and len(report.longrepr) == 3:
                reason = str(report.longrepr[2])
                if reason.startswith("Skipped: "):
                    reason = reason[len("Skipped: "):]
            record_capability(report.nodeid, False, reason)
        except Exception:
            pass  # telemetry bookkeeping must never fail a test run


@pytest.fixture(autouse=True)
def clean_pipeline_env():
    from keystone_tpu.workflow.env import PipelineEnv
    from keystone_tpu.parallel.mesh import reset_default_mesh

    PipelineEnv.reset()
    reset_default_mesh()
    yield
    PipelineEnv.reset()
    reset_default_mesh()
