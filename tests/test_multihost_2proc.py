"""True multi-process multihost test: two OS processes, 4 virtual CPU
devices each, joined into one 8-device job via jax.distributed (Gloo
over localhost ≈ DCN). The reference has no analog — its multi-node
behavior is delegated to Spark and never tested beyond local mode
(SURVEY §4) — so this goes beyond reference density on purpose: the
multi-host claim in parallel/multihost.py is executed, not just
unit-tested in a single process.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "multihost_worker.py")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_job_dataset_and_solver():
    # bounded by the shared 240 s reap deadline below
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker sets device count via jax.config
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for i in range(2)
    ]
    import time

    outs = ["", ""]
    deadline = time.monotonic() + 240  # shared budget across both reaps
    timed_out = False
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=max(1.0, deadline - time.monotonic()))
            outs[i] = out
        except subprocess.TimeoutExpired as e:
            outs[i] = (e.stdout or "") + "\n<worker timed out>"
            timed_out = True
    if timed_out:
        for p in procs:
            if p.poll() is None:
                p.kill()
                out, _ = p.communicate()  # reap; collect partial output
                outs[procs.index(p)] += out or ""
        pytest.fail(
            "multihost workers timed out:\n"
            + "\n".join(f"--- worker {i}:\n{o}" for i, o in enumerate(outs))
        )
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} rc={p.returncode}\n{out}"
        assert "MULTIHOST_OK" in out, f"worker {i} output:\n{out}"
