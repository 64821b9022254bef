"""Orbax checkpoint backend for FittedPipeline (save/load round-trip).

Runs in a SUBPROCESS held to the CPU with eight virtual devices, so the
orbax save path sees the same mesh whatever the parent process has
initialized. The pickle backend's in-process test lives in
test_pipeline.py.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os
import sys
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import numpy as np

from keystone_tpu.data.dataset import Dataset
from keystone_tpu.nodes.learning import LinearMapEstimator
from keystone_tpu.nodes.stats import StandardScaler
from keystone_tpu.parallel.mesh import make_mesh
from keystone_tpu.nodes.util import Identity
from keystone_tpu.workflow import FittedPipeline

out = sys.argv[1]
mesh = make_mesh()
rng = np.random.default_rng(0)
X = rng.normal(size=(64, 5)).astype(np.float32)
W = rng.normal(size=(5, 3)).astype(np.float32)
Y = X @ W

train = Dataset(X, mesh=mesh)
labels = Dataset(Y, mesh=mesh)
pipe = Identity().and_then(StandardScaler(), train).and_then(
    LinearMapEstimator(lam=1e-6), train, labels)
fitted = pipe.fit()
want = fitted(train).numpy()

path = out + "/fitted_orbax"
fitted.save(path, format="orbax")
assert os.path.isdir(path), path
assert os.path.exists(path + "/skeleton.pkl")
assert os.path.isdir(path + "/arrays"), "expected an orbax array ckpt"

loaded = FittedPipeline.load(path)
got = loaded(train).numpy()
np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

# single-datum path survives the round trip too
d_want = np.asarray(fitted(X[0]))
d_got = np.asarray(loaded(X[0]))
np.testing.assert_allclose(d_got, d_want, rtol=1e-5, atol=1e-5)

# unpickling the payload outside a load context must fail loudly
import pickle
wrapper = pickle.load(open(path + "/skeleton.pkl", "rb"))
assert wrapper["format"] == "keystone-orbax-v1"
assert wrapper["n_arrays"] > 0
try:
    pickle.loads(wrapper["payload"])
except RuntimeError as e:
    assert "load_pytree_orbax" in str(e)
else:
    raise AssertionError("bare payload unpickle should have raised")

# a torn save (sidecar id != skeleton id) must be rejected loudly
open(path + "/arrays_id.txt", "w").write("deadbeef")
try:
    FittedPipeline.load(path)
except RuntimeError as e:
    assert "torn" in str(e)
else:
    raise AssertionError("torn artifact should have raised")
open(path + "/arrays_id.txt", "w").write(wrapper["artifact_id"])

# a partial copy (missing arrays/) must be rejected loudly
import shutil
shutil.rmtree(path + "/arrays")
try:
    FittedPipeline.load(path)
except RuntimeError as e:
    assert "arrays" in str(e)
else:
    raise AssertionError("missing arrays dir should have raised")

print("ORBAX_OK")
"""


def test_orbax_roundtrip_subprocess(tmp_path):
    import importlib.util

    if importlib.util.find_spec("orbax") is None:
        pytest.skip("orbax is not installed")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-u", "-c", WORKER, str(tmp_path)],
        env=env, cwd=REPO, timeout=300, capture_output=True, text=True,
    )
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert "ORBAX_OK" in r.stdout
