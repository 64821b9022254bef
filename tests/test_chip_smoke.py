"""`chip_smoke.py` rehearsed without the chip: its phase functions at a
tiny size on the eight-device CPU mesh, and `main()` refusing to pass
anywhere but on a TPU. What the smoke proves it proves on the chip;
these only keep its paths, arguments and control flow from rotting."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from keystone_tpu.parallel.mesh import make_mesh
from keystone_tpu.pipelines.random_patch_cifar import RandomPatchCifarConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 0
# d = 2*2*(2*16) = 128 features, two BCD blocks; 0.5 is far above chance
# (0.10) on the calibrated task at this size and far below the smoke's
# own bar, which is the bench's band at 50,000 images
CONFIG = RandomPatchCifarConfig(
    num_filters=16, block_size=64, microbatch=32, seed=SEED)
N_TRAIN, N_TEST, MIN_ACCURACY = 256, 64, 0.5


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(jax.devices())


@pytest.fixture(scope="module")
def fitted_state(smoke, mesh):
    """One fit and one apply phase shared by the tests below (the
    module's autouse `clean_pipeline_env` resets between tests; a fitted
    pipeline carries its own state)."""
    train, test = smoke.make_data(N_TRAIN, N_TEST, SEED, mesh)
    fit_record, predictor, preds = smoke.phase_fit(
        train, test, CONFIG, mesh, MIN_ACCURACY)
    apply_record, fitted, batch_preds = smoke.phase_apply(
        predictor, test, mesh, reps=2)
    return {"test": test, "fit": fit_record, "apply": apply_record,
            "fitted": fitted, "preds": preds, "batch_preds": batch_preds}


def test_fit_phase_fits_cold_then_warm(fitted_state):
    record = fitted_state["fit"]
    json.dumps(record)
    assert record["n_train"] == N_TRAIN and record["n_test"] == N_TEST
    assert record["test_accuracy"] >= MIN_ACCURACY
    assert record["cold"]["executed"] > 0 and record["warm"]["executed"] > 0
    # the second fit refits (programs run again) from compiled programs
    assert record["warm"]["compiled"] == 0, record["warm"]


def test_apply_phase_compiles_nothing_on_the_second_pass(fitted_state):
    record = fitted_state["apply"]
    json.dumps(record)
    assert record["warm"]["compiled"] == 0
    assert record["warm"]["cache_hits"] == 0
    assert record["warm"]["executed"] >= 1
    for fence in ("block_until_ready", "sync_pull"):
        assert len(record["warm_seconds_by_fence"][fence]) == 2
    # the fitted pipeline answers as the lazy one did
    np.testing.assert_array_equal(
        fitted_state["batch_preds"], fitted_state["preds"])


def test_kernel_phase_agrees_with_the_reference(smoke):
    record = smoke.phase_kernel(CONFIG, SEED, n=8)
    json.dumps(record)
    # off the chip the dispatchers are the reference path and say so
    assert record["backend"] == "cpu"
    assert record["verdicts"] == {"fused_conv": None,
                                  "rectify_pool_vectorize": None}
    assert not any(record["tpu_custom_call"].values())
    assert all(e < smoke.KERNEL_REL_TOL for e in record["max_rel_err"].values())


def test_serve_phase_answers_as_the_batch_apply(smoke, mesh, fitted_state):
    record = smoke.phase_serve(
        fitted_state["fitted"], fitted_state["test"],
        fitted_state["batch_preds"], mesh, n_requests=12, n_clients=3,
        max_batch=4)
    json.dumps(record)
    assert record["certified"] and record["warmed_sites"] >= 1
    assert record["after_start"]["compiled"] == 0
    assert set(record["dispatched_shapes"]) <= set(record["ladder"])
    assert record["watchdog"]["checked"] >= 1


def test_mesh_phase_on_four_virtual_devices(smoke):
    record = smoke.phase_mesh(
        N_TRAIN, N_TEST, CONFIG, SEED, jax.devices()[:4], MIN_ACCURACY)
    json.dumps(record)
    assert record["devices"] == 4
    assert record["fits"]["data"]["mesh"] == {"data": 4}
    assert record["fits"]["data_model"]["mesh"] == {"data": 2, "model": 2}
    for name in ("data", "data_model"):
        fit = record["fits"][name]
        assert fit["agreement"] >= smoke.MESH_AGREEMENT
        assert fit["shards"]["images"]["shard_shape"][0] == N_TRAIN // (
            4 if name == "data" else 2)
    # the featurizer's output is split by rows: four ways on the 1-D
    # mesh, two on the 2-D one (and held twice, across `model`)
    assert record["fits"]["data"]["shards"]["features"]["parts"] == 4
    assert record["fits"]["data_model"]["shards"]["features"]["parts"] == 2
    assert len(record["solver_matrix"]) == 9


def test_shard_check_refuses_an_array_on_one_device(smoke):
    """The check the mesh phase leans on: an array that sits whole on
    the first device, or whole on every device, is refused."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(jax.devices()[:4])
    x = np.zeros((8, 4), np.float32)
    with pytest.raises(AssertionError, match="shards on 1 devices"):
        smoke._check_shards(jax.device_put(x, jax.devices()[0]), mesh, "x")
    with pytest.raises(AssertionError, match="splits the array 1 ways"):
        smoke._check_shards(
            jax.device_put(x, NamedSharding(mesh, P())), mesh, "x")
    got = smoke._check_shards(
        jax.device_put(x, NamedSharding(mesh, P("data"))), mesh, "x")
    assert got["shard_shape"] == [2, 4]


def test_main_refuses_to_pass_off_the_chip(smoke, capsys):
    """With the CPU for a platform `main()` exits non-zero before any
    phase and prints no result."""
    assert smoke.main([]) == 1
    assert smoke.main(["--chips", "4"]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "not a TPU" in out.err


def test_a_run_that_asked_for_the_chip_fails_off_it(monkeypatch):
    """`--backend tpu` / `KEYSTONE_BACKEND=tpu` used to do nothing: the
    run carried on wherever jax landed."""
    from keystone_tpu import __main__ as cli

    monkeypatch.delenv("KEYSTONE_BACKEND", raising=False)
    assert cli._pop_backend_flag(["--backend", "tpu", "x"]) == ["x"]
    with pytest.raises(SystemExit, match="not a TPU"):
        cli._apply_backend_env()
    monkeypatch.setenv("KEYSTONE_BACKEND", "gpu")
    with pytest.raises(SystemExit, match="must be tpu or cpu"):
        cli._apply_backend_env()


def test_the_smoke_and_the_cell_are_the_same_task(smoke):
    """The start-up check and `cifar_fit` draw their images from one
    distribution and hold a fit to one lower bar; no third file holds
    the numbers, so the two are held to each other here."""
    with open(os.path.join(
            REPO, "benchmark", "configs", "random_patch_cifar.json")) as f:
        sizes = json.load(f)
    assert smoke.TASK_NOISE == sizes["assumed"]["noise"]
    assert smoke.TASK_CONFUSION == sizes["assumed"]["confusion"]
    assert smoke.MIN_ACCURACY == sizes["accuracy_band"][0]


def test_nothing_imports_a_second_benchmark():
    """`BENCHMARK.json`'s command is the one benchmark: its module is in
    the checkout, and no program or script file imports a module named
    `bench` (the root's old `bench.py`, deleted in PR 31)."""
    import ast
    import glob

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    module = command[command.index("-m") + 1]
    assert os.path.isfile(
        os.path.join(REPO, *module.split(".")) + ".py"), command

    paths = glob.glob(os.path.join(REPO, "*.py"))
    for top in ("keystone_tpu", "scripts", "benchmark"):
        paths += glob.glob(
            os.path.join(REPO, top, "**", "*.py"), recursive=True)
    assert len(paths) > 100
    importers = []
    for path in paths:
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                names = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom) and not node.level:
                names = [node.module]
            else:
                continue
            if any(name.split(".")[0] == "bench" for name in names):
                importers.append(os.path.relpath(path, REPO))
    assert not importers, importers
