"""The benchmark's harness rehearsed without the chip: its files load and
name each other, a dropped-in cell and metric are found with no edit,
mode `fit` runs tiny on the CPU and tells a right model from a wrong
one, the trace reduction and the readers give the numbers worked out by
hand, and the command refuses to pass anywhere but on a TPU. What the
benchmark measures it measures on the chip; nothing here is a time or a
rate."""

import copy
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import datagen, files, run, trace_reduce, tracing  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SEED = 2**31 + 12345  # the driver's seeds are larger than 32 signed bits hold

# RandomPatchCifar tiny: d = 2*2*(2*16) = 128 features in two blocks
TINY_CIFAR = {
    "num_filters": 16, "block_size": 64, "num_train": 256, "num_test": 64,
    "sample_patches": 10000, "feature_dim": 128, "lam": 10.0,
    "assumed": {"noise": 1.2, "confusion": 0.6, "microbatch": 32},
    "accuracy_band": [0.5, 1.0], "reference_agreement": 0.9}


@pytest.fixture(scope="module")
def bench():
    return files.BenchFiles()


def copy_of_the_data_files(root, manifest):
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", sub),
                        os.path.join(root, "benchmark", sub),
                        ignore=shutil.ignore_patterns("*.py", "__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return files.BenchFiles(root)


def tiny(bench, config, changes):
    return {**bench.sizes(config), **changes}


def quiet(record):
    pass


def test_every_file_loads_and_names_files_that_exist(bench):
    m = bench.manifest
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[section]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
    for config in m["configs"]:
        assert any(config["file"].startswith(p + "/") for p in m["paths"])
        sizes = bench.sizes(config["name"])
        assert all(NAME.match(k) and k in sizes or k.startswith("num_")
                   for k in config["reduced"])
        adapter = files.module("configs", config["name"])
        reference = files.module("reference", config["name"])
        assert callable(adapter.build) and callable(adapter.make_data)
        assert callable(reference.predict)
    for cell in m["workloads"]:
        assert NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
        bench.sizes(cell["config"])
        mode = files.module("modes", bench.traffic(cell["traffic"])["mode"])
        assert callable(mode.run)
    for metric in m["per_layer"]:
        spec = bench.reader_spec(metric["name"])
        assert callable(files.module("readers", spec["reader"]).read)
        if "cost" in spec.get("args", {}):
            assert callable(files.module("costs", spec["args"]["cost"]).cost)


def test_manifest_keeps_the_contracts_rules(bench):
    m = bench.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    cells = [c["name"] for c in m["workloads"]]
    pairs = [(c["config"], c["traffic"]) for c in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert {c["config"] for c in m["workloads"]} == {
        c["name"] for c in m["configs"]}
    end_to_end = {e["name"]: e for e in m["end_to_end"]}
    assert "workloads" not in end_to_end["setup_s"]
    for metric in m["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for cell in cells:
        reported = {e["name"] for e in bench.metrics("end_to_end", cell)}
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert bench.metrics("per_layer", cell), cell
    for metric in m["per_layer"]:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        for cell in metric.get("workloads", cells):
            assert cell in cells
            assert metric["moves"] in {
                e["name"] for e in bench.metrics("end_to_end", cell)}, metric
    if any(n.endswith("_roofline") for n in (x["name"] for x in m["per_layer"])):
        assert all(x["unit"] == "%" for x in m["per_layer"]
                   if x["name"].endswith("_roofline"))


@pytest.fixture(scope="module")
def cifar_fit(bench):
    """One tiny `cifar_fit`, traced, shared by the tests below. The
    window is shorter than one iteration: the loop still goes on until
    whole iterations have been traced."""
    return run.measure(
        bench, "cifar_fit", SEED, 0.01, 1, jax.devices()[:1],
        sizes=tiny(bench, "random_patch_cifar", TINY_CIFAR), log=quiet)


def test_fit_mode_tiny_is_correct_and_the_reference_agrees(bench, cifar_fit):
    assert cifar_fit["correct"] and cifar_fit["failed"] == 0
    assert cifar_fit["attempted"] == cifar_fit["stats"]["fits"] >= 1
    assert cifar_fit["stats"]["applies"] == cifar_fit["stats"]["fits"]
    assert set(cifar_fit["end_to_end"]) == {"fit_throughput", "apply_throughput"}
    # counts are the same on any backend: the readers find them by name
    metrics = run.layer_metrics(
        bench, "cifar_fit", cifar_fit, {"flops": 1.0, "bytes_per_s": 1.0},
        log=quiet)
    assert metrics["programs_per_apply"]["value"] == 1.0
    assert metrics["programs_per_fit"]["value"] >= 1.0
    # no device plane on the CPU: every device reader returns nothing
    # and its metric is left out, never reported as 0
    device_metrics = {m["name"] for m in bench.manifest["per_layer"]
                      if m["source"] == "device_trace"}
    assert not device_metrics & set(metrics)


def test_a_traced_run_traces_whole_iterations(bench, cifar_fit):
    """One iteration before the trace, `TRACE_ITERATIONS` inside it, and
    every phase annotation seen that often, however short the window."""
    n = tracing.TRACE_ITERATIONS
    trace = cifar_fit["trace"]
    assert trace["devices"] == 0 and trace["window_s"] > 0
    assert cifar_fit["stats"]["fits"] >= 1 + n
    for phase in ("fit", "apply", "evaluate"):
        assert trace["phases"][phase]["count"] == n, phase
    assert 0 < trace["phases"]["fit"]["host_s"] < trace["window_s"]


def _shuffled_labels(adapter):
    class ShuffledLabels:
        """The same pipeline fitted to labels that say nothing."""
        make_data = staticmethod(adapter.make_data)

        @staticmethod
        def build(train, sizes, seed):
            from keystone_tpu.data.dataset import Dataset
            from keystone_tpu.loaders.csv_loader import LabeledData

            labels = np.random.default_rng(0).permutation(
                np.asarray(train.labels.numpy()))
            return adapter.build(
                LabeledData(labels=Dataset(labels, mesh=train.data.mesh),
                            data=train.data), sizes, seed)

    return ShuffledLabels


def test_fit_mode_calls_a_corrupted_model_incorrect(bench):
    from benchmark.modes import fit
    from keystone_tpu.parallel.mesh import make_mesh

    record = fit.run(
        _shuffled_labels(files.module("configs", "random_patch_cifar")),
        files.module("reference", "random_patch_cifar"),
        tiny(bench, "random_patch_cifar", TINY_CIFAR),
        bench.traffic("warm_fit_apply"), SEED, 0.1,
        make_mesh(jax.devices()[:1]), log=quiet)
    assert not record["correct"]
    assert record["failed"] == record["attempted"] >= 1


def test_a_dropped_in_cell_and_metric_are_found_without_an_edit(bench, tmp_path):
    """A later PR adds a traffic file, a layer-metric file and manifest
    entries; no file that is there changes."""
    root = str(tmp_path)
    manifest = copy.deepcopy(bench.manifest)
    manifest["workloads"].append({
        "name": "made_up_cell", "config": "random_patch_cifar",
        "traffic": "made_up_traffic", "chips": 1, "why": "a test's"})
    manifest["per_layer"].append({
        "name": "solver_steps_per_fit", "unit": "steps", "better": "lower",
        "source": "program_counter", "layer": "solvers (nodes/learning/)",
        "moves": "fit_throughput", "workloads": ["made_up_cell"]})
    for metric in manifest["end_to_end"]:
        if metric["name"] in ("fit_throughput", "apply_throughput"):
            metric["workloads"].append("made_up_cell")
    copy_of_the_data_files(root, manifest)
    with open(os.path.join(root, "benchmark", "traffic",
                           "made_up_traffic.json"), "w") as f:
        json.dump({"mode": "fit", "why": "a test's"}, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "solver_steps_per_fit.json"), "w") as f:
        json.dump({"reader": "counter_delta", "args": {
            "counter": "solver.steps", "phase": "fit", "per": "fits"}}, f)

    dropped = files.BenchFiles(root)
    sizes = tiny(dropped, "random_patch_cifar", TINY_CIFAR)
    record = run.measure(dropped, "made_up_cell", SEED, 0.1, 0,
                         jax.devices()[:1], sizes=sizes, log=quiet)
    assert record["correct"]
    metrics = run.layer_metrics(dropped, "made_up_cell", record, {}, log=quiet)
    assert metrics == {"solver_steps_per_fit": {
        "value": float(sizes["bcd_iters"]), "unit": "steps"}}  # a step an epoch
    reported = run.end_to_end_metrics(dropped, "made_up_cell", record, 1.0)
    assert set(reported) >= {"setup_s", "fit_throughput", "apply_throughput"}


def test_the_data_is_a_function_of_the_seed_and_takes_a_large_one():
    def images(seed):
        (train, labels), (test, _) = datagen.cifar_like(64, 16, seed)
        return np.asarray(train), np.asarray(labels), np.asarray(test)

    first, again, other = images(SEED), images(SEED), images(SEED + 1)
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(first[0], other[0])
    assert first[0].shape == (64, 32, 32, 3) and first[2].shape[0] == 16
    assert first[0].min() >= 0.0 and first[0].max() <= 255.0
    assert not np.array_equal(first[0][:16], first[2])  # test is not train
    assert 0 <= datagen.program_seed(SEED) < 2**31 - 1


def test_the_cost_functions_count_what_the_shapes_say(bench):
    sizes = bench.sizes("random_patch_cifar")
    n, K = sizes["num_train"], sizes["num_filters"]
    assert sizes["feature_dim"] == 2 * 2 * 2 * K  # a 2 x 2 grid, both signs
    conv = files.module("costs", "conv_rectify_pool").cost(sizes)
    assert conv["flops"] == n * 27 * 27 * K * (2 * 6 * 6 * 3 + 3)
    assert conv["bytes"] == 4 * n * (32 * 32 * 3 + 8 * K)
    bcd = files.module("costs", "bcd").cost(
        {"num_train": 100, "num_classes": 2, "feature_dim": 12,
         "block_size": 8, "bcd_iters": 3})
    # two blocks of 8 (the second padded), three epochs
    step = 2 * 100 * 64 + 6 * 100 * 8 * 2 + 8**3 / 3 + 2 * 64 * 2
    assert bcd["flops"] == pytest.approx(3 * 2 * step)
    assert bcd["bytes"] == 3 * 2 * 4 * (100 * 8 + 2 * 100 * 2) + 8 * 100 * 16


def test_the_readers_on_the_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    trace = trace_reduce.reduce_planes(recorded["planes"])
    context = {"trace": trace, "counters": {}, "stats": {}, "peaks": {}}
    read = lambda reader, **args: files.module("readers", reader).read(
        context, **args)
    fit = trace["phases"]["fit"]
    assert read("device_idle", phase="fit") == pytest.approx(
        100.0 * (1.0 - fit["device_busy_s"] / fit["host_s"]))
    assert read("device_idle") == pytest.approx(
        recorded["by_hand"]["idle_percent"])
    assert read("device_idle", phase="fit") != read("device_idle")
    assert read("device_idle", phase="no_such_phase") is None
    assert read("phase_device_ms", phase="apply") == pytest.approx(
        1e3 * trace["phases"]["apply"]["device_busy_s"]
        / trace["phases"]["apply"]["count"])
    # the one module that began in the excerpt's `fit` annotation
    assert read("device_ms_matching", kind="modules", pattern="^jit__learn_",
                phase="fit") == pytest.approx(1e3 * 0.018173815 / fit["count"])
    assert read("device_ms_matching", kind="ops", pattern=r"/copy\.10$",
                phase="fit") == pytest.approx(1e3 * 0.004997638)
    assert read("device_ms_matching", kind="modules", pattern="^no_such$",
                phase="fit") is None
    # off the chip there is no device plane, and nothing is read
    context["trace"] = dict(trace, devices=0)
    assert read("device_idle", phase="fit") is None
    assert read("phase_device_ms", phase="apply") is None


def test_trace_reduction_gives_the_numbers_worked_out_by_hand():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        recorded = json.load(f)
    got = trace_reduce.reduce_planes(recorded["planes"])
    want = recorded["by_hand"]
    assert got["devices"] == want["devices"]
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    idle = 100.0 * (1.0 - got["busy_s"] / got["window_s"])
    assert idle == pytest.approx(want["idle_percent"], rel=1e-9)
    for name, seconds in want["modules"].items():
        assert got["modules"][name] == pytest.approx(seconds, rel=1e-9)
    for phase, entry in want["phases"].items():
        assert got["phases"][phase]["count"] == entry["count"]
        assert got["phases"][phase]["device_busy_s"] == pytest.approx(
            entry["device_busy_s"], rel=1e-9)
    assert got["idle_gaps"][0][0] == want["longest_gap"][0]
    assert got["idle_gaps"][0][1] == pytest.approx(want["longest_gap"][1])
    assert len(got["top_ops"]) <= 10 and len(got["idle_gaps"]) <= 5


def test_the_command_exits_nonzero_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = files.BenchFiles().manifest["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "not a TPU" in done.stderr
    from benchmark.peaks import peaks_for

    with pytest.raises(RuntimeError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    assert importlib.import_module("benchmark.peaks").DEVICE_PEAKS[
        "TPU v5 lite"]["flops"] == 1.97e14
