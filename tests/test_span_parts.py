"""A part under a layer (PR 38): `span(..., layer=, part=)` adds its self
time to ``host.<layer>.<part>.seconds`` as well as to the layer, so the
parts of a layer sum to the layer; a span without a part is what it was.
Counts and structure only: nothing here is a time of the chip."""

import threading
import time

import pytest

from keystone_tpu.telemetry import LAYERS, dispatch, registry, span, trace_run
from keystone_tpu.telemetry import spans as spans_mod


@pytest.fixture(autouse=True)
def fresh_metrics():
    registry().reset()
    yield
    registry().reset()


def counters():
    return {k: c.value for k, c in registry().counters.items() if c.value}


def parts_of(moved, layer):
    prefix, suffix = f"host.{layer}.", ".seconds"
    return {k[len(prefix):-len(suffix)]: v for k, v in moved.items()
            if k.startswith(prefix) and k.endswith(suffix)
            and k != prefix + "seconds"}


def test_the_parts_of_a_layer_sum_to_the_layer():
    with span("optimize", cat="phase", layer="optimize", part="rules"):
        time.sleep(0.002)
        with span("planner", cat="phase", layer="optimize", part="solve"):
            with span("specs", cat="phase", layer="optimize", part="specs"):
                time.sleep(0.003)
            time.sleep(0.001)
            with span("price", cat="phase", layer="optimize", part="price"):
                time.sleep(0.002)
        with span("specs", cat="phase", layer="optimize", part="specs"):
            time.sleep(0.001)
    moved = counters()
    parts = parts_of(moved, "optimize")
    assert set(parts) == {"rules", "solve", "specs", "price"}
    assert sum(parts.values()) == pytest.approx(
        moved["host.optimize.seconds"], rel=1e-9)
    assert moved["host.optimize.spans"] == 5
    assert moved["host.optimize.specs.spans"] == 2
    assert sum(v for k, v in moved.items() if k.startswith("host.optimize.")
               and k.endswith(".spans") and k != "host.optimize.spans") == 5


def test_a_part_s_seconds_are_self_time():
    with span("planner", cat="phase", layer="optimize", part="solve"):
        time.sleep(0.01)
        with span("specs", cat="phase", layer="optimize", part="specs"):
            time.sleep(0.03)
        with dispatch("prog"):  # another layer's span leaves it too
            time.sleep(0.02)
    moved = counters()
    assert 0.01 <= moved["host.optimize.solve.seconds"] < 0.01 + 0.015
    assert 0.03 <= moved["host.optimize.specs.seconds"] < 0.03 + 0.015
    assert 0.02 <= moved["host.dispatch.seconds"] < 0.02 + 0.015
    assert moved["host.optimize.seconds"] == pytest.approx(
        moved["host.optimize.solve.seconds"]
        + moved["host.optimize.specs.seconds"])


@pytest.mark.parametrize("layer", LAYERS)
def test_a_span_without_a_part_behaves_as_before(layer):
    with span("s", cat=layer, layer=layer):
        pass
    moved = {k: c.value for k, c in registry().counters.items()}
    assert set(moved) == {f"host.{layer}.seconds", f"host.{layer}.spans"}
    assert moved[f"host.{layer}.spans"] == 1


@pytest.mark.parametrize("layer", LAYERS)
def test_every_layer_takes_a_part(layer):
    with span("s", cat=layer, layer=layer, part="p"):
        pass
    moved = {k: c.value for k, c in registry().counters.items()}
    assert moved[f"host.{layer}.p.spans"] == moved[f"host.{layer}.spans"] == 1
    assert moved[f"host.{layer}.p.seconds"] == moved[f"host.{layer}.seconds"]


def test_an_unknown_layer_still_raises_with_a_part():
    with pytest.raises(ValueError, match="layer"):
        span("x", layer="featurize", part="rules")
    assert counters() == {}


def test_a_part_needs_a_layer():
    with pytest.raises(ValueError, match="part"):
        span("x", cat="chunk", part="rules")
    # and a span with neither is still the shared no-op
    assert span("row", cat="chunk") is spans_mod._NOOP


def test_the_annotation_and_the_tracer_s_record_carry_no_part():
    with trace_run() as tr:
        with span("specs", cat="phase", layer="optimize", part="specs",
                  passes=1):
            pass
    (rec,) = [s for s in tr.spans if s.name == "specs"]
    assert rec.cat == "phase" and rec.args == {"passes": 1}


def test_worker_threads_adopted_by_a_parent_still_charge_its_part():
    """What the workers' spans take leaves the waiting span's part as it
    leaves its layer, and a worker's own span charges the part it names."""
    def worker(parent):
        spans_mod.adopt_layer_parent(parent)
        with span("pull", cat="sync", layer="sync", part="worker"):
            time.sleep(0.03)

    with span("walk", cat="phase", layer="force", part="walk"):
        t = threading.Thread(target=worker, args=(spans_mod.layer_parent(),))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        time.sleep(0.01)
    moved = counters()
    assert moved["host.sync.worker.seconds"] == moved["host.sync.seconds"]
    assert moved["host.sync.seconds"] >= 0.03
    assert moved["host.force.walk.seconds"] == moved["host.force.seconds"]
    # the 0.04 s of the span less the worker's 0.03
    assert 0.01 <= moved["host.force.walk.seconds"] < 0.01 + 0.02


def test_a_closed_measurement_leaves_the_part_too():
    with span("prepare", cat="phase", layer="force", part="prepare"):
        time.sleep(0.01)
        spans_mod.record_layer_complete("compile", 0.008)
    moved = counters()
    assert moved["host.compile.seconds"] == pytest.approx(0.008)
    assert 0.0 <= moved["host.force.prepare.seconds"] < 0.01
    assert moved["host.force.prepare.seconds"] == moved["host.force.seconds"]
