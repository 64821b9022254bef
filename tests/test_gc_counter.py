"""Collections of the garbage collector, counted where they happen
(PR 38): the `gc.callbacks` hook of `telemetry.gc_events` adds every
collection's seconds to ``host.gc.seconds`` and takes them out of the
layer span open around it. Counts only."""

import gc
import time

import pytest

import keystone_tpu.telemetry  # noqa: F401  (installs the hook)
from keystone_tpu.telemetry import gc_events, registry, span


@pytest.fixture(autouse=True)
def fresh_metrics():
    registry().reset()
    yield
    registry().reset()


def value(name):
    c = registry().counters.get(name)
    return c.value if c is not None else 0.0


def cycles(n=100_000):
    """Garbage that only the cycle collector frees, enough of it that a
    full collection takes milliseconds."""
    for _ in range(n):
        a, b = [], []
        a.append(b)
        b.append(a)


def test_the_hook_is_installed_once_with_the_package():
    assert gc.callbacks.count(gc_events._on_gc) == 1
    gc_events.install_gc_hook()
    assert gc.callbacks.count(gc_events._on_gc) == 1


@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_collection_is_counted_by_its_generation(generation):
    gc.collect(generation)
    assert value("host.gc.collections") >= 1
    assert value("host.gc.seconds") > 0.0
    assert value("host.gc.full_collections") == (1 if generation == 2 else 0)


def test_a_collection_inside_a_layer_span_is_not_charged_to_the_layer():
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()  # only the collection below runs under the span
    try:
        cycles()
        registry().reset()
        with span("planner", cat="phase", layer="optimize", part="solve"):
            t0 = time.perf_counter()
            gc.collect()
            pause = time.perf_counter() - t0
    finally:
        if was_enabled:
            gc.enable()
    assert value("host.gc.full_collections") == 1
    assert value("host.gc.collections") == 1
    seconds = value("host.gc.seconds")
    assert 0.0 < seconds <= pause
    assert seconds > 0.5 * pause  # the pause is the collection
    # the layer and its part lose what the collector holds
    assert value("host.optimize.seconds") <= pause - seconds + 1e-4
    assert value("host.optimize.solve.seconds") == value(
        "host.optimize.seconds")


def test_a_collection_under_no_span_is_only_counted():
    gc.collect()
    assert value("host.gc.full_collections") == 1
    assert not [k for k in registry().counters
                if k.startswith("host.") and not k.startswith("host.gc.")]


def test_the_hook_takes_no_lock_of_the_registry():
    """The collector may stop a thread that holds the registry's lock
    (`metrics._LOCK` is not reentrant): the hook must not wait for it."""
    from keystone_tpu.telemetry import metrics

    with metrics._LOCK:
        gc.collect()
    assert value("host.gc.full_collections") == 1
