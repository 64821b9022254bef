"""The one span primitive and its three sinks (PR 25): the profiler's
annotation, the layer clock and the host tracer; `dispatch(label)`; the
`ks.` scopes in lowered programs; the `device` reader on planes made by
hand; the batcher's timestamps under a fake clock. Counts and
structure only: nothing here is a time of the chip."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keystone_tpu import Dataset
from keystone_tpu.telemetry import (
    LAYERS,
    device,
    dispatch,
    registry,
    scope_name,
    span,
    trace_run,
)
from keystone_tpu.telemetry import spans as spans_mod


@pytest.fixture(autouse=True)
def fresh_metrics():
    registry().reset()
    yield
    registry().reset()


def counters():
    return {k: c.value for k, c in registry().counters.items() if c.value}


# ------------------------------------------------------ profiler annotation


def host_events(trace_dir, prefix="ks:"):
    """[(name, start ns, end ns, stats)] of the host plane's events whose
    names start with ``prefix``, from the one xplane under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    start = float(ev.start_ns)
                    out.append((ev.name, start,
                                start + float(ev.duration_ns),
                                dict(ev.stats)))
    return out


def test_span_in_a_profiler_session_is_an_annotation_nested_as_opened(
        tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("root A", cat="phase", layer="force", rows=7):
            with dispatch("program B"):
                time.sleep(0.002)
            with span("pull C", cat="sync", layer="sync", rid="r-1"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    events = {name: (s, e, stats) for name, s, e, stats in
              host_events(str(tmp_path))}
    assert set(events) == {"ks:force:root A", "ks:dispatch:program B",
                           "ks:sync:pull C"}
    a, b, c = (events[n] for n in ("ks:force:root A",
                                   "ks:dispatch:program B",
                                   "ks:sync:pull C"))
    assert a[0] <= b[0] and b[1] <= c[0] and c[1] <= a[1]
    assert a[2]["rows"] == 7 and c[2]["rid"] == "r-1"


def test_span_with_no_session_moves_the_layer_counters_and_nothing_else():
    with span("root", cat="phase", layer="force") as rec:
        pass
    assert rec is None  # no host tracer: no record
    moved = counters()
    assert set(moved) == {"host.force.seconds", "host.force.spans"}
    assert moved["host.force.spans"] == 1
    # no layer and no tracer: the shared no-op, not even a counter
    registry().reset()
    ctx = span("row", cat="chunk")
    assert ctx is spans_mod._NOOP
    with ctx:
        pass
    assert counters() == {}


def test_span_rejects_a_layer_that_is_not_one():
    with pytest.raises(ValueError, match="layer"):
        span("x", layer="featurize")


@pytest.mark.parametrize("layer", LAYERS)
def test_every_layer_has_its_two_counters(layer):
    with span("s", cat=layer, layer=layer):
        pass
    moved = counters()
    assert moved[f"host.{layer}.spans"] == 1
    assert moved[f"host.{layer}.seconds"] >= 0.0


def test_layer_span_is_recorded_by_the_host_tracer_with_its_parent():
    with trace_run() as tr:
        with span("root", cat="phase", layer="force"):
            with dispatch("prog", rows=3):
                pass
            with span("row", cat="chunk", rid="r-9"):
                pass
    by_name = {s.name: s for s in tr.spans}
    assert by_name["prog"].parent == by_name["root"].sid
    assert by_name["prog"].cat == "dispatch"
    assert by_name["prog"].args["rows"] == 3
    assert by_name["row"].parent == by_name["root"].sid
    assert by_name["row"].args["rid"] == "r-9"


# ------------------------------------------------------------- layer clock


def test_self_time_goes_to_each_layer_and_the_parent_keeps_the_rest():
    with span("root", cat="phase", layer="force"):
        time.sleep(0.02)
        with dispatch("prog"):
            time.sleep(0.03)
        with span("pull", cat="sync", layer="sync"):
            time.sleep(0.04)
            with span("inner pull", cat="sync", layer="sync"):
                time.sleep(0.01)
    moved = counters()
    force, disp, sync = (moved[f"host.{k}.seconds"]
                         for k in ("force", "dispatch", "sync"))
    assert 0.02 <= force < 0.03 + 0.02   # the remainder, not the 0.10
    assert 0.03 <= disp < 0.03 + 0.02
    assert 0.05 <= sync < 0.05 + 0.02    # nested spans of one layer add up
    assert moved["host.sync.spans"] == 2
    assert force + disp + sync < 0.10 + 0.03


def test_a_closed_measurement_leaves_the_open_spans_self_time():
    with dispatch("first call"):
        time.sleep(0.01)
        spans_mod.record_layer_complete("compile", 0.008)
    moved = counters()
    assert moved["host.compile.seconds"] == pytest.approx(0.008)
    assert moved["host.compile.spans"] == 1
    assert 0.0 <= moved["host.dispatch.seconds"] < 0.01
    # with no span open it is only counted
    spans_mod.record_layer_complete("compile", 0.5)
    assert counters()["host.compile.seconds"] == pytest.approx(0.508)


def test_layer_stacks_are_per_thread():
    import threading

    def other():
        with span("pull", cat="sync", layer="sync"):
            time.sleep(0.03)

    with span("root", cat="phase", layer="force"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    moved = counters()
    # the other thread's span is no child of this thread's
    assert moved["host.force.seconds"] >= 0.03
    assert moved["host.sync.seconds"] >= 0.03


# ---------------------------------------------------------------- dispatch


@pytest.mark.parametrize("n", [1, 3])
def test_dispatch_counts_what_record_dispatch_counted(n, monkeypatch):
    from keystone_tpu.telemetry import instrument

    monkeypatch.setattr(instrument, "_proc_dim_cache", "p1")
    instrument.record_dispatch(n)
    by_hand = counters()
    registry().reset()
    with dispatch("prog", n):
        pass
    moved = counters()
    for name in ("dispatch.programs_executed",
                 "dispatch.programs_executed.p1"):
        assert moved[name] == by_hand[name] == n
    assert moved["host.dispatch.spans"] == 1


def test_dispatch_that_raises_launched_nothing_and_closes_its_span():
    with pytest.raises(RuntimeError, match="refused"):
        with trace_run() as tr:
            with dispatch("prog"):
                raise RuntimeError("refused")
    moved = counters()
    assert "dispatch.programs_executed" not in moved
    assert moved["host.dispatch.spans"] == 1
    assert next(s for s in tr.spans if s.name == "prog").error
    # the stack unwound: the next span is nobody's child
    with dispatch("again"):
        pass
    assert counters()["dispatch.programs_executed"] == 1


def test_no_bare_record_dispatch_call_site_is_left():
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bare = []
    for path in glob.glob(os.path.join(root, "keystone_tpu", "**", "*.py"),
                          recursive=True):
        if path.endswith(os.path.join("telemetry", "instrument.py")):
            continue
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if re.search(r"\brecord_dispatch\(", line):
                    bare.append(f"{path}:{i}")
    assert bare == []


def test_map_batches_dispatch_bears_the_functions_name():
    def plus_one(a):
        return a + 1.0

    with trace_run() as tr:
        Dataset(np.ones((8, 2), np.float32)).map_batches(plus_one)
    assert [s.name for s in tr.spans if s.cat == "dispatch"] == ["plus_one"]


# ------------------------------------------------------------ named scopes


def test_scope_names_are_stable_and_free_of_separators():
    assert scope_name("Fused[A >> B]") == "ks.Fused[A>>B]"
    assert scope_name("_ConvRectifyPoolStage") == "ks.ConvRectifyPoolStage"
    assert "/" not in scope_name("a/b c")


def test_fused_program_lowers_with_a_scope_per_stage():
    from keystone_tpu.nodes.images.core import (
        Convolver,
        ImageVectorizer,
        PixelScaler,
        Pooler,
        SymmetricRectifier,
    )
    from keystone_tpu.nodes.util.fusion import FusedBatchTransformer

    rng = np.random.default_rng(0)
    filters = rng.normal(size=(8, 27)).astype(np.float32)
    fused = FusedBatchTransformer(
        [PixelScaler(),
         Convolver(filters, 8, 8, 3, normalize_patches=True),
         SymmetricRectifier(alpha=0.25), Pooler(2, 3, pool_fn="sum"),
         ImageVectorizer()], microbatch=4)
    data = Dataset(rng.normal(size=(16, 8, 8, 3)).astype(np.float32))
    statics, flat, treedef, fns = fused._decompose()
    program = fused._build_program(
        data.mesh, data.n_shards, data.padded_count, treedef, fns,
        statics=statics)
    text = program.lower(flat, data.array, data.mask).as_text(
        debug_info=True)
    for scope in ("ks.PixelScaler", "ks.ConvRectifyPoolStage", "ks.conv",
                  "ks.rectify", "ks.pool", "ks.ImageVectorizer"):
        assert scope in text, scope
    # metadata only: the same program without its debug info has none
    assert "ks." not in program.lower(flat, data.array, data.mask).as_text()


def test_bcd_programs_lower_with_their_parts_named():
    from keystone_tpu.nodes.learning.block_ls import (
        _bcd_epoch,
        _bcd_finalize,
        _bcd_prepare,
    )

    X = jnp.ones((16, 8), jnp.float32)
    Y = jnp.ones((16, 3), jnp.float32)
    mask = jnp.ones((16,), jnp.float32)
    prepare = _bcd_prepare.lower(X, Y, mask, 4, 2, True).as_text(
        debug_info=True)
    assert "ks.bcd.centre" in prepare
    W = jnp.zeros((2, 4, 3), jnp.float32)
    epoch = _bcd_epoch.lower(W, Y, X, jnp.float32(1.0), 4, 2).as_text(
        debug_info=True)
    for scope in ("ks.bcd.gram", "ks.bcd.solve", "ks.bcd.residual"):
        assert scope in epoch, scope
    final = _bcd_finalize.lower(W, jnp.zeros((8,)), jnp.zeros((3,))).as_text(
        debug_info=True)
    assert "ks.bcd.intercept" in final


def test_pallas_calls_bear_names():
    """Every `pl.pallas_call` in `ops/` is named `ks_<kernel>`, so a
    Mosaic call in a device trace is no longer `closed_call.<n>`."""
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for path in glob.glob(os.path.join(root, "keystone_tpu", "ops", "*.py")):
        with open(path) as f:
            text = f.read()
        calls = len(re.findall(r"pl\.pallas_call\(", text))
        names = len(re.findall(r'\bname="ks_[a-z_]+"', text))
        assert calls == names, path


# ------------------------------------------------------ the `device` reader


def E(name, start, end, scope=""):
    return device.Event(name, float(start), float(end), scope)


def planes_by_hand():
    """One device: two modules; op times in ns.

        0        100      200      300   340      500          700  ns
        |-- ks:force:root ---------------------------------------|  (0..700)
           |ks:dispatch:A|     |ks:dispatch:B|  |-- ks:sync:pull --|
           10..60              210..260         420..700
        device ops:  100..200 (conv), 200..300 (rect), 340..500 (while,
        holding 350..450 gram), then nothing to 700... and 640..680 (copy)
    """
    host = {"python": [
        E("ks:force:root", 0, 700), E("ks:dispatch:A", 10, 60),
        E("ks:dispatch:B", 210, 260), E("ks:sync:pull", 420, 700),
        E("bench:fit", 0, 700), E("PjitFunction(f)", 10, 50)]}
    dev = {
        device.MODULES_LINE: [E("jit_per_shard(123)", 100, 300),
                              E("jit__bcd_epoch(7)", 340, 500),
                              E("jit_copy(9)", 640, 680)],
        device.OPS_LINE: [
            E("%fusion.27 = f32[2] fusion(...)", 100, 200,
              "jit(per_shard)/ks.Conv/ks.conv/conv_general_dilated"),
            E("%add_maximum_fusion.3 = f32[2] fusion(...)", 200, 300,
              "jit(per_shard)/ks.Conv/ks.rectify/max"),
            E("%while.1 = (f32[2]) while(...)", 340, 500,
              "jit(_bcd_epoch)/while"),
            E("%fusion.9 = f32[2] fusion(...)", 350, 450,
              "jit(_bcd_epoch)/while/body/ks.bcd.gram/dot_general"),
            E("%copy.1 = f32[2] copy(...)", 640, 680, ""),
        ]}
    return {"/host:CPU": host, "/device:TPU:0": dev,
            "/device:TPU:1": {device.OPS_LINE: []}}


def test_device_reader_gives_the_numbers_worked_out_by_hand():
    table = device.reduce_planes(planes_by_hand(),
                                 prefixes=("ks:", "bench:"))
    assert table["devices"] == 1
    assert table["device_busy_s"] == pytest.approx(400e-9)
    spans = table["spans"]
    assert spans["ks:force:root"] == pytest.approx(
        {"runs": 1, "host_s": 700e-9, "device_busy_s": 400e-9,
         "launches": 3})
    assert spans["ks:dispatch:A"]["device_busy_s"] == 0.0
    assert spans["ks:dispatch:B"]["device_busy_s"] == pytest.approx(50e-9)
    assert spans["ks:sync:pull"] == pytest.approx(
        {"runs": 1, "host_s": 280e-9, "device_busy_s": 120e-9,
         "launches": 1})
    assert spans["bench:fit"]["launches"] == 3
    assert "PjitFunction(f)" not in spans
    scopes = table["scopes"]
    assert scopes["ks.Conv/ks.conv"]["device_s"] == pytest.approx(100e-9)
    assert list(scopes["ks.Conv/ks.conv"]["ops"]) == ["fusion.27"]
    assert list(scopes["ks.Conv/ks.rectify"]["ops"]) == [
        "add_maximum_fusion.3"]
    assert scopes["ks.bcd.gram"]["device_s"] == pytest.approx(100e-9)
    # the while's self time (160 less the 100 nested in it) and the copy
    assert scopes[device.NO_SCOPE]["device_s"] == pytest.approx(100e-9)
    assert table["modules"]["jit_per_shard"] == pytest.approx(
        {"launches": 1, "device_s": 200e-9})
    gaps = [(round(g["seconds"] * 1e9), g["span"]) for g in table["gaps"]]
    # 500..640 falls in the pull (its middle, 570), 300..340 in the root
    assert gaps == [(140, "ks:sync:pull"), (40, "ks:force:root")]


def test_device_reader_names_a_gap_outside_every_span():
    planes = planes_by_hand()
    planes["/host:CPU"]["python"] = [E("ks:dispatch:A", 10, 60)]
    table = device.reduce_planes(planes)
    assert {g["span"] for g in table["gaps"]} == {device.NO_SPAN}


def test_device_subcommand_reads_a_trace_directory(tmp_path, capsys):
    """The CLI end to end on a CPU trace: the spans are there; a CPU has
    no device plane, so busy time and scopes are empty, not an error."""
    from keystone_tpu.telemetry.__main__ import main

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with span("root", cat="phase", layer="force"):
            Dataset(np.ones((8, 2), np.float32)).map_batches(jnp.sin).sync()
    finally:
        jax.profiler.stop_trace()
    assert main(["device", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "ks:force:root" in out and "ks:dispatch:sin" in out
    assert "ks:sync:Dataset.sync" in out
    assert main(["device", str(tmp_path / "nothing-here")]) == 2


# ------------------------------------------------------------- the batcher


def test_batcher_waits_order_and_sum_under_a_fake_clock():
    from keystone_tpu.serving.batcher import MicroBatcher, _Pending

    ticks = iter(range(100, 200))

    def clock():
        return float(next(ticks))

    seen = []

    def apply_fn(stacked):
        seen.append(stacked.shape[0])
        return stacked * 2.0

    batcher = MicroBatcher(apply_fn, max_batch=4, clock=clock)
    pendings = [_Pending(np.full((2,), i, np.float32), clock())
                for i in range(3)]          # submitted at 100, 101, 102
    for p in pendings:
        batcher._queue.put_nowait(p)
    batch = batcher._drain_batch()          # taken at 103, 104, 105
    assert batch == pendings
    batcher._dispatch(batch)                # dispatched at 106
    for p in pendings:
        assert p.t_submit <= p.t_taken <= 106.0
        assert p.done.is_set() and p.error is None
    np.testing.assert_allclose(pendings[2].result, [4.0, 4.0])
    moved = counters()
    assert moved["serving.queue_wait_seconds"] == 3 + 3 + 3
    assert moved["serving.coalesce_wait_seconds"] == 3 + 2 + 1
    assert moved["serving.rows_dispatched"] == 3 == seen[0]
    assert moved["serving.dispatches"] == 1
    assert moved["host.serve.spans"] == 1
    # a request's whole wait is the sum of its two
    assert (moved["serving.queue_wait_seconds"]
            + moved["serving.coalesce_wait_seconds"]
            == sum(106.0 - p.t_submit for p in pendings))


def test_batcher_inline_path_counts_its_row():
    from keystone_tpu.serving.batcher import MicroBatcher

    batcher = MicroBatcher(lambda x: x + 1.0, max_batch=4)  # never started
    out = batcher.submit(np.zeros((2,), np.float32))
    np.testing.assert_allclose(out, [1.0, 1.0])
    moved = counters()
    assert moved["serving.rows_dispatched"] == 1
    assert moved["serving.dispatches"] == 1
    assert "serving.queue_wait_seconds" not in moved
