"""The `device` reader's idle tables (PR 38), on planes made by hand:
`idle_by_span_s` holds every gap between device ops under the innermost
``ks:`` span at its middle and sums to the device's idle time; a gap
that the four planes of a mesh see is listed once, split by the spans
that were open under it. Plain data: nothing here ran on a chip."""

import pytest

from keystone_tpu.telemetry import device


def E(name, start, end):
    return device.Event(name, float(start), float(end), "")


HOST = [
    E("ks:force:root fit", 0, 2000),
    E("ks:optimize:optimize", 100, 900),
    E("ks:optimize:optimizer:unified", 150, 700),
    E("ks:optimize:unified_planner", 160, 690),
    E("ks:optimize:specs", 170, 300),
    E("ks:optimize:price", 310, 520),
    E("ks:optimize:enforce", 600, 680),
    E("ks:optimize:optimizer:place", 700, 880),
    E("ks:optimize:sharding_planner", 710, 870),
    E("ks:optimize:specs", 720, 800),
    E("ks:sync:pull", 1000, 2000),
    E("bench:fit", 0, 2000),
]


def ops(shift=0):
    """One chip: a draw, the planner's gap, two programs with a small gap
    between them. ``shift`` moves the gap's ends, as the chips of a mesh
    differ by a little."""
    return {device.OPS_LINE: [
        E("%draw.1 = f32[2] fusion(...)", 50, 120 + shift),
        E("%fusion.1 = f32[2] fusion(...)", 920 + shift, 1400),
        E("%fusion.2 = f32[2] fusion(...)", 1410, 1900)]}


def mesh_planes(chips):
    planes = {"/host:CPU": {"python": list(HOST)}}
    for chip in range(chips):
        planes[f"/device:TPU:{chip}"] = ops(shift=chip)
    return planes


@pytest.mark.parametrize("chips", [1, 4])
def test_idle_by_span_sums_to_the_device_s_idle_time(chips):
    table = device.reduce_planes(mesh_planes(chips))
    assert table["devices"] == chips
    # first op's start to last op's end, less the busy time, a chip
    window = 1900e-9 - 50e-9
    assert table["device_idle_s"] == pytest.approx(
        window - table["device_busy_s"])
    assert sum(table["idle_by_span_s"].values()) == pytest.approx(
        table["device_idle_s"])
    # the long gap's middle (520) lies in the planner's own self time,
    # the short one's (1405) in the pull
    assert set(table["idle_by_span_s"]) == {
        "ks:optimize:unified_planner", "ks:sync:pull"}
    assert table["idle_by_span_s"]["ks:sync:pull"] == pytest.approx(10e-9)


def test_a_gap_seen_by_four_planes_is_listed_once():
    table = device.reduce_planes(mesh_planes(4))
    long_gap, short_gap = table["gaps"]
    assert len(table["gaps"]) == 2
    assert long_gap["planes"] == short_gap["planes"] == 4
    # 120..920 on chip 0; every further chip's gap is as long, shifted
    assert long_gap["seconds"] == pytest.approx(800e-9)
    assert long_gap["span"] == "ks:optimize:unified_planner"
    assert short_gap == {"seconds": pytest.approx(10e-9), "planes": 4,
                         "span": "ks:sync:pull",
                         "under": {"ks:sync:pull": pytest.approx(10e-9)}}


def test_a_long_gap_is_split_by_the_spans_under_it_in_order():
    (long_gap, _) = device.reduce_planes(mesh_planes(1))["gaps"]
    under = {k: round(v * 1e9) for k, v in long_gap["under"].items()}
    assert list(under) == [
        "ks:optimize:optimize", "ks:optimize:optimizer:unified",
        "ks:optimize:unified_planner", "ks:optimize:specs",
        "ks:optimize:price", "ks:optimize:enforce",
        "ks:optimize:optimizer:place", "ks:optimize:sharding_planner",
        "ks:force:root fit"]
    assert under["ks:optimize:specs"] == 130 + 80  # both planners' passes
    assert under["ks:optimize:price"] == 210
    assert under["ks:optimize:enforce"] == 80
    # the planner's self time: 530 less its three children
    assert under["ks:optimize:unified_planner"] == 530 - 130 - 210 - 80
    assert under["ks:optimize:sharding_planner"] == 160 - 80
    assert sum(under.values()) == 800


def test_gaps_of_different_moments_stay_apart_on_a_mesh():
    planes = mesh_planes(2)
    # chip 1 alone waits once more, later
    planes["/device:TPU:1"][device.OPS_LINE][2] = E(
        "%fusion.2 = f32[2] fusion(...)", 1500, 1900)
    table = device.reduce_planes(planes)
    by_planes = sorted((g["planes"], round(g["seconds"] * 1e9))
                       for g in table["gaps"])
    assert by_planes == [(1, 10), (1, 100), (2, 800)]


def test_the_rendering_prints_the_idle_table():
    text = device.render(device.reduce_planes(mesh_planes(4)))
    assert "idle_by_span_s" in text
    assert "under ks:optimize:price" in text
