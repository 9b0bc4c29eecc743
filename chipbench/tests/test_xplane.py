"""The trace reduction on a small recorded trace (five resident-sweep
segments of a mine.diff1_solo run on a TPU v5 lite)."""

import json
import os

import pytest
import xplane

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "mine_trace_small.json")


@pytest.fixture(scope="module")
def planes():
    with open(DATA) as f:
        return json.load(f)["planes"]


def test_union_merges_overlaps_and_keeps_gaps():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_is_the_union_of_the_op_intervals(planes):
    red = xplane.reduce(planes)
    ops = next(line for p in planes if p["name"] == "/device:TPU:0"
               for line in p["lines"] if line["name"] == "XLA Ops")
    # %while.2 holds its body's ops: a plain sum counts them twice
    plain_sum = sum(d for _, _, d in ops["events"]) / 1e9
    assert red["busy_s"] == pytest.approx(0.003885452, rel=1e-9)
    assert plain_sum > 1.9 * red["busy_s"]
    assert red["devices"] == 1


def test_idle_share_and_window(planes):
    red = xplane.reduce(planes)
    assert red["window_s"] == pytest.approx(0.012373732, rel=1e-9)
    assert red["idle_share"] == pytest.approx(
        1 - 0.003885452 / 0.012373732, rel=1e-9)
    gaps = dict(red["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert all(name.startswith("chipbench.") for name in gaps)


def test_module_time_by_name(planes):
    mods = xplane.reduce(planes)["modules"]
    assert mods["jit_sweep_fast_jit"]["count"] == 5
    assert mods["jit_sweep_fast_jit"]["seconds"] == pytest.approx(
        0.003991874, rel=1e-6)
    assert mods["jit_convert_element_type"]["count"] == 8
    assert xplane.module_name("jit__glv_dev_program(123)") == \
        "jit__glv_dev_program"


def test_top_ops_are_named_short(planes):
    ops = xplane.reduce(planes, top=3)["device_ops"]
    assert [n for n, _ in ops[:2]] == ["%while.2", "%fusion.7"]
    assert len(ops) == 3 and all(len(n) <= 80 for n, _ in ops)


def test_explicit_window_clips_busy(planes):
    whole = xplane.reduce(planes)
    half = xplane.reduce(planes, window_ns=(0, 6_000_000))
    assert 0 < half["busy_s"] < whole["busy_s"]
    assert half["window_s"] == pytest.approx(0.006)


def test_a_trace_without_a_device_plane_is_an_error(planes):
    hosts = [p for p in planes if not p["name"].startswith("/device:TPU")]
    with pytest.raises(ValueError, match="no TPU device plane"):
        xplane.reduce(hosts)


def test_gaps_are_named_by_the_innermost_span_the_program_or_the_harness():
    """A device busy 10-20 and 50-60 in a window 0-100; the program's spans
    (bcp.*) lie inside the harness's: each idle gap is cut at their edges
    and each piece goes to the span open there that started last."""
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["%a = x", 10, 10],
                                           ["%b = y", 50, 10]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                ["chipbench.window", 0, 100], ["chipbench.import", 5, 85],
                ["bcp.import.connect", 20, 25], ["bcp.import.flush", 60, 20],
                ["other.thing", 0, 100]]},
            {"name": "shard", "events": [["bcp.store.commit", 70, 5]]}]}]
    red = xplane.reduce(planes)
    assert red["window_s"] == pytest.approx(100e-9)
    assert {n: round(v * 1e9) for n, v in red["idle_gaps"]} == {
        "chipbench.window": 5 + 10,     # 0-5, 90-100
        "chipbench.import": 5 + 5 + 10,  # 5-10, 45-50, 80-90
        "bcp.import.connect": 25,       # 20-45
        "bcp.import.flush": 10 + 5,     # 60-70, 75-80
        "bcp.store.commit": 5}          # 70-75: it started last
    # the window is the harness's outermost annotation, whatever else the
    # trace was loaded with
    planes[1]["lines"][0]["events"].append(["bcp.node.init", 0, 500])
    assert xplane.reduce(planes)["window_s"] == pytest.approx(100e-9)
    assert xplane.HOST_PREFIXES == ("chipbench.", "bcp.")


def test_idle_by_span_leaves_what_no_span_covers_unannotated():
    table = xplane.idle_by_span([(0, 10), (20, 30)], [("s", 5, 25)])
    assert table == {"unannotated": 5 + 5, "s": 5 + 5}
    assert xplane.idle_by_span([(0, 10)], []) == {"unannotated": 10}
