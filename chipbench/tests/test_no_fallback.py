"""checks.no_fallback on two gettpuinfo snapshots captured around a window on
the chip (data/gettpuinfo_window.json: reindex.p2pkh_dense, whole 8,190-lane
slices; ``parent`` there is what the parent commit's no_fallback returned on
them). Without a stated dispatch count the rule is the parent's; with one
the count is held exactly, through run.main too."""

import copy
import json
import os

import checks
import pytest
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "gettpuinfo_window.json")
STATED = "dev_decompose.dispatches moved by the count the driver states"
WHOLE = "dev_decompose.dispatches moved by the full buckets"


@pytest.fixture(scope="module")
def captured() -> dict:
    with open(DATA) as f:
        return json.load(f)


def names(bad: list) -> list:
    return [item["check"] for item in bad]


def test_without_a_stated_count_the_verdicts_are_the_parents(captured):
    before, after = captured["before"], captured["after"]
    for case in captured["parent"]:
        assert checks.no_fallback(before, after, **case["kwargs"]) \
            == case["returned"], case["kwargs"]
    # the window as it was: nothing failed; a bucket more was expected: the
    # lane count and the dispatch count both say so
    sigs = captured["sigs"]
    assert checks.no_fallback(before, after, sigs=sigs) == []
    assert names(checks.no_fallback(before, after, sigs=sigs + 8190)) == [
        "batch.sigs_verified moved by the window's signatures", WHOLE]


@pytest.mark.parametrize("off, failed", [(0, []), (1, [STATED]),
                                         (-1, [STATED])])
def test_a_stated_count_is_held_exactly(captured, off, failed):
    before, after, sigs = (captured[k] for k in ("before", "after", "sigs"))
    moved = (after["ecdsa"]["dev_decompose"]["dispatches"]
             - before["ecdsa"]["dev_decompose"]["dispatches"])
    bad = checks.no_fallback(before, after, sigs=sigs,
                             dispatches=moved + off)
    assert names(bad) == failed
    if failed:
        assert bad[0]["read"] == {"moved": moved, "stated": moved + off}


def drained(captured: dict, tails: int) -> dict:
    """The same window with ``tails`` more dispatches, as a flush that
    drains the aggregate's tail into the smaller buckets makes them."""
    after = copy.deepcopy(captured["after"])
    after["ecdsa"]["dev_decompose"]["dispatches"] += tails
    return after


def test_a_window_that_drains_tails_needs_the_stated_count(captured):
    before, sigs = captured["before"], captured["sigs"]
    after = drained(captured, 3)
    whole = -(-sigs // 8190)
    assert names(checks.no_fallback(before, after, sigs=sigs)) == [WHOLE]
    assert checks.no_fallback(before, after, sigs=sigs,
                              dispatches=whole + 3) == []
    assert names(checks.no_fallback(before, after, sigs=sigs,
                                    dispatches=whole + 2)) == [STATED]


@pytest.mark.parametrize("count", [2.0, True, -1, "3"])
def test_a_stated_count_is_a_whole_number(captured, count):
    with pytest.raises(TypeError, match="whole number"):
        checks.no_fallback(captured["before"], captured["after"],
                           sigs=captured["sigs"], dispatches=count)


REPLAY_DRIVER = '''
import json
import checks
def setup(ctx):
    with open(ctx.traffic["snapshots"]) as f:
        ctx.state["captured"] = json.load(f)
def warm(ctx): ctx.state["setup"] = ctx.state["captured"]["before"]
def window(ctx):
    c = ctx.state["captured"]
    result = {"before": c["before"], "after": c["after"], "window_s": 1.0,
              "sigs": c["sigs"], "attempted": c["sigs"], "failed": 0,
              "values": {"answers_per_s": float(c["sigs"])}}
    if "dispatches" in ctx.traffic:
        result["dispatches"] = ctx.traffic["dispatches"]
    return result
def check(ctx, result):
    return [checks.compared("wrong_answers", 0, 0)]
def close(ctx): pass
'''


@pytest.mark.parametrize("tails, stated, correct", [
    (0, None, True),      # today's cells: whole slices, no count stated
    (3, None, False),     # a flush cadence without a count cannot pass
    (3, 3, True), (3, 4, False), (3, 2, False)])
def test_through_run_main(captured, tmp_path, capsys, monkeypatch,
                          tails, stated, correct):
    """A whole run, the look for a chip skipped (the snapshots say tpu):
    run.py hands the driver's ``dispatches`` to the check, and a count that
    is off by one comes out ``correct: false``."""
    from bitcoincashplus_tpu.util import devicewatch

    bench = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "drivers", "layer_metrics"):
        (bench / sub).mkdir(parents=True)
    for table in ("peaks.json", "opcounts.json"):
        (bench / table).write_text(
            open(os.path.join(run.HERE, table)).read())
    snap = dict(captured, after=drained(captured, tails))
    snap["after"]["device"]["compilation_cache"]["dir"] = \
        devicewatch.compile_cache_dir()
    (tmp_path / "snap.json").write_text(json.dumps(snap))
    traffic = {"snapshots": str(tmp_path / "snap.json")}
    if stated is not None:
        traffic["dispatches"] = -(-captured["sigs"] // 8190) + stated
    (bench / "traffic" / "replayed.json").write_text(json.dumps(traffic))
    (bench / "configs" / "replay.json").write_text(json.dumps(
        {"name": "replay", "driver": "replay"}))
    (bench / "drivers" / "replay.py").write_text(REPLAY_DRIVER)
    (bench / "layer_metrics" / "answers.seen.py").write_text(
        "def read(obs):\n    return obs['result']['attempted']\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["chipbench"],
        "configs": [{"name": "replay",
                     "file": "chipbench/configs/replay.json"}],
        "workloads": [{"name": "replay.replayed", "config": "replay",
                       "traffic": "replayed", "chips": 1}],
        "end_to_end": [
            {"name": "answers_per_s", "unit": "1/s"},
            {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "answers.seen", "unit": "1",
                       "moves": "answers_per_s"}]}))
    monkeypatch.setattr(run, "build_native", lambda: 0.0)
    monkeypatch.setattr(run, "claim_device", lambda chips, rehearse: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    rc = run.main(["--workload", "replay.replayed", "--seed", "1",
                   "--seconds", "1"], root=str(tmp_path))
    printed = capsys.readouterr()
    lines = [json.loads(line) for line in printed.out.strip().splitlines()]
    assert rc == 0 and lines[-1]["correct"] is correct
    # each number compared beside its limit: the result's last key, and
    # the last lines of standard error
    assert list(lines[-1])[-1] == "compared"
    assert lines[-1]["compared"] == {
        "wrong_answers": {"value": 0, "limit": 0},
        "fallback_checks_failed": {"value": 0 if correct else 1, "limit": 0}}
    assert printed.err.strip().splitlines()[-1] == (
        "compared fallback_checks_failed = "
        + ("0 (limit 0)" if correct else "1 (limit 0) NOT WITHIN IT"))
    failed = next(n for n in lines if n.get("phase") == "compared"
                  and n["name"] == "fallback_checks_failed")
    assert failed["value"] == (0 if correct else 1) and failed["limit"] == 0
    said = [n["check"] for n in lines if n.get("phase") == "fallback"]
    assert said == ([] if correct else [STATED if stated is not None
                                        else WHOLE])
