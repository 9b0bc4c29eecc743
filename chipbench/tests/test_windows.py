"""A run of several windows (drivers/reindex.run_windows) and a traced
window that lost device events (run.traced_window): the rate is all the
work over all the time, every window is held to its own snapshots, one
window that fails makes the run fail, and a trace with a module event
missing is made again or refused."""

import json
import os
import statistics

import checks
import pytest
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
reindex = run.load_module("drivers", "reindex")


class Ctx:
    def __init__(self, windows, copies, trace=False):
        self.trace = trace
        self.traffic = {"windows": windows} if windows else {}
        self.state = {"before": {"n": 0},
                      "datadirs": [f"copy{i}" for i in range(copies)]}


class Node:
    closed = False

    def close(self):
        self.closed = True


def stub_window(walls, nodes, seen):
    """one_window(ctx, datadir, before): 100 answers in walls[i] seconds;
    notes what it was handed and whether the node before it was closed."""
    def one(ctx, datadir, before):
        i = len(seen)
        seen.append({"datadir": datadir, "before": before, "open": [
            n for n in nodes if not n.closed]})
        nodes.append(Node())
        ctx.state["node"] = nodes[-1]
        return {"before": before, "after": {"n": i + 1, "import": {
                    "wall_s": walls[i], "dispatches": 2, "phases": {
                        "import.connect": {"self_s": walls[i] / 2}}}},
                "window_s": walls[i], "sigs": 100, "dispatches": 2,
                "attempted": 100, "failed": int(i == 1),
                "values": {"reindex_sigs_per_s": 100 / walls[i]},
                "report": {"main_thread_cpu_s": 0.5, "process_cpu_s": 1.0,
                           "gc_s": 0.0, "gc_full_collections": 0,
                           "last": i}}
    return one


def test_a_run_of_three_windows_is_all_the_work_over_all_the_time():
    walls, nodes, seen = [2.0, 4.0, 2.5], [], []
    ctx = Ctx(3, copies=3)
    result = reindex.run_windows(ctx, stub_window(walls, nodes, seen))
    assert result["window_s"] == 8.5 and result["attempted"] == 300
    assert result["failed"] == 1
    assert result["values"] == {"reindex_sigs_per_s": 300 / 8.5}
    # the steadier statistic stands beside it, and is not the metric
    assert result["report"]["median_sigs_per_s"] == statistics.median(
        100 / w for w in walls) == 40.0
    # every window over a copy of its own, after the one before it, whose
    # node was closed before it started (outside its clock); the last
    # node stays open for the check
    assert [s["datadir"] for s in seen] == ["copy0", "copy1", "copy2"]
    assert [s["before"]["n"] for s in seen] == [0, 1, 2]
    assert all(s["open"] == [] for s in seen)
    assert [n.closed for n in nodes] == [True, True, False]
    assert ctx.state["node"] is nodes[-1] and ctx.state["datadirs"] == []
    # each window's snapshots for the checks, the first's before on top
    assert [w["after"]["n"] for w in result["windows"]] == [1, 2, 3]
    assert result["before"] == {"n": 0} and result["after"]["n"] == 3
    lines = result["report"]["windows"]
    assert [line["window_s"] for line in lines] == walls
    assert lines[1]["spans"] == {"import.connect": 2.0}
    assert lines[1]["dispatches"] == 2 and lines[1]["wall_s"] == 4.0
    assert result["report"]["last"] == 2


@pytest.mark.parametrize("windows, trace, copies, ran", [
    (3, True, 2, 1),      # a traced run is one window; its spare stays
    (None, False, 1, 1),  # a traffic file without the key: one window
    (1, False, 1, 1)])
def test_one_window_is_what_it_was(windows, trace, copies, ran):
    seen = []
    ctx = Ctx(windows, copies, trace)
    result = reindex.run_windows(ctx, stub_window([2.0] * 3, [], seen))
    assert len(seen) == len(result["windows"]) == ran
    assert result["window_s"] == 2.0
    assert result["values"] == {"reindex_sigs_per_s": 50.0}
    assert len(ctx.state["datadirs"]) == copies - ran
    # traced once more: the next copy, after the window before it
    again = reindex.run_windows(ctx, stub_window([2.0] * 3, [], seen)) \
        if trace else None
    if again:
        assert seen[-1]["datadir"] == "copy1"
        assert seen[-1]["before"] == result["after"]


def test_a_window_without_a_copy_left_is_an_error():
    ctx = Ctx(3, copies=2)
    with pytest.raises(RuntimeError, match="no work copy"):
        reindex.run_windows(ctx, stub_window([1.0] * 3, [], []))


def numbers(gap, note=""):
    return [checks.compared("tip_height_gap", gap, 0, note=note),
            checks.compared("slow_path_blocks", 0 if gap < 2 else -1, 0,
                            ok=gap < 2)]


def test_one_failing_window_fails_the_runs_number():
    assert checks.worst_of([numbers(0)]) == numbers(0)
    sound = checks.worst_of([numbers(0, "of 9"), numbers(0), numbers(0)])
    assert [n["ok"] for n in sound] == [True, True]
    assert sound[0]["note"] == "of 9; worst of 3 windows"
    one_off = checks.worst_of([numbers(0), numbers(1), numbers(3)])
    assert [(n["name"], n["value"], n["ok"]) for n in one_off] == [
        ("tip_height_gap", 1, False), ("slow_path_blocks", -1, False)]
    assert one_off[0]["note"] == "window 2 of 3; not within it in [2, 3]"
    assert one_off[1]["note"] == "window 3 of 3; not within it in [3]"
    with pytest.raises(ValueError):
        checks.worst_of([numbers(0), numbers(0)[::-1]])
    with pytest.raises(ValueError):
        checks.worst_of([numbers(0), numbers(0)[:1]])


def rehearse(capsys, workload, seed, *extra):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1", "--rehearse", *extra])
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    return rc, lines


def test_a_rehearsed_cell_measures_its_three_windows(capsys):
    rc, lines = rehearse(capsys, "reindex.p2pkh_dense", 2**31 + 21)
    assert rc == 0 and lines[-1]["correct"] is True
    window = next(n for n in lines if n.get("phase") == "window")
    each = window["windows"]
    assert len(each) == run.load_cell("reindex.p2pkh_dense")["traffic"][
        "windows"] == 3
    assert window["window_s"] == pytest.approx(
        sum(w["window_s"] for w in each))
    assert window["attempted"] == lines[-1]["attempted"] == 3 * 600
    assert window["median_sigs_per_s"] == statistics.median(
        w["sigs_per_s"] for w in each)
    for one in each:
        assert one["sigs_per_s"] == pytest.approx(600 / one["window_s"])
        assert 0 < one["wall_s"] < one["window_s"]
        assert one["main_thread_cpu_s"] > 0 and one["dispatches"] >= 1
        # the import's self seconds add up to its wall
        imports = sum(s for name, s in one["spans"].items()
                      if not name.startswith("node."))
        assert imports == pytest.approx(one["wall_s"], rel=0.02)
    reference = next(n for n in lines if n.get("phase") == "reference")
    assert len(reference["node"]) == 3  # one replay, three chains held to it


def test_one_window_that_went_wrong_makes_the_run_not_correct(
        capsys, monkeypatch):
    """The second window's node loses a coin: a sound chain, two sound
    windows, and the run is not correct."""
    # every driver's module is loaded anew by run.py: patch where they
    # all end, the store's count
    from bitcoincashplus_tpu.node.node import Node as Bcpd

    real, calls = Bcpd.__init__, []

    def init(self, config):
        real(self, config)
        calls.append(self)
        if len(calls) == 3:  # the warm-up's, the first window's, this one
            count = self.coins_db.count_coins
            self.coins_db.count_coins = lambda: count() - 1

    monkeypatch.setattr(Bcpd, "__init__", init)
    rc, lines = rehearse(capsys, "reindex.p2pkh_dense", 2**31 + 21)
    compared = {n["name"]: n for n in lines if n.get("phase") == "compared"}
    assert len(calls) == 4 and lines[-1]["correct"] is False
    assert compared["utxo_count_gap"]["value"] == 1
    assert compared["utxo_count_gap"]["note"] == \
        "window 2 of 3; not within it in [2]"
    assert all(n["ok"] for name, n in compared.items()
               if name != "utxo_count_gap")


def test_a_fault_chain_is_not_correct_in_any_window(capsys):
    rc, lines = rehearse(capsys, "reindex.p2pkh_dense", 2**31 + 22,
                         "--fault", "wrong-key-sig")
    compared = {n["name"]: n for n in lines if n.get("phase") == "compared"}
    assert lines[-1]["correct"] is False
    assert not compared["sampled_signatures_refused_by_reference"]["ok"]
    assert "not within it in [1, 2, 3]" in compared[
        "sampled_signatures_refused_by_reference"]["note"]


REPLAY_DRIVER = '''
import json
import checks
def setup(ctx):
    with open(ctx.traffic["snapshots"]) as f:
        ctx.state["captured"] = json.load(f)
def warm(ctx): ctx.state["setup"] = ctx.state["captured"]["before"]
def window(ctx):
    c = ctx.state["captured"]
    each = [{"before": c["before"], "after": c["after"], "window_s": 1.0,
             "sigs": c["sigs"], "dispatches": stated,
             "attempted": c["sigs"], "failed": 0}
            for stated in ctx.traffic["dispatches"]]
    return {**each[-1], "window_s": 3.0, "attempted": 3 * c["sigs"],
            "values": {"answers_per_s": float(c["sigs"])}, "windows": each}
def check(ctx, result):
    return [checks.compared("wrong_answers", 0, 0)]
def close(ctx): pass
'''


@pytest.mark.parametrize("off, failed", [((0, 0, 0), []),
                                         ((0, 1, 0), [2]),
                                         ((1, 0, -1), [1, 3])])
def test_no_fallback_gets_each_windows_own_dispatches(
        tmp_path, capsys, monkeypatch, off, failed):
    """A whole run, the look for a chip skipped (the snapshots say tpu):
    each window's stated ``dispatches`` and snapshots go to the check, and
    a window whose count is off by one makes the run not correct."""
    from bitcoincashplus_tpu.util import devicewatch

    with open(os.path.join(DATA, "gettpuinfo_window.json")) as f:
        captured = json.load(f)
    captured["after"]["device"]["compilation_cache"]["dir"] = \
        devicewatch.compile_cache_dir()
    bench = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "drivers", "layer_metrics"):
        (bench / sub).mkdir(parents=True)
    for table in ("peaks.json", "opcounts.json"):
        (bench / table).write_text(
            open(os.path.join(run.HERE, table)).read())
    (tmp_path / "snap.json").write_text(json.dumps(captured))
    whole = -(-captured["sigs"] // 8190)
    (bench / "traffic" / "replayed.json").write_text(json.dumps({
        "snapshots": str(tmp_path / "snap.json"),
        "dispatches": [whole + d for d in off]}))
    (bench / "configs" / "replay.json").write_text(json.dumps(
        {"name": "replay", "driver": "replay"}))
    (bench / "drivers" / "replay.py").write_text(REPLAY_DRIVER)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "paths": ["chipbench"],
        "configs": [{"name": "replay",
                     "file": "chipbench/configs/replay.json"}],
        "workloads": [{"name": "replay.replayed", "config": "replay",
                       "traffic": "replayed", "chips": 1}],
        "end_to_end": [{"name": "answers_per_s", "unit": "1/s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": []}))
    monkeypatch.setattr(run, "build_native", lambda: 0.0)
    monkeypatch.setattr(run, "claim_device", lambda chips, rehearse: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    given = []
    real = checks.no_fallback
    monkeypatch.setattr(checks, "no_fallback", lambda *a, **kw: (
        given.append(kw["dispatches"]), real(*a, **kw))[1])
    rc = run.main(["--workload", "replay.replayed", "--seed", "1",
                   "--seconds", "1"], root=str(tmp_path))
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and given == [whole + d for d in off]
    assert lines[-1]["correct"] is (not failed)
    assert lines[-1]["compared"]["fallback_checks_failed"] == {
        "value": len(failed), "limit": 0}
    assert [n["window"] for n in lines
            if n.get("phase") == "fallback"] == failed


@pytest.fixture(scope="module")
def recorded() -> dict:
    with open(os.path.join(DATA, "trace_modules_lost.json")) as f:
        return json.load(f)


def test_a_reduction_with_a_module_event_missing_is_seen(recorded):
    result = {"dispatches": recorded["dispatches"],
              "dispatch_modules": reindex.DISPATCH_MODULES}
    assert run.lost_module_events(result, recorded["sound"]) == {}
    lost = [run.lost_module_events(result, modules)
            for modules in recorded["lost"].values()]
    assert lost == [{"jit__glv_prepare_program": 1,
                     "jit__glv_dev_program": 1},
                    {"jit__glv_dev_program": 3}]
    # a trace without the program at all lost every event of it
    assert run.lost_module_events(result, {}) == dict.fromkeys(
        reindex.DISPATCH_MODULES, 0)
    # a driver that states no count (the miner's) is held to none
    assert run.lost_module_events({"attempted": 7}, {}) == {}
    # the names are the stage jits' own
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    assert reindex.DISPATCH_MODULES == (
        "jit_" + dev._glv_prepare_program.__name__,
        "jit_" + dev._glv_dev_program.__name__)


@pytest.mark.parametrize("traces, windows", [
    (["sound"], 1), (["lost", "sound"], 2), (["lost", "lost"], 2)])
def test_a_trace_that_lost_events_is_made_again_or_refused(
        recorded, monkeypatch, traces, windows):
    modules = {"sound": recorded["sound"],
               "lost": recorded["lost"]["reindex.pre_fork"]}
    made = []

    def traced_once(ctx, driver):
        kind = traces[len(made)]
        made.append(kind)
        result = {"dispatches": 4, "attempt": len(made),
                  "dispatch_modules": reindex.DISPATCH_MODULES}
        return result, {"busy_s": 0.17, "modules": modules[kind]}, \
            run.lost_module_events(result, modules[kind])

    monkeypatch.setattr(run, "_traced_once", traced_once)
    if traces[-1] == "lost":
        with pytest.raises(run.BenchError, match="lost device events twice"):
            run.traced_window(None, None)
    else:
        result, trace = run.traced_window(None, None)
        assert result["attempt"] == windows
        assert trace["modules"] == recorded["sound"]
    assert len(made) == windows
