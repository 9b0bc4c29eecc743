"""The harness is driven by data: a cell is found from BENCHMARK.json by
name, a missing file or an unknown device is an error, and a new cell needs
only new files and one new entry each."""

import json
import os
import shutil

import pytest
import run

ROOT = run.ROOT


def test_both_cells_load():
    for name, driver in (("reindex.p2pkh_dense", "reindex"),
                         ("mine.diff1_solo", "mine")):
        loaded = run.load_cell(name)
        assert loaded["config"]["driver"] == driver
        assert {m["name"] for m in loaded["end_to_end"]} >= {"setup_s"}
        assert all(m["moves"] in {e["name"] for e in loaded["end_to_end"]}
                   for m in loaded["per_layer"])
        for metric in loaded["per_layer"]:
            assert callable(run.load_module("layer_metrics",
                                            metric["name"]).read)


def test_unknown_workload_is_an_error():
    with pytest.raises(run.BenchError, match="unknown workload"):
        run.load_cell("reindex.no_such_mix")


def _copy_manifest(tmp_path) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return manifest


def test_a_workload_naming_a_missing_file_is_an_error(tmp_path):
    manifest = _copy_manifest(tmp_path)
    manifest["workloads"].append({
        "name": "reindex.ghost", "config": "archival-reindex",
        "traffic": "ghost", "chips": 1, "why": "names no file"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(run.BenchError, match="traffic/ghost.json"):
        run.load_cell("reindex.ghost", str(tmp_path))


def test_an_unknown_device_kind_is_an_error():
    assert run.load_tables("TPU v5 lite")["peaks"]["vpu_u32_ops_per_s"] > 0
    with pytest.raises(run.BenchError, match="not in chipbench/peaks.json"):
        run.load_tables("TPU v9 imaginary")


THROWAWAY_DRIVER = '''
import checks
def setup(ctx): ctx.state["n"] = ctx.traffic["answers"]
def warm(ctx): ctx.state["setup"] = {}
def window(ctx):
    n = ctx.state["n"]
    return {"before": {"device": {"programs": {}}},
            "after": {"device": {"programs": {}}}, "window_s": 0.001,
            "attempted": n, "failed": 0, "values": {"answers_per_s": n}}
def check(ctx, result):
    return [checks.compared("wrong_answers", ctx.config["wrong"], 0)]
def close(ctx): pass
'''


def test_a_new_cell_needs_only_files_and_entries(tmp_path, capsys):
    """A throw-away configuration, traffic mix, driver and per-layer metric,
    added without touching a file that was there; then a whole (rehearsed)
    run of the new cell through run.main."""
    manifest = _copy_manifest(tmp_path)
    bench = tmp_path / "chipbench"
    (bench / "configs" / "throwaway.json").write_text(json.dumps(
        {"name": "throwaway", "driver": "throwaway", "wrong": 0}))
    (bench / "traffic" / "few.json").write_text(json.dumps({"answers": 7}))
    (bench / "drivers" / "throwaway.py").write_text(THROWAWAY_DRIVER)
    (bench / "layer_metrics" / "answers.seen.py").write_text(
        "def read(obs):\n    return obs['result']['attempted']\n")
    manifest["configs"].append({
        "name": "throwaway", "source": "none", "reduced": [], "why": "test",
        "file": "chipbench/configs/throwaway.json"})
    manifest["workloads"].append({
        "name": "throwaway.few", "config": "throwaway", "traffic": "few",
        "chips": 1, "why": "test"})
    manifest["end_to_end"].append({
        "name": "answers_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["throwaway.few"]})
    manifest["per_layer"].append({
        "name": "answers.seen", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "answers_per_s", "workloads": ["throwaway.few"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    loaded = run.load_cell("throwaway.few", str(tmp_path))
    assert [m["name"] for m in loaded["per_layer"]] == [
        "compile.listener_s", "answers.seen"]
    reader = run.load_module("layer_metrics", "answers.seen",
                             loaded["bench"])
    assert reader.read({"result": {"attempted": 7}}) == 7
    rc = run.main(["--workload", "throwaway.few", "--seed", "1",
                   "--seconds", "1", "--rehearse"], root=str(tmp_path))
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is True and last["attempted"] == 7
    assert "metrics" not in last and last["platform"] == "cpu"
