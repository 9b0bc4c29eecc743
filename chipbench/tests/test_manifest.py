"""BENCHMARK.json against the files it names: every cell loads by name, and
what belongs to it exists. One case a cell, so that a cell a later PR adds
is held to the same without an edit here."""

import json
import os

import pytest
import run

with open(os.path.join(run.ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _traffic(cell: dict) -> dict:
    with open(os.path.join(run.HERE, "traffic",
                           cell["traffic"] + ".json")) as f:
        return json.load(f)


# cells whose window is a chain sized from a rate (the reindex drivers')
RATED = [w["name"] for w in MANIFEST["workloads"]
         if "buckets_per_window_second" in _traffic(w)]


@pytest.mark.parametrize("workload", CELLS)
def test_the_cell_loads_and_its_files_exist(workload):
    loaded = run.load_cell(workload)
    config, traffic = loaded["config"], loaded["traffic"]
    assert config["name"] == loaded["cell"]["config"]
    assert isinstance(config["flags"], list) and config["guarantees"]
    driver = run.load_module("drivers", config["driver"], loaded["bench"])
    for door in ("setup", "warm", "window", "check", "close"):
        assert callable(getattr(driver, door)), door
    reported = {m["name"] for m in loaded["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded["per_layer"]
    for metric in loaded["per_layer"]:
        assert metric["moves"] in reported
        assert callable(run.load_module("layer_metrics", metric["name"],
                                        loaded["bench"]).read)
    assert isinstance(traffic["what"], str)


@pytest.mark.parametrize("workload", RATED)
def test_a_reindex_window_says_where_its_rate_comes_from(workload):
    """buckets_per_window_second is 0.9 of what a sweep on the chip read,
    on a whole number of twentieths, and the traffic file keeps the sweep."""
    traffic = run.load_cell(workload)["traffic"]
    rate = traffic["buckets_per_window_second"]
    assert round(rate * 20, 9) % 1 == 0
    assert len(traffic["rate_from"]) > 80 and "sweep" in traffic["rate_from"]


@pytest.mark.parametrize("workload", RATED)
def test_a_reindex_run_says_how_many_windows_it_measures_and_why(workload):
    """``windows`` imports a run, their signatures over their seconds the
    rate; ``windows_from`` keeps the reading on the chip that chose it."""
    traffic = run.load_cell(workload)["traffic"]
    assert type(traffic["windows"]) is int and 1 <= traffic["windows"] <= 5
    assert "windows" not in traffic["rehearse"]  # a rehearsal runs them all
    said = traffic["windows_from"]
    assert len(said) > 80 and "PR 43" in said and "s_w" in said


def test_every_configuration_has_a_cell_and_every_reader_a_metric():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    readers = {leaf[:-3] for leaf in os.listdir(
        os.path.join(run.HERE, "layer_metrics")) if leaf.endswith(".py")}
    assert readers == {m["name"] for m in MANIFEST["per_layer"]}
