"""BENCHMARK.json against the limits the driver refuses a PR for before any
run (PR 27 was: a configuration's ``source`` of 201 characters), against
the files it names and against PERF.md (chipbench/tests holds the harness
to the same, but the driver's test command does not collect it): every cell
loads by name, every per-layer metric has a layer PERF.md section 3 names,
and every reader reads an observation recorded on the chip
(tests/unit/data/obs_*.json) and, without raising, the same observation cut
to the keys the parent commit's has."""

import copy
import json
import os
import re
import string
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "chipbench")]

import run  # noqa: E402  (chipbench/run.py)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PRINTABLE = set(string.printable) - set("\t\n\r\x0b\x0c")
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
DATA = os.path.join(os.path.dirname(__file__), "data")
# what a reader sees, recorded on the chip from the tree that last added a
# reader and cut to what a reader may look at, by the cell it was recorded
# in (every metric of a reindex cell lists reindex.pre_fork). Each file also
# holds "parent_keys": the keys of a snapshot and of last_import_stats at
# the parent of that tree, which the driver runs under this tree's readers.
RECORDED = {"reindex.pre_fork": "obs_reindex_pre_fork.json",
            "mine.diff1_solo": "obs_mine_diff1_solo.json",
            "reindex.schnorr_dense": "obs_reindex_schnorr_dense.json",
            "reindex.flush64": "obs_reindex_flush64.json"}


def recorded(metric: dict, as_the_parent_has_it: bool) -> dict:
    cells = metric.get("workloads", list(CELLS))
    with open(os.path.join(DATA, next(
            RECORDED[cell] for cell in RECORDED if cell in cells))) as f:
        obs = json.load(f)
    if as_the_parent_has_it:
        keys = obs["parent_keys"]
        for part in ("before", "after", "setup"):
            snap = {k: copy.deepcopy(v) for k, v in obs[part].items()
                    if k in keys["snapshot"]}
            if snap.get("import"):
                snap["import"] = {k: v for k, v in snap["import"].items()
                                  if k in keys["import"]}
            obs[part] = snap
    return obs


def layers_of_perf_md() -> set:
    """The first column of the table in PERF.md's section 3."""
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    section = re.search(r"^## 3\. .*?(?=^## 4\. )", text, re.M | re.S).group()
    rows = [line.split("|")[1].strip() for line in section.splitlines()
            if line.startswith("| ")]
    return {row for row in rows if row not in ("layer", "---")}


def line_ok(text) -> bool:
    """1 to 200 printable ASCII characters, on one line, no tab."""
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and set(text) <= PRINTABLE)


def test_line_ok_knows_the_limit():
    assert line_ok("x" * 200) and not line_ok("x" * 201)
    assert not line_ok("") and not line_ok("a\tb") and not line_ok("a\nb")
    assert not line_ok("café")


def test_the_manifest_is_small_and_its_names_are_unique():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for key in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[key]]
        assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert 1 <= len(MANIFEST["per_layer"]) <= 128
    assert all(line_ok(word) for word in MANIFEST["command"])
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    readers = {leaf[:-3] for leaf in os.listdir(os.path.join(
        ROOT, MANIFEST["paths"][0], "layer_metrics")) if leaf.endswith(".py")}
    assert readers == set(PER_LAYER), "a reader without its metric"


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_configuration_entry_and_its_file(name):
    entry = CONFIGS[name]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.fullmatch(name)
    assert line_ok(entry["source"]) and line_ok(entry["why"])
    assert len(entry["reduced"]) <= 16
    assert all(NAME.fullmatch(key) for key in entry["reduced"])
    assert any(entry["file"].startswith(p + "/") for p in MANIFEST["paths"])
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", entry["file"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    # the configuration as it is run carries the source it was taken from,
    # letter for letter, and says which of its sizes were cut
    assert config["source"] == entry["source"]
    assert config["name"] == name
    assert config["reduced"] == entry["reduced"]
    assert set(config.get("reductions", {})) == set(entry["reduced"])
    assert any(w["config"] == name for w in MANIFEST["workloads"])
    files = [c["file"] for c in MANIFEST["configs"]]
    assert files.count(entry["file"]) == 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_entry_and_its_files(name):
    cell = CELLS[name]
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.fullmatch(name) and NAME.fullmatch(cell["traffic"])
    assert cell["config"] in CONFIGS
    assert cell["chips"] in (1, 4)
    assert line_ok(cell["why"])
    bench = MANIFEST["paths"][0]
    assert os.path.isfile(os.path.join(
        ROOT, bench, "traffic", cell["traffic"] + ".json"))
    with open(os.path.join(ROOT, CONFIGS[cell["config"]]["file"])) as f:
        driver = json.load(f)["driver"]
    assert os.path.isfile(os.path.join(ROOT, bench, "drivers",
                                       driver + ".py"))
    # every cell reports setup_s, one more end-to-end metric and a
    # per-layer metric of its own
    felt = [m for m in MANIFEST["end_to_end"]
            if name in m.get("workloads", [name])]
    assert "setup_s" in {m["name"] for m in felt} and len(felt) >= 2
    assert any(name in m.get("workloads", [name])
               for m in MANIFEST["per_layer"])
    # and loads by name, as the harness loads it
    loaded = run.load_cell(name, ROOT)
    assert loaded["config"]["name"] == cell["config"]
    module = run.load_module("drivers", driver, loaded["bench"])
    for door in ("setup", "warm", "window", "check", "close"):
        assert callable(getattr(module, door)), door
    assert {m["name"] for m in loaded["end_to_end"]} == {
        m["name"] for m in felt}
    assert loaded["per_layer"]


def test_at_most_half_the_cells_ask_for_four_chips():
    four = sum(1 for w in MANIFEST["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MANIFEST["workloads"]) // 2)


@pytest.mark.parametrize("name", sorted(END_TO_END))
def test_end_to_end_metric(name):
    metric = END_TO_END[name]
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "bound", "source"}
    assert NAME.fullmatch(name) and UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0 < metric["bound"] < 1
    assert set(metric.get("workloads", [])) <= set(CELLS)


@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_per_layer_metric_moves_an_end_to_end_metric_its_cells_report(name):
    metric = PER_LAYER[name]
    assert set(metric) - {"workloads"} == {
        "name", "unit", "better", "source", "layer", "moves"}
    assert NAME.fullmatch(name) and UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert line_ok(metric["layer"])
    moved = END_TO_END[metric["moves"]]  # KeyError: moves nothing felt
    for cell in metric.get("workloads", []):
        assert cell in CELLS
        assert cell in moved.get("workloads", [cell])
    if "roofline" in name or "mfu" in name:
        assert metric["unit"] == "%"
    assert os.path.isfile(os.path.join(
        ROOT, MANIFEST["paths"][0], "layer_metrics", name + ".py"))
    assert metric["layer"] in layers_of_perf_md(), (
        f"PERF.md section 3 has no layer {metric['layer']!r}")
    for cell in metric.get("workloads", CELLS):
        assert name in {m["name"] for m in
                        run.load_cell(cell, ROOT)["per_layer"]}


@pytest.mark.parametrize("shape", ["recorded", "parent"])
@pytest.mark.parametrize("name", sorted(PER_LAYER))
def test_the_reader_on_a_recorded_observation(name, shape):
    """Every reader reads a number, a share within 0..100, from the
    observation as this tree gives it. The driver also runs the parent
    commit under this tree's readers: cut to the parent's keys no reader
    raises, and one that still reads there reads what it read before (what
    a PR adds to the program moves no accepted metric); the others, those
    the PR added, find nothing."""
    metric = PER_LAYER[name]
    reader = run.load_module("layer_metrics", name)
    value = reader.read(recorded(metric, False))
    if shape == "parent":
        assert reader.read(recorded(metric, True)) in (None, value)
        return
    assert isinstance(value, (int, float)) and value == value, value
    if metric["unit"] == "%":
        assert 0.0 <= value <= 100.0


@pytest.mark.parametrize("modules, kernel_ms, prepare_ms", [
    # the two stages a bucket since PR 39: each reader its own module
    ({"jit__glv_prepare_program": {"seconds": 0.0135, "count": 4},
      "jit__glv_dev_program": {"seconds": 0.156, "count": 4}}, 39.0, 3.375),
    # one program a bucket (the parents of PR 39): no prepare to read
    ({"jit__glv_dev_program": {"seconds": 0.3256, "count": 4}}, 81.4, None),
    ({}, None, None),
])
def test_the_glv_readers_take_one_stage_each(modules, kernel_ms, prepare_ms):
    """glv.kernel_ms + glv.prepare_ms is a bucket's device time; the
    module names are the stage jits' own (ops/secp256k1)."""
    import importlib.util

    from bitcoincashplus_tpu.ops import secp256k1 as dev

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "reader", os.path.join(ROOT, MANIFEST["paths"][0],
                                   "layer_metrics", name + ".py"))
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    kernel, prepare = reader("glv.kernel_ms"), reader("glv.prepare_ms")
    assert kernel.MODULE == "jit_" + dev._glv_dev_program.__name__
    assert prepare.MODULE == "jit_" + dev._glv_prepare_program.__name__
    for obs in ({"trace": {"modules": modules}}, {"trace": None}):
        want = (kernel_ms, prepare_ms) if obs["trace"] else (None, None)
        assert kernel.read(obs) == pytest.approx(want[0])
        assert prepare.read(obs) == pytest.approx(want[1])


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader", os.path.join(ROOT, MANIFEST["paths"][0], "layer_metrics",
                               name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("modules, lanes, kernel_ms, roofline", [
    # a Schnorr bucket's two stages: the roofline's divisor is both
    ({"jit__glv_prepare_program": {"seconds": 0.0136, "count": 4},
      "jit__glv_schnorr_program": {"seconds": 0.1864, "count": 4}},
     4 * 8190, 46.6, 100 * 4 * 8190 * 956918 / 6.17e12 / 0.2),
    # the prepare stage also served ECDSA buckets: its seconds an event,
    # for as many events as the Schnorr stage has
    ({"jit__glv_prepare_program": {"seconds": 0.0272, "count": 8},
      "jit__glv_schnorr_program": {"seconds": 0.1864, "count": 4},
      "jit__glv_dev_program": {"seconds": 0.156, "count": 4}},
     4 * 8190, 46.6, 100 * 4 * 8190 * 956918 / 6.17e12 / 0.2),
    # a program without the Schnorr stage (every parent of PR 44)
    ({"jit__glv_prepare_program": {"seconds": 0.0135, "count": 4},
      "jit__glv_dev_program": {"seconds": 0.156, "count": 4}},
     0, None, None),
    ({}, 0, None, None),
])
def test_the_schnorr_readers_take_both_stages(modules, lanes, kernel_ms,
                                              roofline):
    """schnorr.kernel_ms reads the second stage alone (a bucket's device
    time is schnorr.kernel_ms + glv.prepare_ms); schnorr_roofline divides
    the lanes' operations (chipbench/opcounts_schnorr.json, which it loads
    itself) by the seconds of both stages, so that numerator and divisor
    cover the same work; the module names are the stage jits' own."""
    from bitcoincashplus_tpu.ops import secp256k1 as dev

    kernel, share = _reader("schnorr.kernel_ms"), _reader("schnorr_roofline")
    assert kernel.MODULE == share.MODULE == (
        "jit_" + dev._glv_schnorr_program.__name__)
    assert share.PREPARE == "jit_" + dev._glv_prepare_program.__name__
    assert share.OPS_PER_LANE == 956918
    obs = {"trace": {"modules": modules},
           "peaks": {"vpu_u32_ops_per_s": 6.17e12},
           "before": {"batch": {"schnorr_lanes": 16}},
           "after": {"batch": {"schnorr_lanes": 16 + lanes}}}
    assert kernel.read(obs) == pytest.approx(kernel_ms)
    assert share.read(obs) == pytest.approx(roofline)
    for less in (dict(obs, trace=None),
                 dict(obs, before={"batch": {}}, after={"batch": {}})):
        assert share.read(less) is None


@pytest.mark.parametrize("stats, want", [
    ({"sigscan_thread_s": 2.0, "schnorr_challenge_s": 0.05}, 2.5),
    ({"sigscan_thread_s": 2.0, "schnorr_challenge_s": 0.0}, 0.0),
    ({"sigscan_thread_s": 2.0}, None),       # a program before PR 44
    ({"sigscan_thread_s": 0.0, "schnorr_challenge_s": 0.0}, None),
    (None, None),                            # the import aborted
], ids=["some", "none", "no-stopwatch", "no-scan", "no-import"])
def test_schnorr_challenge_share_reads_the_scans_stopwatches(stats, want):
    reader = _reader("sigscan.schnorr_challenge_share")
    assert reader.read({"after": {"import": stats}}) == (
        None if want is None else pytest.approx(want))


# -- reindex.flush64 (PR 46): the shipped flush cadence ----------------------

CADENCE = ("import.flush_share", "store.us_per_row",
           "store.rows_lock_wait_share", "import.store_read_share",
           "import.drain_share")


def test_the_default_deployment_states_what_it_runs_and_holds():
    """archival-reindex-default: the flags written out, the four
    guarantees, every cut and every assumption with its reason, the files
    the cell needs beside the harness's."""
    entry = CONFIGS["archival-reindex-default"]
    assert len(entry["source"]) <= 200  # PR 27 was refused for 201
    assert entry["reduced"] == ["chain_length", "keys", "coin_set"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["flags"] == ["-regtest", "-tpu=1", "-reindex", "-listen=0",
                               "-flushinterval=64", "-dbcache=300"]
    assert [flag for flag in config["rehearse_flags"] if "tpu" not in flag] \
        == [flag for flag in config["flags"] if "tpu" not in flag]
    assert set(config["guarantees"]) == {"chain", "signatures", "sample",
                                         "durability"}
    assert {"spent_output_age", "intervals", "aligned_flushes"} <= set(
        config["assumed"])
    age = config["assumed"]["spent_output_age"]
    for words in ("worst case", "not a measured share", "2019/611",
                  "CCoinsViewCache::Flush", "not for a share"):
        assert words in age, words
    cell = CELLS["reindex.flush64"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "archival-reindex-default", "flush64", 1)
    bench = os.path.join(ROOT, MANIFEST["paths"][0])
    for leaf in ("gen/agedchain.py", "reference_flush64.py",
                 "drivers/reindex_flush64.py", "traffic/flush64.json"):
        assert os.path.isfile(os.path.join(bench, leaf)), leaf
    with open(os.path.join(bench, "traffic", "flush64.json")) as f:
        traffic = json.load(f)
    assert traffic["flush_interval"] == 64 and traffic["windows"] == 1
    # the cell joins the control's metrics as they stand
    joined = {m["name"] for m in run.load_cell("reindex.flush64",
                                               ROOT)["per_layer"]}
    control = {m["name"] for m in run.load_cell("reindex.p2pkh_dense",
                                                ROOT)["per_layer"]}
    assert joined == control | set(CADENCE)


@pytest.mark.parametrize("name", CADENCE)
def test_the_cadences_metrics_list_the_one_cell_that_runs_it(name):
    metric = PER_LAYER[name]
    assert metric["workloads"] == ["reindex.flush64"]
    assert (metric["moves"], metric["source"], metric["better"]) == (
        "reindex_sigs_per_s", "program_span", "lower")
    assert metric["layer"] in ("import engine", "coins store")


def test_the_cadences_shares_are_parts_of_one_wall():
    """import.flush, import.drain and import.store_read are spans of the
    importing thread that do not nest in each other: their shares of the
    recorded window add up to no more than it."""
    obs = recorded(PER_LAYER["import.flush_share"], False)
    shares = [_reader(name).read(obs) for name in (
        "import.flush_share", "import.store_read_share",
        "import.drain_share")]
    assert all(share is not None for share in shares)
    assert sum(shares) <= 100.0
    stats = obs["after"]["import"]
    assert stats["flush_rows"] == stats["flush_puts"] + stats["flush_deletes"]
    assert stats["tail_dispatches"] == 0
    assert len(stats["flush_log"]) == stats["flushes"]


def _cadence_obs(stats=None, spans=(None, None)) -> dict:
    return {"before": {"spans": spans[0]} if spans[0] is not None else {},
            "after": {"import": stats,
                      **({"spans": spans[1]} if spans[1] is not None
                         else {})}}


@pytest.mark.parametrize("name, obs, want", [
    ("import.flush_share", _cadence_obs(
        {"wall_s": 50.0, "flush_rows": 10,
         "phases": {"import.flush": {"s": 30.0, "self_s": 1.0, "n": 6}}}),
     60.0),
    ("import.drain_share", _cadence_obs(
        {"wall_s": 50.0, "flush_rows": 10,
         "phases": {"import.drain": {"s": 0.5, "self_s": 0.1, "n": 6}}}),
     1.0),
    ("import.store_read_share", _cadence_obs(
        {"wall_s": 50.0, "store_read_rows": 7,
         "phases": {"import.store_read": {"s": 10.0, "self_s": 10.0,
                                          "n": 99}}}), 20.0),
    ("store.us_per_row", _cadence_obs(
        {"wall_s": 50.0, "flush_rows": 1_000_000,
         "phases": {"store.commit": {"s": 25.0, "self_s": 2.0, "n": 8}}}),
     25.0),
    ("store.rows_lock_wait_share", _cadence_obs(
        {}, ({"store.shard_write": {"s": 1.0}, "store.rows_lock_wait":
              {"s": 0.5}},
             {"store.shard_write": {"s": 21.0}, "store.rows_lock_wait":
              {"s": 8.5}})), 40.0),
    # a program without the cadence's counters (every parent of PR 46):
    # the spans are there since PR 40, the metrics are not its to report
    ("import.flush_share", _cadence_obs(
        {"wall_s": 50.0,
         "phases": {"import.flush": {"s": 30.0, "self_s": 1.0, "n": 6}}}),
     None),
    ("import.drain_share", _cadence_obs(
        {"wall_s": 50.0,
         "phases": {"import.drain": {"s": 0.5, "self_s": 0.1, "n": 6}}}),
     None),
    ("import.store_read_share", _cadence_obs({"wall_s": 50.0, "phases": {}}),
     None),
    ("store.us_per_row", _cadence_obs(
        {"wall_s": 50.0,
         "phases": {"store.commit": {"s": 25.0, "self_s": 2.0, "n": 8}}}),
     None),
    ("store.us_per_row", _cadence_obs(None), None),  # the import aborted
    ("store.rows_lock_wait_share", _cadence_obs({}), None),
    ("store.rows_lock_wait_share", _cadence_obs(
        {}, ({"store.shard_write": {"s": 1.0}},
             {"store.shard_write": {"s": 1.0}})), None),  # no commit
], ids=lambda v: v if isinstance(v, str) else None)
def test_the_cadences_readers(name, obs, want):
    value = _reader(name).read(obs)
    assert value == (None if want is None else pytest.approx(want))
