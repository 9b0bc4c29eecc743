"""Tier-1 telemetry smoke test (ISSUE 6 satellite): boot a real in-process
Node with -telemetry=trace, import a small corpus through the pipelined
Python engine, and validate the dumped trace's JSON schema plus the
/metrics + getmetrics subsystem coverage — the whole observability
surface exercised end to end, CPU backend, no sockets."""

from __future__ import annotations

import json
import sys

import pytest

from bitcoincashplus_tpu.node.config import Config, ConfigError
from bitcoincashplus_tpu.node.node import Node
from bitcoincashplus_tpu.util import telemetry as tm

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

pytestmark = pytest.mark.telemetry

SPK = bytes.fromhex("76a914") + b"\x22" * 20 + bytes.fromhex("88ac")


def _mk_node(path, **args):
    cfg = Config()
    cfg.args["datadir"] = [str(path)]
    cfg.args["regtest"] = ["1"]
    for k, v in args.items():
        cfg.args[k] = [str(v)]
    return Node(config=cfg)


@pytest.fixture
def restore_mode():
    yield
    tm.reset()


def test_node_trace_smoke(tmp_path, monkeypatch, restore_mode):
    datadir = tmp_path / "node"
    tracefile = tmp_path / "trace.json"

    # phase 1: mine a small chain (telemetry default: counters)
    node = _mk_node(datadir)
    node.generate_to_script(SPK, 6)
    node.close()

    # phase 2: -reindex through the PIPELINED PYTHON engine with
    # -telemetry=trace and a -tracefile sink (native fast-import pinned
    # off so the settle-horizon spans are the ones under test)
    monkeypatch.setenv("BCP_NO_NATIVE_IMPORT", "1")
    tm.TRACER.clear()
    node = _mk_node(datadir, reindex=1, pipelinedepth=4,
                    telemetry="trace", tracefile=str(tracefile))
    assert node.telemetry_mode == "trace"
    try:
        assert node.chainstate.tip().height == 6

        # gettpuinfo stays a superset of its PR-5 shape on a REAL node
        from bitcoincashplus_tpu.rpc.control import (dumptrace, getmetrics,
                                                     gettpuinfo)

        info = gettpuinfo(node, [])
        for key in ("backend", "batch", "breakers", "sigcache", "pipeline",
                    "telemetry"):
            assert key in info
        assert info["telemetry"]["mode"] == "trace"
        assert info["telemetry"]["spans"]["recorded"] > 0

        # getmetrics + /metrics cover every subsystem the issue names
        # (net via the collector a connman would register — simulated
        # here so the smoke test stays socket-free)
        tm.register_collector("net", lambda: [{
            "name": "bcp_net_peers", "type": "gauge", "help": "",
            "samples": [({}, 0)]}])
        snap = getmetrics(node, [])
        from bitcoincashplus_tpu.rpc.rest import handle_metrics

        _st, _ct, body = handle_metrics(node)
        text = body.decode()
        for prefix in ("bcp_dispatch_", "bcp_ecdsa_", "bcp_pipeline_",
                       "bcp_sigcache_", "bcp_mempool_", "bcp_net_",
                       # device-lane families (util/devicewatch): the
                       # compile sentinel, transfer totals, and the
                       # memory collector must be visible after a
                       # regtest import — ISSUE 8 acceptance surface
                       "bcp_xla_compile_", "bcp_device_transfer_bytes",
                       "bcp_device_memory_", "bcp_watchdog_"):
            assert any(n.startswith(prefix) for n in snap), prefix
            assert prefix in text, prefix
        # the pipelined import actually recorded per-block legs
        scan = snap["bcp_pipeline_scan_seconds"]["values"][0]
        assert scan["count"] >= 6
        assert {"p50", "p90", "p99"} <= set(scan)

        # dumptrace mid-flight works too (independent of -tracefile)
        mid = dumptrace(node, [str(tmp_path / "mid.json")])
        assert mid["events"] > 0 and mid["mode"] == "trace"
    finally:
        node.close()
        tm.REGISTRY.unregister_collector("net")  # the simulated one

    # phase 3: the -tracefile shutdown dump, schema-validated
    assert tracefile.exists()
    trace = json.loads(tracefile.read_text())
    events = trace["traceEvents"]
    assert isinstance(events, list) and events
    names = set()
    for ev in events:
        assert isinstance(ev["name"], str)
        assert ev["ph"] in ("X", "i")
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert isinstance(ev["args"], dict)
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert isinstance(ev["args"]["corr"], int)
            assert isinstance(ev["args"]["span_id"], int)
        names.add(ev["name"])
    # the pipeline's span vocabulary made it into the dump
    assert {"block.scan", "block.settle", "block.commit"} <= names

    # and the offline summarizer measures a per-block overlap from it
    from tools import trace_view

    blocks = trace_view.block_overlap(events)
    assert len(blocks) >= 6
    for b in blocks:
        assert 0.0 <= b["overlap"] <= 1.0
    report = trace_view.summarize(events)
    assert "aggregate overlap fraction:" in report
    assert "top 10 slowest settles" in report


def test_unknown_telemetry_level_rejected_at_startup(tmp_path,
                                                     restore_mode):
    with pytest.raises(ConfigError, match="telemetry"):
        _mk_node(tmp_path / "bad", telemetry="verbose")


def test_tracefile_implies_trace_mode(tmp_path, restore_mode):
    node = _mk_node(tmp_path / "imp", tracefile=str(tmp_path / "t.json"))
    try:
        assert node.telemetry_mode == "trace"
    finally:
        node.close()
    assert (tmp_path / "t.json").exists()


def test_tracefile_with_lower_level_rejected(tmp_path, restore_mode):
    """-telemetry=counters -tracefile=x would silently write an empty
    dump — the contradiction is rejected at startup instead."""
    with pytest.raises(ConfigError, match="tracefile"):
        _mk_node(tmp_path / "c", telemetry="counters",
                 tracefile=str(tmp_path / "t.json"))


def test_close_unregisters_node_collectors(tmp_path, restore_mode):
    """A closed node's bound-method collectors must not keep its object
    graph alive in the process-global registry."""
    node = _mk_node(tmp_path / "u")
    reg = tm.REGISTRY
    assert {"sigcache", "pipeline", "mempool"} <= set(reg._collectors)
    node.close()
    assert not ({"sigcache", "pipeline", "mempool"}
                & set(reg._collectors))


def test_no_duplicate_metric_families_in_exposition(restore_mode):
    """The ecdsa collector must not re-emit names owned by native
    families (bcp_ecdsa_in_flight was once emitted as BOTH a gauge and a
    collected counter — an invalid duplicate-TYPE exposition)."""
    from bitcoincashplus_tpu.ops import ecdsa_batch

    ecdsa_batch.STATS.in_flight = 1
    try:
        ecdsa_batch._IN_FLIGHT_G.set(1)
        text = tm.REGISTRY.prometheus_text()
    finally:
        ecdsa_batch.STATS.in_flight = 0
    type_lines = [ln for ln in text.splitlines() if ln.startswith("# TYPE ")]
    names = [ln.split()[2] for ln in type_lines]
    assert len(names) == len(set(names)), (
        f"duplicate families: {sorted(n for n in names if names.count(n) > 1)}")


def test_logjson_stamps_correlation_ids(tmp_path, restore_mode):
    """-logjson: records are JSON objects; one emitted inside an active
    span carries its correlation id (log <-> trace cross-reference)."""
    from bitcoincashplus_tpu.util.log import log_init, log_printf

    node = _mk_node(tmp_path / "lj", logjson=1, telemetry="trace")
    try:
        logfile = tmp_path / "lj" / "regtest" / "debug.log"
        with tm.span("logtest") as sp:
            log_printf("correlated hello")
        lines = [json.loads(ln) for ln in
                 logfile.read_text().splitlines() if ln.strip()]
        hits = [rec for rec in lines if rec.get("msg") == "correlated hello"]
        assert hits and hits[0]["corr"] == sp.corr
        assert all("ts" in rec and "msg" in rec for rec in lines)
    finally:
        node.close()
        # node.close() logged through the json logger; restore the plain
        # text logger for whatever runs next in this process
        log_init()
