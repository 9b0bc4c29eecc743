"""One span primitive, three modes (util/telemetry, PR 40), and what stands on
it: the native import's phase table, whose self times add up to its wall;
the RPC server's wait for cs_main apart from its handlers; the node's
start-up by stage; ``tools/trace_view.py --xplane``, which puts the device's
idle time under the spans' host events.

Marker: ``telemetry`` (the mode and the totals are process-global).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import types

import pytest

import test_native_connect as native_connect
from bitcoincashplus_tpu.consensus.tx import COutPoint
from bitcoincashplus_tpu.util import telemetry as tm

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

from tools import trace_view  # noqa: E402

pytestmark = pytest.mark.telemetry

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture
def mode():
    """Set the process-global telemetry mode for one test; totals and ring
    start empty and the env-derived default comes back afterwards."""
    def set_(name):
        tm.reset()
        tm.set_mode(name)
        return tm

    yield set_
    tm.reset()


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation: keeps the order of
    entries and exits."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    _Annotation.log = []
    monkeypatch.setattr(tm, "_ANNOTATION", _Annotation)
    return _Annotation.log


def _nested():
    with tm.span("outer", k=1) as outer:
        with tm.span("inner"):
            time.sleep(0.002)
        with tm.span("inner"):
            time.sleep(0.002)
        time.sleep(0.001)
    return outer


# ---------------------------------------------------------------------------
# (a) the modes
# ---------------------------------------------------------------------------

def test_off_is_the_shared_null_span_and_counts_nothing(mode, annotations):
    mode("off")
    outer = _nested()
    assert outer is tm._NULL_SPAN and tm.span("x") is tm._NULL_SPAN
    assert outer.seconds == 0.0 and outer.totals is None
    assert tm.span_totals() == {}
    assert tm.TRACER.events() == [] and annotations == []


def test_counters_fill_the_totals_with_self_times(mode, annotations):
    mode("counters")
    outer = _nested()
    totals = tm.span_totals()
    assert set(totals) == {"outer", "inner"}
    assert totals["inner"]["n"] == 2 and totals["outer"]["n"] == 1
    assert totals["inner"]["self_s"] == totals["inner"]["s"] >= 0.004
    assert totals["outer"]["s"] == outer.seconds
    # self time is the duration less what the children covered
    assert totals["outer"]["self_s"] == pytest.approx(
        totals["outer"]["s"] - totals["inner"]["s"], abs=1e-9)
    assert 0.001 <= totals["outer"]["self_s"] < totals["outer"]["s"]
    # nothing goes to the ring, and no correlation context is handed out
    assert tm.TRACER.events() == []
    with tm.span("x"):
        assert tm.trace_context() is None
    # the profiler's annotations, in nesting order
    assert annotations[:6] == [
        ("enter", "bcp.outer"), ("enter", "bcp.inner"),
        ("exit", "bcp.inner"), ("enter", "bcp.inner"),
        ("exit", "bcp.inner"), ("exit", "bcp.outer")]


def test_a_collecting_span_keeps_its_own_subtree(mode):
    mode("counters")
    with tm.span("elsewhere"):
        pass
    with tm.span("root", collect=True) as root:
        with tm.span("child"):
            with tm.span("leaf"):
                time.sleep(0.001)
    assert set(root.totals) == {"root", "child", "leaf"}
    assert sum(r["self_s"] for r in root.totals.values()) == pytest.approx(
        root.seconds, abs=1e-9)
    assert set(tm.span_totals()) == {"elsewhere", "root", "child", "leaf"}


def test_a_span_on_another_thread_is_nobodys_child(mode):
    mode("counters")
    with tm.span("main") as main:
        t = threading.Thread(target=lambda: _nested())
        t.start()
        t.join()
    totals = tm.span_totals()
    assert totals["main"]["self_s"] == main.seconds  # no child on its thread
    assert totals["outer"]["n"] == 1


def test_trace_also_records_the_event_with_its_parent(mode, annotations):
    mode("trace")
    _nested()
    events = tm.TRACER.events()
    assert [ev["name"] for ev in events] == ["inner", "inner", "outer"]
    outer = events[-1]
    assert all(ev["args"]["parent"] == outer["args"]["span_id"]
               for ev in events[:2])
    assert outer["args"]["k"] == 1
    assert tm.span_totals()["inner"]["n"] == 2
    assert ("enter", "bcp.outer") in annotations
    other = tm.TRACER.chrome_trace()["otherData"]
    assert abs(other["epoch_unix_ns"] - time.time_ns()) < 3600 * 10**9


def test_a_span_never_imports_jax():
    """In a process where nothing imported jax, spans find no annotation
    class and leave jax alone."""
    code = (
        "import sys\n"
        "from bitcoincashplus_tpu.util import telemetry as tm\n"
        "tm.set_mode('counters')\n"
        "with tm.span('a'):\n"
        "    with tm.span('b'):\n"
        "        pass\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "assert tm._ANNOTATION is None\n"
        "assert tm.span_totals()['a']['n'] == 1\n")
    root = __file__.rsplit("/tests/", 1)[0]
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_the_totals_are_on_the_metrics_endpoint(mode):
    """What bcp_dispatch_phase_seconds was for (removed by PR 40): the
    host's legs of a dispatch, by name, on /metrics."""
    mode("counters")
    with tm.span("ecdsa.pack"):
        pass
    text = tm.REGISTRY.prometheus_text()
    assert "# TYPE bcp_span_seconds_total counter" in text
    assert 'bcp_span_count_total{span="ecdsa.pack"} 1' in text
    assert 'bcp_span_self_seconds_total{span="ecdsa.pack"}' in text
    assert "bcp_dispatch_phase_seconds" not in text


# ---------------------------------------------------------------------------
# (b) the native import: every second under a span
# ---------------------------------------------------------------------------

OLD_KEYS = ("blocks", "bytes", "native_connect_s", "sigscan_s", "verify_s",
            "fallback_s", "flush_s", "slow_path_blocks", "fallback_inputs",
            "template_inputs", "interp_inputs", "fast_inputs",
            "prefork_blocks", "sigscan_thread_s", "legacy_digests",
            "legacy_sighash_bytes", "legacy_sighash_s", "wall_s",
            "multisig_groups", "multisig_lanes", "multisig_group_confirms",
            "inline_legacy_sigs")


@pytest.fixture(scope="module")
def imported(tmp_path_factory):
    """A regtest chain of 102 coinbase blocks and 30 blocks of 17 signed
    inputs each through Node(-reindex), under -telemetry=counters: (stats,
    gettpuinfo, flushes the coins store saw)."""
    from bitcoincashplus_tpu.node.node import _IMPORT_LEGS
    from bitcoincashplus_tpu.rpc.control import gettpuinfo
    from bitcoincashplus_tpu.store import sharded

    tm.reset()
    tm.set_mode("counters")
    chain = native_connect._DiskChain(tmp_path_factory.mktemp("spans"))
    fan = None
    for b in range(30):
        # one coinbase matures a block: it fans out to 16 outputs, which
        # the next block spends in one transaction of 16 inputs
        cb = chain.coinbases[b]
        txs = [native_connect._spend([COutPoint(cb.txid, 0)],
                                     [cb.vout[0].value], n_out=16)]
        if fan is not None:
            txs.append(native_connect._spend(
                [COutPoint(fan.txid, i) for i in range(16)],
                [out.value for out in fan.vout]))
        fan = txs[0]
        chain.push(tuple(txs))
    commits = []
    commit = sharded.ShardedCoinsDB._commit_sharded

    def counting(self, entries, best_block):
        commits.append(best_block)
        return commit(self, entries, best_block)

    sharded.ShardedCoinsDB._commit_sharded = counting
    try:
        node = chain.reindex()
    finally:
        sharded.ShardedCoinsDB._commit_sharded = commit
    try:
        yield types.SimpleNamespace(
            stats=node.last_import_stats, info=gettpuinfo(node, []),
            commits=len(commits), legs=_IMPORT_LEGS, node=node)
    finally:
        node.close()
        tm.reset()


def test_import_self_times_add_up_to_its_wall(imported):
    stats = imported.stats
    phases = stats["phases"]
    assert stats["blocks"] == 132 and stats["slow_path_blocks"] == 0
    # the identity, by the spans' own clock: every span's self time is its
    # duration less its children's, so over the root's subtree they add up
    # to the root's duration, whatever the host was doing meanwhile
    root_s = phases["import"]["s"]
    assert sum(row["self_s"] for row in phases.values()) == pytest.approx(
        root_s, abs=1e-9 * sum(row["n"] for row in phases.values()))
    # wall_s is a clock of its own around the root span (it reads under
    # -telemetry=off too): it encloses the span, by two clock reads
    assert stats["wall_s"] >= root_s
    # every phase of the import is inside a child span of its own ...
    assert {"import.read", "import.header", "import.connect",
            "import.store_read", "import.lanes", "import.index",
            "import.pack", "import.enqueue", "import.settle_wait",
            "import.settle", "import.drain", "import.flush",
            "import.close"} <= set(phases)
    # ... so what no child covers, the loop's glue between ~700 spans, is
    # the import's own, and it is little: 1.8-2.8% here on an idle sandbox,
    # 2.5-4.9% beside sixteen busy loops on its eight cores (the 5% this
    # line held until PR 45 failed there); 0.4-0.9% on the chip, PERF.md
    # section 5
    assert phases["import"]["self_s"] < 0.15 * root_s
    assert phases["import.connect"]["n"] == stats["blocks"]
    assert phases["import.index"]["n"] == stats["blocks"]
    # one read span a record and one a block file
    assert phases["import.read"]["n"] >= stats["blocks"] + 1


@pytest.mark.parametrize("key", ["native_connect_s", "verify_s",
                                 "fallback_s", "flush_s"])
def test_import_keeps_its_keys_as_sums_of_spans(imported, key):
    stats = imported.stats
    for old in OLD_KEYS:
        assert old in stats, f"last_import_stats lost {old!r}"
    summed = sum(stats["phases"][name]["s"] for name in imported.legs[key]
                 if name in stats["phases"])
    assert stats[key] == pytest.approx(summed, abs=1e-6)
    bench = imported.info["connectblock"]
    assert {"connect_ms", "verify_ms", "flush_ms", "blocks"} <= set(bench)


def test_import_counts_the_dispatch_queue(imported):
    stats = imported.stats
    assert stats["dispatches"] >= 1
    assert sum(stats["inflight_at_enqueue"]) == stats["dispatches"]
    assert len(stats["inflight_at_enqueue"]) == 4
    assert stats["phases"]["import.enqueue"]["n"] == stats["dispatches"]
    assert stats["phases"]["import.settle_wait"]["n"] == stats["dispatches"]
    assert stats["tail_dispatches"] <= stats["dispatches"]
    assert stats["tail_lanes"] <= 2046 * stats["tail_dispatches"]
    assert 0.0 <= stats["queue_empty_s"] <= stats["wall_s"]
    # the flushes made: 133 records (genesis first) at the default interval
    # of 64 flush after the 64th and the 128th, and once at the end; each
    # is one commit of the coins store, and the node's own flushes before
    # and after the import make more
    assert stats["flushes"] == stats["phases"]["import.flush"]["n"] == 3
    assert imported.commits >= stats["phases"]["store.commit"]["n"] >= 3


def test_import_under_off_keeps_its_wall_and_counters_and_says_so(tmp_path):
    """-telemetry=off: spans are null, so the legs are not taken (0, and
    span_times says why), the dispatch queue is not polled; the wall and
    the counters are still the import's."""
    from bitcoincashplus_tpu.rpc.control import gettpuinfo

    tm.reset()
    tm.set_mode("off")
    chain = native_connect._DiskChain(tmp_path)
    cb = chain.coinbases[0]
    chain.push((native_connect._spend([COutPoint(cb.txid, 0)],
                                      [cb.vout[0].value], n_out=2),))
    try:
        node = chain.reindex()
        try:
            stats = node.last_import_stats
            info = gettpuinfo(node, [])
        finally:
            node.close()
    finally:
        tm.reset()
    assert stats["blocks"] == 103 and stats["wall_s"] > 0.0
    assert stats["phases"] == {}
    assert [stats[key] for key in ("native_connect_s", "verify_s",
                                   "fallback_s", "flush_s")] == [0.0] * 4
    assert stats["dispatches"] >= 1 and stats["flushes"] == 2
    assert sum(stats["inflight_at_enqueue"]) == 0
    assert stats["queue_empty_s"] == 0.0
    assert info["telemetry"]["mode"] == "off"
    assert info["telemetry"]["span_times"] is False
    assert tm.span_totals() == {}


def test_the_store_says_where_a_commit_went(imported):
    spans = imported.info["store"]["last_flush"]["spans"]
    assert {"store.commit", "store.old_reads", "store.muhash",
            "store.journal", "store.shard_write",
            "store.manifest"} <= set(spans)
    assert "store.rows_lock_wait" not in spans
    assert spans["store.shard_write"]["n"] == 4  # one a shard
    assert spans["store.old_reads"]["n"] == 4  # one a shard, served or not
    assert spans["store.commit"]["s"] >= spans["store.manifest"]["s"]


@pytest.mark.parametrize("name", ["off", "counters", "trace"])
def test_a_shard_write_says_its_rows_and_statements(tmp_path, mode, name):
    """The ring's store.shard_write events carry what the shard wrote;
    the other modes keep no event and the commit is the same commit."""
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB

    mode(name)
    db = ShardedCoinsDB(str(tmp_path), n_shards=2)
    db.batch_write_serialized(
        [(os.urandom(36), b"\x02\x05\x01\x51") for _ in range(40)],
        b"\x01" * 32)
    stats = db.stats()
    db.close()
    assert stats["write_statements"] == 2 and stats["rows_put"] == 40
    writes = [ev["args"] for ev in tm.TRACER.events()
              if ev["name"] == "store.shard_write"]
    assert len(writes) == (2 if name == "trace" else 0)
    assert sorted(w["shard"] for w in writes) == list(range(len(writes)))
    assert all(w["statements"] == 1 for w in writes)
    assert sum(w["rows"] for w in writes) == (40 + 2 * 3 if writes else 0)


@pytest.mark.parametrize("name", ["off", "counters", "trace"])
def test_an_old_read_says_what_it_remembered(tmp_path, mode, name):
    """store.old_reads opens once a shard a commit, rows served or not, and
    the ring's events split its keys into those the store remembered
    serving and those it asked sqlite for; the other modes keep no event
    and the commit takes the same old values."""
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB

    mode(name)
    rows = [(os.urandom(36), b"\x02\x05\x01\x51") for _ in range(40)]
    db = ShardedCoinsDB(str(tmp_path), n_shards=2)
    db.batch_write_serialized(rows, b"\x01" * 32)
    assert len(db.get_serialized_many([k for k, _ in rows[:30]])) == 30
    db.batch_write_serialized([(k, None) for k, _ in rows], b"\x02" * 32)
    stats = db.stats()
    db.close()
    assert stats["last_flush"]["old_values"] == {
        "remembered": 30, "looked_up": 10, "found": 10}
    assert stats["old_values"]["remembered"] == 30
    reads = [ev["args"] for ev in tm.TRACER.events()
             if ev["name"] == "store.old_reads"]
    assert len(reads) == (4 if name == "trace" else 0)
    assert [r["shard"] for r in reads] == [0, 1, 0, 1][:len(reads)]
    assert sum(r["keys"] for r in reads) == (80 if reads else 0)
    assert sum(r["remembered"] for r in reads) == (30 if reads else 0)
    # the first commit's keys were all the bloom's to refuse or let through
    assert sum(r["looked_up"] for r in reads[2:]) == (10 if reads else 0)
    if name != "off":
        assert tm.span_totals()["store.old_reads"]["n"] == 4


# ---------------------------------------------------------------------------
# (d) gettpuinfo: startup and rpc beside every key it had
# ---------------------------------------------------------------------------

def test_gettpuinfo_has_startup_and_rpc_and_keeps_its_keys(imported):
    from test_telemetry import PR5_KEYS

    info = imported.info
    for key in PR5_KEYS + ("mining", "store", "telemetry", "device"):
        assert key in info, f"gettpuinfo lost {key!r}"
    startup = info["startup"]
    assert list(startup) == ["config", "stores", "device", "index",
                             "import", "verify_db", "services"]
    assert startup["import"] >= imported.stats["wall_s"]
    assert all(seconds >= 0.0 for seconds in startup.values())
    assert info["rpc"] == {}  # no server started
    assert {"emit_s", "dispatch_s"} <= set(info["ecdsa"])
    assert info["telemetry"]["span_times"] is True


# ---------------------------------------------------------------------------
# (c) the RPC server: the wait for cs_main apart from the handler
# ---------------------------------------------------------------------------

def _server(node):
    from bitcoincashplus_tpu.rpc.server import RPCServer

    server = RPCServer.__new__(RPCServer)  # no socket: execute() alone
    server.node = node
    server._calls, server._calls_lock = {}, threading.Lock()
    return server


@pytest.fixture
def handlers():
    """Handlers registered for one test: {name: function} into the RPC
    table, taken out again afterwards."""
    from bitcoincashplus_tpu.rpc.registry import RPC_METHODS

    added = []

    def register(**methods):
        RPC_METHODS.update(methods)
        added.extend(methods)

    yield register
    for name in added:
        del RPC_METHODS[name]


def test_rpc_lock_wait_is_counted_apart_from_the_handler(mode, handlers):
    mode("counters")
    node = types.SimpleNamespace(cs_main=threading.RLock())
    server = _server(node)
    handlers(testonly_read=lambda node_, params: 7)
    held = threading.Event()

    def hold():
        with node.cs_main:
            held.set()
            time.sleep(0.05)

    holder = threading.Thread(target=hold)
    holder.start()
    held.wait()
    out = server.execute({"id": 1, "method": "testonly_read", "params": []})
    holder.join()
    assert out["error"] is None and out["result"] == 7
    row = server.call_stats()["testonly_read"]
    assert row["calls"] == 1
    assert row["lock_wait_s"] >= 0.04 and row["handler_s"] < 0.05
    totals = tm.span_totals()
    assert totals["rpc.lock_wait"]["s"] == row["lock_wait_s"]
    assert totals["rpc.handler"]["s"] == row["handler_s"]


def test_rpc_handler_without_cs_main_records_no_wait(mode, handlers):
    mode("counters")
    node = types.SimpleNamespace(cs_main=threading.RLock())
    server = _server(node)

    def blocking(node_, params):
        assert not node_.cs_main._is_owned()
        return "ok"

    def failing(node_, params):
        assert node_.cs_main._is_owned()
        raise ValueError("no")

    blocking.no_cs_main = True
    handlers(testonly_blocking=blocking, testonly_failing=failing)
    out = server.execute({"id": 2, "method": "testonly_blocking"})
    assert out["result"] == "ok"
    row = server.call_stats()["testonly_blocking"]
    assert (row["calls"], row["lock_wait_s"]) == (1, 0.0)
    assert row["handler_s"] == tm.span_totals()["rpc.handler"]["s"]
    assert "rpc.lock_wait" not in tm.span_totals()
    # a handler that raises is counted too, and lets go of the lock
    failed = server.execute({"id": 3, "method": "testonly_failing"})
    assert failed["error"]["message"] == "no"
    assert server.call_stats()["testonly_failing"]["calls"] == 1
    assert not node.cs_main._is_owned()


# ---------------------------------------------------------------------------
# (e) tools/trace_view.py --xplane on a small recorded trace
# ---------------------------------------------------------------------------

PLANES = os.path.join(DATA, "xplane_spans_small.json")


def test_xplane_report_puts_idle_time_under_the_innermost_span():
    with open(PLANES) as f:
        planes = json.load(f)
    ms = 10_000  # the recorded trace's unit: 10 us, so 100 units a ms
    spans = trace_view._host_events(planes, "bcp.")
    busy = trace_view._device_busy(planes)
    assert busy == [[1000 * ms, 1500 * ms], [3000 * ms, 3500 * ms]]
    gaps = [(0, 1000 * ms), (1500 * ms, 3000 * ms), (3500 * ms, 5000 * ms)]
    table = trace_view.idle_by_span(gaps, spans)
    assert sum(table.values()) == 4000 * ms
    assert table == {name: units * ms for name, units in {
        "unannotated": 1200, "bcp.import": 700, "bcp.import.connect": 1100,
        "bcp.import.enqueue": 200, "bcp.import.settle_wait": 100,
        "bcp.import.flush": 700}.items()}
    # from the first enqueue's start to the last settle_wait's end
    assert trace_view.dispatch_stretch(spans) == (900 * ms, 3500 * ms)
    assert trace_view.dispatch_stretch(spans[:3]) is None


def test_xplane_report_text_and_cross_checks(capsys):
    rc = trace_view.main(["trace_view.py", "--xplane", PLANES])
    text = capsys.readouterr().out
    assert rc == 0
    assert "device idle 40.000 ms (80.00%) in 3 gaps, 8 bcp.* host" in text
    assert ("inside chipbench.import: idle 30.000 ms, 93.33% of it under a "
            "bcp.import* span, 93.33% under any bcp.* span") in text
    assert "bcp.import* events outside chipbench.import: 0 of 8" in text
    # idle inside the stretch: 1 ms before the first kernel, 15 ms between
    assert ("between the first enqueue and the last settle (26.000 ms): "
            "device idle 16.000 ms, 16.000 ms of it in gaps of 1 ms") in text
    assert trace_view.main(["trace_view.py", "--xplane"]) == 2
