"""Device-lane observability suite (ISSUE 8): the compile/retrace
sentinel, transfer & memory accounting, dispatch-phase plumbing, the
profiler RPC round trip, and the stall watchdog on a fake clock.

Tier-1, CPU backend ('devprof' marker — conftest orders it after the
telemetry group, before serving). Kernel-heavy integration (the ecdsa
programs' real budgets) is covered by the driver bench, not here: every
jit in this file is a trivially-compiling toy so the suite stays fast.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax

from bitcoincashplus_tpu.util import devicewatch as dw
from bitcoincashplus_tpu.util import telemetry as tm

pytestmark = pytest.mark.devprof


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Fresh program/transfer/watchdog state per test; the telemetry
    families survive (module-level handles) but are zeroed."""
    monkeypatch.setenv("BCP_TELEMETRY", "counters")
    tm.reset()
    dw.reset()
    yield
    tm.reset()
    dw.reset()


def _family_value(name: str, **labels) -> float:
    fam = tm.REGISTRY.snapshot().get(name, {"values": []})
    for v in fam["values"]:
        if all(v["labels"].get(k) == str(val) for k, val in labels.items()):
            return v.get("value", v.get("count", 0.0))
    return 0.0


# ---------------------------------------------------------------------------
# compile/retrace sentinel
# ---------------------------------------------------------------------------


def test_retrace_sentinel_fires_beyond_budget(monkeypatch):
    """Forcing an un-bucketed shape — a distinct signature beyond the
    declared budget — must fire the counter, a warning, and keep the
    verdict path untouched (observe-only)."""
    warnings = []
    monkeypatch.setattr(dw, "log_printf",
                        lambda msg, *a: warnings.append(msg % a))
    f = jax.jit(lambda x: x + 1)
    pw = dw.program("sentinel_prog", shape_budget=2)
    for n in (4, 8):  # inside the budget: no sentinel
        x = np.arange(n, dtype=np.float32)
        with pw.dispatch(x.shape):
            f(x)
    assert pw.snapshot()["retraces_unexpected"] == 0
    assert not warnings
    x = np.arange(16, dtype=np.float32)  # the un-bucketed shape
    with pw.dispatch(x.shape):
        f(x)
    snap = pw.snapshot()
    assert snap["shapes"] == 3
    assert snap["retraces_unexpected"] == 1
    assert "unexpected retrace" in snap["last_warning"]
    assert "sentinel_prog" in snap["last_warning"]
    assert any("unexpected retrace" in w for w in warnings)
    assert _family_value("bcp_xla_retrace_unexpected_total",
                         program="sentinel_prog") == 1
    # a REPEAT of a known shape is not a retrace
    with pw.dispatch((16,)):
        f(np.arange(16, dtype=np.float32))
    assert pw.snapshot()["retraces_unexpected"] == 1


def test_compile_accounting_counts_compiles_not_dispatches():
    f = jax.jit(lambda x: x * 3)
    pw = dw.program("compile_prog")
    x = np.arange(8, dtype=np.float32)
    for _ in range(3):  # one compile, three dispatches
        with pw.dispatch(x.shape):
            f(x)
    snap = pw.snapshot()
    assert snap["dispatches"] == 3
    assert snap["compiles"] == 1
    assert snap["compile_seconds"] > 0
    assert snap["signatures"] == {str(((8,),)): 3}
    with pw.dispatch((16,)):  # second shape, second compile
        f(np.arange(16, dtype=np.float32))
    assert pw.snapshot()["compiles"] == 2
    assert _family_value("bcp_xla_compiles_total",
                         program="compile_prog") == 2
    # the compile-time histogram saw both
    fam = tm.REGISTRY.snapshot()["bcp_xla_compile_seconds"]
    counts = {tuple(v["labels"].items()): v["count"]
              for v in fam["values"]}
    assert counts[(("program", "compile_prog"),)] == 2


def test_cost_analysis_captured_at_first_compile():
    f = jax.jit(lambda x: (x * 2 + 1).sum())
    pw = dw.program("cost_prog")
    x = np.arange(64, dtype=np.float32)
    with pw.dispatch(x.shape, jitfn=f, args=(x,)):
        f(x)
    cost = pw.snapshot()["cost"]
    assert str(((64,),)) in cost
    assert cost[str(((64,),))]["flops"] > 0
    # never: the knob must suppress the second compile entirely
    import os

    os.environ["BCP_DEVICEWATCH_COST"] = "never"
    try:
        with pw.dispatch((128,), jitfn=f,
                         args=(np.arange(128, dtype=np.float32),)):
            f(np.arange(128, dtype=np.float32))
        assert str(((128,),)) not in pw.snapshot()["cost"]
    finally:
        os.environ.pop("BCP_DEVICEWATCH_COST", None)


def test_dispatch_bookkeeping_survives_a_raising_call():
    """A failed kernel call (the glv->w4 degradation path) still counts
    the shape attempt — and the watch context unwinds cleanly."""
    pw = dw.program("boom_prog", shape_budget=1)
    with pytest.raises(RuntimeError):
        with pw.dispatch((32,)):
            raise RuntimeError("mosaic says no")
    snap = pw.snapshot()
    assert snap["dispatches"] == 1
    assert snap["shapes"] == 1
    assert dw._ctx_stack() == []


def test_ecdsa_programs_declare_budgets():
    """The ecdsa dispatch legs register watched programs with the bucket
    design's declared shape budgets at import."""
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    progs = dw.snapshot()["programs"]
    # ops/ecdsa_batch was imported (and thus registered) by other suites;
    # after dw.reset() re-derive the handles the module holds
    assert eb._PW_GLV_DEV.shape_budget == eb.PALLAS_SHAPE_BUDGET
    assert eb._PW_W4_BYTES.shape_budget == eb.PALLAS_SHAPE_BUDGET
    assert eb._PW_MSM.shape_budget == len(eb._MSM_BUCKETS)
    assert isinstance(progs, dict)


@pytest.mark.parametrize("calls, froze", [(1, 1), (3, 1)])
def test_a_newly_traced_verify_shape_freezes_the_heap_once(
        monkeypatch, calls, froze):
    """What tracing a verify program leaves lives as long as the process:
    it moves out of the cyclic collector's reach when the shape is first
    called, once a shape and not once a dispatch; a call that raised froze
    nothing."""
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    frozen = []
    monkeypatch.setattr(eb.gc, "freeze", lambda: frozen.append(1))
    pw = dw.program("test_freeze_once", shape_budget=2)
    arrays = (np.zeros((4, 32), np.uint8),)

    def boom():
        raise RuntimeError("lowering refused")

    with pytest.raises(RuntimeError):
        eb._watched_kernel(pw, 2048, arrays, boom)
    assert frozen == []
    for _ in range(calls):
        assert eb._watched_kernel(pw, 8192, arrays, lambda: "ok") == "ok"
    assert len(frozen) == froze


def test_frozen_heap_is_out_of_the_collectors_reach():
    import gc

    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    before = gc.get_freeze_count()
    try:
        eb._freeze_traced_heap()
        assert gc.get_freeze_count() > before
    finally:
        gc.unfreeze()


# ---------------------------------------------------------------------------
# transfer & memory accounting
# ---------------------------------------------------------------------------


def test_transfer_accounting_totals_and_families():
    dw.note_transfer("ecdsa", "h2d", 1024)
    dw.note_transfer("ecdsa", "h2d", 512)
    dw.note_transfer("ecdsa", "d2h", 16, seconds=0.002)
    assert dw.transfer_snapshot() == {
        "ecdsa": {"d2h": 16, "h2d": 1536}}
    assert _family_value("bcp_device_transfer_bytes_total",
                         site="ecdsa", direction="h2d") == 1536
    assert _family_value("bcp_device_transfer_bytes_total",
                         site="ecdsa", direction="d2h") == 16
    # the transfer-time histogram only saw the timed crossing
    fam = tm.REGISTRY.snapshot()["bcp_device_transfer_seconds"]
    assert sum(v["count"] for v in fam["values"]) == 1


def test_memory_collector_is_a_graceful_noop_on_cpu():
    """CPU devices answer memory_stats() with None: the families still
    export (stable namespace) with supported=0 and no byte samples."""
    fams = {f["name"]: f for f in dw._collect_device_memory()}
    assert set(fams) == {"bcp_device_memory_bytes",
                         "bcp_device_memory_supported",
                         "bcp_device_count"}
    assert fams["bcp_device_memory_bytes"]["samples"] == []
    sups = fams["bcp_device_memory_supported"]["samples"]
    assert sups and all(v == 0 for _labels, v in sups)
    assert fams["bcp_device_count"]["samples"][0][1] >= 1
    # and the scrape surfaces them (collector registered at import)
    text = tm.REGISTRY.prometheus_text()
    for name in ("bcp_device_memory_bytes", "bcp_device_memory_supported",
                 "bcp_device_count", "bcp_xla_compile_seconds",
                 "bcp_device_transfer_bytes_total"):
        assert f"# TYPE {name}" in text, name


def test_a_dispatchs_legs_are_spans_not_a_phase_histogram():
    """bcp_dispatch_phase_seconds went with PR 40 (every sample sat beside a
    span or a latency histogram of the same interval): a dispatch's host
    legs are spans, their totals on /metrics by name, and the ecdsa fetch
    keeps its bytes without a second timing."""
    assert not hasattr(dw, "phase") and not hasattr(dw, "note_phase")
    with tm.span("ecdsa.pack"):
        pass
    dw.note_transfer("ecdsa", "d2h", 16)
    snap = tm.REGISTRY.snapshot()
    assert "bcp_dispatch_phase_seconds" not in snap
    seen = {v["labels"]["span"]: v["value"]
            for v in snap["bcp_span_count_total"]["values"]}
    assert seen["ecdsa.pack"] == 1
    assert sum(v["count"] for v in
               snap["bcp_device_transfer_seconds"]["values"]) == 0


# ---------------------------------------------------------------------------
# profiler RPC round trip
# ---------------------------------------------------------------------------


def test_profiler_rpc_round_trip(tmp_path):
    import gzip
    import os
    import types

    from bitcoincashplus_tpu.rpc.control import startprofile, stopprofile
    from bitcoincashplus_tpu.rpc.registry import RPCError

    node = types.SimpleNamespace(datadir=str(tmp_path))
    with pytest.raises(RPCError):
        stopprofile(node, [])  # not running yet
    out = startprofile(node, [])
    assert out["active"] and out["path"] == str(tmp_path / "profile")
    with pytest.raises(RPCError):  # double start rejected
        startprofile(node, [])
    jax.jit(lambda x: x + 1)(np.arange(8, dtype=np.float32))
    stopped = stopprofile(node, [])
    assert stopped["path"] == out["path"]
    assert stopped["seconds"] >= 0
    # TensorBoard-compatible dump landed (plugins/profile/<ts>/...)
    files = []
    for root, _dirs, fs in os.walk(out["path"]):
        files += [os.path.join(root, f) for f in fs]
    assert any(f.endswith(".xplane.pb") for f in files), files
    tj = [f for f in files if f.endswith("trace.json.gz")]
    assert tj and gzip.open(tj[0]).read(1)  # non-empty, readable
    with pytest.raises(RPCError):
        stopprofile(node, [])  # stopped twice
    assert dw.profile_snapshot() == {"active": False, "path": None,
                                     "dumps": 1}


def test_gettpuinfo_gains_device_section():
    import types

    from bitcoincashplus_tpu.rpc.control import gettpuinfo
    from bitcoincashplus_tpu.validation.sigcache import SignatureCache

    node = types.SimpleNamespace(
        backend="cpu",
        sigcache=SignatureCache(),
        chainstate=types.SimpleNamespace(
            bench={}, pipeline_snapshot=lambda: {}, bip30_stats={}),
        connman=None,
    )
    dw.note_transfer("ecdsa", "h2d", 64)
    out = gettpuinfo(node, [])
    dev = out["device"]
    assert {"programs", "transfer_bytes", "profiler",
            "watchdog", "unattributed_compiles"} <= set(dev)
    assert dev["transfer_bytes"]["ecdsa"]["h2d"] == 64
    assert dev["profiler"]["active"] is False


# ---------------------------------------------------------------------------
# stall watchdog (fake clock)
# ---------------------------------------------------------------------------


def test_watchdog_fires_and_clears_on_fake_clock(monkeypatch):
    warnings = []
    monkeypatch.setattr(dw, "log_printf",
                        lambda msg, *a: warnings.append(msg % a))
    clk = [0.0]
    pending = [0]
    wd = dw.Watchdog(clock=lambda: clk[0])
    wd.register("svc", pending_fn=lambda: pending[0], quiet_s=5.0)

    assert wd.check() == []          # idle, no pending: never stalls
    clk[0] = 100.0
    assert wd.check() == []
    pending[0] = 7                   # work appears
    wd.beat("svc")                   # progress at t=100
    clk[0] = 104.9
    assert wd.check() == []          # inside the quiet period
    clk[0] = 105.1
    assert wd.check() == ["svc"]     # quiet period elapsed: stalled
    snap = wd.snapshot()["svc"]
    assert snap["stalled"] and snap["episodes"] == 1
    assert any("stalled" in w and "observe-only" in w for w in warnings)
    assert wd.check() == ["svc"]     # still stalled: ONE episode, no spam
    assert wd.snapshot()["svc"]["episodes"] == 1
    wd.beat("svc")                   # progress clears it
    assert not wd.snapshot()["svc"]["stalled"]
    assert wd.check() == []
    clk[0] = 200.0                   # second episode
    assert wd.check() == ["svc"]
    assert wd.snapshot()["svc"]["episodes"] == 2
    pending[0] = 0                   # work drained without a beat: clear
    assert wd.check() == []
    assert not wd.snapshot()["svc"]["stalled"]


def test_watchdog_quiet_zero_disables_detection():
    clk = [0.0]
    wd = dw.Watchdog(clock=lambda: clk[0])
    wd.register("off", pending_fn=lambda: 5, quiet_s=0)
    clk[0] = 1e6
    assert wd.check() == []
    assert wd.snapshot()["off"]["stalled"] is False


def test_watchdog_beat_on_unregistered_name_is_a_noop():
    wd = dw.Watchdog(clock=lambda: 0.0)
    wd.beat("ghost")  # must not raise
    wd.register("x", pending_fn=lambda: 0)
    wd.unregister("x")
    wd.beat("x")
    assert wd.check() == []


def test_watchdog_gauge_and_episode_counter_export(monkeypatch):
    clk = [0.0]
    wd = dw.Watchdog(clock=lambda: clk[0])
    wd.register("expo", pending_fn=lambda: 3, quiet_s=1.0)
    clk[0] = 2.0
    wd.check()
    assert _family_value("bcp_watchdog_stalled", subsystem="expo") == 1
    assert _family_value("bcp_watchdog_stall_episodes_total",
                         subsystem="expo") == 1
    wd.beat("expo")
    assert _family_value("bcp_watchdog_stalled", subsystem="expo") == 0


def test_sigservice_wires_the_watchdog():
    """The service registers on start, beats per flush, unregisters on
    stop — the wiring the node knob (-watchdogquiet) parameterizes."""
    from bitcoincashplus_tpu.crypto import secp256k1 as oracle
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord
    from bitcoincashplus_tpu.serving import SigService

    svc = SigService(backend="cpu", deadline_ms=1, lanes=4,
                     watchdog_quiet=123.0).start()
    try:
        assert "sigservice" in dw.WATCHDOG.snapshot()
        assert dw.WATCHDOG.snapshot()["sigservice"]["quiet_s"] == 123.0
        sk = 0x1234
        e = 0x5678
        r, s = oracle.ecdsa_sign(sk, e)
        rec = SigCheckRecord(oracle.point_mul(sk, oracle.G), r, s, e)
        assert svc.submit([rec]).result().tolist() == [True]
        assert dw.WATCHDOG.beat_totals().get("sigservice", 0) >= 1
        assert svc.snapshot()["watchdog"]["beats"] >= 1
    finally:
        svc.stop()
    assert "sigservice" not in dw.WATCHDOG.snapshot()


def test_chainstate_registers_pipeline_watchdog():
    """A ChainstateManager registers the settle-horizon probe at init
    (the node re-registers with -watchdogquiet and unregisters at
    close); the probe reads the live horizon depth."""
    from bitcoincashplus_tpu.consensus.params import regtest_params
    from bitcoincashplus_tpu.store.blockstore import MemoryBlockStore
    from bitcoincashplus_tpu.validation.chainstate import ChainstateManager
    from bitcoincashplus_tpu.validation.coins import MemoryCoinsView

    cs = ChainstateManager(regtest_params(), MemoryCoinsView(),
                           MemoryBlockStore(), script_verifier=None)
    assert "pipeline" in dw.WATCHDOG.snapshot()
    # the probe tracks the speculation tree's total entry count
    # (ISSUE 9: _horizon is now the derived winning-path view; the
    # pending work the watchdog cares about is every open layer)
    cs._spec[b"\x11" * 32] = {"idx": None, "parent": None,
                              "children": []}
    clk_entry = dw.WATCHDOG._entries["pipeline"]
    assert clk_entry["pending_fn"]() == 1
    cs._spec.clear()
    assert clk_entry["pending_fn"]() == 0


def test_persistent_cache_hits_surface_in_snapshot(tmp_path, monkeypatch):
    """Second compile of the same program is served from the persistent
    cache and the monitoring listener tallies it — the cache_hits field
    gettpuinfo.device.compilation_cache exposes (and that the functional
    suite asserts > 0 on re-spawned nodes via the directory conftest
    resolves and exports). Toy jit, so the 2 s min-compile-time floor is
    lowered for the duration; all cache config is restored after."""
    saved_dir = jax.config.jax_compilation_cache_dir
    saved_min = jax.config.jax_persistent_cache_min_compile_time_secs
    saved_cc = dict(dir=dw._COMPILE_CACHE["dir"],
                    enabled=dw._COMPILE_CACHE["enabled"])
    monkeypatch.delenv(dw.CACHE_ENV, raising=False)  # let the flag decide
    try:
        dw.enable_compile_cache(str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

        @jax.jit
        def f(x):
            return x * 2 + 1

        assert int(f(np.int32(20))) == 41  # cold: writes the cache entry
        jax.clear_caches()  # drop the in-memory executable
        assert int(f(np.int32(20))) == 41  # warm: persistent-cache read
        snap = dw.compile_cache_snapshot()
        assert snap["enabled"]
        assert snap["dir"] == str(tmp_path / "cache")
        assert snap["cache_hits"] > 0
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved_min)
        if saved_dir is not None:
            jax.config.update("jax_compilation_cache_dir", saved_dir)
        with dw._LOCK:
            dw._COMPILE_CACHE.update(saved_cc)
