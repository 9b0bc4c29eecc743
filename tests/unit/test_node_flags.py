"""-par / -dbcache flag wiring (SURVEY §6.6 parity-flag contract:
advertised flags must be consumed, not help-text-only)."""

import os

import pytest

from bitcoincashplus_tpu import native
from bitcoincashplus_tpu.node.config import Config
from bitcoincashplus_tpu.node.node import Node


def _mk_node(tmp_path, **args):
    cfg = Config()
    cfg.args["datadir"] = [str(tmp_path)]
    cfg.args["regtest"] = ["1"]
    for k, v in args.items():
        cfg.args[k] = [str(v)]
    return Node(config=cfg)


# The -tpu / compile-cache cases come first: the tests further down mine
# blocks through the generic XLA-CPU sweep, which can sit for the rest of a
# run on a slow host, and --dist loadfile runs a file in order on one worker.


@pytest.mark.parametrize("source", ["env", "flag", "default"])
def test_compilecache_knob(tmp_path, monkeypatch, source):
    """One resolver places the persistent compilation cache (default ON):
    JAX_COMPILATION_CACHE_DIR beats -compilecache=<dir> beats
    <checkout>/.jax_cache. When the environment names the directory the
    code makes NO jax_compilation_cache_dir update (jax reads the variable
    itself); otherwise the resolved directory is exported for child
    processes. gettpuinfo.device carries the compilation_cache block."""
    import jax

    from bitcoincashplus_tpu.util import devicewatch as dw

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env_dir, flag_dir = tmp_path / "env-cache", tmp_path / "flag-cache"
    want = {"env": str(env_dir), "flag": str(flag_dir),
            "default": os.path.join(repo, ".jax_cache")}[source]
    monkeypatch.delenv(dw.CACHE_ENV, raising=False)
    if source == "env":
        monkeypatch.setenv(dw.CACHE_ENV, str(env_dir))
    dir_updates = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            dir_updates.append(value)
        real_update(name, value)

    old_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax.config, "update", spy)
    try:
        args = {} if source == "default" else {"compilecache": flag_dir}
        node = _mk_node(tmp_path / "cc", **args)
        try:
            assert node.compile_cache == want
            assert dir_updates == ([] if source == "env" else [want])
            assert os.environ[dw.CACHE_ENV] == want  # children inherit
            assert os.path.isdir(want)
            snap = dw.snapshot()["compilation_cache"]
            assert snap["enabled"] and snap["dir"] == want
            assert "cache_hits" in snap
        finally:
            node.close()
    finally:
        real_update("jax_compilation_cache_dir", old_dir)


def test_tpu_flag_requires_tpu(tmp_path):
    """-tpu=1 on a machine whose first JAX device is not a TPU refuses to
    start, naming the platform it found."""
    from bitcoincashplus_tpu.node.node import InitError

    with pytest.raises(InitError, match="'cpu'"):
        _mk_node(tmp_path / "t", tpu=1)


@pytest.mark.parametrize("tpu,connect,serve", [
    ("tpu", "device", "auto"),
    ("auto", "auto", "auto"),
    ("cpu", "cpu", "cpu"),
])
def test_tpu_backend_mapping(tpu, connect, serve):
    """One meaning for -tpu: block connect is forced to the device under
    -tpu=1, the mempool/SigService sites keep the lane floor."""
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    assert eb.dispatch_backend(tpu) == connect
    assert eb.dispatch_backend(tpu, lane_floor=True) == serve
    assert connect in eb.BACKENDS and serve in eb.BACKENDS


def test_unknown_backend_rejected():
    """The -tpu flag's own string used to reach dispatch_batch and fall
    silently to the CPU oracle; an unknown backend now raises."""
    import numpy as np

    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    with pytest.raises(ValueError):
        eb.dispatch_backend("gpu")
    with pytest.raises(ValueError):
        eb.dispatch_batch([object()], backend="tpu")
    z = np.zeros((1, 64), np.uint8)
    with pytest.raises(ValueError):
        eb.dispatch_packed(z, z, z[:, :32], z[:, :32], z[:, 0],
                           backend="tpu")


def test_tpu_flag_0_keeps_cpu(tmp_path):
    """-tpu=0 / unset reach the verifier as backends dispatch_batch knows."""
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    node = _mk_node(tmp_path / "u", tpu=0)
    try:
        assert node.connect_backend == node.serve_backend == "cpu"
        assert node.chainstate.script_verifier.backend == "cpu"
        assert eb._REQUIRE_DEVICE is False
    finally:
        node.close()


def test_require_device_makes_refusal_fatal():
    """Under -tpu=1 (require_device) a deterministic compiler refusal
    raises KernelRefused instead of latching the rung broken; transient
    errors and the auto policy are unchanged."""
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    refusal = NotImplementedError("Unimplemented primitive in Pallas TPU "
                                  "lowering: dynamic_slice")
    assert eb._compiler_refused(refusal) is True  # auto: latch-worthy
    assert eb._compiler_refused(RuntimeError("socket closed")) is False
    eb.require_device(True)
    try:
        assert eb._compiler_refused(RuntimeError("socket closed")) is False
        for note in (eb._note_glv_failure, eb._note_pallas_failure):
            with pytest.raises(eb.KernelRefused, match="dynamic_slice"):
                note(refusal)
        assert not (eb._GLV_BROKEN or eb._PALLAS_BROKEN)
        assert issubclass(eb.KernelRefused, eb.SURFACE_ERRORS)
    finally:
        eb.require_device(False)
        s = eb.STATS
        s.glv_fallbacks -= 1
        s.pallas_fallbacks -= 1


def test_gettpuinfo_reports_device(tmp_path):
    """gettpuinfo.device names the device as JAX reports it."""
    import jax

    from bitcoincashplus_tpu.rpc.control import gettpuinfo

    node = _mk_node(tmp_path / "g")
    try:
        dev = gettpuinfo(node, [])["device"]
        d0 = jax.devices()[0]
        assert (dev["platform"], dev["kind"], dev["count"]) == (
            d0.platform, d0.device_kind, len(jax.devices()))
        assert dev["platform"] == "cpu" and dev["count"] == 8
    finally:
        node.close()


def test_par_sets_native_thread_budget(tmp_path):
    old = native.PAR_THREADS
    try:
        node = _mk_node(tmp_path / "a", par=2)
        assert native.PAR_THREADS == 2
        node.close()
        # negative -par keeps reference leave-N-cores-free semantics
        node = _mk_node(tmp_path / "b", par=-1)
        assert native.PAR_THREADS == max(1, (os.cpu_count() or 1) - 1)
        node.close()
    finally:
        native.PAR_THREADS = old


def test_dbcache_bounds_coins_cache(tmp_path):
    from bitcoincashplus_tpu.mining.generate import generate_blocks

    node = _mk_node(tmp_path / "c", dbcache=7)
    try:
        assert node.dbcache_bytes == 7 * 1024 * 1024
        # force the memory trigger: pretend the budget is 1 byte — the next
        # connected block must flush the coins cache even though the
        # block-interval policy wouldn't
        node.dbcache_bytes = 1
        node.flush_interval = 10_000
        spk = bytes.fromhex("76a914") + b"\x11" * 20 + bytes.fromhex("88ac")
        with node.cs_main:
            generate_blocks(node.chainstate, spk, 1, tile=1 << 12)
        assert node.chainstate.coins.cache_size() == 0  # flushed
        assert node._blocks_since_flush == 0
    finally:
        node.close()


def test_rescan_yields_cs_main(tmp_path):
    """VERDICT r3 #10: the O(height) wallet rescan must not hold cs_main
    for the whole walk — another thread can take the lock mid-rescan."""
    import threading

    from bitcoincashplus_tpu.mining.generate import generate_blocks

    node = _mk_node(tmp_path / "d")
    try:
        node.SCAN_CHUNK = 5
        spk = bytes.fromhex("76a914") + b"\x11" * 20 + bytes.fromhex("88ac")
        with node.cs_main:
            generate_blocks(node.chainstate, spk, 30, tile=1 << 12)
        wallet = node.load_wallet()
        wallet.get_new_address()  # give the wallet keys so rescan runs

        acquired_mid_rescan = threading.Event()
        rescan_started = threading.Event()

        orig_connected = wallet.block_connected

        def slow_connected(block, idx):
            rescan_started.set()
            orig_connected(block, idx)

        wallet.block_connected = slow_connected

        def contender():
            rescan_started.wait(timeout=10)
            # must get the lock while the rescan is still in progress
            if node.cs_main.acquire(timeout=10):
                node.cs_main.release()
                acquired_mid_rescan.set()

        t = threading.Thread(target=contender)
        t.start()
        with node.cs_main:  # simulate the RPC layer's hold
            node._rescan_wallet()
        t.join(timeout=15)
        assert acquired_mid_rescan.is_set()
    finally:
        node.close()


def test_txindex_backfill_background(tmp_path):
    """-txindex backfill syncs on a background thread; lookups work once
    synced; the flag persists so a restart skips the backfill."""
    import time as _t

    from bitcoincashplus_tpu.mining.generate import generate_blocks

    d = tmp_path / "e"
    node = _mk_node(d)
    spk = bytes.fromhex("76a914") + b"\x33" * 20 + bytes.fromhex("88ac")
    with node.cs_main:
        generate_blocks(node.chainstate, spk, 20, tile=1 << 12)
        coinbase_txid = node.chainstate.get_block(
            node.chainstate.chain[7].hash
        ).vtx[0].txid
    node.close()

    node = _mk_node(d, txindex=1)
    try:
        deadline = _t.time() + 30
        while not node._txindex_synced and _t.time() < deadline:
            _t.sleep(0.1)
        assert node._txindex_synced
        assert node.txindex_lookup(coinbase_txid) == \
            node.chainstate.chain[7].hash
    finally:
        node.close()
