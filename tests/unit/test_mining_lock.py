"""The scope of the cs_main hold in the mining call (node.generate_to_script).

cs_main is held for the template and again for the connect; the nonce
search between them holds the ``miner`` lock alone. Each test parks a
mining call inside its search (a gate in front of the engine's real sweep)
and does from another thread what the hold used to keep out: a read through
RPCServer.execute, a second mining call, a block connected at the same
height, a gettpuinfo snapshot. Every wait and join is bounded, so a
deadlock fails its test instead of hanging the suite.
"""

import sys
import threading

import pytest

from bitcoincashplus_tpu.mining.generate import mine_block
from bitcoincashplus_tpu.node.config import Config
from bitcoincashplus_tpu.node.node import Node
from bitcoincashplus_tpu.ops.miner import sweep_header_cpu
from bitcoincashplus_tpu.rpc.control import gettpuinfo
from bitcoincashplus_tpu.rpc.server import RPCServer
from bitcoincashplus_tpu.util import lockwatch
from bitcoincashplus_tpu.validation.chain import BlockStatus
from bitcoincashplus_tpu.wallet.keys import script_to_address

pytestmark = pytest.mark.wall_limit(
    120, reason="a deadlocked mining call must fail, not hang the suite")

SPK = bytes.fromhex("76a914") + b"\x11" * 20 + bytes.fromhex("88ac")
SPK_OTHER = bytes.fromhex("76a914") + b"\x22" * 20 + bytes.fromhex("88ac")
WAIT_S = 20  # the bound of every wait; the engines compile in the fixture

# the engines _select_sweep picks on the CPU backend, and the flag for each
ENGINES = {"scalar-host": {}, "resident-exact": {"residentminer": "force"}}


class SearchGate:
    """Stands in front of the sweep _select_sweep returns: a search signals
    that it is inside, waits to be released, then runs the engine's real
    sweep. Counts the searches inside at once."""

    def __init__(self, node):
        self.entered = threading.Event()
        self.release = threading.Event()
        self._mu = threading.Lock()
        self.inside = 0
        self.most_inside = 0
        self.searches = 0
        real_select = node._select_sweep

        def select():
            sweep = real_select()

            def gated(header80, target, **kw):
                with self._mu:
                    self.inside += 1
                    self.searches += 1
                    self.most_inside = max(self.most_inside, self.inside)
                self.entered.set()
                try:
                    assert self.release.wait(WAIT_S), "gate never released"
                    return sweep(header80, target, **kw)
                finally:
                    with self._mu:
                        self.inside -= 1

            return gated

        node._select_sweep = select


class Call(threading.Thread):
    """fn(*args) on a thread of its own; finish() joins it within WAIT_S and
    hands back its result, or raises what it raised."""

    def __init__(self, fn, *args):
        super().__init__(daemon=True)
        self.fn, self.args = fn, args
        self.result = self.error = None

    def run(self):
        try:
            self.result = self.fn(*self.args)
        except BaseException as e:  # noqa: BLE001 — raised by finish()
            self.error = e

    def finish(self):
        self.join(WAIT_S)
        assert not self.is_alive(), f"{self.fn.__name__} wedged"
        if self.error is not None:
            raise self.error
        return self.result


def Miner(node, n_blocks=1, spk=SPK) -> Call:
    """One generate_to_script call, not yet started."""
    return Call(node.generate_to_script, spk, n_blocks)


def _in_thread(fn):
    call = Call(fn)
    call.start()
    return call.finish()


def _mk_node(tmp_path, engine):
    cfg = Config()
    cfg.args["datadir"] = [str(tmp_path)]
    cfg.args["regtest"] = ["1"]
    for k, v in ENGINES[engine].items():
        cfg.args[k] = [v]
    return Node(config=cfg)


@pytest.fixture(params=sorted(ENGINES))
def mining(request, tmp_path):
    """(node, gate, engine): a regtest node three blocks high whose next
    searches stop at the gate."""
    node = _mk_node(tmp_path, request.param)
    gate = SearchGate(node)
    gate.release.set()
    assert len(node.generate_to_script(SPK, 3)) == 3  # warms the engine up
    assert node.sweep_engine == request.param
    gate.release.clear()
    gate.entered.clear()
    try:
        yield node, gate, request.param
    finally:
        gate.release.set()
        node.close()


def test_read_is_answered_while_the_search_runs(mining):
    node, gate, _ = mining
    node.rpc_server = RPCServer(node, port=0)
    node.rpc_server.start()
    miner = Miner(node)
    miner.start()
    assert gate.entered.wait(WAIT_S)

    def read():
        with node.cs_main:  # free: the search holds the miner lock alone
            height = node.chainstate.tip().height
        reply = node.rpc_server.execute(
            {"id": 1, "method": "getblockcount", "params": []})
        return height, reply

    height, reply = _in_thread(read)
    assert gate.inside == 1, "the search ended before the read was made"
    assert reply["error"] is None and reply["result"] == height == 3
    row = gettpuinfo(node, [])["rpc"]["getblockcount"]
    assert row["calls"] == 1
    assert row["lock_wait_s"] < 0.005
    gate.release.set()
    assert len(miner.finish()) == 1
    assert node.chainstate.tip().height == 4
    # the mining handler took no lock in execute: it manages cs_main itself
    reply = node.rpc_server.execute(
        {"id": 2, "method": "generatetoaddress",
         "params": [1, script_to_address(SPK, node.params)]})
    assert reply["error"] is None and len(reply["result"]) == 1
    assert gettpuinfo(node, [])["rpc"]["generatetoaddress"][
        "lock_wait_s"] == 0.0


def test_two_mining_calls_never_search_at_once(mining):
    node, gate, _ = mining
    first, second = Miner(node, 2), Miner(node, 3, SPK_OTHER)
    first.start()
    assert gate.entered.wait(WAIT_S)
    second.start()
    # the second call waits for the miner lock, not inside the sweep
    second.join(0.3)
    assert second.is_alive() and gate.inside == 1
    gate.release.set()
    mined = first.finish() + second.finish()
    assert len(mined) == len(set(mined)) == 5
    assert gate.most_inside == 1
    assert gate.searches >= 5
    assert node.chainstate.tip().height == 3 + 5
    assert node.mining_snapshot()["calls"] == 1 + 2  # the fixture's too


def test_many_mining_calls_lose_no_block_and_no_tally(mining):
    """More callers than cores, the interpreter switching threads every
    few bytecodes: the searches still run one at a time, every block
    connects, and the tallies the miner lock guards lose no update."""
    node, gate, _ = mining
    gate.release.set()
    miners = [Miner(node, 2, SPK if i % 2 else SPK_OTHER) for i in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for m in miners:
            m.start()
        mined = [h for m in miners for h in m.finish()]
    finally:
        sys.setswitchinterval(interval)
    assert len(mined) == len(set(mined)) == 24
    assert gate.most_inside == 1
    assert node.chainstate.tip().height == 3 + 24
    snap = node.mining_snapshot()
    assert snap["calls"] == 1 + 12
    assert snap["search_s"] > 0.0 and snap["cs_main_held_s"] > 0.0


def test_block_connected_during_the_search_leaves_a_side_branch(mining):
    """The tip moves while the call searches: its block is handed to
    process_new_block all the same, which does with it what it does with
    any block whose parent is no longer the tip (as submitblock would
    leave it): stored, indexed, an equal-work side branch beside the block
    that came first. Nothing is re-checked, nothing thrown away."""
    node, gate, _ = mining
    cs = node.chainstate
    parent = cs.tip()
    miner = Miner(node)
    miner.start()
    assert gate.entered.wait(WAIT_S)

    def connect_rival():
        with node.cs_main:
            rival = mine_block(
                node.assembler(), SPK_OTHER, extranonce_start=7,
                sweep=lambda h, t, max_nonces, tile: sweep_header_cpu(
                    h, t, max_nonces=max_nonces))
            assert node.submit_block(rival) is None
            return rival

    rival = _in_thread(connect_rival)
    assert cs.tip().hash == rival.get_hash()
    gate.release.set()
    (ours,) = miner.finish()
    assert ours != rival.get_hash()
    with node.cs_main:
        # the first block seen at the height keeps the tip
        assert cs.tip().hash == rival.get_hash()
        assert cs.tip().height == parent.height + 1
        idx = cs.block_index[ours]
        assert idx.prev is parent and idx.height == parent.height + 1
        assert idx.status & BlockStatus.HAVE_DATA
        assert not idx.status & BlockStatus.FAILED_MASK
        assert idx.chain_work == cs.tip().chain_work
        assert cs.chain[idx.height] is not idx
        assert cs.get_block(ours) is not None
        # the coins view is the active chain's: the rival's coinbase is
        # there, the side branch's is not
        from bitcoincashplus_tpu.consensus.tx import COutPoint

        assert cs.coins.best_block() == rival.get_hash()
        assert cs.coins.have_coin(COutPoint(rival.vtx[0].txid, 0))
        side = cs.get_block(ours)
        assert not cs.coins.have_coin(COutPoint(side.vtx[0].txid, 0))
        assert node.verify_db(n_blocks=4, level=3)
    # and the next call builds on the tip that won
    (nxt,) = node.generate_to_script(SPK, 1)
    assert cs.block_index[nxt].prev.hash == rival.get_hash()


def test_mining_snapshot_does_not_wait_for_the_search(mining):
    node, gate, engine = mining
    miner = Miner(node)
    miner.start()
    assert gate.entered.wait(WAIT_S)
    snap = _in_thread(node.mining_snapshot)
    info = _in_thread(lambda: gettpuinfo(node, []))["mining"]
    assert gate.inside == 1
    for s in (snap, info):
        assert s["engine"] == engine
        assert s["resident"] is (engine != "scalar-host")
        assert s["calls"] == 2  # the fixture's and the one in flight
        assert s["search_s"] > 0.0 and s["cs_main_held_s"] > 0.0
    gate.release.set()
    miner.finish()
    after = node.mining_snapshot()
    assert after["calls"] == 2
    assert after["search_s"] > snap["search_s"]
    assert after["cs_main_held_s"] > snap["cs_main_held_s"]


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_lockwatch_sees_miner_before_cs_main(engine, tmp_path, monkeypatch):
    monkeypatch.setenv("BCP_LOCKWATCH", "1")
    lockwatch.MONITOR.reset()
    node = _mk_node(tmp_path, engine)
    try:
        node.rpc_server = RPCServer(node, port=0)
        node.rpc_server.start()
        gate = SearchGate(node)
        miner = Miner(node, 2)
        miner.start()
        assert gate.entered.wait(WAIT_S)
        for method in ("getblockcount", "getmininginfo", "gettpuinfo"):
            reply = _in_thread(lambda: node.rpc_server.execute(
                {"id": 1, "method": method, "params": []}))
            assert reply["error"] is None
        gate.release.set()
        assert len(miner.finish()) == 2
        snap = gettpuinfo(node, [])["lockwatch"]
    finally:
        node.close()
        after_close = lockwatch.snapshot()
        lockwatch.MONITOR.reset()
    assert snap["enabled"] is True
    assert "miner" in snap["locks"]
    assert snap["order_edges"]["miner->cs_main"] >= 4  # two holds a block
    assert "cs_main->miner" not in snap["order_edges"]
    assert snap["inversions"] == 0 and snap["cycles"] == []
    assert after_close["inversions"] == 0  # close() takes them in order too
    assert set(Node.GUARDED_BY) <= set(snap["declared_guards"]["miner"])
