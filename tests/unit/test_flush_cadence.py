"""The shipped flush cadence (-flushinterval, default 64) of the native
-reindex import, and the cell that measures it (reindex.flush64):

* the cadence counts connected blocks across block files: a chain written
  over two block files under -flushinterval=8 is flushed after every 8th
  connected block and is never more than 8 blocks ahead of its manifest;
* chipbench/gen/agedchain.py's chain at rehearsal sizes under
  -flushinterval=64 against chipbench/reference_flush64.py (the tip, the
  unspent set, every version of the manifest, the rows on disk) and against
  the same chain under one flush;
* the reference's MuHash against store/muhash.py on seeded rows, and the
  generator's age and alignment rules at every chain length.
"""

import json
import os
import random
import shutil
import struct
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
sys.path[:0] = [ROOT, BENCH]

import reference_flush64 as ref  # noqa: E402  (chipbench/)
import run  # noqa: E402  (chipbench/run.py)

agedchain = run.load_module("gen", "agedchain")
driver = run.load_module("drivers", "reindex_flush64")

with open(os.path.join(BENCH, "traffic", "flush64.json")) as _f:
    TRAFFIC = json.load(_f)
REHEARSAL = dict(TRAFFIC, **TRAFFIC["rehearse"])
SEED = 2147483777


def _node(datadir: str, *flags: str):
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    config = Config()
    config.parse_args(["-regtest", "-tpu=0", "-reindex", "-listen=0",
                       *flags, f"-datadir={datadir}"])
    return Node(config)


def _work_copy(chain: str, into) -> str:
    """The chain's block files alone, in a data directory of their own."""
    dst = os.path.join(into, "regtest", "blocks")
    os.makedirs(dst)
    src = os.path.join(chain, "regtest", "blocks")
    for leaf in os.listdir(src):
        if leaf.startswith("blk"):
            shutil.copy(os.path.join(src, leaf), dst)
    return str(into)


# -- the cadence -------------------------------------------------------------

def _coinbase_chain(datadir: str, blocks: int, file_bytes: int) -> None:
    """``blocks`` coinbase-only regtest blocks in block files of at most
    ``file_bytes``."""
    from bitcoincashplus_tpu.consensus.block import CBlock, CBlockHeader
    from bitcoincashplus_tpu.consensus.merkle import block_merkle_root
    from bitcoincashplus_tpu.consensus.params import (
        get_block_subsidy,
        regtest_params,
    )
    from bitcoincashplus_tpu.consensus.pow import check_proof_of_work
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.mining.assembler import bip34_coinbase_script_sig
    from bitcoincashplus_tpu.store.blockstore import BlockStore
    from bitcoincashplus_tpu.store.chainstatedb import BlockIndexDB, CoinsDB
    from bitcoincashplus_tpu.store.kvstore import KVStore
    from bitcoincashplus_tpu.validation.chainstate import ChainstateManager

    params = regtest_params()
    net_dir = os.path.join(datadir, "regtest")
    os.makedirs(os.path.join(net_dir, "blocks"))
    index_kv = KVStore(os.path.join(net_dir, "blocks", "index.sqlite"))
    coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
    store = BlockStore(net_dir, params.netmagic, max_file_size=file_bytes)
    cs = ChainstateManager(params, CoinsDB(coins_kv), store,
                           script_verifier=None,
                           index_db=BlockIndexDB(index_kv))
    clock = params.genesis.header.time
    for _ in range(blocks):
        tip = cs.tip()
        height, clock = tip.height + 1, clock + 60
        coinbase = CTransaction(
            version=1,
            vin=(CTxIn(COutPoint(), bip34_coinbase_script_sig(height)
                       + b"cadence", 0xFFFFFFFF),),
            vout=(CTxOut(get_block_subsidy(height, params.consensus),
                         b"\x51"),))
        root, _ = block_merkle_root(type("V", (), {"vtx": (coinbase,)})())
        header = CBlockHeader(
            version=0x20000000, hash_prev_block=tip.hash,
            hash_merkle_root=root, time=clock,
            bits=params.genesis.header.bits, nonce=0)
        while not check_proof_of_work(header.get_hash(), header.bits,
                                      params.consensus):
            header = header.with_nonce(header.nonce + 1)
        cs.process_new_block(CBlock(header, (coinbase,)))
    store.flush()
    store.close()
    index_kv.close()
    coins_kv.close()


def test_the_cadence_counts_connected_blocks_across_block_files(tmp_path):
    """Until PR 46 the import counted the records of a block file, the
    duplicate genesis among them, from 0 at the head of every file: this
    chain's second file begins inside a flush interval, where that count
    left 11 blocks unflushed under -flushinterval=8."""
    from bitcoincashplus_tpu import native
    from bitcoincashplus_tpu.consensus.params import regtest_params

    if not native.engine_available():
        pytest.skip("no native connect engine")
    chain = tmp_path / "chain"
    _coinbase_chain(str(chain), 30, 4000)
    files = sorted(leaf for leaf in os.listdir(chain / "regtest" / "blocks")
                   if leaf.startswith("blk"))
    assert len(files) == 2
    records = sum(1 for _ in ref.ref.read_block_files(
        str(chain / "regtest" / "blocks")))
    assert records == 31  # the genesis and the thirty
    datadir = _work_copy(str(chain), tmp_path / "node")
    watch = driver.ManifestWatch(os.path.join(datadir, "regtest")).start()
    try:
        node = _node(datadir, "-flushinterval=8")
    finally:
        versions = watch.stop()
    try:
        stats = node.last_import_stats
        assert node.chainstate.tip().height == 30
        by_hash = {idx.hash[::-1].hex(): idx.height
                   for idx in node.chainstate.block_index.values()}
    finally:
        node.close()
    # a flush after every 8th connected block, whichever file holds it,
    # and the closing one
    assert [e["height"] for e in stats["flush_log"]] == [8, 16, 24, 30]
    assert stats["flushes"] == 4 and stats["blocks"] == 30
    epochs = [e["epoch"] for e in stats["flush_log"]]
    assert epochs == list(range(epochs[0], epochs[0] + 4))
    # the second file begins inside an interval: no multiple of 8 blocks
    # lies in the first
    with open(chain / "regtest" / "blocks" / files[0], "rb") as f:
        in_first = f.read().count(regtest_params().netmagic) - 1
    assert 0 < in_first < 30 and in_first % 8
    # the manifest as a client saw it: never more than 8 blocks behind the
    # block the import connected next, each version one epoch on
    heights = [by_hash[v["best_block"]] for v in versions]
    assert heights == sorted(heights) and heights[-1] == 30
    assert all(b - a <= 8 for a, b in zip(heights, heights[1:]))
    assert [v["epoch"] for v in versions] == list(
        range(versions[0]["epoch"], versions[0]["epoch"] + len(versions)))


# -- the cell's chain against its reference, at rehearsal sizes --------------

@pytest.fixture(scope="module")
def aged(tmp_path_factory):
    """One rehearsal chain of two steady intervals, its summary and its
    reference."""
    chain = str(tmp_path_factory.mktemp("aged"))
    summary = agedchain.generate(chain, SEED, 2, REHEARSAL, workers=2)
    replay = ref.scan_chain(os.path.join(chain, "regtest", "blocks"), SEED,
                            8, REHEARSAL["flush_interval"])
    return chain, summary, replay


def test_the_shipped_cadence_against_the_reference(aged, tmp_path):
    from bitcoincashplus_tpu import native

    if not native.engine_available():
        pytest.skip("no native connect engine")
    chain, summary, replay = aged
    flushes = replay["flushes"]
    assert (replay["height"], replay["tip_hash"], replay["utxos"]) == (
        summary["tip_height"], summary["tip_hash"], summary["txouts"])
    assert replay["first_bad_height"] is None

    datadir = _work_copy(chain, tmp_path / "cadence")
    watch = driver.ManifestWatch(os.path.join(datadir, "regtest")).start()
    try:
        node = _node(datadir, "-flushinterval=64", "-dbcache=300")
    finally:
        versions = watch.stop()
    try:
        stats = node.last_import_stats
        store = node.store_info()
        tip = node.chainstate.tip()
        assert (tip.height, tip.hash[::-1].hex()) == (
            replay["height"], replay["tip_hash"])
        assert node.coins_db.count_coins() == replay["utxos"]
    finally:
        node.close()
    made = flushes[1:]
    assert stats["flushes"] == len(made) == summary["tip_height"] // 64 + 1
    assert [e["height"] for e in stats["flush_log"]] == [
        f["height"] for f in made]
    assert [e["rows"] for e in stats["flush_log"]] == [
        f["puts"] + f["deletes"] for f in made]
    assert stats["flush_puts"] == sum(f["puts"] for f in made)
    assert stats["flush_deletes"] == sum(f["deletes"] for f in made)
    assert stats["flush_rows"] == stats["flush_puts"] + stats["flush_deletes"]
    assert (stats["store_read_keys"] == stats["store_read_rows"]
            == replay["store_reads"] == summary["sigs"])
    assert stats["slow_path_blocks"] == 0
    # gettpuinfo.store counts every commit of the store's life: the
    # genesis state's and the closing ones around the import's
    assert store["rows_put"] == stats["flush_puts"] + 1
    assert store["rows_deleted"] == stats["flush_deletes"]
    assert store["commits"] >= stats["flushes"] + 1
    assert store["commit_seconds"] > 0
    # MuHash's old values: every row a flush deleted was on disk and had
    # been served to the import since the flush before, so the store
    # remembered it; sqlite is asked for what the bloom lets through of the
    # rest alone. That the remembered bytes were the persisted ones is what
    # the digests of the manifest's versions, below, prove.
    old = store["old_values"]
    assert old["remembered"] == sum(f["deletes"] for f in made)
    assert old["remembered"] == replay["store_reads"]
    assert old["looked_up"] <= (store["bloom"]["checked"]
                                - store["bloom"]["skipped"])
    assert old["found"] == 0 and store["remembered_rows"] == 0
    # every version a client saw: the reference's states in order, none
    # skipped, each one epoch on
    assert driver._walk_versions(versions, flushes) == {
        "unsound": 0, "missed": 0, "epoch_steps": 0}
    assert len(versions) >= len(driver._distinct(flushes))
    # one skipped, one out of order, one epoch twice: each is seen
    states = {(f["best_block"], f["digest"]) for f in flushes}
    loaded = [i for i, v in enumerate(versions)
              if (v["best_block"], v["muhash"]) in states and i]
    cut = versions[:loaded[2]] + versions[loaded[2] + 1:]
    assert driver._walk_versions(cut, flushes)["unsound"] >= 1
    assert driver._walk_versions(cut, flushes)["epoch_steps"] == 1
    assert driver._walk_versions(versions[:loaded[2]], flushes)["missed"] >= 1
    # the rows on disk, read without the program
    disk = ref.disk_rows(os.path.join(datadir, "regtest"))
    assert disk == {"rows": replay["utxos"],
                    "digest": flushes[-1]["digest"]}
    with open(os.path.join(datadir, "regtest", driver.MANIFEST)) as f:
        assert json.load(f)["muhash"] == disk["digest"]
    driver._drop_one_row(datadir)
    dropped = ref.disk_rows(os.path.join(datadir, "regtest"))
    assert dropped["rows"] == replay["utxos"] - 1
    assert dropped["digest"] != disk["digest"]

    # the same chain under one flush: the same tip, the same set
    once = _work_copy(chain, tmp_path / "once")
    node = _node(once, "-flushinterval=1000000")
    try:
        assert node.chainstate.tip().hash[::-1].hex() == replay["tip_hash"]
        assert node.last_import_stats["flushes"] == 1
        assert node.last_import_stats["store_read_rows"] == 0
        assert node.coins_db.muhash_digest().hex() == flushes[-1]["digest"]
    finally:
        node.close()


# -- the reference's arithmetic and the generator's rules --------------------

def _rows(seed: int, n: int) -> list:
    rng = random.Random(seed)
    return [(rng.randbytes(32) + struct.pack("<I", rng.randrange(4000)),
             ref.coin_row(b"", rng.randrange(600000), rng.random() < 0.1,
                          rng.randrange(21 * 10**14),
                          rng.randbytes(rng.choice((22, 23, 25, 35)))))
            for _ in range(n)]


def _muhash_case(seed: int, n: int, removed: int):
    from bitcoincashplus_tpu.store import muhash

    rows = _rows(seed, n)
    acc = muhash.MuHash()
    acc.apply([muhash.coin_product(rows)],
              [muhash.coin_product(rows[:removed])] if removed else [])
    mine = ref.product(k + v for k, v in rows)
    if removed:
        gone = ref.product(k + v for k, v in rows[:removed])
        mine = ref._mod_p(mine * pow(gone, -1, ref.P))
    assert ref.digest_of(mine) == acc.digest().hex()
    assert ref.element(rows[0][0] + rows[0][1]) == muhash.coin_element(
        *rows[0])
    assert (ref.P, ref.C) == (muhash.MUHASH_P, muhash.MUHASH_C)


def _coin_row_case(height: int, coinbase: bool, value: int, spk_len: int):
    from bitcoincashplus_tpu.consensus.tx import CTxOut
    from bitcoincashplus_tpu.validation.coins import Coin

    spk = bytes(range(spk_len % 256)) * (spk_len // 256 + 1)
    spk = spk[:spk_len]
    key = bytes(36)
    assert ref.coin_row(key, height, coinbase, value, spk) == (
        key + Coin(CTxOut(value, spk), height, coinbase).serialize())


def _plan_case(traffic: dict, intervals: int):
    """The layout's arithmetic: the signatures of every interval are whole
    buckets, the blocks fit the interval, and no dense block is nearer than
    one interval and a block to the fan-out it spends."""
    lay = agedchain.plan(traffic, intervals)
    span, lanes = lay["flush_interval"], traffic["lanes"]
    assert lay["dense_blocks"] + lay["fan_blocks"] <= span
    assert lay["dense_inputs"] + lay["fan_txs"] == (
        lanes * lay["buckets_per_interval"])
    assert lay["runway_fan_txs"] + lay["pad_inputs"] == lanes
    assert lay["fan_txs"] * traffic["fan_k"] >= lay["dense_inputs"]
    assert lay["sigs"] == lanes * lay["buckets"]
    youngest = span + (span - lay["dense_blocks"] + 1) - lay["fan_blocks"]
    assert youngest >= span + 1
    assert lay["payout"] * lay["payout_blocks"] >= lay["payout_coins"]
    # the first spend of a payout coinbase is 100 blocks after the last
    assert span * agedchain.RUNWAY_FAN_INTERVAL + 1 - lay["payout_blocks"] \
        == agedchain.MATURITY
    puts = lay["fan_txs"] * traffic["fan_k"]
    assert abs(puts - lay["dense_inputs"]) <= 0.02 * puts


def _replay_case(aged):
    """The generated chain as the reference replays it: no input younger
    than a flush, no dense input younger than 65 blocks, every interval's
    signatures whole buckets, steady intervals alike."""
    _, summary, replay = aged
    lanes = REHEARSAL["lanes"]
    assert replay["young_inputs"] == replay["young_dense_inputs"] == 0
    assert replay["youngest_dense_age"] >= 65
    assert replay["store_reads"] == replay["signed_inputs"] == summary["sigs"]
    assert all(f["signatures"] % lanes == 0 for f in replay["flushes"])
    steady = replay["flushes"][-3:-1]
    assert [f["height"] for f in steady] == [256, 320]
    for key in ("puts", "deletes"):
        a, b = (f[key] for f in steady)
        assert abs(a - b) <= 0.02 * a
    assert abs(steady[0]["puts"] - steady[0]["deletes"]) <= (
        0.06 * steady[0]["puts"])  # a rehearsal's coinbases weigh more
    assert summary["max_block_bytes"] <= REHEARSAL["block_bytes"]


def _young_coin_case(tmp_path):
    """The fault young-coin: one dense input spends a coin of its own
    interval, and the reference counts it."""
    chain = str(tmp_path / "young")
    summary = agedchain.generate(chain, SEED, 1, REHEARSAL, workers=2,
                                 fault="young-coin")
    replay = ref.scan_chain(os.path.join(chain, "regtest", "blocks"), SEED,
                            8, REHEARSAL["flush_interval"])
    assert summary["fault_at"]["height"] > 192
    assert replay["young_dense_inputs"] == replay["young_inputs"] == 1
    assert replay["store_reads"] == summary["sigs"] - 1
    assert replay["youngest_dense_age"] < 64
    assert replay["first_bad_height"] is None


@pytest.mark.parametrize("case", [
    ("muhash", 1, 1, 0), ("muhash", 2, 3, 1), ("muhash", 3, 70, 0),
    ("muhash", 4, 200, 64), ("muhash", 5, 130, 130),
    ("coin_row", 0, True, 50 * 10**8, 25), ("coin_row", 126, False, 252, 23),
    ("coin_row", 32768, False, 65535, 253), ("coin_row", 2**31, True,
                                             2**32, 70000),
    ("plan", "window", 2), ("plan", "window", 1), ("plan", "window", 0),
    ("plan", "window", 5), ("plan", "rehearsal", 2), ("plan", "rehearsal", 1),
    ("plan", "rehearsal", 0), ("replay",), ("young-coin",),
], ids=lambda case: "-".join(str(part) for part in case))
def test_reference_arithmetic_and_generator_rules(case, request, tmp_path):
    kind, *args = case
    if kind == "muhash":
        _muhash_case(*args)
    elif kind == "coin_row":
        _coin_row_case(*args)
    elif kind == "plan":
        _plan_case(TRAFFIC if args[0] == "window" else REHEARSAL, args[1])
    elif kind == "replay":
        _replay_case(request.getfixturevalue("aged"))
    else:
        _young_coin_case(tmp_path)


def test_the_window_is_the_traffic_files_two_intervals():
    """--seconds 30 (BENCHMARK.json's run_seconds) gives the two steady
    intervals the traffic file states, a traced run one."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    buckets = round(seconds * TRAFFIC["buckets_per_window_second"])
    assert agedchain.intervals_for(TRAFFIC, buckets) == TRAFFIC["intervals"]
    assert abs(agedchain.plan(TRAFFIC, TRAFFIC["intervals"])["buckets"]
               - buckets) <= 1
    assert agedchain.intervals_for(TRAFFIC, TRAFFIC["trace_buckets"]) == 1
    assert agedchain.plan(TRAFFIC, 1)["buckets"] == TRAFFIC["trace_buckets"]
    assert agedchain.plan(TRAFFIC, 0)["buckets"] == TRAFFIC["warm_buckets"]
