"""Differential tests for the native block-connect engine
(native/connect.cpp) against the Python validation engine
(validation/chainstate.py) — the fast -reindex import path's correctness
contract: same undo blobs, same chainstate rows, same accept/reject
verdicts, and sig-scan records that match the Python interpreter's
deferred SigCheckRecords bit for bit.

Reference: src/validation.cpp ConnectBlock / LoadExternalBlockFile — the
reference's import pipeline is a single C++ engine; here the native engine
must agree with the Python reference implementation instead.
"""

from __future__ import annotations

import os
import struct

import pytest

from bitcoincashplus_tpu import native
from bitcoincashplus_tpu.consensus.block import CBlock, CBlockHeader
from bitcoincashplus_tpu.consensus.params import (
    get_block_subsidy,
    regtest_params,
)
from bitcoincashplus_tpu.consensus.pow import compact_to_target
from bitcoincashplus_tpu.consensus.serialize import ByteReader
from bitcoincashplus_tpu.consensus.tx import (
    COutPoint,
    CTransaction,
    CTxIn,
    CTxOut,
)
from bitcoincashplus_tpu.crypto.hashes import sha256d
from bitcoincashplus_tpu.mining.assembler import bip34_coinbase_script_sig
from bitcoincashplus_tpu.script.interpreter import (
    DeferringSignatureChecker,
    VerifyScript,
)
from bitcoincashplus_tpu.script.script import script_int
from bitcoincashplus_tpu.script.sighash import SighashCache
from bitcoincashplus_tpu.store.blockstore import MemoryBlockStore
from bitcoincashplus_tpu.validation.chainstate import (
    BlockValidationError,
    ChainstateManager,
)
from bitcoincashplus_tpu.validation.coins import MemoryCoinsView
from bitcoincashplus_tpu.validation.scriptcheck import block_script_flags
from bitcoincashplus_tpu.wallet.keys import CKey
from bitcoincashplus_tpu.wallet.signing import sign_transaction

pytestmark = pytest.mark.skipif(
    not native.engine_available(), reason="native connect engine unavailable"
)

PARAMS = regtest_params()
KEY = CKey(0xB00B1E5 * 31, compressed=True)
SPK = KEY.p2pkh_script()


def _key_for(ident):
    return KEY if ident in (KEY.pubkey_hash, KEY.pubkey) else None


def _mine(header: CBlockHeader) -> CBlockHeader:
    target, _ = compact_to_target(header.bits)
    nonce = 0
    raw = bytearray(header.serialize())
    while True:
        struct.pack_into("<I", raw, 76, nonce)
        if int.from_bytes(sha256d(bytes(raw)), "little") <= target:
            return header.with_nonce(nonce)
        nonce += 1


def _block(prev_hash: bytes, height: int, t: int, txs=()) -> CBlock:
    from bitcoincashplus_tpu.consensus.merkle import block_merkle_root

    fees = 10_000 * len(txs)
    coinbase = CTransaction(
        version=1,
        vin=(CTxIn(COutPoint(), bip34_coinbase_script_sig(height) + b"t",
                   0xFFFFFFFF),),
        vout=(CTxOut(fees + get_block_subsidy(height, PARAMS.consensus),
                     SPK),),
    )
    vtx = (coinbase, *txs)

    class _V:
        pass

    v = _V()
    v.vtx = vtx
    root, _ = block_merkle_root(v)
    header = CBlockHeader(
        version=0x20000000, hash_prev_block=prev_hash,
        hash_merkle_root=root, time=t,
        bits=PARAMS.genesis.header.bits, nonce=0,
    )
    return CBlock(_mine(header), vtx)


def _spend(prevouts, values, n_out=1) -> CTransaction:
    total = sum(values) - 10_000
    unsigned = CTransaction(
        version=1,
        vin=tuple(CTxIn(op, b"", 0xFFFFFFFE) for op in prevouts),
        vout=tuple(CTxOut(total // n_out, SPK) for _ in range(n_out)),
    )
    return sign_transaction(unsigned, [(SPK, v) for v in values], _key_for,
                            enable_forkid=True)


class _Chain:
    """A tiny spendable regtest chain built through the PYTHON engine,
    with per-block raw bytes and undo blobs recorded for comparison."""

    def __init__(self, runway=102):
        self.cs = ChainstateManager(PARAMS, MemoryCoinsView(),
                                    MemoryBlockStore(), script_verifier=None)
        self.undo = {}
        orig = self.cs.block_store.put_undo
        self.cs.block_store.put_undo = (
            lambda h, raw: (self.undo.__setitem__(h, raw), orig(h, raw))[1]
        )
        self.raws = []
        self.t = PARAMS.genesis.header.time
        self.coinbases = []  # (txid, value)
        for _ in range(runway):
            blk = self.push()
            self.coinbases.append((blk.vtx[0].txid, blk.vtx[0].vout[0].value))

    def push(self, txs=()):
        tip = self.cs.tip()
        self.t += 60
        blk = _block(tip.hash, tip.height + 1, self.t, tuple(txs))
        self.cs.process_new_block(blk)
        self.raws.append(blk.serialize())
        return blk

    def spendable(self, i):
        return self.coinbases[i]


@pytest.fixture(scope="module")
def chain():
    c = _Chain()
    # two spend blocks: a fan-out then a many-input spend (sig-dense shape)
    txid, value = c.spendable(0)
    fan = _spend([COutPoint(txid, 0)], [value], n_out=8)
    c.push([fan])
    per = fan.vout[0].value
    spend = _spend([COutPoint(fan.txid, i) for i in range(8)], [per] * 8)
    c.push([spend])
    # a 2-tx chain within one block (intra-block spend)
    txid2, value2 = c.spendable(1)
    a = _spend([COutPoint(txid2, 0)], [value2], n_out=2)
    b = _spend([COutPoint(a.txid, 0)], [a.vout[0].value])
    c.push([a, b])
    return c


def _engine_for(chain) -> native.ConnectEngine:
    eng = native.ConnectEngine()
    genesis = PARAMS.genesis
    eng.set_best(genesis.get_hash())
    for tx in genesis.vtx:
        for i, out in enumerate(tx.vout):
            eng.insert(tx.txid + struct.pack("<I", i), 1, out.value,
                       out.script_pubkey)
    return eng


def _replay(chain, eng, want_sigs=True, upto=None):
    """Run the recorded raw blocks through the native engine; returns the
    per-block NativeConnectResults."""
    results = []
    height = 0
    headers = [PARAMS.genesis.header]
    for raw in chain.raws[:upto]:
        height += 1
        times = sorted(h.time for h in headers[-11:])
        mtp = times[len(times) // 2]
        flags = block_script_flags(height,
                                   struct.unpack_from("<I", raw, 68)[0],
                                   PARAMS)
        res = eng.connect_block(
            raw, height, get_block_subsidy(height, PARAMS.consensus),
            PARAMS.max_block_size, PARAMS.consensus.coinbase_maturity, mtp,
            script_int(height), flags, want_sigs=want_sigs)
        results.append(res)
        headers.append(CBlockHeader.deserialize(ByteReader(raw[:80])))
    return results


def test_undo_blobs_match_python(chain):
    eng = _engine_for(chain)
    results = _replay(chain, eng)
    assert len(results) == len(chain.raws)
    for res in results:
        assert chain.undo[res.block_hash] == res.undo
    assert eng.best() == chain.cs.tip().hash
    eng.close()


def test_flush_rows_match_python_coins(chain):
    eng = _engine_for(chain)
    _replay(chain, eng)
    chain.cs.coins.flush()
    py = {
        op.hash + struct.pack("<I", op.n): coin.serialize()
        for op, coin in chain.cs.coins.base.all_coins()
    }
    nat = {k: ser for k, ser in eng.flush_entries() if ser is not None}
    # the genesis coin was seeded CLEAN into the engine (it is in the base
    # store in real operation) — exclude it from the dirty-flush comparison
    gen_txid = PARAMS.genesis.vtx[0].txid
    py.pop(gen_txid + struct.pack("<I", 0), None)
    assert nat == py
    eng.close()


def test_sigscan_matches_interpreter_records(chain):
    """The native P2PKH scan's (pubkey, r, s, msg) blobs must equal the
    records the Python interpreter defers for the same blocks."""
    eng = _engine_for(chain)
    results = _replay(chain, eng)
    for raw, res in zip(chain.raws, results):
        if res.n_inputs == 0:
            continue
        assert int((res.sig_status == 0).sum()) == res.n_inputs
        block = CBlock.from_bytes(raw)
        height = chain.cs.block_index[res.block_hash].height
        flags = block_script_flags(height, block.header.time, PARAMS)
        g = 0
        for t_i, tx in enumerate(block.vtx[1:], start=1):
            cache = SighashCache(tx)
            for in_i, txin in enumerate(tx.vin):
                records = []
                spk = bytes(res.spent_spk_blob[
                    int(res.spent_spk_offsets[g]):
                    int(res.spent_spk_offsets[g + 1])])
                checker = DeferringSignatureChecker(
                    tx, in_i, int(res.spent_values[g]), records, cache)
                VerifyScript(txin.script_sig, spk, flags, checker)
                assert len(records) == 1
                rec = records[0]
                assert rec.pubkey[0].to_bytes(32, "big") == \
                    res.sig_pub[g, :32].tobytes()
                assert rec.pubkey[1].to_bytes(32, "big") == \
                    res.sig_pub[g, 32:].tobytes()
                assert rec.r.to_bytes(32, "big") == \
                    res.sig_rs[g, :32].tobytes()
                assert rec.s.to_bytes(32, "big") == \
                    res.sig_rs[g, 32:].tobytes()
                assert rec.msg_hash.to_bytes(32, "big") == \
                    res.sig_msg[g].tobytes()
                assert (t_i, in_i) == (int(res.sig_txin[g, 0]),
                                       int(res.sig_txin[g, 1]))
                g += 1
    eng.close()


def test_dispatch_packed_verifies(chain):
    """End to end: native sigscan blobs through the packed batch dispatch
    (CPU lane here) — all lanes verify; a corrupted message fails its
    lane only."""
    import numpy as np

    from bitcoincashplus_tpu.ops import ecdsa_batch

    eng = _engine_for(chain)
    results = _replay(chain, eng)
    res = next(r for r in results if r.n_inputs >= 8)
    ok = ecdsa_batch.dispatch_packed(
        res.sig_pub, res.sig_rs, res.sig_msg, res.sig_rn, res.sig_wrap,
        backend="cpu").result()
    assert bool(np.all(ok))
    bad_msg = res.sig_msg.copy()
    bad_msg[3, 0] ^= 0xFF
    ok = ecdsa_batch.dispatch_packed(
        res.sig_pub, res.sig_rs, bad_msg, res.sig_rn, res.sig_wrap,
        backend="cpu").result()
    assert not ok[3] and bool(np.all(np.delete(ok, 3)))
    eng.close()


def test_missing_inputs_roundtrip(chain):
    """Spends of flushed-out coins surface as EngineMissing; inserting the
    base rows and retrying succeeds (the import loop's miss servicing)."""
    eng = _engine_for(chain)
    _replay(chain, eng, upto=len(chain.raws) - 1)
    # flush-and-clear, then connect the last block: its inputs are gone
    rows = {k: ser for k, ser in eng.flush_entries()}
    best = eng.best()
    eng.clear()
    eng.set_best(best)
    height = len(chain.raws)
    raw = chain.raws[-1]
    times = sorted(
        CBlockHeader.deserialize(ByteReader(r[:80])).time
        for r in chain.raws[-12:-1]
    )
    mtp = times[len(times) // 2]
    flags = block_script_flags(height, struct.unpack_from("<I", raw, 68)[0],
                               PARAMS)

    def connect():
        return eng.connect_block(
            raw, height, get_block_subsidy(height, PARAMS.consensus),
            PARAMS.max_block_size, PARAMS.consensus.coinbase_maturity, mtp,
            script_int(height), flags, want_sigs=True)

    with pytest.raises(native.EngineMissing) as exc:
        connect()
    for key in exc.value.keys:
        ser = rows.get(key)
        assert ser is not None
        r = ByteReader(ser)
        from bitcoincashplus_tpu.consensus.serialize import (
            deser_compact_size,
            deser_var_bytes,
        )

        code = deser_compact_size(r, range_check=False)
        value = deser_compact_size(r, range_check=False)
        eng.insert(key, code, value, deser_var_bytes(r))
    res = connect()
    assert chain.undo[res.block_hash] == res.undo
    eng.close()


def test_invalid_blocks_rejected_with_matching_reasons(chain):
    """Mutated blocks must be rejected by BOTH engines, and the native
    reason must map onto the Python reject reason."""
    eng = _engine_for(chain)
    _replay(chain, eng, upto=len(chain.raws) - 1)
    height = len(chain.raws)
    raw = bytearray(chain.raws[-1])
    times = sorted(
        CBlockHeader.deserialize(ByteReader(r[:80])).time
        for r in chain.raws[-12:-1]
    )
    mtp = times[len(times) // 2]
    flags = block_script_flags(height, struct.unpack_from("<I", raw, 68)[0],
                               PARAMS)

    def native_verdict(mutated: bytes):
        try:
            eng.connect_block(
                bytes(mutated), height,
                get_block_subsidy(height, PARAMS.consensus),
                PARAMS.max_block_size, PARAMS.consensus.coinbase_maturity,
                mtp, script_int(height), flags, want_sigs=True,
                commit=False)
        except native.EngineError as e:
            eng.abort()
            return e.reason
        except native.EngineMissing:
            eng.abort()
            return "missing"
        eng.abort()
        return None

    def python_verdict(mutated: bytes):
        try:
            blk = CBlock.from_bytes(bytes(mutated))
        except Exception:
            return "deserialize"
        try:
            chain.cs.check_block(blk, check_pow=False)
            # context + connect on a throwaway view
            from bitcoincashplus_tpu.validation.coins import CoinsCache
            from bitcoincashplus_tpu.validation.chain import CBlockIndex

            idx = CBlockIndex(blk.header, blk.get_hash(), chain.cs.tip())
            chain.cs.connect_block(blk, idx, check_scripts=False,
                                   view=CoinsCache(chain.cs.coins))
        except BlockValidationError as e:
            return e.reason
        return None

    # merkle-root corruption
    bad = bytearray(raw)
    bad[40] ^= 0xFF
    assert native_verdict(bad) == "bad-txnmrklroot" == python_verdict(bad)
    # truncated tail
    bad = raw[: len(raw) - 3]
    assert native_verdict(bad) == "deserialize" == python_verdict(bad)
    # valid block connects cleanly in both (sanity that the fixture works)
    assert native_verdict(raw) is None
    eng.close()


def test_clean_inserts_not_flushed(chain):
    eng = native.ConnectEngine()
    eng.insert(b"\x11" * 36, 7, 1234, b"\x51")
    assert eng.get(b"\x11" * 36) == (7, 1234, b"\x51")
    assert eng.flush_entries() == []
    assert eng.entries() == 1
    eng.clear()
    assert eng.entries() == 0
    eng.close()


def test_mutation_matrix_verdicts_agree(chain):
    """Broader native-vs-Python verdict agreement: structured mutations of
    a valid block must be rejected by BOTH engines with the same reason
    class (the fast import falls back to Python on any native error, so
    agreement on 'invalid at all' is the safety bar; the reason match is
    the quality bar)."""
    import random

    eng = _engine_for(chain)
    _replay(chain, eng, upto=len(chain.raws) - 1)
    height = len(chain.raws)
    raw = chain.raws[-1]
    times = sorted(
        CBlockHeader.deserialize(ByteReader(r[:80])).time
        for r in chain.raws[-12:-1]
    )
    mtp = times[len(times) // 2]
    flags = block_script_flags(height, struct.unpack_from("<I", raw, 68)[0],
                               PARAMS)

    def native_verdict(mutated: bytes):
        try:
            eng.connect_block(
                bytes(mutated), height,
                get_block_subsidy(height, PARAMS.consensus),
                PARAMS.max_block_size, PARAMS.consensus.coinbase_maturity,
                mtp, script_int(height), flags, want_sigs=True,
                commit=False)
        except native.EngineError as e:
            eng.abort()
            return e.reason
        except native.EngineMissing:
            eng.abort()
            return "missing-inputs"
        eng.abort()
        return None

    # a Python chainstate at height len-1: the fixture's cs already holds
    # the final block, whose coinbase would trip BIP30 and mask the real
    # reason for any mutation that keeps the original coinbase
    cs2 = ChainstateManager(PARAMS, MemoryCoinsView(), MemoryBlockStore(),
                            script_verifier=None)
    for r in chain.raws[:-1]:
        cs2.process_new_block(CBlock.from_bytes(r))

    def python_verdict(mutated: bytes):
        try:
            blk = CBlock.from_bytes(bytes(mutated))
        except Exception:
            return "deserialize"
        from bitcoincashplus_tpu.validation.chain import CBlockIndex
        from bitcoincashplus_tpu.validation.coins import CoinsCache

        try:
            cs2.check_block(blk, check_pow=False)
            idx = CBlockIndex(blk.header, blk.get_hash(), cs2.tip())
            cs2.connect_block(blk, idx, check_scripts=False,
                              view=CoinsCache(cs2.coins))
        except BlockValidationError as e:
            return e.reason
        return None

    block = CBlock.from_bytes(raw)

    def rebuild(vtx, header=None):
        from bitcoincashplus_tpu.consensus.merkle import block_merkle_root

        class _V:
            pass

        v = _V()
        v.vtx = tuple(vtx)
        root, _ = block_merkle_root(v)
        hdr = header or block.header
        hdr = CBlockHeader(
            version=hdr.version, hash_prev_block=hdr.hash_prev_block,
            hash_merkle_root=root, time=hdr.time, bits=hdr.bits,
            nonce=hdr.nonce)
        return CBlock(hdr, tuple(vtx)).serialize()

    spend = block.vtx[1]
    cases = []
    # duplicate input within a tx
    t = CTransaction(spend.version,
                     (spend.vin[0], spend.vin[0]) + spend.vin[1:],
                     spend.vout, spend.locktime)
    cases.append(("dup-input", rebuild([block.vtx[0], t])))
    # output value negative
    t = CTransaction(spend.version, spend.vin,
                     (CTxOut(-1, spend.vout[0].script_pubkey),),
                     spend.locktime)
    cases.append(("neg-value", rebuild([block.vtx[0], t])))
    # in < out (value conjured from nowhere)
    t = CTransaction(spend.version, spend.vin,
                     (CTxOut(spend.vout[0].value + 10**12,
                             spend.vout[0].script_pubkey),),
                     spend.locktime)
    cases.append(("in-below-out", rebuild([block.vtx[0], t])))
    # spend of a nonexistent outpoint
    t = CTransaction(spend.version,
                     (CTxIn(COutPoint(b"\x77" * 32, 1), spend.vin[0].script_sig,
                            0xFFFFFFFE),),
                     spend.vout, spend.locktime)
    cases.append(("missing-prevout", rebuild([block.vtx[0], t])))
    # double coinbase
    cases.append(("double-coinbase",
                  rebuild([block.vtx[0], block.vtx[0], *block.vtx[1:]])))
    # no coinbase first
    cases.append(("cb-not-first", rebuild(list(block.vtx[1:]))))
    # corrupt a signature byte (NULLFAIL-era: script error)
    mutated = bytearray(raw)
    # find the first scriptSig push in the spend tx region and flip a byte
    off = raw.index(spend.vin[0].script_sig[:20])
    mutated[off + 5] ^= 0x01
    cases.append(("bad-sig-byte", bytes(mutated)))
    # random byte flips (parse-level chaos)
    rng = random.Random(7)
    for i in range(20):
        m = bytearray(raw)
        pos = rng.randrange(80, len(m))
        m[pos] ^= 1 << rng.randrange(8)
        cases.append((f"flip-{pos}", bytes(m)))

    for name, mut in cases:
        nv = native_verdict(mut)
        pv = python_verdict(mut)
        if name == "bad-sig-byte":
            # native catches it in the sigscan; the scripts-off python
            # connect above doesn't check sigs — native must reject, and
            # the full python interpreter agrees (covered by the
            # scriptcheck differential suites); only assert native reject
            assert nv is not None, name
            continue
        assert (nv is None) == (pv is None), (name, nv, pv)
        if nv is not None and nv != "missing-inputs" \
                and pv != "bad-txns-duplicate" and nv != "deserialize":
            # exact reason match, modulo check-order differences where a
            # mutation violates several rules at once
            assert nv == pv or {nv, pv} <= {
                "bad-txns-inputs-missingorspent", "bad-txns-BIP30",
                "bad-cb-multiple", "bad-txnmrklroot",
            }, (name, nv, pv)
    eng.close()


def test_fast_import_falls_back_on_invalid_block(tmp_path):
    """Node-level fast/slow interplay: a blk file containing a valid chain,
    an INVALID block (premature coinbase spend), then more valid blocks on
    the honest tip. The native fast path must reject the bad block, defer
    to the Python engine for the authoritative verdict, and keep importing
    the valid remainder."""
    import os

    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node
    from bitcoincashplus_tpu.store.blockstore import BlockStore
    from bitcoincashplus_tpu.store.chainstatedb import BlockIndexDB, CoinsDB
    from bitcoincashplus_tpu.store.kvstore import KVStore
    from bitcoincashplus_tpu.validation.chain import BlockStatus

    net_dir = os.path.join(tmp_path, "regtest")
    blocks_dir = os.path.join(net_dir, "blocks")
    os.makedirs(blocks_dir, exist_ok=True)
    index_kv = KVStore(os.path.join(blocks_dir, "index.sqlite"))
    coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
    store = BlockStore(net_dir, PARAMS.netmagic)
    cs = ChainstateManager(PARAMS, CoinsDB(coins_kv), store,
                           script_verifier=None,
                           index_db=BlockIndexDB(index_kv))

    t = PARAMS.genesis.header.time
    coinbases = []
    for _ in range(103):
        t += 60
        tip = cs.tip()
        blk = _block(tip.hash, tip.height + 1, t, ())
        cs.process_new_block(blk)
        coinbases.append((blk.vtx[0].txid, blk.vtx[0].vout[0].value))

    # invalid: spends the height-103 coinbase at height 104 (immature) —
    # write the raw record into the blk file BEHIND the store's back
    tip = cs.tip()
    bad_spend = _spend([COutPoint(coinbases[-1][0], 0)], [coinbases[-1][1]])
    t += 60
    bad = _block(tip.hash, tip.height + 1, t, (bad_spend,))
    # valid continuation on the same tip: spends the MATURE height-1 coin
    good_spend = _spend([COutPoint(coinbases[0][0], 0)], [coinbases[0][1]])
    good = _block(tip.hash, tip.height + 1, t + 60, (good_spend,))
    raw_bad = bad.serialize()
    raw_good = good.serialize()
    with open(os.path.join(blocks_dir, "blk00000.dat"), "ab") as f:
        f.write(PARAMS.netmagic + struct.pack("<I", len(raw_bad)) + raw_bad)
        f.write(PARAMS.netmagic + struct.pack("<I", len(raw_good)) + raw_good)
    cs.flush()
    store.close()
    index_kv.close()
    coins_kv.close()

    cfg = Config()
    cfg.args["datadir"] = [str(tmp_path)]
    cfg.args["regtest"] = ["1"]
    cfg.args["reindex"] = ["1"]
    node = Node(config=cfg)
    try:
        assert node.chainstate.tip().hash == good.get_hash()
        bad_idx = node.chainstate.block_index.get(bad.get_hash())
        assert bad_idx is not None
        assert bad_idx.status & BlockStatus.FAILED_MASK
        if node.last_import_stats:  # native path ran
            assert node.last_import_stats["slow_path_blocks"] >= 1
    finally:
        node.close()


class _DiskChain:
    """A regtest chain written to a datadir's block files through the
    Python engine with no script verifier (it stores whatever spends it is
    given), for Node(-reindex) to import: 102 coinbase blocks to start."""

    def __init__(self, tmp_path):
        from bitcoincashplus_tpu.store.blockstore import BlockStore
        from bitcoincashplus_tpu.store.chainstatedb import (
            BlockIndexDB,
            CoinsDB,
        )
        from bitcoincashplus_tpu.store.kvstore import KVStore

        self.datadir = str(tmp_path)
        net_dir = os.path.join(tmp_path, "regtest")
        blocks_dir = os.path.join(net_dir, "blocks")
        os.makedirs(blocks_dir, exist_ok=True)
        self.index_kv = KVStore(os.path.join(blocks_dir, "index.sqlite"))
        self.coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
        self.store = BlockStore(net_dir, PARAMS.netmagic)
        self.cs = ChainstateManager(
            PARAMS, CoinsDB(self.coins_kv), self.store, script_verifier=None,
            index_db=BlockIndexDB(self.index_kv))
        self.t = PARAMS.genesis.header.time
        self.coinbases = [self.push().vtx[0] for _ in range(102)]

    def push(self, txs=()):
        self.t += 60
        tip = self.cs.tip()
        blk = _block(tip.hash, tip.height + 1, self.t, txs)
        self.cs.process_new_block(blk)
        return blk

    def fund(self, i: int, spks) -> CTransaction:
        """Coinbase ``i`` paid out to ``spks`` in equal shares."""
        value = self.coinbases[i].vout[0].value
        each = (value - 10_000) // len(spks)
        unsigned = CTransaction(
            1, (CTxIn(COutPoint(self.coinbases[i].txid, 0), b"",
                      0xFFFFFFFE),),
            tuple(CTxOut(each, spk) for spk in spks))
        return sign_transaction(unsigned, [(SPK, value)], _key_for,
                                enable_forkid=True)

    def reindex(self):
        """Close the stores and import the block files with a new Node."""
        from bitcoincashplus_tpu.node.config import Config
        from bitcoincashplus_tpu.node.node import Node

        self.cs.flush()
        self.store.close()
        self.index_kv.close()
        self.coins_kv.close()
        cfg = Config()
        cfg.args["datadir"] = [self.datadir]
        cfg.args["regtest"] = ["1"]
        cfg.args["reindex"] = ["1"]
        return Node(config=cfg)


def test_fast_import_keeps_a_schnorr_signature_in_the_native_engine(tmp_path):
    """A 65-byte Schnorr signature under a pay-to-pubkey output reaches the
    generic-script leg of the native import (the P2PKH scan never matches
    it) and, from the fork height on, the P2PK template takes it as a
    Schnorr lane (until PR 44 it declined, the record was no ECDSA lane and
    the block took the slow path through the Python engine): the block
    stays in the native engine and the lane rides a bucket of its own
    kind (tests/unit/test_schnorr_lanes.py holds the kinds apart)."""
    from bitcoincashplus_tpu.crypto import secp256k1 as secp
    from bitcoincashplus_tpu.script.script import p2pk_script, push_data_raw
    from bitcoincashplus_tpu.script.sighash import signature_hash

    chain = _DiskChain(tmp_path)
    # a pay-to-pubkey output, then its spend under a Schnorr signature
    pk_spk = p2pk_script(KEY.pubkey)
    fund = chain.fund(0, [pk_spk])
    chain.push((fund,))
    value = fund.vout[0].value
    unsigned = CTransaction(
        1, (CTxIn(COutPoint(fund.txid, 0), b"", 0xFFFFFFFE),),
        (CTxOut(value - 10_000, SPK),))
    digest = signature_hash(pk_spk, unsigned, 0, 0x41, value,
                            enable_forkid=True)
    r, s = secp.schnorr_sign(KEY.secret, int.from_bytes(digest, "big"))
    sig = r.to_bytes(32, "big") + s.to_bytes(32, "big") + b"\x41"
    spend = CTransaction(
        1, (CTxIn(unsigned.vin[0].prevout, push_data_raw(sig), 0xFFFFFFFE),),
        unsigned.vout)
    chain.push((spend,))
    last = chain.push((_spend([COutPoint(chain.coinbases[1].txid, 0)],
                              [chain.coinbases[1].vout[0].value]),))
    node = chain.reindex()
    try:
        assert node.chainstate.tip().hash == last.get_hash()
        stats = node.last_import_stats
        assert stats["slow_path_blocks"] == 0
        assert stats["fallback_inputs"] == 1  # the Schnorr input, once
        assert (stats["template_inputs"], stats["interp_inputs"]) == (1, 0)
        assert stats["schnorr_inputs"] == 1
    finally:
        node.close()


def test_fast_import_joins_template_lanes_and_the_interpreters(tmp_path):
    """One block whose generic-script leg is half the templates' and half
    the interpreter's: a bare 1-of-2 and a pay-to-pubkey input fit, the same
    two scripts behind ``OP_1 OP_VERIFY`` fit no template and go through
    VerifyScript. The block connects through the native engine, the
    interpreter's group lies behind the templates' lanes, and every walk
    succeeds on the batch's verdicts."""
    from bitcoincashplus_tpu.ops import ecdsa_batch
    from bitcoincashplus_tpu.script.script import (
        multisig_script,
        p2pk_script,
        push_data_raw,
    )
    from bitcoincashplus_tpu.wallet.signing import make_signature

    other = CKey(0xFACADE, compressed=True)
    prefix = b"\x51\x69"  # OP_1 OP_VERIFY
    one_of_two = multisig_script(1, [other.pubkey, KEY.pubkey])
    spks = [one_of_two, prefix + p2pk_script(KEY.pubkey),
            prefix + one_of_two, p2pk_script(other.pubkey)]
    signers = [KEY, KEY, other, other]
    chain = _DiskChain(tmp_path)
    fund = chain.fund(0, spks)
    chain.push((fund,))
    each = fund.vout[0].value
    unsigned = CTransaction(
        1, tuple(CTxIn(COutPoint(fund.txid, i), b"", 0xFFFFFFFE)
                 for i in range(4)),
        (CTxOut(4 * each - 10_000, SPK),))
    sigs = [push_data_raw(make_signature(key, spk, unsigned, i, each,
                                         enable_forkid=True))
            for i, (key, spk) in enumerate(zip(signers, spks))]
    script_sigs = [b"\x00" + sigs[0], sigs[1], b"\x00" + sigs[2], sigs[3]]
    spend = CTransaction(
        1, tuple(CTxIn(txin.prevout, ss, txin.sequence)
                 for txin, ss in zip(unsigned.vin, script_sigs)),
        unsigned.vout)
    last = chain.push((spend,))
    before = ecdsa_batch.STATS.snapshot()
    node = chain.reindex()
    try:
        assert node.chainstate.tip().hash == last.get_hash()
        stats = node.last_import_stats
    finally:
        node.close()
    after = ecdsa_batch.STATS.snapshot()
    assert stats["slow_path_blocks"] == 0
    assert stats["fast_inputs"] == 1  # the funding transaction's
    assert (stats["fallback_inputs"], stats["template_inputs"],
            stats["interp_inputs"]) == (4, 2, 2)
    assert (stats["multisig_groups"], stats["multisig_lanes"]) == (2, 4)
    assert stats["multisig_group_confirms"] == 0
    assert after["eager_multisig_sigs"] == before["eager_multisig_sigs"]
    assert after["reject_confirm_sigs"] == before["reject_confirm_sigs"]


@pytest.mark.skipif(not os.environ.get("BCP_SLOW_TESTS"),
                    reason="slow randomized campaign (BCP_SLOW_TESTS=1)")
def test_randomized_differential_campaign():
    """170-block randomized stream (random input counts, fan-outs,
    intra-block chains) through both engines: identical undo blobs and
    final coin sets. Run with BCP_SLOW_TESTS=1 (several minutes)."""
    import random

    rng = random.Random(20260731)
    chain = _Chain(runway=140)
    heights = {txid: i + 1 for i, (txid, _v) in enumerate(chain.coinbases)}
    for _bi in range(30):
        txs = []
        next_h = chain.cs.tip().height + 1
        mature = [e for e in chain.coinbases
                  if next_h - heights[e[0]] >= 100]
        for _ in range(rng.randrange(1, 4)):
            if not mature:
                break
            txid, value = mature.pop(rng.randrange(len(mature)))
            chain.coinbases.remove((txid, value))
            t = _spend([COutPoint(txid, 0)], [value],
                       n_out=rng.randrange(1, 5))
            txs.append(t)
            if rng.random() < 0.5:
                t2 = _spend([COutPoint(t.txid, 0)], [t.vout[0].value])
                txs.append(t2)
        blk = chain.push(txs)
        assert chain.cs.tip().height == next_h
        chain.coinbases.append((blk.vtx[0].txid, blk.vtx[0].vout[0].value))
        heights[blk.vtx[0].txid] = next_h

    eng = _engine_for(chain)
    results = _replay(chain, eng)
    assert all(chain.undo[res.block_hash] == res.undo for res in results)
    chain.cs.coins.flush()
    py = {op.hash + struct.pack("<I", op.n): c.serialize()
          for op, c in chain.cs.coins.base.all_coins()}
    py.pop(PARAMS.genesis.vtx[0].txid + struct.pack("<I", 0), None)
    nat = {k: s for k, s in eng.flush_entries() if s is not None}
    assert nat == py
    eng.close()
