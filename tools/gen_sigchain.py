"""Synthetic signature-dense regtest chain generator — the workload for the
north-star reindex benchmark (BASELINE.json: "mainnet -reindex wall-clock").

Builds a regtest chain whose validation cost is dominated by ECDSA
signature checks (the same shape as a mainnet reindex above the checkpoint
era, src/init.cpp:~600 ThreadImport): a coinbase runway, fan-out
transactions splitting mature coinbases into thousands of P2PKH outputs,
then dense blocks of many-input P2PKH spends — every input one signature.

The chain is written through the normal BlockStore (blk?????.dat with
netmagic framing), so `bcpd -reindex` / Node(reindex) imports it through
exactly the code path the reference's LoadExternalBlockFile occupies.
Generation skips script verification (script_verifier=None) — blocks are
valid by construction (signed with the native signer, bit-identical to the
oracle) and the reindex run IS the validation.

CLI:  python tools/gen_sigchain.py --datadir D --sigs 40000
Emits one JSON line: {"blocks": N, "txs": N, "sigs": N, "bytes": N}.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bitcoincashplus_tpu.consensus.block import CBlock, CBlockHeader  # noqa: E402
from bitcoincashplus_tpu.consensus.merkle import block_merkle_root  # noqa: E402
from bitcoincashplus_tpu.consensus.params import (  # noqa: E402
    get_block_subsidy,
    regtest_params,
)
from bitcoincashplus_tpu.consensus.pow import compact_to_target  # noqa: E402
from bitcoincashplus_tpu.consensus.serialize import hash_to_hex  # noqa: E402
from bitcoincashplus_tpu.consensus.tx import (  # noqa: E402
    COutPoint,
    CTransaction,
    CTxIn,
    CTxOut,
)
from bitcoincashplus_tpu.mining.assembler import (  # noqa: E402
    bip34_coinbase_script_sig,
)
from bitcoincashplus_tpu.store.blockstore import BlockStore  # noqa: E402
from bitcoincashplus_tpu.store.chainstatedb import CoinsDB  # noqa: E402
from bitcoincashplus_tpu.store.kvstore import KVStore  # noqa: E402
from bitcoincashplus_tpu.store.chainstatedb import BlockIndexDB  # noqa: E402
from bitcoincashplus_tpu.validation.chainstate import (  # noqa: E402
    ChainstateManager,
)
from bitcoincashplus_tpu.wallet.keys import CKey  # noqa: E402
from bitcoincashplus_tpu.wallet.signing import sign_transaction  # noqa: E402

FEE = 10_000  # flat per-tx fee (sat) — keeps every output above dust


def _mine(header: CBlockHeader, target: int) -> CBlockHeader:
    """Regtest difficulty-1 PoW: a couple of nonce tries on average."""
    from bitcoincashplus_tpu.crypto.hashes import sha256d

    nonce = 0
    raw = bytearray(header.serialize())
    while True:
        struct.pack_into("<I", raw, 76, nonce)
        if int.from_bytes(sha256d(bytes(raw)), "little") <= target:
            return header.with_nonce(nonce)
        nonce += 1


def _make_block(prev_hash: bytes, height: int, block_time: int, bits: int,
                target: int, txs: tuple, spk: bytes) -> CBlock:
    fees = FEE * (len(txs))
    coinbase = CTransaction(
        version=1,
        vin=(CTxIn(COutPoint(),
                   bip34_coinbase_script_sig(height) + b"sigchain", 0xFFFFFFFF),),
        vout=(CTxOut(fees + get_block_subsidy(height, regtest_params().consensus),
                     spk),),
    )
    vtx = (coinbase, *txs)

    class _V:
        pass

    v = _V()
    v.vtx = vtx
    root, _ = block_merkle_root(v)
    header = CBlockHeader(
        version=0x20000000, hash_prev_block=prev_hash, hash_merkle_root=root,
        time=block_time, bits=bits, nonce=0,
    )
    return CBlock(_mine(header, target), vtx)


def _mixed_phase(utxos, push, key, spk, total_sigs, inputs_per_tx,
                 progress):
    """Heterogeneous segment (VERDICT r4 #6): varied input counts across
    the dispatch padding buckets, P2PK spends (generic-interpreter deferred
    path), and P2SH 2-of-3 multisig spends (the eager CPU CHECKMULTISIG
    path) — the script-shape mix a real mainnet block range has, where the
    uniform P2PKH chain is the TPU fast path's best case. Returns the
    number of ECDSA checks generated."""
    import itertools

    from bitcoincashplus_tpu.crypto.hashes import hash160
    from bitcoincashplus_tpu.script.script import (
        multisig_script,
        p2pk_script,
        p2sh_script_for_redeem,
    )

    keys = [CKey(0xA11CE0 + 7 * i, compressed=(i % 2 == 0))
            for i in range(3)]
    redeem = multisig_script(2, [k.pubkey for k in keys])
    p2sh_spk = p2sh_script_for_redeem(redeem)
    pk_spk = p2pk_script(keys[0].pubkey)

    def key_for(ident):
        if ident in (key.pubkey_hash, key.pubkey):
            return key
        for k in keys:
            if ident in (k.pubkey_hash, k.pubkey):
                return k
        return None

    sizes = itertools.cycle([1, 3, 25, 80, min(250, inputs_per_tx)])
    sigs_done = 0
    carry = []  # (txid, idx, value, spk, redeem|None) to spend next block
    while (sigs_done < total_sigs and utxos) or carry:
        txs = []
        if carry:
            spent = [(s, v) for _, _, v, s, _ in carry]
            unsigned = CTransaction(
                version=1,
                vin=tuple(CTxIn(COutPoint(t, i), b"", 0xFFFFFFFE)
                          for t, i, _, _, _ in carry),
                vout=(CTxOut(sum(v for _, _, v, _, _ in carry) - FEE,
                             spk),),
            )
            rs = {hash160(r): r for *_, r in carry if r}
            txs.append(sign_transaction(unsigned, spent, key_for,
                                        enable_forkid=True,
                                        redeem_scripts=rs))
            sigs_done += sum(2 if r else 1 for *_, r in carry)
            carry = []
        if sigs_done < total_sigs and utxos:
            k = next(sizes)
            chunk = utxos[:k]
            del utxos[:k]
            total_in = sum(v for _, _, v in chunk)
            out_each = (total_in - FEE) // 3
            assert out_each > 546, "chunk too small for the 3-way split"
            unsigned = CTransaction(
                version=1,
                vin=tuple(CTxIn(COutPoint(t, i), b"", 0xFFFFFFFE)
                          for t, i, _ in chunk),
                vout=(CTxOut(out_each, pk_spk),
                      CTxOut(out_each, p2sh_spk),
                      CTxOut(out_each, spk)),
            )
            txs.append(sign_transaction(
                unsigned, [(spk, v) for _, _, v in chunk], key_for,
                enable_forkid=True))
            sigs_done += len(chunk)
            carry = [(txs[-1].txid, 0, out_each, pk_spk, None),
                     (txs[-1].txid, 1, out_each, p2sh_spk, redeem)]
        push(txs)
        progress(f"mixed block: {sigs_done}/{total_sigs} sigs")
    return sigs_done


def generate(datadir: str, total_sigs: int, inputs_per_tx: int = 250,
             txs_per_block: int = 8, fan_k: int = 2000,
             mixed: bool = False,
             progress=lambda s: None) -> dict:
    params = regtest_params()
    net_dir = os.path.join(datadir, "regtest")
    blocks_dir = os.path.join(net_dir, "blocks")
    os.makedirs(blocks_dir, exist_ok=True)

    index_kv = KVStore(os.path.join(blocks_dir, "index.sqlite"))
    coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
    store = BlockStore(net_dir, params.netmagic)
    coins_db = CoinsDB(coins_kv)
    cs = ChainstateManager(
        params, coins_db, store, script_verifier=None,
        index_db=BlockIndexDB(index_kv),
    )

    key = CKey(0x53C5A1F4E0B1DE5FCE, compressed=True)
    spk = key.p2pkh_script()

    def key_for_id(ident):
        return key if ident in (key.pubkey_hash, key.pubkey) else None

    bits = params.genesis.header.bits
    target, _ = compact_to_target(bits)
    t = [params.genesis.header.time]
    n_blocks = [0]
    n_txs = [0]
    n_bytes = [0]

    def push(txs=()):
        tip = cs.tip()
        t[0] += 60
        blk = _make_block(tip.hash, tip.height + 1, t[0], bits, target,
                          tuple(txs), spk)
        cs.process_new_block(blk)
        n_blocks[0] += 1
        n_txs[0] += len(blk.vtx)
        n_bytes[0] += len(blk.serialize())
        return blk

    def finish(n_sigs: int, **extra) -> dict:
        """Flush, close, and summarize what a -reindex over this datadir
        must reproduce (tip, and the UTXO count of this chainstate)."""
        store.flush()
        cs.flush()
        summary = {
            "blocks": n_blocks[0],
            "txs": n_txs[0],
            "sigs": n_sigs,
            "bytes": n_bytes[0],
            "tip_height": n_blocks[0],
            "tip_hash": hash_to_hex(cs.tip().hash),
            "txouts": coins_db.count_coins(),
            **extra,
        }
        store.close()
        index_kv.close()
        coins_kv.close()
        return summary

    # Phase 1: coinbase runway. Fan-out txs each consume one MATURE (100+
    # deep) coinbase, so mint enough and add the maturity padding.
    sigs_per_dense_block = inputs_per_tx * txs_per_block
    n_fan = (total_sigs + fan_k - 1) // fan_k
    runway = n_fan + 100
    progress(f"runway: {runway} coinbase blocks")
    coinbases = []  # (txid, vout_value, height)
    for _ in range(runway):
        blk = push()
        coinbases.append((blk.vtx[0].txid, blk.vtx[0].vout[0].value))
    coinbases = coinbases[:n_fan]

    # Phase 2: fan-out — split each mature coinbase into fan_k P2PKH outputs.
    progress(f"fan-out: {n_fan} txs x {fan_k} outputs")
    utxos = []  # (txid, index, value)
    fan_batch = []
    for txid, value in coinbases:
        per_out = (value - FEE) // fan_k
        assert per_out > 546, "fan_k too large for the subsidy"
        unsigned = CTransaction(
            version=1,
            vin=(CTxIn(COutPoint(txid, 0), b"", 0xFFFFFFFE),),
            vout=tuple(CTxOut(per_out, spk) for _ in range(fan_k)),
        )
        signed = sign_transaction(unsigned, [(spk, value)], key_for_id,
                                  enable_forkid=True)
        fan_batch.append(signed)
        for i in range(fan_k):
            utxos.append((signed.txid, i, per_out))
        if len(fan_batch) == 5:
            push(fan_batch)
            fan_batch = []
    if fan_batch:
        push(fan_batch)

    # Phase 3: dense blocks — txs_per_block txs of inputs_per_tx P2PKH
    # spends each; every input is one ECDSA verification at reindex.
    # (mixed=True swaps in the heterogeneous segment instead.)
    utxos = utxos[:total_sigs]
    if mixed:
        n_sigs = _mixed_phase(utxos, push, key, spk, total_sigs,
                              inputs_per_tx, progress)
        return finish(n_sigs, mixed=True)
    progress(f"dense: {len(utxos)} sig-inputs, "
             f"{sigs_per_dense_block} per block")
    sigs_done = 0
    pos = 0
    t0 = time.monotonic()
    while pos < len(utxos):
        txs = []
        for _ in range(txs_per_block):
            chunk = utxos[pos:pos + inputs_per_tx]
            if not chunk:
                break
            pos += len(chunk)
            total_in = sum(v for _, _, v in chunk)
            unsigned = CTransaction(
                version=1,
                vin=tuple(CTxIn(COutPoint(txid, i), b"", 0xFFFFFFFE)
                          for txid, i, _ in chunk),
                vout=(CTxOut(total_in - FEE, spk),),
            )
            txs.append(sign_transaction(
                unsigned, [(spk, v) for _, _, v in chunk], key_for_id,
                enable_forkid=True,
            ))
        blk = push(txs)
        sigs_done = pos
        progress(f"dense block {n_blocks[0]}: {sigs_done}/{len(utxos)} sigs "
                 f"({sigs_done / (time.monotonic() - t0):.0f} sigs/s gen)")

    return finish(len(utxos))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--datadir", required=True)
    ap.add_argument("--sigs", type=int, default=40_000)
    ap.add_argument("--inputs-per-tx", type=int, default=250)
    ap.add_argument("--txs-per-block", type=int, default=8)
    ap.add_argument("--fan-k", type=int, default=2000)
    ap.add_argument("--mixed", action="store_true",
                    help="heterogeneous script shapes (see _mixed_phase)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args()
    progress = (lambda s: None) if args.quiet else (
        lambda s: print(f"[gen_sigchain] {s}", file=sys.stderr, flush=True))
    summary = generate(args.datadir, args.sigs, args.inputs_per_tx,
                       args.txs_per_block, args.fan_k, args.mixed, progress)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
