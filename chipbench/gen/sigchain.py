"""Seeded signature-dense regtest chain: the reindex cells' traffic generator.

A copy of tools/gen_sigchain.py's dense phase, made a function of a seed and
a data file of parameters (chipbench/traffic/*.json): the signing key, the
block-time steps and the coinbase tag come from the seed, the transactions
are signed by a pool of worker processes (rfc6979, so the chain does not
depend on how many), and the total signature count is exact, so that an
import dispatches whole 8,190-lane slices only.

Layout of a chain of T signatures: a runway of F + 100 coinbase blocks, F
fan-out transactions (one signature each, ``fan_k`` P2PKH outputs, five to a
block), then D = T - F dense inputs in transactions of ``inputs_per_tx``
P2PKH spends, ``txs_per_block`` to a block; F = ceil(D / fan_k).

Runs as a child pinned to the CPU (the package's imports pull in JAX):

    python chipbench/gen/sigchain.py --datadir D --seed N --sigs T [--fault wrong-key-sig]

and prints one JSON line: what a -reindex of D has to reproduce.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import multiprocessing
import os
import random
import struct
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

FEE = 10_000  # flat per-tx fee (sat): keeps every output above dust
SECP_N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
FAULTS = ("wrong-key-sig",)


def secret_from_seed(seed: int, tag: bytes = b"key") -> int:
    digest = hashlib.sha256(b"chipbench-sigchain-" + tag
                            + str(int(seed)).encode()).digest()
    return int.from_bytes(digest, "big") % (SECP_N - 1) + 1


def split_sigs(total: int, fan_k: int) -> tuple[int, int]:
    """(dense inputs D, fan-out transactions F) with D + F == total and
    F == ceil(D / fan_k)."""
    fan = -(-total // (fan_k + 1))
    dense = total - fan
    if dense <= 0 or -(-dense // fan_k) != fan:
        raise ValueError(f"cannot split {total} signatures at fan_k={fan_k}")
    return dense, fan


# -- signing workers ---------------------------------------------------------

_W: dict = {}


def _worker_init(seed: int) -> None:
    from bitcoincashplus_tpu.wallet.keys import CKey

    key = CKey(secret_from_seed(seed), compressed=True)
    # the fault's signer: the right public key in the scriptSig, another
    # secret behind the signature, so the ECDSA equation itself fails
    bad = CKey(secret_from_seed(seed, b"other"), compressed=True)
    bad.pubkey = key.pubkey
    _W.update(key=key, bad=bad, spk=key.p2pkh_script())


def _sign_spend(job: tuple) -> bytes:
    """job = (inputs [(txid, index, value)], outputs [(value, n)], spent
    script values, bad_input or None) -> the signed transaction's bytes."""
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.wallet.signing import sign_transaction

    inputs, out_value, out_count, bad_input = job
    key, spk = _W["key"], _W["spk"]
    unsigned = CTransaction(
        version=1,
        vin=tuple(CTxIn(COutPoint(t, i), b"", 0xFFFFFFFE)
                  for t, i, _ in inputs),
        vout=tuple(CTxOut(out_value, spk) for _ in range(out_count)),
    )
    spent = [(spk, v) for _, _, v in inputs]
    signed = sign_transaction(unsigned, spent, lambda ident: key,
                              enable_forkid=True)
    if bad_input is not None:
        forged = sign_transaction(unsigned, spent, lambda ident: _W["bad"],
                                  enable_forkid=True)
        vin = list(signed.vin)
        vin[bad_input] = forged.vin[bad_input]
        signed = CTransaction(signed.version, tuple(vin), signed.vout,
                              signed.locktime)
    return signed.serialize()


# -- the chain ---------------------------------------------------------------

def generate(datadir: str, seed: int, total_sigs: int, *,
             inputs_per_tx: int = 250, txs_per_block: int = 27,
             fan_k: int = 2000, fault: str = "", workers: int = 0) -> dict:
    from bitcoincashplus_tpu.consensus.block import CBlock, CBlockHeader
    from bitcoincashplus_tpu.consensus.merkle import block_merkle_root
    from bitcoincashplus_tpu.consensus.params import (
        get_block_subsidy,
        regtest_params,
    )
    from bitcoincashplus_tpu.consensus.pow import compact_to_target
    from bitcoincashplus_tpu.consensus.serialize import hash_to_hex
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.crypto.hashes import sha256d
    from bitcoincashplus_tpu.mining.assembler import bip34_coinbase_script_sig
    from bitcoincashplus_tpu.store.blockstore import BlockStore
    from bitcoincashplus_tpu.store.chainstatedb import BlockIndexDB, CoinsDB
    from bitcoincashplus_tpu.store.kvstore import KVStore
    from bitcoincashplus_tpu.validation.chainstate import ChainstateManager
    from bitcoincashplus_tpu.wallet.keys import CKey

    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    dense, n_fan = split_sigs(total_sigs, fan_k)
    params = regtest_params()
    net_dir = os.path.join(datadir, "regtest")
    blocks_dir = os.path.join(net_dir, "blocks")
    os.makedirs(blocks_dir, exist_ok=True)
    index_kv = KVStore(os.path.join(blocks_dir, "index.sqlite"))
    coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
    store = BlockStore(net_dir, params.netmagic)
    coins_db = CoinsDB(coins_kv)
    # script_verifier=None: blocks are valid by construction (the fault's
    # one signature excepted, which is the point), and the reindex IS the
    # validation
    cs = ChainstateManager(params, coins_db, store, script_verifier=None,
                           index_db=BlockIndexDB(index_kv))

    rng = random.Random(int(seed))
    key = CKey(secret_from_seed(seed), compressed=True)
    spk = key.p2pkh_script()
    tag = b"chipbench" + struct.pack("<Q", int(seed) & (2**64 - 1))
    bits = params.genesis.header.bits
    target, _ = compact_to_target(bits)
    clock = [params.genesis.header.time]
    counts = {"blocks": 0, "txs": 0, "bytes": 0}

    def push(txs=()):
        tip = cs.tip()
        height = tip.height + 1
        clock[0] += 30 + rng.randrange(60)
        coinbase = CTransaction(
            version=1,
            vin=(CTxIn(COutPoint(), bip34_coinbase_script_sig(height) + tag,
                       0xFFFFFFFF),),
            vout=(CTxOut(FEE * len(txs)
                         + get_block_subsidy(height, params.consensus),
                         spk),),
        )
        vtx = (coinbase, *txs)
        root, _ = block_merkle_root(type("V", (), {"vtx": vtx})())
        header = CBlockHeader(
            version=0x20000000, hash_prev_block=tip.hash,
            hash_merkle_root=root, time=clock[0], bits=bits, nonce=0)
        raw = bytearray(header.serialize())
        nonce = 0
        while True:  # regtest proof of work: a couple of tries
            struct.pack_into("<I", raw, 76, nonce)
            if int.from_bytes(sha256d(bytes(raw)), "little") <= target:
                break
            nonce += 1
        blk = CBlock(header.with_nonce(nonce), vtx)
        cs.process_new_block(blk)
        counts["blocks"] += 1
        counts["txs"] += len(vtx)
        counts["bytes"] += len(blk.serialize())
        return blk

    t0 = time.monotonic()
    coinbases = []
    for _ in range(n_fan + 100):  # fan-out inputs must be 100 deep
        blk = push()
        coinbases.append((blk.vtx[0].txid, blk.vtx[0].vout[0].value))
    coinbases = coinbases[:n_fan]

    n_workers = workers or max(1, min(12, (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(n_workers, initializer=_worker_init,
                  initargs=(int(seed),)) as pool:
        fan_jobs = []
        for txid, value in coinbases:
            per_out = (value - FEE) // fan_k
            if per_out <= 546:
                raise ValueError("fan_k too large for the subsidy")
            fan_jobs.append(([(txid, 0, value)], per_out, fan_k, None))
        utxos = []
        batch = []
        for raw_tx, job in zip(pool.imap(_sign_spend, fan_jobs), fan_jobs):
            tx = CTransaction.from_bytes(raw_tx)
            batch.append(tx)
            utxos += [(tx.txid, i, job[1]) for i in range(fan_k)]
            if len(batch) == 5:
                push(batch)
                batch = []
        if batch:
            push(batch)
        t_fan = time.monotonic()

        utxos = utxos[:dense]
        jobs = []
        for pos in range(0, dense, inputs_per_tx):
            chunk = utxos[pos:pos + inputs_per_tx]
            jobs.append((chunk, sum(v for _, _, v in chunk) - FEE, 1, None))
        fault_at = None
        if fault == "wrong-key-sig":
            # first input of the last dense block's first transaction
            j = ((len(jobs) - 1) // txs_per_block) * txs_per_block
            jobs[j] = jobs[j][:3] + (0,)
            fault_at = {"dense_tx": j, "input": 0}
        block_txs = []
        for raw_tx in pool.imap(_sign_spend, jobs, chunksize=2):
            block_txs.append(CTransaction.from_bytes(raw_tx))
            if len(block_txs) == txs_per_block:
                push(block_txs)
                block_txs = []
        if block_txs:
            push(block_txs)

    store.flush()
    cs.flush()
    summary = {
        "seed": int(seed), "sigs": total_sigs, "dense_sigs": dense,
        "fan_sigs": n_fan, **counts, "tip_height": counts["blocks"],
        "tip_hash": hash_to_hex(cs.tip().hash),
        "txouts": coins_db.count_coins(), "fault": fault or None,
        "fault_at": fault_at, "workers": n_workers,
        "fan_s": round(t_fan - t0, 3),
        "generate_s": round(time.monotonic() - t0, 3),
    }
    store.close()
    index_kv.close()
    coins_kv.close()
    return summary


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--datadir", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sigs", type=int, required=True)
    ap.add_argument("--inputs-per-tx", type=int, default=250)
    ap.add_argument("--txs-per-block", type=int, default=27)
    ap.add_argument("--fan-k", type=int, default=2000)
    ap.add_argument("--fault", default="")
    ap.add_argument("--workers", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps(generate(
        args.datadir, args.seed, args.sigs,
        inputs_per_tx=args.inputs_per_tx, txs_per_block=args.txs_per_block,
        fan_k=args.fan_k, fault=args.fault, workers=args.workers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
