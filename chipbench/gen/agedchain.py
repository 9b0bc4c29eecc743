"""Seeded signature-dense regtest chain whose spent coins are all older than
a flush: the traffic generator of the cell reindex.flush64.

gen/sigchain.py's shapes (the seed's key, clock and coinbase tag; fan-out
transactions of one input and ``fan_k`` pay-to-pubkey-hash outputs; dense
blocks of ``txs_per_block`` transactions of ``inputs_per_tx`` P2PKH inputs
and one output; signed by its workers, imported, not edited), laid out by
flush interval instead of in one deck. With B = ``flush_interval`` connected
blocks between two flushes of the node, interval j is heights B*j + 1 ..
B*(j + 1), and (``plan``):

* intervals 0 and 1, the runway: coinbase-only blocks. The first B*2 - 99
  coinbases are payout coinbases of ``payout`` P2PKH outputs each (a pool
  paying its miners from the coinbase): every coin the chain's first
  signatures can spend, 100 blocks deep by the time they do.
* interval 2, the runway's fan-out: ``fan_txs`` fan-out transactions, one
  payout output each, ``fan_per_block`` to a block (what fits 1,000,000
  bytes), then pad transactions in the dense shape that sweep payout outputs
  until the interval holds ``lanes`` signatures exactly, then empty blocks:
  the gap in which the fan-out's coins are flushed.
* ``intervals`` steady intervals: the fan-out blocks first (the coins the
  next interval spends; the last interval's stay unspent, a real set
  grows), then empty blocks if any, then the dense blocks at the interval's
  end, which spend the fan-out of the interval before in the order it was
  made. A dense block at offset o >= B - dense_blocks + 1 spends a coin of
  offset <= fan_blocks in the interval before: B + o - fan_blocks >= B + 1
  blocks earlier and at most 2B - 1, since fan_blocks + dense_blocks <= B.
  So every input of the chain spends a coin that a flush has written and
  cleared from the node's cache (the fan-out's and the pad's coinbase
  inputs are 100 deep), whatever ``intervals`` is.

Signatures of a steady interval: dense inputs + fan-out transactions =
``lanes`` x k exactly, k the largest count whose blocks fit B (42 at the
cell's shapes: 343,808 dense inputs in 51 blocks, 172 fan-out transactions
in 13); the runway's interval holds ``lanes`` x 1. So every flush of the
import finds whole slices in the aggregate and no tail.

Faults (``--fault``): ``wrong-key-sig`` (sigchain's forged signature, at the
first input of the last steady interval's first dense transaction, so the
replay after the abort is one interval long) and ``young-coin`` (that same
input spends the first coin of its own interval's fan-out instead, one made
since the last flush; the coin it would have spent stays unspent).

    python chipbench/gen/agedchain.py --datadir D --seed N --intervals I [--fault F]

prints one JSON line: what a -reindex of D has to reproduce.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import random
import struct
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import sigchain  # noqa: E402  (puts the repo's root on sys.path)

FAULTS = ("wrong-key-sig", "young-coin")
MATURITY = 100
RUNWAY_FAN_INTERVAL = 2  # the first interval whose blocks can spend 100 deep
MAX_BLOCK_BYTES = 1_000_000
_FAN_TX_OVERHEAD = 200   # a fan-out transaction beside its outputs, and slack
_OUTPUT_BYTES = 34       # value, length, 25 bytes of P2PKH script


def plan(traffic: dict, intervals: int) -> dict:
    """The layout of a chain of ``intervals`` steady intervals under the
    shapes of a traffic file: pure arithmetic, no package import."""
    lanes, fan_k = traffic["lanes"], traffic["fan_k"]
    span = traffic["flush_interval"]
    per_block = traffic["inputs_per_tx"] * traffic["txs_per_block"]
    fan_per_block = ((traffic.get("block_bytes", MAX_BLOCK_BYTES) - 1000)
                     // (fan_k * _OUTPUT_BYTES + _FAN_TX_OVERHEAD))
    if fan_per_block < 1:
        raise ValueError(f"no fan-out transaction of {fan_k} outputs fits "
                         f"a block")

    def shape(k: int):
        dense, fan = sigchain.split_sigs(lanes * k, fan_k)
        return dense, fan, -(-dense // per_block), -(-fan // fan_per_block)

    k = 1
    while sum(shape(k + 1)[2:]) <= span:
        k += 1
    dense, fan, dense_blocks, fan_blocks = shape(k)
    if dense_blocks + fan_blocks > span:
        raise ValueError(f"one {lanes}-lane bucket does not fit {span} "
                         f"blocks of these shapes")
    fan_txs = fan if intervals else 0
    pad = lanes - fan_txs
    pad_blocks = -(-pad // per_block)
    if -(-fan_txs // fan_per_block) + pad_blocks > span:
        raise ValueError("the runway's fan-out and pad do not fit an interval")
    first_spend = span * RUNWAY_FAN_INTERVAL + 1
    payout_blocks = first_spend - MATURITY
    payout_coins = lanes + fan * intervals
    return {
        "flush_interval": span, "intervals": intervals,
        "buckets_per_interval": k, "buckets": 1 + k * intervals,
        "sigs": lanes * (1 + k * intervals),
        "dense_inputs": dense, "fan_txs": fan,
        "dense_blocks": dense_blocks, "fan_blocks": fan_blocks,
        "fan_per_block": fan_per_block, "inputs_per_block": per_block,
        "runway_fan_txs": fan_txs, "pad_inputs": pad,
        "payout_blocks": payout_blocks,
        "payout": -(-payout_coins // payout_blocks),
        "payout_coins": payout_coins,
        "tip_height": span * (RUNWAY_FAN_INTERVAL + 1 + intervals),
    }


def intervals_for(traffic: dict, buckets: int) -> int:
    """The steady intervals of the chain nearest to ``buckets`` buckets,
    at least one."""
    k = plan(traffic, 1)["buckets_per_interval"]
    return max(1, round((buckets - 1) / k))


def generate(datadir: str, seed: int, intervals: int, traffic: dict, *,
             fault: str = "", workers: int = 0) -> dict:
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
    if fault and not intervals:
        raise ValueError("a fault needs a steady interval to sit in")
    lay = plan(traffic, intervals)
    span, fan_k = lay["flush_interval"], traffic["fan_k"]
    inputs_per_tx = traffic["inputs_per_tx"]
    params = regtest_params()
    net_dir = os.path.join(datadir, "regtest")
    blocks_dir = os.path.join(net_dir, "blocks")
    os.makedirs(blocks_dir, exist_ok=True)
    index_kv = KVStore(os.path.join(blocks_dir, "index.sqlite"))
    coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
    store = BlockStore(net_dir, params.netmagic)
    # script_verifier=None: blocks are valid by construction (the fault's
    # one signature excepted), and the reindex IS the validation; the
    # Python engine still holds every spend to maturity, value and existence
    cs = ChainstateManager(params, CoinsDB(coins_kv), store,
                           script_verifier=None,
                           index_db=BlockIndexDB(index_kv))

    rng = random.Random(int(seed))
    key = CKey(sigchain.secret_from_seed(seed), compressed=True)
    spk = key.p2pkh_script()
    tag = b"chipbench" + struct.pack("<Q", int(seed) & (2**64 - 1))
    bits = params.genesis.header.bits
    target, _ = compact_to_target(bits)
    clock = [params.genesis.header.time]
    # the genesis coinbase is a coin of the node's set
    counts = {"blocks": 0, "txs": 0, "bytes": 0, "max_block_bytes": 0,
              "txouts": 1}
    fee = sigchain.FEE

    def push(txs=(), payout: int = 1):
        """One block on the tip: its coinbase pays ``payout`` equal outputs
        (the remainder to the first); returns the coinbase's coins."""
        tip = cs.tip()
        height = tip.height + 1
        clock[0] += 30 + rng.randrange(60)
        value = fee * len(txs) + get_block_subsidy(height, params.consensus)
        each = value // payout
        coinbase = CTransaction(
            version=1,
            vin=(CTxIn(COutPoint(), bip34_coinbase_script_sig(height) + tag,
                       0xFFFFFFFF),),
            vout=(CTxOut(value - each * (payout - 1), spk),
                  *(CTxOut(each, spk) for _ in range(payout - 1))),
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
        size = len(blk.serialize())
        if size > MAX_BLOCK_BYTES:
            raise ValueError(f"block {height} is {size} bytes")
        counts["blocks"] += 1
        counts["txs"] += len(vtx)
        counts["txouts"] += (sum(len(tx.vout) for tx in vtx)
                             - sum(len(tx.vin) for tx in txs))
        counts["bytes"] += size
        counts["max_block_bytes"] = max(counts["max_block_bytes"], size)
        return [(coinbase.txid, i, out.value)
                for i, out in enumerate(coinbase.vout)]

    def push_to(height: int) -> None:
        while cs.tip().height < height:
            push()

    def in_blocks(pool, jobs: list, per_block: int, chunksize: int = 1):
        """Sign ``jobs`` in order and push them ``per_block`` to a block;
        returns the transactions."""
        made = []
        for raw_tx in pool.imap(sigchain._sign_spend, jobs, chunksize):
            made.append(CTransaction.from_bytes(raw_tx))
            if len(made) % per_block == 0:
                push(made[-per_block:])
        if len(made) % per_block:
            push(made[-(len(made) % per_block):])
        return made

    def fan_out(pool, coins: list) -> list:
        """One fan-out transaction a coin; returns the coins they make."""
        jobs = []
        for txid, n, value in coins:
            per_out = (value - fee) // fan_k
            if per_out <= 546:
                raise ValueError("fan_k too large for a payout output")
            jobs.append(([(txid, n, value)], per_out, fan_k, None))
        txs = in_blocks(pool, jobs, lay["fan_per_block"])
        return [(tx.txid, i, job[1]) for tx, job in zip(txs, jobs)
                for i in range(fan_k)]

    def sweep_jobs(coins: list) -> list:
        """Dense-shaped transactions over ``coins``: ``inputs_per_tx``
        inputs and one output each."""
        return [(chunk, sum(v for _, _, v in chunk) - fee, 1, None)
                for chunk in (coins[p:p + inputs_per_tx]
                              for p in range(0, len(coins), inputs_per_tx))]

    t0 = time.monotonic()
    payouts = []
    for _ in range(lay["payout_blocks"]):
        payouts += push(payout=lay["payout"])
    push_to(span * RUNWAY_FAN_INTERVAL)

    fault_at = None
    n_workers = workers or max(1, min(12, (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(n_workers, initializer=sigchain._worker_init,
                  initargs=(int(seed),)) as pool:
        # the runway's interval: the fan-out the first steady interval
        # spends, the pad up to one bucket, the gap
        take = iter(payouts)
        aged = fan_out(pool, [next(take)
                              for _ in range(lay["runway_fan_txs"])])
        in_blocks(pool, sweep_jobs([next(take)
                                    for _ in range(lay["pad_inputs"])]),
                  traffic["txs_per_block"], chunksize=2)
        push_to(span * (RUNWAY_FAN_INTERVAL + 1))
        t_fan = time.monotonic()

        for j in range(intervals):
            start = cs.tip().height
            fresh = fan_out(pool, [next(take)
                                   for _ in range(lay["fan_txs"])])
            push_to(start + span - lay["dense_blocks"])
            jobs = sweep_jobs(aged[:lay["dense_inputs"]])
            if fault and j == intervals - 1:
                fault_at = {"height": cs.tip().height + 1, "dense_tx": 0,
                            "input": 0}
                spent, out_value, out_count, _ = jobs[0]
                if fault == "wrong-key-sig":
                    jobs[0] = (spent, out_value, out_count, 0)
                else:  # young-coin: a coin of this interval's own fan-out
                    young = fresh[0]
                    jobs[0] = ([young] + spent[1:],
                               out_value - spent[0][2] + young[2],
                               out_count, None)
            in_blocks(pool, jobs, traffic["txs_per_block"], chunksize=2)
            if cs.tip().height != start + span:
                raise ValueError(f"interval {j} ended at height "
                                 f"{cs.tip().height}, not {start + span}")
            aged = fresh

    store.flush()
    tip = cs.tip()
    summary = {
        "seed": int(seed), **lay, **counts, "tip_height": tip.height,
        "tip_hash": hash_to_hex(tip.hash), "fault": fault or None,
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
    ap.add_argument("--intervals", type=int, required=True)
    ap.add_argument("--traffic", required=True,
                    help="the traffic file's shapes, as a JSON object")
    ap.add_argument("--fault", default="")
    ap.add_argument("--workers", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps(generate(
        args.datadir, args.seed, args.intervals, json.loads(args.traffic),
        fault=args.fault, workers=args.workers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
