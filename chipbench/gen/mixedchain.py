"""Seeded mixed-script regtest chain: the traffic generator of the cell
reindex.mixed_era.

The script forms of the chain from the fork height on (regtest has the
fork's rules from genesis), at assumed shares and at a size a run can
import: inputs that spend pay-to-pubkey-hash, pay-to-script-hash 2-of-3
multisig, pay-to-pubkey (65-byte keys) and bare 1-of-2 multisig outputs, in
transactions of 1 to 250 inputs, in blocks of about a megabyte. Every draw (script kind, inputs a
transaction, signer set, keys) comes from a shuffled deck that holds each
value at exactly its share, so two seeds differ in order and not in mix.

Layout: a runway of F + 100 coinbase blocks, F fan-out transactions (one
pay-to-pubkey-hash input each, ``fan_k`` outputs of the kinds the spends
will need, five to a block), then the spends, packed into blocks of at most
``block_bytes``. A device lane is one signature check of the batch: one a
single-signature input (pay-to-pubkey-hash or pay-to-pubkey, the fan-outs'
among them) and m(n-m+1) an m-of-n OP_CHECKMULTISIG, the candidate pairs of
its key-trial walk (4 for 2-of-3, 2 for 1-of-2: a function of the script,
not of who signed). The plan stops drawing when the next transaction would
pass ``--lanes`` and pads with pay-to-pubkey-hash inputs, so the chain
holds exactly ``--lanes`` of them. Where each multisig input's walk ends
follows from its signer set (the walk of reference_mixed.py starts at the
last key); the sets are kept beside the chain (``signers.json``), in chain
order, for the check.

Runs as a child pinned to the CPU (the package's imports pull in JAX):

    python chipbench/gen/mixedchain.py --datadir D --seed N --lanes T \\
        --traffic chipbench/traffic/mixed_era.json [--rehearse] \\
        [--fault wrong-key-multisig|wrong-key-sig]

and prints one JSON line: what a -reindex of D has to reproduce, and what
its counters have to read.
"""

from __future__ import annotations

import argparse
import copy
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
FAULTS = ("wrong-key-multisig", "wrong-key-sig")
KINDS = ("p2pkh", "p2sh_multisig", "p2pk", "bare_multisig")
# keys in a script, signatures it wants
MULTISIG = {"p2sh_multisig": (3, 2), "bare_multisig": (2, 1)}
# the signer sets of each multisig kind, in the order signers.json codes them
SIGNER_SETS = {"p2sh_multisig": ((0, 1), (0, 2), (1, 2)),
               "bare_multisig": ((0,), (1,))}
FAN_TXS_PER_BLOCK = 5
MAX_INPUTS = 250


def secret_from_seed(seed: int, index: int) -> int:
    digest = hashlib.sha256(b"chipbench-mixedchain-key-%d-" % index
                            + str(int(seed)).encode()).digest()
    return int.from_bytes(digest, "big") % (SECP_N - 1) + 1


class Deck:
    """Draws without replacement from ``count`` copies of each value, in a
    seeded order; a new shuffle when the deck runs out."""

    def __init__(self, rng: random.Random, counts: dict):
        self.rng = rng
        self.cards = [v for v, n in counts.items() for _ in range(n)]
        self.left: list = []

    def draw(self):
        if not self.left:
            self.left = list(self.cards)
            self.rng.shuffle(self.left)
        return self.left.pop()


def lanes_of(kind: str) -> int:
    """Device lanes one input of ``kind`` takes: m(n-m+1) candidate pairs a
    multisig operation, one a single signature."""
    if kind in MULTISIG:
        n, m = MULTISIG[kind]
        return m * (n - m + 1)
    return 1


def make_plan(seed: int, lanes: int, traffic: dict) -> dict:
    """Every spend of the chain before anything is signed: ``txs`` is a list
    of transactions, each a list of inputs (kind, keys, signers); ``fan`` the
    number of fan-out transactions that fund them."""
    rng = random.Random(int(seed))
    n_keys = traffic["keys"]
    kinds = Deck(rng, traffic["input_mix"])
    sizes = Deck(rng, {int(k): v for k, v in
                       traffic["inputs_per_tx"].items()})
    signer_sets = {kind: Deck(rng, dict.fromkeys(sets, 1))
                   for kind, sets in SIGNER_SETS.items()}
    fan_k = traffic["fan_k"]

    def draw_input(kind: str) -> tuple:
        if kind in MULTISIG:
            keys = tuple(rng.sample(range(n_keys), MULTISIG[kind][0]))
            return kind, keys, signer_sets[kind].draw()
        return kind, (rng.randrange(n_keys),), (0,)

    def lanes_with(device: int, inputs: int) -> int:
        return device + -(-inputs // fan_k)  # each fan-out is a lane too

    txs: list = []
    device = inputs = 0
    while True:
        tx = [draw_input(kinds.draw()) for _ in range(sizes.draw())]
        tx_device = sum(lanes_of(i[0]) for i in tx)
        if lanes_with(device + tx_device, inputs + len(tx)) > lanes:
            break
        txs.append(tx)
        device += tx_device
        inputs += len(tx)
    padded = 0
    while lanes_with(device, inputs) < lanes:
        n = min(MAX_INPUTS, lanes - lanes_with(device, inputs))
        if lanes_with(device + n, inputs + n) > lanes:
            n -= 1  # the pad itself needed one more fan-out
        if n <= 0:
            raise ValueError(f"cannot reach exactly {lanes} lanes")
        txs.append([draw_input("p2pkh") for _ in range(n)])
        device += n
        inputs += n
        padded += n
    if not txs or lanes_with(device, inputs) != lanes:
        raise ValueError(f"cannot reach exactly {lanes} lanes")
    return {"txs": txs, "fan": -(-inputs // fan_k), "inputs": inputs,
            "padded_inputs": padded}


# -- scripts and signing (workers and the main process share these) ----------

class Keyring:
    def __init__(self, seed: int, n_keys: int):
        from bitcoincashplus_tpu.wallet.keys import CKey

        # one point multiplication a key (Python integers): the 33-byte
        # form is cut from the 65-byte one
        self.long = [CKey(secret_from_seed(seed, i), compressed=False)
                     for i in range(n_keys)]
        self.keys = [copy.copy(k) for k in self.long]
        for k in self.keys:
            k.compressed = True
            k.pubkey = bytes([2 | k.pubkey[64] & 1]) + k.pubkey[1:33]
        # the fault's signer: a key that is in no script of the chain
        self.outsider = CKey(secret_from_seed(seed, n_keys), compressed=True)
        self.change_spk = self.keys[0].p2pkh_script()

    def scripts(self, kind: str, keys: tuple) -> tuple:
        """(script of the output, script code its signatures commit to)."""
        from bitcoincashplus_tpu.script.script import (
            multisig_script,
            p2pk_script,
            p2sh_script_for_redeem,
        )

        if kind == "p2pkh":
            spk = self.keys[keys[0]].p2pkh_script()
            return spk, spk
        if kind == "p2pk":
            spk = p2pk_script(self.long[keys[0]].pubkey)
            return spk, spk
        script = multisig_script(MULTISIG[kind][1],
                                 [self.keys[k].pubkey for k in keys])
        if kind == "bare_multisig":
            return script, script
        return p2sh_script_for_redeem(script), script


_W: dict = {}


def _worker_init(seed: int, n_keys: int, forkid: bool = True) -> None:
    _W["ring"] = Keyring(seed, n_keys)
    _W["forkid"] = forkid


def _sign_spend(job: tuple) -> bytes:
    """job = (prevouts [(txid, index, value)], inputs [(kind, keys,
    signers)], outputs [(value, script)], bad_input or None) -> the signed
    transaction's bytes."""
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.script.script import push_data_raw
    from bitcoincashplus_tpu.script.sighash import SighashCache
    from bitcoincashplus_tpu.wallet.signing import make_signature

    prevouts, inputs, outputs, bad_input = job
    ring = _W["ring"]
    unsigned = CTransaction(
        version=1,
        vin=tuple(CTxIn(COutPoint(t, i), b"", 0xFFFFFFFE)
                  for t, i, _ in prevouts),
        vout=tuple(CTxOut(v, spk) for v, spk in outputs),
    )
    cache = SighashCache(unsigned)
    vin = []
    for n, ((kind, keys, signers), (_, _, value)) in enumerate(
            zip(inputs, prevouts)):
        _, code = ring.scripts(kind, keys)
        pool = ring.long if kind == "p2pk" else ring.keys
        signing = [pool[keys[s]] for s in signers]
        if n == bad_input:
            signing[-1] = ring.outsider
        sigs = [make_signature(key, code, unsigned, n, value,
                               enable_forkid=_W["forkid"], cache=cache)
                for key in signing]
        script_sig = b"".join(push_data_raw(sig) for sig in sigs)
        if kind == "p2pkh":
            # the script's own key even under the fault: the hash matches
            # and the ECDSA equation itself fails
            script_sig += push_data_raw(pool[keys[0]].pubkey)
        elif kind in MULTISIG:
            script_sig = b"\x00" + script_sig  # CHECKMULTISIG's dummy
            if kind == "p2sh_multisig":
                script_sig += push_data_raw(code)
        vin.append(CTxIn(unsigned.vin[n].prevout, script_sig, 0xFFFFFFFE))
    return CTransaction(1, tuple(vin), unsigned.vout, 0).serialize()


# -- the chain ---------------------------------------------------------------

def generate(datadir: str, seed: int, lanes: int, traffic: dict, *,
             fault: str = "", workers: int = 0,
             forkid: bool = True) -> dict:
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
    from bitcoincashplus_tpu.script.script import count_sigops
    from bitcoincashplus_tpu.store.blockstore import BlockStore
    from bitcoincashplus_tpu.store.chainstatedb import BlockIndexDB, CoinsDB
    from bitcoincashplus_tpu.store.kvstore import KVStore
    from bitcoincashplus_tpu.validation.chainstate import ChainstateManager

    if fault and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    t0 = time.monotonic()
    plan = make_plan(seed, lanes, traffic)
    n_fan, fan_k = plan["fan"], traffic["fan_k"]
    ring = Keyring(seed, traffic["keys"])
    params = regtest_params()
    net_dir = os.path.join(datadir, "regtest")
    blocks_dir = os.path.join(net_dir, "blocks")
    os.makedirs(blocks_dir, exist_ok=True)
    index_kv = KVStore(os.path.join(blocks_dir, "index.sqlite"))
    coins_kv = KVStore(os.path.join(net_dir, "chainstate.sqlite"))
    store = BlockStore(net_dir, params.netmagic)
    coins_db = CoinsDB(coins_kv)
    # script_verifier=None: blocks are valid by construction (the fault's
    # one input excepted, which is the point), and the reindex IS the
    # validation
    cs = ChainstateManager(params, coins_db, store, script_verifier=None,
                           index_db=BlockIndexDB(index_kv))

    rng = random.Random(int(seed) ^ 0xC10C)
    tag = b"chipbench" + struct.pack("<Q", int(seed) & (2**64 - 1))
    bits = params.genesis.header.bits
    target, _ = compact_to_target(bits)
    clock = [params.genesis.header.time]
    counts = {"blocks": 0, "txs": 0, "bytes": 0}
    biggest = {"max_block_bytes": 0, "max_block_sigops": 0}

    def push(txs=(), sigops: int = 0):
        tip = cs.tip()
        height = tip.height + 1
        clock[0] += 30 + rng.randrange(60)
        coinbase = CTransaction(
            version=1,
            vin=(CTxIn(COutPoint(), bip34_coinbase_script_sig(height) + tag,
                       0xFFFFFFFF),),
            vout=(CTxOut(FEE * len(txs)
                         + get_block_subsidy(height, params.consensus),
                         ring.change_spk),),
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
        size = len(blk.serialize())
        if size > params.max_block_size or sigops > params.max_block_sigops:
            raise ValueError(f"block {height}: {size} bytes, {sigops} "
                             f"sigops: over a consensus limit")
        cs.process_new_block(blk)
        counts["blocks"] += 1
        counts["txs"] += len(vtx)
        counts["bytes"] += size
        biggest["max_block_bytes"] = max(biggest["max_block_bytes"], size)
        biggest["max_block_sigops"] = max(biggest["max_block_sigops"],
                                          sigops)
        return blk

    coinbases = []
    for _ in range(n_fan + 100):  # fan-out inputs must be 100 deep
        blk = push()
        coinbases.append((blk.vtx[0].txid, blk.vtx[0].vout[0].value))
    coinbases = coinbases[:n_fan]

    # what each spend input will find, in spend order
    wanted = [inp for tx in plan["txs"] for inp in tx]
    by_kind = {kind: 0 for kind in KINDS}
    signer_sets: dict = {}
    signers_in_order = {kind: [] for kind in MULTISIG}
    signatures = multisig_sigs = multisig_lanes = 0
    for kind, _, signers in wanted:
        by_kind[kind] += 1
        signatures += len(signers)
        if kind in MULTISIG:
            name = kind + ":" + ",".join(map(str, signers))
            signer_sets[name] = signer_sets.get(name, 0) + 1
            signers_in_order[kind].append(
                str(SIGNER_SETS[kind].index(signers)))
            multisig_sigs += len(signers)
            multisig_lanes += lanes_of(kind)
    by_kind["p2pkh"] += n_fan
    signatures += n_fan

    n_workers = workers or max(1, min(12, (os.cpu_count() or 2) - 1))
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(n_workers, initializer=_worker_init,
                  initargs=(int(seed), traffic["keys"], forkid)) as pool:
        fan_jobs = []
        for f, (txid, value) in enumerate(coinbases):
            chunk = wanted[f * fan_k:(f + 1) * fan_k]
            per_out = (value - FEE) // fan_k
            if per_out <= 546 + FEE:
                raise ValueError("fan_k too large for the subsidy")
            outputs = [(per_out, ring.scripts(kind, keys)[0])
                       for kind, keys, _ in chunk]
            fan_jobs.append(([(txid, 0, value)], [("p2pkh", (0,), (0,))],
                             outputs, None))
        utxos = []
        batch, batch_sigops = [], 0
        for raw_tx, job in zip(pool.imap(_sign_spend, fan_jobs), fan_jobs):
            tx = CTransaction.from_bytes(raw_tx)
            batch.append(tx)
            batch_sigops += sum(count_sigops(spk) for _, spk in job[2])
            utxos += [(tx.txid, i, v) for i, (v, _) in enumerate(job[2])]
            if len(batch) == FAN_TXS_PER_BLOCK:
                push(batch, batch_sigops)
                batch, batch_sigops = [], 0
        if batch:
            push(batch, batch_sigops)
        t_fan = time.monotonic()

        jobs, pos = [], 0
        for tx in plan["txs"]:
            chunk = utxos[pos:pos + len(tx)]
            pos += len(tx)
            out_value = sum(v for _, _, v in chunk) - FEE
            jobs.append((chunk, tx, [(out_value, ring.change_spk)], None))
        fault_at = None
        if fault:
            # the chain's last pay-to-script-hash input (its last signature
            # by a key that is not in the redeem script) or its last
            # pay-to-pubkey-hash input (the script's key, another secret)
            kind = {"wrong-key-multisig": "p2sh_multisig",
                    "wrong-key-sig": "p2pkh"}[fault]
            j, n = max((j, n) for j, tx in enumerate(plan["txs"])
                       for n, inp in enumerate(tx) if inp[0] == kind)
            jobs[j] = jobs[j][:3] + (n,)
            fault_at = {"spend_tx": j, "input": n}
        limit = traffic["block_bytes"] - 1000  # header, count, coinbase
        block_txs, block_size, block_sigops = [], 0, 0
        for raw_tx, job in zip(pool.imap(_sign_spend, jobs, chunksize=8),
                               jobs):
            if block_txs and block_size + len(raw_tx) > limit:
                push(block_txs, block_sigops)
                block_txs, block_size, block_sigops = [], 0, 0
            tx = CTransaction.from_bytes(raw_tx)
            block_txs.append(tx)
            block_size += len(raw_tx)
            # one CHECKSIG an output, and a redeem script's keys an input
            block_sigops += len(tx.vout) + sum(
                MULTISIG[kind][0] for kind, _, _ in job[1]
                if kind == "p2sh_multisig")
        if block_txs:
            push(block_txs, block_sigops)

    store.flush()
    cs.flush()
    # each multisig input's signer set, in chain order, one digit an input
    # (its place in SIGNER_SETS): what the check holds the reference's walk to
    with open(os.path.join(datadir, "signers.json"), "w") as f:
        json.dump({kind: "".join(codes)
                   for kind, codes in signers_in_order.items()}, f)
    summary = {
        "seed": int(seed), "device_lanes": lanes, "sigs": signatures,
        "inputs": plan["inputs"] + n_fan, "inputs_by_kind": by_kind,
        "non_p2pkh_inputs": plan["inputs"] + n_fan - by_kind["p2pkh"],
        "multisig_groups": sum(by_kind[kind] for kind in MULTISIG),
        "multisig_sigs": multisig_sigs, "multisig_lanes": multisig_lanes,
        "signer_sets": signer_sets,
        "padded_inputs": plan["padded_inputs"], "fan_txs": n_fan,
        "spend_txs": len(plan["txs"]), **counts, **biggest,
        "tip_height": counts["blocks"],
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
    ap.add_argument("--lanes", type=int, required=True,
                    help="single-signature checks in the chain, exactly")
    ap.add_argument("--traffic", required=True,
                    help="the cell's traffic file (input_mix, "
                         "inputs_per_tx, keys, fan_k, block_bytes)")
    ap.add_argument("--rehearse", action="store_true",
                    help="lay the file's ``rehearse`` sizes over it")
    ap.add_argument("--fault", default="")
    ap.add_argument("--workers", type=int, default=0)
    ap.add_argument("--legacy-sighash", action="store_true",
                    help="sign SIGHASH_ALL without SIGHASH_FORKID: the "
                         "same chain as history from below the fork "
                         "height has it, which the cell does not run "
                         "(the node verifies it inline on the host)")
    args = ap.parse_args()
    with open(args.traffic) as f:
        traffic = json.load(f)
    if args.rehearse:
        traffic = dict(traffic, **traffic["rehearse"])
    print(json.dumps(generate(args.datadir, args.seed, args.lanes, traffic,
                              fault=args.fault, workers=args.workers,
                              forkid=not args.legacy_sighash)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
