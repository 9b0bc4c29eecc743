"""The plain reference of the configuration archival-reindex-default.

reference.py's replay of the block files (a linear chain, a UTXO set kept as
a dict, a sample of the signed inputs verified by its own ECDSA and sighash),
and beside it what the shipped flush cadence has to leave on disk, written
here from the documents and not from the program. Nothing here imports the
program: block files, transaction parsing and the sample's verification come
from reference.py.

The cadence (bcpd's help text: "-flushinterval=<n>: flush chainstate every
<n> connected blocks"; Core's FlushStateToDisk): a flush after every n-th
block connected since the last one, and one when the import ends. A flush
writes the cache and empties it (CCoinsViewCache::Flush), and coins.h's rule
decides what it writes: a coin made and spent between two flushes (FRESH)
never reaches the database, a coin made since the last flush and still
unspent is a put, a spend of a coin the database holds is a delete, and such
a coin has to be read back from the database before it can be spent.

The digest of the unspent set as the store defines it (store/muhash.py's
docstrings: MuHash3072): one element a row,

    elem = SHAKE256(outpoint key || coin serialisation) to 384 bytes, read
           little-endian, mod p, p = 2^3072 - 1103717 (0 stands as 1)
    H    = SHA-256 of (product of the elements mod p) as 384 bytes big-endian

the outpoint key being the 32-byte txid and the output's index as four bytes
little-endian, the coin serialisation CompactSize(height * 2 + coinbase),
CompactSize(value), CompactSize(script length), script. Spent rows are
divided out an interval at a time: one ``pow(x, -1, p)`` of the product of
the interval's deleted elements. Reduction folds 2^3072 = 1103717 (mod p).

The rows on disk (``disk_rows``): the shards' sqlite files opened read-only,
table ``kv``, the rows whose key starts with ``C`` followed by the 36-byte
outpoint key; their count and their digest by the arithmetic above.
"""

from __future__ import annotations

import glob
import hashlib
import os
import random
import sqlite3
import struct

import reference as ref

C = 1103717
P = (1 << 3072) - C
_MASK = (1 << 3072) - 1


def _mod_p(x: int) -> int:
    while x >> 3072:
        x = (x >> 3072) * C + (x & _MASK)
    return x - P if x >= P else x


def element(row: bytes) -> int:
    v = _mod_p(int.from_bytes(hashlib.shake_256(row).digest(384), "little"))
    return v or 1


def product(rows) -> int:
    acc = 1
    for row in rows:
        acc = _mod_p(acc * element(row))
    return acc


def digest_of(acc: int) -> str:
    return hashlib.sha256(acc.to_bytes(384, "big")).hexdigest()


def _compact(n: int) -> bytes:
    if n < 253:
        return bytes([n])
    if n <= 0xFFFF:
        return b"\xfd" + struct.pack("<H", n)
    if n <= 0xFFFFFFFF:
        return b"\xfe" + struct.pack("<I", n)
    return b"\xff" + struct.pack("<Q", n)


def coin_row(key36: bytes, height: int, coinbase: bool, value: int,
             spk: bytes) -> bytes:
    """The bytes one unspent output is hashed as."""
    return (key36 + _compact(height * 2 + coinbase) + _compact(value)
            + _compact(len(spk)) + spk)


def scan_chain(blocks_dir: str, seed: int, sample: int,
               flush_interval: int) -> dict:
    """Replay a linear chain from its block files under a flush every
    ``flush_interval`` connected blocks and at the end.

    Returns reference.scan_chain's numbers (the tip is the last block
    before the first sampled input that does not verify) and ``flushes``:
    the state after the genesis block (what a node holds before it imports
    anything), then one entry a flush the import has to make, in order:
    ``height``, ``best_block`` (display order), ``puts`` and ``deletes`` of
    that flush, the ``signatures`` of the blocks since the flush before, the
    ``digest`` and ``utxos`` of the unspent set it leaves.
    ``store_reads`` counts the inputs whose coin a flush before their block
    had written (each is read back from the database), ``young_inputs``
    those whose coin no flush had, ``young_dense_inputs`` the ones among
    them that spend no coinbase, ``youngest_dense_age`` the fewest blocks
    between a spent output that is no coinbase's and its spend."""
    utxo: dict = {}      # key36 -> (height, coinbase, value, spk)
    fresh: dict = {}     # made since the last flush, still unspent -> row
    gone = [1]           # product of the elements deleted since then
    acc = [1]
    counts = {"deletes": 0, "signatures": 0, "store_reads": 0,
              "young_inputs": 0, "young_dense_inputs": 0,
              "youngest_dense_age": None}
    flushes: list = []
    flushed = [0]
    height = -1
    prev_hash = None
    tips = []
    signed = []

    def flush(best: bytes) -> None:
        state = _mod_p(acc[0] * product(fresh.values()))
        if gone[0] != 1:
            state = _mod_p(state * pow(gone[0], -1, P))
        acc[0] = state
        flushes.append({"height": height, "best_block": ref.hash_hex(best),
                        "puts": len(fresh), "deletes": counts["deletes"],
                        "signatures": counts["signatures"],
                        "digest": digest_of(state), "utxos": len(utxo)})
        fresh.clear()
        gone[0], flushed[0] = 1, height
        counts["deletes"] = counts["signatures"] = 0

    for header, txs in ref.read_block_files(blocks_dir):
        if prev_hash is not None and header[4:36] != prev_hash:
            raise ValueError(f"block after height {height} does not extend "
                             f"the one before it: not a linear chain")
        height += 1
        prev_hash = ref.sha256d(header)
        for t, tx in enumerate(txs):
            if t:
                for i, (prevout, _, _) in enumerate(tx["vin"]):
                    made, coinbase, value, spk = utxo.pop(prevout)
                    signed.append((height, tx, i, value, spk))
                    counts["signatures"] += 1
                    if not coinbase:
                        youngest = counts["youngest_dense_age"]
                        if youngest is None or height - made < youngest:
                            counts["youngest_dense_age"] = height - made
                    if made > flushed[0]:
                        counts["young_inputs"] += 1
                        counts["young_dense_inputs"] += not coinbase
                        del fresh[prevout]
                    else:
                        counts["store_reads"] += 1
                        counts["deletes"] += 1
                        gone[0] = _mod_p(gone[0] * element(coin_row(
                            prevout, made, coinbase, value, spk)))
            for n, (value, spk) in enumerate(tx["vout"]):
                key = tx["txid"] + struct.pack("<I", n)
                utxo[key] = (height, t == 0, value, spk)
                fresh[key] = coin_row(key, height, t == 0, value, spk)
        tips.append((prev_hash, len(utxo)))
        if height == 0 or height - flushed[0] >= flush_interval:
            flush(prev_hash)
    flush(prev_hash)  # the end of the import

    if sample >= len(signed):
        chosen = list(range(len(signed)))
    else:
        rng = random.Random(int(seed) ^ 0x5EED)
        chosen = sorted({0, len(signed) - 1,
                         *rng.sample(range(len(signed)), sample - 2)})
    first_bad = None
    for k in chosen:
        h, tx, i, value, spk = signed[k]
        if not ref.verify_p2pkh_input(tx, i, value, spk):
            first_bad = h
            break
    tip_height = height if first_bad is None else first_bad - 1
    return {
        "height": tip_height,
        "tip_hash": ref.hash_hex(tips[tip_height][0]),
        "utxos": tips[tip_height][1],
        "signed_inputs": sum(1 for s in signed if s[0] <= tip_height),
        "sampled": len(chosen), "first_bad_height": first_bad,
        "flushes": flushes,
        **{k: counts[k] for k in ("store_reads", "young_inputs",
                                  "young_dense_inputs",
                                  "youngest_dense_age")},
    }


def disk_rows(chainstate_dir: str) -> dict:
    """The coin rows of the shard files as they lie on disk: their count
    and their digest. The files are opened read-only."""
    count, acc = 0, 1
    for path in sorted(glob.glob(os.path.join(chainstate_dir,
                                              "chainstate.shard*.sqlite"))):
        db = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
        try:
            rows = db.execute("SELECT k, v FROM kv WHERE k >= x'43' "
                              "AND k < x'44'")
            for k, v in rows:
                count += 1
                acc = _mod_p(acc * element(k[1:] + v))
        finally:
            db.close()
    return {"rows": count, "digest": digest_of(acc)}
