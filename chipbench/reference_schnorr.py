"""The plain reference of the configuration archival-reindex-schnorr.

reference.py's replay of the block files (a linear chain, a UTXO set kept as
a dict, the signed inputs in chain order, a sample of them drawn from the
seed with the first and the last always among them) for a chain whose every
signature is a BCH Schnorr signature, and this file's own verification of
the sampled inputs: the SIGHASH_FORKID digest and the Schnorr equation, both
written here from their specifications. Nothing here imports the program:
block files, transaction parsing and the curve's point arithmetic come from
reference.py.

The signature (bitcoincashorg, 2019-05-15-schnorr.md): 64 bytes r || s, then
the hashtype byte, 65 in all in OP_CHECKSIG; with P the public key's point
and m the input's 32-byte digest

    fail if r >= p or s >= n
    e  = SHA256(r as 32 bytes || P in compressed form || m) mod n
    R' = s*G + (n - e)*P
    accept iff R' is not infinity, jacobi(R'.y) = 1 and R'.x = r

with jacobi(y) = y^((p-1)/2) mod p. The digest (the replay-protected
sighash of the 2017-08-01 fork, BIP143's layout): double SHA-256 of version,
the hash of all prevouts, the hash of all sequences, this input's prevout,
its script code with its length, the spent amount, this input's sequence,
the hash of all outputs, the locktime and the hashtype as four bytes. Only
SIGHASH_ALL|SIGHASH_FORKID (0x41) is known here: the chain has no other.
"""

from __future__ import annotations

import hashlib
import random
import struct

import reference as ref

P, N, G = ref.P, ref.N, ref.G
HASHTYPE = 0x41  # SIGHASH_ALL | SIGHASH_FORKID


def _dsha(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def _compact(n: int) -> bytes:
    if n < 0xFD:
        return bytes([n])
    if n <= 0xFFFF:
        return b"\xfd" + struct.pack("<H", n)
    return b"\xfe" + struct.pack("<I", n)


def forkid_digest(tx: dict, index: int, script_code: bytes,
                  amount: int) -> bytes:
    """The digest input ``index`` signs under SIGHASH_ALL|SIGHASH_FORKID."""
    prevouts = b"".join(prevout for prevout, _, _ in tx["vin"])
    sequences = b"".join(struct.pack("<I", seq) for _, _, seq in tx["vin"])
    outputs = b"".join(struct.pack("<q", value) + _compact(len(spk)) + spk
                       for value, spk in tx["vout"])
    prevout, _, sequence = tx["vin"][index]
    return _dsha(b"".join((
        struct.pack("<I", tx["version"]), _dsha(prevouts), _dsha(sequences),
        prevout, _compact(len(script_code)), script_code,
        struct.pack("<q", amount), struct.pack("<I", sequence),
        _dsha(outputs), struct.pack("<I", tx["locktime"]),
        struct.pack("<I", HASHTYPE))))


def jacobi(y: int) -> int:
    return pow(y, (P - 1) // 2, P)


def schnorr_verify(pubkey: bytes, sig64: bytes, digest: bytes) -> bool:
    """The specification's verification, line for line."""
    point = ref.parse_pubkey(pubkey)
    if point is None or len(sig64) != 64:
        return False
    r = int.from_bytes(sig64[:32], "big")
    s = int.from_bytes(sig64[32:], "big")
    if r >= P or s >= N:
        return False
    compressed = bytes([2 | (point[1] & 1)]) + point[0].to_bytes(32, "big")
    e = int.from_bytes(hashlib.sha256(
        sig64[:32] + compressed + digest).digest(), "big") % N
    found = ref._add(ref._mul(s, G), ref._mul((N - e) % N, point))
    return found is not None and jacobi(found[1]) == 1 and found[0] == r


def verify_p2pkh_schnorr_input(tx: dict, index: int, spent_value: int,
                               spent_spk: bytes) -> bool:
    """One pay-to-pubkey-hash input under a 65-byte signature: the script
    and the signature both."""
    if not (len(spent_spk) == 25 and spent_spk[:3] == b"\x76\xa9\x14"
            and spent_spk[23:] == b"\x88\xac"):
        return False
    items = ref._push_items(tx["vin"][index][1])
    if len(items) != 2 or len(items[0]) != 65:
        return False
    sig, pubkey = items
    digest160 = hashlib.new("ripemd160", hashlib.sha256(pubkey).digest())
    if digest160.digest() != spent_spk[3:23] or sig[64] != HASHTYPE:
        return False
    return schnorr_verify(
        pubkey, sig[:64], forkid_digest(tx, index, spent_spk, spent_value))


def scan_chain(blocks_dir: str, seed: int, sample: int) -> dict:
    """reference.scan_chain for a Schnorr chain: heights, the tip, the
    unspent outputs, the signed inputs and how many of them carry a 65-byte
    signature; a sample of the signed inputs drawn from the seed (all of
    them where there are no more than ``sample``), the chain's first and
    last signed input always among them, verified here. The tip reported is
    the last block before the first sampled input that does not verify."""
    utxo: dict = {}
    height = -1
    prev_hash = None
    tips = []    # (block hash, unspent outputs after it) by height
    signed = []  # (height, tx, input index, spent value, spent spk)
    for header, txs in ref.read_block_files(blocks_dir):
        if prev_hash is not None and header[4:36] != prev_hash:
            raise ValueError(f"block after height {height} does not extend "
                             f"the one before it: not a linear chain")
        height += 1
        prev_hash = ref.sha256d(header)
        for t, tx in enumerate(txs):
            if t:
                for i, (prevout, _, _) in enumerate(tx["vin"]):
                    value, spk = utxo.pop(prevout)  # KeyError: a bad spend
                    signed.append((height, tx, i, value, spk))
            for n, out in enumerate(tx["vout"]):
                utxo[tx["txid"] + struct.pack("<I", n)] = out
        tips.append((prev_hash, len(utxo)))
    if sample >= len(signed):
        chosen = list(range(len(signed)))
    else:
        rng = random.Random(int(seed) ^ 0x5EED)
        chosen = sorted({0, len(signed) - 1,
                         *rng.sample(range(len(signed)), sample - 2)})
    first_bad = None
    for k in chosen:
        h, tx, i, value, spk = signed[k]
        if not verify_p2pkh_schnorr_input(tx, i, value, spk):
            first_bad = h
            break
    tip_height = height if first_bad is None else first_bad - 1
    kept = [s for s in signed if s[0] <= tip_height]
    return {
        "height": tip_height,
        "tip_hash": ref.hash_hex(tips[tip_height][0]),
        "utxos": tips[tip_height][1],
        "signed_inputs": len(kept),
        "schnorr_inputs": sum(
            1 for _, tx, i, _, _ in kept
            if tx["vin"][i][1][:1] == b"\x41"),  # a push of 65 bytes
        "sampled": len(chosen), "first_bad_height": first_bad,
    }
