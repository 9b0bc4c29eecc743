"""The plain references the cells are compared with.

Straightforward implementations of the same semantics, independent of the
code under test: nothing here imports the program. Pure Python and hashlib.

* block files: the (magic, size, block) records of blk?????.dat, blocks and
  transactions parsed by hand, a UTXO set kept as a dict;
* signatures: the BIP143-style SIGHASH_FORKID digest and ECDSA verification
  over secp256k1 with Python integers;
* proof of work: double SHA-256 of an 80-byte header against the target its
  nBits decode to.
"""

from __future__ import annotations

import glob
import hashlib
import os
import random
import struct

P = 2**256 - 2**32 - 977
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
     0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8)


def sha256d(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def hash_hex(h: bytes) -> str:
    """Display order: byte-reversed."""
    return h[::-1].hex()


# -- proof of work -----------------------------------------------------------

def compact_to_target(bits: int) -> int:
    exponent, mantissa = bits >> 24, bits & 0x007FFFFF
    if exponent <= 3:
        return mantissa >> (8 * (3 - exponent))
    return mantissa << (8 * (exponent - 3))


def header_pow(header80: bytes) -> tuple[str, int, int]:
    """(hash in display order, hash as a number, target from the header's
    own nBits)."""
    if len(header80) != 80:
        raise ValueError(f"a header is 80 bytes, got {len(header80)}")
    h = sha256d(header80)
    (bits,) = struct.unpack_from("<I", header80, 72)
    return hash_hex(h), int.from_bytes(h, "little"), compact_to_target(bits)


# -- secp256k1 ---------------------------------------------------------------

def _add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    if a[0] == b[0]:
        if (a[1] + b[1]) % P == 0:
            return None
        lam = 3 * a[0] * a[0] * pow(2 * a[1], -1, P) % P
    else:
        lam = (b[1] - a[1]) * pow(b[0] - a[0], -1, P) % P
    x = (lam * lam - a[0] - b[0]) % P
    return x, (lam * (a[0] - x) - a[1]) % P


def _mul(k: int, pt):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, pt)
        pt = _add(pt, pt)
        k >>= 1
    return acc


def parse_pubkey(raw: bytes):
    if len(raw) == 33 and raw[0] in (2, 3):
        x = int.from_bytes(raw[1:], "big")
        y = pow((x * x * x + 7) % P, (P + 1) // 4, P)
        if (y * y - x * x * x - 7) % P or x >= P:
            return None
        return x, y if (y & 1) == (raw[0] & 1) else P - y
    if len(raw) == 65 and raw[0] == 4:
        x, y = (int.from_bytes(raw[1:33], "big"),
                int.from_bytes(raw[33:], "big"))
        return (x, y) if (y * y - x * x * x - 7) % P == 0 else None
    return None


def parse_der(sig: bytes):
    """Strict-enough DER: 0x30 len 0x02 rlen r 0x02 slen s."""
    if len(sig) < 8 or sig[0] != 0x30 or sig[1] != len(sig) - 2:
        return None
    if sig[2] != 0x02:
        return None
    rlen = sig[3]
    r = int.from_bytes(sig[4:4 + rlen], "big")
    rest = sig[4 + rlen:]
    if len(rest) < 2 or rest[0] != 0x02 or rest[1] != len(rest) - 2:
        return None
    return r, int.from_bytes(rest[2:], "big")


def ecdsa_verify(pubkey: bytes, der_sig: bytes, digest: bytes) -> bool:
    q, rs = parse_pubkey(pubkey), parse_der(der_sig)
    if q is None or rs is None:
        return False
    r, s = rs
    if not (0 < r < N and 0 < s < N):
        return False
    w = pow(s, -1, N)
    z = int.from_bytes(digest, "big")
    pt = _add(_mul(z * w % N, G), _mul(r * w % N, q))
    return pt is not None and pt[0] % N == r


# -- blocks and transactions -------------------------------------------------

class _Reader:
    def __init__(self, data: bytes, pos: int = 0):
        self.data, self.pos = data, pos

    def take(self, n: int) -> bytes:
        out = self.data[self.pos:self.pos + n]
        if len(out) != n:
            raise ValueError("truncated")
        self.pos += n
        return out

    def u32(self) -> int:
        return struct.unpack("<I", self.take(4))[0]

    def u64(self) -> int:
        return struct.unpack("<Q", self.take(8))[0]

    def varint(self) -> int:
        first = self.take(1)[0]
        if first < 0xFD:
            return first
        width = {0xFD: 2, 0xFE: 4, 0xFF: 8}[first]
        return int.from_bytes(self.take(width), "little")

    def varbytes(self) -> bytes:
        return self.take(self.varint())


def parse_tx(r: _Reader) -> dict:
    start = r.pos
    version = r.u32()
    vin = []
    for _ in range(r.varint()):
        prevout = r.take(36)
        script_sig = r.varbytes()
        vin.append((prevout, script_sig, r.u32()))
    vout = []
    for _ in range(r.varint()):
        value = r.u64()
        vout.append((value, r.varbytes()))
    locktime = r.u32()
    return {"version": version, "vin": vin, "vout": vout,
            "locktime": locktime, "txid": sha256d(r.data[start:r.pos])}


def read_block_files(blocks_dir: str):
    """Yield (header80, [tx]) for every record of blk?????.dat, in file
    order; the magic is whatever the first file starts with."""
    magic = None
    for path in sorted(glob.glob(os.path.join(blocks_dir, "blk?????.dat"))):
        with open(path, "rb") as f:
            data = f.read()
        pos = 0
        while pos + 8 <= len(data):
            if magic is None:
                magic = data[:4]
            if data[pos:pos + 4] != magic:
                pos += 1
                continue
            (size,) = struct.unpack_from("<I", data, pos + 4)
            if size == 0 or pos + 8 + size > len(data):
                break
            r = _Reader(data, pos + 8)
            header = r.take(80)
            yield header, [parse_tx(r) for _ in range(r.varint())]
            pos += 8 + size


def _push_items(script: bytes) -> list:
    """The data pushes of a push-only script (direct pushes 1..75 bytes)."""
    items, pos = [], 0
    while pos < len(script):
        n = script[pos]
        if not 1 <= n <= 75:
            return []
        items.append(script[pos + 1:pos + 1 + n])
        pos += 1 + n
    return items


def forkid_digest(tx: dict, index: int, script_code: bytes, amount: int,
                  hashtype: int) -> bytes:
    """SIGHASH_ALL | SIGHASH_FORKID, the BIP143 layout."""
    prevouts = sha256d(b"".join(p for p, _, _ in tx["vin"]))
    sequences = sha256d(b"".join(struct.pack("<I", s)
                                 for _, _, s in tx["vin"]))
    outputs = sha256d(b"".join(
        struct.pack("<Q", v) + bytes([len(spk)]) + spk
        for v, spk in tx["vout"]))
    prevout, _, sequence = tx["vin"][index]
    return sha256d(
        struct.pack("<I", tx["version"]) + prevouts + sequences + prevout
        + bytes([len(script_code)]) + script_code + struct.pack("<Q", amount)
        + struct.pack("<I", sequence) + outputs
        + struct.pack("<I", tx["locktime"]) + struct.pack("<I", hashtype))


def verify_p2pkh_input(tx: dict, index: int, spent_value: int,
                       spent_spk: bytes) -> bool:
    """One pay-to-pubkey-hash input, script and signature both."""
    if not (len(spent_spk) == 25 and spent_spk[:3] == b"\x76\xa9\x14"
            and spent_spk[23:] == b"\x88\xac"):
        return False
    items = _push_items(tx["vin"][index][1])
    if len(items) != 2 or not items[0]:
        return False
    sig, pubkey = items
    hash160 = hashlib.new("ripemd160", hashlib.sha256(pubkey).digest())
    if hash160.digest() != spent_spk[3:23]:
        return False
    hashtype = sig[-1]
    if hashtype != 0x41:
        return False
    digest = forkid_digest(tx, index, spent_spk, spent_value, hashtype)
    return ecdsa_verify(pubkey, sig[:-1], digest)


def scan_chain(blocks_dir: str, seed: int, sample: int) -> dict:
    """Replay a linear chain from its block files: heights, the tip, the
    unspent outputs, the signed inputs; verify a sample of the signed
    inputs drawn from the seed (all of them where there are no more than
    ``sample``), the chain's first and last signed input always among them.

    The tip reported is the last block before the first sampled input that
    does not verify: what a validator has to stop at."""
    utxo: dict = {}
    height = -1
    prev_hash = None
    tips = []    # (block hash, unspent outputs after it) by height
    signed = []  # (height, tx, input index, spent value, spent spk)
    for header, txs in read_block_files(blocks_dir):
        if prev_hash is not None and header[4:36] != prev_hash:
            raise ValueError(f"block after height {height} does not extend "
                             f"the one before it: not a linear chain")
        height += 1
        prev_hash = sha256d(header)
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
        if not verify_p2pkh_input(tx, i, value, spk):
            first_bad = h
            break
    tip_height = height if first_bad is None else first_bad - 1
    return {
        "height": tip_height,
        "tip_hash": hash_hex(tips[tip_height][0]),
        "utxos": tips[tip_height][1],
        "signed_inputs": sum(1 for s in signed if s[0] <= tip_height),
        "sampled": len(chosen), "first_bad_height": first_bad,
    }
