"""The plain reference of the configuration archival-reindex-prefork.

reference_mixed.py's copy for the same four script kinds signed as history
below the fork height has them: the digest is the original SignatureHash,
written here from Bitcoin Core's description of it (interpreter.cpp,
CTransactionSignatureSerializer) and checked once against
tests/data/sighash.json (tests/unit/test_prefork_lanes.py). Nothing here
imports the program: block files, transactions and ECDSA come from
reference.py, the script matchers from reference_mixed.py.

The original SignatureHash of input ``i`` under hashtype ``h``: the
transaction serialised again with the script code (OP_CODESEPARATORs and
every push of the signature itself removed) as input i's script and every
other input's script empty; under SIGHASH_NONE no outputs, under
SIGHASH_SINGLE the outputs up to i with those before i blanked (value -1, no
script), and in both the other inputs' sequences zeroed; under ANYONECANPAY
input i alone; then ``h`` as four bytes; SHA-256 twice. SIGHASH_SINGLE
without an output i signs the number 1. Every digest hashes the whole
transaction again: quadratic in its inputs (``legacy_preimage_bytes``).

Without NULLDUMMY, STRICTENC and LOW_S the era accepts more encodings than
the chain from the fork height on; this reference knows the ones the
generator writes (strict DER, low S, SIGHASH_ALL, the dummy OP_0) and the
other five defined hashtypes, and refuses the rest.
"""

from __future__ import annotations

import random
import struct

import reference as ref
import reference_mixed as forms

KINDS = forms.KINDS
MULTISIG_KINDS = forms.MULTISIG_KINDS
SIGHASH_NONE, SIGHASH_SINGLE, ANYONECANPAY = 2, 3, 0x80
HASHTYPES = (1, 2, 3, 0x81, 0x82, 0x83)
OP_CODESEPARATOR = 0xAB
ONE = (1).to_bytes(32, "little")


def _varint(n: int) -> bytes:
    if n < 0xFD:
        return bytes([n])
    if n <= 0xFFFF:
        return b"\xfd" + struct.pack("<H", n)
    if n <= 0xFFFFFFFF:
        return b"\xfe" + struct.pack("<I", n)
    return b"\xff" + struct.pack("<Q", n)


def _ops(script: bytes):
    """(start, end, opcode) of every operation, pushes with their data; stops
    where a push runs past the end, as upstream's GetOp does."""
    pos = 0
    while pos < len(script):
        op, start = script[pos], pos
        pos += 1
        if op <= 0x4E:
            if op < 0x4C:
                size = op
            else:
                width = {0x4C: 1, 0x4D: 2, 0x4E: 4}[op]
                if pos + width > len(script):
                    return
                size = int.from_bytes(script[pos:pos + width], "little")
                pos += width
            if pos + size > len(script):
                return
            pos += size
        yield start, pos, op


def script_code_for(script: bytes, sig: bytes = b"") -> bytes:
    """The script a legacy signature commits to: ``script`` without its
    OP_CODESEPARATORs and without any push of ``sig`` (FindAndDelete: whole
    operations equal to the signature's minimal push, nothing inside
    another push's data)."""
    push = b""
    if sig:
        head = (bytes([len(sig)]) if len(sig) < 0x4C
                else b"\x4c" + bytes([len(sig)]))
        push = head + sig
    out, copied = bytearray(), 0
    for start, end, op in _ops(script):
        if op == OP_CODESEPARATOR or (push and script[start:end] == push):
            out += script[copied:start]
            copied = end
    return bytes(out + script[copied:])


def legacy_preimage(tx: dict, index: int, script_code: bytes,
                    hashtype: int) -> bytes:
    """What the original SignatureHash hashes, or b"" where it signs the
    number 1 instead."""
    base = hashtype & 0x1F
    if index >= len(tx["vin"]) or (base == SIGHASH_SINGLE
                                   and index >= len(tx["vout"])):
        return b""
    cut = base in (SIGHASH_NONE, SIGHASH_SINGLE)
    inputs = []
    for j, (prevout, _, sequence) in enumerate(tx["vin"]):
        if j == index:
            inputs.append(prevout + _varint(len(script_code)) + script_code
                          + struct.pack("<I", sequence))
        elif not hashtype & ANYONECANPAY:
            inputs.append(prevout + b"\x00"
                          + struct.pack("<I", 0 if cut else sequence))
    if base == SIGHASH_NONE:
        outputs = []
    elif base == SIGHASH_SINGLE:
        outputs = [(0xFFFFFFFFFFFFFFFF, b"")] * index + [tx["vout"][index]]
    else:
        outputs = tx["vout"]
    return (struct.pack("<I", tx["version"]) + _varint(len(inputs))
            + b"".join(inputs) + _varint(len(outputs))
            + b"".join(struct.pack("<Q", value) + _varint(len(spk)) + spk
                       for value, spk in outputs)
            + struct.pack("<I", tx["locktime"])
            + struct.pack("<I", hashtype & 0xFFFFFFFF))


def legacy_digest(tx: dict, index: int, script_code: bytes,
                  hashtype: int) -> bytes:
    preimage = legacy_preimage(tx, index, script_code, hashtype)
    return ref.sha256d(preimage) if preimage else ONE


def legacy_preimage_bytes(tx: dict, index: int, script_code: bytes) -> int:
    """len(legacy_preimage(..., SIGHASH_ALL)) without building it: every
    input at 41 bytes but this one, which carries the script code."""
    outputs = sum(8 + len(_varint(len(spk))) + len(spk)
                  for _, spk in tx["vout"])
    return (4 + len(_varint(len(tx["vin"]))) + 41 * len(tx["vin"])
            - 1 + len(_varint(len(script_code))) + len(script_code)
            + len(_varint(len(tx["vout"]))) + outputs + 4 + 4)


def _check_one(tx: dict, index: int, script: bytes, sig: bytes,
               pubkey: bytes) -> bool:
    if len(sig) < 9 or sig[-1] not in HASHTYPES:
        return False
    digest = legacy_digest(tx, index, script_code_for(script, sig), sig[-1])
    return ref.ecdsa_verify(pubkey, sig[:-1], digest)


def multisig_trials(tx: dict, index: int, script: bytes, m: int, keys: list,
                    sigs: list) -> tuple:
    """Upstream's key-trial walk, from the last signature and key to the
    first: (verdict, key trials, positions of the keys that matched)."""
    if len(sigs) != m:
        return False, 0, ()
    # every signature's push is cut from the script before the first trial
    for sig in sigs:
        script = script_code_for(script, sig)
    si, ki = len(sigs) - 1, len(keys) - 1
    trials, matched = 0, []
    while si >= 0:
        trials += 1
        if _check_one(tx, index, script, sigs[si], keys[ki]):
            matched.append(ki)
            si -= 1
        ki -= 1
        if si > ki:  # more signatures than keys left
            return False, trials, tuple(sorted(matched))
    return True, trials, tuple(sorted(matched))


def _scripts_of(tx: dict, index: int, spent_spk: bytes) -> tuple:
    """(kind, the script the input's signatures commit to, its signatures,
    the key of a single-signature kind); the script is None where the
    scriptSig is not the kind's."""
    script_sig = tx["vin"][index][1]
    kind = forms.kind_of(spent_spk, script_sig)
    items = forms.push_items(script_sig) or []
    refused = kind, None, [], b""
    if kind == "p2pkh":
        if len(items) != 2 or forms.hash160(items[1]) != spent_spk[3:23]:
            return refused
        return kind, spent_spk, items[:1], items[1]
    if kind == "p2pk":
        if len(items) != 1:
            return refused
        return kind, spent_spk, items, spent_spk[1:-1]
    # the dummy element as wallets wrote it
    if kind is None or not items or items[0] != b"":
        return refused
    if kind == "p2sh_multisig":
        if forms.hash160(items[-1]) != spent_spk[2:22]:
            return refused
        return kind, items[-1], items[1:-1], b""
    return kind, spent_spk, items[1:], b""


def verify_input(tx: dict, index: int, spent_spk: bytes) -> dict:
    """One input of one of the four kinds, script and signatures both:
    {kind, ok, trials, signers}; ``trials`` counts OP_CHECKMULTISIG's key
    trials. The legacy digest does not commit to the spent value."""
    kind, script, sigs, key = _scripts_of(tx, index, spent_spk)
    out = {"kind": kind, "ok": False, "trials": 0, "signers": ()}
    if script is None:
        return out
    if kind in MULTISIG_KINDS:
        m, keys = forms.parse_multisig(script)
        out["ok"], out["trials"], out["signers"] = multisig_trials(
            tx, index, script, m, keys, sigs)
    else:
        out["ok"] = _check_one(tx, index, script, sigs[0], key)
    return out


def sighash_cost(tx: dict, index: int, spent_spk: bytes) -> tuple:
    """(digests, bytes they hash) of one input, from its scripts alone: one
    digest a distinct hashtype among its signatures, each over the whole
    transaction serialised again."""
    _, script, sigs, _ = _scripts_of(tx, index, spent_spk)
    if script is None:
        return 0, 0
    hashtypes = {sig[-1] for sig in sigs if sig}
    if hashtypes == {1}:
        return 1, legacy_preimage_bytes(tx, index, script)
    return len(hashtypes), sum(
        len(legacy_preimage(tx, index, script, h)) for h in hashtypes)


def scan_chain(blocks_dir: str, seed: int, sample: int) -> dict:
    """reference_mixed.scan_chain under the legacy digest: replay the block
    files, sort every signed input by kind, count what the chain's digests
    hash, and verify ``sample`` inputs of each kind drawn from the seed (all
    of a kind where it has no more), the first and the last of each kind
    always among them. The tip reported is the last block before the first
    sampled input that does not verify."""
    utxo: dict = {}
    height = -1
    prev_hash = None
    tips = []
    by_kind = {kind: [] for kind in KINDS}
    unknown = digests = sighash_bytes = spend_blocks = 0
    for header, txs in ref.read_block_files(blocks_dir):
        if prev_hash is not None and header[4:36] != prev_hash:
            raise ValueError(f"block after height {height} does not extend "
                             f"the one before it: not a linear chain")
        height += 1
        prev_hash = ref.sha256d(header)
        spend_blocks += len(txs) > 1
        for t, tx in enumerate(txs):
            if t:
                for i, (prevout, script_sig, _) in enumerate(tx["vin"]):
                    _, spk = utxo.pop(prevout)  # KeyError: a bad spend
                    kind = forms.kind_of(spk, script_sig)
                    if kind is None:
                        unknown += 1
                        continue
                    by_kind[kind].append((height, tx, i, spk))
                    n, size = sighash_cost(tx, i, spk)
                    digests += n
                    sighash_bytes += size
            for n, out in enumerate(tx["vout"]):
                utxo[tx["txid"] + struct.pack("<I", n)] = out
        tips.append((prev_hash, len(utxo)))
    rng = random.Random(int(seed) ^ 0x5EED)
    chosen = []
    for kind in KINDS:
        signed = by_kind[kind]
        if sample >= len(signed):
            picks = range(len(signed))
        else:
            picks = sorted({0, len(signed) - 1,
                            *rng.sample(range(len(signed)), sample - 2)})
        chosen += [(*signed[k], k) for k in picks]
    chosen.sort(key=lambda item: item[0])
    first_bad = None
    sampled = {kind: 0 for kind in KINDS}
    multisig_sampled = []
    for h, tx, i, spk, place in chosen:
        got = verify_input(tx, i, spk)
        sampled[got["kind"]] += 1
        if got["kind"] in MULTISIG_KINDS:
            multisig_sampled.append(
                (got["kind"], place, list(got["signers"]), got["trials"]))
        if not got["ok"]:
            first_bad = h
            break
    tip_height = height if first_bad is None else first_bad - 1
    inputs = {kind: sum(1 for s in by_kind[kind] if s[0] <= tip_height)
              for kind in KINDS}
    return {
        "height": tip_height,
        "tip_hash": ref.hash_hex(tips[tip_height][0]),
        "utxos": tips[tip_height][1],
        "blocks": height, "spend_blocks": spend_blocks,
        "signed_inputs": sum(inputs.values()), "inputs_by_kind": inputs,
        "inputs_of_unknown_kind": unknown,
        "legacy_digests": digests, "legacy_sighash_bytes": sighash_bytes,
        "sampled": sum(sampled.values()), "sampled_by_kind": sampled,
        "multisig_sampled": multisig_sampled,
        "first_bad_height": first_bad,
    }
