"""The plain reference of the configuration archival-reindex-mixed.

reference.py's copy for a chain of four script kinds: the templates are
matched by hand, the SIGHASH_FORKID digest takes the redeem script as script
code under pay-to-script-hash, and OP_CHECKMULTISIG's key trials are replayed
in Python integers and counted. Nothing here imports the program; block
files, transactions, digest and ECDSA come from reference.py.

Kinds (of the output an input spends):

* ``p2pkh``          DUP HASH160 <20> EQUALVERIFY CHECKSIG, scriptSig <sig> <key>
* ``p2pk``           <key> CHECKSIG, scriptSig <sig>
* ``bare_multisig``  m <key>.. n CHECKMULTISIG, scriptSig 0 <sig>..
* ``p2sh_multisig``  HASH160 <20> EQUAL over such a script, scriptSig
                     0 <sig>.. <redeem script>

The key-trial rule (Bitcoin Core's EvalScript, src/test/multisig_tests.cpp):
signatures and keys are walked from the last pushed to the first; a trial
compares the current signature with the current key, a match moves on to the
next signature, every trial moves on to the next key, and the walk fails as
soon as more signatures than keys are left.
"""

from __future__ import annotations

import hashlib
import random
import struct

import reference as ref

KINDS = ("p2pkh", "p2sh_multisig", "p2pk", "bare_multisig")
MULTISIG_KINDS = ("p2sh_multisig", "bare_multisig")
HASHTYPE = 0x41  # SIGHASH_ALL | SIGHASH_FORKID, the only one this chain signs
OP_CHECKSIG, OP_CHECKMULTISIG = 0xAC, 0xAE


def hash160(data: bytes) -> bytes:
    return hashlib.new("ripemd160", hashlib.sha256(data).digest()).digest()


def push_items(script: bytes):
    """The items of a push-only script (OP_0, direct pushes, PUSHDATA1/2), or
    None where the script is anything else."""
    items, pos = [], 0
    while pos < len(script):
        op = script[pos]
        pos += 1
        if op == 0:
            items.append(b"")
            continue
        if op <= 75:
            n = op
        elif op == 0x4C and pos + 1 <= len(script):
            n = script[pos]
            pos += 1
        elif op == 0x4D and pos + 2 <= len(script):
            (n,) = struct.unpack_from("<H", script, pos)
            pos += 2
        else:
            return None
        if pos + n > len(script):
            return None
        items.append(script[pos:pos + n])
        pos += n
    return items


def parse_multisig(script: bytes):
    """(m, [key]) of ``m <key>.. n CHECKMULTISIG`` with 33- or 65-byte keys,
    or None."""
    if len(script) < 3 or script[-1] != OP_CHECKMULTISIG:
        return None
    m, n = script[0] - 0x50, script[-2] - 0x50
    keys, pos = [], 1
    while pos < len(script) - 2:
        size = script[pos]
        if size not in (33, 65) or pos + 1 + size > len(script) - 2:
            return None
        keys.append(script[pos + 1:pos + 1 + size])
        pos += 1 + size
    if not 1 <= m <= n <= 16 or len(keys) != n:
        return None
    return m, keys


def kind_of(spent_spk: bytes, script_sig: bytes = b""):
    """The kind of a spent output, from its script alone where that is
    enough; under pay-to-script-hash the scriptSig's last push decides."""
    if (len(spent_spk) == 25 and spent_spk[:3] == b"\x76\xa9\x14"
            and spent_spk[23:] == b"\x88\xac"):
        return "p2pkh"
    if (len(spent_spk) in (35, 67) and spent_spk[0] == len(spent_spk) - 2
            and spent_spk[-1] == OP_CHECKSIG):
        return "p2pk"
    if (len(spent_spk) == 23 and spent_spk[:2] == b"\xa9\x14"
            and spent_spk[22] == 0x87):
        items = push_items(script_sig)
        if items and parse_multisig(items[-1]) is not None:
            return "p2sh_multisig"
        return None
    if parse_multisig(spent_spk) is not None:
        return "bare_multisig"
    return None


def _check_one(tx: dict, index: int, value: int, script_code: bytes,
               sig: bytes, pubkey: bytes) -> bool:
    if len(sig) < 9 or sig[-1] != HASHTYPE:
        return False
    digest = ref.forkid_digest(tx, index, script_code, value, HASHTYPE)
    return ref.ecdsa_verify(pubkey, sig[:-1], digest)


def multisig_trials(tx: dict, index: int, value: int, script_code: bytes,
                    m: int, keys: list, sigs: list) -> tuple:
    """(verdict, key trials, positions of the keys that matched, first key
    of the script = 0)."""
    if len(sigs) != m:
        return False, 0, ()
    si, ki = len(sigs) - 1, len(keys) - 1
    trials, matched = 0, []
    while si >= 0:
        trials += 1
        if _check_one(tx, index, value, script_code, sigs[si], keys[ki]):
            matched.append(ki)
            si -= 1
        ki -= 1
        if si > ki:  # more signatures than keys left
            return False, trials, tuple(sorted(matched))
    return True, trials, tuple(sorted(matched))


def verify_input(tx: dict, index: int, spent_value: int,
                 spent_spk: bytes) -> dict:
    """One input of one of the four kinds, script and signatures both:
    {kind, ok, trials, signers}. ``trials`` counts OP_CHECKMULTISIG's key
    trials (0 for a single-signature kind)."""
    script_sig = tx["vin"][index][1]
    kind = kind_of(spent_spk, script_sig)
    out = {"kind": kind, "ok": False, "trials": 0, "signers": ()}
    items = push_items(script_sig)
    if kind is None or items is None:
        return out
    if kind == "p2pkh":
        out["ok"] = ref.verify_p2pkh_input(tx, index, spent_value, spent_spk)
        return out
    if kind == "p2pk":
        out["ok"] = len(items) == 1 and _check_one(
            tx, index, spent_value, spent_spk, items[0], spent_spk[1:-1])
        return out
    if kind == "p2sh_multisig":
        script_code = items.pop()
        if hash160(script_code) != spent_spk[2:22]:
            return out
    else:
        script_code = spent_spk
    m, keys = parse_multisig(script_code)
    if not items or items[0] != b"":  # the dummy element, NULLDUMMY
        return out
    out["ok"], out["trials"], out["signers"] = multisig_trials(
        tx, index, spent_value, script_code, m, keys, items[1:])
    return out


def scan_chain(blocks_dir: str, seed: int, sample: int) -> dict:
    """reference.scan_chain for the mixed chain: replay the block files,
    sort every signed input by kind, and verify ``sample`` inputs of each
    kind drawn from the seed (all of a kind where it has no more), the
    first and the last of each kind always among them.

    The tip reported is the last block before the first sampled input that
    does not verify. ``multisig_sampled`` lists what the key-trial replay
    found on each sampled multisig input: (kind, its place among the
    chain's inputs of that kind, signers, trials)."""
    utxo: dict = {}
    height = -1
    prev_hash = None
    tips = []
    by_kind = {kind: [] for kind in KINDS}
    unknown = 0
    for header, txs in ref.read_block_files(blocks_dir):
        if prev_hash is not None and header[4:36] != prev_hash:
            raise ValueError(f"block after height {height} does not extend "
                             f"the one before it: not a linear chain")
        height += 1
        prev_hash = ref.sha256d(header)
        for t, tx in enumerate(txs):
            if t:
                for i, (prevout, script_sig, _) in enumerate(tx["vin"]):
                    value, spk = utxo.pop(prevout)  # KeyError: a bad spend
                    kind = kind_of(spk, script_sig)
                    if kind is None:
                        unknown += 1
                    else:
                        by_kind[kind].append((height, tx, i, value, spk))
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
    for h, tx, i, value, spk, place in chosen:
        got = verify_input(tx, i, value, spk)
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
        "signed_inputs": sum(inputs.values()), "inputs_by_kind": inputs,
        "inputs_of_unknown_kind": unknown,
        "sampled": sum(sampled.values()), "sampled_by_kind": sampled,
        "multisig_sampled": multisig_sampled,
        "first_bad_height": first_bad,
    }
