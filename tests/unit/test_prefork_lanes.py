"""History below the fork height on the native engine (native/connect.cpp,
script/interpreter.py ``last_check_form``): the legacy SignatureHash in
C++ against its Python specification and upstream's vectors, the script
templates under flags without NULLFAIL / STRICTENC / LOW_S / NULLDUMMY
against the Python templates byte for byte, what they decline against the
inline interpreter's verdict, and generated chains through
``Node(-regtest -reindex -uahfheight=<n>)`` against the plain reference of
the cell reindex.pre_fork (chipbench/reference_prefork.py)."""

import json
import os
import random
import struct
import sys

import numpy as np
import pytest

import test_multisig_lanes as lanes
import test_native_connect as nc
from bitcoincashplus_tpu import native
from bitcoincashplus_tpu.consensus.serialize import ByteReader
from bitcoincashplus_tpu.consensus.tx import (
    COutPoint,
    CTransaction,
    CTxIn,
    CTxOut,
)
from bitcoincashplus_tpu.crypto import secp256k1 as secp
from bitcoincashplus_tpu.crypto.hashes import hash160
from bitcoincashplus_tpu.ops import ecdsa_batch
from bitcoincashplus_tpu.script import script as S
from bitcoincashplus_tpu.script.interpreter import (
    SCRIPT_ENABLE_SIGHASH_FORKID,
    SCRIPT_VERIFY_CHECKLOCKTIMEVERIFY,
    SCRIPT_VERIFY_CHECKSEQUENCEVERIFY,
    SCRIPT_VERIFY_DERSIG,
    SCRIPT_VERIFY_P2SH,
    DeferringSignatureChecker,
    ScriptError,
    TransactionSignatureChecker,
    VerifyScript,
    last_check_form,
    multisig_walk,
)
from bitcoincashplus_tpu.script.sighash import (
    signature_hash_legacy,
    strip_code_separators,
)
from bitcoincashplus_tpu.wallet.keys import CKey
from bitcoincashplus_tpu.wallet.signing import make_signature

sys.path.insert(0, lanes.BENCH)
import reference as plain  # noqa: E402  (chipbench/reference.py)
import reference_prefork  # noqa: E402
import run as bench_run  # noqa: E402  (chipbench/run.py)

pytestmark = lanes.needs_engine

# mainnet's flags of heights 419,328 to 478,558 (block_script_flags)
PRE = (SCRIPT_VERIFY_P2SH | SCRIPT_VERIFY_DERSIG
       | SCRIPT_VERIFY_CHECKLOCKTIMEVERIFY
       | SCRIPT_VERIFY_CHECKSEQUENCEVERIFY)
AMOUNT = lanes.AMOUNT
KEYS, OUTSIDER, LONG_KEY = lanes.KEYS, lanes.OUTSIDER, lanes.LONG_KEY
HASHTYPES = (1, 2, 3, 0x81, 0x82, 0x83)
with open(os.path.join(lanes.ROOT, "tests", "data", "sighash.json")) as _f:
    VECTORS = json.load(_f)[1:]


# -- (a) the digest -----------------------------------------------------------

@pytest.mark.parametrize("n", range(len(VECTORS)))
def test_native_legacy_digest_equals_upstreams_vector(n):
    tx_hex, code_hex, in_idx, hashtype, _, legacy_hex, _ = VECTORS[n]
    raw, code = bytes.fromhex(tx_hex), bytes.fromhex(code_hex)
    # the scan's templates hold no OP_CODESEPARATOR: its digest takes the
    # script code as it is given
    digest, hashed = native.sighash_legacy(
        raw, in_idx, strip_code_separators(code), hashtype)
    assert digest.hex() == legacy_hex
    tx = CTransaction.deserialize(ByteReader(raw))
    assert digest == signature_hash_legacy(code, tx, in_idx, hashtype)
    # and the plain reference of the cell, which strips for itself
    ref_tx = plain.parse_tx(plain._Reader(raw))
    assert reference_prefork.legacy_digest(
        ref_tx, in_idx, reference_prefork.script_code_for(code),
        hashtype).hex() == legacy_hex
    assert hashed == len(reference_prefork.legacy_preimage(
        ref_tx, in_idx, strip_code_separators(code), hashtype))


def _random_tx(rng: random.Random) -> CTransaction:
    n_in, n_out = rng.choice([1, 2, 5, 40, 300]), rng.choice([1, 2, 3, 260])
    return CTransaction(
        version=rng.choice([1, 2, 0x7FFFFFFF, -1]),
        vin=tuple(CTxIn(COutPoint(rng.randbytes(32), rng.randrange(2**32)),
                        rng.randbytes(rng.randrange(0, 120)),
                        rng.choice([0, 0xFFFFFFFE, 0xFFFFFFFF,
                                    rng.randrange(2**32)]))
                  for _ in range(n_in)),
        vout=tuple(CTxOut(rng.randrange(21 * 10**14),
                          rng.randbytes(rng.choice([0, 25, 23, 35, 300])))
                   for _ in range(n_out)),
        locktime=rng.choice([0, 499_999_999, rng.randrange(2**32)]))


@pytest.mark.parametrize("hashtype", HASHTYPES, ids=hex)
@pytest.mark.parametrize("seed", [2**31 + 3201, 77, 987654321, 4])
def test_native_legacy_digest_equals_its_specification(seed, hashtype):
    """Seeded random transactions, every input of each (SIGHASH_SINGLE past
    the last output among them: the digest of 1), over the script codes a
    template can hand the digest, one of them longer than 252 bytes."""
    rng = random.Random(seed)
    ones = 0
    for _ in range(6):
        tx = _random_tx(rng)
        raw = tx.serialize()
        codes = [KEYS[0].p2pkh_script(), S.p2pk_script(LONG_KEY.pubkey),
                 S.multisig_script(15, [k.pubkey for k in KEYS[:15]])]
        for in_idx in range(len(tx.vin)):
            code = codes[in_idx % 3]
            want = signature_hash_legacy(code, tx, in_idx, hashtype)
            got, hashed = native.sighash_legacy(raw, in_idx, code, hashtype)
            assert got == want
            single_past_end = (hashtype & 0x1F == 3
                               and in_idx >= len(tx.vout))
            assert (want == (1).to_bytes(32, "little")) == single_past_end
            assert (hashed == 0) == single_past_end
            ones += single_past_end
    if hashtype & 0x1F == 3:
        assert ones  # the deck has transactions with more inputs than outputs


# -- one input through the scan, the Python templates and the interpreter -----

def _tx_around(script_sig: bytes, at: int, n_in: int, n_out: int):
    return CTransaction(
        version=1,
        vin=tuple(CTxIn(COutPoint(bytes([0x70 + i]) * 32, i),
                        script_sig if i == at else b"", 0xFFFFFFFE - i)
                  for i in range(n_in)),
        vout=tuple(CTxOut(AMOUNT // 2 - 1000 * i, b"\x51")
                   for i in range(n_out)))


class Spend:
    """One input under test: its place in a transaction of ``n_in`` inputs
    and ``n_out`` outputs (the other inputs spend OP_1 outputs with empty
    scriptSigs), the script it spends, and its scriptSig once signed."""

    def __init__(self, spk: bytes, at: int = 0, n_in: int = 1,
                 n_out: int = 1):
        self.spk, self.at, self.n_in, self.n_out = spk, at, n_in, n_out
        self.script_sig = b""

    @property
    def tx(self) -> CTransaction:
        return _tx_around(self.script_sig, self.at, self.n_in, self.n_out)

    def sign(self, key: CKey, code: bytes, hashtype: int = 1,
             forkid: bool = False) -> bytes:
        return make_signature(key, code, self.tx, self.at, AMOUNT, hashtype,
                              enable_forkid=forkid)

    def signed(self, script_sig: bytes) -> "Spend":
        self.script_sig = script_sig
        return self


def native_scan(spend: Spend, flags: int):
    """The spend through the native scan: ("error", the script error the
    P2PKH body gave) or (sig_status of the input, its lanes, its rows)."""
    tx = spend.tx
    coinbase = CTransaction(
        1, (CTxIn(COutPoint(), b"\x01\x01", 0xFFFFFFFF),),
        (CTxOut(50 * 10**8, b"\x51"),))
    raw = bytes(80) + b"\x02" + coinbase.serialize() + tx.serialize()
    eng = native.ConnectEngine()
    try:
        for i, txin in enumerate(tx.vin):
            eng.insert(txin.prevout.hash + struct.pack("<I", txin.prevout.n),
                       2, AMOUNT, spend.spk if i == spend.at else b"\x51")
        res = eng.connect_block(raw, 5, 50 * 10**8, 32_000_000, 100, 0, None,
                                flags, want_sigs=True, check_merkle=False,
                                commit=False)
    except native.EngineError as e:
        assert (e.tx_idx, e.in_idx) == (1, spend.at)
        return "error", e.reason, None
    finally:
        eng.close()
    g = spend.at
    status = int(res.sig_status[g])
    assert [int(s) for i, s in enumerate(res.sig_status) if i != g] == (
        [1] * (spend.n_in - 1))
    if status == 0:  # the P2PKH body's record, in the input's own slot
        arrays = (res.sig_pub[g:g + 1], res.sig_rs[g:g + 1],
                  res.sig_msg[g:g + 1], res.sig_rn[g:g + 1],
                  res.sig_wrap[g:g + 1], np.zeros(1, np.uint8))
        return status, arrays, [[g, 0, 0, 0]]
    return status, res.leg_lanes[:6], res.leg_table.tolist()


def python_lanes(spend: Spend, flags: int) -> tuple:
    """The Python templates: the form is one whose check comes last, and the
    deferring checker records under VerifyScript what the interpreter would
    have checked."""
    tx = spend.tx
    assert last_check_form(spend.script_sig, spend.spk, flags)
    records, groups = [], []
    VerifyScript(spend.script_sig, spend.spk, flags, DeferringSignatureChecker(
        tx, spend.at, AMOUNT, records, groups=groups, last_operation=True))
    cand = np.zeros(len(records), np.uint8)
    for g in groups:
        cand[g.start:g.start + g.lanes] = 1
    rows = ([[spend.at, g.start, g.m, g.n] for g in groups]
            or [[spend.at, 0, 0, 0]])
    return (*ecdsa_batch.records_to_blobs(records), cand), rows


def inline_verdict(spend: Spend, flags: int) -> str:
    """Today's path below the fork height: every check verified at once."""
    try:
        VerifyScript(spend.script_sig, spend.spk, flags,
                     TransactionSignatureChecker(spend.tx, spend.at, AMOUNT))
    except ScriptError as e:
        return e.code
    return "OK"


def lanes_pass(arrays: tuple, rows: list) -> bool:
    """The batch's verdicts (the CPU's here) as the import settles them:
    every must-verify lane true, every group's walk through."""
    *blobs, cand = arrays
    cand = np.asarray(cand).reshape(-1).astype(bool)
    ok = ecdsa_batch.dispatch_packed(
        *blobs, backend="cpu", candidate=cand if cand.any() else None
    ).result()
    return bool(np.all(ok | cand)) and all(
        multisig_walk(m, n, ok[first:first + m * (n - m + 1)])
        for _, first, m, n in rows if m)


# -- (b) shapes that fit ------------------------------------------------------

def _p2pkh(key: CKey, signer: CKey = None, hashtype: int = 1, **place):
    spend = Spend(key.p2pkh_script(), **place)
    sig = spend.sign(signer or key, spend.spk, hashtype)
    return spend.signed(S.push_data_raw(sig) + S.push_data_raw(key.pubkey))


def _p2pk(key: CKey, signer: CKey = None, hashtype: int = 1, **place):
    spend = Spend(S.p2pk_script(key.pubkey), **place)
    return spend.signed(S.push_data_raw(
        spend.sign(signer or key, spend.spk, hashtype)))


def _multisig(m: int, keys: list, signers: list, *, p2sh: bool = True,
              hashtypes=None, dummy: bytes = b"\x00", **place):
    code = S.multisig_script(m, [k.pubkey for k in keys])
    spend = Spend(S.p2sh_script_for_redeem(code) if p2sh else code, **place)
    hashtypes = hashtypes or [1] * len(signers)
    sigs = [spend.sign(k, code, h) for k, h in zip(signers, hashtypes)]
    return spend.signed(dummy + b"".join(map(S.push_data_raw, sigs))
                        + (S.push_data_raw(code) if p2sh else b""))


def _with_sig(spend: Spend, change) -> Spend:
    """The spend with its first signature put through ``change``."""
    pos = 1 if spend.script_sig[0] == 0 else 0
    size = spend.script_sig[pos]
    sig = change(spend.script_sig[pos + 1:pos + 1 + size])
    return spend.signed(spend.script_sig[:pos] + S.push_data_raw(sig)
                        + spend.script_sig[pos + 1 + size:])


def _fitting_cases() -> dict:
    """name -> (spend, flags, the inline verdict it ends with)."""
    cases = {}
    # PR 31's 44 shapes, signed as history below the fork height has them
    for m, n, subset in lanes.SUBSETS:
        for p2sh in (True, False):
            name = f"{'p2sh' if p2sh else 'bare'}-{m}of{n}-" + "".join(
                map(str, subset))
            cases[name] = (_multisig(m, KEYS[:n], [KEYS[i] for i in subset],
                                     p2sh=p2sh), PRE, "OK")
    cases["p2pk-33-byte-key"] = (_p2pk(KEYS[0]), PRE, "OK")
    cases["p2pk-65-byte-key"] = (_p2pk(LONG_KEY), PRE, "OK")
    # without NULLFAIL a failed last check is the script's false
    cases["p2pk-signed-by-another-key"] = (
        _p2pk(KEYS[0], OUTSIDER), PRE, "eval-false")
    cases["bare-16of16"] = (_multisig(16, KEYS[:16], KEYS[:16], p2sh=False),
                            PRE, "OK")
    cases["p2sh-1of15"] = (_multisig(1, KEYS[:15], [KEYS[9]]), PRE, "OK")
    for name, signers in (
            ("signatures_out_of_key_order", [KEYS[1], KEYS[0]]),
            ("one_signature_twice", [KEYS[1], KEYS[1]]),
            ("a_signer_outside_the_script", [KEYS[0], OUTSIDER]),
            ("both_signers_outside_the_script", [OUTSIDER, KEYS[5]])):
        cases[name] = (_multisig(2, KEYS[:3], signers), PRE, "eval-false")
    cases["uncompressed_keys_in_the_script"] = (
        _multisig(1, [LONG_KEY, KEYS[1]], [LONG_KEY]), PRE, "OK")
    # the P2PKH body
    cases["p2pkh"] = (_p2pkh(KEYS[0]), PRE, "OK")
    cases["p2pkh-65-byte-key"] = (_p2pkh(LONG_KEY), PRE, "OK")
    cases["p2pkh-signed-by-another-secret"] = (
        _p2pkh(KEYS[0], OUTSIDER), PRE, "eval-false")
    # no LOW_S below the fork height: a high S is a lane
    cases["p2pkh-high-s"] = (_with_sig(_p2pkh(KEYS[0]), lanes._high_s),
                             PRE, "OK")
    cases["p2pk-high-s"] = (_with_sig(_p2pk(KEYS[0]), lanes._high_s),
                            PRE, "OK")
    cases["p2sh-2of3-high-s"] = (_with_sig(_multisig(
        2, KEYS[:3], [KEYS[0], KEYS[2]]), lanes._high_s), PRE, "OK")
    # the six defined hashtypes, on the middle input of three with two
    # outputs, and SIGHASH_SINGLE on the input that has no output: the
    # digest of 1, which anybody can sign
    place = dict(at=1, n_in=3, n_out=2)
    for h in HASHTYPES:
        cases[f"p2pkh-hashtype-{h:#04x}"] = (
            _p2pkh(KEYS[0], hashtype=h, **place), PRE, "OK")
        cases[f"p2pk-hashtype-{h:#04x}"] = (
            _p2pk(LONG_KEY, hashtype=h, **place), PRE, "OK")
        cases[f"p2sh-2of3-hashtype-{h:#04x}"] = (_multisig(
            2, KEYS[:3], [KEYS[0], KEYS[1]], hashtypes=[h, h], **place),
            PRE, "OK")
    past = dict(at=2, n_in=3, n_out=2)
    cases["p2pkh-single-without-an-output"] = (
        _p2pkh(KEYS[0], hashtype=3, **past), PRE, "OK")
    cases["p2pk-single-without-an-output"] = (
        _p2pk(KEYS[0], hashtype=0x83, **past), PRE, "OK")
    cases["bare-1of2-single-without-an-output"] = (_multisig(
        1, KEYS[:2], [KEYS[1]], p2sh=False, hashtypes=[3], **past),
        PRE, "OK")
    # two digests an input where its signatures differ in hashtype
    cases["p2sh-2of3-two-hashtypes"] = (_multisig(
        2, KEYS[:3], [KEYS[0], KEYS[2]], hashtypes=[1, 0x83], **place),
        PRE, "OK")
    cases["bare-3of5-hashtypes-1-2-1"] = (_multisig(
        3, KEYS[:5], [KEYS[0], KEYS[1], KEYS[4]], p2sh=False,
        hashtypes=[1, 2, 1], **place), PRE, "OK")
    # no option chooses the digest: with FORKID enabled and STRICTENC not,
    # the hashtype byte does, signature by signature
    spend = Spend(S.p2pk_script(KEYS[0].pubkey))
    cases["p2pk-forkid-hashtype-where-forkid-is-enabled"] = (
        spend.signed(S.push_data_raw(
            spend.sign(KEYS[0], spend.spk, forkid=True))),
        PRE | SCRIPT_ENABLE_SIGHASH_FORKID, "OK")
    cases["p2pk-legacy-hashtype-where-forkid-is-enabled"] = (
        _p2pk(KEYS[0]), PRE | SCRIPT_ENABLE_SIGHASH_FORKID, "OK")
    cases["p2pkh-legacy-hashtype-where-forkid-is-enabled"] = (
        _p2pkh(KEYS[0]), PRE | SCRIPT_ENABLE_SIGHASH_FORKID, "OK")
    return cases


FITTING = _fitting_cases()


def test_the_fitting_shapes_hold_pr_31s_forty_four():
    assert len([name for name in FITTING
                if name in lanes.FITTING]) == len(lanes.FITTING) == 44


@pytest.mark.parametrize("name", sorted(FITTING))
def test_native_template_writes_the_python_templates_lanes(name):
    spend, flags, want = FITTING[name]
    status, arrays, rows = native_scan(spend, flags)
    assert status == (0 if last_check_form(
        spend.script_sig, spend.spk, flags) == "p2pkh" else 2)
    lanes.assert_lanes_equal((arrays, rows), python_lanes(spend, flags))
    # a template emits, it gives no verdict: the batch's lanes and the walk
    # end where the inline interpreter of today ends
    assert inline_verdict(spend, flags) == want
    assert lanes_pass(arrays, rows) == (want == "OK")


# -- (c) shapes the templates decline -----------------------------------------

def _padded_r(sig: bytes) -> bytes:
    """The signature with a zero byte too many before R: BER that the lax
    parser of the years before BIP66 reads, and strict DER refuses."""
    r, s = secp.sig_der_decode(sig[:-1])
    rb = b"\x00\x00" + r.to_bytes(32, "big")
    sb = s.to_bytes(33, "big").lstrip(b"\x00")
    sb = (b"\x00" if sb[0] & 0x80 else b"") + sb
    body = b"\x02" + bytes([len(rb)]) + rb + b"\x02" + bytes([len(sb)]) + sb
    return b"\x30" + bytes([len(body)]) + body + sig[-1:]


def _hybrid(key: CKey) -> bytes:
    x, y = secp.pubkey_parse(key.pubkey)
    return bytes([6 | y & 1]) + x.to_bytes(32, "big") + y.to_bytes(32, "big")


def _declined_cases() -> dict:
    """name -> (spend, flags, the inline verdict of today; what the native
    scan says: 1 = the interpreter's, or the script error of the P2PKH
    body, which is the inline verdict's)."""
    cases = {}
    # the false of a failed check consumed by the rest of the script: a
    # wrong signature SUCCEEDS here without NULLFAIL, and must never ride a
    # lane (a lane that fails aborts the import)
    not_code = S.p2pk_script(KEYS[0].pubkey) + bytes([S.OP_NOT])
    for name, signer, want in (("checksig_not_wrong_signature", OUTSIDER,
                                "OK"),
                               ("checksig_not_right_signature", KEYS[0],
                                "eval-false")):
        spend = Spend(not_code)
        cases[name] = (spend.signed(S.push_data_raw(
            spend.sign(signer, not_code))), PRE, want, 1)
    if_code = (S.p2pk_script(KEYS[0].pubkey)
               + bytes([S.OP_IF, S.OP_0, S.OP_ELSE, S.OP_1, S.OP_ENDIF]))
    spend = Spend(if_code)
    cases["checksig_if_wrong_signature"] = (spend.signed(S.push_data_raw(
        spend.sign(OUTSIDER, if_code))), PRE, "OK", 1)
    # keys in a form STRICTENC would refuse
    hybrid = _hybrid(LONG_KEY)
    spend = Spend(S.p2pk_script(hybrid))
    cases["p2pk_hybrid_key"] = (spend.signed(S.push_data_raw(
        spend.sign(LONG_KEY, spend.spk))), PRE, "OK", 1)
    spend = Spend(S.p2pkh_script(hash160(hybrid)))
    cases["p2pkh_hybrid_key"] = (spend.signed(
        S.push_data_raw(spend.sign(LONG_KEY, spend.spk))
        + S.push_data_raw(hybrid)), PRE, "OK", 1)
    code = lanes._raw_multisig(b"\x51", [hybrid, KEYS[1].pubkey], b"\x52")
    spend = Spend(code)
    cases["bare_1of2_hybrid_key"] = (spend.signed(b"\x00" + S.push_data_raw(
        spend.sign(KEYS[1], code))), PRE, "OK", 1)
    # hashtypes STRICTENC would refuse: the legacy digest takes any byte
    for h in (0x00, 0x04, 0x41, 0xFF):
        cases[f"p2pk_undefined_hashtype_{h:#04x}"] = (
            _p2pk(KEYS[0], hashtype=h), PRE, "OK", 1)
        cases[f"p2pkh_undefined_hashtype_{h:#04x}"] = (
            _p2pkh(KEYS[0], hashtype=h), PRE, "OK", 1)
    cases["p2sh_2of3_undefined_hashtype"] = (_multisig(
        2, KEYS[:3], [KEYS[0], KEYS[2]], hashtypes=[1, 0x50]), PRE, "OK", 1)
    # no NULLDUMMY: any dummy passes the interpreter
    cases["p2sh_2of3_non_null_dummy"] = (_multisig(
        2, KEYS[:3], [KEYS[0], KEYS[2]], dummy=b"\x51"), PRE, "OK", 1)
    # loose DER: what the years below BIP66 accepted, and DERSIG refuses
    before_bip66 = PRE & ~SCRIPT_VERIFY_DERSIG
    cases["p2pk_loose_der_below_bip66"] = (
        _with_sig(_p2pk(KEYS[0]), _padded_r), before_bip66, "OK", 1)
    cases["p2pkh_loose_der_below_bip66"] = (
        _with_sig(_p2pkh(KEYS[0]), _padded_r), before_bip66, "OK", 1)
    cases["p2sh_2of3_loose_der_below_bip66"] = (_with_sig(_multisig(
        2, KEYS[:3], [KEYS[0], KEYS[2]]), _padded_r), before_bip66, "OK", 1)
    cases["p2pk_loose_der_under_dersig"] = (
        _with_sig(_p2pk(KEYS[0]), _padded_r), PRE, "sig-der", 1)
    cases["p2pkh_loose_der_under_dersig"] = (
        _with_sig(_p2pkh(KEYS[0]), _padded_r), PRE, "sig-der", "sig-der")
    # pushes a wallet did not write
    spend = _p2pk(KEYS[0])
    sig = spend.script_sig[1:]
    cases["p2pk_signature_pushed_with_pushdata1"] = (spend.signed(
        bytes([S.OP_PUSHDATA1, len(sig)]) + sig), PRE, "OK", 1)
    spend = _p2pkh(KEYS[0])
    size = spend.script_sig[0]
    cases["p2pkh_signature_pushed_with_pushdata1"] = (spend.signed(
        bytes([S.OP_PUSHDATA1, size]) + spend.script_sig[1:]), PRE, "OK", 1)
    cases["p2pk_a_push_below_the_signature"] = (_p2pk(KEYS[0]).signed(
        b"\x51" + _p2pk(KEYS[0]).script_sig), PRE, "OK", 1)
    # checks decided without arithmetic
    cases["p2pk_empty_signature"] = (
        Spend(S.p2pk_script(KEYS[0].pubkey)).signed(b"\x00"), PRE,
        "eval-false", 1)
    cases["p2pkh_empty_signature"] = (
        Spend(KEYS[0].p2pkh_script()).signed(
            b"\x00" + S.push_data_raw(KEYS[0].pubkey)), PRE, "eval-false",
        "eval-false")
    cases["p2sh_2of3_one_empty_signature"] = (_with_sig(_multisig(
        2, KEYS[:3], [KEYS[0], KEYS[2]]), lambda sig: b""), PRE,
        "eval-false", 1)
    cases["p2pk_signature_of_65_bytes"] = (
        Spend(S.p2pk_script(KEYS[0].pubkey)).signed(
            S.push_data_raw(b"\x01" * 64 + b"\x01")), PRE, "eval-false", 1)
    # strict DER of 20 bytes, the length of the one push FindAndDelete
    # could cut from a pay-to-pubkey-hash script code
    short = bytes.fromhex("3011020601020304050602070102030405060701")
    cases["p2pkh_signature_of_20_bytes"] = (
        Spend(KEYS[0].p2pkh_script()).signed(
            S.push_data_raw(short) + S.push_data_raw(KEYS[0].pubkey)), PRE,
        "eval-false", 1)
    off_curve = b"\x02" + (5).to_bytes(32, "big")
    spend = Spend(S.p2pkh_script(hash160(off_curve)))
    cases["p2pkh_key_off_the_curve"] = (spend.signed(
        S.push_data_raw(spend.sign(KEYS[0], spend.spk))
        + S.push_data_raw(off_curve)), PRE, "eval-false", "eval-false")
    cases["p2pkh_another_keys_hash"] = (_p2pkh(KEYS[0]).signed(
        _p2pkh(KEYS[0]).script_sig[:-33] + KEYS[1].pubkey), PRE,
        "equalverify", "equalverify")
    # forms no template names
    code = S.p2pk_script(KEYS[0].pubkey)
    spend = Spend(S.p2sh_script_for_redeem(code))
    cases["p2sh_of_pay_to_pubkey"] = (spend.signed(
        S.push_data_raw(spend.sign(KEYS[0], code)) + S.push_data_raw(code)),
        PRE, "OK", 1)
    code = bytes([S.OP_CODESEPARATOR]) + lanes.CODE_2OF3
    spend = Spend(S.p2sh_script_for_redeem(code))
    cases["codeseparator_in_the_redeem_script"] = (spend.signed(
        b"\x00" + b"".join(S.push_data_raw(spend.sign(k, lanes.CODE_2OF3))
                           for k in (KEYS[0], KEYS[2]))
        + S.push_data_raw(code)), PRE, "OK", 1)
    spend = _multisig(2, KEYS[:3], [KEYS[0], KEYS[2]])
    cases["p2sh_without_the_p2sh_flag"] = (
        spend, PRE & ~SCRIPT_VERIFY_P2SH, "OK", 1)
    return cases


DECLINED = _declined_cases()


@pytest.mark.parametrize("name", sorted(DECLINED))
def test_native_template_declines_and_the_verdict_is_todays(name):
    spend, flags, want, native_says = DECLINED[name]
    assert inline_verdict(spend, flags) == want
    status, arrays, rows = native_scan(spend, flags)
    if native_says == 1:
        # the interpreter's: no lane, no row, nothing deferred
        assert status == 1 and rows == []
        assert [len(a) for a in arrays] == [0] * 6
    else:
        # the P2PKH body's script error is the interpreter's own (the block
        # goes to the Python engine, which names it)
        assert (status, arrays) == ("error", want) and native_says == want


def test_a_consumed_false_is_no_last_check_form():
    """The Python specification agrees: only the four forms are named, and
    VerifyScript refuses a deferring checker on anything else below the
    fork height."""
    for name in ("checksig_not_wrong_signature",
                 "checksig_if_wrong_signature", "p2sh_of_pay_to_pubkey"):
        spend, flags, _, _ = DECLINED[name]
        assert last_check_form(spend.script_sig, spend.spk, flags) is None
        with pytest.raises(AssertionError, match="last operation"):
            VerifyScript(spend.script_sig, spend.spk, flags,
                         DeferringSignatureChecker(
                             spend.tx, 0, AMOUNT, [], groups=[],
                             last_operation=True))
    forms = {last_check_form(s.script_sig, s.spk, f)
             for s, f, _ in FITTING.values()}
    assert forms == {"p2pkh", "p2pk", "multisig", "p2sh-multisig"}


# -- (d) generated chains against the cell's plain reference -------------------

@pytest.mark.parametrize("seed", [2**31 + 3200, 2**31 + 3217, 32, 123456789])
def test_legacy_signed_chain_reindexes_to_the_references_answer(seed,
                                                                tmp_path):
    chain_dir = tmp_path / "chain"
    gen = lanes._generate(chain_dir, "--legacy-sighash", seed=seed)
    node, moved = lanes._reindex(chain_dir, tmp_path / "node",
                                 "-uahfheight=1000000000")
    try:
        tip, stats = lanes._tip(node), node.last_import_stats
    finally:
        node.close()
    ref = reference_prefork.scan_chain(
        os.path.join(chain_dir, "regtest", "blocks"), seed, 6)
    assert tip == (ref["height"], ref["tip_hash"], ref["utxos"])
    assert tip == (gen["tip_height"], gen["tip_hash"], gen["txouts"])
    assert ref["inputs_by_kind"] == gen["inputs_by_kind"]
    assert ref["first_bad_height"] is None
    assert all(n >= 2 for n in ref["sampled_by_kind"].values())
    assert stats["slow_path_blocks"] == 0
    assert stats["inline_legacy_sigs"] == moved["inline_legacy_sigs"] == 0
    assert stats["prefork_blocks"] == stats["blocks"] == ref["blocks"]
    assert moved["prefork_lanes"] == moved["cpu_fallback_sigs"] == lanes.LANES
    assert (stats["interp_inputs"], stats["template_inputs"]) == (
        0, gen["non_p2pkh_inputs"])
    assert moved["eager_multisig_sigs"] == moved["reject_confirm_sigs"] == 0
    assert moved["multisig_group_confirms"] == 0
    # the program's word for what its digests hashed is the reference's
    # count of the serialised bytes, to the byte
    assert stats["legacy_digests"] == ref["legacy_digests"] == gen["inputs"]
    assert stats["legacy_sighash_bytes"] == ref["legacy_sighash_bytes"]
    assert 0 < stats["legacy_sighash_s"] <= stats["sigscan_thread_s"]


# -- (e), (f) chains by hand through Node(-reindex -uahfheight) ---------------

FORK = 105  # blocks 1..102 coinbases, 103 funds, 104 below the fork


def _fund(chain, i: int, spks: list, forkid: bool) -> CTransaction:
    value = chain.coinbases[i].vout[0].value
    each = (value - 10_000) // len(spks)
    unsigned = CTransaction(
        1, (CTxIn(COutPoint(chain.coinbases[i].txid, 0), b"", 0xFFFFFFFE),),
        tuple(CTxOut(each, spk) for spk in spks))
    return nc.sign_transaction(unsigned, [(nc.SPK, value)], nc._key_for,
                               enable_forkid=forkid)


def _spend_all(fund: CTransaction, forms: list, forkid: bool) -> CTransaction:
    """One transaction spending every output of ``fund``; ``forms[i]`` is
    (script code, signers, scriptSig prefix, scriptSig suffix) of input i."""
    each = fund.vout[0].value
    unsigned = CTransaction(
        1, tuple(CTxIn(COutPoint(fund.txid, i), b"", 0xFFFFFFFE)
                 for i in range(len(forms))),
        (CTxOut(len(forms) * each - 10_000, nc.SPK),))
    script_sigs = []
    for i, (code, signers, prefix, suffix) in enumerate(forms):
        sigs = [make_signature(key, code, unsigned, i, each,
                               enable_forkid=forkid) for key in signers]
        script_sigs.append(prefix + b"".join(map(S.push_data_raw, sigs))
                           + suffix)
    return CTransaction(
        1, tuple(CTxIn(txin.prevout, ss, txin.sequence)
                 for txin, ss in zip(unsigned.vin, script_sigs)),
        unsigned.vout)


def _four_forms(wrong_key_at: int = None) -> tuple:
    """(output scripts, their spends' forms): pay-to-pubkey-hash,
    pay-to-pubkey, pay-to-script-hash 2-of-3 and bare 1-of-2."""
    two_of_three = S.multisig_script(2, [k.pubkey for k in KEYS[:3]])
    one_of_two = S.multisig_script(1, [k.pubkey for k in KEYS[:2]])
    forms = [
        (nc.SPK, [nc.KEY], b"", S.push_data_raw(nc.KEY.pubkey)),
        (S.p2pk_script(LONG_KEY.pubkey), [LONG_KEY], b"", b""),
        (two_of_three, [KEYS[0], KEYS[2]], b"\x00",
         S.push_data_raw(two_of_three)),
        (one_of_two, [KEYS[1]], b"\x00", b""),
    ]
    spks = [nc.SPK, forms[1][0], S.p2sh_script_for_redeem(two_of_three),
            one_of_two]
    if wrong_key_at is not None:
        code, signers, prefix, suffix = forms[wrong_key_at]
        forms[wrong_key_at] = (code, [OUTSIDER] * len(signers), prefix,
                               suffix)
    return spks, forms


def _reindex(chain, *extra):
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    chain.cs.flush()
    chain.store.close()
    chain.index_kv.close()
    chain.coins_kv.close()
    config = Config()
    config.parse_args(["-regtest", "-tpu=0", "-reindex", "-listen=0",
                       f"-datadir={chain.datadir}", *extra])
    return Node(config)


def test_a_chain_that_crosses_the_fork_height_takes_both_digests(tmp_path):
    """Legacy-signed blocks, then -uahfheight, then FORKID-signed blocks, in
    one import: every block through the native engine, the digest chosen
    block by block from the flags and signature by signature from the
    hashtype."""
    chain = nc._DiskChain(tmp_path)
    spks, forms = _four_forms()
    funds = [_fund(chain, 0, spks, False), _fund(chain, 1, spks, False)]
    chain.push(funds)                                          # 103
    chain.push((_spend_all(funds[0], forms, False),))          # 104
    assert chain.cs.tip().height == FORK - 1
    chain.push((_spend_all(funds[1], forms, True),))           # 105
    last = chain.push((nc._spend(
        [COutPoint(chain.coinbases[2].txid, 0)],
        [chain.coinbases[2].vout[0].value]),))                 # 106
    before = ecdsa_batch.STATS.snapshot()
    node = _reindex(chain, f"-uahfheight={FORK}")
    try:
        assert node.chainstate.tip().hash == last.get_hash()
        assert node.params.consensus.uahf_height == FORK
        stats = node.last_import_stats
    finally:
        node.close()
    after = ecdsa_batch.STATS.snapshot()
    assert stats["slow_path_blocks"] == 0 and stats["blocks"] == FORK + 1
    assert stats["prefork_blocks"] == FORK - 1
    # below the fork: two fundings and four spends, one digest an input
    assert stats["legacy_digests"] == 6
    assert (stats["fast_inputs"], stats["template_inputs"],
            stats["interp_inputs"]) == (5, 6, 0)
    assert stats["inline_legacy_sigs"] == 0
    assert after["prefork_lanes"] - before["prefork_lanes"] == 2 + 1 + 1 + 4 + 2
    # -tpu=0: the batch is the CPU's
    assert (after["cpu_fallback_sigs"]
            - before["cpu_fallback_sigs"]) == 2 * 8 + 2 + 1


def test_a_declined_form_below_the_fork_is_verified_inline_in_its_block(
        tmp_path):
    """``<key> OP_CHECKSIG OP_NOT`` with a wrong signature beside the four
    template forms in one block below the fork height: the templates' lanes
    join the batch, the declined input runs through VerifyScript with the
    eager checker on the importing thread and succeeds, and the block stays
    on the native engine."""
    chain = nc._DiskChain(tmp_path)
    spks, forms = _four_forms()
    not_code = S.p2pk_script(KEYS[3].pubkey) + bytes([S.OP_NOT])
    fund = _fund(chain, 0, spks + [not_code], False)
    chain.push((fund,))
    last = chain.push((_spend_all(
        fund, forms + [(not_code, [OUTSIDER], b"", b"")], False),))
    node = _reindex(chain, "-uahfheight=1000000000")
    try:
        assert node.chainstate.tip().hash == last.get_hash()
        stats = node.last_import_stats
    finally:
        node.close()
    assert stats["slow_path_blocks"] == 0
    assert (stats["fallback_inputs"], stats["template_inputs"],
            stats["interp_inputs"]) == (4, 3, 1)
    assert stats["inline_legacy_sigs"] == 1


@pytest.mark.parametrize("form", range(4),
                         ids=["p2pkh", "p2pk", "p2sh-2of3", "bare-1of2"])
def test_a_wrong_key_signature_below_the_fork_names_its_block(form,
                                                              tmp_path):
    """A lane that fails (or a walk that fails and is confirmed on the
    host) aborts the native import, and the Python replay rejects exactly
    that block, as above the fork."""
    from bitcoincashplus_tpu.validation.chain import BlockStatus

    chain = nc._DiskChain(tmp_path)
    spks, forms = _four_forms()
    funds = [_fund(chain, 0, spks, False), _fund(chain, 1, spks, False)]
    chain.push(funds)                                          # 103
    chain.push((_spend_all(funds[0], forms, False),))          # 104
    bad = chain.push((_spend_all(
        funds[1], _four_forms(wrong_key_at=form)[1], False),))  # 105
    before = ecdsa_batch.STATS.snapshot()
    node = _reindex(chain, "-uahfheight=1000000000")
    try:
        assert node.chainstate.tip().height == 104
        assert node.last_import_stats is None  # the native import aborted
        failed = [idx.hash for idx in node.chainstate.block_index.values()
                  if idx.status & BlockStatus.FAILED_MASK]
    finally:
        node.close()
    after = ecdsa_batch.STATS.snapshot()
    assert failed == [bad.get_hash()]
    assert (after["multisig_group_confirms"]
            - before["multisig_group_confirms"]) == (form >= 2)
    # the replay is the Python engine's: every check of it inline
    assert after["inline_legacy_sigs"] > before["inline_legacy_sigs"]


# -- (g) the door ------------------------------------------------------------

@pytest.mark.parametrize("args,match", [
    (["-uahfheight=5"], "regtest option"),
    (["-testnet", "-uahfheight=5"], "regtest option"),
    (["-regtest", "-uahfheight=-1"], "must be >= 0"),
], ids=["main", "test", "negative"])
def test_uahfheight_is_refused_off_regtest(args, match, tmp_path):
    from bitcoincashplus_tpu.node.config import Config, ConfigError
    from bitcoincashplus_tpu.node.node import Node

    config = Config()
    config.parse_args([*args, "-listen=0", f"-datadir={tmp_path}"])
    with pytest.raises(ConfigError, match=match):
        Node(config)


def test_uahfheight_sets_the_consensus_parameter_and_nothing_else(tmp_path):
    import dataclasses

    from bitcoincashplus_tpu.consensus.params import regtest_params
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node
    from bitcoincashplus_tpu.script.interpreter import SCRIPT_VERIFY_NULLFAIL
    from bitcoincashplus_tpu.validation.scriptcheck import block_script_flags

    config = Config()
    config.parse_args(["-regtest", "-tpu=0", "-listen=0", "-uahfheight=7",
                       f"-datadir={tmp_path}"])
    node = Node(config)
    try:
        params = node.params
    finally:
        node.close()
    assert params == dataclasses.replace(
        regtest_params(), consensus=dataclasses.replace(
            regtest_params().consensus, uahf_height=7))
    assert block_script_flags(6, 0, params) == PRE
    assert block_script_flags(7, 0, params) & SCRIPT_VERIFY_NULLFAIL
    assert regtest_params().consensus.uahf_height == 0


# -- (h) the cell's readers ---------------------------------------------------

READERS = {
    "sighash.legacy_kb_per_sig": (
        {"legacy_sighash_bytes": 5_500_000}, "legacy_sighash_bytes", 5.5),
    "sigscan.legacy_sighash_share": (
        {"legacy_sighash_s": 0.75, "sigscan_thread_s": 1.0},
        "legacy_sighash_s", 75.0),
    "prefork.device_lane_share": (
        {"prefork_blocks": 3, "inline_legacy_sigs": 20}, "prefork_blocks",
        80.0),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_the_imports_counters_or_nothing(name):
    """Each new per-layer metric from what the import counted; None, and no
    exception, on a program that lacks the counter (the parent commit) or
    left no stopwatch (an aborted import)."""
    stats, needs, want = READERS[name]
    read = bench_run.load_module("layer_metrics", name).read

    def obs(stats):
        return {"after": {"import": stats,
                          "batch": {"sigs_verified": 1100,
                                    "eager_multisig_sigs": 7}},
                "before": {"batch": {"sigs_verified": 1020,
                                     "eager_multisig_sigs": 7}},
                "result": {"attempted": 1000}}

    assert read(obs(stats)) == pytest.approx(want)
    older = {k: v for k, v in stats.items() if k != needs}
    assert read(obs(older)) is None
    assert read(obs(None)) is None
