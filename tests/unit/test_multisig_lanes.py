"""OP_CHECKMULTISIG as candidate lanes of the batch (script/interpreter.py,
module docstring): deferred == eager on verdict and error code, the walk
over verdicts is upstream's, a device verdict alone rejects nothing, and the
native import settles groups that straddle two dispatches.

The plain reference of the cell reindex.mixed_era
(chipbench/reference_mixed.py: hand-written parser, template match, sighash,
ECDSA and key-trial walk in Python integers) is the third opinion."""

import itertools
import json
import os
import shutil
import struct
import subprocess
import sys

import numpy as np
import pytest

from bitcoincashplus_tpu import native
from bitcoincashplus_tpu.consensus.tx import (
    COutPoint,
    CTransaction,
    CTxIn,
    CTxOut,
)
from bitcoincashplus_tpu.crypto import secp256k1 as secp
from bitcoincashplus_tpu.ops import ecdsa_batch
from bitcoincashplus_tpu.script import script as S
from bitcoincashplus_tpu.script.interpreter import (
    SCRIPT_ENABLE_SIGHASH_FORKID,
    SCRIPT_VERIFY_DERSIG,
    SCRIPT_VERIFY_LOW_S,
    SCRIPT_VERIFY_NULLDUMMY,
    SCRIPT_VERIFY_NULLFAIL,
    SCRIPT_VERIFY_P2SH,
    SCRIPT_VERIFY_STRICTENC,
    DeferringSignatureChecker,
    MultisigGroup,
    ScriptError,
    TransactionSignatureChecker,
    VerifyScript,
    multisig_walk,
)
from bitcoincashplus_tpu.wallet.keys import CKey
from bitcoincashplus_tpu.wallet.signing import make_signature

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
sys.path.insert(0, BENCH)
import reference as plain  # noqa: E402  (chipbench/reference.py)
import reference_mixed  # noqa: E402

# what validation/scriptcheck.block_script_flags gives a post-fork block
FLAGS = (SCRIPT_VERIFY_P2SH | SCRIPT_VERIFY_DERSIG | SCRIPT_VERIFY_STRICTENC
         | SCRIPT_VERIFY_LOW_S | SCRIPT_VERIFY_NULLDUMMY
         | SCRIPT_VERIFY_NULLFAIL | SCRIPT_ENABLE_SIGHASH_FORKID)
AMOUNT = 50_000_000
KEYS = [CKey(0xC0FFEE + i) for i in range(20)]
OUTSIDER = CKey(0xBAD5EED)
LONG_KEY = CKey(0xC0FFEE, compressed=False)


def _unsigned() -> CTransaction:
    return CTransaction(
        version=1,
        vin=(CTxIn(COutPoint(b"\x77" * 32, 3), b"", 0xFFFFFFFE),),
        vout=(CTxOut(AMOUNT - 1000, b"\x51"),))


def _with_script_sig(script_sig: bytes) -> CTransaction:
    tx = _unsigned()
    return CTransaction(1, (CTxIn(tx.vin[0].prevout, script_sig,
                                  0xFFFFFFFE),), tx.vout, 0)


def _sign(key: CKey, code: bytes, forkid: bool = True) -> bytes:
    return make_signature(key, code, _unsigned(), 0, AMOUNT,
                          enable_forkid=forkid)


def multisig_spend(m: int, pubkeys: list, signers: list, *, p2sh: bool = True,
                   sigs: list = None) -> tuple:
    """(scriptSig, scriptPubKey) of an m-of-n spend signed by ``signers``
    (CKeys, in the order their signatures are pushed); ``sigs`` replaces the
    signatures outright."""
    code = S.multisig_script(m, pubkeys)
    if sigs is None:
        sigs = [_sign(key, code) for key in signers]
    script_sig = b"\x00" + b"".join(S.push_data_raw(s) if s else b"\x00"
                                    for s in sigs)
    if not p2sh:
        return script_sig, code
    return (script_sig + S.push_data_raw(code),
            S.p2sh_script_for_redeem(code))


def eager(script_sig: bytes, spk: bytes, flags: int = FLAGS) -> tuple:
    """Today's path: a deferring checker that was given no group list.
    Returns (verdict or error code, key trials it walked on the host)."""
    tx = _with_script_sig(script_sig)
    records: list = []
    before = ecdsa_batch.STATS.eager_multisig_sigs
    try:
        VerifyScript(script_sig, spk, flags,
                     DeferringSignatureChecker(tx, 0, AMOUNT, records))
        code = "OK"
    except ScriptError as e:
        code = e.code
    trials = ecdsa_batch.STATS.eager_multisig_sigs - before
    if code == "OK" and not all(ecdsa_batch._verify_cpu(records)):
        code = "sig-nullfail"  # what the replay of a failed batch reports
    return code, trials


def deferred(script_sig: bytes, spk: bytes, flags: int = FLAGS,
             flip=None) -> tuple:
    """The native import's path in small: candidate lanes, their verdicts
    from the CPU verifier, the walk, and the host where it fails. Returns
    (verdict or error code, groups deferred, key trials on the host, host
    confirmations). ``flip(verdicts)`` stands in for a device that errs."""
    tx = _with_script_sig(script_sig)
    records, groups = [], []
    before = ecdsa_batch.STATS.eager_multisig_sigs
    try:
        VerifyScript(script_sig, spk, flags, DeferringSignatureChecker(
            tx, 0, AMOUNT, records, groups=groups))
    except ScriptError as e:
        return (e.code, len(groups),
                ecdsa_batch.STATS.eager_multisig_sigs - before, 0)
    trials = ecdsa_batch.STATS.eager_multisig_sigs - before
    verdicts = np.asarray(ecdsa_batch._verify_cpu(records), bool)
    if flip is not None:
        flip(verdicts)
    candidate = np.zeros(len(records), bool)
    for g in groups:
        candidate[g.start:g.start + g.lanes] = True
    confirms = sum(
        not multisig_walk(g.m, g.n, verdicts[g.start:g.start + g.lanes])
        for g in groups)
    code = "OK"
    if confirms or not np.all(verdicts | candidate):
        try:  # the host decides
            VerifyScript(script_sig, spk, flags,
                         TransactionSignatureChecker(tx, 0, AMOUNT))
        except ScriptError as e:
            code = e.code
    return code, len(groups), trials, confirms


# -- B(b): deferred == eager --------------------------------------------------

SHAPES = [(1, 1), (1, 2), (2, 2), (2, 3), (3, 5)]
SUBSETS = [(m, n, subset) for m, n in SHAPES
           for subset in itertools.combinations(range(n), m)]


@pytest.mark.parametrize("p2sh", [True, False], ids=["p2sh", "bare"])
@pytest.mark.parametrize(
    "m,n,subset", SUBSETS,
    ids=[f"{m}of{n}-" + "".join(map(str, s)) for m, n, s in SUBSETS])
def test_every_signer_subset_defers_and_agrees(m, n, subset, p2sh):
    pubkeys = [k.pubkey for k in KEYS[:n]]
    script_sig, spk = multisig_spend(m, pubkeys, [KEYS[i] for i in subset],
                                     p2sh=p2sh)
    assert eager(script_sig, spk)[0] == "OK"
    code, groups, trials, confirms = deferred(script_sig, spk)
    assert (code, groups, trials, confirms) == ("OK", 1, 0, 0)


@pytest.mark.parametrize("signer", [0, 7, 19])
def test_one_of_twenty_takes_twenty_lanes(signer):
    """The widest operation the opcode allows (bare: 20 keys do not fit a
    520-byte redeem script)."""
    pubkeys = [k.pubkey for k in KEYS]
    script_sig, spk = multisig_spend(1, pubkeys, [KEYS[signer]], p2sh=False)
    tx = _with_script_sig(script_sig)
    records, groups = [], []
    VerifyScript(script_sig, spk, FLAGS, DeferringSignatureChecker(
        tx, 0, AMOUNT, records, groups=groups))
    assert [(g.start, g.m, g.n, g.lanes) for g in groups] == [(0, 1, 20, 20)]
    verdicts = ecdsa_batch._verify_cpu(records)
    # the walk starts at the last key: lane j is key 19 - j
    assert list(np.nonzero(verdicts)[0]) == [19 - signer]
    assert eager(script_sig, spk) == ("OK", 20 - signer)
    assert deferred(script_sig, spk) == ("OK", 1, 0, 0)


def _high_s(sig: bytes) -> bytes:
    r, s = secp.sig_der_decode(sig[:-1])
    return secp.sig_der_encode(r, secp.N - s) + sig[-1:]


def _two_of_three(signers, **kw):
    return multisig_spend(2, [k.pubkey for k in KEYS[:3]], signers, **kw)


def _bad_key_at(position: int):
    """2-of-3 signed by keys 1 and 2, with the key at ``position`` given a
    prefix no encoding has. The walk starts at the last key and is done
    after keys 2 and 1: it never visits position 0."""
    pubkeys = [k.pubkey for k in KEYS[:3]]
    code = S.multisig_script(2, pubkeys)
    pubkeys[position] = b"\x05" + pubkeys[position][1:]
    broken = S.multisig_script(2, pubkeys)
    sigs = [_sign(KEYS[1], broken), _sign(KEYS[2], broken)]
    assert len(broken) == len(code)
    return multisig_spend(2, pubkeys, None, sigs=sigs)


def _off_curve_key_at(position: int):
    """As _bad_key_at, but the key passes its encoding check and is no
    point: x = 5 has no y on secp256k1."""
    pubkeys = [k.pubkey for k in KEYS[:3]]
    pubkeys[position] = b"\x02" + (5).to_bytes(32, "big")
    assert secp.pubkey_parse(pubkeys[position]) is None
    broken = S.multisig_script(2, pubkeys)
    return multisig_spend(2, pubkeys, None, sigs=[
        _sign(KEYS[1], broken), _sign(KEYS[2], broken)])


CODE_2OF3 = S.multisig_script(2, [k.pubkey for k in KEYS[:3]])
GOOD = [_sign(KEYS[0], CODE_2OF3), _sign(KEYS[2], CODE_2OF3)]

# name -> (spend, verdict, groups deferred, key trials on the host by the
# deferring path, host confirmations)
EDGE_CASES = {
    "signatures_out_of_key_order":
        (_two_of_three([KEYS[1], KEYS[0]]), "sig-nullfail", 1, 0, 1),
    "one_signature_twice":
        (_two_of_three([KEYS[1], KEYS[1]]), "sig-nullfail", 1, 0, 1),
    "a_signer_outside_the_script":
        (_two_of_three([KEYS[0], OUTSIDER]), "sig-nullfail", 1, 0, 1),
    "both_signers_outside_the_script":
        (_two_of_three([OUTSIDER, KEYS[5]]), "sig-nullfail", 1, 0, 1),
    # an empty signature is decided without arithmetic: eager, 3 trials
    # (key 2's signature meets key 2, the empty one fails keys 1 and 0)
    "one_empty_signature_among_full_ones":
        (_two_of_three(None, sigs=[b"", GOOD[1]]), "sig-nullfail", 0, 3, 0),
    "all_signatures_empty":
        (_two_of_three(None, sigs=[b"", b""]), "eval-false", 0, 2, 0),
    "bad_key_the_walk_never_visits": (_bad_key_at(0), "OK", 0, 2, 0),
    "bad_key_the_walk_visits": (_bad_key_at(2), "pubkeytype", 0, 0, 0),
    "off_curve_key_the_walk_never_visits":
        (_off_curve_key_at(0), "OK", 0, 2, 0),
    "off_curve_key_the_walk_visits":
        (_off_curve_key_at(2), "sig-nullfail", 0, 2, 0),
    "high_s_signature":
        (_two_of_three(None, sigs=[GOOD[0], _high_s(GOOD[1])]),
         "sig-high-s", 0, 0, 0),
    "schnorr_sized_signature":
        (_two_of_three(None, sigs=[GOOD[0], b"\x01" * 64 + b"\x41"]),
         "sig-badlength", 0, 0, 0),
    "non_forkid_hashtype":
        (_two_of_three(None, sigs=[
            GOOD[0], _sign(KEYS[2], CODE_2OF3, forkid=False)]),
         "must-use-forkid", 0, 0, 0),
    "undefined_hashtype":
        (_two_of_three(None, sigs=[GOOD[0], GOOD[1][:-1] + b"\x44"]),
         "sig-hashtype", 0, 0, 0),
    "garbled_der":
        (_two_of_three(None, sigs=[GOOD[0], b"\x30\x01\x02\x41"]),
         "sig-der", 0, 0, 0),
    "non_null_dummy":
        ((b"\x51" + _two_of_three([KEYS[0], KEYS[2]])[0][1:],
          _two_of_three([KEYS[0], KEYS[2]])[1]), "sig-nulldummy", 1, 0, 0),
    "uncompressed_keys_in_the_script":
        (multisig_spend(1, [LONG_KEY.pubkey, KEYS[1].pubkey], [LONG_KEY]),
         "OK", 1, 0, 0),
}


@pytest.mark.parametrize("name", sorted(EDGE_CASES))
def test_edge_case_gives_the_eager_verdict_and_error_code(name):
    (script_sig, spk), want, groups, trials, confirms = EDGE_CASES[name]
    assert eager(script_sig, spk)[0] == want
    assert deferred(script_sig, spk) == (want, groups, trials, confirms)


def test_eager_trials_of_the_empty_signature_cases_are_upstreams():
    """The walk gives up as soon as more signatures than keys are left: two
    empty signatures of a 2-of-3 cost 2 trials (the first fails keys 2 and
    1, then 2 signatures are left for 1 key)."""
    for name in ("one_empty_signature_among_full_ones",
                 "all_signatures_empty"):
        (script_sig, spk), want, _, trials, _ = EDGE_CASES[name]
        assert eager(script_sig, spk) == (want, trials)


def test_without_nullfail_nothing_defers():
    script_sig, spk = _two_of_three([KEYS[0], KEYS[2]])
    tx = _with_script_sig(script_sig)
    groups: list = []
    checker = DeferringSignatureChecker(tx, 0, AMOUNT, [], groups=groups)
    assert not checker.defer_multisig(
        GOOD, [k.pubkey for k in KEYS[:3]], CODE_2OF3,
        FLAGS & ~SCRIPT_VERIFY_NULLFAIL)
    assert checker.defer_multisig(
        GOOD, [k.pubkey for k in KEYS[:3]], CODE_2OF3, FLAGS)
    assert [g.lanes for g in groups] == [4]


def test_two_operations_in_one_script_are_two_groups():
    """<2-of-3> CHECKMULTISIGVERIFY <1-of-2> CHECKMULTISIG, bare."""
    first = S.multisig_script(2, [k.pubkey for k in KEYS[:3]])
    second = S.multisig_script(1, [k.pubkey for k in KEYS[3:5]])
    spk = first[:-1] + bytes([S.OP_CHECKMULTISIGVERIFY]) + second
    sigs = [[_sign(KEYS[4], spk)], [_sign(KEYS[0], spk), _sign(KEYS[1], spk)]]
    script_sig = b"".join(
        b"\x00" + b"".join(S.push_data_raw(s) for s in part)
        for part in sigs)
    assert eager(script_sig, spk)[0] == "OK"
    tx = _with_script_sig(script_sig)
    records, groups = [], []
    VerifyScript(script_sig, spk, FLAGS, DeferringSignatureChecker(
        tx, 0, AMOUNT, records, groups=groups))
    assert [(g.start, g.m, g.n) for g in groups] == [(0, 2, 3), (4, 1, 2)]
    assert deferred(script_sig, spk) == ("OK", 2, 0, 0)


# -- the native scan's templates against the Python leg ------------------------
# native/connect.cpp scan_templates: an input that fits P2PK, bare or P2SH
# CHECKMULTISIG gets the lanes the Python leg would have written, byte for
# byte; anything else is declined (sig_status 1, no lane) and the interpreter
# decides it as before.

needs_engine = pytest.mark.skipif(
    not native.engine_available(), reason="native connect engine unavailable")


def native_result(script_sig: bytes, spk: bytes, flags: int = FLAGS):
    """One input through the native scan: a block of a coinbase and the
    spend, the spent coin put into the engine by hand. Returns the
    connect's result."""
    spend = _with_script_sig(script_sig)
    coinbase = CTransaction(
        1, (CTxIn(COutPoint(), b"\x01\x01", 0xFFFFFFFF),),
        (CTxOut(50 * 10**8, b"\x51"),))
    raw = bytes(80) + b"\x02" + coinbase.serialize() + spend.serialize()
    prevout = spend.vin[0].prevout
    eng = native.ConnectEngine()
    try:
        eng.insert(prevout.hash + struct.pack("<I", prevout.n), 2, AMOUNT,
                   spk)
        res = eng.connect_block(raw, 5, 50 * 10**8, 32_000_000, 100, 0, None,
                                flags, want_sigs=True, check_merkle=False,
                                commit=False)
    finally:
        eng.close()
    return res


def native_scan(script_sig: bytes, spk: bytes, flags: int = FLAGS) -> tuple:
    """native_result as (sig_status of the input, the six lane arrays, the
    table's rows); the lanes' kinds are test_schnorr_lanes.py's."""
    res = native_result(script_sig, spk, flags)
    return int(res.sig_status[0]), res.leg_lanes[:6], res.leg_table.tolist()


def python_leg(script_sig: bytes, spk: bytes, flags: int = FLAGS) -> tuple:
    """What node.py's script leg makes of the input through the
    interpreter: (the six lane arrays, the table's rows)."""
    tx = _with_script_sig(script_sig)
    records, groups = [], []
    VerifyScript(script_sig, spk, flags, DeferringSignatureChecker(
        tx, 0, AMOUNT, records, groups=groups))
    cand = np.zeros(len(records), np.uint8)
    for g in groups:
        cand[g.start:g.start + g.lanes] = 1
    rows = [[0, g.start, g.m, g.n] for g in groups] or [[0, 0, 0, 0]]
    return (*ecdsa_batch.records_to_blobs(records), cand), rows


def assert_lanes_equal(got: tuple, want: tuple) -> None:
    for name, a, b in zip(("pub", "rs", "msg", "rn", "wrap", "cand"),
                          got[0], want[0]):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    assert got[1] == want[1]


def _p2pk_spend(key: CKey, sig: bytes = None) -> tuple:
    spk = S.p2pk_script(key.pubkey)
    return S.push_data_raw(_sign(key, spk) if sig is None else sig), spk


def settled_verdict(script_sig: bytes, spk: bytes, lanes: tuple,
                    rows: list) -> str:
    """Where the native lanes end, as the import settles them: the batch's
    verdicts (the CPU's here), each group's walk, and the eager checker on
    the host where a walk or a must-verify lane fails."""
    *blobs, cand = lanes
    ok = ecdsa_batch.dispatch_packed(
        *blobs, backend="cpu", candidate=cand.astype(bool)).result()
    walks = all(multisig_walk(m, n, ok[first:first + m * (n - m + 1)])
                for _, first, m, n in rows if m)
    if walks and bool(np.all(ok | cand.astype(bool))):
        return "OK"
    try:
        VerifyScript(script_sig, spk, FLAGS, TransactionSignatureChecker(
            _with_script_sig(script_sig), 0, AMOUNT))
    except ScriptError as e:
        return e.code
    return "OK"


def _fitting_cases() -> dict:
    """name -> (spend, the verdict it ends with)."""
    cases = {}
    for m, n, subset in SUBSETS:
        for p2sh in (True, False):
            name = f"{'p2sh' if p2sh else 'bare'}-{m}of{n}-" + "".join(
                map(str, subset))
            cases[name] = (multisig_spend(
                m, [k.pubkey for k in KEYS[:n]], [KEYS[i] for i in subset],
                p2sh=p2sh), "OK")
    cases["p2pk-33-byte-key"] = (_p2pk_spend(KEYS[0]), "OK")
    cases["p2pk-65-byte-key"] = (_p2pk_spend(LONG_KEY), "OK")
    cases["p2pk-signed-by-another-key"] = (_p2pk_spend(
        KEYS[0], _sign(OUTSIDER, S.p2pk_script(KEYS[0].pubkey))),
        "sig-nullfail")
    cases["bare-16of16"] = (multisig_spend(
        16, [k.pubkey for k in KEYS[:16]], KEYS[:16], p2sh=False), "OK")
    cases["p2sh-1of15"] = (multisig_spend(
        1, [k.pubkey for k in KEYS[:15]], [KEYS[9]]), "OK")
    # what defer_multisig takes, whatever the walk will say of it
    for name, (spend, want, groups, _, _) in EDGE_CASES.items():
        if groups == 1 and want != "sig-nulldummy":
            cases[name] = (spend, want)
    return cases


FITTING = _fitting_cases()


@needs_engine
@pytest.mark.parametrize("name", sorted(FITTING))
def test_native_template_writes_the_python_legs_lanes(name):
    (script_sig, spk), want = FITTING[name]
    status, lanes, rows = native_scan(script_sig, spk)
    assert status == 2
    assert_lanes_equal((lanes, rows), python_leg(script_sig, spk))
    m, n = rows[0][2:]
    assert len(lanes[4]) == (m * (n - m + 1) if m else 1)
    # a template emits, it gives no verdict: that is the batch's, the
    # walk's and the host's, and it is the one of today
    assert settled_verdict(script_sig, spk, lanes, rows) == want
    assert deferred(script_sig, spk)[0] == want


def _raw_multisig(m_op: bytes, pubkeys: list, n_op: bytes) -> bytes:
    return (m_op + b"".join(S.push_data_raw(k) for k in pubkeys) + n_op
            + bytes([S.OP_CHECKMULTISIG]))


def _declined_cases() -> dict:
    """name -> (spend, the verdict the interpreter gives it today)."""
    cases = {name: (spend, want)
             for name, (spend, want, groups, _, _) in EDGE_CASES.items()
             if groups == 0 or want == "sig-nulldummy"}
    pubkeys = [k.pubkey for k in KEYS[:3]]
    good_ss, good_spk = _two_of_three([KEYS[0], KEYS[2]])
    cases["wrong_redeem_hash"] = (
        (good_ss, S.p2sh_script(bytes(20))), "eval-false")
    # FLAGS has no MINIMALDATA: the interpreter takes these pushes, the
    # template names direct pushes only
    sigs = [_sign(KEYS[0], CODE_2OF3), _sign(KEYS[2], CODE_2OF3)]
    pushdata1 = b"".join(bytes([S.OP_PUSHDATA1, len(x)]) + x for x in sigs)
    cases["signatures_pushed_with_pushdata1"] = (
        (b"\x00" + pushdata1 + S.push_data_raw(CODE_2OF3), good_spk), "OK")
    code = (b"\x52" + b"".join(bytes([S.OP_PUSHDATA1, len(k)]) + k
                               for k in pubkeys)
            + b"\x53" + bytes([S.OP_CHECKMULTISIG]))
    cases["keys_pushed_with_pushdata1"] = (multisig_spend(
        2, pubkeys, None, p2sh=False,
        sigs=[_sign(KEYS[0], code), _sign(KEYS[2], code)])[:1] + (code,),
        "OK")
    cases["redeem_script_pushed_with_pushdata2"] = (
        (b"\x00" + b"".join(S.push_data_raw(x) for x in sigs)
         + bytes([S.OP_PUSHDATA2]) + struct.pack("<H", len(CODE_2OF3))
         + CODE_2OF3, good_spk), "OK")
    code = _raw_multisig(b"\x53", pubkeys[:2], b"\x52")  # 3-of-2
    cases["m_above_n"] = ((b"\x00" + b"".join(
        S.push_data_raw(_sign(k, code)) for k in KEYS[:3]), code),
        "sig-count")
    cases["one_of_twenty"] = (multisig_spend(
        1, [k.pubkey for k in KEYS], [KEYS[7]], p2sh=False), "OK")
    code = _raw_multisig(b"\x01\x01", pubkeys[:2], b"\x01\x02")
    cases["counts_pushed_as_bytes"] = (
        (b"\x00" + S.push_data_raw(_sign(KEYS[1], code)), code), "OK")
    cases["a_signature_short"] = (
        (b"\x00" + S.push_data_raw(sigs[0]) + S.push_data_raw(CODE_2OF3),
         good_spk), "invalid-stack-operation")
    cases["a_signature_too_many"] = (
        (b"\x00" + b"".join(S.push_data_raw(x) for x in sigs[:1] + sigs),
         CODE_2OF3), "sig-nulldummy")  # the first one is where the dummy is
    cases["a_push_below_the_dummy"] = (  # no CLEANSTACK in a block's flags
        (b"\x00\x00" + b"".join(S.push_data_raw(x) for x in sigs),
         CODE_2OF3), "OK")
    code = CODE_2OF3 + bytes([S.OP_NOP])
    cases["an_opcode_after_checkmultisig"] = ((b"\x00" + b"".join(
        S.push_data_raw(_sign(k, code)) for k in (KEYS[0], KEYS[2])), code),
        "OK")
    code = bytes([S.OP_CODESEPARATOR]) + CODE_2OF3
    cases["codeseparator_in_the_redeem_script"] = ((b"\x00" + b"".join(
        S.push_data_raw(_sign(k, CODE_2OF3)) for k in (KEYS[0], KEYS[2]))
        + S.push_data_raw(code), S.p2sh_script_for_redeem(code)), "OK")
    code = S.p2pk_script(KEYS[0].pubkey)
    cases["p2sh_of_pay_to_pubkey"] = (
        (S.push_data_raw(_sign(KEYS[0], code)) + S.push_data_raw(code),
         S.p2sh_script_for_redeem(code)), "OK")
    code = b"\x51" + bytes([S.OP_VERIFY]) + S.p2pk_script(KEYS[0].pubkey)
    cases["verify_before_checksig"] = (
        (S.push_data_raw(_sign(KEYS[0], code)), code), "OK")
    spk = S.p2pk_script(KEYS[0].pubkey)
    cases["p2pk_schnorr_sized_signature"] = (
        _p2pk_spend(KEYS[0], b"\x01" * 64 + b"\x41"), "sig-nullfail")
    cases["p2pk_empty_signature"] = ((b"\x00", spk), "eval-false")
    cases["p2pk_non_forkid_hashtype"] = (
        _p2pk_spend(KEYS[0], _sign(KEYS[0], spk, forkid=False)),
        "must-use-forkid")
    cases["p2pk_high_s_signature"] = (
        _p2pk_spend(KEYS[0], _high_s(_sign(KEYS[0], spk))), "sig-high-s")
    cases["p2pk_a_push_below_the_signature"] = (
        (b"\x51" + S.push_data_raw(_sign(KEYS[0], spk)), spk), "OK")
    off_curve = b"\x02" + (5).to_bytes(32, "big")
    cases["p2pk_key_off_the_curve"] = (
        (S.push_data_raw(_sign(KEYS[0], S.p2pk_script(off_curve))),
         S.p2pk_script(off_curve)), "sig-nullfail")
    hybrid = b"\x06" + LONG_KEY.pubkey[1:]
    cases["p2pk_hybrid_key"] = (
        (S.push_data_raw(_sign(LONG_KEY, S.p2pk_script(hybrid))),
         S.p2pk_script(hybrid)), "pubkeytype")
    return cases


DECLINED = _declined_cases()


@needs_engine
@pytest.mark.parametrize("name", sorted(DECLINED))
def test_native_template_declines_and_the_interpreter_decides(name):
    (script_sig, spk), want = DECLINED[name]
    status, lanes, rows = native_scan(script_sig, spk)
    if name == "p2pk_schnorr_sized_signature":
        # from the fork height on 65 bytes are a Schnorr lane (r < p,
        # s < n here), whose False the settle turns into the same verdict
        assert status == 2 and rows == [[0, 0, 0, 0]]
        assert [len(a) for a in lanes] == [1] * 6
        assert native_result(script_sig, spk).leg_lanes[6].tolist() == [1]
    else:
        assert status == 1 and rows == []
        assert [len(a) for a in lanes] == [0] * 6
    # the verdict and error code of today, on both of the leg's paths
    assert eager(script_sig, spk)[0] == want
    assert deferred(script_sig, spk)[0] == want


@needs_engine
@pytest.mark.parametrize("without,fits", [
    (SCRIPT_VERIFY_NULLFAIL, True), (SCRIPT_ENABLE_SIGHASH_FORKID, False),
    (SCRIPT_VERIFY_P2SH, False)], ids=["nullfail", "forkid", "p2sh"])
def test_native_templates_need_the_flags_that_let_the_leg_defer(without,
                                                                fits):
    """NULLFAIL is not among them: the check is the script's last
    operation, so its false is the script's (tests/unit/
    test_prefork_lanes.py). Without FORKID under STRICTENC the hashtype is
    illegal and the interpreter's to refuse; without P2SH a redeem script
    is not run."""
    script_sig, spk = _two_of_three([KEYS[0], KEYS[2]])
    with_all = native_scan(script_sig, spk)
    assert with_all[0] == 2
    status, lanes, rows = native_scan(script_sig, spk, FLAGS & ~without)
    if fits:
        assert status == 2
        assert_lanes_equal((lanes, rows), with_all[1:])
    else:
        assert status == 1 and rows == [] and len(lanes[4]) == 0


# -- the walk -----------------------------------------------------------------

def _upstream_walk(m: int, n: int, matches) -> tuple:
    """Upstream's loop on a predicate, recording the pairs it visits."""
    si = ki = 0
    visited = []
    while si < m:
        visited.append((si, ki))
        if matches(si, ki):
            si += 1
        ki += 1
        if m - si > n - ki:
            return False, visited
    return True, visited


@pytest.mark.parametrize("m,n", [(1, 1), (1, 3), (2, 3), (2, 4), (3, 5)])
def test_walk_over_every_verdict_matrix_stays_in_the_band(m, n):
    """Every assignment of verdicts to the m(n-m+1) candidate pairs: the
    replay is upstream's loop, and no walk leaves the band i <= j <= i+n-m
    (so the lanes deferred are all the lanes any walk can ask for)."""
    width = n - m + 1
    for bits in itertools.product([False, True], repeat=m * width):
        def matches(si, ki):
            assert 0 <= ki - si < width, (si, ki)
            return bits[si * width + ki - si]

        want, _ = _upstream_walk(m, n, matches)
        assert multisig_walk(m, n, bits) is want


# -- B(c): a device verdict alone rejects nothing -----------------------------

def _flip_first(value: bool):
    def flip(verdicts):
        k = int(np.nonzero(verdicts == value)[0][0])
        verdicts[k] = not value
    return flip


def test_a_flipped_true_goes_to_the_host_and_the_host_accepts():
    script_sig, spk = _two_of_three([KEYS[0], KEYS[2]])
    assert deferred(script_sig, spk, flip=_flip_first(True)) == (
        "OK", 1, 0, 1)


def test_a_flipped_false_changes_no_sound_verdict():
    script_sig, spk = _two_of_three([KEYS[0], KEYS[2]])
    code, groups, _, _ = deferred(script_sig, spk, flip=_flip_first(False))
    assert (code, groups) == ("OK", 1)


def test_batch_handle_confirms_must_verify_lanes_only():
    """A False on a candidate lane is an answer: BatchHandle.result leaves
    it alone. A False on a must-verify lane is still confirmed on the host
    before it can reject anything."""
    script_sig, spk = _two_of_three([KEYS[0], KEYS[2]])
    tx = _with_script_sig(script_sig)
    records: list = []
    VerifyScript(script_sig, spk, FLAGS, DeferringSignatureChecker(
        tx, 0, AMOUNT, records, groups=[]))
    wrong = np.zeros(len(records), bool)  # a device that says no to all
    candidate = np.ones(len(records), bool)
    candidate[0] = False                  # lane 0 rides as must-verify

    def handle(mask):
        return ecdsa_batch.BatchHandle(
            len(records), bucket=len(records), device_ok=wrong.copy(),
            records=records, candidate=mask)

    before = ecdsa_batch.STATS.reject_confirm_sigs
    truth = np.asarray(ecdsa_batch._verify_cpu(records), bool)
    out = handle(candidate).result()
    assert ecdsa_batch.STATS.reject_confirm_sigs - before == 1
    assert out[0] == truth[0] and not out[1:].any()
    out = handle(None).result()
    assert ecdsa_batch.STATS.reject_confirm_sigs - before == 1 + len(records)
    assert list(out) == list(truth)


class _Recorder:
    def __init__(self):
        self.owners = []

    def __call__(self, owner):
        self.owners.append(owner)


def _settler_over(groups, verdicts, cuts):
    """Feed ``verdicts`` to a _MultisigSettler in slices ending at ``cuts``;
    returns the owners it sent to the host, and after which slice."""
    from bitcoincashplus_tpu.node.node import _MultisigSettler

    confirm = _Recorder()
    settler = _MultisigSettler(confirm)
    settler.add(100, groups)  # lane numbers need not start at 0
    seen, first = [], 0
    for end in cuts:
        settler.settled(100 + first, verdicts[first:end])
        seen.append(list(confirm.owners))
        first = end
    assert not settler.pending and not settler.slices
    return seen


@pytest.mark.parametrize("cuts", [
    (12,), (4, 12), (5, 12), (6, 12), (7, 12), (2, 5, 12), (1, 2, 3, 12),
    tuple(range(1, 13))])
def test_a_group_settles_with_the_dispatch_that_holds_its_last_lane(cuts):
    """Three 2-of-3 groups on lanes 0-3, 4-7, 8-11; the middle one fails its
    walk. However the lanes are cut into dispatches, exactly that group goes
    to the host, and not before its last lane has settled."""
    ok = [True, False, False, True]      # signers {0, 2}
    bad = [True, False, False, False]    # second signature matches no key
    verdicts = np.array(ok + bad + ok)
    groups = [MultisigGroup(4 * k, 2, 3, owner=f"g{k}") for k in range(3)]
    seen = _settler_over(groups, verdicts, cuts)
    assert seen[-1] == ["g1"]
    for end, owners in zip(cuts, seen):
        assert owners == (["g1"] if end >= 8 else [])


# -- B(a): the interpreter against the plain reference, kind by kind ----------

def _reference_view(tx: CTransaction) -> dict:
    raw = tx.serialize()
    return plain.parse_tx(plain._Reader(raw, 0))


def _spend_of_kind(kind: str, signers=None):
    if kind == "p2pkh":
        spk = KEYS[0].p2pkh_script()
        key = signers[0] if signers else KEYS[0]
        return (S.push_data_raw(_sign(key, spk))
                + S.push_data_raw(KEYS[0].pubkey)), spk
    if kind == "p2pk":
        spk = S.p2pk_script(LONG_KEY.pubkey)
        return S.push_data_raw(_sign(signers[0] if signers else LONG_KEY,
                                     spk)), spk
    if kind == "p2sh_multisig":
        return _two_of_three(signers or [KEYS[0], KEYS[2]])
    return multisig_spend(1, [k.pubkey for k in KEYS[:2]],
                          signers or [KEYS[1]], p2sh=False)


@pytest.mark.parametrize("sound", [True, False], ids=["sound", "wrong-key"])
@pytest.mark.parametrize("kind", reference_mixed.KINDS)
def test_interpreter_and_plain_reference_agree(kind, sound):
    signers = None if sound else (
        [KEYS[0], OUTSIDER] if kind == "p2sh_multisig" else [OUTSIDER])
    script_sig, spk = _spend_of_kind(kind, signers)
    tx = _with_script_sig(script_sig)
    got = reference_mixed.verify_input(_reference_view(tx), 0, AMOUNT, spk)
    assert got["kind"] == kind and got["ok"] is sound
    assert (eager(script_sig, spk)[0] == "OK") is sound
    assert (deferred(script_sig, spk)[0] == "OK") is sound
    if sound and kind == "p2sh_multisig":
        # signers {0, 2}: the walk ends at key 0, after all three
        assert (got["signers"], got["trials"]) == ((0, 2), 3)
        assert eager(script_sig, spk)[1] == 3


# -- B(c), B(d): the native import --------------------------------------------

LANES = 2600  # one 2,046-lane slice and a tail: some group straddles them


def _generate(datadir, *extra, seed: int = 2**31 + 2800,
              lanes: int = LANES) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "gen", "mixedchain.py"),
         "--datadir", str(datadir), "--seed", str(seed), "--lanes",
         str(lanes), "--traffic",
         os.path.join(BENCH, "traffic", "mixed_era.json"), "--rehearse",
         "--workers", "2", *extra],
        check=True, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound_chain(tmp_path_factory):
    datadir = tmp_path_factory.mktemp("mixed-sound")
    return datadir, _generate(datadir)


@pytest.fixture(scope="module")
def faulted_chain(tmp_path_factory):
    datadir = tmp_path_factory.mktemp("mixed-fault")
    return datadir, _generate(datadir, "--fault", "wrong-key-multisig")


def _reindex(chain_dir, tmp_path, *extra):
    """Node(-regtest -tpu=0 -reindex) over a copy of the chain's block
    files; returns (node, what gettpuinfo.batch moved by)."""
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    blocks = os.path.join(tmp_path, "regtest", "blocks")
    os.makedirs(blocks)
    src = os.path.join(chain_dir, "regtest", "blocks")
    for leaf in os.listdir(src):
        if leaf.startswith("blk"):
            shutil.copy(os.path.join(src, leaf), blocks)
    config = Config()
    config.parse_args(["-regtest", "-tpu=0", "-reindex", "-listen=0",
                       "-flushinterval=1000000", f"-datadir={tmp_path}",
                       *extra])
    before = ecdsa_batch.STATS.snapshot()
    node = Node(config)
    after = ecdsa_batch.STATS.snapshot()
    return node, {k: after[k] - before[k] for k in (
        "eager_multisig_sigs", "multisig_groups", "multisig_lanes",
        "multisig_group_confirms", "reject_confirm_sigs",
        "cpu_fallback_sigs", "inline_legacy_sigs", "prefork_lanes")}


def _tip(node) -> tuple:
    from bitcoincashplus_tpu.consensus.serialize import hash_to_hex

    tip = node.chainstate.tip()
    return tip.height, hash_to_hex(tip.hash), node.coins_db.count_coins()


def test_generated_chain_reindexes_to_the_references_answer(
        sound_chain, tmp_path):
    chain_dir, gen = sound_chain
    node, moved = _reindex(chain_dir, tmp_path)
    try:
        stats = node.last_import_stats
        tip = _tip(node)
    finally:
        node.close()
    ref = reference_mixed.scan_chain(
        os.path.join(chain_dir, "regtest", "blocks"), 28, 6)
    assert tip == (ref["height"], ref["tip_hash"], ref["utxos"])
    assert tip == (gen["tip_height"], gen["tip_hash"], gen["txouts"])
    assert ref["inputs_by_kind"] == gen["inputs_by_kind"]
    assert ref["first_bad_height"] is None
    assert all(n >= 2 for n in ref["sampled_by_kind"].values())
    # every signature check of the chain went to the batch, none to the host
    assert moved == {
        "eager_multisig_sigs": 0, "multisig_groups": gen["multisig_groups"],
        "multisig_lanes": gen["multisig_lanes"],
        "multisig_group_confirms": 0, "reject_confirm_sigs": 0,
        "cpu_fallback_sigs": LANES,  # -tpu=0: the batch is the CPU's
        "inline_legacy_sigs": 0, "prefork_lanes": 0}
    assert stats["slow_path_blocks"] == 0
    assert stats["fallback_inputs"] == gen["non_p2pkh_inputs"]
    assert stats["fast_inputs"] == gen["inputs_by_kind"]["p2pkh"]
    assert stats["multisig_lanes"] == gen["multisig_lanes"]
    assert stats["multisig_groups"] == gen["multisig_groups"]
    assert 0 < stats["fallback_s"] <= stats["verify_s"]
    # 4 lanes a 2-of-3, 2 a 1-of-2, whoever signed
    assert gen["multisig_lanes"] == (4 * gen["inputs_by_kind"]["p2sh_multisig"]
                                     + 2 * gen["inputs_by_kind"]["bare_multisig"])


def _blocks_of(chain_dir) -> list:
    """The raw blocks of the chain's block files, genesis left out."""
    import glob

    blocks = []
    for path in sorted(glob.glob(os.path.join(
            chain_dir, "regtest", "blocks", "blk?????.dat"))):
        with open(path, "rb") as f:
            data = f.read()
        pos = 0
        while pos + 8 <= len(data) and data[pos:pos + 4] == data[:4]:
            (size,) = struct.unpack_from("<I", data, pos + 4)
            blocks.append(data[pos + 8:pos + 8 + size])
            pos += 8 + size
    return blocks[1:]


def _leg_differential(chain_dir) -> dict:
    """Every block of a generated chain through the native engine, and
    every input its P2PKH scan did not take through the interpreter as
    node.py's script leg ran it before the templates: the native lanes,
    candidate mask and table equal the records' and groups', byte for byte
    and in order. Returns what it counted."""
    from bitcoincashplus_tpu.consensus.params import (
        get_block_subsidy,
        regtest_params,
    )
    from bitcoincashplus_tpu.script.sighash import SighashCache
    from bitcoincashplus_tpu.validation.scriptcheck import block_script_flags

    params = regtest_params()
    eng = native.ConnectEngine()
    eng.set_best(params.genesis.get_hash())
    times = [params.genesis.header.time]
    seen = {"template_inputs": 0, "lanes": 0, "groups": 0, "threaded": 0}
    try:
        for height, raw in enumerate(_blocks_of(chain_dir), start=1):
            (when,) = struct.unpack_from("<I", raw, 68)
            flags = block_script_flags(height, when, params)
            res = eng.connect_block(
                raw, height, get_block_subsidy(height, params.consensus),
                params.max_block_size, params.consensus.coinbase_maturity,
                sorted(times[-11:])[len(times[-11:]) // 2], None, flags,
                want_sigs=True, nthreads=4)
            times.append(when)
            status = (res.sig_status if res.n_inputs
                      else np.zeros(0, np.uint8))  # a coinbase-only block
            assert not (status == 1).any()
            leg_idx = np.nonzero(status == 2)[0]
            records, groups, rows, txs = [], [], [], {}
            for g in leg_idx:
                t_i, in_i = (int(v) for v in res.sig_txin[g])
                if t_i not in txs:
                    tx = CTransaction.from_bytes(raw[
                        int(res.tx_offsets[t_i, 0]):
                        int(res.tx_offsets[t_i, 1])])
                    txs[t_i] = (tx, SighashCache(tx))
                tx, cache = txs[t_i]
                spk = res.spent_spk_blob[int(res.spent_spk_offsets[g]):
                                         int(res.spent_spk_offsets[g + 1])]
                first, had = len(records), len(groups)
                VerifyScript(tx.vin[in_i].script_sig, spk, flags,
                             DeferringSignatureChecker(
                                 tx, in_i, int(res.spent_values[g]), records,
                                 cache, groups))
                rows.append([int(g), first, *(
                    (groups[-1].m, groups[-1].n) if len(groups) > had
                    else (0, 0))])
            cand = np.zeros(len(records), np.uint8)
            for grp in groups:
                cand[grp.start:grp.start + grp.lanes] = 1
            assert_lanes_equal(
                (res.leg_lanes, res.leg_table.tolist()),
                ((*ecdsa_batch.records_to_blobs(records), cand), rows))
            seen["template_inputs"] += len(rows)
            seen["lanes"] += len(records)
            seen["groups"] += len(groups)
            seen["threaded"] += bool(res.n_inputs >= 64 and len(rows))
    finally:
        eng.close()
    return seen


@needs_engine
@pytest.mark.parametrize("seed", [2**31 + 2800, 2**31 + 3111, 31, 987654321])
def test_native_leg_equals_the_python_leg_over_a_generated_chain(
        seed, sound_chain, tmp_path):
    """A seeded campaign of mixed blocks in the generator's shapes (P2SH
    2-of-3, P2PK, bare 1-of-2 among P2PKH, 1 to 250 inputs a transaction):
    no input is left to the interpreter, and some blocks are wide enough
    for the scan's threads, whose lanes are joined in input order."""
    if seed == 2**31 + 2800:
        chain_dir, gen = sound_chain
    else:
        chain_dir, gen = tmp_path, _generate(tmp_path, seed=seed, lanes=1300)
    seen = _leg_differential(chain_dir)
    assert seen["template_inputs"] == gen["non_p2pkh_inputs"]
    assert seen["groups"] == gen["multisig_groups"]
    assert seen["lanes"] == (gen["multisig_lanes"]
                             + gen["inputs_by_kind"]["p2pk"])
    assert seen["threaded"] >= 1


def test_reindex_settles_every_template_input_in_the_native_scan(
        sound_chain, tmp_path):
    """The leg's counters over the generated chain: every input the P2PKH
    scan did not take fits a native template, none reaches VerifyScript,
    and the chain is the reference's."""
    chain_dir, gen = sound_chain
    node, moved = _reindex(chain_dir, tmp_path)
    try:
        stats = node.last_import_stats
        tip = _tip(node)
    finally:
        node.close()
    ref = reference_mixed.scan_chain(
        os.path.join(chain_dir, "regtest", "blocks"), 28, 6)
    assert tip == (ref["height"], ref["tip_hash"], ref["utxos"])
    assert stats["fallback_inputs"] == (stats["template_inputs"]
                                        + stats["interp_inputs"])
    assert stats["template_inputs"] == gen["non_p2pkh_inputs"]
    assert stats["interp_inputs"] == 0
    assert stats["multisig_lanes"] == gen["multisig_lanes"]
    assert stats["slow_path_blocks"] == 0
    assert (moved["multisig_groups"], moved["multisig_lanes"]) == (
        gen["multisig_groups"], gen["multisig_lanes"])
    assert moved["eager_multisig_sigs"] == 0


class _Flipping:
    """dispatch_packed, with one candidate verdict of the whole import
    turned over at settle time: the first whose honest value is ``value``."""

    def __init__(self, monkeypatch, value: bool):
        self.value, self.flipped, self.straddled = value, 0, 0
        self.real = ecdsa_batch.dispatch_packed
        monkeypatch.setattr(ecdsa_batch, "dispatch_packed", self)

    def __call__(self, *arrays, backend="auto", candidate=None, **kind):
        handle = self.real(*arrays, backend=backend, candidate=candidate,
                           **kind)
        if candidate is not None and candidate[-1] and candidate[-2]:
            self.straddled += 1  # a slice that ends inside a group: a
            # 2-of-3's four lanes or a 1-of-2's two cannot all be there
        outer = self

        class Handle:
            done = handle.done

            def result(self):
                ok = handle.result().copy()
                if candidate is not None and not outer.flipped:
                    hits = np.nonzero(candidate & (ok == outer.value))[0]
                    if hits.size:
                        ok[hits[0]] = not outer.value
                        outer.flipped += 1
                return ok

        return Handle()


@pytest.mark.parametrize("value", [True, False],
                         ids=["flipped-true", "flipped-false"])
def test_reindex_with_a_wrong_candidate_verdict_completes(
        sound_chain, tmp_path, monkeypatch, value):
    chain_dir, gen = sound_chain
    device = _Flipping(monkeypatch, value)
    node, moved = _reindex(chain_dir, tmp_path)
    try:
        assert _tip(node) == (gen["tip_height"], gen["tip_hash"],
                              gen["txouts"])
        assert node.last_import_stats["slow_path_blocks"] == 0
    finally:
        node.close()
    assert device.flipped == 1
    # a True turned False fails its group's walk, the host confirms the
    # input and the import goes on; a False turned True lets a walk end
    # sooner, on a group that was sound anyway
    assert moved["multisig_group_confirms"] == (1 if value else 0)
    assert moved["eager_multisig_sigs"] == 0


def test_reindex_settles_a_group_that_straddles_two_dispatches(
        sound_chain, tmp_path, monkeypatch):
    """The chain's lanes leave in slices of 2,046; the seed is one whose
    first slice ends inside a group (the fixture says so, or this test
    would prove nothing)."""
    chain_dir, gen = sound_chain
    device = _Flipping(monkeypatch, True)
    device.flipped = 1  # flip nothing
    node, moved = _reindex(chain_dir, tmp_path)
    try:
        assert _tip(node)[:2] == (gen["tip_height"], gen["tip_hash"])
    finally:
        node.close()
    assert device.straddled >= 1
    assert moved["multisig_group_confirms"] == 0


def test_reindex_aborts_on_a_group_no_dispatch_settles(
        sound_chain, tmp_path, monkeypatch):
    """A slip in the lane numbering (every group recorded one lane late):
    the groups read their neighbours' verdicts, so walks fail and the host
    confirms those inputs; the last group ends one lane past the last
    dispatch and no verdict ever reaches it. Nothing is written on its
    speculative success: the native import aborts and the Python engine
    connects the chain."""
    from bitcoincashplus_tpu.node import node as node_module

    chain_dir, gen = sound_chain
    real_add = node_module._MultisigSettler.add
    monkeypatch.setattr(
        node_module._MultisigSettler, "add",
        lambda self, base, groups: real_add(self, base + 1, groups))
    node, moved = _reindex(chain_dir, tmp_path)
    try:
        assert node.last_import_stats is None  # the native import aborted
        assert _tip(node) == (gen["tip_height"], gen["tip_hash"],
                              gen["txouts"])
    finally:
        node.close()
    assert moved["multisig_group_confirms"] > 0
    # the Python engine walked every multisig on the host
    assert moved["eager_multisig_sigs"] > 0


def test_history_below_the_fork_height_stays_on_the_native_engine(tmp_path):
    """The same chain signed as history from before the fork has it
    (SIGHASH_ALL, no FORKID) under a fork height it never reaches
    (-uahfheight): its blocks carry no NULLFAIL, but in each of its four
    script forms the check is the script's last operation, so every input
    rides lanes over the legacy digest as the chain above the fork does over
    FORKID's. No block leaves the native engine, no check runs inline."""
    chain_dir = tmp_path / "chain"
    gen = _generate(chain_dir, "--legacy-sighash")
    node, moved = _reindex(chain_dir, tmp_path / "node",
                           "-uahfheight=1000000000")
    try:
        assert _tip(node) == (gen["tip_height"], gen["tip_hash"],
                              gen["txouts"])
        stats = node.last_import_stats
    finally:
        node.close()
    assert stats["slow_path_blocks"] == 0
    assert stats["blocks"] == stats["prefork_blocks"] == gen["tip_height"]
    assert stats["fast_inputs"] == gen["inputs_by_kind"]["p2pkh"]
    assert (stats["fallback_inputs"], stats["template_inputs"],
            stats["interp_inputs"]) == (
        gen["non_p2pkh_inputs"], gen["non_p2pkh_inputs"], 0)
    assert stats["inline_legacy_sigs"] == 0
    # one legacy digest an input: a 2-of-3's two signatures share theirs
    assert stats["legacy_digests"] == gen["inputs"]
    assert moved == {
        "eager_multisig_sigs": 0, "multisig_groups": gen["multisig_groups"],
        "multisig_lanes": gen["multisig_lanes"],
        "multisig_group_confirms": 0, "reject_confirm_sigs": 0,
        "cpu_fallback_sigs": LANES, "inline_legacy_sigs": 0,
        "prefork_lanes": LANES}


def test_reindex_of_a_wrong_key_multisig_names_the_block(
        faulted_chain, tmp_path):
    """The last pay-to-script-hash input signed by a key outside its script:
    every encoding is fine, so the operation defers; the walk fails on
    honest verdicts, the host confirms the failure, the import aborts and
    the Python engine rejects exactly that block."""
    from bitcoincashplus_tpu.validation.chain import BlockStatus

    chain_dir, gen = faulted_chain
    node, moved = _reindex(chain_dir, tmp_path)
    try:
        height, _, _ = _tip(node)
        failed = [idx for idx in node.chainstate.block_index.values()
                  if idx.status & BlockStatus.FAILED_MASK]
        assert node.last_import_stats is None  # the native import aborted
    finally:
        node.close()
    ref = reference_mixed.scan_chain(
        os.path.join(chain_dir, "regtest", "blocks"), 28, 10**6)
    assert ref["first_bad_height"] == gen["tip_height"] == height + 1
    assert [idx.height for idx in failed] == [gen["tip_height"]]
    assert moved["multisig_group_confirms"] == 1
    assert moved["reject_confirm_sigs"] == 0


def test_schnorr_record_takes_no_ecdsa_lane():
    """records_to_blobs packs ECDSA lanes only: a Schnorr record (a 65-byte
    signature under OP_CHECKSIG) is refused, not packed as a lane that
    reads False."""
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord

    pt = secp.pubkey_parse(KEYS[0].pubkey)
    good = SigCheckRecord(pt, 5, 7, 11)
    assert len(ecdsa_batch.records_to_blobs([good])[2]) == 1
    with pytest.raises(ValueError, match="ECDSA"):
        ecdsa_batch.records_to_blobs(
            [good, SigCheckRecord(pt, 5, 7, 11, algo="schnorr")])


@pytest.mark.parametrize("stats,want", [
    ({"fallback_inputs": 8, "template_inputs": 6, "interp_inputs": 2}, 75.0),
    ({"fallback_inputs": 8, "template_inputs": 8, "interp_inputs": 0}, 100.0),
    ({"fallback_inputs": 8}, None),               # a program before PR 31
    ({"fallback_inputs": 0, "template_inputs": 0}, None),  # no leg at all
    (None, None),                                 # the import aborted
], ids=["some", "all", "no-counter", "no-leg", "no-stopwatch"])
def test_template_share_reader_reads_the_legs_counters(stats, want):
    """chipbench/layer_metrics/script_leg.template_share.py: the share of
    the leg's inputs a native template settled; nothing (and no raise)
    where the program has no such counter."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "template_share", os.path.join(
            BENCH, "layer_metrics", "script_leg.template_share.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({"after": {"import": stats}}) == want
