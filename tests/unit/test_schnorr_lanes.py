"""BCH Schnorr as a lane kind of the packed path (PR 44).

The device program (ops/secp256k1._glv_schnorr_program behind the shared
prepare stage) runs here on CPU JAX at 64 lanes, one XLA compile,
persistent-cached, against crypto/secp256k1.schnorr_verify and against the
specification stated over the lane's own scalars, with the edge lanes the
mathematics needs among random ones: above all a signature whose equation
and R'.x hold and whose jacobi(R'.y) is -1, which a program without the
Euler power accepts. The native challenge, signer and batch verifier are
held to the same oracle on the same seeds; the dispatch layer (a bucket of
one kind, Schnorr known-answer lanes, the ladder of rungs without a w4
form) runs on conftest's stand-in kernels; the native import takes blocks
with ECDSA and Schnorr inputs side by side through both bucket kinds and
refuses the chains the benchmark's faults make.
"""

from __future__ import annotations

import os
import random
import shutil

import numpy as np
import pytest

from bitcoincashplus_tpu import native
from bitcoincashplus_tpu.crypto import secp256k1 as oracle
from bitcoincashplus_tpu.ops import ecdsa_batch
from bitcoincashplus_tpu.ops import secp256k1 as dev
from bitcoincashplus_tpu.script.interpreter import SigCheckRecord

SEEDS = (44, 1905, 2019_05_15)
LANES = 64


def _be(value: int) -> np.ndarray:
    return np.frombuffer(value.to_bytes(32, "big"), np.uint8)


def _unnegated(secret: int, msg: int, start: int) -> tuple:
    """A signature under a nonce whose R.y is a non-residue, kept."""
    k = start
    while oracle.jacobi(oracle.point_mul(k, oracle.G)[1]) == 1:
        k += 1
    return oracle.schnorr_sign_with_nonce(secret, msg, k, negate=False)


def spec(u1: int, u2: int, key, r: int, jacobi: bool = True) -> bool:
    """The specification over a lane's scalars: R' = u1*G + u2*P finite,
    R'.x = r and (unless ``jacobi`` is off) jacobi(R'.y) = 1."""
    if key is None:
        return False
    found = oracle.point_add(oracle.point_mul(u1, oracle.G),
                             oracle.point_mul(u2, key))
    return (found is not None and found[0] == r
            and (not jacobi or oracle.jacobi(found[1]) == 1))


def test_euler_chain_is_the_power_of_eulers_criterion():
    """The addition chain on exponents: squaring doubles, multiplying adds;
    254 squarings and 14 multiplications make (p - 1) / 2."""
    steps = [0, 0]

    def sqr_n(v, k):
        steps[0] += k
        return v << k

    def mul(a, b):
        steps[1] += 1
        return a + b

    assert dev._euler_chain(1, sqr_n, mul) == (oracle.P - 1) // 2
    assert steps == [254, 14]
    # and on field elements: the chain is the Jacobi symbol
    for value in (2, 3, 5, oracle.GY, oracle.P - 1):
        got = dev._euler_chain(value, lambda v, k: pow(v, 1 << k, oracle.P),
                               lambda a, b: a * b % oracle.P)
        assert got == oracle.jacobi(value)


# -- the native library against the oracle ------------------------------------

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native library unavailable")


def _seeded_records(seed: int, count: int = 24) -> list:
    """(record, what the oracle says) with bad ones among them."""
    rng = random.Random(seed)
    out = []
    for i in range(count):
        d = rng.randrange(1, oracle.N)
        m = rng.randrange(1 << 256)
        r, s = oracle.schnorr_sign(d, m)
        key = oracle.point_mul(d, oracle.G)
        if i % 6 == 1:
            m ^= 1 << rng.randrange(256)
        elif i % 6 == 2:
            r, s = _unnegated(d, m, rng.randrange(1, 1 << 64))
        elif i % 6 == 3:
            s = (s + 1) % oracle.N
        elif i % 6 == 4 and i % 12 == 4:
            r += oracle.P  # >= p: refused before any arithmetic
        elif i % 6 == 4:
            s += oracle.N
        out.append(SigCheckRecord(key, r, s, m, algo="schnorr"))
    return out


@needs_native
@pytest.mark.parametrize("seed", SEEDS)
def test_native_signer_is_the_oracles(seed):
    rng = random.Random(seed)
    for _ in range(12):
        d, m = rng.randrange(1, oracle.N), rng.randrange(1 << 256)
        assert native.schnorr_sign(d, m) == oracle.schnorr_sign(d, m)


@needs_native
@pytest.mark.parametrize("seed", SEEDS)
def test_native_challenge_is_the_oracles(seed):
    records = [r for r in _seeded_records(seed) if r.r < 1 << 256
               and r.s < 1 << 256]
    pub, rs, msg, u2, wrap = ecdsa_batch.schnorr_records_to_blobs(records)
    assert not wrap.any()
    for rec, row in zip(records, u2):
        e = oracle.schnorr_challenge(rec.r, rec.pubkey, rec.msg_hash)
        assert int.from_bytes(row.tobytes(), "big") == (oracle.N - e) % oracle.N
    _, ok = native.schnorr_challenge_blobs(pub.tobytes(), rs.tobytes(),
                                           msg.tobytes(), len(records))
    assert ok == [rec.r < oracle.P and rec.s < oracle.N for rec in records]


@needs_native
@pytest.mark.parametrize("seed", SEEDS)
def test_native_batch_verifier_is_the_oracles(seed):
    records = _seeded_records(seed) + [
        SigCheckRecord(None, 5, 7, 11, algo="schnorr")]
    want = [oracle.schnorr_verify(r.pubkey, r.r, r.s, r.msg_hash)
            for r in records]
    assert any(want) and not all(want)
    assert native.schnorr_verify_batch(records) == want
    before = ecdsa_batch.STATS.schnorr_cpu_sigs
    assert ecdsa_batch._schnorr_oracle(records).tolist() == want
    assert ecdsa_batch.STATS.schnorr_cpu_sigs == before + len(records)


def test_the_interpreters_eager_check_takes_the_native_verify():
    from bitcoincashplus_tpu.script.interpreter import _schnorr_verify_scalar

    for rec in _seeded_records(7, 12):
        assert _schnorr_verify_scalar(
            rec.pubkey, rec.r, rec.s, rec.msg_hash) == oracle.schnorr_verify(
                rec.pubkey, rec.r, rec.s, rec.msg_hash)


# -- the device program, lane by lane ------------------------------------------

def _edge_lanes() -> dict:
    """name -> (u1, u2, key or None, r, what a verifier must say, whether
    the equation and R'.x hold without the Jacobi test)."""
    rng = random.Random(20190515)
    d, m = rng.randrange(1, oracle.N), rng.randrange(1 << 256)
    key = oracle.point_mul(d, oracle.G)
    r, s = oracle.schnorr_sign(d, m)
    e = oracle.schnorr_challenge(r, key, m)
    lanes = {"valid": (s, (oracle.N - e) % oracle.N, key, r)}
    lanes["challenge_off_by_one"] = (s, (oracle.N - e - 1) % oracle.N, key, r)
    rj, sj = _unnegated(d, m, 77)
    ej = oracle.schnorr_challenge(rj, key, m)
    assert not oracle.schnorr_verify(key, rj, sj, m)
    lanes["jacobi_minus_one"] = (sj, (oracle.N - ej) % oracle.N, key, rj)
    # r in [n, p): a field element no ECDSA r can be. No signer reaches one
    # (2^-128), so the key is solved for: P = (R - u1*G) / u2
    x = oracle.N
    while oracle.schnorr_lift_x(x) is None:
        x += 1
    big_r = oracle.schnorr_lift_x(x)
    u1, u2 = rng.randrange(1, oracle.N), rng.randrange(1, oracle.N)
    minus = oracle.point_mul(oracle.N - u1, oracle.G)
    solved = oracle.point_mul(pow(u2, -1, oracle.N),
                              oracle.point_add(big_r, minus))
    assert oracle.N <= x < oracle.P
    lanes["r_between_n_and_p"] = (u1, u2, solved, x)
    lanes["r_between_n_and_p_wrong_key"] = (u1, u2, key, x)
    # e = 0: the key's scalar is 0 and R' = s*G
    k = rng.randrange(1, oracle.N)
    if oracle.jacobi(oracle.point_mul(k, oracle.G)[1]) != 1:
        k = oracle.N - k
    lanes["challenge_zero"] = (k, 0, key, oracle.point_mul(k, oracle.G)[0])
    # s*G = e*P: R' is the point at infinity
    e0 = rng.randrange(1, oracle.N)
    lanes["r_prime_infinite"] = (e0 * d % oracle.N, oracle.N - e0, key, r)
    lanes["s_zero"] = (0, (oracle.N - e) % oracle.N, key, r)
    lanes["key_at_infinity"] = (s, (oracle.N - e) % oracle.N, None, r)
    return lanes


EDGE = _edge_lanes()
WANT = {"valid": True, "challenge_off_by_one": False,
        "jacobi_minus_one": False, "r_between_n_and_p": True,
        "r_between_n_and_p_wrong_key": False, "challenge_zero": True,
        "r_prime_infinite": False, "s_zero": False, "key_at_infinity": False}


@pytest.fixture(scope="module")
def device_verdicts():
    """The program's (ok, degen) over the edge lanes, then seeded signatures
    good and bad, LANES in all; and what the oracle says of the seeded."""
    rows = list(EDGE.values())
    said = []
    for rec in _seeded_records(44, LANES - len(rows)):
        fits = rec.r < oracle.P and rec.s < oracle.N
        e = oracle.schnorr_challenge(rec.r % (1 << 256), rec.pubkey,
                                     rec.msg_hash)
        rows.append((rec.s % oracle.N, (oracle.N - e) % oracle.N,
                     rec.pubkey if fits else None, rec.r % oracle.P))
        said.append(oracle.schnorr_verify(rec.pubkey, rec.r, rec.s,
                                          rec.msg_hash))
    g = oracle.G
    arrays = [np.stack([_be(row[0]) for row in rows]),
              np.stack([_be(row[1]) for row in rows]),
              np.stack([_be((row[2] or g)[0]) for row in rows]),
              np.stack([_be((row[2] or g)[1]) for row in rows]),
              np.array([row[2] is None for row in rows], np.uint8),
              np.stack([_be(row[3]) for row in rows])]
    ok, degen = dev.schnorr_verify_batch_glv_dev(*arrays)
    return np.asarray(ok), np.asarray(degen), said


@pytest.mark.wall_limit(900, reason="one XLA:CPU compile of two programs")
@pytest.mark.parametrize("name", sorted(EDGE))
def test_device_program_edge_lane(device_verdicts, name):
    ok, degen, _ = device_verdicts
    i = list(EDGE).index(name)
    u1, u2, key, r = EDGE[name]
    assert spec(u1, u2, key, r) is WANT[name]
    if name == "r_prime_infinite":
        # the cheap adds flag what they cannot add: the host re-checks it
        assert not ok[i] or degen[i]
    else:
        assert bool(ok[i]) is WANT[name] and not degen[i]
    if name == "jacobi_minus_one":
        # the lane that needs the Euler power: all else of it holds
        assert spec(u1, u2, key, r, jacobi=False)


def test_device_program_agrees_with_the_oracle_on_seeded_lanes(
        device_verdicts):
    ok, degen, said = device_verdicts
    assert any(said) and not all(said)
    assert ok[len(EDGE):].tolist() == said
    assert not degen[len(EDGE):].any()


# -- the packer and the dispatch layer ----------------------------------------

def test_ge_be_compares_rows_as_numbers():
    rows = np.stack([_be(v) for v in (0, oracle.N - 1, oracle.N,
                                      oracle.N + 1, oracle.P - 1, oracle.P,
                                      (1 << 256) - 1)])
    assert ecdsa_batch._ge_be(rows, oracle.N).tolist() == [
        False, False, True, True, True, True, True]
    assert ecdsa_batch._ge_be(rows, oracle.P).tolist() == [
        False, False, False, False, False, True, True]


def test_schnorr_bucket_poisons_what_the_spec_refuses_unverified():
    """r >= p and s >= n never reach the arithmetic: their lanes and the
    padding are q_inf = 1; u1 is s, u2 the rn slot, r the r slot."""
    good, _ = ecdsa_batch._schnorr_kat_records()
    recs = [good,
            SigCheckRecord(good.pubkey, oracle.P, good.s, good.msg_hash,
                           algo="schnorr"),
            SigCheckRecord(good.pubkey, good.r, oracle.N, good.msg_hash,
                           algo="schnorr"),
            SigCheckRecord(good.pubkey, oracle.N, good.s, good.msg_hash,
                           algo="schnorr")]
    pub, rs, msg, u2, wrap = ecdsa_batch.schnorr_records_to_blobs(recs)
    u1m, u2m, qx, qy, q_inf, r0 = ecdsa_batch.pack_lanes(
        pub, rs, msg, u2, wrap, 8, schnorr=True)
    assert q_inf.tolist() == [0, 1, 1, 0, 1, 1, 1, 1]
    assert u1m[0].tobytes() == rs[0, 32:].tobytes()
    assert u2m[0].tobytes() == u2[0].tobytes()
    assert r0[3].tobytes() == oracle.N.to_bytes(32, "big")
    with pytest.raises(ValueError, match="Schnorr records only"):
        ecdsa_batch.schnorr_records_to_blobs([SigCheckRecord(
            good.pubkey, 5, 7, 11)])


def _blobs(records):
    return ecdsa_batch.schnorr_records_to_blobs(records)


def test_schnorr_bucket_rides_its_own_program_and_kat_lanes(
        stub_verify_kernels):
    """One dispatch of one kind: the Schnorr rung alone runs, its two
    known-answer lanes are Schnorr's and sit behind the real lanes, and the
    counters move as the issue fixes them."""
    records = [r for r in _seeded_records(1905, 12)
               if r.r < oracle.P and r.s < oracle.N]
    want = [oracle.schnorr_verify(r.pubkey, r.r, r.s, r.msg_hash)
            for r in records]
    before = ecdsa_batch.STATS.snapshot()
    out = ecdsa_batch.dispatch_packed(*_blobs(records), backend="device",
                                      schnorr=True).result()
    after = ecdsa_batch.STATS.snapshot()
    assert out.tolist() == want
    assert stub_verify_kernels.rungs() == ["schnorr"]
    n = len(records)
    moved = {k: after[k] - before[k] for k in (
        "schnorr_lanes", "schnorr_dispatches", "sigs_verified",
        "glv_dispatches", "schnorr_cpu_sigs", "cpu_fallback_sigs",
        "kat_failures", "reject_confirm_sigs")}
    assert moved == {"schnorr_lanes": n, "schnorr_dispatches": 1,
                     "sigs_verified": n, "glv_dispatches": 1,
                     "schnorr_cpu_sigs": 0, "cpu_fallback_sigs": 0,
                     "kat_failures": 0,
                     # the device's Falses are confirmed on the host
                     "reject_confirm_sigs": want.count(False)}
    (_, arrays), = stub_verify_kernels.calls
    kat = ecdsa_batch._schnorr_kat_blobs()
    assert arrays[5][n:n + 2].tobytes() == kat[1][:, :32].tobytes()
    assert arrays[4][:n + 2].tolist() == [0] * (n + 2)


@pytest.mark.parametrize("case", ["kernel-fails", "kat-lies",
                                  "breaker-open"])
def test_schnorr_bucket_has_no_w4_rung(stub_verify_kernels, case,
                                       monkeypatch):
    """A failed Schnorr dispatch goes to the retries and then to the native
    threaded Schnorr verify, never to the w4 kernel; a lying mask trips the
    known-answer gate; the verdicts are the oracle's either way."""
    from bitcoincashplus_tpu.ops import dispatch

    records = [r for r in _seeded_records(44, 12)
               if r.r < oracle.P and r.s < oracle.N]
    want = [oracle.schnorr_verify(r.pubkey, r.r, r.s, r.msg_hash)
            for r in records]
    dispatch.reset()
    dispatch.configure(backoff_base=0.0)
    br = dispatch.breaker("ecdsa")
    before = ecdsa_batch.STATS.snapshot()
    if case == "kernel-fails":
        stub_verify_kernels.fail["schnorr"] = RuntimeError("device gone")
    elif case == "kat-lies":
        stub_verify_kernels.verdicts = lambda a: np.ones(len(a[4]), bool)
    else:
        monkeypatch.setattr(br, "allow", lambda: False)
    try:
        out = ecdsa_batch.dispatch_packed(*_blobs(records), backend="device",
                                          schnorr=True).result()
    finally:
        dispatch.reset()
    after = ecdsa_batch.STATS.snapshot()
    assert out.tolist() == want
    assert "w4" not in stub_verify_kernels.rungs()
    assert "glv" not in stub_verify_kernels.rungs()
    assert after["cpu_fallback_sigs"] - before["cpu_fallback_sigs"] == len(
        records)
    assert after["pallas_fallbacks"] == before["pallas_fallbacks"]
    if case == "kernel-fails":
        assert stub_verify_kernels.rungs() == ["schnorr"] * (
            br.cfg.retries + 1)
        assert after["glv_fallbacks"] - before["glv_fallbacks"] == (
            br.cfg.retries + 1)
    if case == "kat-lies":
        assert after["kat_failures"] - before["kat_failures"] == 1


def test_degenerate_schnorr_lanes_are_rechecked_by_the_native_verify(
        stub_verify_kernels):
    """The cheap adds' flag: such a lane's verdict is the CPU's, counted
    where ECDSA's are, and the Python oracle's counter stays put where the
    library is loaded."""
    records = [r for r in _seeded_records(44, 6)
               if r.r < oracle.P and r.s < oracle.N]
    want = [oracle.schnorr_verify(r.pubkey, r.r, r.s, r.msg_hash)
            for r in records]
    handle = ecdsa_batch.dispatch_packed(*_blobs(records), backend="device",
                                         schnorr=True)
    handle._degen = np.ones(handle._bucket, bool)   # every lane flagged
    handle._device_ok = np.asarray(handle._device_ok) | True
    handle._device_ok[len(records) + 1] = False     # the bad KAT lane
    before = ecdsa_batch.STATS.snapshot()
    assert handle.result().tolist() == want
    after = ecdsa_batch.STATS.snapshot()
    assert (after["degenerate_rechecks"]
            - before["degenerate_rechecks"]) == len(records)
    if native.available():
        assert after["schnorr_cpu_sigs"] == before["schnorr_cpu_sigs"]


# -- through the door: the native import --------------------------------------

import test_native_connect as tnc  # noqa: E402  (tests/unit is on sys.path)

needs_engine = pytest.mark.skipif(not native.engine_available(),
                                  reason="native connect engine unavailable")


def _mixed_chain(tmp_path, bad: str = ""):
    """102 coinbase blocks, a fund, then one block whose spends carry ECDSA
    and Schnorr pay-to-pubkey-hash inputs side by side (in one transaction
    too) and a pay-to-pubkey spend under Schnorr, then an ECDSA block.
    ``bad`` breaks one Schnorr input of the mixed block."""
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.script.script import p2pk_script, push_data_raw
    from bitcoincashplus_tpu.script.sighash import signature_hash
    from bitcoincashplus_tpu.wallet.keys import CKey
    from bitcoincashplus_tpu.wallet.signing import sign_transaction

    chain = tnc._DiskChain(tmp_path)
    pk_spk = p2pk_script(tnc.KEY.pubkey)
    fund = chain.fund(0, [tnc.SPK] * 7 + [pk_spk])
    chain.push((fund,))
    each = fund.vout[0].value

    def spend(outs, schnorr, spk=tnc.SPK, key_for=tnc._key_for):
        unsigned = CTransaction(
            1, tuple(CTxIn(COutPoint(fund.txid, i), b"", 0xFFFFFFFE)
                     for i in outs),
            (CTxOut(each * len(outs) - 10_000, tnc.SPK),))
        return sign_transaction(unsigned, [(spk, each)] * len(outs),
                                key_for, enable_forkid=True,
                                schnorr=schnorr)

    # one transaction with both kinds: inputs 5 ECDSA, 6 Schnorr
    both = spend([5, 6], False)
    vin = list(both.vin)
    vin[1] = spend([5, 6], True).vin[1]
    both = CTransaction(both.version, tuple(vin), both.vout, both.locktime)
    broken = spend([0, 1], True)
    if bad:
        unsigned = CTransaction(
            1, tuple(CTxIn(v.prevout, b"", v.sequence) for v in broken.vin),
            broken.vout)
        digest = signature_hash(tnc.SPK, unsigned, 1, 0x41, each,
                                enable_forkid=True)
        if bad == "wrong-jacobi":
            r, s = _unnegated(tnc.KEY.secret, int.from_bytes(digest, "big"),
                              99)
            assert not oracle.schnorr_verify(
                oracle.point_mul(tnc.KEY.secret, oracle.G), r, s,
                int.from_bytes(digest, "big"))
        else:
            r, s = oracle.schnorr_sign(0xFACADE, int.from_bytes(digest, "big"))
        sig = r.to_bytes(32, "big") + s.to_bytes(32, "big") + b"\x41"
        vin = list(broken.vin)
        vin[1] = CTxIn(vin[1].prevout, push_data_raw(sig)
                       + push_data_raw(tnc.KEY.pubkey), vin[1].sequence)
        broken = CTransaction(broken.version, tuple(vin), broken.vout,
                              broken.locktime)
    mixed = chain.push((broken, spend([2, 3], False), spend([4], True),
                        both, spend([7], True, pk_spk)))
    last = chain.push((tnc._spend(
        [COutPoint(chain.coinbases[1].txid, 0)],
        [chain.coinbases[1].vout[0].value]),))
    return chain, mixed, last


def _coins(node) -> tuple:
    return node.coins_db.count_coins(), node.coins_db.muhash_digest()


@needs_engine
def test_import_takes_both_kinds_in_the_same_blocks(tmp_path,
                                                    stub_verify_kernels,
                                                    monkeypatch):
    """No block leaves the native engine; the Schnorr inputs (4 pay-to-
    pubkey-hash, 1 pay-to-pubkey) fill a bucket of their own beside the
    ECDSA one and both drain on the device path; tip and coins are the
    Python engine's."""
    chain, _, last = _mixed_chain(tmp_path / "a")
    shutil.copytree(tmp_path / "a", tmp_path / "b")
    # every import dispatch on the (stand-in) device path, as under -tpu=1
    monkeypatch.setattr(ecdsa_batch, "dispatch_backend",
                        lambda backend, lane_floor=False:
                        "auto" if lane_floor else "device")
    before = ecdsa_batch.STATS.snapshot()
    node = chain.reindex()
    try:
        after = ecdsa_batch.STATS.snapshot()
        stats = node.last_import_stats
        assert node.chainstate.tip().hash == last.get_hash()
        assert stats["slow_path_blocks"] == 0
        assert stats["interp_inputs"] == 0
        assert stats["schnorr_inputs"] == 5
        assert stats["template_inputs"] == 1   # the pay-to-pubkey spend
        assert stats["schnorr_challenge_s"] > 0
        assert stats["dispatches"] == 2        # one bucket a kind
        assert sorted(stub_verify_kernels.rungs()) == ["glv", "schnorr"]
        assert after["schnorr_lanes"] - before["schnorr_lanes"] == 5
        assert after["schnorr_dispatches"] - before["schnorr_dispatches"] == 1
        assert after["schnorr_cpu_sigs"] == before["schnorr_cpu_sigs"]
        assert after["cpu_fallback_sigs"] == before["cpu_fallback_sigs"]
        # the fund's input, the last block's and 3 ECDSA inputs of the
        # mixed block beside its 5 Schnorr
        assert after["sigs_verified"] - before["sigs_verified"] == 10
        native_coins = _coins(node)
    finally:
        node.close()
    monkeypatch.setenv("BCP_NO_NATIVE_IMPORT", "1")
    python_node = _reindex_copy(tmp_path / "b")
    try:
        assert python_node.chainstate.tip().hash == last.get_hash()
        assert _coins(python_node) == native_coins
    finally:
        python_node.close()


@needs_engine
def test_import_keeps_the_interpreters_schnorr_records_and_its_groups(
        tmp_path):
    """One block's generic-script leg with both kinds from both sources:
    a pay-to-pubkey input under Schnorr and a bare 1-of-2 fit templates;
    the same two scripts behind ``OP_1 OP_VERIFY`` fit none, so the
    interpreter defers a Schnorr record and, behind it, a multisig group.
    The record joins the Schnorr lanes, the group's first lane is counted
    among the ECDSA lanes alone, every walk succeeds and the block stays
    in the native engine."""
    from bitcoincashplus_tpu.consensus.tx import (
        COutPoint,
        CTransaction,
        CTxIn,
        CTxOut,
    )
    from bitcoincashplus_tpu.script.script import (
        multisig_script,
        p2pk_script,
        push_data_raw,
    )
    from bitcoincashplus_tpu.wallet.keys import CKey
    from bitcoincashplus_tpu.wallet.signing import make_signature

    other = CKey(0xFACADE, compressed=True)
    prefix = b"\x51\x69"  # OP_1 OP_VERIFY
    one_of_two = multisig_script(1, [other.pubkey, tnc.KEY.pubkey])
    spks = [p2pk_script(tnc.KEY.pubkey), one_of_two,
            prefix + p2pk_script(other.pubkey), prefix + one_of_two]
    signers = [tnc.KEY, tnc.KEY, other, other]
    schnorr = [True, False, True, False]
    chain = tnc._DiskChain(tmp_path)
    fund = chain.fund(0, spks)
    chain.push((fund,))
    each = fund.vout[0].value
    unsigned = CTransaction(
        1, tuple(CTxIn(COutPoint(fund.txid, i), b"", 0xFFFFFFFE)
                 for i in range(4)),
        (CTxOut(4 * each - 10_000, tnc.SPK),))
    sigs = [push_data_raw(make_signature(key, spk, unsigned, i, each,
                                         enable_forkid=True, schnorr=kind))
            for i, (key, spk, kind) in enumerate(zip(signers, spks,
                                                     schnorr))]
    script_sigs = [sigs[0], b"\x00" + sigs[1], sigs[2], b"\x00" + sigs[3]]
    spend = CTransaction(
        1, tuple(CTxIn(txin.prevout, ss, txin.sequence)
                 for txin, ss in zip(unsigned.vin, script_sigs)),
        unsigned.vout)
    last = chain.push((spend,))
    before = ecdsa_batch.STATS.snapshot()
    node = chain.reindex()
    try:
        assert node.chainstate.tip().hash == last.get_hash()
        stats = node.last_import_stats
    finally:
        node.close()
    after = ecdsa_batch.STATS.snapshot()
    assert stats["slow_path_blocks"] == 0
    assert (stats["fallback_inputs"], stats["template_inputs"],
            stats["interp_inputs"]) == (4, 2, 2)
    assert stats["schnorr_inputs"] == 1      # the scan's own; the other
    assert stats["dispatches"] == 2          # came from the interpreter
    assert (stats["multisig_groups"], stats["multisig_lanes"]) == (2, 4)
    assert stats["multisig_group_confirms"] == 0
    assert after["eager_multisig_sigs"] == before["eager_multisig_sigs"]


def _reindex_copy(datadir, **flags):
    """Node(-reindex) over a datadir whose stores are closed."""
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    cfg = Config()
    cfg.args["datadir"] = [str(datadir)]
    cfg.args["regtest"] = ["1"]
    cfg.args["reindex"] = ["1"]
    for name, value in flags.items():
        cfg.args[name] = [str(value)]
    return Node(config=cfg)


@needs_engine
@pytest.mark.parametrize("bad", ["wrong-jacobi", "wrong-key-sig"])
def test_both_engines_refuse_a_bad_schnorr_signature_at_its_block(
        tmp_path, monkeypatch, bad):
    """The benchmark's two faults: the block before the bad one is the tip
    under the native import and under the Python engine, and the bad block
    is marked failed with the same reject reason."""
    from bitcoincashplus_tpu.validation.chain import BlockStatus

    chain, mixed, _ = _mixed_chain(tmp_path / "a", bad)

    def verdict(node):
        idx = node.chainstate.block_index.get(mixed.get_hash())
        assert idx is not None and idx.status & BlockStatus.FAILED_MASK
        return (node.chainstate.tip().hash,
                node.chainstate.tip().height)

    reasons = []
    from bitcoincashplus_tpu.validation.chainstate import (
        BlockValidationError,
        ChainstateManager,
    )

    real = ChainstateManager.connect_block

    def spy(self, block, *args, **kwargs):
        try:
            return real(self, block, *args, **kwargs)
        except BlockValidationError as err:
            if block.get_hash() == mixed.get_hash():
                reasons.append(err.reason)
            raise

    monkeypatch.setattr(ChainstateManager, "connect_block", spy)
    chain.cs.flush()
    chain.store.close()
    chain.index_kv.close()
    chain.coins_kv.close()
    shutil.copytree(tmp_path / "a", tmp_path / "b")
    # -pipelinedepth=1: the Python engine's verdict is connect_block's own
    node = _reindex_copy(tmp_path / "a", pipelinedepth=1)
    try:
        native_verdict = verdict(node)
    finally:
        node.close()
    native_reasons, reasons[:] = list(reasons), []
    monkeypatch.setenv("BCP_NO_NATIVE_IMPORT", "1")
    python_node = _reindex_copy(tmp_path / "b", pipelinedepth=1)
    try:
        assert verdict(python_node) == native_verdict
    finally:
        python_node.close()
    assert native_verdict[0] == mixed.header.hash_prev_block
    assert native_reasons == reasons == ["blk-bad-inputs"]


@needs_engine
@pytest.mark.parametrize("form", ["p2pkh", "p2pk"])
def test_scan_takes_65_bytes_from_the_fork_height_on_only(form):
    """The lane kind in what the scan hands back, by era and form: a
    Schnorr lane (its rn slot (n - e) mod n) under flags with FORKID and
    NULLFAIL, the path of today below the fork height."""
    import test_multisig_lanes as tml
    from bitcoincashplus_tpu.script import script as S
    from bitcoincashplus_tpu.script.interpreter import (
        SCRIPT_ENABLE_SIGHASH_FORKID,
        SCRIPT_VERIFY_NULLFAIL,
    )
    from bitcoincashplus_tpu.script.sighash import signature_hash

    key = tml.KEYS[0]
    spk = (S.p2pk_script(key.pubkey) if form == "p2pk"
           else key.p2pkh_script())
    digest = signature_hash(spk, tml._with_script_sig(b""), 0, 0x41,
                            tml.AMOUNT, enable_forkid=True)
    sig = key.sign_schnorr(digest) + b"\x41"
    script_sig = S.push_data_raw(sig) + (
        b"" if form == "p2pk" else S.push_data_raw(key.pubkey))
    res = tml.native_result(script_sig, spk)
    point = oracle.pubkey_parse(key.pubkey)
    e = oracle.schnorr_challenge(int.from_bytes(sig[:32], "big"), point,
                                 int.from_bytes(digest, "big"))
    u2 = ((oracle.N - e) % oracle.N).to_bytes(32, "big")
    assert res.schnorr_inputs == 1
    if form == "p2pk":
        assert int(res.sig_status[0]) == 2
        pub, rs, msg, rn, wrap, cand, kind = res.leg_lanes
        assert res.leg_table.tolist() == [[0, 0, 0, 0]]
    else:
        assert int(res.sig_status[0]) == 0
        pub, rs, msg, rn, wrap, kind = (
            res.sig_pub, res.sig_rs, res.sig_msg, res.sig_rn, res.sig_wrap,
            res.sig_kind)
    assert kind.tolist() == [1] and wrap.tolist() == [0]
    assert rs[0].tobytes() == sig[:64] and msg[0].tobytes() == digest
    assert rn[0].tobytes() == u2
    assert pub[0].tobytes() == (point[0].to_bytes(32, "big")
                                + point[1].to_bytes(32, "big"))
    # below the fork height (no NULLFAIL, no FORKID): as before this PR
    old = tml.FLAGS & ~(SCRIPT_VERIFY_NULLFAIL | SCRIPT_ENABLE_SIGHASH_FORKID)
    try:
        below = tml.native_result(script_sig, spk, old)
    except native.EngineError as err:
        assert form == "p2pkh" and err.reason in ("sig-der", "illegal-forkid")
    else:
        assert below.schnorr_inputs == 0 and int(below.sig_status[0]) == 1


@needs_engine
@pytest.mark.parametrize("what", ["r_is_p", "s_is_n", "hashtype_0x01",
                                  "hashtype_0x44", "hybrid_key"])
def test_scan_declines_what_a_schnorr_lane_cannot_carry(what):
    """Declined to the interpreter, which fails it by name: never a
    verdict of the scan's own."""
    import test_multisig_lanes as tml
    from bitcoincashplus_tpu.script import script as S
    from bitcoincashplus_tpu.script.sighash import signature_hash

    key = tml.LONG_KEY if what == "hybrid_key" else tml.KEYS[0]
    pubkey = (b"\x06" + key.pubkey[1:]) if what == "hybrid_key" else key.pubkey
    from bitcoincashplus_tpu.crypto.hashes import hash160
    spk = S.p2pkh_script(hash160(pubkey))
    digest = signature_hash(spk, tml._with_script_sig(b""), 0, 0x41,
                            tml.AMOUNT, enable_forkid=True)
    sig = bytearray(key.sign_schnorr(digest) + b"\x41")
    if what == "r_is_p":
        sig[:32] = oracle.P.to_bytes(32, "big")
    elif what == "s_is_n":
        sig[32:64] = oracle.N.to_bytes(32, "big")
    elif what.startswith("hashtype"):
        sig[64] = int(what[-4:], 16)
    script_sig = S.push_data_raw(bytes(sig)) + S.push_data_raw(pubkey)
    res = tml.native_result(script_sig, spk)
    assert int(res.sig_status[0]) == 1 and res.schnorr_inputs == 0
    want = {"r_is_p": "sig-nullfail", "s_is_n": "sig-nullfail",
            "hashtype_0x01": "must-use-forkid",
            "hashtype_0x44": "sig-hashtype", "hybrid_key": "pubkeytype"}
    assert tml.eager(script_sig, spk)[0] == want[what]
