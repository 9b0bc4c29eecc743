"""ECDSA batch dispatch layer tests.

Fast tests exercise packing, bucketing, CPU fallback, and stats; the
device-kernel differential (single-device and 8-chip sharded) is marked
``slow`` — the 256-step verify loop costs minutes of XLA compile on the
CPU test backend (it compiles once per bucket on real hardware).
"""

import random

import numpy as np
import pytest

from bitcoincashplus_tpu.crypto import secp256k1 as oracle
from bitcoincashplus_tpu.ops import ecdsa_batch
from bitcoincashplus_tpu.ops.ecdsa_batch import (
    BUCKETS,
    _bucket_for,
    decompose_scalars,
    pack_records,
    verify_batch,
)
from bitcoincashplus_tpu.script.interpreter import SigCheckRecord

rng = random.Random(99)


def make_records(n, n_bad=0):
    recs, expected = [], []
    for i in range(n):
        d = rng.randrange(1, oracle.N)
        pub = oracle.point_mul(d, oracle.G)
        e = rng.randrange(1 << 256)
        r, s = oracle.ecdsa_sign(d, e)
        if i < n_bad:
            e ^= 1
        recs.append(SigCheckRecord(pub, r, s, e))
        expected.append(oracle.ecdsa_verify(pub, r, s, e))
    return recs, expected


def test_bucket_selection():
    assert _bucket_for(1) == BUCKETS[0]
    assert _bucket_for(BUCKETS[0]) == BUCKETS[0]
    assert _bucket_for(BUCKETS[0] + 1) == BUCKETS[1]
    assert _bucket_for(BUCKETS[-1] + 1) == 2 * BUCKETS[-1]


def test_decompose_scalars_matches_oracle_math():
    recs, _ = make_records(4)
    for rec, (u1, u2) in zip(recs, decompose_scalars(recs)):
        w = pow(rec.s, oracle.N - 2, oracle.N)
        assert u1 == rec.msg_hash * w % oracle.N
        assert u2 == rec.r * w % oracle.N
        # u1*G + u2*Q lands on x = r (the verify equation, oracle side)
        pt = oracle.point_add(
            oracle.point_mul(u1, oracle.G), oracle.point_mul(u2, rec.pubkey)
        )
        assert pt is not None and (pt[0] - rec.r) % oracle.N == 0


def test_pack_padding_is_poisoned():
    recs, _ = make_records(3)
    u1b, u2b, qx, qy, q_inf, r0, rn, wrap_ok = pack_records(recs, 8)
    assert q_inf.tolist() == [False] * 3 + [True] * 5
    assert not wrap_ok[3:].any()
    assert u1b.shape == (256, 8) and qx.shape[1] == 8
    # bit planes reconstruct the scalars
    u1, _ = decompose_scalars(recs[:1])[0]
    got = 0
    for i in range(256):
        got = (got << 1) | int(u1b[i, 0])
    assert got == u1


def test_cpu_fallback_small_batch():
    recs, expected = make_records(3, n_bad=1)
    before = ecdsa_batch.STATS.cpu_fallback_sigs
    ok = verify_batch(recs, backend="auto")  # 3 < CPU_FLOOR
    assert ok.tolist() == expected
    assert ecdsa_batch.STATS.cpu_fallback_sigs == before + 3


def test_empty_batch():
    assert verify_batch([]).shape == (0,)


def test_device_batch_minimal_differential():
    """ALWAYS runs (not slow-marked): the consensus-critical kernel path —
    one valid lane, one invalid lane, plus the wrap_ok gating — must be
    exercised by every default suite run. First fresh run pays the XLA
    compile; the persistent cache (conftest) amortizes it afterwards."""
    recs, expected = make_records(2, n_bad=1)
    ok = verify_batch(recs, backend="device")
    assert ok.tolist() == expected


def test_wrap_ok_gate_blocks_bogus_wraparound():
    """A signature whose r is replaced by r' = x_R - n (claiming the
    wraparound) must NOT verify unless r' + n < p actually held — the
    in-kernel wrap_ok mask (ADVICE r1 finding). Exercised via the CPU
    oracle equivalence: the kernel's gate mirrors
    secp256k1_ecdsa_sig_verify's r+n<p retry bound."""
    d = rng.randrange(1, oracle.N)
    pub = oracle.point_mul(d, oracle.G)
    e = rng.randrange(1 << 256)
    r, s = oracle.ecdsa_sign(d, e)
    recs = [SigCheckRecord(pub, r, s, e)]
    u1b, u2b, qx, qy, q_inf, r0, rn, wrap_ok = pack_records(recs, 2)
    assert wrap_ok[0] == (r + oracle.N < oracle.P)
    # the padded lane stays gated off
    assert not wrap_ok[1] and q_inf[1]


@pytest.mark.slow
def test_device_batch_differential():
    recs, expected = make_records(12, n_bad=4)
    ok = verify_batch(recs, backend="device")
    assert ok.tolist() == expected
    assert ecdsa_batch.STATS.dispatches >= 1


@pytest.mark.slow
def test_sharded_batch_differential():
    from bitcoincashplus_tpu.parallel.sig_shard import verify_batch_sharded

    recs, expected = make_records(16, n_bad=5)
    # pin w4: this is the w4 sharded differential (the GLV sharded one
    # lives in test_glv.py) — the default kernel would route to GLV
    ok = verify_batch_sharded(recs, 8, kernel="w4")
    assert ok.tolist() == expected


def test_pallas_bucket_ladder_boundaries():
    """The w4 bucket ladder: every bucket is >= n, a multiple of 1024 (the
    3D program's hard assert), and drawn from the bounded shape set."""
    from bitcoincashplus_tpu.ops.ecdsa_batch import _bucket_for

    allowed = {1024, 2048, 4096} | set(range(6144, 16385, 2048))
    for n in (129, 1000, 1024, 1025, 2048, 2049, 4096, 4097, 6144, 6145,
              10000, 16384):
        b = _bucket_for(n, pallas=True)
        assert b >= n and b % 1024 == 0, (n, b)
        assert b in allowed, (n, b)
    # beyond the split point: 16384-granular multiples
    for n in (16385, 30000, 32769):
        b = _bucket_for(n, pallas=True)
        assert b >= n and b % 16384 == 0, (n, b)
    # small batches keep the 2D kernel's buckets
    assert _bucket_for(128, pallas=True) == 128
    assert _bucket_for(8, pallas=True) == 32


def test_pallas_programming_errors_are_not_swallowed(monkeypatch):
    """A NameError/AttributeError inside the Pallas path is a BUG, not a
    toolchain limitation — it must propagate, not degrade silently to the
    XLA fallback (regression: a refactor deleted a module constant and
    every test stayed green on the fallback)."""
    import pytest

    from bitcoincashplus_tpu.ops import ecdsa_batch as eb

    with pytest.raises(NameError):
        eb._note_pallas_failure(NameError("name '_GONE' is not defined"))
    with pytest.raises(AttributeError):
        eb._note_pallas_failure(AttributeError("no attribute"))
    # toolchain-class failures still fall back (and latch when Mosaic)
    before = eb.STATS.pallas_fallbacks
    eb._note_pallas_failure(RuntimeError("transient compile hiccup"))
    assert eb.STATS.pallas_fallbacks == before + 1
