"""ECDSA batch dispatch layer tests.

Fast tests exercise packing, bucketing, CPU fallback, stats and — with
stub kernels (tests/conftest.py) — the one supervised dispatch function
and its rungs; the device-kernel differential (single-device and 8-chip
sharded) is marked ``slow`` — the verify program costs minutes of XLA
compile on the CPU test backend (once per bucket on real hardware).
"""

import random

import numpy as np
import pytest

from bitcoincashplus_tpu.crypto import secp256k1 as oracle
from bitcoincashplus_tpu.ops import dispatch, ecdsa_batch
from bitcoincashplus_tpu.ops.ecdsa_batch import (
    _bucket_for,
    decompose_scalars,
    pack_lanes,
    records_to_blobs,
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
    """One floor for every batch: the smallest compiled shape is 1024."""
    assert _bucket_for(1) == 1024
    assert _bucket_for(3) == _bucket_for(128) == _bucket_for(1024) == 1024
    assert _bucket_for(1025) == 2048
    assert _bucket_for(8192) == 8192


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
    u1m, u2m, qxb, qyb, q_inf, r0b, rnb, wrap_ok = pack_lanes(
        *records_to_blobs(recs), 8)
    assert q_inf.tolist() == [0] * 3 + [1] * 5
    assert not wrap_ok[3:].any()
    assert u1m.shape == (8, 32) and qxb.shape == (8, 32)
    assert not u1m[3:].any() and not qxb[3:].any()
    # the byte rows are the scalars and the fields, big-endian
    u1, u2 = decompose_scalars(recs[:1])[0]
    assert int.from_bytes(u1m[0].tobytes(), "big") == u1
    assert int.from_bytes(u2m[0].tobytes(), "big") == u2
    assert int.from_bytes(qyb[2].tobytes(), "big") == recs[2].pubkey[1]
    assert int.from_bytes(r0b[1].tobytes(), "big") == recs[1].r


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
    small = (1 << 100) + 7  # r + n < p: the +n candidate is admissible
    recs = [SigCheckRecord(pub, r, s, e), SigCheckRecord(pub, small, s, e)]
    _, _, _, _, q_inf, r0b, rnb, wrap_ok = pack_lanes(
        *records_to_blobs(recs), 3)
    assert wrap_ok[0] == (r + oracle.N < oracle.P)
    assert wrap_ok[1] == 1
    assert int.from_bytes(rnb[1].tobytes(), "big") == small + oracle.N
    assert int.from_bytes(r0b[1].tobytes(), "big") == small
    # the padded lane stays gated off
    assert not wrap_ok[2] and q_inf[2]


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
    """The bucket ladder: every bucket is >= n, a multiple of 1024 (the
    3D program's hard assert), and drawn from the bounded shape set."""
    allowed = {1024, 2048, 4096} | set(range(6144, 16385, 2048))
    for n in (8, 129, 1000, 1024, 1025, 2048, 2049, 4096, 4097, 6144,
              6145, 10000, 16384):
        b = _bucket_for(n)
        assert b >= n and b % 1024 == 0, (n, b)
        assert b in allowed, (n, b)
    assert len(allowed) == ecdsa_batch.PALLAS_SHAPE_BUDGET
    # beyond the split point: 16384-granular multiples
    for n in (16385, 30000, 32769):
        b = _bucket_for(n)
        assert b >= n and b % 16384 == 0, (n, b)


def test_pallas_programming_errors_are_not_swallowed(monkeypatch):
    """A NameError/AttributeError inside the Pallas path is a BUG, not a
    toolchain limitation — it must propagate, not degrade silently to the
    CPU fallback (regression: a refactor deleted a module constant and
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


# ---- the one supervised dispatch function, with stub kernels ---------------


def _mixed_lanes(n):
    """n records cycling over a few signed ones (valid, and one in three
    with a nudged message), with a wrap-around lane (r + n < p, so the
    +n candidate rides) and a range-invalid lane (s = 0: poisoned by the
    packer) among them."""
    base, _ = make_records(6, n_bad=2)
    recs = [base[i % len(base)] for i in range(n)]
    b = base[-1]
    recs[n // 2] = SigCheckRecord(b.pubkey, (1 << 90) + 11, b.s, b.msg_hash)
    if n > 1:
        recs[-1] = SigCheckRecord(b.pubkey, b.r, 0, b.msg_hash)
    return recs


@pytest.mark.parametrize("n", [1, 9, 200, 1022])
def test_records_and_blobs_take_the_same_dispatch(stub_verify_kernels, n):
    """dispatch_batch(records) and dispatch_packed(*records_to_blobs(
    records)) are one arrow: the kernel is handed identical arrays (KAT
    lanes, poison and padding included) and the verdicts are identical,
    and equal to the CPU engine's."""
    stub = stub_verify_kernels
    dispatch.reset()
    recs = _mixed_lanes(n)
    cpu = ecdsa_batch._verify_cpu(recs)
    # the stub answers with the CPU engine's verdicts + the two KAT lanes
    stub.verdicts = lambda arrays: np.concatenate(
        [cpu, [True, False], np.zeros(len(arrays[4]) - n - 2, bool)])
    by_records = ecdsa_batch.dispatch_batch(recs, backend="device").result()
    by_blobs = ecdsa_batch.dispatch_packed(
        *records_to_blobs(recs), backend="device").result()
    assert stub.rungs() == ["glv", "glv"]
    (_, a), (_, b) = stub.calls
    assert len(a) == len(b) == 8
    for x, y in zip(a, b):
        assert x.dtype == y.dtype == np.uint8 and np.array_equal(x, y)
    bucket = 1024
    assert a[0].shape == (bucket, 32) and a[4].shape == (bucket,)
    q_inf, wrap = a[4], a[7]
    assert q_inf[n:n + 2].tolist() == [0, 0] and q_inf[n + 2:].all()
    assert wrap[n // 2] == 1
    if n > 1:
        assert q_inf[n - 1] == 1  # s = 0: poisoned, never the ladder's call
    assert by_records.tolist() == by_blobs.tolist() == cpu.tolist()


@pytest.mark.parametrize("case", ["glv_fails", "both_fail", "w4_selected"])
def test_dispatch_rungs(stub_verify_kernels, case):
    """The ladder, rung by rung: GLV failed -> the w4 program once, in
    the same attempt, verdicts stand; both failed -> breaker charged,
    the CPU engine's verdicts, fault_fallback_sigs moved; w4 selected ->
    the GLV program is never called."""
    stub = stub_verify_kernels
    dispatch.reset()
    dispatch.configure(threshold=1, retries=0, cooldown=1e9, probe=0.0)
    recs, expected = make_records(4, n_bad=1)
    st = ecdsa_batch.STATS
    fb0, ff0, pf0 = st.glv_fallbacks, st.fault_fallback_sigs, \
        st.pallas_fallbacks
    kernel = "glv"
    if case == "w4_selected":
        kernel = "w4"
    else:
        stub.fail["glv"] = RuntimeError("transient sneeze")
    if case == "both_fail":
        stub.fail["w4"] = RuntimeError("transient sneeze")
    try:
        got = verify_batch(recs, backend="device", kernel=kernel)
        assert got.tolist() == expected
        br = dispatch.breaker("ecdsa").snapshot()
        if case == "glv_fails":
            assert stub.rungs() == ["glv", "w4"]
            assert st.glv_fallbacks == fb0 + 1
            assert st.fault_fallback_sigs == ff0
            assert br["state"] == "closed" and br["fallback_items"] == 0
        elif case == "both_fail":
            assert stub.rungs() == ["glv", "w4"]
            assert st.glv_fallbacks == fb0 + 1
            assert st.pallas_fallbacks == pf0 + 1
            assert st.fault_fallback_sigs == ff0 + len(recs)
            assert br["state"] == "open"
            assert br["fallback_items"] == len(recs)
        else:
            assert stub.rungs() == ["w4"]
            assert st.glv_fallbacks == fb0
            assert br["state"] == "closed"
        assert not ecdsa_batch._GLV_BROKEN and not ecdsa_batch._PALLAS_BROKEN
    finally:
        dispatch.reset()


def test_gettpuinfo_keeps_the_keys_the_benchmark_reads():
    """chipbench/checks.py::no_fallback and chip_smoke.py read these out
    of gettpuinfo; their names and meanings are not this layer's to
    change."""
    from types import SimpleNamespace

    from bitcoincashplus_tpu.rpc.control import gettpuinfo
    from bitcoincashplus_tpu.validation.sigcache import SignatureCache

    node = SimpleNamespace(backend="auto", sigcache=SignatureCache(),
                           chainstate=SimpleNamespace(bench={}))
    info = gettpuinfo(node, [])
    for key in ("sigs_verified", "cpu_fallback_sigs", "fault_fallback_sigs",
                "kat_failures", "pallas_fallbacks", "multisig_lanes"):
        assert isinstance(info["batch"][key], int), key
    ecdsa = info["ecdsa"]
    assert ecdsa["kernel"] in ecdsa["kernels"]
    assert ecdsa["glv_broken"] is False
    assert isinstance(ecdsa["glv_fallbacks"], int)
    dd = ecdsa["dev_decompose"]
    assert dd["broken"] is False and dd["enabled"] is True
    assert isinstance(dd["fallbacks"], int)
    assert isinstance(dd["dispatches"], int)
    for key in ("emit_s", "dispatch_s", "table_build_s"):
        assert isinstance(ecdsa[key], float), key
    # the trace's module name jit__glv_dev_program and the watch's name
    # are what the benchmark's layer metrics and shape check find
    assert ecdsa_batch._PW_GLV_DEV.name == "ecdsa_glv_decompose"
    assert ecdsa_batch._PW_W4_BYTES.name == "ecdsa_w4_bytes"
    for name, pw in info["device"]["programs"].items():
        for key in ("compiles", "shapes", "shape_budget",
                    "retraces_unexpected"):
            assert key in pw, (name, key)
    assert isinstance(info["breakers"], dict)
