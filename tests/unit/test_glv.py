"""GLV verification kernel tests (ISSUE 5).

Host-side suite (decomposition lattice, fixed-base comb tables) is
plain-fast. Kernel differentials run the GLV program at its floor bucket
(1024) — one XLA compile, persistent-cached (conftest) — against both
the w4 oracle kernel and the pure-CPU verifier, including the
adversarial edge corpus (wrap-claim lanes, k2=0 splits, λ-boundary
scalars, negative-half decompositions, u1=0, poisoned lanes). The 10k
random corpus differential is `slow`-marked like the other full kernel
differentials; the `glv` marker selects this suite (ordered with the
unit group by conftest).
"""

import hashlib
import random

import numpy as np
import pytest

from bitcoincashplus_tpu.crypto import secp256k1 as oracle
from bitcoincashplus_tpu.ops import ecdsa_batch
from bitcoincashplus_tpu.ops import secp256k1 as dev
from bitcoincashplus_tpu.script.interpreter import SigCheckRecord

rng = random.Random(1905)

pytestmark = pytest.mark.glv


def _recompose(k):
    s1, n1, s2, n2 = dev.glv_decompose(k)
    k1 = -s1 if n1 else s1
    k2 = -s2 if n2 else s2
    return (k1 + k2 * dev.LAMBDA) % oracle.N


def test_glv_constants():
    assert pow(dev.LAMBDA, 3, oracle.N) == 1 and dev.LAMBDA != 1
    assert pow(dev.BETA, 3, oracle.P) == 1 and dev.BETA != 1
    # φ(G) = λ·G — the endomorphism the kernel's λ streams rely on
    assert oracle.point_mul(dev.LAMBDA, oracle.G) == (
        dev.BETA * oracle.GX % oracle.P, oracle.GY)
    # the lattice basis annihilates λ mod n
    assert (dev._GLV_A1 - dev._GLV_MINUS_B1 * dev.LAMBDA) % oracle.N == 0
    assert (dev._GLV_A2 + dev._GLV_B2 * dev.LAMBDA) % oracle.N == 0


def test_glv_decompose_properties():
    cases = [0, 1, 2, oracle.N - 1, oracle.N - 2, dev.LAMBDA,
             dev.LAMBDA - 1, dev.LAMBDA + 1, oracle.N - dev.LAMBDA,
             oracle.N // 2, 1 << 128, (1 << 128) - 1, 1 << 255]
    cases += [rng.randrange(oracle.N) for _ in range(3000)]
    sign_combos = set()
    for k in cases:
        s1, n1, s2, n2 = dev.glv_decompose(k)
        assert s1 < (1 << 128) and s2 < (1 << 128), k
        assert _recompose(k) == k % oracle.N, k
        sign_combos.add((n1, n2))
    # the corpus must hit every sign quadrant (negative-half scalars)
    assert sign_combos == {(0, 0), (0, 1), (1, 0), (1, 1)}
    # k2 = 0 split: tiny scalars stay in the first lattice cell
    assert dev.glv_decompose(5) == (5, 0, 0, 0)


def test_glv_comb_tables():
    gx, gy, lx = dev._glv_comb()
    T = dev.GLV_COMB_TEETH
    assert gx.shape == gy.shape == lx.shape == (T, 512, dev.N_LIMBS)
    assert dev.GLV_TABLE_BUILD_S > 0.0  # build time surfaced (gettpuinfo)
    for i, d in ((0, 1), (0, 255), (4, 129), (T - 1, 7)):
        pt = oracle.point_mul(d * (1 << (8 * i)), oracle.G)
        assert dev.from_limbs_np(gx[i, d]) == pt[0]
        assert dev.from_limbs_np(gy[i, d]) == pt[1]
        # sign half: negated y, same x
        assert dev.from_limbs_np(gx[i, 256 + d]) == pt[0]
        assert dev.from_limbs_np(gy[i, 256 + d]) == oracle.P - pt[1]
        # λ stream: x mapped through β (φ leaves y alone)
        assert dev.from_limbs_np(lx[i, d]) == pt[0] * dev.BETA % oracle.P
    # d = 0 slots are the masked dummy (= d = 1), never garbage
    assert dev.from_limbs_np(gx[0, 0]) == oracle.GX
    # built once per process: same object back
    assert dev._glv_comb() is dev._glv_comb()


@pytest.mark.parametrize("lanes", [16, 200, 1024])
def test_glv_table_read_matches_numpy(lanes):
    """The ladder's per-lane table read alone (one form, chip and CPU):
    out of random stacked (16, 20, B) tables, with every window value
    0..15 among the lanes, it returns the three coordinates numpy
    indexing does."""
    import jax

    nprng = np.random.default_rng(lanes)
    table = tuple(
        nprng.integers(0, 1 << 32, (16, dev.N_LIMBS, lanes), dtype=np.uint32)
        for _ in range(3))
    w = np.arange(lanes, dtype=np.int32) % 16
    nprng.shuffle(w)
    got = jax.jit(dev._glv_tab_read)(table, w[None])
    for coord, read in zip(table, got):
        assert np.array_equal(np.asarray(read),
                              coord[w, :, np.arange(lanes)].T)


def test_kernel_selection_knob():
    old = ecdsa_batch._KERNEL
    try:
        assert ecdsa_batch.set_kernel("w4") == "w4"
        assert ecdsa_batch.active_kernel() == "w4"
        assert ecdsa_batch.set_kernel("glv") == "glv"
        with pytest.raises(ValueError, match="ecdsakernel"):
            ecdsa_batch.set_kernel("turbo9000")
        assert ecdsa_batch.active_kernel() == "glv"  # rejected = unchanged
    finally:
        ecdsa_batch._KERNEL = old


def test_node_rejects_unknown_kernel_at_startup(tmp_path):
    from bitcoincashplus_tpu.node.config import Config, ConfigError
    from bitcoincashplus_tpu.node.node import Node

    cfg = Config()
    cfg.args["datadir"] = [str(tmp_path)]
    cfg.args["regtest"] = ["1"]
    cfg.args["ecdsakernel"] = ["frobnicate"]
    old = ecdsa_batch._KERNEL
    try:
        with pytest.raises(ConfigError, match="frobnicate"):
            Node(config=cfg)
    finally:
        ecdsa_batch._KERNEL = old


def test_glv_failure_bookkeeping():
    """Programming errors in the GLV rung re-raise (no silent w4 green);
    toolchain errors latch, transients don't — mirror of the pallas
    bookkeeping invariant. One latch and one counter for the one rung."""
    before = ecdsa_batch.STATS.glv_fallbacks
    with pytest.raises(NameError):
        ecdsa_batch._note_glv_failure(NameError("name '_GONE' is not defined"))
    with pytest.raises(AttributeError):
        ecdsa_batch._note_glv_failure(
            AttributeError("module has no attribute '_GONE'"))
    old = ecdsa_batch._GLV_BROKEN
    try:
        ecdsa_batch._note_glv_failure(RuntimeError("transient sneeze"))
        assert ecdsa_batch.STATS.glv_fallbacks == before + 1
        assert not ecdsa_batch._GLV_BROKEN and ecdsa_batch.glv_enabled()
        ecdsa_batch._note_glv_failure(RuntimeError("Mosaic lowering died"))
        assert ecdsa_batch._GLV_BROKEN and not ecdsa_batch.glv_enabled()
        ecdsa_batch._GLV_BROKEN = False
        ecdsa_batch._note_glv_failure(
            RuntimeError("NotImplementedError: no lowering"))
        assert ecdsa_batch._GLV_BROKEN
        info = ecdsa_batch.kernel_info()
        assert info["glv_broken"] and info["dev_decompose"]["broken"]
        assert (info["glv_fallbacks"] == info["dev_decompose"]["fallbacks"]
                == before + 3)
    finally:
        ecdsa_batch._GLV_BROKEN = old


def _records_with_scalars(triples):
    """Forge valid signatures with CHOSEN verify scalars: given (u1, u2,
    q) with u2 != 0, R = u1·G + u2·Q determines r = R.x mod n, then
    s = r·u2⁻¹ and e = u1·s reproduce exactly (u1, u2) in the verifier —
    the λ-boundary / k2=0 / negative-half edges become directly
    constructible. Returns [(record, expected_bool)]."""
    out = []
    for u1, u2, q in triples:
        Q = oracle.point_mul(q, oracle.G)
        R = oracle.point_add(oracle.point_mul(u1, oracle.G),
                             oracle.point_mul(u2, Q))
        if R is None:
            continue
        r = R[0] % oracle.N
        if r == 0 or u2 % oracle.N == 0:
            continue
        s = r * pow(u2, oracle.N - 2, oracle.N) % oracle.N
        if s == 0:
            continue
        e = u1 * s % oracle.N
        rec = SigCheckRecord(Q, r, s, e)
        assert oracle.ecdsa_verify(Q, r, s, e)
        out.append((rec, True))
    return out


def _edge_corpus():
    """Adversarial edges: λ-boundary and k2 = 0 scalar splits, every sign
    quadrant, u1 = 0 (comb idle), tiny u2 (ladder nearly idle), bogus
    x-wraparound claims (rn lane + wrap_ok gate), and corrupt twins."""
    L = dev.LAMBDA
    n = oracle.N
    specials = [0, 1, 7, (1 << 128) - 1, L - 1, L, L + 1, n - L, n - 1,
                n // 2, 1 << 127]
    triples = []
    for u2 in specials:
        if u2 % n == 0:
            continue
        triples.append((rng.randrange(n), u2, rng.randrange(1, n)))
    for u1 in specials:
        triples.append((u1, rng.randrange(1, n), rng.randrange(1, n)))
    recs = _records_with_scalars(triples)
    # corrupt twins: same lanes, message nudged -> must be False everywhere
    bad = [(SigCheckRecord(r.pubkey, r.r, r.s, (r.msg_hash + 1) % n), False)
           for r, _ in recs[::3]]
    # bogus wraparound claim: tiny r with wrap_ok admissible — the rn
    # candidate lane is exercised and must still reject
    base = recs[0][0]
    bad.append((SigCheckRecord(base.pubkey, 5, base.s, base.msg_hash),
                False))
    # a corrupt r, another key's signature, and two lanes the packer
    # poisons (s = 0 and s = n are out of range: q_inf, never a verdict
    # of the ladder's)
    other = recs[1][0]
    bad.append((SigCheckRecord(base.pubkey, (base.r + 1) % n or 1, base.s,
                               base.msg_hash), False))
    bad.append((SigCheckRecord(other.pubkey, base.r, base.s,
                               base.msg_hash), False))
    bad.append((SigCheckRecord(base.pubkey, base.r, 0, base.msg_hash),
                False))
    bad.append((SigCheckRecord(base.pubkey, base.r, n, base.msg_hash),
                False))
    return recs + bad


def _cpu_verdicts(records):
    return [oracle.ecdsa_verify(r.pubkey, r.r, r.s, r.msg_hash)
            for r in records]


def test_glv_kernel_edge_differential():
    """ALWAYS runs (tier-1): the GLV kernel over the adversarial edge
    corpus, bit-identical to the CPU verifier. One bucket-1024 compile,
    persistent-cached."""
    pairs = _edge_corpus()
    records = [r for r, _ in pairs]
    expected = _cpu_verdicts(records)
    assert expected == [e for _, e in pairs]
    got = ecdsa_batch.verify_batch(records, backend="device", kernel="glv")
    assert got.tolist() == expected
    assert ecdsa_batch.STATS.glv_dispatches >= 1


def _stage_corpus():
    """Lanes from a generator of their own (the module's is consumed in
    test order, and PLANES_SHA256 below is of these): verify scalars whose
    lattice split puts the two Q-stream signs in every quadrant, three
    lanes each, so the table's y-select negates and leaves alone behind
    both folds of qy; u2 of one nonzero window and of one nonzero bit (the
    ladder adds nothing in the other windows), and the edge corpus' other
    kinds: nudged twins, out-of-range s and r (the packer's infinity
    lanes, as its padding is)."""
    r = random.Random(39)
    n = oracle.N
    triples, seen = [], {}
    while len(triples) < 12:
        u2 = r.randrange(1, n)
        _, n1, _, n2 = dev.glv_decompose(u2)
        if seen.setdefault((n1, n2), 0) < 3:
            seen[(n1, n2)] += 1
            triples.append((r.randrange(n), u2, r.randrange(1, n)))
    for u2 in (1, 15, 1 << 64, 9 << 124, (1 << 128) - 1, dev.LAMBDA,
               n - dev.LAMBDA):
        triples.append((r.randrange(n), u2, r.randrange(1, n)))
    recs = _records_with_scalars(triples)
    base = recs[0][0]
    bad = [(SigCheckRecord(q.pubkey, q.r, q.s, (q.msg_hash + 1) % n), False)
           for q, _ in recs[::2]]
    bad += [(SigCheckRecord(base.pubkey, base.r, s, base.msg_hash), False)
            for s in (0, n)]
    bad.append((SigCheckRecord(base.pubkey, 0, base.s, base.msg_hash),
                False))
    return recs + bad


# sha256 of the (2, 1024) uint32 planes the single program of PRs 30-38
# gave for _stage_corpus()'s lanes (computed with that program, PR 39): the
# two stages give the same bits
PLANES_SHA256 = "d215287f9aff24b911e12036af735580fba6ba855f06a99e3da0a30cbf026f47"


@pytest.mark.parametrize("corpus", ["edge", "stages"])
def test_glv_planes_differential(corpus):
    """The raw (ok, degen) planes of the two stages, composed as the
    dispatch composes them, over a bucket of 1,024 lanes: every lane
    settled (no degenerate flag), `ok` equal to the CPU verifier's verdict,
    padding and out-of-range lanes False; on the fixed lanes of
    _stage_corpus, bit for bit what the single program gave."""
    pairs = _edge_corpus() if corpus == "edge" else _stage_corpus()
    records = [r for r, _ in pairs]
    expected = _cpu_verdicts(records)
    assert expected == [e for _, e in pairs]
    if corpus == "stages":
        # u2 = r / s is the scalar of the two Q streams
        quadrants = {dev.glv_decompose(
            r.r * pow(r.s, oracle.N - 2, oracle.N) % oracle.N)[1::2]
            for r in records[:12]}
        assert quadrants == {(0, 0), (0, 1), (1, 0), (1, 1)}
    lanes = 1024
    arrays = ecdsa_batch.pack_lanes(
        *ecdsa_batch.records_to_blobs(records), lanes)
    planes = np.asarray(dev._glv_dev_planes(*arrays))
    assert planes.shape == (2, lanes) and planes.dtype == np.uint32
    assert not planes[1].any()
    assert planes[0, :len(records)].astype(bool).tolist() == expected
    assert not planes[0, len(records):].any()
    if corpus == "stages":
        assert hashlib.sha256(planes.tobytes()).hexdigest() == PLANES_SHA256


def test_glv_two_stages_are_one_watched_dispatch():
    """A bucket is two programs on the device and ONE dispatch to the
    watch: `gettpuinfo.device.programs` keeps its one GLV entry, whose
    `dispatches` counts buckets and whose one shape is the bucket."""
    from types import SimpleNamespace

    from bitcoincashplus_tpu.rpc.control import gettpuinfo
    from bitcoincashplus_tpu.util import devicewatch as dw
    from bitcoincashplus_tpu.validation.sigcache import SignatureCache

    # the handle the dispatch leg holds (see the sentinel test below)
    pw = ecdsa_batch._PW_GLV_DEV
    records = [r for r, _ in _stage_corpus()[:9]]
    before = pw.snapshot()["dispatches"]
    for fill in (2, 5, 9):
        got = ecdsa_batch.verify_batch(records[:fill], backend="device",
                                       kernel="glv")
        assert got.all()
    snap = pw.snapshot()
    assert snap["dispatches"] == before + 3
    assert snap["retraces_unexpected"] == 0
    node = SimpleNamespace(backend="auto", sigcache=SignatureCache(),
                           chainstate=SimpleNamespace(bench={}))
    programs = gettpuinfo(node, [])["device"]["programs"]
    # one entry a lane kind (ECDSA's, and since PR 44 the Schnorr
    # bucket's): the prepare stage has no entry of its own
    assert {n for n in programs if n.startswith("ecdsa_glv")} <= {
        pw.name, ecdsa_batch._PW_GLV_SCHNORR.name}
    if dw.program(pw.name) is pw:
        assert programs[pw.name]["dispatches"] == snap["dispatches"]


@pytest.mark.parametrize("site", [ecdsa_batch.GLV_SITE,
                                  ecdsa_batch.GLV_DEV_SITE])
@pytest.mark.parametrize("mode", ["fail-always", "poison-output"])
def test_glv_fallback_drill(fault_harness, site, mode):
    """Dispatch-breaker drill, either site name aimed at the one GLV rung:
    a failed GLV kernel degrades glv -> w4 in the same attempt with
    verdict parity and a metered fallback; a poisoned one is caught by the
    riding KAT lanes at settle and the verdict is a fresh CPU
    re-verification."""
    pairs = _edge_corpus()[:10]
    records = [r for r, _ in pairs]
    expected = _cpu_verdicts(records)
    fault_harness(mode, ops=site)
    fb0 = ecdsa_batch.STATS.glv_fallbacks
    glv0 = ecdsa_batch.STATS.glv_dispatches
    kat0 = ecdsa_batch.STATS.kat_failures
    ff0 = ecdsa_batch.STATS.fault_fallback_sigs
    w4_0 = ecdsa_batch._PW_W4_BYTES.snapshot()["dispatches"]
    got = ecdsa_batch.verify_batch(records, backend="device", kernel="glv")
    assert got.tolist() == expected
    w4_calls = ecdsa_batch._PW_W4_BYTES.snapshot()["dispatches"] - w4_0
    if mode == "fail-always":
        assert ecdsa_batch.STATS.glv_fallbacks == fb0 + 1
        assert ecdsa_batch.STATS.glv_dispatches == glv0
        assert w4_calls == 1
        assert ecdsa_batch.STATS.fault_fallback_sigs == ff0
    else:
        assert ecdsa_batch.STATS.glv_fallbacks == fb0
        assert ecdsa_batch.STATS.glv_dispatches == glv0 + 1
        assert w4_calls == 0
        assert ecdsa_batch.STATS.kat_failures == kat0 + 1
        assert ecdsa_batch.STATS.fault_fallback_sigs >= ff0 + len(records)


@pytest.mark.slow
def test_glv_differential_corpus_10k():
    """The 10k random + adversarial corpus: GLV vs the w4 oracle kernel
    vs the CPU verifier, bit-identical verdicts (acceptance criterion)."""
    from bitcoincashplus_tpu import native

    distinct = []
    sign = native.ecdsa_sign if native.available() else oracle.ecdsa_sign
    for i in range(128):
        d = rng.randrange(1, oracle.N)
        pub = oracle.point_mul(d, oracle.G)
        e = rng.getrandbits(256)
        r, s = sign(d, e)
        if i % 5 == 4:
            e ^= 0xFF  # invalid lanes ride along
        distinct.append(SigCheckRecord(pub, r, s, e))
    edge = [r for r, _ in _edge_corpus()]
    records = [distinct[i % len(distinct)] for i in range(10238 - len(edge))]
    records += edge
    if native.available():
        cpu = list(native.ecdsa_verify_batch(records))
    else:
        cpu = _cpu_verdicts(records)
    glv = ecdsa_batch.verify_batch(records, backend="device", kernel="glv")
    w4 = ecdsa_batch.verify_batch(records, backend="device", kernel="w4")
    assert glv.tolist() == cpu
    assert w4.tolist() == cpu


@pytest.mark.slow
def test_glv_sharded_differential():
    """The GLV program sharded over the 8-chip virtual mesh (parallel/
    sig_shard) agrees with the CPU verifier."""
    from bitcoincashplus_tpu.parallel.sig_shard import verify_batch_sharded

    pairs = _edge_corpus()[:12]
    records = [r for r, _ in pairs]
    expected = _cpu_verdicts(records)
    got = verify_batch_sharded(records, 8, kernel="glv")
    assert got.tolist() == expected


# ---- device-side decomposition (ISSUE 11) ----------------------------------


def _decompose_edge_scalars():
    """Crafted decompose inputs: λ-boundary, k2 = 0 (tiny scalars), u1 = 0,
    max-limb carry patterns (all-ones limbs ripple end to end in the limb
    normalizers), and enough random mass to hit every sign quadrant."""
    n = oracle.N
    specials = [0, 1, 5, 7, dev.LAMBDA - 1, dev.LAMBDA, dev.LAMBDA + 1,
                n - dev.LAMBDA, n - 1, n - 2, n // 2, (1 << 128) - 1,
                1 << 127, 1 << 128, (1 << 255) % n,
                int("1fff" * 16, 16) % n,       # all-ones 13-bit limbs
                int("ffff" * 16, 16),           # all-ones 16-bit limbs
                ((1 << 256) - 1) % n]
    specials += [rng.randrange(n) for _ in range(64)]
    return specials


def _scalar_bytes(ks):
    return np.frombuffer(
        b"".join(k.to_bytes(32, "big") for k in ks), np.uint8
    ).reshape(len(ks), 32)


def test_device_decompose_differential():
    """The in-kernel device split is bit-identical to the glv_decompose
    Python-int oracle over the crafted edge corpus — exact rounding, not
    estimate-grade."""
    ks = _decompose_edge_scalars()[:32]
    m1, n1, m2, n2 = dev.glv_decompose_device_batch(_scalar_bytes(ks))
    for i, k in enumerate(ks):
        s1, e1, s2, e2 = dev.glv_decompose(k)
        got = (int.from_bytes(m1[i].tobytes(), "little"), int(n1[i]),
               int.from_bytes(m2[i].tobytes(), "little"), int(n2[i]))
        assert got == (s1, e1, s2, e2), hex(k)


def test_glv_dev_retrace_sentinel_and_packer():
    """devicewatch acceptance: >= 3 decompose-program dispatches at
    DISTINCT batch fills stay inside the declared shape budget with
    retraces_unexpected == 0 (the fills share the 1024 bucket — that IS
    the bounded-shape design); one of them rides the cross-block
    LanePacker so the aggregation layer provably feeds the fused
    program."""
    # the handle the dispatch leg holds, not dw.program(name): a
    # dw.reset() by a suite earlier in this worker mints a fresh watch
    # under the same name, and the leg's counts stay on the old one
    pw = ecdsa_batch._PW_GLV_DEV
    assert pw.name == "ecdsa_glv_decompose"
    d0 = pw.snapshot()["dispatches"]
    dev0 = ecdsa_batch.STATS.glv_dispatches
    emit0 = ecdsa_batch.STATS.glv_emit_s

    fills = (6, 40, 90)
    pairs = _edge_corpus()
    records = [r for r, _ in pairs]
    expected = _cpu_verdicts(records)
    for i, fill in enumerate(fills):
        recs = [records[j % len(records)] for j in range(fill)]
        exp = [expected[j % len(records)] for j in range(fill)]
        if i == 1:
            packer = ecdsa_batch.LanePacker(backend="device", lanes=fill,
                                            kernel="glv")
            fut = packer.add(recs)
            packer.flush()
            got = fut.result()
        else:
            got = ecdsa_batch.verify_batch(recs, backend="device",
                                           kernel="glv")
        assert got.tolist() == exp, fill

    snap = pw.snapshot()
    assert snap["dispatches"] >= d0 + 3
    assert snap["retraces_unexpected"] == 0
    assert snap["shape_budget"] == ecdsa_batch.PALLAS_SHAPE_BUDGET
    assert snap["shapes"] <= snap["shape_budget"]
    assert ecdsa_batch.STATS.glv_dispatches >= dev0 + 3
    # the host pays byte EMISSION only
    assert ecdsa_batch.STATS.glv_emit_s > emit0
    info = ecdsa_batch.kernel_info()
    assert info["dev_decompose"]["enabled"]
    assert info["dev_decompose"]["dispatches"] >= 3
    for key in ("emit_s", "dispatch_s"):
        assert key in info
