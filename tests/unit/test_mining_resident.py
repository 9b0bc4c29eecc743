"""Device-resident mining loop + chunk-2 midstate hoisting (ISSUE 10).

Covers: hoisted-vs-unhoisted bit-identity against the CPU oracle, the
2^32 tile-accounting clamp, resident-loop rollover/template-refresh
semantics, the devicewatch retrace sentinel staying quiet across buffer
swaps, the regtest-CPU scalar fast path, knob validation, and the
bcp_mining_* telemetry families. ``mining`` marker: conftest orders this
suite after devprof and before serving.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bitcoincashplus_tpu.crypto.hashes import (
    chunk2_round_state,
    header_midstate,
    sha256d,
)
from bitcoincashplus_tpu.ops import miner
from bitcoincashplus_tpu.ops import sha256 as gen_sha
from bitcoincashplus_tpu.ops.sha256 import bytes_to_words_np
from bitcoincashplus_tpu.ops.sha256_sweep import (
    hoist_template,
    sweep_digest_hoisted,
    sweep_h7_hoisted,
    sweep_header_fast,
)
from bitcoincashplus_tpu.mining.resident import ResidentSweep

pytestmark = pytest.mark.mining

EASY = 0x7FFFFF << (8 * 29)  # regtest-grade target


def _parts(header80):
    mid = np.array(header_midstate(header80), dtype=np.uint32)
    tail = bytes_to_words_np(np.frombuffer(header80[64:76], dtype=np.uint8))
    return list(mid), list(tail)


def _oracle_digest_words(header80, nonce):
    dig = sha256d(header80[:76] + int(nonce).to_bytes(4, "little"))
    return [int.from_bytes(dig[4 * j:4 * j + 4], "big") for j in range(8)]


def _first_hit_from(header80, target, start, budget):
    """Scalar oracle over the resident sweep order (rollover wrap)."""
    for i in range(budget):
        n = (start + i) & 0xFFFFFFFF
        hdr = header80[:76] + n.to_bytes(4, "little")
        if int.from_bytes(sha256d(hdr), "little") <= target:
            return n
    return None


# ---------------------------------------------------------------------------
# Chunk-2 hoist correctness
# ---------------------------------------------------------------------------

def test_hoist_state_matches_cpu_oracle():
    """The hoisted early-round state (chunk-2 rounds 0..2) is pinned
    bit-exactly against the pure-Python oracle."""
    rng = np.random.default_rng(11)
    for _ in range(8):
        header = rng.integers(0, 256, size=80, dtype=np.uint8).tobytes()
        mid, tail = _parts(header)
        pre = hoist_template(mid, tail)
        got = tuple(int(x) for x in pre["st3"])
        exp = chunk2_round_state(header_midstate(header), header[64:76])
        assert got == exp


def test_hoisted_digests_bit_identical():
    """Randomized 80-byte headers: hoisted full-digest and h7 kernels are
    bit-identical to BOTH the hashlib oracle and the unhoisted generic
    sweep digest (ops/sha256.header_sweep_digest). Eager on purpose: the
    subject is the unrolled form's arithmetic, which XLA:CPU cannot run
    jitted (ops/miner._sweep_tile)."""
    rng = np.random.default_rng(12)
    with jax.disable_jit():
        for _ in range(4):
            header = rng.integers(0, 256, size=80, dtype=np.uint8).tobytes()
            mid, tail = _parts(header)
            nonces = rng.integers(0, 2**32, size=32, dtype=np.uint32)
            pre = hoist_template(mid, tail)
            h8 = [np.asarray(x)
                  for x in sweep_digest_hoisted(pre, jnp.asarray(nonces))]
            h7 = np.asarray(sweep_h7_hoisted(pre, jnp.asarray(nonces)))
            un8 = [np.asarray(x) for x in gen_sha.header_sweep_digest(
                [np.uint32(m) for m in mid], [np.uint32(t) for t in tail],
                jnp.asarray(nonces))]
            for i, n in enumerate(nonces):
                exp = _oracle_digest_words(header, n)
                assert [int(h8[j][i]) for j in range(8)] == exp
                assert [int(un8[j][i]) for j in range(8)] == exp
                assert int(h7[i]) == exp[7]


def test_hoisted_hits_identical_nonces():
    """The jitted sweeps find hits at the same nonces as the scalar CPU
    reference loop (sweep_header_cpu) — generic and h7 paths."""
    header = b"\xab" * 80
    n_cpu, _ = miner.sweep_header_cpu(header, EASY, max_nonces=1 << 10)
    n_gen, _ = miner.sweep_header(header, EASY, max_nonces=1 << 10,
                                  tile=1 << 7)
    n_fast, _ = sweep_header_fast(header, EASY, max_nonces=1 << 10,
                                  tile=1 << 7)
    assert n_cpu is not None
    assert n_gen == n_cpu
    assert n_fast == n_cpu


# ---------------------------------------------------------------------------
# Satellite: 2^32 boundary tile clamp
# ---------------------------------------------------------------------------

def test_boundary_tile_clamp_math():
    t = 1 << 16
    # plenty of space: clamp is the max_nonces ceiling
    assert miner._boundary_tiles(0, 1 << 20, t) == (1 << 20) // t
    # near the top: space wins over max_nonces
    start = (1 << 32) - 3 * t
    assert miner._boundary_tiles(start, 1 << 32, t) == 3
    # unaligned start: ceil of the remaining space
    start = (1 << 32) - 3 * t - 7
    assert miner._boundary_tiles(start, 1 << 32, t) == 4


def test_sweep_header_clamps_at_boundary():
    """A sweep starting near the top of the nonce space must stop at
    2^32 — no wrap into (re-hashing of) low nonces, and the attempted-
    hash count is bounded by the remaining space."""
    header = b"\xab" * 80
    tile = 1 << 7
    start = (1 << 32) - 4 * tile
    space = (1 << 32) - start
    # impossible target: full clamped sweep, honest accounting
    nonce, hashes = miner.sweep_header(header, 0, start_nonce=start,
                                       max_nonces=1 << 32, tile=tile)
    assert nonce is None
    assert hashes <= space
    # the fast path clamps identically
    nonce_f, hashes_f = sweep_header_fast(header, 0, start_nonce=start,
                                          max_nonces=1 << 32, tile=tile)
    assert nonce_f is None
    assert hashes_f <= space
    # a hit that exists only BELOW the start (i.e. past the wrap) must
    # NOT be found by the clamped per-dispatch sweep
    low_hit = _first_hit_from(header, EASY, 0, 1 << 10)
    assert low_hit is not None and low_hit < start
    nonce, _ = miner.sweep_header(header, EASY, start_nonce=start,
                                  max_nonces=1 << 32, tile=tile)
    if nonce is not None:  # a hit inside [start, 2^32) is legitimate
        assert nonce >= start


# ---------------------------------------------------------------------------
# Resident loop semantics
# ---------------------------------------------------------------------------

@pytest.fixture
def resident():
    rs = ResidentSweep(tile=1 << 9, seg_tiles=2, inflight=2, kernel="exact")
    yield rs
    rs.close()


def test_resident_matches_cpu_oracle(resident):
    header = b"\xab" * 80
    n, hashes = resident.sweep(header, EASY, max_nonces=1 << 13)
    n_cpu, _ = miner.sweep_header_cpu(header, EASY, max_nonces=1 << 13)
    assert n == n_cpu and hashes >= 1


def test_resident_h7_matches_cpu_oracle():
    rs = ResidentSweep(tile=1 << 9, seg_tiles=2, inflight=2, kernel="h7")
    try:
        header = b"\xcd" * 80
        n, _ = rs.sweep(header, EASY, max_nonces=1 << 13)
        n_cpu, _ = miner.sweep_header_cpu(header, EASY, max_nonces=1 << 13)
        assert n == n_cpu
    finally:
        rs.close()


def test_resident_rollover_wrap_hit(resident):
    """A sweep crossing 2^32 rolls over on-loop and finds the first hit
    in wrap order — identical to the scalar oracle's uint32 semantics."""
    header = b"\xab" * 80
    start = (1 << 32) - (1 << 10)
    n, _ = resident.sweep(header, EASY, start_nonce=start,
                          max_nonces=1 << 13)
    assert n == _first_hit_from(header, EASY, start, 1 << 13)
    assert resident.passes >= 1
    assert resident.snapshot()["rollover_passes"] >= 1


def test_template_refresh_mid_sweep(resident):
    """In-flight segments of the OLD template are discarded at a refresh
    and the hit comes from the NEW template (the buffer-swap path)."""
    header_a, header_b = b"\x11" * 80, b"\x22" * 80
    resident.set_template(header_a, 0)          # impossible target
    resident._pump(1 << 12)                     # segments in flight for A
    assert len(resident._segments) > 0
    swaps_before = resident.buffer_swaps
    n, _ = resident.sweep(header_b, EASY, max_nonces=1 << 13)
    n_cpu, _ = miner.sweep_header_cpu(header_b, EASY, max_nonces=1 << 13)
    assert n == n_cpu                           # hit from the NEW template
    assert resident.buffer_swaps == swaps_before + 1
    assert resident.segments_discarded > 0


def test_resident_fifo_poll_surface(resident):
    """advance()/take_hits(): the host polls a bounded FIFO instead of
    blocking on (found, nonce, tiles)."""
    resident.set_template(b"\x33" * 80, 1 << 250)  # several hits expected
    parked = resident.advance(1 << 13)
    assert parked >= 1
    assert resident.snapshot()["fifo_depth"] == parked
    hits = resident.take_hits()
    assert len(hits) == parked
    gen = resident.generation
    for h in hits:
        assert h["generation"] == gen
        hdr = b"\x33" * 76 + h["nonce"].to_bytes(4, "little")
        assert int.from_bytes(sha256d(hdr), "little") <= (1 << 250)
    assert resident.snapshot()["fifo_depth"] == 0


def test_advance_resumes_past_false_positive():
    """advance() must not drop the unsearched remainder of a segment
    after an h7 false positive: the cursor already moved past the whole
    segment at dispatch time, so the loop resumes synchronously (as
    sweep() does) and a REAL hit later in the same segment is still
    parked in the FIFO."""
    header = b"\x66" * 80
    target = 1 << 250
    real = [n for n in range(1 << 11)
            if int.from_bytes(
                sha256d(header[:76] + n.to_bytes(4, "little")),
                "little") <= target]
    assert len(real) >= 2
    rs = ResidentSweep(tile=1 << 10, seg_tiles=2, inflight=1, kernel="h7")
    try:
        true_confirm = rs._confirm
        rejected = []

        def confirm(nonce):
            # simulate the ~2^-32 limb7 tie on the first real hit
            if nonce == real[0] and not rejected:
                rejected.append(nonce)
                return False
            return true_confirm(nonce)

        rs._confirm = confirm
        rs.set_template(header, target)
        parked = rs.advance(1 << 11)
        got = [h["nonce"] for h in rs.take_hits()]
        assert rejected, "the planted false positive never fired"
        assert rs.false_positives >= 1
        assert real[0] not in got
        assert real[1] in got   # resumed remainder found the next hit
        assert parked == len(got)
    finally:
        rs.close()


def test_template_swaps_do_not_retrace():
    """>= 3 template refreshes re-dispatch the SAME compiled shape: the
    devicewatch retrace sentinel stays quiet and the shape count is flat
    (the swap is a buffer swap, not a recompile)."""
    from bitcoincashplus_tpu.mining.resident import PROGRAM
    from bitcoincashplus_tpu.util import devicewatch as dw

    rs = ResidentSweep(tile=1 << 9, seg_tiles=2, inflight=2, kernel="exact")
    try:
        rs.sweep(b"\x41" * 80, EASY, max_nonces=1 << 11)
        snap = dw.program(PROGRAM).snapshot()
        shapes_after_first = snap["shapes"]
        retraces_before = snap["retraces_unexpected"]
        for fill in (0x42, 0x43, 0x44):
            rs.sweep(bytes([fill]) * 80, EASY, max_nonces=1 << 11)
        snap = dw.program(PROGRAM).snapshot()
        assert rs.buffer_swaps >= 4
        assert snap["shapes"] == shapes_after_first
        assert snap["retraces_unexpected"] == retraces_before
    finally:
        rs.close()


def test_supervised_resident_degrades_to_scalar(fault_harness):
    """The resident loop rides the miner breaker: a dead device path
    degrades to the scalar host sweep with an identical hit."""
    from bitcoincashplus_tpu.ops import dispatch

    fault_harness("fail-always", ops="miner")
    rs = ResidentSweep(tile=1 << 9, seg_tiles=2, inflight=2, kernel="exact")
    try:
        sweep = dispatch.supervised_resident_sweep(rs)
        header = b"\xab" * 80
        n, _ = sweep(header, EASY, max_nonces=1 << 12)
        n_cpu, _ = miner.sweep_header_cpu(header, EASY, max_nonces=1 << 12)
        assert n == n_cpu
        assert dispatch.breaker("miner").fallback_calls >= 1
        assert rs.polls == 0  # the resident loop itself never ran
    finally:
        rs.close()


def test_mining_telemetry_families():
    """bcp_mining_* native families exist with correct TYPEs and count
    resident activity."""
    from bitcoincashplus_tpu.util import telemetry

    rs = ResidentSweep(tile=1 << 9, seg_tiles=2, inflight=2, kernel="exact")
    try:
        rs.sweep(b"\x55" * 80, EASY, max_nonces=1 << 12)
    finally:
        rs.close()
    fams = telemetry.REGISTRY.snapshot()
    assert fams["bcp_mining_tiles_swept_total"]["type"] == "counter"
    assert fams["bcp_mining_template_swaps_total"]["type"] == "counter"
    assert fams["bcp_mining_candidates_total"]["type"] == "counter"
    assert fams["bcp_mining_fifo_depth"]["type"] == "gauge"
    assert fams["bcp_mining_poll_seconds"]["type"] == "histogram"
    tiles = sum(v["value"]
                for v in fams["bcp_mining_tiles_swept_total"]["values"])
    assert tiles >= 1


# ---------------------------------------------------------------------------
# Node wiring: engine selection, knob validation, gettpuinfo section
# ---------------------------------------------------------------------------

def _mk_node(tmp_path, **args):
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    cfg = Config()
    cfg.args["datadir"] = [str(tmp_path)]
    cfg.args["regtest"] = ["1"]
    for k, v in args.items():
        cfg.args[k] = [str(v)]
    return Node(config=cfg)


def test_regtest_cpu_keeps_scalar_fastpath(tmp_path):
    """Regtest CPU nodes keep the PR 7 ~1 ms/block scalar host sweep —
    the resident loop must NOT replace the trivial-target fast path."""
    node = _mk_node(tmp_path / "scalar")
    try:
        spk = bytes.fromhex("76a914") + b"\x11" * 20 + bytes.fromhex("88ac")
        hashes = node.generate_to_script(spk, 2)
        assert len(hashes) == 2
        assert node.sweep_engine == "scalar-host"
        assert node.resident_miner is None
        snap = node.mining_snapshot()
        assert snap["engine"] == "scalar-host"
        assert snap["resident"] is False
    finally:
        node.close()


def test_residentminer_force_engages_loop(tmp_path):
    node = _mk_node(tmp_path / "force", residentminer="force")
    try:
        spk = bytes.fromhex("76a914") + b"\x11" * 20 + bytes.fromhex("88ac")
        hashes = node.generate_to_script(spk, 2)
        assert len(hashes) == 2
        assert node.sweep_engine == "resident-exact"
        snap = node.mining_snapshot()
        assert snap["resident"] is True
        assert snap["template_generation"] >= 2   # one swap per extranonce
        assert snap["hits"] >= 2
        # the registry projection exports the state gauges
        from bitcoincashplus_tpu.util import telemetry

        fams = telemetry.REGISTRY.snapshot()
        assert fams["bcp_mining_state_tiles_swept"]["type"] == "gauge"
    finally:
        node.close()


def test_testnet_cpu_node_mines_with_resident_exact(tmp_path):
    """Outside regtest a node on the CPU backend picks the resident loop
    with the exact kernel (ops/miner.sweep_jit), and that choice mines:
    one jitted segment over a trivial target returns the scalar loop's
    nonce. (A real testnet target is 2^32 hashes away.)"""
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    cfg = Config()
    cfg.args.update({"datadir": [str(tmp_path)], "testnet": ["1"],
                     "listen": ["0"], "connect": ["0"], "dnsseed": ["0"]})
    node = Node(config=cfg)
    try:
        assert node.params.network == "test"
        sweep = node._select_sweep()
        assert node.sweep_engine == "resident-exact"
        assert node.resident_miner.kernel == "exact"
        header = b"\x5a" * 80
        for target in ((1 << 255) - 1, EASY):
            n, hashes = sweep(header, target, max_nonces=1 << 15)
            n_cpu, _ = miner.sweep_header_cpu(header, target,
                                              max_nonces=1 << 15)
            assert n is not None and n == n_cpu
            assert hashes >= node.resident_miner.tile
        assert node.resident_miner.polls >= 2   # the loop itself ran
        assert node.resident_miner.hits == 2
    finally:
        node.close()


def test_residentminer_knob_validation(tmp_path):
    from bitcoincashplus_tpu.node.config import ConfigError

    with pytest.raises(ConfigError):
        _mk_node(tmp_path / "bad", residentminer="sideways")
