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
        compiled = miner.sweep_jit._cache_size()
        # a segment's length is a traced argument like the template: the
        # lengths the sizing rule moves through are one compiled shape
        for fill, rs.seg_tiles in ((0x42, 1), (0x43, 4), (0x44, 64)):
            rs.sweep(bytes([fill]) * 80, EASY, max_nonces=1 << 11)
            rs.sweep(bytes([fill]) * 80, 0, max_nonces=1 << 11)
        snap = dw.program(PROGRAM).snapshot()
        assert rs.buffer_swaps >= 7
        assert snap["shapes"] == shapes_after_first
        assert snap["retraces_unexpected"] == retraces_before
        assert miner.sweep_jit._cache_size() == compiled
    finally:
        rs.close()


# ---------------------------------------------------------------------------
# ISSUE 26: segments sized by device time, one dispatch and one fetch each
# ---------------------------------------------------------------------------

class _Pending:
    """What the stub kernel hands back: readable only through the fetch."""

    def __init__(self, ready, value):
        self.ready, self.value = ready, value


class _FakeChip:
    """A stub kernel and clock. The device runs segments in order at
    ``per_tile`` seconds a tile; each dispatch and each fetch costs the
    host ``host_s``, and ``stall`` maps a fetch's index to extra seconds
    the host loses before it (a hiccup). Never finds a nonce."""

    def __init__(self, per_tile, host_s, stall=None):
        self.per_tile, self.host_s, self.stall = per_tile, host_s, stall or {}
        self.t, self.free = 1.0, 0.0
        self.calls, self.fetches = [], 0

    def now(self):
        return self.t

    def kernel(self, mid, tail, tgt, start, n_tiles, tile):
        self.t += self.host_s
        self.free = max(self.t, self.free) + int(n_tiles) * self.per_tile
        self.calls.append((int(start), int(n_tiles)))
        return _Pending(self.free, (False, 0, int(n_tiles)))

    def fetch(self, out):
        self.t += self.stall.get(self.fetches, 0.0)
        self.fetches += 1
        self.t = max(self.t + self.host_s, out.ready)
        return out.value

    def drive(self, monkeypatch, **kw):
        from bitcoincashplus_tpu.mining import resident

        rs = ResidentSweep(tile=1 << 16, kernel="h7", **kw)
        rs._jitfn = lambda: self.kernel
        rs._fetch = self.fetch
        monkeypatch.setattr(resident, "_now", self.now)
        return rs

    def longest_s(self):
        return max(n for _, n in self.calls) * self.per_tile


V5E_TILE_S = 1e-4      # 2^16 nonces at 0.6565 GH/s (ledger, PR 25)
HOST_TRIP_S = 1.25e-3  # half of the ~2.5 ms a segment cost the host there
CALL = 1 << 29         # the benchmark's maxtries


def test_host_bound_loop_grows_to_the_ceiling(monkeypatch):
    """A chip-like device under a host that needs three kernel-times a
    segment: the length doubles until one segment is ~51 ms, every nonce
    of the budget is dispatched once and in order, and the next call
    starts at the learned length."""
    from bitcoincashplus_tpu.mining.resident import (
        SEG_CEILING_S, SEG_TILES_START)

    chip = _FakeChip(V5E_TILE_S, HOST_TRIP_S)
    rs = chip.drive(monkeypatch)
    assert rs.seg_tiles == SEG_TILES_START
    assert rs.sweep(b"\x71" * 80, 0, max_nonces=CALL) == (None, CALL)
    assert rs.seg_tiles == 512
    assert SEG_CEILING_S / 2 < chip.longest_s() <= SEG_CEILING_S
    assert sum(n for _, n in chip.calls) == CALL >> 16
    cursor = 0
    for start, n in chip.calls:
        assert start == cursor
        cursor += n << 16
    snap = rs.snapshot()
    assert snap["seg_tiles"] == 512 and snap["tile"] == 1 << 16
    assert snap["poll_wait_share"] > 0.9      # the device sets the pace
    first_call = len(chip.calls)
    rs.sweep(b"\x72" * 80, 0, max_nonces=CALL)
    assert chip.calls[first_call] == (0, 512)           # no second ramp
    assert len(chip.calls) - first_call == (CALL >> 16) // 512


@pytest.mark.parametrize("per_tile,host_s", [
    (48e-3, 1e-3),     # XLA:CPU at tile 2^14: 8 tiles are already 0.4 s
    (1e-3, 1e-5),      # under the ceiling, but the host always waits
])
def test_device_bound_loop_holds(monkeypatch, per_tile, host_s):
    from bitcoincashplus_tpu.mining.resident import SEG_TILES_START

    chip = _FakeChip(per_tile, host_s)
    rs = chip.drive(monkeypatch)
    rs.sweep(b"\x73" * 80, 0, max_nonces=1 << 23)
    rs.sweep(b"\x74" * 80, 0, max_nonces=1 << 23)
    assert rs.seg_tiles == SEG_TILES_START
    assert {n for _, n in chip.calls} == {SEG_TILES_START}
    assert rs.snapshot()["poll_wait_share"] > 0.99


@pytest.mark.parametrize("per_tile,host_s,stall", [
    (V5E_TILE_S, HOST_TRIP_S, {}),
    (V5E_TILE_S, 1e-4, {}),
    (3e-5, 5e-3, {}),
    (7e-4, 2e-3, {}),
    # a host that loses 45-90 ms now and then settles late, and the gap
    # after a late settle is short: that must not ratchet the length up
    (V5E_TILE_S, HOST_TRIP_S, {k: 0.045 for k in range(40, 4000, 3)}),
    (V5E_TILE_S, HOST_TRIP_S, {k: 0.09 for k in range(40, 4000, 5)}),
    (V5E_TILE_S, 2e-4, {k: 0.03 for k in range(0, 4000, 2)}),
])
def test_segment_never_outgrows_the_ceiling(monkeypatch, per_tile, host_s,
                                            stall):
    """Whatever the device's and the host's speeds, no segment carries
    more than the ceiling's device time (4/3 of it under hiccups: the
    bound SIZING_POLLS states)."""
    from bitcoincashplus_tpu.mining.resident import SEG_CEILING_S

    chip = _FakeChip(per_tile, host_s, stall)
    rs = chip.drive(monkeypatch)
    for fill in (0x75, 0x76, 0x77, 0x78):
        rs.sweep(bytes([fill]) * 80, 0, max_nonces=CALL)
    assert chip.longest_s() <= SEG_CEILING_S * (4 / 3 if stall else 1)
    assert sum(n for _, n in chip.calls) == 4 * (CALL >> 16)


def test_explicit_seg_tiles_pins_the_length(monkeypatch):
    chip = _FakeChip(V5E_TILE_S, HOST_TRIP_S)
    rs = chip.drive(monkeypatch, seg_tiles=8)
    rs.sweep(b"\x79" * 80, 0, max_nonces=1 << 26)
    assert rs.seg_tiles == 8
    assert {n for _, n in chip.calls} == {8}
    assert rs.snapshot()["poll_wait_share"] < 0.6       # and it starves


def test_short_budget_dispatches_what_it_did(monkeypatch):
    """A budget under the sizing window (regtest, the default maxtries of
    10^6) never moves the length: its segments are those of seg_tiles=8."""
    chip = _FakeChip(V5E_TILE_S, HOST_TRIP_S)
    rs = chip.drive(monkeypatch)
    for fill in range(6):
        rs.sweep(bytes([fill]) * 80, 0, max_nonces=1 << 20)
    assert rs.seg_tiles == 8
    assert [n for _, n in chip.calls] == [8, 8] * 6


def test_segment_is_one_dispatch_and_one_fetch(monkeypatch):
    """A segment costs the host one program and one blocking fetch: the
    start and the tile count reach the kernel as numpy scalars, arguments
    of that one call (a jax array here was a convert_element_type program
    of its own, two a segment), and its three results come back through
    one device_get."""
    rs = ResidentSweep(tile=1 << 9, seg_tiles=2, inflight=2, kernel="exact")
    real, seen, gets = rs._jitfn(), [], []

    def kernel(mid, tail, tgt, start, n_tiles, tile):
        seen.append((start, n_tiles))
        return real(mid, tail, tgt, start, n_tiles, tile=tile)

    def device_get(tree):
        gets.append(tree)
        return real_get(tree)

    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", device_get)
    rs._jitfn = lambda: kernel
    try:
        assert rs.sweep(b"\x7a" * 80, 0, max_nonces=1 << 13) == (None, 1 << 13)
    finally:
        rs.close()
    assert len(seen) == rs.polls == 8
    for start, n_tiles in seen:
        assert type(start) is np.uint32 and type(n_tiles) is np.uint32
    assert len(gets) == 8 and all(len(tree) == 3 for tree in gets)


# one header whose double hashes are known around the scenarios below
# (hashlib, offline): over nonces [-49152, 131072) the lowest hash is at
# 74763, and over [-700, 48452) at 3743
DIFF_HEADER = b"\x03" * 80
DIFF_TILE = 1 << 9
DIFF_BUDGET = 96 * DIFF_TILE
LOWEST, LOWEST_PAST_WRAP = 74763, 3743


def _hash_at(header80, nonce):
    return int.from_bytes(
        sha256d(header80[:76] + nonce.to_bytes(4, "little")), "little")


@pytest.mark.parametrize("kernel", ["exact", "h7"])
@pytest.mark.parametrize("seg_tiles", [1, 2, 64, 1 << 20])
def test_segment_lengths_differential(seg_tiles, kernel):
    """Segment lengths 1, 2, 64 tiles and longer than the budget, both
    kernels, against sweep_header_cpu: which nonce wins does not depend on
    how the budget is cut into segments, and nonces_swept takes a hit's
    tile whole and a discarded segment not at all."""
    tile, budget = DIFF_TILE, DIFF_BUDGET
    seg_nonces = min(seg_tiles * tile, budget)
    rs = ResidentSweep(tile=tile, seg_tiles=seg_tiles, inflight=2,
                       kernel=kernel)

    def run(target, start):
        before = rs.nonces_swept, rs.segments_discarded
        got = rs.sweep(DIFF_HEADER, target, start_nonce=start,
                       max_nonces=budget)
        want = miner.sweep_header_cpu(DIFF_HEADER, target,
                                      start_nonce=start, max_nonces=budget)
        assert got[0] == want[0]
        counted = rs.nonces_swept - before[0]
        assert got[1] == counted
        if want[0] is None:
            assert counted == budget
        else:   # the hit's tile whole, nothing behind it
            assert 0 <= counted - want[1] < tile
        return want, rs.segments_discarded - before[1]

    try:
        # first hit in nonce order, of many
        (nonce, _), _ = run(1 << 250, 0)
        assert nonce is not None
        # a hit in the first tile: the segment behind it is discarded
        (nonce, tried), dropped = run(_hash_at(DIFF_HEADER, LOWEST),
                                      LOWEST - 5)
        assert (nonce, tried) == (LOWEST, 6)
        assert dropped == (1 if seg_nonces < budget else 0)
        # a hit in the last tile of the first segment
        (nonce, tried), _ = run(_hash_at(DIFF_HEADER, LOWEST),
                                LOWEST - (seg_nonces - 3))
        assert nonce == LOWEST and (tried - 1) // tile == seg_nonces // tile - 1
        # the 2^32 rollover, from an unaligned start
        passes = rs.passes
        (nonce, tried), _ = run(_hash_at(DIFF_HEADER, LOWEST_PAST_WRAP),
                                (1 << 32) - 700)
        assert (nonce, tried) == (LOWEST_PAST_WRAP, 700 + LOWEST_PAST_WRAP + 1)
        assert rs.passes == passes + 1
        # no hit: the whole budget, counted exactly
        run(0, 12345)
    finally:
        rs.close()


@pytest.mark.parametrize("kernel", ["exact", "h7"])
@pytest.mark.parametrize("seg_tiles", [1, 2, 64, 1 << 20])
def test_false_positive_resumed_inside_segment(seg_tiles, kernel):
    """A candidate the host refuses (the h7 limb tie, planted) is resumed
    past inside its segment, however long: the next real hit wins."""
    target = 1 << 250
    real = [n for n in range(1 << 11)
            if _hash_at(DIFF_HEADER, n) <= target]
    assert len(real) >= 2
    rs = ResidentSweep(tile=DIFF_TILE, seg_tiles=seg_tiles, inflight=2,
                       kernel=kernel)
    try:
        true_confirm = rs._confirm
        rs._confirm = lambda n: n != real[0] and true_confirm(n)
        nonce, tried = rs.sweep(DIFF_HEADER, target, max_nonces=DIFF_BUDGET)
        assert nonce == real[1]
        assert rs.false_positives == 1 and rs.hits == 1
        assert tried == rs.nonces_swept >= real[1] + 1
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
