"""Device-resident mining loop (the ISSUE 10 tentpole; sized by ISSUE 26).

The per-call shape (``ops/miner.sweep_header``) pays, on EVERY poll:
host->device staging of the template (midstate/tail/target), a fresh
program dispatch, a blocking scalar fetch, and the full devicewatch/
breaker bookkeeping — serially, with the device idle between calls.
The loss is above the kernel, not in it: on one TPU v5e the h7 kernel
runs at 0.6565 GH/s (62.56% of its op-bound ceiling) while segments of a
fixed 8 tiles, 0.8 ms of kernel against ~2.5 ms of host each, gave
0.209-0.211 GH/s end to end with the chip idle 73% of the window
(PERF_LEDGER.jsonl, PR 25, cell ``mine.diff1_solo``).

``ResidentSweep`` keeps the sweep resident instead:

- **One compiled program, long-lived buffers.** The template (midstate,
  tail words, target limbs) lives in device buffers; a template refresh
  is a same-shape buffer swap (``set_template``), never a retrace — the
  compiled shape is keyed only by the static tile, declared to the
  devicewatch compile sentinel as the ``miner_resident`` program with a
  shape budget. The retrace-sentinel test asserts repeated swaps stay
  inside it.
- **Pipelined segments.** The nonce space is swept in segments of
  ``seg_tiles`` tiles, one program dispatch and one blocking fetch each;
  up to ``inflight`` segments ride the device queue at once (JAX async
  dispatch), so the host settles segment k while k+1 already executes —
  enqueue/fetch overhead overlaps the hash work instead of serializing
  with it.
- **A segment is a time, not a tile count.** How much device work one
  host round trip buys follows what the loop observes (``_resize``):
  while the host spends under ``DEVICE_PACED_SHARE`` of its poll cadence
  blocked on the device, the host sets the pace and the chip starves, so
  the segment doubles (``n_tiles`` is a traced argument: no new compile)
  until one segment reaches ``SEG_CEILING_S`` of device time. A backend
  on which the host always waits (XLA:CPU, ~48 ms a tile) never grows.
  The learned length lives on the long-lived object, so only a
  process's first call ramps. An explicit ``seg_tiles=`` pins it.
- **On-chip nonce-space rollover.** Segment arithmetic is uint32; the
  host cursor clamps each segment at the 2^32 boundary
  (``ops/miner._boundary_tiles`` semantics) and wraps to 0, counting
  passes — a sweep crossing the boundary continues at nonce 0 without
  re-hashing the straddled range and without a fresh program.
- **Candidate-hit FIFO.** Device hits are host exact-verified (the
  scalar oracle — 2 hashes, free next to a sweep) and pushed into a
  bounded FIFO the caller polls; with the truncated-h7 kernel a false
  positive (limb7 tie, ~2^-32) is resumed past synchronously, so
  results stay bit-identical to the CPU oracle.

``sweep()`` adapts the loop to the ``sweep_header`` contract (first hit
in nonce order wins, ``(nonce | None, hashes_attempted)``) so
``mining/generate.mine_block`` and ``node._select_sweep`` drive the
persistent loop through the supervised-dispatch/breaker path unchanged:
a dead device degrades to the scalar host loop under the miner breaker,
and every settle beats the ``miner`` watchdog subsystem.

Telemetry: ``bcp_mining_*`` counter/histogram families below (native,
TYPEs per the PR 6/PR 7 lessons); the node projects ``snapshot()`` into
``bcp_mining_state_*`` gauges and ``gettpuinfo.mining``.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional

import numpy as np

from ..crypto.hashes import header_midstate, sha256d
from ..util import devicewatch as dw
from ..util import telemetry as tm

_now = time.perf_counter

PROGRAM = "miner_resident"
# compiled-shape budget for the resident program: (kernel, tile)
# specializations — a node mints at most the exact + h7 kernels at the
# production tile plus a regtest/bench tile each; a template swap that
# starts recompiling trips the sentinel (asserted in the mining tests)
SHAPE_BUDGET = 4

# where a loop that sizes its own segments starts (what every segment was
# before ISSUE 26): a short budget or a slow backend stays here
SEG_TILES_START = 8
# most device time one segment may carry, in seconds. Two segments ride
# the queue, so at a template swap or behind a hit at most ~130 ms of
# stale work stands before the new template's first nonce (0.02% of a
# 600 s block interval), and the ``miner`` watchdog still beats more than
# ten times a second. On a v5e that is 512 tiles of 2^16 (51 ms at
# 0.6565 GH/s): 16 round trips for a 2^29-nonce call instead of 1,024.
SEG_CEILING_S = 0.064
# the device sets the pace once the host spends at least this share of
# its poll cadence blocked in the fetch: what is left, the host's own work
# a round trip, is all a longer segment could still hide, and under a
# hundredth it is not worth the staleness
DEVICE_PACED_SHARE = 0.99
# consecutive polls one sizing decision sums over. A host that settles
# late shortens the next gap, but any n consecutive gaps of a two-deep
# queue hold n-1 whole segments, so their mean is at least 3/4 of a
# segment's device time: a hiccup cannot ratchet the length past 4/3 of
# the ceiling, and a doubling is judged only on segments that have it
SIZING_POLLS = 4
_NO_POLLS = (0, 0.0, 0.0)
# weight of the newest poll in the cadence and wait averages
_EMA = 0.2

_TILES_C = tm.counter(
    "bcp_mining_tiles_swept_total",
    "Nonce tiles swept by the resident mining loop")
_CANDS_C = tm.counter(
    "bcp_mining_candidates_total",
    "Device candidate hits by outcome (confirmed = host-verified PoW hit, "
    "false_positive = truncated-limb tie resumed past, stale = hit from a "
    "pre-swap template generation, dropped = FIFO overflow)",
    labels=("result",))
_SWAPS_C = tm.counter(
    "bcp_mining_template_swaps_total",
    "Template refreshes applied as device buffer swaps (no retrace)")
_POLLS_C = tm.counter(
    "bcp_mining_polls_total",
    "Host polls of the resident loop (one settled segment each)")
_ROLLOVER_C = tm.counter(
    "bcp_mining_rollovers_total",
    "Nonce-space rollovers (cursor wrapped past 2^32 to 0)")
_POLL_H = tm.histogram(
    "bcp_mining_poll_seconds",
    "Blocking settle wait per resident-loop poll (the d2h scalar fetch "
    "of the oldest in-flight segment)")
_FIFO_G = tm.gauge(
    "bcp_mining_fifo_depth",
    "Confirmed candidate hits parked in the resident loop's FIFO")


def _ema(avg: float, x: float) -> float:
    """``avg`` moved toward ``x``; an average still at 0.0 starts at x."""
    return x if avg == 0.0 else (1 - _EMA) * avg + _EMA * x


def _clamp_segment(cursor: int, want: int, tile: int, cap: int):
    """Boundary-clamped ``(n_tiles, nonces)`` for a segment at ``cursor``:
    the shared ops/miner._boundary_tiles clamp (no wrap past 2^32 inside
    one dispatch) plus the per-segment tile cap."""
    from ..ops.miner import _boundary_tiles

    n_tiles = min(cap, _boundary_tiles(cursor, want, tile))
    return n_tiles, min(n_tiles * tile, (1 << 32) - cursor)


class _Segment:
    __slots__ = ("gen", "start", "n_tiles", "nonces", "out")

    def __init__(self, gen, start, n_tiles, nonces, out):
        self.gen = gen              # template generation at enqueue
        self.start = start          # first nonce of the segment
        self.n_tiles = n_tiles
        self.nonces = nonces        # boundary-clamped nonce count
        self.out = out              # (found, nonce, tiles) device futures


class ResidentSweep:
    """Long-lived device-resident PoW sweep (see module docstring).

    ``kernel``: "exact" runs the full 8-limb on-device compare
    (ops/miner.sweep_jit — no false positives); "h7" runs the truncated
    top-limb kernel (ops/sha256_sweep.sweep_fast_jit — fewer ops/nonce,
    candidates host-verified). ``tile`` is the STATIC compiled shape;
    the loop never recompiles for a template swap, only for a new
    (kernel, tile) pair, bounded by the devicewatch shape budget.
    ``seg_tiles`` pins the tiles a segment carries; left out, the loop
    sizes its segments from its own poll timing (``_resize``)."""

    def __init__(self, tile: int = 1 << 16,
                 seg_tiles: Optional[int] = None,
                 inflight: int = 2, fifo_depth: int = 16,
                 kernel: str = "exact"):
        if kernel not in ("exact", "h7"):
            raise ValueError(f"resident kernel {kernel!r}: exact or h7")
        self.tile = int(tile)
        self._sized = seg_tiles is None
        self.seg_tiles = (SEG_TILES_START if self._sized
                          else max(1, int(seg_tiles)))
        self.inflight = max(1, int(inflight))
        self.kernel = kernel
        self.fifo = deque(maxlen=max(1, int(fifo_depth)))
        self.generation = 0
        self._header76: Optional[bytes] = None
        self._target: Optional[int] = None
        self._mid = self._tail = self._tgt = None   # device buffers
        self._mid_np = self._tail_np = self._tgt_np = None
        self._cursor = 0
        self._segments: deque[_Segment] = deque()
        self._watchdog = False
        # cumulative stats (snapshot() / gettpuinfo.mining)
        self.tiles_swept = 0
        self.nonces_swept = 0
        self.passes = 0
        self.buffer_swaps = 0
        self.polls = 0
        self.hits = 0
        self.false_positives = 0
        self.stale_hits = 0
        self.segments_discarded = 0
        self.fifo_dropped = 0
        self._poll_ema_s = 0.0      # gap between settles of a call (EMA)
        self._wait_ema_s = 0.0      # the part of it blocked in the fetch
        self._last_poll_t: Optional[float] = None  # last settle, this call
        self._win = _NO_POLLS       # sizing window: polls, gaps, waits

    # -- template lifecycle (buffer swap, never a retrace) --------------

    def set_template(self, header80: bytes, target: int) -> int:
        """Install a template. A changed (header bytes 0..75, target)
        swaps the device buffers in place — same shapes, same compiled
        program — bumps the generation, and invalidates in-flight
        segments (their results are counted stale, never trusted).
        Idempotent for an unchanged template."""
        import jax

        from ..ops.sha256 import bytes_to_words_np, target_to_limbs_np

        assert len(header80) == 80
        header76 = header80[:76]
        if header76 == self._header76 and target == self._target:
            return self.generation
        self._header76 = header76
        self._target = target
        self._mid_np = np.array(header_midstate(header80), dtype=np.uint32)
        self._tail_np = bytes_to_words_np(
            np.frombuffer(header80[64:76], dtype=np.uint8))
        limbs = target_to_limbs_np(target)
        self._tgt_np = (np.uint32(limbs[7]) if self.kernel == "h7"
                        else limbs)
        nbytes = int(self._mid_np.nbytes + self._tail_np.nbytes
                     + np.asarray(self._tgt_np).nbytes)
        dw.note_transfer("miner_resident", "h2d", nbytes)
        # the swap: fresh same-shape device buffers replace the old ones
        # (the old buffers are freed once their in-flight segments settle);
        # a plain transfer, no program (jnp.asarray of the h7 target's
        # numpy scalar ran a convert_element_type a template)
        self._mid, self._tail, self._tgt = jax.device_put(
            (self._mid_np, self._tail_np, self._tgt_np))
        self.generation += 1
        self.buffer_swaps += 1
        _SWAPS_C.inc()
        self._cursor = 0
        return self.generation

    # -- segment pipeline -----------------------------------------------

    def _jitfn(self):
        if self.kernel == "h7":
            from ..ops.sha256_sweep import sweep_fast_jit

            return sweep_fast_jit
        from ..ops.miner import sweep_jit

        return sweep_jit

    def _dispatch(self, start: int, n_tiles: int):
        """Enqueue one segment: ONE program under the compile sentinel.
        The shape signature is (kernel, tile) — template swaps and
        segment lengths re-dispatch the SAME signature, so the shapes
        count must stay flat. ``start`` and ``n_tiles`` go in as numpy
        scalars, arguments of that one call (a ``jnp.uint32(...)`` each
        would be a ``convert_element_type`` program of its own)."""
        jitfn = self._jitfn()
        start, n_tiles = np.uint32(start), np.uint32(n_tiles)
        with tm.span("miner.enqueue", tiles=int(n_tiles)), \
                dw.program(PROGRAM, shape_budget=SHAPE_BUDGET).dispatch(
                    self.kernel, self.tile, jitfn=jitfn,
                    args=(self._mid_np, self._tail_np, self._tgt_np,
                          start, n_tiles),
                    kwargs={"tile": self.tile}):
            out = jitfn(self._mid, self._tail, self._tgt, start, n_tiles,
                        tile=self.tile)
        dw.note_transfer("miner_resident", "h2d", 8)  # 2 uint32 scalars
        return out

    @staticmethod
    def _fetch(out):
        """ONE blocking fetch of a segment's (found, nonce, tiles): the
        three copies are started together and waited for once."""
        import jax

        found, nonce, tiles = jax.device_get(out)
        return bool(found), int(nonce), int(tiles)

    def _pump(self, budget_left: int) -> int:
        """Enqueue segments (rollover-aware) until the in-flight window
        is full or ``budget_left`` nonces are covered; returns the nonce
        count newly planned."""
        planned = 0
        while (len(self._segments) < self.inflight
               and budget_left - planned > 0):
            n_tiles, nonces = _clamp_segment(
                self._cursor, budget_left - planned, self.tile,
                self.seg_tiles)
            out = self._dispatch(self._cursor, n_tiles)
            self._segments.append(_Segment(
                self.generation, self._cursor, n_tiles, nonces, out))
            planned += nonces
            self._cursor = (self._cursor + nonces) & 0xFFFFFFFF
            if self._cursor == 0:
                self.passes += 1
                _ROLLOVER_C.inc()
        return planned

    def _settle_oldest(self):
        """Block on the oldest in-flight segment; returns (seg, found,
        cand_nonce, tiles_done). Meters the poll, beats the watchdog."""
        seg = self._segments.popleft()
        # the blocking fetch: the span for the totals and the profiler's
        # trace; the sizing rule keeps a clock of its own (it has to size
        # the segments under -telemetry=off too), _POLL_H its distribution
        t0 = _now()
        with tm.span("miner.poll_wait", tiles=seg.n_tiles):
            found, nonce, tiles = self._fetch(seg.out)
        now = _now()
        dt = now - t0
        _POLL_H.observe(dt)
        _POLLS_C.inc()
        dw.note_transfer("miner_resident", "d2h", 12)
        if self._last_poll_t is None:   # a call's first settle: no gap
            self._win = _NO_POLLS
        else:
            gap = now - self._last_poll_t
            self._poll_ema_s = _ema(self._poll_ema_s, gap)
            self._wait_ema_s = _ema(self._wait_ema_s, dt)
            self._resize(seg, dt, gap)
        self._last_poll_t = now
        self.polls += 1
        self.tiles_swept += tiles
        _TILES_C.inc(tiles)
        dw.WATCHDOG.beat("miner")
        return seg, found, nonce, tiles

    def _resize(self, seg: _Segment, wait: float, gap: float) -> None:
        """The sizing rule. ``gap`` is the time since the previous settle
        of this call and ``wait`` the part of it blocked in the fetch,
        summed over SIZING_POLLS consecutive segments of the present
        length (one clamped to a budget or the 2^32 boundary, or still in
        flight from before a doubling, is no evidence and starts the
        window again). With the queue kept full the mean gap stands for
        a segment's device time: the device cannot finish them faster.
        While the waits are under DEVICE_PACED_SHARE of the gaps the host
        sets the pace: double, unless a doubled segment could pass
        SEG_CEILING_S."""
        if not self._sized:
            return
        if seg.n_tiles != self.seg_tiles:
            self._win = _NO_POLLS
            return
        n, gaps, waits = self._win
        n, gaps, waits = n + 1, gaps + gap, waits + wait
        if n < SIZING_POLLS:
            self._win = (n, gaps, waits)
            return
        self._win = _NO_POLLS
        if (waits < DEVICE_PACED_SHARE * gaps
                and 2 * gaps / n <= SEG_CEILING_S):
            self.seg_tiles *= 2

    def _confirm(self, nonce: int) -> bool:
        """Host exact-verify of a device candidate (the scalar oracle)."""
        hdr = self._header76 + int(nonce).to_bytes(4, "little")
        return int.from_bytes(sha256d(hdr), "little") <= self._target

    def _resweep_exact(self, start: int, nonces_left: int):
        """Synchronous in-segment resume past an h7 false positive
        (~2^-32 per hash): sweep [start, start+nonces_left) blocking.
        Returns ``(hit, hashed)`` — the first CONFIRMED hit (or None) and
        the number of nonces hashed here, which the caller must fold into
        its attempted-hash accounting (the per-dispatch twin
        sweep_header_fast counts resumed work the same way)."""
        hashed = 0
        while nonces_left > 0:
            n_tiles, nonces = _clamp_segment(
                start, nonces_left, self.tile, self.seg_tiles)
            found, cand, tiles = self._fetch(self._dispatch(start, n_tiles))
            done = min(tiles * self.tile, nonces)
            self.tiles_swept += tiles
            self.nonces_swept += done
            hashed += done
            _TILES_C.inc(tiles)
            if not found:
                return None, hashed
            if self._confirm(cand):
                return cand, hashed
            self.false_positives += 1
            _CANDS_C.labels(result="false_positive").inc()
            consumed = ((cand - start) & 0xFFFFFFFF) + 1
            nonces_left -= consumed
            start = (cand + 1) & 0xFFFFFFFF
        return None, hashed

    # -- the sweep_header-contract driver -------------------------------

    def sweep(self, header80: bytes, target: int, start_nonce: int = 0,
              max_nonces: int = 1 << 32, tile: Optional[int] = None):
        """Search [start_nonce, start_nonce+max_nonces) (rollover past
        2^32, one full pass max) for the first nonce in sweep order with
        sha256d(header) <= target. Same contract as
        ops/miner.sweep_header; ``tile`` is accepted for signature
        compatibility and ignored — the resident loop owns its compiled
        tile. A changed header/target is a buffer swap; in-flight
        segments of the old generation are discarded unsettled."""
        gen = self.set_template(header80, target)
        # stale in-flight segments (previous template or previous call's
        # cursor) never contribute: drop the references — the device work
        # completes harmlessly and the buffers are collected
        self.segments_discarded += len(self._segments)
        self._segments.clear()
        self._cursor = start_nonce & 0xFFFFFFFF
        self._last_poll_t = None
        budget = min(max_nonces, 1 << 32)
        swept = 0
        planned = self._pump(budget)
        while self._segments:
            seg, found, cand, tiles = self._settle_oldest()
            done = min(tiles * self.tile, seg.nonces)
            swept += done
            self.nonces_swept += done
            if found and seg.gen != gen:  # defensive: direct-pump users
                self.stale_hits += 1
                _CANDS_C.labels(result="stale").inc()
            elif found:
                if self._confirm(cand):
                    self._record_hit()
                    self.segments_discarded += len(self._segments)
                    self._segments.clear()
                    return cand, swept
                # h7 limb tie: resume synchronously inside the segment
                self.false_positives += 1
                _CANDS_C.labels(result="false_positive").inc()
                after = ((cand - seg.start) & 0xFFFFFFFF) + 1
                hit, hashed = self._resweep_exact(
                    (cand + 1) & 0xFFFFFFFF, seg.nonces - after)
                swept += hashed
                if hit is not None:
                    self._record_hit()
                    self.segments_discarded += len(self._segments)
                    self._segments.clear()
                    return hit, swept
            planned += self._pump(budget - planned)
        return None, swept

    def advance(self, nonce_budget: int) -> int:
        """Continuous-mining poll surface: sweep up to ``nonce_budget``
        nonces forward from the loop's cursor (rollover-aware, template
        already installed via set_template), parking confirmed hits in
        the FIFO for ``take_hits()`` instead of returning the first one —
        the host polls a buffer, it never blocks on (found, nonce,
        tiles). A hit does not stop the sweep; the loop moves on to the
        next segment (at real difficulty a template yields ~one hit, and
        the driver refreshes the template on pickup, so the skipped
        segment remainder is dead work either way). Returns the number
        of new confirmed hits parked."""
        assert self._header76 is not None, "set_template first"
        gen = self.generation
        new_hits = 0
        self._last_poll_t = None
        planned = self._pump(nonce_budget)
        while self._segments:
            seg, found, cand, tiles = self._settle_oldest()
            self.nonces_swept += min(tiles * self.tile, seg.nonces)
            if found and seg.gen == gen and self._confirm(cand):
                self._push_hit(cand)
                new_hits += 1
            elif found and seg.gen == gen:
                # h7 limb tie: the kernel early-exited the segment at the
                # false positive, but the cursor already moved past the
                # whole segment at dispatch time — resume the remainder
                # synchronously (as sweep() does) or a REAL hit in
                # (cand, seg end) would be silently lost until a full
                # 2^32 rollover
                self.false_positives += 1
                _CANDS_C.labels(result="false_positive").inc()
                after = ((cand - seg.start) & 0xFFFFFFFF) + 1
                hit, _ = self._resweep_exact(
                    (cand + 1) & 0xFFFFFFFF, seg.nonces - after)
                if hit is not None:
                    self._push_hit(hit)
                    new_hits += 1
            elif found:
                self.stale_hits += 1
                _CANDS_C.labels(result="stale").inc()
            planned += self._pump(nonce_budget - planned)
        return new_hits

    def _record_hit(self) -> None:
        self.hits += 1
        _CANDS_C.labels(result="confirmed").inc()

    def _push_hit(self, nonce: int) -> None:
        """Park a confirmed hit in the bounded FIFO (oldest dropped on
        overflow, metered — the host poll cadence bounds staleness)."""
        if len(self.fifo) == self.fifo.maxlen:
            self.fifo_dropped += 1
            _CANDS_C.labels(result="dropped").inc()
        self.fifo.append({"nonce": int(nonce),
                          "generation": self.generation})
        self._record_hit()
        _FIFO_G.set(len(self.fifo))

    def take_hits(self) -> list:
        """Drain the confirmed-candidate FIFO (host poll surface)."""
        out = list(self.fifo)
        self.fifo.clear()
        _FIFO_G.set(0)
        return out

    # -- lifecycle / observability --------------------------------------

    def register_watchdog(self, quiet_s: Optional[float] = None) -> None:
        """Register the ``miner`` stall-watchdog subsystem: pending work
        is the in-flight segment count; every settled poll beats."""
        dw.WATCHDOG.register("miner",
                             pending_fn=lambda: len(self._segments),
                             quiet_s=quiet_s)
        self._watchdog = True

    def close(self) -> None:
        self._segments.clear()
        self._mid = self._tail = self._tgt = None
        if self._watchdog:
            dw.WATCHDOG.unregister("miner")
            self._watchdog = False

    def snapshot(self) -> dict:
        """gettpuinfo's ``mining`` section (resident-loop state). An
        unlocked read of counters by design: it runs beside a live sweep
        (node.mining_snapshot takes no ``miner`` lock — it would wait out
        the mining call), so the values are each current, not one
        instant's."""
        return {
            "resident": True,
            "kernel": self.kernel,
            "tile": self.tile,
            "seg_tiles": self.seg_tiles,
            "inflight_limit": self.inflight,
            "inflight": len(self._segments),
            "template_generation": self.generation,
            "buffer_swaps": self.buffer_swaps,
            "tiles_swept": self.tiles_swept,
            "nonces_swept": self.nonces_swept,
            "rollover_passes": self.passes,
            "polls": self.polls,
            "poll_cadence_s": round(self._poll_ema_s, 6),
            # ~0: the host starves the chip; near 1: the device paces
            "poll_wait_share": round(
                self._wait_ema_s / self._poll_ema_s, 4)
            if self._poll_ema_s else 0.0,
            "fifo_depth": len(self.fifo),
            "fifo_capacity": self.fifo.maxlen,
            "fifo_dropped": self.fifo_dropped,
            "hits": self.hits,
            "false_positives": self.false_positives,
            "stale_hits": self.stale_hits,
            "segments_discarded": self.segments_discarded,
        }
