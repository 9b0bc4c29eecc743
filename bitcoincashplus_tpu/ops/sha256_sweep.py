"""Specialized SHA-256d nonce-sweep kernel (op-count-minimal h7 path).

The generic sweep (ops/miner.py + ops/sha256.py) computes the full 8-word
double-SHA digest per nonce and an 8-limb target compare. This module is the
miner-grade specialization of the same search — the moral equivalent of the
hand-scheduled Transform specializations the reference keeps per-ISA
(src/crypto/sha256_sse4.cpp, sha256_avx2.cpp: same math, fewer ops per hash):

  1. **Shared prefix** — header bytes 0..63 are midstate (already exploited);
     on top of that, rounds 0..2 of the second compression consume only
     header words w0..w2 (merkle tail / nTime / nBits), which are constant
     across the sweep, so those rounds and every schedule term not touching
     the nonce fold to constants (the AsicBoost-style schedule sharing of
     PAPERS.md item 2, applied to the nonce axis).
  2. **Zero/constant padding algebra** — block 2 of the first hash is
     [w0,w1,w2,nonce,PAD,0*10,len]; most σ0/σ1 schedule terms vanish or fold.
  3. **Truncated tail + h7-first early exit** — PoW compares the hash as a
     little-endian uint256, whose topmost 32 bits are digest word h[7]
     byte-swapped (src/pow.cpp:~74 CheckProofOfWork / arith_uint256). h[7] =
     IV7 + e_61, and e_61 = a_57 + t1_60, so rounds 61..63 of the second
     compression are never computed and rounds 57..60 need only their
     e-chain (t1); the other seven digest words are never produced. The
     device returns *candidate* nonces (limb7 <= target limb7); the host
     re-verifies the full 256-bit compare with the scalar oracle and resumes
     the sweep past false positives (~2^-32 per hash when limb7 ties).
  4. **Chunk-2 midstate hoisting** (``hoist_template``) — the per-template
     precompute is now EXPLICIT instead of relying on numpy's left-to-right
     constant folding: the first three compression rounds of chunk 2, every
     K[i]+w[i] pair whose message word is sweep-constant, and the
     constant-only legs of the schedule expansion (words 16..32 carried as
     (scalar, vector) pairs, materialized lazily) are computed ONCE per
     template — on the host as numpy scalars (trace-time folded into the
     compiled program) or on device as traced scalars (the resident mining
     loop's template swap: XLA lifts them out of the per-nonce vector
     fusion, so a swap never changes the compiled shape). The explicit
     grouping also removes the add-0 / scalar-chain vector ops the implicit
     folding missed — a measured ops/nonce reduction in the roofline census
     (ROOFLINE.md §8) with bit-identical digests vs the CPU oracle.

All round/schedule code below is polymorphic over numpy uint32 scalars and
traced jax arrays: anything not data-dependent on the nonce lane vector stays
a numpy scalar at trace time (folded into the program as a literal), or a
traced scalar (hoisted by XLA out of the vector fusion) when the midstate is
passed as a device array. Only nonce-dependent values become (tile,)-shaped
vector ops — the count that sets throughput on the VPU (see ROOFLINE.md).

Differential-tested against hashlib in tests/unit/test_sha256_sweep.py and
tests/unit/test_mining_resident.py (hoisted vs sweep_header_cpu).
"""

from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.hashes import SHA256_INIT, SHA256_K, header_midstate, sha256d
from .sha256 import _use_unrolled, bswap32, bytes_to_words_np
from .sha256 import header_sweep_digest, target_to_limbs_np

_K = [np.uint32(k) for k in SHA256_K]
_IV = [np.uint32(v) for v in SHA256_INIT]
_PAD = np.uint32(0x80000000)
_Z = np.uint32(0)
_LEN80 = np.uint32(640)
_LEN32 = np.uint32(256)


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _S0(x):
    return _rotr(x, 2) ^ _rotr(x, 13) ^ _rotr(x, 22)


def _S1(x):
    return _rotr(x, 6) ^ _rotr(x, 11) ^ _rotr(x, 25)


def _s0(x):
    return _rotr(x, 7) ^ _rotr(x, 18) ^ (x >> np.uint32(3))


def _s1(x):
    return _rotr(x, 17) ^ _rotr(x, 19) ^ (x >> np.uint32(10))


def _ch(e, f, g):
    # g ^ (e & (f ^ g)) == (e & f) | (~e & g): one op fewer than the
    # textbook form (no ~), and f^g is shared when f,g are still scalar.
    return g ^ (e & (f ^ g))


def _maj(a, b, c):
    # (a & (b ^ c)) ^ (b & c): 4 ops vs 5 for the three-AND form.
    return ((b ^ c) & a) ^ (b & c)


def _round(state, k, w):
    a, b, c, d, e, f, g, h = state
    t1 = h + _S1(e) + _ch(e, f, g) + k + w
    t2 = _S0(a) + _maj(a, b, c)
    return (t1 + t2, a, b, c, d + t1, e, f, g)


def _round_kw(state, kw, vecw=None):
    """One compression round with the round constant pre-folded: ``kw`` is
    K[i] + (the sweep-constant part of w[i]) — one vector add instead of
    two; ``vecw`` is the nonce-dependent remainder of the message word
    (None for fully-constant words)."""
    a, b, c, d, e, f, g, h = state
    t1 = (h + kw) + _S1(e) + _ch(e, f, g)
    if vecw is not None:
        t1 = t1 + vecw
    t2 = _S0(a) + _maj(a, b, c)
    return (t1 + t2, a, b, c, d + t1, e, f, g)


# ---------------------------------------------------------------------------
# Per-template chunk-2 hoist
# ---------------------------------------------------------------------------

# chunk-2 schedule words carried as (scalar, vector) pairs: index -> True
# when the scalar leg is identically zero for every template (w5..w14 are
# padding zeros), so materialization skips the add.
_SC_ZERO = frozenset((21, 28))

# chunk-3 (second hash) K+w folds for the padding rounds 8..15 — template-
# independent global constants: w8=PAD, w9..w14=0, w15=LEN32.
_KW3 = tuple(
    np.uint32((SHA256_K[8 + i] + w) & 0xFFFFFFFF)
    for i, w in enumerate(
        (0x80000000, 0, 0, 0, 0, 0, 0, 256))
)
_S1_LEN32 = _s1(_LEN32)  # σ1 of the chunk-3 length word (constant)
_S0_PAD = _s0(_PAD)      # σ0 of the padding word (constant)


def hoist_template(midstate8, tail3):
    """Per-template chunk-2 precompute (AsicBoost-style shared-computation
    reuse, PAPERS.md 1604.00575): everything in the second compression of
    the first hash that does not depend on the nonce, computed once per
    template instead of once per nonce.

    midstate8: 8 uint32 scalars (numpy or traced) — SHA-256 state after
    header bytes 0..63. tail3: 3 uint32 scalars — BE words of bytes 64..75
    (merkle tail, nTime, nBits). Returns a dict of sweep-constant scalars:

      mid    the midstate (for the chunk-2 feedback add)
      tail   the tail words as given (with mid, all the looped form needs)
      st3    compression state after rounds 0..2 (they consume only
             w0..w2 — hoisted entirely)
      c3t1   round 3's folded scalar leg: h3 + Σ1(e3) + ch(e3,f3,g3) + K3
             (the round's t1 is this plus the nonce word)
      t2_3   round 3's t2 (pure scalar)
      kw     K[i]+w[i] for rounds 4..15 (w = PAD / zeros / length — all
             sweep-constant)
      sc     scalar legs of schedule words 16..32 (16/17 are FULLY scalar;
             18..32 split into scalar + nonce-dependent vector parts;
             indices in _SC_ZERO are identically zero and omitted)
      kwsc   K[i] + sc[i] for rounds 16..32, pre-folded for _round_kw

    Polymorphic: numpy inputs fold at trace time (per-dispatch host
    hoist); traced scalars are computed on device once per template and
    lifted out of the per-nonce vector fusion by XLA — the resident
    loop's buffer swap re-runs only this scalar prologue, never a
    retrace (asserted by the devicewatch sentinel test)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        w0, w1, w2 = tail3
        st = tuple(midstate8)
        for i, w in enumerate((w0, w1, w2)):
            st = _round(st, _K[i], w)
        a3, b3, c3, d3, e3, f3, g3, h3 = st
        c3t1 = h3 + _S1(e3) + _ch(e3, f3, g3) + _K[3]
        t2_3 = _S0(a3) + _maj(a3, b3, c3)
        # rounds 4..15: the message words are PAD / zeros / LEN80
        w_const = [_PAD] + [_Z] * 10 + [_LEN80]
        kw = [_K[4 + i] + w for i, w in enumerate(w_const)]
        # schedule words 16/17 are fully sweep-constant; 18..32 carry a
        # scalar leg next to their nonce-dependent vector leg
        sc = {}
        sc[16] = w0 + _s0(w1)                      # + w9 + σ1(w14), both 0
        sc[17] = w1 + _s0(w2) + _s1(_LEN80)        # + w10 = 0
        sc[18] = w2 + _s1(sc[16])                  # + w11 = 0; σ0(nonce) vec
        sc[19] = _s0(_PAD) + _s1(sc[17])           # + w12 = 0; + nonce vec
        sc[20] = _PAD                              # σ0(w5)=0, w13=0
        # sc[21] == 0 (w5 + σ0(w6) + w14)
        sc[22] = _LEN80                            # w6 + σ0(w7) + w15
        sc[23] = sc[16]                            # w7 + σ0(w8) + w16
        sc[24] = sc[17]                            # w8 + σ0(w9) + w17
        sc[25] = sc[18]                            # w9 + σ0(w10) + sc(w18)
        sc[26] = sc[19]
        sc[27] = sc[20]
        # sc[28] == 0 (sc[21])
        sc[29] = sc[22]
        sc[30] = _s0(_LEN80) + sc[23]              # w14=0, σ0(w15) const
        sc[31] = _LEN80 + _s0(sc[16]) + sc[24]     # w15 + σ0(w16) + sc(w24)
        sc[32] = sc[16] + _s0(sc[17]) + sc[25]     # w16 + σ0(w17) + sc(w25)
        kwsc = {i: (_K[i] + sc[i]) if i in sc else _K[i]
                for i in range(16, 33)}
        return {"mid": list(midstate8), "tail": list(tail3), "st3": st,
                "c3t1": c3t1, "t2_3": t2_3, "kw": kw, "sc": sc, "kwsc": kwsc}


def _chunk2_digest_hoisted(pre, nonces):
    """First-hash digest words (8 vectors shaped like ``nonces``) from a
    hoisted template: compression 2 over [w0,w1,w2,nonce,PAD,0*10,len]
    with every sweep-constant leg taken from ``pre``."""
    n = bswap32(nonces)
    sc = pre["sc"]
    vec = {18: _s0(n), 19: n}
    full = {16: sc[16], 17: sc[17]}

    def mat(i):
        """Materialize schedule word i (scalar + vector legs, memoized;
        zero scalar legs skip the add)."""
        w = full.get(i)
        if w is None:
            w = vec[i] if i in _SC_ZERO else sc[i] + vec[i]
            full[i] = w
        return w

    for i in range(20, 25):
        vec[i] = _s1(mat(i - 2))
    for i in range(25, 33):
        vec[i] = vec[i - 7] + _s1(mat(i - 2))
    for i in range(33, 64):
        full[i] = (mat(i - 16) + _s0(mat(i - 15)) + mat(i - 7)
                   + _s1(mat(i - 2)))

    # rounds 0..2 hoisted (pre["st3"]); round 3 consumes the nonce word
    a3, b3, c3, d3, e3, f3, g3, h3 = pre["st3"]
    t1 = pre["c3t1"] + n
    st = (t1 + pre["t2_3"], a3, b3, c3, d3 + t1, e3, f3, g3)
    for i in range(4, 16):
        st = _round_kw(st, pre["kw"][i - 4])
    for i in range(16, 33):
        st = _round_kw(st, pre["kwsc"][i], vec.get(i))
    for i in range(33, 64):
        st = _round(st, _K[i], full[i])
    return [m + s for m, s in zip(pre["mid"], st)]  # feedback -> digest


def _chunk3_words(d8, upto: int) -> list:
    """Second-hash message schedule [d8 || PAD || 0*6 || len], expanded to
    ``upto`` words with the constant legs folded (zero words skipped,
    σ of the padding/length words as module constants)."""
    w = list(d8) + [None] * (upto - 8)  # indices 8..15 never read below
    w[16] = w[0] + _s0(w[1])                       # w9=0, σ1(w14)=0
    w[17] = w[1] + _s0(w[2]) + _S1_LEN32           # w10=0
    for i in range(18, 22):                        # w11..w14 = 0
        w[i] = w[i - 16] + _s0(w[i - 15]) + _s1(w[i - 2])
    w[22] = w[6] + _s0(w[7]) + _LEN32 + _s1(w[20])
    w[23] = (w[7] + _S0_PAD) + w[16] + _s1(w[21])
    w[24] = (w[17] + _s1(w[22])) + _PAD            # σ0(w9)=0
    for i in range(25, 30):          # w[i-16] = 0, σ0(w[i-15]) = σ0(0) = 0
        w[i] = w[i - 7] + _s1(w[i - 2])
    w[30] = w[23] + _s1(w[28]) + _s0(_LEN32)       # w14 = 0, w15 = len
    w[31] = _LEN32 + _s0(w[16]) + w[24] + _s1(w[29])
    for i in range(32, upto):
        w[i] = w[i - 16] + _s0(w[i - 15]) + w[i - 7] + _s1(w[i - 2])
    return w


def _chunk3_rounds(w, upto: int):
    """Run second-hash compression rounds 0..upto-1 from the fresh IV;
    rounds 8..15 use the pre-folded K+w constants (_KW3)."""
    st = tuple(_IV)
    for i in range(min(8, upto)):
        st = _round(st, _K[i], w[i])
    for i in range(8, min(16, upto)):
        st = _round_kw(st, _KW3[i - 8])
    for i in range(16, upto):
        st = _round(st, _K[i], w[i])
    return st


def sweep_h7_hoisted(pre, nonces):
    """Digest word h[7] of sha256d(header) for each nonce, from a hoisted
    template (``hoist_template``). Returns (tile,) uint32 h[7] values; the
    PoW limb is bswap32(h7) (top 32 bits of the LE uint256 hash)."""
    with warnings.catch_warnings():
        # numpy scalar uint32 arithmetic wraps mod 2^32 (what SHA needs)
        # but warns; the traced side never warns.
        warnings.simplefilter("ignore", RuntimeWarning)
        d8 = _chunk2_digest_hoisted(pre, nonces)
        # second hash, truncated to the h7 chain: rounds 61..63 never run,
        # w61..w63 never expanded, 7 of 8 digest words never formed.
        w = _chunk3_words(d8, 61)
        st = _chunk3_rounds(w, 57)
        a57, b57, c57, d57, e, f, g, h = st
        # rounds 57..59: e-chain only (t1); a/b/c/d successors are known
        # shifts of a57..c57, so no Σ0/maj work is ever done here.
        for r, dprev in zip((57, 58, 59), (d57, c57, b57)):
            t1 = h + _S1(e) + _ch(e, f, g) + _K[r] + w[r]
            e, f, g, h = dprev + t1, e, f, g
        # round 60: only t1 is needed; e_61 = d_60 + t1_60 with d_60 = a_57.
        t1_60 = h + _S1(e) + _ch(e, f, g) + _K[60] + w[60]
        return _IV[7] + a57 + t1_60


def sweep_digest_hoisted(pre, nonces):
    """Full 8-word sha256d digest state per nonce from a hoisted template —
    the exact-compare twin of ``sweep_h7_hoisted`` (same hoisted chunk 2,
    full second compression). Used by the generic sweep tile
    (ops/miner._sweep_tile) and the resident mining loop's exact on-device
    compare; same output contract as ops/sha256.header_sweep_digest."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        d8 = _chunk2_digest_hoisted(pre, nonces)
        w = _chunk3_words(d8, 64)
        st = _chunk3_rounds(w, 64)
        return [v + s for v, s in zip(_IV, st)]


def sweep_h7(midstate8, tail3, nonces):
    """Digest word h[7] of sha256d(header) for each nonce in `nonces`.

    midstate8: 8 uint32 scalars (numpy or traced) — SHA-256 state after
    header bytes 0..63. tail3: 3 uint32 scalars — BE words of bytes 64..75.
    nonces: (tile,) uint32 device array. Hoists the template once
    (``hoist_template``) and runs the per-nonce remainder."""
    return sweep_h7_hoisted(hoist_template(midstate8, tail3), nonces)


@partial(jax.jit, static_argnames=("tile",))
def sweep_fast_jit(midstate, tail, t7, start_nonce, n_tiles, tile: int):
    """Candidate sweep of [start, start + n_tiles*tile): first nonce whose
    hash's top LE limb (bswap32(h7)) is <= t7.

    midstate: (8,) uint32; tail: (3,) uint32; t7: uint32 scalar (top limb of
    the target; 0 for any real-difficulty target). Returns (found, nonce,
    tiles_done). Candidates must be host-verified against the full 256-bit
    target (sweep_header_fast does); at limb equality the compare is
    undecided at this truncation.
    """
    mid8 = [midstate[i] for i in range(8)]
    tail3 = [tail[i] for i in range(3)]
    # template hoist: traced scalars, computed once per dispatch and lifted
    # out of the while_loop by XLA (loop-invariant)
    pre = hoist_template(mid8, tail3)

    def tile_fn(base):
        lanes = jax.lax.broadcasted_iota(jnp.uint32, (tile, 1), 0).squeeze(-1)
        nonces = base + lanes
        if _use_unrolled():
            h7 = sweep_h7_hoisted(pre, nonces)
        else:  # as ops/miner._sweep_tile; the looped form has no h7 twin
            h7 = header_sweep_digest(pre["mid"], pre["tail"], nonces)[7]
        ok = bswap32(h7) <= t7
        return jnp.any(ok), nonces[jnp.argmax(ok)]

    def cond(carry):
        i, found, _ = carry
        return jnp.logical_and(i < n_tiles, jnp.logical_not(found))

    def body(carry):
        i, _, _ = carry
        hit, nonce = tile_fn(start_nonce + i.astype(jnp.uint32) * np.uint32(tile))
        return i + np.uint32(1), hit, nonce

    tiles, found, nonce = jax.lax.while_loop(
        cond, body, (jnp.uint32(0), jnp.array(False), jnp.uint32(0))
    )
    return found, nonce, tiles


DEFAULT_TILE = 1 << 20


def sweep_header_fast(header80: bytes, target: int, start_nonce: int = 0,
                      max_nonces: int = 1 << 32, tile: int = DEFAULT_TILE):
    """Host API: find a nonce with sha256d(header) <= target, or None.

    Same contract as ops.miner.sweep_header (first hit in nonce order wins,
    returns (nonce_or_None, hashes_attempted)) but on the truncated-h7
    kernel: device candidates are exact-verified on the host and the sweep
    resumes past false positives, so the result is bit-identical to the
    generic path while doing fewer vector ops per nonce. Like sweep_header,
    the search stops at the 2^32 nonce-space boundary (no silent wrap into
    already-swept territory — the resident loop owns rollover policy).
    """
    assert len(header80) == 80
    midstate = jnp.asarray(np.array(header_midstate(header80), dtype=np.uint32))
    tail_np = bytes_to_words_np(np.frombuffer(header80[64:76], dtype=np.uint8))
    tail = jnp.asarray(tail_np)
    t7 = jnp.uint32(target_to_limbs_np(target)[7])

    hashes = 0
    nonce = start_nonce & 0xFFFFFFFF
    remaining = min(max_nonces, (1 << 32) - nonce)
    while remaining > 0:
        space = (1 << 32) - nonce  # tiles left before the 2^32 boundary
        n_tiles = min((remaining + tile - 1) // tile,
                      (space + tile - 1) // tile)
        found, cand, tiles = sweep_fast_jit(
            midstate, tail, t7, jnp.uint32(nonce), jnp.uint32(n_tiles), tile=tile
        )
        done = min(int(tiles) * tile, space)
        hashes += done
        if not bool(found):
            return None, hashes
        cand = int(cand)
        # exact host check of the candidate (scalar oracle)
        hdr = header80[:76] + int(cand).to_bytes(4, "little")
        if int.from_bytes(sha256d(hdr), "little") <= target:
            return cand, hashes
        # false positive (limb7 tie): resume just past it. The tiles the
        # device already swept before the candidate stay counted; the
        # candidate's own tile is partially re-swept, which is harmless.
        consumed = (cand - nonce) & 0xFFFFFFFF
        remaining -= consumed + 1
        nonce = (cand + 1) & 0xFFFFFFFF
        remaining = min(remaining, (1 << 32) - nonce)
    return None, hashes
