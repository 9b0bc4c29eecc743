"""TPU proof-of-work nonce sweep.

Replaces the scalar CPU mining loop in src/rpc/mining.cpp:~120
(generateBlocks):

    while (nMaxTries > 0 && nNonce < nInnerLoopCount &&
           !CheckProofOfWork(pblock->GetHash(), nBits, params)) ++nNonce;

with a data-parallel sweep: a `lax.while_loop` over nonce tiles, each tile
hashing TILE nonces at once from the header midstate (2 compressions per
nonce), comparing against the target as 8xu32 LE limbs on-device, and
early-exiting the loop on the first hit. One dispatch sweeps up to the whole
32-bit nonce space; the host polls a tiny (found, nonce, tiles) result.

Multi-chip sharding over ICI lives in parallel/nonce_shard.py (shard_map over
a ('chip',) mesh; each chip owns a contiguous nonce range).
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.hashes import header_midstate
from .sha256 import (
    _use_unrolled,
    bytes_to_words_np,
    digest_to_limbs,
    header_sweep_digest,
    le256,
    target_to_limbs_np,
)
from .sha256_sweep import hoist_template, sweep_digest_hoisted

# Default tile: 64Ki nonces per device loop iteration. Large enough to fill
# the 8x128 VPU lanes many times over (amortizing loop overhead), small
# enough to stay comfortably in VMEM (~16 live u32 vectors * 256KiB).
DEFAULT_TILE = 1 << 16


def _sweep_tile(pre, target_limbs, base_nonce, tile: int):
    """Hash one tile of `tile` consecutive nonces; return (hit, nonce).
    `nonce` is the lowest in-tile hit when hit is True (argmax finds the
    first True lane; nonces are base+iota so lane order == nonce order).
    ``pre`` is the per-template chunk-2 hoist (ops/sha256_sweep.
    hoist_template) — computed once per dispatch (or per template swap in
    the resident loop), never per nonce."""
    lanes = jax.lax.broadcasted_iota(jnp.uint32, (tile, 1), 0).squeeze(-1)
    nonces = base_nonce + lanes
    if _use_unrolled():
        h8 = sweep_digest_hoisted(pre, nonces)
    else:
        # XLA:CPU never returns from the unrolled per-nonce programs: its
        # instruction fusion duplicates their memoised schedule words
        # into every consumer. The looped compress, from the same template
        h8 = header_sweep_digest(pre["mid"], pre["tail"], nonces)
    ok = le256(digest_to_limbs(h8), target_limbs)
    hit = jnp.any(ok)
    idx = jnp.argmax(ok)
    return hit, nonces[idx]


def _boundary_tiles(start_nonce: int, max_nonces: int, tile: int) -> int:
    """Tile count for a sweep from ``start_nonce``, clamped against the
    2^32 nonce-space boundary: a sweep starting near the top must not
    wrap into (and re-hash / over-count) nonces below the start — the
    resident loop's rollover owns wrap policy, one full pass at a time."""
    space = (1 << 32) - (start_nonce & 0xFFFFFFFF)
    return min((max_nonces + tile - 1) // tile, (space + tile - 1) // tile)


@partial(jax.jit, static_argnames=("tile",))
def sweep_jit(midstate, tail, target_limbs, start_nonce, n_tiles, tile: int = DEFAULT_TILE):
    """Sweep [start_nonce, start_nonce + n_tiles*tile) for a PoW hit.

    midstate: (8,) uint32; tail: (3,) uint32 BE words of header bytes 64..75;
    target_limbs: (8,) uint32 LE limbs; start_nonce, n_tiles: uint32 scalars.
    Returns (found bool, nonce uint32, tiles_done uint32). Nonce arithmetic
    wraps mod 2^32 exactly like the reference's uint32 nNonce.
    """
    tgt = [target_limbs[j] for j in range(8)]
    # per-template hoist: traced scalars, computed once per dispatch and
    # lifted out of the while_loop by XLA (loop-invariant)
    pre = hoist_template([midstate[i] for i in range(8)],
                         [tail[i] for i in range(3)])

    def cond(carry):
        i, found, _ = carry
        return jnp.logical_and(i < n_tiles, jnp.logical_not(found))

    def body(carry):
        i, _, _ = carry
        base = start_nonce + i.astype(jnp.uint32) * np.uint32(tile)
        hit, nonce = _sweep_tile(pre, tgt, base, tile)
        return i + np.uint32(1), hit, nonce

    i0 = jnp.uint32(0)
    found0 = jnp.array(False)
    nonce0 = jnp.uint32(0)
    tiles, found, nonce = jax.lax.while_loop(cond, body, (i0, found0, nonce0))
    return found, nonce, tiles


def sweep_header_cpu(header80: bytes, target: int, start_nonce: int = 0,
                     max_nonces: int = 1 << 32):
    """Scalar host sweep — the reference generateBlocks inner loop
    (src/rpc/mining.cpp:~120) verbatim. This is the degraded-mode engine
    the miner circuit breaker falls back to when the device path is dead
    (ops/dispatch.supervised_sweep); same contract as sweep_header: first
    hit in nonce order wins, (nonce | None, hashes_attempted)."""
    from ..crypto.hashes import sha256d

    assert len(header80) == 80
    base = header80[:76]
    for i in range(max_nonces):
        nonce = (start_nonce + i) & 0xFFFFFFFF
        h = sha256d(base + nonce.to_bytes(4, "little"))
        if int.from_bytes(h, "little") <= target:
            return nonce, i + 1
    return None, max_nonces


def sweep_header(header80: bytes, target: int, start_nonce: int = 0,
                 max_nonces: int = 1 << 32, tile: int = DEFAULT_TILE):
    """Host API: search for a nonce making sha256d(header) <= target.

    Returns (nonce or None, hashes_attempted). The header's own nonce field is
    ignored; bytes 0..75 define the search. Mirrors generateBlocks' semantics
    (bounded attempts, first hit wins) at tile granularity. The search is
    clamped at the 2^32 nonce-space boundary (``_boundary_tiles``): a sweep
    starting near the top stops there instead of wrapping into — and
    over-counting / re-hashing — nonces below the start; rollover across
    the boundary is the resident loop's job (mining/resident.py).
    """
    from ..util import devicewatch as dw
    from ..util import telemetry as tm

    assert len(header80) == 80
    midstate = np.array(header_midstate(header80), dtype=np.uint32)
    tail = bytes_to_words_np(np.frombuffer(header80[64:76], dtype=np.uint8))
    tgt = target_to_limbs_np(target)
    n_tiles = _boundary_tiles(start_nonce, max_nonces, tile)
    # watched dispatch: the compiled shape is the (tile,) specialization —
    # a node mints at most a couple (DEFAULT_TILE + the regtest/CPU tile),
    # so a sweep that starts recompiling per call trips the sentinel
    dw.note_transfer("miner", "h2d",
                     int(midstate.nbytes + tail.nbytes + tgt.nbytes))
    with tm.span("miner.sweep", tiles=n_tiles), \
            dw.program("miner_sweep", shape_budget=4).dispatch(
                tile, jitfn=sweep_jit,
                args=(midstate, tail, tgt, np.uint32(start_nonce),
                      np.uint32(n_tiles)),
                kwargs={"tile": tile}):
        found, nonce, tiles = sweep_jit(
            jnp.asarray(midstate), jnp.asarray(tail), jnp.asarray(tgt),
            jnp.uint32(start_nonce), jnp.uint32(n_tiles), tile=tile,
        )
        # the jit call above only ENQUEUES — settle inside the watch and
        # the span so the sweep itself lands there (the int() fetch below
        # would otherwise be billed the whole kernel as "transfer")
        jax.block_until_ready(tiles)
    t0 = time.perf_counter()
    # attempted-hash accounting is also boundary-clamped: the final tile
    # may straddle 2^32, but nonces past the boundary were never part of
    # this sweep's contract
    hashes = min(int(tiles) * tile, (1 << 32) - (start_nonce & 0xFFFFFFFF))
    hit = bool(found)
    dw.note_transfer("miner", "d2h", 12,
                     seconds=time.perf_counter() - t0)
    if hit:
        return int(nonce), hashes
    return None, hashes
