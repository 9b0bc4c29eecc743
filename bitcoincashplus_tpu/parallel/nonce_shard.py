"""Multi-chip PoW nonce-space sharding (P2 in SURVEY.md §3.2).

The reference mines one nonce at a time on one CPU thread
(src/rpc/mining.cpp:~120 generateBlocks); real deployments shard the nonce +
extranonce space across machines via getblocktemplate. Here the 32-bit nonce
space is sharded across TPU chips directly: `shard_map` over a ('chip',)
mesh, each chip sweeping a contiguous stripe with the single-chip tile loop
(ops/miner.sweep_jit's body), and the winning (found, nonce) reduced over ICI
with a min-nonce `psum`-style reduction — the payload is 2 scalars, so the
collective cost is negligible next to the hash work.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..crypto.hashes import header_midstate
from ..ops.miner import DEFAULT_TILE, _sweep_tile
from ..ops.sha256 import bytes_to_words_np, target_to_limbs_np
from ..ops.sha256_sweep import hoist_template
from .mesh import CHIP_AXIS, chip_mesh, local_devices, shard_map_nocheck


def _shard_body(midstate, tail, target_limbs, start_nonce, n_tiles, tile: int):
    """Per-chip sweep of a contiguous stripe of the nonce space.

    Runs under shard_map: axis_index picks this chip's stripe. Returns
    (found, nonce) reduced across chips to the globally smallest hit nonce
    (deterministic winner regardless of which chip finds one first).
    """
    chip = jax.lax.axis_index(CHIP_AXIS).astype(jnp.uint32)
    n_chips = jnp.uint32(jax.lax.axis_size(CHIP_AXIS))
    stripe = start_nonce + chip * n_tiles * np.uint32(tile)

    tgt = [target_limbs[j] for j in range(8)]
    # per-template chunk-2 hoist, once per dispatch (shared across every
    # tile of this chip's stripe — the same pre the single-chip sweep uses)
    pre = hoist_template([midstate[i] for i in range(8)],
                         [tail[i] for i in range(3)])

    def cond(carry):
        i, found, _ = carry
        return jnp.logical_and(i < n_tiles, jnp.logical_not(found))

    def body(carry):
        i, _, _ = carry
        base = stripe + i * np.uint32(tile)
        hit, nonce = _sweep_tile(pre, tgt, base, tile)
        return i + jnp.uint32(1), hit, nonce

    # Initial carry must be device-varying (derived from `stripe`, which
    # carries the chip axis) — shard_map rejects an invariant init whose
    # body output varies per chip.
    zero_v = stripe * jnp.uint32(0)
    tiles, found, nonce = jax.lax.while_loop(
        cond, body, (zero_v, zero_v > jnp.uint32(0), zero_v)
    )
    # Reduce to the smallest found nonce across chips; losers contribute MAX.
    key = jnp.where(found, nonce, jnp.uint32(0xFFFFFFFF))
    # Tie-break toward lower nonce; a lone 0xFFFFFFFF hit is recovered via
    # any_found (it would be indistinguishable from "none" by key alone).
    best = jax.lax.pmin(key, CHIP_AXIS)
    any_found = jax.lax.pmax(found.astype(jnp.uint32), CHIP_AXIS) > 0
    total_tiles = jax.lax.psum(tiles, CHIP_AXIS)
    # per-chip tiles-done, gathered over the chip axis (shard imbalance
    # observability — SURVEY §6.5)
    per_chip = tiles.reshape(1)
    return any_found, best, total_tiles, per_chip


@partial(jax.jit, static_argnames=("tile", "n_chips"))
def _sharded_sweep_jit(midstate, tail, target_limbs, start_nonce, n_tiles,
                       tile: int, n_chips: int):
    mesh = chip_mesh(n_chips)
    fn = shard_map_nocheck(
        partial(_shard_body, tile=tile),
        mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(CHIP_AXIS)),
    )
    return fn(midstate, tail, target_limbs, start_nonce, n_tiles)


def sweep_header_sharded(header80: bytes, target: int, start_nonce: int = 0,
                         max_nonces: int = 1 << 32,
                         tile: int = DEFAULT_TILE,
                         n_chips: int | None = None,
                         return_per_chip: bool = False):
    """Host API: multi-chip PoW search. Returns (nonce or None, total_hashes)
    — or (nonce, total_hashes, per_chip_tiles) with return_per_chip.

    Same signature contract as ops.miner.sweep_header so callers
    (mining/generate.mine_block's `sweep` hook) can inject either. max_nonces
    is the TOTAL budget across chips; chip c owns the contiguous stripe
    [start + c*span, start + (c+1)*span) with span = max_nonces / n_chips.
    """
    assert len(header80) == 80
    if n_chips is None:
        n_chips = len(local_devices())
    midstate = jnp.asarray(np.array(header_midstate(header80), dtype=np.uint32))
    tail = jnp.asarray(
        bytes_to_words_np(np.frombuffer(header80[64:76], dtype=np.uint8))
    )
    tgt = jnp.asarray(target_to_limbs_np(target))
    n_tiles = max(1, max_nonces // n_chips // tile)
    found, nonce, tiles, per_chip = _sharded_sweep_jit(
        midstate, tail, tgt, jnp.uint32(start_nonce), jnp.uint32(n_tiles),
        tile=tile, n_chips=n_chips,
    )
    hashes = int(tiles) * tile
    result = int(nonce) if bool(found) else None
    if return_per_chip:
        return result, hashes, [int(v) for v in np.asarray(per_chip)]
    return result, hashes
