"""Pallas/Mosaic TPU kernel for the SHA-256d nonce sweep.

Same search as ops/sha256_sweep.sweep_fast_jit (truncated-h7 candidate
sweep — see that module for the specialization math and the reference
citations), but hand-lowered through Pallas so the whole sweep runs as ONE
Mosaic kernel:

  - the nonce lattice is a VMEM-resident (sublanes, 128) u32 tile per grid
    step, generated in-register from a 2D iota (no HBM traffic at all:
    inputs are 8+3+2 scalars in SMEM, outputs are 3 scalars);
  - the grid dimension walks nonce tiles sequentially (TPU grid semantics),
    with an SMEM `found` flag checked via pl.when — tiles after the first
    hit are skipped, giving the same early-exit the lax.while_loop path has;
  - the first hit inside a tile is extracted with a min-reduction over
    linear lane indices (u32), avoiding 1D reshapes Mosaic dislikes.

The XLA and Pallas paths are differential-tested against each other and the
hashlib oracle. Nothing under the node selects this one: ROADMAP S1/D2
decide with a chip run whether it goes under the resident loop or goes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto.hashes import header_midstate, sha256d
from .sha256 import bswap32, bytes_to_words_np, target_to_limbs_np
from .sha256_sweep import sweep_h7

# Mosaic has no unsigned reductions, so the first-hit min runs on int32
# linear indices (always < 2^31 for any sane tile size).
_NOHIT = np.int32(0x7FFFFFFF)

# Tile geometry: (sublanes, 128) u32 lattice per grid step, swept on the
# real chip (tools/roofline.py): small tiles with very large grids win —
# the ~120-vector live set of the unrolled rounds must stay far below VMEM
# (64x128 u32 = 32KiB/vector ≈ 4MiB live), and the sequential grid is the
# cheap way to amortize per-dispatch overhead. Measured v5e-lite optimum:
# sublanes=64, grid 256Ki (0.95 GH/s vs 0.36-0.81 for 128-512 sublanes).
DEFAULT_SUBLANES = 64
LANES = 128


def _sweep_kernel(mid_ref, tail_ref, t7_ref, start_ref, ntiles_ref,
                  found_ref, nonce_ref, tiles_ref, *, sublanes: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        found_ref[0] = np.uint32(0)
        nonce_ref[0] = np.uint32(0)
        tiles_ref[0] = np.uint32(0)

    live = jnp.logical_and(found_ref[0] == 0,
                           i.astype(jnp.uint32) < ntiles_ref[0])

    @pl.when(live)
    def _work():
        tile = np.uint32(sublanes * LANES)
        base = start_ref[0] + i.astype(jnp.uint32) * tile
        rows = jax.lax.broadcasted_iota(jnp.uint32, (sublanes, LANES), 0)
        cols = jax.lax.broadcasted_iota(jnp.uint32, (sublanes, LANES), 1)
        lin = rows * np.uint32(LANES) + cols
        nonces = base + lin
        mid8 = [mid_ref[j] for j in range(8)]
        tail3 = [tail_ref[j] for j in range(3)]
        h7 = sweep_h7(mid8, tail3, nonces)
        ok = bswap32(h7) <= t7_ref[0]
        # first hit == smallest linear index among hits (lane order == nonce
        # order); _NOHIT if the tile has none.
        idx = jnp.min(jnp.where(ok, lin.astype(jnp.int32), _NOHIT))
        tiles_ref[0] = tiles_ref[0] + np.uint32(1)

        @pl.when(idx != _NOHIT)
        def _record():
            found_ref[0] = np.uint32(1)
            nonce_ref[0] = base + idx.astype(jnp.uint32)

    del _init, _work


@partial(jax.jit, static_argnames=("sublanes", "max_tiles", "interpret"))
def pallas_sweep_jit(midstate, tail, t7, start_nonce, n_tiles,
                     sublanes: int = DEFAULT_SUBLANES,
                     max_tiles: int = 4096, interpret: bool = False):
    """Candidate sweep of [start, start + n_tiles*tile) on the Pallas kernel.

    The grid is static (max_tiles); n_tiles (dynamic, <= max_tiles) gates the
    live programs so one compilation serves every sweep length. Returns
    (found bool, nonce u32, tiles_done u32) — the same contract as
    sha256_sweep.sweep_fast_jit; candidates need the host exact-check.
    """
    kernel = partial(_sweep_kernel, sublanes=sublanes)
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)  # noqa: E731
    found, nonce, tiles = pl.pallas_call(
        kernel,
        grid=(max_tiles,),
        in_specs=[smem(), smem(), smem(), smem(), smem()],
        out_specs=[smem(), smem(), smem()],
        out_shape=[
            jax.ShapeDtypeStruct((1,), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(midstate, tail, jnp.reshape(t7, (1,)), jnp.reshape(start_nonce, (1,)),
      jnp.reshape(n_tiles, (1,)))
    return found[0] != 0, nonce[0], tiles[0]


def sweep_header_pallas(header80: bytes, target: int, start_nonce: int = 0,
                        max_nonces: int = 1 << 32,
                        sublanes: int = DEFAULT_SUBLANES,
                        max_tiles: int = 4096, interpret: bool = False):
    """Host API mirroring ops.sha256_sweep.sweep_header_fast on the Pallas
    kernel: exact (first-hit, bit-identical) results via host verification
    of device candidates."""
    assert len(header80) == 80
    midstate = jnp.asarray(np.array(header_midstate(header80), dtype=np.uint32))
    tail = jnp.asarray(bytes_to_words_np(np.frombuffer(header80[64:76], np.uint8)))
    t7 = jnp.uint32(target_to_limbs_np(target)[7])
    tile = sublanes * LANES

    hashes = 0
    nonce = start_nonce & 0xFFFFFFFF
    remaining = max_nonces
    while remaining > 0:
        want = min((remaining + tile - 1) // tile, (1 << 32) // tile)
        n_tiles = min(want, max_tiles)
        found, cand, tiles = pallas_sweep_jit(
            midstate, tail, t7, jnp.uint32(nonce), jnp.uint32(n_tiles),
            sublanes=sublanes, max_tiles=max_tiles, interpret=interpret,
        )
        hashes += int(tiles) * tile
        if bool(found):
            cand = int(cand)
            hdr = header80[:76] + cand.to_bytes(4, "little")
            if int.from_bytes(sha256d(hdr), "little") <= target:
                return cand, hashes
            consumed = (cand - nonce) & 0xFFFFFFFF
            remaining -= consumed + 1
            nonce = (cand + 1) & 0xFFFFFFFF
        else:
            remaining -= int(tiles) * tile
            nonce = (nonce + int(tiles) * tile) & 0xFFFFFFFF
            if int(tiles) == 0:
                break
    return None, hashes
