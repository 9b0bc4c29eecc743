"""Batched SHA-256 / SHA-256d on TPU (jnp core).

Replaces the reference's CPU SHA-256 paths for bulk work:
  - src/crypto/sha256.cpp:~40 (CSHA256::Transform) — 64-round compression,
    here fully unrolled over a uint32 batch so XLA maps it onto the 8x128
    VPU lanes (one message per lane).
  - src/primitives/block.cpp:~13 (CBlockHeader::GetHash) — 80-byte header
    double-SHA, both the full path and the midstate nonce-sweep path
    (SURVEY.md §4.5: header bytes 0..63 are constant across a sweep).
  - src/consensus/merkle.cpp:~45 (ComputeMerkleRoot) — one tree level =
    double-SHA of 64-byte concatenated digest pairs (see ops/merkle.py).

Conventions:
  - All hash state/words are big-endian 32-bit words (SHA-256's native view).
  - "limbs" arrays are the hash reinterpreted as a little-endian uint256 (the
    arith_uint256 view used by CheckProofOfWork): limb[j] = bits 32j..32j+31,
    i.e. limb[j] = bswap32(h[j]).
  - Everything is uint32; additions wrap mod 2^32 as SHA requires.

The scalar Python oracle lives in crypto/hashes.py (sha256_compress); tests
differential-check this module against it and hashlib.
"""

from __future__ import annotations

import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.hashes import SHA256_INIT, SHA256_K

U32 = jnp.uint32

_K = [np.uint32(k) for k in SHA256_K]
_INIT = np.array(SHA256_INIT, dtype=np.uint32)

# SHA-256 bit lengths for the message sizes we batch (in the padding word w15).
_LEN_80B = np.uint32(640)
_LEN_64B = np.uint32(512)
_LEN_32B = np.uint32(256)
_PAD_WORD = np.uint32(0x80000000)
_ZERO = np.uint32(0)


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def bswap32(x):
    """Byte-swap each uint32 lane (wire LE <-> SHA BE word views)."""
    return (
        ((x & np.uint32(0xFF)) << np.uint32(24))
        | ((x & np.uint32(0xFF00)) << np.uint32(8))
        | ((x >> np.uint32(8)) & np.uint32(0xFF00))
        | (x >> np.uint32(24))
    )


def backend_is_cpu() -> bool:
    """True when JAX's default backend is the XLA CPU backend. The one
    home for every caller that picks a compile-friendly kernel form per
    backend (here, ops/secp256k1.field_parallel, node._select_sweep)."""
    return jax.default_backend() == "cpu"


def _use_unrolled() -> bool:
    """Unrolled rounds on TPU (best VPU schedule), lax.fori_loop on CPU.

    XLA's CPU backend (LLVM) compiles the fully-unrolled 64-round dataflow
    superlinearly slowly (minutes per variant — measured this session), while
    the TPU (Mosaic/XLA-TPU) handles it fine. The looped form compiles in ms
    everywhere and is the CI/test path; numerics are identical and both forms
    are differential-tested against hashlib. The nonce sweep's tile bodies
    (ops/miner._sweep_tile, ops/sha256_sweep.sweep_fast_jit) ask here too.
    """
    override = os.environ.get("BCP_SHA_UNROLL")
    if override is not None:
        return override not in ("0", "false", "")
    return not backend_is_cpu()


def _compress_unrolled(state8: list, w16: list) -> list:
    ws = list(w16)
    a, b, c, d, e, f, g, h = state8
    for i in range(64):
        if i < 16:
            wi = ws[i]
        else:
            x15, x2 = ws[(i - 15) % 16], ws[(i - 2) % 16]
            s0 = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> np.uint32(3))
            s1 = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> np.uint32(10))
            ws[i % 16] = ws[i % 16] + s0 + ws[(i - 7) % 16] + s1
            wi = ws[i % 16]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + _K[i] + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
    out = (a, b, c, d, e, f, g, h)
    return [s + o for s, o in zip(state8, out)]


_K_ARR = np.array(SHA256_K, dtype=np.uint32)


def _compress_looped(state8: list, w16: list) -> list:
    """fori_loop form with a 16-word rolling schedule ring. The index
    identities (i-15)%16 == (i+1)%16 etc. keep all ring offsets positive."""
    zero = state8[0] * _ZERO
    for w in w16:
        zero = zero + w * _ZERO  # unify broadcast shape across state & words
    ws = jnp.stack([w + zero for w in w16])  # (16, ...)
    k = jnp.asarray(_K_ARR)

    def body(i, carry):
        a, b, c, d, e, f, g, h, ws = carry
        j = jax.lax.rem(i, 16)
        x16 = jax.lax.dynamic_index_in_dim(ws, j, 0, keepdims=False)
        x15 = jax.lax.dynamic_index_in_dim(ws, jax.lax.rem(i + 1, 16), 0, keepdims=False)
        x7 = jax.lax.dynamic_index_in_dim(ws, jax.lax.rem(i + 9, 16), 0, keepdims=False)
        x2 = jax.lax.dynamic_index_in_dim(ws, jax.lax.rem(i + 14, 16), 0, keepdims=False)
        s0w = _rotr(x15, 7) ^ _rotr(x15, 18) ^ (x15 >> np.uint32(3))
        s1w = _rotr(x2, 17) ^ _rotr(x2, 19) ^ (x2 >> np.uint32(10))
        wnew = x16 + s0w + x7 + s1w
        wi = jnp.where(i >= 16, wnew, x16)
        ws = jax.lax.dynamic_update_index_in_dim(ws, wi, j, 0)
        ki = jax.lax.dynamic_index_in_dim(k, i, 0, keepdims=False)
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + ki + wi
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        # rotation in (a..h) carry order: a'=t1+t2, b'=a, ..., e'=d+t1, ...
        return (t1 + t2, a, b, c, d + t1, e, f, g, ws)

    init = tuple(s + zero for s in state8) + (ws,)
    *out, _ = jax.lax.fori_loop(0, 64, body, init)
    return [s + o for s, o in zip(state8, out)]


def compress(state8: list, w16: list) -> list:
    """One SHA-256 compression over a batch — CSHA256::Transform
    (src/crypto/sha256.cpp:~40).

    state8: list of 8 uint32 arrays (broadcastable), w16: list of 16 uint32
    arrays (the message schedule seed). Returns the new state as a list of 8
    arrays. List-of-arrays (SoA) form keeps every round a pure elementwise
    VPU op with no gathers on the unrolled path.
    """
    if _use_unrolled():
        return _compress_unrolled(state8, w16)
    return _compress_looped(state8, w16)


def _init_state(like) -> list:
    """Fresh SHA-256 initial state broadcast against `like`'s shape."""
    zero = like * _ZERO
    return [zero + np.uint32(v) for v in _INIT]


def sha256_of_state(h8: list) -> list:
    """SHA-256 of a 32-byte digest held as 8 state words — the second hash of
    every double-SHA. Single padded block: msg || 0x80 || len=256."""
    zero = h8[0] * _ZERO
    w = list(h8) + [zero + _PAD_WORD] + [zero] * 6 + [zero + _LEN_32B]
    return compress(_init_state(h8[0]), w)


def sha256d_64(w16: list) -> list:
    """Double-SHA256 of a 64-byte message given as 16 BE words (batched).
    The Merkle inner-node hash (src/consensus/merkle.cpp:~45): 3 compressions
    (message block, padding block, second hash)."""
    zero = w16[0] * _ZERO
    h = compress(_init_state(w16[0]), w16)
    pad_block = [zero + _PAD_WORD] + [zero] * 14 + [zero + _LEN_64B]
    h = compress(h, pad_block)
    return sha256_of_state(h)


def sha256d_80(w20: list) -> list:
    """Double-SHA256 of an 80-byte message given as 20 BE words (batched) —
    CBlockHeader::GetHash without midstate reuse (full-header batch path,
    used for validating many headers at once)."""
    zero = w20[0] * _ZERO
    h = compress(_init_state(w20[0]), w20[:16])
    tail_block = (
        w20[16:20] + [zero + _PAD_WORD] + [zero] * 10 + [zero + _LEN_80B]
    )
    h = compress(h, tail_block)
    return sha256_of_state(h)


def header_sweep_digest(midstate8: list, tail3: list, nonces):
    """SHA-256d digests for a nonce sweep from a precomputed midstate.

    midstate8: 8 scalars/arrays — SHA-256 state after header bytes 0..63
    (crypto/hashes.header_midstate). tail3: BE words of header bytes 64..75
    (merkle tail, nTime, nBits). nonces: uint32 array of candidate nonces
    (host byte order; the header stores them LE so the BE message word is
    bswap32(nonce)).

    Returns 8 digest state words, each shaped like `nonces`. Cost: 2
    compressions per nonce (vs 3 without midstate) — the optimization the
    scalar reference loop (src/rpc/mining.cpp:~120) misses.

    This is the UNHOISTED form, the sweep tile's digest on the CPU backend;
    on accelerators ops/miner._sweep_tile rides sweep_digest_hoisted,
    which additionally hoists the chunk-2 sweep-constant rounds/schedule
    legs per template (ROOFLINE.md §8); tests differential the two.
    """
    zero = nonces * _ZERO
    w = (
        [zero + t for t in tail3]
        + [bswap32(nonces)]
        + [zero + _PAD_WORD]
        + [zero] * 10
        + [zero + _LEN_80B]
    )
    h = compress([zero + m for m in midstate8], w)
    return sha256_of_state(h)


def digest_to_limbs(h8: list) -> list:
    """Reinterpret digest state words as little-endian uint256 limbs
    (arith_uint256 view): limb[j] = bswap32(h[j]), limb 7 most significant."""
    return [bswap32(h) for h in h8]


def le256(limbs: list, target_limbs: list):
    """Branchless lexicographic hash <= target over LE limb arrays —
    CheckProofOfWork's arith_uint256 compare (src/pow.cpp:~74), evaluated
    per lane from the most significant limb down."""
    le = limbs[0] <= target_limbs[0]
    for j in range(1, 8):
        l, t = limbs[j], target_limbs[j]
        le = (l < t) | ((l == t) & le)
    return le


# ---- host-side packing helpers (numpy, not traced) ----

def target_to_limbs_np(target: int) -> np.ndarray:
    """256-bit target -> 8 LE uint32 limbs for the on-chip compare."""
    return np.array(
        [(target >> (32 * j)) & 0xFFFFFFFF for j in range(8)], dtype=np.uint32
    )


def digests_to_bytes(h8) -> np.ndarray:
    """Device digest state (8 arrays shaped (...,)) -> (..., 32) uint8 wire
    digests (BE bytes per word, as SHA outputs)."""
    stacked = np.stack([np.asarray(h) for h in h8], axis=-1)  # (..., 8)
    return stacked.astype(">u4").view(np.uint8).reshape(*stacked.shape[:-1], 32)


def bytes_to_words_np(data: np.ndarray) -> np.ndarray:
    """(..., 4k) uint8 byte array -> (..., k) uint32 BE words."""
    assert data.dtype == np.uint8 and data.shape[-1] % 4 == 0
    return (
        data.reshape(*data.shape[:-1], data.shape[-1] // 4, 4)
        .view(">u4")  # big-endian words, SHA's native view
        .squeeze(-1)
        .astype(np.uint32)
    )


def headers_to_words_np(headers: np.ndarray) -> np.ndarray:
    """(B, 80) uint8 serialized headers -> (B, 20) uint32 BE words."""
    assert headers.shape[-1] == 80
    return bytes_to_words_np(headers)


# ---- jitted batch entry points ----

@jax.jit
def sha256d_headers_jit(words20):
    """(B, 20) uint32 BE header words -> (B, 8) digest state words."""
    h8 = sha256d_80([words20[:, i] for i in range(20)])
    return jnp.stack(h8, axis=-1)


@jax.jit
def check_headers_pow_jit(words20, target_limbs):
    """(B, 20) header words + (8,) target limbs -> ((B,8) digests, (B,) ok).
    Batch header PoW validation for headers-first sync / reindex."""
    h8 = sha256d_80([words20[:, i] for i in range(20)])
    ok = le256(digest_to_limbs(h8), [target_limbs[j] for j in range(8)])
    return jnp.stack(h8, axis=-1), ok


def sha256d_headers_cpu(headers: np.ndarray) -> np.ndarray:
    """Reference CPU engine for the batch header hash — the sha256 circuit
    breaker's fallback target (ops/dispatch)."""
    from ..crypto.hashes import sha256d

    return np.frombuffer(
        b"".join(sha256d(headers[i].tobytes())
                 for i in range(headers.shape[0])),
        dtype=np.uint8,
    ).reshape(-1, 32)


def sha256d_headers(headers: np.ndarray) -> np.ndarray:
    """Convenience host API: (B, 80) uint8 headers -> (B, 32) uint8 digests.

    Supervised (ops/dispatch): the device batch is spot-checked against the
    host hash of lane 0 before it is trusted; failures/poison degrade to
    the per-header CPU loop without changing a single digest. The device
    leg is watched (util/devicewatch): header batches legitimately vary
    in size, so the program carries NO shape budget — compiles are
    counted and timed, never flagged."""
    from ..crypto.hashes import sha256d
    from ..util import devicewatch as dw
    from . import dispatch

    if headers.shape[0] == 0:
        return np.zeros((0, 32), dtype=np.uint8)

    def device() -> np.ndarray:
        words_np = headers_to_words_np(headers)
        dw.note_transfer("sha256", "h2d", int(words_np.nbytes))
        words = jnp.asarray(words_np)
        with dw.program("sha256_headers").dispatch(
                words_np.shape, jitfn=sha256d_headers_jit,
                args=(words_np,)):
            h = sha256d_headers_jit(words)
        t0 = time.perf_counter()
        out = digests_to_bytes([np.asarray(h[:, i]) for i in range(8)])
        dw.note_transfer("sha256", "d2h", int(out.nbytes),
                         seconds=time.perf_counter() - t0)
        return out

    def validate(digests: np.ndarray) -> bool:
        return digests[0].tobytes() == sha256d(headers[0].tobytes())

    out, _ = dispatch.supervised_call(
        "sha256", device, lambda: sha256d_headers_cpu(headers),
        validate=validate,
        poison=lambda d: np.bitwise_xor(d, np.uint8(0xFF)),
        items=int(headers.shape[0]),
    )
    return out
