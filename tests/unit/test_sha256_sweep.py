"""Differential tests for the specialized truncated-h7 sweep kernel
(ops/sha256_sweep.py) against the hashlib scalar oracle."""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from bitcoincashplus_tpu.crypto.hashes import header_midstate, sha256d
from bitcoincashplus_tpu.ops.sha256 import bytes_to_words_np, target_to_limbs_np
from bitcoincashplus_tpu.ops import miner
from bitcoincashplus_tpu.ops.sha256_sweep import sweep_h7, sweep_header_fast


def _oracle_h7(header80: bytes) -> int:
    """Digest word h[7] (BE) of sha256d(header) == digest bytes 28..32."""
    return int.from_bytes(sha256d(header80)[28:32], "big")


def _parts(header80):
    mid = np.array(header_midstate(header80), dtype=np.uint32)
    tail = bytes_to_words_np(np.frombuffer(header80[64:76], dtype=np.uint8))
    return mid, tail


def test_h7_matches_oracle_numpy_consts():
    """Trace-time-folded path: midstate/tail as numpy scalars."""
    rng = np.random.default_rng(7)
    header = rng.integers(0, 256, size=80, dtype=np.uint8).tobytes()
    mid, tail = _parts(header)
    nonces = rng.integers(0, 2**32, size=64, dtype=np.uint32)
    h7 = np.asarray(sweep_h7(list(mid), list(tail), jnp.asarray(nonces)))
    for i, n in enumerate(nonces):
        hdr = header[:76] + int(n).to_bytes(4, "little")
        assert int(h7[i]) == _oracle_h7(hdr)


def test_h7_matches_oracle_device_scalars():
    """The resident loop's path: midstate/tail as device arrays, so the
    hoist runs in jax scalars instead of folding in numpy. Eager on
    purpose: the subject is the unrolled form's arithmetic, which
    XLA:CPU cannot run jitted (ops/miner._sweep_tile); the
    chip compiles it (test_chip_compile.py) and runs it (chip_smoke.py)."""
    import jax

    rng = np.random.default_rng(8)
    header = rng.integers(0, 256, size=80, dtype=np.uint8).tobytes()
    mid, tail = _parts(header)

    def f(mid, tail, nonces):
        return sweep_h7([mid[i] for i in range(8)], [tail[i] for i in range(3)], nonces)

    nonces = rng.integers(0, 2**32, size=32, dtype=np.uint32)
    with jax.disable_jit():
        h7 = np.asarray(f(jnp.asarray(mid), jnp.asarray(tail), jnp.asarray(nonces)))
    for i, n in enumerate(nonces):
        hdr = header[:76] + int(n).to_bytes(4, "little")
        assert int(h7[i]) == _oracle_h7(hdr)


def test_sweep_fast_agrees_with_generic_sweep():
    """Same first-hit nonce as ops.miner.sweep_header on a regtest-easy
    target (exercises the candidate/verify/resume loop end to end)."""
    header = bytes(range(80))
    target = (1 << 255) - 1  # ~every second hash passes: forces candidates
    n_ref, _ = miner.sweep_header(header, target, max_nonces=1 << 10, tile=1 << 7)
    n_fast, _ = sweep_header_fast(header, target, max_nonces=1 << 10, tile=1 << 7)
    assert n_ref is not None and n_fast == n_ref


def test_sweep_fast_false_positive_resume():
    """A target whose top limb matches some hash's limb7 while the full
    256-bit compare fails forces the candidate/reject/resume path: pick the
    target just below a known hash so limb7 ties but the hash is > target."""
    header = b"\xab" * 80
    # hash of nonce 0 for this header
    h0 = int.from_bytes(sha256d(header[:76] + b"\x00" * 4), "little")
    target = h0 - 1  # limb7 equal (almost surely), full compare fails
    nonce, _ = sweep_header_fast(header, target, max_nonces=1 << 9, tile=1 << 7)
    if nonce is not None:
        hdr = header[:76] + nonce.to_bytes(4, "little")
        assert int.from_bytes(sha256d(hdr), "little") <= target
        assert nonce != 0


def test_sweep_fast_regtest_difficulty():
    """Regtest-grade target (top limb 0x007fffff): hit must exact-verify
    and be the first passing nonce."""
    header = b"\xab" * 80
    target = 0x7FFFFF << (8 * 29)
    nonce, hashes = sweep_header_fast(header, target, max_nonces=1 << 14, tile=1 << 9)
    assert nonce is not None
    hdr = header[:76] + nonce.to_bytes(4, "little")
    assert int.from_bytes(sha256d(hdr), "little") <= target
    # and it is the FIRST such nonce
    for n in range(nonce):
        h = header[:76] + n.to_bytes(4, "little")
        assert int.from_bytes(sha256d(h), "little") > target


def test_sweep_fast_no_hit():
    """Impossible target: full sweep, no result, correct hash count."""
    header = b"\x01" * 80
    nonce, hashes = sweep_header_fast(header, 0, max_nonces=1 << 9, tile=1 << 7)
    # limb7 == 0 prefilter can fire spuriously only with p ~ 2^-32; with 512
    # nonces a candidate is (overwhelmingly) never produced, and any produced
    # candidate would be rejected by the exact host check anyway.
    assert nonce is None
    assert hashes >= 1 << 9
