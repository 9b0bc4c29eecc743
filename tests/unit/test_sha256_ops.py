"""Differential tests: TPU (jnp) SHA-256d paths vs hashlib / the Python
oracle — the reference's crypto_tests.cpp + randomized-equivalence strategy
(SURVEY.md §5.4.4)."""

import hashlib
import os
import struct

import numpy as np
import pytest

from bitcoincashplus_tpu.consensus.block import CBlockHeader
from bitcoincashplus_tpu.consensus.merkle import compute_merkle_root
from bitcoincashplus_tpu.consensus.params import main_params, regtest_params
from bitcoincashplus_tpu.consensus.pow import compact_to_target
from bitcoincashplus_tpu.crypto.hashes import header_midstate, sha256d
from bitcoincashplus_tpu.ops import miner as tpu_miner
from bitcoincashplus_tpu.ops import sha256 as ops_sha
from bitcoincashplus_tpu.ops.merkle import compute_merkle_root_tpu
from bitcoincashplus_tpu.ops.sha256_sweep import sweep_header_fast

import jax.numpy as jnp

rng = np.random.default_rng(1234)


def _random_headers(n):
    return rng.integers(0, 256, size=(n, 80), dtype=np.uint8)


class TestBatchedHeaderHash:
    def test_vs_hashlib_random(self):
        headers = _random_headers(257)
        got = ops_sha.sha256d_headers(headers)
        for i in range(len(headers)):
            expect = sha256d(headers[i].tobytes())
            assert got[i].tobytes() == expect

    def test_genesis_header(self):
        params = main_params()
        h80 = params.genesis.header.serialize()
        got = ops_sha.sha256d_headers(np.frombuffer(h80, np.uint8).reshape(1, 80))
        assert got[0].tobytes() == params.genesis.get_hash()

    def test_pow_check_batch(self):
        params = main_params()
        h80 = params.genesis.header.serialize()
        bad = bytearray(h80)
        bad[76] ^= 1  # wrong nonce
        headers = np.stack(
            [np.frombuffer(bytes(x), np.uint8) for x in (h80, bytes(bad))]
        )
        target, _ = compact_to_target(params.genesis.header.bits)
        words = jnp.asarray(ops_sha.headers_to_words_np(headers))
        tgt = jnp.asarray(ops_sha.target_to_limbs_np(target))
        _, ok = ops_sha.check_headers_pow_jit(words, tgt)
        assert bool(ok[0]) and not bool(ok[1])


class TestSweepDigest:
    def test_midstate_sweep_vs_hashlib(self):
        header = _random_headers(1)[0].tobytes()
        midstate = np.array(header_midstate(header), dtype=np.uint32)
        tail = ops_sha.bytes_to_words_np(np.frombuffer(header[64:76], np.uint8))
        nonces = rng.integers(0, 1 << 32, size=64, dtype=np.uint32)
        h8 = ops_sha.header_sweep_digest(
            [jnp.uint32(m) for m in midstate],
            [jnp.uint32(t) for t in tail],
            jnp.asarray(nonces),
        )
        digests = ops_sha.digests_to_bytes([np.asarray(h) for h in h8])
        for i, n in enumerate(nonces):
            expect = sha256d(header[:76] + struct.pack("<I", int(n)))
            assert digests[i].tobytes() == expect

    def test_limb_compare_vs_python_int(self):
        # Random 256-bit hash/target pairs: limb compare == int compare.
        hashes = rng.integers(0, 256, size=(128, 32), dtype=np.uint8)
        target = int.from_bytes(rng.integers(0, 256, size=32, dtype=np.uint8).tobytes(), "little")
        # hash words (BE view of digest bytes) -> limbs
        h_words = ops_sha.bytes_to_words_np(hashes)
        limbs = [jnp.asarray(ops_sha.bswap32(h_words[:, j])) for j in range(8)]
        tgt = ops_sha.target_to_limbs_np(target)
        got = np.asarray(ops_sha.le256(limbs, [jnp.uint32(t) for t in tgt]))
        for i in range(len(hashes)):
            expect = int.from_bytes(hashes[i].tobytes(), "little") <= target
            assert bool(got[i]) == expect


class TestSweep:
    # both host APIs, jitted, on the backend the tests run on (CPU), at
    # the tile generate_blocks gives them: the pair that stood still for
    # fifteen PRs (ops/miner._sweep_tile)
    SWEEPS = pytest.mark.parametrize(
        "sweep", [tpu_miner.sweep_header, sweep_header_fast],
        ids=["generic", "fast"])

    @SWEEPS
    def test_finds_known_nonce_regtest(self, sweep):
        """Mine a regtest-difficulty header: the scalar loop's nonce."""
        params = regtest_params()
        hdr = CBlockHeader(
            version=0x20000000,
            hash_prev_block=params.genesis.get_hash(),
            hash_merkle_root=rng.integers(0, 256, 32, dtype=np.uint8).tobytes(),
            time=1_300_000_000,
            bits=0x207FFFFF,
            nonce=0,
        )
        target, _ = compact_to_target(hdr.bits)
        nonce, hashes = sweep(
            hdr.serialize(), target, tile=4096, max_nonces=1 << 20
        )
        assert nonce is not None
        mined = hdr.with_nonce(nonce)
        assert int.from_bytes(mined.get_hash(), "little") <= target
        want, _ = tpu_miner.sweep_header_cpu(hdr.serialize(), target,
                                             max_nonces=4096)
        assert nonce == want

    @SWEEPS
    def test_first_hit_and_the_next_equal_scalar_loop(self, sweep):
        hdr = _random_headers(1)[0].tobytes()
        target, _ = compact_to_target(0x1F7FFFFF)  # a hit every ~512
        want, _ = tpu_miner.sweep_header_cpu(hdr, target, max_nonces=1 << 14)
        nonce, hashes = sweep(hdr, target, tile=4096, max_nonces=1 << 14)
        assert want is not None and nonce == want
        assert hashes == (want // 4096 + 1) * 4096
        want2, _ = tpu_miner.sweep_header_cpu(
            hdr, target, start_nonce=want + 1, max_nonces=1 << 14)
        nonce2, _ = sweep(hdr, target, start_nonce=want + 1, tile=4096,
                          max_nonces=1 << 14)
        assert nonce2 == want2

    @SWEEPS
    def test_not_found_at_impossible_target(self, sweep):
        hdr = _random_headers(1)[0].tobytes()
        nonce, hashes = sweep(hdr, target=0, max_nonces=1 << 14, tile=4096)
        assert nonce is None
        assert hashes == 1 << 14

    def test_nonce_wraparound(self):
        """Sweep starting near 2^32 wraps like the reference's uint32."""
        params = regtest_params()
        hdr = CBlockHeader(
            version=1, hash_prev_block=b"\x11" * 32, hash_merkle_root=b"\x22" * 32,
            time=1_300_000_123, bits=0x207FFFFF, nonce=0,
        )
        target, _ = compact_to_target(hdr.bits)
        nonce, _ = tpu_miner.sweep_header(
            hdr.serialize(), target, start_nonce=(1 << 32) - 2048, tile=4096,
            max_nonces=1 << 16,
        )
        assert nonce is not None
        assert int.from_bytes(hdr.with_nonce(nonce).get_hash(), "little") <= target


class TestMerkleTPU:
    @pytest.mark.parametrize("n", [1, 2, 3, 5, 32, 33, 127, 513])
    def test_vs_cpu(self, n):
        hashes = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(n)]
        root_cpu, mut_cpu = compute_merkle_root(hashes)
        root_tpu, mut_tpu = compute_merkle_root_tpu(hashes)
        assert root_cpu == root_tpu
        assert mut_cpu == mut_tpu

    @pytest.mark.parametrize("n,dup_tail", [(3, 1), (6, 2)])
    def test_mutation_detected(self, n, dup_tail):
        """CVE-2012-2459: appending a copy of the final odd-duplicated
        node(s) yields the SAME root but must set the mutated flag."""
        h = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(n)]
        dup = h + h[-dup_tail:]
        root_cpu, mut_cpu = compute_merkle_root(dup)
        root_tpu, mut_tpu = compute_merkle_root_tpu(dup)
        assert root_cpu == root_tpu
        assert mut_cpu and mut_tpu
        # and the mutated root equals the honest root (the actual CVE)
        assert root_cpu == compute_merkle_root(h)[0]

    def test_odd_duplication_not_flagged(self):
        h = [rng.integers(0, 256, 32, dtype=np.uint8).tobytes() for _ in range(3)]
        _, mutated = compute_merkle_root_tpu(h)
        assert not mutated

    def test_empty(self):
        assert compute_merkle_root_tpu([]) == (b"\x00" * 32, False)
