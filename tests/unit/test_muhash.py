"""MuHash3072 accumulator algebra (store/muhash.py).

The sharded chainstate's set digest must be a true multiset homomorphism:
order/partition independent, invertible, and the numpy limb batch-product
path must agree bit-for-bit with the python-int reference. These are the
properties the cross-shard digest, snapshot verification, and the
incremental commit-time maintenance all lean on.
"""

import random

import pytest

from bitcoincashplus_tpu.store import muhash


def _rand_elems(rng, n):
    return [muhash.element(rng.randbytes(rng.randint(1, 80)))
            for _ in range(n)]


class TestElement:
    def test_element_is_reduced_and_nonzero(self):
        rng = random.Random(1)
        for _ in range(50):
            e = muhash.element(rng.randbytes(40))
            assert 0 < e < muhash.MUHASH_P

    def test_element_deterministic(self):
        assert muhash.element(b"abc") == muhash.element(b"abc")
        assert muhash.element(b"abc") != muhash.element(b"abd")

    def test_coin_element_binds_key_and_value(self):
        k = b"k" * 36
        assert muhash.coin_element(k, b"v1") != muhash.coin_element(k, b"v2")
        assert muhash.coin_element(k, b"v1") != \
            muhash.coin_element(b"j" * 36, b"v1")


class TestAccumulator:
    def test_insert_remove_roundtrip(self):
        acc = muhash.MuHash()
        base = acc.digest()
        acc.insert(b"one")
        acc.insert(b"two")
        acc.remove(b"one")
        acc.remove(b"two")
        assert acc.digest() == base

    def test_order_independence(self):
        items = [b"a", b"b", b"c", b"d"]
        a, b = muhash.MuHash(), muhash.MuHash()
        for it in items:
            a.insert(it)
        for it in reversed(items):
            b.insert(it)
        assert a.digest() == b.digest()

    def test_apply_batch_equals_singles(self):
        rng = random.Random(2)
        added = [rng.randbytes(20) for _ in range(17)]
        removed = added[:5]
        a = muhash.MuHash()
        for it in added:
            a.insert(it)
        for it in removed:
            a.remove(it)
        b = muhash.MuHash()
        b.apply([muhash.element(x) for x in added],
                [muhash.element(x) for x in removed])
        assert a.digest() == b.digest()

    def test_serialization_roundtrip(self):
        acc = muhash.MuHash()
        acc.insert(b"state")
        again = muhash.MuHash.from_bytes(acc.to_bytes())
        assert again.digest() == acc.digest()
        assert len(acc.to_bytes()) == 384

    def test_partition_independence(self):
        """digest(all) == digest(combine(per-shard states)) for any split
        — the cross-shard invariant gettxoutsetinfo relies on."""
        rng = random.Random(3)
        items = [rng.randbytes(30) for _ in range(40)]
        whole = muhash.MuHash()
        shards = [muhash.MuHash() for _ in range(4)]
        for it in items:
            whole.insert(it)
            shards[rng.randrange(4)].insert(it)
        combined = muhash.combine([s.state for s in shards])
        assert muhash.digest_of(combined) == whole.digest()


class TestBatchProduct:
    @pytest.mark.parametrize("n", [1, 2, 7, 8, 9, 31, 64, 100])
    def test_limb_backend_matches_reference(self, n):
        if muhash._np is None:
            pytest.skip("numpy unavailable")
        rng = random.Random(n)
        vals = _rand_elems(rng, n)
        assert muhash._batch_product_limbs(vals) == \
            muhash.batch_product_ref(vals)

    @pytest.mark.parametrize("n", [1, 8, 100])
    def test_dispatch_matches_reference(self, n):
        rng = random.Random(100 + n)
        vals = _rand_elems(rng, n)
        assert muhash.batch_product(vals) == muhash.batch_product_ref(vals)

    def test_values_near_p(self):
        """Reduction edge: products whose partial results straddle p."""
        if muhash._np is None:
            pytest.skip("numpy unavailable")
        vals = [muhash.MUHASH_P - 1, muhash.MUHASH_P - 2,
                muhash.MUHASH_P - muhash.MUHASH_C, 2, 3, 5, 7, 11]
        assert muhash._batch_product_limbs(vals) == \
            muhash.batch_product_ref(vals)

    def test_empty(self):
        assert muhash.batch_product([]) == 1

    def test_limb_roundtrip(self):
        if muhash._np is None:
            pytest.skip("numpy unavailable")
        rng = random.Random(5)
        vals = _rand_elems(rng, 8)
        limbs = muhash._to_limbs(vals)
        assert [muhash._from_limbs(limbs[i]) for i in range(8)] == vals


class TestNativeBackend:
    """native/muhash.cpp against the python-int specification: the product
    (48 limbs, the fold, threads) and the hash-to-group beside it."""

    @pytest.fixture(autouse=True)
    def _needs_library(self):
        from bitcoincashplus_tpu import native

        if not native.available():
            pytest.skip("native library unavailable")
        self.native = native

    @pytest.mark.parametrize("n", [0, 1, 2, 63, 64, 255, 256, 257, 3000])
    def test_product_matches_reference(self, n):
        rng = random.Random(200 + n)
        vals = _rand_elems(rng, n)
        assert self.native.muhash_product(vals) == \
            muhash.batch_product_ref(vals)

    def test_product_of_values_at_the_fold(self):
        """Operands at and above p (any value below 2^3072 is taken),
        partial products that straddle it, and the one bit the fold's own
        addition can carry out."""
        top = (1 << 3072) - 1
        vals = [muhash.MUHASH_P - 1, muhash.MUHASH_P - 2, muhash.MUHASH_P,
                muhash.MUHASH_P - muhash.MUHASH_C, top, top - 1, 1, 2, 3]
        for k in range(1, len(vals) + 1):
            assert self.native.muhash_product(vals[:k]) == \
                muhash.batch_product_ref(vals[:k]), k
        assert self.native.muhash_product([top] * 300) == \
            muhash.batch_product_ref([top] * 300)

    # SHAKE256's rate is 136 bytes: an empty row, one short of a block,
    # a whole block, one over, and rows of several blocks
    @pytest.mark.parametrize("size", [0, 1, 36, 61, 135, 136, 137, 271,
                                      272, 273, 1000, 10000])
    def test_element_product_matches_reference(self, size):
        rng = random.Random(300 + size)
        rows = [rng.randbytes(size) for _ in range(5)]
        assert self.native.muhash_element_product(rows[:1]) == \
            muhash.element(rows[0])
        assert self.native.muhash_element_product(rows) == \
            muhash.batch_product_ref(map(muhash.element, rows))

    @pytest.mark.parametrize("n", [0, 1, 63, 64, 1000])
    def test_coin_product_matches_coin_elements(self, n):
        """Both sides of coin_product's floor, rows of mixed lengths."""
        rng = random.Random(400 + n)
        rows = [(rng.randbytes(36), rng.randbytes(rng.randint(4, 300)))
                for _ in range(n)]
        assert muhash.coin_product(iter(rows)) == muhash.batch_product_ref(
            muhash.coin_element(k, ser) for k, ser in rows)

    @pytest.mark.parametrize("n", [64, 500])
    def test_dispatch_takes_the_library_and_matches(self, n, monkeypatch):
        rng = random.Random(500 + n)
        vals = _rand_elems(rng, n)
        calls = []
        real = self.native.muhash_product
        monkeypatch.setattr(self.native, "muhash_product",
                            lambda v: calls.append(len(v)) or real(v))
        assert muhash.batch_product(vals) == muhash.batch_product_ref(vals)
        assert calls == [n]

    def test_without_the_library_the_python_ints_answer(self, monkeypatch):
        monkeypatch.setattr(muhash, "_native", lambda: None)
        rng = random.Random(6)
        rows = [(rng.randbytes(36), rng.randbytes(30)) for _ in range(80)]
        assert muhash.coin_product(rows) == muhash.batch_product_ref(
            muhash.coin_element(k, ser) for k, ser in rows)
        vals = _rand_elems(rng, 80)
        assert muhash.batch_product(vals) == muhash.batch_product_ref(vals)
