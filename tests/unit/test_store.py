"""Persistence tests: KV batch atomicity, block files, coins DB round-trip —
the reference's dbwrapper_tests.cpp / coins_tests.cpp flush coverage."""

import os

import pytest

from bitcoincashplus_tpu.consensus.params import regtest_params
from bitcoincashplus_tpu.consensus.tx import COutPoint, CTxOut
from bitcoincashplus_tpu.store.blockstore import BlockStore, MemoryBlockStore
from bitcoincashplus_tpu.store.chainstatedb import BlockIndexDB, CoinsDB
from bitcoincashplus_tpu.store.kvstore import KVStore
from bitcoincashplus_tpu.validation.coins import Coin, CoinsCache


class TestKVStore:
    def test_put_get_delete(self, tmp_path):
        kv = KVStore(str(tmp_path / "kv.sqlite"))
        kv.put(b"a", b"1")
        assert kv.get(b"a") == b"1"
        kv.put(b"a", b"2")
        assert kv.get(b"a") == b"2"
        kv.delete(b"a")
        assert kv.get(b"a") is None

    def test_batch_and_ordered_iteration(self, tmp_path):
        kv = KVStore(str(tmp_path / "kv.sqlite"))
        kv.write_batch({b"Cb": b"2", b"Ca": b"1", b"D": b"x"}, [])
        assert [k for k, _ in kv.iterate(b"C")] == [b"Ca", b"Cb"]
        kv.write_batch({}, [b"Ca"])
        assert [k for k, _ in kv.iterate(b"C")] == [b"Cb"]

    def test_reopen_persists(self, tmp_path):
        path = str(tmp_path / "kv.sqlite")
        kv = KVStore(path)
        kv.write_batch({b"k": b"v"}, [], sync=True)
        kv.close()
        assert KVStore(path).get(b"k") == b"v"


class TestBlockStore:
    def test_roundtrip_and_framing(self, tmp_path):
        params = regtest_params()
        bs = BlockStore(str(tmp_path), params.netmagic)
        raw = params.genesis.serialize()
        h = params.genesis_hash
        bs.put_block(h, raw)
        bs.put_undo(h, b"\x00")
        assert bs.get_block(h) == raw
        assert bs.get_undo(h) == b"\x00"
        bs.flush()
        # on-disk framing: netmagic + LE size + payload (reference layout)
        with open(os.path.join(str(tmp_path), "blocks", "blk00000.dat"), "rb") as f:
            data = f.read()
        assert data[:4] == params.netmagic
        assert int.from_bytes(data[4:8], "little") == len(raw)
        assert data[8 : 8 + len(raw)] == raw

    def test_positions_reusable_after_reopen(self, tmp_path):
        params = regtest_params()
        bs = BlockStore(str(tmp_path), params.netmagic)
        raw = params.genesis.serialize()
        h = params.genesis_hash
        bs.put_block(h, raw)
        pos = bs.positions[h]
        bs.flush()
        bs.close()
        bs2 = BlockStore(str(tmp_path), params.netmagic)
        bs2.positions[h] = pos  # normally restored via BlockIndexDB
        assert bs2.get_block(h) == raw


class TestCoinsDB:
    def test_flush_and_reload(self, tmp_path):
        kv = KVStore(str(tmp_path / "chainstate.sqlite"))
        db = CoinsDB(kv)
        cache = CoinsCache(db)
        op = COutPoint(b"\xaa" * 32, 1)
        coin = Coin(CTxOut(777, b"\x51"), 9, False)
        cache.add_coin(op, coin)
        cache.set_best_block(b"\xbb" * 32)
        cache.flush()
        # fresh cache over the same DB sees the flushed state
        cache2 = CoinsCache(CoinsDB(kv))
        assert cache2.get_coin(op) == coin
        assert cache2.best_block() == b"\xbb" * 32
        # spend + flush removes it
        cache2.spend_coin(op)
        cache2.flush()
        assert CoinsDB(kv).get_coin(op) is None

    def test_tombstone_layering(self, tmp_path):
        kv = KVStore(str(tmp_path / "cs.sqlite"))
        db = CoinsDB(kv)
        l1 = CoinsCache(db)
        op = COutPoint(b"\xcc" * 32, 0)
        l1.add_coin(op, Coin(CTxOut(5, b""), 1, False))
        l2 = CoinsCache(l1)
        assert l2.get_coin(op) is not None
        l2.spend_coin(op)
        assert l2.get_coin(op) is None
        assert l1.get_coin(op) is not None  # not yet merged
        l2.flush()
        assert l1.get_coin(op) is None  # tombstone propagated


class TestBlockIndexDB:
    def test_index_roundtrip(self, tmp_path):
        params = regtest_params()
        kv = KVStore(str(tmp_path / "index.sqlite"))
        db = BlockIndexDB(kv)
        h = params.genesis_hash
        db.put_index_batch(
            [(h, params.genesis.header.serialize(), 0, 0x1D, 1, (0, 8, 285), None)]
        )
        rows = list(db.iterate_index())
        assert len(rows) == 1
        rh, header, height, status, n_tx, blkpos, undopos = rows[0]
        assert rh == h
        assert header.get_hash() == h
        assert (height, status, n_tx) == (0, 0x1D, 1)
        assert blkpos == (0, 8, 285) and undopos is None

    def test_flags(self, tmp_path):
        kv = KVStore(str(tmp_path / "index.sqlite"))
        db = BlockIndexDB(kv)
        assert not db.get_flag(b"txindex")
        db.put_flag(b"txindex", True)
        assert db.get_flag(b"txindex")


def test_concurrent_write_batches_serialize(tmp_path):
    """Two threads batching into one store must not interleave sqlite
    transactions ('cannot start a transaction within a transaction' — the
    txindex-backfill-vs-init race)."""
    import threading

    from bitcoincashplus_tpu.store.kvstore import KVStore

    kv = KVStore(str(tmp_path / "kv.sqlite"))
    errors = []

    def writer(tag: bytes):
        try:
            for i in range(200):
                kv.write_batch({tag + bytes([i % 256]): tag * 4})
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(bytes([t]),))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert kv.get(b"\x00\x00") is not None
    kv.close()


@pytest.mark.parametrize("stores", [2, 4, 8])
def test_stores_written_at_once_take_turns_at_their_rows(tmp_path, stores):
    """The coins shards' flush pool: batches written to several stores at
    the same time bind their rows one batch at a time (kvstore._ROWS_LOCK:
    together the threads hand the GIL over once a row), and each store
    ends with its own rows, its deletes applied, nobody else's."""
    import threading

    from bitcoincashplus_tpu.store import kvstore

    kvs = [KVStore(str(tmp_path / f"kv{t}.sqlite")) for t in range(stores)]
    for t, kv in enumerate(kvs):
        kv.write_batch({bytes([t]) + b"old" + bytes([i]): b"x"
                        for i in range(50)})
    seen = []
    held = kvstore._ROWS_LOCK
    real = held.acquire

    class Watch:
        """The lock, noting how many threads are inside it at a time."""

        def __init__(self):
            self.inside = 0

        def acquire(self):
            real()
            self.inside += 1
            seen.append(self.inside)

        def release(self):
            self.inside -= 1
            held.release()

    kvstore._ROWS_LOCK = Watch()
    try:
        def writer(t: int):
            kvs[t].write_batch(
                {bytes([t]) + i.to_bytes(2, "big"): bytes([t]) * 8
                 for i in range(2000)},
                [bytes([t]) + b"old" + bytes([i]) for i in range(50)],
                sync=True)

        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(stores)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        kvstore._ROWS_LOCK = held
    assert seen == [1] * stores
    for t, kv in enumerate(kvs):
        rows = list(kv.iterate())
        assert len(rows) == 2000
        assert all(k[0] == t and v == bytes([t]) * 8 for k, v in rows)
        kv.close()
