"""Persistence tests: KV batch atomicity, block files, coins DB round-trip —
the reference's dbwrapper_tests.cpp / coins_tests.cpp flush coverage."""

import os
import sqlite3

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


_CHUNK = 64


@pytest.fixture
def chunk(monkeypatch):
    """Rows a statement, cut down so that a batch of several chunks and a
    tail is a few hundred rows; a store reads it when it is opened."""
    from bitcoincashplus_tpu.store import kvstore

    monkeypatch.setattr(kvstore, "ROWS_PER_STATEMENT", _CHUNK)
    return _CHUNK


@pytest.mark.parametrize("stores", [2, 4, 8])
def test_stores_written_at_once_each_end_with_their_own_rows(
        tmp_path, chunk, stores):
    """The coins shards' flush pool: batches of several chunks and a tail,
    written to several stores at the same time with nothing between the
    threads but the GIL, and each store ends with its own rows, its
    deletes applied, nobody else's."""
    import threading

    n_old, n_new = 2 * chunk + 5, 3 * chunk + 7
    kvs = [KVStore(str(tmp_path / f"kv{t}.sqlite")) for t in range(stores)]
    for t, kv in enumerate(kvs):
        kv.write_batch({bytes([t]) + b"old" + i.to_bytes(2, "big"): b"x"
                        for i in range(n_old)})
    wrote = [None] * stores

    def writer(t: int):
        wrote[t] = kvs[t].write_batch(
            {bytes([t]) + i.to_bytes(2, "big"): bytes([t]) * 8
             for i in range(n_new)},
            [bytes([t]) + b"old" + i.to_bytes(2, "big")
             for i in range(1, n_old)],
            sync=True)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(stores)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)
    assert wrote == [3 + 4] * stores
    for t, kv in enumerate(kvs):
        rows = dict(kv.iterate())
        assert rows.pop(bytes([t]) + b"old\x00\x00") == b"x"  # not deleted
        assert len(rows) == n_new
        assert all(k[0] == t and v == bytes([t]) * 8 for k, v in rows.items())
        kv.close()


def _batch_of(n_rows):
    """(what the store holds first, puts, deletes, rows after, row
    statements) of a batch of n_rows puts."""
    puts = {b"p" + i.to_bytes(3, "big"): i.to_bytes(4, "big")
            for i in range(n_rows)}
    return {}, puts, [], puts, -(-n_rows // _CHUNK)


def _deletes_and_puts():
    before = {b"d" + i.to_bytes(3, "big"): b"old" for i in range(3 * _CHUNK)}
    deletes = sorted(before)[:2 * _CHUNK + 1]
    puts = {b"p" + i.to_bytes(3, "big"): b"new" for i in range(_CHUNK + 1)}
    after = {k: v for k, v in before.items() if k not in deletes}
    return before, puts, deletes, {**after, **puts}, 3 + 2


def _key_in_both():
    """Deletes go before puts, whichever chunks the two fall into: a key
    in both ends as the put."""
    before = {b"k" + i.to_bytes(3, "big"): b"old" for i in range(2 * _CHUNK)}
    puts = {k: b"put" for k in sorted(before)[_CHUNK - 1:_CHUNK + 1]}
    return before, puts, sorted(before), puts, 2 + 1


@pytest.mark.parametrize("case", [
    pytest.param(_batch_of(0), id="0-rows"),
    pytest.param(_batch_of(1), id="1-row"),
    pytest.param(_batch_of(_CHUNK - 1), id="chunk-less-1"),
    pytest.param(_batch_of(_CHUNK), id="chunk"),
    pytest.param(_batch_of(_CHUNK + 1), id="chunk-and-1"),
    pytest.param(_batch_of(3 * _CHUNK + 7), id="3-chunks-and-7"),
    pytest.param(_deletes_and_puts(), id="deletes-and-puts"),
    pytest.param(_key_in_both(), id="key-in-both-ends-as-the-put"),
])
def test_a_batch_goes_by_the_chunk_and_reads_back_exactly(
        tmp_path, chunk, case):
    before, puts, deletes, after, statements = case
    path = str(tmp_path / "kv.sqlite")
    kv = KVStore(path)
    kv.write_batch(before)
    assert kv.write_batch(puts, deletes, sync=True) == statements
    assert dict(kv.iterate()) == after
    kv.close()
    assert dict(KVStore(path).iterate()) == after


def test_a_batch_that_fails_in_its_second_chunk_leaves_no_row(
        tmp_path, chunk):
    """All or nothing across the chunks: a value sqlite cannot bind, in
    the second statement, takes the first statement's rows back with it,
    and the deletes before them."""
    kv = KVStore(str(tmp_path / "kv.sqlite"))
    before = {b"old" + bytes([i]): b"x" for i in range(10)}
    kv.write_batch(before)
    puts = {b"p" + i.to_bytes(3, "big"): b"v" for i in range(2 * chunk)}
    puts[b"p" + (chunk + 3).to_bytes(3, "big")] = object()
    with pytest.raises(sqlite3.Error, match="parameter %d" % (2 * 3 + 2)):
        kv.write_batch(puts, list(before)[:5])
    assert dict(kv.iterate()) == before
    assert kv.write_batch({b"after": b"1"}) == 1  # no transaction left open
    kv.close()


@pytest.mark.parametrize("n_shards", [1, 4])
def test_write_statements_counts_the_flushes_row_statements(
        tmp_path, chunk, n_shards):
    """gettpuinfo.store's write_statements: a shard's deletes by the chunk
    and its puts (the coins and its three meta rows) by the chunk, summed
    over the shards and over the store's commits."""
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB, shard_of

    db = ShardedCoinsDB(str(tmp_path), n_shards=n_shards)
    keys = [os.urandom(32) + i.to_bytes(4, "little") for i in range(700)]
    db.batch_write_serialized([(k, b"\x02\x05\x01\x51") for k in keys],
                              b"\x01" * 32)

    def per_shard(rows):
        per = [0] * n_shards
        for k in rows:
            per[shard_of(k, n_shards)] += 1
        return per

    def statements(puts, deletes):
        return sum(-(-(p + 3) // chunk) - (-d // chunk)
                   for p, d in zip(per_shard(puts), per_shard(deletes)))

    first = statements(keys, [])
    assert db.stats()["write_statements"] == first
    spent, fresh = keys[:300], [os.urandom(36) for _ in range(10)]
    db.batch_write_serialized(
        [(k, None) for k in spent] + [(k, b"\x02\x05\x01\x51")
                                      for k in fresh], b"\x02" * 32)
    stats = db.stats()
    assert stats["write_statements"] == first + statements(fresh, spent)
    assert (stats["rows_put"], stats["rows_deleted"]) == (710, 300)
    db.close()
