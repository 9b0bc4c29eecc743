"""ShardedCoinsDB facade (store/sharded.py) + snapshot format.

Differential against the single-file CoinsDB reference (the facade is a
pure partition of the same contract), incremental-accumulator equality
with a from-scratch recompute, the store_shard fault site's whole-commit
abort semantics, manifest shard-count pinning, dump/load round-trips
across shard counts including digest-rejection, and the facade's memory of
the rows it served since its last write (a commit's old values for MuHash).
"""

import os
import struct
import time

import pytest

from bitcoincashplus_tpu.store import muhash
from bitcoincashplus_tpu.store import snapshot as snapshot_mod
from bitcoincashplus_tpu.store.chainstatedb import CoinsDB
from bitcoincashplus_tpu.store.kvstore import KVStore
from bitcoincashplus_tpu.store.sharded import (
    MANIFEST_NAME,
    STORE_SHARD_SITE,
    ShardedCoinsDB,
    shard_of,
)
from bitcoincashplus_tpu.util.faults import InjectedFault


def _key(i: int) -> bytes:
    return bytes([i % 251]) * 32 + struct.pack("<I", i)


def _coin(i: int) -> bytes:
    # valid Coin serialization: compact(height*2+cb), compact(value),
    # var_bytes(script) — height 1, value 5, 20-byte script
    return bytes([2, 5, 20]) + bytes([i % 256]) * 20


def _entries(lo: int, hi: int, delete=()):
    out = [(_key(i), _coin(i)) for i in range(lo, hi)]
    out += [(_key(i), None) for i in delete]
    return out


@pytest.fixture
def sharded(tmp_path):
    db = ShardedCoinsDB(str(tmp_path), n_shards=4)
    yield db
    db.close()


class TestFacade:
    def test_power_of_two_enforced(self, tmp_path):
        for bad in (0, 3, 5, 300, -1):
            with pytest.raises(ValueError):
                ShardedCoinsDB(str(tmp_path), n_shards=bad)

    def test_differential_vs_single_coinsdb(self, tmp_path, sharded):
        """Same batches through the facade and a plain CoinsDB — every
        read surface must agree (the facade is only a partition)."""
        ref_kv = KVStore(str(tmp_path / "ref.sqlite"))
        ref = CoinsDB(ref_kv)
        best1 = b"\x01" * 32
        best2 = b"\x02" * 32
        sharded.batch_write_serialized(_entries(0, 200), best1)
        ref.batch_write_serialized(_entries(0, 200), best1)
        # overwrite a run, delete a run
        sharded.batch_write_serialized(
            _entries(50, 80, delete=range(100, 140)), best2)
        ref.batch_write_serialized(
            _entries(50, 80, delete=range(100, 140)), best2)

        assert sharded.best_block() == ref.best_block() == best2
        assert sharded.count_coins() == ref.count_coins() == 160
        keys = [_key(i) for i in range(0, 220)]
        assert sharded.get_serialized_many(keys) == \
            ref.get_serialized_many(keys)
        assert dict(sharded.iterate_coins()) == dict(ref.iterate_coins())
        ref_kv.close()

    def test_rows_actually_partition(self, sharded, tmp_path):
        sharded.batch_write_serialized(_entries(0, 64), b"\x01" * 32)
        per_shard = []
        for i in range(4):
            kv = sharded.shards[i].kv
            rows = {k[1:]: v for k, v in kv.iterate(b"C")}
            for k36 in rows:
                assert shard_of(k36, 4) == i
            per_shard.append(len(rows))
        assert sum(per_shard) == 64
        assert sum(1 for n in per_shard if n > 0) > 1  # really spread

    def test_incremental_digest_tracks_recompute(self, sharded):
        best = b"\x01" * 32
        sharded.batch_write_serialized(_entries(0, 100), best)
        assert sharded.muhash_digest() == sharded.recompute_digest()
        sharded.batch_write_serialized(
            _entries(20, 40, delete=range(60, 90)), best)
        assert sharded.muhash_digest() == sharded.recompute_digest()
        # digest must be independent of the shard count: a 1-shard store
        # with the same coin set lands on the same value
        assert sharded.muhash_digest() != muhash.digest_of(1)

    def test_epoch_and_manifest_pinning(self, tmp_path, sharded):
        sharded.batch_write_serialized(_entries(0, 10), b"\x01" * 32)
        epoch = sharded.epoch
        assert epoch >= 1
        sharded.close()
        # reopen asking for a different count: the manifest wins
        again = ShardedCoinsDB(str(tmp_path), n_shards=16)
        assert again.n_shards == 4
        assert again.requested_shards == 16
        assert again.epoch == epoch
        assert again.muhash_digest() == again.recompute_digest()
        again.close()

    def test_stats_shape(self, sharded):
        sharded.batch_write_serialized(_entries(0, 10), b"\x01" * 32)
        s = sharded.stats()
        assert s["shards"] == 4
        assert s["epoch"] >= 1
        assert len(s["shard_bytes"]) == 4
        assert s["last_flush"]["fanout"] == 4


class TestShardFaultSite:
    def test_one_failing_shard_aborts_whole_commit(self, tmp_path,
                                                   fault_harness):
        db = ShardedCoinsDB(str(tmp_path), n_shards=4)
        best = b"\x01" * 32
        db.batch_write_serialized(_entries(0, 40), best)
        epoch = db.epoch
        digest = db.muhash_digest()
        fault_harness("fail-once", ops=STORE_SHARD_SITE)
        with pytest.raises(InjectedFault):
            db.batch_write_serialized(
                _entries(40, 80, delete=range(0, 10)), b"\x02" * 32)
        # clean abort: no journal survives, no shard moved past the
        # manifest epoch, state is exactly pre-commit
        for i in range(4):
            assert not os.path.exists(
                os.path.join(str(tmp_path), f"chainstate.shard{i}.journal"))
        assert db.epoch == epoch
        assert db.best_block() == best
        assert db.count_coins() == 40
        assert db.muhash_digest() == digest == db.recompute_digest()
        db.close()
        # and the store reopens consistent (recovery sees nothing to do)
        again = ShardedCoinsDB(str(tmp_path), n_shards=4)
        assert again.epoch == epoch
        assert again.count_coins() == 40
        again.close()

    def test_all_does_not_arm_store_shard(self, tmp_path, fault_harness):
        """BCP_FAULT_OPS=all must keep meaning the accelerator subsystems
        — a dead-backend drill may not fail chainstate flushes."""
        fault_harness("fail-always", ops="all")
        db = ShardedCoinsDB(str(tmp_path), n_shards=2)
        db.batch_write_serialized(_entries(0, 10), b"\x01" * 32)
        assert db.count_coins() == 10
        db.close()


# -- the rows served since the last write, as a commit's old values ----------

BEST = b"\x01" * 32


def _open(path, n_shards: int) -> ShardedCoinsDB:
    return ShardedCoinsDB(str(path), n_shards=n_shards)


def _read(db: ShardedCoinsDB, ids) -> dict:
    return db.get_serialized_many([_key(i) for i in ids])


def _shard_states(db: ShardedCoinsDB) -> list:
    """Each shard's accumulator state from its rows (what a bulk load's
    caller hands finalize_bulk_load)."""
    return [muhash.batch_product([muhash.coin_element(k, v) for k, v
                                  in db.iterate_shard_coins(i)])
            for i in range(db.n_shards)]


def _served_half_case(tmp_path, n_shards, fault_harness):
    """(a) half of the deleted rows were served: they are remembered, the
    other half is looked up, and the digest is the one of a store that was
    reopened before the commit and remembers nothing."""
    db = _open(tmp_path / "a", n_shards)
    twin = _open(tmp_path / "twin", n_shards)
    for store in (db, twin):
        store.batch_write_serialized(_entries(0, 200), BEST)
    twin.close()
    twin = _open(tmp_path / "twin", n_shards)
    try:
        # a miss is not remembered
        assert len(_read(db, range(0, 100))) == 100
        assert _read(db, range(500, 520)) == {}
        assert db.stats()["remembered_rows"] == 100
        assert twin.stats()["remembered_rows"] == 0
        for store in (db, twin):
            store.batch_write_serialized(
                _entries(200, 400, delete=range(0, 200)), BEST)
        assert (db.muhash_digest() == db.recompute_digest()
                == twin.muhash_digest())
        mine, theirs = (s.last_flush["old_values"] for s in (db, twin))
        bloom = db.last_flush["bloom"]
        assert mine["remembered"] == 100 and mine["found"] == 100
        # the other half and the bloom's false positives among the puts;
        # the remembered keys never reached the bloom
        assert bloom["checked"] == 300
        assert mine["looked_up"] == bloom["checked"] - bloom["skipped"]
        assert 100 <= mine["looked_up"] < 300
        assert theirs["remembered"] == 0 and theirs["found"] == 200
        assert theirs["looked_up"] == mine["looked_up"] + 100
        totals = db.stats()["old_values"]
        assert totals["remembered"] == 100
        assert totals["found"] == 100
    finally:
        db.close()
        twin.close()


def _overwritten_case(tmp_path, n_shards, fault_harness):
    """(b) a served row the commit overwrites is divided out with its
    persisted value, and (c) a served row a commit does not touch is
    forgotten by that commit all the same."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 50), BEST)
        _read(db, range(0, 50))
        db.batch_write_serialized(
            [(_key(i), _coin(i + 7)) for i in range(0, 25)], BEST)
        assert db.last_flush["old_values"] == {
            "remembered": 25, "looked_up": 0, "found": 0}
        assert db.stats()["remembered_rows"] == 0
        assert db.muhash_digest() == db.recompute_digest()
        # rows 25-49 were served before that commit and not since: the
        # next commit looks them up
        db.batch_write_serialized(_entries(0, 0, delete=range(25, 50)), BEST)
        assert db.last_flush["old_values"] == {
            "remembered": 0, "looked_up": 25, "found": 25}
        assert db.muhash_digest() == db.recompute_digest()
    finally:
        db.close()


def _untouched_case(tmp_path, n_shards, fault_harness):
    """(c) the memory is empty after every commit, whatever it changed, so
    it cannot grow across intervals."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 50), BEST)
        for lo in (50, 60, 70):
            _read(db, range(0, 50))
            assert db.stats()["remembered_rows"] == 50
            # what the import counts against -dbcache beside the engine:
            # more than the rows' own bytes, and nothing once forgotten
            assert db.served_bytes() > sum(
                len(_key(i)) + len(_coin(i)) for i in range(50))
            db.batch_write_serialized(_entries(lo, lo + 10), BEST)
            assert db.last_flush["old_values"]["remembered"] == 0
            assert db.stats()["remembered_rows"] == 0
            assert db.served_bytes() == 0
        assert db.muhash_digest() == db.recompute_digest()
        # an empty commit drops it too
        _read(db, range(0, 50))
        db.batch_write_serialized([], BEST)
        assert db.stats()["remembered_rows"] == 0
    finally:
        db.close()


def _ingest_rows_case(tmp_path, n_shards, fault_harness):
    """(d) rows loaded behind the commit path: what was served before is
    not what is persisted after."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 50), BEST)
        _read(db, range(0, 50))
        db.ingest_rows([(_key(i), _coin(i + 9)) for i in range(0, 50)])
        assert db.stats()["remembered_rows"] == 0
        db.finalize_bulk_load(BEST, _shard_states(db))
        db.batch_write_serialized(_entries(0, 0, delete=range(0, 50)), BEST)
        assert db.last_flush["old_values"]["remembered"] == 0
        assert db.last_flush["old_values"]["found"] == 50
        assert db.muhash_digest() == db.recompute_digest()
    finally:
        db.close()


def _clear_coins_case(tmp_path, n_shards, fault_harness):
    """(d) every row dropped behind the commit path: nothing that was
    served is on disk any more."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 50), BEST)
        _read(db, range(0, 50))
        db.clear_coins()
        assert db.stats()["remembered_rows"] == 0
        db.finalize_bulk_load(BEST, [1] * n_shards)
        db.batch_write_serialized(
            [(_key(i), _coin(i + 3)) for i in range(0, 30)], BEST)
        # and the blooms, rebuilt from no rows, prove every key absent
        assert db.last_flush["old_values"] == {
            "remembered": 0, "looked_up": 0, "found": 0}
        assert db.muhash_digest() == db.recompute_digest()
    finally:
        db.close()


def _replay_case(tmp_path, n_shards, fault_harness):
    """(d) a commit that failed in step 2 on one shard, rows served while
    its journals wait, then the replay: the rows the replay changes are no
    longer the ones that were served."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 50), BEST)
        epoch = db.epoch
        kv = db.shards[0].kv
        real = kv.write_batch

        def refuse(*args, **kwargs):
            raise OSError("no space left on device")

        kv.write_batch = refuse
        batch = ([(_key(i), _coin(i + 5)) for i in range(20, 50)]
                 + _entries(50, 80, delete=range(0, 20)))
        try:
            with pytest.raises(OSError):
                db.batch_write_serialized(batch, BEST)
        finally:
            kv.write_batch = real
        deadline = time.monotonic() + 30
        while any(db._shard_epoch(i) != epoch + 1
                  for i in range(1, n_shards)):
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert db._shard_epoch(0) == epoch
        served = _read(db, range(0, 80))
        stale = [i for i in range(20, 50) if shard_of(_key(i), n_shards) == 0]
        assert stale and all(served[_key(i)] == _coin(i) for i in stale)
        assert db.stats()["remembered_rows"] == len(served)
        assert db.recover_journal() is True
        assert db.stats()["remembered_rows"] == 0
        assert db.epoch == epoch + 1
        assert db.muhash_digest() == db.recompute_digest()
        db.batch_write_serialized(_entries(0, 0, delete=range(20, 80)), BEST)
        assert db.last_flush["old_values"] == {
            "remembered": 0, "looked_up": 60, "found": 60}
        assert db.count_coins() == 0
        assert db.muhash_digest() == db.recompute_digest()
    finally:
        db.close()


def _aborted_then_retried_case(tmp_path, n_shards, fault_harness):
    """(e) a commit the store_shard fault site aborts in step 1 has changed
    no row and has dropped the memory; the retry looks every row up."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 100), BEST)
        digest = db.muhash_digest()
        _read(db, range(0, 50))
        batch = _entries(100, 150, delete=range(0, 100))
        fault_harness("fail-once", ops=STORE_SHARD_SITE)
        with pytest.raises(InjectedFault):
            db.batch_write_serialized(batch, BEST)
        assert db.stats()["remembered_rows"] == 0
        assert db.muhash_digest() == digest == db.recompute_digest()
        db.batch_write_serialized(batch, BEST)
        assert db.last_flush["old_values"]["remembered"] == 0
        assert db.last_flush["old_values"]["found"] == 100
        assert db.count_coins() == 50
        assert db.muhash_digest() == db.recompute_digest()
        totals = db.stats()["old_values"]
        # the aborted attempt counted what it took: 50 remembered, 50 read
        assert totals["remembered"] == 50 and totals["found"] == 150
    finally:
        db.close()


def _served_during_applies_case(tmp_path, n_shards, fault_harness):
    """A read that overlaps a commit's applies (no caller makes one) may
    have seen rows from before them: the commit's end forgets it, so the
    next commit looks those rows up."""
    db = _open(tmp_path, n_shards)
    try:
        db.batch_write_serialized(_entries(0, 50), BEST)
        kv = db.shards[0].kv
        real = kv.write_batch

        def read_then_write(*args, **kwargs):
            _read(db, range(0, 50))
            return real(*args, **kwargs)

        kv.write_batch = read_then_write
        try:
            db.batch_write_serialized(
                [(_key(i), _coin(i + 7)) for i in range(0, 50)], BEST)
        finally:
            kv.write_batch = real
        assert db.stats()["remembered_rows"] == 0
        db.batch_write_serialized(_entries(0, 0, delete=range(0, 50)), BEST)
        assert db.last_flush["old_values"] == {
            "remembered": 0, "looked_up": 50, "found": 50}
        assert db.muhash_digest() == db.recompute_digest()
    finally:
        db.close()


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("case", [
    _served_half_case, _overwritten_case, _untouched_case,
    _ingest_rows_case, _clear_coins_case, _replay_case,
    _aborted_then_retried_case, _served_during_applies_case], ids=lambda c: c.__name__.strip("_"))
def test_served_rows_are_a_commits_old_values(case, n_shards, tmp_path,
                                              fault_harness):
    case(tmp_path, n_shards, fault_harness)


class TestSnapshot:
    @pytest.mark.parametrize("src,dst", [(4, 4), (4, 1), (1, 4), (2, 8)])
    def test_round_trip_across_shard_counts(self, tmp_path, src, dst):
        a = ShardedCoinsDB(str(tmp_path / "a"), n_shards=src)
        best = b"\xaa" * 32
        a.batch_write_serialized(_entries(0, 300), best)
        digest = a.muhash_digest()
        headers = [bytes(80)]
        manifest = snapshot_mod.dump_snapshot(
            a, str(tmp_path / "snap"), headers, 0, best, "regtest")
        assert manifest["muhash"] == digest.hex()
        assert manifest["coins"] == 300

        b = ShardedCoinsDB(str(tmp_path / "b"), n_shards=dst)
        info = snapshot_mod.load_snapshot(
            str(tmp_path / "snap"), b, "regtest",
            expected_hash=best, expected_digest=digest)
        assert info["best_block"] == best
        assert b.count_coins() == 300
        assert b.best_block() == best
        assert b.muhash_digest() == digest == b.recompute_digest()
        assert dict(b.iterate_coins()) == dict(a.iterate_coins())
        assert b.snapshot_state is not None
        assert b.snapshot_state["validated"] is False
        a.close()
        b.close()

    def test_bad_digest_rejected_and_wiped(self, tmp_path):
        a = ShardedCoinsDB(str(tmp_path / "a"), n_shards=2)
        best = b"\xaa" * 32
        a.batch_write_serialized(_entries(0, 50), best)
        snapshot_mod.dump_snapshot(a, str(tmp_path / "snap"),
                                   [bytes(80)], 0, best, "regtest")
        a.close()
        # corrupt one utxo stream (keep its length so the row parse
        # succeeds and only the checksum/digest trips)
        target = next(str(p) for p in (tmp_path / "snap").iterdir()
                      if p.name.startswith("utxo-") and p.stat().st_size)
        blob = bytearray(open(target, "rb").read())
        blob[-1] ^= 0xFF
        open(target, "wb").write(bytes(blob))

        b = ShardedCoinsDB(str(tmp_path / "b"), n_shards=2)
        with pytest.raises(snapshot_mod.SnapshotError):
            snapshot_mod.load_snapshot(str(tmp_path / "snap"), b, "regtest")
        assert b.count_coins() == 0  # wiped, not half-loaded
        assert b.snapshot_state is None
        b.close()

    def test_wrong_authorization_rejected(self, tmp_path):
        a = ShardedCoinsDB(str(tmp_path / "a"), n_shards=2)
        best = b"\xaa" * 32
        a.batch_write_serialized(_entries(0, 20), best)
        snapshot_mod.dump_snapshot(a, str(tmp_path / "snap"),
                                   [bytes(80)], 0, best, "regtest")
        a.close()
        b = ShardedCoinsDB(str(tmp_path / "b"), n_shards=2)
        with pytest.raises(snapshot_mod.SnapshotError):
            snapshot_mod.load_snapshot(
                str(tmp_path / "snap"), b, "regtest",
                expected_hash=b"\xbb" * 32)
        with pytest.raises(snapshot_mod.SnapshotError):
            snapshot_mod.load_snapshot(
                str(tmp_path / "snap"), b, "regtest",
                expected_digest=b"\xcc" * 32)
        with pytest.raises(snapshot_mod.SnapshotError):
            snapshot_mod.load_snapshot(str(tmp_path / "snap"), b, "test")
        b.close()

    def test_legacy_store_detection(self, tmp_path):
        """A datadir with chainstate.sqlite and no manifest is the legacy
        layout — the node keeps it on plain CoinsDB (checked here at the
        layout level: the manifest only appears after a sharded commit)."""
        kv = KVStore(str(tmp_path / "chainstate.sqlite"))
        CoinsDB(kv).batch_write_serialized(_entries(0, 5), b"\x01" * 32)
        kv.close()
        assert not os.path.exists(str(tmp_path / MANIFEST_NAME))
