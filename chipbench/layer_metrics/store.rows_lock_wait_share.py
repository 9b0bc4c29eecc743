"""Share of the shard threads' write time spent waiting for each other:
the seconds of the ``store.rows_lock_wait`` spans (the wait for
kvstore._ROWS_LOCK, one batch of rows bound at a time) over those of the
``store.shard_write`` spans that contain them, summed over the flush pool's
threads, as deltas of the program's span totals over the window (what
/metrics serves as bcp_span_seconds_total; the driver keeps them beside its
snapshots as ``spans``, since the pool's spans are in no import's
``phases``). Nothing to read where the driver kept none."""


def read(obs):
    before, after = obs["before"].get("spans"), obs["after"].get("spans")
    if before is None or after is None:
        return None

    def moved(name):
        return (after.get(name, {}).get("s", 0.0)
                - before.get(name, {}).get("s", 0.0))

    wrote = moved("store.shard_write")
    if not wrote > 0:
        return None
    return 100.0 * moved("store.rows_lock_wait") / wrote
