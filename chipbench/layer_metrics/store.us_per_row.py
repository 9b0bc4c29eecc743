"""What the coins store's commit costs a row: the seconds of the
``store.commit`` spans on the importing thread (store/sharded.
_commit_sharded: old-value reads, MuHash, journals, the shards' writes, the
manifest) over ``flush_rows``, the rows the import's flushes handed the
store (puts and deletes), in microseconds (node.last_import_stats). The
cost rises with the size of a commit (14.6 us at 130k rows, 27.3 at 590k;
PERF.md section 6, PR 38). Nothing to read in a program without
``flush_rows`` (the parents of PR 46)."""


def read(obs):
    stats = obs["after"].get("import") or {}
    row = (stats.get("phases") or {}).get("store.commit")
    if not row or not stats.get("flush_rows"):
        return None
    return 1e6 * row["s"] / stats["flush_rows"]
