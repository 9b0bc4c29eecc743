"""Share of the measured import's wall that the importing thread spent
inside its flushes: the seconds of the ``import.flush`` spans (the block
files' sync, the index batch and the coins store's commit,
store/sharded._commit_sharded, on this thread) over ``wall_s``
(node.last_import_stats["phases"]). Nothing to read in a program without
the cadence's counters (the parents of PR 46)."""


def read(obs):
    stats = obs["after"].get("import") or {}
    row = (stats.get("phases") or {}).get("import.flush")
    if not row or "flush_rows" not in stats or not stats.get("wall_s"):
        return None
    return 100.0 * row["s"] / stats["wall_s"]
