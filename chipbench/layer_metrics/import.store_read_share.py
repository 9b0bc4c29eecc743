"""Share of the measured import's wall spent reading spent coins back from
the store because a flush had cleared them from the engine's cache: the
seconds of the ``import.store_read`` spans (``service_misses``: the shards'
lookups and the engine's inserts, inside ``import.connect``) over ``wall_s``
(node.last_import_stats["phases"]). Nothing to read in a program without
``store_read_rows`` (the parents of PR 46)."""


def read(obs):
    stats = obs["after"].get("import") or {}
    row = (stats.get("phases") or {}).get("import.store_read")
    if not row or "store_read_rows" not in stats or not stats.get("wall_s"):
        return None
    return 100.0 * row["s"] / stats["wall_s"]
