"""Share of the measured import's wall that the importing thread spent
waiting for the chip to empty before it may commit: the seconds of the
``import.drain`` spans (every dispatch in flight settled, the aggregate's
tail dispatched and settled, ahead of each flush) over ``wall_s``
(node.last_import_stats["phases"]). Nothing to read in a program without
the cadence's counters (the parents of PR 46)."""


def read(obs):
    stats = obs["after"].get("import") or {}
    row = (stats.get("phases") or {}).get("import.drain")
    if not row or "flush_rows" not in stats or not stats.get("wall_s"):
        return None
    return 100.0 * row["s"] / stats["wall_s"]
