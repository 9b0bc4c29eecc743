"""Share of the measured import's wall spent in the host legs: native
connect (the signature scan is inside it) and flush
(node.last_import_stats)."""


def read(obs):
    stats = obs["after"].get("import")
    if not stats or not stats.get("wall_s"):
        return None
    host = stats["native_connect_s"] + stats["flush_s"]
    return 100.0 * host / stats["wall_s"]
