"""Share of the measured import's wall that the import engine spent in its
verify leg (pack, dispatch, settle): node.last_import_stats."""


def read(obs):
    stats = obs["after"].get("import")
    if not stats or not stats.get("wall_s"):
        return None
    return 100.0 * stats["verify_s"] / stats["wall_s"]
