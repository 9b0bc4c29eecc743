"""Share of the measured import's wall that the import engine spent in its
generic-script leg (the Python interpreter over every input the native scan
did not match, their sighashes and key parses): node.last_import_stats,
around the span import.script_leg. The leg's time is inside verify_s too."""


def read(obs):
    stats = obs["after"].get("import")
    if not stats or not stats.get("wall_s") or "fallback_s" not in stats:
        return None
    return 100.0 * stats["fallback_s"] / stats["wall_s"]
