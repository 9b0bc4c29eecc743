"""Share of the native signature scan's thread-seconds spent inside the
legacy SignatureHash: node.last_import_stats legacy_sighash_s over
sigscan_thread_s (both summed over the scan's threads; sigscan_s is the
scan's wall). A program without the stopwatch reports nothing."""


def read(obs):
    stats = obs["after"].get("import")
    if (not stats or not stats.get("sigscan_thread_s")
            or "legacy_sighash_s" not in stats):
        return None
    return 100.0 * stats["legacy_sighash_s"] / stats["sigscan_thread_s"]
