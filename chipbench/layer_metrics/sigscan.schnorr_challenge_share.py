"""Share of the native signature scan's thread-seconds spent in the Schnorr
lanes' challenge step (SHA-256 over r, the compressed key and the digest,
then n - e): node.last_import_stats schnorr_challenge_s over
sigscan_thread_s. The step lives in the scan (native/connect.cpp, on the
scan's threads, beside the digest and the key's decompression), not in the
packer, so both are sums over the same threads; sigscan_s is the scan's
wall. A program without the stopwatch reports nothing."""


def read(obs):
    stats = obs["after"].get("import")
    if (not stats or not stats.get("sigscan_thread_s")
            or "schnorr_challenge_s" not in stats):
        return None
    return 100.0 * stats["schnorr_challenge_s"] / stats["sigscan_thread_s"]
