"""Kilobytes of serialised transaction the native scan's legacy
SignatureHash hashed for one signature of the chain:
node.last_import_stats legacy_sighash_bytes over the signatures the window
attempted (the generator's count), over 1,000. Each digest serialises the
whole transaction again, so this grows with the inputs a transaction has;
a midstate over the bytes before the signed input would halve it. A
program without the counter reports nothing."""


def read(obs):
    stats = obs["after"].get("import")
    sigs = obs["result"].get("attempted")
    if not stats or not sigs or "legacy_sighash_bytes" not in stats:
        return None
    return stats["legacy_sighash_bytes"] / sigs / 1000.0
