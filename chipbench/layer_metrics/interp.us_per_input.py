"""Host time the generic-script leg takes for one input it handles
(interpreter, one sighash a signature, one parse a key, the lanes'
records): fallback_s over fallback_inputs (node.last_import_stats)."""


def read(obs):
    stats = obs["after"].get("import")
    if (not stats or not stats.get("fallback_inputs")
            or "fallback_s" not in stats):
        return None
    return 1e6 * stats["fallback_s"] / stats["fallback_inputs"]
