"""Device lanes the import took for one multisig signature: the candidate
lanes it counted (node.last_import_stats multisig_lanes, m(n-m+1) an
operation) over the signatures of the chain's multisig inputs (m an
operation, the generator's count). 2.0 for 2-of-3 and 1-of-2 alike."""


def read(obs):
    stats = obs["after"].get("import")
    sigs = obs["result"].get("report", {}).get("multisig_sigs")
    if not stats or not sigs or "multisig_lanes" not in stats:
        return None
    return stats["multisig_lanes"] / sigs
