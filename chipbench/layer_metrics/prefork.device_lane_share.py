"""Share of the signature checks of an import below the fork height that
the device verified: the lanes it took (batch.sigs_verified over the
window) over those lanes plus the checks that ran on the host at once, the
scripts no template fits (node.last_import_stats inline_legacy_sigs) and
eager multisig key trials (batch.eager_multisig_sigs over the window). 100
on a chain of template forms. A program that connects no block natively
below the fork height (no prefork_blocks) reports nothing."""


def read(obs):
    stats = obs["after"].get("import")
    if not stats or not stats.get("prefork_blocks"):
        return None
    b, a = obs["before"]["batch"], obs["after"]["batch"]
    lanes = a["sigs_verified"] - b["sigs_verified"]
    host = (stats["inline_legacy_sigs"]
            + a["eager_multisig_sigs"] - b["eager_multisig_sigs"])
    return 100.0 * lanes / (lanes + host) if lanes + host else None
