"""Share of the generic-script leg's inputs (every input the native P2PKH
scan did not take) that a native script template settled without the
Python interpreter: template_inputs over fallback_inputs
(node.last_import_stats). A program without the counter reports nothing."""


def read(obs):
    stats = obs["after"].get("import")
    if (not stats or not stats.get("fallback_inputs")
            or not stats.get("template_inputs")):
        return None
    return 100.0 * stats["template_inputs"] / stats["fallback_inputs"]
