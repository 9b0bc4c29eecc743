"""Share of the measured import's wall spent in the Python loop around the
native connect: the self times of ``import.read`` (block files, framing,
the header's hash, the index lookups), ``import.header`` (the header's
checks and the index entry), ``import.index`` (the bookkeeping after the
engine's commit) and of the ``import`` span itself (what no child covers),
over ``wall_s`` (node.last_import_stats["phases"]). Nothing to read in a
program without the spans."""

NAMES = ("import", "import.read", "import.header", "import.index")


def read(obs):
    stats = obs["after"].get("import") or {}
    phases = stats.get("phases")
    if not phases or "import" not in phases or not stats.get("wall_s"):
        return None
    loop = sum(phases[name]["self_s"] for name in NAMES if name in phases)
    return 100.0 * loop / stats["wall_s"]
