"""Share of the measured import's wall that the importing thread spent
blocked on the chip: the seconds inside the ``import.settle_wait`` spans
(``handle.result()`` alone) over ``wall_s``
(node.last_import_stats["phases"]). The spans' whole duration, not their
self time: ``ecdsa.settle``, the blocking fetch itself, nests inside. A
program without the spans (the parents of PR 40) has no ``phases``: nothing
to read."""


def read(obs):
    stats = obs["after"].get("import") or {}
    row = (stats.get("phases") or {}).get("import.settle_wait")
    if not row or not stats.get("wall_s"):
        return None
    return 100.0 * row["s"] / stats["wall_s"]
