"""Mean number of dispatches still unfinished on the device when the import
enqueued one: sum(k x n_k) over sum(n_k) of ``inflight_at_enqueue``
(node.last_import_stats; by the runtime's word on each handle in flight,
the last slot counts three or more). Near 3 the chip paces the import, near
0 the host does. Nothing to read in a program without the counter."""


def read(obs):
    counts = (obs["after"].get("import") or {}).get("inflight_at_enqueue")
    if not counts or not sum(counts):
        return None
    return sum(k * n for k, n in enumerate(counts)) / sum(counts)
