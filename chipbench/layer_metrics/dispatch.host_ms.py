"""The host's share of one dispatch on the importing thread: the seconds
inside the ``import.pack`` spans (the aggregation's concatenate) and the
``import.enqueue`` spans (``dispatch_packed``: the scalars' precompute, the
known-answer lanes, ``ecdsa.pack``, and ``ecdsa.enqueue``, the program
call) over the import's ``dispatches``, in milliseconds
(node.last_import_stats). The spans' whole durations, not their self times:
the ecdsa.* spans nest inside ``import.enqueue`` and are what the metric is
for. Nothing to read in a program without the spans."""


def read(obs):
    stats = obs["after"].get("import") or {}
    phases = stats.get("phases") or {}
    if "import.enqueue" not in phases or not stats.get("dispatches"):
        return None
    host = sum(phases[name]["s"]
               for name in ("import.pack", "import.enqueue")
               if name in phases)
    return 1e3 * host / stats["dispatches"]
