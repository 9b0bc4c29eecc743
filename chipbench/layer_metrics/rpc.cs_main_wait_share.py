"""Share of the monitoring reads' time inside the RPC server that was the
wait for cs_main: the window's change in ``lock_wait_s`` over its change in
``lock_wait_s`` + ``handler_s``, summed over the three read methods
(gettpuinfo["rpc"]: the ``rpc.lock_wait`` and ``rpc.handler`` spans).
Nothing to read in a program without the section."""

METHODS = ("getblockcount", "getmininginfo", "getblockheader")


def delta(obs, key):
    before, after = obs["before"].get("rpc"), obs["after"].get("rpc")
    if before is None or not after:
        return None
    return sum(after[m][key] - before.get(m, {}).get(key, 0)
               for m in METHODS if m in after)


def read(obs):
    waited, ran = delta(obs, "lock_wait_s"), delta(obs, "handler_s")
    if waited is None or not waited + ran > 0:
        return None
    return 100.0 * waited / (waited + ran)
