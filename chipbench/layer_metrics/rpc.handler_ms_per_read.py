"""What one monitoring read costs once it holds cs_main: the window's
change in ``handler_s`` over its change in ``calls``, summed over the three
read methods, in milliseconds (gettpuinfo["rpc"]: the ``rpc.handler``
spans). Nothing to read in a program without the section."""

METHODS = ("getblockcount", "getmininginfo", "getblockheader")


def read(obs):
    before, after = obs["before"].get("rpc"), obs["after"].get("rpc")
    if before is None or not after:
        return None
    calls, ran = (sum(after[m][key] - before.get(m, {}).get(key, 0)
                      for m in METHODS if m in after)
                  for key in ("calls", "handler_s"))
    return 1e3 * ran / calls if calls else None
