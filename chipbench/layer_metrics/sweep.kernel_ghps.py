"""Nonces the resident sweep counted over the device time of its XLA-module
events: the kernel's own rate, without the host between segments."""

MODULE = "jit_sweep_fast_jit"


def read(obs):
    module = (obs["trace"] or {}).get("modules", {}).get(MODULE)
    if not module or not module["seconds"]:
        return None
    swept = (obs["after"]["mining"].get("nonces_swept", 0)
             - obs["before"]["mining"].get("nonces_swept", 0))
    return swept / module["seconds"] / 1e9
