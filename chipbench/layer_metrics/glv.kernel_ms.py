"""Device time of one execution of the fused GLV verify program: the
duration of its XLA-module events in the trace over their count."""

MODULE = "jit__glv_dev_program"


def read(obs):
    module = (obs["trace"] or {}).get("modules", {}).get(MODULE)
    if not module or not module["count"]:
        return None
    return 1e3 * module["seconds"] / module["count"]
