"""Device time of one execution of the Schnorr bucket's second stage (the
GLV ladder, the comb, X == r*Z^2 and the Euler power of Y*Z): the duration
of its XLA-module events in the trace over their count. A bucket's device
time is schnorr.kernel_ms + glv.prepare_ms. A program without the module
(every parent of PR 44) has nothing to read."""

MODULE = "jit__glv_schnorr_program"


def read(obs):
    module = (obs["trace"] or {}).get("modules", {}).get(MODULE)
    if not module or not module["count"]:
        return None
    return 1e3 * module["seconds"] / module["count"]
