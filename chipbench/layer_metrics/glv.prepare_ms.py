"""Device time of one execution of the GLV verify's prepare stage
(decompose, expansions, per-lane tables): the duration of its XLA-module
events in the trace over their count. Since PR 39 the kernel is two
programs a bucket, and glv.kernel_ms reads the second alone: a bucket's
device time is glv.kernel_ms + glv.prepare_ms. A program with the one
stage (the parents of PR 39) has no such module: nothing to read."""

MODULE = "jit__glv_prepare_program"


def read(obs):
    module = (obs["trace"] or {}).get("modules", {}).get(MODULE)
    if not module or not module["count"]:
        return None
    return 1e3 * module["seconds"] / module["count"]
