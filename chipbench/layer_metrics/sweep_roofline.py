"""Share of the VPU's peak the resident sweep reaches: nonces counted x
operations a nonce (opcounts.json) over the peak (peaks.json) over the
program's device time. Bound by operations: the sweep moves no bytes."""

MODULE = "jit_sweep_fast_jit"


def read(obs):
    module = (obs["trace"] or {}).get("modules", {}).get(MODULE)
    if not module or not module["seconds"]:
        return None
    swept = (obs["after"]["mining"].get("nonces_swept", 0)
             - obs["before"]["mining"].get("nonces_swept", 0))
    ops = swept * obs["opcounts"]["programs"][MODULE]["u32_ops_per_unit"]
    return 100.0 * ops / obs["peaks"]["vpu_u32_ops_per_s"] / module["seconds"]
