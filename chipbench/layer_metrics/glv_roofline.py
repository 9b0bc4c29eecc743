"""Share of the VPU's peak the fused GLV verify program reaches: the
operations the signatures verified in the traced window need (opcounts.json)
over the peak (peaks.json) over the program's device time. Bound by
operations, not bytes."""

MODULE = "jit__glv_dev_program"


def read(obs):
    module = (obs["trace"] or {}).get("modules", {}).get(MODULE)
    if not module or not module["seconds"]:
        return None
    sigs = (obs["after"]["batch"]["sigs_verified"]
            - obs["before"]["batch"]["sigs_verified"])
    ops = sigs * obs["opcounts"]["programs"][MODULE]["u32_ops_per_unit"]
    least_s = ops / obs["peaks"]["vpu_u32_ops_per_s"]
    return 100.0 * least_s / module["seconds"]
