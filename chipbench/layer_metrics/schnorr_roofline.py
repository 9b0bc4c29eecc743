"""Share of the VPU's peak the two programs of a Schnorr bucket reach
together: the operations the Schnorr lanes verified in the traced window
need (chipbench/opcounts_schnorr.json, which this reader loads itself: the
census of both stages as PR 44 landed them, a fixed yardstick) over the peak
(peaks.json) over the device time of BOTH stages, the prepare stage's taken
per event for as many events as the Schnorr stage has, so that numerator and
divisor cover the same work. Bound by operations, not bytes. A program
without the Schnorr stage or the counter has nothing to read."""

import json
import os

PREPARE = "jit__glv_prepare_program"
MODULE = "jit__glv_schnorr_program"
with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "opcounts_schnorr.json")) as _f:
    OPS_PER_LANE = json.load(_f)["programs"][
        PREPARE + "+" + MODULE]["u32_ops_per_unit"]


def read(obs):
    modules = (obs["trace"] or {}).get("modules", {})
    prepare, schnorr = modules.get(PREPARE), modules.get(MODULE)
    batch = obs["after"].get("batch") or {}
    if (not prepare or not prepare["count"] or not schnorr
            or not schnorr["seconds"] or "schnorr_lanes" not in batch):
        return None
    lanes = batch["schnorr_lanes"] - obs["before"]["batch"]["schnorr_lanes"]
    seconds = schnorr["seconds"] + (
        prepare["seconds"] / prepare["count"] * schnorr["count"])
    least_s = lanes * OPS_PER_LANE / obs["peaks"]["vpu_u32_ops_per_s"]
    return 100.0 * least_s / seconds
