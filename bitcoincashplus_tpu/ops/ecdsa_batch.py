"""ECDSA batch dispatch — the host side of the TPU signature graft.

Reference: this layer replaces CCheckQueue (src/checkqueue.h:~30) +
ThreadScriptCheck (src/validation.cpp): instead of fanning CScriptCheck
closures to worker threads, the block's deferred sigcheck records are
packed into byte matrices and verified in ONE device dispatch
(SURVEY.md §3.2 P1, §8.4 "ECDSA batch").

One arrow: dispatch_batch(records) -> records_to_blobs ->
_dispatch_packed_device <- dispatch_packed(blobs). A bucket is of one lane
kind: ECDSA's (below), or BCH Schnorr's (dispatch_packed(schnorr=True): the
65-byte signatures the import's scan took; u1 = s, u2 = n - e, no modular
inverse, the program _glv_schnorr_program behind the same prepare stage;
its ladder of rungs has no w4 form: device program, retries, breaker,
native threaded Schnorr verify, Python oracle). Pipeline per ECDSA batch:
  1. host: w = s⁻¹ mod n, u1 = e·w, u2 = r·w (native C++, threaded;
     Python ints without the library). The GLV lattice split
     (k = k1 + λ·k2, |k1|,|k2| < 2^128) runs inside the device program
  2. pack (pack_lanes, the one packer): u1/u2/qx/qy/r/rn as (B, 32)
     big-endian byte matrices, q_inf/wrap_ok as (B,) uint8; wrap_ok =
     (r + n < p) per lane (the kernel gates the x-wraparound candidate
     on it); window, digit and limb expansion happen on the device
  3. pad B up to a bucket size (bounds XLA recompiles); padded lanes are
     poisoned (q_inf) and ignored
  4. one jit dispatch under the ecdsa breaker: the selected kernel
     (_glv_prepare_program and _glv_dev_program back to back, or
     _w4_bytes_program under -ecdsakernel=w4), then
     _w4_bytes_program if GLV failed, then retries, then the breaker
  5. device returns (B,) validity + degenerate masks; BatchHandle.result
     checks the two known-answer lanes and host-confirms every False

CPU fallback (``backend="cpu"``, batches below the dispatch floor, an
open breaker, a failed dispatch) is the threaded native verify, else the
Python-int oracle — the reference's single-threaded VerifyScript path.
"""

from __future__ import annotations

import gc
import os
import random
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from ..crypto import secp256k1 as oracle
from ..util import devicewatch as dw
from ..util import telemetry as tm
from ..util.faults import INJECTOR, Backoff, PoisonedOutput
from ..util.log import log_printf
from . import dispatch

# -- telemetry families (util/telemetry): device settle-wait
# distribution, dispatch/flush lane-size histograms, and the lane-fill /
# in-flight gauges. STATS itself is projected onto the registry by the
# collector below, so getmetrics' /metrics namespace and gettpuinfo's
# `batch` section read the same counters. The host's stages a dispatch are
# spans (ecdsa.pack, ecdsa.enqueue, ecdsa.settle: bcp_span_* on /metrics,
# host events on a profiler trace); STATS.glv_emit_s, glv_dispatch_s and
# device_seconds are views of those spans' seconds.
_SETTLE_H = tm.histogram(
    "bcp_ecdsa_settle_wait_seconds",
    "Blocking wait at BatchHandle.result() — near zero when the pipeline "
    "hid the device latency")
_LANES_H = tm.histogram(
    "bcp_ecdsa_dispatch_lanes",
    "Real (unpadded) lanes per device dispatch",
    buckets=(32, 128, 512, 1024, 2048, 4096, 8192, 16384, 32768))
_PACKER_FLUSH_H = tm.histogram(
    "bcp_packer_flush_lanes",
    "Lanes per cross-block LanePacker bucket flush",
    buckets=(32, 128, 512, 1024, 2046, 4096, 8190, 16384))
_LANE_FILL_G = tm.gauge(
    "bcp_packer_lane_fill_pct",
    "Cumulative real-lane fill of padded device buckets (percent)")
_IN_FLIGHT_G = tm.gauge(
    "bcp_ecdsa_in_flight",
    "Device verify dispatches currently in flight")


def _collect_ecdsa_stats():
    """Registry collector: every numeric BatchStats field as
    bcp_ecdsa_<field>, plus per-bucket dispatch counts. in_flight is
    excluded — the native _IN_FLIGHT_G gauge already owns that name, and
    a collector re-emitting it would duplicate the family with a
    conflicting TYPE in the Prometheus exposition."""
    snap = STATS.snapshot()
    snap.pop("in_flight", None)
    buckets = snap.pop("buckets_used", {})
    out = tm.flat_families("bcp_ecdsa", snap, typ="counter",
                           help="ops/ecdsa_batch.STATS")
    if buckets:
        out.append({
            "name": "bcp_ecdsa_bucket_dispatches_total", "type": "counter",
            "help": "Device dispatches per padded bucket size",
            "samples": [({"bucket": str(b)}, n)
                        for b, n in sorted(buckets.items())],
        })
    return out


tm.register_collector("ecdsa_stats", _collect_ecdsa_stats)

# Below this lane count a device round-trip costs more than host verify.
CPU_FLOOR = 8

# ---- device-lane watches (util/devicewatch) --------------------------------
# The bucket design's WHOLE POINT is a bounded compiled-shape set; these
# declared budgets turn that invariant into a runtime check (a dispatch
# that mints a shape beyond its program's budget fires
# bcp_xla_retrace_unexpected_total + a log warning + a trace instant).
# The byte-pipeline ladder is {1024, 2048, 4096} then 2048-granular to
# 16384 = 9 shapes (_bucket_for; >16384 splits per program call, so no
# extra shapes), and both verify programs share it.
PALLAS_SHAPE_BUDGET = 9
_PW_GLV_DEV = dw.program("ecdsa_glv_decompose",
                         shape_budget=PALLAS_SHAPE_BUDGET)
_PW_W4_BYTES = dw.program("ecdsa_w4_bytes", shape_budget=PALLAS_SHAPE_BUDGET)
# the Schnorr bucket's two programs (the shared prepare stage and
# _glv_schnorr_program): traced and compiled by a node that sees a Schnorr
# lane, never before
_PW_GLV_SCHNORR = dw.program("ecdsa_glv_schnorr",
                             shape_budget=PALLAS_SHAPE_BUDGET)
# Pippenger MSM batch-verification program (ISSUE 19): term counts pad to
# the _MSM_BUCKETS ladder, and the canary batches reuse the smallest
# bucket, so the compiled-shape set is exactly that ladder.
_MSM_BUCKETS = (64, 256, 1024, 4096, 8192, 16384)
MSM_SHAPE_BUDGET = len(_MSM_BUCKETS)
_PW_MSM = dw.program("ecdsa_msm", shape_budget=MSM_SHAPE_BUDGET)
# A batch of n Schnorr sigs costs M = 2n+1 MSM terms (R_i, P_i, and the
# shared G term); the cap keeps M inside the largest bucket — bigger
# submissions chunk (the MSM sum cannot ride the ladder kernels' 16384-
# lane program splitting, each chunk is an independent batch equation).
MSM_MAX_RECORDS = 8190
# Below this the bisection hands lanes straight to the per-lane oracle —
# a device round trip per 8 sigs costs more than 8 scalar verifies.
MSM_MIN_BATCH = 8


def _watched_kernel(pw, bucket: int, arrays, fn, jitfn=None, kwargs=None,
                    split: int | None = 16384):
    """One watched kernel call: the program watch sees the compiled-shape
    signature and attributes compile time; h2d staging bytes and the
    execute phase land in the transfer/phase accounting. ``arrays`` are
    the packed host-side numpy inputs (their nbytes IS the staging
    payload); ``jitfn`` enables first-compile cost-analysis capture.

    ``split`` is the wrapper's per-program-call cap: the glv / w4-bytes
    entry points slice batches beyond 16384 lanes into 16384-lane
    program calls, so the COMPILED shape — the signature the retrace
    sentinel must see — is min(bucket, split), never the raw bucket (an
    unclamped 32768 would read as a fresh shape and fire a false
    invariant alarm). Pass split=None for programs that do not slice
    (the MSM program compiles the padded bucket as-is)."""
    dw.note_transfer("ecdsa", "h2d",
                     sum(int(a.nbytes) for a in arrays))
    sig = bucket if split is None else min(bucket, split)
    traced = (sig,) not in pw.signatures
    with pw.dispatch(sig, jitfn=jitfn, args=arrays, kwargs=kwargs):
        out = fn()
    if traced:
        _freeze_traced_heap()
    return out


def _freeze_traced_heap() -> None:
    """Tracing and lowering a verify program leaves millions of objects
    that live as long as the process (jaxprs, the lowering's caches), and
    every full pass of Python's cyclic collector walks all of them: 0.55 s
    a pass behind the GLV programs, three or four passes in a 30 s
    import (PERF.md, PR 28). Once a shape has been traced, what is alive
    moves to the permanent generation, which no pass visits. Reference
    counts still free what dies; only a cycle alive at this moment is kept
    for good, and this runs once a compiled shape (the program's call is
    already on the device, so the chip does not wait for it)."""
    gc.collect()
    gc.freeze()

# ---- kernel selection (-ecdsakernel=glv|w4|msm) ----------------------------
# "glv": the λ-endomorphism split verifier (ops/secp256k1 GLV core — 32
# windows / 128 doublings over four addition streams + the fixed-base G
# comb, lattice split in the program). "w4": the 64-window Pallas kernel,
# the rung a failed GLV dispatch falls to and the differential oracle.
# Selection is validated at node startup (node.py rejects unknown values
# before the first batch).
# "msm": the Pippenger batch-verification rung (ISSUE 19) — it applies to
# SCHNORR records only (the batch equation needs Schnorr's linear verify
# relation); ECDSA records under -ecdsakernel=msm ride the GLV ladder,
# and a failed/rejected MSM batch bisects down to the per-lane oracle.
ECDSA_KERNELS = ("glv", "w4", "msm")
# Fault-injection sites for the GLV rung (explicit opt-in only, like
# util/faults' "net" site: BCP_FAULT_OPS=all keeps meaning the four
# accelerator subsystems, so existing dead-backend drills are unchanged).
# fail-* modes prove the glv -> w4 dispatch fallback; poison-output proves
# the KAT gate catches a lying GLV mask and settles on the CPU engine.
# Two names, one rung: operators' drills arm either.
GLV_SITE = "ecdsa_glv"
GLV_DEV_SITE = "ecdsa_glv_dev"
# MSM batch-verification site (ISSUE 19), explicit-only like the GLV
# legs: fail-* proves the msm -> per-lane fallback rung (a dead MSM
# program degrades to the scalar oracle, never drops verdicts),
# poison-output proves the canary gate catches a lying batch verdict
# (the per-lane KAT gate cannot ride a ONE-bit batch result, so the MSM
# path carries its own known-answer batches — see _msm_verify_records).
MSM_SITE = "ecdsa_msm"
_KERNEL = "glv"  # set_kernel() (-ecdsakernel) replaces it


def active_kernel() -> str:
    """The kernel the next device dispatch will try first."""
    return _KERNEL


def set_kernel(name: str) -> str:
    """Select the device verify kernel; raises ValueError on unknown names
    (node startup turns that into a ConfigError — reject at init, not at
    the first batch)."""
    global _KERNEL
    if name not in ECDSA_KERNELS:
        raise ValueError(
            f"-ecdsakernel={name!r}: unknown kernel "
            f"(valid: {', '.join(ECDSA_KERNELS)})"
        )
    _KERNEL = name
    return name


def kernel_info() -> dict:
    """gettpuinfo's ``ecdsa`` section: the active kernel, GLV health, the
    one-time fixed-base-table build cost, and the host's two stages a
    dispatch: emit (numpy byte emission) and dispatch (program enqueue).
    There is one GLV rung, so ``glv_broken``/``glv_fallbacks`` and
    ``dev_decompose.broken``/``fallbacks`` read the same latch and the
    same counter (chipbench/checks.py and chip_smoke.py read both)."""
    from . import secp256k1 as dev_mod

    return {
        "kernel": active_kernel(),
        "kernels": list(ECDSA_KERNELS),
        "glv_broken": _GLV_BROKEN,
        "glv_dispatches": STATS.glv_dispatches,
        "glv_fallbacks": STATS.glv_fallbacks,
        "table_build_s": round(dev_mod.GLV_TABLE_BUILD_S, 4),
        "emit_s": round(STATS.glv_emit_s, 4),
        "dispatch_s": round(STATS.glv_dispatch_s, 4),
        "dev_decompose": {
            "enabled": glv_enabled(),
            "broken": _GLV_BROKEN,
            "dispatches": STATS.glv_dispatches,
            "fallbacks": STATS.glv_fallbacks,
        },
        "msm": {
            "schnorr_sigs": STATS.schnorr_sigs,
            "schnorr_cpu_sigs": STATS.schnorr_cpu_sigs,
            "dispatches": STATS.msm_dispatches,
            "batches_accepted": STATS.msm_batches_accepted,
            "batches_rejected": STATS.msm_batches_rejected,
            "bisects": STATS.msm_bisects,
            "bisect_depth_max": STATS.msm_bisect_depth_max,
            "fallback_sigs": STATS.msm_fallback_sigs,
            "canary_failures": STATS.msm_canary_failures,
        },
    }


@dataclass
class BatchStats:
    """Per-dispatch metrics surfaced via gettpuinfo (SURVEY.md §6.5)."""

    dispatches: int = 0
    sigs_verified: int = 0
    sigs_padded: int = 0
    cpu_fallback_sigs: int = 0
    # sigchecks that never reach the batch at all (gettpuinfo honesty:
    # what fraction of a block's sigops actually ran on the chip):
    eager_multisig_sigs: int = 0   # CHECKMULTISIG trials, verified inline
    # OP_CHECKMULTISIG operations whose key trials joined the batch as
    # candidate lanes (script/interpreter.py, module docstring): groups
    # deferred, lanes they took (m(n-m+1) a group, inside sigs_verified
    # too), and groups whose walk failed on the device's verdicts and went
    # back to the host's eager checker for the verdict
    multisig_groups: int = 0
    multisig_lanes: int = 0
    multisig_group_confirms: int = 0
    # checks below the fork height (flags without NULLFAIL) that ran on the
    # host at once: every check of the Python engines, and in the native
    # import those of the scripts its templates declined
    inline_legacy_sigs: int = 0
    # lanes the native import took for blocks below the fork height (their
    # signature check is the script's last operation: interpreter.py)
    prefork_lanes: int = 0
    sigcache_hits: int = 0         # records dropped by the sigcache probe
    p2pkh_fast_path: int = 0       # inputs that skipped the generic EvalScript
    device_seconds: float = 0.0
    last_batch: int = 0
    # P3 pipeline overlap: dispatches currently in flight / high-water mark
    in_flight: int = 0
    max_in_flight: int = 0
    pallas_fallbacks: int = 0  # w4 kernel failures (Mosaic refusals latch)
    # w4/glv kernel lanes flagged degenerate (adversarially-crafted H == 0
    # collisions) and re-verified on the CPU path — see ops/secp256k1.py
    degenerate_rechecks: int = 0
    # GLV kernel accounting (gettpuinfo `ecdsa` section): dispatches that
    # ran the GLV program, GLV failures that degraded to the w4 kernel,
    # and the host's two stages a dispatch: emit_s is the numpy byte
    # emission (pack_lanes: the ecdsa.pack spans' seconds), dispatch_s the
    # enqueue of the GLV program (the ecdsa.enqueue spans' seconds)
    glv_dispatches: int = 0
    glv_fallbacks: int = 0
    glv_emit_s: float = 0.0
    glv_dispatch_s: float = 0.0
    # supervised-dispatch accounting (ops/dispatch breaker layer): sigs
    # re-verified on the CPU engine because the device path failed or its
    # known-answer lanes came back wrong. NOTE sigs_padded includes the 2
    # KAT lanes riding every device batch.
    fault_fallback_sigs: int = 0
    kat_failures: int = 0
    # device-False lanes host-confirmed before they could reject a block
    # (reject-side verdicts are never the device's alone to make)
    reject_confirm_sigs: int = 0
    # Schnorr + MSM batch verification (ISSUE 19): schnorr_sigs counts
    # every Schnorr record entering dispatch; schnorr_cpu_sigs the ones
    # settled by the per-lane oracle (no MSM, bisect base cases, and
    # fallback re-verifies). msm_dispatches counts MSM PROGRAM calls
    # (canary batches included); a rejected batch bisects (msm_bisects,
    # with the deepest split level in msm_bisect_depth_max — O(log N)
    # per forged sig). msm_fallback_sigs are lanes that abandoned the
    # MSM rung entirely (dead program after retries -> per-lane oracle);
    # msm_canary_failures are canary-gate trips (also kat_failures).
    schnorr_sigs: int = 0
    schnorr_cpu_sigs: int = 0
    # Schnorr as a lane kind of the packed path (PR 44): lanes verified by
    # the device's Schnorr program (inside sigs_verified too) and its
    # dispatches (inside glv_dispatches too: the prepare stage does the
    # device split for both kinds)
    schnorr_lanes: int = 0
    schnorr_dispatches: int = 0
    msm_dispatches: int = 0
    msm_batches_accepted: int = 0
    msm_batches_rejected: int = 0
    msm_bisects: int = 0
    msm_bisect_depth_max: int = 0
    msm_fallback_sigs: int = 0
    msm_canary_failures: int = 0
    buckets_used: dict = field(default_factory=dict)

    def snapshot(self) -> dict:
        d = self.__dict__.copy()
        d["buckets_used"] = dict(self.buckets_used)
        return d


STATS = BatchStats()


def _note_device_dispatch(n: int, bucket: int) -> None:
    """Shared per-dispatch STATS bookkeeping (record-level and packed
    entry points must never diverge on the gettpuinfo counters)."""
    STATS.dispatches += 1
    STATS.sigs_verified += n
    STATS.sigs_padded += bucket - n
    STATS.last_batch = n
    STATS.buckets_used[bucket] = STATS.buckets_used.get(bucket, 0) + 1
    STATS.in_flight += 1
    STATS.max_in_flight = max(STATS.max_in_flight, STATS.in_flight)
    _LANES_H.observe(n)
    _IN_FLIGHT_G.set(STATS.in_flight)


def _bucket_for(n: int) -> int:
    """Lanes -> the padded size both byte programs compile for: {1024,
    2048, 4096}, then 2048-granular up to 16384, then 16384-granular (the
    programs split at 16384 per call). The jit bakes B into shapes and
    grid, so bucket sizes ARE compiled-program shapes and must stay a
    small bounded set (a fresh compile is minutes per shape; at most 9
    shapes exist, and only the ones actually hit compile).
    2048-granularity bounds worst-case padding waste at ~33% (n=4097 ->
    6144) and ~20% at the 10k scale. The floor is 1024 for every batch:
    a smaller shape would be one more program to compile, on the chip as
    on the CPU backend."""
    if n <= 1024:
        return 1024
    if n <= 4096:
        return 2048 if n <= 2048 else 4096
    if n <= 16384:
        return ((n + 2047) // 2048) * 2048
    return ((n + 16383) // 16384) * 16384


def decompose_scalars(records: Sequence) -> list[tuple[int, int]]:
    """(u1, u2) per record, Python ints: the no-native form of the
    precompute. Records carry (r, s, msg_hash)."""
    out = []
    for rec in records:
        w = pow(rec.s, oracle.N - 2, oracle.N)
        out.append((rec.msg_hash * w % oracle.N, rec.r * w % oracle.N))
    return out


def _pad(mat: np.ndarray, bucket: int) -> np.ndarray:
    """(m, w) uint8 rows -> (bucket, w), the rows past m zero."""
    out = np.zeros((bucket, mat.shape[1]), np.uint8)
    out[:len(mat)] = mat
    return out


def _ge_be(rows: np.ndarray, bound: int) -> np.ndarray:
    """rows (m, 32) big-endian uint8 >= bound, per row."""
    const = np.frombuffer(bound.to_bytes(32, "big"), np.uint8)
    differs = rows != const
    first = differs.argmax(axis=1)
    at = np.arange(len(rows))
    return ~differs.any(axis=1) | (rows[at, first] > const[first])


def pack_lanes(pub: np.ndarray, rs: np.ndarray, msg: np.ndarray,
               rn: np.ndarray, wrap: np.ndarray, bucket: int,
               schnorr: bool = False) -> list:
    """The one packer: m blob rows (records_to_blobs' layout, which is the
    native scan's) -> the eight arrays both byte programs take, padded to
    ``bucket`` lanes: u1, u2, qx, qy, r, rn as (bucket, 32) big-endian
    uint8 matrices, q_inf and wrap_ok as (bucket,) uint8. Padding lanes
    and lanes whose r or s the precompute range-flags are poisoned
    (q_inf = 1: the kernel reports False), so they can never turn a bad
    batch good or a good batch bad. u1/u2 come from the threaded native
    modular-inverse leg; the Python-int loop only if the library is
    missing.

    A Schnorr bucket (``schnorr``; schnorr_records_to_blobs' layout, the
    scan's for its Schnorr lanes) needs no inverse: u1 is s, u2 the rn
    slot's (n - e) mod n; lanes with r >= p or s >= n are poisoned. Six
    arrays: u1, u2, qx, qy, q_inf, r."""
    from .. import native

    m = len(msg)
    if schnorr:
        q_inf = np.ones(bucket, np.uint8)
        q_inf[:m] = _ge_be(rs[:, :32], oracle.P) | _ge_be(rs[:, 32:],
                                                           oracle.N)
        with tm.span("ecdsa.pack", lanes=m, bucket=bucket) as packed:
            arrays = [_pad(rs[:, 32:], bucket), _pad(rn, bucket),
                      _pad(pub[:, :32], bucket), _pad(pub[:, 32:], bucket),
                      q_inf, _pad(rs[:, :32], bucket)]
        STATS.glv_emit_s += packed.seconds
        return arrays
    if native.available():
        u1_blob, u2_blob, ok = native.ecdsa_precompute_blobs(
            rs.tobytes(), msg.tobytes(), m)
        u1 = np.frombuffer(u1_blob, np.uint8).reshape(m, 32)
        u2 = np.frombuffer(u2_blob, np.uint8).reshape(m, 32)
        range_bad = ~np.asarray(ok, bool)
    else:
        recs = _LazyRecords(pub, rs, msg)
        scalars = decompose_scalars([recs[i] for i in range(m)])
        u1 = np.frombuffer(
            b"".join(a.to_bytes(32, "big") for a, _ in scalars),
            np.uint8).reshape(m, 32)
        u2 = np.frombuffer(
            b"".join(b.to_bytes(32, "big") for _, b in scalars),
            np.uint8).reshape(m, 32)
        range_bad = np.zeros(m, bool)
    q_inf = np.ones(bucket, np.uint8)
    q_inf[:m] = range_bad.astype(np.uint8)
    wrap8 = np.zeros(bucket, np.uint8)
    wrap8[:m] = wrap
    with tm.span("ecdsa.pack", lanes=m, bucket=bucket) as packed:
        arrays = [_pad(u1, bucket), _pad(u2, bucket),
                  _pad(pub[:, :32], bucket), _pad(pub[:, 32:], bucket),
                  q_inf, _pad(rs[:, :32], bucket), _pad(rn, bucket), wrap8]
    STATS.glv_emit_s += packed.seconds
    return arrays


def _verify_cpu_ecdsa(records: Sequence) -> np.ndarray:
    """ECDSA CPU lane: the native C++ scalar module (threaded via -par)
    when available, else the Python-int oracle. Differential parity is
    covered by tests/unit/test_native.py."""
    from .. import native

    if native.available():
        return np.array(native.ecdsa_verify_batch(records), dtype=bool)
    return np.array(
        [
            oracle.ecdsa_verify(rec.pubkey, rec.r, rec.s, rec.msg_hash)
            for rec in records
        ],
        dtype=bool,
    )


def _schnorr_oracle(records: Sequence) -> np.ndarray:
    """Per-lane Schnorr verify on the CPU — the accept/reject reference
    every MSM verdict must match byte-identically (and the reject-side
    engine the bisection funnels into): the native threaded verify when the
    library loaded (differentially tested against the Python-int oracle,
    tests/unit/test_schnorr_lanes.py; 123 ms a signature without it), else
    that oracle."""
    from .. import native

    STATS.schnorr_cpu_sigs += len(records)
    if native.available():
        return np.array(native.schnorr_verify_batch(records), dtype=bool)
    return np.array(
        [
            oracle.schnorr_verify(rec.pubkey, rec.r, rec.s, rec.msg_hash)
            for rec in records
        ],
        dtype=bool,
    )


def _verify_cpu(records: Sequence) -> np.ndarray:
    """CPU lane, algorithm-aware: ECDSA records take the native/oracle
    scalar path, Schnorr records the Schnorr oracle. Mixed batches are
    partitioned and re-merged in submission order (the deferral layer
    tags every SigCheckRecord with ``algo``; blob-path _LazyRecords and
    legacy callers without the field default to ECDSA)."""
    algos = [getattr(rec, "algo", "ecdsa") for rec in records]
    if "schnorr" not in algos:
        return _verify_cpu_ecdsa(records)
    out = np.zeros(len(records), bool)
    ecd = [i for i, a in enumerate(algos) if a != "schnorr"]
    sch = [i for i, a in enumerate(algos) if a == "schnorr"]
    if ecd:
        out[ecd] = _verify_cpu_ecdsa([records[i] for i in ecd])
    out[sch] = _schnorr_oracle([records[i] for i in sch])
    return out


_KAT = None


def _kat_records() -> tuple:
    """Known-answer probe lanes appended to every device batch: one
    signature that MUST verify and one that MUST NOT (same sig, different
    message). A device that inverts, zeroes, or fabricates the validity
    mask gets both polarities wrong-side and the batch is discarded before
    any verdict can see it (BatchHandle.result's KAT gate). Generated once
    from the Python-int oracle."""
    global _KAT
    if _KAT is None:
        import hashlib

        from ..script.interpreter import SigCheckRecord

        d = 0x1D3F2A9C5B7E6D4F8A1B2C3D4E5F60718293A4B5C6D7E8F9
        e = int.from_bytes(
            hashlib.sha256(b"bcp-supervised-dispatch-kat").digest(), "big"
        ) % oracle.N
        r, s = oracle.ecdsa_sign(d, e)
        pub = oracle.point_mul(d, oracle.G)
        good = SigCheckRecord(pub, r, s, e)
        bad = SigCheckRecord(pub, r, s, (e + 1) % oracle.N)
        _KAT = (good, bad)
    return _KAT


# ---- Schnorr MSM batch verification (ISSUE 19) -----------------------------
#
# The device kernel (ops/secp256k1._msm_program) answers ONE bit per
# batch: does Σ a_i·R_i + Σ (a_i·e_i)·P_i + ((n − Σ a_i·s_i) mod n)·G
# land on the point at infinity. Trust architecture around that bit:
#
#   accept side — a CANARY gate per verify session: the program must
#     accept a known-good batch AND reject that batch with a known-bad
#     sig appended, before any real verdict is trusted (the per-lane KAT
#     gate can't ride a one-bit result). With the canary green, a false
#     accept requires the 2^-128 coefficient collision.
#   reject side — never the device's alone (repo invariant): a rejected
#     batch BISECTS with fresh coefficients per sub-batch; sub-batches at
#     or below MSM_MIN_BATCH settle on the per-lane Python oracle. One
#     forged signature therefore costs O(log N) sub-batch checks, and
#     every False the caller sees was produced by the oracle.
#   host prechecks — r/s range and the R = lift_x(r) existence test run
#     on the host and pre-reject without any device work. This cannot
#     diverge from the oracle: schnorr_verify accepts only if R'.x == r
#     for the computed finite R', which forces r³+7 to be a quadratic
#     residue — exactly the condition lift_x tests (and the oracle's
#     jacobi(R'.y) gate matches lift_x's root choice).

_SCHNORR_KAT = None


def _schnorr_kat_records() -> tuple:
    """Known-answer Schnorr records for the MSM canary batches: one
    signature that MUST verify and one that MUST NOT (same sig, shifted
    message). Generated once from the Python-int oracle."""
    global _SCHNORR_KAT
    if _SCHNORR_KAT is None:
        import hashlib

        from ..script.interpreter import SigCheckRecord

        d = 0x5A7D1C9E3B8F6A2D4C1E8B7F9A3D5C6E8F1A2B4D6C8E9F1B3A5C7E9D2B4F6A8C
        d %= oracle.N
        e = int.from_bytes(
            hashlib.sha256(b"bcp-msm-batch-kat").digest(), "big"
        ) % oracle.N
        r, s = oracle.schnorr_sign(d, e)
        pub = oracle.point_mul(d, oracle.G)
        good = SigCheckRecord(pub, r, s, e, algo="schnorr")
        bad = SigCheckRecord(pub, r, s, (e + 1) % oracle.N, algo="schnorr")
        _SCHNORR_KAT = (good, bad)
    return _SCHNORR_KAT


def _verify_cpu_schnorr_blobs(pub, rs, msg, n: int) -> np.ndarray:
    """CPU verdicts for n Schnorr blob rows: the native threaded Schnorr
    verify, else the Python oracle (counted in schnorr_cpu_sigs)."""
    from .. import native

    if native.available():
        return np.asarray(native.schnorr_verify_batch_blobs(
            pub.tobytes(), rs.tobytes(), msg.tobytes(), n), bool)
    recs = _LazyRecords(pub, rs, msg, "schnorr")
    return _schnorr_oracle([recs[i] for i in range(n)])


def schnorr_records_to_blobs(records: Sequence):
    """Schnorr SigCheckRecords in the blob layout of a Schnorr bucket: pub
    (n,64), r||s (n,64), msg (n,32), and in the rn slot (n - e) mod n, the
    key's scalar in R' = s*G + (n - e)*P; wrap is 0. r, s and msg go in
    mod 2^256 (pack_lanes range-flags r >= p, s >= n). The challenge hash
    runs on the native library's threads, else in Python, inside the span
    ``schnorr.precompute`` (the import's scan has done it for the lanes it
    took: last_import_stats.schnorr_challenge_s)."""
    from .. import native

    n = len(records)
    if any(getattr(r, "algo", "ecdsa") != "schnorr" for r in records):
        raise ValueError("schnorr_records_to_blobs packs Schnorr records only")
    top = 1 << 256
    pub, rs, msg = _record_rows(records)
    with tm.span("schnorr.precompute", lanes=n):
        if native.available():
            blob, _ = native.schnorr_challenge_blobs(
                pub.tobytes(), rs.tobytes(), msg.tobytes(), n)
        else:
            blob = b"".join(
                ((oracle.N - oracle.schnorr_challenge(
                    r.r % top, r.pubkey, r.msg_hash)) % oracle.N
                 ).to_bytes(32, "big") for r in records)
    u2 = np.frombuffer(blob, np.uint8).reshape(n, 32)
    return pub, rs, msg, u2, np.zeros(n, np.uint8)


_SCHNORR_KAT_BLOBS = None


def _schnorr_kat_blobs() -> tuple:
    """The two known-answer lanes of a Schnorr bucket (_schnorr_kat_records:
    one that MUST verify, one that MUST NOT), in blob layout; made once."""
    global _SCHNORR_KAT_BLOBS
    if _SCHNORR_KAT_BLOBS is None:
        _SCHNORR_KAT_BLOBS = schnorr_records_to_blobs(
            list(_schnorr_kat_records()))
    return _SCHNORR_KAT_BLOBS


def _msm_rng() -> random.Random:
    """Coefficient RNG for one verify session. Security rests on the
    coefficients being unpredictable to whoever crafted the signatures;
    os.urandom seeds each session. BCP_MSM_SEED pins the stream for
    deterministic drills/benches (never set in production)."""
    seed = os.environ.get("BCP_MSM_SEED")
    if seed is not None:
        return random.Random(int(seed, 0))
    return random.Random(int.from_bytes(os.urandom(16), "big"))


def _schnorr_precheck(rec):
    """Host-side pre-reject + R lift: returns the affine R = lift_x(r)
    for a structurally admissible record, None where the oracle is
    guaranteed to reject (range violation, unliftable r, missing
    pubkey) — see the section comment for the oracle-consistency
    argument."""
    if rec.pubkey is None:
        return None
    if not (0 <= rec.r < oracle.P and 0 <= rec.s < oracle.N):
        return None
    return oracle.schnorr_lift_x(rec.r)


def _msm_bucket_for(m: int) -> int:
    for b in _MSM_BUCKETS:
        if m <= b:
            return b
    raise ValueError(f"MSM term count {m} exceeds the bucket ladder")


def _msm_pack(terms, bucket: int):
    """(x, y, scalar) Python-int terms -> the MSM program's byte
    matrices, padded to ``bucket`` with infinity-flagged zero-scalar
    lanes (contribute nothing by construction)."""
    m = len(terms)
    xm = np.zeros((bucket, 32), np.uint8)
    ym = np.zeros((bucket, 32), np.uint8)
    km = np.zeros((bucket, 32), np.uint8)
    inf8 = np.ones(bucket, np.uint8)
    xm[:m] = np.frombuffer(
        b"".join(x.to_bytes(32, "big") for x, _, _ in terms),
        np.uint8).reshape(m, 32)
    ym[:m] = np.frombuffer(
        b"".join(y.to_bytes(32, "big") for _, y, _ in terms),
        np.uint8).reshape(m, 32)
    km[:m] = np.frombuffer(
        b"".join(k.to_bytes(32, "big") for _, _, k in terms),
        np.uint8).reshape(m, 32)
    inf8[:m] = 0
    return xm, ym, inf8, km


def _msm_device_check(pairs, rng: random.Random) -> bool:
    """ONE batch-equation check on the device: ``pairs`` is a list of
    (record, lifted_R) with every record already through
    _schnorr_precheck. Draws FRESH random coefficients (bisection calls
    this per sub-batch — reusing coefficients across splits would let a
    crafted pair of forgeries cancel in one half). Returns the batch
    verdict."""
    from . import secp256k1 as dev

    INJECTOR.on_call(MSM_SITE)
    s_acc = 0
    terms = []
    for i, (rec, lift) in enumerate(pairs):
        # a_0 = 1 is safe (the adversary can't anticipate which sig lands
        # first in a *sub*-batch) and saves one 128-bit scalar ladder
        a = 1 if i == 0 else rng.getrandbits(128) | 1
        e = oracle.schnorr_challenge(rec.r, rec.pubkey, rec.msg_hash)
        s_acc = (s_acc + a * rec.s) % oracle.N
        terms.append((lift[0], lift[1], a))
        terms.append((rec.pubkey[0], rec.pubkey[1], (a * e) % oracle.N))
    terms.append((oracle.GX, oracle.GY, (oracle.N - s_acc) % oracle.N))
    bucket = _msm_bucket_for(len(terms))
    with tm.span("ecdsa.pack", terms=len(terms), bucket=bucket):
        arrays = _msm_pack(terms, bucket)
    out = _watched_kernel(
        _PW_MSM, bucket, arrays,
        lambda: dev.schnorr_msm_is_infinity(*arrays),
        jitfn=dev._msm_program, split=None)
    STATS.msm_dispatches += 1
    ok = bool(np.asarray(out)[0])
    if INJECTOR.should_poison(MSM_SITE):
        ok = not ok
    return ok


def _msm_verify_records(records: Sequence) -> np.ndarray:
    """Verdicts for a pure-Schnorr batch via the MSM batch check +
    bisection. Byte-identical to the per-lane oracle: pre-rejected lanes
    are oracle-guaranteed False, rejected batches funnel to the oracle,
    and accepted batches are wrong only on a 2^-128 coefficient
    collision (with the canary proving the program can tell good from
    bad at all). Raises on device/canary failure — _dispatch_msm owns
    the retry/fallback supervision."""
    n = len(records)
    out = np.zeros(n, bool)
    lifts = [_schnorr_precheck(rec) for rec in records]
    live = [i for i in range(n) if lifts[i] is not None]
    if not live:
        return out
    rng = _msm_rng()

    # canary gate (see section comment): both polarities must be right
    # before any real verdict from this session is trusted
    kg, kb = _schnorr_kat_records()
    kgl = _schnorr_precheck(kg)
    kbl = _schnorr_precheck(kb)
    if (not _msm_device_check([(kg, kgl)], rng)
            or _msm_device_check([(kg, kgl), (kb, kbl)], rng)):
        STATS.msm_canary_failures += 1
        STATS.kat_failures += 1
        raise PoisonedOutput("ecdsa msm canary batches wrong")

    depth_max = 0

    def solve(idxs, depth: int) -> None:
        nonlocal depth_max
        depth_max = max(depth_max, depth)
        if len(idxs) <= MSM_MIN_BATCH:
            out[idxs] = _schnorr_oracle([records[i] for i in idxs])
            return
        if _msm_device_check([(records[i], lifts[i]) for i in idxs], rng):
            out[idxs] = True
            STATS.msm_batches_accepted += 1
            return
        STATS.msm_batches_rejected += 1
        STATS.msm_bisects += 1
        mid = len(idxs) // 2
        solve(idxs[:mid], depth + 1)
        solve(idxs[mid:], depth + 1)

    # chunk so M = 2n+1 stays inside the bucket ladder; each chunk is an
    # independent batch equation
    for s in range(0, len(live), MSM_MAX_RECORDS):
        solve(live[s:s + MSM_MAX_RECORDS], 0)
    STATS.msm_bisect_depth_max = max(STATS.msm_bisect_depth_max, depth_max)
    return out


def _dispatch_msm(records: Sequence, br) -> Optional[BatchHandle]:
    """Supervised MSM dispatch for a pure-Schnorr record list. EAGER
    (synchronous settle): the bisection ladder is verdict-driven, so
    there is nothing to pipeline — the returned handle already carries
    the final verdicts. Returns None when every attempt failed (caller
    owns the per-lane fallback). Mirrors _dispatch_packed_device's
    supervision: breaker retries with backoff, programming errors
    re-raise, canary trips are PoisonedOutput and retried like any
    device fault."""
    boff = Backoff(base=br.cfg.backoff_base, maximum=1.0)
    last: Optional[BaseException] = None
    for attempt in range(br.cfg.retries + 1):
        try:
            INJECTOR.on_call("ecdsa")
            out = _msm_verify_records(records)
            br.record_success()
            return BatchHandle(len(records), cpu_ok=out)
        except (KeyboardInterrupt, SystemExit):
            raise
        except SURFACE_ERRORS:
            raise  # programming errors must not degrade silently
        except Exception as e:  # noqa: BLE001 — supervised boundary
            last = e
            if attempt < br.cfg.retries:
                time.sleep(boff.next())
    br.record_failure(last)
    br.note_fallback(len(records))
    STATS.msm_fallback_sigs += len(records)
    log_printf("schnorr msm dispatch failed (%s: %s) — per-lane oracle "
               "fallback for %d sig(s)", type(last).__name__,
               str(last)[:120], len(records))
    return None


def _dispatch_schnorr(records: Sequence, backend: str,
                      kernel: str | None) -> "BatchHandle":
    """Dispatch a pure-Schnorr record list: the MSM batch check when the
    msm kernel is selected and a device is worth dispatching to, else
    the per-lane oracle. The per-lane path IS the reference engine — no
    KAT/confirm layer needed."""
    n = len(records)
    STATS.schnorr_sigs += n
    kern = kernel if kernel in ECDSA_KERNELS else active_kernel()
    use_device = kern == "msm" and (
        backend == "device"
        or (backend == "auto" and n >= CPU_FLOOR and _device_available())
    )
    if use_device:
        br = dispatch.breaker("ecdsa")
        if br.allow():
            handle = _dispatch_msm(records, br)
            if handle is not None:
                return handle
            STATS.fault_fallback_sigs += n
        else:
            br.note_fallback(n)
            STATS.fault_fallback_sigs += n
    STATS.cpu_fallback_sigs += n
    return BatchHandle(n, cpu_ok=_schnorr_oracle(records))


class _MergedHandle:
    """Mixed ECDSA/Schnorr dispatch: per-algorithm sub-handles re-merged
    into submission order at settle. Result is memoized like
    BatchHandle; _bucket mirrors the widest sub-dispatch so LanePacker's
    fill metering keeps working through mixed batches."""

    __slots__ = ("_n", "_parts", "_result", "_bucket")

    def __init__(self, n: int, parts):
        self._n = n
        self._parts = parts  # [(handle, submission indices), ...]
        self._bucket = max(
            (getattr(h, "_bucket", 0) for h, _ in parts), default=0)
        self._result = None

    def result(self) -> np.ndarray:
        if self._result is None:
            out = np.zeros(self._n, bool)
            for handle, idxs in self._parts:
                out[idxs] = handle.result()
            self._result = out
            self._parts = ()
        return self._result


def _device_available() -> bool:
    """True when the JAX backend is worth dispatching to. An accelerator
    always is. When JAX is CPU-only, the XLA form of the verify kernel is
    ~20x slower than the native C++ batch (measured 250 vs 4600 sigs/s),
    so "auto" prefers the CPU lane — but only when the native library
    actually loaded; without it the CPU lane is the per-sig Python oracle
    (~10 sigs/s), and the XLA kernel is still the best option.
    backend="device" always forces the XLA path (virtual-mesh tests)."""
    if os.environ.get("BCP_NO_DEVICE"):
        return False
    from .sha256 import backend_is_cpu

    if backend_is_cpu():
        from .. import native

        return not native.available()
    return True


BACKENDS = ("auto", "device", "cpu")


def dispatch_backend(tpu_backend: str, lane_floor: bool = False) -> str:
    """The one meaning of -tpu: Config.tpu_backend ("auto" | "tpu" |
    "cpu") -> what dispatch_batch / dispatch_packed / LanePacker /
    SigService understand. -tpu=1 forces the block-connect consumers onto
    the device; lane_floor=True (mempool accept, SigService) keeps "auto"
    there, so the lane floor still decides and a one-signature
    transaction is not padded to a 1,024-lane dispatch."""
    if tpu_backend == "tpu":
        return "auto" if lane_floor else "device"
    if tpu_backend in ("auto", "cpu"):
        return tpu_backend
    raise ValueError(f"unknown -tpu backend {tpu_backend!r}")


def _check_backend(backend: str) -> None:
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown dispatch backend {backend!r} (one of {BACKENDS})")


class KernelRefused(RuntimeError):
    """The compiler deterministically refused a verify kernel while the
    device is required (-tpu=1): fatal, never a rung down the ladder."""


# errors every supervised boundary re-raises instead of degrading: a
# programming error must not hide behind a green fallback forever, and
# neither may a refused kernel under -tpu=1
SURFACE_ERRORS = (NameError, AttributeError, UnboundLocalError,
                  KernelRefused)

_REQUIRE_DEVICE = False


def require_device(on: bool) -> None:
    """-tpu=1: a deterministic compiler refusal raises KernelRefused where
    it would otherwise latch a kernel broken and carry on one rung down.
    Transient errors keep going through the breaker either way."""
    global _REQUIRE_DEVICE
    _REQUIRE_DEVICE = bool(on)


def _compiler_refused(e: Exception) -> bool:
    """True for a deterministic Mosaic/lowering refusal (latch-worthy), as
    opposed to a transient error; raises KernelRefused under -tpu=1."""
    text = f"{type(e).__name__}: {e}"
    refused = ("Mosaic" in text or "NotImplementedError" in text
               or "lowering" in text)
    if refused and _REQUIRE_DEVICE:
        raise KernelRefused(text) from e
    return refused


class BatchHandle:
    """An in-flight verify dispatch (P3 pipeline overlap, SURVEY.md §3.2).

    JAX dispatch is asynchronous: `dispatch_batch` returns immediately with
    the device computation enqueued, and the host keeps interpreting the
    next transactions' scripts while the chip verifies — the CCheckQueue
    master/worker overlap, with XLA's async runtime as the worker pool.
    `.result()` materializes (blocks) and finalizes stats.

    Supervision (ops/dispatch): device-path handles carry the records and
    the ecdsa breaker; a materialization error or a wrong known-answer
    lane at settle time counts a breaker failure and the verdict is a
    FRESH CPU re-verification of the real records — never a cached or
    fabricated mask."""

    __slots__ = ("_n", "_bucket", "_device_ok", "_cpu_ok", "_degen",
                 "_records", "_breaker", "_kat", "_recover", "_ctx",
                 "_candidate", "_recheck")

    def __init__(self, n, bucket=0, device_ok=None, cpu_ok=None,
                 degen=None, records=None, breaker=None, kat=False,
                 recover=None, ctx=None, candidate=None, recheck=None):
        self._n = n
        self._bucket = bucket
        self._device_ok = device_ok
        self._cpu_ok = cpu_ok
        self._degen = degen
        self._records = records
        self._breaker = breaker
        self._kat = kat
        self._recover = recover  # whole-batch CPU verdict over the blobs
        # enqueue-side trace context: the settle span (possibly another
        # thread, possibly many blocks later) links back to the span that
        # dispatched this batch
        self._ctx = ctx
        # multisig candidate lanes (bool mask over the n real lanes, or
        # None): a False there is an answer, not an alarm
        self._candidate = candidate
        # CPU verdicts for a few lanes by index (degenerate lanes, device
        # Falses); None: _verify_cpu over ``records``
        self._recheck = recheck

    def _redo(self, idxs) -> np.ndarray:
        if self._recheck is not None:
            return self._recheck(idxs)
        return _verify_cpu([self._records[i] for i in idxs])

    def _device_failed(self, err: BaseException) -> np.ndarray:
        """Settle-time device failure: breaker bookkeeping + CPU re-verify
        of the real lanes (the verdict that reaches the caller is computed
        by the reference engine, not recycled device output)."""
        if self._breaker is not None:
            self._breaker.record_failure(err)
            self._breaker.note_fallback(self._n)
        STATS.cpu_fallback_sigs += self._n
        STATS.fault_fallback_sigs += self._n
        log_printf("ecdsa device batch failed at settle (%s: %s) — CPU "
                   "re-verify of %d sig(s)",
                   type(err).__name__, str(err)[:120], self._n)
        out = self._recover()
        self._degen = None
        self._records = self._recover = self._recheck = None
        self._cpu_ok = np.asarray(out, dtype=bool)
        return self._cpu_ok

    def done(self) -> bool:
        """Whether result() would return without waiting for the device:
        asks the runtime, blocks on nothing."""
        ready = getattr(self._device_ok, "is_ready", None)
        return ready is None or ready()  # a host array is ready

    def result(self) -> np.ndarray:
        if self._device_ok is None:
            return self._cpu_ok
        settle = tm.span("ecdsa.settle", parent=self._ctx, lanes=self._n,
                         bucket=self._bucket)
        try:
            with settle:
                ok = np.asarray(self._device_ok)  # blocks until chip done
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # device died between enqueue and settle
            STATS.in_flight = max(0, STATS.in_flight - 1)
            _IN_FLIGHT_G.set(STATS.in_flight)
            self._device_ok = None
            return self._device_failed(e)
        # device_seconds counts only the blocking wait — when the P3
        # overlap is doing its job the host hid the latency and this is
        # near zero; summing dispatch->settle spans would double-count
        # concurrent chunks and absorb host interpreter time. The wait is
        # timed once, by the span; _SETTLE_H keeps its distribution.
        STATS.device_seconds += settle.seconds
        _SETTLE_H.observe(settle.seconds)
        # result fetch: the d2h crossing this settle paid (the mask's bytes)
        dw.note_transfer("ecdsa", "d2h", int(np.asarray(ok).nbytes))
        STATS.in_flight = max(0, STATS.in_flight - 1)
        _IN_FLIGHT_G.set(STATS.in_flight)
        self._device_ok = None
        ok = np.asarray(ok, dtype=bool)
        if INJECTOR.should_poison("ecdsa"):
            ok = ~ok
        if self._kat:
            # known-answer gate: lanes n and n+1 are the good/bad probe
            # records appended at dispatch; both polarities must be right
            # before ANY lane of this batch is trusted
            if not bool(ok[self._n]) or bool(ok[self._n + 1]):
                STATS.kat_failures += 1
                return self._device_failed(
                    PoisonedOutput("ecdsa known-answer lanes wrong"))
        out = ok[: self._n].copy()
        if self._degen is not None:
            # w4 kernel: degenerate lanes (adversarial H == 0 collisions)
            # carry garbage — re-verify them on the scalar CPU path. The
            # kernel's verdict for those lanes is NEVER trusted.
            degen = np.asarray(self._degen)[: self._n]
            idxs = np.nonzero(degen)[0]
            if idxs.size:
                STATS.degenerate_rechecks += int(idxs.size)
                out[idxs] = self._redo(idxs)
            self._degen = None
        if self._records is not None:
            # reject-side host confirmation: a device False is never
            # allowed to reject a block on its own (the KAT lanes can't
            # see a single corrupted real lane) — same contract as the
            # pow.py batch check and dispatch.merkle_root. Honest-valid
            # blocks have zero False lanes, so this is free in the common
            # case; an invalid-sig block pays one oracle verify per bad
            # lane, which the pure-CPU reference paid anyway.
            # A multisig candidate lane is exempt from this per-lane step
            # and from nothing else: most of a group's trials fail by
            # design. The rule holds for the group as a whole instead: a
            # walk that fails on these verdicts goes back to the host's
            # eager checker (the caller that passed ``candidate``).
            bad = ~out
            if self._candidate is not None:
                bad &= ~self._candidate
            bad = np.nonzero(bad)[0]
            if bad.size:
                STATS.reject_confirm_sigs += int(bad.size)
                out[bad] = self._redo(bad)
        if self._breaker is not None:
            self._breaker.record_success()
        self._records = self._recover = self._recheck = None  # the blobs go
        self._cpu_ok = out
        return self._cpu_ok


def dispatch_batch(records: Sequence, backend: str = "auto",
                   kernel: str | None = None) -> BatchHandle:
    """Enqueue a verify batch without waiting; returns a BatchHandle.

    backend: "auto" (device if available and batch >= CPU_FLOOR),
    "device" (force), "cpu" (force oracle — synchronous).
    kernel: per-call override of the device verify kernel
    ("glv"/"w4"/"msm"); None uses active_kernel() (the -ecdsakernel
    startup selection). "msm" selects the Pippenger batch check for the
    Schnorr lanes; ECDSA lanes under "msm" ride the GLV ladder (the MSM
    batch equation is Schnorr-shaped).

    The device leg is supervised (ops/dispatch): the ecdsa circuit breaker
    gates it, bounded retries absorb transient dispatch errors, and a
    failed dispatch degrades to a fresh CPU verification of the same
    records — the verdict the caller sees is never dropped or fabricated."""
    _check_backend(backend)
    if not records:
        return BatchHandle(0, cpu_ok=np.zeros(0, bool))
    n = len(records)
    # Schnorr lanes (script interpreter 64-byte-sig discrimination) take
    # the MSM batch path; mixed batches split per algorithm and re-merge
    # in submission order at settle
    algos = [getattr(r, "algo", "ecdsa") for r in records]
    if any(a == "schnorr" for a in algos):
        if all(a == "schnorr" for a in algos):
            return _dispatch_schnorr(records, backend, kernel)
        e_idx = [i for i, a in enumerate(algos) if a != "schnorr"]
        s_idx = [i for i, a in enumerate(algos) if a == "schnorr"]
        return _MergedHandle(n, [
            (dispatch_batch([records[i] for i in e_idx], backend,
                            kernel=kernel), e_idx),
            (_dispatch_schnorr([records[i] for i in s_idx], backend,
                               kernel), s_idx),
        ])
    use_device = backend == "device" or (
        backend == "auto"
        and n >= CPU_FLOOR
        and _device_available()
    )
    if use_device:
        br = dispatch.breaker("ecdsa")
        if br.allow():
            handle = _dispatch_packed_device(
                *records_to_blobs(records), n, br,
                kernel if kernel in ECDSA_KERNELS else active_kernel(),
                records=records)
            if handle is not None:
                return handle
            # device leg failed after retries (breaker already charged):
            # fresh CPU re-verification, counted as fault fallback
            STATS.fault_fallback_sigs += n
        else:
            br.note_fallback(n)
            STATS.fault_fallback_sigs += n
    STATS.cpu_fallback_sigs += n
    return BatchHandle(n, cpu_ok=_verify_cpu(records))


def _interpret_kernels() -> bool:
    """True when the Pallas w4 kernel must run in interpret mode: CPU
    backends have no Mosaic (pallas_call raises "Only interpret mode is
    supported on CPU backend"). Interpret mode lowers the real w4 kernel
    through XLA — the same arrangement parallel/sig_shard uses on virtual
    CPU meshes."""
    from .sha256 import backend_is_cpu

    return backend_is_cpu()


# One latch a rung: set by a deterministic compiler refusal only, so a
# toolchain that cannot compile a program stops trying once, not per
# dispatch. Transient errors (injected drill faults among them) do not
# latch, and under -tpu=1 a refusal raises KernelRefused instead.
_PALLAS_BROKEN = False
_GLV_BROKEN = False


def glv_enabled() -> bool:
    """Gate for the GLV rung (kernel selection happens separately — see
    active_kernel)."""
    return not _GLV_BROKEN


def _note_glv_failure(e: Exception, then: str = "w4 fallback") -> None:
    """GLV-rung failure bookkeeping: the dispatch degrades to the w4
    kernel (same supervised attempt; a Schnorr bucket has no w4 form and
    fails the attempt: ``then`` says which, for the log). Programming
    errors re-raise — same invariant as _note_pallas_failure: a NameError
    in the GLV core must not hide behind a green w4 fallback forever."""
    global _GLV_BROKEN
    if isinstance(e, SURFACE_ERRORS):
        raise e
    STATS.glv_fallbacks += 1
    text = f"{type(e).__name__}: {e}"
    if _compiler_refused(e):
        _GLV_BROKEN = True
    log_printf("glv verify kernel failed (%s) — %s%s",
               text[:200], then,
               " (latched)" if _GLV_BROKEN else "")


def pallas_enabled() -> bool:
    """Gate for the w4 rung."""
    return not _PALLAS_BROKEN


def _note_pallas_failure(e: Exception) -> None:
    """w4-rung failure bookkeeping (jit compilation is synchronous, so
    failures surface at the dispatch call); the attempt fails and the
    retry loop, then the breaker, take over.

    Programming errors are NOT toolchain failures: a NameError inside the
    kernel code would otherwise degrade silently to the CPU engine
    forever (it happened — a refactor deleted a module constant and every
    test stayed green on the fallback). Those re-raise."""
    global _PALLAS_BROKEN
    if isinstance(e, SURFACE_ERRORS):
        raise e
    STATS.pallas_fallbacks += 1
    text = f"{type(e).__name__}: {e}"
    if _compiler_refused(e):
        _PALLAS_BROKEN = True  # this toolchain can't compile it
    log_printf("pallas ECDSA kernel failed (%s)%s", text[:200],
               " (latched)" if _PALLAS_BROKEN else "")


def verify_batch(records: Sequence, backend: str = "auto",
                 kernel: str | None = None) -> np.ndarray:
    """Verify all records synchronously; returns (len(records),) bool."""
    return dispatch_batch(records, backend, kernel=kernel).result()


# ---------------------------------------------------------------------------
# Cross-block lane packer — the pipelined IBD engine's aggregation layer.
#
# A single mainnet-shaped block rarely fills a padded bucket, so per-block
# dispatch pays padding (and a whole dispatch round trip) for
# partially-filled lanes. The packer aggregates deferred records from
# MULTIPLE in-flight blocks (the ChainstateManager settle horizon) and
# dispatches only full buckets; each contributing block gets its own
# SigBatchFuture whose lanes map back into the shared BatchHandles, so
# failure attribution and settle order stay per-block. Supervision is
# unchanged: every underlying dispatch is the breaker/KAT-gated
# dispatch_batch, and BatchHandle.result() is memoized, so many futures
# can share one handle safely.
# ---------------------------------------------------------------------------


class SigBatchFuture:
    """One block's slice of the cross-block packed dispatches. result()
    returns a bool verdict per record in submission order; it forces a
    packer flush if any of this block's records are still undispatched
    (settling the horizon's oldest block must never deadlock on lanes
    parked behind it)."""

    __slots__ = ("_packer", "_segments", "_queued", "_result", "_tag")

    def __init__(self, packer):
        self._packer = packer
        self._segments = []  # (handle-wrapper, start, end), dispatch order
        self._queued = 0     # records still in the packer's pending buffer
        self._result = None
        self._tag = None     # speculation-tree branch attribution

    def result(self) -> np.ndarray:
        if self._result is None:
            if self._queued:
                self._packer.flush_for(self)
            parts = [self._packer._settle(h)[s:e]
                     for h, s, e in self._segments]
            self._result = (np.concatenate(parts) if parts
                            else np.zeros(0, bool))
            self._segments = []
        return self._result

    def drain(self) -> None:
        """Abort-path settle: records still parked in the packer's pending
        buffer are DISCARDED (verifying doomed lanes — up to a whole
        horizon's worth on an unwind — would be pure waste), while
        already-dispatched segments are materialized so STATS.in_flight
        and a breaker probe riding one of them never strand. Verdicts are
        ignored."""
        try:
            if self._queued:
                self._packer.discard(self)
            for pd, _s, _e in self._segments:
                try:
                    self._packer._settle(pd)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception:  # noqa: BLE001 — abort-path drain
                    pass
        finally:
            self._segments = []
            if self._result is None:
                self._result = np.zeros(0, bool)


class _PackedDispatch:
    """A shared BatchHandle plus the overlap-metering timestamps."""

    __slots__ = ("handle", "t_enqueue", "settled")

    def __init__(self, handle, t_enqueue):
        self.handle = handle
        self.t_enqueue = t_enqueue
        self.settled = False


class LanePacker:
    """Aggregate SigCheckRecords across blocks into full padded buckets.

    ``lanes`` is the dispatch size; the default (2046) fills the 2048
    bucket exactly once the supervised dispatch appends its 2 known-answer
    lanes. When the ecdsa breaker is not healthy the packer stops
    aggregating (target 0): every add flushes immediately, because with
    the device path open all lanes go to the CPU engine and aggregation
    would only add settle latency."""

    # branch-attribution bound: the per-tag lane tallies must not grow
    # without limit under a fork storm minting fresh branch tags
    MAX_TAGS = 64

    def __init__(self, backend: str = "auto", lanes: int = 2046,
                 kernel: str | None = None):
        self.backend = backend
        self.lanes = lanes
        self.kernel = kernel  # per-packer -ecdsakernel override (wiring)
        self._pending: list = []           # records awaiting dispatch
        self._pending_futs: list = []      # (future, count) per add(), order
        self.stats = {
            "dispatches": 0, "lanes_real": 0, "lanes_padded": 0,
            "lanes_discarded": 0, "blocks": 0,
            "inflight_s": 0.0, "blocked_s": 0.0,
        }
        # speculation-tree branch attribution (ISSUE 9): lanes added /
        # discarded per branch tag — competing branches share buckets,
        # this is the per-branch split of the shared device work
        self.branch_lanes: dict[str, int] = {}
        self.branch_discards: dict[str, int] = {}

    def _tag_note(self, table: dict, tag: str | None, n: int) -> None:
        if tag is None or n <= 0:
            return
        if tag not in table and len(table) >= self.MAX_TAGS:
            table.pop(next(iter(table)))  # oldest tag out
        table[tag] = table.get(tag, 0) + n

    def _target_lanes(self) -> int:
        if self.backend == "cpu":
            return self.lanes  # no padding concept, but batching still wins
        if not dispatch.breaker("ecdsa").healthy():
            return 0  # device path distrusted: no point holding lanes back
        return self.lanes

    def add(self, records: Sequence, tag: str | None = None
            ) -> SigBatchFuture:
        """Enqueue one block's fresh (sigcache-missed) records; returns the
        block's future. Dispatches fire whenever a full bucket is banked.
        ``tag`` attributes the lanes to a speculation-tree branch."""
        fut = SigBatchFuture(self)
        fut._queued = len(records)
        fut._tag = tag
        self._tag_note(self.branch_lanes, tag, len(records))
        if records:
            self._pending.extend(records)
            self._pending_futs.append((fut, len(records)))
        target = self._target_lanes()
        if target <= 0:
            self.flush()  # device distrusted: don't hold lanes back
        else:
            while len(self._pending) >= target:
                self._dispatch(target)
        return fut

    def flush(self) -> None:
        """Dispatch everything still pending (sub-bucket tail included)."""
        while self._pending:
            self._dispatch(min(len(self._pending), max(self.lanes, 1)))

    def discard(self, fut: SigBatchFuture) -> None:
        """Drop ``fut``'s still-undispatched records from the pending
        buffer (abort path — see SigBatchFuture.drain)."""
        if fut._queued <= 0:
            return
        off = 0
        for i, (f, count) in enumerate(self._pending_futs):
            if f is fut:
                del self._pending[off:off + count]
                self._pending_futs.pop(i)
                self.stats["lanes_discarded"] += count
                self._tag_note(self.branch_discards, fut._tag, count)
                fut._queued = 0
                return
            off += count

    def flush_for(self, fut: SigBatchFuture) -> None:
        """Dispatch only the pending PREFIX up to (and including) ``fut``'s
        records — settling the horizon's oldest block must not also ship
        younger blocks' sub-bucket tails, which can keep aggregating
        toward full buckets (lanes queue FIFO, so the prefix is exactly
        what fut needs)."""
        while fut._queued > 0 and self._pending:
            self._dispatch(min(len(self._pending), max(self.lanes, 1)))

    def _dispatch(self, n: int) -> None:
        batch = self._pending[:n]
        del self._pending[:n]
        try:
            handle = dispatch_batch(batch, backend=self.backend,
                                    kernel=self.kernel)
        except (KeyboardInterrupt, SystemExit, *SURFACE_ERRORS):
            raise  # programming errors must surface, not degrade
        except Exception:
            # same last-line-of-defense contract as the per-block verifier:
            # a supervision-layer crash must not drop the batch
            STATS.fault_fallback_sigs += len(batch)
            handle = dispatch_batch(batch, backend="cpu")
        pd = _PackedDispatch(handle, time.monotonic())
        st = self.stats
        st["dispatches"] += 1
        st["lanes_real"] += len(batch)
        _PACKER_FLUSH_H.observe(len(batch))
        # padding booked from the handle's ACTUAL bucket (0 = the dispatch
        # took the CPU lane, which has no padding concept); the 2 KAT lanes
        # ride every device batch and are excluded from the fill metric
        bucket = getattr(handle, "_bucket", 0)
        if bucket:
            st["lanes_padded"] += max(0, bucket - len(batch) - 2)
        total = st["lanes_real"] + st["lanes_padded"]
        if total:
            _LANE_FILL_G.set(round(100.0 * st["lanes_real"] / total, 2))
        # carve the dispatched records back into per-block segments
        pos = 0
        consumed = []
        for i, (fut, count) in enumerate(self._pending_futs):
            take = min(count, n - pos)
            if take <= 0:
                break
            fut._segments.append((pd, pos, pos + take))
            fut._queued -= take
            pos += take
            if take == count:
                consumed.append(i)
                st["blocks"] += 1
            else:
                self._pending_futs[i] = (fut, count - take)
        for i in reversed(consumed):
            self._pending_futs.pop(i)

    def _settle(self, pd: _PackedDispatch) -> np.ndarray:
        """Settle a shared dispatch (first consumer pays the blocking wait
        and the overlap metering; BatchHandle memoizes for the rest)."""
        if pd.settled:
            return pd.handle.result()
        t0 = time.monotonic()
        out = pd.handle.result()
        now = time.monotonic()
        pd.settled = True
        self.stats["blocked_s"] += now - t0
        self.stats["inflight_s"] += now - pd.t_enqueue
        return out

    def snapshot(self) -> dict:
        st = dict(self.stats)
        total = st["lanes_real"] + st["lanes_padded"]
        st["lane_fill_pct"] = round(100.0 * st["lanes_real"] / total, 2) \
            if total else 100.0
        # fraction of dispatched-batch lifetime the host spent NOT blocked
        # on settle — >0 means the pipeline actually hid device latency
        # (on a synchronous CPU backend the verify cost lands at enqueue,
        # inside the scan leg, and this reads as fully hidden)
        st["overlap_fraction"] = round(
            1.0 - st["blocked_s"] / st["inflight_s"], 4) \
            if st["inflight_s"] > 0 else 0.0
        st["pending_lanes"] = len(self._pending)
        st["branch_lanes"] = dict(self.branch_lanes)
        st["branch_discards"] = dict(self.branch_discards)
        return st


# ---------------------------------------------------------------------------
# Blob-level dispatch — the native connect engine's sigscan
# (native/connect.cpp) emits (pub64, r||s, msg, rn, wrap) byte blobs;
# dispatch_packed feeds them straight into the device program (or the
# native threaded CPU verify) with zero per-record Python-int work. The
# record-level dispatch_batch above (script interpreter output) packs its
# records into the same blobs and takes the same device leg.
# ---------------------------------------------------------------------------

class _LazyRecords:
    """SigCheckRecord view over packed blobs, materialized per index — only
    degenerate-lane rechecks (rare) ever touch it."""

    __slots__ = ("pub", "rs", "msg", "algo")

    def __init__(self, pub: np.ndarray, rs: np.ndarray, msg: np.ndarray,
                 algo: str = "ecdsa"):
        self.pub = pub
        self.rs = rs
        self.msg = msg
        self.algo = algo

    def __getitem__(self, i: int):
        from ..script.interpreter import SigCheckRecord

        pub = self.pub[i].tobytes()
        rs = self.rs[i].tobytes()
        return SigCheckRecord(
            (int.from_bytes(pub[:32], "big"), int.from_bytes(pub[32:], "big")),
            int.from_bytes(rs[:32], "big"), int.from_bytes(rs[32:], "big"),
            int.from_bytes(self.msg[i].tobytes(), "big"), algo=self.algo,
        )


def _record_rows(records: Sequence) -> tuple:
    """pub (n,64), r||s (n,64), msg (n,32) of SigCheckRecords, r, s and msg
    mod 2^256: the rows both lane kinds' blob layouts start with."""
    n = len(records)
    top = 1 << 256
    pub = np.frombuffer(
        b"".join(r.pubkey[0].to_bytes(32, "big") + r.pubkey[1].to_bytes(32, "big")
                 for r in records), np.uint8).reshape(n, 64)
    rs = np.frombuffer(
        b"".join((r.r % top).to_bytes(32, "big")
                 + (r.s % top).to_bytes(32, "big")
                 for r in records), np.uint8).reshape(n, 64)
    msg = np.frombuffer(
        b"".join((r.msg_hash % top).to_bytes(32, "big")
                 for r in records), np.uint8).reshape(n, 32)
    return pub, rs, msg


def records_to_blobs(records: Sequence):
    """Pack script-interpreter SigCheckRecords into the blob layout (the
    one place that turns a record into bytes): pub (n,64), r||s (n,64),
    msg (n,32), rn (n,32), wrap (n,) — the x-wraparound candidate
    rn = r + n and its gate wrap = (r + n < p). r, s and msg go in mod
    2^256; the precompute range-rejects them (pack_lanes). ECDSA only: a
    Schnorr record packed here would ride an ECDSA lane and read False."""
    n = len(records)
    if any(getattr(r, "algo", "ecdsa") != "ecdsa" for r in records):
        raise ValueError("records_to_blobs packs ECDSA records only")
    top = 1 << 256
    pub, rs, msg = _record_rows(records)
    wraps = [0 <= r.r and r.r + oracle.N < oracle.P for r in records]
    rn = np.frombuffer(
        b"".join((r.r + oracle.N if w else r.r % top).to_bytes(32, "big")
                 for r, w in zip(records, wraps)), np.uint8).reshape(n, 32)
    return pub, rs, msg, rn, np.asarray(wraps, np.uint8)


# below this lane count the device round trip loses to the threaded native
# CPU verify even on real hardware (dispatch+transfer latency)
PACKED_DEVICE_FLOOR = 512


def dispatch_packed(pub: np.ndarray, rs: np.ndarray, msg: np.ndarray,
                    rn: np.ndarray, wrap: np.ndarray,
                    backend: str = "auto",
                    candidate: Optional[np.ndarray] = None,
                    schnorr: bool = False) -> BatchHandle:
    """Enqueue a packed verify batch: pub (n,64), rs (n,64), msg (n,32),
    rn (n,32), wrap (n,) — all uint8, big-endian fields, caller-validated
    ranges (1 <= r,s < N; pubkey on-curve affine). Device leg is breaker-
    supervised like dispatch_batch (same KAT lanes, same CPU re-verify on
    failure). ``candidate`` (n,) bool marks multisig candidate lanes, whose
    False the caller settles by group (BatchHandle.result).

    ``schnorr``: every lane is a BCH Schnorr signature (a bucket is of one
    kind), in schnorr_records_to_blobs' layout: rn holds (n - e) mod n,
    wrap is unused. Two Schnorr known-answer lanes ride the bucket; a
    failed dispatch is verified by the native threaded Schnorr verify."""
    _check_backend(backend)
    n = len(msg)
    if n == 0:
        return BatchHandle(0, cpu_ok=np.zeros(0, bool))
    use_device = backend == "device" or (
        backend == "auto" and n >= PACKED_DEVICE_FLOOR and _device_available()
    )
    if not use_device:
        return _packed_cpu_handle(pub, rs, msg, n, schnorr)
    br = dispatch.breaker("ecdsa")
    if not br.allow():
        br.note_fallback(n)
        STATS.fault_fallback_sigs += n
        return _packed_cpu_handle(pub, rs, msg, n, schnorr)
    handle = _dispatch_packed_device(pub, rs, msg, rn, wrap, n, br,
                                     active_kernel(), candidate=candidate,
                                     schnorr=schnorr)
    if handle is None:
        STATS.fault_fallback_sigs += n
        return _packed_cpu_handle(pub, rs, msg, n, schnorr)
    return handle


def _verify_cpu_blobs(pub, rs, msg, n: int) -> np.ndarray:
    """CPU verdicts for n blob rows: the native threaded verify when the
    library loaded (orders of magnitude ahead of walking _LazyRecords
    through the Python-int oracle at reindex batch sizes), else that
    oracle."""
    from .. import native

    if native.available():
        return np.asarray(native.ecdsa_verify_batch_blobs(
            pub.tobytes(), rs.tobytes(), msg.tobytes(), n), bool)
    recs = _LazyRecords(pub, rs, msg)
    return _verify_cpu([recs[i] for i in range(n)])


def _packed_cpu_handle(pub, rs, msg, n: int,
                       schnorr: bool = False) -> BatchHandle:
    STATS.cpu_fallback_sigs += n
    verify = _verify_cpu_schnorr_blobs if schnorr else _verify_cpu_blobs
    return BatchHandle(n, cpu_ok=verify(pub, rs, msg, n))


def _dispatch_packed_device(pub, rs, msg, rn, wrap, n: int, br, kern: str,
                            candidate=None, records=None,
                            schnorr: bool = False) -> Optional[BatchHandle]:
    """The one supervised device enqueue (retries + KAT lanes), fed by
    blobs; None when every attempt failed or no rung is left — the caller
    owns the CPU fallback. Two known-answer lanes (good + bad signature)
    ride after the real lanes so BatchHandle.result can detect a lying
    validity mask (they ride — and therefore exercise — whichever kernel
    actually ran).

    Rungs: ``kern`` ("glv": _glv_dev_planes, unless latched broken; "w4":
    _w4_bytes_program), then _w4_bytes_program within the same attempt if
    GLV failed (metered in STATS.glv_fallbacks). A w4 failure fails the
    attempt: retries, then the breaker, then the caller's CPU verify.
    ``records`` are the caller's SigCheckRecords where it has them
    (dispatch_batch), for the degenerate-lane and reject-side rechecks;
    the packed entry has none and gets a lazy view over the blobs.

    A Schnorr bucket (``schnorr``) has one device rung, the GLV ladder's
    _glv_schnorr_program behind the shared prepare stage, whatever
    -ecdsakernel says, and no w4 form: a failed attempt goes to the
    retries, then the breaker, then the caller's native Schnorr verify. Its
    known-answer lanes are Schnorr's; its degenerate lanes and device
    Falses are re-checked by the native Schnorr verify."""
    from . import secp256k1 as dev

    if schnorr:
        return _dispatch_schnorr_bucket(dev, pub, rs, msg, rn, n, br)
    if kern == "msm":
        # the MSM batch equation verifies Schnorr sigs only; ECDSA lanes
        # under -ecdsakernel=msm keep the strongest per-lane ladder
        kern = "glv"
    if not ((kern == "glv" and glv_enabled()) or pallas_enabled()):
        # the compiler refused every kernel this process could run
        br.note_fallback(n)
        return None
    # KAT probe lanes appended after the real records (blob layout)
    kpub, krs, kmsg, krn, kwrap = records_to_blobs(list(_kat_records()))
    pub2 = np.concatenate([pub, kpub])
    rs2 = np.concatenate([rs, krs])
    msg2 = np.concatenate([msg, kmsg])
    rn2 = np.concatenate([rn, krn])
    wrap2 = np.concatenate([np.asarray(wrap, np.uint8), kwrap])
    bucket = _bucket_for(n + 2)

    boff = Backoff(base=br.cfg.backoff_base, maximum=1.0)
    last: Optional[BaseException] = None
    # the enqueuing span (block.scan during the pipelined import) is the
    # settle span's parent — settle may run threads/blocks away
    ctx = tm.trace_context()
    for attempt in range(br.cfg.retries + 1):
        try:
            INJECTOR.on_call("ecdsa")
            arrays = pack_lanes(pub2, rs2, msg2, rn2, wrap2, bucket)
            device_ok = degen = None
            if kern == "glv" and glv_enabled():
                try:
                    INJECTOR.on_call(GLV_DEV_SITE)
                    INJECTOR.on_call(GLV_SITE)
                    # no jitfn: the call is two programs enqueued back to
                    # back, and the watch's cost capture lowers one
                    with tm.span("ecdsa.enqueue", lanes=n,
                                 bucket=bucket) as enqueued:
                        device_ok, degen = _watched_kernel(
                            _PW_GLV_DEV, bucket, arrays,
                            lambda: dev.ecdsa_verify_batch_glv_dev(*arrays))
                    STATS.glv_dispatch_s += enqueued.seconds
                    if (INJECTOR.should_poison(GLV_DEV_SITE)
                            or INJECTOR.should_poison(GLV_SITE)):
                        device_ok = ~device_ok
                    STATS.glv_dispatches += 1
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    _note_glv_failure(e)
                    device_ok = degen = None
                    if not pallas_enabled():
                        raise
            if device_ok is None:
                try:
                    interp = _interpret_kernels()
                    device_ok, degen = _watched_kernel(
                        _PW_W4_BYTES, bucket, arrays,
                        lambda: dev.ecdsa_verify_batch_pallas_w4_bytes(
                            *arrays, interpret=interp),
                        jitfn=(dev._w4_bytes_program
                               if bucket <= 16384 else None),
                        kwargs={"interpret": interp})
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    # pallas bookkeeping scoped to the KERNEL call only —
                    # a failure in the precompute/pack legs above must not
                    # latch _PALLAS_BROKEN (may re-raise programming
                    # errors)
                    _note_pallas_failure(e)
                    raise
            _note_device_dispatch(n, bucket)
            return BatchHandle(
                n, bucket, device_ok, degen=degen,
                records=(records if records is not None
                         else _LazyRecords(pub, rs, msg)),
                breaker=br, kat=True, ctx=ctx, candidate=candidate,
                recover=lambda: _verify_cpu_blobs(pub, rs, msg, n))
        except (KeyboardInterrupt, SystemExit):
            raise
        except SURFACE_ERRORS:
            raise  # programming errors must not degrade silently
        except Exception as e:  # noqa: BLE001 — supervised boundary
            last = e
            if attempt < br.cfg.retries:
                time.sleep(boff.next())
    br.record_failure(last)
    br.note_fallback(n)
    log_printf("ecdsa device dispatch failed (%s: %s) — CPU fallback for "
               "%d sig(s)", type(last).__name__, str(last)[:120], n)
    return None


def _dispatch_schnorr_bucket(dev, pub, rs, msg, u2, n: int,
                             br) -> Optional[BatchHandle]:
    """_dispatch_packed_device for a bucket of Schnorr lanes."""
    if not glv_enabled():
        br.note_fallback(n)
        return None
    kpub, krs, kmsg, ku2, _ = _schnorr_kat_blobs()
    pub2 = np.concatenate([pub, kpub])
    rs2 = np.concatenate([rs, krs])
    msg2 = np.concatenate([msg, kmsg])
    u22 = np.concatenate([u2, ku2])
    bucket = _bucket_for(n + 2)

    boff = Backoff(base=br.cfg.backoff_base, maximum=1.0)
    last: Optional[BaseException] = None
    ctx = tm.trace_context()
    for attempt in range(br.cfg.retries + 1):
        try:
            INJECTOR.on_call("ecdsa")
            arrays = pack_lanes(pub2, rs2, msg2, u22, None, bucket,
                                schnorr=True)
            try:
                INJECTOR.on_call(GLV_DEV_SITE)
                INJECTOR.on_call(GLV_SITE)
                with tm.span("ecdsa.enqueue", lanes=n,
                             bucket=bucket) as enqueued:
                    device_ok, degen = _watched_kernel(
                        _PW_GLV_SCHNORR, bucket, arrays,
                        lambda: dev.schnorr_verify_batch_glv_dev(*arrays))
                STATS.glv_dispatch_s += enqueued.seconds
                if (INJECTOR.should_poison(GLV_DEV_SITE)
                        or INJECTOR.should_poison(GLV_SITE)):
                    device_ok = ~device_ok
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                _note_glv_failure(e, then="the attempt fails")
                raise
            STATS.glv_dispatches += 1
            STATS.schnorr_dispatches += 1
            STATS.schnorr_lanes += n
            _note_device_dispatch(n, bucket)
            return BatchHandle(
                n, bucket, device_ok, degen=degen,
                records=_LazyRecords(pub, rs, msg, "schnorr"),
                breaker=br, kat=True, ctx=ctx,
                recheck=lambda idxs: _verify_cpu_schnorr_blobs(
                    pub[idxs], rs[idxs], msg[idxs], len(idxs)),
                recover=lambda: _verify_cpu_schnorr_blobs(pub, rs, msg, n))
        except (KeyboardInterrupt, SystemExit):
            raise
        except SURFACE_ERRORS:
            raise  # programming errors must not degrade silently
        except Exception as e:  # noqa: BLE001 — supervised boundary
            last = e
            if attempt < br.cfg.retries:
                time.sleep(boff.next())
    br.record_failure(last)
    br.note_fallback(n)
    log_printf("schnorr device dispatch failed (%s: %s) — CPU fallback for "
               "%d sig(s)", type(last).__name__, str(last)[:120], n)
    return None
