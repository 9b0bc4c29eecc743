"""Driver ``reindex_schnorr``: ``bcpd -reindex`` over the control's deck
signed with BCH Schnorr signatures, in-process.

The sibling of drivers/reindex.py, whose warm-up (a first ``Node(config)``
over a one-bucket chain of the same kind, so the window finds the Schnorr
program compiled), windows (``run_windows``) and ``close`` it keeps. What
differs is the scheme: the chain comes from chipbench/gen/schnorrchain.py
(every signature 65 bytes, r || s || 0x41), every bucket is of Schnorr
lanes, and ``correct`` holds the node to what the configuration
archival-reindex-schnorr guarantees: the chain equals an independent replay
(chipbench/reference_schnorr.py), every signature is verified on the device
as a Schnorr lane (``sigs_verified`` and ``schnorr_lanes`` move by the
reference's signature count, ``schnorr_cpu_sigs`` and every fallback counter
by 0), no block leaves the native engine, and the sampled inputs verify
under the reference's own digest and Schnorr equation.

A program whose gettpuinfo.batch has no ``schnorr_lanes`` sends every block
with a 65-byte signature to the Python engine, 123 ms a signature: the cell
does not describe it, and ``setup`` refuses with exit code 2 before anything
is generated.

Faults (each has to come out not correct: the node must refuse the chain
at the faulted block): ``wrong-key-sig`` (the sibling's) and
``wrong-jacobi`` (one input signed with a nonce whose R.y is a non-residue,
not negated).

Traffic parameters (chipbench/traffic/<mix>.json): those of
drivers/reindex.py.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
import time

import checks
import reference_schnorr

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FAULTS = ("wrong-key-sig", "wrong-jacobi")
# the device programs of one Schnorr dispatch, by their XLA module names
DISPATCH_MODULES = ("jit__glv_prepare_program", "jit__glv_schnorr_program")
# counters of gettpuinfo.batch that a sound window leaves where they were
STILL = ("schnorr_cpu_sigs", "reject_confirm_sigs", "degenerate_rechecks",
         "eager_multisig_sigs", "inline_legacy_sigs")


def _sibling():
    spec = importlib.util.spec_from_file_location(
        "chipbench_drivers_reindex_helpers",
        os.path.join(HERE, "drivers", "reindex.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sibling = _sibling()  # drivers/reindex.py
warm = sibling.warm
close = sibling.close


def _generator(ctx, kind: str, sigs: int, fault: str = ""):
    """Start (or skip, when cached) the generator for one chain; returns
    (cache dir, Popen | None)."""
    traffic = ctx.traffic
    cache = ctx.chain_cache(kind, sigs)
    if os.path.isfile(os.path.join(cache, "summary.json")):
        return cache, None
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    sibling._prune(ctx.cache_root)
    cmd = [sys.executable, os.path.join(HERE, "gen", "schnorrchain.py"),
           "--datadir", cache, "--seed", str(ctx.seed), "--sigs", str(sigs),
           "--inputs-per-tx", str(traffic["inputs_per_tx"]),
           "--txs-per-block", str(traffic["txs_per_block"]),
           "--fan-k", str(traffic["fan_k"])]
    if fault:
        cmd += ["--fault", fault]
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return cache, proc


def setup(ctx) -> None:
    from bitcoincashplus_tpu.ops import ecdsa_batch

    if "schnorr_lanes" not in ecdsa_batch.STATS.snapshot():
        print("chipbench: this program's gettpuinfo.batch has no "
              "schnorr_lanes: it sends every block with a 65-byte signature "
              "to the Python engine, and the configuration "
              "archival-reindex-schnorr wants every signature on the device "
              "as a Schnorr lane", file=sys.stderr, flush=True)
        raise SystemExit(2)
    if ctx.fault and ctx.fault not in FAULTS:
        raise ValueError(f"driver reindex_schnorr knows the faults "
                         f"{FAULTS}, not {ctx.fault!r}")
    st = ctx.state
    if ctx.rehearse:
        ctx.traffic = dict(ctx.traffic, **ctx.traffic["rehearse"])
    traffic = ctx.traffic
    if "buckets" in traffic:
        buckets = traffic["buckets"]
    elif ctx.trace:
        buckets = traffic["trace_buckets"]
    else:
        buckets = max(1, round(ctx.seconds
                               * traffic["buckets_per_window_second"]))
    st["buckets"] = buckets
    t0 = time.monotonic()
    st["warm_cache"], proc = _generator(
        ctx, "warm", traffic["lanes"] * traffic["warm_buckets"])
    st["warm_gen"] = sibling._collect(st["warm_cache"], proc)
    st["warm_gen_s"] = time.monotonic() - t0
    # the measured chain signs on the other cores while warm() traces
    st["main_cache"], st["main_proc"] = _generator(
        ctx, "main", traffic["lanes"] * buckets, ctx.fault)


def _one_window(ctx, datadir: str, before: dict) -> dict:
    """The sibling's window, with the scheme's counters beside it."""
    result = sibling._one_window(ctx, datadir, before)
    result["dispatch_modules"] = DISPATCH_MODULES
    stats = result["after"]["import"] or {}
    was, now = before["batch"], result["after"]["batch"]
    report = result["report"]
    report["import"].update({k: stats.get(k) for k in (
        "interp_inputs", "template_inputs", "schnorr_inputs",
        "schnorr_challenge_s", "sigscan_thread_s")})
    for key in ("schnorr_lanes", "schnorr_dispatches") + STILL:
        report[key] = now[key] - was[key]
    if ctx.rehearse:  # a rehearsal has no device to count on
        report["schnorr_lanes"] = result["sigs"]
    return result


def window(ctx) -> dict:
    return sibling.run_windows(ctx, _one_window)


def _numbers(one: dict, ref: dict) -> list:
    """One window's numbers: the sibling's against this cell's replay, and
    the scheme's among them."""
    stats = one["after"]["import"] or {}
    report = one["report"]
    signed = ref["signed_inputs"]
    return sibling._numbers(one, ref) + [
        checks.compared("sigs_verified_gap",
                        abs(report["sigs_on_device"] - signed), 0,
                        note=f"of {signed} in the reference's replay"),
        checks.compared("schnorr_lanes_gap",
                        abs(report["schnorr_lanes"] - signed), 0,
                        note=f"of {signed}"),
        checks.compared("schnorr_inputs_gap",
                        abs((stats.get("schnorr_inputs") or 0)
                            - ref["schnorr_inputs"]), 0,
                        note=f"of {ref['schnorr_inputs']} 65-byte "
                             f"signatures in the files"),
        checks.compared("slow_path_blocks",
                        stats.get("slow_path_blocks", -1), 0,
                        ok=stats.get("slow_path_blocks") == 0),
        checks.compared("interp_inputs", stats.get("interp_inputs", -1), 0,
                        ok=stats.get("interp_inputs") == 0),
        *(checks.compared(key + "_moved", report[key], 0) for key in STILL),
    ]


def check(ctx, result: dict) -> list:
    """Every window's chain and counters against one independent replay of
    the same block files (chipbench/reference_schnorr.py), made after the
    last window has closed and the node's stores are shut."""
    st = ctx.state
    node = st.pop("node", None)
    if node is not None:
        node.close()
    t0 = time.monotonic()
    ref = reference_schnorr.scan_chain(
        os.path.join(st["main_cache"], "regtest", "blocks"), ctx.seed,
        ctx.traffic["sample_sigs"])
    ctx.emit({"phase": "reference", "seconds": time.monotonic() - t0,
              **ref, "generator_tip": st["gen"]["tip_hash"],
              "generator_height": st["gen"]["tip_height"],
              "node": [w["after"]["chain"] for w in result["windows"]]})
    if not ctx.fault:
        # the generator's word is no reference, but a disagreement between
        # it and the replay is a fault of the harness, not of the node
        if (ref["height"], ref["tip_hash"], ref["utxos"],
                ref["signed_inputs"], ref["schnorr_inputs"]) != (
                st["gen"]["tip_height"], st["gen"]["tip_hash"],
                st["gen"]["txouts"], st["gen"]["sigs"], st["gen"]["sigs"]):
            raise RuntimeError(f"reference {ref} and generator "
                               f"{st['gen']} disagree on a sound chain")
    return checks.worst_of([_numbers(w, ref) for w in result["windows"]])
