"""Driver ``reindex_mixed``: ``bcpd -reindex`` over a generated mixed-script
chain, in-process.

The sibling of drivers/reindex.py, whose windows it keeps (``run_windows``:
the host clock around ``Node(config)``, as many imports a run as the traffic
file says), a first ``Node(config)`` over a one-bucket chain of the same mix
as warm-up, the process otherwise as bcpd would have it. It
calls the sibling's helpers that take all they need as arguments (the chain
cache, the work copy, ``Node(config)``, the snapshot) and has its own
``setup`` and ``warm``. What differs is the chain
(chipbench/gen/mixedchain.py), the reference (chipbench/reference_mixed.py)
and what ``correct`` holds the node to: every signature check of the chain
on the device, the single ones and the candidate lanes of every
OP_CHECKMULTISIG, no key trial on the host, no block out of the native
engine.

``attempted`` and ``reindex_sigs_per_s`` count the chain's signatures (one a
single-signature input, m an m-of-n input); what run.py's ``no_fallback``
gets as ``sigs`` is the chain's device lanes (m(n-m+1) a multisig
operation), which is what ``sigs_verified`` and the dispatches count.

A program whose gettpuinfo.batch has no ``multisig_lanes`` verifies multisig
on the host: the cell does not describe it, and ``setup`` refuses before
anything is generated.

Traffic parameters (chipbench/traffic/<mix>.json): lanes,
buckets_per_window_second, windows, warm_buckets, trace_buckets, input_mix,
inputs_per_tx, block_bytes, keys, fan_k, sample_sigs, rehearse.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

import checks
import reference_mixed

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FAULTS = ("wrong-key-multisig", "wrong-key-sig")
# keys in a script, and its signer sets in the order signers.json codes them
# (chipbench/gen/mixedchain.py)
MULTISIG = {"p2sh_multisig": (3, ((0, 1), (0, 2), (1, 2))),
            "bare_multisig": (2, ((0,), (1,)))}
# counters of gettpuinfo.batch that a sound window leaves where they were
STILL = ("eager_multisig_sigs", "reject_confirm_sigs",
         "multisig_group_confirms")


def _sibling():
    spec = importlib.util.spec_from_file_location(
        "chipbench_drivers_reindex_helpers",
        os.path.join(HERE, "drivers", "reindex.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sibling = _sibling()
close = sibling.close


def _generator(ctx, kind: str, lanes: int, fault: str = ""):
    """Start (or skip, when cached) the generator for one chain; returns
    (cache dir, Popen | None)."""
    cache = ctx.chain_cache(kind, lanes)
    if os.path.isfile(os.path.join(cache, "summary.json")):
        return cache, None
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    sibling._prune(ctx.cache_root)
    cmd = [sys.executable, os.path.join(HERE, "gen", "mixedchain.py"),
           "--datadir", cache, "--seed", str(ctx.seed), "--lanes",
           str(lanes), "--traffic", os.path.join(
               HERE, "traffic", ctx.cell["traffic"] + ".json")]
    if ctx.rehearse:
        cmd.append("--rehearse")
    if fault:
        cmd += ["--fault", fault]
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return cache, proc


def setup(ctx) -> None:
    from bitcoincashplus_tpu.ops import ecdsa_batch

    if "multisig_lanes" not in ecdsa_batch.STATS.snapshot():
        raise RuntimeError(
            "this program's gettpuinfo.batch has no multisig_lanes: it "
            "verifies OP_CHECKMULTISIG on the host, and the configuration "
            "archival-reindex-mixed wants every signature on the device")
    if ctx.fault and ctx.fault not in FAULTS:
        raise ValueError(f"driver reindex_mixed knows the faults {FAULTS}, "
                         f"not {ctx.fault!r}")
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


def warm(ctx) -> None:
    st = ctx.state
    t0 = time.monotonic()
    node = sibling._node(ctx, sibling._datadir(ctx, st["warm_cache"], "warm"))
    try:
        warm_s = time.monotonic() - t0
        snap = sibling.snapshot(node)
    finally:
        node.close()
    gen = st["warm_gen"]
    if (snap["chain"]["height"], snap["chain"]["tip_hash"]) != (
            gen["tip_height"], gen["tip_hash"]):
        raise RuntimeError(f"the warm-up import stopped at "
                           f"{snap['chain']}, the generator made {gen}")
    st["setup"] = st["before"] = snap
    t0 = time.monotonic()
    gen = st["gen"] = sibling._collect(st["main_cache"], st.pop("main_proc"))
    st["datadirs"] = sibling._datadirs(ctx, st["main_cache"])
    st["setup_report"] = {
        "buckets": st["buckets"], "sigs": gen["sigs"],
        "warm_generate_s": st["warm_gen_s"], "warm_import_s": warm_s,
        "main_generate_s": gen.get("generate_s"),
        "main_cached": gen["cached"],
        "waited_for_generator_s": time.monotonic() - t0,
        "blocks": gen["blocks"], "chain_bytes": gen["bytes"],
        **{k: gen[k] for k in (
            "device_lanes", "inputs_by_kind", "multisig_groups",
            "multisig_sigs", "multisig_lanes", "signer_sets",
            "padded_inputs", "max_block_bytes")},
        "sync_s": sibling._settle_disk()}


def _one_window(ctx, datadir: str, before: dict) -> dict:
    gen = ctx.state["gen"]
    lanes, sigs = gen["device_lanes"], gen["sigs"]
    wall, host = sibling._measured_import(ctx, datadir)
    after = sibling.snapshot(ctx.state["node"])

    def moved(key: str) -> int:
        return after["batch"][key] - before["batch"][key]

    on_device = moved("sigs_verified")
    if ctx.rehearse:
        on_device = lanes  # a rehearsal has no device to count on
    still = {key: moved(key) for key in STILL}
    # signatures somebody else than the device decided: a lane that missed
    # it, a key trial walked on the host, a verdict the host had to confirm
    missed = min(sigs, max(0, lanes - on_device) + sum(still.values()))
    stats = after["import"] or {}
    return {
        "before": before, "after": after, "window_s": wall, "sigs": lanes,
        "dispatches": -(-lanes // ctx.traffic["lanes"]),
        "dispatch_modules": sibling.DISPATCH_MODULES,
        "attempted": sigs, "failed": missed,
        "values": {"reindex_sigs_per_s": sigs / wall},
        "report": {"import": {k: stats.get(k) for k in (
            "blocks", "bytes", "wall_s", "native_connect_s", "sigscan_s",
            "verify_s", "fallback_s", "flush_s", "slow_path_blocks",
            "fallback_inputs", "fast_inputs", "multisig_groups",
            "multisig_lanes", "multisig_group_confirms")},
            "lanes": lanes, "lanes_on_device": on_device, **still,
            "multisig_sigs": gen["multisig_sigs"],
            **host},
    }


def window(ctx) -> dict:
    return sibling.run_windows(ctx, _one_window)


def _walks_differ(cache: str, replayed: list) -> int:
    """Sampled multisig inputs on which the reference's walk (its signers,
    its trial count) is not the one the generator's signer set gives."""
    with open(os.path.join(cache, "signers.json")) as f:
        planned = json.load(f)
    differ = 0
    for kind, place, signers, trials in replayed:
        n_keys, sets = MULTISIG[kind]
        want = sets[int(planned[kind][place])]
        # the walk starts at the last key: a signer at position p costs the
        # trials from the last key down to p, shared with later signers
        differ += (tuple(signers) != want
                   or trials != n_keys - min(want))
    return differ


def _numbers(one: dict, ref: dict, gen: dict, walks_differ: int,
             replayed: int) -> list:
    """One window's numbers against the replay and the chain's counts."""
    chain = one["after"]["chain"]
    stats = one["after"]["import"] or {}
    report = one["report"]
    return [
        checks.compared("tip_height_gap",
                        abs(chain["height"] - ref["height"]), 0),
        checks.compared("tip_hash_differs",
                        int(chain["tip_hash"] != ref["tip_hash"]), 0),
        checks.compared("utxo_count_gap",
                        abs(chain["utxos"] - ref["utxos"]), 0),
        checks.compared("lanes_not_verified_on_device",
                        max(0, one["sigs"] - report["lanes_on_device"]),
                        0, note=f"of {one['sigs']}"),
        checks.compared("multisig_lanes_gap",
                        abs((stats.get("multisig_lanes") or 0)
                            - gen["multisig_lanes"]), 0,
                        note=f"of {gen['multisig_lanes']} in "
                             f"{gen['multisig_groups']} groups"),
        *(checks.compared(key + "_moved", report[key], 0) for key in STILL),
        checks.compared("sampled_multisig_walks_differ", walks_differ, 0,
                        note=f"{replayed} replayed"),
        checks.compared("slow_path_blocks",
                        stats.get("slow_path_blocks", -1), 0, ok=(
                            stats.get("slow_path_blocks") == 0)),
        checks.compared("fallback_inputs_gap",
                        abs(stats.get("fallback_inputs", 0)
                            - gen["non_p2pkh_inputs"]), 0,
                        note=f"of {gen['non_p2pkh_inputs']}"),
        checks.compared("sampled_inputs_refused_by_reference",
                        int(ref["first_bad_height"] is not None), 0,
                        note=f"{ref['sampled']} sampled"),
    ]


def check(ctx, result: dict) -> list:
    """Every window's chain against one independent replay of the same
    block files (chipbench/reference_mixed.py), and its counters against
    the chain's own counts; made after the last window has closed and the
    node's stores are shut."""
    st = ctx.state
    gen = st["gen"]
    node = st.pop("node", None)
    if node is not None:
        node.close()
    t0 = time.monotonic()
    ref = reference_mixed.scan_chain(
        os.path.join(st["main_cache"], "regtest", "blocks"), ctx.seed,
        ctx.traffic["sample_sigs"])
    replayed = ref.pop("multisig_sampled")
    ctx.emit({"phase": "reference", "seconds": time.monotonic() - t0,
              **ref, "generator_tip": gen["tip_hash"],
              "generator_height": gen["tip_height"],
              "node": [w["after"]["chain"] for w in result["windows"]]})
    if not ctx.fault:
        # the generator's word is no reference, but a disagreement between
        # it and the replay is a fault of the harness, not of the node
        said = (gen["tip_height"], gen["tip_hash"], gen["txouts"],
                gen["inputs_by_kind"], 0)
        if (ref["height"], ref["tip_hash"], ref["utxos"],
                ref["inputs_by_kind"], ref["inputs_of_unknown_kind"]) != said:
            raise RuntimeError(f"reference {ref} and generator {gen} "
                               f"disagree on a sound chain")
    differ = _walks_differ(st["main_cache"], replayed)
    return checks.worst_of([_numbers(w, ref, gen, differ, len(replayed))
                            for w in result["windows"]])
