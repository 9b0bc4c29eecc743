"""Driver ``reindex_prefork``: ``bcpd -reindex -uahfheight=<above the
chain>`` over the mixed-script chain signed as history below the fork height
has it, in-process.

The sibling of drivers/reindex_mixed.py, whose chain (chipbench/gen/
mixedchain.py, here with ``--legacy-sighash``), warm-up and window it keeps:
the host clock around ``Node(config)``, a first ``Node(config)`` over a
one-bucket chain of the same mix, the process otherwise as bcpd would have
it. What differs is the era. The blocks' flags have no NULLFAIL, no
STRICTENC, no LOW_S, no NULLDUMMY and no FORKID, every signature commits to
the original SignatureHash, and ``correct`` holds the node to what the
configuration archival-reindex-prefork guarantees: the chain equals an
independent replay (chipbench/reference_prefork.py), every block is
connected by the native engine under flags without NULLFAIL, every
signature check rides a device lane, none runs inline on the host, and the
bytes the program says its legacy digests hashed are the bytes the
reference counts in the chain's serialised transactions.

A program whose gettpuinfo.batch has no ``prefork_lanes`` connects such
blocks through the Python engine and verifies every check inline: the cell
does not describe it, and ``setup`` refuses before anything is generated.

Traffic parameters (chipbench/traffic/<mix>.json): those of
drivers/reindex_mixed.py.
"""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
import time

import checks
import reference_prefork

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FAULTS = ("wrong-key-multisig", "wrong-key-sig")


def _sibling():
    spec = importlib.util.spec_from_file_location(
        "chipbench_drivers_reindex_mixed_helpers",
        os.path.join(HERE, "drivers", "reindex_mixed.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


mixed = _sibling()
sibling = mixed.sibling  # drivers/reindex.py
close = sibling.close
warm = mixed.warm
# counters of gettpuinfo.batch that a sound window leaves where they were
STILL = mixed.STILL


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
           str(lanes), "--legacy-sighash", "--traffic", os.path.join(
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

    if "prefork_lanes" not in ecdsa_batch.STATS.snapshot():
        raise RuntimeError(
            "this program's gettpuinfo.batch has no prefork_lanes: it "
            "connects blocks below the fork height through the Python "
            "engine and verifies every check inline, and the configuration "
            "archival-reindex-prefork wants every signature on the device")
    if ctx.fault and ctx.fault not in FAULTS:
        raise ValueError(f"driver reindex_prefork knows the faults "
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
    """The sibling's window, with the era's counters beside it: a check
    that ran inline on the host is a signature the device did not decide."""
    result = mixed._one_window(ctx, datadir, before)
    stats = result["after"]["import"] or {}
    was, now = before["batch"], result["after"]["batch"]
    report = result["report"]
    report["import"].update({k: stats.get(k) for k in (
        "template_inputs", "interp_inputs", "prefork_blocks",
        "sigscan_thread_s", "legacy_digests", "legacy_sighash_bytes",
        "legacy_sighash_s", "inline_legacy_sigs")})
    report["inline_legacy_sigs"] = (now["inline_legacy_sigs"]
                                    - was["inline_legacy_sigs"])
    report["prefork_lanes"] = now["prefork_lanes"] - was["prefork_lanes"]
    result["failed"] = min(result["attempted"], result["failed"]
                           + report["inline_legacy_sigs"])
    return result


def window(ctx) -> dict:
    return sibling.run_windows(ctx, _one_window)


def _numbers(one: dict, ref: dict, gen: dict, walks_differ: int,
             replayed: int) -> list:
    """One window's numbers: the sibling's, and the era's among them."""
    stats = one["after"]["import"] or {}
    report = one["report"]
    hashed = stats.get("legacy_sighash_bytes") or 0
    return mixed._numbers(one, ref, gen, walks_differ, replayed) + [
        checks.compared("prefork_lanes_gap",
                        abs(report["prefork_lanes"] - one["sigs"]), 0,
                        note=f"of {one['sigs']}"),
        checks.compared("prefork_blocks_gap",
                        abs((stats.get("prefork_blocks") or 0)
                            - ref["blocks"]), 0,
                        note=f"of {ref['blocks']}, {ref['spend_blocks']} "
                             f"with spends"),
        checks.compared("inline_legacy_sigs_moved",
                        report["inline_legacy_sigs"], 0),
        checks.compared("interp_inputs", stats.get("interp_inputs", -1), 0,
                        ok=stats.get("interp_inputs") == 0),
        checks.compared("legacy_digests_gap",
                        abs((stats.get("legacy_digests") or 0)
                            - ref["legacy_digests"]), 0,
                        note=f"of {ref['legacy_digests']}"),
        checks.compared("legacy_sighash_bytes_gap",
                        abs(hashed - ref["legacy_sighash_bytes"]), 0,
                        note=f"of {ref['legacy_sighash_bytes']}"),
    ]


def check(ctx, result: dict) -> list:
    """Every window's chain against one independent replay of the same
    block files (chipbench/reference_prefork.py), and its counters against
    the chain's own counts; made after the last window has closed and the
    node's stores are shut."""
    st = ctx.state
    gen = st["gen"]
    node = st.pop("node", None)
    if node is not None:
        node.close()
    t0 = time.monotonic()
    ref = reference_prefork.scan_chain(
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
                gen["inputs_by_kind"], 0, gen["inputs"])
        if (ref["height"], ref["tip_hash"], ref["utxos"],
                ref["inputs_by_kind"], ref["inputs_of_unknown_kind"],
                ref["legacy_digests"]) != said:
            raise RuntimeError(f"reference {ref} and generator {gen} "
                               f"disagree on a sound chain")
    differ = mixed._walks_differ(st["main_cache"], replayed)
    return checks.worst_of([_numbers(w, ref, gen, differ, len(replayed))
                            for w in result["windows"]])
