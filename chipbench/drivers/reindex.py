"""Driver ``reindex``: ``bcpd -reindex`` over a generated chain, in-process.

The import runs inside ``Node.__init__``, so the measured window is the
host clock around ``Node(config)`` for the measured data directory; it
returns with every signature batch settled. Warm-up is a first
``Node(config)`` in the same process over a chain of exactly one bucket,
closed before the window, so the window finds its one program compiled.

The chains come from chipbench/gen/sigchain.py, run as children pinned to
the CPU. The measured chain's child signs while this process traces and
lowers the verify program for the warm-up: the two do not share a core.

Traffic parameters (chipbench/traffic/<mix>.json): lanes,
buckets_per_window_second, warm_buckets, trace_buckets, inputs_per_tx,
txs_per_block, fan_k, sample_sigs, rehearse.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import subprocess
import sys
import time

import checks
import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FAULTS = ("wrong-key-sig",)
KEEP_SEEDS = 8  # cached seeds kept on disk (a 30 s chain is ~80 MB)


def _prune(cache_root: str, keep: int = KEEP_SEEDS) -> None:
    """Drop the oldest cached seeds beyond ``keep``."""
    if not os.path.isdir(cache_root):
        return
    dirs = [os.path.join(cache_root, d) for d in os.listdir(cache_root)]
    dirs = sorted((d for d in dirs if os.path.isdir(d)),
                  key=os.path.getmtime)
    for old in dirs[:max(0, len(dirs) - keep)]:
        shutil.rmtree(old, ignore_errors=True)


def _generator(ctx, kind: str, sigs: int, fault: str = ""):
    """Start (or skip, when cached) the generator for one chain; returns
    (cache dir, Popen | None)."""
    traffic = ctx.traffic
    cache = ctx.chain_cache(kind, sigs)
    if os.path.isfile(os.path.join(cache, "summary.json")):
        return cache, None
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    _prune(ctx.cache_root)
    cmd = [sys.executable, os.path.join(HERE, "gen", "sigchain.py"),
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


def _collect(cache: str, proc) -> dict:
    """Wait for a generator, keep its summary beside the chain; returns the
    summary (with ``cached`` set where nothing was signed)."""
    path = os.path.join(cache, "summary.json")
    if proc is None:
        with open(path) as f:
            return dict(json.load(f), cached=True)
    out, err = proc.communicate()
    if proc.returncode != 0:
        shutil.rmtree(cache, ignore_errors=True)
        raise RuntimeError(f"chain generator failed (rc={proc.returncode})"
                           f":\n{err[-3000:]}")
    summary = json.loads(out.strip().splitlines()[-1])
    with open(path + ".tmp", "w") as f:
        json.dump(summary, f)
    os.replace(path + ".tmp", path)
    return dict(summary, cached=False)


def _datadir(ctx, cache: str, name: str) -> str:
    """A work copy of a cached chain's block files: the cache stays as the
    generator left it."""
    src = os.path.join(cache, "regtest", "blocks")
    dst = os.path.join(ctx.workdir, name, "regtest", "blocks")
    os.makedirs(dst)
    for leaf in os.listdir(src):
        if leaf.startswith("blk") and leaf.endswith(".dat"):
            shutil.copy(os.path.join(src, leaf), dst)
    return os.path.join(ctx.workdir, name)


def _settle_disk() -> float:
    """The set-up's own writes go to disk before the window. It has just
    copied the chain's block files into the work directory, and the
    window's flush ends in a dozen fsyncs, which must not wait for them: a
    node that reindexes finds its block files on disk. Returns the seconds
    it took."""
    t0 = time.monotonic()
    os.sync()
    return time.monotonic() - t0


def _node(ctx, datadir: str):
    """The lines of cli/bcpd.main up to the end of the import."""
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    flags = ctx.config["rehearse_flags" if ctx.rehearse else "flags"]
    config = Config()
    config.parse_args(list(flags) + [f"-datadir={datadir}"])
    return Node(config)


def snapshot(node) -> dict:
    """gettpuinfo read in-process, with the import's own stopwatch and the
    chainstate's answers beside it."""
    from bitcoincashplus_tpu.consensus.serialize import hash_to_hex
    from bitcoincashplus_tpu.rpc.control import gettpuinfo

    tip = node.chainstate.tip()
    return dict(gettpuinfo(node, []),
                **{"import": node.last_import_stats,
                   "chain": {"height": tip.height,
                             "tip_hash": hash_to_hex(tip.hash),
                             "utxos": node.coins_db.count_coins()}})


def setup(ctx) -> None:
    if ctx.fault and ctx.fault not in FAULTS:
        raise ValueError(f"driver reindex knows the faults {FAULTS}, "
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
    warm_cache, warm_proc = _generator(
        ctx, "warm", traffic["lanes"] * traffic["warm_buckets"])
    st["warm_gen"] = _collect(warm_cache, warm_proc)
    st["warm_cache"] = warm_cache
    st["warm_gen_s"] = time.monotonic() - t0
    # the measured chain signs on the other cores while warm() traces
    st["main_cache"], st["main_proc"] = _generator(
        ctx, "main", traffic["lanes"] * buckets, ctx.fault)


def warm(ctx) -> None:
    st = ctx.state
    t0 = time.monotonic()
    node = _node(ctx, _datadir(ctx, st["warm_cache"], "warm"))
    try:
        warm_s = time.monotonic() - t0
        snap = snapshot(node)
    finally:
        node.close()
    gen = st["warm_gen"]
    if (snap["chain"]["height"], snap["chain"]["tip_hash"]) != (
            gen["tip_height"], gen["tip_hash"]):
        raise RuntimeError(f"the warm-up import stopped at "
                           f"{snap['chain']}, the generator made {gen}")
    st["setup"] = st["before"] = snap
    t0 = time.monotonic()
    st["gen"] = _collect(st["main_cache"], st.pop("main_proc"))
    st["datadir"] = _datadir(ctx, st["main_cache"], "main")
    st["setup_report"] = {
        "buckets": st["buckets"], "sigs": st["gen"]["sigs"],
        "warm_generate_s": st["warm_gen_s"], "warm_import_s": warm_s,
        "main_generate_s": st["gen"].get("generate_s"),
        "main_cached": st["gen"]["cached"],
        "waited_for_generator_s": time.monotonic() - t0,
        "blocks": st["gen"]["blocks"], "chain_bytes": st["gen"]["bytes"],
        "sync_s": _settle_disk()}


class _GcClock:
    """Seconds Python's cyclic collector ran inside a ``with`` block, and
    how many of its passes were full ones."""

    def __init__(self):
        self.seconds, self.full, self._t0 = 0.0, 0, 0.0

    def _note(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.full += info["generation"] == 2

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)


def _measured_import(ctx) -> tuple:
    """The window itself: ``Node(config)`` over the measured data directory,
    left in ``ctx.state["node"]``. Returns (host-clock seconds, where the
    runs of a cell spread): what the one importing thread got of the window
    (against the import's ``wall_s``: a stolen core shows as wall at the same
    CPU seconds, a slower host as both going up) and what Python's collector
    took. A full pass over the heap that tracing the verify program leaves
    is ~0.55 s; a program that moves that heap out of the collector's reach
    once the shape is traced (gc_frozen_objects in the millions, not the
    interpreter's own few hundred) pays milliseconds a pass. The harness
    sets nothing aside itself: bcpd pays what this process does."""
    st = ctx.state
    collected = _GcClock()
    cpu0, all0, t0 = time.thread_time(), time.process_time(), time.monotonic()
    with ctx.annotate("import"), collected:
        st["node"] = _node(ctx, st["datadir"])
    wall = time.monotonic() - t0
    return wall, {"main_thread_cpu_s": time.thread_time() - cpu0,
                  "process_cpu_s": time.process_time() - all0,
                  "gc_s": collected.seconds,
                  "gc_full_collections": collected.full,
                  "gc_frozen_objects": gc.get_freeze_count()}


def window(ctx) -> dict:
    st = ctx.state
    sigs = st["gen"]["sigs"]
    wall, host = _measured_import(ctx)
    node = st["node"]
    after = snapshot(node)
    before = st["before"]
    on_device = (after["batch"]["sigs_verified"]
                 - before["batch"]["sigs_verified"])
    if ctx.rehearse:
        on_device = sigs  # a rehearsal has no device to count on
    stats = after["import"] or {}
    return {
        "before": before, "after": after, "window_s": wall, "sigs": sigs,
        "attempted": sigs, "failed": max(0, sigs - on_device),
        "values": {"reindex_sigs_per_s": sigs / wall},
        "report": {"import": {k: stats.get(k) for k in (
            "blocks", "bytes", "wall_s", "native_connect_s", "sigscan_s",
            "verify_s", "flush_s", "slow_path_blocks", "fallback_inputs",
            "fast_inputs")}, "sigs_on_device": on_device, **host},
    }


def check(ctx, result: dict) -> list:
    """The node's chain against an independent replay of the same block
    files (chipbench/reference.py), made after the window has closed and
    the node's stores are shut."""
    st = ctx.state
    chain = result["after"]["chain"]
    node = st.pop("node", None)
    if node is not None:
        node.close()
    t0 = time.monotonic()
    # the generator's files as it left them: the node appended its own
    # genesis record to the work copy when it wiped the index
    ref = reference.scan_chain(
        os.path.join(st["main_cache"], "regtest", "blocks"), ctx.seed,
        ctx.traffic["sample_sigs"])
    ctx.emit({"phase": "reference", "seconds": time.monotonic() - t0,
              **ref, "generator_tip": st["gen"]["tip_hash"],
              "generator_height": st["gen"]["tip_height"],
              "node": chain})
    if not ctx.fault:
        # the generator's word is no reference, but a disagreement between
        # it and the replay is a fault of the harness, not of the node
        if (ref["height"], ref["tip_hash"], ref["utxos"]) != (
                st["gen"]["tip_height"], st["gen"]["tip_hash"],
                st["gen"]["txouts"]):
            raise RuntimeError(f"reference {ref} and generator "
                               f"{st['gen']} disagree on a sound chain")
    return [
        checks.compared("tip_height_gap",
                        abs(chain["height"] - ref["height"]), 0),
        checks.compared("tip_hash_differs",
                        int(chain["tip_hash"] != ref["tip_hash"]), 0),
        checks.compared("utxo_count_gap",
                        abs(chain["utxos"] - ref["utxos"]), 0),
        checks.compared("signatures_not_verified_on_device",
                        result["failed"], 0,
                        note=f"of {result['sigs']}"),
        checks.compared("sampled_signatures_refused_by_reference",
                        int(ref["first_bad_height"] is not None), 0,
                        note=f"{ref['sampled']} sampled"),
    ]


def close(ctx) -> None:
    st = ctx.state
    proc = st.pop("main_proc", None)
    if proc is not None and proc.poll() is None:
        proc.kill()
        proc.communicate()
    node = st.pop("node", None)
    if node is not None:
        node.close()
