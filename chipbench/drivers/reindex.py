"""Driver ``reindex``: ``bcpd -reindex`` over a generated chain, in-process.

The import runs inside ``Node.__init__``, so the measured window is the
host clock around ``Node(config)`` for the measured data directory; it
returns with every signature batch settled. Warm-up is a first
``Node(config)`` in the same process over a chain of exactly one bucket,
closed before the window, so the window finds its one program compiled.

The chains come from chipbench/gen/sigchain.py, run as children pinned to
the CPU. The measured chain's child signs while this process traces and
lowers the verify program for the warm-up: the two do not share a core.

A run measures ``windows`` such imports one after the other (``run_windows``),
each over a work copy of the same block files made before the first, the
node before it closed outside the timed region: the rate is every window's
signatures over every window's seconds, and each window is held to every
check against its own snapshots. A traced run is one window.

Traffic parameters (chipbench/traffic/<mix>.json): lanes,
buckets_per_window_second, windows, warm_buckets, trace_buckets,
inputs_per_tx, txs_per_block, fan_k, sample_sigs, rehearse.
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

import checks
import reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FAULTS = ("wrong-key-sig",)
# the device programs of one dispatch, by their XLA module names: a trace
# that kept every device event has one event of each a dispatch
DISPATCH_MODULES = ("jit__glv_prepare_program", "jit__glv_dev_program")
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


def _datadirs(ctx, cache: str) -> list:
    """The work copies a run needs, all made before its first window: one a
    window, and for a traced run its one window's and a spare (run.py
    traces once more where the profiler lost device events)."""
    count = 2 if ctx.trace else ctx.traffic.get("windows", 1)
    return [_datadir(ctx, cache, f"main{i}") for i in range(count)]


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
    st["datadirs"] = _datadirs(ctx, st["main_cache"])
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


def _measured_import(ctx, datadir: str) -> tuple:
    """The window itself: ``Node(config)`` over one work copy of the
    measured chain, left in ``ctx.state["node"]``. Returns (host-clock seconds, where the
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
        st["node"] = _node(ctx, datadir)
    wall = time.monotonic() - t0
    return wall, {"main_thread_cpu_s": time.thread_time() - cpu0,
                  "process_cpu_s": time.process_time() - all0,
                  "gc_s": collected.seconds,
                  "gc_full_collections": collected.full,
                  "gc_frozen_objects": gc.get_freeze_count()}


def _window_line(result: dict) -> dict:
    """What the ``window`` phase line says of one window: where its seconds
    went, by the import's own spans (self seconds: they add up to the
    import's ``wall_s``), so that whoever reads a refused set sees which
    window was off and in which span."""
    stats = result["after"]["import"] or {}
    report = result["report"]
    return {"window_s": result["window_s"],
            "sigs_per_s": result["values"]["reindex_sigs_per_s"],
            "wall_s": stats.get("wall_s"),
            **{k: report[k] for k in ("main_thread_cpu_s", "process_cpu_s",
                                      "gc_s", "gc_full_collections")},
            "dispatches": stats.get("dispatches"),
            "spans": {name: phase["self_s"] for name, phase in
                      sorted((stats.get("phases") or {}).items())}}


def run_windows(ctx, one_window) -> dict:
    """The run's windows one after the other. ``one_window(ctx, datadir,
    before)`` measures one import and returns its result: ``before``,
    ``after``, ``window_s``, ``sigs`` and ``dispatches`` (what
    checks.no_fallback holds it to), ``attempted``, ``failed``, ``values``,
    ``report``. The node of the window before is closed here, outside the
    timed region; the last stays open in ``ctx.state["node"]``. Returns the
    last window's result with the run's totals over it: ``window_s``,
    ``attempted`` and ``failed`` are sums, the rate is all the signatures
    over all the seconds (the windows' median stands beside it in the
    report), and ``windows`` holds each window's result for the checks."""
    st = ctx.state
    count = 1 if ctx.trace else ctx.traffic.get("windows", 1)
    each = []
    for _ in range(count):
        node = st.pop("node", None)
        if node is not None:
            node.close()
        if not st["datadirs"]:
            raise RuntimeError("no work copy of the chain is left to import")
        each.append(one_window(ctx, st["datadirs"].pop(0), st["before"]))
        st["before"] = each[-1]["after"]
    total = {key: sum(w[key] for w in each)
             for key in ("window_s", "attempted", "failed")}
    rates = [w["values"]["reindex_sigs_per_s"] for w in each]
    return {**each[-1], **total, "before": each[0]["before"],
            "values": {"reindex_sigs_per_s":
                       total["attempted"] / total["window_s"]},
            "report": {**each[-1]["report"],
                       "median_sigs_per_s": statistics.median(rates),
                       "windows": [_window_line(w) for w in each]},
            "windows": each}


def _one_window(ctx, datadir: str, before: dict) -> dict:
    sigs = ctx.state["gen"]["sigs"]
    wall, host = _measured_import(ctx, datadir)
    after = snapshot(ctx.state["node"])
    on_device = (after["batch"]["sigs_verified"]
                 - before["batch"]["sigs_verified"])
    if ctx.rehearse:
        on_device = sigs  # a rehearsal has no device to count on
    stats = after["import"] or {}
    return {
        "before": before, "after": after, "window_s": wall, "sigs": sigs,
        "dispatches": -(-sigs // ctx.traffic["lanes"]),
        "dispatch_modules": DISPATCH_MODULES,
        "attempted": sigs, "failed": max(0, sigs - on_device),
        "values": {"reindex_sigs_per_s": sigs / wall},
        "report": {"import": {k: stats.get(k) for k in (
            "blocks", "bytes", "wall_s", "native_connect_s", "sigscan_s",
            "verify_s", "flush_s", "slow_path_blocks", "fallback_inputs",
            "fast_inputs")}, "sigs_on_device": on_device, **host},
    }


def window(ctx) -> dict:
    return run_windows(ctx, _one_window)


def _numbers(one: dict, ref: dict) -> list:
    """One window's numbers against the replay."""
    chain = one["after"]["chain"]
    return [
        checks.compared("tip_height_gap",
                        abs(chain["height"] - ref["height"]), 0),
        checks.compared("tip_hash_differs",
                        int(chain["tip_hash"] != ref["tip_hash"]), 0),
        checks.compared("utxo_count_gap",
                        abs(chain["utxos"] - ref["utxos"]), 0),
        checks.compared("signatures_not_verified_on_device",
                        one["failed"], 0, note=f"of {one['sigs']}"),
        checks.compared("sampled_signatures_refused_by_reference",
                        int(ref["first_bad_height"] is not None), 0,
                        note=f"{ref['sampled']} sampled"),
    ]


def check(ctx, result: dict) -> list:
    """Every window's chain against one independent replay of the same
    block files (chipbench/reference.py), made after the last window has
    closed and the node's stores are shut."""
    st = ctx.state
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
              "node": [w["after"]["chain"] for w in result["windows"]]})
    if not ctx.fault:
        # the generator's word is no reference, but a disagreement between
        # it and the replay is a fault of the harness, not of the node
        if (ref["height"], ref["tip_hash"], ref["utxos"]) != (
                st["gen"]["tip_height"], st["gen"]["tip_hash"],
                st["gen"]["txouts"]):
            raise RuntimeError(f"reference {ref} and generator "
                               f"{st['gen']} disagree on a sound chain")
    return checks.worst_of([_numbers(w, ref) for w in result["windows"]])


def close(ctx) -> None:
    st = ctx.state
    proc = st.pop("main_proc", None)
    if proc is not None and proc.poll() is None:
        proc.kill()
        proc.communicate()
    node = st.pop("node", None)
    if node is not None:
        node.close()
