"""Driver ``reindex_flush64``: ``bcpd -reindex`` under the shipped flush
cadence (``-flushinterval=64 -dbcache=300``), in-process.

The sibling of drivers/reindex.py, whose warm-up, windows (``run_windows``)
and ``close`` it keeps. What differs is the deployment: the chain comes from
chipbench/gen/agedchain.py (every input spends a coin that a flush has
written and cleared from the node's cache; every flush interval's signatures
are whole 8,190-lane slices), and ``correct`` holds the node to what the
configuration archival-reindex-default guarantees, against
chipbench/reference_flush64.py: the siblings' three (the chain, every
signature on the device, the sample) and durability at the cadence.

Durability is read from outside the program. While a window runs, a thread
of this driver waits on inotify for renames onto ``chainstate.manifest.json``
in the node's data directory (blocked in ``read``: it takes no GIL until the
store publishes a manifest) and keeps every version. ``check`` holds them
to the reference: in order and with none skipped they name the best block
and the MuHash digest of each flush height (a version that repeats the one
before it, as a flush with nothing to write does, is sound), each with an
epoch one above the one before; ``flushes``, ``flush_puts``,
``flush_deletes`` and ``store_read_rows`` of ``last_import_stats`` equal the
reference's counts; and once the node has closed, the rows of the shard
files, opened read-only, count to the reference's unspent set and digest to
the manifest on disk.

A program whose ``gettpuinfo.store`` has no cumulative ``rows_put`` has
none of the cadence's counters (they came together): ``setup`` refuses it
with exit code 2 before anything is generated.

Faults (each has to come out not correct): ``wrong-key-sig`` (the
sibling's; the node must refuse the chain at the faulted block),
``rows-dropped`` (after the import this driver deletes one row of one shard:
the rows' witness has to see it) and ``young-coin`` (the generator spends
one coin made since the last flush: ``young_dense_inputs`` reads 1, so the
age rule is held by the check, not assumed).

Traffic parameters (chipbench/traffic/<mix>.json): those of
drivers/reindex.py and ``flush_interval``; the chain's length in buckets is
1 + ``buckets_per_interval`` x steady intervals (gen/agedchain.plan).
"""

from __future__ import annotations

import ctypes
import importlib.util
import json
import os
import select
import shutil
import sqlite3
import struct
import subprocess
import sys
import tempfile
import threading
import time

import checks
import reference_flush64

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
FAULTS = ("wrong-key-sig", "rows-dropped", "young-coin")
GENERATOR_FAULTS = ("wrong-key-sig", "young-coin")
MANIFEST = "chainstate.manifest.json"
# the traffic file's keys that are the generator's shapes
SHAPES = ("lanes", "flush_interval", "inputs_per_tx", "txs_per_block",
          "fan_k", "block_bytes")


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


sibling = _load("chipbench_drivers_reindex_helpers",
                os.path.join(HERE, "drivers", "reindex.py"))
# the layout's arithmetic alone: the generator itself runs as a child
agedchain = _load("chipbench_gen_agedchain",
                  os.path.join(HERE, "gen", "agedchain.py"))
close = sibling.close


# -- the manifest, watched from the client's side -----------------------------

class ManifestWatch:
    """Every version of ``<directory>/chainstate.manifest.json`` published
    between ``start`` and ``stop``, in order: the file is read at each
    rename onto its name (inotify IN_MOVED_TO on the directory)."""

    IN_MOVED_TO = 0x80
    _EVENT = struct.Struct("iIII")

    def __init__(self, directory: str):
        self.directory = directory
        self.versions: list = []
        self._libc = ctypes.CDLL(None, use_errno=True)
        self._fd = self._libc.inotify_init1(os.O_CLOEXEC)
        if self._fd < 0 or self._libc.inotify_add_watch(
                self._fd, os.fsencode(directory), self.IN_MOVED_TO) < 0:
            raise OSError(ctypes.get_errno(),
                          f"inotify cannot watch {directory}")
        self._stop_r, self._stop_w = os.pipe()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="chipbench-manifest-watch")

    def start(self) -> "ManifestWatch":
        self._thread.start()
        return self

    def stop(self) -> list:
        os.write(self._stop_w, b"x")
        self._thread.join()
        for fd in (self._fd, self._stop_r, self._stop_w):
            os.close(fd)
        return self.versions

    def _run(self) -> None:
        path = os.path.join(self.directory, MANIFEST)
        while True:
            ready, _, _ = select.select([self._fd, self._stop_r], [], [])
            if self._fd in ready:
                data, pos = os.read(self._fd, 65536), 0
                while pos < len(data):
                    _, _, _, length = self._EVENT.unpack_from(data, pos)
                    name = data[pos + 16:pos + 16 + length].rstrip(b"\0")
                    pos += 16 + length
                    if name == MANIFEST.encode():
                        with open(path, "rb") as f:
                            self.versions.append(json.loads(f.read()))
            elif self._stop_r in ready:
                return


# -- set-up --------------------------------------------------------------------

def _generator(ctx, kind: str, intervals: int, fault: str = ""):
    """Start (or skip, when cached) the generator for one chain; returns
    (cache dir, Popen | None)."""
    traffic = ctx.traffic
    lay = agedchain.plan(traffic, intervals)
    cache = ctx.chain_cache(kind, lay["sigs"])
    if os.path.isfile(os.path.join(cache, "summary.json")):
        return cache, None
    shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache)
    sibling._prune(ctx.cache_root)
    shapes = {k: traffic[k] for k in SHAPES if k in traffic}
    cmd = [sys.executable, os.path.join(HERE, "gen", "agedchain.py"),
           "--datadir", cache, "--seed", str(ctx.seed),
           "--intervals", str(intervals), "--traffic", json.dumps(shapes)]
    if fault:
        cmd += ["--fault", fault]
    proc = subprocess.Popen(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return cache, proc


def _has_cadence_counters() -> bool:
    """Whether this program's gettpuinfo.store (the sharded store's
    ``stats``) has the cumulative commit tallies, asked of an empty store
    in a directory of its own."""
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB

    scratch = tempfile.mkdtemp(prefix="chipbench-store-")
    try:
        store = ShardedCoinsDB(scratch)
        try:
            return "rows_put" in store.stats()
        finally:
            store.close()
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def setup(ctx) -> None:
    if not _has_cadence_counters():
        print("chipbench: this program's gettpuinfo.store has no cumulative "
              "commits / rows_put / rows_deleted, and its last_import_stats "
              "no flush_puts, flush_deletes, store_read_rows: the "
              "configuration archival-reindex-default holds the shipped "
              "flush cadence to them", file=sys.stderr, flush=True)
        raise SystemExit(2)
    if ctx.fault and ctx.fault not in FAULTS:
        raise ValueError(f"driver reindex_flush64 knows the faults "
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
    intervals = agedchain.intervals_for(traffic, buckets)
    st["buckets"] = agedchain.plan(traffic, intervals)["buckets"]
    t0 = time.monotonic()
    # the warm-up's chain: the runway and its one bucket, no steady interval
    st["warm_cache"], proc = _generator(ctx, "warm", 0)
    st["warm_gen"] = sibling._collect(st["warm_cache"], proc)
    st["warm_gen_s"] = time.monotonic() - t0
    # the measured chain signs on the other cores while warm() traces
    st["main_cache"], st["main_proc"] = _generator(
        ctx, "main", intervals,
        ctx.fault if ctx.fault in GENERATOR_FAULTS else "")


def _span_totals() -> dict:
    """The program's span totals (what /metrics serves as bcp_span_*): the
    shard threads' spans are in no import's ``phases``."""
    from bitcoincashplus_tpu.util import telemetry

    return telemetry.span_totals()


def warm(ctx) -> None:
    sibling.warm(ctx)
    stats = ctx.state["before"]["import"] or {}
    if "flush_puts" not in stats:
        print("chipbench: this program's last_import_stats has no "
              "flush_puts", file=sys.stderr, flush=True)
        raise SystemExit(2)
    ctx.state["before"]["spans"] = _span_totals()
    ctx.state["setup_report"]["intervals"] = ctx.state["gen"]["intervals"]


# -- the window ---------------------------------------------------------------

def _one_window(ctx, datadir: str, before: dict) -> dict:
    """The sibling's window with the manifest watched beside it; the watch
    starts and stops outside the clock."""
    watch = ManifestWatch(os.path.join(datadir, "regtest")).start()
    try:
        result = sibling._one_window(ctx, datadir, before)
    finally:
        versions = watch.stop()
    result["after"]["spans"] = _span_totals()
    result["datadir"] = datadir
    result["manifest_versions"] = versions
    stats = result["after"]["import"] or {}
    result["report"]["import"].update({k: stats.get(k) for k in (
        "flushes", "flush_rows", "flush_puts", "flush_deletes",
        "store_read_keys", "store_read_rows", "tail_dispatches",
        "flush_log")})
    result["report"]["manifest_versions"] = len(versions)
    return result


def window(ctx) -> dict:
    return sibling.run_windows(ctx, _one_window)


# -- the check ----------------------------------------------------------------

def _distinct(flushes: list) -> list:
    """The reference's flush states without those that repeat the one
    before (a flush with nothing to write)."""
    out: list = []
    for f in flushes:
        state = (f["best_block"], f["digest"])
        if not out or out[-1] != state:
            out.append(state)
    return out


def _walk_versions(versions: list, flushes: list) -> dict:
    """The manifest's versions against the reference's flush states, in
    order: how many versions are no state the reference allows next, how
    many states no version named, how many epochs are not one above the
    one before."""
    states = _distinct(flushes)
    at, unsound, steps = -1, 0, 0
    for i, v in enumerate(versions):
        state = (v.get("best_block"), v.get("muhash"))
        if at >= 0 and state == states[at]:
            pass
        elif at + 1 < len(states) and state == states[at + 1]:
            at += 1
        else:
            unsound += 1
        if i and v.get("epoch") != versions[i - 1].get("epoch", -1) + 1:
            steps += 1
    return {"unsound": unsound, "missed": len(states) - 1 - at,
            "epoch_steps": steps}


def _drop_one_row(datadir: str) -> None:
    """The fault ``rows-dropped``: one coin row of the first shard that has
    one is deleted behind the closed node's back."""
    net = os.path.join(datadir, "regtest")
    for leaf in sorted(os.listdir(net)):
        if leaf.startswith("chainstate.shard") and leaf.endswith(".sqlite"):
            db = sqlite3.connect(os.path.join(net, leaf))
            try:
                with db:
                    done = db.execute(
                        "DELETE FROM kv WHERE k = (SELECT k FROM kv WHERE "
                        "k >= x'43' AND k < x'44' LIMIT 1)").rowcount
            finally:
                db.close()
            if done:
                return
    raise RuntimeError("no coin row to drop")


def _numbers(ctx, one: dict, ref: dict) -> list:
    """One window's numbers: the sibling's against this cell's replay, and
    the cadence's among them."""
    stats = one["after"]["import"] or {}
    flushes = ref["flushes"]
    made = flushes[1:]  # the import's own: the first is the genesis state
    walk = _walk_versions(one["manifest_versions"], flushes)
    disk = reference_flush64.disk_rows(os.path.join(one["datadir"],
                                                    "regtest"))
    with open(os.path.join(one["datadir"], "regtest", MANIFEST)) as f:
        on_disk = json.load(f)
    log = [entry["height"] for entry in stats.get("flush_log") or ()]

    def gap(key: str, expected: int) -> dict:
        return checks.compared(key + "_gap",
                               abs(stats.get(key, -1) - expected), 0,
                               note=f"of {expected}")

    numbers = sibling._numbers(one, ref) + [
        checks.compared("manifest_versions_unsound", walk["unsound"], 0,
                        note=f"{len(one['manifest_versions'])} seen"),
        checks.compared("manifest_flush_heights_missed", walk["missed"], 0,
                        note=f"of {len(_distinct(flushes))} states"),
        checks.compared("manifest_epoch_steps_not_one",
                        walk["epoch_steps"], 0),
        gap("flushes", len(made)),
        checks.compared("flush_log_heights_differ",
                        int(log != [f["height"] for f in made]), 0),
        gap("flush_puts", sum(f["puts"] for f in made)),
        gap("flush_deletes", sum(f["deletes"] for f in made)),
        gap("store_read_rows", ref["store_reads"]),
        checks.compared("young_dense_inputs", ref["young_dense_inputs"], 0),
        checks.compared("disk_rows_gap",
                        abs(disk["rows"] - flushes[-1]["utxos"]), 0,
                        note=f"of {flushes[-1]['utxos']}"),
        checks.compared("disk_digest_differs_from_manifest",
                        int(disk["digest"] != on_disk.get("muhash")), 0),
        checks.compared("manifest_digest_differs_from_reference",
                        int(on_disk.get("muhash") != flushes[-1]["digest"]),
                        0),
        checks.compared("slow_path_blocks",
                        stats.get("slow_path_blocks", -1), 0,
                        ok=stats.get("slow_path_blocks") == 0),
    ]
    if not ctx.rehearse:  # a rehearsal's 300-lane buckets are all tails
        numbers.append(checks.compared(
            "tail_dispatches", stats.get("tail_dispatches", -1), 0,
            ok=stats.get("tail_dispatches") == 0))
    return numbers


def check(ctx, result: dict) -> list:
    """Every window's chain, counters, manifest versions and rows on disk
    against one independent replay of the same block files
    (chipbench/reference_flush64.py), made after the last window has closed
    and the node's stores are shut."""
    st = ctx.state
    node = st.pop("node", None)
    if node is not None:
        node.close()
    if ctx.fault == "rows-dropped":
        _drop_one_row(result["windows"][-1]["datadir"])
    t0 = time.monotonic()
    ref = reference_flush64.scan_chain(
        os.path.join(st["main_cache"], "regtest", "blocks"), ctx.seed,
        ctx.traffic["sample_sigs"], ctx.traffic["flush_interval"])
    ctx.emit({"phase": "reference", "seconds": time.monotonic() - t0,
              **ref, "generator_tip": st["gen"]["tip_hash"],
              "generator_height": st["gen"]["tip_height"],
              "node": [w["after"]["chain"] for w in result["windows"]],
              "manifest_versions": [w["manifest_versions"]
                                    for w in result["windows"]]})
    if ctx.fault not in GENERATOR_FAULTS:
        # the generator's word is no reference, but a disagreement between
        # it and the replay is a fault of the harness, not of the node
        if (ref["height"], ref["tip_hash"], ref["utxos"],
                ref["signed_inputs"], ref["young_inputs"]) != (
                st["gen"]["tip_height"], st["gen"]["tip_hash"],
                st["gen"]["txouts"], st["gen"]["sigs"], 0):
            raise RuntimeError(f"reference {ref} and generator "
                               f"{st['gen']} disagree on a sound chain")
    return checks.worst_of([_numbers(ctx, w, ref)
                            for w in result["windows"]])
