"""Headline benchmarks — one JSON object per line, headline metric LAST
(the driver parses the final line; the tail carries all five BASELINE.json
configs, VERDICT r2 item 5).

Configs (BASELINE.json):
  1. batched 80-byte header double-SHA (device), correctness-anchored against
     the known mainnet genesis hash + hashlib vectors
  2. getblocktemplate nonce-sweep miner, single chip  <- HEADLINE (last line)
  3. Merkle-root construction over a 4096-tx snapshot
  4. secp256k1 ECDSA batch-verify, 10k-sig ConnectBlock-scale batch
  5. 8-chip nonce shard — reported from the 8-device VIRTUAL CPU mesh
     (no multi-chip hardware on this host; the metric is scaling speedup,
     clearly labeled, not GH/s)

Timing: every timed run takes fresh arguments; medians over repeats; a
warmup dispatch absorbs compile. The sweep timings end in a scalar host
fetch (int(tiles)), which waits for the device.
"""

import json
import os
import random
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

BASELINE_GHS = 500.0  # BASELINE.json north star, per chip (see ROOFLINE.md)

# BENCH_r*.json schema: v1 = the unstamped r01-r07 shape; v2 adds this
# stamp (schema_version + host fingerprint) so the bench trajectory is
# comparable across hosts — a number measured on a 1-core CI sandbox and
# one from a v5e host must never be read as the same series point.
BENCH_SCHEMA_VERSION = 2


def _bench_stamp() -> dict:
    """schema_version + host fingerprint for every BENCH_r*.json write."""
    import platform

    host = {
        "platform": platform.platform(),
        "python": platform.python_version(),
        "machine": platform.machine(),
        "host_cpus": os.cpu_count(),
    }
    try:
        host["jax_version"] = jax.__version__
        host["backend"] = jax.default_backend()
        devs = jax.devices()
        host["device_count"] = len(devs)
        host["device_kind"] = (getattr(devs[0], "device_kind", None)
                               if devs else None)
    except Exception:  # pragma: no cover - backend-less environments
        pass
    return {"schema_version": BENCH_SCHEMA_VERSION, "host": host}


def emit(metric, value, unit, vs_baseline, **extra):
    line = {"metric": metric, "value": value, "unit": unit,
            "vs_baseline": vs_baseline}
    line.update(extra)
    print(json.dumps(line), flush=True)


def bench_header_hash():
    """Config 1: device batch header double-SHA, anchored to known vectors."""
    import hashlib

    from bitcoincashplus_tpu.consensus.params import main_params
    from bitcoincashplus_tpu.ops.sha256 import sha256d_headers

    # correctness anchor: mainnet genesis header hashes to the known hash
    genesis = main_params().genesis
    hdr = genesis.header.serialize()
    digest = sha256d_headers(np.frombuffer(hdr, np.uint8).reshape(1, 80))[0]
    assert bytes(digest) == genesis.get_hash(), "genesis vector mismatch"

    B = 1 << 16
    rng = np.random.default_rng(1)
    warm = rng.integers(0, 256, (B, 80), dtype=np.uint8)
    out = sha256d_headers(warm)
    # spot-check a lane against hashlib
    h0 = hashlib.sha256(hashlib.sha256(warm[0].tobytes()).digest()).digest()
    assert bytes(out[0]) == h0
    ts = []
    for _ in range(3):
        batch = rng.integers(0, 256, (B, 80), dtype=np.uint8)
        t0 = time.perf_counter()
        out = sha256d_headers(batch)
        ts.append(time.perf_counter() - t0)
    dt = sorted(ts)[1]
    mhs = B / dt / 1e6
    # device-resident form: same kernel with the batch already on device —
    # separates chip throughput from host<->device transfer
    import jax.numpy as jnp

    from bitcoincashplus_tpu.ops.sha256 import (
        headers_to_words_np,
        sha256d_headers_jit,
    )

    dev_words = jnp.asarray(headers_to_words_np(batch))
    sha256d_headers_jit(dev_words).block_until_ready()
    dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        sha256d_headers_jit(dev_words).block_until_ready()
        dts.append(time.perf_counter() - t0)
    dev_mhs = B / sorted(dts)[1] / 1e6
    # honest CPU comparison: the native C++ scalar path on the same batch
    # (hashlib-equivalent; what one host core does) — VERDICT r3 #5
    cpu_mhs = None
    from bitcoincashplus_tpu import native as _nat

    if _nat.available():
        flat = batch.tobytes()
        t0 = time.perf_counter()
        _nat.hash_headers(flat)
        cpu_mhs = B / (time.perf_counter() - t0) / 1e6
    emit("header_hash_batch_throughput", round(mhs, 2), "MH/s",
         round(mhs / cpu_mhs, 4) if cpu_mhs else 0.0,
         device_resident_mhs=round(dev_mhs, 2),
         cpu_native_mhs=round(cpu_mhs, 2) if cpu_mhs else None,
         note="64Ki-header batch incl host pack/unpack + host<->device "
              "transfers; device_resident_mhs excludes "
              "host<->device transfer; vs_baseline = end-to-end device / "
              "one-native-CPU-core ratio (the 500 GH/s north star would "
              "round to 0 at this scale; see ROOFLINE.md §4); "
              "genesis+hashlib anchored")
    return {"header_mhs": round(mhs, 2),
            "header_device_resident_mhs": round(dev_mhs, 2)}


def bench_merkle():
    """Config 3: 4096-tx Merkle root on device vs the scalar host oracle."""
    from bitcoincashplus_tpu.consensus.merkle import compute_merkle_root
    from bitcoincashplus_tpu.ops.merkle import compute_merkle_root_tpu

    rng = np.random.default_rng(2)
    txids = [rng.bytes(32) for _ in range(4096)]
    root_ref, _ = compute_merkle_root(txids)
    root_dev, _ = compute_merkle_root_tpu(txids)  # warm + correctness
    assert root_dev == root_ref
    ts = []
    for _ in range(3):
        txids = [rng.bytes(32) for _ in range(4096)]
        t0 = time.perf_counter()
        compute_merkle_root_tpu(txids)
        ts.append(time.perf_counter() - t0)
    dt = sorted(ts)[1]
    # honest CPU comparison: native C++ (or hashlib) on the same snapshot —
    # the point of the config is kernel validation
    from bitcoincashplus_tpu import native as _nat

    t0 = time.perf_counter()
    if _nat.available():
        _nat.merkle_root(txids)
    else:
        compute_merkle_root(txids)
    cpu_ms = (time.perf_counter() - t0) * 1e3
    emit("merkle_root_4096tx", round(dt * 1e3, 2), "ms",
         round(cpu_ms / (dt * 1e3), 4),
         cpu_native_ms=round(cpu_ms, 2),
         note="single-dispatch on-device tree reduction (masked "
              "odd-duplication); vs_baseline = cpu_ms/device_ms, "
              "one dispatch round trip included; see ROOFLINE.md §6")
    return {"merkle_ms": round(dt * 1e3, 1)}


def _make_sig_records(rng, n_distinct: int, n_total: int):
    """n_total SigCheckRecords tiled from n_distinct fresh (key, sig, msg)
    triples — FRESH per timed run, so no run can be served from a
    result of the one before."""
    from bitcoincashplus_tpu import native as _nat
    from bitcoincashplus_tpu.crypto import secp256k1 as oracle
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord

    sign = _nat.ecdsa_sign if _nat.available() else oracle.ecdsa_sign
    base = []
    for _ in range(n_distinct):
        secret = int.from_bytes(rng.bytes(32), "big") % (oracle.N - 1) + 1
        pub = oracle.point_mul(secret, oracle.G)
        e = int.from_bytes(rng.bytes(32), "big") % oracle.N
        r, s = sign(secret, e)
        base.append((pub, r, s, e))
    return [SigCheckRecord(*base[i % n_distinct], b"\x00" * 32, 0)
            for i in range(n_total)]


def bench_ecdsa_batch():
    """Config 4: the 10k-sig ConnectBlock batch through the real dispatch
    path (pack -> bucket-pad -> device kernel -> unpack). Every timed run
    verifies a freshly signed batch (content-randomized per iteration —
    VERDICT r4 item 3). Returns the measured device sigs/s for the reindex
    projection."""
    from bitcoincashplus_tpu.ops import ecdsa_batch

    rng = np.random.default_rng(5)
    warm = _make_sig_records(rng, 64, 10_000)
    ok = ecdsa_batch.verify_batch(warm, backend="device")  # warm/compile
    assert bool(ok.all())
    ts = []
    for _ in range(3):
        records = _make_sig_records(rng, 64, 10_000)  # fresh content
        t0 = time.perf_counter()
        ok = ecdsa_batch.verify_batch(records, backend="device")
        ts.append(time.perf_counter() - t0)
        assert bool(ok.all())
    dt = sorted(ts)[1]
    sps = len(warm) / dt
    from bitcoincashplus_tpu.ops.ecdsa_batch import STATS as _st
    from bitcoincashplus_tpu.ops.ecdsa_batch import pallas_enabled as _pe

    # label from the same predicate dispatch uses (a disabled/fallen-back
    # pallas path must not be reported as pallas)
    kernel = "pallas-w4-3d" if _pe() and not _st.pallas_fallbacks else "xla"
    # honest CPU comparison: the native C++ scalar verify on the same
    # records (one thread per core; 1 core on this host)
    from bitcoincashplus_tpu import native as _nat

    cpu_sps = None
    if _nat.available():
        sample = warm[:1000]
        t0 = time.perf_counter()
        _nat.ecdsa_verify_batch(sample)
        cpu_sps = len(sample) / (time.perf_counter() - t0)
    emit("ecdsa_batch_verify_10k", round(sps), "sigs/s",
         round(sps / cpu_sps, 2) if cpu_sps else 0.0,
         kernel=kernel,
         cpu_native_sigs_per_s=round(cpu_sps) if cpu_sps else None,
         note=f"B=10000, fresh signatures per timed run ({dt:.2f}s, median "
              "of 3); w=4 windowed Pallas ladder; vs_baseline = "
              "device/cpu-core ratio")
    return sps


def bench_virtual_shard():
    """Config 5: nonce-shard scaling CURVE (1/2/4/8) on the VIRTUAL CPU
    mesh, with per-chip tiles-done (shard-imbalance observability) and an
    8-way sig_shard leg (config 4 x config 5 composition). One real chip on
    this host, so these numbers measure the shard_map program's scaling on
    a CPU mesh — NOISY and not ICI: virtual devices share host cores, so
    the curve is a lower bound sanity check, not a hardware claim (the r3
    run printed 1.84x, an earlier r4 run 4.45x for the same code). The
    program itself is identical to what rides ICI on real hardware.
    Subprocess keeps JAX_PLATFORMS clean."""
    code = r"""
import os, time, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, %r)
import jax
from bitcoincashplus_tpu.util import devicewatch
devicewatch.enable_compile_cache()
from bitcoincashplus_tpu.parallel.nonce_shard import sweep_header_sharded
header = bytes(range(80))
def timed(n_chips, tiles_per_chip):
    t0 = time.perf_counter()
    nonce, hashes, per_chip = sweep_header_sharded(
        header, 0, max_nonces=tiles_per_chip * n_chips * 4096,
        tile=4096, n_chips=n_chips, return_per_chip=True)
    return time.perf_counter() - t0, hashes, per_chip
curve = {}
spread = {}
per_chip_8 = None
for n in (1, 2, 4, 8):
    timed(n, 1)  # warm/compile this mesh shape
    rates = []
    for _ in range(5):  # median-of-5 + spread (VERDICT r4 item 7)
        t, h, pc = timed(n, 16)
        rates.append(h / t)
        if n == 8:
            per_chip_8 = pc
    rates.sort()
    curve[n] = rates[2] / 1e6
    spread[n] = [round(rates[0] / 1e6, 2), round(rates[-1] / 1e6, 2)]
# sig_shard leg: the PRODUCTION w4 kernel sharded over the virtual mesh
# (pallas interpret mode on CPU — same program that rides ICI on hardware)
from dataclasses import dataclass
import random
from bitcoincashplus_tpu.crypto import secp256k1 as o
from bitcoincashplus_tpu.parallel.sig_shard import verify_batch_sharded
@dataclass
class Rec:
    pubkey: tuple; r: int; s: int; msg_hash: int
rng = random.Random(7)
base = []
for _ in range(16):
    sk = rng.randrange(1, o.N); e = rng.getrandbits(256)
    r, s = o.ecdsa_sign(sk, e)
    base.append(Rec(o.point_mul(sk, o.G), r, s, e))
recs = base * 512  # 8192 lanes: 1024-lane shards on the 8-way mesh
sig = {}
sig_spread = {}
for n in (1, 8):
    verify_batch_sharded(recs, n)  # warm/compile this mesh shape
    rates = []
    for _ in range(3):
        t0 = time.perf_counter()
        ok = verify_batch_sharded(recs, n)
        rates.append(len(recs) / (time.perf_counter() - t0))
        assert ok.all()
    rates.sort()
    sig[n] = rates[1]
    sig_spread[n] = [round(rates[0]), round(rates[-1])]
print(json.dumps({"curve_mhs": curve, "curve_spread_mhs": spread,
                  "per_chip_tiles_8": per_chip_8,
                  "sig_1": sig[1], "sig_8": sig[8],
                  "sig_spread": sig_spread}))
""" % os.path.dirname(os.path.abspath(__file__))
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    try:
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, env=env, timeout=1800)
        line = out.stdout.strip().splitlines()[-1] if out.stdout.strip() else "{}"
        r = json.loads(line)
        curve = r["curve_mhs"]
        speedup = round(curve["8"] / curve["1"], 2) if "1" in curve else \
            round(curve[8] / curve[1], 2)
        emit("nonce_shard_virtual8_speedup", speedup, "x", 0.0,
             scaling_curve_mhs={k: round(v, 2) for k, v in curve.items()},
             curve_spread_mhs=r["curve_spread_mhs"],
             per_chip_tiles_8=r["per_chip_tiles_8"],
             sig_shard_sigs_per_s={"1": round(r["sig_1"]),
                                   "8": round(r["sig_8"])},
             sig_shard_spread=r["sig_spread"],
             sig_shard_kernel="pallas-w4-3d (interpret on CPU mesh)",
             host_cpus=os.cpu_count(),
             note="VIRTUAL 8-device CPU mesh (no multi-chip hardware): "
                  "median-of-5 + [min,max] spread; lower-bound sanity "
                  "check, NOT an ICI claim. On a 1-core host a "
                  "work-conserving shard can at best TIE 1-way (the 8-way "
                  "deficit is shard_map partition overhead); the claim is "
                  "kernel identity — the sharded program IS config 4's w4 "
                  "pipeline (sig_shard dryrun proves execution)")
        return {"shard8_speedup": speedup}
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("nonce_shard_virtual8_speedup", -1, "x", 0.0,
             note=f"subprocess failed: {e}")
        return None


def bench_sweep_headline():
    """Config 2 (HEADLINE, printed last): single-chip nonce-sweep GH/s on
    the tuned Pallas kernel, XLA while-loop fallback if Pallas fails."""
    from bitcoincashplus_tpu.crypto.hashes import header_midstate
    from bitcoincashplus_tpu.ops.sha256 import bytes_to_words_np, target_to_limbs_np

    header = bytes(range(80))
    mid = jnp.asarray(np.array(header_midstate(header), dtype=np.uint32))
    tail = jnp.asarray(bytes_to_words_np(np.frombuffer(header[64:76], np.uint8)))

    on_cpu = jax.default_backend() == "cpu"
    kernel = "pallas"
    try:
        if on_cpu:
            raise RuntimeError("pallas TPU kernel needs the chip")
        from bitcoincashplus_tpu.ops.pallas_sweep import pallas_sweep_jit

        sublanes, max_tiles = 64, 262144  # tuned: tools/roofline.py sweep
        # (r5 re-swept 32/64/128 sublanes x 128Ki-512Ki tiles on-chip:
        # alternatives measure within run-to-run noise of this setting;
        # the ~12% gap to the op ceiling is not a tiling artifact)
        tile = sublanes * 128

        def run(start, n):
            _f, _n, t = pallas_sweep_jit(mid, tail, jnp.uint32(0), start, n,
                                         sublanes=sublanes, max_tiles=max_tiles)
            return int(t)

        n_units = max_tiles
        run(jnp.uint32(0), jnp.uint32(1))  # warm/compile INSIDE the try:
        # jax.jit compiles lazily, so a Mosaic lowering failure on another
        # TPU generation surfaces here, not at import
    except Exception:
        kernel = "xla-while"
        from bitcoincashplus_tpu.ops.miner import sweep_jit

        tgt = jnp.asarray(target_to_limbs_np(0))
        tile = 1 << 14 if on_cpu else 1 << 20
        n_units = 4 if on_cpu else 128

        def run(start, n):
            _f, _n, t = sweep_jit(mid, tail, tgt, start, n, tile=tile)
            return int(t)

        run(jnp.uint32(0), jnp.uint32(1))  # warm/compile the fallback
    rates = []
    for _ in range(4):
        start = jnp.uint32(random.getrandbits(32))
        t0 = time.perf_counter()
        tiles = run(start, jnp.uint32(n_units))
        dt = time.perf_counter() - t0
        rates.append(tiles * tile / dt)
    rates = sorted(rates[1:])
    ghs = rates[len(rates) // 2] / 1e9
    emit("sha256d_sweep_throughput_per_chip", round(ghs, 4), "GH/s",
         round(ghs / BASELINE_GHS, 6),
         kernel=kernel,
         note="truncated-h7 specialized double-SHA; r4 measured 88% of "
              "the 1.04 GH/s op-bound VPU ceiling — see ROOFLINE.md")


def _run_reindex(workdir, pipeline_depth=None, force_python=False,
                 telemetry=None):
    """One Node(-reindex) import; returns a stats dict (the native import's
    last_import_stats when that path ran, else a wall/verify decomposition
    from the chainstate bench counters that the Python path populates).
    ``pipeline_depth`` sets -pipelinedepth; ``force_python`` routes around
    the native fast-import engine so the Python validation engine (the
    pipelined-IBD code path) does the work; ``telemetry`` pins the
    -telemetry level (process-global — the telemetry_overhead bench
    restores it afterwards)."""
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node

    cfg = Config()
    cfg.args["datadir"] = [workdir]
    cfg.args["regtest"] = ["1"]
    cfg.args["reindex"] = ["1"]
    if pipeline_depth is not None:
        cfg.args["pipelinedepth"] = [str(pipeline_depth)]
    if telemetry is not None:
        cfg.args["telemetry"] = [str(telemetry)]
    env_save = os.environ.get("BCP_NO_NATIVE_IMPORT")
    if force_python:
        os.environ["BCP_NO_NATIVE_IMPORT"] = "1"
    try:
        t0 = time.perf_counter()
        node = Node(config=cfg)
        wall_total = time.perf_counter() - t0
    finally:
        if force_python:
            if env_save is None:
                os.environ.pop("BCP_NO_NATIVE_IMPORT", None)
            else:
                os.environ["BCP_NO_NATIVE_IMPORT"] = env_save
    stats = node.last_import_stats or {}
    # Python-path import (no native engine): verify time lives in the
    # chainstate bench counters, not last_import_stats
    stats.setdefault("verify_s", node.chainstate.bench["verify_ms"] / 1e3)
    stats["pipeline"] = node.chainstate.pipeline_snapshot()
    tip = node.chainstate.tip()
    node.close()
    stats.setdefault("wall_s", wall_total)
    stats["node_wall_s"] = wall_total
    stats["tip_height"] = tip.height
    return stats


def _chainstate_digest(workdir) -> str:
    """Deterministic digest of the persisted UTXO set + best-block marker:
    coin rows are merged across the (possibly sharded) layout and hashed
    in global key order, so equal digests mean identical coin sets.
    Per-shard epoch/accumulator meta is excluded (flush-cadence local)."""
    import glob
    import hashlib

    from bitcoincashplus_tpu.store.kvstore import KVStore

    root = os.path.join(workdir, "regtest")
    paths = sorted(glob.glob(
        os.path.join(root, "chainstate.shard*.sqlite"))) or \
        [os.path.join(root, "chainstate.sqlite")]
    rows: dict[bytes, bytes] = {}
    for p in paths:
        kv = KVStore(p)
        for k, v in kv.iterate():
            if k[:1] == b"C" or k == b"B":
                rows[k] = v
        kv.close()
    h = hashlib.sha256()
    for k in sorted(rows):
        v = rows[k]
        h.update(len(k).to_bytes(4, "little"))
        h.update(k)
        h.update(len(v).to_bytes(4, "little"))
        h.update(v)
    return h.hexdigest()


def _make_chaos_corpus(srcdir, dstdir, window: int = 6, seed: int = 13):
    """Adversarial framing variant of a generated corpus: block records
    shuffled within a sliding window (out-of-order arrival -> the import
    loop's parking/cascade path, which forces settle-horizon barriers
    mid-pipeline) and garbage bytes interleaved between records (the
    scan-forward framing recovery). Consensus content is untouched, so
    every engine must still land on the identical chainstate."""
    import glob
    import random
    import struct

    from bitcoincashplus_tpu.consensus.params import regtest_params

    magic = regtest_params().netmagic
    records = []
    for path in sorted(glob.glob(
            os.path.join(srcdir, "regtest", "blocks", "blk*.dat"))):
        with open(path, "rb") as f:
            data = f.read()
        pos = 0
        while pos + 8 <= len(data):
            if data[pos:pos + 4] != magic:
                pos += 1
                continue
            (size,) = struct.unpack_from("<I", data, pos + 4)
            if pos + 8 + size > len(data):
                break
            records.append(data[pos + 8:pos + 8 + size])
            pos += 8 + size
    rng = random.Random(seed)
    # window shuffle (keep the genesis record first so the store's genesis
    # short-circuit stays cheap; every other ordering is fair game)
    out = records[:1]
    rest = records[1:]
    i = 0
    while i < len(rest):
        chunk = rest[i:i + window]
        rng.shuffle(chunk)
        out.extend(chunk)
        i += window
    blocks_dir = os.path.join(dstdir, "regtest", "blocks")
    os.makedirs(blocks_dir, exist_ok=True)
    with open(os.path.join(blocks_dir, "blk00000.dat"), "wb") as f:
        for raw in out:
            if rng.random() < 0.15:
                f.write(rng.randbytes(rng.randrange(1, 48)))  # garbage
            f.write(magic + struct.pack("<I", len(raw)) + raw)
    return len(out)


def _run_kernel_dimension(workdir, depth, gen):
    """ecdsa_kernel dimension (ISSUE 5): the pipelined import over the
    SAME mixed corpus once per device verify kernel (glv, w4), each in a
    fresh subprocess with BCP_ECDSA_KERNEL pinned and BCP_NO_NATIVE=1 —
    kernel selection is process-global and the native CPU lane would
    otherwise swallow every batch on CPU hosts (the native handle is also
    memoized at first load, so in-process toggling is unreliable). Each
    run warms its kernel at the packer's bucket shapes before the timed
    import, so compile cost stays out of the walls. Returns
    {kernel: {wall_s, digest, decompose_s, pack_s, device_s, ...}} plus
    glv_speedup."""
    code = r"""
import os, sys, json, time
sys.path.insert(0, %(repo)r)
import jax
from bitcoincashplus_tpu.util import devicewatch
devicewatch.enable_compile_cache()
import numpy as np
import bench
from bitcoincashplus_tpu.ops import ecdsa_batch
kernel = os.environ["BCP_ECDSA_KERNEL"]
# warm the kernel at the cross-block packer's dispatch shapes (2048 and
# the 1024 tail bucket) so XLA compile lands outside the timed legs
rng = np.random.default_rng(3)
for n in (2046, 900):
    ecdsa_batch.verify_batch(bench._make_sig_records(rng, 8, n),
                             backend="device", kernel=kernel)
# end-to-end dispatch path (host pack + lattice decompose + device +
# verdict) over one full packer bucket, fresh-content per run — the leg
# this kernel swap targets, free of the Python byte engine's wall
vts = []
for _ in range(3):
    recs = bench._make_sig_records(rng, 64, 2046)
    t0 = time.perf_counter()
    ok = ecdsa_batch.verify_batch(recs, backend="device", kernel=kernel)
    vts.append(time.perf_counter() - t0)
    assert bool(ok.all())
verify_wall = sorted(vts)[1]
s0 = ecdsa_batch.STATS.snapshot()
t0 = time.perf_counter()
st = bench._run_reindex(%(workdir)r, pipeline_depth=%(depth)d,
                        force_python=True)
wall = time.perf_counter() - t0
s1 = ecdsa_batch.STATS.snapshot()
out = {
    "wall_s": round(st["wall_s"], 2),
    "subprocess_wall_s": round(wall, 2),
    "verify_wall_s": round(verify_wall, 3),
    "verify_sigs_per_s": round(2046 / verify_wall),
    "tip_height": st["tip_height"],
    "digest": bench._chainstate_digest(%(workdir)r),
    "decompose_s": round(s1["glv_decompose_s"] - s0["glv_decompose_s"], 3),
    "pack_s": round(s1["glv_pack_s"] - s0["glv_pack_s"], 3),
    "device_s": round(s1["device_seconds"] - s0["device_seconds"], 3),
    "glv_dispatches": s1["glv_dispatches"] - s0["glv_dispatches"],
    "glv_fallbacks": s1["glv_fallbacks"] - s0["glv_fallbacks"],
    "dispatches": s1["dispatches"] - s0["dispatches"],
    "cpu_fallback_sigs": s1["cpu_fallback_sigs"] - s0["cpu_fallback_sigs"],
}
print("BENCHJSON " + json.dumps(out))
""" % {"repo": os.path.dirname(os.path.abspath(__file__)),
       "workdir": workdir, "depth": depth}
    runs = {}
    for kernel in ("w4", "glv"):
        env = dict(os.environ)
        env["BCP_ECDSA_KERNEL"] = kernel
        env["BCP_NO_NATIVE"] = "1"
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=3600)
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("BENCHJSON ")]
        if not line:
            raise RuntimeError(
                f"kernel-dimension subprocess ({kernel}) failed: "
                f"{out.stderr[-400:]}")
        runs[kernel] = json.loads(line[-1][len("BENCHJSON "):])
        runs[kernel]["sigs_per_s"] = round(
            gen["sigs"] / max(runs[kernel]["wall_s"], 1e-9))
    return runs


def bench_import_pipeline():
    """ISSUE 4 tentpole metric: the pipelined Python IBD engine (settle
    horizon + cross-block lane packer) vs the serial engine on the SAME
    mixed-script corpus — per-leg wall times, measured overlap fraction,
    end-to-end sigs/s, and byte-identical-chainstate checks on both the
    mixed and the chaos (shuffled/garbage-framed) corpora. ISSUE 5 adds
    the ecdsa_kernel dimension: the same mixed corpus imported once per
    device verify kernel (w4 vs GLV, device-forced batches), emitting
    glv_speedup, per-stage packer/decompose/device timings, and the
    cross-kernel chainstate digest equality check."""
    import shutil
    import tempfile

    n_sigs = int(os.environ.get("BCP_BENCH_PIPELINE_SIGS", "4000"))
    depth = int(os.environ.get("BCP_BENCH_PIPELINE_DEPTH", "8"))
    workdir = tempfile.mkdtemp(prefix="bcp-pipe-mixed-")
    chaosdir = tempfile.mkdtemp(prefix="bcp-pipe-chaos-")
    try:
        from tools.gen_sigchain import generate

        gen = generate(workdir, n_sigs, mixed=True)
        _make_chaos_corpus(workdir, chaosdir)

        runs = {}
        digests = {}
        for corpus, cdir in (("mixed", workdir), ("chaos", chaosdir)):
            for mode, d in (("pipelined", depth), ("serial", 1)):
                st = _run_reindex(cdir, pipeline_depth=d, force_python=True)
                runs[(corpus, mode)] = st
                digests[(corpus, mode)] = _chainstate_digest(cdir)

        # ecdsa_kernel dimension: both kernels over the mixed corpus
        # (device-forced, subprocess-isolated); digests must match each
        # other AND the in-process runs above
        try:
            kruns = _run_kernel_dimension(workdir, depth, gen)
            # headline ratio: the verify dispatch path end to end (host
            # pack + lattice decompose + device + verdict) — the leg this
            # kernel swap targets; the import-wall ratio is reported
            # alongside but is byte-engine-bound under BCP_NO_NATIVE
            # (Python deserialization dominates it on CPU hosts)
            glv_speedup = round(
                kruns["w4"]["verify_wall_s"]
                / max(kruns["glv"]["verify_wall_s"], 1e-9), 4)
            glv_import_speedup = round(
                kruns["w4"]["wall_s"] / max(kruns["glv"]["wall_s"], 1e-9), 4)
            kernel_digests_identical = (
                kruns["w4"].pop("digest") == kruns["glv"].pop("digest")
            )
        except Exception as e:  # pragma: no cover - diagnostics only
            kruns = {"error": f"{type(e).__name__}: {e}"}
            glv_speedup = None
            glv_import_speedup = None
            kernel_digests_identical = None

        mp = runs[("mixed", "pipelined")]
        ms = runs[("mixed", "serial")]
        pipe = mp["pipeline"]
        sps_pipe = round(gen["sigs"] / mp["wall_s"])
        sps_serial = round(gen["sigs"] / ms["wall_s"])
        identical = {
            "mixed": digests[("mixed", "pipelined")]
            == digests[("mixed", "serial")],
            "chaos": digests[("chaos", "pipelined")]
            == digests[("chaos", "serial")],
            "mixed_vs_chaos": digests[("mixed", "pipelined")]
            == digests[("chaos", "pipelined")],
        }
        emit(
            "import_pipeline", sps_pipe, "sigs/s",
            round(sps_pipe / max(sps_serial, 1), 4),
            sigs_per_s_end_to_end=sps_pipe,
            serial_sigs_per_s_end_to_end=sps_serial,
            overlap_fraction=pipe.get("overlap_fraction", 0.0),
            legs_ms={
                "scan_ms": round(pipe.get("scan_ms", 0.0), 1),
                "device_ms": round(pipe.get("settle_wait_ms", 0.0), 1),
                "commit_ms": round(pipe.get("commit_ms", 0.0), 1),
            },
            pipeline={
                "depth": pipe.get("depth"),
                "max_depth": pipe.get("max_depth"),
                "settled_blocks": pipe.get("settled_blocks"),
                "unwinds": pipe.get("unwinds"),
                "lane_fill_pct": pipe.get("lane_fill_pct"),
                "packer_dispatches":
                    pipe.get("packer", {}).get("dispatches"),
            },
            corpus={"sigs": gen["sigs"], "blocks": gen["blocks"],
                    "bytes": gen["bytes"], "mixed": True},
            ecdsa_kernel=kruns,
            glv_speedup=glv_speedup,
            glv_import_speedup=glv_import_speedup,
            kernel_digests_identical=kernel_digests_identical,
            chaos={
                "pipelined_wall_s":
                    round(runs[("chaos", "pipelined")]["wall_s"], 2),
                "serial_wall_s":
                    round(runs[("chaos", "serial")]["wall_s"], 2),
                "unwinds": runs[("chaos", "pipelined")]["pipeline"]
                    .get("unwinds"),
            },
            chainstate_identical=identical,
            wall_s={"pipelined": round(mp["wall_s"], 2),
                    "serial": round(ms["wall_s"], 2)},
            note="Python validation engine (BCP_NO_NATIVE_IMPORT=1), "
                 "settle horizon depth vs serial on the identical corpora; "
                 "overlap_fraction = share of dispatched-batch lifetime "
                 "the host spent NOT blocked on settle (sync CPU backend "
                 "books verify at enqueue, inside scan_ms); vs_baseline = "
                 "pipelined/serial end-to-end sigs/s; glv_speedup = w4/glv "
                 "verify-dispatch wall (pack+decompose+device+verdict, "
                 "full 2048 bucket, fresh content, median of 3) — "
                 "glv_import_speedup is the whole-import ratio, byte-"
                 "engine-bound under BCP_NO_NATIVE on CPU hosts; kernel "
                 "runs are device-forced with chainstate digests compared "
                 "across kernels",
        )
        return {"pipeline_sigs_per_s": sps_pipe,
                "pipeline_overlap": pipe.get("overlap_fraction", 0.0),
                "pipeline_identical": all(identical.values()),
                "glv_speedup": glv_speedup}
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("import_pipeline", -1, "sigs/s", 0.0,
             error=f"{type(e).__name__}: {e}")
        return None
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(chaosdir, ignore_errors=True)


def _scalar_sweep(header80, target, max_nonces=1 << 32, tile=0):
    """Scalar host PoW loop for corpus generation — regtest targets hit
    in ~2 nonces, so the batched device sweep's per-dispatch latency
    would dominate corpus build time for no measurement value."""
    import struct as _st

    from bitcoincashplus_tpu.consensus.block import NONCE_OFFSET
    from bitcoincashplus_tpu.crypto.hashes import sha256d

    base = header80[:NONCE_OFFSET]
    for nonce in range(max_nonces):
        raw = base + _st.pack("<I", nonce)
        if int.from_bytes(sha256d(raw), "little") <= target:
            return nonce, nonce + 1
    return None, max_nonces


def bench_mining():
    """ISSUE 10: the device-resident mining loop's end-to-end trajectory.
    Three engines sweep the same nonce work on the same host:

      scalar        sweep_header_cpu — the reference generateBlocks loop
      per_dispatch  supervised sweep_header, one dispatch + blocking
                    scalar fetch per poll (the PR<=9 end-to-end shape);
                    measured at two poll granularities
      resident      mining/resident.ResidentSweep.advance — persistent
                    template buffers, pipelined segments, FIFO polls

    The headline ratio compares the resident path against the
    per-dispatch path at the FINEST poll cadence the per-dispatch shape
    can afford (its per-call overhead floors poll latency near ~1 ms on
    any host; the resident loop polls FASTER than that while sweeping
    bigger segments — the decoupling is the design). The equal-dispatch-
    size ratio is recorded alongside, honestly smaller. Digest parity:
    every engine must find the oracle-identical first hit on an easy
    target before its throughput counts. Writes BENCH_r10.json
    (schema_version=2 + host stamp) with the ROOFLINE.md §8 ops/nonce
    census delta inline."""
    import importlib.util

    from bitcoincashplus_tpu.mining.resident import ResidentSweep
    from bitcoincashplus_tpu.ops.dispatch import supervised_sweep
    from bitcoincashplus_tpu.ops.miner import sweep_header_cpu

    header = b"\xa5" * 80
    easy = 0x7FFFFF << (8 * 29)
    polls = int(os.environ.get("BCP_BENCH_MINING_POLLS", "40"))
    tile_small = 1 << 12   # per-dispatch fine poll granularity
    tile_big = 1 << 14     # resident segment / per-dispatch coarse

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    # --- digest parity gate (easy target, all engines vs the oracle) ---
    n_oracle, _ = sweep_header_cpu(header, easy, max_nonces=1 << 13)
    assert n_oracle is not None
    sup = supervised_sweep()
    n_pd, _ = sup(header, easy, max_nonces=1 << 13, tile=tile_small)
    rs_par = ResidentSweep(tile=tile_small, seg_tiles=2, inflight=2,
                           kernel="exact")
    n_res, _ = rs_par.sweep(header, easy, max_nonces=1 << 13)
    rs_par.close()
    parity_ok = (n_pd == n_oracle and n_res == n_oracle)
    assert parity_ok, (n_oracle, n_pd, n_res)

    # --- scalar engine -------------------------------------------------
    n_scalar = 1 << 14
    t0 = time.perf_counter()
    sweep_header_cpu(header, 0, max_nonces=n_scalar)
    scalar_mhs = n_scalar / (time.perf_counter() - t0) / 1e6

    # --- per-dispatch engine (supervised, one dispatch per poll) -------
    def per_dispatch(tile):
        sup(header, 0, max_nonces=tile, tile=tile)  # warm/compile
        walls = []
        for _r in range(3):
            t0 = time.perf_counter()
            for k in range(polls):
                sup(header, 0, start_nonce=(k * tile) & 0xFFFFFFFF,
                    max_nonces=tile, tile=tile)
            walls.append(time.perf_counter() - t0)
        wall = med(walls)
        return {"tile": tile, "polls": polls,
                "mhs": round(polls * tile / wall / 1e6, 3),
                "poll_wall_ms": round(wall / polls * 1e3, 3)}

    pd_fine = per_dispatch(tile_small)
    pd_coarse = per_dispatch(tile_big)

    # --- resident engine (continuous advance over one template) --------
    rs = ResidentSweep(tile=tile_big, seg_tiles=1, inflight=2,
                       kernel="exact")
    rs.set_template(header, 0)
    rs.advance(tile_big)  # warm (shares the per-dispatch compile cache)
    walls = []
    for _r in range(3):
        t0 = time.perf_counter()
        rs.advance(polls * tile_big)
        walls.append(time.perf_counter() - t0)
    wall = med(walls)
    res = {"tile": tile_big, "seg_tiles": 1, "inflight": 2,
           "mhs": round(polls * tile_big / wall / 1e6, 3),
           "poll_wall_ms": round(wall / polls * 1e3, 3),
           "snapshot": rs.snapshot()}
    rs.close()

    # the headline: resident vs the per-dispatch path at the finest
    # cadence it affords — valid only while the resident loop's own poll
    # wall is no WORSE (it settles one pipelined segment per poll)
    cadence_ok = res["poll_wall_ms"] <= pd_fine["poll_wall_ms"] * 1.25
    headline_x = round(res["mhs"] / pd_fine["mhs"], 2)
    same_size_x = round(res["mhs"] / pd_coarse["mhs"], 2)

    # --- ops/nonce census delta (ROOFLINE.md §8) -----------------------
    census = None
    try:
        spec = importlib.util.spec_from_file_location(
            "bcp_roofline", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tools", "roofline.py"))
        roofline = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(roofline)
        h7, full, full_hoisted, _ = roofline.run_census()
        census = {"h7_hoisted": h7, "h7_pre_hoist": roofline.PRE_HOIST_H7,
                  "full_generic": full, "full_hoisted": full_hoisted}
    except Exception as e:  # pragma: no cover - census is best-effort
        census = {"error": f"{type(e).__name__}: {e}"}

    result = {
        "metric": "mining",
        **_bench_stamp(),
        "scalar_mhs": round(scalar_mhs, 3),
        "per_dispatch_fine": pd_fine,
        "per_dispatch_coarse": pd_coarse,
        "resident": res,
        "resident_vs_dispatch_x": headline_x,
        "resident_same_dispatch_size_x": same_size_x,
        "resident_poll_cadence_ok": cadence_ok,
        "digest_parity": {"oracle_nonce": int(n_oracle),
                          "per_dispatch": int(n_pd),
                          "resident": int(n_res), "ok": parity_ok},
        "census_ops_per_nonce": census,
        "note": "CPU backend = memcpy-scale dispatch lower bound; the "
                "gap on the chip is not measured on the current machine. "
                "headline resident_vs_dispatch_x compares against the "
                "finest poll cadence the per-dispatch shape affords "
                "(per-call overhead floors its poll latency); the "
                "resident loop polls at least as often while dispatching "
                "bigger segments — equal-dispatch-size ratio recorded "
                "as resident_same_dispatch_size_x",
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r10.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    emit("mining_resident_speedup", headline_x, "x", 0.0,
         **{k: v for k, v in result.items() if k != "metric"})
    return {"mining_resident_vs_dispatch_x": headline_x,
            "mining_resident_mhs": res["mhs"]}


def _gen_fork_corpus(workdir, segments=6, seg_len=4, fork_depth=3):
    """A reorg-heavy corpus (ISSUE 9): linear segments punctuated by
    deeper competing branches. Each round mines ``seg_len`` blocks, rolls
    the chain back ``fork_depth`` (invalidateblock), mines a longer
    replacement branch, and reconsiders the stale branch — the block
    files then carry BOTH branches in chronological order, so a reimport
    must fight through a fork war every few blocks: stale branches enter
    the speculation tree, lose on work, and drop (or reorg out if they
    settled first). Returns corpus counts."""
    from bitcoincashplus_tpu.mining.assembler import BlockAssembler
    from bitcoincashplus_tpu.mining.generate import mine_block
    from bitcoincashplus_tpu.node.config import Config
    from bitcoincashplus_tpu.node.node import Node
    from bitcoincashplus_tpu.wallet.keys import CKey

    cfg = Config()
    cfg.args["datadir"] = [workdir]
    cfg.args["regtest"] = ["1"]
    node = Node(config=cfg)
    cs = node.chainstate
    spk = CKey(0x0906).p2pkh_script()
    assembler = BlockAssembler(cs, None)
    xn = [0]

    def mine(n):
        # per-block extranonce entropy: a replacement branch's first
        # block must not assemble byte-identical to the stale one it
        # replaces (same parent/height/time/script -> same hash, which
        # would arrive as a duplicate of a FAILED index)
        for _ in range(n):
            xn[0] += 1009
            blk = mine_block(assembler, spk, sweep=_scalar_sweep,
                             extranonce_start=xn[0])
            cs.process_new_block(blk)

    n_blocks = n_forks = 0
    for _ in range(segments):
        mine(seg_len)
        n_blocks += seg_len
        tip = cs.tip()
        stale_root = tip.get_ancestor(tip.height - fork_depth + 1)
        cs.invalidate_block(stale_root)
        mine(fork_depth + 1)
        n_blocks += fork_depth + 1
        cs.reconsider_block(stale_root)  # stale branch: candidate again
        n_forks += 1
    height = cs.tip().height
    node.close()
    return {"blocks": n_blocks, "forks": n_forks, "height": height,
            "fork_depth": fork_depth}


def bench_fork_storm():
    """ISSUE 9 satellite metric: the speculation-tree pipelined engine vs
    the serial engine over the SAME reorg-heavy corpus — wall times, the
    unwind/branch-drop overhead fraction (speculative connects whose work
    was thrown away), reorg accounting, and the byte-identical-chainstate
    check. Writes BENCH_r09.json (schema_version=2 host stamp)."""
    import shutil
    import tempfile

    segments = int(os.environ.get("BCP_BENCH_FORKSTORM_SEGMENTS", "6"))
    depth = int(os.environ.get("BCP_BENCH_PIPELINE_DEPTH", "8"))
    workdir = tempfile.mkdtemp(prefix="bcp-forkstorm-")
    try:
        corpus = _gen_fork_corpus(workdir, segments=segments)
        runs = {}
        digests = {}
        for mode, d in (("pipelined", depth), ("serial", 1)):
            runs[mode] = _run_reindex(workdir, pipeline_depth=d,
                                      force_python=True)
            digests[mode] = _chainstate_digest(workdir)
        pipe = runs["pipelined"]["pipeline"]
        tree = pipe.get("tree", {})
        settled = max(1, pipe.get("settled_blocks", 0))
        wasted = (pipe.get("unwound_blocks", 0)
                  + tree.get("dropped_blocks", 0))
        overhead_fraction = round(wasted / (settled + wasted), 4)
        speedup = round(runs["serial"]["wall_s"]
                        / max(runs["pipelined"]["wall_s"], 1e-9), 4)
        result = {
            "metric": "fork_storm",
            **_bench_stamp(),
            "corpus": corpus,
            "wall_s": {"pipelined": round(runs["pipelined"]["wall_s"], 3),
                       "serial": round(runs["serial"]["wall_s"], 3)},
            "pipelined_vs_serial_speedup": speedup,
            "unwind_overhead_fraction": overhead_fraction,
            "tree": {
                "reorgs": tree.get("reorgs"),
                "reorg_depth_max": tree.get("reorg_depth_max"),
                "branch_drops": tree.get("branch_drops"),
                "dropped_blocks": tree.get("dropped_blocks"),
                "branches_live_max": tree.get("branches_live_max"),
                "serial_linear_fallbacks":
                    tree.get("serial_linear_fallbacks"),
            },
            "unwinds": pipe.get("unwinds"),
            "chainstate_identical": digests["pipelined"]
            == digests["serial"],
            "note": "Python validation engine (BCP_NO_NATIVE_IMPORT=1) "
                    "over a coinbase-only fork-war corpus: every segment "
                    "carries a stale branch the import must out-work; "
                    "unwind_overhead_fraction = speculative blocks whose "
                    "work was dropped / (settled + dropped) — the price "
                    "of concurrent branch validation on this corpus",
        }
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r09.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        emit("fork_storm", runs["pipelined"]["wall_s"], "s", speedup,
             **{k: v for k, v in result.items() if k != "metric"})
        return {"fork_storm_speedup": speedup,
                "fork_storm_identical": result["chainstate_identical"]}
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("fork_storm", -1, "s", 0.0, error=f"{type(e).__name__}: {e}")
        return None
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _utxo_key(i: int) -> bytes:
    return i.to_bytes(32, "little") + b"\x00\x00\x00\x00"


def _utxo_coin(i: int) -> bytes:
    # valid Coin serialization: compact(height*2+cb), compact(value),
    # var_bytes(20-byte script)
    return bytes([2, 5, 20]) + bytes([i & 0xFF]) * 20


def _churn_store(workdir, n_shards, n_coins, chunk, rounds, half,
                 wal=False, bloom=True):
    """Seed n_coins into a fresh store in `chunk`-sized commits, then run
    `rounds` churn commits of `half` adds + `half` deletes each. Returns
    seed/churn wall times and the store's own flush-phase seconds."""
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB

    db = ShardedCoinsDB(workdir, n_shards=n_shards, wal=wal)
    db.bloom_enabled = bloom
    best = b"\x11" * 32
    t0 = time.perf_counter()
    for lo in range(0, n_coins, chunk):
        hi = min(lo + chunk, n_coins)
        db.batch_write_serialized(
            [(_utxo_key(i), _utxo_coin(i)) for i in range(lo, hi)], best)
    seed_s = time.perf_counter() - t0

    churn_wall = []
    churn_flush = []
    for r in range(rounds):
        adds = range(n_coins + r * half, n_coins + (r + 1) * half)
        dels = range(r * half, (r + 1) * half)
        entries = [(_utxo_key(i), _utxo_coin(i)) for i in adds]
        entries += [(_utxo_key(i), None) for i in dels]
        ta = time.perf_counter()
        db.batch_write_serialized(entries, best)
        churn_wall.append(time.perf_counter() - ta)
        churn_flush.append(db.last_flush["seconds"])
    bl = db.bloom_stats
    return db, {
        "seed_s": round(seed_s, 3),
        "seed_coins_per_s": round(n_coins / seed_s),
        "churn_wall_s": round(sum(churn_wall), 3),
        "churn_flush_s": round(sum(churn_flush), 4),
        "churn_entries_per_s": round(rounds * 2 * half / sum(churn_wall)),
        "flush_entries_per_s": round(rounds * 2 * half / sum(churn_flush)),
        "wal": wal,
        "bloom": {"enabled": bloom, **bl,
                  "old_lookup_cut": round(
                      bl["skipped"] / max(bl["checked"], 1), 4)},
    }


def bench_utxo_store():
    """ISSUE 13 satellite metric: sharded chainstate flush throughput (4
    shards vs the single-shard degenerate case) over a million-coin
    churn, snapshot dump/load rates at the same scale, and the snapshot
    path's time-to-first-RPC. Re-measured multi-core (BENCH_r12 follow-
    up): the sweep now also covers -coinswal=1 at 4 shards and a bloom-
    off control quantifying the write-side accumulator-lookup cut.
    Writes BENCH_r12.json."""
    import shutil
    import tempfile

    from bitcoincashplus_tpu.store import snapshot as snapshot_mod
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB

    n_coins = int(os.environ.get("BCP_BENCH_UTXO_COINS", "1000000"))
    chunk = 100_000
    rounds = 4
    half = max(1, min(50_000, n_coins // (2 * rounds)))
    workdir = tempfile.mkdtemp(prefix="bcp-utxostore-")
    try:
        configs = {}
        snap_stats = {}
        # label -> (n_shards, wal, bloom); "4" is the canonical config
        # (snapshot round-trip hangs off it), the extra legs isolate the
        # WAL commit win and the bloom filter's old-value-lookup cut
        sweep = (("1", 1, False, True), ("4", 4, False, True),
                 ("4_wal", 4, True, True), ("4_nobloom", 4, False, False))
        for label, n_shards, wal, bloom in sweep:
            d = os.path.join(workdir, f"s{label}")
            db, stats = _churn_store(d, n_shards, n_coins, chunk,
                                     rounds, half, wal=wal, bloom=bloom)
            configs[label] = stats
            if label != "4":
                db.close()
                continue
            # snapshot round-trip from the 4-shard store at full size
            live = db.count_coins()
            best = db.best_block()
            digest = db.muhash_digest()
            snap_dir = os.path.join(workdir, "snap")
            ta = time.perf_counter()
            snapshot_mod.dump_snapshot(db, snap_dir, [bytes(80)], 0,
                                       best, "regtest")
            dump_s = time.perf_counter() - ta
            db.close()
            dst = ShardedCoinsDB(os.path.join(workdir, "dst"), n_shards=4)
            tb = time.perf_counter()
            snapshot_mod.load_snapshot(snap_dir, dst, "regtest",
                                       expected_hash=best,
                                       expected_digest=digest)
            load_s = time.perf_counter() - tb
            # first RPC off the snapshot: a point read at the new tip
            probe = _utxo_key(n_coins + rounds * half - 1)  # churn survivor
            tc = time.perf_counter()
            got = dst.get_serialized_many([probe])
            first_read_s = time.perf_counter() - tc
            assert probe in got
            dst.close()
            snap_stats = {
                "coins": live,
                "dump_s": round(dump_s, 3),
                "dump_coins_per_s": round(live / dump_s),
                "load_s": round(load_s, 3),
                "load_coins_per_s": round(live / load_s),
                "first_read_after_load_s": round(first_read_s, 6),
                "time_to_first_rpc_s": round(load_s + first_read_s, 3),
            }
        flush_speedup = round(
            configs["4"]["flush_entries_per_s"]
            / max(configs["1"]["flush_entries_per_s"], 1), 4)
        commit_speedup = round(
            configs["4"]["churn_entries_per_s"]
            / max(configs["1"]["churn_entries_per_s"], 1), 4)
        wal_commit_speedup = round(
            configs["4_wal"]["churn_entries_per_s"]
            / max(configs["4"]["churn_entries_per_s"], 1), 4)
        bloom_commit_speedup = round(
            configs["4"]["churn_entries_per_s"]
            / max(configs["4_nobloom"]["churn_entries_per_s"], 1), 4)
        result = {
            "metric": "utxo_store",
            **_bench_stamp(),
            "coins": n_coins,
            "churn": {"rounds": rounds, "adds": half, "deletes": half},
            "cores_ge_shards": (os.cpu_count() or 1) >= 4,
            "shards": configs,
            "flush_speedup_4v1": flush_speedup,
            "commit_speedup_4v1": commit_speedup,
            "wal_commit_speedup_4": wal_commit_speedup,
            "bloom_commit_speedup_4": bloom_commit_speedup,
            "bloom_old_lookup_cut": configs["4"]["bloom"]["old_lookup_cut"],
            "meets_1_5x_bar": flush_speedup >= 1.5,
            "snapshot": snap_stats,
            "note": "flush_* = the parallel per-shard apply phase "
                    "(journals/manifest/accumulator excluded — those are "
                    "identical work at any fanout); commit_* = whole "
                    "batch_write_serialized wall. On a single-core host "
                    "the fanout win is bounded by the fsync/IO fraction "
                    "of the flush (sqlite page work serializes on the "
                    "one core) — the 1.5x bar presumes cores >= shards "
                    "(cores_ge_shards records whether this host met "
                    "that). 4_wal = -coinswal=1 at the same fanout; "
                    "4_nobloom disables the write-side key bloom, so "
                    "bloom_commit_speedup_4 is the accumulator "
                    "old-value-lookup cut's whole-commit win and "
                    "bloom_old_lookup_cut the fraction of changed-key "
                    "lookups the filter skipped. time_to_first_rpc_s = "
                    "snapshot load + first point read — the assumeutxo "
                    "serve point; a full IBD instead scales with chain "
                    "length (see BENCH.md reindex numbers), not UTXO "
                    "size.",
        }
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r12.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        emit("utxo_store_flush_speedup_4v1", flush_speedup, "x",
             flush_speedup,
             **{k: v for k, v in result.items() if k != "metric"})
        return {"utxo_store_flush_speedup_4v1": flush_speedup,
                "utxo_store_wal_commit_speedup": wal_commit_speedup,
                "utxo_store_bloom_commit_speedup": bloom_commit_speedup,
                "utxo_snapshot_load_coins_per_s":
                    snap_stats.get("load_coins_per_s")}
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("utxo_store_flush_speedup_4v1", -1, "x", 0.0,
             error=f"{type(e).__name__}: {e}")
        return None
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _storm_corpus(n_txs: int, seed: int = 20):
    """Seeded flood corpus: structurally-valid unsigned transactions in
    random package shapes — chains up to the 25-deep ancestor limit,
    1-3-output fans, fees in [100, 50000). Same seed => byte-identical
    corpus, so the batched and per-tx pools see the same flood."""
    import random as _random

    from bitcoincashplus_tpu.consensus.tx import (COutPoint, CTransaction,
                                                  CTxIn, CTxOut)

    rng = _random.Random(seed)
    corpus = []     # (tx, fee)
    open_outs = []  # (txid, vout, depth): spendable in-corpus outpoints
    for i in range(n_txs):
        n_out = rng.randint(1, 3)
        if open_outs and rng.random() < 0.72:
            j = rng.randrange(len(open_outs))
            parent_txid, vout, depth = open_outs[j]
            open_outs[j] = open_outs[-1]
            open_outs.pop()
            inputs = [COutPoint(parent_txid, vout)]
        else:
            depth = 0
            inputs = [COutPoint(i.to_bytes(4, "big") * 8, 0)]
        tx = CTransaction(
            vin=tuple(CTxIn(op, bytes([i & 0xFF, (i >> 8) & 0xFF]))
                      for op in inputs),
            vout=tuple(CTxOut(10_000, b"\x51") for _ in range(n_out)))
        corpus.append((tx, rng.randint(100, 50_000)))
        if depth + 1 < 25:
            for v in range(n_out):
                open_outs.append((tx.txid, v, depth + 1))
    return corpus


def _storm_admit(pool, corpus, mempool_mod):
    """Flood `corpus` through the pool the way AcceptToMemoryPool does —
    add_unchecked + trim_to_size per admission, a prioritise delta every
    97th tx — timing each admission. Returns per-admission seconds."""
    lat = []
    for k, (tx, fee) in enumerate(corpus):
        entry = mempool_mod.MempoolEntry(tx, fee, k, 1)
        t0 = time.perf_counter()
        pool.add_unchecked(entry)
        pool.trim_to_size()
        if k % 97 == 96:
            # mid-storm prioritise (negative deltas included) — the
            # frontier must absorb re-scores while eviction is live
            pool.prioritise(corpus[k - 31][0].txid,
                            ((k * 2654435761) % 11_000) - 3_000)
        lat.append(time.perf_counter() - t0)
    return lat


def bench_mempool_storm():
    """ISSUE 20 headline: flood-scale mempool. Leg (a) feeds the same
    seeded flood (matched scale, -maxmempool sized to force bulk
    eviction) through the batched pool and the per-tx reference pool and
    asserts byte-identical surviving mempool contents AND a
    byte-identical block template, reporting the batched-vs-per-tx
    speedup at saturation. Leg (b) runs the full 100k-tx flood batched
    and enforces the accept-p99 and template-build latency bars. Writes
    BENCH_r20.json."""
    from bitcoincashplus_tpu.consensus.merkle import compute_merkle_root
    from bitcoincashplus_tpu.mempool import mempool as mempool_mod

    n_txs = int(os.environ.get("BCP_BENCH_STORM_TXS", "100000"))
    n_par = min(n_txs, int(os.environ.get("BCP_BENCH_STORM_PARITY_TXS",
                                          "20000")))
    p99_bar_ms = float(os.environ.get("BCP_BENCH_STORM_P99_MS", "2.0"))
    tpl_bar_ms = float(os.environ.get("BCP_BENCH_STORM_TPL_MS", "5000"))
    # block-sized template cap: the reference selector's full scan per
    # emitted package is O(template_txs * pool) — an uncapped template
    # over the whole pool would make the per-tx control take hours at
    # parity scale, and real templates are block-capped anyway
    tpl_cap = int(os.environ.get("BCP_BENCH_STORM_TPL_BYTES", "200000"))
    corpus = _storm_corpus(n_txs)

    def total_bytes(txs):
        return sum(mempool_mod.MempoolEntry(tx, fee, 0, 1).size
                   for tx, fee in txs)

    def quantile(xs, q):
        ys = sorted(xs)
        return ys[min(len(ys) - 1, int(q * len(ys)))]

    def run_flavor(batch, flood, cap):
        pool = mempool_mod.CTxMemPool(max_size_bytes=cap, batch=batch)
        lat = _storm_admit(pool, flood, mempool_mod)
        # template builds at saturation: select + pack + merkle root —
        # the CreateNewBlock work that doesn't need a chainstate
        sel, tpl_times = None, []
        for _ in range(3):
            t0 = time.perf_counter()
            sel = pool.select_for_block(tpl_cap, 2, 1_000_000_000)
            vtx = [e.tx.serialize() for e in sel]
            root, _ = compute_merkle_root(
                [b"\x00" * 32] + [e.txid for e in sel])
            tpl_times.append(time.perf_counter() - t0)
        assert root is not None and vtx is not None
        return pool, lat, tpl_times, sel

    # ---- leg (a): batched-vs-per-tx parity + speedup at saturation ----
    flood_a = corpus[:n_par]
    cap_a = int(total_bytes(flood_a) * 0.6)  # forces bulk eviction
    pool_ref, lat_ref, tpl_ref, sel_ref = run_flavor(False, flood_a, cap_a)
    pool_bat, lat_bat, tpl_bat, sel_bat = run_flavor(True, flood_a, cap_a)
    assert sorted(pool_bat.entries) == sorted(pool_ref.entries), \
        "batched pool diverged from per-tx reference"
    assert pool_bat.total_size == pool_ref.total_size
    tmpl_bat = b"".join(e.tx.serialize() for e in sel_bat)
    tmpl_ref = b"".join(e.tx.serialize() for e in sel_ref)
    assert tmpl_bat == tmpl_ref, "block template diverged"
    # saturation = the flood tail, where eviction + deep frontiers bite
    tail = len(flood_a) // 2
    admit_speedup = sum(lat_ref[tail:]) / max(sum(lat_bat[tail:]), 1e-9)
    tpl_speedup = (sorted(tpl_ref)[len(tpl_ref) // 2]
                   / max(sorted(tpl_bat)[len(tpl_bat) // 2], 1e-9))
    total_speedup = ((sum(lat_ref) + sum(tpl_ref))
                     / max(sum(lat_bat) + sum(tpl_bat), 1e-9))

    # ---- leg (b): full-scale batched flood with latency bars ----------
    cap_b = int(total_bytes(corpus) * 0.7)
    pool_b, lat_b, tpl_b, sel_b = run_flavor(True, corpus, cap_b)
    p50_ms = quantile(lat_b, 0.50) * 1e3
    p99_ms = quantile(lat_b, 0.99) * 1e3
    tpl_ms = sorted(tpl_b)[len(tpl_b) // 2] * 1e3
    perf = pool_b.perf_snapshot()
    meets_p99 = p99_ms <= p99_bar_ms
    meets_tpl = tpl_ms <= tpl_bar_ms

    result = {
        "metric": "mempool_storm",
        **_bench_stamp(),
        "txs": n_txs,
        "template_cap_bytes": tpl_cap,
        "parity": {
            "txs": n_par,
            "maxmempool_bytes": cap_a,
            "survivors": len(pool_bat.entries),
            "template_txs": len(sel_bat),
            "template_bytes": len(tmpl_bat),
            "byte_identical_mempool": True,   # asserted above
            "byte_identical_template": True,  # asserted above
            "admit_speedup_at_saturation": round(admit_speedup, 3),
            "template_speedup": round(tpl_speedup, 3),
            "total_speedup": round(total_speedup, 3),
        },
        "flood": {
            "txs": len(corpus),
            "maxmempool_bytes": cap_b,
            "survivors": len(pool_b.entries),
            "accept_p50_ms": round(p50_ms, 4),
            "accept_p99_ms": round(p99_ms, 4),
            "accept_p99_bar_ms": p99_bar_ms,
            "template_build_ms": round(tpl_ms, 3),
            "template_build_bar_ms": tpl_bar_ms,
            "template_txs": len(sel_b),
            "meets_accept_p99_bar": meets_p99,
            "meets_template_bar": meets_tpl,
            "pool_perf": {k: perf[k] for k in
                          ("frontier_depth", "column_syncs", "rows_synced",
                           "frontier_pushes", "frontier_stale_pops",
                           "frontier_rebuilds", "bulk_evict_episodes",
                           "bulk_evicted", "staged_removals",
                           "select_batched") if k in perf},
        },
        "note": "admission = add_unchecked + trim_to_size per tx (the "
                "ATMP commit path) with prioritise deltas mid-storm; "
                "template = select_for_block + tx pack + merkle root "
                "(the chainstate-free CreateNewBlock work). Saturation "
                "speedup compares the flood tail, where the reference "
                "path's full-scan eviction and selection go quadratic "
                "while the batched pool pops incremental frontiers. "
                "Parity legs assert byte-identical surviving mempool "
                "contents and a byte-identical template vs the per-tx "
                "reference on the same seeded flood.",
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r20.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    emit("mempool_storm_accept_p99_ms", round(p99_ms, 4), "ms",
         round(p99_bar_ms / max(p99_ms, 1e-9), 2), bar_ms=p99_bar_ms,
         p50_ms=round(p50_ms, 4), meets_bar=meets_p99)
    emit("mempool_storm_template_ms", round(tpl_ms, 3), "ms",
         round(tpl_bar_ms / max(tpl_ms, 1e-9), 2), bar_ms=tpl_bar_ms,
         template_txs=len(sel_b), meets_bar=meets_tpl)
    emit("mempool_storm_batched_speedup", round(total_speedup, 3), "x",
         round(total_speedup, 3),
         admit_speedup_at_saturation=round(admit_speedup, 3),
         template_speedup=round(tpl_speedup, 3),
         parity_txs=n_par, flood_txs=n_txs,
         byte_identical=True)
    assert meets_p99, (
        f"accept p99 {p99_ms:.3f}ms over the {p99_bar_ms}ms bar")
    assert meets_tpl, (
        f"template build {tpl_ms:.1f}ms over the {tpl_bar_ms}ms bar")
    return {"mempool_storm_batched_speedup": round(total_speedup, 3),
            "mempool_storm_accept_p99_ms": round(p99_ms, 4),
            "mempool_storm_template_ms": round(tpl_ms, 3)}


def bench_telemetry_overhead():
    """ISSUE 6 satellite: what the unified telemetry layer costs. The
    import_pipeline corpus is imported through the pipelined Python
    engine once per -telemetry level (off / counters / trace), min-of-N
    walls (min is the noise-robust statistic for a fixed workload on a
    shared host). The counters level must stay under the 2% budget —
    asserted, and recorded in BENCH_r06.json next to this script. The
    trace run also schema-checks its own span dump (every event carries
    name/ph/ts, X-phase events carry dur) so the perfetto contract is
    bench-enforced, not just unit-tested."""
    import shutil
    import tempfile

    from bitcoincashplus_tpu.util import telemetry as tm

    n_sigs = int(os.environ.get("BCP_BENCH_TELEMETRY_SIGS", "3000"))
    depth = int(os.environ.get("BCP_BENCH_PIPELINE_DEPTH", "8"))
    repeats = int(os.environ.get("BCP_BENCH_TELEMETRY_REPEATS", "3"))
    workdir = tempfile.mkdtemp(prefix="bcp-telemetry-bench-")
    mode_save = tm.mode()
    try:
        from tools.gen_sigchain import generate

        gen = generate(workdir, n_sigs, mixed=True)
        # untimed warm-up import: the first reindex pays one-off costs
        # (jit/cache warming, sqlite page cache) that would otherwise be
        # billed entirely to whichever level runs first
        _run_reindex(workdir, pipeline_depth=depth, force_python=True,
                     telemetry="counters")
        # INTERLEAVED rounds (off, counters, trace per round), min per
        # level: host-cache drift across a long run would otherwise bias
        # whichever level ran last faster than the first — a consecutive
        # per-level loop measured "off" consistently SLOWER than counters
        walls = {"off": [], "counters": [], "trace": []}
        trace_events = 0
        trace_schema_ok = None
        for _ in range(repeats):
            for level in ("off", "counters", "trace"):
                tm.TRACER.clear()
                st = _run_reindex(workdir, pipeline_depth=depth,
                                  force_python=True, telemetry=level)
                walls[level].append(st["wall_s"])
                if level == "trace":
                    events = tm.TRACER.chrome_trace()["traceEvents"]
                    trace_events = len(events)
                    trace_schema_ok = bool(events) and all(
                        isinstance(ev.get("name"), str)
                        and ev.get("ph") in ("X", "i")
                        and isinstance(ev.get("ts"), (int, float))
                        and (ev["ph"] != "X"
                             or isinstance(ev.get("dur"), (int, float)))
                        for ev in events
                    )
        walls = {k: min(v) for k, v in walls.items()}
        counters_pct = (walls["counters"] / walls["off"] - 1.0) * 100.0
        trace_pct = (walls["trace"] / walls["off"] - 1.0) * 100.0
        # ISSUE 8 gate extension: the measured import path now includes
        # the device-lane accounting (watchdog beats per settled block,
        # program watches + transfer counters on every device dispatch,
        # the scrape-time collectors) — record that it was live so the
        # < 2% budget provably covers it
        from bitcoincashplus_tpu.util import devicewatch as _dw

        beats = _dw.WATCHDOG.beat_totals()
        device_accounting = {
            "included": True,
            "watchdog_beats": beats,
            "watched_programs": sorted(_dw.snapshot()["programs"]),
        }
        assert beats.get("pipeline", 0) > 0, (
            "device accounting not exercised: the pipelined import "
            "recorded no watchdog beats")
        result = {
            "metric": "telemetry_overhead",
            **_bench_stamp(),
            "device_accounting": device_accounting,
            "corpus": {"sigs": gen["sigs"], "blocks": gen["blocks"],
                       "bytes": gen["bytes"], "mixed": True,
                       "pipeline_depth": depth, "repeats": repeats},
            "wall_s": {k: round(v, 3) for k, v in walls.items()},
            "counters_overhead_pct": round(counters_pct, 3),
            "trace_overhead_pct": round(trace_pct, 3),
            "budget_pct": 2.0,
            "counters_under_budget": counters_pct < 2.0,
            "trace_events": trace_events,
            "trace_schema_ok": trace_schema_ok,
            "note": "pipelined Python engine (force_python), min-of-N "
                    "walls per -telemetry level on the import_pipeline "
                    "corpus; trace run schema-checks its span dump",
        }
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_r06.json"), "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
        assert trace_schema_ok, "trace dump failed schema validation"
        assert counters_pct < 2.0, (
            f"counters-mode telemetry overhead {counters_pct:.2f}% "
            f"breaks the 2% budget (walls: {walls})")
        emit("telemetry_overhead", round(counters_pct, 3), "%",
             round(2.0 / max(counters_pct, 1e-3), 4),
             **{k: v for k, v in result.items() if k != "metric"})
        return {"telemetry_overhead_pct": round(counters_pct, 3)}
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("telemetry_overhead", -1, "%", 0.0,
             error=f"{type(e).__name__}: {e}")
        return None
    finally:
        try:
            tm.set_mode(mode_save)
        except ValueError:
            pass
        shutil.rmtree(workdir, ignore_errors=True)


def _bench_serving_levels():
    """ISSUE 7 tentpole metric: synchronous vs serviced accept-path
    signature throughput at several offered-load levels, CPU lower bound.

    The unit of work is a 2-input transaction's fresh sigcheck records.
    'sync' is the -sigservice=off accept shape: one per-tx
    ecdsa_batch.verify_batch call per transaction, fanned across worker
    threads (generous to sync — the real node serializes P2P ingest on
    one event loop). 'serviced' enqueues the same transactions into a
    SigService and awaits the per-tx futures. Levels:

      light      — closed loop, 1 submitter (the latency floor: a lone
                   tx pays kick-flush handoff, never the full deadline)
      concurrent — closed loop, 8 submitters (RPC-thread shape)
      saturation — open loop: submit the whole burst, then await (the
                   tx-storm shape; arrivals outpace service, batches
                   grow to the bucket and the device-lane amortization
                   pays — the acceptance bar is serviced >= 2x sync here)

    Per-tx latencies are enqueue->verdict. Results land in BENCH_r07.json
    (first entry in the serving trajectory)."""
    import threading as _threading

    from bitcoincashplus_tpu import native as _nat
    from bitcoincashplus_tpu.crypto import secp256k1 as _oracle
    from bitcoincashplus_tpu.ops import ecdsa_batch
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord
    from bitcoincashplus_tpu.serving import SigService

    rng = np.random.RandomState(0x5E21)
    ntx = int(os.environ.get("BCP_BENCH_SERVING_TXS", "1000"))
    repeats = int(os.environ.get("BCP_BENCH_SERVING_REPEATS", "2"))

    # a small keypair pool (Python point_mul is ~50 ms each) signing a
    # FRESH message per record: every record still has a distinct
    # (sighash, r, s, pubkey) identity, so SigService in-flight dedup
    # never collapses the workload
    sign = _nat.ecdsa_sign if _nat.available() else _oracle.ecdsa_sign
    keypool = []
    for _ in range(16):
        secret = int.from_bytes(rng.bytes(32), "big") % (_oracle.N - 1) + 1
        keypool.append((secret, _oracle.point_mul(secret, _oracle.G)))

    def fresh_records(n):
        out = []
        for i in range(n):
            secret, pub = keypool[i % len(keypool)]
            e = int.from_bytes(rng.bytes(32), "big") % _oracle.N
            r, s = sign(secret, e)
            out.append(SigCheckRecord(pub, r, s, e))
        return out

    def pctl(lat, q):
        s = sorted(lat)
        return s[min(len(s) - 1, int(q * len(s)))] * 1e3

    def run_sync(txs, workers):
        import queue as _queue

        q = _queue.Queue()
        for t in txs:
            q.put(t)
        lat = []
        lock = _threading.Lock()

        def w():
            while True:
                try:
                    chunk = q.get_nowait()
                except _queue.Empty:
                    return
                t0 = time.monotonic()
                ecdsa_batch.verify_batch(chunk, backend="cpu")
                with lock:
                    lat.append(time.monotonic() - t0)

        threads = [_threading.Thread(target=w) for _ in range(workers)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.monotonic() - t0, lat

    def run_serviced(txs, submitters, open_loop):
        svc = SigService(backend="cpu", deadline_ms=4, lanes=2046).start()
        lat = []
        lock = _threading.Lock()
        chunks = [txs[i::submitters] for i in range(submitters)]

        def w(i):
            if open_loop:
                pairs = [(time.monotonic(), svc.submit(c))
                         for c in chunks[i]]
                for te, f in pairs:
                    f.result()
                    with lock:
                        lat.append(time.monotonic() - te)
            else:
                for c in chunks[i]:
                    t0 = time.monotonic()
                    svc.submit(c).result()
                    with lock:
                        lat.append(time.monotonic() - t0)

        threads = [_threading.Thread(target=w, args=(i,))
                   for i in range(submitters)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        stats = dict(svc.stats)
        svc.stop()
        return wall, lat, stats

    # warm the native/CPU lane outside the timed runs
    ecdsa_batch.verify_batch(fresh_records(4), backend="cpu")
    levels = {
        "light": {"txs": max(50, ntx // 10), "workers": 1,
                  "open_loop": False},
        "concurrent": {"txs": max(200, ntx // 2), "workers": 8,
                       "open_loop": False},
        "saturation": {"txs": ntx, "workers": 1, "open_loop": True},
    }
    out_levels = {}
    stats_at_saturation = None
    for name, cfg in levels.items():
        best = None
        for _ in range(repeats):
            # FRESH records per timed run (the serving memoization caveat
            # in the module docstring; also keeps SigService dedup honest)
            recs = fresh_records(cfg["txs"] * 2)
            txs = [recs[i * 2:(i + 1) * 2] for i in range(cfg["txs"])]
            ws, ls = run_sync(txs, workers=max(cfg["workers"], 8)
                              if name == "saturation" else cfg["workers"])
            wv, lv, st = run_serviced(txs, cfg["workers"],
                                      cfg["open_loop"])
            row = {
                "offered_txs": cfg["txs"],
                "sync_tx_per_s": round(cfg["txs"] / ws, 1),
                "serviced_tx_per_s": round(cfg["txs"] / wv, 1),
                "speedup": round(ws / wv, 3),
                "sync_p50_ms": round(pctl(ls, 0.5), 3),
                "sync_p99_ms": round(pctl(ls, 0.99), 3),
                "serviced_p50_ms": round(pctl(lv, 0.5), 3),
                "serviced_p99_ms": round(pctl(lv, 0.99), 3),
                "serviced_dispatches": st["dispatches"],
                "serviced_lanes": st["lanes_real"],
            }
            if best is None or row["serviced_tx_per_s"] > \
                    best["serviced_tx_per_s"]:
                best = row
                if name == "saturation":
                    stats_at_saturation = st
        out_levels[name] = best
    return out_levels, stats_at_saturation


def bench_serving():
    """Wrapper: run _bench_serving_levels and record BENCH_r07.json; a
    failure is reported, never fatal to the rest of the bench run."""
    try:
        out_levels, stats_at_saturation = _bench_serving_levels()
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("serving_saturation_speedup", -1, "x", 0.0,
             error=f"{type(e).__name__}: {e}")
        return None
    sat = out_levels["saturation"]
    result = {
        "metric": "serving",
        **_bench_stamp(),
        "unit_of_work": "2-input tx (2 fresh sigcheck records)",
        "backend": "cpu",
        "levels": out_levels,
        "saturation_speedup": sat["speedup"],
        "meets_2x_bar": sat["speedup"] >= 2.0,
        "flush_reasons_at_saturation": {
            k.replace("flush_", ""): v
            for k, v in (stats_at_saturation or {}).items()
            if k.startswith("flush_")},
        "note": "sync = per-tx verify_batch across worker threads "
                "(-sigservice=off shape); serviced = SigService shared "
                "lanes, deadline 4 ms, bucket 2046; saturation is the "
                "open-loop tx-storm shape",
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r07.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    emit("serving_saturation_speedup", sat["speedup"], "x", sat["speedup"],
         **{k: v for k, v in result.items() if k != "metric"})
    return {"serving_saturation_speedup": sat["speedup"]}


def bench_dispatch_breakdown():
    """ISSUE 8 tentpole metric, re-run for ISSUE 11: per-phase (pack /
    transfer / execute / fetch) decomposition of one device dispatch,
    for the ecdsa verify path and the nonce-sweep path. Phases are
    isolated with explicit staging (jax.device_put + block_until_ready)
    so transfer is not hidden inside the async dispatch; `execute` runs
    on device-resident inputs.

    Since ISSUE 11 the ecdsa leg rides the device-decompose GLV program:
    the host pack is numpy byte emission only, and the result records a
    per-stage PACK SPLIT (decompose vs emit, for both the shipped device
    path and the retained host-decompose fallback) plus a verdict-parity
    check against the host-decompose oracle program and the CPU engine.
    The acceptance bar host_share < 0.15 at bucket 2048 is ASSERTED.
    Writes BENCH_r11.json (schema v2, host-fingerprint stamped — a
    CPU-sandbox breakdown and a real-chip one are different series;
    BENCH_r08.json keeps the pre-decompose-kernel record)."""
    from bitcoincashplus_tpu.ops import ecdsa_batch
    from bitcoincashplus_tpu.ops import secp256k1 as dev
    from bitcoincashplus_tpu.util import devicewatch as dwatch

    # the GLV/w4 programs are minutes of XLA compile on a cold CPU
    # backend — share the persistent compilation cache the test suite
    # and the kernel-dimension subprocesses already use (routed through
    # the -compilecache plumbing so hits land in the r11 record)
    dwatch.enable_compile_cache()

    n = int(os.environ.get("BCP_BENCH_BREAKDOWN_SIGS", "2046"))
    repeats = int(os.environ.get("BCP_BENCH_BREAKDOWN_REPEATS", "3"))
    rng = np.random.default_rng(8)

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    def run_phases(make_args, stage, execute, fetch):
        """One phased dispatch per repeat; returns median seconds per
        phase + the transfer byte counts of the last repeat."""
        phases = {"pack": [], "transfer": [], "execute": [], "fetch": []}
        nbytes = {"h2d": 0, "d2h": 0}
        for _ in range(repeats):
            t0 = time.perf_counter()
            host_args = make_args()
            phases["pack"].append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            dev_args = stage(host_args)
            jax.block_until_ready(dev_args)
            phases["transfer"].append(time.perf_counter() - t0)
            nbytes["h2d"] = sum(int(np.asarray(a).nbytes)
                                for a in host_args)
            t0 = time.perf_counter()
            out = execute(dev_args)
            jax.block_until_ready(out)
            phases["execute"].append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            host_out = fetch(out)
            phases["fetch"].append(time.perf_counter() - t0)
            nbytes["d2h"] = sum(int(np.asarray(o).nbytes)
                                for o in host_out)
        out_p = {k: round(med(v), 6) for k, v in phases.items()}
        total = sum(out_p.values())
        out_p["total"] = round(total, 6)
        out_p["host_share"] = round(
            1.0 - out_p["execute"] / total, 4) if total else None
        out_p["dispatch_overhead_factor"] = round(
            total / out_p["execute"], 3) if out_p["execute"] else None
        out_p["transfer_bytes"] = nbytes
        return out_p

    # --- ecdsa leg: the packed-bucket verify dispatch ------------------
    wire_n = n + 2  # + the 2 KAT lanes the supervised dispatch appends
    bucket = max(1024, ecdsa_batch._bucket_for(wire_n, pallas=True))
    use_glv = (ecdsa_batch.active_kernel() == "glv"
               and ecdsa_batch.glv_enabled())
    use_glv_dev = use_glv and ecdsa_batch.glv_dev_enabled()

    # Corpus generation happens OUTSIDE the timed pack phase: r08's
    # "pack 3.37 s" was in fact ~3.2 s of the HARNESS's own Python
    # point_mul keygen + ~0.15 s of actual pack — the node's dispatch
    # path receives records from the interpreter/deferral layer and
    # never pays keygen, so timing it as "pack" overstated host_share.
    # Fresh corpus per repeat keeps the memoization caveat honest
    # (repeats + 1: one extra for the warm/compile call below).
    corpora = [_make_sig_records(rng, 64, n)
               + list(ecdsa_batch._kat_records())
               for _ in range(repeats + 1)]

    def ecdsa_args():
        records = corpora.pop()
        if use_glv_dev:
            # ISSUE 11 production path: byte emission only — the lattice
            # split runs inside the fused device program
            return ecdsa_batch.pack_records_w4_bytes(records, bucket)
        if use_glv:
            return ecdsa_batch.pack_records_glv(records, bucket)
        return ecdsa_batch.pack_records_w4_bytes(records, bucket)

    interp = ecdsa_batch._interpret_kernels()

    def ecdsa_exec(dev_args):
        if use_glv_dev:
            return dev._glv_dev_program(*dev_args)
        if use_glv:
            return dev._glv_program(*dev_args)
        return dev._w4_bytes_program(*dev_args, interpret=interp)

    # warm/compile through the WATCHED supervised dispatch first, so the
    # devicewatch program registry (reported below) reflects a real
    # dispatch of this shape — then pre-stage once for the phased runs
    ok = ecdsa_batch.verify_batch(
        _make_sig_records(rng, 8, n), backend="device")
    assert bool(ok.all())
    warm = jax.device_put(ecdsa_args())
    jax.block_until_ready(ecdsa_exec(warm))
    ecdsa_phases = run_phases(
        ecdsa_args, jax.device_put, ecdsa_exec,
        lambda out: [np.asarray(out)])
    ecdsa_phases["kernel"] = "glv-device-decompose" if use_glv_dev else (
        "glv" if use_glv else
        ("w4-bytes-interpret" if interp else "w4-bytes"))
    ecdsa_phases["lanes"] = n
    ecdsa_phases["bucket"] = bucket
    ecdsa_phases["sigs_per_s_end_to_end"] = round(
        n / max(ecdsa_phases["total"], 1e-9))
    ecdsa_phases["sigs_per_s_device_resident"] = round(
        n / max(ecdsa_phases["execute"], 1e-9))

    # per-stage pack split (ISSUE 11 satellite): decompose vs emit, for
    # the shipped device-decompose path AND the retained host fallback —
    # the before/after of moving the lattice split on-device
    if use_glv:
        records = _make_sig_records(rng, 64, n) \
            + list(ecdsa_batch._kat_records())
        st = ecdsa_batch.STATS
        t0 = time.perf_counter()
        emit_args = ecdsa_batch.pack_records_w4_bytes(records, bucket)
        emit_s = time.perf_counter() - t0
        d0, p0 = st.glv_decompose_s, st.glv_pack_s
        t0 = time.perf_counter()
        host_args = ecdsa_batch.pack_records_glv(records, bucket)
        host_total = time.perf_counter() - t0
        # the pre-r11 per-record Python-bigint loop, replicated inline —
        # the honest "before" of the decompose leg (it no longer exists
        # on any path)
        u1b, u2b, _ok = ecdsa_batch._scalar_bitplanes(
            records, len(records))
        t0 = time.perf_counter()
        for i in range(len(records)):
            a1, _n1, a2, _n2 = dev.glv_decompose(
                int.from_bytes(u1b[i].tobytes(), "big"))
            b1, _n3, b2, _n4 = dev.glv_decompose(
                int.from_bytes(u2b[i].tobytes(), "big"))
            a1.to_bytes(16, "little"), a2.to_bytes(16, "little")
            b1.to_bytes(16, "big"), b2.to_bytes(16, "big")
        legacy_s = time.perf_counter() - t0
        ecdsa_phases["pack_split"] = {
            "device_decompose_path": {
                "decompose": 0.0, "emit": round(emit_s, 6),
            },
            "host_fallback_path": {
                "decompose": round(st.glv_decompose_s - d0, 6),
                "emit": round(st.glv_pack_s - p0, 6),
                "total": round(host_total, 6),
            },
            "legacy_per_record_bigint_loop": round(legacy_s, 6),
        }
        # verdict parity: the device-decompose program vs the
        # host-decompose oracle program vs the CPU engine, same lanes
        if use_glv_dev:
            out_dev = np.asarray(ecdsa_exec(jax.device_put(
                ecdsa_batch.pack_records_w4_bytes(records, bucket))))
            out_host = np.asarray(dev._glv_program(*host_args))
            cpu = ecdsa_batch._verify_cpu(records)
            real = slice(0, len(records))
            dev_ok = out_dev[0].reshape(-1)[real].astype(bool)
            host_ok = out_host[0].reshape(-1)[real].astype(bool)
            parity = (dev_ok.tolist() == host_ok.tolist()
                      == np.asarray(cpu, bool).tolist())
            ecdsa_phases["verdict_parity_vs_host_decompose"] = bool(parity)
            assert parity, "device-decompose verdicts diverged"
    if use_glv_dev and bucket == 2048:
        # the ISSUE 11 acceptance bar, enforced where the bench runs
        assert ecdsa_phases["host_share"] < 0.15, ecdsa_phases

    # --- sweep leg: the mining nonce dispatch --------------------------
    from bitcoincashplus_tpu.crypto.hashes import header_midstate
    from bitcoincashplus_tpu.ops.miner import sweep_jit
    from bitcoincashplus_tpu.ops.sha256 import (
        bytes_to_words_np,
        target_to_limbs_np,
    )

    on_cpu = jax.default_backend() == "cpu"
    tile = 1 << 14 if on_cpu else 1 << 16
    n_tiles = 4 if on_cpu else 64

    def sweep_args():
        header = bytes([rng.integers(0, 256) for _ in range(80)])
        return (
            np.array(header_midstate(header), dtype=np.uint32),
            bytes_to_words_np(np.frombuffer(header[64:76], np.uint8)),
            target_to_limbs_np(0),  # no hit: the sweep runs every tile
            np.uint32(rng.integers(0, 1 << 32)),
            np.uint32(n_tiles),
        )

    def sweep_exec(dev_args):
        return sweep_jit(*dev_args, tile=tile)

    warm = jax.device_put(sweep_args())
    jax.block_until_ready(sweep_exec(warm))
    sweep_phases = run_phases(
        sweep_args, jax.device_put, sweep_exec,
        lambda out: [np.asarray(o) for o in out])
    sweep_phases["tile"] = tile
    sweep_phases["n_tiles"] = n_tiles
    sweep_phases["mhs_end_to_end"] = round(
        tile * n_tiles / max(sweep_phases["total"], 1e-9) / 1e6, 3)
    sweep_phases["mhs_device_resident"] = round(
        tile * n_tiles / max(sweep_phases["execute"], 1e-9) / 1e6, 3)

    # serving re-measure (ISSUE 11 satellite): the closed-loop
    # `concurrent` level lost to sync in BENCH_r07 (0.48x) largely on
    # per-lane submit cost — re-measured now that the GLV host pack is
    # byte emission only. Recorded here (BENCH_r07.json keeps the
    # original trajectory entry).
    serving_recheck = None
    if os.environ.get("BCP_BENCH_SKIP_SERVING") != "1":
        try:
            out_levels, _sat = _bench_serving_levels()
            serving_recheck = {
                "levels": out_levels,
                "concurrent_speedup": out_levels["concurrent"]["speedup"],
                "baseline_r07_concurrent_speedup": 0.48,
            }
        except Exception as e:  # pragma: no cover - diagnostics only
            serving_recheck = {"error": f"{type(e).__name__}: {e}"}

    result = {
        "metric": "dispatch_breakdown",
        **_bench_stamp(),
        "repeats": repeats,
        "ecdsa": ecdsa_phases,
        "sweep": sweep_phases,
        "serving_recheck": serving_recheck,
        "device_watch": {
            name: {k: snap[k] for k in
                   ("dispatches", "compiles", "compile_seconds", "shapes",
                    "shape_budget", "retraces_unexpected")}
            for name, snap in dwatch.snapshot()["programs"].items()
        },
        "compilation_cache": dwatch.compile_cache_snapshot(),
        "note": "median-of-N per phase; pack = host byte-matrix emit "
                "(the GLV lattice decompose rides the DEVICE program "
                "since ISSUE 11 — pack_split records the before/after), "
                "transfer = explicit device_put staging, execute = "
                "program on device-resident inputs, fetch = host "
                "materialization of the result. MEASUREMENT CORRECTION "
                "vs BENCH_r08: r08's pack leg timed the harness's own "
                "corpus generation (~3.2 s of Python point_mul keygen) "
                "inside 'pack', overstating host_share — the node's "
                "dispatch path never pays keygen. r11 times the pack "
                "alone; the honest before/after of the real pack is in "
                "pack_split (host_fallback_path vs "
                "device_decompose_path). On a CPU backend the transfer "
                "legs are memcpy-scale lower bounds, not PCIe "
                "numbers",
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r11.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    emit("dispatch_breakdown",
         ecdsa_phases["dispatch_overhead_factor"], "x",
         0.0, **{k: v for k, v in result.items() if k != "metric"})
    return {"ecdsa_dispatch_overhead_x":
            ecdsa_phases["dispatch_overhead_factor"],
            "sweep_dispatch_overhead_x":
            sweep_phases["dispatch_overhead_factor"]}


def bench_reindex(device_sps=None):
    """Config 6 — the NORTH STAR (BASELINE.json: mainnet -reindex wall-clock
    < 45 min on v5e-8): generate a synthetic signature-dense regtest chain
    (tools/gen_sigchain.py), run the full Node(-reindex) import over it
    (native connect engine -> packed TPU sig batches, the production path),
    and project a mainnet wall-clock from measured component rates.

    Projection model (constants are fork-era public chain shape, NOT from
    the empty reference mount), additive (conservative — the import
    pipelines device verify under host byte work, so the true wall is
    closer to max of the legs):
      byte_leg = MAINNET_BYTES / (chain_bytes / non_verify_import_seconds)
      sig_leg  = MAINNET_SIG_INPUTS / device_sigs_per_s   (config 4's
                 content-randomized measurement; the import's own verify
                 waits are partially hidden by pipelining, so the raw
                 dispatch rate is the honest per-sig cost)
    A second, heterogeneous chain (mixed input counts, P2PK, P2SH
    multisig — tools/gen_sigchain._mixed_phase) reports the script-shape
    bias of the uniform best case (VERDICT r4 item 6)."""
    import shutil
    import tempfile

    MAINNET_BLOCKS = 478_558      # the fork height (params.py uahf_height)
    MAINNET_SIG_INPUTS = 550e6    # ~240M txs x ~2.3 inputs avg at that height
    MAINNET_BYTES = 130e9         # ~130 GB serialized chain at that height

    n_sigs = int(os.environ.get("BCP_BENCH_REINDEX_SIGS", "16000"))
    n_mixed = int(os.environ.get("BCP_BENCH_REINDEX_MIXED_SIGS", "4000"))
    workdir = tempfile.mkdtemp(prefix="bcp-reindex-bench-")
    mixdir = tempfile.mkdtemp(prefix="bcp-reindex-mixed-")
    try:
        from tools.gen_sigchain import generate

        from bitcoincashplus_tpu.ops import ecdsa_batch

        gen = generate(workdir, n_sigs)
        genm = generate(mixdir, n_mixed, mixed=True)

        # warm the verify kernel at the import's dispatch shapes: the
        # aggregator slices exact 8192-lane batches plus a sub-8192 tail
        # (bucket 2048 here) — every verify bucket is minutes of compile
        # per shape and must not land inside the measured import
        if jax.default_backend() != "cpu":
            rng = np.random.default_rng(11)
            for n in (8192, 1100, 600):  # buckets 8192 / 2048 / 1024
                ecdsa_batch.verify_batch(_make_sig_records(rng, 8, n),
                                         backend="device")

        stats0 = ecdsa_batch.STATS.snapshot()
        stats = _run_reindex(workdir)
        assert stats["tip_height"] == gen["tip_height"], (stats, gen)
        stats1 = ecdsa_batch.STATS.snapshot()
        device_wait_s = (stats1["device_seconds"]
                         - stats0.get("device_seconds", 0))
        statsm = _run_reindex(mixdir)
        assert statsm["tip_height"] == genm["tip_height"], (statsm, genm)

        wall = stats["wall_s"]
        verify_s = stats.get("verify_s", 0.0)
        sigscan_s = stats.get("sigscan_s", 0.0)
        other_s = max(wall - verify_s - sigscan_s, 1e-9)
        byte_rate = gen["bytes"] / other_s
        sig_sps = device_sps or (gen["sigs"] / max(verify_s, 1e-9))
        proj_byte_leg = MAINNET_BYTES / byte_rate
        proj_sig_leg = MAINNET_SIG_INPUTS / sig_sps
        # host signature scan (sighash + encodings + pubkey parse): per-sig
        # work, threaded under -par — measured here on host_cpus cores
        proj_sigscan_leg = (MAINNET_SIG_INPUTS
                            * (sigscan_s / max(gen["sigs"], 1)))
        proj_min = (proj_sig_leg + proj_byte_leg + proj_sigscan_leg) / 60
        mixed_wall = statsm["wall_s"]
        mixed_other = max(mixed_wall - statsm.get("verify_s", 0.0)
                          - statsm.get("sigscan_s", 0.0), 1e-9)
        emit(
            "reindex_projected_mainnet_min", round(proj_min), "min",
            round(45.0 / max(proj_min, 1e-9), 6),
            measured={
                "sigs": gen["sigs"], "blocks": gen["blocks"],
                "bytes": gen["bytes"],
                # the host's core count bounds the threaded native legs
                # (sigscan, txid hashing, CPU ECDSA): this sandbox exposes
                # 1 core, a real v5e-8 host has >100 — the byte leg
                # projection is a per-core lower bound
                "host_cpus": os.cpu_count(),
                "import_wall_s": round(wall, 2),
                "blocks_per_s": round(gen["blocks"] / wall, 1),
                "sigs_per_s_end_to_end": round(gen["sigs"] / wall),
                "byte_MB_per_s": round(byte_rate / 1e6, 2),
                "verify_wait_s": round(verify_s, 2),
                "device_wait_s": round(device_wait_s, 2),
                "sigscan_s": round(sigscan_s, 2),
                "sigscan_us_per_sig": round(
                    sigscan_s / max(gen["sigs"], 1) * 1e6, 1),
                "native_connect_s": round(
                    stats.get("native_connect_s", 0.0), 2),
                "flush_s": round(stats.get("flush_s", 0.0), 2),
                "slow_path_blocks": stats.get("slow_path_blocks"),
            },
            mixed={
                "sigs": genm["sigs"], "bytes": genm["bytes"],
                "blocks": genm["blocks"],
                "import_wall_s": round(mixed_wall, 2),
                "sigs_per_s_end_to_end": round(genm["sigs"] / mixed_wall),
                "byte_MB_per_s": round(genm["bytes"] / mixed_other / 1e6,
                                       2),
                "fallback_inputs": statsm.get("fallback_inputs"),
            },
            projection={
                "sig_leg_min": round(proj_sig_leg / 60),
                "byte_leg_min": round(proj_byte_leg / 60),
                "host_sigscan_leg_min": round(proj_sigscan_leg / 60),
                # v5e-8 model: sig leg /8 (parallel/sig_shard over ICI);
                # host legs UNSCALED from this host's core count — a real
                # v5e-8 host threads them across >100 cores
                "v5e8_modeled_min": round(
                    (proj_sig_leg / 8 + proj_byte_leg
                     + proj_sigscan_leg) / 60),
                "device_sigs_per_s": round(sig_sps),
                "model_sig_inputs": MAINNET_SIG_INPUTS,
                "model_bytes": MAINNET_BYTES,
                "model_blocks": MAINNET_BLOCKS,
                # the reference's DEFAULT -reindex skips script/sig checks
                # below the assumevalid checkpoint (~90% of history) —
                # that skips the host sigscan too, not just the device leg
                "assumevalid_projected_min": round(
                    ((proj_sig_leg + proj_sigscan_leg) * 0.10
                     + proj_byte_leg) / 60
                ),
                "model_above_assumevalid_fraction": 0.10,
                # settle-horizon bound: with the pipelined engine the three
                # legs overlap, so the wall converges on max(legs) instead
                # of their sum (measured overlap: import_pipeline metric)
                "pipelined_max_leg_min": round(
                    max(proj_sig_leg, proj_byte_leg, proj_sigscan_leg) / 60),
            },
            note="native C++ import engine + packed TPU batches; mixed = "
                 "heterogeneous script shapes; additive projection "
                 "(pipelining makes it conservative); vs_baseline = "
                 "45/projected",
        )
        return {"projected_min": round(proj_min),
                "byte_MBs": round(byte_rate / 1e6, 1)}
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("reindex_projected_mainnet_min", -1, "min", 0.0,
             error=f"{type(e).__name__}: {e}")
        return None
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(mixdir, ignore_errors=True)


def _load_functional_framework():
    """tests/functional/framework.py as a module (the fleet bench drives
    real bcpd processes through the same harness the functional suite
    uses; tests/ is not an installed package, so load by path)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tests", "functional", "framework.py")
    spec = importlib.util.spec_from_file_location("bcp_fleet_framework", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gw_request(conn_box, port, auth, client_id, method, params,
                timeout=60.0):
    """One JSON-RPC call against the gateway's HTTP front door with an
    explicit per-client identity (X-Client-Id is what the gateway's
    token buckets key on — every bench client is its own principal).
    Returns (kind, payload, latency_s) where kind is 'ok' | 'shed' |
    'rpc_error'. Keep-alive connection per worker, one reconnect on a
    stale socket."""
    from http.client import HTTPConnection

    body = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                       "params": params}).encode()
    headers = {"Authorization": "Basic " + auth,
               "Content-Type": "application/json",
               "X-Client-Id": client_id}
    for attempt in (0, 1):
        conn = conn_box[0]
        if conn is None:
            conn = conn_box[0] = HTTPConnection("127.0.0.1", port,
                                                timeout=timeout)
        t0 = time.monotonic()
        try:
            conn.request("POST", "/", body, headers)
            resp = conn.getresponse()
            data = json.loads(resp.read())
        except Exception:
            try:
                conn.close()
            finally:
                conn_box[0] = None
            if attempt:
                raise
            continue
        lat = time.monotonic() - t0
        err = data.get("error")
        if resp.status == 429 or (err and err.get("code") == -429):
            return "shed", err, lat
        if err:
            return "rpc_error", err, lat
        return "ok", data.get("result"), lat


def bench_fleet():
    """ISSUE 16 acceptance harness: >= 1000 concurrent seeded clients
    hold a p99 latency bar against the gateway while a forkfeeder-driven
    fork storm reorgs the validator underneath and a chaos kill -9 takes
    a replica out (and back) mid-run. Asserted: zero inconsistent
    replies (every replied tip is a block the validator recognizes),
    nonzero shed + coalesce counters, >= 1 mid-request failover, and a
    byte-identical chainstate digest across validator and replicas at
    quiesce. Writes BENCH_r16.json (schema_version=2 host stamp)."""
    import base64
    import threading
    from concurrent.futures import ThreadPoolExecutor

    fw = _load_functional_framework()
    from bitcoincashplus_tpu.consensus.params import regtest_params
    from bitcoincashplus_tpu.wallet.keys import CKey

    n_clients = int(os.environ.get("BCP_BENCH_FLEET_CLIENTS", "1000"))
    reqs_per = int(os.environ.get("BCP_BENCH_FLEET_REQS", "3"))
    workers = int(os.environ.get("BCP_BENCH_FLEET_WORKERS", "16"))
    p99_bar_ms = float(os.environ.get("BCP_BENCH_FLEET_P99_MS", "2500"))
    seed = int(os.environ.get("BCP_BENCH_FLEET_SEED", "1607"))
    chain_h = 24
    addr = CKey(0xF1EE7).p2pkh_address(regtest_params())

    f = fw.FunctionalFramework(num_nodes=4)
    # node0 validator+gateway, nodes 1-2 replicas, node3 storm miner
    # (NOT in the pool). Tight per-client buckets so the hot clients
    # below provably shed: burst 10, refill 5/s, read floor 2.5.
    fw.setup_fleet(f, replicas=f.nodes[1:3])
    f.nodes[0].extra_args += ["-gatewayrate=5", "-gatewayburst=10"]
    t_run0 = time.monotonic()
    with f:
        validator, r1, r2, storm = f.nodes
        gw_port, auth = validator.gateway_port, base64.b64encode(
            f"{fw.FLEET_USER}:{fw.FLEET_PASSWORD}".encode()).decode()
        validator.rpc.generatetoaddress(chain_h, addr)
        fw.connect_nodes(storm, validator)
        fw.sync_blocks([validator, storm], timeout=60)

        # snapshot-bootstrap both replicas (the 30-second spin-up path)
        snap = os.path.join(validator.datadir, "fleet-bench-snapshot")
        dump = validator.rpc.dumptxoutset(snap)
        for rep in (r1, r2):
            fw.bootstrap_replica_from_snapshot(rep, validator, snap, dump)

        def rotation():
            pool = validator.rpc.gettpuinfo()["gateway"]["pool"]
            return {r["name"] for r in pool["replicas"] if r["in_rotation"]}

        fw.wait_until(lambda: len(rotation()) == 2, timeout=60)
        for rep in (r1, r2):
            fw.wait_until(lambda rep=rep: rep.rpc.gettpuinfo()["store"]
                          ["snapshot"]["validated"], timeout=180, sleep=1.0)

        # pre-mine the competing branch: the storm miner forks the tip
        # and out-works the validator's own extension by one block. Its
        # raw blocks become the forkfeeder's ammunition; the miner then
        # leaves the stage (this host is small).
        fw.disconnect_nodes(storm, validator)
        validator.rpc.generatetoaddress(3, addr)
        b_hashes = storm.rpc.generatetoaddress(4, addr)
        branch_b = [bytes.fromhex(storm.rpc.getblock(h, 0))
                    for h in b_hashes]
        b_tip = b_hashes[-1]
        storm.stop()

        # -- the storm: seeded client fleet + fork reorg + chaos kill --
        state = {"tip": validator.rpc.getbestblockhash()}
        storm_done = threading.Event()
        rng = random.Random(seed)
        jobs = []
        for i in range(n_clients):
            crng = random.Random(seed + i)
            for _ in range(reqs_per):
                r = crng.random()
                if r < 0.5:
                    jobs.append((f"c{i}", "getbestblockhash", None))
                elif r < 0.7:
                    jobs.append((f"c{i}", "getblockcount", None))
                elif r < 0.9:
                    jobs.append((f"c{i}", "getblock", "TIP"))
                else:
                    jobs.append((f"c{i}", "getblockhash",
                                 [crng.randint(1, chain_h)]))
        rng.shuffle(jobs)
        # 5 hot clients hammer 40 rapid reads each, spliced in as
        # CONTIGUOUS runs (shuffling would spread them across the whole
        # run and let their buckets refill): 40 near-simultaneous reads
        # against a burst-10 bucket guarantees the shed counter moves
        for h in range(5):
            cut = (h + 1) * len(jobs) // 6
            jobs[cut:cut] = [(f"hot{h}", "getbestblockhash", None)] * 40
        job_q, counts_lock = iter(jobs), threading.Lock()
        shared = {"lat": [], "tips": set(), "ok": 0, "shed": 0,
                  "rpc_error": 0, "transport_error": 0}

        def drain(job_iter, wid):
            conn_box, local_lat, local_tips = [None], [], set()
            ok = shed = rpc_err = terr = 0
            k = 0
            while True:
                with counts_lock:
                    job = next(job_iter, None)
                if job is None:
                    if storm_done.is_set():
                        break
                    # keep the pressure on until the storm script ends:
                    # filler reads on rotating seeded identities
                    job = (f"c{(k * 131 + wid) % n_clients}",
                           "getbestblockhash", None)
                    k += 1
                cid, method, params = job
                if params == "TIP":
                    params = [state["tip"]]
                try:
                    kind, payload, lat = _gw_request(
                        conn_box, gw_port, auth, cid, method, params or [])
                except Exception:
                    terr += 1
                    continue
                if kind == "shed":
                    shed += 1
                    continue
                if kind == "rpc_error":
                    rpc_err += 1
                    local_lat.append(lat)
                    continue
                ok += 1
                local_lat.append(lat)
                if method == "getbestblockhash":
                    local_tips.add(payload)
                    state["tip"] = payload
                elif method == "getblock":
                    local_tips.add(payload["hash"])
            with counts_lock:
                shared["lat"] += local_lat
                shared["tips"] |= local_tips
                shared["ok"] += ok
                shared["shed"] += shed
                shared["rpc_error"] += rpc_err
                shared["transport_error"] += terr

        pool_exec = ThreadPoolExecutor(max_workers=workers)
        futures = [pool_exec.submit(drain, job_q, w)
                   for w in range(workers)]
        events = {}
        try:
            # event 1: forkfeeder replays the longer competing branch —
            # the validator MUST reorg underneath the serving load
            t0 = time.monotonic()
            feeder = fw.ChaosPeer(validator.p2p_port, "forkfeeder",
                                  seed=seed, blocks=branch_b,
                                  block_rate=200)
            feeder.start()
            fw.wait_until(
                lambda: validator.rpc.getbestblockhash() == b_tip,
                timeout=90)
            events["reorg_s"] = round(time.monotonic() - t0, 3)
            feeder.stop()

            # event 2: chaos kill -9 of replica 1 mid-run, then restart
            # and re-admission — serving must not flinch in between
            t0 = time.monotonic()
            r1.kill9()
            time.sleep(1.0)
            r1.start()
            fw.connect_nodes(r1, validator)
            fw.wait_until(lambda: len(rotation()) == 2, timeout=120)
            events["kill_rejoin_s"] = round(time.monotonic() - t0, 3)

            # event 3: one more reorg cycle (invalidate/extend/
            # reconsider) so the storm has > 1 reorg in it
            count = validator.rpc.getblockcount()
            h = validator.rpc.getblockhash(count - 1)
            validator.rpc.invalidateblock(h)
            validator.rpc.generatetoaddress(3, addr)
            validator.rpc.reconsiderblock(h)
            events["reorgs"] = 2
        finally:
            storm_done.set()
            for fut in futures:
                fut.result(timeout=300)
            pool_exec.shutdown()

        # coalesce flush: one barrier-released wave of identical reads
        # (the organic mix usually coalesces too; this makes it certain)
        tip = validator.rpc.getbestblockhash()
        barrier = threading.Barrier(workers)

        def identical(w):
            # distinct client ids: coalescing keys on method+params, and
            # a shared id would shed the wave in its own token bucket
            box = [None]
            barrier.wait()
            return _gw_request(box, gw_port, auth, f"burst{w}", "getblock",
                               [tip])
        with ThreadPoolExecutor(max_workers=workers) as ex:
            burst = list(ex.map(identical, range(workers)))
        assert all(k == "ok" and p["hash"] == tip for k, p, _ in burst)

        # -- quiesce: settle, then the byte-identical chainstate check --
        validator.rpc.generatetoaddress(1, addr)
        final_tip = validator.rpc.getbestblockhash()
        fw.wait_until(lambda: r1.rpc.getbestblockhash() == final_tip
                      and r2.rpc.getbestblockhash() == final_tip,
                      timeout=120)
        infos = [n.rpc.gettxoutsetinfo() for n in (validator, r1, r2)]
        identical_chainstate = (
            len({i["muhash"] for i in infos}) == 1
            and len({i["bestblock"] for i in infos}) == 1)

        # consistency: every tip a client was ever told is a block the
        # validator recognizes — no invented, corrupt, or cross-wired
        # reply survived the storm
        inconsistent = 0
        for h in shared["tips"]:
            try:
                validator.rpc.getblockheader(h)
            except Exception:
                inconsistent += 1
        stats = validator.rpc.gettpuinfo()["gateway"]

    lat = sorted(shared["lat"])

    def pctl(q):
        return round(lat[int(q * (len(lat) - 1))] * 1e3, 2)

    p99 = pctl(0.99)
    served = shared["ok"] + shared["rpc_error"]
    # the acceptance bar, asserted (env-tunable for slower hosts)
    assert inconsistent == 0, f"{inconsistent} inconsistent replies"
    assert identical_chainstate, "chainstate digests diverged at quiesce"
    assert stats["sheds"]["read"] > 0, "shed counter never moved"
    assert stats["coalesce_hits"] > 0, "coalesce counter never moved"
    assert stats["failovers"] >= 1, "no mid-request failover recorded"
    assert shared["shed"] > 0 and served >= n_clients
    p99_ok = p99 <= p99_bar_ms
    assert p99_ok, f"p99 {p99} ms over the {p99_bar_ms} ms bar"
    result = {
        "metric": "fleet_storm",
        **_bench_stamp(),
        "clients": n_clients,
        "workers": workers,
        "requests": {"served": served, "ok": shared["ok"],
                     "shed": shared["shed"],
                     "rpc_error": shared["rpc_error"],
                     "transport_error": shared["transport_error"]},
        "latency_ms": {"p50": pctl(0.50), "p95": pctl(0.95), "p99": p99},
        "p99_bar_ms": p99_bar_ms,
        "p99_ok": p99_ok,
        "events": events,
        "gateway": {"admitted": stats["admitted"],
                    "sheds": stats["sheds"],
                    "coalesce_hits": stats["coalesce_hits"],
                    "failovers": stats["failovers"],
                    "validator_fallback": stats["validator_fallback"],
                    "rotations_out": stats["pool"]["rotations_out"]},
        "distinct_tips_replied": len(shared["tips"]),
        "inconsistent_replies": inconsistent,
        "chainstate_identical": identical_chainstate,
        "wall_s": round(time.monotonic() - t_run0, 3),
        "note": "gateway front door over 2 snapshot-bootstrapped "
                "replicas: seeded client fleet holds the p99 bar while "
                "a forkfeeder fork storm reorgs the validator and a "
                "chaos kill -9 takes a replica out and back mid-run; "
                "every replied tip verified against the validator's "
                "block index, chainstate digests compared at quiesce",
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r16.json"), "w") as fh:
        json.dump(result, fh, indent=1)
        fh.write("\n")
    emit("fleet_storm_p99", p99, "ms", round(p99_bar_ms / max(p99, 1e-3), 3),
         **{k: v for k, v in result.items() if k != "metric"})
    return {"fleet_p99_ms": p99,
            "fleet_inconsistent_replies": inconsistent,
            "fleet_chainstate_identical": identical_chainstate}


def _forge_epoch_cert(snap_path: str, forge_height: int) -> None:
    """Offline equivalent of the ``snapshot_cert`` poison-output drill:
    flip one bit in the committed digest of the checkpoint at
    ``forge_height`` and RE-SEAL the commitment chain over the forged
    trajectory — structurally valid at load, content-forged, caught only
    by the shadow validator's epoch tripwire."""
    from bitcoincashplus_tpu.store import certificate as cert_mod

    cert_file = os.path.join(snap_path, cert_mod.CERT_NAME)
    with open(cert_file) as f:
        cert = json.load(f)
    for ep in cert["epochs"]:
        if ep["height"] == forge_height:
            raw = bytearray(bytes.fromhex(ep["muhash"]))
            raw[0] ^= 0x01
            ep["muhash"] = bytes(raw).hex()
            break
    else:
        raise RuntimeError(f"no checkpoint at height {forge_height}")
    cert["commitment"] = cert_mod.commitment_chain(
        bytes.fromhex(cert["mmr_root"]), cert["height"],
        cert["epoch_blocks"], cert["epochs"]).hex()
    with open(cert_file, "w") as f:
        json.dump(cert, f)


def bench_snapshot_cert():
    """ISSUE 17 acceptance harness, three legs. (a) Store-level at 10^6
    coins: certificate build time at dump and verify-at-load time
    against the bar "seconds, not minutes" (the alternative this
    replaces is hours of blind shadow re-validation). (b) Node-level
    over real bcpd processes: honest full shadow re-validation vs
    -snapshotspotcheck onboarding wall-clock (byte-identical final
    digests asserted) vs forged-epoch detection latency (the hard abort
    at the first divergent checkpoint). (c) Fleet: gateway p99 over a
    3-node pool while one replica sits quarantined on a cert-less
    snapshot, zero inconsistent replies. Writes BENCH_r17.json
    (schema_version=2 host stamp)."""
    import base64
    import shutil
    import struct
    import tempfile
    import threading
    from concurrent.futures import ThreadPoolExecutor

    from bitcoincashplus_tpu.crypto.hashes import sha256d
    from bitcoincashplus_tpu.store import certificate as cert_mod
    from bitcoincashplus_tpu.store import snapshot as snapshot_mod
    from bitcoincashplus_tpu.store.sharded import ShardedCoinsDB

    n_coins = int(os.environ.get("BCP_BENCH_CERT_COINS", "1000000"))
    height = int(os.environ.get("BCP_BENCH_CERT_HEIGHT", "2048"))
    epoch = int(os.environ.get("BCP_BENCH_CERT_EPOCH", "64"))
    verify_bar_s = float(os.environ.get("BCP_BENCH_CERT_VERIFY_BAR_S", "60"))
    p99_bar_ms = float(os.environ.get("BCP_BENCH_CERT_P99_MS", "2500"))
    result = {"metric": "snapshot_cert", **_bench_stamp()}

    # -- leg (a): certificate algebra at the million-coin scale --------
    workdir = tempfile.mkdtemp(prefix="bcp_cert_bench_")
    try:
        db = ShardedCoinsDB(os.path.join(workdir, "src"), n_shards=4)
        best = b"\x17" * 32
        chunk = 50_000
        t0 = time.perf_counter()
        for lo in range(0, n_coins, chunk):
            db.batch_write_serialized(
                [(_utxo_key(i), _utxo_coin(i))
                 for i in range(lo, min(lo + chunk, n_coins))], best)
        seed_s = time.perf_counter() - t0
        headers = [sha256d(struct.pack("<I", i)) * 3
                   for i in range(height + 1)]
        headers = [h[:80] for h in headers]
        header_hashes = [sha256d(h) for h in headers]

        def deltas():
            # every coin created, none spent: coin i belongs to block
            # (i % height) + 1, walked tip -> 1 as the builder requires
            for h in range(height, 0, -1):
                yield (h, [(_utxo_key(i), _utxo_coin(i))
                           for i in range(h - 1, n_coins, height)], [])

        t0 = time.perf_counter()
        cert = cert_mod.build_certificate(
            header_hashes, height, epoch, db.muhash_state(), deltas())
        build_s = time.perf_counter() - t0
        snap = os.path.join(workdir, "snap")
        t0 = time.perf_counter()
        snapshot_mod.dump_snapshot(db, snap, headers, height, best,
                                   "regtest", certificate=cert)
        dump_s = time.perf_counter() - t0
        digest = db.muhash_digest()
        db.close()

        # the verify the loader runs BEFORE streaming a single row
        t0 = time.perf_counter()
        cps = cert_mod.verify_certificate(cert, header_hashes, height,
                                          digest.hex())
        verify_cert_s = time.perf_counter() - t0
        assert len(cps) == len(cert["epochs"])
        dst = ShardedCoinsDB(os.path.join(workdir, "dst"), n_shards=4)
        t0 = time.perf_counter()
        info = snapshot_mod.load_snapshot(snap, dst, "regtest",
                                          expected_hash=best,
                                          expected_digest=digest)
        load_s = time.perf_counter() - t0
        assert info["cert_checkpoints"]
        assert dst.muhash_digest() == digest  # byte-identical honest path
        dst.close()
        assert verify_cert_s < verify_bar_s, (
            f"verify-at-load {verify_cert_s:.1f}s breaks the "
            f"'seconds, not minutes' bar ({verify_bar_s}s)")
        result["algebra"] = {
            "coins": n_coins, "height": height, "epoch_blocks": epoch,
            "epochs": len(cert["epochs"]),
            "seed_s": round(seed_s, 3),
            "cert_build_s": round(build_s, 3),
            "dump_s": round(dump_s, 3),
            "verify_at_load_s": round(verify_cert_s, 4),
            "verify_bar_s": verify_bar_s,
            "certified_load_s": round(load_s, 3),
            "cert_overhead_pct": round(100 * verify_cert_s / load_s, 2),
        }
        emit("snapshot_cert_verify_at_load", round(verify_cert_s, 4), "s",
             round(verify_bar_s / max(verify_cert_s, 1e-6), 1),
             coins=n_coins, headers=height + 1)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    # -- legs (b) + (c): real bcpd processes ---------------------------
    fw = _load_functional_framework()
    from bitcoincashplus_tpu.consensus.params import regtest_params
    from bitcoincashplus_tpu.wallet.keys import CKey

    mature = int(os.environ.get("BCP_BENCH_CERT_MATURE", "120"))
    spend_blocks = int(os.environ.get("BCP_BENCH_CERT_SPEND_BLOCKS", "16"))
    tx_per_block = int(os.environ.get("BCP_BENCH_CERT_TX_PER_BLOCK", "6"))
    tail_blocks = int(os.environ.get("BCP_BENCH_CERT_TAIL", "24"))
    node_epoch = 16
    chain_h = mature + spend_blocks + tail_blocks

    f = fw.FunctionalFramework(
        num_nodes=2, extra_args=[[f"-snapshotepoch={node_epoch}"], []])
    with f:
        a, b = f.nodes
        waddr = a.rpc.getnewaddress()
        a.rpc.generatetoaddress(mature, waddr)
        # spend blocks live in MIDDLE epochs (the tail keeps them out of
        # the always-sampled final checkpoint): spot-check onboarding
        # skips their script verification, full re-validation pays it
        for _ in range(spend_blocks):
            for _ in range(tx_per_block):
                a.rpc.sendtoaddress(waddr, 0.05)
            a.rpc.generatetoaddress(1, waddr)
        a.rpc.generatetoaddress(tail_blocks, waddr)
        assert a.rpc.getblockcount() == chain_h
        snap_path = os.path.join(a.datadir, "cert-bench-snapshot")
        dump = a.rpc.dumptxoutset(snap_path)
        assert dump["certified"] is True
        forged = os.path.join(a.datadir, "cert-bench-forged")
        shutil.copytree(snap_path, forged)
        forge_at = (chain_h // node_epoch // 2) * node_epoch
        _forge_epoch_cert(forged, forge_at)
        auth_arg = f"-assumeutxo={dump['bestblock']}:{dump['muhash']}"

        def onboard(path, extra, wait_dead=False):
            """Fresh-datadir onboarding; returns wall seconds from the
            P2P connect to validated (or, for the forged run, to the
            node's hard abort)."""
            b.stop()
            shutil.rmtree(b.datadir, ignore_errors=True)
            b.extra_args = [arg for arg in b.extra_args
                            if not arg.startswith(("-assumeutxo",
                                                   "-snapshotspotcheck",
                                                   "-netseed"))]
            b.extra_args += [auth_arg] + extra
            b.start()
            b.rpc.loadtxoutset(path)
            t0 = time.monotonic()
            fw.connect_nodes(b, a)
            if wait_dead:
                fw.wait_until(lambda: b.process.poll() is not None,
                              timeout=600, sleep=0.2)
            else:
                fw.wait_until(
                    lambda: b.rpc.gettpuinfo()["store"]["snapshot"]
                    ["validated"], timeout=600, sleep=0.2)
            return time.monotonic() - t0

        full_s = onboard(snap_path, [])
        digest_full = b.rpc.gettxoutsetinfo()["muhash"]
        spot_s = onboard(snap_path, ["-snapshotspotcheck=1", "-netseed=17"])
        digest_spot = b.rpc.gettxoutsetinfo()["muhash"]
        detect_s = onboard(forged, [], wait_dead=True)
        with open(os.path.join(b.datadir, "debug.log")) as fh:
            log = fh.read()
        assert "EPOCH DIGEST DIVERGENCE" in log
        assert f"checkpoint {forge_at}" in log
        b.process = None  # the corpse is the result; don't re-stop it
        digest_a = a.rpc.gettxoutsetinfo()["muhash"]

    assert digest_full == digest_spot == digest_a, \
        "onboarded chainstate digests diverged from the validator"
    assert spot_s < full_s, (
        f"spot-check onboarding ({spot_s:.1f}s) did not beat full shadow "
        f"re-validation ({full_s:.1f}s)")
    # the O(epoch) detection-latency claim is proven STRUCTURALLY above
    # (divergence logged at the forged mid-chain checkpoint, never the
    # final one); at regtest scale the wall-clock gap sits inside
    # connect/backfill fixture noise, so only gate on gross regression
    assert detect_s < full_s * 1.5, (
        f"forged-epoch detection ({detect_s:.1f}s) took >1.5x the full "
        f"re-validation window ({full_s:.1f}s)")
    result["onboarding"] = {
        "chain_height": chain_h, "epoch_blocks": node_epoch,
        "spend_txs": spend_blocks * tx_per_block,
        "full_validation_s": round(full_s, 3),
        "spotcheck_validation_s": round(spot_s, 3),
        "spotcheck_speedup": round(full_s / spot_s, 3),
        "forged_epoch_height": forge_at,
        "forged_detect_s": round(detect_s, 3),
        "detect_vs_full": round(detect_s / full_s, 3),
        "digests_identical": True,
    }

    # -- leg (c): fleet-quarantine drill p99 ---------------------------
    reads = int(os.environ.get("BCP_BENCH_CERT_READS", "400"))
    workers = int(os.environ.get("BCP_BENCH_CERT_WORKERS", "8"))
    fleet_h = 16
    addr = CKey(0x17BE7).p2pkh_address(regtest_params())
    f = fw.FunctionalFramework(num_nodes=3)
    fw.setup_fleet(f)
    with f:
        validator, r1, r2 = f.nodes
        r2_name = f"127.0.0.1:{r2.rpc_port}"
        gw_port = validator.gateway_port
        auth = base64.b64encode(
            f"{fw.FLEET_USER}:{fw.FLEET_PASSWORD}".encode()).decode()
        validator.rpc.generatetoaddress(fleet_h, addr)
        snap = os.path.join(validator.datadir, "fleet-cert-snapshot")
        dump = validator.rpc.dumptxoutset(snap)
        nocert = os.path.join(validator.datadir, "fleet-nocert-snapshot")
        shutil.copytree(snap, nocert)
        os.remove(os.path.join(nocert, "CERTIFICATE.json"))

        fw.bootstrap_replica_from_snapshot(r1, validator, snap, dump)
        # r2: cert-less, disconnected — the poisoned replica stand-in
        # that can never flip certificate_verified during the drill
        r2.stop()
        r2.extra_args.append(
            f"-assumeutxo={dump['bestblock']}:{dump['muhash']}")
        r2.start()
        r2.rpc.loadtxoutset(nocert)

        def pool_doc():
            return validator.rpc.gettpuinfo()["gateway"]["pool"]

        fw.wait_until(
            lambda: any(r["name"] == r2_name and r["quarantined"]
                        for r in pool_doc()["replicas"]), timeout=60)
        tip = validator.rpc.getbestblockhash()
        lat: list = []
        tips: set = set()
        lock = threading.Lock()

        def worker(w):
            box = [None]
            local = []
            seen = set()
            for k in range(reads // workers):
                kind, payload, dt = _gw_request(
                    box, gw_port, auth, f"q{w}", "getbestblockhash", [])
                if kind == "ok":
                    local.append(dt)
                    seen.add(payload)
            with lock:
                lat.extend(local)
                tips.update(seen)

        with ThreadPoolExecutor(max_workers=workers) as ex:
            list(ex.map(worker, range(workers)))
        pool = pool_doc()
        by_name = {r["name"]: r for r in pool["replicas"]}
        assert by_name[r2_name]["quarantined"], \
            "the cert-less replica left quarantine mid-drill"
        assert tips == {tip}, f"inconsistent replies: {len(tips)} tips"
        quarantines = pool["quarantines"]

    lat.sort()
    p99 = round(lat[int(0.99 * (len(lat) - 1))] * 1e3, 2)
    assert p99 <= p99_bar_ms, \
        f"quarantine-drill p99 {p99} ms over the {p99_bar_ms} ms bar"
    result["fleet_quarantine"] = {
        "reads": len(lat),
        "latency_ms": {
            "p50": round(lat[len(lat) // 2] * 1e3, 2),
            "p99": p99,
        },
        "p99_bar_ms": p99_bar_ms,
        "p99_ok": True,
        "quarantines": quarantines,
        "inconsistent_replies": 0,
    }
    result["note"] = (
        "proof-carrying snapshots: million-coin certificate built at "
        "dump and verified at load in seconds (vs hours of blind shadow "
        "re-validation); node-level spot-check onboarding beats full "
        "re-validation with byte-identical digests; forged epoch "
        "hard-aborts at the divergent checkpoint; gateway p99 holds "
        "while a cert-less replica sits quarantined")
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r17.json"), "w") as fh:
        json.dump(result, fh, indent=1)
        fh.write("\n")
    emit("snapshot_cert_spotcheck_speedup",
         result["onboarding"]["spotcheck_speedup"], "x",
         result["onboarding"]["spotcheck_speedup"],
         **{k: v for k, v in result.items() if k != "metric"})
    return {
        "snapcert_verify_at_load_s": result["algebra"]["verify_at_load_s"],
        "snapcert_spotcheck_speedup":
            result["onboarding"]["spotcheck_speedup"],
        "snapcert_quarantine_p99_ms": p99,
    }


def _device_reachable(timeout_s: int = 180) -> bool:
    """Guard against a backend init that hangs inside C code, where
    neither signals nor KeyboardInterrupt land: probe from a killable
    subprocess (which exits, releasing the device, before this process
    touches jax backends)."""
    try:
        probe = subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices(); print('ok')"],
            capture_output=True, text=True, timeout=timeout_s,
        )
        return probe.returncode == 0 and "ok" in probe.stdout
    except subprocess.TimeoutExpired:
        return False


def bench_schnorr_msm():
    """ISSUE 19: Schnorr batch verification — Pippenger MSM batch check
    vs the per-lane ladder, with the batch-vs-ladder crossover curve.

    For each batch size N the same records run through (a) the per-lane
    CPU oracle (the reference engine and the accept/reject oracle the
    batch path must match byte-identically) and (b) the full MSM dispatch
    (canary batches, host pack, one device batch equation). Sizes map to
    MSM buckets 64/64/256 by default — the bucket-1024 rung is a
    many-minute XLA compile on a CPU backend, opt in via
    BCP_BENCH_MSM_SIZES. Writes BENCH_r19.json (schema 2 + host stamp)."""
    import hashlib

    from bitcoincashplus_tpu.crypto import secp256k1 as oracle
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord
    from bitcoincashplus_tpu.util import devicewatch as dwatch

    # bucket compiles are minutes cold on the XLA CPU backend — share
    # the persistent cache the test suite / dispatch_breakdown use
    dwatch.enable_compile_cache()

    sizes = [int(x) for x in os.environ.get(
        "BCP_BENCH_MSM_SIZES", "8,31,127").split(",") if x.strip()]

    def srec(i):
        d = 0xB00 + i
        e = int.from_bytes(hashlib.sha256(b"bench%d" % i).digest(),
                           "big") % oracle.N
        r, s = oracle.schnorr_sign(d, e)
        return SigCheckRecord(oracle.point_mul(d, oracle.G), r, s, e,
                              algo="schnorr")

    curve = []
    crossover = None
    for n in sizes:
        recs = [srec(i) for i in range(n)]
        expect = [oracle.schnorr_verify(r.pubkey, r.r, r.s, r.msg_hash)
                  for r in recs]

        def run_msm():
            out = eb.dispatch_batch(
                recs, backend="device", kernel="msm").result()
            assert out.tolist() == expect, "msm verdicts diverged"
            return out

        run_msm()  # warm: pay the bucket's XLA compile outside timing
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            run_msm()
            ts.append(time.perf_counter() - t0)
        msm_s = sorted(ts)[1]

        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            out = eb.dispatch_batch(recs, backend="cpu").result()
            ts.append(time.perf_counter() - t0)
            assert out.tolist() == expect
        lad_s = sorted(ts)[1]

        point = {
            "batch_sigs": n,
            "msm_bucket": eb._msm_bucket_for(2 * n + 1),
            "msm_sigs_per_s": round(n / msm_s, 1),
            "ladder_sigs_per_s": round(n / lad_s, 1),
            "msm_speedup": round(lad_s / msm_s, 3),
        }
        curve.append(point)
        if crossover is None and msm_s < lad_s:
            crossover = n
        emit("schnorr_msm_sigs_per_s", point["msm_sigs_per_s"], "sigs/s",
             point["msm_speedup"], batch=n)

    result = {
        "metric": "schnorr_msm_crossover",
        **_bench_stamp(),
        "curve": curve,
        "crossover_batch_sigs": crossover,
        "msm_seeded": "BCP_MSM_SEED" in os.environ,
        "note": "per-dispatch cost includes the 2 canary batches + host "
                "pack + challenge hashing; the ladder column is the "
                "per-lane Python-int oracle (the byte-identical "
                "accept/reject reference). Crossover = smallest measured "
                "batch where the MSM dispatch beats the ladder; "
                "-ecdsakernel=msm routes Schnorr lanes through it while "
                "ECDSA lanes keep riding glv.",
    }
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BENCH_r19.json"), "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    best = max(curve, key=lambda p: p["msm_speedup"]) if curve else {}
    return {"schnorr_msm_crossover_sigs": crossover,
            "schnorr_msm_best_speedup": best.get("msm_speedup")}


def main():
    if not _device_reachable():
        sys.exit("bench.py: no JAX device answered (backend init failed "
                 "or timed out); nothing was measured")
    on_cpu = jax.default_backend() == "cpu"
    recap = {}
    recap.update(bench_header_hash() or {})
    recap.update(bench_merkle() or {})
    device_sps = None
    if not on_cpu:
        # device kernel; CPU fallback would not be news
        device_sps = bench_ecdsa_batch()
    recap["ecdsa_sigs_per_s"] = round(device_sps) if device_sps else None
    recap.update(bench_reindex(device_sps) or {})  # config 6: north star
    recap.update(bench_import_pipeline() or {})  # ISSUE 4: settle horizon
    recap.update(bench_fork_storm() or {})  # ISSUE 9: speculation tree
    try:
        recap.update(bench_mining() or {})  # ISSUE 10: resident loop
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("mining_resident_speedup", -1, "x", 0.0,
             error=f"{type(e).__name__}: {e}")
    try:
        recap.update(bench_utxo_store() or {})  # ISSUE 13: sharded store
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("utxo_store_flush_speedup_4v1", -1, "x", 0.0,
             error=f"{type(e).__name__}: {e}")
    try:
        recap.update(bench_mempool_storm() or {})  # ISSUE 20: flood pool
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("mempool_storm_batched_speedup", -1, "x", 0.0,
             error=f"{type(e).__name__}: {e}")
    recap.update(bench_telemetry_overhead() or {})  # ISSUE 6: < 2% budget
    recap.update(bench_serving() or {})  # ISSUE 7: serviced >= 2x sync
    if os.environ.get("BCP_BENCH_FLEET", "1") != "0":
        try:
            recap.update(bench_fleet() or {})  # ISSUE 16: front door
        except Exception as e:  # pragma: no cover - diagnostics only
            emit("fleet_storm_p99", -1, "ms", 0.0,
                 error=f"{type(e).__name__}: {e}")
    if os.environ.get("BCP_BENCH_SNAPCERT", "1") != "0":
        try:
            recap.update(bench_snapshot_cert() or {})  # ISSUE 17: certs
        except Exception as e:  # pragma: no cover - diagnostics only
            emit("snapshot_cert_verify_at_load", -1, "s", 0.0,
                 error=f"{type(e).__name__}: {e}")
    if os.environ.get("BCP_BENCH_MSM", "1") != "0":
        try:
            recap.update(bench_schnorr_msm() or {})  # ISSUE 19: MSM
        except Exception as e:  # pragma: no cover - diagnostics only
            emit("schnorr_msm_sigs_per_s", -1, "sigs/s", 0.0,
                 error=f"{type(e).__name__}: {e}")
    try:
        recap.update(bench_dispatch_breakdown() or {})  # ISSUE 8: phases
    except Exception as e:  # pragma: no cover - diagnostics only
        emit("dispatch_breakdown", -1, "x", 0.0,
             error=f"{type(e).__name__}: {e}")
    recap.update(bench_virtual_shard() or {})
    # compact recap line so every config's headline value survives the
    # driver's 2000-byte tail capture (VERDICT r4 item 5); the true
    # headline still goes LAST (the driver parses the final line)
    emit("summary_recap", 1, "-", 0.0, values=recap)
    bench_sweep_headline()  # headline LAST


if __name__ == "__main__":
    # `python bench.py dispatch_breakdown` / `fork_storm` / `mining` run
    # one section alone (all are also part of the full run)
    if len(sys.argv) > 1 and sys.argv[1] == "dispatch_breakdown":
        bench_dispatch_breakdown()
    elif len(sys.argv) > 1 and sys.argv[1] == "fork_storm":
        bench_fork_storm()
    elif len(sys.argv) > 1 and sys.argv[1] == "mining":
        bench_mining()
    elif len(sys.argv) > 1 and sys.argv[1] == "utxo_store":
        bench_utxo_store()
    elif len(sys.argv) > 1 and sys.argv[1] == "mempool_storm":
        # flood-scale mempool differential + latency bars (ISSUE 20):
        # pure pool mechanics, no device needed
        bench_mempool_storm()
    elif len(sys.argv) > 1 and sys.argv[1] == "fleet":
        # multi-process fleet storm: children force JAX_PLATFORMS=cpu,
        # no device needed in this process either
        bench_fleet()
    elif len(sys.argv) > 1 and sys.argv[1] == "schnorr_msm":
        # Schnorr MSM batch-vs-ladder crossover (ISSUE 19): CPU backend
        # is fine — the MSM program is plain XLA
        bench_schnorr_msm()
    elif len(sys.argv) > 1 and sys.argv[1] == "snapshot_cert":
        # proof-carrying snapshot harness (ISSUE 17): store-level at
        # 10^6 coins plus real-process onboarding/fleet legs on CPU
        bench_snapshot_cert()
    else:
        main()
