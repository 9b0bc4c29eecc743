"""The verify kernels' raw verdict planes over one fixed, seeded input set.

A kernel PR (ROADMAP S2) must leave every lane's `ok` and `degen` bit where
its parent had it. Run this on the chip once a tree and compare the files:

    python tools/kernel_planes.py run --tree .parent --out chiprun_out/a.npz
    python tools/kernel_planes.py run --tree . --out chiprun_out/b.npz
    python tools/kernel_planes.py compare chiprun_out/a.npz chiprun_out/b.npz

One process a tree (a module's jits belong to the tree that imported it).
Inputs: tests/unit/test_glv.py's edge corpus, then RFC 6979 signatures by
64 seeded keys over seeded messages, one lane in seven with its message
nudged and one in 97 carrying another lane's r. `_glv_dev_program` runs
the corpus in its first 8,192-lane bucket and ``--buckets`` more of seeded
lanes; `_w4_bytes_program` runs one 1,024-lane bucket (corpus first).
`run` also holds `ok` to the CPU verifier; `compare` exits 1 on any
difference in inputs or planes.

    python tools/kernel_planes.py window [--lanes 8192] [--no-profile]

times the ladder's 32-window loop on the device in the forms PR 33 probed
and PR 39 read: the loop as a program of its own with its tables handed in
(`args`), the loop behind `_glv_q_tables` in one program (`tables-inside`),
prepare, ladder, comb and final as ONE program (`one-program`, the form
until PR 39), and the two stages as the node dispatches them (`shipped`).
It prints us a window by the host's clock and, from a profile of each
form, the loop's wall a window against the summed time of the operations
inside it (the rest is gaps between launches), launches a window and the
operation classes that take the most time. A tool: no cell runs it.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.util
import os
import random
import sys

import numpy as np

W4_LANES = 1024


def _seeded_records(rng: random.Random, count: int) -> list:
    from bitcoincashplus_tpu import native
    from bitcoincashplus_tpu.crypto import secp256k1 as oracle
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord

    keys = [rng.randrange(1, oracle.N) for _ in range(64)]
    pubs = [oracle.point_mul(d, oracle.G) for d in keys]
    sign = native.ecdsa_sign if native.available() else oracle.ecdsa_sign
    out = []
    for i in range(count):
        k = rng.randrange(len(keys))
        e = rng.getrandbits(256)
        r, s = sign(keys[k], e)
        if i % 7 == 3:
            e ^= 1
        if i % 97 == 5 and out:
            r = out[-1].r
        out.append(SigCheckRecord(pubs[k], r, s, e))
    return out


def _run(tree: str, out: str, buckets: int, seed: int, lanes: int) -> int:
    sys.path.insert(0, os.path.abspath(tree))
    import jax

    from bitcoincashplus_tpu.ops import ecdsa_batch
    from bitcoincashplus_tpu.ops import secp256k1 as dev
    from bitcoincashplus_tpu.util import devicewatch

    devicewatch.enable_compile_cache()  # the node's: one compile serves both
    spec = importlib.util.spec_from_file_location(
        "test_glv", os.path.join(tree, "tests", "unit", "test_glv.py"))
    test_glv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(test_glv)
    # the (2, B) planes: one program until PR 39, two stages since
    planes = getattr(dev, "_glv_dev_planes", None) or dev._glv_dev_program
    rng = random.Random(seed)
    edge = [r for r, _ in test_glv._edge_corpus()]
    fill = lanes - 2  # the node's slices leave two lanes to the KAT
    sets = [edge + _seeded_records(rng, fill - len(edge))]
    sets += [_seeded_records(rng, fill) for _ in range(buckets)]
    digest = hashlib.sha256()
    glv, cpu = [], []
    for records in sets:
        blobs = ecdsa_batch.records_to_blobs(records)
        arrays = ecdsa_batch.pack_lanes(*blobs, lanes)
        for a in arrays:
            digest.update(np.ascontiguousarray(a).tobytes())
        glv.append(np.asarray(planes(*arrays)))
        want = np.zeros(lanes, bool)
        want[:len(records)] = ecdsa_batch._verify_cpu_ecdsa(records)
        cpu.append(want)
    glv, cpu = np.stack(glv), np.stack(cpu)
    w4_fill = min(W4_LANES, lanes) - 2
    blobs = ecdsa_batch.records_to_blobs(sets[0][:w4_fill])
    arrays = ecdsa_batch.pack_lanes(*blobs, W4_LANES)
    w4 = np.asarray(dev._w4_bytes_program(
        *arrays, interpret=ecdsa_batch._interpret_kernels()))
    w4 = w4.reshape(2, W4_LANES)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    np.savez(out, glv=glv, w4=w4, cpu=cpu,
             inputs=np.frombuffer(digest.digest(), np.uint8))
    settled = glv[:, 1] == 0
    wrong = int((settled & ((glv[:, 0] != 0) != cpu)).sum())
    want = np.zeros(W4_LANES, bool)
    want[:w4_fill] = cpu[0, :w4_fill]  # the lanes after them are padding
    wrong += int(((w4[1] == 0) & ((w4[0] != 0) != want)).sum())
    print(f"{os.path.dirname(dev.__file__)}: "
          f"{jax.devices()[0].device_kind}, glv {glv.shape[0]} x "
          f"{lanes} lanes: ok {int(glv[:, 0].sum())}, degen "
          f"{int(glv[:, 1].sum())}, cpu ok {int(cpu.sum())}; w4 {W4_LANES} "
          f"lanes: ok {int(w4[0].sum())}, degen {int(w4[1].sum())}; settled "
          f"lanes against the CPU verifier: {wrong} wrong")
    return 1 if wrong else 0


def _compare(a_path: str, b_path: str) -> int:
    a, b = np.load(a_path), np.load(b_path)
    differ = {k: int((a[k] != b[k]).sum()) if a[k].shape == b[k].shape
              else -1 for k in ("inputs", "glv", "w4")}
    print(f"{a_path} against {b_path}: glv {a['glv'].shape}, w4 "
          f"{a['w4'].shape}; differing entries {differ}")
    return 1 if any(differ.values()) else 0


WINDOWS = 32
WINDOW_SEED = 39
WINDOW_REPS = 5  # a form's time by the host's clock is the best of these


def _window_forms(dev, arrays):
    """name -> (callable, arguments): the ladder loop in its four forms.
    Inputs of `args` and `tables-inside` are made here, outside the timed
    calls, by the same stage functions the node runs."""
    import jax
    import jax.numpy as jnp

    prepared = dev._glv_prepare_program(*arrays)
    w1, w2, t1, t2 = prepared[:4]
    q_inf_u = prepared[8]
    qx, qy, ydiff_u = jax.jit(dev._glv_expand)(*arrays)[6:9]

    def tables_inside(w1, w2, qx, qy, ydiff_u, q_inf_u):
        one = jnp.broadcast_to(dev._ONE_CONST, qx.shape).astype(jnp.uint32)
        t1, t2 = dev._glv_q_tables(qx, qy, ydiff_u, q_inf_u, one)
        return dev._glv_ladder(w1, w2, t1, t2, q_inf_u)

    return {
        "args": (jax.jit(dev._glv_ladder), (w1, w2, t1, t2, q_inf_u)),
        "tables-inside": (jax.jit(tables_inside),
                          (w1, w2, qx, qy, ydiff_u, q_inf_u)),
        # nested jits inline: both stages as one program, the form until
        # PR 39
        "one-program": (jax.jit(dev._glv_dev_planes), tuple(arrays)),
        "shipped": (dev._glv_dev_planes, tuple(arrays)),
    }


def _ladder_in_trace(planes: list) -> dict:
    """The ladder's loop in one traced execution: the longest `while` of
    the device's operations line, and the operations that ran inside it
    (an inner loop's own event left out, its operations kept)."""
    from chipbench import xplane

    device = next(p for p in planes if xplane.DEVICE_PLANE.match(p["name"]))
    ops = next(ln["events"] for ln in device["lines"]
               if ln["name"] == xplane.OPS_LINE)
    loops = [ev for ev in ops if xplane.op_name(ev[0]).startswith("%while")]
    loop = max(loops, key=lambda ev: ev[2])
    start, end = loop[1], loop[1] + loop[2]
    inside = [ev for ev in ops if start <= ev[1] and ev[1] + ev[2] <= end
              and not xplane.op_name(ev[0]).startswith("%while")]
    busy = sum(b - a for a, b in xplane.union(
        [(s, s + d) for _, s, d in inside if d > 0]))
    classes: dict = {}
    for name, _, dur in inside:
        stem = xplane.op_name(name).rstrip("0123456789.")
        entry = classes.setdefault(stem, [0, 0])
        entry[0] += 1
        entry[1] += dur
    durs = sorted(d for _, _, d in inside)
    return {
        "loop": xplane.op_name(loop[0]),
        "wall_us": loop[2] / WINDOWS / 1e3,
        "sum_us": sum(durs) / WINDOWS / 1e3,
        "gap_us": (loop[2] - busy) / WINDOWS / 1e3,
        "launches": len(inside) / WINDOWS,
        "median_ns": durs[len(durs) // 2], "p90_ns": durs[len(durs) * 9 // 10],
        "classes": sorted(((stem, n / WINDOWS, ns / WINDOWS / 1e3)
                           for stem, (n, ns) in classes.items()),
                          key=lambda c: -c[2])[:6],
        "modules_ms": {name: 1e3 * m["seconds"] / m["count"] for name, m
                       in xplane.reduce(planes)["modules"].items()},
    }


def _traced_line(read: dict) -> str:
    top = ", ".join(f"{stem} {n:.0f} x {us / n * 1e3:.0f} ns"
                    for stem, n, us in read["classes"])
    mods = ", ".join(f"{m} {ms:.3f} ms"
                     for m, ms in read["modules_ms"].items())
    return (f"; traced: {read['loop']} {read['wall_us']:.1f} us a window, "
            f"operations inside {read['sum_us']:.1f} us "
            f"({read['launches']:.0f} launches, median {read['median_ns']} "
            f"ns, p90 {read['p90_ns']} ns), gaps {read['gap_us']:.1f} us; "
            f"{mods}; most time: {top}")


def _window(lanes: int, profile: bool) -> int:
    import tempfile
    import time

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    from bitcoincashplus_tpu.ops import ecdsa_batch
    from bitcoincashplus_tpu.ops import secp256k1 as dev
    from bitcoincashplus_tpu.util import devicewatch
    from chipbench import xplane

    devicewatch.enable_compile_cache()
    records = _seeded_records(random.Random(WINDOW_SEED), lanes - 2)
    arrays = [jax.device_put(a) for a in ecdsa_batch.pack_lanes(
        *ecdsa_batch.records_to_blobs(records), lanes)]
    print(f"{jax.devices()[0].device_kind}, {lanes} lanes, {WINDOWS} "
          f"windows a loop, best of {WINDOW_REPS}")
    for name, (fn, args) in _window_forms(dev, arrays).items():
        jax.block_until_ready(fn(*args))  # compile
        best = float("inf")
        for _ in range(WINDOW_REPS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        line = f"{name:14s} {best * 1e3:8.3f} ms a call"
        if name == "args":
            line += f" = {best * 1e6 / WINDOWS:7.1f} us a window"
        if profile:
            with tempfile.TemporaryDirectory() as logdir:
                with jax.profiler.trace(logdir):
                    jax.block_until_ready(fn(*args))
                planes = xplane.load(xplane.find_xplane(logdir))
            if any(xplane.DEVICE_PLANE.match(p["name"]) for p in planes):
                line += _traced_line(_ladder_in_trace(planes))
        print(line, flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    run = sub.add_parser("run")
    run.add_argument("--tree", default=".")
    run.add_argument("--out", required=True)
    run.add_argument("--buckets", type=int, default=8)
    run.add_argument("--seed", type=int, default=33)
    run.add_argument("--lanes", type=int, default=8192,
                     help="the GLV bucket (1024 rehearses on a CPU)")
    cmp_ = sub.add_parser("compare")
    cmp_.add_argument("a")
    cmp_.add_argument("b")
    win = sub.add_parser("window")
    win.add_argument("--lanes", type=int, default=8192,
                     help="2048 and 1024 are the shipped default's tails")
    win.add_argument("--no-profile", action="store_true",
                     help="host clock only (a CPU has no device trace)")
    args = ap.parse_args()
    if args.cmd == "run":
        return _run(args.tree, args.out, args.buckets, args.seed, args.lanes)
    if args.cmd == "window":
        return _window(args.lanes, not args.no_profile)
    return _compare(args.a, args.b)


if __name__ == "__main__":
    sys.exit(main())
