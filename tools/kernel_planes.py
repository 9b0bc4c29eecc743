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
        glv.append(np.asarray(dev._glv_dev_program(*arrays)))
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
    args = ap.parse_args()
    if args.cmd == "run":
        return _run(args.tree, args.out, args.buckets, args.seed, args.lanes)
    return _compare(args.a, args.b)


if __name__ == "__main__":
    sys.exit(main())
