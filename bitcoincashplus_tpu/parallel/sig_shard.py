"""Multi-chip ECDSA batch sharding (P1 in SURVEY.md §3.2).

The signature-batch axis is embarrassingly parallel: shard the B lanes of
the w=4 windowed Pallas pipeline (ops/secp256k1._w4_bytes_program) across
the ('chip',) mesh with shard_map. Inputs are the byte matrices ((B, 32)
uint8 per field, ops/ecdsa_batch.pack_lanes) sharded on
the batch axis; each chip expands its shard to window planes / 13-bit limbs
on device and runs the Pallas grid locally; the per-lane validity mask
gathers back over ICI, and a psum'd failure count gives the block-level
verdict without a host round trip. This is the 8-chip scale-out of the
CCheckQueue replacement: the reference's `-par=N` worker threads become mesh
shards.

On CPU meshes (the virtual-8 dryrun — no Mosaic backend) the same
kernel runs in pallas interpret mode, so the sharded program is the real
w4 pipeline everywhere, not a stand-in ladder.

The GLV kernel (ops/secp256k1._glv_dev_planes, -ecdsakernel=glv, the
default) shards the same way via _sharded_glv_dev_jit — plain XLA end to
end, so no interpret split: the fixed-base comb constants replicate per
chip, the inputs are the same raw byte matrices as the w4 pipeline, and
each chip lattice-decomposes its own shard on device.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.secp256k1 import _w4_bytes_program
from .mesh import CHIP_AXIS, chip_mesh, shard_map_nocheck

# per-chip lane granularity: the w4 bytes program reshapes its local batch
# to (8, T) vregs with T a multiple of 128
_CHIP_BUCKET = 1024

# Sharded-MSM program watch (ISSUE 19). Unlike the legacy sig_shard_*
# registrations (mesh-width x load-dependent bucket — counted, baselined
# in tools/bcplint), the MSM shape set IS bounded: per-chip buckets come
# off ops/ecdsa_batch._MSM_BUCKETS (6 rungs) and virtual meshes sweep
# widths {1, 2, 4, 8}, so the signature space is 6 x 4.
from ..util import devicewatch as _dw

_PW_SHARD_MSM = _dw.program("sig_shard_msm", shape_budget=24)


def _use_interpret(n_chips: int) -> bool:
    """Interpret mode iff the mesh's devices are CPUs — NOT the default
    backend: an accelerator plugin can win default-backend selection while
    the virtual mesh is still CPU (tests/conftest.py documents the same
    trap), and Mosaic-vs-interpret must follow where the kernel RUNS."""
    return chip_mesh(n_chips).devices.flat[0].platform == "cpu"


@partial(jax.jit, static_argnames=("n_chips",))
def _sharded_glv_dev_jit(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8,
                         n_chips: int):
    """Sharded decompose+verify GLV program: raw scalar byte matrices
    shard on the batch axis and every chip runs the exact in-kernel
    lattice split over its own lanes — the host ships bytes, never split
    scalars. Plain XLA end to end (no interpret-mode split: the GLV core
    never enters Mosaic, and its fixed-base comb rides as captured XLA
    constants the partitioner replicates per chip).

    The jit + shard_map around _glv_dev_planes inlines its two stages
    into ONE program, so the ladder's loop reads tables the same program
    built: the slow form on the chip (2.02 ms a window against 1.03,
    PERF.md §6, PR 39). Nothing reaches this path from bcpd (ROADMAP S8);
    when something does, it needs two shard_map programs, prepare and
    ladder, with the tables handed over sharded."""
    from ..ops.secp256k1 import _glv_dev_planes

    mesh = chip_mesh(n_chips)
    row = P(CHIP_AXIS)

    def body(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8):
        out = _glv_dev_planes(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8)
        b_local = qxb.shape[0]
        ok = out[0].reshape(b_local).astype(bool)
        degen = out[1].reshape(b_local).astype(bool)
        fails = jax.lax.psum(
            jnp.sum(((~ok | degen) & (qinf8 == 0)).astype(jnp.uint32)),
            CHIP_AXIS,
        )
        return ok, degen, fails

    fn = shard_map_nocheck(
        body,
        mesh,
        in_specs=(row,) * 8,
        out_specs=(P(CHIP_AXIS), P(CHIP_AXIS), P()),
    )
    return fn(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8)


@partial(jax.jit, static_argnames=("n_chips", "interpret"))
def _sharded_w4_jit(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8,
                    n_chips: int, interpret: bool):
    mesh = chip_mesh(n_chips)
    row = P(CHIP_AXIS)  # (B, 32) byte matrices: shard the batch axis

    def body(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8):
        out = _w4_bytes_program(u1m, u2m, qxb, qyb, qinf8, r0b, rnb,
                                wrap8, interpret=interpret)
        b_local = u1m.shape[0]
        ok = out[0].reshape(b_local).astype(bool)
        degen = out[1].reshape(b_local).astype(bool)
        # block verdict: total failures among real (non-poisoned) lanes,
        # reduced over ICI (degenerate lanes settle on host; count them
        # as failures here so the fast verdict stays conservative)
        fails = jax.lax.psum(
            jnp.sum(((~ok | degen) & (qinf8 == 0)).astype(jnp.uint32)),
            CHIP_AXIS,
        )
        return ok, degen, fails

    fn = shard_map_nocheck(
        body,
        mesh,
        in_specs=(row,) * 8,
        out_specs=(P(CHIP_AXIS), P(CHIP_AXIS), P()),
        # pallas_call's out_shape carries no varying-mesh-axes annotation;
        # the specs state the sharding explicitly (check disabled)
    )
    return fn(u1m, u2m, qxb, qyb, qinf8, r0b, rnb, wrap8)


@partial(jax.jit, static_argnames=("n_chips",))
def _sharded_msm_jit(xm, ym, inf8, km, n_chips: int):
    """Sharded Pippenger MSM (ISSUE 19): the TERM axis shards across the
    mesh — MSM is a sum, so it distributes over row shards with no
    cross-chip traffic during accumulation. Each chip runs the full
    bucket-accumulation pipeline (ops/secp256k1._msm_accumulate) over its
    local terms and emits its packed (61, 1) Jacobian partial; the host
    folds n_chips partials with the Python-int oracle (a length-n_chips
    fold of exact point adds — microseconds, and it keeps the
    accept-side completeness argument in one place instead of re-proving
    it for a psum tree of in-field adds)."""
    from ..ops.secp256k1 import _msm_accumulate

    mesh = chip_mesh(n_chips)
    row = P(CHIP_AXIS)

    def body(xm, ym, inf8, km):
        acc = _msm_accumulate(xm, ym, inf8, km)
        return jnp.concatenate(
            [acc["X"], acc["Y"], acc["Z"],
             acc["inf"].astype(jnp.uint32).reshape(1, 1)], axis=0)

    fn = shard_map_nocheck(
        body,
        mesh,
        in_specs=(row, row, row, row),
        out_specs=P(None, CHIP_AXIS),  # (61, n_chips) packed partials
    )
    return fn(xm, ym, inf8, km)


def msm_is_infinity_sharded(terms, n_chips: int) -> bool:
    """Batch-equation check over the mesh: ``terms`` is the host-side
    [(x, y, scalar)] list from the Schnorr batch equation
    (ops/ecdsa_batch builds it); returns True iff Σ kᵢ·Pᵢ is the point
    at infinity. Pads the term count to an MSM bucket per chip so the
    compiled shapes stay on the declared ladder."""
    from ..crypto import secp256k1 as oracle
    from ..ops.ecdsa_batch import _msm_bucket_for, _msm_pack
    from ..ops.secp256k1 import N_LIMBS, from_limbs_np
    from ..util import devicewatch as dw

    per_chip = _msm_bucket_for(
        max(1, (len(terms) + n_chips - 1) // n_chips))
    bucket = per_chip * n_chips
    arrays = [np.asarray(a) for a in _msm_pack(terms, bucket)]
    dw.note_transfer("sig_shard", "h2d",
                     sum(int(a.nbytes) for a in arrays))
    with _PW_SHARD_MSM.dispatch((bucket, n_chips)):
        out = np.asarray(jax.block_until_ready(
            _sharded_msm_jit(*arrays, n_chips=n_chips)))
    # host fold: Jacobian partials -> affine -> oracle point_add chain
    acc = None
    for c in range(n_chips):
        col = out[:, c]
        if col[3 * N_LIMBS]:
            continue  # chip saw only padded lanes
        x = from_limbs_np(col[0:N_LIMBS]) % oracle.P
        y = from_limbs_np(col[N_LIMBS:2 * N_LIMBS]) % oracle.P
        z = from_limbs_np(col[2 * N_LIMBS:3 * N_LIMBS]) % oracle.P
        if z == 0:
            continue
        zi = pow(z, oracle.P - 2, oracle.P)
        pt = ((x * zi * zi) % oracle.P,
              (y * zi * zi * zi) % oracle.P)
        acc = pt if acc is None else oracle.point_add(acc, pt)
    return acc is None


def verify_batch_sharded(records, n_chips: int,
                         kernel: str | None = None) -> np.ndarray:
    """Shard a record batch across the mesh; returns (len(records),) bool.
    Pads B up to n_chips * 1024-lane shards with poisoned lanes; degenerate
    lanes (H == 0 collisions) re-verify on the host scalar path exactly
    like the single-chip dispatch (ops/ecdsa_batch.BatchHandle). ``kernel``
    overrides the -ecdsakernel selection for this call (None = active)."""
    from ..ops import ecdsa_batch
    from ..ops.ecdsa_batch import _verify_cpu
    from ..util import devicewatch as dw

    n = len(records)
    per_chip = max(
        _CHIP_BUCKET,
        ((n + n_chips - 1) // n_chips + _CHIP_BUCKET - 1)
        // _CHIP_BUCKET * _CHIP_BUCKET,
    )
    bucket = per_chip * n_chips
    arrays = ecdsa_batch.pack_lanes(
        *ecdsa_batch.records_to_blobs(records), bucket)
    dw.note_transfer("sig_shard", "h2d",
                     sum(int(a.nbytes) for a in arrays))
    kern = kernel if kernel in ecdsa_batch.ECDSA_KERNELS \
        else ecdsa_batch.active_kernel()
    # mesh-width x bucket is the compiled-shape signature; no budget —
    # virtual meshes legitimately sweep 1/2/4/8
    if kern == "glv" and ecdsa_batch.glv_enabled():
        with dw.program("sig_shard_glv_dev").dispatch((bucket, n_chips)):
            ok, degen, _fails = jax.block_until_ready(
                _sharded_glv_dev_jit(*arrays, n_chips=n_chips)
            )
    else:
        with dw.program("sig_shard_w4").dispatch((bucket, n_chips)):
            ok, degen, _fails = jax.block_until_ready(
                _sharded_w4_jit(*arrays, n_chips=n_chips,
                                interpret=_use_interpret(n_chips))
            )
    out = np.asarray(ok)[:n].copy()
    degen = np.asarray(degen)[:n]
    idxs = np.nonzero(degen)[0]
    if idxs.size:
        from ..ops.ecdsa_batch import STATS

        STATS.degenerate_rechecks += int(idxs.size)
        out[idxs] = _verify_cpu([records[i] for i in idxs])
    return out


def dryrun(n_devices: int) -> None:
    """Driver dryrun leg: one sharded w4 sig-batch dispatch on the virtual
    mesh — one valid and one invalid signature among padded lanes."""
    import random

    from ..crypto import secp256k1 as oracle
    from ..script.interpreter import SigCheckRecord

    rng = random.Random(1)
    recs, expected = [], []
    for i in range(2):
        d = rng.randrange(1, oracle.N)
        pub = oracle.point_mul(d, oracle.G)
        e = rng.randrange(1 << 256)
        r, s = oracle.ecdsa_sign(d, e)
        if i == 1:
            e ^= 1  # corrupt: lane must report False
        recs.append(SigCheckRecord(pub, r, s, e))
        expected.append(oracle.ecdsa_verify(pub, r, s, e))
    from ..ops.ecdsa_batch import active_kernel

    got = verify_batch_sharded(recs, n_devices)
    assert got.tolist() == expected, (got.tolist(), expected)
    print(f"sig_shard dryrun: {n_devices}-chip sharded "
          f"{active_kernel()} sig batch OK")
