"""SHA-256d roofline evidence generator (see ROOFLINE.md for the analysis).

Three measurements, run on the real chip (TPU v5e):

  1. op census   — count the (tile,)-shaped vector ops per nonce in the
                   traced kernels (jaxpr walk). This is the op count the VPU
                   actually executes; scalar/host-folded work is excluded.
  2. op probe    — sustained u32 elementwise throughput on dependency
                   chains of SHA-like op mixes, measured MARGINALLY (two
                   loop lengths, delta-work / delta-time) so the fixed
                   per-dispatch cost cancels out.
  3. achieved    — the tuned Pallas sweep's GH/s, converted to executed
                   vector-ops/s via the census.

Peak reference: v5e TensorCore VPU = (8,128) lanes x 4 ALUs; clock derived
from the published 197.4 Tbf16FLOP/s over 4 MXUs of 128x128 MACs
(= 1.506 GHz) -> 6.17e12 u32 op/s theoretical ceiling.

Usage: python tools/roofline.py   (needs the TPU; ~3 min)
"""

from __future__ import annotations

import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# --ecdsa: trace-only ECDSA vector-op census (w4 vs GLV kernels) — no
# device needed, and the accelerator plugin must not wedge a CPU-only
# tool run, so pin the backend BEFORE jax imports. BCP_SECP_PARALLEL=1
# traces the parallel field forms — the ops the device VPU executes —
# rather than the CPU backend's compile-friendly scan forms.
# --mining: sweep-kernel census (generic vs chunk-2-hoisted, ISSUE 10)
# plus the live compiled-flops drift check of the resident miner program;
# CPU-pinned the same way.
# --schnorr: the same census of the Schnorr bucket's two programs (PR 44),
# the count chipbench/opcounts_schnorr.json keeps.
ECDSA_MODE = "--ecdsa" in sys.argv
MINING_MODE = "--mining" in sys.argv
SCHNORR_MODE = "--schnorr" in sys.argv
if ECDSA_MODE or MINING_MODE or SCHNORR_MODE:
    os.environ["JAX_PLATFORMS"] = "cpu"
if ECDSA_MODE or SCHNORR_MODE:
    os.environ["BCP_SECP_PARALLEL"] = "1"

import jax
import jax.numpy as jnp
import numpy as np

from bitcoincashplus_tpu.crypto.hashes import header_midstate
from bitcoincashplus_tpu.ops import sha256 as gen
from bitcoincashplus_tpu.ops.sha256 import bswap32, bytes_to_words_np
from bitcoincashplus_tpu.ops.sha256_sweep import sweep_h7

VPU_PEAK_OPS = 8 * 128 * 4 * 1.506e9  # lanes x ALUs x clock = 6.17e12

HEADER = bytes(range(80))
MID = list(np.array(header_midstate(HEADER), dtype=np.uint32))
TAIL = list(bytes_to_words_np(np.frombuffer(HEADER[64:76], np.uint8)))


# ---- 1. vector-op census ----------------------------------------------------

def census(f, *args, tile=1024):
    jaxpr = jax.make_jaxpr(f)(*args)
    counts: dict[str, int] = {}

    def walk(jx):
        for eqn in jx.eqns:
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
            shapes = [v.aval.shape for v in eqn.outvars if hasattr(v.aval, "shape")]
            if any(s and int(np.prod(s)) >= tile for s in shapes):
                counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1

    walk(jaxpr.jaxpr)
    return counts


def run_census():
    from bitcoincashplus_tpu.ops.sha256_sweep import (
        hoist_template,
        sweep_digest_hoisted,
    )

    nonces = jnp.zeros((1024,), jnp.uint32)
    # sweep_h7 routes through hoist_template since ISSUE 10 — this IS the
    # post-hoist h7 count (pre-hoist was 5923; see ROOFLINE.md §8)
    spec = census(lambda n: sweep_h7(MID, TAIL, n), nonces)

    unroll_save = os.environ.get("BCP_SHA_UNROLL")
    os.environ["BCP_SHA_UNROLL"] = "1"

    def generic(n):
        h8 = gen.header_sweep_digest(
            [np.uint32(m) for m in MID], [np.uint32(t) for t in TAIL], n
        )
        return gen.le256(gen.digest_to_limbs(h8), [np.uint32(0)] * 8)

    def hoisted_full(n):
        h8 = sweep_digest_hoisted(hoist_template(MID, TAIL), n)
        return gen.le256(gen.digest_to_limbs(h8), [np.uint32(0)] * 8)

    full = census(generic, nonces)
    hoisted = census(hoisted_full, nonces)
    if unroll_save is None:
        os.environ.pop("BCP_SHA_UNROLL", None)
    else:
        os.environ["BCP_SHA_UNROLL"] = unroll_save
    return sum(spec.values()), sum(full.values()), sum(hoisted.values()), spec


# ---- 2. sustained-op probe --------------------------------------------------

def _rotr(x, n):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


PROBE_MIXES = {
    # naive op counting convention: rotr = 3 ops (2 shifts + or)
    "sigma": (lambda x, c: (_rotr(x, 2) ^ _rotr(x, 13) ^ _rotr(x, 22)) + c, 12),
    "ch": (lambda x, c: ((x & c) ^ (~x & _rotr(x, 6))) + c, 8),
    "addrot": (lambda x, c: (x + c) ^ _rotr(x, 7), 5),
}

PROBE_N = 1 << 20
PROBE_INNER = 256


def _probe_fn(body, outer):
    @jax.jit
    def f(x):
        def o(i, x):
            c0 = i.astype(jnp.uint32) * np.uint32(0x9E3779B9)
            for j in range(PROBE_INNER):
                x = body(x, c0 + np.uint32(j))
            return x
        return jax.lax.fori_loop(0, outer, o, x)[0]
    return f


def _timed(f):
    rng = np.random.default_rng(0)
    _ = int(f(jnp.asarray(rng.integers(0, 2**32, PROBE_N, dtype=np.uint32))))
    ts = []
    for _i in range(3):
        x = jnp.asarray(rng.integers(0, 2**32, PROBE_N, dtype=np.uint32))
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        _ = int(f(x))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[1]


def run_probe():
    out = {}
    for name, (body, ops) in PROBE_MIXES.items():
        t_lo = _timed(_probe_fn(body, 32))
        t_hi = _timed(_probe_fn(body, 288))
        dwork = PROBE_N * PROBE_INNER * (288 - 32) * ops
        out[name] = dwork / (t_hi - t_lo)
    return out


# ---- 3. achieved sweep rate -------------------------------------------------

def run_sweep_rate(sublanes=64, max_tiles=262144):
    from bitcoincashplus_tpu.ops.pallas_sweep import pallas_sweep_jit

    mid = jnp.asarray(np.array(MID, dtype=np.uint32))
    tail = jnp.asarray(np.array(TAIL, dtype=np.uint32))
    t7 = jnp.uint32(0)
    tile = sublanes * 128

    def f(s, n):
        return pallas_sweep_jit(mid, tail, t7, s, n,
                                sublanes=sublanes, max_tiles=max_tiles)

    r = f(jnp.uint32(0), jnp.uint32(1))
    _ = int(r[2])
    rates = []
    for _i in range(4):
        t0 = time.perf_counter()
        out = f(jnp.uint32(random.getrandbits(32)), jnp.uint32(max_tiles))
        tiles = int(out[2])
        rates.append(tiles * tile / (time.perf_counter() - t0))
    return sorted(rates[1:])[len(rates[1:]) // 2]


# ---- ECDSA vector-op census (--ecdsa) ---------------------------------------
#
# Counts the lane-shaped vector ops per verify for the w4 and GLV kernels
# by tracing each kernel PHASE separately (table build, ladder window,
# comb tooth, final check) and scaling by its trip count — the cores run
# their windows under lax.fori_loop, whose body a plain jaxpr walk counts
# once. Same counting convention as the SHA census: only ops whose output
# carries the lane axis; scalar/host work is excluded.

def _lane_ops(B: int, f, *args) -> int:
    """The equations of f's jaxpr, loop and call bodies once each, whose
    output carries the lane axis (at least B elements)."""
    total = 0

    def walk(jx):
        nonlocal total
        for eqn in jx.eqns:
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)
            shapes = [v.aval.shape for v in eqn.outvars
                      if hasattr(v.aval, "shape")]
            if any(s and int(np.prod(s)) >= B for s in shapes):
                total += 1

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return total


def _ecdsa_census_parts(B: int = 128):
    import jax.numpy as jnp

    from bitcoincashplus_tpu.crypto import secp256k1 as orc
    from bitcoincashplus_tpu.ops import secp256k1 as S

    rng = random.Random(9)

    def limbs():
        return jnp.asarray(
            S.pack_batch_np([rng.randrange(orc.P) for _ in range(B)])
        )

    qx, qy, r0, rn = limbs(), limbs(), limbs(), limbs()
    one = jnp.asarray(
        np.broadcast_to(S.to_limbs_np(1).reshape(S.N_LIMBS, 1), (S.N_LIMBS, B))
    ).astype(jnp.uint32)
    q_inf_u = jnp.zeros((1, B), jnp.int32)
    never_inf = jnp.zeros((1, B), jnp.int32)
    wrap2 = jnp.zeros((1, B), jnp.uint32)
    win = jnp.ones((1, B), jnp.int32) * 7
    acc = {"X": qx, "Y": qy, "Z": qx, "inf": jnp.zeros((1, B), jnp.int32)}
    degen = jnp.zeros((1, B), jnp.int32)
    shape = (S.N_LIMBS, B)

    def count(f, *args):
        return _lane_ops(B, f, *args)

    # w4 phases
    w4_tables = count(
        lambda qx, qy: S._w4_tables(qx, qy, q_inf_u, one, shape)[1], qx, qy
    )

    def w4_step(qx, qy, acc_in):
        g_tab, q_tab = S._w4_tables(qx, qy, q_inf_u, one, shape)
        return S._w4_window_step((acc_in, degen), win, win, g_tab, q_tab,
                                 q_inf_u, one, never_inf)

    w4_window = count(w4_step, qx, qy, acc) - count(
        lambda qx, qy: S._w4_tables(qx, qy, q_inf_u, one, shape), qx, qy
    )
    w4_final = count(
        lambda a, r0, rn: S._verify_final(a, degen, q_inf_u, r0, rn, wrap2),
        acc, r0, rn,
    )

    # GLV phases
    glv_tables = count(
        lambda qx, qy: S._glv_q_tables(qx, qy, q_inf_u * 0, q_inf_u, one),
        qx, qy,
    )

    def glv_step(qx, qy, acc_in):
        t1, t2 = S._glv_q_tables(qx, qy, q_inf_u * 0, q_inf_u, one)
        return S._glv_window_step((acc_in, degen), win, win, t1, t2, q_inf_u)

    glv_window = count(glv_step, qx, qy, acc) - glv_tables
    # device-side lattice decomposition (ISSUE 11): per-scalar cost of
    # the in-kernel split (limb-expand + exact rounding + magnitude
    # emission); a fused verify pays it twice (u1 and u2) plus the
    # window/digit planes — all O(1) per lane against the ladder
    km8 = jnp.zeros((B, 32), jnp.uint8)
    glv_decompose = count(
        lambda m: S._glv_split_device(S._expand_limb_cols(m)), km8)
    glv_emit = count(
        lambda m: (
            S._bits_to_comb_digits(
                S._mag_bits128(S._expand_limb_cols(m)[:10])),
            S._bits_to_nibble_windows(
                S._mag_bits128(S._expand_limb_cols(m)[:10])),
        ),
        km8)
    comb = S._glv_comb()
    tab_x = jnp.asarray(comb[0][0])
    tab_y = jnp.asarray(comb[1][0])
    drow = jnp.ones((B,), jnp.int32) * 9
    sgrow = jnp.zeros((B,), jnp.int32)
    glv_tooth = count(
        lambda a: S._glv_comb_step((a, degen), drow, sgrow, tab_x, tab_y,
                                   one, never_inf),
        acc,
    )
    glv_final = w4_final  # shared epilogue (_verify_final)

    w4_total = w4_tables + 64 * w4_window + w4_final
    glv_total = (glv_tables + S.GLV_WINDOWS * glv_window
                 + 2 * S.GLV_COMB_TEETH * glv_tooth + glv_final)
    return {
        "w4": {"tables": w4_tables, "window": w4_window, "windows": 64,
               "final": w4_final, "total": w4_total},
        "glv": {"tables": glv_tables, "window": glv_window,
                "windows": S.GLV_WINDOWS, "comb_tooth": glv_tooth,
                "comb_adds": 2 * S.GLV_COMB_TEETH, "final": glv_final,
                "total": glv_total,
                # the fused device-decompose program's extra per-lane
                # cost: two splits (u1, u2) + the magnitude plane emits
                "decompose_per_scalar": glv_decompose,
                "decompose_emit": glv_emit,
                "decompose_total": 2 * (glv_decompose + glv_emit),
                "total_with_decompose":
                    glv_total + 2 * (glv_decompose + glv_emit)},
    }


def run_ecdsa_census():
    parts = _ecdsa_census_parts()
    w4, glv = parts["w4"], parts["glv"]
    print("ECDSA verify kernels — vector ops per lane "
          "(parallel field forms, jaxpr census)")
    print(f"{'phase':<28}{'w4':>12}{'glv':>12}")
    print(f"{'table build (per batch)':<28}{w4['tables']:>12,}"
          f"{glv['tables']:>12,}")
    print(f"{'ladder window (each)':<28}{w4['window']:>12,}"
          f"{glv['window']:>12,}")
    print(f"{'ladder windows':<28}{w4['windows']:>12}{glv['windows']:>12}")
    print(f"{'comb tooth (each)':<28}{'-':>12}{glv['comb_tooth']:>12,}")
    print(f"{'comb adds':<28}{'-':>12}{glv['comb_adds']:>12}")
    print(f"{'final check':<28}{w4['final']:>12,}{glv['final']:>12,}")
    print(f"{'TOTAL per verify':<28}{w4['total']:>12,}{glv['total']:>12,}")
    red = 1.0 - glv['total'] / w4['total']
    print(f"GLV reduction vs w4: {red * 100:.1f}% "
          f"({'meets' if red >= 0.30 else 'MISSES'} the >=30% target)")
    print("\ndevice-side decompose census (ISSUE 11, per lane):")
    print(f"{'split (per scalar)':<28}{glv['decompose_per_scalar']:>12,}")
    print(f"{'plane emit (per scalar)':<28}{glv['decompose_emit']:>12,}")
    print(f"{'decompose total (x2)':<28}{glv['decompose_total']:>12,}")
    oh = glv['decompose_total'] / glv['total']
    print(f"{'fused verify total':<28}"
          f"{glv['total_with_decompose']:>12,}  "
          f"(+{oh * 100:.2f}% over the ladder)")
    return parts


# ---- Schnorr lane census (--schnorr, PR 44) ---------------------------------
#
# A Schnorr bucket runs _glv_prepare_program (the lattice split of u1 = s
# and u2 = n - e, the plane emits, the per-lane tables) and
# _glv_schnorr_program (the ladder's 32 windows, the comb's 32 adds, the
# Schnorr final stage). Everything but the final stage is the ECDSA census's
# own count; the final stage's squarings run as 14 loops whose bodies a
# jaxpr walk counts once, so the 254 - 14 further squarings are added.

def _schnorr_census_parts(B: int = 128):
    import jax.numpy as jnp

    from bitcoincashplus_tpu.crypto import secp256k1 as orc
    from bitcoincashplus_tpu.ops import secp256k1 as S

    rng = random.Random(44)

    def limbs():
        return jnp.asarray(
            S.pack_batch_np([rng.randrange(orc.P) for _ in range(B)]))

    x, y, z, r0 = limbs(), limbs(), limbs(), limbs()
    acc = {"X": x, "Y": y, "Z": z, "inf": jnp.zeros((1, B), jnp.int32)}
    mask = jnp.zeros((1, B), jnp.int32)
    steps = [0, 0]  # squarings, loops

    def sqr_n(v, k):
        steps[0] += k
        steps[1] += 1
        return v

    S._euler_chain(0, sqr_n, lambda a, b: a)
    squarings, loops = steps
    f_sqr = _lane_ops(B, S.f_sqr, x)
    final_once = _lane_ops(
        B, lambda a, r: S._schnorr_final(a, mask, mask, r), acc, r0)
    glv = _ecdsa_census_parts(B)["glv"]
    prepare = glv["tables"] + glv["decompose_total"]
    ladder = glv["windows"] * glv["window"]
    comb = glv["comb_adds"] * glv["comb_tooth"]
    final = final_once + (squarings - loops) * f_sqr
    return {"prepare": prepare, "ladder": ladder, "comb": comb,
            "final": final, "f_sqr": f_sqr, "squarings": squarings,
            "ecdsa_final": glv["final"],
            "schnorr_program": ladder + comb + final,
            "total": prepare + ladder + comb + final}


def run_schnorr_census():
    parts = _schnorr_census_parts()
    print("Schnorr bucket, two programs: vector ops per lane "
          "(parallel field forms, jaxpr census)")
    for name, key in (
            ("prepare: split x2, emits, tables", "prepare"),
            ("ladder: 32 windows", "ladder"),
            ("comb: 32 adds", "comb"),
            ("final: X == r*Z^2, Euler power", "final"),
            ("  one squaring", "f_sqr"),
            ("  squarings in the power", "squarings"),
            ("  (ECDSA's final stage)", "ecdsa_final"),
            ("_glv_schnorr_program", "schnorr_program"),
            ("TOTAL per Schnorr lane", "total")):
        print(f"{name:<36}{parts[key]:>12,}")
    return parts


# ---- Schnorr MSM census (--ecdsa, ISSUE 19) ---------------------------------
#
# The Pippenger bucket-accumulation program (ops/secp256k1._msm_accumulate)
# amortizes ONE batch equation over M terms (M = 2·sigs + 1), so its unit
# is vector ops per TERM, not per verify lane. Same phase-and-scale
# convention as the w4/GLV census: each loop body is traced once and
# multiplied by its trip count. Census shape M = 64 (the test/drill rung:
# K = 2 streams x 32 steps); the per-term number is K-independent because
# a step always processes K terms across K·64 lanes.

def _msm_census_parts(M: int = 64):
    import jax.numpy as jnp

    from bitcoincashplus_tpu.crypto import secp256k1 as orc
    from bitcoincashplus_tpu.ops import secp256k1 as S

    rng = random.Random(9)
    K = max(1, min(128, M // 32))
    steps = M // K
    lanes = K * 64

    def count(f, *args, floor=64):
        """Vector ops whose output carries >= ``floor`` elements (the MSM
        reduction phases run at width 64; the Horner epilogue runs at
        width 1 and is counted with floor=1 — see below)."""
        jaxpr = jax.make_jaxpr(f)(*args)
        total = 0

        def walk(jx):
            nonlocal total
            for eqn in jx.eqns:
                for sub in eqn.params.values():
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
                shapes = [v.aval.shape for v in eqn.outvars
                          if hasattr(v.aval, "shape")]
                if any(s and int(np.prod(s)) >= floor for s in shapes):
                    total += 1

        walk(jaxpr.jaxpr)
        return total

    def limbs(width):
        return jnp.asarray(S.pack_batch_np(
            [rng.randrange(orc.P) for _ in range(width)]))

    # step phase: bucket gather + complete mixed add + one-hot scatter,
    # emulated at the real (lanes, 16) bucket shape
    bk = {"X": jnp.ones((S.N_LIMBS, lanes, 16), jnp.uint32),
          "Y": jnp.ones((S.N_LIMBS, lanes, 16), jnp.uint32),
          "Z": jnp.zeros((S.N_LIMBS, lanes, 16), jnp.uint32),
          "inf": jnp.ones((lanes, 16), bool)}
    d = jnp.ones((lanes,), jnp.int32) * 7
    qx, qy = limbs(lanes), limbs(lanes)
    qi = jnp.zeros((lanes,), bool)
    bucket_ids = jnp.arange(16, dtype=jnp.int32)

    def step_body(bk, qx, qy):
        cur = {
            "X": jnp.take_along_axis(bk["X"], d[None, :, None],
                                     axis=2)[..., 0],
            "Y": jnp.take_along_axis(bk["Y"], d[None, :, None],
                                     axis=2)[..., 0],
            "Z": jnp.take_along_axis(bk["Z"], d[None, :, None],
                                     axis=2)[..., 0],
            "inf": jnp.take_along_axis(bk["inf"], d[:, None],
                                       axis=1)[:, 0],
        }
        new = S.pt_add_mixed(cur, qx, qy, qi)
        hit = (bucket_ids[None, :] == d[:, None]) & ((d > 0) & ~qi)[:, None]
        return {
            "X": jnp.where(hit[None], new["X"][:, :, None], bk["X"]),
            "Y": jnp.where(hit[None], new["Y"][:, :, None], bk["Y"]),
            "Z": jnp.where(hit[None], new["Z"][:, :, None], bk["Z"]),
            "inf": jnp.where(hit, new["inf"][:, None], bk["inf"]),
        }

    step = count(step_body, bk, qx, qy, floor=lanes)

    # merge / reduction phases: one COMPLETE Jacobian add each (the jaxpr
    # op count of pt_add_full is width-independent; widths halve down the
    # merge tree and sit at 64 through the bucket reduction)
    w = 64
    pt_a = {"X": limbs(w), "Y": limbs(w), "Z": limbs(w),
            "inf": jnp.zeros((w,), bool)}
    pt_b = {"X": limbs(w), "Y": limbs(w), "Z": limbs(w),
            "inf": jnp.zeros((w,), bool)}
    full_add = count(S.pt_add_full, pt_a, pt_b, floor=w)
    merge_levels = int(np.log2(K)) if K > 1 else 0
    # suffix running sums: running += B_b; total += running  (2 adds x 15)
    red = 2 * full_add

    # Horner epilogue at width 1: 64 x (4 doubles + 1 add) — counted with
    # floor=1 (every op is a (20, 1) vector op on device; excluded from
    # the >=64-wide phases above by the same rule that excludes scalar
    # work from the SHA census)
    pt_1 = {"X": limbs(1), "Y": limbs(1), "Z": limbs(1),
            "inf": jnp.zeros((1,), bool)}
    horner = count(
        lambda a, b: S.pt_add_full(S.pt_double(S.pt_double(S.pt_double(
            S.pt_double(a)))), b), pt_1, pt_1, floor=1)

    total = (steps * step + merge_levels * full_add + 15 * red
             + 64 * horner)
    return {
        "M": M, "K": K, "steps": steps, "lanes": lanes,
        "step": step, "full_add": full_add, "merge_levels": merge_levels,
        "reduction": 15 * red, "horner": 64 * horner,
        "total": total, "per_term": total / M,
    }


def run_msm_census():
    p = _msm_census_parts()
    print(f"\nSchnorr MSM bucket accumulation — vector ops "
          f"(M = {p['M']} terms: K = {p['K']} streams x {p['steps']} "
          f"steps, {p['lanes']} window lanes)")
    print(f"{'phase':<34}{'ops':>12}")
    print(f"{'bucket step (each)':<34}{p['step']:>12,}")
    print(f"{'bucket steps':<34}{p['steps']:>12}")
    print(f"{'stream merge (full adds)':<34}{p['merge_levels']:>12}")
    print(f"{'bucket reduction (15 rounds)':<34}{p['reduction']:>12,}")
    print(f"{'Horner epilogue (64 windows)':<34}{p['horner']:>12,}")
    print(f"{'TOTAL per batch equation':<34}{p['total']:>12,}")
    print(f"{'amortized per term':<34}{p['per_term']:>12,.1f}")
    return p


# ---- live cost-analysis drift check (--ecdsa) -------------------------------
#
# The static jaxpr census above is a MODEL derived from a specific kernel
# + compiler state; the compiled executable's own cost_analysis() is what
# XLA actually admitted to for the SAME state, recorded below as the
# census's compiled twin. The units are not cross-comparable (census =
# lane-shaped primitives of the kernel cores; cost_analysis = element
# flops of the whole lowered program — the w4 path additionally lowers
# through pallas interpret on CPU), so drift is per kernel against its
# OWN recorded baseline: a live compiled-flops number that moved > 10%
# from the baseline means a kernel or compiler change shifted the real
# op mix and BOTH the census and these baselines must be re-derived.
#
# This drives one real dispatch per kernel through the util/devicewatch
# program registry (BCP_DEVICEWATCH_COST=always captures cost_analysis
# at first compile into the SAME "ecdsa_glv_decompose"/"ecdsa_w4_bytes"
# programs a running node populates — the live registry, not a side
# channel).

DRIFT_BUDGET = 0.10

# compiled flops/lane at bucket 1024, recorded when the §7 census was
# last validated (jax 0.4.37). Keyed by the lowering arrangement — the
# CPU arrangement is plain-XLA GLV + pallas-INTERPRET w4; a Mosaic (TPU)
# run lowers differently and reports without flagging until a baseline
# for that arrangement is recorded here.
COST_BASELINES = {
    # the three ECDSA programs were recorded again with the §7 census of
    # PR 33 (jax 0.9.0), whose normalisers run 6 or 1 carry rounds where
    # every call ran 15 (w4 1,618,602, GLV 4,052,615, MSM 3,716,708 before)
    "cpu": {"ecdsa_w4_bytes": 992_871.0,
            # the GLV decompose+verify program — the parallel-form
            # lowering's whole-program flop accounting weighs the unrolled
            # carry rounds far above their census primitive count, which
            # is exactly why drift is per kernel against its OWN twin
            "ecdsa_glv_decompose": 3_314_756.0,
            # Schnorr MSM batch check (ISSUE 19): compiled flops per
            # TERM-SLOT at bucket 64 (the whole batch-equation program's
            # flop count / 64 slots — the smallest, unit-test-priced
            # rung; bigger buckets amortize the fixed Horner epilogue so
            # their per-slot number is NOT comparable). §10's census
            # counts 26.1k primitives/term at this shape — same units
            # caveat as the fused decompose twin above
            "ecdsa_msm": 2_334_039.0,
            # miner_resident compiled flops/nonce at tile 1024 (exact =
            # looped-compress lowering — the form a CPU backend compiles;
            # h7 = the fully-unrolled trace, which XLA's whole-program
            # flop accounting weighs differently — hence per-kernel
            # baselines), recorded when the §8 post-hoist census was
            # validated (jax 0.4.37) — the census's compiled twin for
            # the mining drift check
            "miner_resident_exact": 6_244.4,
            "miner_resident_h7": 11_791.4},
}


def run_ecdsa_live_drift(parts, bucket: int = 1024):
    os.environ["BCP_DEVICEWATCH_COST"] = "always"
    from bitcoincashplus_tpu.util import devicewatch

    devicewatch.enable_compile_cache()

    from bitcoincashplus_tpu.crypto import secp256k1 as orc
    from bitcoincashplus_tpu.ops import ecdsa_batch as eb
    from bitcoincashplus_tpu.ops import secp256k1 as S
    from bitcoincashplus_tpu.ops.sha256 import backend_is_cpu
    from bitcoincashplus_tpu.script.interpreter import SigCheckRecord
    from bitcoincashplus_tpu.util import devicewatch as dwatch

    rng = random.Random(17)
    records = []
    for _ in range(4):
        sk = rng.randrange(1, orc.N)
        e = rng.getrandbits(256) % orc.N
        r, s = orc.ecdsa_sign(sk, e)
        records.append(SigCheckRecord(orc.point_mul(sk, orc.G), r, s, e))

    print(f"\nlive cost-analysis drift check (bucket {bucket}, one real "
          "dispatch per kernel through the devicewatch registry)...")
    args = eb.pack_lanes(*eb.records_to_blobs(records), bucket)
    # the GLV verify is two programs a bucket since PR 39: each stage is
    # costed as the node runs it, and a lane's flops are their sum
    glv = dwatch.program("ecdsa_glv_decompose")
    with glv.dispatch(bucket, "prepare", jitfn=S._glv_prepare_program,
                      args=args):
        prepared = jax.block_until_ready(S._glv_prepare_program(*args))
    with glv.dispatch(bucket, jitfn=S._glv_dev_program, args=prepared):
        jax.block_until_ready(S._glv_dev_program(*prepared))
    interp = backend_is_cpu()
    with dwatch.program("ecdsa_w4_bytes").dispatch(
            bucket, jitfn=S._w4_bytes_program, args=args,
            kwargs={"interpret": interp}):
        jax.block_until_ready(
            S._w4_bytes_program(*args, interpret=interp))
    # Schnorr MSM batch-equation program (ISSUE 19) at ITS census rung —
    # bucket 64, the smallest _MSM_BUCKETS shape (1024 is a many-minute
    # XLA compile on a CPU backend; the flops/term-slot unit is bucket-
    # normalized either way). One canary-sized batch through the real
    # dispatch helper populates the same "ecdsa_msm" watch a node feeds.
    msm_bucket = 64
    kg, kb = eb._schnorr_kat_records()
    eb._msm_device_check(
        [(kg, eb._schnorr_precheck(kg)), (kb, eb._schnorr_precheck(kb))],
        random.Random(17))

    progs = dwatch.snapshot()["programs"]
    per_name_bucket = {"ecdsa_glv_decompose": bucket,
                       "ecdsa_w4_bytes": bucket, "ecdsa_msm": msm_bucket}
    live = {}
    for name, bkt in per_name_bucket.items():
        sigs = [(bkt,)] + ([(bkt, "prepare")] if name == glv.name else [])
        costs = [progs.get(name, {}).get("cost", {}).get(str(sig))
                 for sig in sigs]
        if not all(costs):
            print("live drift check: cost_analysis unavailable on this "
                  "backend — skipped")
            return None
        live[name] = sum(cost["flops"] for cost in costs) / bkt

    arrangement = "cpu" if interp else "mosaic"
    baselines = COST_BASELINES.get(arrangement)
    census_ratio = parts["glv"]["total"] / parts["w4"]["total"]
    print(f"{'':<28}{'w4':>14}{'glv+dec':>14}")
    print(f"{'census ops/lane':<28}{parts['w4']['total']:>14,}"
          f"{parts['glv']['total_with_decompose']:>14,}")
    print(f"{'compiled flops/lane':<28}{live['ecdsa_w4_bytes']:>14,.0f}"
          f"{live['ecdsa_glv_decompose']:>14,.0f}")
    print(f"census glv/w4 ratio: {census_ratio:.4f} "
          "(primitive counts of the kernel cores — see §7)")
    print(f"msm compiled flops/term-slot (bucket {msm_bucket}): "
          f"{live['ecdsa_msm']:>14,.0f}")
    if baselines is None:
        print(f"no compiled-cost baseline recorded for the "
              f"{arrangement!r} lowering arrangement — reporting only "
              "(record one in COST_BASELINES to arm the drift flag)")
        return {"live": live, "drift": None, "ok": None}
    out = {"live": live, "ok": True}
    for name, base in baselines.items():
        if name not in live:
            continue  # other tools' baselines (miner_resident_*) share
            # the arrangement dict — only compare what THIS check ran
        drift = abs(live[name] - base) / base
        flagged = drift > DRIFT_BUDGET
        out[name] = {"baseline": base, "live": live[name], "drift": drift}
        out["ok"] = out["ok"] and not flagged
        verdict = ("DRIFT EXCEEDS BUDGET — a kernel/compiler change "
                   "moved the real op mix; re-derive the §7 census AND "
                   "this baseline") if flagged else "within budget"
        print(f"{name}: live {live[name]:,.0f} vs baseline {base:,.0f} "
              f"flops/lane — drift {drift * 100:.1f}% "
              f"(budget {DRIFT_BUDGET * 100:.0f}%) — {verdict}")
    for name in live:
        if name not in baselines:
            print(f"{name}: live {live[name]:,.0f} flops/lane — no "
                  "baseline recorded for this arrangement yet (record "
                  "one in COST_BASELINES to arm the drift flag)")
            out["ok"] = None if out["ok"] is True else out["ok"]
    return out


# ---- mining sweep census + live drift (--mining) ----------------------------
#
# The ISSUE 10 twin of the ECDSA section: the chunk-2 hoist's ops/nonce
# claim (ROOFLINE.md §8) as a re-runnable census, plus the compiled-flops
# drift check of the resident miner program. One real dispatch per kernel
# goes through the SAME devicewatch program a running node populates
# ("miner_resident", sig = (kernel, tile)); cost_analysis at first
# compile is compared per kernel against its recorded baseline — the
# units (census primitive counts vs whole-program element flops, body of
# the while_loop counted once) are not cross-comparable, so drift is
# per kernel against its OWN compiled twin, flagged at > 10%.

PRE_HOIST_H7 = 5923      # ops/nonce before the chunk-2 hoist (§2)
PRE_HOIST_FULL = 7041    # generic full-digest sweep, unhoisted (§2)


def run_mining_census():
    spec_ops, full_ops, hoisted_full_ops, _detail = run_census()
    print("nonce-sweep kernels — vector ops per nonce (jaxpr census)")
    print(f"{'kernel':<42}{'ops/nonce':>12}")
    print(f"{'generic full-digest (unhoisted)':<42}{full_ops:>12,}")
    print(f"{'full-digest + chunk-2 hoist (resident exact)':<42}"
          f"{hoisted_full_ops:>12,}")
    print(f"{'truncated-h7, pre-hoist (r10 baseline)':<42}"
          f"{PRE_HOIST_H7:>12,}")
    print(f"{'truncated-h7 + chunk-2 hoist':<42}{spec_ops:>12,}")
    red = 1.0 - spec_ops / PRE_HOIST_H7
    print(f"chunk-2 hoist reduction vs pre-hoist h7: {red * 100:.2f}% "
          f"({'below' if spec_ops < PRE_HOIST_H7 else 'NOT below'} "
          f"the 5923 baseline)")
    return {"h7_hoisted": spec_ops, "full_generic": full_ops,
            "full_hoisted": hoisted_full_ops,
            "h7_pre_hoist": PRE_HOIST_H7}


def run_mining_live_drift(census_d, tile: int = 1024):
    os.environ["BCP_DEVICEWATCH_COST"] = "always"
    from bitcoincashplus_tpu.util import devicewatch

    devicewatch.enable_compile_cache()

    from bitcoincashplus_tpu.mining.resident import (
        PROGRAM,
        SHAPE_BUDGET,
        ResidentSweep,
    )
    from bitcoincashplus_tpu.ops.sha256 import backend_is_cpu
    from bitcoincashplus_tpu.util import devicewatch as dwatch

    print(f"\nlive cost-analysis drift check (tile {tile}, one real "
          "segment dispatch per kernel through the devicewatch "
          f"{PROGRAM!r} program, shape budget {SHAPE_BUDGET})...")
    header = HEADER
    target = 0  # impossible: the segment runs its full tile
    live = {}
    for kernel in ("exact", "h7"):
        rs = ResidentSweep(tile=tile, seg_tiles=1, inflight=1,
                           kernel=kernel)
        rs.sweep(header, target, max_nonces=tile)
        rs.close()
        snap = dwatch.program(PROGRAM).snapshot()
        cost = snap["cost"].get(str((kernel, tile)))
        if not cost:
            print("live drift check: cost_analysis unavailable on this "
                  "backend — skipped")
            return None
        live[f"miner_resident_{kernel}"] = cost["flops"] / tile
    arrangement = "cpu" if backend_is_cpu() else "mosaic"
    baselines = COST_BASELINES.get(arrangement, {})
    print(f"{'kernel':<28}{'census ops/nonce':>18}{'flops/nonce':>16}")
    print(f"{'exact (full digest)':<28}{census_d['full_hoisted']:>18,}"
          f"{live['miner_resident_exact']:>16,.1f}")
    print(f"{'h7 (truncated)':<28}{census_d['h7_hoisted']:>18,}"
          f"{live['miner_resident_h7']:>16,.1f}")
    out = {"live": live, "ok": True}
    for name, val in live.items():
        base = baselines.get(name)
        if base is None:
            print(f"{name}: live {val:,.1f} flops/nonce — no baseline "
                  f"recorded for the {arrangement!r} arrangement "
                  "(record one in COST_BASELINES to arm the drift flag)")
            out["ok"] = None
            continue
        drift = abs(val - base) / base
        flagged = drift > DRIFT_BUDGET
        out[name] = {"baseline": base, "live": val, "drift": drift}
        if out["ok"] is not None:
            out["ok"] = out["ok"] and not flagged
        verdict = ("DRIFT EXCEEDS BUDGET — a kernel/compiler change "
                   "moved the real op mix; re-derive the §8 census AND "
                   "this baseline") if flagged else "within budget"
        print(f"{name}: live {val:,.1f} vs baseline {base:,.1f} "
              f"flops/nonce — drift {drift * 100:.1f}% "
              f"(budget {DRIFT_BUDGET * 100:.0f}%) — {verdict}")
    return out


def main():
    if SCHNORR_MODE:
        run_schnorr_census()
        return
    if ECDSA_MODE:
        parts = run_ecdsa_census()
        run_msm_census()
        run_ecdsa_live_drift(parts)
        return
    if MINING_MODE:
        census_d = run_mining_census()
        run_mining_live_drift(census_d)
        return
    spec_ops, full_ops, hoisted_full_ops, spec_detail = run_census()
    print(f"census: specialized h7 sweep = {spec_ops} vector ops/nonce "
          f"(chunk-2 hoisted; pre-hoist {PRE_HOIST_H7})")
    print(f"census: generic full-digest  = {full_ops} vector ops/nonce "
          f"(hoisted full-digest: {hoisted_full_ops})")
    print(f"census detail: {spec_detail}")

    on_tpu = jax.default_backend() != "cpu"
    if not on_tpu:
        print("(CPU backend: skipping device measurements)")
        return

    probe = run_probe()
    for name, rate in probe.items():
        print(f"probe {name}: {rate/1e12:.2f} T u32-ops/s sustained (naive count)")

    ghs = run_sweep_rate() / 1e9
    achieved_ops = ghs * 1e9 * spec_ops
    print(f"pallas sweep: {ghs:.4f} GH/s -> {achieved_ops/1e12:.2f} T vector-ops/s")
    print(f"VPU theoretical peak: {VPU_PEAK_OPS/1e12:.2f} T u32-ops/s")
    print(f"roofline utilization: {achieved_ops/VPU_PEAK_OPS*100:.1f}%")
    print(f"op-bound ceiling at this census: {VPU_PEAK_OPS/spec_ops/1e9:.3f} GH/s")


if __name__ == "__main__":
    main()
