"""Differential tests: TPU secp256k1 field/point/verify vs the Python-int
oracle (crypto/secp256k1.py) — the secp tests.c randomized-identity strategy
(SURVEY.md §5.4.4)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bitcoincashplus_tpu.crypto import secp256k1 as oracle
from bitcoincashplus_tpu.ops import secp256k1 as S

rng = random.Random(4242)


def rand_field(n):
    return [rng.randrange(oracle.P) for _ in range(n)]


def limbs(vals):
    return jnp.asarray(S.pack_batch_np(vals))


def unpack(arr):
    a = np.asarray(arr)
    return [S.from_limbs_np(a[:, k]) for k in range(a.shape[1])]


class TestFieldOps:
    def test_mul(self):
        va, vb = rand_field(64), rand_field(64)
        out = unpack(jax.jit(S.f_mul)(limbs(va), limbs(vb)))
        for g, a, b in zip(out, va, vb):
            assert g % oracle.P == a * b % oracle.P

    def test_mul_extremes(self):
        va = [0, 1, oracle.P - 1, oracle.P - 1, 2**256 % oracle.P, 0x1FFF]
        vb = [5, oracle.P - 1, oracle.P - 1, 1, 977, 0x1FFF]
        out = unpack(jax.jit(S.f_mul)(limbs(va), limbs(vb)))
        for g, a, b in zip(out, va, vb):
            assert g % oracle.P == a * b % oracle.P

    def test_add_sub_roundtrip(self):
        va, vb = rand_field(32), rand_field(32)
        add = unpack(jax.jit(lambda a, b: S.f_carry(S.f_add(a, b)))(limbs(va), limbs(vb)))
        sub = unpack(jax.jit(S.f_carry_sub)(limbs(va), limbs(vb)))
        for g, a, b in zip(add, va, vb):
            assert g % oracle.P == (a + b) % oracle.P
        for g, a, b in zip(sub, va, vb):
            assert g % oracle.P == (a - b) % oracle.P

    def test_canonical_and_eq(self):
        va = rand_field(16)
        # a and a+p must compare equal; a and a+1 must not
        a_pl = limbs(va)
        b_pl = limbs([(v + oracle.P) % (1 << 260) for v in va])  # non-canonical alias
        c_pl = limbs([(v + 1) % oracle.P for v in va])
        eq_ab = np.asarray(jax.jit(S.f_eq)(a_pl, b_pl))
        eq_ac = np.asarray(jax.jit(S.f_eq)(a_pl, c_pl))
        assert eq_ab.all()
        assert not eq_ac.any()
        canon = unpack(jax.jit(S.f_canonical)(b_pl))
        for g, v in zip(canon, va):
            assert g == v

    def test_sqr_matches_mul(self):
        va = rand_field(32)
        sq = unpack(jax.jit(S.f_sqr)(limbs(va)))
        for g, a in zip(sq, va):
            assert g % oracle.P == a * a % oracle.P


def _scalar_mult_device(ks, pts):
    """Device k*Q for test purposes: reuses the verify loop with u1=0."""
    B = len(ks)
    bits = np.zeros((256, B), np.uint32)
    for j, k in enumerate(ks):
        for i in range(256):
            bits[i, j] = (k >> (255 - i)) & 1
    qx = limbs([p[0] for p in pts])
    qy = limbs([p[1] for p in pts])

    @jax.jit
    def run(bits, qx, qy):
        B = qx.shape[1]
        never = jnp.zeros((B,), bool)

        def step(i, acc):
            acc = S.pt_double(acc)
            added = S.pt_add_mixed(acc, qx, qy, never)
            return S.pt_select(bits[i].astype(bool), added, acc)

        acc = jax.lax.fori_loop(0, 256, step, S.pt_infinity(B))
        return (
            S.f_canonical(acc["X"]),
            S.f_canonical(acc["Y"]),
            S.f_canonical(acc["Z"]),
            acc["inf"],
        )

    X, Y, Z, inf = run(jnp.asarray(bits), qx, qy)
    out = []
    for j, (x, y, z) in enumerate(zip(unpack(X), unpack(Y), unpack(Z))):
        if bool(np.asarray(inf)[j]):
            out.append(None)
            continue
        zi = pow(z, oracle.P - 2, oracle.P)
        out.append((x * zi * zi % oracle.P, y * zi * zi * zi % oracle.P))
    return out


@pytest.mark.slow
class TestPointOps:
    def test_scalar_mult_matches_oracle(self):
        ks = [1, 2, 3, 0, oracle.N - 1, rng.randrange(oracle.N), rng.randrange(oracle.N)]
        pts = [oracle.G] * len(ks)
        got = _scalar_mult_device(ks, pts)
        for k, g in zip(ks, got):
            expect = oracle.point_mul(k, oracle.G)
            assert g == expect, f"k={k}"

    def test_scalar_mult_random_points(self):
        ks, pts = [], []
        for _ in range(5):
            d = rng.randrange(1, oracle.N)
            pts.append(oracle.point_mul(d, oracle.G))
            ks.append(rng.randrange(oracle.N))
        got = _scalar_mult_device(ks, pts)
        for k, p, g in zip(ks, pts, got):
            assert g == oracle.point_mul(k, p)

    def test_distributivity_on_device(self):
        # (a+b)G == aG + bG via two device multiplies + oracle add
        a, b = rng.randrange(oracle.N), rng.randrange(oracle.N)
        got = _scalar_mult_device([a, b, (a + b) % oracle.N], [oracle.G] * 3)
        assert oracle.point_add(got[0], got[1]) == got[2]
