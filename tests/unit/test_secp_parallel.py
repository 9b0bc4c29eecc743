"""Differential tests for the parallel-form field ops (the TPU device path
of ops/secp256k1: _pcarry_round/_fold_parallel/_exact_norm20 and the
parallel f_mul/f_carry/f_is_zero) against the Python-int oracle. Runs the
ops EAGERLY with BCP_SECP_PARALLEL=1 — no XLA compile, so these stay in the
default CPU suite."""

import numpy as np
import jax.numpy as jnp
import pytest

import bitcoincashplus_tpu.ops.secp256k1 as dev
from bitcoincashplus_tpu.crypto import secp256k1 as oracle
from bitcoincashplus_tpu.crypto.secp256k1 import P

B = 8


@pytest.fixture(autouse=True)
def _force_parallel(monkeypatch):
    monkeypatch.setenv("BCP_SECP_PARALLEL", "1")


def _vals(rng, n=B):
    return [int.from_bytes(rng.bytes(32), "big") % P for _ in range(n)]


def _pack(vals):
    return np.stack([dev.to_limbs_np(v) for v in vals], axis=1)


def _unpack(arr):
    return [dev.from_limbs_np(arr[:, b]) for b in range(arr.shape[1])]


def _cols_value(cols):
    return [
        sum(int(cols[i, b]) << (13 * i) for i in range(cols.shape[0]))
        for b in range(cols.shape[1])
    ]


def _fill(rows, value):
    return np.full((rows, B), value, np.uint32)


def _square_cols(limb):
    """f_mul's 39 product columns for a value whose 20 limbs all read
    ``limb`` (column k has min(k, 38 - k) + 1 terms)."""
    terms = np.minimum(np.arange(39), 38 - np.arange(39)) + 1
    return np.repeat((terms * limb * limb).astype(np.uint32)[:, None], B, 1)


def _weak(value):
    """Exact 13-bit limbs (a value of 2^256 or more shows in the top limb)."""
    return np.repeat(dev.to_limbs_np(value)[:, None], B, 1)


def _eps(value):
    """The same value with every limb but the top over 13 bits: 2^13 lent
    down from the limb above, as _BIAS_2P is built."""
    limbs = _weak(value).astype(np.int64)
    limbs[:-1] += 1 << 13
    limbs[1:] -= 1
    assert limbs.min() >= 0
    return limbs.astype(np.uint32)


_FILLS = (8191, 8192, 8193, 16383, 32800)
_RNG = np.random.default_rng(33)

# f_carry (limbs < 2^31, 20..39 rows) takes every case; f_carry_loose the
# 20-row ones under its own bound, limbs < 2^16
_ANY_CASES = [
    ("39x(2^31-1)", _fill(39, (1 << 31) - 1)),   # f_carry's stated bound
    ("30x(2^31-1)", _fill(30, (1 << 31) - 1)),
    ("21x(2^31-1)", _fill(21, (1 << 31) - 1)),
    ("20x(2^31-1)", _fill(20, (1 << 31) - 1)),
    ("39x0", _fill(39, 0)),
    ("8200-limbs-squared", _square_cols(8200)),
    ("39-rows-random", _RNG.integers(0, 1 << 31, (39, 2000),
                                     dtype=np.uint32)),
] + [(f"39x{v}", _fill(39, v)) for v in _FILLS]
_LOOSE_CASES = [
    ("20x(2^16-1)", _fill(20, (1 << 16) - 1)),   # f_carry_loose's bound
    ("20x0", _fill(20, 0)),
    ("p-weak", _weak(P)),
    ("p-eps", _eps(P)),
    ("2p-1-weak", _weak(2 * P - 1)),
    ("2p-1-eps", _eps(2 * P - 1)),
    ("20-rows-random", _RNG.integers(0, 1 << 16, (20, 2000),
                                     dtype=np.uint32)),
] + [(f"20x{v}", _fill(20, v)) for v in _FILLS]
_CARRY_CASES = (
    [("f_carry", *c) for c in _ANY_CASES + _LOOSE_CASES]
    + [("f_carry_loose", *c) for c in _LOOSE_CASES]
)


@pytest.mark.parametrize(
    "fn,cols", [pytest.param(fn, cols, id=f"{fn}-{name}")
                for fn, name, cols in _CARRY_CASES])
def test_parallel_carry_extremes(fn, cols):
    """Both normalisers against Python integers, at and inside their stated
    input bounds; the output is weak as their docstrings bound it: limbs
    <= 8,200 (multiply-safe: 20 x 8,200^2 < 2^31), top limb <= 0x1FF,
    value < 2p."""
    out = np.asarray(getattr(dev, fn)(jnp.asarray(cols)))
    assert out.shape == (20, cols.shape[1])
    for want, got in zip(_cols_value(cols), _unpack(out)):
        assert got % P == want % P
        assert got < 2 * P
    assert out.max() <= 8200
    assert out[19].max() <= 0x1FF


def _affine(pt, lane):
    """Jacobian lane -> affine (x, y) Python ints (None at Z == 0)."""
    x, y, z = (dev.from_limbs_np(np.asarray(pt[c])[:, lane]) % P
               for c in "XYZ")
    if z == 0:
        return None
    zi = pow(z, P - 2, P)
    return x * zi * zi % P, y * zi * zi * zi % P


def _jac(points, zs):
    """Affine (x, y) lanes lifted to Jacobian with the given Z's."""
    return {
        "X": jnp.asarray(_pack([x * z * z % P for (x, _), z in
                                zip(points, zs)])),
        "Y": jnp.asarray(_pack([y * z * z * z % P for (_, y), z in
                                zip(points, zs)])),
        "Z": jnp.asarray(_pack(zs)),
        "inf": jnp.zeros((1, len(zs)), jnp.int32),
    }


# lanes: three curve points, then coordinates 0, 1 and p - 1 (the oracle's
# chord and tangent are algebra in the coordinates, the curve's constant
# never enters: any pair with y != 0 lies on *some* y^2 = x^3 + b and
# serves as a lane), each lifted with a Z of its own, 1 and p - 1 too
_PTS_A = [oracle.point_mul(k, oracle.G) for k in (1, 2, 0xDEADBEEF)] + [
    (0, 1), (1, P - 1), (P - 1, 1), (P - 1, P - 1), (1, 1)]
_PTS_B = [oracle.point_mul(k, oracle.G) for k in (7, 3, 0xC0FFEE)] + [
    (1, 1), (P - 1, 1), (0, P - 1), (0, 1), (P - 1, P - 1)]
_ZS_A = [1, P - 1, 0x1234567, 2, 1, P - 1, 3, P - 2]
_ZS_B = [P - 1, 1, 0x7654321, 1, 5, 1, P - 1, 2]


def test_parallel_pt_double_matches_python_points():
    out = dev.pt_double(_jac(_PTS_A, _ZS_A))
    for lane, a in enumerate(_PTS_A):
        assert _affine(out, lane) == oracle.point_double(a), lane
    assert max(np.asarray(out[c]).max() for c in "XYZ") <= 8200


@pytest.mark.parametrize("form", ["mixed", "full"])
def test_parallel_cheap_adds_match_python_points(form):
    """_pt_add_mixed_cheap_u / _pt_add_full_cheap_u against the oracle's
    chord; the last lane adds a point to itself (H == 0): the flag must
    rise, and only there."""
    pts_a = _PTS_A[:-1] + [_PTS_B[-1]]
    pt = _jac(pts_a, _ZS_A)
    never = jnp.zeros((1, B), jnp.int32)
    if form == "mixed":
        one = jnp.asarray(_pack([1] * B))
        out, hz = dev._pt_add_mixed_cheap_u(
            pt, jnp.asarray(_pack([x for x, _ in _PTS_B])),
            jnp.asarray(_pack([y for _, y in _PTS_B])), never, one)
    else:
        out, hz = dev._pt_add_full_cheap_u(pt, _jac(_PTS_B, _ZS_B))
    assert np.asarray(hz).ravel().tolist() == [0] * (B - 1) + [1]
    for lane, (a, b_) in enumerate(zip(pts_a[:-1], _PTS_B[:-1])):
        assert _affine(out, lane) == oracle.point_add(a, b_), lane


# two ladder windows, a lane: ((w1, w2) of the first, (w1, w2) of the
# second, whether the two Q-stream signs differ, whether Q is at infinity)
_WINDOW_LANES = [
    ((3, 5), (7, 9), 0, 0), ((3, 5), (7, 9), 1, 0),
    ((0, 0), (1, 15), 0, 0),   # a window of zeros first: still at infinity
    ((15, 0), (0, 0), 1, 0),   # then a window of zeros: four doublings only
    ((0, 4), (4, 0), 1, 0), ((2, 2), (2, 2), 0, 0),
    ((9, 9), (1, 1), 0, 1),    # Q at infinity: nothing is ever added
    ((0, 0), (0, 0), 1, 0)]


@pytest.mark.parametrize("tables", ["lambda", "twin"])
def test_parallel_window_steps_match_python_points(tables):
    """_glv_q_tables and two _glv_window_steps from the ladder's start,
    against the oracle's multiples: R = 16 (a1 Q + a2 L) + b1 Q + b2 L with
    L = phi(Q), negated where the two Q-stream signs differ ("lambda": the
    tables as the program builds them, both signs of the y-select). "twin"
    hands the second stream the first one's table, L = Q: the one way to
    meet H == 0 here, where a lane's two first digits are equal, and the
    flag must rise there and nowhere else."""
    ks = [5, 0xDEADBEEF, 7, 11, 0xC0FFEE, 13, 3, 17]
    pts = [oracle.point_mul(k, oracle.G) for k in ks]
    one = jnp.asarray(_pack([1] * B))
    plane = lambda vals: jnp.asarray([list(vals)], jnp.int32)  # noqa: E731
    ydiff = plane(lane[2] for lane in _WINDOW_LANES)
    q_inf = plane(lane[3] for lane in _WINDOW_LANES)
    t1, t2 = dev._glv_q_tables(
        jnp.asarray(_pack([x for x, _ in pts])),
        jnp.asarray(_pack([y for _, y in pts])), ydiff, q_inf, one)
    if tables == "twin":
        t2 = t1
    zero = jnp.zeros((dev.N_LIMBS, B), jnp.uint32)
    carry = ({"X": one, "Y": one, "Z": zero,
              "inf": jnp.ones((1, B), jnp.int32)},
             jnp.zeros((1, B), jnp.int32))
    for step in (0, 1):
        carry = dev._glv_window_step(
            carry, plane(lane[step][0] for lane in _WINDOW_LANES),
            plane(lane[step][1] for lane in _WINDOW_LANES), t1, t2, q_inf)
    acc, degen = carry
    twins = [int(tables == "twin" and a1 == a2 != 0 and not inf)
             for (a1, a2), _, _, inf in _WINDOW_LANES]
    assert np.asarray(degen).ravel().tolist() == twins
    for lane, ((a1, a2), (b1, b2), diff, inf) in enumerate(_WINDOW_LANES):
        q = pts[lane]
        lam = q if tables == "twin" else oracle.point_mul(dev.LAMBDA, q)
        if diff and tables == "lambda":
            lam = (lam[0], P - lam[1])
        want = None
        for k, base in ((16 * a1 + b1, q), (16 * a2 + b2, lam)):
            if k and not inf:
                want = oracle.point_add(want, oracle.point_mul(k, base))
        if twins[lane]:
            continue  # flagged: the caller verifies the lane again
        if want is None:
            assert int(np.asarray(acc["inf"])[0, lane]) == 1, lane
        else:
            assert int(np.asarray(acc["inf"])[0, lane]) == 0, lane
            assert _affine(acc, lane) == want, lane


def test_parallel_verify_final_matches_python_points():
    """X_R == r * Z^2 for r in {r0, rn}: lanes that match on r0, on rn
    (with and without the wrap gate), on neither, and at infinity."""
    xs = [x for x, _ in _PTS_A]
    miss = [(x + 1) % P for x in xs]

    def plane(bits, dtype=jnp.int32):
        return jnp.asarray([bits], dtype)

    # lane:     r0  r0+q_inf  rn  rn-ungated  none  none  none  r0+inf
    r0 = xs[:2] + miss[2:7] + xs[7:]
    rn = miss[:2] + xs[2:4] + miss[4:]
    acc = dict(_jac(_PTS_A, _ZS_A), inf=plane([0, 0, 0, 0, 0, 0, 0, 1]))
    ok, dg = dev._verify_final(
        acc, plane([0, 1, 0, 0, 1, 0, 0, 0]), plane([0, 1, 0, 0, 0, 0, 0, 0]),
        jnp.asarray(_pack(r0)), jnp.asarray(_pack(rn)),
        plane([1, 1, 1, 0, 1, 1, 1, 1], jnp.uint32))
    assert np.asarray(ok).ravel().tolist() == [1, 0, 1, 0, 0, 0, 0, 0]
    # a degenerate flag survives except where Q is at infinity
    assert np.asarray(dg).ravel().tolist() == [0, 0, 0, 0, 1, 0, 0, 0]


def test_loose_callers_stay_under_the_stated_bound(monkeypatch):
    """f_carry_loose REQUIRES limbs < 2^16. Its callers' worst inputs, from
    the point formulas, for weak rows W <= 8,200 and _BIAS_2P's largest row
    16,382:
      pt_double      X+B 2W | A+C 2W | 2D 2W | 3A 3W | 2D 2W | 4C 4W = 32,800
                     | 2(4C) 2W | 2YZ 2W | three f_sub W + 16,382 = 24,582
      the four adds  2V 2W | HHH+2V 2W | five f_sub (H, R, X3, V-X3, Y3)
      _verify_final  two f_sub;  f_eq one f_sub
      _f_neg         _BIAS_2P - y <= 16,382 (_glv_q_tables, _glv_dev_program)
    Observed here as well: every input the loose normaliser is handed
    while the point functions run on the largest weak rows."""
    assert int(dev._BIAS_2P.max()) == 16382
    assert max(4 * 8200, 8200 + 16382) < 1 << 16
    seen = []
    inner = dev._f_carry_loose_parallel

    def watched(limbs20):
        seen.append(int(np.asarray(limbs20).max()))
        return inner(limbs20)

    monkeypatch.setattr(dev, "_f_carry_loose_parallel", watched)
    big = jnp.asarray(np.vstack([_fill(19, 8200), _fill(1, 0x1FF)]))
    never = jnp.zeros((1, B), jnp.int32)
    pt = {"X": big, "Y": big, "Z": big, "inf": never}
    dev.pt_double(pt)
    dev._pt_add_mixed_cheap_u(pt, big, big, never, big)
    dev._pt_add_full_cheap_u(pt, pt)
    dev._verify_final(pt, never, never, big, big, never)
    dev._f_neg(big)
    finite = jnp.zeros((B,), bool)
    pt_b = dict(pt, inf=finite)  # the complete adds take bool masks
    dev.pt_add_mixed(pt_b, big, big, finite)
    dev.pt_add_full(pt_b, pt_b)
    assert len(seen) >= 60
    assert 3 * 8191 < max(seen) <= 4 * 8200


def test_parallel_carry_random():
    rng = np.random.default_rng(1)
    cols = rng.integers(0, 1 << 31, (39, B), dtype=np.uint32)
    out = np.asarray(dev.f_carry(jnp.asarray(cols)))
    for want, got in zip(_cols_value(cols), _unpack(out)):
        assert got % P == want % P


def test_parallel_mul_random_and_worst_case():
    rng = np.random.default_rng(2)
    va, vb = _vals(rng), _vals(rng)
    out = np.asarray(dev.f_mul(jnp.asarray(_pack(va)), jnp.asarray(_pack(vb))))
    for a, b_, got in zip(va, vb, _unpack(out)):
        assert got % P == (a * b_) % P
    # all limbs at the weak bound: products must not overflow u32 columns
    w = np.full((20, B), 8200, np.uint32)
    vw = dev.from_limbs_np(w[:, 0])
    out = np.asarray(dev.f_mul(jnp.asarray(w), jnp.asarray(w)))
    assert _unpack(out)[0] % P == (vw * vw) % P
    assert out.max() <= 10000


def test_parallel_mul_chain_maintains_discipline():
    """50 chained muls: magnitudes must stay multiply-safe forever."""
    rng = np.random.default_rng(3)
    va, vb = _vals(rng), _vals(rng)
    x, b_ = _pack(va), jnp.asarray(_pack(vb))
    want = list(va)
    for _ in range(50):
        x = np.asarray(dev.f_mul(jnp.asarray(x), b_))
        want = [(w * v) % P for w, v in zip(want, vb)]
        assert x.max() <= 10000
    assert [g % P for g in _unpack(x)] == want


def test_exact_norm_and_is_zero():
    rng = np.random.default_rng(4)
    vals = _vals(rng)
    vals[3] = 0
    vals[5] = P  # non-canonical zero (value == p)
    arr = jnp.asarray(_pack(vals))
    # weak-ify through a carry first (representation with eps limbs)
    weak = dev.f_carry(jnp.asarray(np.asarray(arr, np.uint32)))
    z = np.asarray(dev.f_is_zero(weak))
    assert list(z) == [v % P == 0 for v in vals]
    # exact normalization yields canonical 13-bit limbs
    exact = np.asarray(dev._exact_norm20(weak))
    assert exact.max() <= 0x1FFF
    for v, got in zip(vals, _unpack(exact)):
        assert got % P == v % P


def test_f_eq_parallel():
    rng = np.random.default_rng(6)
    va = _vals(rng)
    a = jnp.asarray(_pack(va))
    b_ = jnp.asarray(_pack(list(reversed(va))))
    eq = np.asarray(dev.f_eq(a, a))
    assert eq.all()
    neq = np.asarray(dev.f_eq(a, b_))
    expected = [x == y for x, y in zip(va, reversed(va))]
    assert list(neq) == expected
