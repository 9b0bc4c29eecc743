// Native scalar secp256k1 ECDSA verification — the CPU-side verify path
// promised by SURVEY §3.1's binding plan ("Pallas batch-verify kernel +
// C++ scalar fallback module", ref src/secp256k1/src/secp256k1.c:~340).
//
// Role in the framework: the TPU Pallas kernel (ops/secp256k1.py) is the
// block-validation batch path; THIS module is what ATMP's standard-flags
// verify, inline legacy checks, and small batches below the dispatch floor
// run on. The Python-int oracle (crypto/secp256k1.py) stays the consensus
// reference; tests/unit/test_native.py differentially checks this module
// against it on valid/invalid/edge vectors.
//
// Design (own derivation for a generic 64-bit host, not a port):
//   - 256-bit values as 4 x uint64 little-endian limbs; products via
//     __uint128_t schoolbook with explicit spill tracking.
//   - One generic Solinas-style reduction for BOTH moduli: p and n are
//     each 2^256 - K with a small K (33 bits for p, 129 bits for n), so
//     an 8-word product folds by repeatedly rewriting high*2^256 as
//     high*K. Four folds + conditional subtracts fully reduce.
//   - Inversions are Fermat powers (s^-1 = s^(n-2)); verification is not
//     side-channel sensitive, so no constant-time machinery (same stance
//     as the reference's _var verify paths).
//   - u1*G + u2*Q via Straus/Shamir with wNAF digits: w=7 fixed affine
//     table for G (32 odd multiples, built once), w=5 Jacobian table for
//     Q (8 odd multiples per verify).
//   - The final x-coordinate check avoids any field inversion:
//     accept iff X == r*Z^2 or (r + n < p and X == (r+n)*Z^2), exactly
//     the oracle's (x_R - r) % n == 0 acceptance set.

#include <cstdint>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#include "common.h"

namespace {

typedef uint64_t u64;
typedef unsigned __int128 u128;

struct N256 {
    u64 d[4];
};

// p = 2^256 - 0x1000003D1
static const N256 P_M = {{0xFFFFFFFEFFFFFC2FULL, 0xFFFFFFFFFFFFFFFFULL,
                          0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL}};
static const u64 P_K[3] = {0x1000003D1ULL, 0, 0};
// n (group order) = 2^256 - 0x14551231950B75FC4402DA1732FC9BEBF
static const N256 N_M = {{0xBFD25E8CD0364141ULL, 0xBAAEDCE6AF48A03BULL,
                          0xFFFFFFFFFFFFFFFEULL, 0xFFFFFFFFFFFFFFFFULL}};
static const u64 N_K[3] = {0x402DA1732FC9BEBFULL, 0x4551231950B75FC4ULL, 1};

static const N256 GX_C = {{0x59F2815B16F81798ULL, 0x029BFCDB2DCE28D9ULL,
                           0x55A06295CE870B07ULL, 0x79BE667EF9DCBBACULL}};
static const N256 GY_C = {{0x9C47D08FFB10D4B8ULL, 0xFD17B448A6855419ULL,
                           0x5DA4FBFC0E1108A8ULL, 0x483ADA7726A3C465ULL}};
static const N256 ONE_C = {{1, 0, 0, 0}};

static inline int cmp_n(const N256& a, const N256& b) {
    for (int i = 3; i >= 0; i--) {
        if (a.d[i] < b.d[i]) return -1;
        if (a.d[i] > b.d[i]) return 1;
    }
    return 0;
}

static inline bool is_zero_n(const N256& a) {
    return (a.d[0] | a.d[1] | a.d[2] | a.d[3]) == 0;
}

static inline u64 add_n(N256& a, const N256& b) {
    u128 c = 0;
    for (int i = 0; i < 4; i++) {
        c += (u128)a.d[i] + b.d[i];
        a.d[i] = (u64)c;
        c >>= 64;
    }
    return (u64)c;
}

static inline u64 sub_n(N256& a, const N256& b) {
    u128 borrow = 0;
    for (int i = 0; i < 4; i++) {
        u128 t = (u128)a.d[i] - b.d[i] - borrow;
        a.d[i] = (u64)t;
        borrow = (t >> 64) & 1;
    }
    return (u64)borrow;
}

// 4x4 schoolbook by diagonals. Column sums of four 128-bit products can
// exceed u128; `spill` counts wraparounds and re-enters at +2^64 of the
// shifted carry.
static void mul_wide(const N256& a, const N256& b, u64 out[8]) {
    u128 acc = 0;
    u64 spill = 0;
    for (int k = 0; k < 7; k++) {
        int lo = k >= 4 ? k - 3 : 0;
        int hi = k < 4 ? k : 3;
        for (int i = lo; i <= hi; i++) {
            u128 pr = (u128)a.d[i] * b.d[k - i];
            acc += pr;
            if (acc < pr) spill++;
        }
        out[k] = (u64)acc;
        acc = (acc >> 64) + ((u128)spill << 64);
        spill = 0;
    }
    out[7] = (u64)acc;
}

// Squaring: off-diagonal products doubled (10 muls instead of 16).
static void sqr_wide(const N256& a, u64 out[8]) {
    u128 acc = 0;
    u64 spill = 0;
    for (int k = 0; k < 7; k++) {
        int lo = k >= 4 ? k - 3 : 0;
        for (int i = lo; 2 * i < k; i++) {
            u128 pr = (u128)a.d[i] * a.d[k - i];
            acc += pr;
            if (acc < pr) spill++;
            acc += pr;
            if (acc < pr) spill++;
        }
        if ((k & 1) == 0) {
            u128 pr = (u128)a.d[k / 2] * a.d[k / 2];
            acc += pr;
            if (acc < pr) spill++;
        }
        out[k] = (u64)acc;
        acc = (acc >> 64) + ((u128)spill << 64);
        spill = 0;
    }
    out[7] = (u64)acc;
}

// Fold an 8-word product to a canonical 4-word residue mod m = 2^256 - K.
// Each round rewrites words 4..7 (value H) as H*K added to the low part;
// magnitudes shrink fast (K <= 2^129), four rounds always suffice, then at
// most two conditional subtracts.
static void reduce_wide(u64 l[8], const u64 K[3], const N256& m, N256& out) {
    for (int round = 0; round < 4; round++) {
        u64 hi[4] = {l[4], l[5], l[6], l[7]};
        if ((hi[0] | hi[1] | hi[2] | hi[3]) == 0) break;
        l[4] = l[5] = l[6] = l[7] = 0;
        u64 prod[8] = {0, 0, 0, 0, 0, 0, 0, 0};
        for (int i = 0; i < 4; i++) {
            u128 carry = 0;
            for (int j = 0; j < 3; j++) {
                u128 cur = (u128)prod[i + j] + (u128)hi[i] * K[j] + carry;
                prod[i + j] = (u64)cur;
                carry = cur >> 64;
            }
            for (int k = i + 3; carry; k++) {
                u128 cur = (u128)prod[k] + carry;
                prod[k] = (u64)cur;
                carry = cur >> 64;
            }
        }
        u128 c = 0;
        for (int i = 0; i < 8; i++) {
            c += (u128)l[i] + prod[i];
            l[i] = (u64)c;
            c >>= 64;
        }
    }
    memcpy(out.d, l, 32);
    while (cmp_n(out, m) >= 0) sub_n(out, m);
}

static void modmul(const N256& a, const N256& b, const u64 K[3],
                   const N256& m, N256& out) {
    u64 w[8];
    mul_wide(a, b, w);
    reduce_wide(w, K, m, out);
}

static void modpow(const N256& base, const N256& exp, const u64 K[3],
                   const N256& m, N256& out) {
    // 4-bit fixed-window square-and-multiply: 14 precompute muls + 252
    // squarings + <=63 window muls (~330 modmuls) vs the plain ladder's
    // ~480 for the high-hamming-weight exponents this module actually
    // raises to ((p+1)/4 sqrt, n-2 / p-2 inverses) — the per-signature
    // host cost of pubkey decompression and scalar inversion.
    N256 tbl[16];
    tbl[1] = base;
    for (int i = 2; i < 16; i++) modmul(tbl[i - 1], base, K, m, tbl[i]);
    N256 acc = ONE_C;
    bool started = false;
    for (int i = 63; i >= 0; i--) {
        int nib = int((exp.d[i >> 4] >> ((i & 15) * 4)) & 0xF);
        if (!started) {
            if (nib == 0) continue;
            acc = tbl[nib];
            started = true;
            continue;
        }
        for (int k = 0; k < 4; k++) modmul(acc, acc, K, m, acc);
        if (nib) modmul(acc, tbl[nib], K, m, acc);
    }
    out = acc;
}

// ---- field ops mod p (inputs/outputs always canonical, < p) ----

static inline void fmul(N256& r, const N256& a, const N256& b) {
    u64 w[8];
    mul_wide(a, b, w);
    reduce_wide(w, P_K, P_M, r);
}

static inline void fsqr(N256& r, const N256& a) {
    u64 w[8];
    sqr_wide(a, w);
    reduce_wide(w, P_K, P_M, r);
}

static inline void fadd(N256& r, const N256& a, const N256& b) {
    r = a;
    u64 c = add_n(r, b);
    if (c || cmp_n(r, P_M) >= 0) sub_n(r, P_M);
}

static inline void fsub(N256& r, const N256& a, const N256& b) {
    r = a;
    if (sub_n(r, b)) add_n(r, P_M);
}

static inline void fneg(N256& r, const N256& a) {
    N256 v = a;  // r may alias a
    if (is_zero_n(v)) {
        r = v;
    } else {
        r = P_M;
        sub_n(r, v);
    }
}

// ---- point arithmetic (Jacobian; y^2 = x^3 + 7) ----

struct Jac {
    N256 X, Y, Z;
    bool inf;
};

struct Aff {
    N256 x, y;
};

// dbl-2009-l (a = 0). secp256k1 has no 2-torsion, so Y = 0 never occurs
// for a finite on-curve point and doubling stays finite.
static void pt_double(Jac& r, const Jac& p) {
    if (p.inf) {
        r = p;
        return;
    }
    N256 A, B, C, D, E, F, t, X3, Y3, Z3;
    fsqr(A, p.X);
    fsqr(B, p.Y);
    fsqr(C, B);
    fadd(t, p.X, B);
    fsqr(t, t);
    fsub(t, t, A);
    fsub(t, t, C);
    fadd(D, t, t);
    fadd(E, A, A);
    fadd(E, E, A);
    fsqr(F, E);
    fadd(t, D, D);
    fsub(X3, F, t);
    fsub(t, D, X3);
    fmul(Y3, E, t);
    fadd(t, C, C);
    fadd(t, t, t);
    fadd(t, t, t);  // 8C
    fsub(Y3, Y3, t);
    fmul(Z3, p.Y, p.Z);
    fadd(Z3, Z3, Z3);
    r.X = X3;
    r.Y = Y3;
    r.Z = Z3;
    r.inf = false;
}

// madd-2007-bl: Jacobian P + affine Q, with the complete case analysis
// (P = inf -> Q, same -> double, opposite -> infinity) done by branch —
// the branchless select dance of the TPU kernel is unnecessary on a CPU.
static void pt_add_mixed(Jac& r, const Jac& p, const Aff& q) {
    if (p.inf) {
        r.X = q.x;
        r.Y = q.y;
        r.Z = ONE_C;
        r.inf = false;
        return;
    }
    N256 Z1Z1, U2, S2, H, R, HH, HHH, V, t, X3, Y3, Z3;
    fsqr(Z1Z1, p.Z);
    fmul(U2, q.x, Z1Z1);
    fmul(t, p.Z, Z1Z1);
    fmul(S2, q.y, t);
    fsub(H, U2, p.X);
    fsub(R, S2, p.Y);
    if (is_zero_n(H)) {
        if (is_zero_n(R)) {
            pt_double(r, p);
        } else {
            r.inf = true;
        }
        return;
    }
    fsqr(HH, H);
    fmul(HHH, H, HH);
    fmul(V, p.X, HH);
    fsqr(X3, R);
    fsub(X3, X3, HHH);
    fadd(t, V, V);
    fsub(X3, X3, t);
    fsub(t, V, X3);
    fmul(Y3, R, t);
    fmul(t, p.Y, HHH);
    fsub(Y3, Y3, t);
    fmul(Z3, p.Z, H);
    r.X = X3;
    r.Y = Y3;
    r.Z = Z3;
    r.inf = false;
}

// Full Jacobian + Jacobian add (add-2007-bl shape).
static void pt_add(Jac& r, const Jac& p, const Jac& q) {
    if (p.inf) {
        r = q;
        return;
    }
    if (q.inf) {
        r = p;
        return;
    }
    N256 Z1Z1, Z2Z2, U1, U2, S1, S2, H, R, HH, HHH, V, t, X3, Y3, Z3;
    fsqr(Z1Z1, p.Z);
    fsqr(Z2Z2, q.Z);
    fmul(U1, p.X, Z2Z2);
    fmul(U2, q.X, Z1Z1);
    fmul(t, q.Z, Z2Z2);
    fmul(S1, p.Y, t);
    fmul(t, p.Z, Z1Z1);
    fmul(S2, q.Y, t);
    fsub(H, U2, U1);
    fsub(R, S2, S1);
    if (is_zero_n(H)) {
        if (is_zero_n(R)) {
            pt_double(r, p);
        } else {
            r.inf = true;
        }
        return;
    }
    fsqr(HH, H);
    fmul(HHH, H, HH);
    fmul(V, U1, HH);
    fsqr(X3, R);
    fsub(X3, X3, HHH);
    fadd(t, V, V);
    fsub(X3, X3, t);
    fsub(t, V, X3);
    fmul(Y3, R, t);
    fmul(t, S1, HHH);
    fsub(Y3, Y3, t);
    fmul(t, p.Z, q.Z);
    fmul(Z3, t, H);
    r.X = X3;
    r.Y = Y3;
    r.Z = Z3;
    r.inf = false;
}

// ---- wNAF recoding ----
// Digits are 0 or odd in [-(2^(w-1)-1), 2^(w-1)-1]; at most 257 of them.

static int wnaf_recode(const N256& s, int w, int8_t out[260]) {
    u64 d[4] = {s.d[0], s.d[1], s.d[2], s.d[3]};
    int pos = 0;
    const u64 mask = (1u << w) - 1;
    while (d[0] | d[1] | d[2] | d[3]) {
        int8_t digit = 0;
        if (d[0] & 1) {
            int word = (int)(d[0] & mask);
            if (word >= (1 << (w - 1))) word -= (1 << w);
            digit = (int8_t)word;
            if (word > 0) {
                u128 borrow = (u128)(u64)word;
                for (int i = 0; i < 4 && borrow; i++) {
                    u128 t = (u128)d[i] - borrow;
                    d[i] = (u64)t;
                    borrow = (t >> 64) & 1;
                }
            } else {
                u128 carry = (u128)(u64)(-word);
                for (int i = 0; i < 4 && carry; i++) {
                    carry += d[i];
                    d[i] = (u64)carry;
                    carry >>= 64;
                }
            }
        }
        out[pos++] = digit;
        d[0] = (d[0] >> 1) | (d[1] << 63);
        d[1] = (d[1] >> 1) | (d[2] << 63);
        d[2] = (d[2] >> 1) | (d[3] << 63);
        d[3] >>= 1;
    }
    return pos;
}

// ---- fixed-base G table (w=7: odd multiples 1G..63G, affine) ----

static Aff g_tab[32];
static std::once_flag g_tab_once;

static void build_g_tab() {
    Jac j[32];
    j[0].X = GX_C;
    j[0].Y = GY_C;
    j[0].Z = ONE_C;
    j[0].inf = false;
    Jac g2;
    pt_double(g2, j[0]);
    for (int i = 1; i < 32; i++) pt_add(j[i], j[i - 1], g2);
    // one-time naive affine conversion (Fermat inverse per entry)
    N256 pm2 = P_M;
    pm2.d[0] -= 2;
    for (int i = 0; i < 32; i++) {
        N256 zi, zi2, zi3;
        modpow(j[i].Z, pm2, P_K, P_M, zi);
        fsqr(zi2, zi);
        fmul(zi3, zi2, zi);
        fmul(g_tab[i].x, j[i].X, zi2);
        fmul(g_tab[i].y, j[i].Y, zi3);
    }
}

// acc = u1*G + u2*Q (Straus, wNAF); false where it is the point at infinity
static bool ecmult(const N256& u1, const N256& u2, const Aff& Q, Jac& acc) {
    std::call_once(g_tab_once, build_g_tab);

    // per-verify w=5 table of odd Q multiples (1Q, 3Q, ..., 15Q)
    Jac q_tab[8];
    q_tab[0].X = Q.x;
    q_tab[0].Y = Q.y;
    q_tab[0].Z = ONE_C;
    q_tab[0].inf = false;
    Jac q2;
    pt_double(q2, q_tab[0]);
    for (int i = 1; i < 8; i++) pt_add(q_tab[i], q_tab[i - 1], q2);

    int8_t w1[260], w2[260];
    int l1 = wnaf_recode(u1, 7, w1);
    int l2 = wnaf_recode(u2, 5, w2);
    int len = l1 > l2 ? l1 : l2;

    acc.inf = true;
    for (int i = len - 1; i >= 0; i--) {
        pt_double(acc, acc);
        if (i < l1 && w1[i]) {
            int dg = w1[i];
            if (dg > 0) {
                pt_add_mixed(acc, acc, g_tab[(dg - 1) >> 1]);
            } else {
                Aff neg = g_tab[(-dg - 1) >> 1];
                fneg(neg.y, neg.y);
                pt_add_mixed(acc, acc, neg);
            }
        }
        if (i < l2 && w2[i]) {
            int dg = w2[i];
            if (dg > 0) {
                pt_add(acc, acc, q_tab[(dg - 1) >> 1]);
            } else {
                Jac neg = q_tab[(-dg - 1) >> 1];
                fneg(neg.Y, neg.Y);
                pt_add(acc, acc, neg);
            }
        }
    }
    return !(acc.inf || is_zero_n(acc.Z));
}

// ---- u1*G + u2*Q with the r / r+n x-coordinate acceptance check ----

static bool ecmult_check(const N256& u1, const N256& u2, const Aff& Q,
                         const N256& r_sig) {
    Jac acc;
    if (!ecmult(u1, u2, Q, acc)) return false;

    // x_R == r (mod n) without inverting Z: X == r*Z^2, or the wraparound
    // candidate X == (r+n)*Z^2 admissible only when r + n < p.
    N256 zz, cand;
    fsqr(zz, acc.Z);
    fmul(cand, r_sig, zz);
    if (cmp_n(cand, acc.X) == 0) return true;
    N256 rn = r_sig;
    u64 carry = add_n(rn, N_M);
    if (!carry && cmp_n(rn, P_M) < 0) {
        fmul(cand, rn, zz);
        if (cmp_n(cand, acc.X) == 0) return true;
    }
    return false;
}

static inline N256 load_be(const uint8_t* p) {
    N256 out;
    for (int i = 0; i < 4; i++) {
        u64 v = 0;
        for (int j = 0; j < 8; j++) v = (v << 8) | p[8 * (3 - i) + j];
        out.d[i] = v;
    }
    return out;
}

static inline void store_be(const N256& v, uint8_t* p) {
    for (int i = 0; i < 4; i++)
        for (int j = 0; j < 8; j++)
            p[8 * (3 - i) + j] = (uint8_t)(v.d[i] >> (56 - 8 * j));
}

// Full single verify. Matches crypto/secp256k1.py ecdsa_verify on every
// reachable input (pubkeys arrive pre-validated from pubkey_parse; the
// on-curve check here is defense in depth, not a semantic difference).
static bool verify_one(const uint8_t pub[64], const uint8_t rs[64],
                       const uint8_t msg[32]) {
    N256 qx = load_be(pub), qy = load_be(pub + 32);
    if (cmp_n(qx, P_M) >= 0 || cmp_n(qy, P_M) >= 0) return false;
    N256 y2, x3, seven = {{7, 0, 0, 0}};
    fsqr(y2, qy);
    fsqr(x3, qx);
    fmul(x3, x3, qx);
    fadd(x3, x3, seven);
    if (cmp_n(y2, x3) != 0) return false;

    N256 r = load_be(rs), s = load_be(rs + 32), e = load_be(msg);
    if (is_zero_n(r) || cmp_n(r, N_M) >= 0) return false;
    if (is_zero_n(s) || cmp_n(s, N_M) >= 0) return false;
    if (cmp_n(e, N_M) >= 0) sub_n(e, N_M);  // e < 2^256 < 2n: one subtract

    N256 nm2 = N_M;
    nm2.d[0] -= 2;
    N256 w, u1, u2;
    modpow(s, nm2, N_K, N_M, w);  // w = s^-1 mod n
    modmul(e, w, N_K, N_M, u1);
    modmul(r, w, N_K, N_M, u2);
    Aff Q = {qx, qy};
    return ecmult_check(u1, u2, Q, r);
}

static void run_chunked(long n, int nthreads, void (*fn)(long, long, void*),
                        void* ctx) {
    if (nthreads <= 0) {
        unsigned hw = std::thread::hardware_concurrency();
        nthreads = hw ? (int)hw : 1;
    }
    if ((long)nthreads > n) nthreads = (int)(n > 0 ? n : 1);
    if (nthreads <= 1) {
        fn(0, n, ctx);
        return;
    }
    std::vector<std::thread> threads;
    long per = (n + nthreads - 1) / nthreads;
    for (int t = 0; t < nthreads; t++) {
        long lo = t * per;
        long hi = lo + per < n ? lo + per : n;
        if (lo >= hi) break;
        threads.emplace_back(fn, lo, hi, ctx);
    }
    for (auto& th : threads) th.join();
}

struct VerifyCtx {
    const uint8_t* pub;
    const uint8_t* rs;
    const uint8_t* msg;
    uint8_t* ok;
};

struct PrecompCtx {
    const uint8_t* rs;
    const uint8_t* msg;
    uint8_t* u1;
    uint8_t* u2;
    uint8_t* ok;
};

static void verify_range(long lo, long hi, void* p) {
    VerifyCtx* c = (VerifyCtx*)p;
    for (long i = lo; i < hi; i++)
        c->ok[i] = verify_one(c->pub + 64 * i, c->rs + 64 * i,
                              c->msg + 32 * i)
                       ? 1
                       : 0;
}

static void precompute_range(long lo, long hi, void* p) {
    // Montgomery batch inversion: ONE Fermat inversion for the whole
    // chunk plus 3 multiplications per element (prefix products, invert
    // the total, unwind) — vs a ~384-modmul modpow per signature. Range-
    // invalid s values are substituted with 1 to keep the running product
    // invertible; their lanes are flagged ok=0 and never trusted.
    PrecompCtx* c = (PrecompCtx*)p;
    long n = hi - lo;
    if (n <= 0) return;
    std::vector<N256> s_eff((size_t)n), prefix((size_t)n);
    N256 nm2 = N_M;
    nm2.d[0] -= 2;
    for (long i = 0; i < n; i++) {
        N256 r = load_be(c->rs + 64 * (lo + i));
        N256 s = load_be(c->rs + 64 * (lo + i) + 32);
        bool bad = is_zero_n(s) || cmp_n(s, N_M) >= 0 || is_zero_n(r) ||
                   cmp_n(r, N_M) >= 0;
        c->ok[lo + i] = bad ? 0 : 1;
        s_eff[(size_t)i] = bad ? ONE_C : s;
        if (i == 0) {
            prefix[0] = s_eff[0];
        } else {
            modmul(prefix[(size_t)i - 1], s_eff[(size_t)i], N_K, N_M,
                   prefix[(size_t)i]);
        }
    }
    N256 inv_run;
    modpow(prefix[(size_t)n - 1], nm2, N_K, N_M, inv_run);
    for (long i = n - 1; i >= 0; i--) {
        N256 w;
        if (i == 0) {
            w = inv_run;
        } else {
            modmul(inv_run, prefix[(size_t)i - 1], N_K, N_M, w);
            modmul(inv_run, s_eff[(size_t)i], N_K, N_M, inv_run);
        }
        long idx = lo + i;
        if (!c->ok[idx]) {
            memset(c->u1 + 32 * idx, 0, 32);
            memset(c->u2 + 32 * idx, 0, 32);
            continue;
        }
        N256 r = load_be(c->rs + 64 * idx);
        N256 e = load_be(c->msg + 32 * idx);
        if (cmp_n(e, N_M) >= 0) sub_n(e, N_M);
        N256 u1, u2;
        modmul(e, w, N_K, N_M, u1);
        modmul(r, w, N_K, N_M, u2);
        store_be(u1, c->u1 + 32 * idx);
        store_be(u2, c->u2 + 32 * idx);
    }
}

// k*G as affine x and, where asked for, y (mod p), via the fixed wNAF G
// table. Returns false for k = 0 / k >= n or if the ladder lands at
// infinity (unreachable for valid k, kept for safety).
static bool base_mult_affine(const N256& k, N256& x_out, N256* y_out) {
    std::call_once(g_tab_once, build_g_tab);
    if (is_zero_n(k) || cmp_n(k, N_M) >= 0) return false;
    int8_t w1[260];
    int l1 = wnaf_recode(k, 7, w1);
    Jac acc;
    acc.inf = true;
    for (int i = l1 - 1; i >= 0; i--) {
        pt_double(acc, acc);
        if (w1[i]) {
            int dg = w1[i];
            if (dg > 0) {
                pt_add_mixed(acc, acc, g_tab[(dg - 1) >> 1]);
            } else {
                Aff neg = g_tab[(-dg - 1) >> 1];
                fneg(neg.y, neg.y);
                pt_add_mixed(acc, acc, neg);
            }
        }
    }
    if (acc.inf || is_zero_n(acc.Z)) return false;
    N256 pm2 = P_M, zi, zi2;
    pm2.d[0] -= 2;
    modpow(acc.Z, pm2, P_K, P_M, zi);
    fsqr(zi2, zi);
    fmul(x_out, acc.X, zi2);
    if (y_out) {
        fmul(zi2, zi2, zi);
        fmul(*y_out, acc.Y, zi2);
    }
    return true;
}

static bool base_mult_affine_x(const N256& k, N256& x_out) {
    return base_mult_affine(k, x_out, nullptr);
}

// ---- BCH Schnorr (spec 2019-05-15-schnorr.md; the oracle is
// crypto/secp256k1.py schnorr_verify / schnorr_sign) ----

// (p - 1) / 2: Euler's criterion, jacobi(a) = a^((p-1)/2)
static const N256 P_HALF = {{0xFFFFFFFF7FFFFE17ULL, 0xFFFFFFFFFFFFFFFFULL,
                             0xFFFFFFFFFFFFFFFFULL, 0x7FFFFFFFFFFFFFFFULL}};

static bool is_residue(const N256& a) {
    N256 pw;
    modpow(a, P_HALF, P_K, P_M, pw);
    return cmp_n(pw, ONE_C) == 0;
}

// u2 = (n - e) mod n for e = SHA256(r32 || compressed(P) || m32) mod n,
// 32 bytes big-endian: the scalar of P in R' = s*G + (n - e)*P.
static void schnorr_neg_challenge(const uint8_t r32[32],
                                  const uint8_t pub64[64],
                                  const uint8_t m32[32], uint8_t out32[32]) {
    uint8_t buf[97], h[32];
    memcpy(buf, r32, 32);
    buf[32] = 2 | (pub64[63] & 1);
    memcpy(buf + 33, pub64, 32);
    memcpy(buf + 65, m32, 32);
    bcpn::sha256(buf, 97, h);
    N256 e = load_be(h);
    if (cmp_n(e, N_M) >= 0) sub_n(e, N_M);
    N256 u2 = {{0, 0, 0, 0}};
    if (!is_zero_n(e)) {
        u2 = N_M;
        sub_n(u2, e);
    }
    store_be(u2, out32);
}

// r < p and s < n: the spec's range rule (0 is in range for both)
static bool schnorr_in_range(const uint8_t rs[64]) {
    N256 r = load_be(rs), s = load_be(rs + 32);
    return cmp_n(r, P_M) < 0 && cmp_n(s, N_M) < 0;
}

static bool schnorr_verify_one(const uint8_t pub[64], const uint8_t rs[64],
                               const uint8_t msg[32]) {
    N256 qx = load_be(pub), qy = load_be(pub + 32);
    if (cmp_n(qx, P_M) >= 0 || cmp_n(qy, P_M) >= 0) return false;
    N256 y2, x3, seven = {{7, 0, 0, 0}};
    fsqr(y2, qy);
    fsqr(x3, qx);
    fmul(x3, x3, qx);
    fadd(x3, x3, seven);
    if (cmp_n(y2, x3) != 0 || !schnorr_in_range(rs)) return false;
    uint8_t u2b[32];
    schnorr_neg_challenge(rs, pub, msg, u2b);
    Aff Q = {qx, qy};
    Jac acc;
    if (!ecmult(load_be(rs + 32), load_be(u2b), Q, acc)) return false;
    // R'.x == r as X == r*Z^2; jacobi(R'.y) = jacobi(Y/Z^3) = jacobi(Y*Z)
    N256 zz, cand, yz;
    fsqr(zz, acc.Z);
    fmul(cand, load_be(rs), zz);
    if (cmp_n(cand, acc.X) != 0) return false;
    fmul(yz, acc.Y, acc.Z);
    return is_residue(yz);
}

static void schnorr_verify_range(long lo, long hi, void* p) {
    VerifyCtx* c = (VerifyCtx*)p;
    for (long i = lo; i < hi; i++)
        c->ok[i] = schnorr_verify_one(c->pub + 64 * i, c->rs + 64 * i,
                                      c->msg + 32 * i)
                       ? 1
                       : 0;
}

struct ChallengeCtx {
    const uint8_t* pub;
    const uint8_t* rs;
    const uint8_t* msg;
    uint8_t* u2;
    uint8_t* ok;
};

static void schnorr_challenge_range(long lo, long hi, void* p) {
    ChallengeCtx* c = (ChallengeCtx*)p;
    for (long i = lo; i < hi; i++) {
        c->ok[i] = schnorr_in_range(c->rs + 64 * i) ? 1 : 0;
        schnorr_neg_challenge(c->rs + 64 * i, c->pub + 64 * i,
                              c->msg + 32 * i, c->u2 + 32 * i);
    }
}

}  // namespace

extern "C" {

// ECDSA sign with a caller-supplied nonce (the RFC6979 derivation stays in
// Python so signatures are bit-identical to the oracle signer — HMAC cost
// is microseconds; the EC math here is what was slow). Writes r||s (32-byte
// big-endian each) with low-s normalization. Returns 1, or 0 when the
// caller must retry with the next nonce (r == 0 or s == 0) or inputs are
// out of range.
int bcp_ecdsa_sign(const uint8_t* sk32, const uint8_t* e32,
                   const uint8_t* k32, uint8_t* rs64_out) {
    N256 sk = load_be(sk32), e = load_be(e32), k = load_be(k32);
    if (is_zero_n(sk) || cmp_n(sk, N_M) >= 0) return 0;
    if (cmp_n(e, N_M) >= 0) sub_n(e, N_M);
    N256 x;
    if (!base_mult_affine_x(k, x)) return 0;
    N256 r = x;
    while (cmp_n(r, N_M) >= 0) sub_n(r, N_M);
    if (is_zero_n(r)) return 0;
    // s = k^-1 (e + r*sk) mod n
    N256 nm2 = N_M, kinv, rd, sum, s;
    nm2.d[0] -= 2;
    modpow(k, nm2, N_K, N_M, kinv);
    modmul(r, sk, N_K, N_M, rd);
    sum = e;
    if (add_n(sum, rd) || cmp_n(sum, N_M) >= 0) sub_n(sum, N_M);
    modmul(kinv, sum, N_K, N_M, s);
    if (is_zero_n(s)) return 0;
    // low-s: if s > n/2, s = n - s  (n odd: n/2 rounds down, so the
    // comparison s*2 > n is exact via add-with-carry)
    N256 s2 = s;
    u64 c = add_n(s2, s);
    if (c || cmp_n(s2, N_M) > 0) {
        N256 ns = N_M;
        sub_n(ns, s);
        s = ns;
    }
    store_be(r, rs64_out);
    store_be(s, rs64_out + 32);
    return 1;
}

// Single ECDSA verify: pub = 64-byte x||y (32-byte big-endian each),
// rs = 64-byte r||s, msg = 32-byte message hash. Returns 1 valid / 0 not.
int bcp_ecdsa_verify(const uint8_t* pub, const uint8_t* rs,
                     const uint8_t* msg) {
    return verify_one(pub, rs, msg) ? 1 : 0;
}

// Batch verify across nthreads host threads (nthreads <= 0: one per core).
void bcp_ecdsa_verify_batch(const uint8_t* pub, const uint8_t* rs,
                            const uint8_t* msg, long n, uint8_t* ok,
                            int nthreads) {
    VerifyCtx c = {pub, rs, msg, ok};
    run_chunked(n, nthreads, verify_range, &c);
}

// Scalar precomputation for the TPU batch packer: per signature computes
// u1 = e * s^-1 mod n and u2 = r * s^-1 mod n (32-byte big-endian out).
// ok[i] = 0 flags out-of-range r/s (caller must not trust u1/u2 there).
void bcp_ecdsa_precompute(const uint8_t* rs, const uint8_t* msg, long n,
                          uint8_t* u1, uint8_t* u2, uint8_t* ok,
                          int nthreads) {
    PrecompCtx c = {rs, msg, u1, u2, ok};
    run_chunked(n, nthreads, precompute_range, &c);
}

// The Schnorr lane's scalars for the device's GLV ladder (ops/ecdsa_batch
// pack_lanes): u1 is s as it stands, u2[i] = (n - e_i) mod n with e_i the
// challenge hash over (r_i, compressed P_i, m_i). ok[i] = 0 flags r >= p or
// s >= n (the spec refuses them before any arithmetic). No modular inverse:
// lighter than bcp_ecdsa_precompute.
void bcp_schnorr_challenge(const uint8_t* pub, const uint8_t* rs,
                           const uint8_t* msg, long n, uint8_t* u2,
                           uint8_t* ok, int nthreads) {
    ChallengeCtx c = {pub, rs, msg, u2, ok};
    run_chunked(n, nthreads, schnorr_challenge_range, &c);
}

// The one lane's form of the above, for native/connect.cpp's scan threads.
void bcp_schnorr_neg_challenge(const uint8_t* r32, const uint8_t* pub64,
                               const uint8_t* m32, uint8_t* out32) {
    schnorr_neg_challenge(r32, pub64, m32, out32);
}

// BCH Schnorr batch verify across nthreads host threads: the rung under the
// device program, and the re-check of its degenerate lanes.
void bcp_schnorr_verify_batch(const uint8_t* pub, const uint8_t* rs,
                              const uint8_t* msg, long n, uint8_t* ok,
                              int nthreads) {
    VerifyCtx c = {pub, rs, msg, ok};
    run_chunked(n, nthreads, schnorr_verify_range, &c);
}

// BCH Schnorr sign with a caller-supplied nonce (the RFC6979 derivation with
// "Schnorr+SHA256  " stays in Python, as for bcp_ecdsa_sign): R = k*G, k
// negated where jacobi(R.y) != 1, r = R.x, s = k + e*sk mod n. Writes r||s.
// Returns 1, or 0 where sk or k is out of range.
int bcp_schnorr_sign(const uint8_t* sk32, const uint8_t* m32,
                     const uint8_t* k32, uint8_t* rs64_out) {
    N256 sk = load_be(sk32), k = load_be(k32);
    if (is_zero_n(sk) || cmp_n(sk, N_M) >= 0) return 0;
    N256 rx, ry, px, py;
    if (!base_mult_affine(k, rx, &ry) || !base_mult_affine(sk, px, &py))
        return 0;
    if (!is_residue(ry)) {
        N256 nk = N_M;
        sub_n(nk, k);
        k = nk;
    }
    uint8_t pub[64], r32[32], u2b[32];
    store_be(px, pub);
    store_be(py, pub + 32);
    store_be(rx, r32);
    schnorr_neg_challenge(r32, pub, m32, u2b);
    // s = k + e*sk = k - (n - e)*sk mod n
    N256 prod, s = k;
    modmul(load_be(u2b), sk, N_K, N_M, prod);
    if (cmp_n(s, prod) < 0) add_n(s, N_M);
    sub_n(s, prod);
    memcpy(rs64_out, r32, 32);
    store_be(s, rs64_out + 32);
    return 1;
}

// Pubkey parse/decompress (CPubKey / secp256k1_ec_pubkey_parse semantics,
// matching crypto/secp256k1.pubkey_parse): 33-byte 02/03 compressed,
// 65-byte 04 uncompressed or 06/07 hybrid (hybrid requires matching y
// parity). Writes affine x||y (32-byte big-endian each); returns 1 ok,
// 0 malformed/off-curve.
int bcp_pubkey_parse(const uint8_t* data, long len, uint8_t* out64) {
    if (len == 33 && (data[0] == 2 || data[0] == 3)) {
        N256 x = load_be(data + 1);
        if (cmp_n(x, P_M) >= 0) return 0;
        // y^2 = x^3 + 7; sqrt via pow((p+1)/4) — p = 3 mod 4
        N256 y2, x3, seven = {{7, 0, 0, 0}};
        fsqr(x3, x);
        fmul(x3, x3, x);
        fadd(y2, x3, seven);
        // (p+1)/4
        static const N256 P14 = {{0xFFFFFFFFBFFFFF0CULL, 0xFFFFFFFFFFFFFFFFULL,
                                  0xFFFFFFFFFFFFFFFFULL, 0x3FFFFFFFFFFFFFFFULL}};
        N256 y;
        modpow(y2, P14, P_K, P_M, y);
        N256 chk;
        fsqr(chk, y);
        if (cmp_n(chk, y2) != 0) return 0;  // non-residue: off-curve x
        if ((y.d[0] & 1) != (data[0] & 1)) {
            N256 ny;
            fneg(ny, y);
            y = ny;
        }
        store_be(x, out64);
        store_be(y, out64 + 32);
        return 1;
    }
    if (len == 65 && (data[0] == 4 || data[0] == 6 || data[0] == 7)) {
        N256 x = load_be(data + 1), y = load_be(data + 33);
        if (cmp_n(x, P_M) >= 0 || cmp_n(y, P_M) >= 0) return 0;
        if ((data[0] == 6 || data[0] == 7) && (y.d[0] & 1) != (data[0] & 1))
            return 0;
        N256 y2, x3, seven = {{7, 0, 0, 0}};
        fsqr(y2, y);
        fsqr(x3, x);
        fmul(x3, x3, x);
        fadd(x3, x3, seven);
        if (cmp_n(y2, x3) != 0) return 0;
        store_be(x, out64);
        store_be(y, out64 + 32);
        return 1;
    }
    return 0;
}

}  // extern "C"
