// The UTXO-set accumulator's bulk arithmetic (store/muhash.py is the
// specification and stays the reference): hash-to-group of coin rows
// (SHAKE256 to 384 bytes, little-endian, reduced mod p, 0 -> 1) and products
// mod p = 2^3072 - 1103717, across host threads. A flush of the coins
// store multiplies one element a changed row into its shard's accumulator:
// Python's big integers spend ~27 us an element there, almost all of it in
// the division; 48 64-bit limbs and the fold 2^3072 = c (mod p) spend ~1.

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <thread>
#include <vector>

namespace {

typedef unsigned __int128 u128;

const int L = 48;            // 3072 bits in 64-bit limbs, little-endian
const uint64_t C = 1103717;  // 2^3072 - p

struct Num { uint64_t d[L]; };

// x in [0, 2^3072) -> x mod p: one subtraction, since 2^3072 < 2p.
void canonical(Num& x) {
    for (int i = 1; i < L; i++)
        if (x.d[i] != ~uint64_t(0)) return;
    if (x.d[0] < uint64_t(0) - C) return;
    x.d[0] -= uint64_t(0) - C;  // x - p = x + c - 2^3072
    for (int i = 1; i < L; i++) x.d[i] = 0;
}

// r = a * b mod p, as a value below 2^3072 (not always below p: every
// operation here takes such a value, and canonical() ends a product).
void mulmod(Num& r, const Num& a, const Num& b) {
    uint64_t t[2 * L] = {0};
    for (int i = 0; i < L; i++) {
        uint64_t carry = 0, ai = a.d[i];
        for (int j = 0; j < L; j++) {
            u128 v = u128(ai) * b.d[j] + t[i + j] + carry;
            t[i + j] = uint64_t(v);
            carry = uint64_t(v >> 64);
        }
        t[i + L] = carry;
    }
    // hi * 2^3072 + lo = hi * c + lo (mod p): below 2^3094
    uint64_t carry = 0;
    for (int i = 0; i < L; i++) {
        u128 v = u128(t[i + L]) * C + t[i] + carry;
        t[i] = uint64_t(v);
        carry = uint64_t(v >> 64);
    }
    // the 22 bits above limb 47 fold again, and so does the one bit that
    // this addition can carry out (what is left below is then tiny)
    while (carry) {
        u128 v = u128(carry) * C;
        carry = 0;
        for (int i = 0; i < L && v; i++) {
            v += t[i];
            t[i] = uint64_t(v);
            v >>= 64;
        }
        carry = uint64_t(v);
    }
    memcpy(r.d, t, sizeof r.d);
}

// Keccak-f[1600] (FIPS 202), for SHAKE256: rate 136 bytes, suffix 0x1F.
const uint64_t RC[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808aULL,
    0x8000000080008000ULL, 0x000000000000808bULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008aULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000aULL,
    0x000000008000808bULL, 0x800000000000008bULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800aULL, 0x800000008000000aULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL};
const int ROT[24] = {1, 3, 6, 10, 15, 21, 28, 36, 45, 55, 2, 14,
                     27, 41, 56, 8, 25, 43, 62, 18, 39, 61, 20, 44};
const int PI[24] = {10, 7, 11, 17, 18, 3, 5, 16, 8, 21, 24, 4,
                    15, 23, 19, 13, 12, 2, 20, 14, 22, 9, 6, 1};

inline uint64_t rotl(uint64_t x, int n) { return (x << n) | (x >> (64 - n)); }

void keccak_f(uint64_t s[25]) {
    for (int round = 0; round < 24; round++) {
        uint64_t bc[5];
        for (int i = 0; i < 5; i++)
            bc[i] = s[i] ^ s[i + 5] ^ s[i + 10] ^ s[i + 15] ^ s[i + 20];
        for (int i = 0; i < 5; i++) {
            uint64_t t = bc[(i + 4) % 5] ^ rotl(bc[(i + 1) % 5], 1);
            for (int j = 0; j < 25; j += 5) s[j + i] ^= t;
        }
        uint64_t t = s[1];
        for (int i = 0; i < 24; i++) {
            int j = PI[i];
            uint64_t keep = s[j];
            s[j] = rotl(t, ROT[i]);
            t = keep;
        }
        for (int j = 0; j < 25; j += 5) {
            for (int i = 0; i < 5; i++) bc[i] = s[j + i];
            for (int i = 0; i < 5; i++)
                s[j + i] ^= ~bc[(i + 1) % 5] & bc[(i + 2) % 5];
        }
        s[0] ^= RC[round];
    }
}

const size_t RATE = 136;

// muhash.element(): SHAKE256(data) to 384 bytes, little-endian, mod p,
// and 1 where that is 0 (little-endian hosts only, as the rest of native/).
void element(Num& out, const uint8_t* data, size_t len) {
    uint64_t s[25] = {0};
    uint8_t* sb = reinterpret_cast<uint8_t*>(s);
    while (len >= RATE) {
        for (size_t i = 0; i < RATE; i++) sb[i] ^= data[i];
        keccak_f(s);
        data += RATE;
        len -= RATE;
    }
    for (size_t i = 0; i < len; i++) sb[i] ^= data[i];
    sb[len] ^= 0x1F;
    sb[RATE - 1] ^= 0x80;
    uint8_t* ob = reinterpret_cast<uint8_t*>(out.d);
    for (size_t got = 0; got < sizeof out.d; got += RATE) {
        keccak_f(s);
        size_t take = sizeof out.d - got < RATE ? sizeof out.d - got : RATE;
        memcpy(ob + got, sb, take);
    }
    canonical(out);
    bool zero = true;
    for (int i = 0; i < L; i++) zero = zero && out.d[i] == 0;
    if (zero) out.d[0] = 1;
}

// prod over i in [0, n) of item(i), on up to nthreads threads (<= 0: one a
// core, at most 8: a flush is short and the import's other threads are not
// idle), written as 384 canonical little-endian bytes.
template <typename Item>
void product(size_t n, int nthreads, uint8_t* out384, Item item) {
    unsigned hw = nthreads > 0 ? unsigned(nthreads)
                               : std::thread::hardware_concurrency();
    if (nthreads <= 0 && hw > 8) hw = 8;
    size_t parts = n / 256 + 1;  // a thread is not worth fewer
    if (parts > hw) parts = hw ? hw : 1;
    std::vector<Num> partial(parts);
    auto run = [&](size_t k) {
        Num acc = {{1}}, e;
        for (size_t i = n * k / parts; i < n * (k + 1) / parts; i++) {
            item(e, i);
            mulmod(acc, acc, e);
        }
        partial[k] = acc;
    };
    std::vector<std::thread> th;
    for (size_t k = 1; k < parts; k++) th.emplace_back(run, k);
    run(0);
    for (auto& t : th) t.join();
    Num acc = partial[0];
    for (size_t k = 1; k < parts; k++) mulmod(acc, acc, partial[k]);
    canonical(acc);
    memcpy(out384, acc.d, sizeof acc.d);
}

}  // namespace

extern "C" {

// prod of n values mod p; rows = n x 384 bytes, little-endian, each below
// 2^3072 (muhash.batch_product).
void bcp_muhash_product(const uint8_t* rows, size_t n, int nthreads,
                        uint8_t* out384) {
    product(n, nthreads, out384, [&](Num& e, size_t i) {
        memcpy(e.d, rows + i * sizeof e.d, sizeof e.d);
    });
}

// prod of element(blob[offsets[i] : offsets[i + 1]]) over n byte strings
// (muhash.coin_product: a row is its 36-byte key and the coin's bytes).
void bcp_muhash_element_product(const uint8_t* blob, const uint64_t* offsets,
                                size_t n, int nthreads, uint8_t* out384) {
    product(n, nthreads, out384, [&](Num& e, size_t i) {
        element(e, blob + offsets[i], size_t(offsets[i + 1] - offsets[i]));
    });
}

}  // extern "C"
