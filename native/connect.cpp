// Native block-connect engine — the C++ hot path for -reindex / block import.
//
// The reference keeps its entire import pipeline in C++
// (src/validation.cpp:~4000 LoadExternalBlockFile, src/serialize.h codecs,
// src/coins.cpp UpdateCoins, src/consensus/tx_verify.cpp CheckTransaction);
// the round-4 profile showed the equivalent pure-Python path here sustains
// ~1.3 MB/s, projecting the mainnet byte leg alone to ~29 hours. This module
// is the TPU-framework answer: the HOST side of ConnectBlock (wire parse,
// sanity checks, merkle, UTXO apply, undo construction, and the P2PKH
// signature scan that feeds the TPU ECDSA batch) in native code, while the
// Python layer keeps orchestration (header context, block index, flush
// ordering) and the chip keeps the signature math.
//
// Semantics contract: behavior mirrors the Python reference implementation
// in this repo (validation/chainstate.py _connect_block_inner,
// consensus/tx_check.py, validation/scriptcheck.py) — differential-tested in
// tests/unit/test_native_connect.py. On ANY validation error the engine
// mutates nothing and the caller re-runs the block through the Python path
// for the authoritative verdict; the fast path is only ever taken to a
// successful, bit-identical conclusion (same undo blob, same chainstate
// rows) or abandoned wholesale.

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <unordered_map>
#include <vector>
#include <thread>
#include <atomic>
#include <chrono>
#include <functional>

#include "common.h"

// from secp256k1.cpp (same .so)
extern "C" int bcp_pubkey_parse(const uint8_t* data, long len, uint8_t* out64);
extern "C" void bcp_schnorr_neg_challenge(const uint8_t* r32,
                                          const uint8_t* pub64,
                                          const uint8_t* m32, uint8_t* out32);

namespace {

using bcpn::WireReader;
using bcpn::put_compact;

// ---------------------------------------------------------------------------
// constants (consensus/tx_check.py, crypto/secp256k1.py)
// ---------------------------------------------------------------------------

constexpr int64_t COIN = 100000000;
constexpr int64_t MAX_MONEY = 21000000 * COIN;
constexpr uint64_t MAX_TX_SIZE = 8000000;  // tx_check.MAX_BLOCK_SIZE
constexpr uint32_t LOCKTIME_THRESHOLD = 500000000;

// secp256k1 group order N, field prime P, N/2 (low-s bound) — big-endian
static const uint8_t SECP_N[32] = {
    0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFE,
    0xBA,0xAE,0xDC,0xE6,0xAF,0x48,0xA0,0x3B,0xBF,0xD2,0x5E,0x8C,0xD0,0x36,0x41,0x41};
static const uint8_t SECP_P[32] = {
    0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,
    0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFE,0xFF,0xFF,0xFC,0x2F};
static const uint8_t SECP_N_HALF[32] = {
    0x7F,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,0xFF,
    0x5D,0x57,0x6E,0x73,0x57,0xA4,0x50,0x1D,0xDF,0xE9,0x2F,0x46,0x68,0x1B,0x20,0xA0};

// script flag bits (script/interpreter.py)
constexpr uint32_t F_P2SH = 1 << 0;
constexpr uint32_t F_DERSIG = 1 << 2;
constexpr uint32_t F_LOW_S = 1 << 3;
constexpr uint32_t F_STRICTENC = 1 << 1;
constexpr uint32_t F_NULLFAIL = 1 << 14;
constexpr uint32_t F_FORKID = 1 << 16;
constexpr uint8_t SIGHASH_ANYONECANPAY = 0x80;
constexpr uint8_t SIGHASH_FORKID = 0x40;
constexpr uint8_t SIGHASH_NONE = 2;
constexpr uint8_t SIGHASH_SINGLE = 3;

// error codes (mapped to reject-reason strings in native.py)
enum {
    OK = 0,
    MISSING = 1,  // prevouts absent from the map: fetch-and-retry
    E_PARSE = -1,
    E_MERKLE = -2,
    E_MUTATED = -3,
    E_EMPTY = -4,
    E_OVERSIZE = -5,
    E_CB_MISSING = -6,
    E_CB_MULTIPLE = -7,
    E_VIN_EMPTY = -8,
    E_VOUT_EMPTY = -9,
    E_TX_OVERSIZE = -10,
    E_VOUT_NEG = -11,
    E_VOUT_TOOLARGE = -12,
    E_TXOUTTOTAL = -13,
    E_DUP_INPUTS = -14,
    E_CB_LENGTH = -15,
    E_PREVOUT_NULL = -16,
    E_NONFINAL = -17,
    E_BIP34 = -18,
    E_BIP30 = -19,
    E_MISSING_SPENT = -20,
    E_PREMATURE_CB = -21,
    E_INPUTVALUES = -22,
    E_IN_BELOWOUT = -23,
    E_FEE_RANGE = -24,
    E_CB_AMOUNT = -25,
    // script errors during the native P2PKH scan (block-fatal)
    E_S_EQUALVERIFY = -101,
    E_S_SIG_DER = -102,
    E_S_SIG_HIGH_S = -103,
    E_S_SIG_HASHTYPE = -104,
    E_S_ILLEGAL_FORKID = -105,
    E_S_MUST_USE_FORKID = -106,
    E_S_PUBKEYTYPE = -107,
    E_S_SIG_NULLFAIL = -108,
    E_S_EVAL_FALSE = -109,
};

// ---------------------------------------------------------------------------
// 256-bit big-endian helpers (for r/s range, low-s, r+N<P wraparound)
// ---------------------------------------------------------------------------

static int cmp256(const uint8_t a[32], const uint8_t b[32]) {
    return memcmp(a, b, 32);
}

static bool is_zero256(const uint8_t a[32]) {
    for (int i = 0; i < 32; i++) if (a[i]) return false;
    return true;
}

// out = a + N; returns carry (out is 32 bytes, big-endian)
static int add_n256(const uint8_t a[32], uint8_t out[32]) {
    unsigned carry = 0;
    for (int i = 31; i >= 0; i--) {
        unsigned s = unsigned(a[i]) + unsigned(SECP_N[i]) + carry;
        out[i] = uint8_t(s);
        carry = s >> 8;
    }
    return int(carry);
}

// ---------------------------------------------------------------------------
// parsed block (pointers into the caller's raw buffer: valid only during
// the connect call; export buffers copy whatever outlives it)
// ---------------------------------------------------------------------------

struct PIn {
    const uint8_t* prevout;  // 36 bytes
    const uint8_t* ss;
    uint32_t ss_len;
    uint32_t sequence;
};

struct POut {
    int64_t value;
    const uint8_t* spk;
    uint32_t spk_len;
};

struct PTx {
    const uint8_t* start;
    uint32_t size;
    int32_t version;
    uint32_t locktime;
    std::vector<PIn> vin;
    std::vector<POut> vout;
    uint8_t txid[32];
    uint32_t in_base;  // global input index of vin[0] (coinbase excluded)
};

struct Key36 {
    uint8_t b[36];
    bool operator==(const Key36& o) const { return memcmp(b, o.b, 36) == 0; }
};

struct KeyHash {
    size_t operator()(const Key36& k) const {
        uint64_t h;
        memcpy(&h, k.b, 8);  // txids are sha256d: uniformly distributed
        uint32_t n;
        memcpy(&n, k.b + 32, 4);
        return size_t(h ^ (uint64_t(n) * 0x9E3779B97F4A7C15ULL));
    }
};

// coin entry flags
constexpr uint8_t C_DIRTY = 1;   // differs from base since last flush
constexpr uint8_t C_FRESH = 2;   // base never saw it (spend = pure erase)
constexpr uint8_t C_SPENT = 4;   // tombstone: delete from base at flush

struct CoinEnt {
    int64_t value = 0;
    uint32_t height_code = 0;  // height*2 | coinbase (Coin.serialize code)
    uint8_t flags = 0;
    std::vector<uint8_t> spk;
};

// The lanes the script templates wrote for inputs of the generic-script
// leg (P2PK, bare and P2SH CHECKMULTISIG), in input order, in the format of
// sig_pub .. sig_wrap; `cand` marks the candidate lanes of multisig groups,
// `kind` a lane's scheme (LANE_ECDSA, LANE_SCHNORR: a Schnorr lane's RN slot
// holds (n - e) mod n, the scalar of its key, and its WRAP is 0).
// One table row an input: its number g, its first lane, and m and n of its
// OP_CHECKMULTISIG (m(n-m+1) lanes), or m = 0 for the one lane of an
// OP_CHECKSIG.
constexpr uint8_t LANE_ECDSA = 0;
constexpr uint8_t LANE_SCHNORR = 1;

struct LegLanes {
    enum { PUB, RS, MSG, RN, WRAP, CAND, KIND, N_BLOBS };
    std::vector<uint8_t> blob[N_BLOBS];
    std::vector<uint32_t> table;

    uint32_t lanes() const { return uint32_t(blob[WRAP].size()); }

    void clear() {
        for (auto& b : blob) b.clear();
        table.clear();
    }

    void row(uint32_t g, uint32_t m, uint32_t n) {
        const uint32_t r[4] = {g, lanes(), m, n};
        table.insert(table.end(), r, r + 4);
    }

    void lane(const uint8_t pub64[64], const uint8_t r32[32],
              const uint8_t s32[32], const uint8_t msg32[32],
              bool candidate) {
        blob[PUB].insert(blob[PUB].end(), pub64, pub64 + 64);
        blob[RS].insert(blob[RS].end(), r32, r32 + 32);
        blob[RS].insert(blob[RS].end(), s32, s32 + 32);
        blob[MSG].insert(blob[MSG].end(), msg32, msg32 + 32);
        // rn = r + N if r + N < P else r, as scan_input writes it
        uint8_t sum[32];
        bool wrapped = add_n256(r32, sum) == 0 && cmp256(sum, SECP_P) < 0;
        const uint8_t* x = wrapped ? sum : r32;
        blob[RN].insert(blob[RN].end(), x, x + 32);
        blob[WRAP].push_back(wrapped ? 1 : 0);
        blob[CAND].push_back(candidate ? 1 : 0);
        blob[KIND].push_back(LANE_ECDSA);
    }

    // the one lane of an OP_CHECKSIG under a 65-byte Schnorr signature
    void schnorr_lane(const uint8_t pub64[64], const uint8_t r32[32],
                      const uint8_t s32[32], const uint8_t msg32[32],
                      const uint8_t u2[32]) {
        blob[PUB].insert(blob[PUB].end(), pub64, pub64 + 64);
        blob[RS].insert(blob[RS].end(), r32, r32 + 32);
        blob[RS].insert(blob[RS].end(), s32, s32 + 32);
        blob[MSG].insert(blob[MSG].end(), msg32, msg32 + 32);
        blob[RN].insert(blob[RN].end(), u2, u2 + 32);
        blob[WRAP].push_back(0);
        blob[CAND].push_back(0);
        blob[KIND].push_back(LANE_SCHNORR);
    }

    // a later thread's lanes behind this one's
    void append(const LegLanes& o) {
        uint32_t base = lanes();
        for (int i = 0; i < N_BLOBS; i++)
            blob[i].insert(blob[i].end(), o.blob[i].begin(), o.blob[i].end());
        for (size_t i = 0; i < o.table.size(); i++)
            table.push_back(o.table[i] + (i % 4 == 1 ? base : 0));
    }
};

static uint64_t ns_since(std::chrono::steady_clock::time_point t0) {
    return uint64_t(std::chrono::duration_cast<std::chrono::nanoseconds>(
        std::chrono::steady_clock::now() - t0).count());
}

// What the legacy digest cost one scan thread (summed after the join):
// digests made, bytes of serialised transaction they hashed, nanoseconds
// inside sighash_legacy; and the thread's nanoseconds in the scan as a whole.
// Beside them the inputs whose 65-byte signature the scan took as a Schnorr
// lane, and the nanoseconds in their challenge hash and n - e.
struct ScanCounters {
    uint64_t legacy_digests = 0;
    uint64_t legacy_bytes = 0;
    uint64_t legacy_ns = 0;
    uint64_t thread_ns = 0;
    uint64_t schnorr_inputs = 0;
    uint64_t schnorr_ns = 0;

    void add(const ScanCounters& o) {
        legacy_digests += o.legacy_digests;
        legacy_bytes += o.legacy_bytes;
        legacy_ns += o.legacy_ns;
        thread_ns += o.thread_ns;
        schnorr_inputs += o.schnorr_inputs;
        schnorr_ns += o.schnorr_ns;
    }
};

struct Engine {
    std::unordered_map<Key36, CoinEnt, KeyHash> map;
    uint8_t best[32] = {0};
    uint64_t mem_bytes = 0;

    // per-connect outputs (valid until the next call on this engine)
    std::vector<PTx> txs;
    std::vector<uint8_t> undo;
    std::vector<uint8_t> txids;         // n_tx * 32
    std::vector<uint64_t> tx_offsets;   // n_tx * 2 (start, end)
    std::vector<uint32_t> tx_out_counts;
    std::vector<uint8_t> missing;       // n_missing * 36
    // spent-coin export, one slot per non-coinbase input (global order)
    std::vector<int64_t> spent_values;
    std::vector<uint32_t> spent_hc;
    std::vector<uint32_t> spent_spk_off;  // n_inputs + 1
    std::vector<uint8_t> spent_spk;
    // sig-scan export, one slot per non-coinbase input
    // 0 = fast P2PKH record in this input's slot, 1 = the Python
    // interpreter decides, 2 = a script template wrote the input's lanes
    // into `leg`
    std::vector<uint8_t> sig_status;
    std::vector<uint8_t> sig_msg;     // n * 32
    std::vector<uint8_t> sig_rs;      // n * 64
    std::vector<uint8_t> sig_pub;     // n * 64
    std::vector<uint8_t> sig_rn;      // n * 32
    std::vector<uint8_t> sig_wrap;    // n
    std::vector<uint8_t> sig_kind;    // n: LANE_ECDSA, or LANE_SCHNORR
                                      // (then sig_rn holds (n - e) mod n)
    std::vector<uint32_t> sig_txin;   // n * 2 (tx index, input index)
    LegLanes leg;                     // the template inputs' lanes

    long err_code = 0;
    long err_tx = -1;
    long err_in = -1;
    uint64_t sigscan_ns = 0;  // last connect's signature-scan wall time
    ScanCounters scan;        // and its threads' counters, summed

    // deferred-commit overlay: connect(commit=0) validates and stages the
    // block's UTXO edits here; bcp_engine_commit applies them (or
    // bcp_engine_abort discards) — the Python-side fallback script checks
    // run between the two (see node.py _import_block_files_native)
    struct OvEnt {
        bool spent = false;
        bool created = false;
        int64_t value = 0;
        uint32_t height_code = 0;
        std::vector<uint8_t> spk;
    };
    std::unordered_map<Key36, OvEnt, KeyHash> ov;
    bool ov_valid = false;
    uint8_t pending_best[32] = {0};

    // flush export buffer
    std::vector<uint8_t> flush_buf;

    void note_err(long code, long t, long i) {
        err_code = code; err_tx = t; err_in = i;
    }

    uint64_t ent_mem(const CoinEnt& e) const {
        // rough accounting mirroring CoinsCache.estimated_bytes intent:
        // map node + key + entry + spk heap
        return 96 + e.spk.size();
    }
};

// ---------------------------------------------------------------------------
// block parse (wire layout identical to consensus/{tx,block}.py)
// ---------------------------------------------------------------------------

static bool parse_tx(WireReader& r, PTx& tx) {
    size_t start = r.pos;
    uint32_t version;
    if (!r.u32(&version)) return false;
    tx.version = int32_t(version);
    uint64_t nin;
    if (!r.compact(&nin)) return false;
    tx.vin.resize(0);
    tx.vin.reserve(size_t(nin) <= 4096 ? size_t(nin) : 4096);
    for (uint64_t i = 0; i < nin; i++) {
        PIn in;
        if (r.len - r.pos < 36) return false;
        in.prevout = r.p + r.pos;
        r.pos += 36;
        uint64_t sl;
        if (!r.compact(&sl)) return false;
        if (r.len - r.pos < sl) return false;
        in.ss = r.p + r.pos;
        in.ss_len = uint32_t(sl);
        r.pos += sl;
        if (!r.u32(&in.sequence)) return false;
        tx.vin.push_back(in);
    }
    uint64_t nout;
    if (!r.compact(&nout)) return false;
    tx.vout.resize(0);
    tx.vout.reserve(size_t(nout) <= 4096 ? size_t(nout) : 4096);
    for (uint64_t i = 0; i < nout; i++) {
        POut out;
        if (!r.i64(&out.value)) return false;
        uint64_t sl;
        if (!r.compact(&sl)) return false;
        if (r.len - r.pos < sl) return false;
        out.spk = r.p + r.pos;
        out.spk_len = uint32_t(sl);
        r.pos += sl;
        tx.vout.push_back(out);
    }
    if (!r.u32(&tx.locktime)) return false;
    tx.start = r.p + start;
    tx.size = uint32_t(r.pos - start);
    return true;
}

static bool parse_block(const uint8_t* raw, size_t len, std::vector<PTx>& txs) {
    WireReader r{raw, len};
    if (!r.skip(80)) return false;
    uint64_t n;
    if (!r.compact(&n)) return false;
    txs.resize(0);
    txs.reserve(size_t(n));
    uint32_t in_base = 0;
    for (uint64_t i = 0; i < n; i++) {
        txs.emplace_back();
        if (!parse_tx(r, txs.back())) return false;
        txs.back().in_base = in_base;
        if (i > 0)  // coinbase inputs don't occupy sig slots
            in_base += uint32_t(txs.back().vin.size());
    }
    return r.pos == len;  // CBlock.from_bytes rejects trailing bytes
}

// merkle root over txids with the CVE-2012-2459 mutation flag
// (consensus/merkle.py semantics)
static bool merkle_root(const std::vector<uint8_t>& txids, long n,
                        uint8_t root[32], bool* mutated) {
    if (n <= 0) return false;
    std::vector<uint8_t> level(txids.begin(), txids.begin() + n * 32);
    *mutated = false;
    long cnt = n;
    uint8_t pair[64];
    while (cnt > 1) {
        long next = 0;
        for (long i = 0; i < cnt; i += 2) {
            long j = (i + 1 < cnt) ? i + 1 : i;
            if (i + 1 < cnt &&
                memcmp(level.data() + 32 * i, level.data() + 32 * j, 32) == 0)
                *mutated = true;
            memcpy(pair, level.data() + 32 * i, 32);
            memcpy(pair + 32, level.data() + 32 * j, 32);
            bcpn::sha256d(pair, 64, level.data() + 32 * next);
            next++;
        }
        cnt = next;
    }
    memcpy(root, level.data(), 32);
    return true;
}

static bool is_coinbase(const PTx& tx) {
    if (tx.vin.size() != 1) return false;
    const uint8_t* p = tx.vin[0].prevout;
    for (int i = 0; i < 32; i++) if (p[i]) return false;
    uint32_t nidx;
    memcpy(&nidx, p + 32, 4);
    return nidx == 0xFFFFFFFF;
}

static bool prevout_is_null(const uint8_t* p) {
    for (int i = 0; i < 32; i++) if (p[i]) return false;
    uint32_t nidx;
    memcpy(&nidx, p + 32, 4);
    return nidx == 0xFFFFFFFF;
}

// CheckTransaction (consensus/tx_check.py) — returns 0 or error code
static long check_transaction(const PTx& tx) {
    if (tx.vin.empty()) return E_VIN_EMPTY;
    if (tx.vout.empty()) return E_VOUT_EMPTY;
    if (tx.size > MAX_TX_SIZE) return E_TX_OVERSIZE;
    int64_t total = 0;
    for (const POut& o : tx.vout) {
        if (o.value < 0) return E_VOUT_NEG;
        if (o.value > MAX_MONEY) return E_VOUT_TOOLARGE;
        total += o.value;
        if (total < 0 || total > MAX_MONEY) return E_TXOUTTOTAL;
    }
    if (tx.vin.size() > 1) {
        // duplicate-input check; small vins use O(n^2) (cache-friendly),
        // large vins a hash set
        if (tx.vin.size() <= 32) {
            for (size_t i = 0; i < tx.vin.size(); i++)
                for (size_t j = i + 1; j < tx.vin.size(); j++)
                    if (memcmp(tx.vin[i].prevout, tx.vin[j].prevout, 36) == 0)
                        return E_DUP_INPUTS;
        } else {
            std::unordered_map<Key36, char, KeyHash> seen;
            seen.reserve(tx.vin.size() * 2);
            for (const PIn& in : tx.vin) {
                Key36 k;
                memcpy(k.b, in.prevout, 36);
                if (!seen.emplace(k, 1).second) return E_DUP_INPUTS;
            }
        }
    }
    if (is_coinbase(tx)) {
        uint32_t l = tx.vin[0].ss_len;
        if (l < 2 || l > 100) return E_CB_LENGTH;
    } else {
        for (const PIn& in : tx.vin)
            if (prevout_is_null(in.prevout)) return E_PREVOUT_NULL;
    }
    return OK;
}

// IsFinalTx (consensus/tx_check.py) — block_time is the MTP (BIP113)
static bool is_final(const PTx& tx, uint32_t height, int64_t mtp) {
    if (tx.locktime == 0) return true;
    int64_t cutoff = tx.locktime < LOCKTIME_THRESHOLD ? int64_t(height) : mtp;
    if (int64_t(tx.locktime) < cutoff) return true;
    for (const PIn& in : tx.vin)
        if (in.sequence != 0xFFFFFFFF) return false;
    return true;
}

// ---------------------------------------------------------------------------
// P2PKH fast-path signature scan (validation/scriptcheck.py semantics)
// ---------------------------------------------------------------------------

// the two forms check_pubkey_encoding takes under STRICTENC
static bool strict_key_form(const uint8_t* pub, uint32_t len) {
    return (len == 33 && (pub[0] == 2 || pub[0] == 3)) ||
           (len == 65 && pub[0] == 4);
}

// strict DER + hashtype tail (interpreter.py is_valid_signature_encoding)
static bool valid_sig_encoding(const uint8_t* sig, uint32_t len) {
    if (len < 9 || len > 73) return false;
    if (sig[0] != 0x30 || sig[1] != len - 3) return false;
    uint32_t len_r = sig[3];
    if (5 + len_r >= len) return false;
    uint32_t len_s = sig[5 + len_r];
    if (len_r + len_s + 7 != len) return false;
    if (sig[2] != 0x02 || len_r == 0 || (sig[4] & 0x80)) return false;
    if (len_r > 1 && sig[4] == 0x00 && !(sig[5] & 0x80)) return false;
    if (sig[len_r + 4] != 0x02 || len_s == 0 || (sig[len_r + 6] & 0x80)) return false;
    if (len_s > 1 && sig[len_r + 6] == 0x00 && !(sig[len_r + 7] & 0x80)) return false;
    return true;
}

// extract a DER integer into a 32-byte big-endian buffer; false if it does
// not fit in 256 bits (after the optional 0x00 sign byte)
static bool der_int_to_32(const uint8_t* p, uint32_t len, uint8_t out[32]) {
    while (len > 0 && p[0] == 0x00) { p++; len--; }
    if (len > 32) return false;
    memset(out, 0, 32);
    memcpy(out + 32 - len, p, len);
    return true;
}

// two direct pushes covering the whole scriptSig (scriptcheck._p2pkh_template)
static bool p2pkh_template(const uint8_t* ss, uint32_t ss_len,
                           const uint8_t* spk, uint32_t spk_len,
                           const uint8_t** sig, uint32_t* sig_len,
                           const uint8_t** pub, uint32_t* pub_len) {
    if (spk_len != 25 || spk[0] != 0x76 || spk[1] != 0xA9 || spk[2] != 20 ||
        spk[23] != 0x88 || spk[24] != 0xAC)
        return false;
    uint32_t pos = 0;
    const uint8_t* items[2];
    uint32_t lens[2];
    for (int k = 0; k < 2; k++) {
        if (pos >= ss_len) return false;
        uint8_t op = ss[pos];
        if (op == 0) {
            items[k] = ss + pos + 1;
            lens[k] = 0;
            pos += 1;
        } else if (op >= 1 && op <= 75) {
            if (pos + 1 + op > ss_len) return false;
            items[k] = ss + pos + 1;
            lens[k] = op;
            pos += 1 + op;
        } else {
            return false;
        }
    }
    if (pos != ss_len) return false;
    *sig = items[0]; *sig_len = lens[0];
    *pub = items[1]; *pub_len = lens[1];
    return true;
}

// forkid (BIP143-style) sighash midstates per tx (script/sighash.py
// SighashCache)
struct TxMidstates {
    uint8_t hash_prevouts[32];
    uint8_t hash_sequence[32];
    uint8_t hash_outputs[32];
};

static void compute_midstates(const PTx& tx, TxMidstates& m) {
    {
        bcpn::Sha256 a;
        for (const PIn& in : tx.vin) a.update(in.prevout, 36);
        uint8_t mid[32]; a.final(mid);
        bcpn::sha256(mid, 32, m.hash_prevouts);
    }
    {
        bcpn::Sha256 a;
        for (const PIn& in : tx.vin) {
            uint8_t seq[4];
            memcpy(seq, &in.sequence, 4);
            a.update(seq, 4);
        }
        uint8_t mid[32]; a.final(mid);
        bcpn::sha256(mid, 32, m.hash_sequence);
    }
    {
        bcpn::Sha256 a;
        for (const POut& o : tx.vout) {
            uint8_t v[8];
            memcpy(v, &o.value, 8);
            a.update(v, 8);
            std::vector<uint8_t> cs;
            put_compact(cs, o.spk_len);
            a.update(cs.data(), cs.size());
            a.update(o.spk, o.spk_len);
        }
        uint8_t mid[32]; a.final(mid);
        bcpn::sha256(mid, 32, m.hash_outputs);
    }
}

// signature_hash_forkid (script/sighash.py) for input in_idx with
// script_code = the 25-byte P2PKH spk and the spent amount
static void sighash_forkid(const PTx& tx, const TxMidstates& m,
                           uint32_t in_idx, uint8_t hashtype,
                           const uint8_t* script_code, uint32_t sc_len,
                           int64_t amount, uint8_t out[32]) {
    static const uint8_t zero[32] = {0};
    uint8_t base = hashtype & 0x1F;
    bool acp = (hashtype & SIGHASH_ANYONECANPAY) != 0;
    const uint8_t* hp = acp ? zero : m.hash_prevouts;
    const uint8_t* hs =
        (acp || base == SIGHASH_NONE || base == SIGHASH_SINGLE)
            ? zero : m.hash_sequence;
    uint8_t single_out[32];
    const uint8_t* ho;
    if (base != SIGHASH_NONE && base != SIGHASH_SINGLE) {
        ho = m.hash_outputs;
    } else if (base == SIGHASH_SINGLE && in_idx < tx.vout.size()) {
        const POut& o = tx.vout[in_idx];
        bcpn::Sha256 a;
        uint8_t v[8];
        memcpy(v, &o.value, 8);
        a.update(v, 8);
        std::vector<uint8_t> cs;
        put_compact(cs, o.spk_len);
        a.update(cs.data(), cs.size());
        a.update(o.spk, o.spk_len);
        uint8_t mid[32]; a.final(mid);
        bcpn::sha256(mid, 32, single_out);
        ho = single_out;
    } else {
        ho = zero;
    }
    bcpn::Sha256 a;
    uint8_t u32buf[4];
    uint32_t ver = uint32_t(tx.version);
    memcpy(u32buf, &ver, 4);
    a.update(u32buf, 4);
    a.update(hp, 32);
    a.update(hs, 32);
    a.update(tx.vin[in_idx].prevout, 36);
    std::vector<uint8_t> cs;
    put_compact(cs, sc_len);
    a.update(cs.data(), cs.size());
    a.update(script_code, sc_len);
    uint8_t amt[8];
    memcpy(amt, &amount, 8);
    a.update(amt, 8);
    memcpy(u32buf, &tx.vin[in_idx].sequence, 4);
    a.update(u32buf, 4);
    a.update(ho, 32);
    memcpy(u32buf, &tx.locktime, 4);
    a.update(u32buf, 4);
    uint32_t ht32 = hashtype;
    memcpy(u32buf, &ht32, 4);
    a.update(u32buf, 4);
    uint8_t mid[32];
    a.final(mid);
    bcpn::sha256(mid, 32, out);
}

// What the digests of one transaction share, each made when the first
// input asks for it: the FORKID digest's three midstates, and for the
// legacy digest the serialisation with every scriptSig empty.
struct TxDigests {
    const PTx& tx;
    ScanCounters& counters;
    TxMidstates m;
    bool have_mid = false;
    // version | compact(n_in), then 41 bytes an input: prevout | 0x00 |
    // sequence. compact(n_out) | outputs | locktime are the transaction's
    // own bytes from the end of its last input on.
    std::vector<uint8_t> head, blank;

    TxDigests(const PTx& t, ScanCounters& c) : tx(t), counters(c) {}

    const TxMidstates& mid() {
        if (!have_mid) {
            compute_midstates(tx, m);
            have_mid = true;
        }
        return m;
    }

    void skeleton() {
        if (!head.empty()) return;
        uint32_t ver = uint32_t(tx.version);
        head.resize(4);
        memcpy(head.data(), &ver, 4);
        put_compact(head, tx.vin.size());
        blank.resize(tx.vin.size() * 41);
        uint8_t* p = blank.data();
        for (const PIn& in : tx.vin) {
            memcpy(p, in.prevout, 36);
            p[36] = 0;
            memcpy(p + 37, &in.sequence, 4);
            p += 41;
        }
    }
};

// signature_hash_legacy (script/sighash.py; upstream interpreter.cpp
// SignatureHash over CTransactionSignatureSerializer): the transaction
// serialised again for this input, with `code` as the input's script and
// every other script empty; SIGHASH_NONE and SIGHASH_SINGLE zero the other
// inputs' sequences and cut the outputs, ANYONECANPAY keeps this input
// alone; the 32-bit hashtype behind it; SHA-256 twice. SIGHASH_SINGLE
// without an output of the input's number signs the number 1. `code` holds
// no OP_CODESEPARATOR and no push of the signature (the callers' scripts
// cannot), so nothing is deleted from it.
static void sighash_legacy(TxDigests& d, uint32_t in_idx, uint32_t hashtype,
                           const uint8_t* code, uint32_t code_len,
                           uint8_t out[32]) {
    auto t0 = std::chrono::steady_clock::now();
    const PTx& tx = d.tx;
    uint32_t base = hashtype & 0x1F;
    d.counters.legacy_digests++;
    if (in_idx >= tx.vin.size() ||
        (base == SIGHASH_SINGLE && in_idx >= tx.vout.size())) {
        memset(out, 0, 32);
        out[0] = 1;
        return;
    }
    d.skeleton();
    const PIn& in = tx.vin[in_idx];
    bcpn::Sha256 a;
    auto signed_input = [&]() {  // prevout | compact(len) code | sequence
        std::vector<uint8_t> len;
        put_compact(len, code_len);
        a.update(in.prevout, 36);
        a.update(len.data(), len.size());
        a.update(code, code_len);
        a.update(reinterpret_cast<const uint8_t*>(&in.sequence), 4);
    };
    bool cut = base == SIGHASH_NONE || base == SIGHASH_SINGLE;
    if (hashtype & SIGHASH_ANYONECANPAY) {
        a.update(d.head.data(), 4);
        const uint8_t one = 1;
        a.update(&one, 1);
        signed_input();
    } else if (cut) {
        a.update(d.head.data(), d.head.size());
        uint8_t other[41];
        memset(other + 36, 0, 5);
        for (uint32_t j = 0; j < tx.vin.size(); j++) {
            if (j == in_idx) {
                signed_input();
            } else {
                memcpy(other, tx.vin[j].prevout, 36);
                a.update(other, 41);
            }
        }
    } else {
        a.update(d.head.data(), d.head.size());
        a.update(d.blank.data(), size_t(in_idx) * 41);
        signed_input();
        a.update(d.blank.data() + size_t(in_idx + 1) * 41,
                 d.blank.size() - size_t(in_idx + 1) * 41);
    }
    const PIn& last = tx.vin.back();
    const uint8_t* tail = last.ss + last.ss_len + 4;
    const uint8_t* end = tx.start + tx.size;
    if (!cut) {
        a.update(tail, size_t(end - tail));  // outputs and locktime
    } else {
        std::vector<uint8_t> outs;
        if (base == SIGHASH_NONE) {
            outs.push_back(0);
        } else {
            // the outputs before this input's are CTxOut(): -1, no script
            put_compact(outs, uint64_t(in_idx) + 1);
            for (uint32_t j = 0; j < in_idx; j++) {
                outs.insert(outs.end(), 8, 0xFF);
                outs.push_back(0);
            }
            const POut& o = tx.vout[in_idx];
            const uint8_t* v = reinterpret_cast<const uint8_t*>(&o.value);
            outs.insert(outs.end(), v, v + 8);
            put_compact(outs, o.spk_len);
            outs.insert(outs.end(), o.spk, o.spk + o.spk_len);
        }
        a.update(outs.data(), outs.size());
        a.update(end - 4, 4);
    }
    uint8_t ht[4];
    memcpy(ht, &hashtype, 4);
    a.update(ht, 4);
    d.counters.legacy_bytes += a.total;
    uint8_t mid[32];
    a.final(mid);
    bcpn::sha256(mid, 32, out);
    d.counters.legacy_ns += ns_since(t0);
}

// The hashtypes the scan models under `flags`: ALL, NONE and SINGLE, each
// with or without ANYONECANPAY, with the FORKID bit where and only where
// STRICTENC wants it (_check_hashtype_encoding). Without STRICTENC the
// interpreter hashes any byte; the scan leaves the undefined ones to it.
static bool hashtype_modelled(uint8_t ht, uint32_t flags) {
    bool uses_forkid = (ht & SIGHASH_FORKID) != 0;
    bool forkid_on = (flags & F_FORKID) != 0;
    if (uses_forkid && !forkid_on) return false;
    if ((flags & F_STRICTENC) && forkid_on && !uses_forkid) return false;
    uint8_t base = ht & uint8_t(~(SIGHASH_ANYONECANPAY | SIGHASH_FORKID));
    return base >= 1 && base <= SIGHASH_SINGLE;
}

// The digest a signature of hashtype `ht` commits to under `flags`
// (script/sighash.py signature_hash): FORKID's where the flags enable it
// and the hashtype asks for it, else the legacy one. No option chooses.
static void sighash(TxDigests& d, uint32_t in_idx, uint8_t ht, uint32_t flags,
                    const uint8_t* code, uint32_t code_len, int64_t amount,
                    uint8_t out[32]) {
    if ((flags & F_FORKID) && (ht & SIGHASH_FORKID))
        sighash_forkid(d.tx, d.mid(), in_idx, ht, code, code_len, amount,
                       out);
    else
        sighash_legacy(d, in_idx, ht, code, code_len, out);
}

// ---------------------------------------------------------------------------
// Script templates of the generic-script leg: P2PK, bare and P2SH
// OP_CHECKMULTISIG. The specification is script/interpreter.py:
// DeferringSignatureChecker.check_sig and defer_multisig under VerifyScript.
// A template writes the lanes the Python leg would have written for an input
// it can prove the interpreter would pass speculatively, or declines and
// leaves the input to the interpreter: it never gives a verdict. Where the
// flags would let the interpreter defer more than the scan models (a hybrid
// key or an undefined hashtype without STRICTENC, loose DER without DERSIG,
// a non-null dummy without NULLDUMMY, a non-minimal push), it declines.
//
// In each of these forms, as in P2PKH, the signature check is the script's
// last operation: the script's verdict is the check's, whether a failed
// check raises (NULLFAIL) or pushes false. So the templates hold below the
// fork height as above it; what changes with the block's flags is the
// digest (sighash) and the encoding rules (no STRICTENC: a key is a lane in
// STRICTENC's two forms only; no LOW_S: a high S is a lane).
//
// The legacy digest deletes each signature's push from the script code
// first (FindAndDelete). A push of a strict-DER signature is its length
// (9..73) and then 0x30; in a template's script code an opcode boundary
// holds OP_1..OP_16, OP_CHECKSIG, OP_CHECKMULTISIG or a key's push (0x21
// then 0x02 / 0x03, 0x41 then 0x04): nothing to delete, ever. The P2PKH
// body's script code holds the push of a 20-byte hash besides, so a
// signature of 20 bytes under the legacy digest goes to the interpreter.
// ---------------------------------------------------------------------------

constexpr uint8_t OP_PUSHDATA1 = 0x4C;
constexpr uint8_t OP_PUSHDATA2 = 0x4D;
constexpr uint8_t OP_1 = 0x51;
constexpr uint8_t OP_16 = 0x60;
constexpr uint8_t OP_EQUAL = 0x87;
constexpr uint8_t OP_HASH160 = 0xA9;
constexpr uint8_t OP_CHECKSIG = 0xAC;
constexpr uint8_t OP_CHECKMULTISIG = 0xAE;
constexpr uint32_t MAX_SCRIPT_ELEMENT_SIZE = 520;
constexpr int MAX_TEMPLATE_KEYS = 16;  // a count is OP_1 .. OP_16

struct Push {
    const uint8_t* p;
    uint32_t len;
};

// the direct push (opcodes 1..75) at *pos
static bool direct_push(const uint8_t* sc, uint32_t sc_len, uint32_t* pos,
                        Push* out) {
    if (*pos >= sc_len) return false;
    uint8_t op = sc[*pos];
    if (op < 1 || op > 75 || *pos + 1 + op > sc_len) return false;
    *out = Push{sc + *pos + 1, op};
    *pos += 1 + op;
    return true;
}

// A signature a lane can carry: what check_sig / defer_multisig accept
// under `flags` (non-empty, not Schnorr's 65 bytes, its encoding checks,
// scalars in 1..N-1) in strict DER, with a hashtype the scan models.
static bool template_sig(Push sig, uint32_t flags, uint8_t r32[32],
                         uint8_t s32[32]) {
    if (sig.len == 65 || !valid_sig_encoding(sig.p, sig.len)) return false;
    uint32_t len_r = sig.p[3];
    uint32_t len_s = sig.p[5 + len_r];
    if (!der_int_to_32(sig.p + 4, len_r, r32) ||
        !der_int_to_32(sig.p + 6 + len_r, len_s, s32))
        return false;
    if (is_zero256(r32) || is_zero256(s32) ||
        cmp256(r32, SECP_N) >= 0 || cmp256(s32, SECP_N) >= 0)
        return false;
    if ((flags & F_LOW_S) && cmp256(s32, SECP_N_HALF) > 0) return false;
    return hashtype_modelled(sig.p[sig.len - 1], flags);
}

// A 65-byte signature an OP_CHECKSIG lane can carry as BCH Schnorr (spec
// 2019-05-15-schnorr.md): r = sig[0:32] a field element, s = sig[32:64] a
// scalar, then the hashtype byte. Taken in blocks from the fork height on
// (flags carry FORKID and NULLFAIL; the program has no separate activation
// for the 2019-05-15 upgrade), with a FORKID hashtype the scan models.
// r >= p and s >= n never verify: declined, the interpreter fails them.
// OP_CHECKMULTISIG takes no Schnorr signature (template_sig).
static bool schnorr_sig(const uint8_t* sig, uint32_t sig_len, uint32_t flags,
                        uint8_t r32[32], uint8_t s32[32]) {
    if (sig_len != 65 || !(flags & F_FORKID) || !(flags & F_NULLFAIL))
        return false;
    if (!(sig[64] & SIGHASH_FORKID) || !hashtype_modelled(sig[64], flags))
        return false;
    if (cmp256(sig, SECP_P) >= 0 || cmp256(sig + 32, SECP_N) >= 0)
        return false;
    memcpy(r32, sig, 32);
    memcpy(s32, sig + 32, 32);
    return true;
}

// (n - e) mod n of a Schnorr lane, timed for the thread's counters
static void schnorr_scalar(ScanCounters& c, const uint8_t r32[32],
                           const uint8_t pub64[64], const uint8_t msg[32],
                           uint8_t u2[32]) {
    auto t0 = std::chrono::steady_clock::now();
    bcp_schnorr_neg_challenge(r32, pub64, msg, u2);
    c.schnorr_inputs++;
    c.schnorr_ns += ns_since(t0);
}

// a key in one of STRICTENC's two forms that is a point of the curve
static bool template_key(Push key, uint8_t pub64[64]) {
    return strict_key_form(key.p, key.len) &&
           bcp_pubkey_parse(key.p, long(key.len), pub64);
}

// OP_m <key>*n OP_n OP_CHECKMULTISIG, 1 <= m <= n, and nothing else
static bool multisig_template(const uint8_t* sc, uint32_t sc_len,
                              uint32_t* m, uint32_t* n, Push* keys) {
    if (sc_len < 3 || sc[0] < OP_1 || sc[0] > OP_16) return false;
    *m = sc[0] - (OP_1 - 1);
    uint32_t pos = 1, k = 0;
    while (k < MAX_TEMPLATE_KEYS && direct_push(sc, sc_len, &pos, &keys[k]))
        k++;
    if (pos + 2 != sc_len || sc[pos] != OP_1 - 1 + k ||
        sc[pos + 1] != OP_CHECKMULTISIG)
        return false;
    *n = k;
    return k >= 1 && *m <= k;
}

// The lanes of one input that fits a template, appended to `out` with its
// table row; false (and nothing written) where no template fits.
static bool scan_templates(const Engine& e, TxDigests& d, uint32_t in_idx,
                           uint32_t g, uint32_t flags, const uint8_t* spk,
                           uint32_t spk_len, LegLanes& out) {
    const PIn& in = d.tx.vin[in_idx];
    int64_t amount = e.spent_values[g];
    uint8_t r32[MAX_TEMPLATE_KEYS][32], s32[MAX_TEMPLATE_KEYS][32];
    uint8_t msg[32], pub[MAX_TEMPLATE_KEYS][64];
    uint32_t pos = 0;
    Push sig;

    // P2PK: <sig> | <key> OP_CHECKSIG
    if ((spk_len == 35 || spk_len == 67) && spk[0] == spk_len - 2 &&
        spk[spk_len - 1] == OP_CHECKSIG) {
        if (!direct_push(in.ss, in.ss_len, &pos, &sig) || pos != in.ss_len)
            return false;
        bool schnorr = schnorr_sig(sig.p, sig.len, flags, r32[0], s32[0]);
        if ((!schnorr && !template_sig(sig, flags, r32[0], s32[0])) ||
            !template_key(Push{spk + 1, spk_len - 2}, pub[0]))
            return false;
        sighash(d, in_idx, sig.p[sig.len - 1], flags, spk, spk_len, amount,
                msg);
        out.row(g, 0, 0);
        if (schnorr) {
            uint8_t u2[32];
            schnorr_scalar(d.counters, r32[0], pub[0], msg, u2);
            out.schnorr_lane(pub[0], r32[0], s32[0], msg, u2);
        } else {
            out.lane(pub[0], r32[0], s32[0], msg, false);
        }
        return true;
    }

    // OP_0 <sig>*m | the bare script, or OP_0 <sig>*m <redeem> |
    // OP_HASH160 <20> OP_EQUAL with the bare script as the redeem script
    if (in.ss_len < 1 || in.ss[0] != 0) return false;
    pos = 1;
    Push sigs[MAX_TEMPLATE_KEYS], keys[MAX_TEMPLATE_KEYS];
    uint32_t n_sigs = 0, m, n;
    while (n_sigs < MAX_TEMPLATE_KEYS &&
           direct_push(in.ss, in.ss_len, &pos, &sigs[n_sigs]))
        n_sigs++;
    const uint8_t* code = spk;
    uint32_t code_len = spk_len;
    if (spk_len == 23 && spk[0] == OP_HASH160 && spk[1] == 20 &&
        spk[22] == OP_EQUAL) {
        if (!(flags & F_P2SH)) return false;
        // the redeem script is the last push, in its minimal form
        if (pos == in.ss_len) {
            if (n_sigs < 2) return false;
            n_sigs--;
            code = sigs[n_sigs].p;
            code_len = sigs[n_sigs].len;
        } else {
            uint32_t left = in.ss_len - pos, head;
            if (in.ss[pos] == OP_PUSHDATA1 && left >= 2) {
                head = 2;
                code_len = in.ss[pos + 1];
                if (code_len <= 75) return false;
            } else if (in.ss[pos] == OP_PUSHDATA2 && left >= 3) {
                head = 3;
                code_len = in.ss[pos + 1] | uint32_t(in.ss[pos + 2]) << 8;
                if (code_len <= 255 || code_len > MAX_SCRIPT_ELEMENT_SIZE)
                    return false;
            } else {
                return false;
            }
            if (left != head + code_len) return false;
            code = in.ss + pos + head;
        }
        uint8_t h20[20];
        bcpn::hash160(code, code_len, h20);
        if (memcmp(h20, spk + 2, 20) != 0) return false;
    } else if (pos != in.ss_len) {
        return false;
    }
    if (!multisig_template(code, code_len, &m, &n, keys) || n_sigs != m)
        return false;
    // sigs and keys in the order EvalScript hands them to defer_multisig:
    // from the top of the stack down, so the walk starts at the last key
    for (uint32_t i = 0; i < m; i++)
        if (!template_sig(sigs[m - 1 - i], flags, r32[i], s32[i]))
            return false;
    for (uint32_t j = 0; j < n; j++)
        if (!template_key(keys[n - 1 - j], pub[j])) return false;
    out.row(g, m, n);
    // one digest a hashtype: signatures of one hashtype share theirs
    uint8_t msgs[MAX_TEMPLATE_KEYS][32], hts[MAX_TEMPLATE_KEYS];
    for (uint32_t i = 0; i < m; i++) {
        const Push& s = sigs[m - 1 - i];
        hts[i] = s.p[s.len - 1];
        uint32_t same = 0;
        while (hts[same] != hts[i]) same++;
        if (same < i)
            memcpy(msgs[i], msgs[same], 32);
        else
            sighash(d, in_idx, hts[i], flags, code, code_len, amount,
                    msgs[i]);
        for (uint32_t j = i; j <= i + n - m; j++)
            out.lane(pub[j], r32[i], s32[i], msgs[i], true);
    }
    return true;
}

// One input's fast-path scan. Returns OK and fills the record slot, a
// script error code (block-fatal), or sets *fallback for the Python
// interpreter. Mirrors scriptcheck._p2pkh_fast_verify +
// DeferringSignatureChecker.check_sig exactly.
static long scan_input(Engine& e, TxDigests& d, uint32_t in_idx, uint32_t g,
                       uint32_t flags, LegLanes& leg) {
    const PIn& in = d.tx.vin[in_idx];
    const uint8_t* spk = e.spent_spk.data() + e.spent_spk_off[g];
    uint32_t spk_len = e.spent_spk_off[g + 1] - e.spent_spk_off[g];
    const uint8_t *sig, *pub;
    uint32_t sig_len, pub_len;
    if (!p2pkh_template(in.ss, in.ss_len, spk, spk_len,
                        &sig, &sig_len, &pub, &pub_len)) {
        // another template's lanes, or the generic interpreter (Python)
        e.sig_status[g] =
            scan_templates(e, d, in_idx, g, flags, spk, spk_len, leg) ? 2 : 1;
        return OK;
    }
    // DUP HASH160 <h20> EQUALVERIFY collapse
    uint8_t h20[20];
    bcpn::hash160(pub, pub_len, h20);
    if (memcmp(h20, spk + 3, 20) != 0) return E_S_EQUALVERIFY;
    // 65 bytes from the fork height on: BCH Schnorr by length, before DER's
    // encoding check could answer sig-der. What the lane cannot carry (a
    // hashtype or key form the scan does not model, r >= p, s >= n, a key
    // off the curve) goes to the interpreter, which fails it by name.
    if (sig_len == 65 && (flags & F_FORKID) && (flags & F_NULLFAIL)) {
        uint8_t r32[32], s32[32], pub64[64], msg[32];
        if (!schnorr_sig(sig, sig_len, flags, r32, s32) ||
            !strict_key_form(pub, pub_len) ||
            !bcp_pubkey_parse(pub, long(pub_len), pub64)) {
            e.sig_status[g] = 1;
            return OK;
        }
        sighash(d, in_idx, sig[64], flags, spk, spk_len, e.spent_values[g],
                msg);
        memcpy(e.sig_msg.data() + 32 * g, msg, 32);
        memcpy(e.sig_rs.data() + 64 * g, r32, 32);
        memcpy(e.sig_rs.data() + 64 * g + 32, s32, 32);
        memcpy(e.sig_pub.data() + 64 * g, pub64, 64);
        schnorr_scalar(d.counters, r32, pub64, msg,
                       e.sig_rn.data() + 32 * g);
        e.sig_wrap[g] = 0;
        e.sig_kind[g] = LANE_SCHNORR;
        e.sig_status[g] = 0;
        return OK;
    }
    // check_signature_encoding (empty sig passes encoding, fails later)
    if (sig_len != 0) {
        if ((flags & (F_DERSIG | F_LOW_S | F_STRICTENC)) &&
            !valid_sig_encoding(sig, sig_len))
            return E_S_SIG_DER;
        if (flags & F_LOW_S) {
            uint32_t len_r = sig[3];
            uint32_t len_s = sig[5 + len_r];
            uint8_t s32[32];
            if (!der_int_to_32(sig + 6 + len_r, len_s, s32) ||
                cmp256(s32, SECP_N_HALF) > 0)
                return E_S_SIG_HIGH_S;
        }
        if (flags & F_STRICTENC) {
            uint8_t ht = sig[sig_len - 1];
            uint8_t base = ht & uint8_t(~(SIGHASH_ANYONECANPAY | SIGHASH_FORKID));
            if (base < 1 || base > SIGHASH_SINGLE) return E_S_SIG_HASHTYPE;
            bool uses_forkid = (ht & SIGHASH_FORKID) != 0;
            bool forkid_on = (flags & F_FORKID) != 0;
            if (!forkid_on && uses_forkid) return E_S_ILLEGAL_FORKID;
            if (forkid_on && !uses_forkid) return E_S_MUST_USE_FORKID;
        }
    }
    // check_pubkey_encoding
    if ((flags & F_STRICTENC) && !strict_key_form(pub, pub_len))
        return E_S_PUBKEYTYPE;
    // check_sig: empty sig -> parse fails -> False -> eval-false (empty sig
    // is exempt from NULLFAIL's nullfail code, scriptcheck.py:110-113)
    if (sig_len == 0) return E_S_EVAL_FALSE;
    uint8_t ht = sig[sig_len - 1];
    // what a failed check is: a script error under NULLFAIL, else the false
    // that OP_CHECKSIG leaves as the script's last word
    const long failed =
        (flags & F_NULLFAIL) ? E_S_SIG_NULLFAIL : E_S_EVAL_FALSE;
    if (!(flags & F_STRICTENC)) {
        // what the era's interpreter takes and the scan does not model goes
        // to it: an undefined hashtype, a key in another form than
        // STRICTENC's two, loose DER (and 65 bytes, Schnorr's by length)
        if (!hashtype_modelled(ht, flags) ||
            !strict_key_form(pub, pub_len) || sig_len == 65 ||
            !valid_sig_encoding(sig, sig_len)) {
            e.sig_status[g] = 1;
            return OK;
        }
    }
    bool legacy = !((flags & F_FORKID) && (ht & SIGHASH_FORKID));
    // the one push FindAndDelete could cut out of this script code is the
    // 20 bytes of its key hash: a signature of that length is the
    // interpreter's
    if (legacy && sig_len == 20) {
        e.sig_status[g] = 1;
        return OK;
    }
    // pubkey parse (decompress): failure -> check_sig False
    uint8_t pub64[64];
    if (!bcp_pubkey_parse(pub, long(pub_len), pub64)) return failed;
    // DER decode r, s (structure validated above: under STRICTENC by
    // check_signature_encoding's rule, else by the scan's own)
    uint32_t len_r = sig[3];
    uint32_t len_s = sig[5 + len_r];
    uint8_t r32[32], s32[32];
    if (!der_int_to_32(sig + 4, len_r, r32) ||
        !der_int_to_32(sig + 6 + len_r, len_s, s32))
        return failed;
    // range: 1 <= r, s < N (DeferringSignatureChecker.check_sig)
    if (is_zero256(r32) || is_zero256(s32) ||
        cmp256(r32, SECP_N) >= 0 || cmp256(s32, SECP_N) >= 0)
        return failed;
    // sighash + record emit
    uint8_t msg[32];
    sighash(d, in_idx, ht, flags, spk, spk_len, e.spent_values[g], msg);
    memcpy(e.sig_msg.data() + 32 * g, msg, 32);
    memcpy(e.sig_rs.data() + 64 * g, r32, 32);
    memcpy(e.sig_rs.data() + 64 * g + 32, s32, 32);
    memcpy(e.sig_pub.data() + 64 * g, pub64, 64);
    // rn = r + N if r + N < P else r; wrap flag for the kernel's
    // x-wraparound candidate (ops/ecdsa_batch.records_to_blobs semantics)
    uint8_t rn[32];
    int carry = add_n256(r32, rn);
    bool wrap = (carry == 0) && (cmp256(rn, SECP_P) < 0);
    memcpy(e.sig_rn.data() + 32 * g, wrap ? rn : r32, 32);
    e.sig_wrap[g] = wrap ? 1 : 0;
    e.sig_kind[g] = LANE_ECDSA;
    e.sig_status[g] = 0;
    return OK;
}

static void commit_overlay(Engine& e) {
    if (!e.ov_valid) return;
    for (auto& kv : e.ov) {
        const Key36& k = kv.first;
        Engine::OvEnt& oe = kv.second;
        if (oe.created && !oe.spent) {
            CoinEnt ent;
            ent.value = oe.value;
            ent.height_code = oe.height_code;
            ent.flags = C_DIRTY | C_FRESH;
            ent.spk = std::move(oe.spk);
            auto it = e.map.find(k);
            if (it != e.map.end()) {
                // overwriting a SPENT tombstone of a base coin: the new
                // coin is NOT fresh (base still holds the stale row until
                // the flush's put replaces it)
                if (!(it->second.flags & C_FRESH)) ent.flags = C_DIRTY;
                e.mem_bytes -= e.ent_mem(it->second);
                e.mem_bytes += e.ent_mem(ent);
                it->second = std::move(ent);
            } else {
                e.mem_bytes += e.ent_mem(ent);
                e.map.emplace(k, std::move(ent));
            }
        } else if (oe.spent && !oe.created) {
            auto it = e.map.find(k);
            // (must exist: resolved during connect)
            if (it == e.map.end()) continue;
            if (it->second.flags & C_FRESH) {
                e.mem_bytes -= e.ent_mem(it->second);
                e.map.erase(it);
            } else {
                e.mem_bytes -= it->second.spk.size();
                it->second.flags = C_DIRTY | C_SPENT;
                it->second.spk.clear();
                it->second.spk.shrink_to_fit();
            }
        }
        // created && spent within the block: never touches the map
    }
    memcpy(e.best, e.pending_best, 32);
    e.ov.clear();
    e.ov_valid = false;
}

}  // namespace

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

extern "C" {

void* bcp_engine_new() { return new Engine(); }

void bcp_engine_free(void* e) { delete static_cast<Engine*>(e); }

void bcp_engine_set_best(void* ep, const uint8_t* h32) {
    memcpy(static_cast<Engine*>(ep)->best, h32, 32);
}

void bcp_engine_get_best(void* ep, uint8_t* out32) {
    memcpy(out32, static_cast<Engine*>(ep)->best, 32);
}

uint64_t bcp_engine_mem_bytes(void* ep) {
    return static_cast<Engine*>(ep)->mem_bytes;
}

long bcp_engine_entries(void* ep) {
    return long(static_cast<Engine*>(ep)->map.size());
}

// Insert a CLEAN coin read from the base store (miss servicing).
void bcp_engine_insert(void* ep, const uint8_t* key36, uint32_t height_code,
                       int64_t value, const uint8_t* spk, uint32_t spk_len) {
    Engine& e = *static_cast<Engine*>(ep);
    Key36 k;
    memcpy(k.b, key36, 36);
    CoinEnt ent;
    ent.value = value;
    ent.height_code = height_code;
    ent.flags = 0;
    ent.spk.assign(spk, spk + spk_len);
    auto it = e.map.find(k);
    if (it != e.map.end()) e.mem_bytes -= e.ent_mem(it->second);
    e.mem_bytes += e.ent_mem(ent);
    e.map[k] = std::move(ent);
}

// 1 = live coin (out params filled; spk pointer valid until next mutation),
// 0 = absent, -1 = spent tombstone
int bcp_engine_get(void* ep, const uint8_t* key36, uint32_t* height_code,
                   int64_t* value, const uint8_t** spk, uint32_t* spk_len) {
    Engine& e = *static_cast<Engine*>(ep);
    Key36 k;
    memcpy(k.b, key36, 36);
    auto it = e.map.find(k);
    if (it == e.map.end()) return 0;
    if (it->second.flags & C_SPENT) return -1;
    *height_code = it->second.height_code;
    *value = it->second.value;
    *spk = it->second.spk.data();
    *spk_len = uint32_t(it->second.spk.size());
    return 1;
}

long bcp_engine_error(void* ep, long* tx_idx, long* in_idx) {
    Engine& e = *static_cast<Engine*>(ep);
    *tx_idx = e.err_tx;
    *in_idx = e.err_in;
    return e.err_code;
}

const uint8_t* bcp_engine_missing(void* ep, long* count) {
    Engine& e = *static_cast<Engine*>(ep);
    *count = long(e.missing.size() / 36);
    return e.missing.data();
}

const uint8_t* bcp_engine_undo(void* ep, size_t* len) {
    Engine& e = *static_cast<Engine*>(ep);
    *len = e.undo.size();
    return e.undo.data();
}

long bcp_engine_n_tx(void* ep) {
    return long(static_cast<Engine*>(ep)->txs.size());
}

long bcp_engine_n_inputs(void* ep) {
    return long(static_cast<Engine*>(ep)->spent_values.size());
}

const uint8_t* bcp_engine_txids(void* ep) {
    return static_cast<Engine*>(ep)->txids.data();
}

const uint64_t* bcp_engine_tx_offsets(void* ep) {
    return static_cast<Engine*>(ep)->tx_offsets.data();
}

const uint32_t* bcp_engine_tx_out_counts(void* ep) {
    return static_cast<Engine*>(ep)->tx_out_counts.data();
}

const int64_t* bcp_engine_spent_values(void* ep) {
    return static_cast<Engine*>(ep)->spent_values.data();
}

const uint32_t* bcp_engine_spent_heightcodes(void* ep) {
    return static_cast<Engine*>(ep)->spent_hc.data();
}

const uint32_t* bcp_engine_spent_spk_offsets(void* ep) {
    return static_cast<Engine*>(ep)->spent_spk_off.data();
}

const uint8_t* bcp_engine_spent_spk_blob(void* ep, size_t* len) {
    Engine& e = *static_cast<Engine*>(ep);
    *len = e.spent_spk.size();
    return e.spent_spk.data();
}

const uint8_t* bcp_engine_sig_status(void* ep) {
    return static_cast<Engine*>(ep)->sig_status.data();
}
const uint8_t* bcp_engine_sig_msg(void* ep) {
    return static_cast<Engine*>(ep)->sig_msg.data();
}
const uint8_t* bcp_engine_sig_rs(void* ep) {
    return static_cast<Engine*>(ep)->sig_rs.data();
}
const uint8_t* bcp_engine_sig_pub(void* ep) {
    return static_cast<Engine*>(ep)->sig_pub.data();
}
const uint8_t* bcp_engine_sig_rn(void* ep) {
    return static_cast<Engine*>(ep)->sig_rn.data();
}
const uint8_t* bcp_engine_sig_wrap(void* ep) {
    return static_cast<Engine*>(ep)->sig_wrap.data();
}
const uint8_t* bcp_engine_sig_kind(void* ep) {
    return static_cast<Engine*>(ep)->sig_kind.data();
}
const uint32_t* bcp_engine_sig_txin(void* ep) {
    return static_cast<Engine*>(ep)->sig_txin.data();
}

// The template lanes of the last connect (LegLanes): blob `which` in the
// order pub, rs, msg, rn, wrap, cand, kind, then 7 = the table's uint32
// rows; its length in bytes.
const uint8_t* bcp_engine_leg_blob(void* ep, int which, size_t* len) {
    LegLanes& leg = static_cast<Engine*>(ep)->leg;
    if (which == LegLanes::N_BLOBS) {
        *len = leg.table.size() * sizeof(uint32_t);
        return reinterpret_cast<const uint8_t*>(leg.table.data());
    }
    *len = leg.blob[which].size();
    return leg.blob[which].data();
}

// The connect itself. See the ABI sketch in native.py for argument docs.
long bcp_engine_connect_block(
    void* ep, const uint8_t* raw, size_t raw_len,
    uint32_t height, int64_t subsidy,
    uint32_t max_block_size, uint32_t coinbase_maturity, int64_t mtp,
    const uint8_t* bip34_prefix, uint32_t bip34_len,
    uint32_t script_flags, int want_sigs, int check_merkle, int nthreads,
    int commit, uint8_t* block_hash_out32) {
    Engine& e = *static_cast<Engine*>(ep);
    e.err_code = 0; e.err_tx = -1; e.err_in = -1;
    e.missing.clear();
    e.ov.clear();
    e.ov_valid = false;

    if (!parse_block(raw, raw_len, e.txs)) {
        e.note_err(E_PARSE, -1, -1);
        return E_PARSE;
    }
    std::vector<PTx>& txs = e.txs;
    long n_tx = long(txs.size());
    bcpn::sha256d(raw, 80, block_hash_out32);

    // ---- CheckBlock (chainstate.check_block order) ----
    // txids (threaded: sha256d per tx dominates parse cost)
    e.txids.resize(size_t(n_tx) * 32);
    {
        unsigned hw = nthreads > 0 ? unsigned(nthreads)
                                   : std::thread::hardware_concurrency();
        if (hw == 0) hw = 1;
        unsigned nt = n_tx < 8 ? 1 : (hw > 8 ? 8 : hw);
        if (nt <= 1) {
            for (long i = 0; i < n_tx; i++)
                bcpn::sha256d(txs[i].start, txs[i].size,
                              e.txids.data() + 32 * i);
        } else {
            std::vector<std::thread> th;
            std::atomic<long> next{0};
            for (unsigned t = 0; t < nt; t++)
                th.emplace_back([&]() {
                    long i;
                    while ((i = next.fetch_add(1)) < n_tx)
                        bcpn::sha256d(txs[i].start, txs[i].size,
                                      e.txids.data() + 32 * i);
                });
            for (auto& t : th) t.join();
        }
        for (long i = 0; i < n_tx; i++)
            memcpy(txs[i].txid, e.txids.data() + 32 * i, 32);
    }
    if (check_merkle) {
        uint8_t root[32];
        bool mutated;
        if (!merkle_root(e.txids, n_tx, root, &mutated) ||
            memcmp(root, raw + 36, 32) != 0) {
            e.note_err(E_MERKLE, -1, -1);
            return E_MERKLE;
        }
        if (mutated) {
            e.note_err(E_MUTATED, -1, -1);
            return E_MUTATED;
        }
    }
    if (n_tx == 0) { e.note_err(E_EMPTY, -1, -1); return E_EMPTY; }
    if (raw_len > max_block_size) {
        e.note_err(E_OVERSIZE, -1, -1);
        return E_OVERSIZE;
    }
    if (!is_coinbase(txs[0])) {
        e.note_err(E_CB_MISSING, 0, -1);
        return E_CB_MISSING;
    }
    for (long i = 1; i < n_tx; i++)
        if (is_coinbase(txs[i])) {
            e.note_err(E_CB_MULTIPLE, i, -1);
            return E_CB_MULTIPLE;
        }
    for (long i = 0; i < n_tx; i++) {
        long rc = check_transaction(txs[i]);
        if (rc != OK) { e.note_err(rc, i, -1); return rc; }
    }

    // ---- ContextualCheckBlock: finality + BIP34 ----
    for (long i = 0; i < n_tx; i++)
        if (!is_final(txs[i], height, mtp)) {
            e.note_err(E_NONFINAL, i, -1);
            return E_NONFINAL;
        }
    if (bip34_prefix != nullptr && bip34_len > 0) {
        const PIn& cb = txs[0].vin[0];
        if (cb.ss_len < bip34_len ||
            memcmp(cb.ss, bip34_prefix, bip34_len) != 0) {
            e.note_err(E_BIP34, 0, -1);
            return E_BIP34;
        }
    }

    // ---- tx offsets / out counts export ----
    e.tx_offsets.resize(size_t(n_tx) * 2);
    e.tx_out_counts.resize(size_t(n_tx));
    for (long i = 0; i < n_tx; i++) {
        e.tx_offsets[2 * i] = uint64_t(txs[i].start - raw);
        e.tx_offsets[2 * i + 1] = uint64_t(txs[i].start - raw) + txs[i].size;
        e.tx_out_counts[i] = uint32_t(txs[i].vout.size());
    }

    // ---- BIP30 against the in-memory map (see native.py for the
    // base-store leg, which Python runs for pre-BIP34 heights only) ----
    for (long i = 0; i < n_tx; i++) {
        Key36 k;
        memcpy(k.b, txs[i].txid, 32);
        for (uint32_t o = 0; o < txs[i].vout.size(); o++) {
            memcpy(k.b + 32, &o, 4);
            auto it = e.map.find(k);
            if (it != e.map.end() && !(it->second.flags & C_SPENT)) {
                e.note_err(E_BIP30, i, long(o));
                return E_BIP30;
            }
        }
    }

    // ---- resolve inputs (overlay keeps the engine unmutated on failure)
    long n_inputs = 0;
    for (long i = 1; i < n_tx; i++) n_inputs += long(txs[i].vin.size());
    e.spent_values.assign(size_t(n_inputs), 0);
    e.spent_hc.assign(size_t(n_inputs), 0);
    e.spent_spk_off.assign(size_t(n_inputs) + 1, 0);
    e.spent_spk.clear();
    e.undo.clear();

    // overlay: outputs created by this block + spent marks for this block
    auto& ov = e.ov;
    ov.clear();
    e.ov_valid = false;
    ov.reserve(size_t(n_inputs) * 2 + 64);

    put_compact(e.undo, uint64_t(n_tx - 1));
    int64_t fees = 0;
    uint32_t g = 0;
    bool missing_any = false;

    for (long i = 0; i < n_tx; i++) {
        PTx& tx = txs[i];
        if (i > 0) {
            std::vector<uint8_t> txundo;
            put_compact(txundo, tx.vin.size());
            int64_t value_in = 0;
            for (uint32_t vi = 0; vi < tx.vin.size(); vi++, g++) {
                Key36 k;
                memcpy(k.b, tx.vin[vi].prevout, 36);
                int64_t value;
                uint32_t hc;
                const uint8_t* spk;
                uint32_t spk_len;
                auto oit = ov.find(k);
                if (oit != ov.end() && (oit->second.spent || oit->second.created)) {
                    if (oit->second.spent) {
                        e.note_err(E_MISSING_SPENT, i, vi);
                        return E_MISSING_SPENT;
                    }
                    value = oit->second.value;
                    hc = oit->second.height_code;
                    spk = oit->second.spk.data();
                    spk_len = uint32_t(oit->second.spk.size());
                    oit->second.spent = true;
                } else {
                    auto mit = e.map.find(k);
                    if (mit == e.map.end()) {
                        // not in the cache: the caller fetches from base
                        missing_any = true;
                        e.missing.insert(e.missing.end(), k.b, k.b + 36);
                        continue;
                    }
                    if (mit->second.flags & C_SPENT) {
                        e.note_err(E_MISSING_SPENT, i, vi);
                        return E_MISSING_SPENT;
                    }
                    value = mit->second.value;
                    hc = mit->second.height_code;
                    spk = mit->second.spk.data();
                    spk_len = uint32_t(mit->second.spk.size());
                    Engine::OvEnt& oe = ov[k];
                    oe.spent = true;
                }
                if (missing_any) continue;  // keep collecting misses only
                // coinbase maturity
                if ((hc & 1) &&
                    int64_t(height) - int64_t(hc >> 1) <
                        int64_t(coinbase_maturity)) {
                    e.note_err(E_PREMATURE_CB, i, vi);
                    return E_PREMATURE_CB;
                }
                value_in += value;
                // undo: Coin.serialize framed with its length
                std::vector<uint8_t> coin_ser;
                put_compact(coin_ser, hc);
                put_compact(coin_ser, uint64_t(value));
                put_compact(coin_ser, spk_len);
                coin_ser.insert(coin_ser.end(), spk, spk + spk_len);
                put_compact(txundo, coin_ser.size());
                txundo.insert(txundo.end(), coin_ser.begin(), coin_ser.end());
                // spent export
                e.spent_values[g] = value;
                e.spent_hc[g] = hc;
                e.spent_spk.insert(e.spent_spk.end(), spk, spk + spk_len);
                e.spent_spk_off[g + 1] = uint32_t(e.spent_spk.size());
            }
            if (!missing_any) {
                if (value_in < 0 || value_in > MAX_MONEY) {
                    e.note_err(E_INPUTVALUES, i, -1);
                    return E_INPUTVALUES;
                }
                int64_t value_out = 0;
                for (const POut& o : tx.vout) value_out += o.value;
                if (value_in < value_out) {
                    e.note_err(E_IN_BELOWOUT, i, -1);
                    return E_IN_BELOWOUT;
                }
                int64_t fee = value_in - value_out;
                if (fee < 0 || fee > MAX_MONEY) {
                    e.note_err(E_FEE_RANGE, i, -1);
                    return E_FEE_RANGE;
                }
                fees += fee;
                e.undo.insert(e.undo.end(), txundo.begin(), txundo.end());
            }
        }
        // add this tx's outputs to the overlay EVEN while collecting
        // misses: later intra-block spends must not read as base misses
        uint32_t hc = height * 2 + (i == 0 ? 1 : 0);
        Key36 k;
        memcpy(k.b, tx.txid, 32);
        for (uint32_t o = 0; o < tx.vout.size(); o++) {
            memcpy(k.b + 32, &o, 4);
            Engine::OvEnt& oe = ov[k];
            oe.created = true;
            oe.spent = false;
            oe.value = tx.vout[o].value;
            oe.height_code = hc;
            oe.spk.assign(tx.vout[o].spk, tx.vout[o].spk + tx.vout[o].spk_len);
        }
    }
    if (missing_any) return MISSING;

    // coinbase amount
    int64_t cb_out = 0;
    for (const POut& o : txs[0].vout) cb_out += o.value;
    if (cb_out > fees + subsidy) {
        e.note_err(E_CB_AMOUNT, 0, -1);
        return E_CB_AMOUNT;
    }

    // ---- signature scan (before commit: a script error must leave the
    // map untouched, exactly like the Python path's scratch view) ----
    e.sigscan_ns = 0;
    e.scan = ScanCounters();
    if (want_sigs && n_inputs > 0) {
        auto scan_t0 = std::chrono::steady_clock::now();
        e.sig_status.assign(size_t(n_inputs), 1);
        e.sig_msg.resize(size_t(n_inputs) * 32);
        e.sig_rs.resize(size_t(n_inputs) * 64);
        e.sig_pub.resize(size_t(n_inputs) * 64);
        e.sig_rn.resize(size_t(n_inputs) * 32);
        e.sig_wrap.assign(size_t(n_inputs), 0);
        e.sig_kind.assign(size_t(n_inputs), LANE_ECDSA);
        e.sig_txin.resize(size_t(n_inputs) * 2);
        e.leg.clear();
        unsigned hw = nthreads > 0 ? unsigned(nthreads)
                                   : std::thread::hardware_concurrency();
        if (hw == 0) hw = 1;
        unsigned nt = (n_tx - 1) < 2 || n_inputs < 64 ? 1 : (hw > 16 ? 16 : hw);
        // first error by (tx, input) order wins, deterministically
        std::atomic<long> first_err_pos{-1};
        std::vector<long> err_codes(size_t(n_inputs), 0);
        // each thread counts for itself; the sums are taken after the join
        auto work = [&](long t_begin, long t_end, LegLanes& leg,
                        ScanCounters& counters) {
            auto w0 = std::chrono::steady_clock::now();
            bool stop = false;  // a thread stops at its first error
            for (long i = t_begin; i < t_end && !stop; i++) {
                PTx& tx = txs[i];
                TxDigests d(tx, counters);
                for (uint32_t vi = 0; vi < tx.vin.size() && !stop; vi++) {
                    uint32_t gg = tx.in_base + vi;
                    e.sig_txin[2 * gg] = uint32_t(i);
                    e.sig_txin[2 * gg + 1] = vi;
                    long rc = scan_input(e, d, vi, gg, script_flags, leg);
                    if (rc != OK) {
                        err_codes[gg] = rc;
                        long cur = first_err_pos.load();
                        while ((cur == -1 || long(gg) < cur) &&
                               !first_err_pos.compare_exchange_weak(cur, long(gg))) {}
                        stop = true;
                    }
                }
            }
            counters.thread_ns += ns_since(w0);
        };
        if (nt <= 1) {
            work(1, n_tx, e.leg, e.scan);
        } else {
            // static partition by input count for balance
            std::vector<std::thread> th;
            std::vector<long> bounds;
            bounds.push_back(1);
            long per = (n_inputs + long(nt) - 1) / long(nt);
            long acc = 0;
            for (long i = 1; i < n_tx; i++) {
                acc += long(txs[i].vin.size());
                if (acc >= per && long(bounds.size()) < long(nt)) {
                    bounds.push_back(i + 1);
                    acc = 0;
                }
            }
            bounds.push_back(n_tx);
            // each thread's template lanes, joined in input order
            std::vector<LegLanes> legs(bounds.size() - 1);
            std::vector<ScanCounters> counters(bounds.size() - 1);
            for (size_t t = 0; t + 1 < bounds.size(); t++)
                th.emplace_back(work, bounds[t], bounds[t + 1],
                                std::ref(legs[t]), std::ref(counters[t]));
            for (auto& t : th) t.join();
            for (const LegLanes& leg : legs) e.leg.append(leg);
            for (const ScanCounters& c : counters) e.scan.add(c);
        }
        e.sigscan_ns = ns_since(scan_t0);
        long fe = first_err_pos.load();
        if (fe >= 0) {
            long code = err_codes[size_t(fe)];
            e.note_err(code, e.sig_txin[2 * fe], e.sig_txin[2 * fe + 1]);
            return code;
        }
    } else {
        e.sig_status.assign(size_t(n_inputs), 1);
        e.sig_txin.resize(size_t(n_inputs) * 2);
        e.leg.clear();
        g = 0;
        for (long i = 1; i < n_tx; i++)
            for (uint32_t vi = 0; vi < txs[i].vin.size(); vi++, g++) {
                e.sig_txin[2 * g] = uint32_t(i);
                e.sig_txin[2 * g + 1] = vi;
            }
    }

    // ---- stage / commit the overlay ----
    memcpy(e.pending_best, block_hash_out32, 32);
    e.ov_valid = true;
    if (commit) commit_overlay(e);
    return OK;
}

// Wall nanoseconds the last successful connect spent in the signature
// scan (the per-sig host leg: sighash + encoding checks + pubkey parse) —
// the bench attributes this to the sig leg, not the byte leg.
uint64_t bcp_engine_sigscan_ns(void* ep) {
    return static_cast<Engine*>(ep)->sigscan_ns;
}

// The last successful connect's scan counters (ScanCounters): legacy
// digests, the bytes they hashed, nanoseconds of the scan's threads inside
// them, the threads' nanoseconds in the scan as a whole, the inputs taken
// as Schnorr lanes and the nanoseconds in their challenge scalars.
void bcp_engine_scan_counters(void* ep, uint64_t out[6]) {
    const ScanCounters& c = static_cast<Engine*>(ep)->scan;
    out[0] = c.legacy_digests;
    out[1] = c.legacy_bytes;
    out[2] = c.legacy_ns;
    out[3] = c.thread_ns;
    out[4] = c.schnorr_inputs;
    out[5] = c.schnorr_ns;
}

// sighash_legacy over one serialised transaction, for the differential
// tests (tests/unit/test_prefork_lanes.py): the bytes it hashed, or -1 where
// the transaction does not parse.
long bcp_sighash_legacy(const uint8_t* raw, size_t raw_len, uint32_t in_idx,
                        const uint8_t* code, uint32_t code_len,
                        uint32_t hashtype, uint8_t* out32) {
    WireReader r{raw, raw_len};
    PTx tx;
    if (!parse_tx(r, tx) || r.pos != raw_len || tx.vin.empty()) return -1;
    ScanCounters counters;
    TxDigests d(tx, counters);
    sighash_legacy(d, in_idx, hashtype, code, code_len, out32);
    return long(counters.legacy_bytes);
}

// Apply / discard a connect(commit=0)'s staged overlay.
void bcp_engine_commit(void* ep) { commit_overlay(*static_cast<Engine*>(ep)); }

void bcp_engine_abort(void* ep) {
    Engine& e = *static_cast<Engine*>(ep);
    e.ov.clear();
    e.ov_valid = false;
}

// Flush export. Entry format: key36 | tag u8 (0 = delete, 1 = put) |
// [u32 len | Coin.serialize bytes] — Python maps this 1:1 onto the
// CoinsDB batch (store/chainstatedb.py).
const uint8_t* bcp_engine_flush(void* ep, size_t* len, long* n_entries) {
    Engine& e = *static_cast<Engine*>(ep);
    e.flush_buf.clear();
    long n = 0;
    for (auto& kv : e.map) {
        const CoinEnt& c = kv.second;
        if (!(c.flags & C_DIRTY)) continue;
        e.flush_buf.insert(e.flush_buf.end(), kv.first.b, kv.first.b + 36);
        if (c.flags & C_SPENT) {
            e.flush_buf.push_back(0);
        } else {
            e.flush_buf.push_back(1);
            std::vector<uint8_t> ser;
            put_compact(ser, c.height_code);
            put_compact(ser, uint64_t(c.value));
            put_compact(ser, c.spk.size());
            ser.insert(ser.end(), c.spk.begin(), c.spk.end());
            uint32_t l = uint32_t(ser.size());
            const uint8_t* lp = reinterpret_cast<const uint8_t*>(&l);
            e.flush_buf.insert(e.flush_buf.end(), lp, lp + 4);
            e.flush_buf.insert(e.flush_buf.end(), ser.begin(), ser.end());
        }
        n++;
    }
    *len = e.flush_buf.size();
    *n_entries = n;
    return e.flush_buf.data();
}

// Drop everything (after a successful base batch-write), matching
// CoinsCache.flush()'s clear — memory stays bounded by -dbcache.
void bcp_engine_clear(void* ep) {
    Engine& e = *static_cast<Engine*>(ep);
    e.map.clear();
    e.mem_bytes = 0;
    e.flush_buf.clear();
    e.flush_buf.shrink_to_fit();
}

}  // extern "C"
