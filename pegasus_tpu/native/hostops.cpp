// Native host-runtime kernels for the TPU-native KV engine.
//
// The reference implements its entire runtime in C++; this build keeps the
// device compute in JAX/XLA and implements the host runtime's hot loops
// here: CRC-64 partition hashing (reference consumes dsn::utils::crc64_calc,
// src/base/pegasus_key_schema.h:162), variable-length arena gather (the
// output-SST materialization step of every flush/compaction), sorted-run
// merge ranking, and big-endian prefix packing for the device sort columns.
//
// Built as a plain shared library (no pybind11 in the image); the Python
// side binds with ctypes (pegasus_tpu/native/__init__.py) and falls back to
// the numpy implementations when the toolchain is unavailable.
//
// Build: g++ -O3 -march=native -shared -fPIC -o libhostops.so hostops.cpp

#include <cstdint>
#include <cstring>

extern "C" {

// ---------------------------------------------------------------- crc64

// CRC-64/XZ (reflected 0xC96C5795D7870F42), matching base/crc64.py.
static uint64_t CRC_TABLE[8][256];
static bool crc_init_done = false;

static void crc_init() {
    const uint64_t poly = 0xC96C5795D7870F42ULL;
    for (int i = 0; i < 256; i++) {
        uint64_t crc = (uint64_t)i;
        for (int k = 0; k < 8; k++)
            crc = (crc & 1) ? (crc >> 1) ^ poly : crc >> 1;
        CRC_TABLE[0][i] = crc;
    }
    // slice-by-8 tables
    for (int t = 1; t < 8; t++)
        for (int i = 0; i < 256; i++)
            CRC_TABLE[t][i] = CRC_TABLE[0][CRC_TABLE[t - 1][i] & 0xFF] ^
                              (CRC_TABLE[t - 1][i] >> 8);
    crc_init_done = true;
}

static inline uint64_t crc64_one(const uint8_t* p, int64_t len, uint64_t crc) {
    crc = ~crc;
    while (len >= 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        crc ^= w;
        crc = CRC_TABLE[7][crc & 0xFF] ^ CRC_TABLE[6][(crc >> 8) & 0xFF] ^
              CRC_TABLE[5][(crc >> 16) & 0xFF] ^ CRC_TABLE[4][(crc >> 24) & 0xFF] ^
              CRC_TABLE[3][(crc >> 32) & 0xFF] ^ CRC_TABLE[2][(crc >> 40) & 0xFF] ^
              CRC_TABLE[1][(crc >> 48) & 0xFF] ^ CRC_TABLE[0][crc >> 56];
        p += 8;
        len -= 8;
    }
    while (len-- > 0)
        crc = CRC_TABLE[0][(crc ^ *p++) & 0xFF] ^ (crc >> 8);
    return ~crc;
}

// out[i] = crc64 of arena[offsets[i] .. offsets[i]+lengths[i])
void crc64_batch(const uint8_t* arena, const int64_t* offsets,
                 const int64_t* lengths, int64_t n, uint64_t* out) {
    if (!crc_init_done) crc_init();
    for (int64_t i = 0; i < n; i++)
        out[i] = crc64_one(arena + offsets[i], lengths[i], 0);
}

// ---------------------------------------------------------- arena gather

// Compact the variable-length slices idx[0..nidx) of (arena, off, len32)
// into out (caller sized it as sum of selected lengths); writes the new
// offsets as it goes. Single pass of memcpy — the materialization step of
// every compaction output block.
void gather_arena(const uint8_t* arena, const int64_t* off,
                  const int32_t* len32, const int64_t* idx, int64_t nidx,
                  uint8_t* out, int64_t* out_off) {
    int64_t pos = 0;
    for (int64_t i = 0; i < nidx; i++) {
        int64_t j = idx[i];
        int64_t l = (int64_t)len32[j];
        out_off[i] = pos;
        memcpy(out + pos, arena + off[j], (size_t)l);
        pos += l;
    }
}

// ------------------------------------------------------- prefix packing

// Big-endian pack of each record's first 4*w key bytes into w uint32 lanes
// (zero padded), column-major output: out[col * n + i]. Mirrors
// ops/packing.pack_key_prefixes.
void pack_prefixes(const uint8_t* arena, const int64_t* off,
                   const int32_t* len32, int64_t n, int32_t w,
                   uint32_t* out) {
    for (int64_t i = 0; i < n; i++) {
        const uint8_t* p = arena + off[i];
        int64_t len = (int64_t)len32[i];
        for (int32_t c = 0; c < w; c++) {
            uint32_t v = 0;
            int64_t base = (int64_t)c * 4;
            for (int b = 0; b < 4; b++) {
                int64_t k = base + b;
                uint32_t byte = (k < len) ? p[k] : 0;
                v = (v << 8) | byte;
            }
            out[(int64_t)c * n + i] = v;
        }
    }
}

// ------------------------------------------------- by-run uniform gather

// Materialize a compaction output block from uniform-width records in ONE
// pass over the survivor index: keys (klen bytes each), values (vlen),
// expire/hash32 (u32) and deleted (u8) move together, so idx is read once
// and the random-access source rows are software-prefetched ahead of use.
// The separate-pass form (5 fancy-index sweeps) was measured 2.0-2.9s at
// 8.5M survivors on the 1-core dev host — DRAM-latency-bound on the
// dependent row loads; prefetching + fusion cuts most of the stalls.
//
// The rows come from K runs that are never concatenated: idx is in
// real-concat space, run r owns [starts[r], starts[r+1]) with starts =
// cumsum of the runs' row counts (K+1 entries), and each run brings its
// own base pointers (K = 1: one block's own gather). Resolving an index to
// (run, local row) is a scan over starts at the K a compaction sees (4 L0
// files), a binary search above 8. The scan counts instead of stopping:
// which run a survivor comes from is as good as random, and a mispredicted
// exit a row cost a third of the loop. idx must lie in [0, starts[k]): the
// Python side checks first.
static inline int32_t run_of(const int64_t* starts, int32_t k, int64_t j) {
    if (k <= 8) {
        int32_t r = 0;
        for (int32_t t = 1; t < k; t++) r += (j >= starts[t]);
        return r;
    }
    int32_t lo = 0;   // last r with starts[r] <= j, by conditional moves
    for (int32_t step = 1 << (31 - __builtin_clz((unsigned)k)); step;
         step >>= 1) {
        int32_t m = lo + step;
        lo = (m < k && starts[m] <= j) ? m : lo;
    }
    return lo;
}

// WITH_VALS is a constant at both call sites below; inlined, each wrapper
// keeps a loop without the branch.
static inline __attribute__((always_inline)) void gather_runs_impl(
        const bool WITH_VALS, const uint8_t* const* key_arenas, int64_t klen,
        const uint8_t* const* val_arenas, int64_t vlen,
        const uint32_t* const* expires, const uint32_t* const* hash32s,
        const uint8_t* const* deleteds, const int64_t* starts, int32_t k,
        const int32_t* idx, int64_t n, uint8_t* out_keys, uint8_t* out_vals,
        uint32_t* out_expire, uint32_t* out_hash32, uint8_t* out_deleted) {
    const int64_t AHEAD = WITH_VALS ? 24 : 32;
    uint8_t ahead_run[32];   // run of idx[i], resolved when it was prefetched
    for (int64_t i = 0; i < AHEAD && i < n; i++)
        ahead_run[i & 31] = (uint8_t)run_of(starts, k, (int64_t)idx[i]);
    for (int64_t i = 0; i < n; i++) {
        int32_t r = ahead_run[i & 31];
        if (i + AHEAD < n) {
            int64_t ja = (int64_t)idx[i + AHEAD];
            int32_t ra = run_of(starts, k, ja);
            ahead_run[(i + AHEAD) & 31] = (uint8_t)ra;
            ja -= starts[ra];
            __builtin_prefetch(key_arenas[ra] + ja * klen, 0, 0);
            if (WITH_VALS) {
                const uint8_t* v = val_arenas[ra] + ja * vlen;
                __builtin_prefetch(v, 0, 0);
                if (vlen > 64) __builtin_prefetch(v + 64, 0, 0);
                if (vlen > 128) __builtin_prefetch(v + vlen - 1, 0, 0);
            }
            __builtin_prefetch(expires[ra] + ja, 0, 0);
            __builtin_prefetch(hash32s[ra] + ja, 0, 0);
            __builtin_prefetch(deleteds[ra] + ja, 0, 0);
        }
        int64_t j = (int64_t)idx[i] - starts[r];
        memcpy(out_keys + i * klen, key_arenas[r] + j * klen, (size_t)klen);
        if (WITH_VALS)
            memcpy(out_vals + i * vlen, val_arenas[r] + j * vlen,
                   (size_t)vlen);
        out_expire[i] = expires[r][j];
        out_hash32[i] = hash32s[r][j];
        out_deleted[i] = deleteds[r][j];
    }
}

void gather_block_runs_uniform(const uint8_t* const* key_arenas, int64_t klen,
                               const uint8_t* const* val_arenas, int64_t vlen,
                               const uint32_t* const* expires,
                               const uint32_t* const* hash32s,
                               const uint8_t* const* deleteds,
                               const int64_t* starts, int32_t k,
                               const int32_t* idx, int64_t n,
                               uint8_t* out_keys, uint8_t* out_vals,
                               uint32_t* out_expire, uint32_t* out_hash32,
                               uint8_t* out_deleted) {
    gather_runs_impl(true, key_arenas, klen, val_arenas, vlen, expires,
                     hash32s, deleteds, starts, k, idx, n, out_keys,
                     out_vals, out_expire, out_hash32, out_deleted);
}

// Keys-and-aux-only variant: the device-value-residency materialization
// (ops/compact.py _finish_overlapped) downloads value rows from HBM while
// the host gathers only keys + fixed-width aux — the two halves overlap, so
// this loop must not touch the value arenas at all.
void gather_keys_runs_uniform(const uint8_t* const* key_arenas, int64_t klen,
                              const uint32_t* const* expires,
                              const uint32_t* const* hash32s,
                              const uint8_t* const* deleteds,
                              const int64_t* starts, int32_t k,
                              const int32_t* idx, int64_t n,
                              uint8_t* out_keys, uint32_t* out_expire,
                              uint32_t* out_hash32, uint8_t* out_deleted) {
    gather_runs_impl(false, key_arenas, klen, nullptr, 0, expires, hash32s,
                     deleteds, starts, k, idx, n, out_keys, nullptr,
                     out_expire, out_hash32, out_deleted);
}

// ----------------------------------------------------- sorted-run merge

// Count, for each record of run A (fixed-width keys, itemsize bytes,
// memcmp order), how many records of run B are smaller (side=0, "left") or
// smaller-or-equal (side=1, "right"). Both runs ascending. Galloping two-
// pointer pass: O(na + nb) memcmps instead of numpy's O(na log nb) searches.
void merge_counts(const uint8_t* a, int64_t na, const uint8_t* b, int64_t nb,
                  int64_t itemsize, int32_t side, int64_t* out) {
    int64_t j = 0;
    for (int64_t i = 0; i < na; i++) {
        const uint8_t* ka = a + i * itemsize;
        if (side == 0) {
            while (j < nb && memcmp(b + j * itemsize, ka, (size_t)itemsize) < 0)
                j++;
        } else {
            while (j < nb && memcmp(b + j * itemsize, ka, (size_t)itemsize) <= 0)
                j++;
        }
        out[i] = j;
    }
}

}  // extern "C"
