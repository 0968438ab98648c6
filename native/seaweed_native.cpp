// seaweed_native — C++ hot-path core for the CPU side of the framework.
//
// Provides (C ABI, loaded via ctypes from seaweedfs_tpu/utils/native.py):
//   - sn_crc32c:    CRC32C (Castagnoli), hardware-accelerated on SSE4.2
//   - sn_rs_apply:  GF(2^8) matrix apply (Reed-Solomon encode/reconstruct)
//                   using PSHUFB nibble tables (the same technique the
//                   reference's klauspost/reedsolomon uses on amd64) with
//                   a portable table fallback.
//
// This is the CPU fallback/baseline for the TPU Pallas kernel, and serves
// the latency-sensitive single-interval EC read recovery path where a
// device round-trip is not worth it (SURVEY.md "hard parts" (d)).
//
// Reference behavior being mirrored (not copied):
//   weed/storage/erasure_coding/ec_encoder.go encodeDataOneBatch
//   klauspost/reedsolomon galois arithmetic, poly 0x11D.

#include <cstdint>
#include <cstring>
#include <cstddef>
#include <cstdlib>
#include <cerrno>
#include <atomic>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <sys/uio.h>
#include <time.h>
#include <unistd.h>

#include "sn_net.h"

#if defined(__x86_64__)
#include <immintrin.h>
#endif

extern "C" {

// ---------------------------------------------------------------------------
// The return stamp. Every call below that does real work without the
// interpreter (sn_batch_pread, sn_crc32c_granules, sn_sendv,
// sn_sink_append, sn_send_file, sn_recv_into, sn_plane_fetch) writes
// CLOCK_MONOTONIC through `ret_ns` as its last
// act: Python's time.perf_counter_ns() is the same clock, so the wrapper
// reads how long the calling thread then waited to hold the interpreter
// again (utils/native.py books it when the tracer is armed; a caller
// that does not ask passes NULL).
// ---------------------------------------------------------------------------

static inline int64_t mono_ns() {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return (int64_t)ts.tv_sec * 1000000000LL + (int64_t)ts.tv_nsec;
}

static inline void stamp_return(int64_t* ret_ns) {
    if (ret_ns) *ret_ns = mono_ns();
}

// ---------------------------------------------------------------------------
// CRC32C
// ---------------------------------------------------------------------------

static uint32_t crc32c_table[8][256];
static bool crc32c_table_init_done = false;

static void crc32c_table_init() {
    if (crc32c_table_init_done) return;
    const uint32_t poly = 0x82F63B78u;
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t crc = i;
        for (int j = 0; j < 8; j++) crc = (crc >> 1) ^ ((crc & 1) ? poly : 0);
        crc32c_table[0][i] = crc;
    }
    for (int k = 1; k < 8; k++)
        for (uint32_t i = 0; i < 256; i++)
            crc32c_table[k][i] =
                (crc32c_table[k - 1][i] >> 8) ^ crc32c_table[0][crc32c_table[k - 1][i] & 0xFF];
    crc32c_table_init_done = true;
}

static uint32_t crc32c_sw(uint32_t crc, const uint8_t* p, size_t len) {
    crc32c_table_init();
    crc = ~crc;
    while (len && ((uintptr_t)p & 7)) {
        crc = (crc >> 8) ^ crc32c_table[0][(crc ^ *p++) & 0xFF];
        len--;
    }
    while (len >= 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        w ^= crc;
        crc = crc32c_table[7][w & 0xFF] ^ crc32c_table[6][(w >> 8) & 0xFF] ^
              crc32c_table[5][(w >> 16) & 0xFF] ^ crc32c_table[4][(w >> 24) & 0xFF] ^
              crc32c_table[3][(w >> 32) & 0xFF] ^ crc32c_table[2][(w >> 40) & 0xFF] ^
              crc32c_table[1][(w >> 48) & 0xFF] ^ crc32c_table[0][(w >> 56) & 0xFF];
        p += 8;
        len -= 8;
    }
    while (len--) crc = (crc >> 8) ^ crc32c_table[0][(crc ^ *p++) & 0xFF];
    return ~crc;
}

#if defined(__x86_64__)
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(uint32_t crc, const uint8_t* p, size_t len) {
    crc = ~crc;
    while (len && ((uintptr_t)p & 7)) {
        crc = _mm_crc32_u8(crc, *p++);
        len--;
    }
    while (len >= 8) {
        uint64_t w;
        memcpy(&w, p, 8);
        crc = (uint32_t)_mm_crc32_u64(crc, w);
        p += 8;
        len -= 8;
    }
    while (len--) crc = _mm_crc32_u8(crc, *p++);
    return ~crc;
}
#endif

uint32_t sn_crc32c(uint32_t crc, const uint8_t* data, size_t len) {
#if defined(__x86_64__)
    if (__builtin_cpu_supports("sse4.2")) return crc32c_hw(crc, data, len);
#endif
    return crc32c_sw(crc, data, len);
}

// --- CRC32C combine (zlib crc32_combine technique, Castagnoli poly) ---
// crc(A++B) = shift(crc(A), len(B)) ^ crc(B), with the shift operator
// represented as a GF(2) 32x32 matrix raised to the bit-length. Lets
// the sink fold leaf CRCs into block CRCs WITHOUT a second byte pass.

static uint32_t gf2_matrix_times(const uint32_t* mat, uint32_t vec) {
    uint32_t sum = 0;
    while (vec) {
        if (vec & 1) sum ^= *mat;
        vec >>= 1;
        mat++;
    }
    return sum;
}

static void gf2_matrix_square(uint32_t* square, const uint32_t* mat) {
    for (int n = 0; n < 32; n++) square[n] = gf2_matrix_times(mat, mat[n]);
}

// Fill `op` (32 words) with the matrix advancing a CRC by len2 bytes,
// by square-and-multiply over the shift-by-1-byte operator: acc holds
// the product of cur = base^(2^k) for each set bit k of len2.
static void crc32c_shift_op(uint32_t* op, uint64_t len2) {
    uint32_t even[32], odd[32];
    // one-zero-bit operator for the reflected Castagnoli polynomial
    odd[0] = 0x82F63B78u;
    uint32_t row = 1;
    for (int n = 1; n < 32; n++) {
        odd[n] = row;
        row <<= 1;
    }
    gf2_matrix_square(even, odd);  // 2 bits
    gf2_matrix_square(odd, even);  // 4 bits
    uint32_t cur[32], nxt[32];
    gf2_matrix_square(cur, odd);   // 8 bits = shift-by-1-byte operator
    bool have = false;
    uint32_t acc[32];
    while (len2) {
        if (len2 & 1) {
            if (!have) {
                memcpy(acc, cur, sizeof(acc));
                have = true;
            } else {
                // compose: powers of one base matrix commute
                for (int n = 0; n < 32; n++)
                    nxt[n] = gf2_matrix_times(cur, acc[n]);
                memcpy(acc, nxt, sizeof(acc));
            }
        }
        len2 >>= 1;
        if (len2) {
            gf2_matrix_square(nxt, cur);
            memcpy(cur, nxt, sizeof(cur));
        }
    }
    if (!have) {
        // len2 == 0: identity operator
        for (int n = 0; n < 32; n++) acc[n] = 1u << n;
    }
    memcpy(op, acc, sizeof(acc));
}

uint32_t sn_crc32c_combine(uint32_t crc1, uint32_t crc2, uint64_t len2) {
    if (len2 == 0) return crc1;
    uint32_t op[32];
    crc32c_shift_op(op, len2);
    return gf2_matrix_times(op, crc1) ^ crc2;
}

// ---------------------------------------------------------------------------
// GF(2^8) Reed-Solomon matrix apply
// ---------------------------------------------------------------------------

static uint8_t gf_mul_table[256][256];
static uint8_t gf_nib_lo[256][16];  // low-nibble products per constant
static uint8_t gf_nib_hi[256][16];  // high-nibble products per constant
static bool gf_init_done = false;

static uint8_t gf_mul_slow(uint8_t a, uint8_t b) {
    uint16_t r = 0;
    uint16_t aa = a;
    for (int i = 0; i < 8; i++) {
        if (b & (1 << i)) r ^= (uint16_t)(aa << i);
    }
    // reduce mod x^8+x^4+x^3+x^2+1 (0x11D)
    for (int i = 15; i >= 8; i--) {
        if (r & (1 << i)) r ^= (0x11D << (i - 8));
    }
    return (uint8_t)r;
}

static void gf_init() {
    if (gf_init_done) return;
    for (int a = 0; a < 256; a++)
        for (int b = 0; b < 256; b++)
            gf_mul_table[a][b] = gf_mul_slow((uint8_t)a, (uint8_t)b);
    for (int c = 0; c < 256; c++) {
        for (int n = 0; n < 16; n++) {
            gf_nib_lo[c][n] = gf_mul_table[c][n];
            gf_nib_hi[c][n] = gf_mul_table[c][n << 4];
        }
    }
    gf_init_done = true;
}

// Portable scalar multiply-accumulate: out ^= c * in
static void gf_mul_xor_scalar(uint8_t c, const uint8_t* in, uint8_t* out, size_t n) {
    const uint8_t* t = gf_mul_table[c];
    for (size_t i = 0; i < n; i++) out[i] ^= t[in[i]];
}

#if defined(__x86_64__)
__attribute__((target("avx2")))
static void gf_mul_xor_avx2(uint8_t c, const uint8_t* in, uint8_t* out, size_t n) {
    __m256i lo = _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)gf_nib_lo[c]));
    __m256i hi = _mm256_broadcastsi128_si256(_mm_loadu_si128((const __m128i*)gf_nib_hi[c]));
    __m256i mask = _mm256_set1_epi8(0x0F);
    size_t i = 0;
    for (; i + 32 <= n; i += 32) {
        __m256i v = _mm256_loadu_si256((const __m256i*)(in + i));
        __m256i vlo = _mm256_and_si256(v, mask);
        __m256i vhi = _mm256_and_si256(_mm256_srli_epi64(v, 4), mask);
        __m256i p = _mm256_xor_si256(_mm256_shuffle_epi8(lo, vlo), _mm256_shuffle_epi8(hi, vhi));
        __m256i o = _mm256_loadu_si256((const __m256i*)(out + i));
        _mm256_storeu_si256((__m256i*)(out + i), _mm256_xor_si256(o, p));
    }
    if (i < n) gf_mul_xor_scalar(c, in + i, out + i, n - i);
}

__attribute__((target("ssse3")))
static void gf_mul_xor_ssse3(uint8_t c, const uint8_t* in, uint8_t* out, size_t n) {
    __m128i lo = _mm_loadu_si128((const __m128i*)gf_nib_lo[c]);
    __m128i hi = _mm_loadu_si128((const __m128i*)gf_nib_hi[c]);
    __m128i mask = _mm_set1_epi8(0x0F);
    size_t i = 0;
    for (; i + 16 <= n; i += 16) {
        __m128i v = _mm_loadu_si128((const __m128i*)(in + i));
        __m128i vlo = _mm_and_si128(v, mask);
        __m128i vhi = _mm_and_si128(_mm_srli_epi64(v, 4), mask);
        __m128i p = _mm_xor_si128(_mm_shuffle_epi8(lo, vlo), _mm_shuffle_epi8(hi, vhi));
        __m128i o = _mm_loadu_si128((const __m128i*)(out + i));
        _mm_storeu_si128((__m128i*)(out + i), _mm_xor_si128(o, p));
    }
    if (i < n) gf_mul_xor_scalar(c, in + i, out + i, n - i);
}
#endif

static void gf_mul_xor(uint8_t c, const uint8_t* in, uint8_t* out, size_t n) {
#if defined(__x86_64__)
    if (__builtin_cpu_supports("avx2")) { gf_mul_xor_avx2(c, in, out, n); return; }
    if (__builtin_cpu_supports("ssse3")) { gf_mul_xor_ssse3(c, in, out, n); return; }
#endif
    gf_mul_xor_scalar(c, in, out, n);
}

static void xor_into(const uint8_t* in, uint8_t* out, size_t n) {
    size_t i = 0;
    for (; i + 8 <= n; i += 8) {
        uint64_t a, b;
        memcpy(&a, out + i, 8);
        memcpy(&b, in + i, 8);
        a ^= b;
        memcpy(out + i, &a, 8);
    }
    for (; i < n; i++) out[i] ^= in[i];
}

// out[r] = XOR_j coeffs[r*in_rows+j] * data[j]   (rows are n-byte blocks)
// data: in_rows contiguous rows of n bytes; out: out_rows rows of n bytes.
void sn_rs_apply(const uint8_t* coeffs, int out_rows, int in_rows,
                 const uint8_t* data, uint8_t* out, size_t n) {
    gf_init();
    for (int r = 0; r < out_rows; r++) {
        uint8_t* dst = out + (size_t)r * n;
        memset(dst, 0, n);
        for (int j = 0; j < in_rows; j++) {
            uint8_t c = coeffs[r * in_rows + j];
            if (c == 0) continue;
            const uint8_t* src = data + (size_t)j * n;
            if (c == 1) {
                xor_into(src, dst, n);
            } else {
                gf_mul_xor(c, src, dst, n);
            }
        }
    }
}

uint8_t sn_gf_mul(uint8_t a, uint8_t b) {
    gf_init();
    return gf_mul_table[a][b];
}

// Column-parallel sn_rs_apply: splits the n columns across `nthreads`
// worker threads (parity is columnwise-independent, so any column split
// is bit-exact). Callers via ctypes release the GIL for the whole call.
void sn_rs_apply_mt(const uint8_t* coeffs, int out_rows, int in_rows,
                    const uint8_t* data, uint8_t* out, size_t n,
                    int nthreads) {
    gf_init();
    if (nthreads <= 1 || n < (1u << 16)) {
        sn_rs_apply(coeffs, out_rows, in_rows, data, out, n);
        return;
    }
    size_t chunk = (n + (size_t)nthreads - 1) / (size_t)nthreads;
    chunk = (chunk + 63) & ~(size_t)63;  // cache-line align column splits
    std::vector<std::thread> ts;
    for (size_t lo = 0; lo < n; lo += chunk) {
        size_t w = (lo + chunk <= n) ? chunk : (n - lo);
        ts.emplace_back([=]() {
            // Strided rows: copy each row slice into a contiguous scratch?
            // No — sn_rs_apply reads rows at data + j*n; a column window
            // needs per-row offsets, so inline the loop here instead.
            for (int r = 0; r < out_rows; r++) {
                uint8_t* dst = out + (size_t)r * n + lo;
                memset(dst, 0, w);
                for (int j = 0; j < in_rows; j++) {
                    uint8_t c = coeffs[r * in_rows + j];
                    if (c == 0) continue;
                    const uint8_t* src = data + (size_t)j * n + lo;
                    if (c == 1) xor_into(src, dst, w);
                    else gf_mul_xor(c, src, dst, w);
                }
            }
        });
    }
    for (auto& t : ts) t.join();
}

// ---------------------------------------------------------------------------
// Fused shard append + rolling block-CRC32C (the EC encoder's write stage).
// One call per batch replaces, per shard, a Python tobytes() copy + a
// buffered write + a bytes-slicing CRC loop — host overhead that used to
// dominate the encode's wall time. Mirrors the reference's single-pass
// encode+CRC loop (weed/storage/erasure_coding/ec_encoder.go:427-461).
// ---------------------------------------------------------------------------

static int write_full(int fd, const uint8_t* p, size_t len) {
    while (len) {
        ssize_t w = write(fd, p, len);
        if (w < 0) {
            if (errno == EINTR) continue;
            return -1;
        }
        p += w;
        len -= (size_t)w;
    }
    return 0;
}

// Advance one shard's rolling block-CRC state over `len` bytes; completed
// block CRCs append to out (capacity max_out). Returns count added, -1 on
// overflow.
static int roll_crc_blocks(uint32_t* crc, uint64_t* filled, uint32_t block_size,
                           const uint8_t* p, size_t len, uint32_t* out,
                           int32_t max_out) {
    int added = 0;
    while (len) {
        size_t room = (size_t)block_size - (size_t)*filled;
        size_t take = len < room ? len : room;
        *crc = sn_crc32c(*crc, p, take);
        *filled += take;
        p += take;
        len -= take;
        if (*filled == block_size) {
            if (added >= max_out) return -1;
            out[added++] = *crc;
            *crc = 0;
            *filled = 0;
        }
    }
    return added;
}

// Append `width` bytes from rows[i] to fds[i] and roll shard i's CRC state,
// for all nrows shards, one worker thread per shard (CRC while the bytes
// are cache-hot, then write(2) straight from the source buffer — no
// intermediate copies). crc_state/filled_state persist across calls;
// completed block CRCs land at out_crcs[i*max_out..], counts in
// out_counts[i]. Returns 0, or -(i+1) for the first failed shard.
int sn_shard_append(const int* fds, const uint8_t* const* rows, int nrows,
                    size_t width, uint32_t block_size, uint32_t* crc_state,
                    uint64_t* filled_state, uint32_t* out_crcs,
                    int32_t* out_counts, int32_t max_out) {
    crc32c_table_init();
    std::vector<int> status((size_t)nrows, 0);
    auto work = [&](int i) {
        int added = roll_crc_blocks(&crc_state[i], &filled_state[i], block_size,
                                    rows[i], width,
                                    out_crcs + (size_t)i * (size_t)max_out,
                                    max_out);
        if (added < 0) {
            out_counts[i] = 0;
            status[i] = -1;
            return;
        }
        out_counts[i] = added;
        if (write_full(fds[i], rows[i], width) != 0) status[i] = -1;
    };
    if (nrows > 1 && std::thread::hardware_concurrency() > 1) {
        std::vector<std::thread> ts;
        ts.reserve((size_t)nrows);
        for (int i = 0; i < nrows; i++) ts.emplace_back(work, i);
        for (auto& t : ts) t.join();
    } else {
        for (int i = 0; i < nrows; i++) work(i);
    }
    for (int i = 0; i < nrows; i++)
        if (status[i] != 0) return -(i + 1);
    return 0;
}

// ---------------------------------------------------------------------------
// Native read source: batched positioned reads landing directly in
// caller-owned (optionally O_DIRECT-aligned) buffers, one worker thread
// per row, with an optional fused rolling granule-CRC32C — the read half
// of the zero-copy data plane. One GIL-releasing call per batch replaces
// k Python preadv loops (and, on the rebuild path, k Python-side CRC
// rollers) per batch.
// ---------------------------------------------------------------------------

#include <fcntl.h>

// sn_batch_pread starts a thread a row only for rows this long; the
// encoder's and the rebuild's 16 MiB rows are, a needle's extent is not.
static const size_t kThreadMinWidth = (size_t)4 << 20;

// Read `width` bytes from fds[i] at offsets[i] into dst + i*stride.
// pad_eof!=0 zero-fills past EOF (the encoder's ragged tail); pad_eof==0
// treats a short read as that row's failure (the rebuild contract).
// With granule>0, each row's rolling CRC state (crc_state/filled_state,
// persisting across calls) is advanced over the bytes READ (not the
// zero padding); completed granule CRCs land at out_crcs[i*max_out..],
// counts in out_counts[i] (-1 = out_crcs overflow).
// Returns 0, or -(i+1) for the first failed row.
static int batch_pread(const int* fds, const uint64_t* offsets, int nrows,
                       uint8_t* dst, size_t width, size_t stride, int pad_eof,
                       uint32_t granule, uint32_t* crc_state,
                       uint64_t* filled_state, uint32_t* out_crcs,
                       int32_t* out_counts, int32_t max_out) {
    crc32c_table_init();
    std::vector<int> status((size_t)nrows, 0);
    auto work = [&](int i) {
        uint8_t* p = dst + (size_t)i * stride;
        size_t filled = 0;
        while (filled < width) {
            ssize_t got = pread(fds[i], p + filled, width - filled,
                                (off_t)(offsets[i] + filled));
            if (got < 0) {
                if (errno == EINTR) continue;
                status[i] = -1;
                return;
            }
            if (got == 0) break;  // EOF
            filled += (size_t)got;
        }
        if (filled < width) {
            if (!pad_eof) {
                status[i] = -1;
                return;
            }
            memset(p + filled, 0, width - filled);
        }
        if (granule > 0) {
            int added = roll_crc_blocks(&crc_state[i], &filled_state[i],
                                        granule, p, filled,
                                        out_crcs + (size_t)i * (size_t)max_out,
                                        max_out);
            if (added < 0) {
                out_counts[i] = -1;
                status[i] = -1;
                return;
            }
            out_counts[i] = added;
        } else if (out_counts) {
            out_counts[i] = 0;
        }
    };
    // Page-cache-warm rows are memcpy-bound: more workers than cores
    // just thrash. Cold rows are I/O-bound and still overlap fine at
    // core count (each worker drains rows in a strided loop). Rows
    // under kThreadMinWidth are read in a loop on the caller's thread:
    // a degraded read's matrix has rows of 64 KiB to 1 MiB, which one
    // thread copies faster than ten can be started, and sixteen such
    // reads at once would start 160 (PERF.md, PR 27, has the readings).
    unsigned hw = std::thread::hardware_concurrency();
    int nworkers = (int)(hw ? hw : 1);
    if (nworkers > nrows) nworkers = nrows;
    if (width < kThreadMinWidth) nworkers = 1;
    if (nworkers > 1) {
        std::vector<std::thread> ts;
        ts.reserve((size_t)nworkers);
        for (int w = 0; w < nworkers; w++)
            ts.emplace_back([&, w]() {
                for (int i = w; i < nrows; i += nworkers) work(i);
            });
        for (auto& t : ts) t.join();
    } else {
        for (int i = 0; i < nrows; i++) work(i);
    }
    for (int i = 0; i < nrows; i++)
        if (status[i] != 0) return -(i + 1);
    return 0;
}

int sn_batch_pread(const int* fds, const uint64_t* offsets, int nrows,
                   uint8_t* dst, size_t width, size_t stride, int pad_eof,
                   uint32_t granule, uint32_t* crc_state,
                   uint64_t* filled_state, uint32_t* out_crcs,
                   int32_t* out_counts, int32_t max_out, int64_t* ret_ns) {
    int rc = batch_pread(fds, offsets, nrows, dst, width, stride, pad_eof,
                         granule, crc_state, filled_state, out_crcs,
                         out_counts, max_out);
    stamp_return(ret_ns);
    return rc;
}

// CRC32C of every `granule`-byte piece of each row of a (nrows, width)
// matrix whose rows lie `stride` bytes apart; a row's last piece may be
// short. out[i * per_row + g] with per_row = ceil(width / granule). One
// GIL-releasing call checks the whole sibling matrix of a degraded read
// (or its one output row) against the sidecar's granule CRCs. Rows are
// walked in a loop: a reconstruction's few MiB are a millisecond of
// hardware CRC32C, less than starting a thread per row would cost.
void sn_crc32c_granules(const uint8_t* rows, int nrows, size_t width,
                        size_t stride, uint32_t granule, uint32_t* out,
                        int64_t* ret_ns) {
    size_t per_row = (width + granule - 1) / granule;
    for (int i = 0; i < nrows; i++) {
        const uint8_t* p = rows + (size_t)i * stride;
        for (size_t g = 0; g < per_row; g++) {
            size_t at = g * (size_t)granule;
            size_t len = width - at < granule ? width - at : granule;
            out[(size_t)i * per_row + g] = sn_crc32c(0, p + at, len);
        }
    }
    stamp_return(ret_ns);
}

// Best-effort readahead hint for the NEXT batch's extent; the producer
// issues it before reading the current batch so the kernel can overlap
// the next window's page-in with this batch's compute+write.
int sn_fadvise_willneed(int fd, uint64_t off, uint64_t len) {
#if defined(POSIX_FADV_WILLNEED)
    return posix_fadvise(fd, (off_t)off, (off_t)len, POSIX_FADV_WILLNEED);
#else
    (void)fd; (void)off; (void)len;
    return 0;
#endif
}

// ---------------------------------------------------------------------------
// Network byte plane (ISSUE 12): socket egress/ingress primitives so a
// byte served or rebuilt over the wire is copied (close to) once.
//
//   sn_send_file  - sendfile(2) a shard fd range straight into a socket
//                   (kernel-to-kernel; transparent pread+write fallback
//                   where the kernel path is unsupported);
//   sn_sendv      - scatter-gather writev from caller buffers (pooled
//                   aligned matrices, HTTP response bodies) without a
//                   Python-side join or per-chunk GIL round trips;
//   sn_recv_into  - land a socket stream DIRECTLY in a caller-owned
//                   buffer (a pooled rebuild matrix row), rolling the
//                   fused granule-CRC32C during the copy-in so sidecar
//                   verify costs no extra byte pass.
//
// ctypes releases the GIL for each whole call; timeouts follow the
// sn_net.h convention (Python settimeout sockets are O_NONBLOCK, so
// EAGAIN polls instead of failing).
// ---------------------------------------------------------------------------

int64_t sn_send_file(int out_fd, int in_fd, uint64_t offset, uint64_t len,
                     int timeout_ms, int64_t* ret_ns) {
    int64_t sent = sn_net::send_file(out_fd, in_fd, offset, len, timeout_ms);
    stamp_return(ret_ns);
    return sent;
}

// Scatter-gather send of n buffers. Returns total bytes sent (== sum of
// lens on success) or -errno; a peer that dies mid-stream surfaces as
// -EPIPE/-ECONNRESET, a stalled peer as -ETIMEDOUT.
static int64_t sendv(int fd, const uint8_t* const* bufs,
                     const uint64_t* lens, int n, int timeout_ms) {
    int64_t total = 0;
    int i = 0;
    uint64_t off = 0;  // progress within bufs[i]
    for (;;) {
        while (i < n && off >= lens[i]) {
            i++;
            off = 0;
        }
        if (i >= n) return total;
        struct iovec iov[64];
        int cnt = 0;
        for (int j = i; j < n && cnt < 64; j++) {
            uint64_t skip = (j == i) ? off : 0;
            if (lens[j] <= skip) continue;
            iov[cnt].iov_base = const_cast<uint8_t*>(bufs[j]) + skip;
            iov[cnt].iov_len = (size_t)(lens[j] - skip);
            cnt++;
        }
        ssize_t w = writev(fd, iov, cnt);
        if (w < 0) {
            if (errno == EINTR) continue;
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                int rc = sn_net::wait_fd(fd, POLLOUT, timeout_ms);
                if (rc != 0) return (int64_t)rc;
                continue;
            }
            return -(int64_t)errno;
        }
        total += w;
        uint64_t adv = (uint64_t)w;
        while (i < n && adv) {
            uint64_t rem = lens[i] - off;
            if (adv >= rem) {
                adv -= rem;
                off = 0;
                i++;
            } else {
                off += adv;
                adv = 0;
            }
        }
    }
}

int64_t sn_sendv(int fd, const uint8_t* const* bufs, const uint64_t* lens,
                 int n, int timeout_ms, int64_t* ret_ns) {
    int64_t sent = sendv(fd, bufs, lens, n, timeout_ms);
    stamp_return(ret_ns);
    return sent;
}

// Receive up to `len` bytes from fd straight into dst. With granule>0
// the rolling granule-CRC32C state (crc_state/filled_state, single-row
// arrays persisting across calls if the caller chooses) advances over
// the bytes WHILE they are cache-hot from the kernel copy-in; completed
// granule CRCs append to out_crcs (*out_count total, -1 on overflow of
// max_out). For large fused transfers the socket reads run on a helper
// thread with the CRC chasing the landed bytes from the calling thread
// — the verify OVERLAPS the wire instead of serializing behind it
// (CRC32C is ~5 GB/s on small hosts; inline it would cap ingress well
// below loopback/NIC speed). Returns bytes received — short means the
// peer closed mid-stream (the caller's torn-stream contract) — or
// -errno.

static int64_t recv_plain(int fd, uint8_t* dst, uint64_t len,
                          int timeout_ms, uint64_t* progress) {
    uint64_t got = 0;
    while (got < len) {
        ssize_t r = read(fd, dst + got, (size_t)(len - got));
        if (r < 0) {
            if (errno == EINTR) continue;
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                int rc = sn_net::wait_fd(fd, POLLIN, timeout_ms);
                if (rc != 0) return (int64_t)rc;
                continue;
            }
            return -(int64_t)errno;
        }
        if (r == 0) break;  // peer closed
        got += (uint64_t)r;
        if (progress)
            __atomic_store_n(progress, got, __ATOMIC_RELEASE);
    }
    return (int64_t)got;
}

// Transfers below this run the serial recv+CRC loop: a thread spawn
// costs more than it buys on small ranges (leaf repairs, tails). The
// overlap also needs spare cores: with fewer than 4 hardware threads
// the CRC helper just steals CPU from the socket copy (and, on
// loopback, from the peer's sendfile), measured slower than serial on
// a 2-core host — those run serial too. SEAWEED_EC_NET_OVERLAP
// overrides the CORE gate ("1" = force the overlapped core on, "0" =
// force serial, anything else/unset = the >=4-hardware-threads auto
// heuristic); the size floor always applies — overlapping a leaf-sized
// transfer never pays regardless of cores. The hot path takes the mode
// as a PARAMETER (computed Python-side under the GIL): getenv here
// would race a concurrent setenv from Python's os.environ, which is
// undefined behavior in glibc.
#define SN_RECV_OVERLAP_MIN (256u * 1024u)
#define SN_RECV_OVERLAP_MIN_CORES 4u

// mode: 0 = force serial, 1 = force overlapped, anything else = auto.
static bool recv_overlap_wanted(uint64_t len, int32_t mode) {
    if (len < SN_RECV_OVERLAP_MIN) return false;
    if (mode == 0) return false;
    if (mode == 1) return true;
    return std::thread::hardware_concurrency() >= SN_RECV_OVERLAP_MIN_CORES;
}

// Observability/test hook: whether a fused recv of `len` bytes would
// take the overlapped core under the current env/host. Cold path only
// — callers probe it sequentially, so the getenv here doesn't race.
int sn_recv_overlap_active(uint64_t len) {
    const char* env = getenv("SEAWEED_EC_NET_OVERLAP");
    int32_t mode = -1;
    // check env[0] BEFORE env[1]: an empty value is a 1-byte string
    // and reading past its terminator is out of bounds
    if (env && (env[0] == '0' || env[0] == '1') && env[1] == 0)
        mode = env[0] - '0';
    return recv_overlap_wanted(len, mode) ? 1 : 0;
}

static int64_t recv_into(int fd, uint8_t* dst, uint64_t len, int timeout_ms,
                         uint32_t granule, uint32_t* crc_state,
                         uint64_t* filled_state, uint32_t* out_crcs,
                         int32_t* out_count, int32_t max_out,
                         int32_t overlap_mode) {
    crc32c_table_init();
    if (out_count) *out_count = 0;
    if (granule == 0)
        return recv_plain(fd, dst, len, timeout_ms, nullptr);
    if (!recv_overlap_wanted(len, overlap_mode)) {
        // serial: recv then CRC the fresh bytes, chunk by chunk
        uint64_t got = 0;
        while (got < len) {
            uint64_t before = got;
            ssize_t r = read(fd, dst + got, (size_t)(len - got));
            if (r < 0) {
                if (errno == EINTR) continue;
                if (errno == EAGAIN || errno == EWOULDBLOCK) {
                    int rc = sn_net::wait_fd(fd, POLLIN, timeout_ms);
                    if (rc != 0) return (int64_t)rc;
                    continue;
                }
                return -(int64_t)errno;
            }
            if (r == 0) break;
            got += (uint64_t)r;
            int added = roll_crc_blocks(crc_state, filled_state, granule,
                                        dst + before, (size_t)r,
                                        out_crcs + *out_count,
                                        max_out - *out_count);
            if (added < 0) {
                *out_count = -1;
                return -EOVERFLOW;
            }
            *out_count += added;
        }
        return (int64_t)got;
    }
    // Overlapped: helper thread fills dst, this thread CRCs behind it.
    uint64_t progress = 0;
    int64_t recv_rc = 0;
    bool done = false;
    std::thread reader([&]() {
        recv_rc = recv_plain(fd, dst, len, timeout_ms, &progress);
        __atomic_store_n(&done, true, __ATOMIC_RELEASE);
    });
    uint64_t crc_done = 0;
    bool overflow = false;
    for (;;) {
        uint64_t avail = __atomic_load_n(&progress, __ATOMIC_ACQUIRE);
        bool finished = __atomic_load_n(&done, __ATOMIC_ACQUIRE);
        if (avail > crc_done) {
            int added = roll_crc_blocks(
                crc_state, filled_state, granule, dst + crc_done,
                (size_t)(avail - crc_done), out_crcs + *out_count,
                max_out - *out_count);
            if (added < 0) {
                overflow = true;
                break;
            }
            *out_count += added;
            crc_done = avail;
        } else if (finished) {
            break;
        } else {
            std::this_thread::yield();
        }
    }
    reader.join();
    if (overflow) {
        *out_count = -1;
        return -EOVERFLOW;
    }
    if (recv_rc < 0) return recv_rc;
    // CRC whatever landed after the last loop pass
    uint64_t got = (uint64_t)recv_rc;
    if (got > crc_done) {
        int added = roll_crc_blocks(crc_state, filled_state, granule,
                                    dst + crc_done, (size_t)(got - crc_done),
                                    out_crcs + *out_count,
                                    max_out - *out_count);
        if (added < 0) {
            *out_count = -1;
            return -EOVERFLOW;
        }
        *out_count += added;
    }
    return recv_rc;
}

int64_t sn_recv_into(int fd, uint8_t* dst, uint64_t len, int timeout_ms,
                     uint32_t granule, uint32_t* crc_state,
                     uint64_t* filled_state, uint32_t* out_crcs,
                     int32_t* out_count, int32_t max_out,
                     int32_t overlap_mode, int64_t* ret_ns) {
    int64_t got = recv_into(fd, dst, len, timeout_ms, granule, crc_state,
                            filled_state, out_crcs, out_count, max_out,
                            overlap_mode);
    stamp_return(ret_ns);
    return got;
}

static int pwrite_full(int fd, const uint8_t* p, size_t len, uint64_t off) {
    while (len) {
        ssize_t w = pwrite(fd, p, len, (off_t)off);
        if (w < 0) {
            if (errno == EINTR) continue;
            return -1;
        }
        p += w;
        len -= (size_t)w;
        off += (uint64_t)w;
    }
    return 0;
}

// Land `len` socket bytes straight into file out_fd at `offset`
// (socket -> 256 KiB bounce buffer -> pwrite(2)), rolling ONE CRC32C
// over the whole payload while each chunk is cache-hot — the blob-write
// landing of the net plane's write opcode: the payload never crosses
// into Python. Returns bytes landed — short means the peer closed
// mid-stream (the partial extent is on disk but the caller never ACKs
// it, so the sender's watermark does not advance) — or -errno from the
// socket or the pwrite. *crc_out holds the rolled CRC of the landed
// prefix on any non-negative return.
int64_t sn_recv_file(int fd, int out_fd, uint64_t offset, uint64_t len,
                     int timeout_ms, uint32_t* crc_out) {
    crc32c_table_init();
    const size_t CHUNK = 256u * 1024u;
    std::vector<uint8_t> buf((size_t)(len < CHUNK ? len : CHUNK));
    uint32_t crc = 0;
    uint64_t got = 0;
    while (got < len) {
        size_t want = (size_t)(len - got < (uint64_t)buf.size()
                                   ? len - got
                                   : (uint64_t)buf.size());
        ssize_t r = read(fd, buf.data(), want);
        if (r < 0) {
            if (errno == EINTR) continue;
            if (errno == EAGAIN || errno == EWOULDBLOCK) {
                int rc = sn_net::wait_fd(fd, POLLIN, timeout_ms);
                if (rc != 0) return (int64_t)rc;
                continue;
            }
            return -(int64_t)errno;
        }
        if (r == 0) break;  // peer closed
        crc = sn_crc32c(crc, buf.data(), (size_t)r);
        if (pwrite_full(out_fd, buf.data(), (size_t)r, offset + got) != 0)
            return -(int64_t)errno;
        got += (uint64_t)r;
    }
    if (crc_out) *crc_out = crc;
    return (int64_t)got;
}

// ---------------------------------------------------------------------------
// Stateful fused shard sink: the write half of the zero-copy data plane.
// One handle per encode/rebuild stream; each append pwrite(2)s every
// shard's row straight from the source buffer at an internally-tracked
// offset (the Python file object's position is never moved) and rolls
// BOTH sidecar CRC levels — per-leaf and per-block — in the same
// cache-hot pass, so the v2 .ecsum needs no Python-side folding.
// SN_SINK_EARLY_WB additionally kicks off background writeback
// (sync_file_range) for the just-written extent so the final fsync
// drains an already-flushing page range instead of the whole file.
// ---------------------------------------------------------------------------

#define SN_SINK_EARLY_WB 1u
// Opt-in O_DIRECT write path: bypass the page cache when (and only
// while) every append stays 4096-aligned — pointer, width, and file
// offset. The pooled matrices are 4096-aligned by construction, so
// full batches qualify; the ragged tail (or a filesystem that accepts
// the flag but rejects the write, e.g. 9p) transparently drops THAT
// shard fd back to buffered and the stream continues bit-identically.
#define SN_SINK_DIRECT 2u
#define SN_DIRECT_ALIGN 4096u

static int set_fd_direct(int fd, bool on) {
#if defined(O_DIRECT)
    int fl = fcntl(fd, F_GETFL);
    if (fl < 0) return -1;
    int nfl = on ? (fl | O_DIRECT) : (fl & ~O_DIRECT);
    if (fl == nfl) return 0;
    return fcntl(fd, F_SETFL, nfl) == 0 ? 0 : -1;
#else
    (void)fd;
    (void)on;
    return -1;
#endif
}

struct SnSink {
    std::vector<int> fds;
    std::vector<char> direct;     // shard currently writing O_DIRECT
    std::vector<uint64_t> off;    // next pwrite offset per shard
    uint32_t block_size;
    uint32_t leaf_size;           // 0 = v1 sidecar (block level only)
    uint32_t flags;
    // leaf_size == 0: direct byte-rolled block CRC (bcrc/bfill).
    // leaf_size > 0: the block level is FOLDED from completed leaf
    // CRCs via the cached shift-by-leaf operator (leaf_op) — one byte
    // pass total for both sidecar levels.
    std::vector<uint32_t> bcrc;   // rolling block-CRC state / folded acc
    std::vector<uint64_t> bfill;  // bytes (v1) or completed leaves (v2)
    std::vector<uint32_t> lcrc;   // rolling leaf-CRC state
    std::vector<uint64_t> lfill;
    uint32_t leaf_op[32];         // CRC shift operator for leaf_size bytes
};

void* sn_sink_create(const int* fds, int n, uint32_t block_size,
                     uint32_t leaf_size, uint32_t flags) {
    if (n <= 0 || block_size == 0) return nullptr;
    if (leaf_size != 0 && block_size % leaf_size != 0) return nullptr;
    crc32c_table_init();
    SnSink* s = new SnSink();
    s->fds.assign(fds, fds + n);
    s->direct.assign((size_t)n, 0);
    if (flags & SN_SINK_DIRECT) {
        for (int i = 0; i < n; i++)
            s->direct[(size_t)i] = set_fd_direct(fds[i], true) == 0 ? 1 : 0;
    }
    s->off.assign((size_t)n, 0);
    s->block_size = block_size;
    s->leaf_size = leaf_size;
    s->flags = flags;
    s->bcrc.assign((size_t)n, 0);
    s->bfill.assign((size_t)n, 0);
    s->lcrc.assign((size_t)n, 0);
    s->lfill.assign((size_t)n, 0);
    if (leaf_size) crc32c_shift_op(s->leaf_op, leaf_size);
    return s;
}

// Direct-aware shard write: while shard i is in O_DIRECT mode, keep it
// there only for fully aligned appends; a misaligned append (the
// ragged tail) or a write the filesystem rejects (EINVAL despite
// accepting the flag) drops THAT fd back to buffered — transparently,
// with the same bytes landing at the same offset.
static int sink_pwrite(SnSink* s, int i, const uint8_t* p, size_t len,
                       uint64_t off) {
    if (s->direct[(size_t)i]) {
        bool aligned = ((uintptr_t)p % SN_DIRECT_ALIGN == 0) &&
                       (len % SN_DIRECT_ALIGN == 0) &&
                       (off % SN_DIRECT_ALIGN == 0);
        if (!aligned) {
            set_fd_direct(s->fds[(size_t)i], false);
            s->direct[(size_t)i] = 0;
        }
    }
    while (len) {
        ssize_t w = pwrite(s->fds[(size_t)i], p, len, (off_t)off);
        if (w < 0) {
            if (errno == EINTR) continue;
            if (errno == EINVAL && s->direct[(size_t)i]) {
                set_fd_direct(s->fds[(size_t)i], false);
                s->direct[(size_t)i] = 0;
                continue;  // retry buffered
            }
            return -1;
        }
        p += w;
        off += (uint64_t)w;
        len -= (size_t)w;
    }
    return 0;
}

// Append `width` bytes from rows[i] to shard i for all shards, one
// worker thread per shard. Completed block CRCs land at
// out_block_crcs[i*max_out..] (counts in out_block_counts[i]); with a
// leaf level, completed leaf CRCs likewise in out_leaf_*. A -1 count
// reports out-array overflow. Returns 0 or -(i+1) for the first failed
// shard.
static int sink_append(void* handle, const uint8_t* const* rows, size_t width,
                       uint32_t* out_block_crcs, int32_t* out_block_counts,
                       uint32_t* out_leaf_crcs, int32_t* out_leaf_counts,
                       int32_t max_out) {
    SnSink* s = (SnSink*)handle;
    int n = (int)s->fds.size();
    uint32_t leaves_per_block =
        s->leaf_size ? s->block_size / s->leaf_size : 0;
    std::vector<int> status((size_t)n, 0);
    auto work = [&](int i) {
        // CRC first, while the bytes are cache-hot from the encode
        if (s->leaf_size) {
            // ONE byte pass (leaf granularity); the block level folds
            // from the completed leaf CRCs via the cached operator.
            uint32_t* leaf_out =
                out_leaf_crcs + (size_t)i * (size_t)max_out;
            int added = roll_crc_blocks(&s->lcrc[i], &s->lfill[i],
                                        s->leaf_size, rows[i], width,
                                        leaf_out, max_out);
            if (added < 0) {
                out_leaf_counts[i] = -1;
                status[i] = -1;
                return;
            }
            out_leaf_counts[i] = added;
            uint32_t* block_out =
                out_block_crcs + (size_t)i * (size_t)max_out;
            int nblocks = 0;
            for (int l = 0; l < added; l++) {
                s->bcrc[i] =
                    gf2_matrix_times(s->leaf_op, s->bcrc[i]) ^ leaf_out[l];
                if (++s->bfill[i] == leaves_per_block) {
                    if (nblocks >= max_out) {
                        out_block_counts[i] = -1;
                        status[i] = -1;
                        return;
                    }
                    block_out[nblocks++] = s->bcrc[i];
                    s->bcrc[i] = 0;
                    s->bfill[i] = 0;
                }
            }
            out_block_counts[i] = nblocks;
        } else {
            int added = roll_crc_blocks(
                &s->bcrc[i], &s->bfill[i], s->block_size, rows[i], width,
                out_block_crcs + (size_t)i * (size_t)max_out, max_out);
            if (added < 0) {
                out_block_counts[i] = -1;
                status[i] = -1;
                return;
            }
            out_block_counts[i] = added;
            if (out_leaf_counts) out_leaf_counts[i] = 0;
        }
        uint64_t at = s->off[i];
        if (sink_pwrite(s, i, rows[i], width, at) != 0) {
            status[i] = -1;
            return;
        }
        s->off[i] = at + width;
#if defined(__linux__) && defined(SYNC_FILE_RANGE_WRITE)
        if (s->flags & SN_SINK_EARLY_WB) {
            // best-effort: some filesystems reject it (EINVAL/ESPIPE);
            // writeback then simply waits for the caller's fsync
            (void)sync_file_range(s->fds[i], (off_t)at, (off_t)width,
                                  SYNC_FILE_RANGE_WRITE);
        }
#endif
    };
    if (n > 1 && std::thread::hardware_concurrency() > 1) {
        std::vector<std::thread> ts;
        ts.reserve((size_t)n);
        for (int i = 0; i < n; i++) ts.emplace_back(work, i);
        for (auto& t : ts) t.join();
    } else {
        for (int i = 0; i < n; i++) work(i);
    }
    for (int i = 0; i < n; i++)
        if (status[i] != 0) return -(i + 1);
    return 0;
}

int sn_sink_append(void* handle, const uint8_t* const* rows, size_t width,
                   uint32_t* out_block_crcs, int32_t* out_block_counts,
                   uint32_t* out_leaf_crcs, int32_t* out_leaf_counts,
                   int32_t max_out, int64_t* ret_ns) {
    int rc = sink_append(handle, rows, width, out_block_crcs,
                         out_block_counts, out_leaf_crcs, out_leaf_counts,
                         max_out);
    stamp_return(ret_ns);
    return rc;
}

// Flush the partial-tail CRC of each level (valid flag per shard) and
// report per-shard appended sizes. The sink stays usable only for
// destroy after this.
int sn_sink_finish(void* handle, uint32_t* tail_block_crc,
                   uint8_t* tail_block_valid, uint32_t* tail_leaf_crc,
                   uint8_t* tail_leaf_valid, uint64_t* sizes) {
    SnSink* s = (SnSink*)handle;
    int n = (int)s->fds.size();
    for (int i = 0; i < n; i++) {
        if (s->leaf_size) {
            // partial block = folded completed leaves + partial leaf
            tail_block_valid[i] = (s->bfill[i] || s->lfill[i]) ? 1 : 0;
            tail_block_crc[i] =
                sn_crc32c_combine(s->bcrc[i], s->lcrc[i], s->lfill[i]);
        } else {
            tail_block_valid[i] = s->bfill[i] ? 1 : 0;
            tail_block_crc[i] = s->bcrc[i];
        }
        if (tail_leaf_valid) {
            tail_leaf_valid[i] = (s->leaf_size && s->lfill[i]) ? 1 : 0;
            tail_leaf_crc[i] = s->lcrc[i];
        }
        sizes[i] = s->off[i];
        s->bfill[i] = 0;
        s->bcrc[i] = 0;
        s->lfill[i] = 0;
        s->lcrc[i] = 0;
    }
    return 0;
}

// Per-shard O_DIRECT state (1 = still writing O_DIRECT): lets callers
// and tests observe whether the direct path engaged or fell back.
int sn_sink_direct_flags(void* handle, uint8_t* out) {
    SnSink* s = (SnSink*)handle;
    for (size_t i = 0; i < s->fds.size(); i++) out[i] = (uint8_t)s->direct[i];
    return (int)s->fds.size();
}

void sn_sink_destroy(void* handle) {
    delete (SnSink*)handle;
}

// ---------------------------------------------------------------------------
// Volume .dat scanner: sequential needle walk with CRC verification.
// Mirrors seaweedfs_tpu/storage/volume_scan.py (v2/v3 record layout);
// used by the offline `fix` tool and online scrub for large volumes.
// ---------------------------------------------------------------------------

#include <sys/mman.h>
#include <sys/stat.h>
#include <fcntl.h>
#include <unistd.h>

static inline uint32_t be32(const uint8_t* p) {
    return ((uint32_t)p[0] << 24) | ((uint32_t)p[1] << 16) |
           ((uint32_t)p[2] << 8) | (uint32_t)p[3];
}

static inline uint64_t be64(const uint8_t* p) {
    return ((uint64_t)be32(p) << 32) | be32(p + 4);
}

// Scan `path`; fill parallel arrays (ids, stored offsets in 8-byte units,
// body sizes, crc flags). Returns the record count, -1 on open/format
// error, -2 if max_entries is too small.
int64_t sn_scan_dat(const char* path, uint64_t* ids, uint32_t* offsets,
                    int32_t* sizes, uint8_t* crc_ok, int64_t max_entries) {
    crc32c_table_init();
    int fd = open(path, O_RDONLY);
    if (fd < 0) return -1;
    struct stat st;
    if (fstat(fd, &st) != 0 || st.st_size < 8) {
        close(fd);
        return -1;
    }
    size_t size = (size_t)st.st_size;
    const uint8_t* buf =
        (const uint8_t*)mmap(nullptr, size, PROT_READ, MAP_PRIVATE, fd, 0);
    close(fd);
    if (buf == MAP_FAILED) return -1;

    uint8_t version = buf[0];
    if (version != 2 && version != 3) {  // not a known volume format
        munmap((void*)buf, size);
        return -1;
    }
    size_t footer = 4 + (version == 3 ? 8 : 0);
    int64_t count = 0;
    size_t off = 8;  // superblock
    while (off + 16 <= size) {
        uint64_t nid = be64(buf + off + 4);
        uint32_t body = be32(buf + off + 12);
        size_t rec = 16 + (size_t)body + footer;
        rec = (rec + 7) & ~(size_t)7;  // 8-byte padding
        if (off + rec > size) break;   // truncated tail
        if (count >= max_entries) {
            munmap((void*)buf, size);
            return -2;
        }
        uint8_t ok = 1;
        if (body > 0) {
            // body = [dataSize(4) | data | flags(1) | ...]; CRC covers data
            if (body >= 5) {
                uint32_t data_size = be32(buf + off + 16);
                if ((size_t)data_size + 5 <= body) {
                    uint32_t crc = sn_crc32c(0, buf + off + 20, data_size);
                    uint32_t stored = be32(buf + off + 16 + body);
                    ok = (crc == stored) ? 1 : 0;
                } else {
                    ok = 0;  // corrupt dataSize
                }
            } else {
                ok = 0;
            }
        }
        ids[count] = nid;
        offsets[count] = (uint32_t)(off / 8);
        sizes[count] = (int32_t)body;
        crc_ok[count] = ok;
        count++;
        off += rec;
    }
    munmap((void*)buf, size);
    return count;
}

// ---------------------------------------------------------------------------
// The core-wait probe: a thread that never touches Python sleeps
// `period_ns` at a time (clock_nanosleep to an absolute deadline) and
// records how late it woke: ready to run until it had a core. Its twin
// in utils/interp_probe.py does the same from a Python thread, where
// waking also means taking the interpreter, so the twin's wait less this
// one is the queue for the interpreter itself. Samples (wake time, wait)
// go into a fixed ring; sn_probe_read copies out what a cursor has not
// seen. One probe a process; nothing runs until sn_probe_start.
// ---------------------------------------------------------------------------

static const uint64_t kProbeRing = 4096;  // 20 s at the probe's 5 ms
static int64_t probe_ring[kProbeRing][2];
static std::atomic<uint64_t> probe_head{0};  // samples written, ever
static std::atomic<int> probe_running{0};
static int64_t probe_period_ns = 0;
static pthread_t probe_thread;
static pid_t probe_pid = 0;  // a forked child inherits the flag, not the thread

static void* probe_main(void*) {
    int64_t deadline = mono_ns() + probe_period_ns;
    while (probe_running.load(std::memory_order_relaxed)) {
        struct timespec ts;
        ts.tv_sec = (time_t)(deadline / 1000000000LL);
        ts.tv_nsec = (long)(deadline % 1000000000LL);
        while (clock_nanosleep(CLOCK_MONOTONIC, TIMER_ABSTIME, &ts, nullptr)
               == EINTR) {
        }
        int64_t now = mono_ns();
        uint64_t h = probe_head.load(std::memory_order_relaxed);
        probe_ring[h % kProbeRing][0] = now;
        probe_ring[h % kProbeRing][1] = now - deadline;
        probe_head.store(h + 1, std::memory_order_release);
        deadline = now + probe_period_ns;
    }
    return nullptr;
}

// 0, or -errno (-EBUSY where the probe already runs).
int sn_probe_start(int64_t period_ns) {
    if (period_ns <= 0) return -EINVAL;
    if (probe_pid != getpid()) probe_running.store(0);
    int was = 0;
    if (!probe_running.compare_exchange_strong(was, 1)) return -EBUSY;
    probe_pid = getpid();
    probe_period_ns = period_ns;
    int rc = pthread_create(&probe_thread, nullptr, probe_main, nullptr);
    if (rc != 0) {
        probe_running.store(0);
        return -rc;
    }
    return 0;
}

// Stops and joins the probe (at most one period); 0 where none runs.
int sn_probe_stop() {
    int was = 1;
    if (!probe_running.compare_exchange_strong(was, 0)) return 0;
    if (probe_pid != getpid()) return 0;
    return -pthread_join(probe_thread, nullptr);
}

// Samples written so far: a reader's first cursor.
uint64_t sn_probe_head() {
    return probe_head.load(std::memory_order_acquire);
}

// Copy the samples from *cursor on into out (pairs: wake time, wait; both
// ns on CLOCK_MONOTONIC), at most max_pairs, and move *cursor past them.
// A reader that fell a whole ring behind loses the overwritten ones.
int32_t sn_probe_read(uint64_t* cursor, int64_t* out, int32_t max_pairs) {
    uint64_t head = probe_head.load(std::memory_order_acquire);
    uint64_t at = *cursor;
    if (head - at > kProbeRing) at = head - kProbeRing;
    int32_t n = 0;
    for (; at < head && n < max_pairs; at++, n++) {
        out[2 * n] = probe_ring[at % kProbeRing][0];
        out[2 * n + 1] = probe_ring[at % kProbeRing][1];
    }
    *cursor = at;
    return n;
}

int sn_has_avx2() {
#if defined(__x86_64__)
    return __builtin_cpu_supports("avx2") ? 1 : 0;
#else
    return 0;
#endif
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The holder's side of a shard plane read in ONE call (ISSUE 39). A
// connection thread of ec/net_plane.ShardNetPlane calls sn_plane_serve,
// which waits for the next request on its connection, reads the 38-byte
// header and the metadata, and, where the request is a shard range
// (`SWNP`) whose (volume, shard) the plane's table holds at the asked
// generation (0 matches any) while native egress is admissible, sends the
// 9-byte answer header and sendfile(2)s the range clamped at EOF before it
// returns: the interpreter is not taken once between the request and the
// last byte. Everything else comes back with its header and metadata read,
// for the Python code to serve as before.
//
// The table holds a dup(2) of each shard fd under a reference count: an
// entry dropped while a sendfile reads it is closed by the last reader, so
// no send ever reads a closed fd or a reused fd number. Python drops an
// entry before the volume closes or replaces its own fd.
// ---------------------------------------------------------------------------

namespace {

struct PlaneEntry {
    int fd;
    uint64_t gen;
    int refs;
    bool dropped;
};

struct PlaneTable {
    std::mutex mu;
    std::unordered_map<uint64_t, PlaneEntry*> map;
};

// native egress admissible: the fault registry disarmed (Python pushes it
// on every arm and disarm; a connection's call adds the env gate)
std::atomic<int> plane_admissible{1};

inline uint64_t plane_key(uint32_t vid, uint32_t sid) {
    return ((uint64_t)vid << 32) | sid;
}

// caller holds the table's lock; the entry has left the map
void plane_drop_locked(PlaneEntry* e) {
    e->dropped = true;
    if (e->refs == 0) {
        close(e->fd);
        delete e;
    }
}

void plane_unref_locked(PlaneEntry* e) {
    if (--e->refs == 0 && e->dropped) {
        close(e->fd);
        delete e;
    }
}

inline int64_t thread_cpu_ns() {
    struct timespec ts;
    clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts);
    return (int64_t)ts.tv_sec * 1000000000LL + (int64_t)ts.tv_nsec;
}

// what sn_plane_serve did (its return value and rec[0])
enum {
    PS_SERVED = 0, PS_HANDED_BACK = 1, PS_IDLE = 2, PS_CLOSED = 3,
    PS_NOT_PROTOCOL = 4,
};
// the slots of its record (u64 each): keep utils/native.py's PLANE_REC
// in step
enum {
    PR_WHAT, PR_MAGIC, PR_VID, PR_SID, PR_GEN, PR_OFF, PR_SIZE, PR_MLEN, PR_N,
    PR_SENT, PR_OK, PR_T_REQ, PR_T_RES, PR_T_HDR, PR_T_SENT, PR_C_REQ, PR_C_RES,
    PR_C_HDR, PR_C_SENT, PR_SLOTS,
};

const uint64_t kMaxRequest = 1ULL << 32;  // net_plane._MAX_REQUEST
const uint16_t kMaxMeta = 4096;           // net_plane._MAX_META

}  // namespace

extern "C" {

void* sn_plane_table_new() { return new PlaneTable(); }

// Enter (vid, sid) at generation `gen` with a duplicate of `fd`, replacing
// what was there. 0, or -errno where the dup failed (nothing entered).
int sn_plane_table_put(void* table, uint32_t vid, uint32_t sid, uint64_t gen,
                       int fd) {
    auto* t = static_cast<PlaneTable*>(table);
    int dup_fd = fcntl(fd, F_DUPFD_CLOEXEC, 0);
    if (dup_fd < 0) return -errno;
    auto* e = new PlaneEntry{dup_fd, gen, 0, false};
    std::lock_guard<std::mutex> g(t->mu);
    auto it = t->map.find(plane_key(vid, sid));
    if (it != t->map.end()) {
        plane_drop_locked(it->second);
        it->second = e;
    } else {
        t->map.emplace(plane_key(vid, sid), e);
    }
    return 0;
}

void sn_plane_table_drop(void* table, uint32_t vid, uint32_t sid) {
    auto* t = static_cast<PlaneTable*>(table);
    std::lock_guard<std::mutex> g(t->mu);
    auto it = t->map.find(plane_key(vid, sid));
    if (it == t->map.end()) return;
    plane_drop_locked(it->second);
    t->map.erase(it);
}

// Drop every entry. The table itself is never freed: a connection thread
// may still be inside sn_plane_serve when its plane stops.
void sn_plane_table_clear(void* table) {
    auto* t = static_cast<PlaneTable*>(table);
    std::lock_guard<std::mutex> g(t->mu);
    for (auto& kv : t->map) plane_drop_locked(kv.second);
    t->map.clear();
}

void sn_plane_set_admissible(int yes) {
    plane_admissible.store(yes ? 1 : 0, std::memory_order_release);
}

// One request on connection `conn` (a socket with a Python time-out, so
// O_NONBLOCK: every wait is a poll of at most timeout_ms). `native_ok` is
// the caller's gate (the .so's egress switched on). rec: u64[PR_SLOTS];
// meta: at least kMaxMeta bytes, where the request's metadata lands. The
// stamps are CLOCK_MONOTONIC (time.perf_counter_ns) and the thread's CPU
// clock (time.thread_time_ns) when the request had been read, the range
// looked up, the answer's header sent and the range sent.
int sn_plane_serve(void* table, int conn, int timeout_ms, int native_ok,
                   uint64_t* rec, uint8_t* meta) {
    auto* t = static_cast<PlaneTable*>(table);
    memset(rec, 0, sizeof(uint64_t) * PR_SLOTS);
    rec[PR_WHAT] = PS_CLOSED;
    if (conn < 0) return PS_CLOSED;
    int rc = sn_net::wait_fd(conn, POLLIN, timeout_ms);
    if (rc == -ETIMEDOUT) return rec[PR_WHAT] = PS_IDLE;
    if (rc != 0) return PS_CLOSED;
    uint8_t hdr[38];
    if (sn_net::read_full(conn, hdr, sizeof(hdr), timeout_ms) != (int64_t)sizeof(hdr))
        return PS_CLOSED;
    uint32_t magic, vid, sid;
    uint64_t gen, off, size;
    uint16_t mlen;
    memcpy(&magic, hdr, 4);
    memcpy(&vid, hdr + 4, 4);
    memcpy(&sid, hdr + 8, 4);
    memcpy(&gen, hdr + 12, 8);
    memcpy(&off, hdr + 20, 8);
    memcpy(&size, hdr + 28, 8);
    memcpy(&mlen, hdr + 36, 2);
    bool range = memcmp(hdr, "SWNP", 4) == 0;
    if ((!range && memcmp(hdr, "SWNR", 4) != 0 && memcmp(hdr, "SWNW", 4) != 0)
        || size > kMaxRequest || mlen > kMaxMeta)
        return rec[PR_WHAT] = PS_NOT_PROTOCOL;
    if (mlen && sn_net::read_full(conn, meta, mlen, timeout_ms) != (int64_t)mlen)
        return PS_CLOSED;
    rec[PR_MAGIC] = magic;
    rec[PR_VID] = vid;
    rec[PR_SID] = sid;
    rec[PR_GEN] = gen;
    rec[PR_OFF] = off;
    rec[PR_SIZE] = size;
    rec[PR_MLEN] = mlen;
    rec[PR_T_REQ] = (uint64_t)mono_ns();
    rec[PR_C_REQ] = (uint64_t)thread_cpu_ns();
    rec[PR_WHAT] = PS_HANDED_BACK;
    if (!range || !native_ok ||
        !plane_admissible.load(std::memory_order_acquire))
        return PS_HANDED_BACK;
    PlaneEntry* e = nullptr;
    {
        std::lock_guard<std::mutex> g(t->mu);
        auto it = t->map.find(plane_key(vid, sid));
        if (it != t->map.end() && (gen == 0 || it->second->gen == gen)) {
            e = it->second;
            e->refs++;
        }
    }
    if (e == nullptr) return PS_HANDED_BACK;
    struct stat st;
    if (fstat(e->fd, &st) != 0) {
        std::lock_guard<std::mutex> g(t->mu);
        plane_unref_locked(e);
        return PS_HANDED_BACK;
    }
    uint64_t fsize = (uint64_t)st.st_size;
    uint64_t n = off < fsize ? (size < fsize - off ? size : fsize - off) : 0;
    rec[PR_N] = n;
    rec[PR_T_RES] = (uint64_t)mono_ns();
    rec[PR_C_RES] = (uint64_t)thread_cpu_ns();
    uint8_t resp[9];
    resp[0] = 0;
    memcpy(resp + 1, &n, 8);
    bool ok = sn_net::write_full(conn, resp, sizeof(resp), timeout_ms) == 0;
    rec[PR_T_HDR] = (uint64_t)mono_ns();
    rec[PR_C_HDR] = (uint64_t)thread_cpu_ns();
    if (ok && n) {
        int64_t sent = sn_net::send_file(conn, e->fd, off, n, timeout_ms);
        if (sent < 0) {
            ok = false;
        } else {
            rec[PR_SENT] = (uint64_t)sent;
            ok = (uint64_t)sent == n;
        }
    }
    {
        std::lock_guard<std::mutex> g(t->mu);
        plane_unref_locked(e);
    }
    rec[PR_OK] = ok ? 1 : 0;
    rec[PR_T_SENT] = (uint64_t)mono_ns();
    rec[PR_C_SENT] = (uint64_t)thread_cpu_ns();
    return rec[PR_WHAT] = PS_SERVED;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// The reader's side of shard plane reads in ONE call. ec/net_plane.py's
// NetPlaneClient checks a connection out of its pool for each range and
// packs each request (header and metadata, the read's own trace context
// in it); sn_plane_fetch sends them all, then multiplexes the sockets
// with poll(2), parsing each 9-byte answer header as it comes and landing
// each range in a destination handed out IN THE ORDER THE ANSWERS ARRIVE
// (at the first payload byte: a header alone takes none), lowest index
// first: a reconstruction lists its matrix's open rows first and its
// spare rows after them, so the first answers land where they are used
// and a spare is written only by a range that came later than the rows'
// own. The granule CRCs roll while a range lands, as in
// sn_recv_into. A refusal has its message read, so its connection stays
// in frame. The call returns once `need` ranges have landed whole, or
// when every range has finished or gone silent for timeout_ms; what
// already sits in the socket buffers is landed on the way out, and a
// range still in flight leaves its connection out of frame. The
// interpreter is not taken once between the first request and the
// return.
// ---------------------------------------------------------------------------

namespace {

// what became of a range (its record's PF_STATUS): keep utils/native.py's
// FETCH_STATUS in step
enum {
    PFS_LANDED = 0,     // the whole range landed; the connection is in frame
    PFS_REFUSED = 1,    // refused (answer status != 0), message read: in frame
    PFS_SHORT = 2,      // answered shorter than asked (the EOF clamp)
    PFS_BAD_FRAME = 3,  // answered longer than asked, or a refusal too long
    PFS_TORN = 4,       // the connection failed or closed before the end
    PFS_TIMED_OUT = 5,  // silent for timeout_ms
    PFS_IN_FLIGHT = 6,  // not done when `need` ranges had landed
};
// the slots of a range's record (i64 each): keep utils/native.py's
// FETCH_REC in step. PF_ANSWER is the header's status byte (-1 before
// one came); the stamps are CLOCK_MONOTONIC (time.perf_counter_ns), 0
// where not reached
enum {
    PF_STATUS, PF_ANSWER, PF_N, PF_GOT, PF_DST, PF_CRCS, PF_TAIL_CRC,
    PF_MSG_LEN, PF_T_SENT, PF_T_HDR, PF_T_LAND, PF_SLOTS,
};

const uint64_t kMaxRefusal = 1u << 16;  // net_plane._MAX_ERROR

enum FetchPhase { FP_HEADER, FP_REFUSAL, FP_PAYLOAD, FP_DONE };

struct FetchRange {
    int fd;
    uint64_t size;
    FetchPhase phase;
    uint8_t hdr[9];
    uint32_t hgot;
    uint64_t left;  // refusal bytes still to read
    int dst;
    uint64_t got;
    uint32_t crc;
    uint64_t filled;
    int32_t ncrcs;
    int64_t last_ns;  // the last byte that came, or the request sent
};

}  // namespace

extern "C" {

// n ranges, range i on connected plane socket fds[i], its request reqs[i]
// (req_lens[i] bytes) asking sizes[i] bytes. n_dsts destinations, dsts[d]
// of caps[d] bytes. granule > 0: range i's completed granule CRCs land at
// out_crcs[i * max_out ..] (PF_CRCS of them), its partial tail's in
// PF_TAIL_CRC. msgs: n * msg_cap bytes, where a refusal's message lands
// (its first msg_cap bytes; PF_MSG_LEN). overlap_mode as sn_recv_into's:
// a single range that wants it lands through the overlapped core. rec:
// n * PF_SLOTS. Returns the number of ranges landed, or -EINVAL.
int sn_plane_fetch(int n, const int* fds, const uint8_t* const* reqs,
                   const uint32_t* req_lens, const uint64_t* sizes,
                   int n_dsts, uint8_t* const* dsts, const uint64_t* caps,
                   int need, uint32_t granule, uint32_t* out_crcs,
                   int32_t max_out, uint8_t* msgs, uint32_t msg_cap,
                   int timeout_ms, int32_t overlap_mode, int64_t* rec,
                   int64_t* ret_ns) {
    if (n <= 0 || n_dsts < 0) {
        stamp_return(ret_ns);
        return -EINVAL;
    }
    crc32c_table_init();
    std::vector<FetchRange> r((size_t)n);
    std::vector<char> taken((size_t)n_dsts, 0);
    int landed = 0, active = 0;

    auto slots = [&](int i) { return rec + (size_t)i * PF_SLOTS; };
    auto finish = [&](int i, int status) {
        FetchRange& f = r[i];
        if (status != PFS_LANDED && f.dst >= 0) {
            taken[f.dst] = 0;  // a later answer may take the destination
            f.dst = -1;
        }
        int64_t* q = slots(i);
        q[PF_STATUS] = status;
        q[PF_DST] = f.dst;
        q[PF_GOT] = (int64_t)f.got;
        q[PF_CRCS] = f.ncrcs;
        f.phase = FP_DONE;
        active--;
        if (status == PFS_LANDED) {
            q[PF_TAIL_CRC] = f.crc;
            q[PF_T_LAND] = mono_ns();
            landed++;
        }
    };
    // the first free destination, lowest index first, wide enough
    auto hand_out = [&](uint64_t size) {
        for (int d = 0; d < n_dsts; d++) {
            if (!taken[d] && caps[d] >= size) {
                taken[d] = 1;
                return d;
            }
        }
        return -1;
    };
    // a header has come: where range i goes from here
    auto parse = [&](int i) {
        FetchRange& f = r[i];
        int64_t* q = slots(i);
        q[PF_T_HDR] = mono_ns();
        uint64_t len;
        memcpy(&len, f.hdr + 1, 8);
        q[PF_ANSWER] = f.hdr[0];
        q[PF_N] = (int64_t)len;
        if (f.hdr[0] != 0) {
            if (len > kMaxRefusal) return finish(i, PFS_BAD_FRAME);
            f.left = len;
            f.phase = FP_REFUSAL;
            if (len == 0) finish(i, PFS_REFUSED);
            return;
        }
        if (len > f.size) return finish(i, PFS_BAD_FRAME);
        if (len != f.size) return finish(i, PFS_SHORT);
        f.phase = FP_PAYLOAD;
        if (f.size == 0 || n == 1) {  // no other range to come before it
            f.dst = hand_out(f.size);
            if (f.dst < 0) return finish(i, PFS_BAD_FRAME);
            q[PF_DST] = f.dst;
        }
        if (f.size == 0) return finish(i, PFS_LANDED);
        if (n == 1 && granule && recv_overlap_wanted(f.size, overlap_mode)) {
            // one large range: the socket reads on a helper thread with
            // the CRC chasing them, as sn_recv_into lands it
            int32_t count = 0;
            int64_t got = recv_into(f.fd, dsts[f.dst], f.size, timeout_ms,
                                    granule, &f.crc, &f.filled, out_crcs,
                                    &count, max_out, overlap_mode);
            f.ncrcs = count;
            if (got == (int64_t)f.size) {
                f.got = f.size;
                return finish(i, PFS_LANDED);
            }
            if (got >= 0) f.got = (uint64_t)got;
            return finish(i, got == -ETIMEDOUT ? PFS_TIMED_OUT
                             : got == -EOVERFLOW ? PFS_BAD_FRAME : PFS_TORN);
        }
    };
    // land what range i's socket holds, without waiting for more
    auto step = [&](int i) {
        FetchRange& f = r[i];
        uint8_t scratch[4096];
        while (f.phase != FP_DONE) {
            uint8_t* to;
            size_t want;
            if (f.phase == FP_HEADER) {
                to = f.hdr + f.hgot;
                want = sizeof(f.hdr) - f.hgot;
            } else if (f.phase == FP_REFUSAL) {
                uint64_t kept = (uint64_t)slots(i)[PF_MSG_LEN];
                if (kept < msg_cap) {
                    to = msgs + (size_t)i * msg_cap + kept;
                    want = (size_t)(msg_cap - kept);
                } else {
                    to = scratch;
                    want = sizeof(scratch);
                }
                if (want > f.left) want = (size_t)f.left;
            } else {
                if (f.dst < 0) {
                    // the first payload byte is here: the range takes the
                    // first free destination now
                    uint8_t b;
                    ssize_t p = recv(f.fd, &b, 1, MSG_PEEK | MSG_DONTWAIT);
                    if (p < 0) {
                        if (errno == EINTR) continue;
                        if (errno == EAGAIN || errno == EWOULDBLOCK) return;
                        return finish(i, PFS_TORN);
                    }
                    if (p == 0) return finish(i, PFS_TORN);
                    f.dst = hand_out(f.size);
                    if (f.dst < 0) return finish(i, PFS_BAD_FRAME);
                    slots(i)[PF_DST] = f.dst;
                }
                to = dsts[f.dst] + f.got;
                want = (size_t)(f.size - f.got);
            }
            ssize_t got = recv(f.fd, to, want, MSG_DONTWAIT);
            if (got < 0) {
                if (errno == EINTR) continue;
                if (errno == EAGAIN || errno == EWOULDBLOCK) return;
                return finish(i, PFS_TORN);
            }
            if (got == 0) return finish(i, PFS_TORN);
            f.last_ns = mono_ns();
            if (f.phase == FP_HEADER) {
                f.hgot += (uint32_t)got;
                if (f.hgot == sizeof(f.hdr)) parse(i);
            } else if (f.phase == FP_REFUSAL) {
                if (to != scratch) slots(i)[PF_MSG_LEN] += got;
                f.left -= (uint64_t)got;
                if (f.left == 0) finish(i, PFS_REFUSED);
            } else {
                if (granule) {
                    int added = roll_crc_blocks(
                        &f.crc, &f.filled, granule, to, (size_t)got,
                        out_crcs + (size_t)i * max_out + f.ncrcs,
                        max_out - f.ncrcs);
                    if (added < 0) return finish(i, PFS_BAD_FRAME);
                    f.ncrcs += added;
                }
                f.got += (uint64_t)got;
                if (f.got == f.size) finish(i, PFS_LANDED);
            }
        }
    };

    for (int i = 0; i < n; i++) {
        int64_t* q = slots(i);
        memset(q, 0, sizeof(int64_t) * PF_SLOTS);
        q[PF_ANSWER] = -1;
        q[PF_DST] = -1;
        FetchRange& f = r[i];
        f = FetchRange{};
        f.fd = fds[i];
        f.size = sizes[i];
        f.dst = -1;
        f.phase = FP_HEADER;
        active++;
        int rc = sn_net::write_full(fds[i], reqs[i], req_lens[i], timeout_ms);
        f.last_ns = mono_ns();
        q[PF_T_SENT] = f.last_ns;
        if (rc != 0) {
            finish(i, rc == -ETIMEDOUT ? PFS_TIMED_OUT : PFS_TORN);
            continue;
        }
        q[PF_STATUS] = PFS_IN_FLIGHT;
    }
    std::vector<struct pollfd> pfd;
    std::vector<int> who;
    while (active > 0 && landed < need) {
        pfd.clear();
        who.clear();
        int64_t now = mono_ns();
        int wait_ms = -1;
        for (int i = 0; i < n; i++) {
            if (r[i].phase == FP_DONE) continue;
            if (timeout_ms >= 0) {
                int64_t left_ns = r[i].last_ns + (int64_t)timeout_ms * 1000000 - now;
                if (left_ns <= 0) {
                    finish(i, PFS_TIMED_OUT);
                    continue;
                }
                int ms = (int)((left_ns + 999999) / 1000000);
                if (wait_ms < 0 || ms < wait_ms) wait_ms = ms;
            }
            pfd.push_back({r[i].fd, POLLIN, 0});
            who.push_back(i);
        }
        if (pfd.empty()) break;
        int rc = poll(pfd.data(), (nfds_t)pfd.size(), wait_ms);
        if (rc < 0) {
            if (errno == EINTR) continue;
            for (int i : who) finish(i, PFS_TORN);
            break;
        }
        for (size_t k = 0; k < pfd.size(); k++) {
            if (pfd[k].revents) step(who[k]);
        }
    }
    // on the way out: whatever the socket buffers already hold
    for (int i = 0; i < n; i++) {
        if (r[i].phase != FP_DONE) step(i);
    }
    for (int i = 0; i < n; i++) {
        if (r[i].phase != FP_DONE) {  // still in flight: out of frame
            int64_t* q = slots(i);
            q[PF_DST] = r[i].dst;
            q[PF_GOT] = (int64_t)r[i].got;
        }
    }
    stamp_return(ret_ns);
    return landed;
}

}  // extern "C"
