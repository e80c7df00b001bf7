// GF(2^8) constant-matrix multiply over byte streams — host-side SIMD path.
//
// Plays the role klauspost/reedsolomon's amd64 assembly plays in the
// reference (ref: weed/storage/erasure_coding/ec_encoder.go:198). Three
// tiers, widest the build flags allow:
//
//  1. GFNI + AVX-512BW: multiplication by a constant c in GF(2^8) is a
//     linear map over GF(2), i.e. an 8x8 bit-matrix — VGF2P8AFFINEQB
//     applies it 64 bytes per instruction. This works for ANY field
//     polynomial (we need 0x11D; the fixed-poly VGF2P8MULB is 0x11B-only
//     and useless here). The matmul walks 64-byte columns keeping all
//     output rows in registers: cols loads + rows*cols affine+xor per
//     column, one store per output row — each input byte is read once
//     per output row from L1, written exactly once.
//  2. AVX2 (or SSSE3): the classic PSHUFB nibble-table technique — for
//     each c, 16-entry tables of c*low_nibble and c*high_nibble, applied
//     32 (resp. 16) bytes per instruction.
//  3. Scalar table fallback.
//
// Build: g++ -O3 -mgfni -mavx512f -mavx512bw -mavx2 -shared -fPIC
//        gf256.cpp -o libgf256_gfni-avx512f-avx512bw-avx2.so
// (the Python loader probes /proc/cpuinfo and walks the flag candidates
// down to scalar; VPSHUFB shuffles within each 128-bit lane, so
// broadcasting the 16-entry nibble tables to both lanes gives the
// identical algorithm at 32 B/op)

#include <cerrno>
#include <cstddef>
#include <cstdint>
#include <cstring>
#include <unistd.h>

#if defined(__AVX2__) || defined(__GFNI__)
#include <immintrin.h>
#elif defined(__SSSE3__)
#include <tmmintrin.h>
#endif

#if defined(__GFNI__) && defined(__AVX512F__) && defined(__AVX512BW__)
#define GF_GFNI512 1
#endif

namespace {

constexpr unsigned kPoly = 0x11D;

uint8_t gf_mul_scalar(unsigned a, unsigned b) {
  unsigned r = 0;
  while (b) {
    if (b & 1) r ^= a;
    a <<= 1;
    if (a & 0x100) a ^= kPoly;
    b >>= 1;
  }
  return static_cast<uint8_t>(r);
}

void build_tables(uint8_t c, uint8_t lo[16], uint8_t hi[16]) {
  for (int x = 0; x < 16; x++) {
    lo[x] = gf_mul_scalar(c, x);
    hi[x] = gf_mul_scalar(c, x << 4);
  }
}

void mul_add_row_scalar(uint8_t c, const uint8_t* src, uint8_t* out,
                        size_t begin, size_t n) {
  uint8_t lo[16], hi[16];
  build_tables(c, lo, hi);
  for (size_t i = begin; i < n; i++) {
    out[i] ^= static_cast<uint8_t>(lo[src[i] & 0x0F] ^ hi[src[i] >> 4]);
  }
}

#ifdef GF_GFNI512
// The 8x8 GF(2) bit-matrix for y = c*x in GF(2^8)/0x11D, packed in
// VGF2P8AFFINEQB's convention: result bit i of each byte is
// parity(A.byte[7-i] & src_byte), so byte[7-i] holds the row selecting
// which input bits feed output bit i. (Identity c=1 packs to the familiar
// 0x0102040810204080.)
uint64_t gfni_matrix(uint8_t c) {
  uint8_t rows[8] = {0};
  for (int j = 0; j < 8; j++) {
    uint8_t p = gf_mul_scalar(c, static_cast<uint8_t>(1u << j));
    for (int i = 0; i < 8; i++)
      if (p & (1u << i)) rows[i] |= static_cast<uint8_t>(1u << j);
  }
  uint64_t m = 0;
  for (int i = 0; i < 8; i++)
    m |= static_cast<uint64_t>(rows[i]) << (8 * (7 - i));
  return m;
}
#endif

// out ^= c * src over [0, n)
void mul_add_row(uint8_t c, const uint8_t* src, uint8_t* out, size_t n) {
  if (c == 0) return;
  if (c == 1) {
    size_t i = 0;
#ifdef GF_GFNI512
    for (; i + 64 <= n; i += 64) {
      __m512i v = _mm512_loadu_si512(src + i);
      __m512i o = _mm512_loadu_si512(out + i);
      _mm512_storeu_si512(out + i, _mm512_xor_si512(o, v));
    }
#elif defined(__AVX2__)
    for (; i + 32 <= n; i += 32) {
      __m256i v = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(src + i));
      __m256i o = _mm256_loadu_si256(reinterpret_cast<__m256i*>(out + i));
      _mm256_storeu_si256(reinterpret_cast<__m256i*>(out + i),
                          _mm256_xor_si256(o, v));
    }
#elif defined(__SSSE3__)
    for (; i + 16 <= n; i += 16) {
      __m128i v = _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i));
      __m128i o = _mm_loadu_si128(reinterpret_cast<__m128i*>(out + i));
      _mm_storeu_si128(reinterpret_cast<__m128i*>(out + i),
                       _mm_xor_si128(o, v));
    }
#endif
    for (; i < n; i++) out[i] ^= src[i];
    return;
  }
  size_t i = 0;
#ifdef GF_GFNI512
  const __m512i A = _mm512_set1_epi64(static_cast<long long>(gfni_matrix(c)));
  for (; i + 64 <= n; i += 64) {
    __m512i v = _mm512_loadu_si512(src + i);
    __m512i prod = _mm512_gf2p8affine_epi64_epi8(v, A, 0);
    __m512i o = _mm512_loadu_si512(out + i);
    _mm512_storeu_si512(out + i, _mm512_xor_si512(o, prod));
  }
#elif defined(__AVX2__)
  uint8_t lo[16], hi[16];
  build_tables(c, lo, hi);
  const __m256i vlo = _mm256_broadcastsi128_si256(
      _mm_loadu_si128(reinterpret_cast<const __m128i*>(lo)));
  const __m256i vhi = _mm256_broadcastsi128_si256(
      _mm_loadu_si128(reinterpret_cast<const __m128i*>(hi)));
  const __m256i mask = _mm256_set1_epi8(0x0F);
  for (; i + 32 <= n; i += 32) {
    __m256i v = _mm256_loadu_si256(reinterpret_cast<const __m256i*>(src + i));
    __m256i l = _mm256_and_si256(v, mask);
    __m256i h = _mm256_and_si256(_mm256_srli_epi64(v, 4), mask);
    __m256i prod = _mm256_xor_si256(_mm256_shuffle_epi8(vlo, l),
                                    _mm256_shuffle_epi8(vhi, h));
    __m256i o = _mm256_loadu_si256(reinterpret_cast<__m256i*>(out + i));
    _mm256_storeu_si256(reinterpret_cast<__m256i*>(out + i),
                        _mm256_xor_si256(o, prod));
  }
#elif defined(__SSSE3__)
  uint8_t lo[16], hi[16];
  build_tables(c, lo, hi);
  const __m128i vlo = _mm_loadu_si128(reinterpret_cast<const __m128i*>(lo));
  const __m128i vhi = _mm_loadu_si128(reinterpret_cast<const __m128i*>(hi));
  const __m128i mask = _mm_set1_epi8(0x0F);
  for (; i + 16 <= n; i += 16) {
    __m128i v = _mm_loadu_si128(reinterpret_cast<const __m128i*>(src + i));
    __m128i l = _mm_and_si128(v, mask);
    __m128i h = _mm_and_si128(_mm_srli_epi64(v, 4), mask);
    __m128i prod =
        _mm_xor_si128(_mm_shuffle_epi8(vlo, l), _mm_shuffle_epi8(vhi, h));
    __m128i o = _mm_loadu_si128(reinterpret_cast<__m128i*>(out + i));
    _mm_storeu_si128(reinterpret_cast<__m128i*>(out + i),
                     _mm_xor_si128(o, prod));
  }
#endif
  if (i < n) mul_add_row_scalar(c, src, out, i, n);
}

#ifdef GF_GFNI512

// How many output rows the column-walk keeps live at once. 8 accumulators
// + 1 source register + rematerialized broadcasts stays comfortably inside
// 32 zmm registers; RS(10,4) parity (rows=4) fits in a single pass.
constexpr int kRowBlock = 8;

// One register-blocked pass over [0, n) for up to kRowBlock output rows.
// Every input byte is loaded once per pass (from L1 for the affine of each
// row), every output byte stored exactly once — no read-modify-write of
// out, no memset prepass.
void matmul_cols_gfni(const uint64_t* mats, const uint8_t* cmat, int rows,
                      int cols, const uint8_t* const* data,
                      uint8_t* const* out, size_t n) {
  size_t i = 0;
  for (; i + 64 <= n; i += 64) {
    __m512i acc[kRowBlock];
    for (int r = 0; r < rows; r++) acc[r] = _mm512_setzero_si512();
    for (int j = 0; j < cols; j++) {
      const __m512i v = _mm512_loadu_si512(data[j] + i);
      for (int r = 0; r < rows; r++) {
        const uint64_t m = mats[r * cols + j];
        if (!m) continue;
        acc[r] = _mm512_xor_si512(
            acc[r], _mm512_gf2p8affine_epi64_epi8(
                        v, _mm512_set1_epi64(static_cast<long long>(m)), 0));
      }
    }
    for (int r = 0; r < rows; r++) _mm512_storeu_si512(out[r] + i, acc[r]);
  }
  if (i < n) {
    // tail (<64B): scalar tables
    for (int r = 0; r < rows; r++) {
      std::memset(out[r] + i, 0, n - i);
      for (int j = 0; j < cols; j++) {
        const uint8_t c = cmat[r * cols + j];
        if (c) mul_add_row_scalar(c, data[j] + i, out[r] + i, 0, n - i);
      }
    }
  }
}

#endif  // GF_GFNI512

#ifdef GF_GFNI512

// True when every pointer that will take 64-byte vector stores shares
// 64-byte alignment so non-temporal stores are legal.
bool all_aligned64(const uint8_t* const* ps, int n) {
  for (int i = 0; i < n; i++)
    if (ps[i] && (reinterpret_cast<uintptr_t>(ps[i]) & 63)) return false;
  return true;
}

#endif  // GF_GFNI512

}  // namespace

extern "C" {

// Fused single-pass encode+copy: for k source rows (null = implicit
// zeros), copy row j to dst[j] (null = skip) AND accumulate the prows
// parity rows into pdst, in ONE read of the source. With nt!=0 and
// 64-byte-aligned destinations the copies and parity stores use
// non-temporal stores, halving write-side memory traffic (no RFO) — the
// source is still read through the cache, where the affine reuses it.
// Returns 1 when the fused path ran, 0 when the caller must fall back
// (no GFNI build).
int gf_encode_copy(const uint8_t* matrix, int prows, int k,
                   const uint8_t* const* src, uint8_t* const* dst,
                   uint8_t* const* pdst, size_t n, int nt) {
#ifdef GF_GFNI512
  if (prows > kRowBlock || k > 32) return 0;
  uint64_t mats[kRowBlock * 32];
  for (int r = 0; r < prows; r++)
    for (int j = 0; j < k; j++) mats[r * k + j] = gfni_matrix(matrix[r * k + j]);
  const bool use_nt =
      nt && all_aligned64(dst, k) && all_aligned64(pdst, prows);
  size_t i = 0;
  for (; i + 64 <= n; i += 64) {
    __m512i acc[kRowBlock];
    for (int r = 0; r < prows; r++) acc[r] = _mm512_setzero_si512();
    for (int j = 0; j < k; j++) {
      if (!src[j]) continue;  // implicit zeros: no copy, no parity term
      const __m512i v = _mm512_loadu_si512(src[j] + i);
      if (dst[j]) {
        if (use_nt)
          _mm512_stream_si512(reinterpret_cast<__m512i*>(dst[j] + i), v);
        else
          _mm512_storeu_si512(dst[j] + i, v);
      }
      for (int r = 0; r < prows; r++) {
        const uint64_t m = mats[r * k + j];
        if (!m) continue;
        acc[r] = _mm512_xor_si512(
            acc[r], _mm512_gf2p8affine_epi64_epi8(
                        v, _mm512_set1_epi64(static_cast<long long>(m)), 0));
      }
    }
    for (int r = 0; r < prows; r++) {
      if (use_nt)
        _mm512_stream_si512(reinterpret_cast<__m512i*>(pdst[r] + i), acc[r]);
      else
        _mm512_storeu_si512(pdst[r] + i, acc[r]);
    }
  }
  if (use_nt) _mm_sfence();
  if (i < n) {  // tail (<64B): scalar
    for (int r = 0; r < prows; r++) std::memset(pdst[r] + i, 0, n - i);
    for (int j = 0; j < k; j++) {
      if (!src[j]) continue;
      if (dst[j]) std::memcpy(dst[j] + i, src[j] + i, n - i);
      for (int r = 0; r < prows; r++) {
        const uint8_t c = matrix[r * k + j];
        if (c) mul_add_row_scalar(c, src[j] + i, pdst[r] + i, 0, n - i);
      }
    }
  }
  return 1;
#else
  (void)matrix; (void)prows; (void)k; (void)src; (void)dst; (void)pdst;
  (void)n; (void)nt;
  return 0;
#endif
}

// out[r] = XOR_j matrix[r*cols+j] * data[j], all rows length n.
void gf_matmul(const uint8_t* matrix, int rows, int cols,
               const uint8_t* const* data, uint8_t* const* out, size_t n) {
#ifdef GF_GFNI512
  if (cols <= 32) {
    uint64_t mats[kRowBlock * 32];
    for (int r0 = 0; r0 < rows; r0 += kRowBlock) {
      const int rb = (rows - r0 < kRowBlock) ? (rows - r0) : kRowBlock;
      for (int r = 0; r < rb; r++)
        for (int j = 0; j < cols; j++)
          mats[r * cols + j] = gfni_matrix(matrix[(r0 + r) * cols + j]);
      matmul_cols_gfni(mats, matrix + r0 * cols, rb, cols, data, out + r0, n);
    }
    return;
  }
#endif
  // generic path: chunked so the working set stays cache-resident
  constexpr size_t kChunk = 32 * 1024;
  for (size_t off = 0; off < n; off += kChunk) {
    size_t len = (n - off < kChunk) ? (n - off) : kChunk;
    for (int r = 0; r < rows; r++) {
      std::memset(out[r] + off, 0, len);
      for (int j = 0; j < cols; j++) {
        mul_add_row(matrix[r * cols + j], data[j] + off, out[r] + off, len);
      }
    }
  }
}

// out ^= c*src over n bytes (exported for incremental/update paths)
void gf_mul_add(uint8_t c, const uint8_t* src, uint8_t* out, size_t n) {
  mul_add_row(c, src, out, n);
}

uint8_t gf_mul(uint8_t a, uint8_t b) { return gf_mul_scalar(a, b); }

// Append each of n buffers whole to its own file descriptor, in order, in
// ONE call: a caller through ctypes gives the interpreter lock up once
// for all n writes, not once a write, so a shard writer that other
// threads keep off the lock waits for it once a chunk (encoder.py
// _encode_streamed). Returns 0, or -(i + 1) for the first buffer whose
// write failed, with errno as the write left it.
long write_all(int n, const int* fds, const void* const* bufs,
               const size_t* lens) {
  for (int i = 0; i < n; i++) {
    const char* p = static_cast<const char*>(bufs[i]);
    size_t left = lens[i];
    while (left > 0) {
      ssize_t got = write(fds[i], p, left);
      if (got < 0) {
        if (errno == EINTR) continue;
        return -(long)(i + 1);
      }
      p += got;
      left -= (size_t)got;
    }
  }
  return 0;
}

// Fill n rows in ONE call, the read side of write_all: a reconstruct's
// survivor rows (volume_ec.py _recover_one_interval) give the interpreter
// lock up once for all of them, not once a shard read; the reconstructs
// of one batch give it up once for all of theirs. Row i is dsts[i],
// lens[i] bytes: where fds[i] >= 0 it is read from fds[i] at offs[i],
// looping on short counts and EINTR until the row is full or the file
// ends; else lens[i] bytes of srcs[i] are copied into it. got[i] = the
// bytes read or copied, or -errno where a read failed; every row is tried.
void fill_rows(int n, const int* fds, const int64_t* offs,
               const void* const* srcs, void* const* dsts, const size_t* lens,
               int64_t* got) {
  for (int i = 0; i < n; i++) {
    char* p = static_cast<char*>(dsts[i]);
    if (fds[i] < 0) {
      std::memcpy(p, srcs[i], lens[i]);
      got[i] = (int64_t)lens[i];
      continue;
    }
    size_t done = 0;
    int64_t err = 0;
    while (done < lens[i]) {
      ssize_t r =
          pread(fds[i], p + done, lens[i] - done, offs[i] + (off_t)done);
      if (r < 0) {
        if (errno == EINTR) continue;
        err = -(int64_t)errno;
        break;
      }
      if (r == 0) break;  // the end of the file
      done += (size_t)r;
    }
    got[i] = err ? err : (int64_t)done;
  }
}
}
