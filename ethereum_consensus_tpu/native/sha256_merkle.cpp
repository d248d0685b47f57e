// Native SHA-256 merkle kernels (the CPU-side hot path of SSZ
// hash_tree_root).
//
// Fills the role the reference fills with native crypto (sha2 crate /
// blst's C, SURVEY.md L0): a from-scratch C++ SHA-256 specialized for the
// 64-byte two-children message of binary merkleization, with whole-level
// and whole-tree entry points so the Python merkleizer can hand off entire
// reductions in one call.
//
// Build: g++ -O3 -march=native -shared -fPIC sha256_merkle.cpp -o ...
// ABI (ctypes):
//   void ec_hash_level(const uint8_t* in, uint8_t* out, size_t n_pairs);
//   void ec_merkle_root(const uint8_t* chunks, size_t count, uint32_t depth,
//                       const uint8_t* zero_hashes, uint8_t* out32);
//   uint32_t ec_merkle_groups(const uint8_t* raw, size_t raw_len,
//                             const uint64_t* group_ids, size_t n_groups,
//                             uint32_t group_depth,
//                             const uint8_t* zero_hashes, uint8_t* out,
//                             uint32_t n_threads);
//   uint32_t ec_shuffle_positions(const uint8_t* seed32, uint64_t count,
//                                 uint32_t rounds, const uint64_t* in,
//                                 uint64_t* out, size_t n);
//   uint64_t ec_version(void);

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>

#if defined(EC_USE_SHA_NI) && defined(__SHA__) && defined(__x86_64__)
#define EC_SHA_NI_ACTIVE 1
#include <immintrin.h>
#endif

// 8-way AVX2 multi-buffer path: the merkle level is 8+ independent
// 64-byte messages — the ideal multi-buffer case. Pure integer AVX2
// (no SHA-NI, which this image's hypervisor traps ~20x slower than
// scalar); measured ~6x over the scalar loop on the build machine.
#if !defined(EC_SHA_NI_ACTIVE) && defined(__AVX2__) && defined(__x86_64__)
#define EC_AVX2_ACTIVE 1
#endif

// the AVX-512 kernel below is compiled with target attributes on any
// x86-64 build (runtime-dispatched), so the intrinsics header is needed
// even when the baseline ISA has no AVX2
#if defined(__x86_64__)
#include <immintrin.h>
#endif

namespace {

constexpr uint32_t K[64] = {
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};

constexpr uint32_t H0[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
                            0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19};

inline uint32_t rotr(uint32_t x, int n) { return (x >> n) | (x << (32 - n)); }

inline uint32_t load_be32(const uint8_t* p) {
  return (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
         (uint32_t(p[2]) << 8) | uint32_t(p[3]);
}

inline void store_be32(uint8_t* p, uint32_t v) {
  p[0] = uint8_t(v >> 24);
  p[1] = uint8_t(v >> 16);
  p[2] = uint8_t(v >> 8);
  p[3] = uint8_t(v);
}

inline void compress(uint32_t state[8], const uint32_t w_in[16]) {
  uint32_t w[64];
  std::memcpy(w, w_in, 16 * sizeof(uint32_t));
  for (int t = 16; t < 64; ++t) {
    uint32_t s0 = rotr(w[t - 15], 7) ^ rotr(w[t - 15], 18) ^ (w[t - 15] >> 3);
    uint32_t s1 = rotr(w[t - 2], 17) ^ rotr(w[t - 2], 19) ^ (w[t - 2] >> 10);
    w[t] = w[t - 16] + s0 + w[t - 7] + s1;
  }
  uint32_t a = state[0], b = state[1], c = state[2], d = state[3];
  uint32_t e = state[4], f = state[5], g = state[6], h = state[7];
  for (int t = 0; t < 64; ++t) {
    uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
    uint32_t ch = (e & f) ^ (~e & g);
    uint32_t t1 = h + S1 + ch + K[t] + w[t];
    uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
    uint32_t maj = (a & b) ^ (a & c) ^ (b & c);
    uint32_t t2 = S0 + maj;
    h = g;
    g = f;
    f = e;
    e = d + t1;
    d = c;
    c = b;
    b = a;
    a = t1 + t2;
  }
  state[0] += a;
  state[1] += b;
  state[2] += c;
  state[3] += d;
  state[4] += e;
  state[5] += f;
  state[6] += g;
  state[7] += h;
}

// the constant second block of a 64-byte message (0x80 pad + bit length 512)
constexpr uint32_t PAD_BLOCK[16] = {0x80000000, 0, 0, 0, 0, 0, 0, 0,
                                    0,          0, 0, 0, 0, 0, 0, 512};

// SHA-256 of exactly 64 bytes (one merkle pair) — two compressions, the
// second over a constant schedule.
inline void sha256_64(const uint8_t* in, uint8_t* out) {
  uint32_t state[8];
  std::memcpy(state, H0, sizeof(H0));
  uint32_t w[16];
  for (int i = 0; i < 16; ++i) w[i] = load_be32(in + 4 * i);
  compress(state, w);
  compress(state, PAD_BLOCK);
  for (int i = 0; i < 8; ++i) store_be32(out + 4 * i, state[i]);
}

// The one-block lane routines of the swap-or-not shuffle: each lane's
// message is a single block, already padded, given as 16 words with word t
// of lane j at w[t * stride + j]; the digest's word i of lane j goes to
// out[i * stride + j]. This is the scalar one.
inline void sha256_block(const uint32_t* w_in, size_t stride, uint32_t* out) {
  uint32_t w[16];
  for (int t = 0; t < 16; ++t) w[t] = w_in[t * stride];
  uint32_t state[8];
  std::memcpy(state, H0, sizeof(H0));
  compress(state, w);
  for (int i = 0; i < 8; ++i) out[i * stride] = state[i];
}

#ifdef EC_SHA_NI_ACTIVE
// SHA-NI two-compression digest of a 64-byte message. State is carried in
// the (ABEF, CDGH) register layout the sha256rnds2 instruction expects.
inline void sha256_64_ni(const uint8_t* in, uint8_t* out) {
  const __m128i MASK =
      _mm_set_epi64x(0x0c0d0e0f08090a0bULL, 0x0405060700010203ULL);
  // H0 in ABEF/CDGH layout
  __m128i abef = _mm_set_epi32(0x6a09e667, 0xbb67ae85, 0x510e527f, 0x9b05688c);
  __m128i cdgh = _mm_set_epi32(0x3c6ef372, 0xa54ff53a, 0x1f83d9ab, 0x5be0cd19);

  for (int block = 0; block < 2; ++block) {
    __m128i msg0, msg1, msg2, msg3;
    if (block == 0) {
      msg0 = _mm_shuffle_epi8(
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(in + 0)), MASK);
      msg1 = _mm_shuffle_epi8(
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(in + 16)), MASK);
      msg2 = _mm_shuffle_epi8(
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(in + 32)), MASK);
      msg3 = _mm_shuffle_epi8(
          _mm_loadu_si128(reinterpret_cast<const __m128i*>(in + 48)), MASK);
    } else {
      // constant pad block: 0x80 then zeros, length 512 bits
      msg0 = _mm_set_epi32(0, 0, 0, int(0x80000000));
      msg1 = _mm_setzero_si128();
      msg2 = _mm_setzero_si128();
      msg3 = _mm_set_epi32(512, 0, 0, 0);
    }
    const __m128i save_abef = abef;
    const __m128i save_cdgh = cdgh;
    __m128i msg;

#define ROUNDS4(m, k_hi, k_lo)                                         \
  msg = _mm_add_epi32(m, _mm_set_epi64x(k_hi, k_lo));                  \
  cdgh = _mm_sha256rnds2_epu32(cdgh, abef, msg);                       \
  msg = _mm_shuffle_epi32(msg, 0x0E);                                  \
  abef = _mm_sha256rnds2_epu32(abef, cdgh, msg);

#define SCHED(m0, m1, m2, m3)                                          \
  m0 = _mm_sha256msg1_epu32(m0, m1);                                   \
  m0 = _mm_add_epi32(m0, _mm_alignr_epi8(m3, m2, 4));                  \
  m0 = _mm_sha256msg2_epu32(m0, m3);

    ROUNDS4(msg0, 0xe9b5dba5b5c0fbcfULL, 0x71374491428a2f98ULL)
    ROUNDS4(msg1, 0xab1c5ed5923f82a4ULL, 0x59f111f13956c25bULL)
    ROUNDS4(msg2, 0x550c7dc3243185beULL, 0x12835b01d807aa98ULL)
    ROUNDS4(msg3, 0xc19bf1749bdc06a7ULL, 0x80deb1fe72be5d74ULL)
    SCHED(msg0, msg1, msg2, msg3)
    ROUNDS4(msg0, 0x240ca1cc0fc19dc6ULL, 0xefbe4786e49b69c1ULL)
    SCHED(msg1, msg2, msg3, msg0)
    ROUNDS4(msg1, 0x76f988da5cb0a9dcULL, 0x4a7484aa2de92c6fULL)
    SCHED(msg2, msg3, msg0, msg1)
    ROUNDS4(msg2, 0xbf597fc7b00327c8ULL, 0xa831c66d983e5152ULL)
    SCHED(msg3, msg0, msg1, msg2)
    ROUNDS4(msg3, 0x1429296706ca6351ULL, 0xd5a79147c6e00bf3ULL)
    SCHED(msg0, msg1, msg2, msg3)
    ROUNDS4(msg0, 0x53380d134d2c6dfcULL, 0x2e1b213827b70a85ULL)
    SCHED(msg1, msg2, msg3, msg0)
    ROUNDS4(msg1, 0x92722c8581c2c92eULL, 0x766a0abb650a7354ULL)
    SCHED(msg2, msg3, msg0, msg1)
    ROUNDS4(msg2, 0xc76c51a3c24b8b70ULL, 0xa81a664ba2bfe8a1ULL)
    SCHED(msg3, msg0, msg1, msg2)
    ROUNDS4(msg3, 0x106aa070f40e3585ULL, 0xd6990624d192e819ULL)
    SCHED(msg0, msg1, msg2, msg3)
    ROUNDS4(msg0, 0x34b0bcb52748774cULL, 0x1e376c0819a4c116ULL)
    SCHED(msg1, msg2, msg3, msg0)
    ROUNDS4(msg1, 0x682e6ff35b9cca4fULL, 0x4ed8aa4a391c0cb3ULL)
    SCHED(msg2, msg3, msg0, msg1)
    ROUNDS4(msg2, 0x8cc7020884c87814ULL, 0x78a5636f748f82eeULL)
    SCHED(msg3, msg0, msg1, msg2)
    ROUNDS4(msg3, 0xc67178f2bef9a3f7ULL, 0xa4506ceb90befffaULL)

#undef ROUNDS4
#undef SCHED

    abef = _mm_add_epi32(abef, save_abef);
    cdgh = _mm_add_epi32(cdgh, save_cdgh);
  }

  // unpack ABEF/CDGH → big-endian digest
  uint32_t a = uint32_t(_mm_extract_epi32(abef, 3));
  uint32_t b = uint32_t(_mm_extract_epi32(abef, 2));
  uint32_t e = uint32_t(_mm_extract_epi32(abef, 1));
  uint32_t f = uint32_t(_mm_extract_epi32(abef, 0));
  uint32_t c = uint32_t(_mm_extract_epi32(cdgh, 3));
  uint32_t d = uint32_t(_mm_extract_epi32(cdgh, 2));
  uint32_t g = uint32_t(_mm_extract_epi32(cdgh, 1));
  uint32_t h = uint32_t(_mm_extract_epi32(cdgh, 0));
  store_be32(out + 0, a);
  store_be32(out + 4, b);
  store_be32(out + 8, c);
  store_be32(out + 12, d);
  store_be32(out + 16, e);
  store_be32(out + 20, f);
  store_be32(out + 24, g);
  store_be32(out + 28, h);
}
#endif  // EC_SHA_NI_ACTIVE

// message schedule of the constant pad block, computed once (shared by
// the AVX2 and AVX-512 multi-buffer kernels)
struct PadSchedule {
  uint32_t w[64];
  PadSchedule() {
    std::memcpy(w, PAD_BLOCK, 16 * sizeof(uint32_t));
    for (int t = 16; t < 64; ++t) {
      uint32_t s0 = rotr(w[t - 15], 7) ^ rotr(w[t - 15], 18) ^ (w[t - 15] >> 3);
      uint32_t s1 = rotr(w[t - 2], 17) ^ rotr(w[t - 2], 19) ^ (w[t - 2] >> 10);
      w[t] = w[t - 16] + s0 + w[t - 7] + s1;
    }
  }
};
const PadSchedule PAD_SCHED;

#ifdef EC_AVX2_ACTIVE

inline __m256i rotr8(__m256i x, int n) {
  return _mm256_or_si256(_mm256_srli_epi32(x, n),
                         _mm256_slli_epi32(x, 32 - n));
}

#define EC_ROUND8(wt)                                                        \
  do {                                                                       \
    __m256i S1 = _mm256_xor_si256(_mm256_xor_si256(rotr8(e, 6), rotr8(e, 11)),\
                                  rotr8(e, 25));                             \
    __m256i ch = _mm256_xor_si256(_mm256_and_si256(e, f),                    \
                                  _mm256_andnot_si256(e, g));                \
    __m256i t1 = _mm256_add_epi32(                                           \
        _mm256_add_epi32(_mm256_add_epi32(h, S1), ch),                       \
        _mm256_add_epi32(_mm256_set1_epi32(int(K[t])), (wt)));               \
    __m256i S0 = _mm256_xor_si256(_mm256_xor_si256(rotr8(a, 2), rotr8(a, 13)),\
                                  rotr8(a, 22));                             \
    __m256i maj = _mm256_xor_si256(                                          \
        _mm256_xor_si256(_mm256_and_si256(a, b), _mm256_and_si256(a, c)),    \
        _mm256_and_si256(b, c));                                             \
    __m256i t2 = _mm256_add_epi32(S0, maj);                                  \
    h = g; g = f; f = e; e = _mm256_add_epi32(d, t1);                        \
    d = c; c = b; b = a; a = _mm256_add_epi32(t1, t2);                       \
  } while (0)

// eight independent 64-byte messages -> eight 32-byte digests, lanes
// transposed across one ymm register per word
inline void sha256_64_x8(const uint8_t* in, uint8_t* out) {
  __m256i a = _mm256_set1_epi32(int(H0[0]));
  __m256i b = _mm256_set1_epi32(int(H0[1]));
  __m256i c = _mm256_set1_epi32(int(H0[2]));
  __m256i d = _mm256_set1_epi32(int(H0[3]));
  __m256i e = _mm256_set1_epi32(int(H0[4]));
  __m256i f = _mm256_set1_epi32(int(H0[5]));
  __m256i g = _mm256_set1_epi32(int(H0[6]));
  __m256i h = _mm256_set1_epi32(int(H0[7]));

  // block 1: the data block, schedule extended in a 16-entry ring
  __m256i w[16];
  for (int t = 0; t < 16; ++t) {
    w[t] = _mm256_set_epi32(
        int(load_be32(in + 7 * 64 + 4 * t)), int(load_be32(in + 6 * 64 + 4 * t)),
        int(load_be32(in + 5 * 64 + 4 * t)), int(load_be32(in + 4 * 64 + 4 * t)),
        int(load_be32(in + 3 * 64 + 4 * t)), int(load_be32(in + 2 * 64 + 4 * t)),
        int(load_be32(in + 1 * 64 + 4 * t)), int(load_be32(in + 0 * 64 + 4 * t)));
  }
  for (int t = 0; t < 64; ++t) {
    if (t >= 16) {
      __m256i w15 = w[(t - 15) & 15], w2 = w[(t - 2) & 15];
      __m256i s0 = _mm256_xor_si256(
          _mm256_xor_si256(rotr8(w15, 7), rotr8(w15, 18)),
          _mm256_srli_epi32(w15, 3));
      __m256i s1 = _mm256_xor_si256(
          _mm256_xor_si256(rotr8(w2, 17), rotr8(w2, 19)),
          _mm256_srli_epi32(w2, 10));
      w[t & 15] = _mm256_add_epi32(
          _mm256_add_epi32(w[t & 15], s0),
          _mm256_add_epi32(w[(t - 7) & 15], s1));
    }
    EC_ROUND8(w[t & 15]);
  }
  __m256i sa = _mm256_add_epi32(a, _mm256_set1_epi32(int(H0[0])));
  __m256i sb = _mm256_add_epi32(b, _mm256_set1_epi32(int(H0[1])));
  __m256i sc = _mm256_add_epi32(c, _mm256_set1_epi32(int(H0[2])));
  __m256i sd = _mm256_add_epi32(d, _mm256_set1_epi32(int(H0[3])));
  __m256i se = _mm256_add_epi32(e, _mm256_set1_epi32(int(H0[4])));
  __m256i sf = _mm256_add_epi32(f, _mm256_set1_epi32(int(H0[5])));
  __m256i sg = _mm256_add_epi32(g, _mm256_set1_epi32(int(H0[6])));
  __m256i sh = _mm256_add_epi32(h, _mm256_set1_epi32(int(H0[7])));

  // block 2: constant schedule, no extension work
  a = sa; b = sb; c = sc; d = sd; e = se; f = sf; g = sg; h = sh;
  for (int t = 0; t < 64; ++t) {
    EC_ROUND8(_mm256_set1_epi32(int(PAD_SCHED.w[t])));
  }
  a = _mm256_add_epi32(a, sa);
  b = _mm256_add_epi32(b, sb);
  c = _mm256_add_epi32(c, sc);
  d = _mm256_add_epi32(d, sd);
  e = _mm256_add_epi32(e, se);
  f = _mm256_add_epi32(f, sf);
  g = _mm256_add_epi32(g, sg);
  h = _mm256_add_epi32(h, sh);

  alignas(32) uint32_t lanes[8][8];
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[0]), a);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[1]), b);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[2]), c);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[3]), d);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[4]), e);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[5]), f);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[6]), g);
  _mm256_store_si256(reinterpret_cast<__m256i*>(lanes[7]), h);
  for (int lane = 0; lane < 8; ++lane) {
    for (int i = 0; i < 8; ++i) {
      store_be32(out + 32 * lane + 4 * i, lanes[i][lane]);
    }
  }
}

// eight one-block lanes (layout: sha256_block): the data block of
// sha256_64_x8 alone
inline void sha256_block_x8(const uint32_t* w_in, size_t stride,
                            uint32_t* out) {
  __m256i a = _mm256_set1_epi32(int(H0[0]));
  __m256i b = _mm256_set1_epi32(int(H0[1]));
  __m256i c = _mm256_set1_epi32(int(H0[2]));
  __m256i d = _mm256_set1_epi32(int(H0[3]));
  __m256i e = _mm256_set1_epi32(int(H0[4]));
  __m256i f = _mm256_set1_epi32(int(H0[5]));
  __m256i g = _mm256_set1_epi32(int(H0[6]));
  __m256i h = _mm256_set1_epi32(int(H0[7]));
  __m256i w[16];
  for (int t = 0; t < 16; ++t) {
    w[t] = _mm256_loadu_si256(
        reinterpret_cast<const __m256i*>(w_in + t * stride));
  }
  for (int t = 0; t < 64; ++t) {
    if (t >= 16) {
      __m256i w15 = w[(t - 15) & 15], w2 = w[(t - 2) & 15];
      __m256i s0 = _mm256_xor_si256(
          _mm256_xor_si256(rotr8(w15, 7), rotr8(w15, 18)),
          _mm256_srli_epi32(w15, 3));
      __m256i s1 = _mm256_xor_si256(
          _mm256_xor_si256(rotr8(w2, 17), rotr8(w2, 19)),
          _mm256_srli_epi32(w2, 10));
      w[t & 15] = _mm256_add_epi32(
          _mm256_add_epi32(w[t & 15], s0),
          _mm256_add_epi32(w[(t - 7) & 15], s1));
    }
    EC_ROUND8(w[t & 15]);
  }
  const __m256i state[8] = {a, b, c, d, e, f, g, h};
  for (int i = 0; i < 8; ++i) {
    _mm256_storeu_si256(
        reinterpret_cast<__m256i*>(out + i * stride),
        _mm256_add_epi32(state[i], _mm256_set1_epi32(int(H0[i]))));
  }
}

#undef EC_ROUND8

#endif  // EC_AVX2_ACTIVE

#if defined(__x86_64__)
#define EC_AVX512_COMPILED 1
#define EC_SHA512_TARGET \
  __attribute__((target("avx512f,avx512bw,avx512dq,avx512vl")))

// 16-way AVX-512 multi-buffer path: same transposed-lane scheme as the
// AVX2 kernel, but with the ISA doing real work per instruction — native
// 32-bit rotates (vprord) replace the shift/shift/or triple, and
// vpternlogd fuses ch, maj, and each three-way xor into single ops.
// Runtime-dispatched (the .so is built per machine, but the check stays
// dynamic so a cached binary can never fault on a non-AVX-512 host).

#define EC_ROUND16(wt)                                                       \
  do {                                                                       \
    __m512i S1 = _mm512_ternarylogic_epi32(                                  \
        _mm512_ror_epi32(e, 6), _mm512_ror_epi32(e, 11),                     \
        _mm512_ror_epi32(e, 25), 0x96);                                      \
    __m512i ch = _mm512_ternarylogic_epi32(e, f, g, 0xCA);                   \
    __m512i t1 = _mm512_add_epi32(                                           \
        _mm512_add_epi32(_mm512_add_epi32(h, S1), ch),                       \
        _mm512_add_epi32(_mm512_set1_epi32(int(K[t])), (wt)));               \
    __m512i S0 = _mm512_ternarylogic_epi32(                                  \
        _mm512_ror_epi32(a, 2), _mm512_ror_epi32(a, 13),                     \
        _mm512_ror_epi32(a, 22), 0x96);                                      \
    __m512i maj = _mm512_ternarylogic_epi32(a, b, c, 0xE8);                  \
    __m512i t2 = _mm512_add_epi32(S0, maj);                                  \
    h = g; g = f; f = e; e = _mm512_add_epi32(d, t1);                        \
    d = c; c = b; b = a; a = _mm512_add_epi32(t1, t2);                       \
  } while (0)

// sixteen independent 64-byte messages -> sixteen 32-byte digests
EC_SHA512_TARGET inline void sha256_64_x16(const uint8_t* in, uint8_t* out) {
  __m512i a = _mm512_set1_epi32(int(H0[0]));
  __m512i b = _mm512_set1_epi32(int(H0[1]));
  __m512i c = _mm512_set1_epi32(int(H0[2]));
  __m512i d = _mm512_set1_epi32(int(H0[3]));
  __m512i e = _mm512_set1_epi32(int(H0[4]));
  __m512i f = _mm512_set1_epi32(int(H0[5]));
  __m512i g = _mm512_set1_epi32(int(H0[6]));
  __m512i h = _mm512_set1_epi32(int(H0[7]));

  __m512i w[16];
  for (int t = 0; t < 16; ++t) {
    alignas(64) uint32_t lanes[16];
    for (int lane = 0; lane < 16; ++lane)
      lanes[lane] = load_be32(in + lane * 64 + 4 * t);
    w[t] = _mm512_load_si512(reinterpret_cast<const __m512i*>(lanes));
  }
  for (int t = 0; t < 64; ++t) {
    if (t >= 16) {
      __m512i w15 = w[(t - 15) & 15], w2 = w[(t - 2) & 15];
      __m512i s0 = _mm512_ternarylogic_epi32(
          _mm512_ror_epi32(w15, 7), _mm512_ror_epi32(w15, 18),
          _mm512_srli_epi32(w15, 3), 0x96);
      __m512i s1 = _mm512_ternarylogic_epi32(
          _mm512_ror_epi32(w2, 17), _mm512_ror_epi32(w2, 19),
          _mm512_srli_epi32(w2, 10), 0x96);
      w[t & 15] = _mm512_add_epi32(
          _mm512_add_epi32(w[t & 15], s0),
          _mm512_add_epi32(w[(t - 7) & 15], s1));
    }
    EC_ROUND16(w[t & 15]);
  }
  __m512i sa = _mm512_add_epi32(a, _mm512_set1_epi32(int(H0[0])));
  __m512i sb = _mm512_add_epi32(b, _mm512_set1_epi32(int(H0[1])));
  __m512i sc = _mm512_add_epi32(c, _mm512_set1_epi32(int(H0[2])));
  __m512i sd = _mm512_add_epi32(d, _mm512_set1_epi32(int(H0[3])));
  __m512i se = _mm512_add_epi32(e, _mm512_set1_epi32(int(H0[4])));
  __m512i sf = _mm512_add_epi32(f, _mm512_set1_epi32(int(H0[5])));
  __m512i sg = _mm512_add_epi32(g, _mm512_set1_epi32(int(H0[6])));
  __m512i sh = _mm512_add_epi32(h, _mm512_set1_epi32(int(H0[7])));

  a = sa; b = sb; c = sc; d = sd; e = se; f = sf; g = sg; h = sh;
  for (int t = 0; t < 64; ++t) {
    EC_ROUND16(_mm512_set1_epi32(int(PAD_SCHED.w[t])));
  }
  a = _mm512_add_epi32(a, sa);
  b = _mm512_add_epi32(b, sb);
  c = _mm512_add_epi32(c, sc);
  d = _mm512_add_epi32(d, sd);
  e = _mm512_add_epi32(e, se);
  f = _mm512_add_epi32(f, sf);
  g = _mm512_add_epi32(g, sg);
  h = _mm512_add_epi32(h, sh);

  alignas(64) uint32_t lanes[8][16];
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[0]), a);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[1]), b);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[2]), c);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[3]), d);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[4]), e);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[5]), f);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[6]), g);
  _mm512_store_si512(reinterpret_cast<__m512i*>(lanes[7]), h);
  for (int lane = 0; lane < 16; ++lane) {
    for (int i = 0; i < 8; ++i) {
      store_be32(out + 32 * lane + 4 * i, lanes[i][lane]);
    }
  }
}

#undef EC_ROUND16

EC_SHA512_TARGET inline void hash_level_x16(const uint8_t* in, uint8_t* out,
                                            size_t n16) {
  for (size_t i = 0; i < n16; ++i) {
    sha256_64_x16(in + 64 * 16 * i, out + 32 * 16 * i);
  }
}

inline bool avx512_available() {
  static const bool ok = __builtin_cpu_supports("avx512f") &&
                         __builtin_cpu_supports("avx512bw") &&
                         __builtin_cpu_supports("avx512dq") &&
                         __builtin_cpu_supports("avx512vl");
  return ok;
}
#endif  // __x86_64__

}  // namespace

extern "C" {

// Hash one merkle level: in = n_pairs 64-byte messages, out = n_pairs
// 32-byte digests. in/out may not alias.
void ec_hash_level(const uint8_t* in, uint8_t* out, size_t n_pairs) {
#ifdef EC_SHA_NI_ACTIVE
  for (size_t i = 0; i < n_pairs; ++i) {
    sha256_64_ni(in + 64 * i, out + 32 * i);
  }
#else
  size_t i = 0;
#ifdef EC_AVX512_COMPILED
  if (avx512_available() && n_pairs >= 16) {
    size_t n16 = n_pairs / 16;
    hash_level_x16(in, out, n16);
    i = 16 * n16;
  }
#endif
#ifdef EC_AVX2_ACTIVE
  for (; i + 8 <= n_pairs; i += 8) {
    sha256_64_x8(in + 64 * i, out + 32 * i);
  }
#endif
  for (; i < n_pairs; ++i) {
    sha256_64(in + 64 * i, out + 32 * i);
  }
#endif
}

// Full tree reduction: `chunks` holds `count` populated 32-byte leaves of a
// depth-`depth` virtual tree; `zero_hashes` is depth+1 cached zero-subtree
// roots (32 bytes each). Writes the 32-byte root to `out32`. Matches the
// Python merkleizer bit-for-bit (zero-padding odd levels with the level's
// zero hash).
void ec_merkle_root(const uint8_t* chunks, size_t count, uint32_t depth,
                    const uint8_t* zero_hashes, uint8_t* out32) {
  if (count == 0) {
    std::memcpy(out32, zero_hashes + 32 * size_t(depth), 32);
    return;
  }
  std::vector<uint8_t> nodes(chunks, chunks + 32 * count);
  std::vector<uint8_t> next;
  for (uint32_t level = 0; level < depth; ++level) {
    size_t n = nodes.size() / 32;
    if (n % 2 == 1) {
      nodes.insert(nodes.end(), zero_hashes + 32 * size_t(level),
                   zero_hashes + 32 * size_t(level) + 32);
      ++n;
    }
    next.resize(32 * (n / 2));
    ec_hash_level(nodes.data(), next.data(), n / 2);
    nodes.swap(next);
  }
  std::memcpy(out32, nodes.data(), 32);
}

}  // extern "C"

namespace {

// Root of chunk-group `g` of `raw`: 2^depth chunks of 32 bytes, the bytes
// past `raw_len` zero; what pack_bytes + merkleize_chunks(limit=2^depth)
// give for the group's bytes. The first level reads `raw` in place;
// `scratch` holds 32 << depth bytes, two halves the levels alternate in.
void root_group(const uint8_t* raw, size_t raw_len, uint64_t g,
                uint32_t depth, const uint8_t* zero_hashes, uint8_t* scratch,
                uint8_t* out32) {
  const size_t gbytes = size_t(32) << depth;
  const size_t start = size_t(g) * gbytes;
  const size_t len = start < raw_len ? std::min(gbytes, raw_len - start) : 0;
  if (len == 0) {
    std::memcpy(out32, zero_hashes + 32 * size_t(depth), 32);
    return;
  }
  uint8_t* cur = scratch;
  uint8_t* nxt = scratch + gbytes / 2;
  size_t n = len / 64;
  ec_hash_level(raw + start, cur, n);
  if (len % 64) {
    // the last pair: a partial chunk's padding and an odd chunk's
    // sibling (zero_hashes[0]) are both zero bytes
    uint8_t tail[64] = {0};
    std::memcpy(tail, raw + start + 64 * n, len % 64);
    ec_hash_level(tail, cur + 32 * n, 1);
    ++n;
  }
  for (uint32_t level = 1; level < depth; ++level) {
    if (n % 2 == 1) {
      std::memcpy(cur + 32 * n, zero_hashes + 32 * size_t(level), 32);
      ++n;
    }
    ec_hash_level(cur, nxt, n / 2);
    n /= 2;
    std::swap(cur, nxt);
  }
  std::memcpy(out32, cur, 32);
}

}  // namespace

extern "C" {

// Roots of the chunk-groups `group_ids` of `raw` (each 2^group_depth
// chunks; `zero_hashes` = group_depth+1 zero-subtree roots), 32 bytes each
// into `out` in the order given. Up to `n_threads` threads, spawned here
// and joined before return, take groups off one shared index; the
// calling thread is one of them. Returns the threads that ran, 0 when
// nothing was written (group_depth 0, or no scratch could be allocated).
uint32_t ec_merkle_groups(const uint8_t* raw, size_t raw_len,
                          const uint64_t* group_ids, size_t n_groups,
                          uint32_t group_depth, const uint8_t* zero_hashes,
                          uint8_t* out, uint32_t n_threads) {
  if (n_groups == 0) return 1;
  if (group_depth == 0) return 0;  // a one-chunk group is its own root
  if (n_threads > n_groups) n_threads = uint32_t(n_groups);
  if (n_threads < 1) n_threads = 1;
  const size_t gbytes = size_t(32) << group_depth;
  std::vector<uint8_t> scratch;
  try {
    scratch.resize(gbytes * n_threads);
  } catch (...) {
    return 0;
  }
  std::atomic<size_t> next{0};
  auto work = [&](size_t t) {
    uint8_t* buf = scratch.data() + gbytes * t;
    for (size_t i; (i = next.fetch_add(1)) < n_groups;) {
      root_group(raw, raw_len, group_ids[i], group_depth, zero_hashes, buf,
                 out + 32 * i);
    }
  };
  std::vector<std::thread> pool;
  try {
    pool.reserve(n_threads - 1);
    for (uint32_t t = 1; t < n_threads; ++t) pool.emplace_back(work, t);
  } catch (...) {
    // fewer threads than asked: the ones running take the rest
  }
  work(0);
  for (auto& th : pool) th.join();
  return uint32_t(pool.size() + 1);
}

// The swap-or-not shuffle of the consensus specification, per index and
// round-major: out[j] = compute_shuffled_index(in[j], count, seed) for each
// of the n lanes. Each round's pivot is hashed once. The lanes then go
// through every round a tile at a time, so the scratch is the tile's, on
// the stack: a round hashes each lane's source block (seed + round +
// uint32_le(position >> 8), one padded block) eight lanes at once where the
// build has AVX2, the scalar routine taking the tail. Runs on the calling
// thread. Returns the widest lane routine it has (8, or 1), and 0, having
// written nothing, for count outside 1..2^40 (a source block's number is
// 4 bytes), more than 256 rounds (the round is 1 byte), or an input not
// below count.
uint32_t ec_shuffle_positions(const uint8_t* seed32, uint64_t count,
                              uint32_t rounds, const uint64_t* in,
                              uint64_t* out, size_t n) {
  if (count == 0 || count > (uint64_t(1) << 40) || rounds > 256) return 0;
  for (size_t j = 0; j < n; ++j) {
    if (in[j] >= count) return 0;
  }
  uint32_t seed[8];
  for (int t = 0; t < 8; ++t) seed[t] = load_be32(seed32 + 4 * t);
  // the pivot: the first 8 bytes, little-endian, of SHA-256(seed + round)
  uint64_t pivots[256];
  for (uint32_t r = 0; r < rounds; ++r) {
    uint32_t pw[16] = {0};
    std::memcpy(pw, seed, sizeof(seed));
    pw[8] = (r << 24) | 0x800000u;
    pw[15] = 33 * 8;
    uint32_t ps[8];
    std::memcpy(ps, H0, sizeof(H0));
    compress(ps, pw);
    pivots[r] = (uint64_t(__builtin_bswap32(ps[0])) |
                 uint64_t(__builtin_bswap32(ps[1])) << 32) %
                count;
  }
  constexpr size_t kTile = 256;
  uint32_t words[16 * kTile], digest[8 * kTile];
  uint64_t flip[kTile];
  std::copy(in, in + n, out);
  for (size_t base = 0; base < n; base += kTile) {
    const size_t m = std::min(kTile, n - base);
    uint64_t* lane = out + base;
    // the 37-byte message's words but its round and position: the seed,
    // the padding's zeros and its bit length
    for (int t = 0; t < 8; ++t) {
      std::fill(words + t * m, words + (t + 1) * m, seed[t]);
    }
    std::fill(words + 10 * m, words + 15 * m, 0u);
    std::fill(words + 15 * m, words + 16 * m, uint32_t(37 * 8));
    for (uint32_t r = 0; r < rounds; ++r) {
      for (size_t j = 0; j < m; ++j) {
        const uint64_t index = lane[j];
        flip[j] = (pivots[r] + count - index) % count;
        const uint64_t q = std::max(index, flip[j]) >> 8;
        words[8 * m + j] = (r << 24) | uint32_t(q & 0xff) << 16 |
                           uint32_t((q >> 8) & 0xff) << 8 |
                           uint32_t((q >> 16) & 0xff);
        words[9 * m + j] = uint32_t((q >> 24) & 0xff) << 24 | 0x800000u;
      }
      size_t g = 0;
#ifdef EC_AVX2_ACTIVE
      for (; g + 8 <= m; g += 8) sha256_block_x8(words + g, m, digest + g);
#endif
      for (; g < m; ++g) sha256_block(words + g, m, digest + g);
      for (size_t j = 0; j < m; ++j) {
        const uint64_t position = std::max(lane[j], flip[j]);
        const uint32_t byte_at = uint32_t(position & 255) >> 3;
        const uint32_t word = digest[(byte_at >> 2) * m + j];
        const uint32_t byte = (word >> (24 - 8 * (byte_at & 3))) & 0xff;
        if ((byte >> (position & 7)) & 1) lane[j] = flip[j];
      }
    }
  }
#ifdef EC_AVX2_ACTIVE
  return 8;
#else
  return 1;
#endif
}

uint64_t ec_version(void) { return 1; }

}  // extern "C"
