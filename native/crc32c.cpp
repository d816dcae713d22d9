// crc32c (Castagnoli) in Ceph's convention: a raw update of the register,
// with NO inversion before or after, seeded by the caller (reference
// include/crc32c.h ceph_crc32c; golden vectors in test/common/test_crc32c.cc,
// e.g. crc32c(0, "foo bar baz") = 4119623852).
//
// The implementation is chosen when the library is built, from the CPU it is
// built for (ceph_tpu/native.py builds with -march=native into a directory
// keyed by that CPU), where the reference's common/crc32c.cc
// ceph_choose_crc32 chooses at run time:
//   sse42   x86-64 with SSE4.2: the crc32 instruction, 8 bytes a step
//   armv8   arm64 with the CRC extension: crc32cx / crc32cb
//   table8  otherwise: slicing-by-8 tables (common/sctp_crc32.c)
// The instruction paths run three independent streams over long buffers,
// since the instruction issues every cycle but takes three to finish, and
// join them with a shift-by-zeros operator (crc32c_intel_fast's design).

#include <cstdint>
#include <cstring>

#if defined(__SSE4_2__)
#include <nmmintrin.h>
#elif defined(__ARM_FEATURE_CRC32)
#include <arm_acle.h>
#endif

namespace {

// eight bytes in memory order as one little-endian word, from any address
inline uint64_t load_le64(const uint8_t* p) {
  uint64_t v;
  memcpy(&v, p, 8);
#if __BYTE_ORDER__ == __ORDER_BIG_ENDIAN__
  v = __builtin_bswap64(v);
#endif
  return v;
}

#if defined(__SSE4_2__) || defined(__ARM_FEATURE_CRC32)

#if defined(__SSE4_2__)
constexpr const char* IMPL = "sse42";
inline uint32_t step8(uint32_t crc, uint64_t v) {
  return (uint32_t)_mm_crc32_u64(crc, v);
}
inline uint32_t step1(uint32_t crc, uint8_t b) { return _mm_crc32_u8(crc, b); }
#else
constexpr const char* IMPL = "armv8";
inline uint32_t step8(uint32_t crc, uint64_t v) { return __crc32cd(crc, v); }
inline uint32_t step1(uint32_t crc, uint8_t b) { return __crc32cb(crc, b); }
#endif

// bytes per stream in one round of three streams
constexpr int64_t BLOCK = 2048;

// The register after BLOCK zero bytes is linear in the register before
// them; shift.t[j][b] is its value from the register b << 8j.
struct Shift {
  uint32_t t[4][256];
};

const Shift& block_shift() {
  static const Shift shift = [] {
    uint32_t basis[32];
    for (int i = 0; i < 32; i++) {
      uint32_t c = 1u << i;
      for (int64_t k = 0; k < BLOCK; k += 8) c = step8(c, 0);
      basis[i] = c;
    }
    Shift s;
    for (int j = 0; j < 4; j++)
      for (uint32_t b = 0; b < 256; b++) {
        uint32_t v = 0;
        for (int i = 0; i < 8; i++)
          if (b >> i & 1) v ^= basis[8 * j + i];
        s.t[j][b] = v;
      }
    return s;
  }();
  return shift;
}

inline uint32_t shift_block(const Shift& s, uint32_t c) {
  return s.t[0][c & 0xff] ^ s.t[1][c >> 8 & 0xff] ^ s.t[2][c >> 16 & 0xff] ^
         s.t[3][c >> 24];
}

uint32_t crc_update(uint32_t crc, const uint8_t* p, int64_t n) {
  if (n >= 3 * BLOCK) {
    const Shift& s = block_shift();
    do {
      // crc(r, A B C) = shift(shift(crc(r, A)) ^ crc(0, B)) ^ crc(0, C)
      uint32_t c1 = 0, c2 = 0;
      for (int64_t i = 0; i < BLOCK; i += 8) {
        crc = step8(crc, load_le64(p + i));
        c1 = step8(c1, load_le64(p + BLOCK + i));
        c2 = step8(c2, load_le64(p + 2 * BLOCK + i));
      }
      crc = shift_block(s, crc) ^ c1;
      crc = shift_block(s, crc) ^ c2;
      p += 3 * BLOCK;
      n -= 3 * BLOCK;
    } while (n >= 3 * BLOCK);
  }
  for (; n >= 8; p += 8, n -= 8) crc = step8(crc, load_le64(p));
  for (; n > 0; p++, n--) crc = step1(crc, *p);
  return crc;
}

#else

constexpr const char* IMPL = "table8";
constexpr uint32_t POLY = 0x82f63b78u;  // reflected CRC-32C polynomial

// t[k][b]: the register after the byte b and k zero bytes, from 0
struct Tables {
  uint32_t t[8][256];
};

const Tables& tables() {
  static const Tables tables = [] {
    Tables s;
    for (uint32_t b = 0; b < 256; b++) {
      uint32_t c = b;
      for (int j = 0; j < 8; j++) c = (c & 1) ? (c >> 1) ^ POLY : c >> 1;
      s.t[0][b] = c;
    }
    for (int k = 1; k < 8; k++)
      for (uint32_t b = 0; b < 256; b++)
        s.t[k][b] = (s.t[k - 1][b] >> 8) ^ s.t[0][s.t[k - 1][b] & 0xff];
    return s;
  }();
  return tables;
}

uint32_t crc_update(uint32_t crc, const uint8_t* p, int64_t n) {
  const auto& t = tables().t;
  for (; n >= 8; p += 8, n -= 8) {
    uint64_t v = load_le64(p) ^ crc;
    crc = t[7][v & 0xff] ^ t[6][v >> 8 & 0xff] ^ t[5][v >> 16 & 0xff] ^
          t[4][v >> 24 & 0xff] ^ t[3][v >> 32 & 0xff] ^
          t[2][v >> 40 & 0xff] ^ t[1][v >> 48 & 0xff] ^ t[0][v >> 56];
  }
  for (; n > 0; p++, n--) crc = t[0][(crc ^ *p) & 0xff] ^ (crc >> 8);
  return crc;
}

#endif

}  // namespace

extern "C" {

uint32_t ceph_crc32c(uint32_t crc, const uint8_t* data, int64_t n) {
  return crc_update(crc, data, n);
}

// which of the three this library was built with
const char* ceph_crc32c_impl() { return IMPL; }

}  // extern "C"
