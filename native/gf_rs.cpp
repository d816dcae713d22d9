// Native GF(2^8) region coder — the SIMD-class host path.
//
// Plays the role of the reference's isa-l/jerasure native libraries
// (ec_encode_data; reference src/erasure-code/isa/ErasureCodeIsa.cc:128):
// region multiply-accumulate over GF(2^8) using 4-bit split tables, which
// GCC auto-vectorizes.  Used as the CPU benchmark baseline and as a second
// implementation cross-checking the Python/numpy codec.

#include <cstdint>
#include <cstring>

namespace {

constexpr unsigned POLY = 0x11d;

struct Tables {
  uint8_t mul[256][256];
  bool ready = false;
} g;

void init_tables() {
  if (g.ready) return;
  uint8_t exp[512];
  int log[256] = {0};
  unsigned x = 1;
  for (int i = 0; i < 255; i++) {
    exp[i] = (uint8_t)x;
    log[x] = i;
    x <<= 1;
    if (x & 0x100) x ^= POLY;
  }
  for (int i = 255; i < 512; i++) exp[i] = exp[i - 255];
  for (int a = 1; a < 256; a++)
    for (int b = 1; b < 256; b++)
      g.mul[a][b] = exp[log[a] + log[b]];
  memset(g.mul[0], 0, 256);
  for (int a = 0; a < 256; a++) g.mul[a][0] = 0;
  g.ready = true;
}

// dst ^= coeff * src over a region, via split lo/hi nibble tables
void region_mad(uint8_t coeff, const uint8_t* src, uint8_t* dst, int64_t n) {
  if (coeff == 0) return;
  if (coeff == 1) {
    for (int64_t i = 0; i < n; i++) dst[i] ^= src[i];
    return;
  }
  uint8_t lo[16], hi[16];
  for (int v = 0; v < 16; v++) {
    lo[v] = g.mul[coeff][v];
    hi[v] = g.mul[coeff][v << 4];
  }
  for (int64_t i = 0; i < n; i++) {
    uint8_t b = src[i];
    dst[i] ^= (uint8_t)(lo[b & 0xf] ^ hi[b >> 4]);
  }
}

}  // namespace

extern "C" {

// coding[r][*] = sum_j matrix[r*k+j] * data[j][*]; data/coding are
// contiguous (k, n) and (rows, n) uint8 buffers.
void gf_rs_encode(const uint8_t* matrix, int rows, int k,
                  const uint8_t* data, uint8_t* coding, int64_t n) {
  init_tables();
  memset(coding, 0, (size_t)rows * n);
  for (int r = 0; r < rows; r++)
    for (int j = 0; j < k; j++)
      region_mad(matrix[r * k + j], data + (int64_t)j * n,
                 coding + (int64_t)r * n, n);
}

void gf_region_xor(const uint8_t* a, const uint8_t* b, uint8_t* out,
                   int64_t n) {
  for (int64_t i = 0; i < n; i++) out[i] = a[i] ^ b[i];
}

uint8_t gf_mul_c(uint8_t a, uint8_t b) {
  init_tables();
  return g.mul[a][b];
}

}  // extern "C"
