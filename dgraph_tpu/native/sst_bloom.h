// The SSTable bloom filter's hashes (storage/lsm.py _bloom_hashes), shared
// by the table writer (bulkload.cpp) and the read probe (codec.cpp): zlib's
// crc32 | adler32 << 32 through two splitmix64 finalizers, probed by double
// hashing. Python evaluates (h1 + i*h2) % nbits in arbitrary precision, so
// the probe is 128-bit math, NOT 64-bit wraparound.
#pragma once

#include <cstddef>
#include <cstdint>

static inline const uint32_t* sst_crc32_table() {
  static const struct Tab {
    uint32_t t[256];
    Tab() {
      for (uint32_t i = 0; i < 256; ++i) {
        uint32_t c = i;
        for (int k = 0; k < 8; ++k)
          c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        t[i] = c;
      }
    }
  } tab;
  return tab.t;
}

static inline void sst_bloom_hashes(const uint8_t* p, size_t n, uint64_t* h1,
                                    uint64_t* h2) {
  const uint32_t* tab = sst_crc32_table();
  uint32_t c = 0xFFFFFFFFu, a = 1, b = 0;
  for (size_t i = 0; i < n; ++i) {
    c = tab[(c ^ p[i]) & 0xFF] ^ (c >> 8);
    a = (a + p[i]) % 65521;
    b = (b + a) % 65521;
  }
  uint64_t x = uint64_t(c ^ 0xFFFFFFFFu) | (uint64_t((b << 16) | a) << 32);
  uint64_t z = x + 0x9E3779B97F4A7C15ULL;
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
  *h1 = z ^ (z >> 31);
  z = x + 0x3C6EF372FE94F82AULL;
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
  *h2 = (z ^ (z >> 31)) | 1;
}

static const int SST_BLOOM_HASHES = 3;  // lsm.py _BLOOM_HASHES

static inline uint64_t sst_bloom_bit(uint64_t h1, uint64_t h2, int i,
                                     uint64_t nbits) {
  return uint64_t(
      ((unsigned __int128)h1 + (unsigned __int128)h2 * (unsigned)i) % nbits);
}
