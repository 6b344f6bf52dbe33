// Native tile encode for wormhole-tpu: a keys grid -> the tile kernels'
// packed pair words and the COO overflow list, as one counting placement.
//
// The written specification is wormhole_tpu/ops/tilemm.py (encode_block /
// encode_subblock behind data/crec.py:encode_tile_pairs, numpy); this must
// give the same bits — pw, and the overflow list's members AND order —
// and tests/test_tile_encode_native.py holds it to that. What numpy does
// with a stable sort per subblock is done here by counting: the pairs
// arrive row-major, the tile of a pair is bucket >> 14, and a pair's slot
// inside its (subblock, tile) cell is how many came before it.
//
// ABI (consumed via ctypes from wormhole_tpu/data/native.py):
//   int64 wh_tile_count(keys, rows, nnz, nb, subblocks, tiles, cap,
//                       buckets, counts, offs)        -> overflow pairs
//   void  wh_tile_place(buckets, rows, nnz, subblocks, tiles, cap,
//                       offs, counts, pw, ovf_b, ovf_r)
// Two calls because the overflow list's length is known only after the
// count, and the caller sizes the list to it. Every array is the
// caller's, scratch included (buckets u32[rows*nnz], counts u32[cells],
// offs i64[cells], cells = subblocks*tiles): nothing here is static or
// thread-local, so concurrent callers share nothing.

#include <cstdint>
#include <cstring>

namespace {

constexpr uint32_t kSentinel = 0xFFFFFFFFu;  // crec.SENTINEL_KEY: no pair
constexpr int kTileShift = 14;               // tilemm.TILE = 128 * 128
constexpr uint32_t kTileMask = (1u << kTileShift) - 1;
constexpr int kRsubShift = 13;               // tilemm.RSUB = 64 * 128
constexpr uint32_t kRsubMask = (1u << kRsubShift) - 1;
constexpr uint32_t kPadWord = 511u << 7;     // tilemm.PADWORD

// murmur3's finaliser: hashing.mix32_np
inline uint32_t mix32(uint32_t x) {
  x ^= x >> 16;
  x *= 0x85EBCA6Bu;
  x ^= x >> 13;
  x *= 0xC2B2AE35u;
  return x ^ (x >> 16);
}

}  // namespace

extern "C" {

// Pass 1: fold every real key to its bucket (hashing.fold_keys32: mix32,
// then % nb), keep the bucket in `buckets` (kSentinel where the grid has
// no pair: a bucket is < nb <= 0xFFFFFFFF, so never that), count the
// pairs of each (subblock, tile) cell, and lay the cells' overflow out in
// (subblock, tile) order: offs[cell] is where the cell's pairs past `cap`
// start in the list. That IS the order of the numpy encoder's list
// (subblock by subblock; inside one, by tile; inside a tile, row-major).
// Returns the list's length.
int64_t wh_tile_count(const uint32_t* keys, int64_t rows, int64_t nnz,
                      uint32_t nb, int64_t subblocks, int64_t tiles,
                      uint32_t cap, uint32_t* buckets, uint32_t* counts,
                      int64_t* offs) {
  const int64_t cells = subblocks * tiles;
  memset(counts, 0, static_cast<size_t>(cells) * sizeof(uint32_t));
  const uint32_t mask = nb - 1;
  const bool pow2 = (nb & mask) == 0;
  for (int64_t r = 0; r < rows; ++r) {
    const uint32_t* k = keys + r * nnz;
    uint32_t* b = buckets + r * nnz;
    uint32_t* cnt = counts + (r >> kRsubShift) * tiles;
    for (int64_t c = 0; c < nnz; ++c) {
      if (k[c] == kSentinel) {
        b[c] = kSentinel;
        continue;
      }
      const uint32_t h = mix32(k[c]);
      const uint32_t bucket = pow2 ? (h & mask) : (h % nb);
      b[c] = bucket;
      ++cnt[bucket >> kTileShift];
    }
  }
  int64_t n_ovf = 0;
  for (int64_t cell = 0; cell < cells; ++cell) {
    offs[cell] = n_ovf;
    if (counts[cell] > cap) n_ovf += counts[cell] - cap;
  }
  return n_ovf;
}

// Pass 2: fill pw with PADWORD and place. A pair whose running index in
// its cell is under `cap` goes to pw[(tile*S + sub)*cap + idx] as
// tilemm.pack_fields(bucket % TILE, row % RSUB) — the kernel's
// (T, S//GS, GS*cap) layout, flat — and a pair past it to the overflow
// list at offs[cell] + idx - cap as (bucket, block-global row).
// `counts` is scratch again: the cells' running indices.
void wh_tile_place(const uint32_t* buckets, int64_t rows, int64_t nnz,
                   int64_t subblocks, int64_t tiles, uint32_t cap,
                   const int64_t* offs, uint32_t* counts, uint32_t* pw,
                   uint32_t* ovf_b, uint32_t* ovf_r) {
  const int64_t cells = subblocks * tiles;
  memset(counts, 0, static_cast<size_t>(cells) * sizeof(uint32_t));
  const int64_t words = cells * cap;
  for (int64_t i = 0; i < words; ++i) pw[i] = kPadWord;
  for (int64_t r = 0; r < rows; ++r) {
    const uint32_t* b = buckets + r * nnz;
    const int64_t sub = r >> kRsubShift;
    const int64_t cell0 = sub * tiles;
    // pack_fields: lo | hi<<7 is the in-tile bucket itself, and
    // rlo<<16 | rhi<<23 the in-subblock row shifted by 16
    const uint32_t row_bits = (static_cast<uint32_t>(r) & kRsubMask) << 16;
    for (int64_t c = 0; c < nnz; ++c) {
      const uint32_t bucket = b[c];
      if (bucket == kSentinel) continue;
      const int64_t tile = bucket >> kTileShift;
      const uint32_t idx = counts[cell0 + tile]++;
      if (idx < cap) {
        pw[(tile * subblocks + sub) * cap + idx] =
            (bucket & kTileMask) | row_bits;
      } else {
        const int64_t j = offs[cell0 + tile] + (idx - cap);
        ovf_b[j] = bucket;
        ovf_r[j] = static_cast<uint32_t>(r);
      }
    }
  }
}

}  // extern "C"
