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
//
// The overflow list's hot form (tilemm.py: hot_ranks / encode_hot are the
// written specification, the same bits):
//   int64 wh_hot_rank(ovf_b, ovf_r, n, subblocks, uniq, rank, cell_max)
//                                                   -> distinct buckets
//   int64 wh_hot_place(rank, ovf_r, n, subblocks, tiles, vtiles, cap,
//                      counts, pw)                  -> pairs without room
// Two calls because the room (tiles, vtiles) is the caller's to choose
// from the count of distinct buckets and the fullest cell. wh_hot_rank's
// hash table is the call's own (a vector); everything else is the
// caller's.
//
// A list cut by the key range that owns each bucket, for a table sharded
// over a mesh's MODEL axis (data/crec.py: cut_overflow is the written
// specification), each part then a list of its own to the two above:
//   int64 wh_hot_cut(ovf_b, ovf_r, n, parts, nb_local, out_b, out_r,
//                    counts)                        -> valid pairs, or -1

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <vector>

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

// The list's distinct buckets in ascending order into uniq[0..d) (room
// for n), every pair's index among them into rank[0..n), and into
// *cell_max the most pairs any (subblock, hot tile) cell holds, a hot
// tile being 16,384 consecutive ranks. Returns d. A list of a skewed
// block names a few thousand buckets a million times, so the table is an
// open-addressed one that starts small (it stays in cache) and doubles
// when half full; ids are given in order of first sight and renumbered
// once the distinct buckets are sorted.
int64_t wh_hot_rank(const uint32_t* ovf_b, const uint32_t* ovf_r, int64_t n,
                    int64_t subblocks, uint32_t* uniq, uint32_t* rank,
                    int64_t* cell_max) {
  size_t cap = 1u << 14;
  std::vector<uint32_t> keys(cap, kSentinel), ids(cap);
  int64_t d = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t b = ovf_b[i];
    size_t at = mix32(b) & (cap - 1);
    while (keys[at] != kSentinel && keys[at] != b) at = (at + 1) & (cap - 1);
    if (keys[at] == kSentinel) {
      keys[at] = b;
      ids[at] = static_cast<uint32_t>(d);
      uniq[d++] = b;
      if (static_cast<size_t>(d) * 2 > cap) {
        cap *= 2;
        keys.assign(cap, kSentinel);
        ids.resize(cap);
        for (int64_t j = 0; j < d; ++j) {
          size_t to = mix32(uniq[j]) & (cap - 1);
          while (keys[to] != kSentinel) to = (to + 1) & (cap - 1);
          keys[to] = uniq[j];
          ids[to] = static_cast<uint32_t>(j);
        }
        at = mix32(b) & (cap - 1);
        while (keys[at] != b) at = (at + 1) & (cap - 1);
      }
    }
    rank[i] = ids[at];
  }
  // first-sight id -> place in ascending order
  std::vector<uint32_t> order(static_cast<size_t>(d)), place(
      static_cast<size_t>(d));
  for (int64_t j = 0; j < d; ++j) order[j] = static_cast<uint32_t>(j);
  std::sort(order.begin(), order.end(),
            [&](uint32_t a, uint32_t b) { return uniq[a] < uniq[b]; });
  for (int64_t j = 0; j < d; ++j) place[order[j]] = static_cast<uint32_t>(j);
  std::vector<uint32_t> sorted(static_cast<size_t>(d));
  for (int64_t j = 0; j < d; ++j) sorted[j] = uniq[order[j]];
  if (d) memcpy(uniq, sorted.data(), static_cast<size_t>(d) * sizeof(uint32_t));
  const int64_t tiles = d ? (d + kTileMask) >> kTileShift : 1;
  std::vector<int64_t> cells(static_cast<size_t>(subblocks * tiles), 0);
  int64_t most = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t r = place[rank[i]];
    rank[i] = r;
    const int64_t c = ++cells[(ovf_r[i] >> kRsubShift) * tiles +
                              (r >> kTileShift)];
    if (c > most) most = c;
  }
  *cell_max = most;
  return d;
}

// A stable partition of the list's valid pairs (bucket != the sentinel)
// by owner: part m holds the pairs whose bucket lies in [m*nb_local,
// (m+1)*nb_local), in list order, the bucket made local to the range. The
// parts lie end to end in out_b / out_r (room for n), counts[m] pairs
// each. Returns the valid pairs, or -1 where a bucket lies past the last
// range (nothing is written then).
int64_t wh_hot_cut(const uint32_t* ovf_b, const uint32_t* ovf_r, int64_t n,
                   int64_t parts, uint32_t nb_local, uint32_t* out_b,
                   uint32_t* out_r, int64_t* counts) {
  // the owner by the ranges' ends, summed without a branch: which range
  // a pair falls in is a coin's toss, and a mesh has a few ranges
  std::vector<uint64_t> end(static_cast<size_t>(parts));
  for (int64_t m = 0; m < parts; ++m)
    end[m] = static_cast<uint64_t>(m + 1) * nb_local;
  const auto owner = [&](uint32_t b) {
    int64_t m = 0;
    for (int64_t k = 0; k < parts; ++k) m += b >= end[k];
    return m;
  };
  for (int64_t m = 0; m < parts; ++m) counts[m] = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t b = ovf_b[i];
    if (b == kSentinel) continue;
    const int64_t m = owner(b);
    if (m >= parts) return -1;
    ++counts[m];
  }
  std::vector<int64_t> at(static_cast<size_t>(parts), 0);
  for (int64_t m = 1; m < parts; ++m) at[m] = at[m - 1] + counts[m - 1];
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t b = ovf_b[i];
    if (b == kSentinel) continue;
    const int64_t m = owner(b);
    const int64_t j = at[m]++;
    out_b[j] = b - static_cast<uint32_t>(m) * nb_local;
    out_r[j] = ovf_r[i];
  }
  return at[parts - 1];
}

// Fill pw (tiles*vtiles, S, cap), flat, with PADWORD and place pair i,
// the k-th of its (subblock s, hot tile h = rank >> 14) cell in list
// order, at virtual tile h*vtiles + k/cap, subblock s, slot k%cap, as
// tilemm.pack_fields(rank % TILE, row % RSUB). `counts` is scratch,
// i64[subblocks*tiles]. The caller sized vtiles to the fullest cell
// (wh_hot_rank's cell_max); a pair past it is dropped and counted, and
// the count returned: anything but 0 is the caller's error.
int64_t wh_hot_place(const uint32_t* rank, const uint32_t* ovf_r, int64_t n,
                     int64_t subblocks, int64_t tiles, int64_t vtiles,
                     uint32_t cap, int64_t* counts, uint32_t* pw) {
  memset(counts, 0, static_cast<size_t>(subblocks * tiles) * sizeof(int64_t));
  const int64_t words = tiles * vtiles * subblocks * cap;
  for (int64_t i = 0; i < words; ++i) pw[i] = kPadWord;
  int64_t lost = 0;
  for (int64_t i = 0; i < n; ++i) {
    const uint32_t r = ovf_r[i];
    const int64_t sub = r >> kRsubShift;
    const int64_t h = rank[i] >> kTileShift;
    const int64_t k = counts[sub * tiles + h]++;
    const int64_t vt = k / cap;
    if (vt >= vtiles) {
      ++lost;
      continue;
    }
    pw[((h * vtiles + vt) * subblocks + sub) * cap + k % cap] =
        (rank[i] & kTileMask) | ((r & kRsubMask) << 16);
  }
  return lost;
}

}  // extern "C"
