// Native host kernels: bit-pack codec + sorted-set algebra.
//
// The reference's performance-critical "near-native" pieces (SURVEY.md §2.7)
// are go-groupvarint's SSE decode (codec/codec.go:15) and the adaptive
// intersect loops (algo/uidlist.go). On the TPU build these live in two
// places: the device kernels (ops/setops.py) for batched query execution,
// and THIS file for the host-side paths — disk (de)serialization of UID
// packs and small singleton set ops where device dispatch isn't worth it.
//
// Built with -O3 -march=native when available; the auto-vectorizer turns
// the pack/unpack loops into SIMD shifts/masks (the groupvarint-equivalent).
// Exposed via ctypes (dgraph_tpu/native/__init__.py) — no pybind11 needed.

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <cmath>
#include <string>
#include <thread>
#include <unordered_map>
#include <utility>
#include <vector>

#include "sst_bloom.h"

#if defined(__AVX512VNNI__) || defined(__AVX512BW__) || defined(__AVX2__)
#include <immintrin.h>
#endif

extern "C" {

// ---------------------------------------------------------------------------
// Bit-packing: fixed-width lanes (ref codec.go packBlock; fixed-width instead
// of group-varint so decode is branch-free — see codec/uidpack.py docstring).
// ---------------------------------------------------------------------------

void bitpack(const uint32_t* vals, int64_t n, int width, uint8_t* out) {
    // out must be zeroed, size (n*width+7)/8
    uint64_t bitpos = 0;
    for (int64_t i = 0; i < n; i++) {
        uint64_t v = vals[i];
        uint64_t byte = bitpos >> 3;
        uint64_t shift = bitpos & 7;
        // write up to 5 bytes (width <= 32, shift <= 7)
        uint64_t cur = 0;
        memcpy(&cur, out + byte, 5);
        cur |= (v << shift);
        memcpy(out + byte, &cur, 5);
        bitpos += width;
    }
}

void bitunpack(const uint8_t* data, int64_t nbytes, int64_t n, int width,
               uint32_t* out) {
    uint64_t mask = (width >= 32) ? 0xFFFFFFFFull : ((1ull << width) - 1);
    uint64_t bitpos = 0;
    for (int64_t i = 0; i < n; i++) {
        uint64_t byte = bitpos >> 3;
        uint64_t shift = bitpos & 7;
        uint64_t window = 0;
        int64_t take = nbytes - (int64_t)byte;
        if (take > 8) take = 8;
        if (take > 0) memcpy(&window, data + byte, take);
        out[i] = (uint32_t)((window >> shift) & mask);
        bitpos += width;
    }
}

// Partial UidPack decode: materialize ONLY the listed blocks (codec/
// uidpack.py decode_blocks). offsets is the (nblocks, block_size) u32
// matrix; idxs are ascending block indices. Returns UIDs written.
int64_t pack_decode_blocks(const uint64_t* bases, const int32_t* counts,
                           const uint32_t* offsets, int64_t block_size,
                           const int64_t* idxs, int64_t nidx, uint64_t* out) {
    int64_t k = 0;
    for (int64_t i = 0; i < nidx; i++) {
        int64_t bi = idxs[i];
        uint64_t base = bases[bi];
        const uint32_t* row = offsets + bi * block_size;
        int64_t c = counts[bi];
        for (int64_t j = 0; j < c; j++) out[k++] = base + row[j];
    }
    return k;
}

// Level-batched fan-out fast path: decode N packs (one per parent uid of a
// traversal level) into ONE flat uid buffer + a per-pack prefix-offsets
// array in a single native pass. Per-pack pointer arrays avoid
// concatenating the block matrices host-side; out_offsets has npacks+1
// entries (out_offsets[p]..out_offsets[p+1] is pack p's row). Returns
// total UIDs written.
int64_t packs_decode_many(const uint64_t* const* bases,
                          const int32_t* const* counts,
                          const uint32_t* const* offsets,
                          const int64_t* nblocks, int64_t block_size,
                          int64_t npacks, uint64_t* out,
                          int64_t* out_offsets) {
    int64_t k = 0;
    for (int64_t p = 0; p < npacks; p++) {
        out_offsets[p] = k;
        const uint64_t* pb = bases[p];
        const int32_t* pc = counts[p];
        const uint32_t* po = offsets[p];
        int64_t nb = nblocks[p];
        for (int64_t bi = 0; bi < nb; bi++) {
            uint64_t base = pb[bi];
            const uint32_t* row = po + bi * block_size;
            int64_t c = pc[bi];
            for (int64_t j = 0; j < c; j++) out[k++] = base + row[j];
        }
    }
    out_offsets[npacks] = k;
    return k;
}

// Compressed-domain tiny-frontier intersect (ops/packed_setops.py small
// path; the scalar analog of algo/packed.go IntersectCompressedWithBin):
// for each frontier element binary-search its containing block by base,
// range-check against the block max, then binary-search the in-block
// offsets — the pack is never decoded. Writes hits to out; *touched_uids
// gets the summed count of distinct blocks probed (decode accounting).
int64_t pack_intersect_small(const uint64_t* bases, const int32_t* counts,
                             const uint32_t* offsets, int64_t block_size,
                             int64_t nblocks, const uint64_t* maxes,
                             const uint64_t* a, int64_t na, uint64_t* out,
                             int64_t* touched_uids) {
    int64_t k = 0, touched = 0, last_blk = -1;
    for (int64_t i = 0; i < na; i++) {
        uint64_t x = a[i];
        // last block with base <= x
        int64_t lo = 0, hi = nblocks;
        while (lo < hi) {
            int64_t mid = lo + ((hi - lo) >> 1);
            if (bases[mid] <= x) lo = mid + 1; else hi = mid;
        }
        int64_t bi = lo - 1;
        if (bi < 0 || x > maxes[bi]) continue;
        if (bi != last_blk) { touched += counts[bi]; last_blk = bi; }
        uint32_t off = (uint32_t)(x - bases[bi]);
        const uint32_t* row = offsets + bi * block_size;
        int64_t c = counts[bi], l = 0, h = c;
        while (l < h) {
            int64_t mid = l + ((h - l) >> 1);
            if (row[mid] < off) l = mid + 1; else h = mid;
        }
        if (l < c && row[l] == off) out[k++] = x;
    }
    *touched_uids = touched;
    return k;
}

// ---------------------------------------------------------------------------
// Adaptive set-representation engine (bitmap/packed hybrid containers).
//
// Blocks come in two container forms: sorted uint32 offsets (the encode
// default) and, for dense blocks, a fixed-size bitset over the block's
// u64 base (codec/uidpack.py block_bitmaps, Roaring-style per arxiv
// 1907.01032). The pair kernels below pick per BLOCK PAIR among
//   bitmap ^ bitmap    word-wise AND/ANDNOT + popcount extraction
//   bitmap x packed    probe the bitset while streaming the packed block
//   packed x packed    galloping/linear merge straight off the offsets
// so neither operand ever materializes to a flat u64 array (the "SIMD
// Compression and the Intersection of Sorted Integers" shape, arxiv
// 1401.6399). Word loops are written for the auto-vectorizer
// (-march=native: AVX2/NEON AND + popcount); scalar is the fallback.
// ---------------------------------------------------------------------------

// first index in row[0..n) with row[i] >= x, galloping from lo
static int64_t gallop32(const uint32_t* row, int64_t n, int64_t lo,
                        uint32_t x) {
    int64_t step = 1, hi = lo + 1;
    if (lo < n && row[lo] >= x) return lo;
    while (hi < n && row[hi] < x) {
        lo = hi;
        hi += step;
        step <<= 1;
    }
    if (hi > n) hi = n;
    while (lo < hi) {
        int64_t mid = lo + ((hi - lo) >> 1);
        if (row[mid] < x) lo = mid + 1; else hi = mid;
    }
    return lo;
}

// 64 bits of bitset `bm` (nwords words) starting at bit `bitoff`
static inline uint64_t bm_window(const uint64_t* bm, int64_t nwords,
                                 int64_t bitoff) {
    int64_t w = bitoff >> 6;
    int r = (int)(bitoff & 63);
    if (w >= nwords) return 0;
    uint64_t lo = bm[w] >> r;
    if (r && w + 1 < nwords) lo |= bm[w + 1] << (64 - r);
    return lo;
}

// Scatter eligible blocks' offsets into the COMPACT (n_eligible,
// bm_bits/64) bitset matrix. `rows[bi]` is block bi's row in out_words,
// or -1 for offsets-only blocks (eligibility is decided in ONE place,
// codec/uidpack.bitmap_eligible — the C++ side only scatters); out_words
// must be zeroed by the caller.
void pack_build_bitmaps(const int32_t* counts, const uint32_t* offsets,
                        int64_t block_size, int64_t nblocks,
                        const int32_t* rows, int64_t bm_bits,
                        uint64_t* out_words) {
    int64_t nw = bm_bits >> 6;
    for (int64_t bi = 0; bi < nblocks; bi++) {
        if (rows[bi] < 0) continue;
        uint64_t* w = out_words + (int64_t)rows[bi] * nw;
        const uint32_t* row = offsets + bi * block_size;
        int64_t c = counts[bi];
        for (int64_t j = 0; j < c; j++)
            w[row[j] >> 6] |= 1ull << (row[j] & 63);
    }
}

// kernel_counts layout shared by the engine entry points:
//   [0] bitmap^bitmap block pairs   [1] bitmap-probe block pairs
//   [2] packed-merge block pairs    [3] uids streamed compressed-domain
enum { KC_BITMAP = 0, KC_PROBE = 1, KC_GALLOP = 2, KC_STREAMED = 3 };

// Adaptive pack x pack set op entirely in the compressed domain.
// op: 0 = intersect, 1 = difference (a \ b). Walks the two block-range
// lists with a two-pointer skip (whole blocks outside the other operand's
// ranges are never touched — the packed-skip arm), and runs the cheapest
// kernel on each overlapping pair's window [max(bases), min(maxes)]
// (windows of consecutive pairs are disjoint, so each result uid is
// emitted exactly once, in order). Returns uids written to out.
int64_t pack_pair_setop(
    int op,
    const uint64_t* a_bases, const int32_t* a_counts,
    const uint32_t* a_offsets, int64_t a_block_size, int64_t a_nblocks,
    const uint64_t* a_maxes, const uint64_t* a_bm, const int32_t* a_bm_rows,
    const uint64_t* b_bases, const int32_t* b_counts,
    const uint32_t* b_offsets, int64_t b_block_size, int64_t b_nblocks,
    const uint64_t* b_maxes, const uint64_t* b_bm, const int32_t* b_bm_rows,
    int64_t bm_bits, uint64_t* out, int64_t* kernel_counts) {
    int64_t nw = bm_bits >> 6;
    int64_t ai = 0, bi = 0, k = 0;
    int64_t last_a = -1, last_b = -1;
    int64_t ia_cur = 0;  // difference: next unemitted offset of block ai
    // monotone in-block search hints: windows over one block ascend, so
    // a later window's lower_bound can start where the previous ended
    // (turns a block spanning many peer blocks into one amortized scan)
    int64_t ja_hint = 0, jb_hint = 0;
    while (ai < a_nblocks && bi < b_nblocks) {
        if (a_maxes[ai] < b_bases[bi]) {
            // a block wholly below every remaining b block
            if (op == 1) {
                const uint32_t* row = a_offsets + ai * a_block_size;
                for (int64_t j = ia_cur; j < a_counts[ai]; j++)
                    out[k++] = a_bases[ai] + row[j];
            }
            ai++; ia_cur = 0; ja_hint = 0;
            continue;
        }
        if (b_maxes[bi] < a_bases[ai]) { bi++; jb_hint = 0; continue; }
        uint64_t lo = a_bases[ai] > b_bases[bi] ? a_bases[ai] : b_bases[bi];
        uint64_t hi = a_maxes[ai] < b_maxes[bi] ? a_maxes[ai] : b_maxes[bi];
        if (ai != last_a) {
            kernel_counts[KC_STREAMED] += a_counts[ai]; last_a = ai;
        }
        if (bi != last_b) {
            kernel_counts[KC_STREAMED] += b_counts[bi]; last_b = bi;
        }
        const uint32_t* arow = a_offsets + ai * a_block_size;
        const uint32_t* brow = b_offsets + bi * b_block_size;
        int64_t ac = a_counts[ai], bc = b_counts[bi];
        uint32_t alo = (uint32_t)(lo - a_bases[ai]);
        uint32_t ahi = (uint32_t)(hi - a_bases[ai]);
        if (op == 1) {
            // flush a elements below the window (no b block can hold them)
            while (ia_cur < ac && arow[ia_cur] < alo)
                out[k++] = a_bases[ai] + arow[ia_cur++];
        }
        int abm = a_bm_rows && a_bm_rows[ai] >= 0;
        int bbm = b_bm_rows && b_bm_rows[bi] >= 0;
        if (abm && bbm) {
            // bitmap ^ bitmap: word-wise AND / ANDNOT over the window
            kernel_counts[KC_BITMAP]++;
            int64_t span = (int64_t)(hi - lo) + 1;
            const uint64_t* aw = a_bm + (int64_t)a_bm_rows[ai] * nw;
            const uint64_t* bw = b_bm + (int64_t)b_bm_rows[bi] * nw;
            int64_t aoff = (int64_t)(lo - a_bases[ai]);
            int64_t boff = (int64_t)(lo - b_bases[bi]);
            for (int64_t p = 0; p < span; p += 64) {
                uint64_t wa = bm_window(aw, nw, aoff + p);
                uint64_t wb = bm_window(bw, nw, boff + p);
                uint64_t w = op == 0 ? (wa & wb) : (wa & ~wb);
                if (span - p < 64) w &= (1ull << (span - p)) - 1;
                while (w) {
                    out[k++] = lo + p + __builtin_ctzll(w);
                    w &= w - 1;
                }
            }
            if (op == 1) {
                while (ia_cur < ac && arow[ia_cur] <= ahi) ia_cur++;
            }
        } else if (bbm || (op == 0 && abm)) {
            // bitmap x packed: stream the packed side's offsets through
            // the window, probe the bitset (O(1) per element). For
            // difference only b-as-bitmap streams this way (a's elements
            // must drive the output order).
            kernel_counts[KC_PROBE]++;
            if (op == 0 && !bbm) {
                // a is the bitmap: stream b's offsets, probe a's bits
                const uint64_t* aw = a_bm + (int64_t)a_bm_rows[ai] * nw;
                int64_t j = gallop32(brow, bc, jb_hint,
                                     (uint32_t)(lo - b_bases[bi]));
                uint32_t bhi = (uint32_t)(hi - b_bases[bi]);
                for (; j < bc && brow[j] <= bhi; j++) {
                    uint64_t off = b_bases[bi] + brow[j] - a_bases[ai];
                    if ((aw[off >> 6] >> (off & 63)) & 1)
                        out[k++] = b_bases[bi] + brow[j];
                }
                jb_hint = j;
            } else {
                const uint64_t* bw = b_bm + (int64_t)b_bm_rows[bi] * nw;
                int64_t j = op == 1 ? ia_cur
                                    : gallop32(arow, ac, ja_hint, alo);
                for (; j < ac && arow[j] <= ahi; j++) {
                    uint64_t off = a_bases[ai] + arow[j] - b_bases[bi];
                    int hit = (bw[off >> 6] >> (off & 63)) & 1;
                    if (hit == (op == 0)) out[k++] = a_bases[ai] + arow[j];
                }
                ja_hint = j;
                if (op == 1) ia_cur = j;
            }
        } else {
            // packed x packed: merge the two offset spans in the window
            // without decoding; gallop the long side when skewed
            kernel_counts[KC_GALLOP]++;
            int64_t ja = op == 1 ? ia_cur
                                 : gallop32(arow, ac, ja_hint, alo);
            int64_t jb = gallop32(brow, bc, jb_hint,
                                  (uint32_t)(lo - b_bases[bi]));
            uint32_t bhi = (uint32_t)(hi - b_bases[bi]);
            int64_t abase_rel = (int64_t)(a_bases[ai] - lo);
            int64_t bbase_rel = (int64_t)(b_bases[bi] - lo);
            while (ja < ac && jb < bc && arow[ja] <= ahi &&
                   brow[jb] <= bhi) {
                // compare in window-local space (bases differ per block)
                int64_t va = abase_rel + arow[ja];
                int64_t vb = bbase_rel + brow[jb];
                if (va < vb) {
                    if (op == 1) out[k++] = a_bases[ai] + arow[ja];
                    ja++;
                } else if (va > vb) {
                    jb++;
                    // skewed spans: gallop b forward to a's current value
                    if (jb < bc &&
                        bbase_rel + brow[jb] < abase_rel + arow[ja])
                        jb = gallop32(brow, bc, jb,
                                      (uint32_t)(va - bbase_rel));
                } else {
                    if (op == 0) out[k++] = a_bases[ai] + arow[ja];
                    ja++; jb++;
                }
            }
            if (op == 1) {
                // remaining a elements inside the window have no b peer
                while (ja < ac && arow[ja] <= ahi)
                    out[k++] = a_bases[ai] + arow[ja++];
                ia_cur = ja;
            }
            ja_hint = ja;
            jb_hint = jb;
        }
        if (a_maxes[ai] <= b_maxes[bi]) { ai++; ia_cur = 0; ja_hint = 0; }
        else { bi++; jb_hint = 0; }
    }
    if (op == 1) {
        // b exhausted (or never overlapped): the rest of a survives
        while (ai < a_nblocks) {
            const uint32_t* row = a_offsets + ai * a_block_size;
            for (int64_t j = ia_cur; j < a_counts[ai]; j++)
                out[k++] = a_bases[ai] + row[j];
            ai++; ia_cur = 0;
        }
    }
    return k;
}

// Adaptive sorted-array x pack set op: stream `a` against the pack's
// blocks with a monotone block cursor — per block, probe the bitset when
// the block carries one, else merge against the sorted offsets. The pack
// is never decoded. op: 0 = intersect, 1 = difference (a \ pack).
int64_t pack_stream_setop(
    int op, const uint64_t* a, int64_t na,
    const uint64_t* bases, const int32_t* counts, const uint32_t* offsets,
    int64_t block_size, int64_t nblocks, const uint64_t* maxes,
    const uint64_t* bm, const int32_t* bm_rows, int64_t bm_bits,
    uint64_t* out, int64_t* kernel_counts) {
    int64_t nw = bm_bits >> 6;
    int64_t ia = 0, bi = 0, k = 0;
    while (ia < na) {
        uint64_t x = a[ia];
        while (bi < nblocks && maxes[bi] < x) bi++;
        if (bi == nblocks) {
            if (op == 1) while (ia < na) out[k++] = a[ia++];
            break;
        }
        if (x < bases[bi]) {
            if (op == 1) {
                while (ia < na && a[ia] < bases[bi]) out[k++] = a[ia++];
            } else {
                // gallop a forward to the block's start
                int64_t step = 1, hi2 = ia + 1;
                while (hi2 < na && a[hi2] < bases[bi]) {
                    ia = hi2; hi2 += step; step <<= 1;
                }
                if (hi2 > na) hi2 = na;
                while (ia < hi2) {
                    int64_t mid = ia + ((hi2 - ia) >> 1);
                    if (a[mid] < bases[bi]) ia = mid + 1; else hi2 = mid;
                }
            }
            continue;
        }
        // a run of `a` lands in block bi
        kernel_counts[KC_STREAMED] += counts[bi];
        const uint32_t* row = offsets + bi * block_size;
        int64_t c = counts[bi];
        if (bm_rows && bm_rows[bi] >= 0) {
            kernel_counts[KC_PROBE]++;
            const uint64_t* w = bm + (int64_t)bm_rows[bi] * nw;
            while (ia < na && a[ia] <= maxes[bi]) {
                uint64_t off = a[ia] - bases[bi];
                int hit = (int)((w[off >> 6] >> (off & 63)) & 1);
                if (hit == (op == 0)) out[k++] = a[ia];
                ia++;
            }
        } else {
            kernel_counts[KC_GALLOP]++;
            int64_t j = 0;
            while (ia < na && a[ia] <= maxes[bi]) {
                uint32_t off = (uint32_t)(a[ia] - bases[bi]);
                j = gallop32(row, c, j, off);
                int hit = (j < c && row[j] == off);
                if (hit == (op == 0)) out[k++] = a[ia];
                ia++;
            }
        }
        bi++;
    }
    return k;
}

// ---------------------------------------------------------------------------
// Sorted u64 set algebra (ref algo/uidlist.go IntersectWith:142 adaptive
// strategies; same linear/gallop split here).
// ---------------------------------------------------------------------------

static int64_t gallop(const uint64_t* arr, int64_t n, int64_t lo, uint64_t x) {
    // first index >= x, starting the search at lo
    int64_t step = 1, hi = lo + 1;
    while (hi < n && arr[hi] < x) {
        lo = hi;
        hi += step;
        step <<= 1;
    }
    if (hi > n) hi = n;
    // binary search in (lo, hi]
    while (lo < hi) {
        int64_t mid = lo + ((hi - lo) >> 1);
        if (arr[mid] < x) lo = mid + 1; else hi = mid;
    }
    return lo;
}

int64_t intersect_u64(const uint64_t* a, int64_t na, const uint64_t* b,
                      int64_t nb, uint64_t* out) {
    if (na > nb) { const uint64_t* t = a; a = b; b = t;
                   int64_t tn = na; na = nb; nb = tn; }
    int64_t k = 0;
    if (nb <= na * 32) {  // similar sizes: linear merge
        int64_t i = 0, j = 0;
        while (i < na && j < nb) {
            if (a[i] < b[j]) i++;
            else if (a[i] > b[j]) j++;
            else { out[k++] = a[i]; i++; j++; }
        }
    } else {  // ratio large: gallop the big side (IntersectWithJump/Bin)
        int64_t j = 0;
        for (int64_t i = 0; i < na; i++) {
            j = gallop(b, nb, j, a[i]);
            if (j < nb && b[j] == a[i]) out[k++] = a[i];
            if (j >= nb) break;
        }
    }
    return k;
}

int64_t union_u64(const uint64_t* a, int64_t na, const uint64_t* b,
                  int64_t nb, uint64_t* out) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        if (a[i] < b[j]) out[k++] = a[i++];
        else if (a[i] > b[j]) out[k++] = b[j++];
        else { out[k++] = a[i]; i++; j++; }
    }
    while (i < na) out[k++] = a[i++];
    while (j < nb) out[k++] = b[j++];
    return k;
}

int64_t difference_u64(const uint64_t* a, int64_t na, const uint64_t* b,
                       int64_t nb, uint64_t* out) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        if (a[i] < b[j]) out[k++] = a[i++];
        else if (a[i] > b[j]) j++;
        else { i++; j++; }
    }
    while (i < na) out[k++] = a[i++];
    return k;
}

// k-way merge via repeated 2-way (callers pass scratch; ref MergeSorted)
int64_t merge_sorted_u64(const uint64_t* flat, const int64_t* lens,
                         int64_t nlists, uint64_t* out, uint64_t* scratch) {
    int64_t acc = 0;  // current size in out
    int64_t off = 0;
    for (int64_t l = 0; l < nlists; l++) {
        int64_t n = lens[l];
        int64_t merged = union_u64(out, acc, flat + off, n, scratch);
        memcpy(out, scratch, merged * sizeof(uint64_t));
        acc = merged;
        off += n;
    }
    return acc;
}

// ---------------------------------------------------------------------------
// Quantized vector scoring (models/vector.py quantized engine).
//
// Corpus rows are stored as per-row asymmetric int8: v_ij ~= s_i*c_ij + o_i
// (scale/offset sidecars, plus the EXACT float32 sqnorm of the original
// row). The query is quantized once per call the same way
// (q_j ~= sq*qc_j + oq), so the reconstructed dot product is
//
//   dot(v_i, q) ~= sq*(s_i*dot8(c_i,qc) + o_i*qcsum) + oq*(s_i*csum_i + d*o_i)
//
// where dot8 is the int8 x int8 -> int32 inner product (the only O(d)
// term — it auto-vectorizes to the wide integer-multiply-add forms under
// -march=native, and a row costs 1 byte/component of memory traffic
// instead of the float path's 4). csum_i / qcsum are precomputed code
// sums. Distances reconstructed per metric (0 = squared euclidean,
// 1 = cosine, 2 = negated dot) use the exact sqnorm sidecar, so only
// the dot term carries quantization error — the caller reranks the
// surviving pool in float32 (models/vector.py) to recover exact order.
//
// Both kernels fuse a partial top-k: a per-query max-heap of size k
// (worst kept at the root) lives directly in the caller's output slabs,
// and is heap-sorted ascending before return. Ties break toward the
// LOWER row index — deterministic output for duplicate vectors, which
// the solo-vs-coalesced byte-identity contract relies on.
// ---------------------------------------------------------------------------

// "worse" ordering for the heaps: larger distance, then larger index
static inline int vq_worse(float da, int64_t ia, float db, int64_t ib) {
    return da > db || (da == db && ia > ib);
}

// replace the root with (dv, iv) and sift down over [0, len)
static void vq_sift(float* hd, int64_t* hi, int64_t len, float dv,
                    int64_t iv) {
    int64_t p = 0;
    for (;;) {
        int64_t c = 2 * p + 1;
        if (c >= len) break;
        if (c + 1 < len && vq_worse(hd[c + 1], hi[c + 1], hd[c], hi[c]))
            c++;
        if (!vq_worse(hd[c], hi[c], dv, iv)) break;
        hd[p] = hd[c];
        hi[p] = hi[c];
        p = c;
    }
    hd[p] = dv;
    hi[p] = iv;
}

// heap-sort the k slots ascending (dist, then index); empty slots
// (+inf, -1) end up trailing
static void vq_heapsort(float* hd, int64_t* hi, int64_t k) {
    for (int64_t end = k - 1; end > 0; end--) {
        float dv = hd[end];
        int64_t iv = hi[end];
        hd[end] = hd[0];
        hi[end] = hi[0];
        vq_sift(hd, hi, end, dv, iv);
    }
}

// int8 x int8 -> int32 inner product between the query codes `q` and a
// corpus row `c` whose code sum is `csum_c`. All paths produce the SAME
// integer result (products and sums are exact), so kernel output does
// not depend on which SIMD tier the build machine has.
//
// The VNNI path uses vpdpbusd, which wants unsigned x signed: the query
// side is biased to unsigned on the fly (q + 128 == q ^ 0x80 on int8)
// and the bias is removed with the row's precomputed code sum:
// sum((q+128)*c) - 128*sum(c) == sum(q*c).
static inline int32_t vq_dot8(const int8_t* q, const int8_t* c, int64_t d,
                              int32_t csum_c) {
#if defined(__AVX512VNNI__)
    __m512i acc = _mm512_setzero_si512();
    const __m512i bias = _mm512_set1_epi8((char)0x80);
    int64_t j = 0;
    for (; j + 64 <= d; j += 64) {
        __m512i vq = _mm512_xor_si512(
            _mm512_loadu_si512((const void*)(q + j)), bias);
        __m512i vc = _mm512_loadu_si512((const void*)(c + j));
        acc = _mm512_dpbusd_epi32(acc, vq, vc);
    }
    int32_t r = _mm512_reduce_add_epi32(acc);
    // tail stays in biased space so one correction covers everything
    for (; j < d; j++)
        r += ((int32_t)q[j] + 128) * (int32_t)c[j];
    return r - 128 * csum_c;
#elif defined(__AVX512BW__)
    (void)csum_c;
    __m512i acc = _mm512_setzero_si512();
    int64_t j = 0;
    for (; j + 32 <= d; j += 32) {
        __m512i vq = _mm512_cvtepi8_epi16(
            _mm256_loadu_si256((const __m256i*)(q + j)));
        __m512i vc = _mm512_cvtepi8_epi16(
            _mm256_loadu_si256((const __m256i*)(c + j)));
        acc = _mm512_add_epi32(acc, _mm512_madd_epi16(vq, vc));
    }
    int32_t r = _mm512_reduce_add_epi32(acc);
    for (; j < d; j++) r += (int32_t)q[j] * (int32_t)c[j];
    return r;
#elif defined(__AVX2__)
    (void)csum_c;
    __m256i acc = _mm256_setzero_si256();
    int64_t j = 0;
    for (; j + 16 <= d; j += 16) {
        __m256i vq = _mm256_cvtepi8_epi16(
            _mm_loadu_si128((const __m128i*)(q + j)));
        __m256i vc = _mm256_cvtepi8_epi16(
            _mm_loadu_si128((const __m128i*)(c + j)));
        acc = _mm256_add_epi32(acc, _mm256_madd_epi16(vq, vc));
    }
    __m128i lo = _mm256_castsi256_si128(acc);
    __m128i hi = _mm256_extracti128_si256(acc, 1);
    __m128i s = _mm_add_epi32(lo, hi);
    s = _mm_add_epi32(s, _mm_srli_si128(s, 8));
    s = _mm_add_epi32(s, _mm_srli_si128(s, 4));
    int32_t r = _mm_cvtsi128_si32(s);
    for (; j < d; j++) r += (int32_t)q[j] * (int32_t)c[j];
    return r;
#else
    (void)csum_c;
    int32_t acc = 0;
    for (int64_t j = 0; j < d; j++)
        acc += (int32_t)q[j] * (int32_t)c[j];
    return acc;
#endif
}

static inline float vq_dist(int metric, float dot, float sqn, float vn,
                            float qstat) {
    if (metric == 0) return sqn - 2.0f * dot + qstat;  // squared euclidean
    if (metric == 1) {                                 // cosine
        float denom = vn * qstat;                      // qstat = |q|
        if (denom < 1e-12f) denom = 1e-12f;
        return 1.0f - dot / denom;
    }
    return -dot;                                       // dotproduct
}

// Batched full-corpus scan: nq queries against n rows in ONE pass (the
// corpus is read once per batch — the 768-byte row stays in L1 across
// the query loop). valid[i] == 0 skips tombstoned rows. Per query q,
// out_idx/out_dist rows q*k..q*k+k hold the top-k ascending; unused
// slots are (-1, +inf). qstats[q] is the exact q.q (euclidean) or |q|
// (cosine). Returns the number of valid rows scanned.
int64_t vec_qi8_topk(
    const int8_t* codes, int64_t n, int64_t d,
    const float* scales, const float* offsets, const int32_t* csums,
    const float* sqnorms, const uint8_t* valid,
    const int8_t* qcodes, const float* qscales, const float* qoffsets,
    const int32_t* qcsums, const float* qstats,
    int64_t nq, int metric, int64_t k,
    int64_t* out_idx, float* out_dist) {
    const float inf = __builtin_inff();
    for (int64_t t = 0; t < nq * k; t++) {
        out_idx[t] = -1;
        out_dist[t] = inf;
    }
    int64_t nvalid = 0;
    for (int64_t i = 0; i < n; i++) {
        if (valid && !valid[i]) continue;
        nvalid++;
        const int8_t* row = codes + i * d;
        float s = scales[i];
        float o = offsets[i];
        int32_t cs_i = csums[i];
        float cs = (float)cs_i;
        float sqn = sqnorms[i];
        float vn = metric == 1 ? __builtin_sqrtf(sqn) : 0.0f;
        for (int64_t q = 0; q < nq; q++) {
            int32_t d8 = vq_dot8(qcodes + q * d, row, d, cs_i);
            float dot = qscales[q] * (s * (float)d8 + o * (float)qcsums[q])
                      + qoffsets[q] * (s * cs + (float)d * o);
            float dist = vq_dist(metric, dot, sqn, vn, qstats[q]);
            float* hd = out_dist + q * k;
            int64_t* hi = out_idx + q * k;
            if (vq_worse(hd[0], hi[0], dist, i))
                vq_sift(hd, hi, k, dist, i);
        }
    }
    for (int64_t q = 0; q < nq; q++)
        vq_heapsort(out_dist + q * k, out_idx + q * k, k);
    return nvalid;
}

// Candidate-list scan (the IVF probe): one query against an explicit
// row-id list (the probed cells' concatenated ids). Same scoring,
// heap, and tie-break as the full scan. Returns entries written
// (min(k, valid candidates)).
int64_t vec_qi8_topk_idx(
    const int8_t* codes, int64_t d,
    const float* scales, const float* offsets, const int32_t* csums,
    const float* sqnorms, const uint8_t* valid,
    const int32_t* rows, int64_t nrows,
    const int8_t* qc, float qscale, float qoffset, int32_t qcsum,
    float qstat, int metric, int64_t k,
    int64_t* out_idx, float* out_dist) {
    const float inf = __builtin_inff();
    for (int64_t t = 0; t < k; t++) {
        out_idx[t] = -1;
        out_dist[t] = inf;
    }
    int64_t nvalid = 0;
    for (int64_t t = 0; t < nrows; t++) {
        int64_t i = rows[t];
        // candidate rows are scattered through the code matrix (the
        // scan is DRAM-latency-bound at ~3 GB/s without this); pull the
        // row a few candidates ahead into L2 while scoring this one
        if (t + 12 < nrows) {
            const int8_t* pr = codes + (int64_t)rows[t + 12] * d;
            for (int64_t pj = 0; pj < d; pj += 64)
                __builtin_prefetch(pr + pj, 0, 1);
        }
        if (valid && !valid[i]) continue;
        nvalid++;
        const int8_t* row = codes + i * d;
        int32_t d8 = vq_dot8(qc, row, d, csums[i]);
        float s = scales[i];
        float o = offsets[i];
        float dot = qscale * (s * (float)d8 + o * (float)qcsum)
                  + qoffset * (s * (float)csums[i] + (float)d * o);
        float sqn = sqnorms[i];
        float vn = metric == 1 ? __builtin_sqrtf(sqn) : 0.0f;
        float dist = vq_dist(metric, dot, sqn, vn, qstat);
        if (vq_worse(out_dist[0], out_idx[0], dist, i))
            vq_sift(out_dist, out_idx, k, dist, i);
    }
    vq_heapsort(out_dist, out_idx, k);
    return nvalid < k ? nvalid : k;
}

}  // extern "C"

// Run fn(t) on nt threads (nt==1 stays inline — no spawn cost on the
// small-corpus paths and under sanitizers that dislike short threads).
template <typename F>
static void vq_parallel(int64_t nt, F fn) {
    if (nt <= 1) {
        fn(0);
        return;
    }
    std::vector<std::thread> ths;
    ths.reserve((size_t)(nt - 1));
    for (int64_t t = 1; t < nt; t++) ths.emplace_back(fn, t);
    fn(0);
    for (auto& th : ths) th.join();
}

extern "C" {

// Batched candidate-list scan: nq queries, each against its OWN slice
// rows[begs[q]..ends[q]) of a shared candidate-id array (the probed IVF
// cells in CSR form; slices may alias — the top-2 cell assignment path
// points many queries at one shared per-group centroid list). Scoring,
// heap, and (dist, row) tie-break identical to vec_qi8_topk_idx, so a
// batch row is byte-identical to the solo call — the coalescing
// contract. Threaded over queries (each query's heap lives in its own
// out slab — no sharing); returns total valid candidates scored.
int64_t vec_qi8_topk_lists(
    const int8_t* codes, int64_t d,
    const float* scales, const float* offsets, const int32_t* csums,
    const float* sqnorms, const uint8_t* valid,
    const int32_t* rows, const int64_t* begs, const int64_t* ends,
    const int8_t* qcodes, const float* qscales, const float* qoffsets,
    const int32_t* qcsums, const float* qstats,
    int64_t nq, int metric, int64_t k, int64_t nthreads,
    int64_t* out_idx, float* out_dist) {
    const float inf = __builtin_inff();
    int64_t nt = nthreads < 1 ? 1 : nthreads;
    if (nt > nq) nt = nq < 1 ? 1 : nq;
    if (nt > 64) nt = 64;
    std::vector<int64_t> scanned((size_t)nt, 0);
    vq_parallel(nt, [&](int64_t t) {
        int64_t lo = nq * t / nt, hi = nq * (t + 1) / nt;
        int64_t nvalid = 0;
        for (int64_t q = lo; q < hi; q++) {
            float* hd = out_dist + q * k;
            int64_t* hi_ = out_idx + q * k;
            for (int64_t s = 0; s < k; s++) {
                hi_[s] = -1;
                hd[s] = inf;
            }
            const int8_t* qc = qcodes + q * d;
            float qscale = qscales[q], qoffset = qoffsets[q];
            float qcsum = (float)qcsums[q], qstat = qstats[q];
            for (int64_t s = begs[q]; s < ends[q]; s++) {
                int64_t i = rows[s];
                // same scattered-row prefetch as vec_qi8_topk_idx
                if (s + 12 < ends[q]) {
                    const int8_t* pr = codes + (int64_t)rows[s + 12] * d;
                    for (int64_t pj = 0; pj < d; pj += 64)
                        __builtin_prefetch(pr + pj, 0, 1);
                }
                if (valid && !valid[i]) continue;
                nvalid++;
                int32_t d8 = vq_dot8(qc, codes + i * d, d, csums[i]);
                float sc = scales[i], o = offsets[i];
                float dot = qscale * (sc * (float)d8 + o * qcsum)
                          + qoffset * (sc * (float)csums[i] + (float)d * o);
                float sqn = sqnorms[i];
                float vn = metric == 1 ? __builtin_sqrtf(sqn) : 0.0f;
                float dist = vq_dist(metric, dot, sqn, vn, qstat);
                if (vq_worse(hd[0], hi_[0], dist, i))
                    vq_sift(hd, hi_, k, dist, i);
            }
            vq_heapsort(hd, hi_, k);
        }
        scanned[(size_t)t] = nvalid;
    });
    int64_t total = 0;
    for (int64_t t = 0; t < nt; t++) total += scanned[(size_t)t];
    return total;
}

// Row quantizer for the int8 sidecar store: per-row asymmetric
// v ~= scale*code + offset with codes in [-127, 127], plus the code sum
// and exact float32 squared norm. Bit-identical codes/scales/offsets/
// csums to the numpy mirror in models/vector.py _quantize (same f32 op
// order; rintf under the default round-to-nearest-even mode matches
// np.rint); sqnorms may differ in final ulps (sequential vs pairwise
// accumulation) — consumers rerank in float32, so ordering is immune.
// Threaded over row ranges; returns n.
int64_t vec_qi8_quantize(
    const float* V, int64_t n, int64_t d, int64_t nthreads,
    int8_t* codes, float* scales, float* offsets, int32_t* csums,
    float* sqnorms) {
    int64_t nt = nthreads < 1 ? 1 : nthreads;
    if (nt > n) nt = n < 1 ? 1 : n;
    if (nt > 64) nt = 64;
    vq_parallel(nt, [&](int64_t t) {
        int64_t lo = n * t / nt, hi = n * (t + 1) / nt;
        for (int64_t i = lo; i < hi; i++) {
            const float* row = V + i * d;
            float mn = row[0], mx = row[0];
            float sq = 0.0f;
            for (int64_t j = 0; j < d; j++) {
                float v = row[j];
                if (v < mn) mn = v;
                if (v > mx) mx = v;
                sq += v * v;
            }
            float o = (mx + mn) * 0.5f;
            float s = (mx - mn) / 254.0f;
            if (s < 1e-20f) s = 1e-20f;
            int8_t* crow = codes + i * d;
            int32_t cs = 0;
            for (int64_t j = 0; j < d; j++) {
                float c = rintf((row[j] - o) / s);
                if (c < -127.0f) c = -127.0f;
                if (c > 127.0f) c = 127.0f;
                int32_t ci = (int32_t)c;
                crow[j] = (int8_t)ci;
                cs += ci;
            }
            scales[i] = s;
            offsets[i] = o;
            csums[i] = cs;
            sqnorms[i] = sq;
        }
    });
    return n;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// SSTable entry scans (storage/lsm.py plaintext format):
//   [u32 klen][u64 ts][u64 seq][u32 vlen][key bytes][val bytes]
// The Python per-entry struct unpacking dominated LSM reads; these scan
// in native code and hand back offsets for zero-copy value slicing.
// ---------------------------------------------------------------------------

extern "C" {

static inline int64_t ent_read(const uint8_t* buf, int64_t pos,
                               uint32_t* klen, uint64_t* ts, uint64_t* seq,
                               uint32_t* vlen) {
    memcpy(klen, buf + pos, 4);
    memcpy(ts, buf + pos + 4, 8);
    memcpy(seq, buf + pos + 12, 8);
    memcpy(vlen, buf + pos + 20, 4);
    return pos + 24;
}

static inline int keycmp(const uint8_t* a, int64_t na, const uint8_t* b,
                         int64_t nb) {
    int64_t n = na < nb ? na : nb;
    int c = memcmp(a, b, (size_t)n);
    if (c != 0) return c;
    return na < nb ? -1 : (na > nb ? 1 : 0);
}

// First entry offset with entry_key >= key, scanning from `off`.
int64_t sst_seek(const uint8_t* buf, int64_t end, int64_t off,
                 const uint8_t* key, int64_t klen) {
    int64_t pos = off;
    while (pos + 24 <= end) {
        uint32_t kl, vl; uint64_t ts, seq;
        int64_t body = ent_read(buf, pos, &kl, &ts, &seq, &vl);
        if (keycmp(buf + body, kl, key, klen) >= 0) return pos;
        pos = body + kl + vl;
    }
    return end;
}

// Versions of exactly `key` from `off` (which must be at/before the first
// match): writes (ts, seq, val_off, val_len) per version; returns count.
int64_t sst_versions(const uint8_t* buf, int64_t end, int64_t off,
                     const uint8_t* key, int64_t klen, int64_t max_out,
                     uint64_t* tss, uint64_t* seqs, int64_t* val_offs,
                     int64_t* val_lens) {
    int64_t pos = sst_seek(buf, end, off, key, klen);
    int64_t n = 0;
    while (pos + 24 <= end && n < max_out) {
        uint32_t kl, vl; uint64_t ts, seq;
        int64_t body = ent_read(buf, pos, &kl, &ts, &seq, &vl);
        if (keycmp(buf + body, kl, key, klen) != 0) break;
        tss[n] = ts;
        seqs[n] = seq;
        val_offs[n] = body + kl;
        val_lens[n] = vl;
        n++;
        pos = body + kl + vl;
    }
    return n;
}

// Versions of MANY sorted distinct keys in one pass, the table's own
// pruning included, so that no Python runs per key before the call
// (storage/lsm.py _SSTable.versions_of_many): a key outside [first index
// key, max key] or refused by the bloom filter gets count 0 untouched;
// the others seek from the sparse index's stride head (the last index
// key strictly below the key: a key's versions may begin in the stride
// before the head that equals it). Since keys ascend, the walk position
// is monotone.
//
// `table` is what does not change for a table, ten words the wrapper
// builds once: the mapped file's address and its data end; the bloom's
// bits (0: a table from before blooms) and their count; the sparse index
// as its keys laid end to end, their n_idx + 1 prefix offsets, their file
// offsets, and n_idx; the max key and its length. The probe keys are laid
// end to end in `keys_blob`, key i ending at `key_ends[i]`. `out` is one
// u64 buffer of nkeys + 4 * max_out words: counts[i] versions for key i,
// then (ts, seq, value offset, value length) per version, in key order.
// Returns total versions written, or -(needed) if max_out was too small
// (caller re-runs with a bigger buffer). Six arguments, all plain words:
// the marshalling of nineteen cost more than a small probe's work.
int64_t sst_versions_multi(const void* table, int64_t nkeys,
                           const void* keys_blob_v, const void* key_ends_v,
                           int64_t max_out, void* out_v) {
    const int64_t* tb = (const int64_t*)table;
    const uint8_t* buf = (const uint8_t*)tb[0];
    const int64_t end = tb[1];
    const uint8_t* bloom = (const uint8_t*)tb[2];
    const int64_t bloom_nbits = tb[3];
    const uint8_t* idx_keys = (const uint8_t*)tb[4];
    const int64_t* idx_koffs = (const int64_t*)tb[5];
    const int64_t* idx_foffs = (const int64_t*)tb[6];
    const int64_t n_idx = tb[7];
    const uint8_t* max_key = (const uint8_t*)tb[8];
    const int64_t max_klen = tb[9];
    const uint8_t* keys_blob = (const uint8_t*)keys_blob_v;
    const int64_t* key_ends = (const int64_t*)key_ends_v;
    uint64_t* counts = (uint64_t*)out_v;
    uint64_t* recs = counts + nkeys;
    int64_t pos = 0;
    int64_t out = 0;
    for (int64_t i = 0; i < nkeys; i++) {
        int64_t koff = i ? key_ends[i - 1] : 0;
        const uint8_t* key = keys_blob + koff;
        int64_t klen = key_ends[i] - koff;
        counts[i] = 0;
        if (n_idx == 0 ||
            keycmp(key, klen, idx_keys, idx_koffs[1] - idx_koffs[0]) < 0 ||
            keycmp(key, klen, max_key, max_klen) > 0)
            continue;
        if (bloom != nullptr) {
            uint64_t h1, h2;
            sst_bloom_hashes(key, (size_t)klen, &h1, &h2);
            bool hit = true;
            for (int j = 0; j < SST_BLOOM_HASHES && hit; j++) {
                uint64_t b = sst_bloom_bit(h1, h2, j, (uint64_t)bloom_nbits);
                hit = bloom[b >> 3] & (1 << (b & 7));
            }
            if (!hit) continue;
        }
        // index keys strictly below `key`: [0, lo)
        int64_t lo = 0, hi = n_idx;
        while (lo < hi) {
            int64_t mid = (lo + hi) >> 1;
            if (keycmp(idx_keys + idx_koffs[mid],
                       idx_koffs[mid + 1] - idx_koffs[mid], key, klen) < 0)
                lo = mid + 1;
            else
                hi = mid;
        }
        int64_t start = idx_foffs[lo > 0 ? lo - 1 : 0];
        if (start > pos) pos = start;
        int64_t p = sst_seek(buf, end, pos, key, klen);
        int64_t n = 0;
        while (p + 24 <= end) {
            uint32_t kl, vl; uint64_t ts, seq;
            int64_t body = ent_read(buf, p, &kl, &ts, &seq, &vl);
            if (keycmp(buf + body, kl, key, klen) != 0) break;
            if (out + n >= max_out) return -(out + n + 1);
            uint64_t* r = recs + 4 * (out + n);
            r[0] = ts;
            r[1] = seq;
            r[2] = (uint64_t)(body + kl);
            r[3] = vl;
            n++;
            p = body + kl + vl;
        }
        counts[i] = (uint64_t)n;
        out += n;
        pos = p;
    }
    return out;
}

// Entry headers from `off` while keys start with `prefix` (or all when
// prefix_len == 0): writes (key_off, key_len, ts, seq, val_off, val_len);
// returns count (callers loop with growing max_out).
int64_t sst_scan(const uint8_t* buf, int64_t end, int64_t off,
                 const uint8_t* prefix, int64_t prefix_len, int64_t max_out,
                 int64_t* key_offs, int64_t* key_lens, uint64_t* tss,
                 uint64_t* seqs, int64_t* val_offs, int64_t* val_lens,
                 int64_t* next_pos) {
    int64_t pos = off;
    int64_t n = 0;
    while (pos + 24 <= end && n < max_out) {
        uint32_t kl, vl; uint64_t ts, seq;
        int64_t body = ent_read(buf, pos, &kl, &ts, &seq, &vl);
        if (prefix_len > 0) {
            if ((int64_t)kl < prefix_len ||
                memcmp(buf + body, prefix, (size_t)prefix_len) != 0) {
                break;
            }
        }
        key_offs[n] = body;
        key_lens[n] = kl;
        tss[n] = ts;
        seqs[n] = seq;
        val_offs[n] = body + kl;
        val_lens[n] = vl;
        n++;
        pos = body + kl + vl;
    }
    *next_pos = pos;
    return n;
}

// ---------------------------------------------------------------------------
// Streaming arena result encoder (query/streamjson.py): emit the bulk JSON
// row shapes — hex-uid entity arrays and count-object arrays — straight from
// the ragged level buffers into the caller's byte buffer, one call per
// contiguous run instead of one Python object per row. `pre`/`post` carry
// the constant object framing (e.g. {"uid":"0x ... "}), so one kernel
// serves every key/alias. Output formats are pinned to Python's: lowercase
// unpadded hex (hex(u) minus the 0x that rides in `pre`) and decimal int64
// (str(n)) — the byte-identity contract with json.dumps of the dict
// encoder's output lives or dies on these two formats.
// ---------------------------------------------------------------------------

static inline int64_t put_u64_hex(uint64_t v, uint8_t* out) {
    // lowercase, no leading zeros; "0" for 0 (python hex() semantics)
    static const char digits[] = "0123456789abcdef";
    if (v == 0) {
        out[0] = '0';
        return 1;
    }
    uint8_t tmp[16];
    int n = 0;
    while (v) {
        tmp[n++] = (uint8_t)digits[v & 0xF];
        v >>= 4;
    }
    for (int i = 0; i < n; i++) out[i] = tmp[n - 1 - i];
    return n;
}

static inline int64_t put_i64_dec(int64_t v, uint8_t* out) {
    uint8_t tmp[20];
    int n = 0;
    uint64_t u;
    uint8_t* p = out;
    if (v < 0) {
        *p++ = '-';
        u = (uint64_t)(-(v + 1)) + 1;  // INT64_MIN-safe negation
    } else {
        u = (uint64_t)v;
    }
    if (u == 0) tmp[n++] = '0';
    while (u) {
        tmp[n++] = (uint8_t)('0' + (u % 10));
        u /= 10;
    }
    for (int i = 0; i < n; i++) p[i] = tmp[n - 1 - i];
    return (p - out) + n;
}

// `{"uid":"0x1"},{"uid":"0x2"},...` — comma-separated, no enclosing
// brackets (the caller owns list framing). Caller sizes `out` at
// n * (pre_len + post_len + 17) bytes. Returns bytes written.
int64_t enc_uid_objs(const uint64_t* uids, int64_t n, const uint8_t* pre,
                     int64_t pre_len, const uint8_t* post, int64_t post_len,
                     uint8_t* out) {
    uint8_t* p = out;
    for (int64_t i = 0; i < n; i++) {
        if (i) *p++ = ',';
        if (pre_len) {
            memcpy(p, pre, (size_t)pre_len);
            p += pre_len;
        }
        p += put_u64_hex(uids[i], p);
        if (post_len) {
            memcpy(p, post, (size_t)post_len);
            p += post_len;
        }
    }
    return p - out;
}

// `{"c":5},{"c":3},...` — the count-leaf analog. Caller sizes `out` at
// n * (pre_len + post_len + 21) bytes. Returns bytes written.
int64_t enc_int_objs(const int64_t* vals, int64_t n, const uint8_t* pre,
                     int64_t pre_len, const uint8_t* post, int64_t post_len,
                     uint8_t* out) {
    uint8_t* p = out;
    for (int64_t i = 0; i < n; i++) {
        if (i) *p++ = ',';
        if (pre_len) {
            memcpy(p, pre, (size_t)pre_len);
            p += pre_len;
        }
        p += put_i64_dec(vals[i], p);
        if (post_len) {
            memcpy(p, post, (size_t)post_len);
            p += post_len;
        }
    }
    return p - out;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Mutation write-path kernels (posting/pl.py encode_deltas +
// tok/tok.py TermTokenizer bulk path): the live write path applied
// per-edge Python work for every posting — these move the two hottest
// loops (delta-record serialization, term tokenization) into one
// native call per transaction batch.
// ---------------------------------------------------------------------------

extern "C" {

// Batched posting-delta record encode for the fast scalar/uid shapes
// (no lang, no facets). Wire layout is posting/pl.py's, byte-exact:
//   per key:     kind u8 (=1 KIND_DELTA) | count u32 LE | postings...
//   per posting: flags u8 | uid u64 LE | value_type u8 |
//                lang_len u8 (=0) | vlen u32 LE | value bytes |
//                nfacets u16 (=0)
// Inputs are flat over all keys' postings in order; `vblob` holds the
// value bytes of value postings concatenated (vlens[j]==0 for pure uid
// edges). `out_offs` (n_keys+1) receives each key's record span in
// `out`; caller sizes `out` exactly (5 per key + 17 + vlen per
// posting). Returns total bytes written. Little-endian host assumed,
// like the bit-pack codec above.
int64_t enc_delta_records(
    const int64_t* counts, int64_t n_keys,
    const uint8_t* flags, const uint64_t* uids, const uint8_t* tids,
    const int64_t* vlens, const uint8_t* vblob,
    uint8_t* out, int64_t* out_offs) {
    uint8_t* p = out;
    int64_t j = 0;     // flat posting cursor
    int64_t voff = 0;  // value-blob cursor
    for (int64_t k = 0; k < n_keys; k++) {
        out_offs[k] = p - out;
        *p++ = 1;  // KIND_DELTA
        uint32_t cnt = (uint32_t)counts[k];
        memcpy(p, &cnt, 4);
        p += 4;
        for (int64_t c = 0; c < counts[k]; c++, j++) {
            *p++ = flags[j];
            uint64_t u = uids[j];
            memcpy(p, &u, 8);
            p += 8;
            *p++ = tids[j];
            *p++ = 0;  // lang_len
            uint32_t vl = (uint32_t)vlens[j];
            memcpy(p, &vl, 4);
            p += 4;
            if (vl) {
                memcpy(p, vblob + voff, vl);
                voff += vl;
                p += vl;
            }
            *p++ = 0;
            *p++ = 0;  // nfacets u16
        }
    }
    out_offs[n_keys] = p - out;
    return p - out;
}

// Bulk ASCII term tokenization (tok/tok.py TermTokenizer fast path):
// for each input string — caller guarantees pure ASCII; non-ASCII
// values take the Python unicode pipeline — lowercase, split into
// maximal [a-z0-9_'] runs (the `\w'` class over ASCII), dedupe,
// byte-sort, and emit each token as `prefix` byte + chars: exactly
// sorted({w for w in _word_re.findall(s.lower())}) with the
// tokenizer's identifier prefix applied. CSR output: token t spans
// out[tok_offs[t] : tok_offs[t+1]], input i owns tok_counts[i]
// consecutive tokens. Caller capacities: out >= total input bytes +
// one prefix byte per possible token; tok_offs >= 1 + sum over inputs
// of (len/2 + 1). Returns total token count.
// -------------------------------------------------------------------
// Columnar batch apply: one call turns a whole group-commit batch's
// collected edge columns into ready-to-put (key, delta-record) pairs —
// fusing data/index/reverse key construction, exact/int/bool/term
// tokenization, and posting-delta record encoding (the loops
// enc_delta_records + tok_terms_ascii each did alone, plus the Python
// key/posting assembly between them). Edge columns are flat over all
// members; member m owns edges [m_offs[m], m_offs[m+1]).
//
// Per-predicate plan (pred_ids[j] indexes it): key prefix bytes
// (x/keys.py PredicatePrefix — tag + len + ns + attr, NO kind byte; the
// kernel appends kind + suffix), pflags bits (1=reverse 2=exact 4=int
// 8=bool 16=term, mirrored in posting/colwrite.py), pidents = 4 bytes
// per pred: the exact/int/bool/term tokenizer identifier bytes.
//
// Shapes: 0 = scalar-value SET — emits the data posting
// (flags=3, uid=2^64-1, tid=vtypes[j], value=vblob slice) plus one
// index posting (flags=2, uid=entity) per plan token; 1 = list-uid SET
// — emits the data posting (flags=2, uid=objects[j]) plus the reverse
// posting (flags=2, uid=entity) under PF_REVERSE. Postings group per
// (member, key) in first-touch order, appended in edge order — the
// exact per-key append order the serial Python path produces — and
// each pair's record is pl.py encode_delta byte-exact (kind=1,
// count u32 LE, 17-byte fixed posting fields, little-endian host
// assumed like the codecs above).
//
// Outputs are CSR over pairs: key i = out_keys[out_key_offs[i]:
// out_key_offs[i+1]], record i likewise in out_recs; out_member /
// out_pred / out_kinds (0 data, 2 index, 4 reverse — x/keys.py kind
// bytes) / out_counts (postings in the record) annotate each pair.
// Caller sizes outputs from batch_apply_caps. Returns the pair count,
// or -1 if any cap would overflow (allocation bug — caps are a true
// upper bound).
// void* parameters: the Python wrapper passes raw buffer addresses
// (array.array / bytearray / bytes) — typed-pointer argtypes would
// force a ctypes cast per argument per call, which profiling showed
// dominating small-batch commits (23 pointer args on this entry).
int64_t batch_apply(
    const void* m_offs_v, int64_t n_members,
    const void* shapes_v, const void* entities_v,
    const void* pred_ids_v, const void* objects_v,
    const void* vtypes_v, const void* voffs_v, const void* vblob_v,
    const void* pp_blob_v, const void* pp_offs_v,
    const void* pflags_v, const void* pidents_v, int64_t n_preds,
    void* out_keys_v, void* out_key_offs_v,
    void* out_recs_v, void* out_rec_offs_v,
    void* out_member_v, void* out_pred_v, void* out_kinds_v,
    void* out_counts_v, int64_t max_pairs) {
    (void)n_preds;
    const int64_t* m_offs = (const int64_t*)m_offs_v;
    const uint8_t* shapes = (const uint8_t*)shapes_v;
    const uint64_t* entities = (const uint64_t*)entities_v;
    const int32_t* pred_ids = (const int32_t*)pred_ids_v;
    const uint64_t* objects = (const uint64_t*)objects_v;
    const uint8_t* vtypes = (const uint8_t*)vtypes_v;
    const int64_t* voffs = (const int64_t*)voffs_v;
    const uint8_t* vblob = (const uint8_t*)vblob_v;
    const uint8_t* pp_blob = (const uint8_t*)pp_blob_v;
    const int64_t* pp_offs = (const int64_t*)pp_offs_v;
    const uint8_t* pflags = (const uint8_t*)pflags_v;
    const uint8_t* pidents = (const uint8_t*)pidents_v;
    uint8_t* out_keys = (uint8_t*)out_keys_v;
    int64_t* out_key_offs = (int64_t*)out_key_offs_v;
    uint8_t* out_recs = (uint8_t*)out_recs_v;
    int64_t* out_rec_offs = (int64_t*)out_rec_offs_v;
    int32_t* out_member = (int32_t*)out_member_v;
    int32_t* out_pred = (int32_t*)out_pred_v;
    uint8_t* out_kinds = (uint8_t*)out_kinds_v;
    int32_t* out_counts = (int32_t*)out_counts_v;
    struct Slot {
        std::string key;
        std::string posts;  // posting bytes (record body)
        int32_t count = 0;
        int32_t pred = 0;
        uint8_t kind = 0;
    };
    int64_t npairs = 0;
    int64_t key_w = 0, rec_w = 0;
    std::vector<Slot> slots;
    std::unordered_map<std::string, size_t> by_key;
    std::string kbuf;
    std::vector<uint8_t> low;
    std::vector<std::pair<int64_t, int64_t>> words;
    auto post17 = [](std::string& dst, uint8_t flags, uint64_t uid,
                     uint8_t tid, const uint8_t* val, uint32_t vlen) {
        char hdr[15];
        hdr[0] = (char)flags;
        memcpy(hdr + 1, &uid, 8);
        hdr[9] = (char)tid;
        hdr[10] = 0;  // lang_len
        memcpy(hdr + 11, &vlen, 4);
        dst.append(hdr, 15);
        if (vlen) dst.append((const char*)val, vlen);
        dst.push_back(0);
        dst.push_back(0);  // nfacets u16
    };
    for (int64_t m = 0; m < n_members; m++) {
        slots.clear();
        by_key.clear();
        auto touch = [&](const std::string& key, int32_t pred,
                         uint8_t kind) -> Slot& {
            auto it = by_key.find(key);
            if (it == by_key.end()) {
                it = by_key.emplace(key, slots.size()).first;
                slots.emplace_back();
                slots.back().key = key;
                slots.back().pred = pred;
                slots.back().kind = kind;
            }
            return slots[it->second];
        };
        for (int64_t j = m_offs[m]; j < m_offs[m + 1]; j++) {
            int32_t pid = pred_ids[j];
            const uint8_t* pp = pp_blob + pp_offs[pid];
            size_t pplen = (size_t)(pp_offs[pid + 1] - pp_offs[pid]);
            uint8_t pf = pflags[pid];
            const uint8_t* idents = pidents + 4 * pid;
            uint64_t ent = entities[j];
            if (shapes[j] == 0) {
                const uint8_t* val = vblob + voffs[j];
                uint32_t vlen = (uint32_t)(voffs[j + 1] - voffs[j]);
                // data key: prefix | 0x00 | uid u64 BE
                kbuf.assign((const char*)pp, pplen);
                kbuf.push_back((char)0x00);
                for (int b = 7; b >= 0; b--)
                    kbuf.push_back((char)((ent >> (8 * b)) & 0xff));
                Slot& ds = touch(kbuf, pid, 0x00);
                post17(ds.posts, 3, ~0ULL, vtypes[j], val, vlen);
                ds.count++;
                auto index_post = [&](const std::string& key) {
                    Slot& is = touch(key, pid, 0x02);
                    post17(is.posts, 2, ent, 0, nullptr, 0);
                    is.count++;
                };
                if (pf & 2) {  // exact: ident + value bytes
                    kbuf.assign((const char*)pp, pplen);
                    kbuf.push_back((char)0x02);
                    kbuf.push_back((char)idents[0]);
                    kbuf.append((const char*)val, vlen);
                    index_post(kbuf);
                }
                if (pf & 4) {  // int: ident + BE64(LE i64 + 2^63)
                    int64_t iv;
                    memcpy(&iv, val, 8);
                    uint64_t biased = (uint64_t)iv + (1ULL << 63);
                    kbuf.assign((const char*)pp, pplen);
                    kbuf.push_back((char)0x02);
                    kbuf.push_back((char)idents[1]);
                    for (int b = 7; b >= 0; b--)
                        kbuf.push_back(
                            (char)((biased >> (8 * b)) & 0xff));
                    index_post(kbuf);
                }
                if (pf & 8) {  // bool: ident + stored byte
                    kbuf.assign((const char*)pp, pplen);
                    kbuf.push_back((char)0x02);
                    kbuf.push_back((char)idents[2]);
                    kbuf.push_back((char)(val[0] ? 1 : 0));
                    index_post(kbuf);
                }
                if (pf & 16) {  // term: tok_terms_ascii's algorithm
                    low.resize(vlen);
                    for (uint32_t c = 0; c < vlen; c++) {
                        uint8_t ch = val[c];
                        low[c] = (ch >= 'A' && ch <= 'Z')
                                     ? (uint8_t)(ch + 32)
                                     : ch;
                    }
                    words.clear();
                    int64_t start = -1;
                    for (int64_t c = 0; c <= (int64_t)vlen; c++) {
                        uint8_t ch = c < (int64_t)vlen ? low[(size_t)c]
                                                       : 0;
                        bool w = (ch >= 'a' && ch <= 'z') ||
                                 (ch >= '0' && ch <= '9') ||
                                 ch == '_' || ch == '\'';
                        if (w && start < 0) start = c;
                        if (!w && start >= 0) {
                            words.emplace_back(start, c - start);
                            start = -1;
                        }
                    }
                    const uint8_t* lo = low.data();
                    std::sort(
                        words.begin(), words.end(),
                        [lo](const std::pair<int64_t, int64_t>& a,
                             const std::pair<int64_t, int64_t>& b) {
                            int64_t mn = a.second < b.second
                                             ? a.second
                                             : b.second;
                            int c = memcmp(lo + a.first, lo + b.first,
                                           (size_t)mn);
                            if (c) return c < 0;
                            return a.second < b.second;
                        });
                    for (size_t wi = 0; wi < words.size(); wi++) {
                        if (wi > 0 &&
                            words[wi].second == words[wi - 1].second &&
                            memcmp(lo + words[wi].first,
                                   lo + words[wi - 1].first,
                                   (size_t)words[wi].second) == 0)
                            continue;  // duplicate word
                        kbuf.assign((const char*)pp, pplen);
                        kbuf.push_back((char)0x02);
                        kbuf.push_back((char)idents[3]);
                        kbuf.append((const char*)(lo + words[wi].first),
                                    (size_t)words[wi].second);
                        index_post(kbuf);
                    }
                }
            } else {
                uint64_t obj = objects[j];
                kbuf.assign((const char*)pp, pplen);
                kbuf.push_back((char)0x00);
                for (int b = 7; b >= 0; b--)
                    kbuf.push_back((char)((ent >> (8 * b)) & 0xff));
                Slot& ds = touch(kbuf, pid, 0x00);
                post17(ds.posts, 2, obj, 0, nullptr, 0);
                ds.count++;
                if (pf & 1) {  // reverse: prefix | 0x04 | object BE
                    kbuf.assign((const char*)pp, pplen);
                    kbuf.push_back((char)0x04);
                    for (int b = 7; b >= 0; b--)
                        kbuf.push_back((char)((obj >> (8 * b)) & 0xff));
                    Slot& rs = touch(kbuf, pid, 0x04);
                    post17(rs.posts, 2, ent, 0, nullptr, 0);
                    rs.count++;
                }
            }
        }
        // flush this member's pairs in first-touch order
        for (const Slot& s : slots) {
            if (npairs >= max_pairs) return -1;
            out_key_offs[npairs] = key_w;
            out_rec_offs[npairs] = rec_w;
            memcpy(out_keys + key_w, s.key.data(), s.key.size());
            key_w += (int64_t)s.key.size();
            out_recs[rec_w] = 1;  // KIND_DELTA
            uint32_t cnt = (uint32_t)s.count;
            memcpy(out_recs + rec_w + 1, &cnt, 4);
            memcpy(out_recs + rec_w + 5, s.posts.data(),
                   s.posts.size());
            rec_w += 5 + (int64_t)s.posts.size();
            out_member[npairs] = (int32_t)m;
            out_pred[npairs] = s.pred;
            out_kinds[npairs] = s.kind;
            out_counts[npairs] = s.count;
            npairs++;
        }
    }
    out_key_offs[npairs] = key_w;
    out_rec_offs[npairs] = rec_w;
    return npairs;
}

// Output-capacity upper bounds for batch_apply over the same columns:
// caps[0] = pair count, caps[1] = key bytes, caps[2] = record bytes.
// Term tokens are bounded by len/2 + 1 words of the value; everything
// else is exact. Returns caps[0].
int64_t batch_apply_caps(
    const void* m_offs_v, int64_t n_members, const void* shapes_v,
    const void* pred_ids_v, const void* voffs_v,
    const void* pp_offs_v, const void* pflags_v, int64_t n_preds,
    void* caps_v) {
    (void)n_preds;
    const int64_t* m_offs = (const int64_t*)m_offs_v;
    const uint8_t* shapes = (const uint8_t*)shapes_v;
    const int32_t* pred_ids = (const int32_t*)pred_ids_v;
    const int64_t* voffs = (const int64_t*)voffs_v;
    const int64_t* pp_offs = (const int64_t*)pp_offs_v;
    const uint8_t* pflags = (const uint8_t*)pflags_v;
    int64_t* caps = (int64_t*)caps_v;
    int64_t pairs = 0, keyb = 0, posts = 0, valb = 0;
    for (int64_t j = 0; j < m_offs[n_members]; j++) {
        int32_t pid = pred_ids[j];
        int64_t pplen = pp_offs[pid + 1] - pp_offs[pid];
        int64_t vlen = voffs[j + 1] - voffs[j];
        uint8_t pf = pflags[pid];
        pairs++;  // data pair
        keyb += pplen + 9;
        posts++;
        if (shapes[j] == 0) {
            valb += vlen;
            if (pf & 2) {
                pairs++;
                keyb += pplen + 2 + vlen;
                posts++;
            }
            if (pf & 4) {
                pairs++;
                keyb += pplen + 10;
                posts++;
            }
            if (pf & 8) {
                pairs++;
                keyb += pplen + 3;
                posts++;
            }
            if (pf & 16) {
                int64_t ntok = vlen / 2 + 1;
                pairs += ntok;
                keyb += ntok * (pplen + 2) + vlen;
                posts += ntok;
            }
        } else if (pf & 1) {
            pairs++;
            keyb += pplen + 9;
            posts++;
        }
    }
    caps[0] = pairs;
    caps[1] = keyb;
    caps[2] = 5 * pairs + 17 * posts + valb;
    return pairs;
}

int64_t tok_terms_ascii(
    const uint8_t* blob, const int64_t* offs, int64_t n, int prefix,
    uint8_t* out, int64_t* tok_offs, int64_t* tok_counts) {
    int64_t ntok = 0;
    uint8_t* p = out;
    tok_offs[0] = 0;
    std::vector<uint8_t> low;
    std::vector<std::pair<int64_t, int64_t>> words;  // (start, len)
    for (int64_t i = 0; i < n; i++) {
        const uint8_t* s = blob + offs[i];
        int64_t len = offs[i + 1] - offs[i];
        low.resize((size_t)len);
        for (int64_t c = 0; c < len; c++) {
            uint8_t ch = s[c];
            low[(size_t)c] =
                (ch >= 'A' && ch <= 'Z') ? (uint8_t)(ch + 32) : ch;
        }
        words.clear();
        int64_t start = -1;
        for (int64_t c = 0; c <= len; c++) {
            uint8_t ch = c < len ? low[(size_t)c] : 0;
            bool w = (ch >= 'a' && ch <= 'z') || (ch >= '0' && ch <= '9')
                  || ch == '_' || ch == '\'';
            if (w && start < 0) start = c;
            if (!w && start >= 0) {
                words.emplace_back(start, c - start);
                start = -1;
            }
        }
        const uint8_t* lo = low.data();
        std::sort(words.begin(), words.end(),
                  [lo](const std::pair<int64_t, int64_t>& a,
                       const std::pair<int64_t, int64_t>& b) {
                      int64_t m = a.second < b.second ? a.second : b.second;
                      int c = memcmp(lo + a.first, lo + b.first, (size_t)m);
                      if (c) return c < 0;
                      return a.second < b.second;
                  });
        int64_t emitted = 0;
        for (size_t wi = 0; wi < words.size(); wi++) {
            if (wi > 0 && words[wi].second == words[wi - 1].second &&
                memcmp(lo + words[wi].first, lo + words[wi - 1].first,
                       (size_t)words[wi].second) == 0)
                continue;  // duplicate word
            *p++ = (uint8_t)prefix;
            memcpy(p, lo + words[wi].first, (size_t)words[wi].second);
            p += words[wi].second;
            ntok++;
            emitted++;
            tok_offs[ntok] = p - out;
        }
        tok_counts[i] = emitted;
    }
    return ntok;
}

}  // extern "C"
