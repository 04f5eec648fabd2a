"""UID pack codec: block-compressed sorted u64 UID lists, device-friendly.

TPU-native replacement for the reference's group-varint delta codec
(/root/reference/codec/codec.go:36 Encoder / :139 Decoder, 256-UID blocks,
per-block u64 Base, blocks split when the 32 MSBs differ, codec.go:117).

Design difference (deliberate, per SURVEY.md §2.7(1)): group-varint decode is
a byte-serial SSE trick that does not map to the TPU. We instead store, per
256-UID block, the u64 base plus *absolute* uint32 offsets from that base
(`uid - base`, guaranteed < 2^32 by the same 32-MSB split rule). Offsets are
random-access (no prefix-sum on decode) and upload to the device as plain
uint32 lanes. On disk, offsets are bit-packed to the block's max width
(serialize/deserialize below), giving compression comparable to the
reference's group-varint for clustered UIDs while keeping decode a pure
shift/mask that XLA vectorizes.

Segments: for device set-ops, a pack is viewed as segments keyed by the high
32 bits. Within one segment all UIDs share the hi-32 word, so set algebra
runs in 32-bit local space (ops/setops.py); cross-segment ops align segments
host-side (matching the reference's per-block Base comparisons in
algo/packed.go).
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from dgraph_tpu.x import config

BLOCK_SIZE = 256
_MAGIC = b"UPK1"

# Adaptive per-block container form (Roaring-style, arxiv 1907.01032): a
# block whose uid range fits in a fixed-size bitset AND whose density
# clears 1/8 is "bitmap-eligible" — the set kernels run word-wise
# AND/ANDNOT over the bitset instead of merging sorted offsets, and the
# serializer stores the bitset when it is smaller than the bit-packed
# offsets. BITMAP_BITS is the fixed in-memory bitset size per block
# (DGRAPH_TPU_BITMAP_BLOCK_BITS, multiple of 64; 0 disables the bitmap
# containers entirely).
def _sanitize_bitmap_bits(v: int) -> int:
    if v <= 0:
        return 0
    return max(64, (int(v) + 63) // 64 * 64)


BITMAP_BITS = _sanitize_bitmap_bits(int(config.get("BITMAP_BLOCK_BITS")))
BITMAP_WORDS = BITMAP_BITS // 64
# serialized bitmap container marker: the width byte of a block header is
# <= 32 for bit-packed offsets; 0xFF flags "payload is a bitset"
_BITMAP_FORM = 0xFF


@dataclass
class UidPack:
    """Block-compressed sorted u64 UID list.

    bases:   (nblocks,) uint64 — first UID of each block
    counts:  (nblocks,) int32  — #UIDs in each block (<= BLOCK_SIZE)
    offsets: (nblocks, BLOCK_SIZE) uint32 — uid - base, padded with 0xFFFFFFFF
    num_uids: total count
    """

    bases: np.ndarray
    counts: np.ndarray
    offsets: np.ndarray
    num_uids: int
    # lazily-computed per-block max UIDs (block_maxes); immutable like the
    # block arrays themselves
    _maxes: Optional[np.ndarray] = field(
        default=None, repr=False, compare=False
    )
    # lazily-built bitmap sidecar (block_bitmaps): (words, ok) where words
    # is (nblocks, BITMAP_WORDS) uint64 (None when no block is eligible)
    # and ok is the (nblocks,) bool eligibility mask
    _bm: Optional[tuple] = field(default=None, repr=False, compare=False)

    def __len__(self) -> int:
        return self.num_uids

    @property
    def nblocks(self) -> int:
        return self.bases.shape[0]

    def approx_bytes(self) -> int:
        """On-disk size estimate (per-block best of bit-packed/bitmap;
        same container pick as _serialize_block)."""
        total = len(_MAGIC) + 12 + self.nblocks * 11
        for i in range(self.nblocks):
            c = int(self.counts[i])
            total += _block_payload_bytes(self.offsets[i, :c], c)[0]
        return total


def _width_bits(offsets: np.ndarray) -> int:
    if offsets.size == 0:
        return 0
    m = int(offsets.max())
    return max(1, m.bit_length())


def encode(uids: np.ndarray) -> UidPack:
    """Encode a sorted (strictly increasing) u64 array into a UidPack.

    Blocks hold up to BLOCK_SIZE UIDs and never span a hi-32 boundary
    (mirrors codec.go:117's split rule so offsets always fit uint32).
    """
    uids = np.asarray(uids, dtype=np.uint64)
    n = uids.shape[0]
    if n == 0:
        return UidPack(
            bases=np.zeros((0,), np.uint64),
            counts=np.zeros((0,), np.int32),
            offsets=np.zeros((0, BLOCK_SIZE), np.uint32),
            num_uids=0,
        )
    if n <= BLOCK_SIZE and (uids[-1] >> np.uint64(32)) == (
        uids[0] >> np.uint64(32)
    ):
        # single-block fast path: the dominant bulk-load shape (small
        # per-key lists) — no segment scan, no per-block loop
        offsets = np.full((1, BLOCK_SIZE), 0xFFFFFFFF, np.uint32)
        offsets[0, :n] = (uids - uids[0]).astype(np.uint32)
        return UidPack(
            bases=uids[:1].copy(),
            counts=np.array([n], np.int32),
            offsets=offsets,
            num_uids=n,
        )
    hi = (uids >> np.uint64(32)).astype(np.uint64)
    # block boundary every BLOCK_SIZE elements or at hi-32 changes
    seg_starts = np.flatnonzero(np.concatenate([[True], hi[1:] != hi[:-1]]))
    starts: List[int] = []
    seg_bounds = list(seg_starts) + [n]
    for si in range(len(seg_bounds) - 1):
        s, e = int(seg_bounds[si]), int(seg_bounds[si + 1])
        starts.extend(range(s, e, BLOCK_SIZE))
    nb = len(starts)
    bases = np.zeros((nb,), np.uint64)
    counts = np.zeros((nb,), np.int32)
    offsets = np.full((nb, BLOCK_SIZE), 0xFFFFFFFF, np.uint32)
    bounds = starts + [n]
    for bi in range(nb):
        s = bounds[bi]
        e = min(bounds[bi + 1], s + BLOCK_SIZE)
        blk = uids[s:e]
        # Base is the first UID (not hi-masked): offsets stay small for
        # clustered blocks, minimizing the bit-pack width. Safe because a
        # block never spans a hi-32 boundary, so offsets always fit uint32.
        bases[bi] = blk[0]
        counts[bi] = e - s
        offsets[bi, : e - s] = (blk - bases[bi]).astype(np.uint32)
    return UidPack(bases=bases, counts=counts, offsets=offsets, num_uids=n)


def _frozen(arr: np.ndarray) -> np.ndarray:
    arr.setflags(write=False)
    return arr


# THE empty pack: one shared, immutable object for every list without uid
# edges (half of a social graph's records are a lone value posting), in
# place of three fresh arrays per decoded record
EMPTY = UidPack(
    bases=_frozen(np.zeros((0,), np.uint64)),
    counts=_frozen(np.zeros((0,), np.int32)),
    offsets=_frozen(np.zeros((0, BLOCK_SIZE), np.uint32)),
    num_uids=0,
)
NO_UIDS = _frozen(np.zeros((0,), np.uint64))  # decode(EMPTY), shared
_PAD_ROW = _frozen(np.full((1, BLOCK_SIZE), 0xFFFFFFFF, np.uint32))
_HEADER = struct.Struct("<4sQI")  # magic, num_uids, nblocks
_BLOCK_HEAD = struct.Struct("<QHB")  # base, count, width
# a one-block pack of at most this many uids is cheaper to unpack with
# Python ints than through four numpy calls and a native bit-unpack
SMALL_PACK = 16


def deserialize_small(data: bytes, pos: int, n: int):
    """(pack, uids) for the two commonest serialized packs at
    data[pos:pos+n], else None (the caller takes `deserialize`, the
    general form and the reference): an EMPTY pack is the shared one; a
    single bit-packed block of at most SMALL_PACK uids is unpacked from
    one Python int, its decoded uids alongside. Never raises for bad
    input: whatever it does not recognise is the general decoder's."""
    if n < 16:
        return None
    magic, num_uids, nb = _HEADER.unpack_from(data, pos)
    if magic != _MAGIC:
        return None
    if nb == 0:
        return (EMPTY, NO_UIDS) if n == 16 and num_uids == 0 else None
    if nb != 1 or n < 27:
        return None
    base, c, w = _BLOCK_HEAD.unpack_from(data, pos + 16)
    if (
        c != num_uids or not 0 < c <= SMALL_PACK or w > 32
        or 27 + (c * w + 7) // 8 != n
    ):
        return None
    bits = int.from_bytes(data[pos + 27 : pos + n], "little")
    mask = (1 << w) - 1
    offs = [(bits >> (i * w)) & mask for i in range(c)]
    if base + offs[-1] >= 1 << 64:
        return None
    offsets = _PAD_ROW.copy()
    offsets[0, :c] = offs
    pack = UidPack(
        bases=np.array((base,), np.uint64),
        counts=np.array((c,), np.int32),
        offsets=offsets,
        num_uids=c,
    )
    return pack, np.array([base + o for o in offs], np.uint64)


def decode(pack: UidPack) -> np.ndarray:
    """Decode a UidPack back to a sorted u64 array. Ref codec.go:444 Decode.

    Implemented as a full-range partial decode — one vectorized/native pass
    instead of the old per-block Python loop. Single-block packs (the
    dominant fan-out shape: small per-key lists) take a direct slice, no
    native marshaling."""
    if pack.num_uids == 0:
        return np.zeros((0,), np.uint64)
    if pack.nblocks == 1:
        c = int(pack.counts[0])
        return pack.bases[0] + pack.offsets[0, :c].astype(np.uint64)
    return decode_blocks(pack, np.arange(pack.nblocks, dtype=np.int64))


def block_maxes(pack: UidPack) -> np.ndarray:
    """(nblocks,) uint64 — last (max) UID of each block.

    Together with `pack.bases` this is the per-block skip metadata of the
    compressed-domain set ops (ops/packed_setops.py): a block's UID range is
    [bases[i], maxes[i]], ranges are disjoint and ascending. Derivable from
    the next block's base in the reference (algo/packed.go walks per-block
    Base values); here the last in-block offset gives the exact max. Cached
    on the pack — the metadata is immutable once encoded."""
    if pack._maxes is None:
        nb = pack.nblocks
        if nb == 0:
            pack._maxes = np.zeros((0,), np.uint64)
        else:
            last = np.maximum(pack.counts.astype(np.int64) - 1, 0)
            pack._maxes = pack.bases + pack.offsets[
                np.arange(nb), last
            ].astype(np.uint64)
    return pack._maxes


def bitmap_eligible(pack: UidPack) -> np.ndarray:
    """(nblocks,) bool — True where the block's uid range fits the fixed
    BITMAP_BITS bitset AND its density clears 1/8 (count * 8 > range).
    The per-block cardinality metadata behind the adaptive kernel pick:
    eligible blocks materialize as bitsets (block_bitmaps) and run the
    word-wise AND/ANDNOT kernels; the rest stay sorted-offset form."""
    nb = pack.nblocks
    if nb == 0 or BITMAP_BITS == 0:
        return np.zeros((nb,), bool)
    rng = block_maxes(pack) - pack.bases
    return (rng < np.uint64(BITMAP_BITS)) & (
        pack.counts.astype(np.uint64) * np.uint64(8) > rng
    )


def block_bitmaps(
    pack: UidPack,
) -> Tuple[Optional[np.ndarray], Optional[np.ndarray], np.ndarray]:
    """(words, rows, ok): the pack's bitmap sidecar, COMPACT — `words` is
    a (n_eligible, BITMAP_WORDS) uint64 matrix holding only the eligible
    blocks' fixed-size bitsets (bit j of block i's row <=> uid
    bases[i]+j present), `rows` is the (nblocks,) int32 indirection
    (words-row index, or -1 for offsets-only blocks), and `ok` the bool
    eligibility mask. `words`/`rows` are None when NO block is eligible
    (the all-sparse case: nothing allocates), and a mostly-sparse pack
    pays only for its dense blocks. Cached on the pack like block_maxes;
    the block arrays are immutable once encoded."""
    if pack._bm is None:
        ok = bitmap_eligible(pack)
        if not ok.any():
            pack._bm = (None, None, ok)
            return pack._bm
        idxs = np.flatnonzero(ok)
        rows = np.full((pack.nblocks,), -1, np.int32)
        rows[idxs] = np.arange(idxs.size, dtype=np.int32)
        words = np.zeros((idxs.size, BITMAP_WORDS), np.uint64)
        from dgraph_tpu import native

        if not native.pack_build_bitmaps(
            pack.counts, pack.offsets, rows, BITMAP_BITS, words
        ):
            # numpy fallback: one flat scatter over all eligible blocks
            mat = pack.offsets[idxs]
            valid = (
                np.arange(mat.shape[1], dtype=np.int32)[None, :]
                < pack.counts[idxs][:, None]
            )
            ri, ji = np.nonzero(valid)
            offs = mat[ri, ji].astype(np.uint64)
            np.bitwise_or.at(
                words,
                (ri, (offs >> np.uint64(6)).astype(np.int64)),
                np.uint64(1) << (offs & np.uint64(63)),
            )
        pack._bm = (words, rows, ok)
    return pack._bm


def offsets_to_bitmap(offs: np.ndarray, nbits: int) -> np.ndarray:
    """Conversion helper: uint32 in-block offsets (< nbits) -> uint64
    bitset words, little-endian bit order (bit j <=> offset j)."""
    words = np.zeros(((nbits + 63) // 64,), np.uint64)
    o = np.asarray(offs, np.uint64)
    np.bitwise_or.at(
        words,
        (o >> np.uint64(6)).astype(np.int64),
        np.uint64(1) << (o & np.uint64(63)),
    )
    return words


def bitmap_to_offsets(words: np.ndarray, nbits: int) -> np.ndarray:
    """Inverse of offsets_to_bitmap: set bits -> sorted uint32 offsets."""
    bits = np.unpackbits(
        np.ascontiguousarray(words, np.uint64).view(np.uint8),
        bitorder="little",
    )[:nbits]
    return np.flatnonzero(bits).astype(np.uint32)


def decode_blocks(pack: UidPack, idxs: np.ndarray) -> np.ndarray:
    """Decode ONLY the blocks in `idxs` (sorted ascending) -> sorted u64.

    The partial decoder behind the block-skip set ops: candidate blocks
    found by range overlap decode; everything else stays compressed. The
    native fast path (codec.cpp pack_decode_blocks) avoids the (k, 256)
    gather temp; the numpy fallback is a masked broadcast."""
    idxs = np.asarray(idxs, dtype=np.int64)
    if idxs.size == 0:
        return np.zeros((0,), np.uint64)
    if idxs.size <= 4:
        # few blocks: per-block slices beat the ctypes marshal and the
        # masked broadcast alike
        parts = []
        for bi in idxs:
            c = int(pack.counts[bi])
            parts.append(
                pack.bases[bi] + pack.offsets[bi, :c].astype(np.uint64)
            )
        return parts[0] if len(parts) == 1 else np.concatenate(parts)
    from dgraph_tpu import native

    got = native.pack_decode_blocks(
        pack.bases, pack.counts, pack.offsets, idxs
    )
    if got is not None:
        return got
    counts = pack.counts[idxs].astype(np.int64)
    rows = pack.offsets[idxs]
    mask = np.arange(BLOCK_SIZE, dtype=np.int64)[None, :] < counts[:, None]
    return (pack.bases[idxs][:, None] + rows.astype(np.uint64))[mask]


def decode_packs(packs: List[UidPack]) -> Tuple[np.ndarray, np.ndarray]:
    """Decode N packs into a ragged (flat u64 buffer, int64[n+1] prefix
    offsets) pair in one pass — pack i's uids are
    flat[offsets[i]:offsets[i+1]]. The level-batched fan-out read shape:
    one call materializes a whole traversal level instead of N per-key
    decode round-trips (native fast path codec.cpp packs_decode_many)."""
    from dgraph_tpu import native

    got = native.packs_decode_many(packs)
    if got is not None:
        return got
    rows = [decode(p) for p in packs]
    offs = np.zeros((len(rows) + 1,), np.int64)
    if rows:
        np.cumsum([len(r) for r in rows], out=offs[1:])
    flat = (
        np.concatenate(rows) if rows else np.zeros((0,), np.uint64)
    ).astype(np.uint64, copy=False)
    return flat, offs


def merge_packs(packs: List[UidPack]) -> UidPack:
    """Concatenate packs holding disjoint ascending UID ranges (multi-part
    posting-list parts, ref posting/list.go:519 pIterator) into one logical
    pack WITHOUT decoding — pure block-array concatenation, so the merged
    view feeds the compressed-domain ops directly."""
    packs = [p for p in packs if p.num_uids]
    if not packs:
        return encode(np.zeros((0,), np.uint64))
    if len(packs) == 1:
        return packs[0]
    return UidPack(
        bases=np.concatenate([p.bases for p in packs]),
        counts=np.concatenate([p.counts for p in packs]),
        offsets=np.concatenate([p.offsets for p in packs]),
        num_uids=sum(p.num_uids for p in packs),
    )


def split_segments(uids: np.ndarray) -> Dict[int, np.ndarray]:
    """Split a sorted u64 array into {hi32: sorted uint32 lo-array} segments."""
    uids = np.asarray(uids, dtype=np.uint64)
    out: Dict[int, np.ndarray] = {}
    if uids.size == 0:
        return out
    # sorted input: equal first/last hi-words means ONE segment — the
    # overwhelmingly common case (uids cluster far below 2^32), and this
    # function runs once per row of every level-batched dispatch
    hi0 = int(uids[0] >> np.uint64(32))
    if int(uids[-1] >> np.uint64(32)) == hi0:
        out[hi0] = (uids & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        return out
    hi = (uids >> np.uint64(32)).astype(np.uint64)
    starts = np.flatnonzero(np.concatenate([[True], hi[1:] != hi[:-1]]))
    bounds = list(starts) + [uids.size]
    for si in range(len(bounds) - 1):
        s, e = int(bounds[si]), int(bounds[si + 1])
        out[int(hi[s])] = (uids[s:e] & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return out


def join_segments(segments: Dict[int, np.ndarray]) -> np.ndarray:
    """Inverse of split_segments."""
    parts = []
    for h in sorted(segments):
        lo = segments[h].astype(np.uint64)
        parts.append((np.uint64(h) << np.uint64(32)) | lo)
    if not parts:
        return np.zeros((0,), np.uint64)
    return np.concatenate(parts)


# ---------------------------------------------------------------------------
# Serialization: bit-packed per-block offsets (disk/wire format).
# ---------------------------------------------------------------------------


def _bitpack(vals, width):
    from dgraph_tpu import native

    return native.bitpack(vals, width)


def _bitunpack(data, count, width):
    from dgraph_tpu import native

    return native.bitunpack(data, count, width)


def _block_payload_bytes(offs: np.ndarray, c: int):
    """(payload_bytes, use_bitmap, width, max_offset) — the ONE container
    pick shared by _serialize_block and approx_bytes, so the on-disk
    size estimate can never drift from the serializer."""
    w = _width_bits(offs)
    packed_nbytes = (c * w + 7) // 8
    rng = int(offs[-1]) if c else 0
    if BITMAP_BITS and c and rng <= 0xFFFF:
        bm_nbytes = 2 + (rng + 8) // 8
        if bm_nbytes < packed_nbytes:
            return bm_nbytes, True, w, rng
    return packed_nbytes, False, w, rng


def _serialize_block(base: int, offs: np.ndarray, c: int) -> bytes:
    """One block record, in whichever container form is smaller:

      packed  [<QHB> base count width]  + bit-packed offsets
      bitmap  [<QHB> base count 0xFF]   + <H> max-offset + bitset bytes

    A dense block (small max offset relative to count) stores as a raw
    little-endian bitset over its base — the on-disk face of the bitmap
    containers (Roaring-style, arxiv 1907.01032). The 0xFF marker can
    never collide with a real width (widths are <= 32), so old packed
    records stay readable; records WITH bitmap blocks are not readable
    by pre-bitmap builds (pin DGRAPH_TPU_BITMAP_BLOCK_BITS=0 to keep
    writing the legacy form in a mixed-version store). The native bulk
    writer (bulkload.cpp serialize_uids) emits only the packed form;
    both forms deserialize."""
    _, use_bitmap, w, rng = _block_payload_bytes(offs, c)
    if use_bitmap:
        words = offsets_to_bitmap(offs, rng + 1)
        return (
            struct.pack("<QHB", base, c, _BITMAP_FORM)
            + struct.pack("<H", rng)
            + words.view(np.uint8)[: (rng + 8) // 8].tobytes()
        )
    return struct.pack("<QHB", base, c, w) + _bitpack(offs, w)


def serialize_uids(uids: np.ndarray) -> bytes:
    """Serialized pack straight from a sorted uid array — skips the
    UidPack materialization for the dominant small-list case (bulk-load
    reduce hot path; wire format identical to serialize(encode(uids)))."""
    n = len(uids)
    if n == 0:
        return _MAGIC + struct.pack("<QI", 0, 0)
    if n <= BLOCK_SIZE and (int(uids[-1]) >> 32) == (int(uids[0]) >> 32):
        base = int(uids[0])
        offs = (uids - uids[0]).astype(np.uint32)
        return (
            _MAGIC
            + struct.pack("<QI", n, 1)
            + _serialize_block(base, offs, n)
        )
    return serialize(encode(uids))


def serialize(pack: UidPack) -> bytes:
    """Per-block container pick: bit-packed offsets at the block's max
    width, or a raw bitset when the block is dense enough that the bitset
    is smaller (_serialize_block). Ref codec.go:393 Encode (group-varint
    there; fixed-width lanes / bitmap containers here — see module
    docstring)."""
    parts = [_MAGIC, struct.pack("<QI", pack.num_uids, pack.nblocks)]
    for bi in range(pack.nblocks):
        c = int(pack.counts[bi])
        parts.append(
            _serialize_block(
                int(pack.bases[bi]), pack.offsets[bi, :c], c
            )
        )
    return b"".join(parts)


def deserialize(data: bytes) -> UidPack:
    if data[:4] != _MAGIC:
        raise ValueError("bad UidPack magic")
    num_uids, nb = struct.unpack_from("<QI", data, 4)
    # bound-check untrusted header before allocating (disk/wire input)
    if nb * 11 + 16 > len(data):
        raise ValueError(f"corrupt UidPack: {nb} blocks exceeds data size")
    pos = 4 + 12
    bases = np.zeros((nb,), np.uint64)
    counts = np.zeros((nb,), np.int32)
    offsets = np.full((nb, BLOCK_SIZE), 0xFFFFFFFF, np.uint32)
    for bi in range(nb):
        base, c, w = struct.unpack_from("<QHB", data, pos)
        pos += 11
        if c > BLOCK_SIZE or (w > 32 and w != _BITMAP_FORM):
            raise ValueError(
                f"corrupt UidPack block: count={c} width={w}"
            )
        if w == _BITMAP_FORM:
            # bitmap container: <H> max-offset + little-endian bitset
            if pos + 2 > len(data):
                raise ValueError("truncated UidPack bitmap header")
            (rng,) = struct.unpack_from("<H", data, pos)
            pos += 2
            nbytes = (rng + 8) // 8
            if pos + nbytes > len(data):
                raise ValueError("truncated UidPack block data")
            bits = np.unpackbits(
                np.frombuffer(data, np.uint8, nbytes, pos),
                bitorder="little",
            )[: rng + 1]
            offs = np.flatnonzero(bits).astype(np.uint32)
            if offs.size != c:
                raise ValueError(
                    f"corrupt UidPack bitmap block: popcount "
                    f"{offs.size} != count {c}"
                )
            pos += nbytes
        else:
            nbytes = (c * w + 7) // 8
            if pos + nbytes > len(data):
                raise ValueError("truncated UidPack block data")
            offs = _bitunpack(data[pos : pos + nbytes], c, w)
            pos += nbytes
        bases[bi] = base
        counts[bi] = c
        offsets[bi, :c] = offs
    if int(counts.sum()) != num_uids:
        raise ValueError(
            f"corrupt UidPack: header num_uids={num_uids} != "
            f"sum of block counts {int(counts.sum())}"
        )
    return UidPack(bases=bases, counts=counts, offsets=offsets, num_uids=num_uids)


def _bitpack_py(vals: np.ndarray, width: int) -> bytes:
    """Pack uint32 values into `width`-bit little-endian lanes."""
    if width == 0 or vals.size == 0:
        return b""
    v = vals.astype(np.uint64)
    nbits = vals.size * width
    nbytes = (nbits + 7) // 8
    buf = np.zeros((nbytes,), np.uint8)
    bitpos = np.arange(vals.size, dtype=np.uint64) * np.uint64(width)
    # write each value byte-by-byte (width <= 32 so spans <= 5 bytes)
    for byte_i in range(5):
        byte_idx = (bitpos >> np.uint64(3)) + np.uint64(byte_i)
        shift = (bitpos & np.uint64(7)).astype(np.uint64)
        chunk = ((v << shift) >> np.uint64(8 * byte_i)) & np.uint64(0xFF)
        valid = byte_idx < nbytes
        np.bitwise_or.at(
            buf, byte_idx[valid].astype(np.int64), chunk[valid].astype(np.uint8)
        )
    return buf.tobytes()


def _bitunpack_py(data: bytes, count: int, width: int) -> np.ndarray:
    if width == 0 or count == 0:
        return np.zeros((count,), np.uint32)
    buf = np.frombuffer(data, dtype=np.uint8)
    # read 8 bytes window per value via padded u64 gather
    padded = np.zeros((buf.size + 8,), np.uint8)
    padded[: buf.size] = buf
    bitpos = np.arange(count, dtype=np.uint64) * np.uint64(width)
    byte_idx = (bitpos >> np.uint64(3)).astype(np.int64)
    shift = (bitpos & np.uint64(7)).astype(np.uint64)
    window = np.zeros((count,), np.uint64)
    for b in range(8):
        window |= padded[byte_idx + b].astype(np.uint64) << np.uint64(8 * b)
    mask = (np.uint64(1) << np.uint64(width)) - np.uint64(1)
    return ((window >> shift) & mask).astype(np.uint32)
