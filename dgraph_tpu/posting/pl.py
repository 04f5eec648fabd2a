"""Posting lists: MVCC layered edge/value storage per (predicate, uid) key.

Mirrors /root/reference/posting/list.go semantics with a simplified layer
model (SURVEY.md §7.2):

  - a *rollup* record is the complete immutable state at some commit ts —
    UID edges as a block-compressed UidPack (codec/uidpack.py) plus value
    postings (ref list.go:66 `plist` with UidPack + postings),
  - *delta* records are per-txn changes written at their commit ts
    (ref posting/mvcc.go:266 CommitToDisk),
  - a read at `read_ts` walks KV versions newest->oldest until a rollup,
    then applies the deltas above it in ts order
    (ref posting/mvcc.go:641 ReadPostingList),
  - rollup() recompacts layers into a new rollup record
    (ref list.go:1416 Rollup; incremental trigger posting/mvcc.go:41).

Value postings use the reference's uid conventions: a scalar value posting
has uid VALUE_UID (math.MaxUint64, ref posting/index.go fingerprinting); a
language-tagged or list value posting uses a 64-bit fingerprint of the
lang/value so multiple values coexist in one sorted list.
"""

from __future__ import annotations

import hashlib
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from dgraph_tpu.codec import uidpack
from dgraph_tpu.types.types import TypeID, Val, from_binary, to_binary
from dgraph_tpu.utils.farmhash import (
    fingerprint64 as _farm_fp,
    go_value_binary,
)

OP_SET = 1
OP_DEL = 2

# multi-part list threshold: a rollup whose uid set exceeds this is split
# into part records under keys.SplitKey (ref posting/list.go:44 maxListSize,
# rollup re-split list.go:1590). Tunable for tests / memory budgets; the
# native bulk reduce (loaders/bulk2.py) reads the same registry knob.
from dgraph_tpu.x import config as _config

MAX_PART_UIDS = int(_config.get("MAX_PART_UIDS"))

VALUE_UID = (1 << 64) - 1  # plain scalar value posting


class CorruptRecordError(ValueError):
    """A stored posting record failed structural validation (truncated or
    corrupt bytes) — raised instead of silently decoding garbage
    (mirrors the strict checks in codec/uidpack.deserialize)."""


def fingerprint64(data: bytes) -> int:
    h = hashlib.blake2b(data, digest_size=8).digest()
    v = struct.unpack("<Q", h)[0]
    return v or 1  # avoid uid 0


def lang_uid(lang: str) -> int:
    """Posting uid for a language-tagged value: farm.Fingerprint64 of the
    bare lang tag (ref posting/list.go:826) — the reference accepts the
    lang-vs-value collision this implies, so we must too: posting order
    (= JSON list order) is fingerprint order."""
    if not lang:
        return VALUE_UID
    return _farm_fp(lang.encode("utf-8"))


def value_uid(stored: "Val") -> int:
    """Posting uid for a list-predicate value: farm.Fingerprint64 of the
    value's GO-marshaled bytes (ref posting/list.go:831 + the conversion
    in types/conversion.go Marshal). Matching the reference's hash over
    the reference's bytes makes list-value JSON ordering bit-exact."""
    return _farm_fp(go_value_binary(stored.tid, stored.value))


@dataclass(slots=True)
class Posting:
    uid: int
    op: int = OP_SET
    value: Optional[bytes] = None  # None => pure uid edge
    value_type: TypeID = TypeID.DEFAULT
    lang: str = ""
    facets: Dict[str, bytes] = field(default_factory=dict)
    facet_types: Dict[str, TypeID] = field(default_factory=dict)

    @property
    def is_value(self) -> bool:
        return self.value is not None

    def val(self) -> Val:
        return from_binary(self.value_type, self.value)

    def get_facets(self) -> Dict[str, Val]:
        return {
            k: from_binary(self.facet_types.get(k, TypeID.DEFAULT), v)
            for k, v in self.facets.items()
        }


# ---------------------------------------------------------------------------
# Record serialization (KV value bytes).
# ---------------------------------------------------------------------------

KIND_ROLLUP = 0
KIND_DELTA = 1


def _enc_posting(p: Posting, out: List[bytes]):
    flags = (1 if p.is_value else 0) | (p.op << 1)
    out.append(struct.pack("<BQB", flags, p.uid, int(p.value_type)))
    lang = p.lang.encode("utf-8")
    out.append(struct.pack("<B", len(lang)))
    out.append(lang)
    v = p.value if p.value is not None else b""
    out.append(struct.pack("<I", len(v)))
    out.append(v)
    out.append(struct.pack("<H", len(p.facets)))
    for k in sorted(p.facets):
        kb = k.encode("utf-8")
        fv = p.facets[k]
        out.append(
            struct.pack(
                "<BBH", len(kb), int(p.facet_types.get(k, TypeID.DEFAULT)), len(fv)
            )
        )
        out.append(kb)
        out.append(fv)


def encode_posting_bytes(p: Posting) -> bytes:
    """One posting in the record wire layout (the bulk loader's spill-run
    payload format — shared with native/bulkload.cpp)."""
    out: List[bytes] = []
    _enc_posting(p, out)
    return b"".join(out)


def decode_posting_bytes(data: bytes) -> Posting:
    p, _ = _dec_posting(data, 0)
    return p


def _need(data: bytes, pos: int, n: int):
    if pos + n > len(data):
        raise CorruptRecordError(
            f"posting record truncated: need {n} bytes at {pos}, have {len(data)}"
        )


def _dec_posting(data: bytes, pos: int) -> Tuple[Posting, int]:
    _need(data, pos, 11)
    flags, uid, tid = struct.unpack_from("<BQB", data, pos)
    pos += 10
    (llen,) = struct.unpack_from("<B", data, pos)
    pos += 1
    _need(data, pos, llen)
    lang = data[pos : pos + llen].decode("utf-8")
    pos += llen
    _need(data, pos, 4)
    (vlen,) = struct.unpack_from("<I", data, pos)
    pos += 4
    _need(data, pos, vlen)
    value = data[pos : pos + vlen]
    pos += vlen
    _need(data, pos, 2)
    (nf,) = struct.unpack_from("<H", data, pos)
    pos += 2
    facets: Dict[str, bytes] = {}
    ftypes: Dict[str, TypeID] = {}
    for _ in range(nf):
        _need(data, pos, 4)
        klen, ftid, fvlen = struct.unpack_from("<BBH", data, pos)
        pos += 4
        _need(data, pos, klen + fvlen)
        k = data[pos : pos + klen].decode("utf-8")
        pos += klen
        facets[k] = data[pos : pos + fvlen]
        ftypes[k] = TypeID(ftid)
        pos += fvlen
    is_value = flags & 1
    p = Posting(
        uid=uid,
        op=(flags >> 1) & 0x3,
        value=value if is_value else None,
        value_type=TypeID(tid),
        lang=lang,
        facets=facets,
        facet_types=ftypes,
    )
    return p, pos


def encode_rollup(
    pack,
    postings: List[Posting],
    split_starts: Optional[List[int]] = None,
) -> bytes:
    """Main rollup record. When `split_starts` is non-empty the pack holds
    only value/facet postings' context — the uid set lives in part records
    (one per start uid) under keys.SplitKey(main_key, start).

    `pack` is a UidPack or pre-serialized pack bytes (bulk fast path)."""
    pb = pack if isinstance(pack, bytes) else uidpack.serialize(pack)
    out = [struct.pack("<BI", KIND_ROLLUP, len(pb)), pb]
    out.append(struct.pack("<I", len(postings)))
    for p in postings:
        _enc_posting(p, out)
    ss = split_starts or []
    out.append(struct.pack("<I", len(ss)))
    for st in ss:
        out.append(struct.pack("<Q", st))
    return b"".join(out)


def encode_delta(postings: List[Posting]) -> bytes:
    out = [struct.pack("<BI", KIND_DELTA, len(postings))]
    for p in postings:
        _enc_posting(p, out)
    return b"".join(out)


def encode_deltas(deltas: Dict[bytes, List[Posting]]):
    """Batched delta encode for a whole txn's write set: returns
    [(key, delta_record_bytes)] for every non-empty key (in write-set
    order), byte-identical to per-key encode_delta. The common
    scalar/uid posting shapes (no facets, no lang) encode through ONE
    native call across keys (codec.cpp enc_delta_records); keys
    holding facet/lang postings take the Python encoder PER KEY, so a
    single rich edge never disables the kernel for the whole txn."""
    from dgraph_tpu import native

    from dgraph_tpu.utils.observe import METRICS

    items = [(k, p) for k, p in deltas.items() if p]
    if not items:
        return []
    if not native.NATIVE_AVAILABLE:
        METRICS.inc("mutation_native_fallback_total", len(items))
        METRICS.inc(
            'mutation_native_fallback_total{reason="no_native"}',
            len(items),
        )
        return [(k, encode_delta(p)) for k, p in items]
    fast: List[int] = []  # indices into items taking the native kernel
    out: List = [None] * len(items)
    rich = 0
    for i, (k, posts) in enumerate(items):
        if any(p.facets or p.lang for p in posts):
            out[i] = (k, encode_delta(posts))
            rich += 1
        else:
            fast.append(i)
    if rich:
        # the per-key Python encoder ran: kernel-coverage regression
        # signal for the encode stage (keys, not edges, here)
        METRICS.inc("mutation_native_fallback_total", rich)
        METRICS.inc(
            'mutation_native_fallback_total{reason="rich_posting"}', rich
        )
    if fast:
        recs = _encode_deltas_native([items[i] for i in fast])
        if recs is None:  # native call unavailable after all
            METRICS.inc("mutation_native_fallback_total", len(fast))
            METRICS.inc(
                'mutation_native_fallback_total{reason="no_native"}',
                len(fast),
            )
            for i in fast:
                out[i] = (items[i][0], encode_delta(items[i][1]))
        else:
            for j, i in enumerate(fast):
                out[i] = (items[i][0], recs[j])
    return out


def _encode_deltas_native(items):
    """One-call encode of fast-shape postings (caller pre-filtered:
    no facets, no lang); returns the per-key record list or None when
    the native library is unavailable. Inputs assemble through plain
    lists converted to arrays in bulk — per-element numpy stores would
    cost more than the native call saves."""
    from dgraph_tpu import native

    counts: List[int] = []
    flags: List[int] = []
    uids: List[int] = []
    tids: List[int] = []
    vlens: List[int] = []
    vals: List[bytes] = []
    for _k, posts in items:
        counts.append(len(posts))
        for p in posts:
            v = p.value
            flags.append((1 if v is not None else 0) | (p.op << 1))
            uids.append(p.uid)
            tids.append(int(p.value_type))
            if v is not None:
                vlens.append(len(v))
                vals.append(v)
            else:
                vlens.append(0)
    return native.enc_delta_records(
        np.array(counts, np.int64),
        np.frombuffer(bytes(flags), np.uint8),
        np.array(uids, np.uint64),
        np.frombuffer(bytes(tids), np.uint8),
        np.array(vlens, np.int64),
        b"".join(vals),
    )


def decode_record(data: bytes):
    """Returns (kind, pack_or_None, postings, split_starts)."""
    _need(data, 0, 5)
    kind, n = struct.unpack_from("<BI", data, 0)
    if kind not in (KIND_ROLLUP, KIND_DELTA):
        raise CorruptRecordError(f"unknown record kind {kind}")
    pos = 5
    if kind == KIND_ROLLUP:
        _need(data, pos, n)
        pack = uidpack.deserialize(data[pos : pos + n])
        pos += n
        _need(data, pos, 4)
        (cnt,) = struct.unpack_from("<I", data, pos)
        pos += 4
        postings = []
        for _ in range(cnt):
            p, pos = _dec_posting(data, pos)
            postings.append(p)
        splits: List[int] = []
        if pos < len(data):  # records from before splits lack the tail
            _need(data, pos, 4)
            (ns,) = struct.unpack_from("<I", data, pos)
            pos += 4
            _need(data, pos, 8 * ns)
            for i in range(ns):
                splits.append(struct.unpack_from("<Q", data, pos)[0])
                pos += 8
        return KIND_ROLLUP, pack, postings, splits
    postings = []
    for _ in range(n):
        p, pos = _dec_posting(data, pos)
        postings.append(p)
    return KIND_DELTA, None, postings, []


_REC_HEAD = struct.Struct("<BI")  # kind, pack bytes (rollup) or postings
_POST_HEAD = struct.Struct("<BQBBI")  # flags, uid, type, lang len, value len
_U16 = struct.Struct("<H")
_U32 = struct.Struct("<I")
_TYPE_OF = {int(t): t for t in TypeID}


def decode_cold(key: bytes, ts: int, rec: bytes) -> Optional["PostingList"]:
    """The PostingList of a key whose ONE visible version is `rec`, for
    the shape every bulk-loaded or rolled-up key has, else None: a
    KIND_ROLLUP record, no split, postings without facets or language.
    It decodes what such a record holds and not what a record can hold:
    the pack through `uidpack.deserialize_small` where it is empty or a
    few uids (else through `deserialize`), a posting with one struct
    read, and ONE bounds check for the whole record: a read past the end
    raises `struct.error`, and a slice cut short shows in the final
    position. Whatever it does not recognise (a delta, facets, `@lang`,
    splits, trailing or missing bytes, an unknown type id, a corrupt
    pack) it hands back as None, undecoded, and the caller takes
    `PostingList.from_versions`: `decode_record` stays the reference
    and the one place a corrupt record is reported from."""
    try:
        kind, plen = _REC_HEAD.unpack_from(rec, 0)
        if kind != KIND_ROLLUP:
            return None
        # the postings first: a record with facets (every `knows` list
        # of a social graph) is handed back before its pack is touched
        pos = 5 + plen
        (cnt,) = _U32.unpack_from(rec, pos)
        pos += 4
        posts = []
        for _ in range(cnt):
            flags, uid, tid, llen, vlen = _POST_HEAD.unpack_from(rec, pos)
            end = pos + 15 + vlen
            if llen or _U16.unpack_from(rec, end)[0]:
                return None  # @lang or facets
            posts.append(
                Posting(
                    uid, (flags >> 1) & 0x3,
                    rec[pos + 15 : end] if flags & 1 else None,
                    _TYPE_OF[tid],
                )
            )
            pos = end + 2
        if pos != len(rec) and (
            pos + 4 != len(rec) or _U32.unpack_from(rec, pos)[0]
        ):
            return None  # split starts, or bytes that are not the tail
        got = uidpack.deserialize_small(rec, 5, plen)
        if got is None:
            got = (uidpack.deserialize(rec[5 : 5 + plen]), None)
    except (struct.error, KeyError, ValueError):
        return None
    pl = PostingList(key, pack=got[0], value_postings=posts, min_ts=ts)
    pl._uids_cache = got[1]
    return pl


def rollup_writes(
    key: bytes, uids: np.ndarray, posts: List[Posting], ts: int
) -> List[Tuple[bytes, int, bytes]]:
    """KV writes for a full rollup of `key` with the given uid set —
    split into part records when oversized (used by the bulk loader's
    reduce phase and tablet-move streaming; same split layout as
    PostingList.rollup)."""
    uids = np.asarray(uids, np.uint64)
    if len(uids) <= MAX_PART_UIDS:
        return [
            (key, ts, encode_rollup(uidpack.serialize_uids(uids), list(posts)))
        ]
    from dgraph_tpu.x import keys as _keys

    per = max(1, MAX_PART_UIDS // 2)
    writes: List[Tuple[bytes, int, bytes]] = []
    starts: List[int] = []
    for i in range(0, len(uids), per):
        chunk = uids[i : i + per]
        starts.append(int(chunk[0]))
        writes.append(
            (
                _keys.SplitKey(key, int(chunk[0])),
                ts,
                encode_rollup(uidpack.encode(chunk), []),
            )
        )
    writes.append(
        (key, ts,
         encode_rollup(uidpack.EMPTY, list(posts), split_starts=starts))
    )
    return writes


# ---------------------------------------------------------------------------
# PostingList: reconstruct-at-ts + mutate + rollup.
# ---------------------------------------------------------------------------


class PostingList:
    """A posting list reconstructed at a read timestamp.

    Layers, like ref posting/list.go:66: `pack`+`value_postings` form the
    immutable layer; `deltas` (commit_ts-ordered) are the committed mutable
    layer; uncommitted postings for the reading txn are merged by LocalCache.
    """

    def __init__(
        self,
        key: bytes,
        pack: Optional[uidpack.UidPack] = None,
        value_postings: Optional[List[Posting]] = None,
        deltas: Optional[List[Tuple[int, List[Posting]]]] = None,
        min_ts: int = 0,
    ):
        self.key = key
        self.pack = pack if pack is not None else uidpack.EMPTY
        self.value_postings = value_postings or []
        # committed deltas above the rollup, ascending commit_ts
        self.deltas = deltas or []
        self.min_ts = min_ts  # ts of the rollup layer
        # newest version ts this list was built from — the identity used by
        # the device pack cache (key, latest_ts); 0 = empty/unknown
        self.latest_ts = (
            max(ts for ts, _ in self.deltas) if self.deltas else min_ts
        )
        self._uids_cache: Optional[np.ndarray] = None
        # multi-part list: per-part uid packs in ascending start-uid order
        # (the main record's pack is empty then; ref posting/list.go:519
        # pIterator walking split parts)
        self.part_packs: List[uidpack.UidPack] = []
        self.split_starts: List[int] = []
        # compressed-domain read state: merged multi-part view (block-array
        # concat, no decode) + decoded-block cache for the block-skip set
        # ops (ops/packed_setops.py). Both live on the PostingList, so a
        # commit invalidates them together with the list itself (MemoryLayer
        # drops the entry; DeviceCache mirrors the same invalidation).
        self._merged_pack: Optional[uidpack.UidPack] = None
        self._block_cache: Dict[int, np.ndarray] = {}
        self._has_uid_deltas: Optional[bool] = None

    # -- compressed-domain access -------------------------------------------

    # decoded-block cache bound: 4096 blocks ≈ 1M UIDs ≈ 8 MB per hot list
    BLOCK_CACHE_MAX = 4096

    def merged_pack(self) -> uidpack.UidPack:
        """The full uid set as ONE UidPack — the main pack, or the
        multi-part parts concatenated at the block level WITHOUT decoding
        (parts hold disjoint ascending ranges, so their block arrays chain
        into a valid pack). This is the operand the block-skip set ops
        consume; part packs are no longer eagerly decoded just to exist."""
        if self._merged_pack is None:
            if self.part_packs:
                self._merged_pack = uidpack.merge_packs(self.part_packs)
            else:
                self._merged_pack = self.pack
        return self._merged_pack

    def has_uid_deltas(self) -> bool:
        """True when committed deltas touch the uid set (value-only deltas
        leave the packed view exact)."""
        if self._has_uid_deltas is None:
            self._has_uid_deltas = any(
                not p.is_value for _, posts in self.deltas for p in posts
            )
        return self._has_uid_deltas

    def packed(self) -> Optional[uidpack.UidPack]:
        """The uid set as a UidPack when the compressed view is exact —
        None when committed uid deltas exist (the packed layers are stale
        then and callers must take the decoded path)."""
        if self.has_uid_deltas():
            return None
        return self.merged_pack()

    def decode_blocks(
        self, pack: uidpack.UidPack, idxs: np.ndarray
    ) -> np.ndarray:
        """Partial decoder with a per-list block cache: repeated traversals
        hitting the same candidate blocks stop re-decoding. `pack` must be
        this list's merged_pack() (the cache keys are its block indices)."""
        idxs = np.asarray(idxs, np.int64)
        if idxs.size == 0:
            return np.zeros((0,), np.uint64)
        missing = [int(i) for i in idxs if int(i) not in self._block_cache]
        tmp: Dict[int, np.ndarray] = {}
        if missing:
            decoded = uidpack.decode_blocks(
                pack, np.asarray(missing, np.int64)
            )
            pos = 0
            for bi in missing:
                c = int(pack.counts[bi])
                tmp[bi] = decoded[pos : pos + c]
                pos += c
            # cache-full: still serve cached blocks, just don't grow —
            # a hot list at the cap keeps its cache useful
            if len(self._block_cache) + len(tmp) <= self.BLOCK_CACHE_MAX:
                self._block_cache.update(tmp)
        parts = []
        for i in idxs:
            got = self._block_cache.get(int(i))
            parts.append(got if got is not None else tmp[int(i)])
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    # -- construction from KV versions --------------------------------------

    @classmethod
    def from_versions(
        cls,
        key: bytes,
        versions: List[Tuple[int, bytes]],
        kv=None,
        read_ts: Optional[int] = None,
    ) -> "PostingList":
        """versions: (ts, record) newest first (KV.versions contract).

        When the rollup layer is split (multi-part list), `kv`/`read_ts`
        are used to fetch the part records; without them a split list
        raises (callers with KV access — LocalCache, MemoryLayer, rollups —
        always pass them)."""
        deltas: List[Tuple[int, List[Posting]]] = []
        pack = None
        value_postings: List[Posting] = []
        min_ts = 0
        splits: List[int] = []
        for ts, rec in versions:
            kind, pk, posts, ss = decode_record(rec)
            if kind == KIND_DELTA:
                deltas.append((ts, posts))
            else:
                pack = pk
                value_postings = posts
                min_ts = ts
                splits = ss
                break
        deltas.reverse()  # ascending commit_ts
        pl = cls(
            key,
            pack=pack,
            value_postings=value_postings,
            deltas=deltas,
            min_ts=min_ts,
        )
        if splits:
            if kv is None:
                raise CorruptRecordError(
                    "split posting list needs KV access to read parts"
                )
            from dgraph_tpu.x import keys as _keys

            rts = read_ts if read_ts is not None else min_ts
            pl.split_starts = list(splits)
            for st in splits:
                got = kv.get(_keys.SplitKey(key, st), max(rts, min_ts))
                if got is None:
                    raise CorruptRecordError(
                        f"missing split part start={st} for key {key!r}"
                    )
                _, ppack, _, _ = decode_record(got[1])
                pl.part_packs.append(ppack)
        return pl

    # -- reads ---------------------------------------------------------------

    def adopt_uids(self, uids: np.ndarray) -> None:
        """Install an externally decoded uid set as the memoized
        materialization (level-batched reads decode N lists' packs into one
        flat buffer and hand each list back its slice). Only valid for a
        list whose packed view is exact — callers check has_uid_deltas()
        first; the adopted array must equal what uids() would compute.
        The slice keeps its level buffer alive; total retention matches
        per-list copies while the whole cohort stays cached (one commit
        drops them together via MemoryLayer invalidation)."""
        if self._uids_cache is None:
            self._uids_cache = uids

    def uids(self, extra_deltas: Optional[List[Posting]] = None) -> np.ndarray:
        """Materialized sorted u64 uid set (ref list.go:1758 Uids).

        The no-extra-deltas result is memoized: a PostingList is immutable
        once constructed, and MemoryLayer shares it across queries — without
        this, every traversal level re-decodes the pack."""
        if extra_deltas is None and self._uids_cache is not None:
            return self._uids_cache
        out = self._compute_uids(extra_deltas)
        if extra_deltas is None:
            self._uids_cache = out
        return out

    def _compute_uids(self, extra_deltas: Optional[List[Posting]]) -> np.ndarray:
        # one partial-decoder pass over the merged block view — multi-part
        # lists no longer decode every part pack through its own per-pack
        # call, and packed-path readers that never call uids() decode
        # nothing at all here
        base = uidpack.decode(self.merged_pack())
        # last-writer-wins per uid across layers in commit order
        final_op: Dict[int, int] = {}
        for _, posts in self.deltas:
            for p in posts:
                if not p.is_value:
                    final_op[p.uid] = p.op
        for p in extra_deltas or []:
            if not p.is_value:
                final_op[p.uid] = p.op
        if not final_op:
            return base
        adds = [u for u, op in final_op.items() if op == OP_SET]
        dels = [u for u, op in final_op.items() if op == OP_DEL]
        if dels:
            base = np.setdiff1d(
                base, np.array(dels, np.uint64), assume_unique=False
            )
        if adds:
            base = np.union1d(base, np.array(adds, np.uint64))
        return base.astype(np.uint64)

    def _merged_postings(
        self, extra_deltas: Optional[List[Posting]] = None
    ) -> Dict[int, Posting]:
        """uid -> winning posting (last writer wins by layer order)."""
        merged: Dict[int, Posting] = {p.uid: p for p in self.value_postings}
        for _, posts in self.deltas:
            for p in posts:
                merged[p.uid] = p
        for p in extra_deltas or []:
            merged[p.uid] = p
        return merged

    def get_value(
        self, lang: str = "", extra_deltas=None
    ) -> Optional[Val]:
        """Scalar value read (ref list.go Value/ValueForTag)."""
        merged = self._merged_postings(extra_deltas)
        p = merged.get(lang_uid(lang))
        if p is not None and p.op != OP_DEL and p.is_value:
            return p.val()
        if not lang:
            # fall back to any language (ref list.go:1990 ValueWithLockHeld)
            for uid in sorted(merged):
                p = merged[uid]
                if p.op != OP_DEL and p.is_value:
                    return p.val()
        return None

    def get_all_values(self, extra_deltas=None) -> List[Posting]:
        """All live value postings (list predicates / lang variants),
        posting-uid ascending — with farm-fingerprint uids this reproduces
        the reference's list-value JSON ordering exactly (posting lists
        iterate uid order, ref list.go Iterate)."""
        merged = self._merged_postings(extra_deltas)
        return [
            merged[uid]
            for uid in sorted(merged)
            if merged[uid].op != OP_DEL and merged[uid].is_value
        ]

    def is_empty(self, extra_deltas=None) -> bool:
        return (
            len(self.uids(extra_deltas)) == 0
            and not self.get_all_values(extra_deltas)
        )

    # -- rollup --------------------------------------------------------------

    def rollup(self) -> Tuple[bytes, int, List[Tuple[int, bytes]]]:
        """Compact all layers into a fresh rollup record.

        Returns (main_record_bytes, ts, parts) where parts is
        [(start_uid, part_record_bytes)] — non-empty when the uid set
        exceeds MAX_PART_UIDS and the list splits (ref posting/list.go:1416
        Rollup + :1590 splitUpList re-split; part keys via keys.SplitKey).
        Uid-edge postings that carry facets are kept alongside the pack
        (the pack stores only the uid set; facets live on the posting).
        """
        uids = self.uids()
        posts = self.get_all_values()
        live = set(int(u) for u in uids)
        merged = self._merged_postings()
        for uid in sorted(merged):
            p = merged[uid]
            if not p.is_value and p.op != OP_DEL and p.facets and uid in live:
                posts.append(p)
        ts = max(
            [self.min_ts] + [t for t, _ in self.deltas]
        )
        if len(uids) <= MAX_PART_UIDS:
            return encode_rollup(uidpack.encode(uids), posts), ts, []
        # split: half-threshold parts so in-place growth has headroom
        # before the next re-split (mirrors the reference's size targets)
        per = max(1, MAX_PART_UIDS // 2)
        parts: List[Tuple[int, bytes]] = []
        starts: List[int] = []
        for i in range(0, len(uids), per):
            chunk = uids[i : i + per]
            starts.append(int(chunk[0]))
            parts.append(
                (int(chunk[0]), encode_rollup(uidpack.encode(chunk), []))
            )
        return (
            encode_rollup(uidpack.EMPTY, posts, split_starts=starts), ts, parts
        )
