"""Badger-style key layout for the host posting store.

Mirrors the semantics of /root/reference/x/keys.go (DataKey:201,
IndexKey:258, ReverseKey:223, CountKey:279, SchemaKey:174, TypeKey:186):
keys order by (namespace|attr) prefix first so a whole predicate (tablet) is
one contiguous range — that contiguity is what makes predicate-level
sharding, moves, and prefix iteration work.

Layout (bytes, big-endian so lexicographic order == numeric order):
  [tag:1][len(nsattr):2][nsattr][kind:1][suffix]
    tag:    0x00 data/index/reverse/count, 0x01 schema, 0x02 type
    nsattr: 8-byte namespace (big-endian u64) + attr utf-8
            (ref x/keys.go NamespaceAttr — namespace is baked into the attr)
    kind:   0x00 data(uid u64) | 0x02 index(term bytes) | 0x04 reverse(uid)
            | 0x08 count(u32 count + rev flag)
Split keys (multi-part posting lists, ref x/keys.go:42 ByteSplit) append a
part id; handled by posting/ when lists exceed the split threshold.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass
from typing import Optional

import numpy as np

TAG_DEFAULT = 0x00
TAG_SCHEMA = 0x01
TAG_TYPE = 0x02
TAG_SPLIT = 0x03  # multi-part posting-list part (ref x/keys.go:512 SplitKey)

KIND_DATA = 0x00
KIND_INDEX = 0x02
KIND_REVERSE = 0x04
KIND_COUNT = 0x08

GALAXY_NS = 0  # default namespace (ref x/keys.go GalaxyNamespace)


def namespace_attr(ns: int, attr: str) -> bytes:
    return struct.pack(">Q", ns) + attr.encode("utf-8")


def attr_from_nsattr(nsattr: bytes) -> tuple[int, str]:
    ns = struct.unpack(">Q", nsattr[:8])[0]
    return ns, nsattr[8:].decode("utf-8")


def _prefix(tag: int, nsattr: bytes) -> bytes:
    return struct.pack(">BH", tag, len(nsattr)) + nsattr


# key-kind prefix cache: the (tag, ns, attr, kind) head of a key is
# attr-constant, and the mutation path builds several keys per edge —
# re-packing the prefix each time was measurable on the live write
# path. Bounded by a wholesale clear (attrs are few; a clear only
# costs re-derivation).
_PFX_CACHE: dict = {}


def _kind_prefix(kind: int, attr: str, ns: int) -> bytes:
    ck = (kind, attr, ns)
    p = _PFX_CACHE.get(ck)
    if p is None:
        if len(_PFX_CACHE) > 8192:
            _PFX_CACHE.clear()
        p = _PFX_CACHE[ck] = (
            _prefix(TAG_DEFAULT, namespace_attr(ns, attr)) + bytes([kind])
        )
    return p


def DataKey(attr: str, uid: int, ns: int = GALAXY_NS) -> bytes:
    return _kind_prefix(KIND_DATA, attr, ns) + struct.pack(">Q", uid)


def ReverseKey(attr: str, uid: int, ns: int = GALAXY_NS) -> bytes:
    return _kind_prefix(KIND_REVERSE, attr, ns) + struct.pack(">Q", uid)


def _uid_keys(kind: int, attr: str, uids, ns: int) -> list[bytes]:
    """One key per uid, from the uids' array in one pass: the prefix
    beside each uid's big-endian bytes as rows of one byte matrix,
    handed out as `bytes` row by row by numpy."""
    p = _kind_prefix(kind, attr, ns)
    be = np.asarray(uids, np.uint64).astype(">u8")
    rows = np.empty((len(be), len(p) + 8), np.uint8)
    rows[:, : len(p)] = np.frombuffer(p, np.uint8)
    rows[:, len(p) :] = be.view(np.uint8).reshape(-1, 8)
    return rows.view(f"V{len(p) + 8}").ravel().tolist()


def DataKeys(attr: str, uids, ns: int = GALAXY_NS) -> list[bytes]:
    """[DataKey(attr, u, ns) for u in uids], without the walk."""
    return _uid_keys(KIND_DATA, attr, uids, ns)


def ReverseKeys(attr: str, uids, ns: int = GALAXY_NS) -> list[bytes]:
    """[ReverseKey(attr, u, ns) for u in uids], without the walk."""
    return _uid_keys(KIND_REVERSE, attr, uids, ns)


def IndexKey(attr: str, term: bytes, ns: int = GALAXY_NS) -> bytes:
    if isinstance(term, str):
        term = term.encode("utf-8")
    return _kind_prefix(KIND_INDEX, attr, ns) + term


def CountKey(attr: str, count: int, reverse: bool = False, ns: int = GALAXY_NS) -> bytes:
    return (
        _kind_prefix(KIND_COUNT, attr, ns)
        + struct.pack(">I", count)
        + (b"\x01" if reverse else b"\x00")
    )


def SchemaKey(attr: str, ns: int = GALAXY_NS) -> bytes:
    return _prefix(TAG_SCHEMA, namespace_attr(ns, attr))


def TypeKey(name: str, ns: int = GALAXY_NS) -> bytes:
    return _prefix(TAG_TYPE, namespace_attr(ns, name))


def PredicatePrefix(attr: str, ns: int = GALAXY_NS) -> bytes:
    """Prefix covering all data/index/reverse/count keys of one predicate."""
    return _prefix(TAG_DEFAULT, namespace_attr(ns, attr))


def DataPrefix(attr: str, ns: int = GALAXY_NS) -> bytes:
    return PredicatePrefix(attr, ns) + bytes([KIND_DATA])


def IndexPrefix(attr: str, ns: int = GALAXY_NS) -> bytes:
    return PredicatePrefix(attr, ns) + bytes([KIND_INDEX])


def ReversePrefix(attr: str, ns: int = GALAXY_NS) -> bytes:
    return PredicatePrefix(attr, ns) + bytes([KIND_REVERSE])


def CountPrefix(attr: str, ns: int = GALAXY_NS) -> bytes:
    return PredicatePrefix(attr, ns) + bytes([KIND_COUNT])


def SplitKey(base_key: bytes, start_uid: int) -> bytes:
    """Part key of a multi-part posting list: the base (data/index/reverse)
    key re-tagged into the split region + the part's first uid
    (ref x/keys.go:512 SplitKey — same idea, separate key region so data
    prefix iteration never sees parts)."""
    if base_key[0] != TAG_DEFAULT:
        raise ValueError("only default-region keys can be split")
    return bytes([TAG_SPLIT]) + base_key[1:] + struct.pack(">Q", start_uid)


def base_of_split(split_key: bytes) -> tuple[bytes, int]:
    """Inverse of SplitKey: (base_key, start_uid)."""
    if split_key[0] != TAG_SPLIT:
        raise ValueError("not a split key")
    start = struct.unpack(">Q", split_key[-8:])[0]
    return bytes([TAG_DEFAULT]) + split_key[1:-8], start


def SplitPredicatePrefix(attr: str, ns: int = GALAXY_NS) -> bytes:
    """Prefix covering every part key of one predicate (for drops/moves)."""
    return bytes([TAG_SPLIT]) + PredicatePrefix(attr, ns)[1:]


@dataclass
class ParsedKey:
    """Decoded key (ref x/keys.go:330 ParsedKey)."""

    tag: int
    ns: int
    attr: str
    kind: Optional[int] = None
    uid: Optional[int] = None
    term: Optional[bytes] = None
    count: Optional[int] = None
    count_reverse: bool = False
    split_start: Optional[int] = None  # set for TAG_SPLIT part keys

    @property
    def is_data(self):
        return self.tag == TAG_DEFAULT and self.kind == KIND_DATA

    @property
    def is_index(self):
        return self.tag == TAG_DEFAULT and self.kind == KIND_INDEX

    @property
    def is_reverse(self):
        return self.tag == TAG_DEFAULT and self.kind == KIND_REVERSE

    @property
    def is_count(self):
        return self.tag == TAG_DEFAULT and self.kind == KIND_COUNT

    @property
    def is_schema(self):
        return self.tag == TAG_SCHEMA

    @property
    def is_type(self):
        return self.tag == TAG_TYPE


def attr_of(key_or_prefix: bytes) -> Optional[str]:
    """Extract the attr from a key OR a bare prefix (which lacks the
    kind/uid suffix a full parse_key needs)."""
    if len(key_or_prefix) < 3:
        return None
    tag, nlen = struct.unpack_from(">BH", key_or_prefix, 0)
    if len(key_or_prefix) < 3 + nlen:
        return None
    _, attr = attr_from_nsattr(key_or_prefix[3 : 3 + nlen])
    return attr


def parse_key(key: bytes) -> ParsedKey:
    tag, nlen = struct.unpack_from(">BH", key, 0)
    nsattr = key[3 : 3 + nlen]
    ns, attr = attr_from_nsattr(nsattr)
    rest = key[3 + nlen :]
    if tag in (TAG_SCHEMA, TAG_TYPE):
        return ParsedKey(tag=tag, ns=ns, attr=attr)
    if tag == TAG_SPLIT:
        base, start = base_of_split(key)
        pk = parse_key(base)
        pk.tag = TAG_SPLIT
        pk.split_start = start
        return pk
    kind = rest[0]
    body = rest[1:]
    pk = ParsedKey(tag=tag, ns=ns, attr=attr, kind=kind)
    if kind in (KIND_DATA, KIND_REVERSE):
        pk.uid = struct.unpack(">Q", body)[0]
    elif kind == KIND_INDEX:
        pk.term = body
    elif kind == KIND_COUNT:
        pk.count = struct.unpack(">I", body[:4])[0]
        pk.count_reverse = body[4:5] == b"\x01"
    return pk
