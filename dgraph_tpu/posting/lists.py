"""Transaction-local posting cache + Txn object.

Mirrors /root/reference/posting/lists.go:63 LocalCache (per-txn view that
layers uncommitted deltas over the store) and posting/oracle.go:40 Txn.
Commit writes one delta record per touched key at the commit ts
(ref posting/mvcc.go:266 CommitToDisk).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from dgraph_tpu.posting.pl import (
    Posting,
    PostingList,
    encode_delta,
    fingerprint64,
)
from dgraph_tpu.storage.kv import KV
from dgraph_tpu.utils.observe import METRICS


class ReadCounters:
    """Process-wide cache round-trip accounting (level_batch_read_calls
    benchmark + fan-out observability). Plain unsynchronized ints: point
    reads are the hottest call sites in the engine, so a lock per
    increment (METRICS.inc) is not acceptable there; a lost increment
    under racing threads is noise, not corruption. `publish()` mirrors
    the totals into the Prometheus registry as gauges."""

    __slots__ = ("point_reads", "batch_reads", "batch_read_keys")

    def __init__(self):
        self.point_reads = 0
        self.batch_reads = 0
        self.batch_read_keys = 0

    def snapshot(self) -> dict:
        return {
            "point_reads": self.point_reads,
            "batch_reads": self.batch_reads,
            "batch_read_keys": self.batch_read_keys,
        }

    def publish(self):
        METRICS.set_gauge("cache_point_reads", float(self.point_reads))
        METRICS.set_gauge("cache_batch_reads", float(self.batch_reads))
        METRICS.set_gauge(
            "cache_batch_read_keys", float(self.batch_read_keys)
        )


READ_COUNTERS = ReadCounters()


def cache_tier_snapshot(mem=None) -> dict:
    """Cache-tier counter snapshot for the EXPLAIN `cache` block (one
    shared mapping — the entry points diff two of these around a debug
    query). Process-wide counters: under concurrent queries a delta
    attributes a class of work, not an exact per-query count."""
    out = READ_COUNTERS.snapshot()
    if mem is not None:
        out["memlayer_hits"] = mem.hits
        out["memlayer_misses"] = mem.misses
    return out


class LocalCache:
    """Per-txn read-through cache with uncommitted delta overlay.

    When a shared MemoryLayer is provided, decoded lists are reused across
    transactions/queries (ref posting/mvcc.go MemoryLayer)."""

    def __init__(self, kv: KV, read_ts: int, mem=None):
        self.kv = kv
        self.read_ts = read_ts
        self.mem = mem
        self._plists: Dict[bytes, PostingList] = {}
        self.deltas: Dict[bytes, List[Posting]] = {}

    def get(self, key: bytes) -> PostingList:
        pl = self._plists.get(key)
        if pl is None:
            if self.mem is not None:
                pl = self.mem.read(self.kv, key, self.read_ts)
            else:
                pl = PostingList.from_versions(
                    key,
                    self.kv.versions(key, self.read_ts),
                    kv=self.kv,
                    read_ts=self.read_ts,
                )
            self._plists[key] = pl
        return pl

    def prefetch(self, keys_list) -> None:
        """Batch-read many posting lists ahead of a per-key loop (level-
        batched fan-out, uid_in probes). On the LSM backend this becomes
        one monotone multi-key probe per table instead of a seek per key
        (ref badger iterator prefetch / MultiGet)."""
        if self.mem is None:
            return
        missing = [k for k in keys_list if k not in self._plists]
        if len(missing) < 16:
            return
        self._plists.update(
            self.mem.read_many(self.kv, missing, self.read_ts)
        )

    # -- reads (uncommitted deltas visible to this txn) ----------------------

    def uids(self, key: bytes) -> np.ndarray:
        READ_COUNTERS.point_reads += 1
        return self.get(key).uids(self.deltas.get(key))

    def uids_tok(self, key: bytes):
        """(uids, version token). The token is the posting list's device-
        cache identity (key, latest_ts) — None when this txn has local
        deltas on the key (the materialized view is txn-private then)."""
        READ_COUNTERS.point_reads += 1
        pl = self.get(key)
        extra = self.deltas.get(key)
        uids = pl.uids(extra)
        tok = None if extra else (key, pl.latest_ts)
        return uids, tok

    # -- level-batched reads (one task per (predicate, level)) ---------------

    def _resolve_many(self, keys_list) -> None:
        """Materialize PostingLists for every key in ONE memlayer pass
        (single lock acquisition + one versions_batch LSM probe) instead
        of N read-throughs."""
        missing = [k for k in keys_list if k not in self._plists]
        if not missing:
            return
        if self.mem is not None:
            self._plists.update(
                self.mem.read_many(self.kv, missing, self.read_ts)
            )
        else:
            for k in missing:
                if k not in self._plists:
                    self.get(k)

    def uids_many(self, keys_list):
        """Batched uid read for a whole traversal level: returns
        (flat, offsets, toks) where row i = flat[offsets[i]:offsets[i+1]]
        is key i's sorted uid set and toks[i] is its device-cache version
        token ((key, latest_ts), None when txn-local deltas exist).

        One memlayer/LSM pass resolves every list; all-committed no-delta
        packs then decode through ONE native pass (codec.cpp
        packs_decode_many) into the shared flat buffer — each list adopts
        its slice as the memoized materialization, so later point reads
        stay free. Lists with uid deltas fall back to the layered path."""
        from dgraph_tpu.codec import uidpack

        n = len(keys_list)
        READ_COUNTERS.batch_reads += 1
        READ_COUNTERS.batch_read_keys += n
        self._resolve_many(keys_list)
        rows: list = [None] * n
        toks: list = [None] * n
        batch = []  # (row index, PostingList) pending the one-pass decode
        for i, k in enumerate(keys_list):
            pl = self._plists.get(k)
            if pl is None:
                pl = self.get(k)
            extra = self.deltas.get(k)
            if not extra:
                toks[i] = (k, pl.latest_ts)
                if pl._uids_cache is not None:
                    rows[i] = pl._uids_cache
                elif not pl.has_uid_deltas():
                    batch.append((i, pl))
                else:
                    rows[i] = pl.uids(None)
            else:
                rows[i] = pl.uids(extra)
        if batch:
            flat_b, offs_b = uidpack.decode_packs(
                [pl.merged_pack() for _, pl in batch]
            )
            for j, (i, pl) in enumerate(batch):
                row = flat_b[offs_b[j] : offs_b[j + 1]]
                pl.adopt_uids(row)
                rows[i] = row
        from dgraph_tpu.query.ragged import pack_rows

        flat, offsets = pack_rows(rows)
        METRICS.inc("level_batch_read_bytes", int(flat.nbytes))
        return flat, offsets, toks

    def values_many(self, keys_list):
        """Batched value-posting read: one memlayer/LSM pass for the whole
        level, then the per-list merge (values are heterogeneous posting
        objects — the batched KV probe is the win, not the merge loop).
        Returns a list aligned with keys_list."""
        READ_COUNTERS.batch_reads += 1
        READ_COUNTERS.batch_read_keys += len(keys_list)
        self._resolve_many(keys_list)
        return [
            self.get(k).get_all_values(self.deltas.get(k))
            for k in keys_list
        ]

    def scan_values(self, prefix: bytes):
        """(key, value) of every key under a data prefix that holds an
        untagged scalar value at this read timestamp, in key order: one
        pass over the store, each record decoded once and dropped (a
        value column's build, query/valcol.py: a predicate's worth of
        lists would push the working set out of the MemoryLayer). The
        caller has checked that this txn holds no delta under the
        prefix."""
        from dgraph_tpu.posting.pl import decode_cold

        kv, read_ts = self.kv, self.read_ts
        for k, versions in kv.iterate_versions(prefix, read_ts):
            pl = None
            if len(versions) == 1:
                pl = decode_cold(k, versions[0][0], versions[0][1])
            if pl is None:
                pl = PostingList.from_versions(
                    k, versions, kv=kv, read_ts=read_ts
                )
            v = pl.get_value("", None)
            if v is not None:
                yield k, v

    def packed_operand(self, key: bytes):
        """The posting list as a compressed-domain dispatcher operand
        (query/dispatch.PackedOperand), or None when any uid delta —
        committed or txn-local — makes the packed layers stale. Carries the
        list's block-cached partial decoder, so candidate blocks decode
        once per list per commit epoch."""
        extra = self.deltas.get(key)
        if extra and any(not p.is_value for p in extra):
            return None
        pl = self.get(key)
        pack = pl.packed()
        if pack is None:
            return None
        from dgraph_tpu.query.dispatch import PackedOperand

        return PackedOperand(
            pack,
            decode_fn=pl.decode_blocks,
            uids=pl._uids_cache,
            uids_fn=pl.uids,
        )

    def value(self, key: bytes, lang: str = ""):
        READ_COUNTERS.point_reads += 1
        return self.get(key).get_value(lang, self.deltas.get(key))

    def values(self, key: bytes) -> List[Posting]:
        READ_COUNTERS.point_reads += 1
        return self.get(key).get_all_values(self.deltas.get(key))

    def has(self, key: bytes) -> bool:
        return not self.get(key).is_empty(self.deltas.get(key))

    def edge_facets(self, key: bytes):
        """Facets per target uid for a uid-edge list (ref facets on
        pb.Posting; used by @facets projection/filtering)."""
        merged = self.get(key)._merged_postings(self.deltas.get(key))
        out = {}
        for uid, p in merged.items():
            if not p.is_value and p.facets and p.op == 1:  # OP_SET
                out[uid] = p.get_facets()
        return out

    # -- writes --------------------------------------------------------------

    def add_delta(self, key: bytes, p: Posting):
        self.deltas.setdefault(key, []).append(p)


class Txn:
    """A read-write transaction (ref posting/oracle.go:40 Txn)."""

    def __init__(self, kv: KV, start_ts: int, mem=None):
        self.start_ts = start_ts
        self.cache = LocalCache(kv, start_ts, mem=mem)
        self.conflict_keys: set[int] = set()
        self.committed = False
        self.aborted = False
        # columnar write set (posting/colwrite): engines attach one via
        # colwrite.maybe_enable when the native batch-apply path may
        # consume this txn's writes at commit; None = classic deltas
        self.col = None

    def add_conflict_key(self, key: bytes, extra: bytes = b""):
        """Fingerprint written keys for oracle conflict detection
        (ref posting/list.go:842 GetConflictKey)."""
        self.conflict_keys.add(fingerprint64(key + b"|" + extra))

    def materialize_cols(self):
        """Read-your-writes hook: convert any collected columnar edges
        back into Python deltas before this txn reads its own writes
        (query / upsert entry points call this)."""
        if self.col is not None:
            from dgraph_tpu.posting import colwrite

            if self.col.pending:
                colwrite.count_fallback("read", len(self.col.shapes))
            colwrite.materialize(self)

    def pending_postings(self) -> int:
        """Postings this txn will write at commit (admission control's
        write-size signal): Python deltas plus the columnar estimate."""
        n = sum(len(p) for p in self.cache.deltas.values())
        if self.col is not None:
            n += self.col.nposts_est
        return n

    def write_deltas(self, kv: KV, commit_ts: int):
        """Persist all pending deltas at commit_ts (CommitToDisk)."""
        if self.col is not None and self.col.pending:
            from dgraph_tpu.posting import colwrite

            for key, rec, _attr in colwrite.encode_txn(self):
                kv.put(key, commit_ts, rec)
        for key, posts in self.cache.deltas.items():
            if posts:
                kv.put(key, commit_ts, encode_delta(posts))
