"""MemoryLayer: shared read cache for decoded posting lists.

Mirrors /root/reference/posting/mvcc.go:387 MemoryLayer (ristretto-backed
cache keyed by key bytes): decoding a posting list (KV versions -> record
parse -> UidPack decode) is the host-side hot cost of every traversal
level. This cache keeps *decoded* PostingLists keyed by (key, newest
version ts) so repeated reads — including the same predicate reached from
different query roots — skip straight to the materialized form.

Invalidation mirrors the reference (mvcc.go:510 updates on commit): the
engine calls `invalidate(keys)` with every committed key. Entries also
self-validate by comparing the KV's newest version ts, so even a missed
invalidation only costs a re-decode, never staleness.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Iterable, Optional, Tuple

from dgraph_tpu.posting.pl import PostingList, decode_cold
from dgraph_tpu.utils.observe import METRICS, add_span_attr


class MemoryLayer:
    def __init__(self, max_entries: Optional[int] = None):
        if max_entries is None:
            # must exceed the touched-key count of one large traversal
            # level or the LRU thrashes (a 5M-edge 2-hop touches ~140k
            # lists); decoded entries are small, ~300B typical
            from dgraph_tpu.x import config

            max_entries = int(config.get("MEMLAYER_ENTRIES"))
        self.max_entries = max_entries
        self._lock = threading.Lock()
        # key -> (newest_version_ts, PostingList); LRU by insertion order
        self._cache: "OrderedDict[bytes, Tuple[int, PostingList]]" = (
            OrderedDict()
        )
        self.hits = 0
        self.misses = 0
        # query/valcol.ValueColumns, set by an engine that tells it of
        # every commit before the commit is readable (api/server.py);
        # None: no resident value columns over this store
        self.value_columns = None

    @staticmethod
    def _fast_state(kv, read_ts: int):
        """(seq, complete) for the no-revalidation fast path. An entry is
        reusable WITHOUT a per-key probe by a reader at R2 iff:
          - the KV's global mutation counter hasn't moved since the entry
            was built (store content identical), AND
          - the entry was a COMPLETE view when built — its creation
            read_ts covered every version in the store
            (max_write_ts <= creation read_ts), AND
          - R2 >= the entry's creation read_ts.
        The completeness condition closes the race where a query holding
        an older read_ts caches a partial view after a newer commit."""
        fn = getattr(kv, "mut_seq", None)
        if fn is None:
            return None, False
        mx = getattr(kv, "max_write_ts", None)
        return fn(), (mx is not None and mx() <= read_ts)

    @staticmethod
    def _fast_hit(ent, seq, read_ts: int) -> bool:
        return (
            seq is not None
            and ent[2] == seq
            and ent[4]
            and read_ts >= ent[3]
        )

    def read(self, kv, key: bytes, read_ts: int) -> PostingList:
        """Read-through: returns a PostingList valid at read_ts.

        Cached entries are keyed by the newest version <= read_ts, so a
        reader at an older ts never sees future versions. The version list
        is fetched ONCE and the cache key derives from it — deriving it
        from a separate earlier kv.get would race concurrent commits and
        cache future versions under an old ts. Complete entries skip the
        probe while the store is unchanged (_fast_state)."""
        seq, complete = self._fast_state(kv, read_ts)
        hit = None
        with self._lock:
            got = self._cache.get(key)
            if got is not None and self._fast_hit(got, seq, read_ts):
                self._cache.move_to_end(key)
                hit = got
        if hit is None:
            if hasattr(kv, "versions_batch"):
                # one miss path: the store's unlocked probe, the one-pass
                # decoder, one acquisition of the lock (read_many)
                return self.read_many(kv, (key,), read_ts)[key]
            versions = kv.versions(key, read_ts)
            newest_ts = versions[0][0] if versions else 0
            with self._lock:
                got = self._cache.get(key)
                if got is not None and got[0] == newest_ts:
                    self._cache[key] = (
                        newest_ts, got[1], seq, read_ts, complete
                    )
                    self._cache.move_to_end(key)
                    hit = got
        if hit is not None:
            self.hits += 1
            METRICS.inc("memlayer_hits_total")
            return hit[1]
        self.misses += 1
        METRICS.inc_many({
            "memlayer_misses_total": 1,
            'level_cold_keys_total{path="general"}': 1,
        })
        add_span_attr("cold", 1)
        pl = PostingList.from_versions(key, versions, kv=kv, read_ts=read_ts)
        with self._lock:
            self._cache[key] = (newest_ts, pl, seq, read_ts, complete)
            self._cache.move_to_end(key)
            while len(self._cache) > self.max_entries:
                self._cache.popitem(last=False)
        return pl

    def read_many(self, kv, keys, read_ts: int) -> dict:
        """Batched read-through: one kv.versions_batch for every key (the
        LSM backend probes each table monotonically instead of per-key).
        Returns {key: PostingList}. Falls back to per-key read when the
        backend has no batch API.

        The lock is taken ONCE a level, at the end, for the LRU touches
        and the new entries, with nothing but dict operations inside it:
        sixteen readers that each held it three times a level, loops and
        all, queued behind one another (PERF.md, PR 35). The look-ups
        before it are single `dict.get`s of immutable entries, each
        atomic under the interpreter's lock, and an entry that is
        dropped meanwhile is as valid for this reader as one dropped
        just after a locked look-up was."""
        keys = list(dict.fromkeys(keys))  # dedupe: decode each key once
        vb = getattr(kv, "versions_batch", None)
        if vb is None:
            return {k: self.read(kv, k, read_ts) for k in keys}
        seq, complete = self._fast_state(kv, read_ts)
        cache = self._cache
        out = {}
        need = []
        for k in keys:
            ent = cache.get(k)
            # _fast_hit, spelled out: a call a key is a fifth of a hit
            if (
                ent is not None and seq is not None and ent[2] == seq
                and ent[4] and read_ts >= ent[3]
            ):
                out[k] = ent[1]
            else:
                need.append(k)
        hit_keys = list(out) if need else keys
        fresh = []  # (key, entry) to publish
        fast = general = 0
        if need:
            got = vb(need, read_ts)
            # the decoder beside the general one: for a plaintext store
            # probed natively (an encrypted store, a process without
            # the native library and MemKV keep the one decoder)
            cold = decode_cold if getattr(kv, "native_probe", False) else None
            for k in need:
                versions = got.get(k, ())
                newest_ts = versions[0][0] if versions else 0
                ent = cache.get(k)
                if ent is not None and ent[0] == newest_ts:
                    pl = ent[1]  # unchanged since it was decoded
                else:
                    pl = None
                    if cold is not None:
                        if not versions:
                            pl = PostingList(k)  # no record, none to decode
                        elif len(versions) == 1:
                            pl = cold(k, newest_ts, versions[0][1])
                    if pl is None:
                        pl = PostingList.from_versions(
                            k, versions, kv=kv, read_ts=read_ts
                        )
                        general += 1
                    else:
                        fast += 1
                out[k] = pl
                fresh.append((k, (newest_ts, pl, seq, read_ts, complete)))
        missed = fast + general
        with self._lock:
            try:
                for k in hit_keys:
                    cache.move_to_end(k)
            except KeyError:
                # dropped since the look-up: a commit, a tablet move, the LRU
                for k in hit_keys:
                    if k in cache:
                        cache.move_to_end(k)
            if fresh:
                for k, ent in fresh:
                    cache[k] = ent
                    cache.move_to_end(k)
                while len(cache) > self.max_entries:
                    cache.popitem(last=False)
        self.hits += len(keys) - missed
        if not missed:
            METRICS.inc("memlayer_hits_total", len(keys))
            return out
        self.misses += missed
        METRICS.inc_many({
            "memlayer_hits_total": len(keys) - missed,
            "memlayer_misses_total": missed,
            'level_cold_keys_total{path="fast"}': fast,
            'level_cold_keys_total{path="general"}': general,
        })
        add_span_attr("cold", missed)
        return out

    def invalidate(self, keys: Iterable[bytes]):
        keys = list(keys)
        with self._lock:
            for k in keys:
                self._cache.pop(k, None)
        # the device (HBM) operand cache mirrors this invalidation
        from dgraph_tpu.query.dispatch import DISPATCHER

        DISPATCHER.device_cache.invalidate(keys)

    def invalidate_prefix(self, prefixes: Iterable[bytes]):
        """Drop every cached entry whose key starts with any prefix —
        the tablet-move/drop-attr invalidation: only the moved
        predicate's data/split/index entries go; an unrelated
        predicate's decoded lists survive (the old movers cleared the
        whole layer)."""
        pfx = tuple(bytes(p) for p in prefixes)
        if not pfx:
            return
        with self._lock:
            hit = [k for k in self._cache if k.startswith(pfx)]
            for k in hit:
                del self._cache[k]
        from dgraph_tpu.query.dispatch import DISPATCHER

        DISPATCHER.device_cache.invalidate_prefix(pfx)
        if self.value_columns is not None:
            self.value_columns.invalidate_prefix(pfx)

    def clear(self):
        with self._lock:
            self._cache.clear()

    def stats(self) -> dict:
        return {
            "entries": len(self._cache),
            "hits": self.hits,
            "misses": self.misses,
        }
