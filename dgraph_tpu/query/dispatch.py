"""Batched set-op dispatcher: the device boundary of the query engine.

The reference fans out one goroutine per UID-chunk per attribute
(/root/reference/worker/task.go:816 x.DivideAndRule, query/query.go:2459
child goroutines) and runs scalar intersect loops. Here the SubGraph
executor *collects* every set operation of a query level and hands the whole
batch to this dispatcher, which:

  1. splits u64 operands into hi-32 segments (codec/uidpack.py) so kernels
     run in uint32 local space,
  2. buckets operand pairs by padded (pow2) shapes to bound XLA
     recompilation,
  3. runs one vmapped kernel per bucket (ops/setops.py),
  4. falls back to numpy for tiny batches where PCIe/dispatch overhead
     exceeds the work (the reference's CPU does a 10-vs-1M intersect in
     ~2.4us — algo/benchmarks:45 — so small singleton ops stay host-side).

This is the TPU analog of the adaptive strategy choice in
algo/uidlist.go:142-168 (linear/jump/binary by ratio): we pick host-numpy vs
device-batch by total work and batch width.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from dgraph_tpu.codec import uidpack
from dgraph_tpu.codec.uidpack import join_segments, split_segments
from dgraph_tpu.ops import packed_setops, setops, valcol
from dgraph_tpu.query import ragged
from dgraph_tpu.utils.observe import METRICS, TRACER
from dgraph_tpu.x import config, device

# Below this much total work, host kernels win (dispatch overhead
# dominates). Unset, the threshold follows the platform the process
# serves from (x/device.py): on a CPU backend — only ever by request —
# XLA dispatch never beat the native host kernels (32 rows of 16
# against a shared operand of 1<<10 to 1<<22 ids, host path against
# the jitted one on XLA-CPU, one CPU core: no crossover at any size),
# so everything stays on host; on an accelerator it is 1<<15, a value
# no chip sweep has tuned yet.
# env semantics kept from earlier rounds: setting 0 means "always use
# the device" (total < 0 was never true); unset means platform-aware auto
_env_min_total = config.get("DEVICE_MIN_TOTAL")
_DEVICE_MIN_TOTAL = (
    0 if _env_min_total is None else max(1, int(_env_min_total))
)
_ACCEL_MIN_TOTAL = 1 << 15
_HOST_ONLY = 1 << 62
# A shared operand at/above this size is row-sharded over the device mesh
# (multi-part list data plane) when >1 device is visible.
_SHARD_MIN_B = int(config.get("SHARD_MIN_B"))
# Packed-vs-decode crossover: an array x pack pair takes the
# compressed-domain path (ops/packed_setops.py) when |big| >= ratio *
# |small|. This is a HOST-side crossover of the native adaptive block
# engine (bitmap/packed hybrid containers, codec.cpp pack_pair_setop/
# pack_stream_setop), measured on one CPU core against a 1M-uid pack:
# from ratio 8 up the packed intersect matches or beats full decode +
# dense intersect (3.42 ms against 3.45 at 8, 1.01 against 1.80 at 64;
# below it loses, 5.78 against 4.95 at 4), down from the pre-engine
# 256. No chip run has read it. pack x pack pairs bypass the gate
# entirely: the pair engine streams BOTH operands compressed and on
# the same host stayed within 5% of decode-both or better at every
# ratio (2.70 ms against 4.06 at ratio 1, with ZERO decoded bytes),
# the per-BLOCK kernel pick inside it replacing the old whole-operand
# cliff. Without the engine the packed path decodes candidate blocks
# in Python, which only pays when selective: packed_min_ratio()
# re-applies the old cliff (256) there unless the env pins a value.
_PACKED_MIN_RATIO = int(config.get("PACKED_MIN_RATIO"))
_PACKED_FALLBACK_RATIO = 256
_FORCE_DEVICE = bool(config.get("FORCE_DEVICE"))
_MIN_PAD = 8


def _planner_enabled() -> bool:
    """The cost-based planner's knob (query/planner.py), read here
    without importing the planner — the chain-fold order hook must
    stay import-cycle-free."""
    return bool(config.get("QUERY_PLANNER"))


def _pow2(n: int) -> int:
    return max(_MIN_PAD, 1 << (max(1, n) - 1).bit_length())


def _pow4(n: int) -> int:
    """The bucket of a level's flat ids: the next power of FOUR. On a
    v5e the flat program costs 18.6 ns a padded element (4.9 ms at
    262,144) and 12.5-18.6 s to compile, whatever its size (PERF.md,
    PR 30): one compile buys the padding of thousands of requests, so
    the buckets are twice as far apart as `_pow2`'s. At most 4x the
    ids, 2.2x on average where `_pow2` gives 1.4x."""
    p = _pow2(n)
    return p if p.bit_length() % 2 else p * 2


def _flat_of(rows) -> Tuple[np.ndarray, np.ndarray]:
    """A level's (flat u64 ids, offsets): as it lies when the door is
    handed a `ragged.RaggedRows`, packed once from a list of rows."""
    if isinstance(rows, ragged.RaggedRows):
        return np.asarray(rows.flat, np.uint64), rows.offs
    return ragged.pack_rows([np.asarray(r, np.uint64) for r in rows])


def _cut_rows(op: str, flat, offs, member) -> ragged.RaggedRows:
    """The rows of a ragged (flat, offs) level after intersect or
    difference with a set, given each id's membership in it: ONE kept
    array and its new offsets, whose rows are views cut only when
    someone asks for them."""
    keep = ~member if op == "difference" else member
    return ragged.RaggedRows(*ragged.apply_mask(flat, offs, keep))


def _np_op(op: str, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    # host fallback: native C++ galloping/merge loops when compiled
    # (dgraph_tpu/native), numpy otherwise
    from dgraph_tpu import native

    if op == "intersect":
        return native.intersect(
            np.asarray(a, np.uint64), np.asarray(b, np.uint64)
        )
    if op == "difference":
        return native.difference(
            np.asarray(a, np.uint64), np.asarray(b, np.uint64)
        )
    if op == "union":
        return native.union(np.asarray(a, np.uint64), np.asarray(b, np.uint64))
    raise ValueError(op)


@dataclass(eq=False)
class _CacheEntry:
    """One DeviceCache entry. eq=False: `_by_key` holds the entry
    itself, hashed by identity, so no index operation hashes its
    token."""

    __slots__ = ("token", "arrays", "nbytes", "keys")
    token: tuple
    arrays: tuple
    nbytes: int
    keys: frozenset


class DeviceCache:
    """Device-resident operand cache — the HBM analog of the reference's
    MemoryLayer (posting/mvcc.go:387).

    Entries are uploaded, padded device arrays keyed by the posting lists'
    version identity ((key_bytes, latest_ts) tokens from LocalCache), so a
    hot predicate's pack uploads once and every later query level reuses
    the HBM copy. Commits invalidate by key (mvcc.go:510); a version bump
    also changes the token, so even a missed invalidation only costs a
    re-upload, never staleness. LRU-bounded by device bytes.

    A token can be a tuple of thousands of row tokens, and a tuple is
    hashed anew each time: only the `_entries` lookups of `get`, `put`
    and an invalidation's removal hash one, once each. An entry
    remembers the keys it was put under (a token names its keys: the
    same token always comes with the same ones), so an eviction or an
    invalidation costs the keys of the entries it removes, whatever the
    cache holds besides, and a key whose last entry went is forgotten."""

    def __init__(self, max_bytes: Optional[int] = None):
        self.max_bytes = max_bytes if max_bytes is not None else int(
            config.get("DEVCACHE_BYTES")
        )
        self._lock = threading.Lock()
        # cache token -> entry, in LRU order
        self._entries: "OrderedDict[tuple, _CacheEntry]" = OrderedDict()
        # key bytes -> live entries put under it (for commit invalidation)
        self._by_key: Dict[bytes, set] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, token: tuple):
        with self._lock:
            entry = self._entries.get(token)
            if entry is None:
                self.misses += 1
                METRICS.inc("device_cache_misses_total")
                return None
            self._entries.move_to_end(token)
            self.hits += 1
            METRICS.inc("device_cache_hits_total")
            return entry.arrays

    def put(self, token: tuple, keys_involved, arrays: tuple, nbytes: int):
        if nbytes > self.max_bytes:
            return
        with self._lock:
            if token in self._entries:
                return
            entry = _CacheEntry(
                token, arrays, nbytes, frozenset(keys_involved)
            )
            self._entries[token] = entry
            self._bytes += nbytes
            for k in entry.keys:
                self._by_key.setdefault(k, set()).add(entry)
            while self._bytes > self.max_bytes and self._entries:
                # popitem takes the hash the dict stored: no rehash
                _, old = self._entries.popitem(last=False)
                self._forget(old)
                self.evictions += 1
                METRICS.inc("device_cache_evictions_total")

    def _forget(self, entry: _CacheEntry) -> None:
        """Bookkeeping for an entry already out of `_entries`: its bytes,
        and its place under each of its keys (lock held)."""
        self._bytes -= entry.nbytes
        for k in entry.keys:
            held = self._by_key.get(k)
            if held is not None:
                held.discard(entry)
                if not held:
                    del self._by_key[k]

    def _invalidate_locked(self, keys) -> None:
        for k in keys:
            for entry in self._by_key.pop(k, ()):
                del self._entries[entry.token]
                self._forget(entry)

    def invalidate(self, keys) -> None:
        with self._lock:
            self._invalidate_locked(keys)

    def invalidate_prefix(self, prefixes) -> None:
        """Drop cached operands for every key under any prefix (the
        MemoryLayer's tablet-move invalidation, mirrored in HBM)."""
        pfx = tuple(bytes(p) for p in prefixes)
        if not pfx:
            return
        with self._lock:
            self._invalidate_locked([
                k for k in self._by_key
                if isinstance(k, (bytes, bytearray)) and bytes(k).startswith(pfx)
            ])

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._by_key.clear()
            self._bytes = 0

    def stats(self) -> dict:
        return {
            "entries": len(self._entries),
            "bytes": self._bytes,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "keys": len(self._by_key),
        }


class PackedOperand:
    """A posting list offered to the dispatcher in compressed (UidPack)
    form. The dispatcher decides per pair — size/selectivity threshold —
    whether to run the compressed-domain block-skip ops on it or to decode
    it once and take the dense path.

    `decode_fn` is the owning PostingList's block-cached partial decoder
    (posting/pl.py decode_blocks) so repeated traversals reuse decoded
    blocks; `uids_fn` is the list's memoized full materializer
    (PostingList.uids), so a dense-pair fallback decodes once per commit
    epoch, not once per query."""

    __slots__ = ("pack", "decode_fn", "uids_fn", "_uids")

    def __init__(self, pack, decode_fn=None, uids=None, uids_fn=None):
        self.pack = pack
        self.decode_fn = decode_fn
        self.uids_fn = uids_fn
        self._uids = uids

    def __len__(self) -> int:
        return self.pack.num_uids

    def decode(self) -> np.ndarray:
        if self._uids is None:
            if self.uids_fn is not None:
                # list-memoized: repeated fallbacks re-use the decode
                self._uids = self.uids_fn()
            else:
                # account the full decode so decode_bytes_per_query
                # reflects the fallback cost too
                packed_setops.COUNTERS.decoded_uids += self.pack.num_uids
                self._uids = uidpack.decode(self.pack)
        return self._uids


class _Sharded(NamedTuple):
    """A host operand `_run_device` uploads row-sharded over the mesh."""

    arr: np.ndarray
    sharding: object


def _as_array(x) -> np.ndarray:
    return x.decode() if isinstance(x, PackedOperand) else np.asarray(
        x, np.uint64
    )


class SetOpDispatcher:
    """Batches pairwise sorted-set ops onto the device."""

    def __init__(self):
        self._jit_cache: Dict[tuple, object] = {}
        # serializes first-compilation per (op, shape) key: under
        # concurrent high-QPS traffic two queries hitting the same
        # cold bucket must not both pay the XLA compile
        self._jit_lock = threading.Lock()
        self.device_cache = DeviceCache()

    def packed_min_ratio(self) -> int:
        """big/small size ratio above which an array x pack pair runs
        compressed-domain instead of full-decode + dense kernels (tuned
        crossover 8 with the native adaptive block engine; pack x pack
        pairs skip the gate — the per-block kernel pick (bitmap AND /
        bitmap probe / galloping merge / block skip) inside
        ops/packed_setops.py subsumes the whole-operand decision there).
        Without the engine, candidate blocks decode in Python and only
        selective pairs pay: the pre-engine cliff (256) re-applies unless
        DGRAPH_TPU_PACKED_MIN_RATIO is pinned explicitly."""
        if packed_setops.engine_available() or config.is_set(
            "PACKED_MIN_RATIO"
        ):
            return _PACKED_MIN_RATIO
        return max(_PACKED_MIN_RATIO, _PACKED_FALLBACK_RATIO)

    def _try_packed(self, op: str, a, b) -> Optional[np.ndarray]:
        """Run one (a, b) pair compressed-domain when an operand is packed
        and the pair clears the selectivity crossover (ratio 1 — always —
        when the native block engine is in); None -> caller takes the
        decoded dense path. Fallback candidate spans route back through
        run_pairs, so big spans still hit the vmapped device kernels.

        Debug-mode queries capture the decision inputs (operand sizes,
        packed-ness, the PACKED_MIN_RATIO gate, the verdict) into the
        EXPLAIN plan — see _note_plan_pair."""
        got = self._try_packed_inner(op, a, b)
        self._note_plan_pair(op, a, b, got is not None)
        return got

    def _note_plan_pair(self, op: str, a, b, packed: bool) -> None:
        from dgraph_tpu.utils.observe import current_plan

        plan = current_plan()
        if plan is None:
            return
        a_packed = isinstance(a, PackedOperand)
        b_packed = isinstance(b, PackedOperand)
        plan.note_setop(
            {
                "site": "pair",
                "op": op,
                "a": int(len(a)),
                "b": int(len(b)),
                "a_packed": a_packed,
                "b_packed": b_packed,
                # a packed operand whose decode is memoized takes the
                # dense path regardless of the ratio (sunk cost)
                "decode_sunk": bool(
                    (not a_packed or a._uids is not None)
                    and (not b_packed or b._uids is not None)
                ),
                "min_ratio": int(self.packed_min_ratio()),
                "verdict": "packed" if packed else "decoded",
            }
        )

    def _try_packed_inner(self, op: str, a, b) -> Optional[np.ndarray]:
        if all(
            not isinstance(x, PackedOperand) or x._uids is not None
            for x in (a, b)
        ):
            # every packed operand's full decode is already memoized (on
            # the operand / owning PostingList): the decode cost is sunk,
            # so the dense kernels win regardless of selectivity
            return None
        r = self.packed_min_ratio()
        # both sides compressed: the pair engine skips BOTH decodes —
        # break-even-or-better at every ratio with zero decoded bytes
        # (_PACKED_MIN_RATIO's comment has the host measurement) — so
        # no ratio gate when it's available
        both = isinstance(a, PackedOperand) and isinstance(b, PackedOperand)
        if op in ("intersect", "difference") and isinstance(b, PackedOperand):
            if (
                both and packed_setops.engine_available()
            ) or len(b) >= r * max(1, len(a)):
                if isinstance(a, PackedOperand):
                    # both packed: the pair engine runs block-pair kernels
                    # with BOTH sides compressed. Intersect's fallback
                    # forwards both block-cached decoders so hot lists
                    # decode each candidate block once; difference needs
                    # all of `a` materialized on the fallback, so without
                    # the engine it goes through the operand's memoized
                    # decode instead of a.pack (a fresh full decode).
                    if op == "intersect":
                        return packed_setops.intersect_packed(
                            a.pack,
                            b.pack,
                            decode_b=b.decode_fn,
                            runner=self.run_pairs,
                            decode_a=a.decode_fn,
                        )
                    if packed_setops.engine_available():
                        return packed_setops.difference_packed(
                            a.pack,
                            b.pack,
                            decode_b=b.decode_fn,
                            runner=self.run_pairs,
                        )
                    return packed_setops.difference_packed(
                        _as_array(a),
                        b.pack,
                        decode_b=b.decode_fn,
                        runner=self.run_pairs,
                    )
                fn = (
                    packed_setops.intersect_packed
                    if op == "intersect"
                    else packed_setops.difference_packed
                )
                return fn(
                    _as_array(a),
                    b.pack,
                    decode_b=b.decode_fn,
                    runner=self.run_pairs,
                )
        if op == "intersect" and isinstance(a, PackedOperand):
            if len(a) >= r * max(1, len(b)):
                return packed_setops.intersect_packed(
                    _as_array(b),
                    a.pack,
                    decode_b=a.decode_fn,
                    runner=self.run_pairs,
                )
        return None

    def _min_total(self) -> int:
        """Device threshold: FORCE_DEVICE, the env override, else by
        platform. Reading the platform initializes the backend; one that
        fails to come up, or comes up as an unrequested CPU, raises here
        and so fails the request (x/device.py)."""
        platform = device.platform()
        if _FORCE_DEVICE:
            return 0
        if _DEVICE_MIN_TOTAL:
            return _DEVICE_MIN_TOTAL
        return _HOST_ONLY if platform == "cpu" else _ACCEL_MIN_TOTAL

    def _run_device(
        self, family: str, fetch, operands, ids: int, padded: int,
        keep=None, spans: str = "setop", said=None,
    ) -> tuple:
        """What every device call shares after its operands are padded
        (`setop.pad`, at the call site, which also says how many real
        `ids` it pads to how many `padded` elements: counted here) and
        before its result is cut back into rows (`setop.split`, there
        too); a value column's programs open the same spans under
        `valcol.` (`spans`), their launch naming what `said` gives
        (its `use` and the column's `form`) where a set op names its
        `family`: `setop.upload` of
        the operands still on the host — a numpy array, or a `_Sharded`
        one for the mesh; a device array is a DeviceCache hit — and,
        through `keep(operands as the device holds them)`, the
        caller's DeviceCache inserts, which stay where they always
        were: before the launch, and inside a span (PR 26 measured
        -5.2% qps with them after the wait: PERF.md);
        `setop.launch` (jit fetch and the call that enqueues the
        program) and `setop.wait` (queueing behind other requests'
        programs, execution, download). Returns the outputs as numpy
        arrays."""
        with TRACER.span(spans + ".upload", cpu=True, fine=True) as sp:
            nbytes = hits = misses = 0
            dev = []
            for x in operands:
                if isinstance(x, _Sharded):
                    nbytes += x.arr.nbytes
                    misses += 1
                    x = jax.device_put(jnp.asarray(x.arr), x.sharding)
                elif isinstance(x, np.ndarray):
                    nbytes += x.nbytes
                    misses += 1
                    x = jnp.asarray(x)
                elif isinstance(x, jax.Array):
                    hits += 1
                dev.append(x)
            sp.attrs.update(
                bytes=nbytes, cache_hits=hits, cache_misses=misses
            )
            if keep is not None:
                keep(dev)
        if said is None:
            said = {"family": family}
        with TRACER.span(spans + ".launch", cpu=True, fine=True, **said):
            out = fetch()(*dev)
        # read for its wall time; its CPU time (the read-back's copy) is
        # taken too, so that it comes off the caller's self CPU time
        with TRACER.span(spans + ".wait", cpu=True, fine=True) as sp:
            out = out if isinstance(out, tuple) else (out,)
            host = tuple(np.asarray(o) for o in out)
            down = sum(h.nbytes for h in host)
            sp.attrs["bytes"] = down
        METRICS.inc_many({
            "device_dispatch_total": 1,
            f'device_dispatch_total{{family="{family}"}}': 1,
            "device_upload_bytes_total": nbytes,
            "device_download_bytes_total": down,
            "device_real_ids_total": ids,
            "device_padded_ids_total": padded,
        })
        return host

    # -- shared-big-operand fan-out -----------------------------------------

    def run_rows_vs_one(
        self,
        op: str,
        rows: Sequence[np.ndarray],
        b: np.ndarray,
        row_tokens: Optional[Sequence[Optional[tuple]]] = None,
        b_token: Optional[tuple] = None,
    ) -> Sequence[np.ndarray]:
        """Apply `op` to each (row, b) with ONE shared b operand — the
        dominant query shape (uid_matrix rows vs a filter result, recurse
        frontier vs seen-set). b uploads once per call instead of being
        replicated per pair.

        `row_tokens` / `b_token` are (key, latest_ts) posting-list version
        identities; when present, the padded device uploads are cached in
        the DeviceCache and reused across calls/queries until a commit
        invalidates the key (no re-upload of unchanged packs).

        Falls back to host ops below the device threshold. u64 inputs with
        multiple hi-32 segments fall back to the generic pair path.

        On the device, intersect and difference are FLAT: with one
        shared operand an id's membership is the same whichever row it
        sits in, so the rows' ids go up as one array padded to a power
        of four of their total (`_pow4`), one `setops.membership`
        answers them all, and the mask comes back for the host to keep
        or drop by and cut into rows at the offsets. The program is
        given at most four times the real ids. Only `union#shared`,
        which needs each row's own output width, and the pair buckets
        (`_run_bucket`) pad rows into a stack and vmap over it.

        `rows` is a list of rows, packed once here, or a level as it
        lies (`ragged.RaggedRows`), whose flat ids go through as they
        are: no row is cut or packed on the way. Intersect and
        difference hand back a `ragged.RaggedRows` of the kept ids
        either way; the other paths a list of rows."""
        form = "ragged" if isinstance(rows, ragged.RaggedRows) else "rows"
        if form == "rows":
            rows = list(rows)
        if not len(rows):
            return []
        total = len(b) + (
            rows.flat.size if form == "ragged"
            else sum(len(r) for r in rows)
        )
        if total < self._min_total():
            # the host kernels answer it: no span (these are the
            # small, frequent ones), one counter
            METRICS.inc("device_host_kept_total")
            if op in ("intersect", "difference") and len(rows) > 4:
                # vectorized host fallback: ONE searchsorted over the
                # concatenated rows beats per-row native calls (ctypes
                # marshaling dominates at small sizes)
                b64 = np.asarray(b, np.uint64)
                flat, offs = _flat_of(rows)
                if len(b64) and len(flat):
                    idx = np.minimum(
                        np.searchsorted(b64, flat), len(b64) - 1
                    )
                    mask = b64[idx] == flat
                else:
                    mask = np.zeros(len(flat), bool)
                return _cut_rows(op, flat, offs, mask)
            return [_np_op(op, r, b) for r in rows]
        METRICS.inc(f'setop_door_total{{form="{form}"}}')
        if (
            op in ("intersect", "difference")
            and len(b) >= _SHARD_MIN_B
            and len(jax.devices()) > 1
        ):
            got = self._run_rows_sharded(op, rows, b, b_token)
            if got is not None:
                return got
        if op == "union":
            return self._union_rows_stacked(rows, b, row_tokens, b_token)
        family = op + "#shared"
        with TRACER.span(
            "setop.pad", cpu=True, fine=True, family=family
        ) as sp:
            b64 = np.asarray(b, np.uint64)
            flat, offs = _flat_of(rows)
            # one hi-32 test over the level's ids, not one split a row
            his = [x >> np.uint64(32) for x in (flat, b64) if x.size]
            hi = int(his[0][0]) if his else 0
            one_segment = all((h == hi).all() for h in his)
            if one_segment:
                n, total = len(rows), int(flat.size)
                B, b_key, pb = self._shared_operand(b64, hi, b_token)
                pflat = _pow4(total)
                flat_tok = A = None
                if row_tokens is not None and len(row_tokens) == n and all(
                    t is not None for t in row_tokens
                ):
                    flat_tok = ("stack", hi, pflat, tuple(row_tokens))
                    cached = self.device_cache.get(flat_tok)
                    if cached is not None:
                        A = cached[0]
                if A is None:
                    A = setops.pad_sorted(flat.astype(np.uint32), pflat)
                ids, padded = total + len(b64), pflat + pb
                sp.attrs.update(
                    rows=n, pa=pflat, pb=pb, ids=ids, padded=padded
                )
        if not one_segment:
            return self.run_pairs(op, [(r, b) for r in rows])

        def keep(dev):
            Ad, _, Bd, _ = dev
            if b_key is not None and Bd is not B:
                self.device_cache.put(b_key, [b_token[0]], (Bd,), pb * 4)
            if flat_tok is not None and Ad is not A:
                self.device_cache.put(
                    flat_tok, [t[0] for t in row_tokens], (Ad,), pflat * 4
                )

        (mask,) = self._run_device(
            family,
            lambda: self._get_jitted_shared(op, pflat, pb),
            [A, np.int32(total), B, np.int32(len(b64))],
            ids,
            padded,
            keep,
        )
        with TRACER.span("setop.split", cpu=True, fine=True):
            return _cut_rows(op, flat, offs, mask[:total])

    def _shared_operand(self, b64: np.ndarray, hi: int, b_token):
        """The shared operand's low 32 bits as the device takes them:
        (padded array or its DeviceCache copy, cache key or None, pb)."""
        pb = _pow2(len(b64))
        b_key = None
        if b_token is not None:
            b_key = ("b", b_token, hi, pb)
            cached = self.device_cache.get(b_key)
            if cached is not None:
                return cached[0], b_key, pb
        return setops.pad_sorted(b64.astype(np.uint32), pb), b_key, pb

    def run_column(
        self, use: str, ids: np.ndarray, column: Optional[tuple], *scalars
    ) -> tuple:
        """One program of `ops/valcol.py` over `ids`, uint32 or float32,
        in any order: `use` "filter" or "narrow" against a resident
        value column, `column` = (its device form, "table" or "search";
        its operands as the program takes them; its padded size) with
        the ids' low 32 bits (query/valcol.py sees to the high ones);
        "scores" with no column, the ids then being float32 scores.
        Padded as the flat form pads, to a power of four, so a cell's
        requests share one program a form and padded size. Spans
        `valcol.pad` / `.upload` / `.launch` (`use`, `form`) / `.wait`,
        family `column#<use>`; a lookup in a column counts
        `value_column_lookups_total{form}`. Returns the program's
        outputs, the per-id ones still padded."""
        with TRACER.span("valcol.pad", cpu=True, fine=True) as sp:
            n = len(ids)
            pa = _pow4(n)
            fill = np.nan if use == "scores" else setops.UINT32_MAX
            A = np.full((pa,), fill, dtype=ids.dtype)
            A[:n] = ids
            sp.attrs.update(ids=n, padded=pa)
        form, held, size = column if column is not None else (None, (), 0)
        said = {"use": use} if form is None else {"use": use, "form": form}
        out = self._run_device(
            "column#" + use,
            lambda: self._get_jitted_shared("column_" + use, pa, size, form),
            [A, np.int32(n), *held, *scalars],
            n,
            pa,
            spans="valcol",
            said=said,
        )
        if form is not None:
            METRICS.inc(f'value_column_lookups_total{{form="{form}"}}')
        return out

    def _union_rows_stacked(self, rows, b, row_tokens, b_token):
        """`union#shared`: a row's union needs the row's own output
        width, so the rows stay apart, padded into one (nb, pa) stack
        that the program is vmapped over with `b` unbatched (it then
        sorts a copy of `b` with every row: `padded`)."""
        family = "union#shared"
        with TRACER.span(
            "setop.pad", cpu=True, fine=True, family=family
        ) as sp:
            bseg = split_segments(np.asarray(b, np.uint64))
            row_segs = [
                split_segments(np.asarray(r, np.uint64)) for r in rows
            ]
            his = set(bseg)
            for rs in row_segs:
                his |= set(rs)
            one_segment = len(his) <= 1 and all(
                len(rs) <= 1 for rs in row_segs
            )
            if one_segment:
                hi = next(iter(his)) if his else 0
                b32 = bseg.get(hi, np.zeros((0,), np.uint32))
                B, b_key, pb = self._shared_operand(b32, hi, b_token)
                LB = np.int32(len(b32))

                pa = _pow2(
                    max((len(rs.get(hi, ())) for rs in row_segs), default=1)
                )
                n = len(rows)
                nb = _pow2(n)
                stack_tok = A = None
                if row_tokens is not None and len(row_tokens) == n and all(
                    t is not None for t in row_tokens
                ):
                    stack_tok = ("stack", hi, pa, nb, tuple(row_tokens))
                    cached = self.device_cache.get(stack_tok)
                    if cached is not None:
                        A, LA = cached
                if A is None:
                    A = np.full((nb, pa), setops.UINT32_MAX, np.uint32)
                    LA = np.zeros((nb,), np.int32)
                    for i, rs in enumerate(row_segs):
                        r32 = rs.get(hi, np.zeros((0,), np.uint32))
                        A[i, : len(r32)] = r32
                        LA[i] = len(r32)
                ids = sum(len(r) for r in rows) + len(b32)
                padded = nb * (pa + pb)
                sp.attrs.update(
                    rows=n, pa=pa, pb=pb, ids=ids, padded=padded
                )
        if not one_segment:
            return self.run_pairs("union", [(r, b) for r in rows])

        def keep(dev):
            Ad, LAd, Bd, _ = dev
            if b_key is not None and Bd is not B:
                self.device_cache.put(b_key, [b_token[0]], (Bd,), pb * 4)
            if stack_tok is not None and Ad is not A:
                self.device_cache.put(
                    stack_tok,
                    [t[0] for t in row_tokens],
                    (Ad, LAd),
                    int(nb * pa * 4 + nb * 4),
                )

        out, cnt = self._run_device(
            family,
            lambda: self._get_jitted_shared("union", pa, pb),
            [A, LA, B, LB],
            ids,
            padded,
            keep,
        )
        with TRACER.span("setop.split", cpu=True, fine=True):
            return [
                join_segments({hi: out[i, : cnt[i]]}) for i in range(n)
            ]

    def run_rows_vs_one_ragged(
        self,
        op: str,
        flat: np.ndarray,
        offs: np.ndarray,
        b: np.ndarray,
        row_tokens: Optional[Sequence[Optional[tuple]]] = None,
        b_token: Optional[tuple] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """run_rows_vs_one over a ragged level buffer: rows live in ONE
        flat sorted-per-row u64 array with prefix `offs` (level-batched
        task form, query/ragged.py). Returns the result in the same
        (flat, offs) shape without materializing per-row lists.

        The host path is fully vectorized — one searchsorted over the
        whole flat buffer, `ragged.apply_mask` to rebuild offsets — which is
        the CPU-backend fast path for every traversal level. The device
        path hands `run_rows_vs_one` the level as it lies and takes the
        kept level back the same way; it packs only a plain list of
        rows, which a wrapper around the door may return."""
        n = len(offs) - 1
        b64 = np.asarray(b, np.uint64)
        if n == 0:
            return flat, offs
        if not flat.size and op != "union":
            return flat, offs  # all rows empty: intersect/difference stay so
        if op == "intersect" and not b64.size:
            return np.zeros((0,), np.uint64), np.zeros_like(offs)
        if op in ("difference", "union") and not b64.size:
            return flat, offs
        total = flat.size + b64.size
        host = total < self._min_total()
        if host and op in ("intersect", "difference") and flat.size:
            METRICS.inc("device_host_kept_total")
            idx = np.minimum(
                np.searchsorted(b64, flat), b64.size - 1
            )
            mask = b64[idx] == flat
            if op == "difference":
                mask = ~mask
            return ragged.apply_mask(flat, offs, mask)
        res = self.run_rows_vs_one(
            op,
            ragged.RaggedRows(flat, offs),
            b64,
            row_tokens=row_tokens,
            b_token=b_token,
        )
        if isinstance(res, ragged.RaggedRows):
            return res.flat, res.offs
        return ragged.pack_rows(res)

    def run_chain(self, op: str, parts: Sequence[np.ndarray]) -> np.ndarray:
        """Combine k sorted u64 sets with one associative op (AND/OR filter
        chains, ref query.go:2355-2372) in a single device dispatch instead
        of k-1 sequential pairwise calls.

        Operands may be PackedOperand (compressed posting lists): intersect
        chains fold packed operands compressed-domain when the pair clears
        the packed crossover; everything else decodes once up front."""
        if any(isinstance(p, PackedOperand) for p in parts):
            if op == "intersect":
                return self._run_chain_packed_intersect(list(parts))
            parts = [_as_array(p) for p in parts]
        parts = [np.asarray(p, np.uint64) for p in parts]
        if not parts:
            return np.zeros((0,), np.uint64)
        if len(parts) == 1:
            return parts[0]
        if op == "intersect" and any(len(p) == 0 for p in parts):
            return np.zeros((0,), np.uint64)
        if op == "intersect" and len(parts) > 2 and _planner_enabled():
            # planner hook (query/planner.py): fold smallest-first so
            # the pairwise host chain's running result collapses as
            # early as possible — intersection is commutative and the
            # output is sorted-unique either way, so this is a pure
            # execution-order choice (the chain-site analog of the
            # packed fold's sorted-by-size walk below)
            parts = sorted(parts, key=len)
        total = sum(len(p) for p in parts)
        if op == "union" and len(parts) > 256:
            # k-way union of MANY small rows: one host unique beats both
            # the pairwise loop and a device merge whose padding is mostly
            # air (the uid_in reverse fan-out shape at 5M+ scale)
            return np.unique(np.concatenate(parts))
        if total < self._min_total():
            METRICS.inc("device_host_kept_total")
            if op == "union" and len(parts) > 4:
                return np.unique(np.concatenate(parts))
            out = parts[0]
            for p in parts[1:]:
                out = _np_op(op, out, p)
            return out
        family = op + "#chain"
        with TRACER.span(
            "setop.pad", cpu=True, fine=True, family=family
        ) as sp:
            segs = [split_segments(p) for p in parts]
            his = set()
            for s in segs:
                his |= set(s)
            if len(his) <= 1:
                hi = next(iter(his)) if his else 0
                arrs = [s.get(hi, np.zeros((0,), np.uint32)) for s in segs]
                k = len(arrs)
                pad = _pow2(max(len(a) for a in arrs))
                M = np.full((k, pad), setops.UINT32_MAX, np.uint32)
                L = np.zeros((k,), np.int32)
                for i, a in enumerate(arrs):
                    M[i, : len(a)] = a
                    L[i] = len(a)
                ids, padded = sum(len(a) for a in arrs), k * pad
                sp.attrs.update(
                    rows=k, pa=pad, pb=0, ids=ids, padded=padded
                )
        if len(his) > 1:
            out = parts[0]
            for p in parts[1:]:
                out = self.run_pairs(op, [(out, p)])[0]
            return out
        out, cnt = self._run_device(
            family,
            lambda: self._get_jitted_chain(op, k, pad),
            [M, L],
            ids,
            padded,
        )
        with TRACER.span("setop.split", cpu=True, fine=True):
            return join_segments({hi: out[: int(cnt)]})

    def _run_chain_packed_intersect(self, parts: List) -> np.ndarray:
        """Intersect chain with packed operands: fold from the smallest
        operand outward. Each packed operand either stays compressed (the
        running result is small enough that block-skip pays — the common
        shape: tiny frontier vs huge index lists) or decodes once and joins
        the dense chain."""
        if not parts:
            return np.zeros((0,), np.uint64)
        if any(len(p) == 0 for p in parts):
            return np.zeros((0,), np.uint64)
        r = self.packed_min_ratio()
        parts = sorted(parts, key=len)
        cur = _as_array(parts[0])
        dense: List[np.ndarray] = []
        for p in parts[1:]:
            if (
                isinstance(p, PackedOperand)
                and p._uids is None  # decode not already sunk
                and len(p) >= r * max(1, len(cur))
            ):
                cur = packed_setops.intersect_packed(
                    cur, p.pack, decode_b=p.decode_fn, runner=self.run_pairs
                )
                if len(cur) == 0:
                    return cur
            else:
                dense.append(_as_array(p))
        if not dense:
            return cur
        return self.run_chain("intersect", [cur] + dense)

    def _get_jitted_chain(self, op: str, k: int, pad: int):
        key = (op + "#chain", k, pad)
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._jit_lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    base = (
                        setops.intersect_many
                        if op == "intersect"
                        else setops.merge_sorted
                    )
                    fn = self._jit_cache[key] = jax.jit(
                        setops.scoped(f"setop.{op}.chain", base)
                    )
        return fn

    def _run_rows_sharded(self, op, rows, b, b_token):
        """Row-shard the giant shared operand over the device mesh and
        OR-reduce per-row membership masks (the multi-part list data
        plane). Returns None when shapes don't qualify (caller
        falls through to the single-device path)."""
        from dgraph_tpu.parallel import mesh as pmesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        family = op + "#sharded"
        with TRACER.span(
            "setop.pad", cpu=True, fine=True, family=family
        ) as sp:
            b64 = np.asarray(b, np.uint64)
            bseg = split_segments(b64)
            row_segs = [
                split_segments(np.asarray(r, np.uint64)) for r in rows
            ]
            his = set(bseg)
            for rs in row_segs:
                his |= set(rs)
            if len(his) != 1:
                return None
            hi = next(iter(his))
            b32 = bseg[hi]
            mesh = pmesh.make_mesh()
            ndev = mesh.devices.size

            tile = -(-len(b32) // ndev)
            tile = max(_MIN_PAD, 1 << (tile - 1).bit_length())
            pb = tile * ndev
            b_key = B = None
            if b_token is not None:
                b_key = ("bshard", b_token, hi, pb)
                cached = self.device_cache.get(b_key)
                if cached is not None:
                    B = cached[0]
            if B is None:
                B = _Sharded(
                    setops.pad_sorted(b32, pb),
                    NamedSharding(mesh, P("data")),
                )

            n = len(rows)
            pa = _pow2(
                max((len(rs.get(hi, ())) for rs in row_segs), default=1)
            )
            A = np.full((n, pa), setops.UINT32_MAX, np.uint32)
            LA = np.zeros((n,), np.int32)
            for i, rs in enumerate(row_segs):
                r32 = rs.get(hi, np.zeros((0,), np.uint32))
                A[i, : len(r32)] = r32
                LA[i] = len(r32)
            # every row is searched in every device's tile of b
            ids, padded = int(LA.sum()) + len(b32), n * (pa + pb)
            sp.attrs.update(rows=n, pa=pa, pb=pb, ids=ids, padded=padded)
        # LA rides along as a numpy array: sharded_rows_membership
        # converts it itself, as it always did
        def keep(dev):
            if b_key is not None and dev[1] is not B:
                self.device_cache.put(
                    b_key, [b_token[0]], (dev[1],), pb * 4
                )

        (mask,) = self._run_device(
            family,
            lambda: lambda Ad, Bd: pmesh.sharded_rows_membership(
                mesh, Ad, LA, Bd, len(b32)
            ),
            [A, B],
            ids,
            padded,
            keep,
        )
        with TRACER.span("setop.split", cpu=True, fine=True):
            out = []
            for i in range(n):
                row = A[i, : LA[i]]
                m = mask[i, : LA[i]]
                kept = row[m] if op == "intersect" else row[~m]
                out.append(join_segments({hi: kept}))
        return out

    def _get_jitted_shared(self, op: str, pa: int, pb: int, form=None):
        """intersect / difference: ONE membership of the level's ids,
        flat (`pa` of them, padded), in `b`; the host keeps or drops by
        the mask. union: `setops.union` vmapped over a stack of rows
        `pa` wide, `b` unbatched. column_<use>: `ops/valcol.py`'s
        program over `pa` flat ids and a column of padded size `pb` in
        device form `form`."""
        key = (op + "#shared", pa, pb, form)
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._jit_lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    if op == "union":
                        fn = jax.vmap(
                            setops.scoped("setop.union.shared", setops.union),
                            in_axes=(0, 0, None, None),
                        )
                    elif op.startswith("column_"):
                        # a value column's program (`run_column`): the
                        # column is the shared operand
                        use = op[len("column_"):]
                        fn = setops.scoped(
                            "valcol." + use, valcol.program(use, form)
                        )
                    else:
                        fn = setops.scoped(
                            f"setop.{op}.shared", setops.membership
                        )
                    fn = self._jit_cache[key] = jax.jit(fn)
        return fn

    # -- public API ---------------------------------------------------------

    def run_pairs(
        self, op: str, pairs: Sequence[Tuple[np.ndarray, np.ndarray]]
    ) -> List[np.ndarray]:
        """Apply `op` to each (a, b) pair of sorted u64 arrays (either side
        may be a PackedOperand; qualifying pairs run compressed-domain,
        the rest decode and batch as before).

        Returns sorted u64 result arrays, one per pair.
        """
        if not pairs:
            return []
        out: List[Optional[np.ndarray]] = [None] * len(pairs)
        dense: List[Tuple[np.ndarray, np.ndarray]] = []
        dense_at: List[int] = []
        for i, (a, b) in enumerate(pairs):
            if isinstance(a, PackedOperand) or isinstance(b, PackedOperand):
                got = self._try_packed(op, a, b)
                if got is not None:
                    out[i] = got
                    continue
                a, b = _as_array(a), _as_array(b)
            dense.append((a, b))
            dense_at.append(i)
        # kernel-choice accounting (packed vs decoded) for the per-query
        # profile and the cluster metrics endpoint
        METRICS.inc("setop_pairs_total", len(pairs))
        if len(dense) < len(pairs):
            METRICS.inc("setop_packed_total", len(pairs) - len(dense))
        if dense:
            total = sum(len(a) + len(b) for a, b in dense)
            if total < self._min_total():
                METRICS.inc("device_host_kept_total")
                got = [_np_op(op, a, b) for a, b in dense]
            else:
                got = self._run_pairs_device(op, dense)
            for i, res in zip(dense_at, got):
                out[i] = res
        return out

    def intersect_pairs(self, pairs):
        return self.run_pairs("intersect", pairs)

    def union_pairs(self, pairs):
        return self.run_pairs("union", pairs)

    def difference_pairs(self, pairs):
        return self.run_pairs("difference", pairs)

    # -- device path --------------------------------------------------------

    def _run_pairs_device(self, op, pairs):
        with TRACER.span("setop.pad", cpu=True, fine=True, family=op) as sp:
            # Explode u64 pairs into u32 segment sub-jobs.
            sub: List[Tuple[int, int, np.ndarray, np.ndarray]] = []  # (pair, hi, a, b)
            passthrough: List[Tuple[int, int, np.ndarray]] = []  # (pair, hi, lo)
            for pi, (a, b) in enumerate(pairs):
                sa = split_segments(np.asarray(a, np.uint64))
                sb = split_segments(np.asarray(b, np.uint64))
                his = set(sa) | set(sb)
                for hi in his:
                    la, lb = sa.get(hi), sb.get(hi)
                    if la is not None and lb is not None:
                        sub.append((pi, hi, la, lb))
                    elif la is not None and op in ("union", "difference"):
                        passthrough.append((pi, hi, la))
                    elif lb is not None and op == "union":
                        passthrough.append((pi, hi, lb))

            # Bucket sub-jobs by padded shapes.
            buckets: Dict[Tuple[int, int], List[int]] = {}
            for i, (_, _, a, b) in enumerate(sub):
                buckets.setdefault(
                    (_pow2(len(a)), _pow2(len(b))), []
                ).append(i)
            sp.attrs.update(rows=len(sub), buckets=len(buckets))

        # Regroup per pair in one pass. A (pair, hi) key lands either in a
        # device sub-job (segment present in both operands) or in
        # passthrough (present in exactly one) — never both.
        by_pair: List[Dict[int, np.ndarray]] = [dict() for _ in pairs]
        for (pa, pb), idxs in buckets.items():
            outs = self._run_bucket(op, pa, pb, [sub[i] for i in idxs])
            for (pi, hi, _, _), res in zip((sub[i] for i in idxs), outs):
                by_pair[pi][hi] = res
        for pi, hi, lo in passthrough:
            by_pair[pi][hi] = lo
        with TRACER.span("setop.split", cpu=True, fine=True):
            return [join_segments(segs) for segs in by_pair]

    def _get_jitted(self, op: str, pa: int, pb: int):
        key = (op, pa, pb)
        fn = self._jit_cache.get(key)
        if fn is None:
            with self._jit_lock:
                fn = self._jit_cache.get(key)
                if fn is None:
                    base = {
                        "intersect": setops.intersect,
                        "difference": setops.difference,
                        "union": setops.union,
                    }[op]
                    fn = jax.jit(
                        jax.vmap(setops.scoped(f"setop.{op}.pairs", base))
                    )
                    self._jit_cache[key] = fn
        return fn

    def _run_bucket(self, op, pa, pb, jobs):
        with TRACER.span(
            "setop.pad", cpu=True, fine=True, family=op, rows=len(jobs),
            pa=pa, pb=pb,
        ) as sp:
            n = len(jobs)
            nb = _pow2(n)
            A = np.full((nb, pa), setops.UINT32_MAX, np.uint32)
            B = np.full((nb, pb), setops.UINT32_MAX, np.uint32)
            LA = np.zeros((nb,), np.int32)
            LB = np.zeros((nb,), np.int32)
            for i, (_, _, a, b) in enumerate(jobs):
                A[i, : len(a)] = a
                B[i, : len(b)] = b
                LA[i] = len(a)
                LB[i] = len(b)
            ids, padded = int(LA.sum() + LB.sum()), nb * (pa + pb)
            sp.attrs.update(ids=ids, padded=padded)
        out, cnt = self._run_device(
            op,
            lambda: self._get_jitted(op, pa, pb),
            [A, LA, B, LB],
            ids,
            padded,
        )
        with TRACER.span("setop.split", cpu=True, fine=True):
            return [out[i, : cnt[i]] for i in range(n)]


# Module-level singleton used by the executor.
DISPATCHER = SetOpDispatcher()
