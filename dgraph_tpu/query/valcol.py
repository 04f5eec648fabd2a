"""Resident value columns: one predicate's values on the device, for
the filters and orders that bring tens of thousands of candidates.

The reference reads a candidate's value from its posting list, one
candidate at a time (worker/task.go's compare filter, worker/sort.go
sortWithoutIndex); so did `FuncRunner._compare` and
`Executor._order_uids_generic`, in Python. A COLUMN is the predicate as
two arrays: the uids that have a value, sorted, and for each the dense
RANK of its value among the column's distinct values (int32: the
device's lanes are 32 bits wide). Ranks order as the comparator orders
(`types.compare_vals`): ints and floats by value, a datetime by its UTC
instant, whatever offset it was written with, which is where the date
index's tokens go wrong (ROADMAP D18 (b)). A bound becomes a rank range
by one host searchsorted over the distinct values. The programs are
`ops/valcol.py`'s, reached through `SetOpDispatcher.run_column`.

Which predicates: scalar, not a list, not `@lang`, of type int, float
or datetime, every stored value of that type, every uid under one
high-32 segment. Anything else is remembered as unfit until the next
commit to the predicate.

When it exists: built on the first filter or order that brings the
predicate at least `_min_total()` candidates (the line that sends a
set op to the device), by one scan of the predicate's data keys past
the MemoryLayer; the device arrays live in the dispatcher's
DeviceCache, under the predicate's data prefix, beside the set-op
operands and inside the same byte bound. The column keeps a host copy
of its uids and values, so arrays the cache evicted go up again with no
scan. Once resident it also serves smaller candidate sets, from
`_RESIDENT_MIN_IDS` up: half of an `snb.ic9` request's 47,000
candidates pass its date filter, and 23,600 ids under a line of 32,768
went to the comparator for 1.3 s.

How it follows commits (`ValueColumns`, one per engine, hung on its
MemoryLayer by the engine that keeps the promise below). The engine
tells the registry of every commit, with its timestamp and a way to
read each written key's value at that timestamp, BEFORE the timestamp
becomes readable (`note_commit`; api/server.py does so ahead of its
snapshot watermark). A commit to a predicate the registry knows does
not drop its column: the rows it wrote join the predicate's DELTA, a
host-side log of (uid, commit timestamp, value, deleted) in commit
order. The column as built, the BASE at `built_ts`, stays on the
device. A reader at `read_ts >= built_ts` takes the base and, for each
uid the log holds, that uid's newest row at or below `read_ts`, which
shadows the base's: it sees exactly the rows committed at or below its
read timestamp, MVCC on the column as the store has it. The device is
given only the candidates the delta does not hold (the others are
padding to it), so its filter and its cut are over rows that are true
for this reader; the held candidates are tested on the host, and a
narrowing keeps every one with a value, so the comparator orders a
superset of the window and the answer stays exact. When the delta
outgrows `_DELTA_ROWS_MAX`, a thread merges base and delta on the host
and uploads the result as the new base, off the request's path and
with no scan; the old base serves the readers between the two
timestamps until the next merge.

Where a column cannot follow it is dropped: a written value of another
type than the column's, a float NaN, a uid under another high-32
segment, a commit whose values the engine cannot say (`note_commit`
without `value_of`), a delta with no base to merge into that outgrows
twice the bound, an alter, a bulk load, a restore (`clear`), a tablet
move (`invalidate_prefix`). A reader below the base (and below the
previous base), and one that would have to build from a view older
than the predicate's floor (the newest commit its log no longer holds),
take the value-by-value path. A build that a drop overtook serves its
own request and is not published.
"""

from __future__ import annotations

import datetime as _dt
import itertools
import operator
import threading
from typing import Dict, Optional, Tuple

import numpy as np

from dgraph_tpu.ops import setops
from dgraph_tpu.types.types import TypeID, Val
from dgraph_tpu.utils.observe import METRICS, TRACER
from dgraph_tpu.x import keys

_TYPES = (TypeID.INT, TypeID.FLOAT, TypeID.DATETIME)
_EPOCH = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
_UNFIT = object()
_STALE = object()  # `ValueColumns.use`: no base this reader may take
_IDS = itertools.count(1)
# (op, lower bound) -> side of np.searchsorted that gives the edge rank
_EDGE = {"lt": "left", "le": "right", "gt": "right", "ge": "left"}
_CMP = {"lt": operator.lt, "le": operator.le, "gt": operator.gt,
        "ge": operator.ge}
_NO_UPPER = np.int32(np.iinfo(np.int32).max)
# A RESIDENT column serves this many candidates or more (building one
# takes `_min_total()`). On a v5e's host the comparator costs ~44 us an
# id (1.28 s of `process` CPU for the ~29,000 ids of an `snb.ic9`
# request that fell under the `1<<15` line) and a column dispatch 8.7 ms
# of CPU in its four spans plus ~11 ms of waiting (my chip run, PR 36,
# `chipbench.span_reduce`, seed 2000000503): they break even near 450
# ids, and 2,048 leaves a factor of four. IS2's and IS7's 10-31
# candidates and IC1's ~600 (string keys: no column) stay where they were.
_RESIDENT_MIN_IDS = 2048
# Rows a delta holds past its base before a merge makes them the base.
# On one CPU core of a development host (numpy, one thread), against a base of 305,576
# rows and 53,000 candidates (`snb.ic9`'s sizes), new uids in the delta:
# a use finds the candidates the delta holds (`_held`) in 1.57 ms at
# 1,024 rows, 1.70 at 4,096 and 2.09 at 16,384; a merge costs ~24 ms,
# the upload included. At `snb.mixed16`'s ~30 rows a second 4,096 rows
# take two minutes, so a 45 s window merges nothing and a use pays under
# two milliseconds; a write stream a hundred times faster merges every
# 1.4 s and spends under 2% of a core on it.
_DELTA_ROWS_MAX = 4096


def order_key(v: Val):
    """The number a value orders by: what `types._sort_key` compares,
    with a datetime as its UTC instant in whole microseconds."""
    x = v.value
    if v.tid == TypeID.DATETIME:
        if x.tzinfo is None:
            x = x.replace(tzinfo=_dt.timezone.utc)
        d = x - _EPOCH
        return (d.days * 86400 + d.seconds) * 1_000_000 + d.microseconds
    return x


def _key_dtype(tid):
    return np.float64 if tid == TypeID.FLOAT else np.int64


class Column:
    """What the host keeps of a column: its base's timestamp and shape,
    the distinct values its ranks index, and a copy of its rows (`uids`
    their low 32 bits, `keys` their values' order keys) for a merge and
    for an upload after an eviction; the two device arrays are the
    DeviceCache's, under `token`."""

    __slots__ = ("built_ts", "rows", "pb", "hi", "distinct", "token", "tid",
                 "uids", "keys")

    def __init__(self, built_ts, rows, pb, hi, distinct, token, tid=None,
                 uids=None, keys=None):
        self.built_ts, self.rows, self.pb, self.hi = built_ts, rows, pb, hi
        self.distinct, self.token = distinct, token
        self.tid, self.uids, self.keys = tid, uids, keys

    def rank_range(self, op: str, val: Val) -> Tuple[np.int32, np.int32]:
        """[lo, hi] of the ranks that pass `op val` (empty: hi < lo)."""
        at = int(np.searchsorted(self.distinct, order_key(val), _EDGE[op]))
        if op in ("lt", "le"):
            return np.int32(0), np.int32(at - 1)
        return np.int32(at), _NO_UPPER


class Delta:
    """What one reader sees of a column's log: for each uid committed
    past the base, its newest row at or below the reader's timestamp
    (uids ascending, the value's order key, whether it was deleted)."""

    __slots__ = ("uids", "keys", "dead")

    def __init__(self, uids, keys_, dead):
        self.uids, self.keys, self.dead = uids, keys_, dead

    def __len__(self) -> int:
        return len(self.uids)


class _Log:
    """The rows committed to one predicate, in commit order (timestamps
    ascending): uid, commit timestamp, the value's order key, deleted.
    Appended in place past `n` and replaced whole when it grows or is
    trimmed, so a snapshot (the arrays and `n`) stays valid. `tid` is
    the type of the values written, once one was."""

    __slots__ = ("uid", "ts", "key", "dead", "n", "tid")

    def __init__(self):
        self.n, self.tid = 0, None
        self._alloc(64, np.int64)

    def _alloc(self, size, dtype):
        old = self.n and (self.uid, self.ts, self.key, self.dead)
        self.uid = np.zeros(size, np.uint64)
        self.ts = np.zeros(size, np.int64)
        self.key = np.zeros(size, dtype)
        self.dead = np.zeros(size, bool)
        if old:
            for new, was in zip((self.uid, self.ts, self.key, self.dead), old):
                new[: self.n] = was[: self.n]

    def add(self, rows, ts: int, tid) -> bool:
        """Append one commit's rows [(uid, key, dead)]; False where their
        values' type is not the log's."""
        if tid is not None and self.tid not in (None, tid):
            return False
        dtype = _key_dtype(tid if tid is not None else self.tid)
        if tid is not None and self.tid is None:
            self.tid = tid
        if self.n + len(rows) > len(self.uid) or self.key.dtype != dtype:
            self._alloc(max(2 * len(self.uid), self.n + len(rows)), dtype)
        for i, (uid, key, dead) in enumerate(rows, self.n):
            self.uid[i], self.ts[i], self.dead[i] = uid, ts, dead
            self.key[i] = 0 if dead else key
        self.n += len(rows)
        return True

    def snap(self):
        return self.uid, self.ts, self.key, self.dead, self.n

    def after(self, ts: int) -> int:
        """Rows committed after `ts`."""
        return self.n - int(np.searchsorted(self.ts[: self.n], ts, "right"))

    def trim(self, ts: int) -> None:
        """Forget the rows committed at or before `ts`."""
        cut = int(np.searchsorted(self.ts[: self.n], ts, "right"))
        if not cut:
            return
        keep = self.n - cut
        self.uid, self.ts, self.key, self.dead = (
            a[cut: self.n].copy() for a in (self.uid, self.ts, self.key,
                                             self.dead))
        self.n = keep
        if keep < 64:
            self._alloc(64, self.key.dtype)

    def fits(self, col: Column) -> bool:
        """Whether `col` can follow the rows committed past its base."""
        u, ts, _, dead, n = self.snap()
        cut = int(np.searchsorted(ts[:n], col.built_ts, "right"))
        if cut == n:
            return True
        live = ~dead[cut:n]
        return (self.tid in (None, col.tid) or not live.any()) and bool(
            ((u[cut:n] >> np.uint64(32)) == col.hi).all())


def _visible(snap, base_ts: int, read_ts: int) -> Optional[Delta]:
    """The delta of a reader at `read_ts` over a base at `base_ts`, from
    a log snapshot; None where it holds nothing."""
    if snap is None:
        return None
    u, ts, k, d, n = snap
    a, b = np.searchsorted(ts[:n], (base_ts, read_ts), "right").tolist()
    if a >= b:
        return None
    u, k, d = u[a:b], k[a:b], d[a:b]
    uids, newest = np.unique(u[::-1], return_index=True)
    at = (b - a - 1) - newest
    return Delta(uids, k[at], d[at])


class ValueColumns:
    """The columns one engine may use, by data prefix, their logs, and
    what decides whether a reader may (module docstring). `floor` is the
    newest commit the prefix's log no longer holds: a build from a view
    below it would miss a commit, so it is refused."""

    def __init__(self):
        self.uid = next(_IDS)  # DeviceCache tokens name the engine
        self._mu = threading.Lock()
        self._cols: Dict[bytes, object] = {}  # prefix -> Column | _UNFIT
        self._prev: Dict[bytes, Column] = {}  # the base a merge replaced
        self._logs: Dict[bytes, _Log] = {}
        self._gen: Dict[bytes, int] = {}  # every prefix ever asked for
        self._floor: Dict[bytes, int] = {}
        # the newest commit anywhere: the floor of a prefix nobody has
        # asked for yet, whose own commits went uncounted
        self._floor_all = 0
        self._builds: Dict[bytes, threading.Lock] = {}
        self._merging: set = set()

    def use(self, prefix: bytes, read_ts: int):
        """(the base a reader at `read_ts` may take, or _UNFIT, _STALE or
        None; generation; floor; its Delta or None). The base is the
        newest one at or below `read_ts`. Registers the prefix, so that
        commits to it are logged from here on."""
        with self._mu:
            gen = self._gen.setdefault(prefix, 0)
            floor = self._floor.setdefault(prefix, self._floor_all)
            col = self._cols.get(prefix)
            if isinstance(col, Column) and read_ts < col.built_ts:
                prev = self._prev.get(prefix)
                col = (prev if prev is not None and prev.built_ts <= read_ts
                       else _STALE)
            log = self._logs.get(prefix)
            snap = log.snap() if log is not None else None
        delta = (_visible(snap, col.built_ts, read_ts)
                 if isinstance(col, Column) else None)
        return col, gen, floor, delta

    def peek(self, prefix: bytes):
        """The prefix's column, _UNFIT or None, registering nothing (one
        `dict.get`: atomic under the interpreter's lock)."""
        return self._cols.get(prefix)

    def build_lock(self, prefix: bytes) -> threading.Lock:
        with self._mu:
            return self._builds.setdefault(prefix, threading.Lock())

    def publish(self, prefix: bytes, gen: int, col, put=None) -> bool:
        """Make `col` the prefix's column unless a drop overtook its
        build (or it cannot follow what was committed meanwhile); `put()`
        (the DeviceCache insert) runs under the same lock, so whoever
        sees the column finds its arrays."""
        with self._mu:
            if self._gen.get(prefix) != gen:
                return False
            log = self._logs.get(prefix)
            if isinstance(col, Column) and log is not None:
                if not log.fits(col):
                    self._drop([prefix], int(log.ts[log.n - 1]))
                    return False
                log.trim(col.built_ts)
            if put is not None:
                put()
            self._cols[prefix] = col
            self._prev.pop(prefix, None)
            self._gauge()
            return True

    def _gauge(self) -> None:
        """Lock held."""
        cols = [(p, c) for p, c in self._cols.items() if c is not _UNFIT]
        METRICS.set_gauge("value_column_rows", sum(c.rows for _, c in cols))
        METRICS.set_gauge("value_column_delta_rows", sum(
            self._logs[p].after(c.built_ts) for p, c in cols
            if p in self._logs))

    def _drop(self, prefixes, floor_ts: int) -> None:
        """Lock held: `prefixes` changed in a way no column follows, at
        `floor_ts`."""
        from dgraph_tpu.query.dispatch import DISPATCHER

        dropped = []
        for p in prefixes:
            self._gen[p] += 1
            self._floor[p] = max(self._floor.get(p, 0), floor_ts)
            self._prev.pop(p, None)
            self._logs.pop(p, None)
            col = self._cols.pop(p, None)
            if col is not None and col is not _UNFIT:
                dropped.append(p)
        if dropped:
            METRICS.inc("value_column_invalidations_total", len(dropped))
            DISPATCHER.device_cache.invalidate(dropped)
        self._gauge()

    def note_commit(self, written_keys, commit_ts: int, value_of=None) -> None:
        """A commit at `commit_ts` wrote `written_keys`; called before
        `commit_ts` becomes readable. `value_of(key)` is a written data
        key's value at `commit_ts` (None: it has none); without it the
        predicates written are dropped. Free until a column was asked
        for."""
        if not self._gen:
            self._floor_all = max(self._floor_all, commit_ts)
            return
        with self._mu:
            self._floor_all = max(self._floor_all, commit_ts)
            # a data key is its predicate's data prefix and 8 bytes of uid
            hit: Dict[bytes, list] = {}
            for k in written_keys:
                if k[:-8] in self._gen:
                    hit.setdefault(k[:-8], []).append(k)
            if not hit:
                return
            drop = []
            with TRACER.span("valcol.patch", fine=True) as sp:
                patched = 0
                for p, ks in hit.items():
                    if (value_of is None or self._cols.get(p) is _UNFIT
                            or not self._absorb(p, ks, commit_ts, value_of)):
                        drop.append(p)
                    else:
                        patched += len(ks)
                sp.attrs["rows"] = patched
            if patched:
                METRICS.inc("value_column_patched_rows_total", patched)
            if drop:
                self._drop(drop, commit_ts)
            else:
                self._gauge()

    def _absorb(self, prefix: bytes, written, ts: int, value_of) -> bool:
        """Lock held: log one commit's rows of `prefix`; False where no
        column could follow them."""
        rows, tid = [], None
        for k in written:
            uid = int.from_bytes(k[-8:], "big")
            try:
                v = value_of(k)
            except Exception:  # noqa: BLE001 - a record it cannot read
                # back: dropping the column is always a safe answer, and
                # nothing may leave the commit barrier early
                return False
            if v is None:
                rows.append((uid, 0, True))
                continue
            if v.tid not in _TYPES or tid not in (None, v.tid):
                return False
            tid, key = v.tid, order_key(v)
            if key != key:  # a float NaN has no rank
                return False
            rows.append((uid, key, False))
        col = self._cols.get(prefix)
        if isinstance(col, Column) and (
                tid not in (None, col.tid)
                or any(u >> 32 != col.hi for u, _, _ in rows)):
            return False
        log = self._logs.get(prefix)
        if log is None:
            log = self._logs[prefix] = _Log()
        try:
            if not log.add(rows, ts, tid):
                return False
        except OverflowError:  # an int the column's int64 keys cannot hold
            return False
        if not isinstance(col, Column):
            # a build may be under way: it takes the rows past its view
            return log.n <= 2 * _DELTA_ROWS_MAX
        if (log.after(col.built_ts) > _DELTA_ROWS_MAX
                and prefix not in self._merging):
            self._merging.add(prefix)
            threading.Thread(target=self._merge, args=(prefix,),
                             name="valcol-merge", daemon=True).start()
        return True

    def _merge(self, prefix: bytes) -> None:
        """Make base plus delta the new base (module docstring)."""
        from dgraph_tpu.query.dispatch import DISPATCHER

        try:
            with self._mu:
                col, gen = self._cols.get(prefix), self._gen.get(prefix)
                log = self._logs.get(prefix)
                if not isinstance(col, Column) or log is None or not log.n:
                    return
                snap = log.snap()
            top = int(snap[1][snap[4] - 1])  # the newest commit logged
            new, arrays = _merged(self, prefix, col, gen, top,
                                  _visible(snap, col.built_ts, top))
            with self._mu:
                if (self._gen.get(prefix) != gen
                        or self._cols.get(prefix) is not col):
                    return
                DISPATCHER.device_cache.put(new.token, [prefix], arrays,
                                            new.pb * 8)
                self._cols[prefix], self._prev[prefix] = new, col
                log.trim(col.built_ts)
                self._gauge()
            METRICS.inc("value_column_merges_total")
        finally:
            with self._mu:
                self._merging.discard(prefix)

    def invalidate_prefix(self, prefixes) -> None:
        """Everything under `prefixes` changed hands (a tablet move, a
        dropped predicate)."""
        pfx = tuple(bytes(p) for p in prefixes)
        if not self._gen or not pfx:
            return
        with self._mu:
            self._drop([p for p in self._gen if p.startswith(pfx)], 0)

    def clear(self, floor_ts: int) -> None:
        """The store changed outside the commit path (a bulk load, an
        alter, a restore) and is whole at `floor_ts`."""
        with self._mu:
            self._floor_all = max(self._floor_all, floor_ts)
            self._drop(list(self._gen), floor_ts)


def _fallback(why: str) -> None:
    METRICS.inc(f'value_column_fallback_total{{why="{why}"}}')


def _device_arrays(uids: np.ndarray, rank: np.ndarray, pb: int):
    """A column's two device arrays: the uids' low 32 bits padded as
    the set ops pad, and each row's rank."""
    import jax.numpy as jnp

    pad_k = np.zeros((pb,), np.int32)
    pad_k[: len(rank)] = rank
    return (jnp.asarray(setops.pad_sorted(uids, pb)), jnp.asarray(pad_k))


def _upload(col: Column):
    """A resident column's arrays again, from its host copy, after the
    DeviceCache evicted them: no scan."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    arrays = _device_arrays(
        col.uids, np.searchsorted(col.distinct, col.keys), col.pb)
    DISPATCHER.device_cache.put(col.token, [col.token[2]], arrays,
                                col.pb * 8)
    return arrays


def _merged(cols: ValueColumns, prefix: bytes, col: Column, gen: int,
            top: int, delta: Optional[Delta]):
    """(Column, device arrays) of `col`'s rows with `delta` laid over
    them: the base of a reader at `top`."""
    from dgraph_tpu.query.dispatch import _pow2

    uids = (np.uint64(col.hi) << np.uint64(32)) | col.uids.astype(np.uint64)
    vals = col.keys
    if delta is not None:
        keep = ~np.isin(uids, delta.uids, assume_unique=True)
        live = ~delta.dead
        uids = np.concatenate([uids[keep], delta.uids[live]])
        vals = np.concatenate(
            [vals[keep], delta.keys[live].astype(vals.dtype)])
        order = np.argsort(uids, kind="stable")
        uids, vals = uids[order], vals[order]
    distinct, rank = np.unique(vals, return_inverse=True)
    rows = len(uids)
    # the padded shape stays while the rows fit: a new one compiles anew
    pb = max(col.pb, _pow2(rows))
    low = uids.astype(np.uint32)
    token = ("valcol", cols.uid, prefix, top, gen)
    return (Column(top, rows, pb, col.hi, distinct, token, col.tid, low, vals),
            _device_arrays(low, rank, pb))


def column_for(cache, st, ns: int, attr: str, lang: str, n: int):
    """(Column, (device uids, device keys, rows, padded rows), Delta or
    None) for `n` candidates of `attr` as this reader may see it, or
    None: the caller then reads value by value. `_min_total()`
    candidates build a column; one that is resident serves
    `_RESIDENT_MIN_IDS` or more."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    cols = getattr(getattr(cache, "mem", None), "value_columns", None)
    line = DISPATCHER._min_total()
    if cols is None or lang or n < min(line, _RESIDENT_MIN_IDS):
        return None
    prefix = keys.DataPrefix(attr, ns)
    over = n >= line
    if not over and not isinstance(cols.peek(prefix), Column):
        return None  # under the line and nothing resident: as ever
    su = st.get(attr)
    if su is None:
        return None
    if su.lang or su.is_list or su.value_type not in _TYPES:
        _fallback("type")
        return None
    if any(k.startswith(prefix) for k in cache.deltas):
        _fallback("txn")
        return None
    got = _resident(cols, cache, prefix)
    if got is None and over:
        # one build at a time: the requests that arrive meanwhile wait
        # for it instead of scanning the predicate side by side
        with cols.build_lock(prefix):
            got = _resident(cols, cache, prefix, build=(su, attr))
    return got or None


def _resident(cols, cache, prefix, build=None):
    """The column for this reader, False where it must not use one, None
    where there is none yet (and `build` was not asked for)."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    col, gen, floor, delta = cols.use(prefix, cache.read_ts)
    if col is _UNFIT:
        _fallback("type")
        return False
    if col is _STALE:
        _fallback("stale")
        return False
    if col is not None:
        arrays = DISPATCHER.device_cache.get(col.token)
        if arrays is None:
            arrays = _upload(col)  # evicted
        return col, (*arrays, col.rows, col.pb), delta
    if cache.read_ts < floor:
        _fallback("stale")
        return False
    if build is None:
        return None
    col, arrays = _build(cols, cache, prefix, gen, *build)
    if col is _UNFIT:
        _fallback("type")
        return False
    return col, (*arrays, col.rows, col.pb), None


def _build(cols, cache, prefix, gen, su, attr):
    """Scan the predicate at this reader's view, upload, publish unless
    a drop came meanwhile. Returns (Column, (device uids, keys)) or
    (_UNFIT, None)."""
    from dgraph_tpu.query.dispatch import DISPATCHER, _pow2

    with TRACER.span("valcol.build", attr=attr) as sp:
        tid = su.value_type
        uids, vals = [], []
        fit = True
        for k, v in cache.scan_values(prefix):
            if v.tid != tid:
                fit = False
                break
            uids.append(k[-8:])
            vals.append(order_key(v))
        if fit and uids:
            uid64 = np.frombuffer(b"".join(uids), ">u8").astype(np.uint64)
            his = uid64 >> np.uint64(32)
            key = np.asarray(vals, _key_dtype(tid))
            fit = bool((his == his[0]).all()) and not (
                tid == TypeID.FLOAT and np.isnan(key).any())
        if not fit or not uids:
            # (an empty predicate has nothing to hold; its candidates
            # all fail a filter and sort as they stand: value by value)
            cols.publish(prefix, gen, _UNFIT)
            sp.attrs.update(rows=0, bytes=0)
            return _UNFIT, None
        distinct, rank = np.unique(key, return_inverse=True)
        rows, pb = len(uid64), _pow2(len(uid64))
        # scanned in key order, so sorted
        low = uid64.astype(np.uint32)
        arrays = _device_arrays(low, rank, pb)
        token = ("valcol", cols.uid, prefix, cache.read_ts, gen)
        col = Column(cache.read_ts, rows, pb, int(his[0]), distinct, token,
                     tid, low, key)
        sp.attrs.update(rows=rows, bytes=pb * 8)
        METRICS.inc("value_column_builds_total")
        cols.publish(prefix, gen, col, lambda: DISPATCHER.device_cache.put(
            token, [prefix], arrays, pb * 8))
        return col, arrays


def _low32(col: Column, ids: np.ndarray) -> Optional[np.ndarray]:
    """The ids as the programs take them, or None where one lies under
    another high-32 segment than the column's."""
    ids = np.asarray(ids, np.uint64)
    if len(ids) and not ((ids >> np.uint64(32)) == col.hi).all():
        _fallback("type")
        return None
    return ids.astype(np.uint32)


def _held(uids: np.ndarray, ids: np.ndarray):
    """(mask of the `ids` that are among the sorted `uids`, the index in
    `uids` of each of those)."""
    held = np.isin(ids, uids, kind="sort")
    return held, np.searchsorted(uids, ids[held])


def _split(delta: Optional[Delta], ids, low, keep_of):
    """The candidates the delta holds taken off the device's hands:
    (`low` with their positions made padding, those positions as a mask
    or None, and `keep_of(keys, dead)` of their rows). Opens
    `valcol.delta` (`rows`: the reader's delta, `shadowed`: candidates
    it holds, `kept`: those of them kept)."""
    with TRACER.span("valcol.delta", cpu=True, fine=True) as sp:
        rows = 0 if delta is None else len(delta)
        at = kept = None
        if rows:
            held, pos = _held(delta.uids, np.asarray(ids, np.uint64))
            if len(pos):
                at = held
                kept = keep_of(delta.keys[pos], delta.dead[pos])
                low = np.where(held, setops.UINT32_MAX, low)
        sp.attrs.update(
            rows=rows, shadowed=0 if at is None else int(len(kept)),
            kept=0 if at is None else int(kept.sum()))
    if rows:
        METRICS.inc("value_column_delta_rows_read_total", rows)
    return low, at, kept


def filter_mask(cache, st, ns, attr, lang, ids, bounds) -> Optional[np.ndarray]:
    """mask[i] = ids[i] has a value that passes every (op, Val) of
    `bounds` (one for lt/le/gt/ge, two for between); None where no
    column serves this reader."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    got = column_for(cache, st, ns, attr, lang, len(ids))
    if got is None:
        return None
    col, arrays, delta = got
    low = _low32(col, ids)
    if low is None:
        return None
    lo, hi = np.int32(0), _NO_UPPER
    try:
        edges = [(op, order_key(val)) for op, val in bounds]
        for op, val in bounds:
            a, b = col.rank_range(op, val)
            lo, hi = max(lo, a), min(hi, b)

        def passes(vals, dead):
            ok = ~dead
            for op, edge in edges:
                ok &= _CMP[op](vals, edge)
            return ok

        low, at, kept = _split(delta, ids, low, passes)
    except OverflowError:  # a bound no int64 holds
        return None
    (mask,) = DISPATCHER.run_column("filter", low, arrays, lo, hi)
    mask = mask[: len(ids)]
    if at is not None:
        mask = np.array(mask)
        mask[at] = kept
    return mask


def narrow_mask(cache, st, ns, attr, ids, need: int, desc: bool):
    """(mask of the ids that can reach a window of `need` under a
    leading key `attr`, how many ids have a value), or None."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    got = column_for(cache, st, ns, attr, "", len(ids))
    if got is None:
        return None
    col, arrays, delta = got
    low = _low32(col, ids)
    if low is None:
        return None
    low, at, kept = _split(delta, ids, low, lambda vals, dead: ~dead)
    mask, valued = DISPATCHER.run_column(
        "narrow", low, arrays, np.int32(need), np.bool_(desc))
    mask, valued = mask[: len(ids)], int(valued)
    if at is not None:
        # where the device found fewer than `need` values it kept every
        # position, these too; else it kept none of them
        mask = np.array(mask)
        mask[at] |= kept
        valued += int(kept.sum())
    return mask, valued
