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
operands and inside the same byte bound. Once resident it also serves
smaller candidate sets, from `_RESIDENT_MIN_IDS` up: half of an
`snb.ic9` request's 47,000 candidates pass its date filter, and 23,600
ids under a line of 32,768 went to the comparator for 1.3 s.

When it may be used (`ValueColumns`, one per engine, hung on its
MemoryLayer by the engine that keeps the promise below): a column
built from the view at `built_ts` serves a reader at `read_ts >=
built_ts` whose transaction holds no write to the predicate. The
engine tells the registry of every commit that touches the predicate,
with its commit timestamp, BEFORE that timestamp becomes readable
(`note_commit`; api/server.py does so ahead of its snapshot
watermark), which drops the column; so a column that is present was
built from every commit its reader can see. A reader below `built_ts`,
and one that would have to build from a view older than the
predicate's last commit, take the value-by-value path. A build that a
commit overtook serves its own request and is not published.
"""

from __future__ import annotations

import datetime as _dt
import itertools
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
_IDS = itertools.count(1)
# (op, lower bound) -> side of np.searchsorted that gives the edge rank
_EDGE = {"lt": "left", "le": "right", "gt": "right", "ge": "left"}
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


class Column:
    """What the host keeps of a column; the two device arrays are the
    DeviceCache's, under `token`."""

    __slots__ = ("built_ts", "rows", "pb", "hi", "distinct", "token")

    def __init__(self, built_ts, rows, pb, hi, distinct, token):
        self.built_ts, self.rows, self.pb, self.hi = built_ts, rows, pb, hi
        self.distinct, self.token = distinct, token

    def rank_range(self, op: str, val: Val) -> Tuple[np.int32, np.int32]:
        """[lo, hi] of the ranks that pass `op val` (empty: hi < lo)."""
        at = int(np.searchsorted(self.distinct, order_key(val), _EDGE[op]))
        if op in ("lt", "le"):
            return np.int32(0), np.int32(at - 1)
        return np.int32(at), _NO_UPPER


class ValueColumns:
    """The columns one engine may use, by data prefix, and what decides
    whether a reader may (module docstring). `floor` is the newest
    commit timestamp the predicate is known to hold: a view below it
    misses a commit, so nothing built from it is published."""

    def __init__(self):
        self.uid = next(_IDS)  # DeviceCache tokens name the engine
        self._mu = threading.Lock()
        self._cols: Dict[bytes, object] = {}  # prefix -> Column | _UNFIT
        self._gen: Dict[bytes, int] = {}  # every prefix ever asked for
        self._floor: Dict[bytes, int] = {}
        # the newest commit anywhere: the floor of a prefix nobody has
        # asked for yet, whose own commits went uncounted
        self._floor_all = 0
        self._builds: Dict[bytes, threading.Lock] = {}

    def state(self, prefix: bytes):
        """(column or _UNFIT or None, generation, floor); registers the
        prefix, so that commits to it are counted from here on."""
        with self._mu:
            gen = self._gen.setdefault(prefix, 0)
            floor = self._floor.setdefault(prefix, self._floor_all)
            return self._cols.get(prefix), gen, floor

    def peek(self, prefix: bytes):
        """The prefix's column, _UNFIT or None, registering nothing (one
        `dict.get`: atomic under the interpreter's lock)."""
        return self._cols.get(prefix)

    def build_lock(self, prefix: bytes) -> threading.Lock:
        with self._mu:
            return self._builds.setdefault(prefix, threading.Lock())

    def publish(self, prefix: bytes, gen: int, col, put=None) -> bool:
        """Make `col` the prefix's column unless a commit overtook its
        build; `put()` (the DeviceCache insert) runs under the same
        lock, so whoever sees the column finds its arrays."""
        with self._mu:
            if self._gen.get(prefix) != gen:
                return False
            if put is not None:
                put()
            self._cols[prefix] = col
            self._gauge()
            return True

    def forget(self, prefix: bytes, col) -> None:
        """The DeviceCache no longer holds `col`'s arrays."""
        with self._mu:
            if self._cols.get(prefix) is col:
                del self._cols[prefix]
                self._gauge()

    def _gauge(self) -> None:
        METRICS.set_gauge("value_column_rows", sum(
            c.rows for c in self._cols.values() if c is not _UNFIT))

    def _drop(self, prefixes, floor_ts: int) -> None:
        """Lock held: a commit at `floor_ts` touched `prefixes`."""
        from dgraph_tpu.query.dispatch import DISPATCHER

        dropped = []
        for p in prefixes:
            self._gen[p] += 1
            self._floor[p] = max(self._floor.get(p, 0), floor_ts)
            col = self._cols.pop(p, None)
            if col is not None and col is not _UNFIT:
                dropped.append(p)
        if dropped:
            METRICS.inc("value_column_invalidations_total", len(dropped))
            DISPATCHER.device_cache.invalidate(dropped)
            self._gauge()

    def note_commit(self, written_keys, commit_ts: int) -> None:
        """A commit at `commit_ts` wrote `written_keys`; called before
        `commit_ts` becomes readable. Free until a column was asked
        for."""
        if not self._gen:
            self._floor_all = max(self._floor_all, commit_ts)
            return
        with self._mu:
            self._floor_all = max(self._floor_all, commit_ts)
            # a data key is its predicate's data prefix and 8 bytes of uid
            hit = {k[:-8] for k in written_keys} & self._gen.keys()
            if hit:
                self._drop(hit, commit_ts)

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


def column_for(cache, st, ns: int, attr: str, lang: str, n: int):
    """(Column, (device uids, device keys, rows, padded rows)) for `n`
    candidates of `attr` as this reader may see it, or None: the caller
    then reads value by value. `_min_total()` candidates build a
    column; one that is resident serves `_RESIDENT_MIN_IDS` or more."""
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

    col, gen, floor = cols.state(prefix)
    if col is _UNFIT:
        _fallback("type")
        return False
    if col is not None:
        if cache.read_ts < col.built_ts:
            _fallback("stale")
            return False
        arrays = DISPATCHER.device_cache.get(col.token)
        if arrays is not None:
            return col, (*arrays, col.rows, col.pb)
        cols.forget(prefix, col)  # evicted: build it again
    if cache.read_ts < floor:
        _fallback("stale")
        return False
    if build is None:
        return None
    col, arrays = _build(cols, cache, prefix, gen, *build)
    if col is _UNFIT:
        _fallback("type")
        return False
    return col, (*arrays, col.rows, col.pb)


def _build(cols, cache, prefix, gen, su, attr):
    """Scan the predicate at this reader's view, upload, publish unless
    a commit came meanwhile. Returns (Column, (device uids, keys)) or
    (_UNFIT, None)."""
    import jax.numpy as jnp

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
            key = np.asarray(
                vals, np.float64 if tid == TypeID.FLOAT else np.int64)
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
        pad_u = setops.pad_sorted(uid64.astype(np.uint32), pb)
        pad_k = np.zeros((pb,), np.int32)
        pad_k[:rows] = rank
        arrays = (jnp.asarray(pad_u), jnp.asarray(pad_k))
        token = ("valcol", cols.uid, prefix, cache.read_ts, gen)
        col = Column(cache.read_ts, rows, pb, int(his[0]), distinct, token)
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


def filter_mask(cache, st, ns, attr, lang, ids, bounds) -> Optional[np.ndarray]:
    """mask[i] = ids[i] has a value that passes every (op, Val) of
    `bounds` (one for lt/le/gt/ge, two for between); None where no
    column serves this reader."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    got = column_for(cache, st, ns, attr, lang, len(ids))
    if got is None:
        return None
    col, arrays = got
    low = _low32(col, ids)
    if low is None:
        return None
    lo, hi = np.int32(0), _NO_UPPER
    try:
        for op, val in bounds:
            a, b = col.rank_range(op, val)
            lo, hi = max(lo, a), min(hi, b)
    except OverflowError:  # a bound no int64 holds
        return None
    (mask,) = DISPATCHER.run_column("filter", low, arrays, lo, hi)
    return mask[: len(ids)]


def narrow_mask(cache, st, ns, attr, ids, need: int, desc: bool):
    """(mask of the ids that can reach a window of `need` under a
    leading key `attr`, how many ids have a value), or None."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    got = column_for(cache, st, ns, attr, "", len(ids))
    if got is None:
        return None
    col, arrays = got
    low = _low32(col, ids)
    if low is None:
        return None
    mask, valued = DISPATCHER.run_column(
        "narrow", low, arrays, np.int32(need), np.bool_(desc))
    return mask[: len(ids)], int(valued)
