"""Query digest store — per-(namespace, shape) aggregate statistics.

The pg_stat_statements analog for the flight recorder: every query that
passes an entry point (`Server.query`, `ProcCluster.query`) is folded
into an aggregate row keyed on the plan-cache normalized shape
(`plancache.normalize`: the dql token stream with literals replaced by
`?`) crossed with the resolved namespace. A row accumulates calls,
errors, a latency histogram on the shared `_BUCKETS` ladder, result
rows/bytes, plan/result-cache hits, and the packed-kernel counter
deltas the profile scope already computes — so after a latency spike
the *shapes* responsible are readable from `/debug/digests` without a
rerun.

Capacity is bounded (DGRAPH_TPU_DIGEST_SHAPES distinct rows, LRU).
Eviction never loses counts: the evicted row is folded into a sticky
per-namespace ``other`` bucket (a bare ``other`` can never collide
with a real shape — real shapes contain braces and spaces), so
per-namespace totals stay exact under shape churn.

Accounting is observation-only: `record()` mutates only this store, so
query results are byte-identical with the store on or off
(tests/test_flight_recorder.py::test_recorder_on_off_byte_identity
holds it over the golden smoke subset). The hot path pays one
enabled-check plus one dict update under a short lock; METRICS is
never called while the store's lock is held (lock-order discipline).

Cluster merge: every process serves its local rows over the
``debug.digests`` RPC; `merge_rows()` sums same-keyed rows bucket-wise
so `ProcCluster.merged_digests()` (and `dgraph-tpu top`) shows cluster
totals whose call counts equal the sum of per-process scrapes.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Tuple

from dgraph_tpu.utils.observe import _BUCKETS, METRICS
from dgraph_tpu.x import config

# sticky eviction bucket; real shapes always contain braces/spaces
OTHER_SHAPE = "other"

# numeric fields summed on merge/fold (histogram counts handled apart)
_SUM_FIELDS = (
    "calls", "errors", "lat_sum", "rows", "bytes",
    "plan_hits", "result_hits", "setop_pairs", "setop_packed",
)


class DigestEntry:
    __slots__ = (
        "calls", "errors", "lat_sum", "lat_counts", "rows", "bytes",
        "plan_hits", "result_hits", "setop_pairs", "setop_packed",
    )

    def __init__(self):
        self.calls = 0
        self.errors = 0
        self.lat_sum = 0.0
        self.lat_counts = [0] * (len(_BUCKETS) + 1)
        self.rows = 0
        self.bytes = 0
        self.plan_hits = 0
        self.result_hits = 0
        self.setop_pairs = 0
        self.setop_packed = 0

    def fold(self, other: "DigestEntry") -> None:
        self.calls += other.calls
        self.errors += other.errors
        self.lat_sum += other.lat_sum
        for i, c in enumerate(other.lat_counts):
            self.lat_counts[i] += c
        self.rows += other.rows
        self.bytes += other.bytes
        self.plan_hits += other.plan_hits
        self.result_hits += other.result_hits
        self.setop_pairs += other.setop_pairs
        self.setop_packed += other.setop_packed


class DigestStore:
    """Bounded LRU of (namespace, shape) -> DigestEntry. Thread-safe;
    nothing blocking (and no METRICS call) runs under its lock."""

    def __init__(self, capacity: Optional[int] = None):
        self._lock = threading.Lock()
        self._capacity = capacity
        self._rows: "OrderedDict[Tuple[str, str], DigestEntry]" = (
            OrderedDict()
        )

    def capacity(self) -> int:
        if self._capacity is not None:
            return max(1, int(self._capacity))
        return max(1, int(config.get("DIGEST_SHAPES")))

    @staticmethod
    def enabled() -> bool:
        return bool(config.get("DIGEST"))

    def record(
        self,
        ns: str,
        shape: Optional[str],
        seconds: float,
        rows: int = 0,
        nbytes: int = 0,
        error: bool = False,
        plan_hit: bool = False,
        result_hit: bool = False,
        setop_pairs: int = 0,
        setop_packed: int = 0,
    ) -> None:
        """Fold one query observation into its aggregate row. A query
        whose text does not lex (shape None) accrues to `other`."""
        if not self.enabled():
            return
        key = (str(ns), shape if shape else OTHER_SHAPE)
        cap = self.capacity()
        evicted = 0
        with self._lock:
            e = self._rows.get(key)
            if e is None:
                e = self._rows[key] = DigestEntry()
            else:
                self._rows.move_to_end(key)
            e.calls += 1
            if error:
                e.errors += 1
            e.lat_sum += seconds
            i = len(_BUCKETS)
            for j, b in enumerate(_BUCKETS):
                if seconds <= b:
                    i = j
                    break
            e.lat_counts[i] += 1
            e.rows += int(rows)
            e.bytes += int(nbytes)
            if plan_hit:
                e.plan_hits += 1
            if result_hit:
                e.result_hits += 1
            e.setop_pairs += int(setop_pairs)
            e.setop_packed += int(setop_packed)
            while len(self._rows) > cap:
                old_key, old = self._rows.popitem(last=False)
                sink_key = (old_key[0], OTHER_SHAPE)
                if sink_key == old_key:
                    # `other` itself hit the LRU head: reinsert hottest
                    self._rows[old_key] = old
                    self._rows.move_to_end(old_key, last=True)
                    if len(self._rows) <= cap:
                        break
                    old_key, old = self._rows.popitem(last=False)
                    sink_key = (old_key[0], OTHER_SHAPE)
                sink = self._rows.get(sink_key)
                if sink is None:
                    sink = self._rows[sink_key] = DigestEntry()
                sink.fold(old)
                evicted += 1
        if evicted:
            METRICS.inc("digest_evicted_total", evicted)

    def snapshot(self) -> List[dict]:
        """All rows as plain dicts, sorted by latency share (lat_sum
        desc) — the wire/JSON form debug.digests serves. Also publishes
        the digest_shapes gauge (scrape-time, like tablet_traffic)."""
        with self._lock:
            rows = [
                {
                    "ns": ns,
                    "shape": shape,
                    "calls": e.calls,
                    "errors": e.errors,
                    "lat_sum": e.lat_sum,
                    "lat_counts": list(e.lat_counts),
                    "rows": e.rows,
                    "bytes": e.bytes,
                    "plan_hits": e.plan_hits,
                    "result_hits": e.result_hits,
                    "setop_pairs": e.setop_pairs,
                    "setop_packed": e.setop_packed,
                }
                for (ns, shape), e in self._rows.items()
            ]
        METRICS.set_gauge("digest_shapes", len(rows))
        rows.sort(key=lambda r: (-r["lat_sum"], r["ns"], r["shape"]))
        return rows

    def totals(self) -> Dict[str, float]:
        """Store-wide aggregates: total calls/errors/latency plus the
        top shape's latency share (0 when empty)."""
        rows = self.snapshot()
        calls = sum(r["calls"] for r in rows)
        lat = sum(r["lat_sum"] for r in rows)
        top_share = (rows[0]["lat_sum"] / lat) if rows and lat > 0 else 0.0
        return {
            "shapes": float(len(rows)),
            "calls": float(calls),
            "errors": float(sum(r["errors"] for r in rows)),
            "lat_sum": lat,
            "top_shape_lat_share": top_share,
        }

    def reset(self) -> None:
        with self._lock:
            self._rows.clear()


def merge_rows(row_lists: Iterable[List[dict]]) -> List[dict]:
    """Sum same-keyed rows from several per-process snapshots (bucket-
    wise for the histogram) — merged call counts equal the sum of the
    per-process scrapes by construction."""
    merged: Dict[Tuple[str, str], dict] = {}
    for rows in row_lists:
        for r in rows or []:
            key = (str(r.get("ns", "")), str(r.get("shape", "")))
            m = merged.get(key)
            if m is None:
                m = merged[key] = {
                    "ns": key[0],
                    "shape": key[1],
                    "lat_counts": [0] * (len(_BUCKETS) + 1),
                }
                for f in _SUM_FIELDS:
                    m[f] = 0
            for f in _SUM_FIELDS:
                m[f] += r.get(f, 0)
            for i, c in enumerate(r.get("lat_counts") or []):
                if i < len(m["lat_counts"]):
                    m["lat_counts"][i] += c
    out = list(merged.values())
    out.sort(key=lambda r: (-r["lat_sum"], r["ns"], r["shape"]))
    return out


# process-wide store, like METRICS/TRACER/TABLETS — entry points feed
# it directly and attach_debug_surface serves it without plumbing
DIGESTS = DigestStore()
