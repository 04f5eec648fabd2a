"""Root/filter function execution — the worker/task.go equivalent.

Mirrors /root/reference/worker/task.go function dispatch (parseFuncType:230,
processTask:1012): each function produces a sorted uid set, either from an
index range (eq/inequality/terms/fulltext/trigram/geo/vector) or by value
tests over candidate uids (compare-without-index, regexp verify). Filter
application then reduces to batched set ops on the device
(query/dispatch.py), replacing the reference's per-goroutine scalar loops.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

import numpy as np

from dgraph_tpu.dql.parser import FuncSpec
from dgraph_tpu.posting.lists import LocalCache
from dgraph_tpu.schema.schema import State
from dgraph_tpu.tok.tok import build_tokens, get_tokenizer
from dgraph_tpu.types.types import TypeID, Val, compare_vals, convert
from dgraph_tpu.x import keys


class QueryError(Exception):
    pass


class QueryBudgetError(QueryError):
    """The query exceeded its execution time budget (deadline trip) —
    distinct from semantic QueryErrors so the degraded-admission path
    can convert ONLY budget trips into partial responses, never mask a
    genuine execution error."""


def _as_uids(xs) -> np.ndarray:
    if isinstance(xs, np.ndarray) and xs.dtype.kind in "ui":
        return np.unique(xs.astype(np.uint64, copy=False))
    return np.array(sorted(set(int(x) for x in xs)), dtype=np.uint64)


def _union_sorted(parts: List[np.ndarray]) -> np.ndarray:
    """Sorted, duplicate-free union of uint64 arrays. A uid variable is
    bound from a level's merged rows, so the usual input is ONE part
    already in order: that one is handed back as it lies."""
    if not parts:
        return EMPTY
    if len(parts) > 1:
        return np.unique(np.concatenate(parts))
    (out,) = parts
    if len(out) > 1 and not bool((out[:-1] < out[1:]).all()):
        out = np.unique(out)
    return out


EMPTY = np.zeros((0,), np.uint64)

# broadcast-scalar key for value vars (ref query.go:1593: count-var and
# whole-block aggregates live at math.MaxUint64)
MAXUID = (1 << 64) - 1


class FuncRunner:
    """Executes FuncSpecs against a LocalCache + schema state."""

    def __init__(self, cache: LocalCache, st: State, ns: int = keys.GALAXY_NS,
                 vector_indexes=None, uid_vars=None, val_vars=None,
                 stats=None, ordered_uid_vars=None, batcher=None,
                 planner=None, tally=None, uid_tally=None):
        self.cache = cache
        self.st = st
        self.ns = ns
        self.vector_indexes = vector_indexes or {}
        self.uid_vars = uid_vars or {}
        self.val_vars = val_vars or {}
        self.stats = stats  # StatsHolder: selectivity-ordered index scans
        # vars whose array order is meaningful (shortest-path vars)
        self.ordered_uid_vars = ordered_uid_vars or set()
        # cross-query micro-batcher (serving/microbatch.py): plain
        # similar_to searches may coalesce with other in-flight queries
        self.batcher = batcher
        # cost-based planner (query/planner.py): rootless runs feed
        # their observed cardinalities back into its CardBook — the
        # estimate source for next queries' ordering decisions
        self.planner = planner
        # the executor's count of what went through a value column
        # (`Executor._tally_column`); None: nobody counts
        self.tally = tally
        # likewise of the ids its `uid` functions were given
        # (`Executor._tally_uid_ids`)
        self.uid_tally = uid_tally

    # -- helpers -------------------------------------------------------------

    def _schema(self, attr: str):
        su = self.st.get(attr)
        if su is None:
            raise QueryError(f"predicate {attr!r} not in schema")
        return su

    def _index_uids(self, attr: str, token: bytes) -> np.ndarray:
        return self.cache.uids(keys.IndexKey(attr, token, self.ns))

    def _index_src_intersect(
        self, attr: str, token: bytes, src: np.ndarray
    ) -> np.ndarray:
        """index-posting-list ∩ src with the index list kept COMPRESSED
        when the op clears the (engine-tuned, now ratio-8) crossover —
        the filter hot path: a candidate set vs a huge index list, e.g.
        type(Person) at 1M scale. StatsHolder selectivity picks the
        whole-operand route cheaply — when stats say the list is below
        the crossover the decoded path runs without any packed plumbing;
        cold stats (estimate 0) defer to the actual pack size, which the
        dispatcher re-checks. Once packed, the adaptive engine picks per
        BLOCK among {skip, bitmap op, probe, galloping merge} from the
        per-block cardinality metadata (ops/packed_setops.py)."""
        if len(src) == 0:
            return EMPTY
        from dgraph_tpu.query.dispatch import DISPATCHER

        key = keys.IndexKey(attr, token, self.ns)
        est = (
            self.stats.estimate(attr, token)
            if self.stats is not None
            else 0
        )
        pop = None
        if not (
            0 < est < DISPATCHER.packed_min_ratio() * max(1, len(src))
        ):
            pop = self.cache.packed_operand(key)
        from dgraph_tpu.utils.observe import current_plan

        plan = current_plan()
        if plan is not None:
            # EXPLAIN: the StatsHolder-fed whole-operand route pick at
            # the index-intersect hot path (the cost-based planner's
            # future input): sketch estimate vs the ratio gate, and
            # whether a packed operand was actually available
            plan.note_setop(
                {
                    "site": "index_intersect",
                    "attr": attr,
                    "stats_estimate": int(est),
                    "src": int(len(src)),
                    "min_ratio": int(DISPATCHER.packed_min_ratio()),
                    "verdict": "packed" if pop is not None else "decoded",
                }
            )
        if pop is None:
            return np.intersect1d(
                self.cache.uids(key), src, assume_unique=True
            )
        return DISPATCHER.run_chain(
            "intersect", [np.asarray(src, np.uint64), pop]
        ).astype(np.uint64)

    def _eq_tokenizer(self, su):
        """Pick a non-lossy tokenizer for eq (ref tok.go:372 pickTokenizer)."""
        toks = su.tokenizer_objs()
        for t in toks:
            if not t.is_lossy:
                return t, False
        for t in toks:
            if t.name == "term":
                return t, True  # lossy: needs value verification
        return (toks[0], True) if toks else (None, True)

    def _value_of(self, attr: str, uid: int, lang: str = "") -> Optional[Val]:
        """Value for function evaluation, honoring @lang semantics (ref
        worker/task.go langForFunc + posting ValueForTag): on an @lang
        predicate an untagged lookup matches ONLY the untagged value (no
        any-language fallback — eq(name, "") must not see name@hi), a
        tagged lookup matches that tag, '.' prefers untagged then any."""
        key = keys.DataKey(attr, int(uid), self.ns)
        su = self._schema(attr)
        if su is None or not su.lang:
            return self.cache.value(key, lang)
        posts = [p for p in self.cache.values(key) if p.is_value]
        return _pick_lang_val(posts, lang)

    def _scan_data_uids(self, attr: str) -> np.ndarray:
        """All entities having attr (full tablet scan; ref has at root
        task.go:2679 handleHasFunction).

        Fast path: when the key's newest record is a rollup, liveness is
        read straight from the record header (pack num_uids / posting
        count) without materializing a PostingList — a has() over a
        bulk-loaded 100k-row tablet is header peeks, not decodes."""
        import struct as _struct

        out = []
        prefix = keys.DataPrefix(attr, self.ns)
        deltas = self.cache.deltas
        for k, _, rec in self.cache.kv.iterate(prefix, self.cache.read_ts):
            if k not in deltas and rec and rec[0] == 0 and len(rec) >= 17:
                # KIND_ROLLUP: [B kind][I packlen][4B magic][Q num_uids]...
                (num_uids,) = _struct.unpack_from("<Q", rec, 9)
                if num_uids > 0:
                    out.append(_struct.unpack(">Q", k[-8:])[0])
                    continue
                (packlen,) = _struct.unpack_from("<I", rec, 1)
                if 5 + packlen + 4 <= len(rec):
                    (pc,) = _struct.unpack_from("<I", rec, 5 + packlen)
                    if pc > 0:
                        out.append(_struct.unpack(">Q", k[-8:])[0])
                        continue
                    # empty pack + no postings: split list or truly empty —
                    # fall through to the full check
            if not self.cache.get(k).is_empty(deltas.get(k)):
                out.append(keys.parse_key(k).uid)
        return _as_uids(out)

    # -- dispatch ------------------------------------------------------------

    def run_root(self, fn: FuncSpec) -> np.ndarray:
        """Execute a root function -> sorted uids."""
        return self._run(fn, src=None)

    def run_filter(self, fn: FuncSpec, src: np.ndarray) -> np.ndarray:
        """Evaluate as filter over candidate uids -> surviving uids."""
        return self._run(fn, src=src)

    def _run(self, fn: FuncSpec, src: Optional[np.ndarray]) -> np.ndarray:
        out = self._run_impl(fn, src)
        if src is None and self.planner is not None:
            # planner feedback: observed rootless cardinality -> the
            # CardBook EWMA the next query's cost model reads
            self.planner.note_root(fn, len(out))
        return out

    def _run_impl(self, fn: FuncSpec, src: Optional[np.ndarray]) -> np.ndarray:
        name = fn.name
        if fn.is_count:
            return self._count_func(fn, name, src)
        if name == "uid":
            return self._uid(fn, src)
        if name == "uid_in":
            return self._uid_in(fn, src)
        if name == "type":
            return self._type(fn, src)
        if name == "has":
            return self._has(fn, src)
        if fn.val_var and name in ("eq", "le", "lt", "ge", "gt", "between"):
            return self._val_var_cmp(fn, name, src)
        if name == "eq":
            return self._eq(fn, src)
        if name in ("le", "lt", "ge", "gt"):
            return self._compare(fn, name, src)
        if name == "between":
            return self._between(fn, src)
        if name in ("anyofterms", "allofterms"):
            return self._terms(fn, src, "term", name.startswith("all"))
        if name in ("anyoftext", "alloftext"):
            return self._terms(fn, src, "fulltext", name.startswith("all"))
        if name == "regexp":
            return self._regexp(fn, src)
        if name == "match":
            return self._match(fn, src)
        if name == "similar_to":
            return self._similar_to(fn, src)
        if name in ("near", "within", "contains", "intersects"):
            return self._geo(fn, name, src)
        if name == "checkpwd":
            return self._checkpwd(fn, src)
        raise QueryError(f"function {name!r} not supported")

    def _uid(self, fn: FuncSpec, src: Optional[np.ndarray]) -> np.ndarray:
        """uid(0x1, A, B): the literals and the named variables' sets,
        united. A variable stays the array it was bound as; nothing
        here walks its ids."""
        uvars = fn.uid_var.split(",") if fn.uid_var else []
        parts = [_as_uids(fn.args)] if fn.args else []
        for v in uvars:
            if v in self.uid_vars:
                parts.append(np.asarray(self.uid_vars[v], np.uint64))
            elif v in self.val_vars:
                # uid(value-var): the var's uid key set — INCLUDING the
                # MaxUint64 count-var key (ref query.go:1593; uid(f) on
                # `f as count(uid)` yields that sentinel row)
                held = self.val_vars[v]
                parts.append(np.fromiter(held, np.uint64, len(held)))
        if self.uid_tally is not None:
            self.uid_tally(sum(len(p) for p in parts))
        if (
            not fn.args
            and len(uvars) == 1
            and uvars[0] in self.ordered_uid_vars
            and src is None
        ):
            # uid(A) where A is a shortest-path var: PATH order
            # (ref TestShortestPathRev golden)
            return parts[0]
        out = _union_sorted(parts)
        if src is not None:
            out = np.intersect1d(out, src, assume_unique=True)
        return out

    def _checkpwd(self, fn: FuncSpec, src) -> np.ndarray:
        """checkpwd(pred, "pw") — verify a password-type value
        (ref worker/task.go passwordFn). Salt+PBKDF2 format from acl/."""
        import hmac as _hmac

        from dgraph_tpu.acl.acl import _hash_password

        if not fn.args:
            raise QueryError("checkpwd(pred, password) requires a password")
        cands = src if src is not None else self._scan_data_uids(fn.attr)
        pw = str(fn.args[0])
        out = []
        for u in cands:
            got = self._value_of(fn.attr, u)
            if got is None:
                continue
            try:
                raw = bytes.fromhex(str(got.value))
                salt, want = raw[:16], raw[16:]
                if _hmac.compare_digest(_hash_password(pw, salt), want):
                    out.append(int(u))
            except ValueError:
                continue
        return _as_uids(out)

    def _geo_cells_of_point(self, lon: float, lat: float):
        from dgraph_tpu.tok.tok import GeoTokenizer

        tok = get_tokenizer("geo")
        return [
            tok.prefix() + GeoTokenizer.cell_at(lon, lat, lvl)
            for lvl in range(GeoTokenizer.MIN_LEVEL, GeoTokenizer.MAX_LEVEL + 1)
        ]

    def _geo_contains(self, fn: FuncSpec, src) -> np.ndarray:
        """contains(loc, [lon,lat]) or contains(loc, polygon): stored
        areal geometries containing the query point/polygon
        (ref types/geofilter.go QueryTypeContains)."""
        arg = fn.args[0]
        # polygon arg: [[[lon,lat],...]] or [[lon,lat],...]
        qpts: List[tuple]
        if isinstance(arg[0], list) and isinstance(arg[0][0], list):
            qpts = [(float(p[0]), float(p[1])) for p in arg[0]]
        elif isinstance(arg[0], list):
            qpts = [(float(p[0]), float(p[1])) for p in arg]
        else:
            qpts = [(float(arg[0]), float(arg[1]))]
        cands = set()
        for lon, lat in qpts:
            for key_tok in self._geo_cells_of_point(lon, lat):
                for u in self._index_uids(fn.attr, key_tok):
                    cands.add(int(u))
        out = []
        for u in sorted(cands):
            got = self._value_of(fn.attr, u)
            if got is None:
                continue
            for ring in _geo_rings(got.value):
                if all(_point_in_poly(x, y, ring) for x, y in qpts):
                    out.append(u)
                    break
        res = _as_uids(out)
        if src is not None:
            res = np.intersect1d(res, src, assume_unique=True)
        return res

    def _geo_intersects(self, fn: FuncSpec, src) -> np.ndarray:
        """intersects(loc, polygon): stored geometries intersecting the
        query polygon (ref QueryTypeIntersects)."""
        arg = fn.args[0] if fn.args else None

        def _depth(x):
            d = 0
            while isinstance(x, list) and x:
                x = x[0]
                d += 1
            return d

        d = _depth(arg)
        if d == 4:  # multipolygon: [[ring...]...] per polygon
            outer_rings = [poly[0] for poly in arg if poly]
        elif d == 3:  # polygon: [ring, holes...]
            outer_rings = [arg[0]]
        elif d == 2:  # bare ring
            outer_rings = [arg]
        else:
            outer_rings = []
        outer_rings = [r for r in outer_rings if len(r) >= 3]
        if not outer_rings:
            raise QueryError("intersects() needs a polygon of >=3 points")
        if len(outer_rings) > 1:
            # a geometry intersects a multipolygon iff it intersects any
            # member polygon (ref QueryTypeIntersects over loops)
            parts = [
                self._geo_intersects(
                    FuncSpec(name=fn.name, attr=fn.attr, args=[[r]]),
                    src,
                )
                for r in outer_rings
            ]
            return _as_uids(sorted(set().union(*[set(map(int, p)) for p in parts])))
        qring = [(float(p[0]), float(p[1])) for p in outer_rings[0]]
        # candidates: cover cells of the query polygon bbox across levels
        from dgraph_tpu.tok.tok import GeoTokenizer

        tok = get_tokenizer("geo")
        lons = [p[0] for p in qring]
        lats = [p[1] for p in qring]
        lon0, lon1 = min(lons), max(lons)
        lat0, lat1 = min(lats), max(lats)
        cands = set()
        for lvl in range(GeoTokenizer.MIN_LEVEL, GeoTokenizer.MAX_LEVEL + 1):
            cw = 360.0 / (1 << lvl)
            ch = 180.0 / (1 << lvl)
            if ((lon1 - lon0) / cw + 2) * ((lat1 - lat0) / ch + 2) > 512:
                break
            x = lon0
            while x <= lon1 + cw:
                y = lat0
                while y <= lat1 + ch:
                    cell = GeoTokenizer.cell_at(min(x, lon1), min(y, lat1), lvl)
                    for u in self._index_uids(fn.attr, tok.prefix() + cell):
                        cands.add(int(u))
                    y += ch
                x += cw
        out = []
        for u in sorted(cands):
            got = self._value_of(fn.attr, u)
            if got is None:
                continue
            geo = got.value
            rings = _geo_rings(geo)
            if rings:
                if any(_polys_intersect(qring, r) for r in rings):
                    out.append(u)
            else:
                c = geo.get("coordinates", [None, None])
                if c[0] is not None and _point_in_poly(
                    float(c[0]), float(c[1]), qring
                ):
                    out.append(u)
        res = _as_uids(out)
        if src is not None:
            res = np.intersect1d(res, src, assume_unique=True)
        return res

    # -- implementations -----------------------------------------------------

    def _count_func(self, fn: FuncSpec, op: str, src) -> np.ndarray:
        """eq/lt/le/gt/ge(count(pred), N) — via the @count index when
        present (ref worker/task.go:1222 handleCompareCountFunction),
        else by counting lists. count(~pred) counts reverse edges."""
        reverse = fn.attr.startswith("~")
        attr = fn.attr[1:] if reverse else fn.attr
        su = self._schema(attr)
        if reverse and not su.directive_reverse:
            raise QueryError(f"predicate {attr!r} has no @reverse index")
        want = int(fn.args[0])

        def ok(c: int) -> bool:
            return (
                (op == "eq" and c == want)
                or (op == "le" and c <= want)
                or (op == "lt" and c < want)
                or (op == "ge" and c >= want)
                or (op == "gt" and c > want)
            )

        # count index holds forward counts only (mutation.py); reverse
        # counts always use the fallback scan
        if su.count and src is None and not reverse:
            out = EMPTY
            prefix = keys.CountPrefix(attr, self.ns)
            for k, _, _ in self.cache.kv.iterate(prefix, self.cache.read_ts):
                pk = keys.parse_key(k)
                if ok(pk.count):
                    out = np.union1d(out, self.cache.uids(k))
            return out.astype(np.uint64)

        def key_of(u):
            return (
                keys.ReverseKey(attr, int(u), self.ns)
                if reverse
                else keys.DataKey(attr, int(u), self.ns)
            )

        if src is not None:
            cands = src
        elif reverse:
            # reverse candidates = every uid with a reverse list
            cands = _as_uids(
                keys.parse_key(k).uid
                for k, _, _ in self.cache.kv.iterate(
                    keys.ReversePrefix(attr, self.ns), self.cache.read_ts
                )
            )
        else:
            cands = self._scan_data_uids(attr)
        return _as_uids(
            int(u) for u in cands if ok(len(self.cache.uids(key_of(u))))
        )

    def _has(self, fn: FuncSpec, src) -> np.ndarray:
        attr = fn.attr
        su = self.st.get(attr)  # None for reverse (~pred) / unknown attrs
        if su is not None and su.lang:
            # has(name) on an @lang pred = untagged value present;
            # has(name@hi) = that tag present; has(name@.) = any value
            def ok(u: int) -> bool:
                posts = [
                    p
                    for p in self.cache.values(
                        keys.DataKey(attr, int(u), self.ns)
                    )
                    if p.is_value
                ]
                if not fn.lang:
                    return any(p.lang == "" for p in posts)
                for lang in fn.lang.split(":"):
                    if lang == "." and posts:
                        return True
                    if any(p.lang == lang for p in posts):
                        return True
                return False

            cands = src if src is not None else self._scan_data_uids(attr)
            return _as_uids([int(u) for u in cands if ok(int(u))])
        if src is not None:
            out = [
                int(u)
                for u in src
                if self.cache.has(keys.DataKey(attr, int(u), self.ns))
            ]
            return _as_uids(out)
        return self._scan_data_uids(attr)

    def _type(self, fn: FuncSpec, src) -> np.ndarray:
        # dgraph.type is an exact-indexed string predicate (ref systems schema)
        token = b"\x02" + fn.attr.encode("utf-8")
        if src is not None:
            # filter form: keep the (potentially huge) type index packed
            return self._index_src_intersect("dgraph.type", token, src)
        return self._index_uids("dgraph.type", token)

    def _uid_in(self, fn: FuncSpec, src) -> np.ndarray:
        """uid_in(pred, uids): entities whose pred edge reaches a target
        (ref worker/task.go handleUidIn). With @reverse the targets'
        reverse lists answer it in O(|targets|) reads; otherwise all
        candidate rows go through ONE batched dispatch instead of a
        per-candidate Python intersect (the 1M-suite 2-hop hot path)."""
        targets = set(int(x) for x in fn.args)
        if fn.uid_var:
            targets |= set(int(u) for u in self.uid_vars.get(fn.uid_var, []))
        tarr = _as_uids(targets)
        su = self._schema(fn.attr)
        if su.directive_reverse:
            from dgraph_tpu.query.dispatch import DISPATCHER

            rkeys = [keys.ReverseKey(fn.attr, int(t), self.ns) for t in tarr]
            self.cache.prefetch(rkeys)
            rows = [self.cache.uids(k) for k in rkeys]
            hit = DISPATCHER.run_chain("union", rows) if rows else EMPTY
            if src is None:
                return hit.astype(np.uint64)
            return np.intersect1d(hit, src, assume_unique=True).astype(
                np.uint64
            )
        cands = src if src is not None else self._scan_data_uids(fn.attr)
        if not len(cands):
            return EMPTY
        from dgraph_tpu.query.dispatch import DISPATCHER

        ckeys = [keys.DataKey(fn.attr, int(u), self.ns) for u in cands]
        self.cache.prefetch(ckeys)
        rows = []
        toks = []
        for k in ckeys:
            r, tk = self.cache.uids_tok(k)
            rows.append(r)
            toks.append(tk)
        inter = DISPATCHER.run_rows_vs_one(
            "intersect", rows, tarr, row_tokens=toks
        )
        return _as_uids(
            int(u) for u, r in zip(cands, inter) if len(r)
        )

    def _eq(self, fn: FuncSpec, src) -> np.ndarray:
        su = self._schema(fn.attr)
        if fn.val_var:
            raise QueryError("eq(val(..)) handled by executor")
        # flatten list literals (eq(age, [15, 17, 38])) and resolve
        # val(x) args into the var's value set (eq(name, val(a)))
        vals = []
        for a in fn.args:
            if isinstance(a, list):
                vals.extend(a)
            elif isinstance(a, tuple) and len(a) == 2 and a[0] == "valarg":
                seen = set()
                for v in self.val_vars.get(a[1], {}).values():
                    x = v.value if isinstance(v, Val) else v
                    if isinstance(x, (int, float, str)) and x not in seen:
                        seen.add(x)
                        vals.append(x)
            else:
                vals.append(a)
        out = EMPTY
        tok, needs_verify = (None, True)
        if su.directive_index:
            tok, needs_verify = self._eq_tokenizer(su)
            if su.lang:
                # index tokens come from every language; the lang (or the
                # strict-untagged default) is enforced by value re-check
                needs_verify = True
        for v in vals:
            val = _coerce(v, su.value_type)
            toks_v = build_tokens(val, [tok]) if tok is not None else []
            if tok is not None and toks_v:
                cand = EMPTY
                for tb in toks_v:
                    # as a filter, (∪ tokens) ∩ src distributes to
                    # ∪ (token ∩ src): each token's index list stays
                    # packed against the candidate set
                    l = (
                        self._index_src_intersect(fn.attr, tb, src)
                        if src is not None
                        else self._index_uids(fn.attr, tb)
                    )
                    cand = np.union1d(cand, l)
            elif tok is not None and not toks_v:
                # value produced no tokens (eq(room, "") on a term index):
                # fall back to a value scan (ref handles empty-string eq)
                cand = src if src is not None else self._scan_data_uids(fn.attr)
                needs_verify = True
            else:
                # unindexed eq over src or full scan (ref requires index at
                # root; as filter we value-test)
                cand = src if src is not None else self._scan_data_uids(fn.attr)
                needs_verify = True
            if needs_verify:
                cand = _as_uids(
                    [
                        int(u)
                        for u in cand
                        if _val_eq(self._value_of(fn.attr, u, fn.lang), val)
                    ]
                )
            out = np.union1d(out, cand)
        if src is not None:
            out = np.intersect1d(out, src, assume_unique=True)
        return out.astype(np.uint64)

    def _val_var_cmp(self, fn: FuncSpec, op: str, src) -> np.ndarray:
        """eq/ineq against a value variable: gt(val(a), 18) keeps uids
        whose var value compares true (ref query.go ineq on value vars)."""
        vmap = self.val_vars.get(fn.val_var, {})
        if src is not None:
            cands = [int(u) for u in src]
        else:
            cands = list(vmap)
        out = []
        for u in cands:
            got = vmap.get(u, vmap.get(MAXUID))
            if got is None:
                continue
            try:
                if op == "eq":
                    hit = any(
                        compare_vals(got, _coerce(a, got.tid)) == 0
                        for a in fn.args
                    )
                elif op == "between":
                    lo = _coerce(fn.args[0], got.tid)
                    hi = _coerce(fn.args[1], got.tid)
                    hit = (
                        compare_vals(got, lo) >= 0
                        and compare_vals(got, hi) <= 0
                    )
                else:
                    c = compare_vals(got, _coerce(fn.args[0], got.tid))
                    hit = (
                        (op == "le" and c <= 0)
                        or (op == "lt" and c < 0)
                        or (op == "ge" and c >= 0)
                        or (op == "gt" and c > 0)
                    )
            except (ValueError, TypeError):
                continue
            if hit:
                out.append(u)
        return _as_uids(out)

    def _compare(self, fn: FuncSpec, op: str, src) -> np.ndarray:
        su = self._schema(fn.attr)
        arg = fn.args[0]
        if isinstance(arg, tuple) and len(arg) == 2 and arg[0] == "valarg":
            # ge(number, val(x)): compare against the var's (scalar) value;
            # an empty var matches nothing (ref TestAggregateEmpty3)
            vmap = self.val_vars.get(arg[1], {})
            xs = list(vmap.values())
            if not xs:
                return EMPTY
            arg = xs[0].value if isinstance(xs[0], Val) else xs[0]
        val = _coerce(arg, su.value_type)
        # indexed range scan over sortable tokenizer (ref sortWithIndex path)
        sortable = None
        if su.directive_index and not su.lang:
            # @lang preds take the value-scan path: the index mixes all
            # languages, so each hit needs a lang-aware value re-check
            for t in su.tokenizer_objs():
                if t.is_sortable:
                    sortable = t
                    break
        if sortable is not None and src is None:
            return self._range_scan(fn.attr, sortable, op, val)
        if src is not None:
            mask = self._column_mask(fn, src, [(op, val)])
            if mask is not None:
                return np.unique(np.asarray(src, np.uint64)[mask])
        cands = src if src is not None else self._scan_data_uids(fn.attr)
        out = []
        for u in cands:
            got = self._value_of(fn.attr, u, fn.lang)
            if got is None:
                continue
            try:
                c = compare_vals(convert(got, val.tid), val)
            except ValueError:
                continue
            if (
                (op == "le" and c <= 0)
                or (op == "lt" and c < 0)
                or (op == "ge" and c >= 0)
                or (op == "gt" and c > 0)
            ):
                out.append(int(u))
        return _as_uids(out)

    def _column_mask(self, fn: FuncSpec, ids, bounds) -> Optional[np.ndarray]:
        """The inequality as a mask over `ids` (any order, repeats
        allowed) from the predicate's resident value column, or None:
        fewer candidates than the device line, a predicate or a request
        no column serves (query/valcol.py)."""
        from dgraph_tpu.query import valcol

        mask = valcol.filter_mask(
            self.cache, self.st, self.ns, fn.attr, fn.lang, ids, bounds
        )
        if mask is not None and self.tally is not None:
            self.tally(len(ids), 0)
        return mask

    def column_filter_mask(self, fn: FuncSpec, ids) -> Optional[np.ndarray]:
        """`fn` over a level's flat ids straight from the column, where
        `fn` is one inequality or `between` on a stored predicate with
        literal bounds; None sends the caller down the usual road."""
        if fn.val_var or fn.is_count or fn.name not in (
            "lt", "le", "gt", "ge", "between"
        ):
            return None
        su = self.st.get(fn.attr)
        if su is None or any(isinstance(a, tuple) for a in fn.args):
            return None
        ops = ("ge", "le") if fn.name == "between" else (fn.name,)
        if len(fn.args) < len(ops):
            return None
        try:
            bounds = [
                (op, _coerce(a, su.value_type)) for op, a in zip(ops, fn.args)
            ]
        except (ValueError, TypeError):
            return None  # the usual road raises it as it always has
        return self._column_mask(fn, ids, bounds)

    def _range_scan(self, attr: str, tok, op: str, val: Val) -> np.ndarray:
        """Walk the sortable index range (ref worker/task.go:1881 eq-planning
        and sort.go:189 sortWithIndex bucket walk).

        Token order == value order at bucket granularity, so only the
        BOUNDARY bucket (token == target) can hold mismatches for a lossy
        tokenizer — interior buckets pass without per-uid value reads (the
        old full-candidate verify made ge/le O(matches) value fetches)."""
        target = build_tokens(convert(val, tok.type_id), [tok])[0]
        prefix = keys.IndexPrefix(attr, self.ns) + tok.prefix()
        interior = []
        boundary = []
        for k, _, _ in self.cache.kv.iterate(prefix, self.cache.read_ts):
            token = k[len(keys.IndexPrefix(attr, self.ns)) :]
            if token == target:
                boundary.append(self.cache.uids(k))
            elif (op in ("le", "lt") and token < target) or (
                op in ("ge", "gt") and token > target
            ):
                interior.append(self.cache.uids(k))
        if boundary:
            b = np.unique(np.concatenate(boundary)).astype(np.uint64)
            if tok.is_lossy:
                # e.g. float buckets at int granularity, dates at year
                b = _as_uids(
                    int(u) for u in b if self._cmp_ok(attr, u, op, val)
                )
            elif op in ("lt", "gt"):
                b = EMPTY  # exact tokenizer: equality bucket excluded
            interior.append(b)
        if not interior:
            return EMPTY
        return np.unique(np.concatenate(interior)).astype(np.uint64)

    def _cmp_ok(self, attr, uid, op, val) -> bool:
        su = self.st.get(attr)
        if su is not None and su.is_list:
            # list predicates match when ANY value satisfies the range
            # (ref TestMultipleValueFilter2: le(graduation, 1933) keeps
            # the [1935, 1933] node)
            cands = [
                p.val()
                for p in self.cache.values(
                    keys.DataKey(attr, int(uid), self.ns)
                )
                if p.is_value
            ]
        else:
            got = self._value_of(attr, uid)
            cands = [] if got is None else [got]
        for got in cands:
            try:
                c = compare_vals(convert(got, val.tid), val)
            except ValueError:
                continue
            if (
                (op == "le" and c <= 0)
                or (op == "lt" and c < 0)
                or (op == "ge" and c >= 0)
                or (op == "gt" and c > 0)
            ):
                return True
        return False

    def _between(self, fn: FuncSpec, src) -> np.ndarray:
        if src is not None:
            mask = self.column_filter_mask(fn, src)
            if mask is not None:
                return np.unique(np.asarray(src, np.uint64)[mask])
        lo = FuncSpec(name="ge", attr=fn.attr, args=[fn.args[0]], lang=fn.lang)
        hi = FuncSpec(name="le", attr=fn.attr, args=[fn.args[1]], lang=fn.lang)
        a = self._compare(lo, "ge", src)
        b = self._compare(hi, "le", src)
        return np.intersect1d(a, b, assume_unique=True)

    def _terms(self, fn: FuncSpec, src, tokname: str, require_all: bool) -> np.ndarray:
        su = self._schema(fn.attr)
        if tokname not in su.tokenizers:
            raise QueryError(
                f"predicate {fn.attr!r} needs @index({tokname}) for {fn.name}"
            )
        tok = get_tokenizer(tokname)
        text = Val(TypeID.STRING, str(fn.args[0]))
        toks = build_tokens(text, [tok], lang=fn.lang or "")
        if not toks:
            return EMPTY
        if require_all and self.stats is not None and len(toks) > 1:
            # cheapest (rarest) token first so the intersection collapses
            # early and the remaining lists never load (ref worker/task.go
            # planForEqFilter selectivity ordering via cm-sketch stats)
            toks = self.stats.plan_eq_order(fn.attr, toks)
        out = None
        for tb in toks:
            l = self._index_uids(fn.attr, tb)
            if out is None:
                out = l
            elif require_all:
                out = np.intersect1d(out, l, assume_unique=True)
            else:
                out = np.union1d(out, l)
            if require_all and not len(out):
                return EMPTY  # early exit: later lists never load
        if src is not None:
            out = np.intersect1d(out, src, assume_unique=True)
        if su.lang:
            # lang-aware re-check: the index matched tokens from any
            # language; re-tokenize the value in the requested lang.
            # `name@.` matches in ANY language (ref TestLangDotInFunction)
            want = set(toks)
            any_lang = fn.lang and "." in fn.lang.split(":")
            verified = []
            for u in out:
                if any_lang:
                    have = set()
                    for p in self.cache.values(
                        keys.DataKey(fn.attr, int(u), self.ns)
                    ):
                        if p.is_value:
                            have |= set(
                                build_tokens(p.val(), [tok], lang=p.lang)
                            )
                else:
                    got = self._value_of(fn.attr, int(u), fn.lang)
                    if got is None:
                        continue
                    have = set(
                        build_tokens(got, [tok], lang=fn.lang or "")
                    )
                hit = want <= have if require_all else bool(want & have)
                if hit:
                    verified.append(int(u))
            out = _as_uids(verified)
        return out.astype(np.uint64)

    def _regexp(self, fn: FuncSpec, src) -> np.ndarray:
        su = self._schema(fn.attr)
        arg = fn.args[0]
        if isinstance(arg, str) and len(arg) >= 2 and arg.startswith("/"):
            # $var substitution delivers the literal "/pattern/flags" text
            body, _, fl = arg[1:].rpartition("/")
            arg = ("regex", body, fl)
        if not (isinstance(arg, tuple) and arg[0] == "regex"):
            raise QueryError("regexp expects /pattern/flags")
        pattern, flags = arg[1], arg[2]
        pattern = _go_inline_flags(pattern)
        try:
            rx = re.compile(pattern, re.IGNORECASE if "i" in flags else 0)
        except re.error as e:
            raise QueryError(f"bad regexp {pattern!r}: {e}") from None
        # trigram prefilter (ref worker/task.go:1240 + tok trigram)
        cands = None
        if "trigram" in su.tokenizers:
            plain = _required_trigrams(pattern, flags)
            if plain:
                tok = get_tokenizer("trigram")
                lists = []
                for tri in plain:
                    lists.append(
                        self._index_uids(fn.attr, tok.prefix() + tri.encode())
                    )
                cands = lists[0]
                for l in lists[1:]:
                    cands = np.intersect1d(cands, l, assume_unique=True)
        if cands is None:
            cands = src if src is not None else self._scan_data_uids(fn.attr)
        out = []
        for u in cands:
            got = self._value_of(fn.attr, u, fn.lang)
            if got is not None and rx.search(str(got.value)):
                out.append(int(u))
        res = _as_uids(out)
        if src is not None:
            res = np.intersect1d(res, src, assume_unique=True)
        return res

    def _match(self, fn: FuncSpec, src) -> np.ndarray:
        """Fuzzy match by levenshtein distance over trigram candidates
        (ref worker/task.go:1526 matchFuzzy)."""
        su = self._schema(fn.attr)
        text = str(fn.args[0])
        max_dist = int(fn.args[1]) if len(fn.args) > 1 else 8
        cands = None
        if "trigram" in su.tokenizers:
            tok = get_tokenizer("trigram")
            lists = [
                self._index_uids(fn.attr, tb)
                for tb in tok.tokens(Val(TypeID.STRING, text))
            ]
            if lists:
                cands = lists[0]
                for l in lists[1:]:
                    cands = np.union1d(cands, l)
        if cands is None:
            cands = src if src is not None else self._scan_data_uids(fn.attr)
        out = []
        for u in cands:
            got = self._value_of(fn.attr, u, fn.lang)
            if got is not None and _levenshtein(str(got.value).lower(), text.lower()) <= max_dist:
                out.append(int(u))
        res = _as_uids(out)
        if src is not None:
            res = np.intersect1d(res, src, assume_unique=True)
        return res

    def _similar_to(self, fn: FuncSpec, src) -> np.ndarray:
        import json as _json

        attr = fn.attr
        idx = self.vector_indexes.get(attr)
        if idx is None:
            # an empty val(v) query arg means no query vector at all —
            # return empty rather than erroring (ref TestAggregateEmpty4)
            qa = fn.args[1] if len(fn.args) > 1 else None
            if isinstance(qa, tuple) and qa and qa[0] == "valarg" and \
                    not self.val_vars.get(qa[1]):
                return EMPTY
            raise QueryError(f"no vector index on predicate {attr!r}")
        k = int(fn.args[0])
        qarg = fn.args[1]
        if isinstance(qarg, tuple) and qarg and qarg[0] == "valarg":
            # similar_to(pred, k, val(v)): query by a var's vector value
            vmap = self.val_vars.get(qarg[1], {})
            vecs = [v.value for v in vmap.values()]
            if not vecs:
                return EMPTY
            qvec = np.asarray(vecs[0], dtype=np.float32)
        elif isinstance(qarg, str):
            qvec = np.asarray(_json.loads(qarg), dtype=np.float32)
        elif isinstance(qarg, (int,)):
            got = self._value_of(attr, qarg)
            if got is None:
                return EMPTY
            qvec = np.asarray(got.value, dtype=np.float32)
        else:
            qvec = np.asarray(qarg, dtype=np.float32)
        plain = (
            src is None
            and fn.options.get("ef") is None
            and fn.options.get("distance_threshold") is None
        )
        if plain and idx.dim is not None and qvec.size == idx.dim:
            # plain top-k: the batch-row form of the search (search_one
            # == row 0 of search_batch), so concurrent queries can
            # coalesce into one search_batch dispatch (serving/
            # microbatch.read_similar) with per-row demux — padding uid
            # 0 marks absent slots either way
            from dgraph_tpu.x import config as _config

            if self.batcher is not None and bool(
                _config.get("VEC_COALESCE")
            ):
                uids = self.batcher.read_similar(
                    attr, self.cache, idx, qvec, k
                )
            else:
                uids = idx.search_one(qvec, k)
            return _as_uids(uids[uids != 0])
        uids = idx.search(
            qvec,
            k,
            ef=fn.options.get("ef"),
            distance_threshold=fn.options.get("distance_threshold"),
            allowed=src,
        )
        return _as_uids(uids)

    def _geo(self, fn: FuncSpec, op: str, src) -> np.ndarray:
        from dgraph_tpu.tok.tok import GeoTokenizer

        su = self._schema(fn.attr)
        if "geo" not in su.tokenizers:
            raise QueryError(f"predicate {fn.attr!r} needs @index(geo)")
        if op == "contains":
            return self._geo_contains(fn, src)
        if op == "intersects":
            return self._geo_intersects(fn, src)
        if op == "near":
            coords, dist_m = fn.args[0], float(fn.args[1])
            lon, lat = float(coords[0]), float(coords[1])
            # degree radius approximation; verify with haversine after
            deg = dist_m / 111_000.0
            cand_cells = set()
            # pick the cell level so the disk spans ~8 cells per axis (the
            # S2-covering analog: coarse cells for big disks); tokens exist
            # at every level MIN..MAX so any level in range works
            import math as _math

            lvl = GeoTokenizer.MAX_LEVEL
            if deg > 0:
                want = int(_math.floor(_math.log2(max(2880.0 / deg, 2.0))))
                lvl = min(
                    GeoTokenizer.MAX_LEVEL, max(GeoTokenizer.MIN_LEVEL, want)
                )
            # sample at half the cell pitch so no covered cell is skipped
            step = min(360.0 / (1 << lvl), 180.0 / (1 << lvl)) / 2.0
            g = np.arange(lon - deg, lon + deg + 1e-9, step)
            gy = np.arange(lat - deg, lat + deg + 1e-9, step)
            for x in g:
                for y in gy:
                    cand_cells.add(GeoTokenizer.cell_at(float(x), float(y), lvl))
            tok = get_tokenizer("geo")
            lists = [
                self._index_uids(fn.attr, tok.prefix() + c) for c in cand_cells
            ]
            # areal geometries covering the point may be indexed only at
            # coarser levels — probe the point's cells at every level too
            lists.extend(
                self._index_uids(fn.attr, kt)
                for kt in self._geo_cells_of_point(lon, lat)
            )
            cands = np.unique(np.concatenate(lists)) if lists else EMPTY
            out = []
            for u in cands:
                got = self._value_of(fn.attr, u)
                if got is None:
                    continue
                d = _geo_distance_m(got.value, lon, lat)
                if d is not None and d <= dist_m:
                    out.append(int(u))
            res = _as_uids(out)
            if src is not None:
                res = np.intersect1d(res, src, assume_unique=True)
            return res
        if op == "within":
            # within(loc, [[[lon,lat],...]]) — points inside a polygon
            # (ref types/geofilter.go queryTokensGeo + filterGeo verify)
            ring = fn.args[0] if fn.args else None
            if not isinstance(ring, list) or not ring:
                raise QueryError("within() requires a non-empty polygon")
            if isinstance(ring[0], list) and ring[0] and isinstance(ring[0][0], list):
                ring = ring[0]  # polygon given as [ [ [lon,lat], ... ] ]
            if len(ring) < 3 or not all(
                isinstance(pt, list) and len(pt) >= 2 for pt in ring
            ):
                raise QueryError("within() polygon needs >=3 [lon,lat] points")
            lons = [float(p[0]) for p in ring]
            lats = [float(p[1]) for p in ring]
            # candidate cells: cover the bbox at a radius-matched level
            lon0, lon1 = min(lons), max(lons)
            lat0, lat1 = min(lats), max(lats)
            deg = max(lon1 - lon0, lat1 - lat0, 1e-6) / 2
            cx, cy = (lon0 + lon1) / 2, (lat0 + lat1) / 2
            near_fn = FuncSpec(
                name="near", attr=fn.attr,
                args=[[cx, cy], deg * 111_000.0 * 1.5],
            )
            cands = self._geo(near_fn, "near", src)
            out = []
            for u in cands:
                got = self._value_of(fn.attr, u)
                if got is None:
                    continue
                if _geom_within(got.value, ring):
                    out.append(int(u))
            return _as_uids(out)
        raise QueryError(f"geo function {op!r} not supported yet")


def _coerce(arg, tid: TypeID) -> Val:
    if isinstance(arg, Val):
        v = arg
    elif isinstance(arg, bool):
        v = Val(TypeID.BOOL, arg)
    elif isinstance(arg, int):
        v = Val(TypeID.INT, arg)
    elif isinstance(arg, float):
        v = Val(TypeID.FLOAT, arg)
    else:
        v = Val(TypeID.STRING, str(arg))
    if tid not in (TypeID.DEFAULT,) and v.tid != tid:
        return convert(v, tid)
    return v


def _pick_lang_val(posts, chain: str):
    """Language-preference value pick for @lang predicates (ref dql lang
    list semantics): '' = untagged only, 'en:fr' = first tag with a value,
    '.' = untagged else any."""
    if not chain:
        for p in posts:
            if p.lang == "":
                return p.val()
        return None
    for lang in chain.split(":"):
        if lang == ".":
            for p in posts:
                if p.lang == "":
                    return p.val()
            if posts:
                return posts[0].val()
            continue
        for p in posts:
            if p.lang == lang:
                return p.val()
    return None


def _val_eq(got: Optional[Val], want: Val) -> bool:
    if got is None:
        return False
    try:
        return compare_vals(convert(got, want.tid), want) == 0
    except ValueError:
        return False


def _go_inline_flags(pattern: str) -> str:
    """Translate Go/RE2 inline flag toggles Python re lacks: the common
    `(?i)X(?-i)Y` form becomes `(?i:X)Y` (scoped group)."""
    if "(?-" not in pattern:
        return pattern
    out = re.sub(r"\(\?i\)(.*?)\(\?-i\)", r"(?i:\1)", pattern)
    # strip any unpaired leftovers Python re would reject outright
    out = out.replace("(?-i)", "")
    return out


def _required_trigrams(pattern: str, flags: str = "") -> List[str]:
    """Longest literal run in the regex -> trigrams (ref uses a full regexp
    automaton analysis; literal-run subset). Returns [] (no prefilter, full
    verify) whenever the literal-run argument is unsound: alternation makes
    no single run required, and case-insensitive patterns don't match the
    case-sensitive index tokens."""
    if "|" in pattern or "i" in flags or "(?i" in pattern:
        return []
    # a character class matches many strings — nothing inside it is a
    # required literal (ref TestFilterRegex1 /^[Glen Rh]+$/)
    pat = re.sub(r"\[(?:\\.|[^\]])*\]", ".", pattern)
    # lookaround contents are not required
    pat = re.sub(r"\(\?[=!<][^)]*\)", ".", pat)
    # groups, innermost-first to a fixpoint: a quantified group's body is
    # optional/repeated (blank it); an unquantified group's body is
    # required exactly once (splice it into the surrounding run)
    prev = None
    while prev != pat:
        prev = pat
        pat = re.sub(
            r"\((?:\?:)?(?:\\.|[^()\\])*\)(?:[*?+]|\{[^}]*\})", ".", pat
        )
        pat = re.sub(r"\((?:\?:)?((?:\\.|[^()\\])*)\)", r"\1", pat)
    if "(" in pat or ")" in pat:
        return []  # unbalanced/exotic nesting: no safe prefilter
    # anything quantified by {m,n} or ?/* is not required either
    pat = re.sub(r"(\\.|[^\\])\{[^}]*\}", ".", pat)
    pat = re.sub(r"(\\.|[^\\.*+?{}^$])[*?]", ".", pat)
    lit = max(re.split(r"[\.\*\+\?\[\]\(\)\\\^\$\{\}]", pat), key=len, default="")
    if len(lit) < 3:
        return []
    return [lit[i : i + 3] for i in range(len(lit) - 2)]


def _levenshtein(a: str, b: str) -> int:
    if len(a) < len(b):
        a, b = b, a
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[-1] + 1, prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def _geo_rings(geo) -> list:
    """Outer rings of a stored polygon/multipolygon GeoJSON value."""
    t = geo.get("type", "").lower()
    c = geo.get("coordinates")
    if t == "polygon":
        return [c[0]] if c else []
    if t == "multipolygon":
        return [poly[0] for poly in c if poly]
    return []


def _segments_intersect(p1, p2, p3, p4) -> bool:
    def ccw(a, b, c):
        return (c[1] - a[1]) * (b[0] - a[0]) > (b[1] - a[1]) * (c[0] - a[0])

    return ccw(p1, p3, p4) != ccw(p2, p3, p4) and ccw(p1, p2, p3) != ccw(
        p1, p2, p4
    )


def _polys_intersect(ring_a, ring_b) -> bool:
    """Outer-ring intersection test: vertex containment either way or any
    edge crossing (sufficient for simple polygons, ref geofilter
    Intersects verification)."""
    if any(_point_in_poly(p[0], p[1], ring_b) for p in ring_a):
        return True
    if any(_point_in_poly(p[0], p[1], ring_a) for p in ring_b):
        return True
    ea = list(zip(ring_a, ring_a[1:] + ring_a[:1]))
    eb = list(zip(ring_b, ring_b[1:] + ring_b[:1]))
    return any(
        _segments_intersect(a1, a2, b1, b2) for a1, a2 in ea for b1, b2 in eb
    )


def _on_segment(x, y, x1, y1, x2, y2, eps: float = 1e-12) -> bool:
    """Point (x, y) lies on the segment (x1,y1)-(x2,y2)."""
    cross = (x2 - x1) * (y - y1) - (y2 - y1) * (x - x1)
    if abs(cross) > eps:
        return False
    return min(x1, x2) - eps <= x <= max(x1, x2) + eps and (
        min(y1, y2) - eps <= y <= max(y1, y2) + eps
    )


def _poly_side(x: float, y: float, ring) -> str:
    """Ray-cast classification: 'in', 'edge', or 'out'."""
    n = len(ring)
    j = n - 1
    inside = False
    for i in range(n):
        xi, yi = float(ring[i][0]), float(ring[i][1])
        xj, yj = float(ring[j][0]), float(ring[j][1])
        if _on_segment(x, y, xi, yi, xj, yj):
            return "edge"
        if (yi > y) != (yj > y) and x < (xj - xi) * (y - yi) / (yj - yi) + xi:
            inside = not inside
        j = i
    return "in" if inside else "out"


def _point_in_poly(x: float, y: float, ring) -> bool:
    """Boundary-inclusive point-in-polygon (ref S2 contains semantics:
    a point on the edge or a vertex counts as inside)."""
    return _poly_side(x, y, ring) != "out"


def _geo_distance_m(geom: dict, lon: float, lat: float) -> Optional[float]:
    """Distance in meters from a query point to a stored GeoJSON value:
    0 when an areal geometry contains the point, else min vertex/edge
    distance (ref types/geofilter.go near over points and areas)."""
    t = str(geom.get("type", "")).lower()
    c = geom.get("coordinates")
    if c is None:
        return None
    if t == "point":
        return _haversine_m(lat, lon, float(c[1]), float(c[0]))
    rings = _geo_rings(geom)
    if not rings:
        return None
    best = None
    for ring in rings:
        if _point_in_poly(lon, lat, ring):
            return 0.0
        for p in ring:
            d = _haversine_m(lat, lon, float(p[1]), float(p[0]))
            if best is None or d < best:
                best = d
    return best


def _geom_within(geom: dict, qring) -> bool:
    """Stored geometry fully inside the query ring (vertex containment —
    adequate for convex-ish test fixtures; ref geo.Within)."""
    t = str(geom.get("type", "")).lower()
    c = geom.get("coordinates")
    if c is None:
        return False
    if t == "point":
        return _point_in_poly(float(c[0]), float(c[1]), qring)
    # polygons must be STRICTLY inside: a stored ring identical to the
    # query ring (vertices on the boundary) is NOT within it, matching
    # the reference's nested-loop semantics (ref TestWithinPolygon:
    # Mountain View == the query polygon and is excluded)
    rings = _geo_rings(geom)
    return bool(rings) and all(
        _poly_side(float(p[0]), float(p[1]), qring) == "in"
        for ring in rings
        for p in ring
    )


def _haversine_m(lat1, lon1, lat2, lon2) -> float:
    import math

    r = 6_371_000.0
    p1, p2 = math.radians(lat1), math.radians(lat2)
    dp = math.radians(lat2 - lat1)
    dl = math.radians(lon2 - lon1)
    a = math.sin(dp / 2) ** 2 + math.cos(p1) * math.cos(p2) * math.sin(dl / 2) ** 2
    return 2 * r * math.asin(math.sqrt(a))
