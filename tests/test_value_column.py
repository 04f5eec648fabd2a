"""Resident value columns (query/valcol.py, ops/valcol.py): a filter or
an order that brings a predicate the device line's candidates is
answered from the predicate's values held on the device as (uids, rank
of the value). Every case holds the served answer against the
value-by-value path (`FuncRunner._compare`'s loop,
`Executor._order_uids_generic`) on the same store, pins the counters it
expects, and the validity rules: a commit to the predicate drops the
column before it is readable, a reader below the column's timestamp and
a transaction with its own write take the old path. The jitted programs
run on the CPU backend with the device line lowered.
"""

import argparse
import datetime
import json
import os

import numpy as np
import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.ops import valcol as kernels
from dgraph_tpu.query import dispatch, valcol
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS, TRACER
from dgraph_tpu.x import keys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA = """
grp: string @index(exact) .
name: string @index(exact) .
age: int .
pid: int @index(int) .
score: float .
seen: datetime @index(hour) .
tags: [int] .
"""
N = 600
LINE = 64
UTC = datetime.timezone.utc
COUNTERS = (
    'device_dispatch_total{family="column#filter"}',
    'device_dispatch_total{family="column#narrow"}',
    'order_window_total{path="column"}',
    'order_window_total{path="narrowed"}',
    'order_single_total{path="column"}',
    "order_candidates_total", "order_kept_total",
    "value_column_builds_total", "value_column_invalidations_total",
    "value_column_patched_rows_total",
    'value_column_fallback_total{why="stale"}',
    'value_column_fallback_total{why="txn"}',
    'value_column_fallback_total{why="type"}',
)


def _people():
    """uid -> attrs. `age` has 41 values (ties everywhere) and every
    seventh person none; `pid` differs for everyone; `seen` mixes three
    UTC offsets, repeats instants, and every fifth person has none;
    `score` is a float with quarters; group "few" holds 12 persons of
    whom 3 have an `age`."""
    rng = np.random.default_rng(36)
    pids = rng.permutation(N) + 5000
    people = {}
    for u in range(1, N + 1):
        p = {"grp": "few" if u % 50 == 0 else "all", "name": f"u{u}",
             "pid": int(pids[u - 1]),
             "score": float(rng.integers(0, 12)) + float(
                 rng.choice([0.0, 0.25, 0.5, 0.75]))}
        if u % 7 and (p["grp"] == "all" or u % 200 == 0):
            p["age"] = int(rng.integers(0, 41))
        if u % 5:
            zone = datetime.timezone(datetime.timedelta(
                minutes=int(rng.choice([0, 330, -480]))))
            at = datetime.datetime(2021, 6, 1, tzinfo=UTC) + (
                datetime.timedelta(minutes=int(rng.integers(0, 6000))))
            p["seen"] = at.astimezone(zone).isoformat()
        people[u] = p
    return people


PEOPLE = _people()


def _rdf():
    out = []
    for u, p in PEOPLE.items():
        for attr, v in p.items():
            if attr in ("age", "pid"):
                v = f'"{v}"^^<xs:int>'
            elif attr == "score":
                v = f'"{v}"^^<xs:float>'
            elif attr == "seen":
                v = f'"{v}"^^<xs:dateTime>'
            else:
                v = f'"{v}"'
            out.append(f"<0x{u:x}> <{attr}> {v} .")
        out.append(f'<0x{u:x}> <tags> "{u % 9}"^^<xs:int> .')
    return "\n".join(out)


@pytest.fixture(scope="module")
def server():
    s = Server()
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(), commit_now=True)
    return s


@pytest.fixture
def line(monkeypatch):
    """The device line at 64 candidates on the CPU backend."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)


def _off(monkeypatch):
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 1 << 40)


def _moved(run):
    before = {c: METRICS.value(c) for c in COUNTERS}
    out = run()
    return out, {c: int(METRICS.value(c) - before[c]) for c in COUNTERS
                 if METRICS.value(c) != before[c]}


def _uids(data):
    return [int(r["uid"], 16) for r in data["data"]["q"]]


def _instant(text):
    return datetime.datetime.fromisoformat(text)


def _key(attr, p):
    v = p.get(attr)
    return _instant(v) if attr == "seen" and v is not None else v


def _bound_of(attr):
    """A stored value of `attr` near the middle, as the DQL writes it
    and as Python compares it."""
    vals = sorted(_key(attr, p) for p in PEOPLE.values()
                  if _key(attr, p) is not None)
    v = vals[len(vals) // 2]
    return (f'"{v.isoformat()}"' if attr == "seen" else repr(v)), v


OPS = {"lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
       "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b}


# -- the filter ------------------------------------------------------------


@pytest.mark.parametrize("op", ["lt", "le", "gt", "ge", "between"])
@pytest.mark.parametrize("attr", ["age", "seen", "score"])
def test_a_filter_reads_what_the_value_loop_reads(server, line, monkeypatch,
                                                  attr, op):
    """The bound equals a stored value, so lt and le (gt and ge) differ
    by the ids that hold it; ids without a value fail every bound."""
    text, v = _bound_of(attr)
    if op == "between":
        lo = sorted(_key(attr, p) for p in PEOPLE.values()
                    if _key(attr, p) is not None)[N // 8]
        lo_text = f'"{lo.isoformat()}"' if attr == "seen" else repr(lo)
        fn = f"between({attr}, {lo_text}, {text})"
        want = [u for u, p in PEOPLE.items() if _key(attr, p) is not None
                and lo <= _key(attr, p) <= v]
    else:
        fn = f"{op}({attr}, {text})"
        want = [u for u, p in PEOPLE.items() if _key(attr, p) is not None
                and OPS[op](_key(attr, p), v)]
    q = f"{{ q(func: has(name)) @filter({fn}) {{ uid }} }}"
    got, moved = _moved(lambda: _uids(server.query(q)))
    assert got == want and 0 < len(want) < N
    assert moved['device_dispatch_total{family="column#filter"}'] == 1
    assert not any("fallback" in c for c in moved)
    _off(monkeypatch)
    old, moved = _moved(lambda: _uids(server.query(q)))
    assert old == want and not any("column" in c for c in moved)


def test_a_lone_filter_on_a_level_masks_its_rows_where_they_lie(
        server, line):
    """`~owner @filter(lt(..))`-shaped: the level's flat ids go to the
    column as they lie, and no set op follows."""
    text, v = _bound_of("seen")
    q = ('{ q(func: eq(grp, "few")) { name } '
         f'r(func: eq(grp, "all")) @filter(lt(seen, {text})) {{ uid }} }}')
    before = METRICS.value("device_dispatch_total")
    got = [int(r["uid"], 16) for r in server.query(q)["data"]["r"]]
    assert got == [u for u, p in PEOPLE.items() if p["grp"] == "all"
                   and "seen" in p and _instant(p["seen"]) < v]
    assert METRICS.value("device_dispatch_total") - before == 1


# -- the narrowing ---------------------------------------------------------


def _model_order(group, orders, first, offset=0):
    """Python's sort of the stored values: missing last in both
    directions, ties by uid in the LAST key's direction."""
    ids = [u for u, p in PEOPLE.items() if group in (p["grp"], "any")]

    def cmp(a, b):
        for attr, desc in orders:
            x, y = _key(attr, PEOPLE[a]), _key(attr, PEOPLE[b])
            if x == y:
                continue
            if x is None or y is None:
                return 1 if x is None else -1
            return (-1 if x < y else 1) * (-1 if desc else 1)
        return (-1 if a < b else 1) * (-1 if orders[-1][1] else 1)

    import functools

    ids.sort(key=functools.cmp_to_key(cmp))
    return ids[offset:offset + first]


def _order_text(orders):
    return ", ".join(f"order{'desc' if d else 'asc'}: {a}" for a, d in orders)


@pytest.mark.parametrize("offset", [0, 5])
@pytest.mark.parametrize("desc", [False, True])
@pytest.mark.parametrize("lead", ["age", "seen", "pid", "score"])
def test_a_window_of_two_keys_is_cut_on_the_device(server, line, monkeypatch,
                                                   lead, desc, offset):
    """`age` and `score` have no index, `seen` is a datetime (mixed
    offsets: by instant), `pid`'s 600 buckets are over the walk's
    budget: all four are cut from the column; the comparator orders the
    kept ids by both keys, and ties of the window's last value are
    among them."""
    orders = [(lead, desc), ("name", not desc)]
    page = f", offset: {offset}" if offset else ""
    q = (f'{{ q(func: has(name), {_order_text(orders)}, first: 7{page}) '
         "{ uid } }")
    want = _model_order("any", orders, 7, offset)
    got, moved = _moved(lambda: _uids(server.query(q)))
    assert got == want
    assert moved["order_candidates_total"] == N
    if lead == "pid" and not desc:
        # an ascending walk is lazy and met the window in its first
        # buckets: the column is for what the walks give up on
        assert moved['order_window_total{path="narrowed"}'] == 1
        assert moved["order_kept_total"] == 7 + offset
        return
    assert moved['order_window_total{path="column"}'] == 1
    assert moved['device_dispatch_total{family="column#narrow"}'] == 1
    # the ids at or beyond the window's last value, its ties included
    # (~14 persons share an age)
    vals = sorted((_key(lead, p) for p in PEOPLE.values()
                   if _key(lead, p) is not None), reverse=desc)
    cut = vals[7 + offset - 1]
    assert moved["order_kept_total"] == sum(
        (v >= cut) if desc else (v <= cut) for v in vals) < N // 4
    _off(monkeypatch)
    old, moved = _moved(lambda: _uids(server.query(q)))
    assert old == want and moved["order_kept_total"] == N


@pytest.mark.parametrize("offset", [0, 3])
@pytest.mark.parametrize("desc", [False, True])
def test_one_key_the_walk_gives_up_on_is_cut_on_the_device(
        server, line, monkeypatch, desc, offset):
    """`seen` fills more hour buckets than 600 // 8: a descending walk
    lists them and gives up, an ascending one reads its budget; either
    way the column narrows and the comparator breaks ties as the walk
    would have."""
    page = f", offset: {offset}" if offset else ""
    q = (f"{{ q(func: has(name), order{'desc' if desc else 'asc'}: seen, "
         f"first: 6{page}) {{ uid }} }}")
    got, moved = _moved(lambda: _uids(server.query(q)))
    _off(monkeypatch)
    old, old_moved = _moved(lambda: _uids(server.query(q)))
    assert got == old
    if desc:  # more buckets than the budget to list: the column
        assert moved['order_single_total{path="column"}'] == 1
        assert moved['device_dispatch_total{family="column#narrow"}'] == 1
        assert moved["order_kept_total"] < N // 4
    else:  # the lazy ascending walk met the window inside its budget
        assert 'order_single_total{path="column"}' not in moved
    assert [_key("seen", PEOPLE[u]) for u in got] == sorted(
        (_key("seen", PEOPLE[u]) for u in got), reverse=desc)


@pytest.mark.parametrize("desc", [False, True])
def test_fewer_valued_ids_than_the_window_keeps_every_id(server, monkeypatch,
                                                         desc):
    """Group "few": 12 persons, 3 with an `age`; `first: 10` needs ids
    with no value too, which sort after the valued ones in uid order
    along the last key's direction."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 8)
    orders = [("age", desc), ("pid", desc)]
    q = (f'{{ q(func: eq(grp, "few"), {_order_text(orders)}, first: 10) '
         "{ uid } }")
    got, moved = _moved(lambda: _uids(server.query(q)))
    assert got == _model_order("few", orders, 10)
    assert sum("age" in PEOPLE[u] for u in got) == 3
    assert moved['order_window_total{path="column"}'] == 1
    assert moved["order_kept_total"] == 12  # every candidate


@pytest.mark.parametrize("desc", [False, True])
def test_mixed_utc_offsets_order_by_instant(server, line, desc):
    q = (f"{{ q(func: has(seen), order{'desc' if desc else 'asc'}: seen, "
         "orderasc: pid, first: 40) { uid seen } }")
    rows = server.query(q)["data"]["q"]
    at = [_instant(r["seen"]) for r in rows]
    assert at == sorted(at, reverse=desc)
    assert len({a.utcoffset() for a in at}) == 3
    # as written, offset and all, the texts are NOT in order
    texts = [r["seen"] for r in rows]
    assert texts != sorted(texts, reverse=desc)


def _cache_of(server):
    from dgraph_tpu.posting.lists import LocalCache

    return LocalCache(server.kv, server._snapshot_ts, mem=server.mem)


@pytest.mark.parametrize("use", ["filter", "narrow"])
def test_candidates_in_any_order_and_with_repeats(server, line, use):
    """The programs look every candidate up on its own: the mask lines
    up with the ids as given."""
    rng = np.random.default_rng(5)
    ids = rng.integers(1, N + 40, 900).astype(np.uint64)  # some unknown
    cache = _cache_of(server)
    age = np.array([PEOPLE.get(int(u), {}).get("age", -1) for u in ids])
    if use == "filter":
        from dgraph_tpu.types.types import TypeID, Val

        mask = valcol.filter_mask(
            cache, server.schema, keys.GALAXY_NS, "age", "", ids,
            [("ge", Val(TypeID.INT, 10)), ("lt", Val(TypeID.INT, 25))])
        assert mask.tolist() == ((age >= 10) & (age < 25)).tolist()
    else:
        mask, valued = valcol.narrow_mask(
            cache, server.schema, keys.GALAXY_NS, "age", ids, 30, True)
        assert valued == int((age >= 0).sum())
        cut = np.sort(age[age >= 0])[-30]
        assert mask.tolist() == (age >= cut).tolist()


def test_the_window_cut_keeps_ties_and_refills():
    """`ops/valcol.window_keep` alone, on both key types."""
    import jax.numpy as jnp

    key = jnp.asarray([5, 9, 9, 1, 9, 7, 0, 0], jnp.int32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 0], bool)
    keep, valued = kernels.window_keep(key, valid, 6, 2)
    assert keep.tolist() == [0, 1, 1, 0, 1, 0, 0, 0] and int(valued) == 6
    keep, _ = kernels.window_keep(key, valid, 6, 4)
    assert keep.tolist() == [0, 1, 1, 0, 1, 1, 0, 0]
    keep, valued = kernels.window_keep(key, valid & (key > 8), 6, 4)
    assert keep.tolist() == [1, 1, 1, 1, 1, 1, 0, 0] and int(valued) == 3
    score = jnp.asarray([0.5, jnp.nan, 2.0, 2.0, -1.0, 0.0, 0.0, 0.0])
    keep, valued = kernels.scores_narrow(score, 5, 1)
    assert keep.tolist() == [0, 0, 1, 1, 0, 0, 0, 0] and int(valued) == 4


@pytest.mark.parametrize("kind", ["int", "float"])
def test_the_window_cut_against_a_sort(kind):
    """The cut is found bit by bit, not by sorting: against numpy's sort
    on random keys, negative ones, both zeros and the infinities."""
    import jax

    cut_of = jax.jit(kernels.window_keep)
    rng = np.random.default_rng(17)
    for trial in range(60):
        size = int(rng.choice([8, 64, 256]))
        n = int(rng.integers(0, size + 1))
        if kind == "int":
            key = (rng.integers(-5, 6, size) * int(
                rng.choice([1, 400_000_000]))).astype(np.int32)
        else:
            key = rng.choice([-np.inf, -2.5, -0.0, 0.0, 1.5, 3e38, np.inf],
                             size).astype(np.float32)
        valid = (rng.random(size) < 0.7) & (np.arange(size) < n)
        need = int(rng.integers(1, size + 2))
        keep, valued = cut_of(key, valid, np.int32(n), np.int32(need))
        assert int(valued) == valid.sum()
        want = (np.arange(size) < n if valid.sum() < need
                else valid & (key >= np.sort(key[valid])[-need]))
        assert np.asarray(keep).tolist() == want.tolist(), (trial, need)


# -- the column's two device forms -----------------------------------------

TOP = 0xFFFFFFFF


def _column_shape(name):
    """(sorted low-32 uids, padded rows) of one column shape."""
    rng = np.random.default_rng(4500 + len(name))

    def some(lo, span, rows):  # `rows` of [lo, lo + span), both ends in
        mid = rng.choice(np.arange(lo + 1, lo + span - 1), rows - 2,
                         replace=False)
        return np.sort(np.r_[lo, mid, lo + span - 1]).astype(np.uint32)

    low = {
        "dense": lambda: np.arange(7000, 7300, dtype=np.uint32),
        "half_empty": lambda: some(90_000, 500, 250),
        "at_the_line": lambda: some(5000, 256, 100),  # table == 2 * pb
        "past_the_line": lambda: some(5000, 257, 100),
        "one_row": lambda: np.array([77], np.uint32),
        "lo_zero": lambda: some(0, 180, 150),
        "top_uid": lambda: some(TOP - 299, 300, 260),
    }[name]()
    return low, dispatch._pow2(len(low))


@pytest.mark.parametrize("shape", [
    "dense", "half_empty", "at_the_line", "past_the_line", "one_row",
    "lo_zero", "top_uid"])
def test_the_table_form_answers_as_the_search_form(shape):
    """Both device forms of one column give every candidate the same
    filter mask and the same narrowing, bit for bit: candidates in any
    order and repeated, under the smallest uid and past the span,
    UINT32_MAX padding inside [0, n) (a candidate the delta holds),
    and a column holding the largest low uid. A span wider than twice
    the padded rows takes the search form; the table is built there
    all the same, to hold it to the search form."""
    import jax

    low, pb = _column_shape(shape)
    lo, span = int(low[0]), int(low[-1]) - int(low[0]) + 1
    size = valcol._table_size(low, pb)
    assert (size == 0) == (shape == "past_the_line"), (size, pb)
    size = size or dispatch._pow2(span)
    assert size == dispatch._pow2(span) >= span
    rng = np.random.default_rng(45)
    rank = rng.integers(0, 40, len(low)).astype(np.int32)
    distinct = np.arange(40)

    def column(size):
        col = valcol.Column(0, len(low), pb, 0, distinct, None, uids=low,
                            keys=distinct[rank], size=size)
        return col.operands(valcol._device_arrays(low, rank, pb, size))

    table, search = column(size), column(0)
    assert (table[0], table[2], search[0], search[2]) == (
        "table", size, "search", pb)
    holes = np.setdiff1d(np.arange(lo, lo + span, dtype=np.uint32), low)
    outside = [x % (1 << 32) for x in (lo - 1, lo - 1000, 0, lo + span,
                                       lo + size, lo + size + 5, TOP - 1)]
    cand = np.concatenate([
        rng.choice(low, 700), rng.choice(holes, 100) if len(holes) else [],
        outside]).astype(np.uint32)
    cand = rng.permutation(cand)
    cand[rng.choice(len(cand), 9, replace=False)] = TOP  # held by a delta
    n = len(cand)
    padded = np.full(dispatch._pow4(n), TOP, np.uint32)
    padded[:n] = cand
    held = np.isin(cand, low)
    key = np.full(n, -1)
    key[held] = rank[np.searchsorted(low, cand[held])]
    run = {form: {use: jax.jit(kernels.program(use, form))
                  for use in ("filter", "narrow")}
           for form, ops, _ in (table, search)}

    def both(use, *scalars):
        return [[np.asarray(o) for o in jax.tree_util.tree_leaves(
            run[form][use](padded, np.int32(n), *ops, *scalars))]
            for form, ops, _ in (table, search)]

    for a, b in ((0, 10), (5, 5), (20, np.iinfo(np.int32).max), (3, 2)):
        got, want = both("filter", np.int32(a), np.int32(b))
        assert got[0].tolist() == want[0].tolist()
        passed = held & (key >= a) & (key <= b)
        assert want[0][:n].tolist() == passed.tolist()
        assert not want[0][n:].any()
    for need in (1, 20, int(held.sum()) + 1):
        for desc in (False, True):
            got, want = both("narrow", np.int32(need), np.bool_(desc))
            assert [g.tolist() for g in got] == [w.tolist() for w in want]
            assert int(want[1]) == held.sum()


@pytest.mark.parametrize("desc", [False, True])
def test_a_value_var_order_is_cut_on_the_device_and_sorted_exactly(desc):
    """`_order_uids_topk`: float32 rounding makes ties of 2**24 and
    2**24 + 1; the comparator still orders them by the exact values."""
    s = Server()
    s.alter("rank: int .\nname: string @index(exact) .")
    n, big = 5000, 1 << 24
    rng = np.random.default_rng(9)
    ranks = (rng.permutation(n) + big - 50).tolist()
    s.new_txn().mutate_rdf(set_rdf="\n".join(
        f'<0x{i + 1:x}> <name> "u" .\n'
        f'<0x{i + 1:x}> <rank> "{r}"^^<xs:int> .'
        for i, r in enumerate(ranks) if i % 10), commit_now=True)
    q = ("{ v as var(func: has(rank)) { r as rank } q(func: uid(v), "
         f"order{'desc' if desc else 'asc'}: val(r), first: 9, offset: 2) "
         "{ rank } }")
    before = METRICS.value('device_dispatch_total{family="column#scores"}')
    got = [r["rank"] for r in s.query(q)["data"]["q"]]
    held = sorted((r for i, r in enumerate(ranks) if i % 10), reverse=desc)
    assert got == held[2:11]
    assert METRICS.value(
        'device_dispatch_total{family="column#scores"}') - before == 1


# -- when a column may be used ---------------------------------------------


def _fresh(extra=""):
    s = Server()
    s.alter("age: int .\nname: string @index(exact) .\n"
            "other: int .\ntags: [int] ." + extra)
    s.new_txn().mutate_rdf(set_rdf="\n".join(
        f'<0x{u:x}> <name> "u{u}" .\n<0x{u:x}> <age> "{u % 50}"^^<xs:int> .\n'
        f'<0x{u:x}> <tags> "{u % 3}"^^<xs:int> .'
        for u in range(1, 201)), commit_now=True)
    return s


Q_YOUNG = "{ q(func: has(name)) @filter(lt(age, 3)) { uid } }"
YOUNG = [u for u in range(1, 201) if u % 50 < 3]


def test_a_commit_to_the_predicate_drops_the_column(line):
    """Only a commit the column cannot follow drops it: values of its
    type are patched in at the commit (its delta), with no build and no
    invalidation; a value of another type drops it before it is
    readable, and the next request finds the predicate unfit."""
    s = _fresh()
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == YOUNG and moved["value_column_builds_total"] == 1
    _, moved = _moved(lambda: s.new_txn().mutate_rdf(
        set_rdf='<0x7> <age> "1"^^<xs:int> .\n<0x1> <age> "44"^^<xs:int> .',
        commit_now=True))
    assert moved == {"value_column_patched_rows_total": 2}
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == sorted(set(YOUNG) - {1} | {7})
    assert moved == {'device_dispatch_total{family="column#filter"}': 1}
    from dgraph_tpu.posting.pl import VALUE_UID, Posting
    from dgraph_tpu.types.types import TypeID, Val, to_binary

    t = s.new_txn()
    t.txn.cache.add_delta(keys.DataKey("age", 9), Posting(
        VALUE_UID, 1, to_binary(Val(TypeID.STRING, "x")), TypeID.STRING))
    _, moved = _moved(t.commit)
    assert moved == {"value_column_invalidations_total": 1}
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == sorted(set(YOUNG) - {1} | {7})
    assert moved == {'value_column_fallback_total{why="type"}': 1}


def test_a_commit_to_another_predicate_leaves_it(line):
    s = _fresh()
    s.query(Q_YOUNG)
    _, moved = _moved(lambda: s.new_txn().mutate_rdf(
        set_rdf='<0x7> <other> "1"^^<xs:int> .', commit_now=True))
    assert "value_column_invalidations_total" not in moved
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == YOUNG and "value_column_builds_total" not in moved
    assert moved['device_dispatch_total{family="column#filter"}'] == 1


def test_an_older_read_timestamp_takes_the_value_loop(line):
    s = _fresh()
    old_ts = s._snapshot_ts
    s.new_txn().mutate_rdf(set_rdf='<0x7> <age> "1"^^<xs:int> .',
                           commit_now=True)
    # no column yet: one built from the old view would miss the commit
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG, read_ts=old_ts)))
    assert got == YOUNG
    assert moved == {'value_column_fallback_total{why="stale"}': 1}
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == sorted(YOUNG + [7])
    assert moved["value_column_builds_total"] == 1
    # and with the column resident, the old reader still may not use it
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG, read_ts=old_ts)))
    assert got == YOUNG
    assert moved == {'value_column_fallback_total{why="stale"}': 1}


def test_a_transactions_own_write_takes_the_value_loop(line):
    s = _fresh()
    s.query(Q_YOUNG)
    t = s.new_txn()
    t.mutate_rdf(set_rdf='<0x9> <age> "0"^^<xs:int> .')
    got, moved = _moved(lambda: _uids(t.query(Q_YOUNG)))
    assert got == sorted(YOUNG + [9])
    assert moved == {'value_column_fallback_total{why="txn"}': 1}
    t.discard()
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == YOUNG and "value_column_builds_total" not in moved


@pytest.mark.parametrize("fn", ["lt(tags, 1)", 'lt(name, "u2")'])
def test_a_list_or_a_string_predicate_has_no_column(line, fn):
    s = _fresh()
    q = f"{{ q(func: has(name)) @filter({fn}) {{ uid }} }}"
    got, moved = _moved(lambda: _uids(s.query(q)))
    assert moved == {'value_column_fallback_total{why="type"}': 1}
    assert 0 < len(got) < 200


def test_under_the_line_nothing_is_built_or_counted(monkeypatch):
    s = _fresh()
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 201)
    got, moved = _moved(lambda: _uids(s.query(Q_YOUNG)))
    assert got == YOUNG and moved == {}
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 200)
    _, moved = _moved(lambda: s.query(Q_YOUNG))
    assert moved["value_column_builds_total"] == 1


def test_a_resident_column_serves_smaller_sets_than_build_one(monkeypatch):
    """The device line builds; `_RESIDENT_MIN_IDS` and up are served by
    a column that is there already; fewer read value by value."""
    s = Server()
    s.alter("age: int .\nname: string @index(exact) .\n"
            "grp: string @index(exact) .")
    n = 6000
    s.new_txn().mutate_rdf(set_rdf="\n".join(
        f'<0x{u:x}> <name> "u{u}" .\n<0x{u:x}> <age> "{u % 50}"^^<xs:int> .\n'
        f'<0x{u:x}> <grp> "{"half" if u % 2 else "tenth" if u % 10 == 0 else "x"}" .'
        for u in range(1, n + 1)), commit_now=True)
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 5000)
    assert valcol._RESIDENT_MIN_IDS == 2048

    def young(grp):
        q = f'{{ q(func: eq(grp, "{grp}")) @filter(lt(age, 3)) {{ uid }} }}'
        return _moved(lambda: _uids(s.query(q)))

    got, moved = young("half")  # 3,000 candidates, nothing resident
    assert moved == {} and got == [u for u in range(1, n + 1, 2) if u % 50 < 3]
    _, moved = _moved(lambda: s.query(
        "{ q(func: has(name)) @filter(lt(age, 3)) { uid } }"))
    assert moved["value_column_builds_total"] == 1
    got, moved = young("half")
    assert moved == {'device_dispatch_total{family="column#filter"}': 1}
    assert got == [u for u in range(1, n + 1, 2) if u % 50 < 3]
    got, moved = young("tenth")  # 600 candidates: the value loop
    assert moved == {} and got == [u for u in range(10, n + 1, 10)
                                   if u % 50 < 3]


def test_an_alter_and_a_bulk_load_drop_every_column(line):
    s = _fresh()
    s.query(Q_YOUNG)
    _, moved = _moved(lambda: s.alter("more: int ."))
    assert moved["value_column_invalidations_total"] == 1
    _, moved = _moved(lambda: s.query(Q_YOUNG))
    assert moved["value_column_builds_total"] == 1
    _, moved = _moved(s.bump_snapshot)
    assert moved["value_column_invalidations_total"] == 1


def test_a_build_that_a_commit_overtook_is_not_published():
    cols = valcol.ValueColumns()
    prefix = keys.DataPrefix("age")

    def state(p):  # what a reader above every commit is handed
        return cols.use(p, 1 << 62)[:3]

    _, gen, floor = state(prefix)
    assert (gen, floor) == (0, 0)
    cols.note_commit([keys.DataKey("age", 7)], 41)
    col = valcol.Column(40, 1, 8, 0, np.zeros(1), ("t",))
    assert cols.publish(prefix, gen, col) is False
    got, gen, floor = state(prefix)
    assert got is None and (gen, floor) == (1, 41)
    assert cols.publish(prefix, gen, col) is True
    assert state(prefix)[0] is col
    # a commit elsewhere moves no generation; a prefix first asked for
    # later starts from the newest commit anywhere
    cols.note_commit([keys.DataKey("other", 7)], 50)
    assert state(prefix) == (col, 1, 41)
    assert state(keys.DataPrefix("late"))[2] == 50
    cols.clear(60)
    assert state(prefix) == (None, 2, 60)


def test_an_unfit_predicate_is_remembered_until_its_next_commit(line):
    """Stored values of another type than the schema's: one scan says
    so, later requests do not scan again."""
    s = _fresh()
    from dgraph_tpu.posting.pl import Posting
    from dgraph_tpu.types.types import TypeID, to_binary, Val

    t = s.new_txn()
    t.txn.cache.add_delta(keys.DataKey("age", 300), Posting(
        (1 << 64) - 1, 1, to_binary(Val(TypeID.STRING, "x")),
        TypeID.STRING))
    t.commit()
    calls = []
    real = valcol._build

    def counted(*a):
        calls.append(1)
        return real(*a)

    with pytest.MonkeyPatch.context() as m:
        m.setattr(valcol, "_build", counted)
        for _ in range(2):
            _, moved = _moved(lambda: s.query(Q_YOUNG))
            assert moved == {'value_column_fallback_total{why="type"}': 1}
    assert calls == [1]


# -- spans and counters ----------------------------------------------------


def test_the_spans_of_a_column_dispatch_and_their_counters(
        server, line, monkeypatch):
    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 0.0)
    text, _ = _bound_of("seen")
    q = (f"{{ var(func: has(name)) @filter(lt(seen, {text})) {{ m as uid }} "
         "q(func: uid(m), orderdesc: seen, orderasc: pid, first: 5) "
         "{ uid } }")
    server.query(q)  # the column is resident from here on
    before = METRICS.snapshot("device_dispatch_total")
    looked = METRICS.snapshot("value_column_lookups_total")
    with TRACER.span("http.request") as root:
        server.query(q)
    after = METRICS.snapshot("device_dispatch_total")
    looked = {k: v - looked.get(k, 0) for k, v in
              METRICS.snapshot("value_column_lookups_total").items()}
    spans = TRACER.trace_spans(root.trace_id)
    names = [sp["name"] for sp in spans]
    launches = [sp["attrs"] for sp in spans if sp["name"] == "valcol.launch"]
    # `seen`: 480 uids of 1..600, a table of 1,024 slots against 512 rows
    assert launches == [{"use": "filter", "form": "table"},
                        {"use": "narrow", "form": "table"}]
    for use in ("filter", "narrow"):
        key = f'device_dispatch_total{{family="column#{use}"}}'
        assert after[key] - before.get(key, 0) == 1
    assert after["device_dispatch_total"] - before[
        "device_dispatch_total"] == 2
    assert looked['value_column_lookups_total{form="table"}'] == 2
    assert not looked.get('value_column_lookups_total{form="search"}')
    for name in ("valcol.pad", "valcol.upload", "valcol.launch",
                 "valcol.wait"):
        assert names.count(name) == 2, name
    assert "valcol.build" not in names and not any(
        n.startswith("setop.") for n in names)
    pads = [sp["attrs"] for sp in spans if sp["name"] == "valcol.pad"]
    assert pads[0]["ids"] == N and pads[0]["padded"] == 1024
    ups = [sp["attrs"] for sp in spans if sp["name"] == "valcol.upload"]
    assert pads[1]["padded"] == dispatch._pow4(pads[1]["ids"]) < 1024
    assert ups == [{"bytes": 4 * p["padded"], "cache_hits": 1,
                    "cache_misses": 1} for p in pads], ups  # the table
    waits = [sp["attrs"] for sp in spans if sp["name"] == "valcol.wait"]
    assert waits[0]["bytes"] == 1024  # the mask
    assert waits[1]["bytes"] == pads[1]["padded"] + 4  # and the count
    process = next(sp for sp in spans if sp["name"] == "process")
    assert process["attrs"]["column_cands"] == N + pads[1]["ids"]
    assert 5 <= process["attrs"]["column_kept"] < 40
    assert process["attrs"]["order_kept"] == process["attrs"]["column_kept"]


def test_the_build_is_a_span_with_its_size(line, monkeypatch):
    s = _fresh()
    with TRACER.span("http.request") as root:
        s.query(Q_YOUNG)
    build = [sp for sp in TRACER.trace_spans(root.trace_id)
             if sp["name"] == "valcol.build"]
    assert len(build) == 1
    assert build[0]["attrs"] == {"attr": "age", "rows": 200, "bytes": 1024,
                                 "form": "table", "span": 200}
    assert METRICS.value("value_column_rows") >= 200
    total, count = METRICS.hist_stats("span_valcol.build_seconds")
    assert count >= 1 and total > 0


@pytest.mark.parametrize("form", ["table", "search"])
def test_a_column_takes_its_form_from_its_uid_span(line, monkeypatch, form):
    """300 persons, every fifth without an `age`: on consecutive uids the
    column goes up as a table (a span of 299 in 512 slots, against 256
    padded rows), seven apart as its sorted uids (a span of 2,087).
    Either form answers a filter and a narrowed order as the value loop
    does, and every column dispatch counts one lookup of its form."""
    step = 1 if form == "table" else 7
    ages = {1 + step * i: (i * 37) % 41 for i in range(300) if i % 5}
    s = Server()
    s.alter("age: int .\nname: string @index(exact) .")
    s.new_txn().mutate_rdf(set_rdf="\n".join(
        f'<0x{u:x}> <name> "u{u}" .' + (
            f'\n<0x{u:x}> <age> "{ages[u]}"^^<xs:int> .' if u in ages else "")
        for u in (1 + step * i for i in range(300))), commit_now=True)
    q = ("{ var(func: has(name)) @filter(lt(age, 20)) { m as uid } "
         "q(func: uid(m), orderdesc: age, orderasc: name, first: 7) "
         "{ uid } }")
    lookups = ('value_column_lookups_total{form="table"}',
               'value_column_lookups_total{form="search"}')
    dispatches = ('device_dispatch_total{family="column#filter"}',
                  'device_dispatch_total{family="column#narrow"}')
    before = {c: METRICS.value(c) for c in lookups + dispatches}
    with TRACER.span("http.request") as root:
        got = _uids(s.query(q))
    build = [sp["attrs"] for sp in TRACER.trace_spans(root.trace_id)
             if sp["name"] == "valcol.build"]
    assert build == [{"attr": "age", "rows": 240, "form": form,
                      "span": max(ages) - min(ages) + 1,
                      "bytes": 512 * 4 if form == "table" else 256 * 8}]
    got_again = _uids(s.query(q))  # the column resident
    moved = {c: METRICS.value(c) - before[c] for c in before}
    assert moved[f'value_column_lookups_total{{form="{form}"}}'] == 4
    assert sum(moved[c] for c in lookups) == sum(
        moved[c] for c in dispatches)
    want = sorted((u for u in ages if ages[u] < 20),
                  key=lambda u: (-ages[u], f"u{u}"))[:7]
    assert got == got_again == want
    _off(monkeypatch)
    old, moved = _moved(lambda: _uids(s.query(q)))
    assert old == want and not any("column" in c for c in moved)


# -- complex read 9 as the benchmark sends it ------------------------------


SMALL = {"persons": 1500, "knows_pairs": 9000, "posts": 5000,
         "comments": 10000, "forums": 300}
SEED = 2**31 + 99


def _config(name):
    with open(os.path.join(ROOT, "chipbench", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def feed(tmp_path_factory):
    """(model, engine) of a small SNB store on the served path's engine
    (`cli._server`, backend=lsm)."""
    from chipbench.data import snb
    from dgraph_tpu import cli
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    tmp = tmp_path_factory.mktemp("snb_feed")
    config = dict(_config("snb-sf1-feed"), sizes=SMALL)
    rdf = str(tmp / "snb.rdf")
    model = snb.make(config, SEED, rdf)
    engine = cli._server(argparse.Namespace(
        p=str(tmp / "p"), storage="backend=lsm", encryption_key_file=None))
    engine.alter(snb.SCHEMA)
    loader = ParallelBulkLoader(engine)
    loader.load_files([rdf])
    engine.kv.sync()
    assert loader.nquads == model.nquads
    try:
        yield model, engine
    finally:
        engine.kv.close()


def _ic9_keys(model, count):
    from chipbench.queries import ic9

    with open(os.path.join(ROOT, "chipbench", "mixes", "ic9.json")) as f:
        params = json.load(f)["kinds"][0]["params"]
    catalog = {"model": model}
    rng = np.random.default_rng([SEED, 9])
    return ic9, params, [ic9.request(catalog, params, rng)
                         for _ in range(count)]


def test_ic9_is_what_the_plain_reference_answers(feed, monkeypatch):
    """Every request through the column (a person's circle writes a few
    thousand messages here, over a line of 512) and through the value
    loop, both against numpy over the plain model."""
    model, engine = feed
    ic9, params, reqs = _ic9_keys(model, 8)
    want = ic9.reference(model, params, [k for k, _ in reqs])
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 512)
    got, moved = _moved(lambda: [
        ic9.parse(engine.query(text)) for _, text in reqs])
    assert got == want
    assert moved['device_dispatch_total{family="column#filter"}'] == 8
    assert moved['device_dispatch_total{family="column#narrow"}'] == 8
    assert moved['order_window_total{path="column"}'] == 8
    assert moved["value_column_builds_total"] == 1
    assert moved["order_kept_total"] < 8 * 64
    assert not any("fallback" in c for c in moved)
    numbers = ic9.check(model, params, [k for k, _ in reqs], got)
    assert sum(numbers["wrong_answers"]) == 0
    assert sum(numbers["compared_ic9"]) == 8
    assert sum(numbers["ic9_cut_by_bound"]) >= 1
    assert sum(numbers["ic9_two_step_rows"]) >= 1
    assert all(len(a) == params["limit"] for a in got)
    _off(monkeypatch)
    assert [ic9.parse(engine.query(text)) for _, text in reqs[:2]] == want[:2]


def test_ic9s_control_and_its_shape(feed):
    model, _ = feed
    ic9, params, reqs = _ic9_keys(model, 30)
    keys_ = [k for k, _ in reqs]
    answers, captured = ic9.control(model, params, keys_)
    wrong = sum(ic9.check(model, params, keys_, answers)["wrong_answers"])
    assert captured is None and 0 < wrong < len(keys_)
    first, last = ic9.days({"model": model})
    at = model.messages().ms
    assert first == int(np.median(at)) // ic9.DAY_MS
    assert last == int(at.max()) // ic9.DAY_MS + 1
    assert all(first <= int(k[1]) // ic9.DAY_MS <= last for k in keys_)
    cands, passed = ic9.shape({"model": model}, params, keys_[0])
    assert cands >= passed and {cands, passed} <= {4 ** i for i in range(12)}


def test_the_feed_configuration_is_snb_sf1s_network():
    a, b = _config("snb-sf1"), _config("snb-sf1-feed")
    for key in ("chips", "sizes", "source_sizes", "reduced", "reduced_why"):
        assert a[key] == b[key], key
    for key in ("degree_lognormal_sigma", "degree_cap", "degree_sequence",
                "structure_seed", "first_names"):
        assert a["assumed"][key] == b["assumed"][key], key
    reads = _config("snb-sf1-reads")["guarantees"]
    assert {k: b["guarantees"][k] for k in reads} == reads
    assert "read timestamp" in b["guarantees"]["columns"]
    assert b["data"] == "snb_feed" and len(b["source"]) <= 200
    assert set(b["checks"]) == {
        "wrong_answers", "answers_compared", "compared_ic9",
        "ic9_cut_by_bound", "ic9_two_step_rows"}


def test_the_maker_refuses_a_program_without_value_columns(monkeypatch,
                                                           tmp_path):
    from chipbench.data import snb, snb_feed

    assert observe.registered_metric(snb_feed.NEEDS)
    assert snb_feed.make is snb.make and snb_feed.catalog is snb.catalog
    calls = []
    monkeypatch.setattr(snb, "install", lambda *a: calls.append(a) or "ok")
    assert snb_feed.install({"name": "c"}, 1, None, "d") == "ok"
    monkeypatch.delitem(observe.METRIC_DEFS, snb_feed.NEEDS)
    with pytest.raises(SystemExit) as e:
        snb_feed.install({"name": "snb-sf1-feed"}, 1, None,
                         str(tmp_path / "store"))
    assert snb_feed.NEEDS in str(e.value.code)
    assert len(calls) == 1 and not (tmp_path / "store").exists()
