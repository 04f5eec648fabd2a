"""Multi-key `order .. first: N` through the window walk
(query/subgraph.py Executor._order_uids_window; ref worker/sort.go: the
leading key through its sortable index, the later keys read only for
what the first kept). Every case holds the served answer against the
same query with the walk taken out (the comparator over ALL candidates),
and most against a plain model besides; each pins the
`order_window_total{path}` it expects.
"""

import datetime
import functools
import json

import numpy as np
import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.query.subgraph import Executor
from dgraph_tpu.utils.observe import METRICS

SCHEMA = """
grp: [string] @index(exact) .
lastName: string @index(exact, term) .
pid: int @index(int) .
age: int @index(int) .
born: datetime @index(hour) .
seen: datetime @index(hour) .
score: float @index(float) .
rare: string @index(exact) .
wide: int @index(int) .
rank: int .
nick: string @index(exact) @lang .
tags: [string] @index(exact) .
friend: [uid] .
next: [uid] .
pal: [uid] .
"""

N = 240
PATHS = ("narrowed", "generic", "refilled", "over_budget")
COUNTERS = tuple(f'order_window_total{{path="{p}"}}' for p in PATHS) + (
    "order_candidates_total", "order_kept_total", "order_buckets_total")
_NAMES = ["Ali", "Chen", "Costa", "Dia", "Engel", "Hus", "Khan", "Meyer",
          "Perera", "Roy", "Silva", "Wagner"]


def _people():
    """uid -> attrs. 1..200 have a lastName, 201..240 have none; `wide`
    is different for everyone; 10 have `rare`, in three values."""
    rng = np.random.default_rng(32)
    pids = rng.permutation(N) + 1000
    wide = rng.permutation(N) * 7
    people = {}
    for u in range(1, N + 1):
        p = {
            "pid": int(pids[u - 1]),
            "age": int(rng.integers(20, 41)),
            "born": "2020-03-0%dT%02d:%02d:00Z" % (
                1 + rng.integers(0, 2), 10 + rng.integers(0, 3),
                rng.integers(0, 60)),
            "score": float(rng.integers(0, 10)) + float(
                rng.choice([0.0, 0.25, 0.5, 0.75])),
            "wide": int(wide[u - 1]),
            "rank": int(rng.integers(0, 5)),
            "nick": "n%02d" % rng.integers(0, 30),
            "tags": ["t%d" % rng.integers(0, 6), "t%d" % rng.integers(6, 9)],
        }
        if u <= 200:
            p["lastName"] = _NAMES[int(rng.integers(0, len(_NAMES)))]
        if u % 24 == 0:
            p["rare"] = "r%d" % (u // 24 % 3)
        people[u] = p
    # a run of full ties on (lastName, age), long enough to straddle a
    # window's last place
    for u in range(40, 52):
        people[u].update(lastName="Khan", age=30)
    # `seen`: instants within twelve hours, each written in one of three
    # UTC offsets, so the hour a value is indexed under (its fields as
    # written) and its place in the order (its instant) disagree
    rng = np.random.default_rng(33)
    for u, p in people.items():
        zone = datetime.timezone(datetime.timedelta(
            minutes=int(rng.choice([0, 330, -480]))))
        at = datetime.datetime(
            2021, 6, 1, 6, tzinfo=datetime.timezone.utc
        ) + datetime.timedelta(minutes=int(rng.integers(0, 720)), seconds=u)
        p["seen"] = at.astimezone(zone).isoformat()
    return people


PEOPLE = _people()

# a chain 0x1001 -> .. over `next` whose uids go up and down along the
# way, so a shortest-path var over it hands `uid(P)` unsorted candidates
_CHAIN = [0x1001 + int(i) for i in np.random.default_rng(34).permutation(24)]
CHAIN = {
    u: {"lastName": "Aaa" if i % 3 == 0 else "Aab", "pid": 5000 - 7 * i}
    for i, u in enumerate(_CHAIN)
}
# 0x2001's `pal` edges carry a facet `w` that orders them unlike their uids
HUB = 0x2001
PALS = {u: int(w) for u, w in zip(
    range(3, 183, 3), np.random.default_rng(35).permutation(60))}
ATTRS = {**PEOPLE, **CHAIN}


def _rdf(people) -> str:
    out = []
    for u, p in people.items():
        s = f"<0x{u:x}>"
        out.append(f'{s} <grp> "all" .')
        if u % 3 == 0:
            out.append(f'{s} <grp> "third" .')
        if u % 16 == 0:
            out.append(f'{s} <grp> "few" .')
        for attr, kind in (("pid", "int"), ("age", "int"), ("wide", "int"),
                           ("rank", "int"), ("score", "float"),
                           ("born", "dateTime"), ("seen", "dateTime")):
            out.append(f'{s} <{attr}> "{p[attr]}"^^<xs:{kind}> .')
        for attr in ("lastName", "rare"):
            if attr in p:
                out.append(f'{s} <{attr}> "{p[attr]}" .')
        out.append(f'{s} <nick> "{p["nick"]}" .')
        out.append(f'{s} <nick> "{p["nick"][::-1]}"@en .')
        for t in p["tags"]:
            out.append(f'{s} <tags> "{t}" .')
        for v in range(60):
            out.append(f"{s} <friend> <0x{(u * 7 + v * 11) % N + 1:x}> .")
    for u, p in CHAIN.items():
        out.append(f'<0x{u:x}> <lastName> "{p["lastName"]}" .')
        out.append(f'<0x{u:x}> <pid> "{p["pid"]}"^^<xs:int> .')
    for a, b in zip(_CHAIN, _CHAIN[1:]):
        out.append(f"<0x{a:x}> <next> <0x{b:x}> .")
    for u, w in PALS.items():
        out.append(f"<0x{HUB:x}> <pal> <0x{u:x}> (w={w}) .")
    return "\n".join(out)


@pytest.fixture(scope="module")
def server():
    s = Server()
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(PEOPLE), commit_now=True)
    return s


def _counters() -> dict:
    return {c: METRICS.value(c) for c in COUNTERS}


def _served(run, q):
    """(data, what the order counters rose by) of one query."""
    before = _counters()
    data = run(q)["data"]
    after = _counters()
    return data, {c: int(after[c] - before[c]) for c in COUNTERS}


def _paths(moved: dict) -> dict:
    return {p: moved[c] for p, c in zip(PATHS, COUNTERS) if moved[c]}


def _without_walk(monkeypatch, run, q):
    """The same query with every candidate handed to the comparator."""
    with monkeypatch.context() as m:
        m.setattr(
            Executor, "_order_uids_window",
            lambda self, gq, uids, full: self._order_uids_generic(gq, uids))
        return run(q)["data"]


def _model(uids, order, offset=0, first=None):
    """The plain ordering: a missing value after every present one,
    whatever the direction; full ties by uid in the LAST key's
    direction."""
    def cmp(a, b):
        for attr, desc in order:
            va, vb = ATTRS[a].get(attr), ATTRS[b].get(attr)
            if attr == "seen":
                va, vb = map(datetime.datetime.fromisoformat, (va, vb))
            if va is None and vb is None:
                continue
            if va is None:
                return 1
            if vb is None:
                return -1
            if va != vb:
                lt = -1 if va < vb else 1
                return -lt if desc else lt
        lt = -1 if a < b else 1
        return -lt if order[-1][1] else lt

    out = sorted(uids, key=functools.cmp_to_key(cmp))[offset:]
    return out if first is None else out[:first]


def _group(name):
    return [u for u in PEOPLE
            if name == "all" or (name == "third" and u % 3 == 0)
            or (name == "few" and u % 16 == 0)]


def _query(group, order, first, offset=None, extra=""):
    keys = ", ".join(
        ("orderdesc: " if desc else "orderasc: ") + attr
        for attr, desc in order)
    page = f", first: {first}" + (f", offset: {offset}" if offset else "")
    return (f'{{ q(func: eq(grp, "{group}"), {keys}{page}{extra}) '
            "{ uid } }")


def _uids(data, block="q"):
    return [int(r["uid"], 16) for r in data[block]]


def _dirs(n):
    return [tuple(bool(i >> k & 1) for k in range(n)) for i in range(1 << n)]


# -- the matrix: leading key's index x every combination of directions ------

_TWO = [(lead, d) for lead in ("lastName", "age", "score")
        for d in _dirs(2)]


@pytest.mark.parametrize(
    "lead,dirs", _TWO,
    ids=[f"{lead}-{'d' if a else 'a'}{'d' if b else 'a'}"
         for lead, (a, b) in _TWO])
def test_two_keys_narrowed(server, monkeypatch, lead, dirs):
    """exact, int and float (lossy: ties inside a bucket are decided by
    the real value) leading keys."""
    order = [(lead, dirs[0]), ("pid", dirs[1])]
    q = _query("all", order, 20)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert moved["order_candidates_total"] == N
    assert 20 <= moved["order_kept_total"] < N
    assert 1 <= moved["order_buckets_total"] <= N // 8
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_group("all"), order, 0, 20)


_DATES = [(lead, d) for lead in ("born", "seen") for d in _dirs(2)]


@pytest.mark.parametrize(
    "lead,dirs", _DATES,
    ids=[f"{lead}-{'d' if a else 'a'}{'d' if b else 'a'}"
         for lead, (a, b) in _DATES])
def test_datetime_leading_keys_stay_with_the_comparator(server, monkeypatch,
                                                        lead, dirs):
    """The date tokenizers encode a value's fields as written, its UTC
    offset included, and the order is by instant: `seen` mixes three
    offsets, so 10:30+05:30 (hour bucket 10) sorts before 07:00Z. A walk
    over those buckets would drop ids that belong in the window; `born`
    (all Z) rides along, since the schema cannot tell the two apart."""
    order = [(lead, dirs[0]), ("pid", dirs[1])]
    q = _query("all", order, 20)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"generic": 1}
    assert moved["order_buckets_total"] == 0
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_group("all"), order, 0, 20)


def _spy_on_candidates(monkeypatch):
    """What `_narrow_to_window` was handed, as lists."""
    seen = []
    real = Executor._narrow_to_window

    def narrow(self, o, tk, uids, need):
        seen.append([int(u) for u in uids])
        return real(self, o, tk, uids, need)

    monkeypatch.setattr(Executor, "_narrow_to_window", narrow)
    return seen


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_candidates_in_path_order(server, monkeypatch, desc):
    """`uid(P)` over a shortest-path var keeps the path's order: 24
    candidates, not uid-sorted, against buckets smaller than they are."""
    order = [("lastName", False), ("pid", desc)]
    q = (f"{{ P as shortest(from: 0x{_CHAIN[0]:x}, to: 0x{_CHAIN[-1]:x}, "
         "depth: 30) { next } q(func: uid(P), orderasc: lastName, "
         f"order{'desc' if desc else 'asc'}: pid, first: 5) {{ uid }} }}")
    handed = _spy_on_candidates(monkeypatch)
    data, moved = _served(server.query, q)
    assert handed == [_CHAIN] and _CHAIN != sorted(_CHAIN)
    assert _paths(moved) == {"narrowed": 1}
    assert moved["order_kept_total"] == 8
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_CHAIN, order, 0, 5)


def test_a_child_row_in_facet_order(server, monkeypatch):
    """`@facets(orderdesc: w)` re-orders the row before the order keys
    see it: 60 candidates, not uid-sorted."""
    order = [("lastName", False), ("pid", True)]
    q = (f"{{ q(func: uid(0x{HUB:x})) {{ pal @facets(orderdesc: w) "
         "(orderasc: lastName, orderdesc: pid, first: 5) { uid } } }")
    handed = _spy_on_candidates(monkeypatch)
    data, moved = _served(server.query, q)
    assert len(handed) == 1 and sorted(handed[0]) == sorted(PALS)
    assert handed[0] != sorted(PALS)
    assert _paths(moved) == {"narrowed": 1}
    assert data == _without_walk(monkeypatch, server.query, q)
    assert [int(f["uid"], 16) for f in data["q"][0]["pal"]] == _model(
        list(PALS), order, 0, 5)


@pytest.mark.parametrize(
    "dirs", _dirs(3), ids=["".join("d" if d else "a" for d in ds)
                           for ds in _dirs(3)])
def test_three_keys_narrowed(server, monkeypatch, dirs):
    order = [("lastName", dirs[0]), ("age", dirs[1]), ("pid", dirs[2])]
    q = _query("all", order, 25)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_group("all"), order, 0, 25)


@pytest.mark.parametrize("order,offset,first", [
    ([("lastName", False), ("pid", False)], 15, 10),
    ([("age", True), ("pid", False)], 33, 7),
    ([("score", False), ("age", True), ("pid", True)], 50, 1),
    ([("lastName", False), ("pid", False)], 5, 0),
], ids=["exact-15+10", "int-33+7", "float-50+1", "first-0"])
def test_offset_with_first(server, monkeypatch, order, offset, first):
    q = _query("all", order, first, offset)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert moved["order_kept_total"] >= offset + first
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_group("all"), order, offset, first)


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_full_tie_across_the_windows_last_place(server, monkeypatch, desc):
    """uids 40..51 tie on every key; the window ends inside that run, so
    the uid tie-break (in the last key's direction) decides who is in."""
    order = [("lastName", False), ("age", desc)]
    whole = _model(_group("all"), order)
    run = [i for i, u in enumerate(whole) if 40 <= u <= 51
           and PEOPLE[u]["age"] == 30]
    first = run[0] + 5  # five of the twelve make it
    assert whole[first - 1] in range(40, 52) and whole[first] in range(40, 52)
    q = _query("all", order, first)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == whole[:first]


def test_missing_leading_values_outside_a_filled_window(server, monkeypatch):
    """40 of the 240 have no lastName: they sort last, and a window the
    buckets filled never looks at them."""
    order = [("lastName", True), ("pid", False)]
    q = _query("all", order, 30)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert data == _without_walk(monkeypatch, server.query, q)
    assert all("lastName" in PEOPLE[u] for u in _uids(data))
    assert _uids(data) == _model(_group("all"), order, 0, 30)


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_missing_leading_values_refill_the_window(server, monkeypatch, desc):
    """10 candidates have `rare`, the window wants 20: the buckets run
    out, and the block sorts every candidate as before."""
    order = [("rare", desc), ("pid", False)]
    q = _query("all", order, 20)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"refilled": 1}
    assert moved["order_kept_total"] == N
    assert moved["order_buckets_total"] == 3
    assert data == _without_walk(monkeypatch, server.query, q)
    got = _uids(data)
    assert got == _model(_group("all"), order, 0, 20)
    assert sum("rare" in PEOPLE[u] for u in got) == 10


@pytest.mark.parametrize("first,offset", [(20, None), (10, 5), (15, None)],
                         ids=["under", "equal-with-offset", "equal"])
def test_candidates_that_fit_the_window_bypass(server, monkeypatch, first,
                                               offset):
    """15 candidates (`few`) against a window of 15 or more: IC1's d1."""
    order = [("lastName", False), ("pid", False)]
    assert len(_group("few")) == 15
    q = _query("few", order, first, offset)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"generic": 1}
    assert moved["order_buckets_total"] == 0
    assert moved["order_kept_total"] == moved["order_candidates_total"] == 15
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_group("few"), order, offset or 0, first)


_BYPASS = {
    "lang-leading": '{ q(func: eq(grp, "all"), orderasc: nick, '
                    "orderasc: pid, first: 20) { uid } }",
    "tagged-leading": '{ q(func: eq(grp, "all"), orderasc: nick@en, '
                      "orderasc: pid, first: 20) { uid } }",
    "list-leading": '{ q(func: eq(grp, "all"), orderasc: tags, '
                    "orderasc: pid, first: 20) { uid } }",
    "unindexed-leading": '{ q(func: eq(grp, "all"), orderasc: rank, '
                         "orderasc: pid, first: 20) { uid } }",
    "after": '{ q(func: eq(grp, "all"), orderasc: lastName, '
             "orderasc: pid, first: 20, after: 0x30) { uid } }",
    "negative-first": '{ q(func: eq(grp, "all"), orderasc: lastName, '
                      "orderasc: pid, first: -20) { uid } }",
    "val-leading": '{ var(func: eq(grp, "all")) { a as age } '
                   "q(func: uid(a), orderasc: val(a), orderasc: pid, "
                   "first: 20) { uid } }",
    "cascade": '{ q(func: eq(grp, "all"), orderasc: lastName, '
               "orderasc: pid, first: 20) @cascade { uid rare } }",
    "empty": '{ q(func: eq(grp, "nobody"), orderasc: lastName, '
             "orderasc: pid, first: 20) { uid } }",
}


@pytest.mark.parametrize("case", sorted(_BYPASS))
def test_bypasses_leave_the_answer_alone(server, monkeypatch, case):
    q = _BYPASS[case]
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"generic": 1}
    assert moved["order_buckets_total"] == 0
    assert moved["order_kept_total"] == moved["order_candidates_total"]
    assert data == _without_walk(monkeypatch, server.query, q)
    if case in ("negative-first", "cascade"):
        assert len(data["q"]) in (10, 20)  # `rare`: 10 survive the cascade
    if case == "after":
        assert min(_uids(data)) > 0x30


def test_later_keys_may_be_vals_and_tagged(server, monkeypatch):
    """Only the LEADING key decides: a val(..) or a language-tagged key
    further down is the comparator's business."""
    q = ('{ var(func: eq(grp, "all")) { s as score } '
         'q(func: eq(grp, "all"), orderdesc: lastName, orderasc: val(s), '
         "orderasc: nick@en, orderdesc: pid, first: 20) { uid } }")
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert data == _without_walk(monkeypatch, server.query, q)


@pytest.mark.parametrize("desc", [False, True], ids=["asc", "desc"])
def test_more_distinct_values_than_the_budget(server, monkeypatch, desc):
    """`wide` has 240 distinct values and 80 candidates carry a third of
    them: 20 of them lie ~60 buckets in, the budget is 10. Ascending,
    the walk reads its 10 and gives up; descending, it would have to
    list every key to find the last one, finds more than 10, and reads
    nothing."""
    order = [("wide", desc), ("pid", False)]
    q = _query("third", order, 20)
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"over_budget": 1}
    assert moved["order_buckets_total"] == (0 if desc else 10)
    assert moved["order_kept_total"] == moved["order_candidates_total"] == 80
    assert data == _without_walk(monkeypatch, server.query, q)
    assert _uids(data) == _model(_group("third"), order, 0, 20)


def test_rows_of_a_child_level(server, monkeypatch):
    """Per-row ordering at a child level: 60 friends a row against a
    window of 5, three rows, each with a walk of its own."""
    q = ("{ q(func: uid(0x1, 0x2, 0x3)) { uid friend(orderasc: lastName, "
         "orderdesc: pid, first: 5) { uid } } }")
    data, moved = _served(server.query, q)
    assert _paths(moved) == {"narrowed": 3}
    assert moved["order_candidates_total"] == 180
    assert data == _without_walk(monkeypatch, server.query, q)
    for row in data["q"]:
        u = int(row["uid"], 16)
        friends = sorted({(u * 7 + v * 11) % N + 1 for v in range(60)})
        assert [int(f["uid"], 16) for f in row["friend"]] == _model(
            friends, [("lastName", False), ("pid", True)], 0, 5)


def test_uncommitted_writes_of_the_transaction_are_ordered(server,
                                                           monkeypatch):
    """A transaction that gave one person a last name nobody had (a
    bucket the store does not hold yet), moved another to a committed
    name and took a third's away sees all three where the comparator
    over all candidates puts them; nobody else does."""
    order = [("lastName", False), ("pid", False)]
    q = _query("all", order, 20)
    committed = _uids(server.query(q)["data"])
    late = _model(_group("all"), order)[150]
    t = server.new_txn()
    t.mutate_rdf(
        set_rdf=f'<0x{late:x}> <lastName> "Aaron" .\n'
                f'<0x{230:x}> <lastName> "Ali" .',
        del_rdf=f'<0x{committed[0]:x}> <lastName> * .')
    try:
        data, moved = _served(t.query, q)
        assert _paths(moved) == {"narrowed": 1}
        assert data == _without_walk(monkeypatch, t.query, q)
        got = _uids(data)
        assert got[0] == late and 230 in got and committed[0] not in got
        assert _uids(server.query(q)["data"]) == committed
    finally:
        t.discard()


def test_the_walk_lists_keys_lazily_on_lsm(tmp_path, monkeypatch):
    """On the LSM backend `kv.iterate` is a generator: an ascending walk
    that fills its window from the first buckets lists no further."""
    monkeypatch.setenv("DGRAPH_TPU_STORAGE", "lsm")
    s = Server(data_dir=str(tmp_path / "p"))
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(PEOPLE), commit_now=True)
    listed = []
    real = Executor._index_bucket_stream

    def stream(self, attr, tk):
        for k in real(self, attr, tk):
            listed.append(k)
            yield k

    monkeypatch.setattr(Executor, "_index_bucket_stream", stream)
    order = [("wide", False), ("pid", False)]
    q = _query("all", order, 20)
    data, moved = _served(s.query, q)
    assert _paths(moved) == {"narrowed": 1}
    assert moved["order_buckets_total"] == 20 and len(listed) == 20
    assert _uids(data) == _model(_group("all"), order, 0, 20)
    s.kv.close()


def test_process_span_sums_candidates_and_kept(server):
    """The `process` span carries the request's `order_cands` and
    `order_kept`; no span of the walk's own is opened (the executor's
    CPU metric reads `process`'s SELF time)."""
    from dgraph_tpu.utils import observe

    q = _query("all", [("lastName", False), ("pid", False)], 20)
    before = _counters()
    out = server.query(q)
    moved = {c: int(METRICS.value(c) - before[c]) for c in COUNTERS}
    spans = observe.TRACER.trace_spans(
        int(out["extensions"]["trace_id"], 16))
    names = {s["name"] for s in spans}
    proc = [s for s in spans if s["name"] == "process"]
    if proc:  # a tree that took its fine spans
        assert proc[0]["attrs"]["order_cands"] == N
        assert proc[0]["attrs"]["order_kept"] == moved["order_kept_total"]
    assert not any(n.startswith("order") for n in names)
    assert json.dumps(out["data"])  # served as usual


def test_ic1_as_the_benchmark_sends_it(tmp_path):
    """`chipbench/queries/ic1.request`'s text on a small SNB store:
    the answer is the plain model's, d1 fits its window (generic) and
    d2 and d3 are narrowed."""
    from chipbench.data import snb
    from chipbench.queries import ic1
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    with open("chipbench/configs/snb-sf1.json") as f:
        config = json.load(f)
    config["sizes"].update(persons=2000, knows_pairs=40000, posts=20,
                           comments=40, forums=4)
    rdf = tmp_path / "snb.rdf"
    model = snb.make(config, 32, str(rdf))
    s = Server()
    s.alter(snb.SCHEMA)
    ParallelBulkLoader(s, workers=1).load_text(rdf.read_text())
    params = {"limit": 20, "band": [0.45, 0.55]}
    catalog = {"model": model}
    rng = np.random.default_rng(32)
    first_names = np.array(model.columns()[0])
    sent = 0
    while sent < 3:
        key, text = ic1.request(catalog, params, rng)
        named = [int((first_names[level] == key[1]).sum())
                 for level in model.hops(int(key[0]), 3)]
        if not (named[0] <= 20 and min(named[1:]) >= 64):
            continue  # too few for 16 last names to fill 20 within budget
        sent += 1
        data, moved = _served(s.query, text)
        assert ic1.parse({"data": data}) == ic1.reference(
            model, params, [key])[0]
        assert _paths(moved) == {"narrowed": 2, "generic": 1}
        assert moved["order_candidates_total"] == sum(named)
        assert moved["order_kept_total"] < moved["order_candidates_total"]
        assert moved["order_kept_total"] >= named[0] + 40
