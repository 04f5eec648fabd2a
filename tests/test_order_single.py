"""ONE order key (query/subgraph.py Executor._order_uids,
_order_uids_indexed): the number of candidates decides, before a bucket
key is listed, between the comparator over the stored values ("values")
and the walk of the key's index ("walked", or "over_budget" where
len(ids) // 8 buckets did not do). Every case holds the served answer
against a plain Python sort of the stored values, on both sides of that
threshold, and pins the `order_single_total{path}` it expects and what
`order_buckets_total` rose by.
"""

import datetime
import functools

import numpy as np
import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.query.subgraph import Executor
from dgraph_tpu.utils.observe import METRICS

SCHEMA = """
grp: [string] @index(exact) .
name: string @index(exact) .
pid: int @index(int) .
age: int @index(int) .
score: float @index(float) .
born: datetime @index(hour) .
made: datetime @index(year) .
seen: datetime @index(hour) .
nick: string @index(exact) @lang .
tags: [string] @index(exact) .
rank: int .
"""

N = 240
PATHS = ("values", "walked", "over_budget", "topk")
COUNTERS = tuple(f'order_single_total{{path="{p}"}}' for p in PATHS) + (
    "order_candidates_total", "order_kept_total", "order_buckets_total")
_NAMES = ["Ali", "Chen", "Costa", "Dia", "Engel", "Hus", "Khan", "Meyer"]
# the keys whose index holds equal values in ONE bucket in uid order (an
# exact tokenizer): a walk leaves such ties uid-ascending in both
# directions, and the comparator is told to; the lossy ones (and a key
# with no index) break ties by uid in the key's direction
EXACT = {"name", "pid", "age", "nick", "tags"}
GROUPS = {"all": 1, "some": 16, "few": 40}  # every k-th person


def _people():
    """uid -> attrs. 1..200 have a `name`, 201..240 none; `pid` differs
    for everyone (240 buckets); `age` has 21 values, `born` six hours,
    `made` three years; `seen` mixes three UTC offsets."""
    rng = np.random.default_rng(34)
    pids = rng.permutation(N) + 1000
    people = {}
    for u in range(1, N + 1):
        p = {
            "pid": int(pids[u - 1]),
            "age": int(rng.integers(20, 41)),
            "score": float(rng.integers(0, 10)) + float(
                rng.choice([0.0, 0.25, 0.5, 0.75])),
            "born": "2020-03-0%dT%02d:%02d:00Z" % (
                1 + rng.integers(0, 2), 10 + rng.integers(0, 3),
                rng.integers(0, 60)),
            "made": "20%02d-0%d-11T08:00:00Z" % (
                18 + rng.integers(0, 3), 1 + rng.integers(0, 9)),
            "rank": int(rng.integers(0, 5)),
            "nick": "n%02d" % rng.integers(0, 30),
            "tags": sorted({"t%d" % rng.integers(0, 6),
                            "t%d" % rng.integers(3, 9)}),
        }
        if u <= 200:
            p["name"] = _NAMES[int(rng.integers(0, len(_NAMES)))]
        zone = datetime.timezone(datetime.timedelta(
            minutes=int(rng.choice([0, 330, -480]))))
        at = datetime.datetime(
            2021, 6, 1, 6, tzinfo=datetime.timezone.utc
        ) + datetime.timedelta(minutes=int(rng.integers(0, 720)), seconds=u)
        p["seen"] = at.astimezone(zone).isoformat()
        people[u] = p
    return people


PEOPLE = _people()


def _rdf() -> str:
    out = []
    for u, p in PEOPLE.items():
        s = f"<0x{u:x}>"
        for g, k in GROUPS.items():
            if u % k == 0:
                out.append(f'{s} <grp> "{g}" .')
        for attr, kind in (("pid", "int"), ("age", "int"), ("rank", "int"),
                           ("score", "float"), ("born", "dateTime"),
                           ("made", "dateTime"), ("seen", "dateTime")):
            out.append(f'{s} <{attr}> "{p[attr]}"^^<xs:{kind}> .')
        if "name" in p:
            out.append(f'{s} <name> "{p["name"]}" .')
        out.append(f'{s} <nick> "{p["nick"]}" .')
        out.append(f'{s} <nick> "{p["nick"][::-1]}"@en .')
        for t in p["tags"]:
            out.append(f'{s} <tags> "{t}" .')
    return "\n".join(out)


@pytest.fixture(scope="module")
def server():
    s = Server()
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(), commit_now=True)
    return s


def _served(run, q):
    """(uids of block q, what the order counters rose by)."""
    before = {c: METRICS.value(c) for c in COUNTERS}
    data = run(q)["data"]
    moved = {c: int(METRICS.value(c) - before[c]) for c in COUNTERS}
    return [int(r["uid"], 16) for r in data["q"]], moved


def _paths(moved: dict) -> dict:
    return {p: moved[c] for p, c in zip(PATHS, COUNTERS) if moved[c]}


def _group(name):
    return [u for u in PEOPLE if u % GROUPS[name] == 0]


def _instant(text):
    return datetime.datetime.fromisoformat(text.replace("Z", "+00:00"))


def _sort_value(attr, desc, p):
    """The stored value the order sees: a datetime by its instant; of a
    list, and of an @lang predicate's several values, the one the index
    walk meets first (the least ascending, the greatest descending)."""
    if attr == "tags":
        return (max if desc else min)(p["tags"])
    if attr == "nick":
        return (max if desc else min)(p["nick"], p["nick"][::-1])
    v = p.get(attr)
    return _instant(v) if attr in ("born", "made", "seen") and v else v


def _model(uids, attr, desc, offset=0, first=None, after=None,
           ties_desc=None):
    """A plain sort: a missing value after every present one, whatever
    the direction, by uid along the direction; equal values by uid
    ascending over an exact index, else along the direction."""
    if ties_desc is None:
        ties_desc = desc and attr not in EXACT

    def cmp(a, b):
        va, vb = (_sort_value(attr, desc, PEOPLE[u]) for u in (a, b))
        if va is None or vb is None:
            if va is None and vb is None:
                return (b - a) if desc else (a - b)
            return 1 if va is None else -1
        if va != vb:
            lt = -1 if va < vb else 1
            return -lt if desc else lt
        return (b - a) if ties_desc else (a - b)

    out = sorted(uids, key=functools.cmp_to_key(cmp))
    if after is not None:
        out = [u for u in out if u > after]
    out = out[max(offset, 0):]
    if first is None:
        return out
    return out[:first] if first >= 0 else out[first:]


def _query(group, attr, desc, page="", tail=""):
    key = ("orderdesc: " if desc else "orderasc: ") + attr
    return (f'{{ q(func: eq(grp, "{group}"), {key}{page}){tail} '
            "{ uid } }")


_DIRS = [False, True]
_PAGES = {
    "all": ({}, ""),
    "first": ({"first": 7}, ", first: 7"),
    "offset": ({"first": 5, "offset": 4}, ", first: 5, offset: 4"),
    "negative-offset": ({"first": 5, "offset": -3}, ", first: 5, offset: -3"),
    "negative-first": ({"first": -4}, ", first: -4"),
    "after": ({"first": 6, "after": 0x50}, ", first: 6, after: 0x50"),
}


def _ids(cases):
    return ["-".join(str(x) if not isinstance(x, bool)
                     else ("desc" if x else "asc") for x in c) for c in cases]


# -- few candidates over many buckets: the comparator, nothing listed --------

_FEW = [(attr, desc, page) for attr in ("name", "pid", "score", "born",
                                        "made", "seen", "rank")
        for desc in _DIRS for page in ("all", "first")]
_FEW += [("pid", desc, page) for desc in _DIRS
         for page in ("offset", "negative-offset", "negative-first", "after")]


@pytest.mark.parametrize("attr,desc,page", _FEW, ids=_ids(_FEW))
def test_few_candidates_are_ordered_by_value(server, attr, desc, page):
    """Six candidates cannot pay for one bucket (6 // 8 == 0): exact,
    int, float (lossy), hour, year, a mixed-offset datetime (by instant:
    ROADMAP D18 (b)) and a key with no index at all."""
    kw, text = _PAGES[page]
    got, moved = _served(server.query, _query("few", attr, desc, text))
    assert got == _model(_group("few"), attr, desc, **kw)
    assert _paths(moved) == {"values": 1}
    assert moved["order_buckets_total"] == 0
    assert moved["order_candidates_total"] == 6
    assert moved["order_kept_total"] == 6


# -- many candidates over few buckets: the walk ---------------------------------

_MANY = [(attr, desc, page) for attr in ("name", "age", "score", "born",
                                         "made")
         for desc in _DIRS for page in ("all", "first")]
_MANY += [("age", desc, page) for desc in _DIRS
          for page in ("offset", "negative-offset", "negative-first",
                       "after")]


@pytest.mark.parametrize("attr,desc,page", _MANY, ids=_ids(_MANY))
def test_many_candidates_over_few_buckets_are_walked(server, attr, desc, page):
    """240 candidates over 8 names, 21 ages, 10 ints of a float, 6
    hours, 3 years: inside the budget of 30 buckets. `name` leaves 40
    ids without a value, which follow every valued one."""
    kw, text = _PAGES[page]
    got, moved = _served(server.query, _query("all", attr, desc, text))
    assert got == _model(_group("all"), attr, desc, **kw)
    assert _paths(moved) == {"walked": 1}
    assert 1 <= moved["order_buckets_total"] <= N // 8
    # the comparator saw only what shared a lossy bucket
    lossy = attr not in EXACT
    assert (moved["order_kept_total"] > 0) == lossy
    assert moved["order_kept_total"] <= N


@pytest.mark.parametrize("desc", _DIRS, ids=["asc", "desc"])
def test_a_window_filled_early_stops_the_walk(server, desc):
    got, moved = _served(server.query, _query("all", "age", desc,
                                              ", first: 3"))
    assert got == _model(_group("all"), "age", desc, first=3)
    assert _paths(moved) == {"walked": 1}
    # ascending: one bucket read; descending: 21 keys listed, one read
    assert moved["order_buckets_total"] == (21 if desc else 1)


@pytest.mark.parametrize("group", ["few", "all"])
def test_an_empty_window_reads_nothing(server, group):
    got, moved = _served(server.query, _query(group, "age", False,
                                              ", first: 0"))
    assert got == [] and moved["order_buckets_total"] == 0


# -- many candidates over more buckets than the budget ---------------------------

_OVER = [("some", "pid", desc, page) for desc in _DIRS
         for page in ("all", "first")]
_OVER += [("all", "pid", desc, "all") for desc in _DIRS]
_OVER += [("some", "name", desc, page) for desc in _DIRS
          for page in ("all", "negative-first")]


@pytest.mark.parametrize("group,attr,desc,page", _OVER, ids=_ids(_OVER))
def test_a_walk_over_budget_falls_back_to_the_comparator(server, group, attr,
                                                         desc, page):
    """`pid` has 240 buckets: 15 candidates may read 1, 240 may read 30;
    `name` has 8, and three of the 15 have none: they keep the tail's
    order, by uid along the direction, where equal names go by uid
    ascending. Descending, the listing alone shows the budget to be too
    small; ascending, the buckets run out of it before the candidates
    are placed."""
    kw, text = _PAGES[page]
    uids = _group(group)
    budget = len(uids) // 8
    got, moved = _served(server.query, _query(group, attr, desc, text))
    assert got == _model(uids, attr, desc, **kw)
    assert _paths(moved) == {"over_budget": 1}
    assert moved["order_buckets_total"] == budget
    assert moved["order_kept_total"] == len(uids)


def test_an_ascending_window_inside_the_budget_is_walked(server):
    got, moved = _served(server.query, _query("all", "pid", False,
                                              ", first: 5"))
    assert got == _model(_group("all"), "pid", False, first=5)
    assert _paths(moved) == {"walked": 1}
    assert 5 <= moved["order_buckets_total"] <= N // 8


# -- @lang and list predicates: always the walk ------------------------------------

_MULTI = [(attr, group, desc) for attr in ("tags", "nick")
          for group in ("few", "all") for desc in _DIRS]


@pytest.mark.parametrize("attr,group,desc", _MULTI, ids=_ids(_MULTI))
def test_lang_and_list_predicates_are_walked_whatever_the_count(
        server, attr, group, desc):
    """An id of a list or @lang predicate sits in several buckets and
    the walk places it by the first it meets; the comparator reads one
    value and cannot stand in, so these are walked with no budget, six
    candidates or 240."""
    got, moved = _served(server.query, _query(group, attr, desc))
    assert got == _model(_group(group), attr, desc)
    assert _paths(moved) == {"walked": 1}
    assert moved["order_buckets_total"] >= 1
    assert moved["order_kept_total"] == 0


@pytest.mark.parametrize("desc", _DIRS, ids=["asc", "desc"])
def test_a_language_tagged_key_is_collated_by_the_comparator(server, desc):
    key = ("orderdesc" if desc else "orderasc") + ": nick@en"
    got, moved = _served(
        server.query, f'{{ q(func: eq(grp, "all"), {key}) {{ uid }} }}')
    want = sorted(
        PEOPLE, key=lambda u: PEOPLE[u]["nick"][::-1], reverse=desc)
    assert [PEOPLE[u]["nick"] for u in got] == [
        PEOPLE[u]["nick"] for u in want]
    assert _paths(moved) == {"values": 1}
    assert moved["order_buckets_total"] == 0


# -- mixed UTC offsets: ROADMAP D18 (b) --------------------------------------------


def _hour_as_written(u):
    return PEOPLE[u]["seen"][:13]


@pytest.mark.parametrize("desc", _DIRS, ids=["asc", "desc"])
def test_mixed_offsets_are_ordered_by_instant_on_the_value_path(server, desc):
    """The hour tokenizer files 10:30+05:30 under hour 10, after 07:00Z.
    Few candidates go by their values, so by instant: the one intended
    difference from the walk, which orders by bucket and still does for
    a candidate set large enough to be walked (pinned here as what it
    is, not as what it should be)."""
    few = _group("some")[:6]
    root = "uid(" + ", ".join(hex(u) for u in few) + ")"
    key = "orderdesc: seen" if desc else "orderasc: seen"
    got, moved = _served(server.query,
                         f"{{ q(func: {root}, {key}) {{ uid }} }}")
    assert got == sorted(few, key=lambda u: _instant(PEOPLE[u]["seen"]),
                         reverse=desc)
    assert _paths(moved) == {"values": 1}

    got, moved = _served(server.query, _query("all", "seen", desc))
    assert _paths(moved) == {"walked": 1}
    by_instant = _model(_group("all"), "seen", desc)
    by_bucket = sorted(by_instant, key=_hour_as_written, reverse=desc)
    assert got == by_bucket and got != by_instant


# -- what stays in front of and behind the rule -------------------------------------


@pytest.mark.parametrize("desc", _DIRS, ids=["asc", "desc"])
def test_cascade_orders_every_candidate_by_value(server, desc):
    """`full=True`: pruning follows the order, so nothing may stop
    early; the comparator orders all 240 and breaks ties its own way."""
    q = _query("all", "age", desc, ", first: 5", " @cascade")
    q = q.replace("{ uid }", "{ uid name }")
    got, moved = _served(server.query, q)
    named = [u for u in _model(_group("all"), "age", desc, ties_desc=desc)
             if "name" in PEOPLE[u]]
    assert got == named[:5]
    assert _paths(moved) == {"values": 1}
    assert moved["order_kept_total"] == N
    assert moved["order_buckets_total"] == 0


def test_a_value_var_takes_the_comparator_or_the_device(server):
    """`val(..)` keys never reach the index: under 4,096 candidates the
    comparator; `_order_uids_topk` keeps its place in front."""
    q = ('{ var(func: eq(grp, "all")) { r as rank } '
         "q(func: uid(r), orderdesc: val(r), first: 4) { uid } }")
    got, moved = _served(server.query, q)
    assert [PEOPLE[u]["rank"] for u in got] == [4, 4, 4, 4]
    assert _paths(moved) == {"values": 1}
    calls = []
    real = Executor._order_uids_topk

    def topk(self, gq, o, uids):
        calls.append(len(uids))
        return np.sort(uids)[::-1]

    with pytest.MonkeyPatch.context() as m:
        m.setattr(Executor, "_order_uids_topk", topk)
        got, moved = _served(server.query, _query("all", "age", False))
    assert calls == [N] and got == sorted(_group("all"), reverse=True)
    assert _paths(moved) == {"topk": 1}
    assert moved["order_kept_total"] == moved["order_buckets_total"] == 0
    assert real is Executor._order_uids_topk


def test_rows_of_a_child_level_count_one_each(server):
    """A child level ordered by one key tallies every non-empty row."""
    s = Server()
    s.alter(SCHEMA + "friend: [uid] .\n")
    rows = {1: [3, 5, 7, 9], 2: list(range(10, 70))}
    s.new_txn().mutate_rdf(set_rdf=_rdf() + "\n" + "\n".join(
        f"<0x{a:x}> <friend> <0x{b:x}> ." for a, bs in rows.items()
        for b in bs), commit_now=True)
    before = {c: METRICS.value(c) for c in COUNTERS}
    data = s.query("{ q(func: uid(0x1, 0x2)) { uid friend(orderdesc: born, "
                   "first: 3) { uid } } }")["data"]["q"]
    moved = {c: int(METRICS.value(c) - before[c]) for c in COUNTERS}
    for row in data:
        a = int(row["uid"], 16)
        assert [int(f["uid"], 16) for f in row["friend"]] == _model(
            rows[a], "born", True, first=3)
    # 4 candidates: by value; 60 candidates over six hours: walked
    assert _paths(moved) == {"values": 1, "walked": 1}
    assert moved["order_candidates_total"] == 64


def test_the_walk_lists_keys_lazily_on_lsm(tmp_path, monkeypatch):
    """On the LSM backend `kv.iterate` is a generator: an ascending walk
    stops listing where it stops reading, a descending one lists at most
    its budget and one key more, and few candidates list nothing."""
    monkeypatch.setenv("DGRAPH_TPU_STORAGE", "lsm")
    s = Server(data_dir=str(tmp_path / "p"))
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(), commit_now=True)
    listed = []
    real = Executor._index_bucket_stream

    def stream(self, attr, tk):
        for k in real(self, attr, tk):
            listed.append(k)
            yield k

    monkeypatch.setattr(Executor, "_index_bucket_stream", stream)
    for group, desc, page, keys_listed in (
            ("all", False, ", first: 5", 5), ("all", True, "", N // 8 + 1),
            ("few", True, ", first: 3", 0), ("few", False, "", 0)):
        del listed[:]
        got, _ = _served(s.query, _query(group, "pid", desc, page))
        first = 5 if "5" in page else 3 if "3" in page else None
        assert got == _model(_group(group), "pid", desc, first=first)
        assert len(listed) == keys_listed, (group, desc, page)
    s.kv.close()


def test_uncommitted_writes_of_the_transaction_are_walked(server):
    """A bucket only this transaction's writes have made, and one they
    emptied, are where the comparator would put them."""
    t = server.new_txn()
    t.mutate_rdf(set_rdf='<0x7> <age> "19"^^<xs:int> .',
                 del_rdf="<0x9> <age> * .")
    try:
        got, moved = _served(t.query, _query("all", "age", False))
        assert _paths(moved) == {"walked": 1}
        assert got[0] == 7 and got[-1] == 9
        rest = [u for u in _model(_group("all"), "age", False)
                if u not in (7, 9)]
        assert got[1:-1] == rest
    finally:
        t.discard()


def test_process_span_carries_the_requests_order_buckets(server):
    from dgraph_tpu.utils import observe

    before = METRICS.value("order_buckets_total")
    out = server.query(_query("all", "age", True))
    moved = int(METRICS.value("order_buckets_total") - before)
    spans = observe.TRACER.trace_spans(int(out["extensions"]["trace_id"], 16))
    proc = [s for s in spans if s["name"] == "process"]
    if proc:  # a tree that took its fine spans
        assert proc[0]["attrs"]["order_buckets"] == moved == 21
        assert proc[0]["attrs"]["order_cands"] == N
        assert proc[0]["attrs"]["order_kept"] == 0
    assert not any(s["name"].startswith("order") for s in spans)
