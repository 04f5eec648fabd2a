"""A set of uids stays a numpy array from where a variable is bound to
where it is used (PR 37): the `uid` function (query/functions.py
FuncRunner._uid) and a level's key list (x/keys.py DataKeys /
ReverseKeys) against their id-by-id definitions, a bound variable that
refuses to be walked, and the counter that says how many ids the `uid`
functions of a request were given.
"""

import numpy as np
import pytest

from dgraph_tpu import dql
from dgraph_tpu.api.server import Server
from dgraph_tpu.dql.parser import FuncSpec
from dgraph_tpu.posting.lists import LocalCache
from dgraph_tpu.query.functions import MAXUID, FuncRunner, _as_uids
from dgraph_tpu.query.subgraph import ExecNode, Executor
from dgraph_tpu.types.types import TypeID, Val
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS
from dgraph_tpu.x import keys


class NoWalk(np.ndarray):
    """A uid array that cannot be walked id by id in Python."""

    def __iter__(self):
        raise AssertionError("a uid array was walked id by id")


def _u64(xs) -> np.ndarray:
    return np.array(xs, dtype=np.uint64)


def _old_uid(fn, uid_vars, val_vars, src):
    """The `uid` function as it was defined before PR 37."""
    uids = list(fn.args)
    for v in fn.uid_var.split(",") if fn.uid_var else []:
        if v in uid_vars:
            uids.extend(int(u) for u in uid_vars[v])
        elif v in val_vars:
            uids.extend(val_vars[v].keys())
    out = np.array(sorted(set(int(x) for x in uids)), dtype=np.uint64)
    if src is not None:
        out = np.intersect1d(out, src, assume_unique=True)
    return out


_ONE = Val(TypeID.INT, 1)
_BIG = (1 << 63) + 5
# name -> (literals, variables named, uid vars, value vars, src)
UID_CASES = {
    "one_sorted_variable": ([], "a", {"a": _u64([3, 5, 9, 4000])}, {}, None),
    "two_overlapping_variables": (
        [], "a,b", {"a": _u64([1, 4, 7, 9]), "b": _u64([4, 5, 9, 11])}, {},
        None),
    "literals_with_a_variable": (
        [7, 2], "a", {"a": _u64([2, 3, 50])}, {}, None),
    "unsorted_and_repeated_literals": ([9, 3, 9, 1, 3], "", {}, {}, None),
    "an_empty_variable": ([], "a", {"a": _u64([])}, {}, None),
    "an_empty_variable_beside_another": (
        [], "a,b", {"a": _u64([]), "b": _u64([8, 9])}, {}, None),
    "a_variable_nobody_bound": ([], "nobody", {}, {}, None),
    "a_value_variable_with_maxuid": (
        [], "v", {}, {"v": {MAXUID: _ONE, 12: _ONE, np.uint64(4): _ONE}},
        None),
    "a_uid_above_2_63": (
        [_BIG + 1], "a", {"a": _u64([1, _BIG, MAXUID - 1])}, {}, None),
    "a_variable_with_src": (
        [], "a", {"a": _u64([2, 4, 6, 8, 10])}, {}, _u64([1, 2, 3, 4, 10])),
    "two_variables_with_src": (
        [5], "a,b", {"a": _u64([2, 4]), "b": _u64([4, 6])}, {},
        _u64([4, 5, 7])),
    "a_variable_in_block_order": (
        [], "a", {"a": _u64([9, 2, 7, 2])}, {}, None),
    "a_variable_bound_as_a_list": ([], "a", {"a": [3, 1, 2]}, {}, None),
}


@pytest.mark.parametrize("case", sorted(UID_CASES))
def test_uid_function_against_its_old_definition(case):
    args, uvars, uid_vars, val_vars, src = UID_CASES[case]
    fn = FuncSpec(name="uid", args=list(args), uid_var=uvars)
    runner = FuncRunner(None, None, uid_vars=uid_vars, val_vars=val_vars)
    got = runner._run(fn, src)
    want = _old_uid(fn, uid_vars, val_vars, src)
    assert got.dtype == np.uint64
    assert got.tolist() == want.tolist()


@pytest.mark.parametrize("src", [None, _u64([6, 9, 11])],
                         ids=["root", "with_src"])
def test_shortest_path_variable_keeps_path_order_only_as_a_root(src):
    path = _u64([9, 2, 11, 6])
    fn = FuncSpec(name="uid", uid_var="p")
    runner = FuncRunner(None, None, uid_vars={"p": path},
                        ordered_uid_vars={"p"})
    got = runner._run(fn, src)
    want = path if src is None else _u64([6, 9, 11])
    assert got.dtype == np.uint64 and got.tolist() == want.tolist()
    # beside a literal the variable is a set like any other
    fn = FuncSpec(name="uid", args=[1], uid_var="p")
    assert runner._run(fn, None).tolist() == [1, 2, 6, 9, 11]


@pytest.mark.parametrize("case", [
    "one_sorted_variable", "two_overlapping_variables",
    "literals_with_a_variable", "a_variable_with_src",
    "a_variable_in_block_order"])
def test_a_bound_variable_is_never_walked(case):
    args, uvars, uid_vars, _, src = UID_CASES[case]
    guarded = {v: a.view(NoWalk) for v, a in uid_vars.items()}
    with pytest.raises(AssertionError):
        list(next(iter(guarded.values())))
    fn = FuncSpec(name="uid", args=list(args), uid_var=uvars)
    got = FuncRunner(None, None, uid_vars=guarded)._run(fn, src)
    assert type(got) is np.ndarray
    assert got.tolist() == _old_uid(fn, uid_vars, {}, src).tolist()


@pytest.mark.parametrize("xs,want", [
    (_u64([5, 1, 5, MAXUID, 0]), [0, 1, 5, MAXUID]),
    (np.array([4, 2, 4], np.int64), [2, 4]),
    (np.array([], np.uint64), []),
    (np.array([], np.float64), []),
    ([np.uint64(7), 3, 7], [3, 7]),
    ((x for x in (2, 2, 1)), [1, 2])],
    ids=["uint64", "int64", "empty", "empty_float", "list", "generator"])
def test_as_uids_of_an_array_and_of_anything_else(xs, want):
    got = _as_uids(xs)
    assert got.dtype == np.uint64 and got.tolist() == want


_EDGE_UIDS = [0, 1, 1 << 32, 1 << 63, (1 << 64) - 1]


@pytest.mark.parametrize("ns", [keys.GALAXY_NS, 7], ids=["galaxy", "ns7"])
@pytest.mark.parametrize("uids", [_EDGE_UIDS, [], [42]],
                         ids=["edges", "empty", "one"])
@pytest.mark.parametrize("many,one", [
    (keys.DataKeys, keys.DataKey), (keys.ReverseKeys, keys.ReverseKey)],
    ids=["data", "reverse"])
def test_level_keys_equal_the_per_uid_builders(many, one, uids, ns):
    got = many("knows", _u64(uids).view(NoWalk), ns)
    assert got == [one("knows", u, ns) for u in uids]
    assert all(type(k) is bytes for k in got)
    assert [keys.parse_key(k).uid for k in got] == uids


def test_level_keys_take_the_default_namespace_and_a_list():
    assert keys.DataKeys("n", [3, 1]) == [
        keys.DataKey("n", 3), keys.DataKey("n", 1)]
    assert keys.ReverseKeys("n", [3]) == [keys.ReverseKey("n", 3)]


# -- through the executor ---------------------------------------------------

SCHEMA = """
knows: [uid] @reverse .
name: string @index(exact) .
"""
N = 40


def _friends(u: int) -> list:
    """Who `u` knows: three others, by arithmetic (1..N)."""
    return sorted({(u * k) % N + 1 for k in (3, 7, 11)} - {u})


@pytest.fixture(scope="module")
def server():
    s = Server()
    s.alter(SCHEMA)
    rdf = []
    for u in range(1, N + 1):
        rdf.append(f'<0x{u:x}> <name> "p{u}" .')
        rdf += [f"<0x{u:x}> <knows> <0x{v:x}> ." for v in _friends(u)]
    s.new_txn().mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
    return s


def _hops(start: int):
    f1 = set(_friends(start))
    f2 = {v for u in f1 for v in _friends(u)} - f1 - {start}
    return sorted(f1), sorted(f2)


def _ic1_shape(start: int) -> str:
    return (
        f"{{ me as var(func: uid(0x{start:x})) {{ f1 as knows }} "
        "var(func: uid(f1)) { f2 as knows "
        "@filter(NOT uid(me) AND NOT uid(f1)) } "
        "q(func: uid(f2)) { uid name } }")


@pytest.mark.parametrize("start", [1, 2, 17])
def test_counter_and_span_rise_by_the_ids_given(server, start, monkeypatch):
    f1, f2 = _hops(start)
    # uid(0x..) 1, uid(f1), NOT uid(me) 1, NOT uid(f1), uid(f2)
    given = 1 + len(f1) + 1 + len(f1) + len(f2)
    # this request's tree takes its fine spans, `process` among them
    monkeypatch.setattr(observe.TRACER, "_detailed", 0.0)
    before = METRICS.value("uid_func_ids_total")
    out = server.query(_ic1_shape(start))
    assert [int(r["uid"], 16) for r in out["data"]["q"]] == f2
    assert METRICS.value("uid_func_ids_total") - before == given
    spans = observe.TRACER.trace_spans(
        int(out["extensions"]["trace_id"], 16))
    (proc,) = [s for s in spans if s["name"] == "process"]
    assert proc["attrs"]["uid_ids"] == given


def test_a_request_without_uid_functions_counts_nothing(server, monkeypatch):
    monkeypatch.setattr(observe.TRACER, "_detailed", 0.0)
    before = METRICS.value("uid_func_ids_total")
    out = server.query('{ q(func: eq(name, "p3")) { knows { uid } } }')
    assert len(out["data"]["q"][0]["knows"]) == len(_friends(3))
    assert METRICS.value("uid_func_ids_total") == before
    spans = observe.TRACER.trace_spans(
        int(out["extensions"]["trace_id"], 16))
    (proc,) = [s for s in spans if s["name"] == "process"]
    assert "uid_ids" not in proc["attrs"]


class _Guarded(dict):
    """uid variables that hand themselves out as arrays nobody can
    walk (a view: the level that bound one keeps its own array)."""

    def __setitem__(self, name, uids):
        super().__setitem__(name, np.asarray(uids, np.uint64).view(NoWalk))


def _executor(server) -> Executor:
    cache = LocalCache(server.kv, server.zero.read_ts(), mem=server.mem)
    return Executor(cache, server.schema, stats=server.stats)


@pytest.mark.parametrize("start", [1, 17])
def test_a_served_variable_is_never_walked(server, start):
    ex = _executor(server)
    ex.uid_vars = _Guarded()
    if ex.planner is not None:
        ex.planner.uid_vars = ex.uid_vars
    nodes = ex.process(dql.parse(_ic1_shape(start)))
    f1, f2 = _hops(start)
    assert all(type(v) is NoWalk for v in ex.uid_vars.values())
    assert ex.uid_vars["f1"].tolist() == f1
    assert nodes[-1].dest_uids.tolist() == f2
    assert ex.uid_ids == 2 + 2 * len(f1) + len(f2)


@pytest.mark.parametrize("attr", ["knows", "~knows"])
def test_a_uid_level_does_not_walk_its_parents(server, attr):
    ex = _executor(server)
    parents = _u64([2, 3, 17, 999]).view(NoWalk)
    (cgq,) = dql.parse(f"{{ q(func: uid(1)) {{ {attr} }} }}")[0].children
    cnode = ex._make_child(ExecNode(gq=cgq, dest_uids=parents), cgq)
    if attr == "knows":
        want = [_friends(int(u)) if u <= N else [] for u in (2, 3, 17, 999)]
    else:
        want = [[v for v in range(1, N + 1) if u in _friends(v)]
                for u in (2, 3, 17, 999)]
    assert [list(map(int, r)) for r in cnode.uid_matrix] == want


def test_a_value_level_builds_its_keys_without_a_walk(server, monkeypatch):
    ex = _executor(server)
    uids = [2, 3, 17]
    (cgq,) = dql.parse("{ q(func: uid(1)) { name } }")[0].children
    seen = []

    class _Read(Exception):
        pass

    def values_many(dkeys):
        seen.extend(dkeys)
        raise _Read

    monkeypatch.setattr(ex.cache, "values_many", values_many)
    parent = ExecNode(gq=cgq, dest_uids=_u64(uids).view(NoWalk))
    with pytest.raises(_Read):
        ex._make_child(parent, cgq)
    assert seen == [keys.DataKey("name", u) for u in uids]
