"""A resident value column under commits (query/valcol.py): a commit to
the predicate is patched into the column's delta before it is readable,
and a reader at `read_ts` sees exactly the rows committed at or below
it. Every reader is held to a plain model of the predicate's history
and to the value-by-value path on the same store, at timestamps below,
at and above each commit: inserts, changed and deleted values, one uid
changed twice, a filter and a narrowed order whose window's last value
is tied, a commit to another predicate, and merges of the delta into a
new base while readers stay on the old one. The jitted programs run on
the CPU backend with the device line lowered."""

import struct
import time

import numpy as np
import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.query import dispatch, valcol
from dgraph_tpu.types.types import TypeID, Val
from dgraph_tpu.utils.observe import METRICS, TRACER
from dgraph_tpu.x import keys

N = 600
LINE = 64
SCHEMA = "age: int .\nname: string @index(exact) .\nother: int ."
COUNTERS = (
    'device_dispatch_total{family="column#filter"}',
    'device_dispatch_total{family="column#narrow"}',
    "value_column_builds_total", "value_column_invalidations_total",
    "value_column_patched_rows_total", "value_column_merges_total",
    'value_column_fallback_total{why="stale"}',
    'value_column_fallback_total{why="type"}',
)
FILTERS = {"lt": "lt(age, 20)", "ge": "ge(age, 45)",
           "between": "between(age, 10, 14)"}
# (commit's N-Quads, deleted) in order: inserts, changes (uid 7 twice,
# to the window's tied top and away), a delete, another predicate,
# an insert tied with the window's last value
COMMITS = [
    ('<0x2bc> <name> "u700" .\n<0x2bc> <age> "3"^^<xs:int> .\n'
     '<0x2bd> <name> "u701" .\n<0x2bd> <age> "49"^^<xs:int> .', ""),
    ('<0x7> <age> "49"^^<xs:int> .\n<0xe> <age> "0"^^<xs:int> .', ""),
    ("", "<0x2> <age> * ."),
    ('<0x3> <other> "5"^^<xs:int> .', ""),
    ('<0x7> <age> "12"^^<xs:int> .', ""),
    ('<0x2be> <name> "u702" .\n<0x2be> <age> "48"^^<xs:int> .\n'
     '<0x31> <age> "19"^^<xs:int> .', ""),
]


def _ages0():
    return {u: (u * 7) % 50 for u in range(1, N + 1) if u % 9}


def _rdf(ages):
    return "\n".join(
        f'<0x{u:x}> <name> "u{u}" .'
        + (f'\n<0x{u:x}> <age> "{ages[u]}"^^<xs:int> .' if u in ages else "")
        for u in range(1, N + 1))


def _apply(ages, set_rdf, del_rdf):
    ages = dict(ages)
    for line in set_rdf.splitlines():
        s, p, o = line.split(" ", 2)
        if p == "<age>":
            ages[int(s[1:-1], 16)] = int(o.split('"')[1])
    for line in del_rdf.splitlines():
        ages.pop(int(line.split(" ")[0][1:-1], 16), None)
    return ages


def _named(names, set_rdf):
    return names | {int(line.split(" ")[0][1:-1], 16)
                    for line in set_rdf.splitlines() if "<name>" in line}


def _want(kind, ages, names):
    if kind == "lt":
        return sorted(u for u in names if ages.get(u, 99) < 20)
    if kind == "ge":
        return sorted(u for u in names if ages.get(u, -1) >= 45)
    if kind == "between":
        return sorted(u for u in names if 10 <= ages.get(u, -1) <= 14)
    desc = kind == "desc"
    return sorted(names, key=lambda u: (
        u not in ages, (-ages[u] if desc else ages[u]) if u in ages else 0,
        f"u{u}"))[:7]


def _text(kind):
    if kind in FILTERS:
        return f"{{ q(func: has(name)) @filter({FILTERS[kind]}) {{ uid }} }}"
    return (f"{{ q(func: has(name), order{kind}: age, orderasc: name, "
            "first: 7) { uid } }")


def _moved(run):
    before = {c: METRICS.value(c) for c in COUNTERS}
    out = run()
    return out, {c: int(METRICS.value(c) - before[c]) for c in COUNTERS
                 if METRICS.value(c) != before[c]}


def _uids(data):
    return [int(r["uid"], 16) for r in data["data"]["q"]]


def _settled(cols):
    for _ in range(500):
        with cols._mu:
            if not cols._merging:
                return
        time.sleep(0.01)
    raise AssertionError("a merge did not finish")


@pytest.mark.parametrize("bound", [valcol._DELTA_ROWS_MAX, 3])
def test_readers_see_exactly_the_commits_at_or_below_their_timestamp(
        monkeypatch, bound):
    """`bound` 3: the delta is merged into a new base twice on the way,
    and the readers between the two bases read the old one."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    monkeypatch.setattr(valcol, "_DELTA_ROWS_MAX", bound)
    s = Server()
    s.alter(SCHEMA)
    ages = _ages0()
    s.new_txn().mutate_rdf(set_rdf=_rdf(ages), commit_now=True)
    _, moved = _moved(lambda: s.query(_text("lt")))
    assert moved["value_column_builds_total"] == 1
    cols = s.mem.value_columns
    prefix = keys.DataPrefix("age", keys.GALAXY_NS)
    names = set(range(1, N + 1))
    # (commit ts, the model after it: ages, uids with a name)
    states = [(s._snapshot_ts, ages, names)]
    for i, (set_rdf, del_rdf) in enumerate(COMMITS):
        t = s.new_txn()
        t.mutate_rdf(set_rdf=set_rdf, del_rdf=del_rdf)
        _, moved = _moved(t.commit)
        ages = _apply(ages, set_rdf, del_rdf)
        names = _named(names, set_rdf)
        states.append((s._snapshot_ts, ages, names))
        _settled(cols)
        wrote = sum("<age>" in line for line in
                    (set_rdf + "\n" + del_rdf).splitlines())
        assert moved.get("value_column_patched_rows_total", 0) == wrote
        assert "value_column_invalidations_total" not in moved
        base = cols._cols[prefix]
        prev = cols._prev.get(prefix)
        oldest = min(c.built_ts for c in (base, prev) if c is not None)
        for ts in sorted({st[0] for st in states}
                         | {st[0] - 1 for st in states[1:]}):
            seen, named = next(st[1:] for st in reversed(states)
                               if st[0] <= ts)
            for kind in (*FILTERS, "desc", "asc"):
                got, moved = _moved(lambda: _uids(
                    s.query(_text(kind), read_ts=ts)))
                assert got == _want(kind, seen, named), (i, ts, kind)
                assert "value_column_builds_total" not in moved
                family = "filter" if kind in FILTERS else "narrow"
                used = f'device_dispatch_total{{family="column#{family}"}}'
                if ts >= oldest:
                    assert moved == {used: 1}, (i, ts, kind, moved)
                else:  # below every base kept: the value loop (a
                    # `between` asks once, then once a bound)
                    stale = 'value_column_fallback_total{why="stale"}'
                    assert set(moved) == {stale}, (i, ts, kind, moved)
    merges = 0 if bound > 10 else 2
    assert METRICS.value("value_column_delta_rows") == (
        cols._logs[prefix].after(cols._cols[prefix].built_ts))
    with monkeypatch.context() as m:  # the value loop says the same
        m.setattr(dispatch, "_DEVICE_MIN_TOTAL", 1 << 40)
        for ts, seen, named in states:
            for kind in (*FILTERS, "desc"):
                got = _uids(s.query(_text(kind), read_ts=ts))
                assert got == _want(kind, seen, named)
    assert (cols._cols[prefix].built_ts > states[0][0]) == bool(merges)


def test_a_merge_keeps_what_readers_of_the_old_base_hold(monkeypatch):
    """A reader that took the base and its delta before a merge answers
    from them after it, and the merged base answers the same for a
    reader above it; neither scanned the store."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    s = Server()
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(_ages0()), commit_now=True)
    s.query(_text("lt"))
    cols = s.mem.value_columns
    prefix = keys.DataPrefix("age", keys.GALAXY_NS)
    monkeypatch.setattr(valcol, "_DELTA_ROWS_MAX", 1 << 30)
    s.new_txn().mutate_rdf(set_rdf=COMMITS[1][0], commit_now=True)
    ts = s._snapshot_ts
    held = cols.use(prefix, ts)
    assert len(held[3]) == 2
    monkeypatch.setattr(valcol, "_DELTA_ROWS_MAX", 1)
    _, moved = _moved(lambda: s.new_txn().mutate_rdf(
        set_rdf=COMMITS[0][0], commit_now=True))
    _settled(cols)
    assert METRICS.value("value_column_merges_total") >= 1
    assert cols._prev[prefix] is held[0]
    assert cols._cols[prefix].built_ts == s._snapshot_ts
    assert cols._cols[prefix].rows == held[0].rows + 2
    from dgraph_tpu.posting.lists import LocalCache

    ids = np.arange(1, N + 1, dtype=np.uint64)
    bounds = [("lt", Val(TypeID.INT, 20))]
    old = LocalCache(s.kv, ts, mem=s.mem)
    new = LocalCache(s.kv, s._snapshot_ts, mem=s.mem)
    ages = _apply(_ages0(), COMMITS[1][0], "")
    _, moved = _moved(lambda: [
        valcol.filter_mask(c, s.schema, keys.GALAXY_NS, "age", "", ids, bounds)
        for c in (old, new)])
    assert "value_column_builds_total" not in moved
    got = valcol.filter_mask(old, s.schema, keys.GALAXY_NS, "age", "", ids,
                             bounds)
    assert got.tolist() == [ages.get(int(u), 99) < 20 for u in ids]


@pytest.mark.parametrize("how", ["merge", "upload"])
@pytest.mark.parametrize("step", [1, 5])
def test_a_merge_and_an_upload_keep_the_form_and_the_answers(
        monkeypatch, how, step):
    """A base goes up again in the form its shape chooses: a merge of
    the delta into a new base (`_merged`) and an upload from the host
    copy after the DeviceCache lost the arrays (`_upload`) keep a dense
    column's table (534 uids over a span of 600: 1,024 slots) and a
    spread one's sorted uids (a span of 2,996 over 1,024 padded rows),
    and every reader's answers."""
    from dgraph_tpu.query.dispatch import DISPATCHER

    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    form = "table" if step == 1 else "search"
    ages = {step * u: a for u, a in _ages0().items()}
    names = {step * u for u in range(1, N + 1)}
    s = Server()
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf="\n".join(
        f'<0x{u:x}> <name> "u{u}" .'
        + (f'\n<0x{u:x}> <age> "{ages[u]}"^^<xs:int> .' if u in ages else "")
        for u in sorted(names)), commit_now=True)
    assert _uids(s.query(_text("lt"))) == _want("lt", ages, names)
    cols = s.mem.value_columns
    prefix = keys.DataPrefix("age", keys.GALAXY_NS)
    base = cols._cols[prefix]
    assert (base.form, base.size) == (form, 1024 if step == 1 else 0)
    if how == "merge":
        # a new uid past the span and a changed value, then the merge
        monkeypatch.setattr(valcol, "_DELTA_ROWS_MAX", 1)
        rdf = (f'<0x{step * 601:x}> <name> "u{step * 601}" .\n'
               f'<0x{step * 601:x}> <age> "3"^^<xs:int> .\n'
               f'<0x{step * 7:x}> <age> "49"^^<xs:int> .')
        s.new_txn().mutate_rdf(set_rdf=rdf, commit_now=True)
        _settled(cols)
        assert cols._prev[prefix] is base
        ages = {**ages, step * 601: 3, step * 7: 49}
        names = names | {step * 601}
    else:
        DISPATCHER.device_cache.invalidate([prefix])  # as an eviction would
    for kind in (*FILTERS, "desc"):
        got, moved = _moved(lambda: _uids(s.query(_text(kind))))
        assert got == _want(kind, ages, names), kind
        assert "value_column_builds_total" not in moved
    col = cols._cols[prefix]
    assert (col.form, col.size) == (form, base.size)
    assert (col is base) == (how == "upload")
    arrays = DISPATCHER.device_cache.get(col.token)
    assert len(arrays) == (1 if step == 1 else 2)
    assert DISPATCHER.device_cache._entries[col.token].nbytes == col.nbytes


def test_the_delta_span_on_every_use(monkeypatch):
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    from dgraph_tpu.utils import observe

    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 0.0)
    s = Server()
    s.alter(SCHEMA)
    s.new_txn().mutate_rdf(set_rdf=_rdf(_ages0()), commit_now=True)
    s.query(_text("lt"))
    s.new_txn().mutate_rdf(set_rdf=COMMITS[1][0], commit_now=True)
    spans = {}
    for kind in ("lt", "desc"):
        with TRACER.span("http.request") as root:
            s.query(_text(kind))
        spans[kind] = [sp for sp in TRACER.trace_spans(root.trace_id)
                       if sp["name"] == "valcol.delta"]
    # uid 7 (49) and 14 (0): both candidates, both held; 14 passes lt(20)
    assert [sp["attrs"] for sp in spans["lt"]] == [
        {"rows": 2, "shadowed": 2, "kept": 1}]
    assert [sp["attrs"] for sp in spans["desc"]] == [
        {"rows": 2, "shadowed": 2, "kept": 2}]
    assert METRICS.value("value_column_delta_rows") == 2


@pytest.mark.parametrize("error", [struct.error, TypeError, OSError])
def test_a_value_it_cannot_read_back_drops_the_column(monkeypatch, error):
    """A read-back that raises inside the commit barrier drops the
    predicate's column; the commit still becomes readable and the next
    reader is answered from the store."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    s = Server()
    s.alter(SCHEMA)
    ages = _ages0()
    s.new_txn().mutate_rdf(set_rdf=_rdf(ages), commit_now=True)
    s.query(_text("lt"))
    prefix = keys.DataPrefix("age", keys.GALAXY_NS)
    cols = s.mem.value_columns
    assert isinstance(cols.peek(prefix), valcol.Column)
    told = cols.note_commit

    def unreadable(key):
        raise error("unreadable record")

    monkeypatch.setattr(cols, "note_commit", lambda written, ts, value_of:
                        told(written, ts, unreadable))
    before = s._snapshot_ts
    _, moved = _moved(lambda: s.new_txn().mutate_rdf(
        set_rdf=COMMITS[1][0], commit_now=True))
    assert s._snapshot_ts > before
    assert moved == {"value_column_invalidations_total": 1}
    assert cols.peek(prefix) is None
    monkeypatch.undo()
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    got = _uids(s.query(_text("lt")))
    assert got == _want("lt", _apply(ages, COMMITS[1][0], ""),
                        set(range(1, N + 1)))


def test_a_commit_whose_write_failed_is_read_back(monkeypatch):
    """A commit whose deltas never reached the store tells the column
    what the store holds (read back at the commit's timestamp), not what
    the transaction meant to write."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    s = Server()
    s.alter(SCHEMA)
    ages = _ages0()
    s.new_txn().mutate_rdf(set_rdf=_rdf(ages), commit_now=True)
    s.query(_text("lt"))

    def full(*args):
        raise OSError("no space left on device")

    with monkeypatch.context() as m:
        m.setattr(s.kv, "put", full)
        m.setattr(s.kv, "put_batch", full)
        with pytest.raises(OSError):
            s.new_txn().mutate_rdf(set_rdf=COMMITS[1][0], commit_now=True)
    got, moved = _moved(lambda: _uids(s.query(_text("lt"))))
    assert got == _want("lt", ages, set(range(1, N + 1)))
    assert moved == {'device_dispatch_total{family="column#filter"}': 1}


@pytest.mark.parametrize("how", ["lowered", "deleted"])
def test_a_row_the_delta_changed_holds_no_place_in_the_cut(monkeypatch, how):
    """Distinct values: the five largest are lowered below every other,
    or deleted. A cut taken over the base's old values would keep them
    and lose the window's true last ids; the delta's rows are taken off
    the device's hands, so the cut is over what the reader sees."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", LINE)
    s = Server()
    s.alter(SCHEMA)
    ages = {u: 3 * u for u in range(1, N + 1)}
    s.new_txn().mutate_rdf(set_rdf=_rdf(ages), commit_now=True)
    q = ("{ q(func: has(name), orderdesc: age, orderasc: name, first: 5) "
         "{ uid } }")
    assert _uids(s.query(q)) == [600, 599, 598, 597, 596]
    top = range(596, 601)
    if how == "lowered":
        s.new_txn().mutate_rdf(set_rdf="\n".join(
            f'<0x{u:x}> <age> "{u - 600}"^^<xs:int> .' for u in top),
            commit_now=True)
    else:
        s.new_txn().mutate_rdf(del_rdf="\n".join(
            f"<0x{u:x}> <age> * ." for u in top), commit_now=True)
    got, moved = _moved(lambda: _uids(s.query(q)))
    assert got == [595, 594, 593, 592, 591]
    assert moved == {'device_dispatch_total{family="column#narrow"}': 1}
