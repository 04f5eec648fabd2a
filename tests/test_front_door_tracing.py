"""The front door and the closed loop, traced (api/http_server.py
`_Listener` / `_Front`, utils/observe.py `Stretch`, dgraph_tpu/client.py's
stamp): every `/query` record carries the accept (on a kept
connection's later request, the read of its request line), the hand-off
to the handler thread, the request head, the tail and the client's own
time as attrs of `http.request`, the pieces tile a client's loop from
one stamp to the next on a new connection and on a kept one, and nothing
the span readers read before moves."""

import glob
import importlib
import json
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from dgraph_tpu.api import http_server
from dgraph_tpu.api.http_server import HTTPServer
from dgraph_tpu.api.server import Server
from dgraph_tpu.client import STAMP_HEADER, DgraphClient
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS, TRACER

CLIENTS, PER_CLIENT = 4, 5
QUERY = '{ q(func: eq(name, "p1")) { uid name } }'
FRONT = ("connect_ms", "handoff_ms", "head_ms", "tail_ms")
AT_ACCEPT = ("backlog", "accept_gap_ms")  # a connection's first request
CLIENT_ATTRS = ("http.request.connect_ms", "http.request.client_gap_ms")
# the readers of the request records that were there before the front
# door, and the five that read it
OLD_READERS = ("wire_cpu_ms_per_req", "parse_plan_cpu_ms_per_req",
               "executor_cpu_ms_per_req", "level_read_cpu_ms_per_req",
               "boundary_cpu_ms_per_req", "encode_cpu_ms_per_req",
               "device_wait_ms_per_req", "host_stall_ms_per_req",
               "dispatches_per_req", "order_buckets_per_req",
               "order_sorted_ids_per_req", "valcol_ids_per_req",
               "valcol_kept_per_req")
NEW_READERS = ("front_connect_ms_per_req", "front_handoff_ms_per_req",
               "front_cpu_ms_per_req", "client_gap_ms_per_req")


@pytest.fixture(scope="module")
def served():
    s = Server()
    s.alter("name: string @index(exact) .")
    s.new_txn().mutate_rdf(
        set_rdf="\n".join(f'<0x{i:x}> <name> "p{i % 5}" .'
                          for i in range(1, 60)),
        commit_now=True)
    srv = HTTPServer(s, host="127.0.0.1", port=0).start()
    url = f"http://127.0.0.1:{srv.port}"
    _post(url, QUERY)  # the listener's first accept has no gap before it
    try:
        yield url, srv
    finally:
        srv.stop()


def _post(url, text, stamp=None) -> dict:
    """A raw request, stamped as the caller says (no stamp: None)."""
    headers = {"Content-Type": "application/dql"}
    if stamp is not None:
        headers[STAMP_HEADER] = stamp
    req = urllib.request.Request(url + "/query", data=text.encode(),
                                 headers=headers)
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.status == 200
        return json.loads(r.read())


def _drive(url, fresh=False):
    """CLIENTS closed-loop clients, PER_CLIENT requests each, each client
    on one kept connection, or on a new one a request (`fresh`). Returns
    per client [(stamp it sent, trace id of the answer, seconds from
    the stamp to the answer read)]."""
    out = [None] * CLIENTS

    def loop(i):
        c = DgraphClient(url)
        sent = []
        stamp = c._stamp

        def keep():  # the stamp as it leaves
            sent.append(stamp())
            return sent[-1]

        c._stamp = keep
        got = []
        for _ in range(PER_CLIENT):
            tid = c.query(QUERY)["extensions"]["trace_id"]
            done = time.time()
            at = int(sent[-1].split()[2]) / 1e9
            got.append((sent[-1], tid, done - at))
            if fresh:
                c.close()
        out[i] = got

    threads = [threading.Thread(target=loop, args=(i,))
               for i in range(CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    return out


def _records(tids, profiled=False, key="http.request.tail_ms"):
    """{trace id: record} once each carries `key` (set after the socket
    closes, after the client has its answer)."""
    deadline = time.monotonic() + 20
    while True:
        recs = {r["trace_id"]: r
                for r in TRACER.request_records(1024, profiled=profiled)
                if r["trace_id"] in tids}
        if len(recs) == len(tids) and all(
                key in r["attrs"] for r in recs.values()):
            return recs
        assert time.monotonic() < deadline, (len(recs), len(tids))
        time.sleep(0.01)


@pytest.fixture(scope="module", params=["kept", "fresh"])
def loop(served, request):
    url, _ = served
    c0 = METRICS.value("http_connections_total")
    per = _drive(url, fresh=request.param == "fresh")
    conns = METRICS.value("http_connections_total") - c0
    tids = {tid for got in per for _, tid, _ in got}
    return per, _records(tids), conns, request.param


@pytest.fixture(scope="module")
def profiled_records(served, tmp_path_factory):
    """The records of a driven loop whose roots began under a profiler
    session, and the profile."""
    import jax

    url, _ = served
    tmp = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t0 = time.time()
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    try:
        per = _drive(url)
        tids = {tid for got in per for _, tid, _ in got}
        recs = _records(tids, profiled=True,
                        key="http.request.tail_cpu_ms")
    finally:
        jax.profiler.stop_trace()
    since = [r for r in TRACER.request_records(1024, profiled=True)
             if r["start"] >= t0 and r["name"] == "http.request"]
    return per, recs, since, tmp


@pytest.mark.parametrize("attr", FRONT)
def test_every_query_record_carries_the_front_door(loop, attr):
    _, recs, _, _ = loop
    assert len(recs) == CLIENTS * PER_CLIENT
    for rec in recs.values():
        assert rec["root_attrs"]["path"] == "/query"
        value = rec["attrs"][f"http.request.{attr}"]
        assert value >= 0
        if attr.endswith("_ms"):
            assert value < 10_000
    # the CPU clock is read under a profiler session only
    assert not any("http.request.head_cpu_ms" in r["attrs"]
                   or "http.request.tail_cpu_ms" in r["attrs"]
                   for r in recs.values())


@pytest.mark.parametrize("attr", AT_ACCEPT)
def test_the_accept_only_on_a_connections_first_request(served, loop,
                                                         attr):
    """`backlog` and `accept_gap_ms` are the listener's, read at an
    accept; a kept connection's later request was taken up by its own
    thread's read, so its hand-off is 0."""
    per, recs, _, mode = loop
    if attr == "backlog" and not served[1].httpd._tcp_info:
        # a platform that fills no TCP_INFO (the chip's host): absent
        assert not any("http.request.backlog" in r["attrs"]
                       for r in recs.values())
        return
    for got in per:
        attrs = [recs[tid]["attrs"] for _, tid, _ in got]
        has = [f"http.request.{attr}" in a for a in attrs]
        assert has == [True] + [mode == "fresh"] * (PER_CLIENT - 1)
        assert attrs[0][f"http.request.{attr}"] >= 0
        if mode == "kept":
            assert all(a["http.request.handoff_ms"] == 0.0
                       for a in attrs[1:])


def test_client_gap_only_after_a_clients_first_request(loop):
    per, recs, _, _ = loop
    for got in per:
        has = ["http.request.client_gap_ms" in recs[tid]["attrs"]
               for _, tid, _ in got]
        assert has == [False] + [True] * (PER_CLIENT - 1)


def test_the_pieces_tile_each_clients_loop(loop):
    """connect + handoff + head + http.request's wall + the next
    request's client gap is the time from one stamp to the next; the
    root opens before the client has its answer (and may close after:
    the reply is written inside it)."""
    per, recs, _, _ = loop
    for got in per:
        for (s0, t0, took), (s1, t1, _) in zip(got, got[1:]):
            a, b = recs[t0], recs[t1]["attrs"]
            door = sum(a["attrs"][f"http.request.{k}"] for k in (
                "connect_ms", "handoff_ms", "head_ms"))
            between = (int(s1.split()[2]) - int(s0.split()[2])) / 1e6
            assert door + a["wall_ms"] + b["http.request.client_gap_ms"] \
                == pytest.approx(between, abs=1.0)
            assert door <= took * 1e3
            assert s1.split()[:2] == [s0.split()[0],
                                      str(int(s0.split()[1]) + 1)]


def test_a_connection_a_client_or_a_request(loop):
    """`http_connections_total` counts accepts: one a client while it
    keeps its connection, one a request where each opens its own."""
    _, recs, conns, mode = loop
    assert len(recs) == CLIENTS * PER_CLIENT
    assert conns == (CLIENTS if mode == "kept" else CLIENTS * PER_CLIENT)


def test_the_idle_time_between_kept_requests_is_in_no_piece(served):
    """On a kept connection the head and the connect start where the
    read of the request line returns, and the tail ends where the thread
    turns to its next read: a client's pause falls into its own gap,
    and the pieces still tile the loop."""
    url, _ = served
    c, sent = DgraphClient(url), []
    stamp = c._stamp
    c._stamp = lambda: sent.append(stamp()) or sent[-1]
    c0 = METRICS.value("http_connections_total")
    first = c.query(QUERY)["extensions"]["trace_id"]
    time.sleep(0.3)
    second = c.query(QUERY)["extensions"]["trace_id"]
    assert METRICS.value("http_connections_total") - c0 == 1
    recs = _records({first, second})
    a, b = recs[first]["attrs"], recs[second]["attrs"]
    assert a["http.request.tail_ms"] < 100
    assert b["http.request.connect_ms"] < 100
    assert b["http.request.head_ms"] < 100
    assert b["http.request.handoff_ms"] == 0.0
    assert b["http.request.client_gap_ms"] >= 300
    door = sum(a[f"http.request.{k}"] for k in (
        "connect_ms", "handoff_ms", "head_ms"))
    between = (int(sent[1].split()[2]) - int(sent[0].split()[2])) / 1e6
    assert door + recs[first]["wall_ms"] + b["http.request.client_gap_ms"] \
        == pytest.approx(between, abs=1.0)


@pytest.mark.parametrize("stamp,dropped", [
    (None, 0),
    ("garbage", 1),
    ("c 1 not-a-number", 1),
    ("c 1 2 3", 1),
    (lambda: f"c 1 {time.time_ns() + 10 * 10**9}", 1),  # after the accept
    (lambda: f"c 1 {time.time_ns() - 120 * 10**9}", 1),  # over a minute
], ids=["none", "garbage", "not_a_number", "four_fields", "future", "stale"])
def test_a_request_without_a_sound_stamp_is_served(served, stamp, dropped):
    url, _ = served
    before = METRICS.value("http_client_stamp_dropped_total")
    out = _post(url, QUERY, stamp() if callable(stamp) else stamp)
    assert len(out["data"]["q"]) == 12
    rec = _records({out["extensions"]["trace_id"]})[
        out["extensions"]["trace_id"]]
    assert not set(CLIENT_ATTRS) & set(rec["attrs"])
    assert "http.request.handoff_ms" in rec["attrs"]
    assert METRICS.value("http_client_stamp_dropped_total") - before \
        == dropped


def test_profiled_records_one_per_request_with_head_and_tail_cpu(
        profiled_records):
    """The front door adds no local root: request_records(profiled=True)
    holds one `http.request` record per request sent, and the head's and
    tail's CPU ride in attrs, not in the self CPU."""
    per, recs, since, _ = profiled_records
    assert len(since) == CLIENTS * PER_CLIENT == len(recs)
    for rec in recs.values():
        assert rec["profiled"] is True
        assert rec["attrs"]["http.request.head_cpu_ms"] >= 0
        assert rec["attrs"]["http.request.tail_cpu_ms"] >= 0
        assert not {"http.head", "http.tail"} & set(rec["self_cpu_ms"])
        assert not {"http.head", "http.tail"} & set(rec["self_wall_ms"])
        assert sum(rec["self_wall_ms"].values()) == pytest.approx(
            rec["wall_ms"], rel=1e-6, abs=1e-3)


def _stripped(rec: dict) -> dict:
    """The record as the program gave it before the front door."""
    ours = {f"http.request.{k}" for k in FRONT + AT_ACCEPT + (
        "client_gap_ms", "head_cpu_ms", "tail_cpu_ms")}
    return dict(rec, attrs={k: v for k, v in rec["attrs"].items()
                            if k not in ours},
                root_attrs={k: v for k, v in rec["root_attrs"].items()
                            if f"http.request.{k}" not in ours})


def _reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.mark.parametrize("name", OLD_READERS)
def test_an_older_reader_reads_the_same_with_the_front_door(
        profiled_records, name):
    _, recs, _, _ = profiled_records
    with_door = list(recs.values())
    without = [_stripped(r) for r in with_door]
    assert with_door != without
    assert _reader(name).read({"span_records": with_door}) == \
        _reader(name).read({"span_records": without})


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_front_door_reader_reads_the_records_or_none(
        profiled_records, name):
    _, recs, _, _ = profiled_records
    got = _reader(name).read({"span_records": list(recs.values())})
    # a float: the client's gap is taken from the end of the root that
    # wrote the previous reply, so its median can read below 0
    assert isinstance(got, float)
    assert _reader(name).read(
        {"span_records": [_stripped(r) for r in recs.values()]}) is None
    assert _reader(name).read({"span_records": None}) is None


def test_front_door_readers_on_hand_made_records():
    def rec(**attrs):
        return {"name": "http.request", "attrs": {
            f"http.request.{k}": v for k, v in attrs.items()}}

    recs = [rec(connect_ms=1.0, handoff_ms=4.0, head_cpu_ms=1.0,
                tail_cpu_ms=2.0),
            rec(connect_ms=9.0, handoff_ms=2.0, head_cpu_ms=0.0,
                client_gap_ms=7.0),
            rec(connect_ms=5.0, handoff_ms=3.0, client_gap_ms=-1.0)]
    ctx = {"span_records": recs}
    assert {n: _reader(n).read(ctx) for n in NEW_READERS} == {
        "front_connect_ms_per_req": 5.0,
        "front_handoff_ms_per_req": 3.0,
        "front_cpu_ms_per_req": 1.5,  # over the records that carry it
        "client_gap_ms_per_req": 3.0,
    }


def test_head_and_tail_are_annotations_in_the_requests_trace(
        profiled_records):
    """Under a profiler session `http.head` and `http.tail` are events
    on the host plane carrying the trace id of their request's
    `http.request`, so span_reduce names the idle time they cover."""
    from jax.profiler import ProfileData

    from chipbench import span_reduce

    _, recs, _, tmp = profiled_records
    path = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)[0]
    ids = {}  # name -> trace ids
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "trace_id" in stats:
                    ids.setdefault(ev.name, set()).add(
                        str(stats["trace_id"]).zfill(32))
    assert set(recs) <= ids["http.request"]
    assert set(recs) <= ids["http.head"]
    assert set(recs) <= ids["http.tail"]
    names = span_reduce.span_names(span_reduce.read_planes(str(tmp)))
    assert {"http.head", "http.tail", "http.request"} <= names


def test_trace_off_counts_the_connection_and_nothing_else(served,
                                                          monkeypatch):
    url, srv = served
    made = []
    init = observe.Stretch.__init__

    def counting(self, *a, **kw):
        made.append(a[0])
        init(self, *a, **kw)

    monkeypatch.setattr(observe.Stretch, "__init__", counting)
    monkeypatch.setenv("DGRAPH_TPU_TRACE", "0")
    c0 = METRICS.value("http_connections_total")
    d0 = METRICS.value("http_client_stamp_dropped_total")
    out = _post(url, QUERY, "garbage")
    assert "trace_id" not in out["extensions"]
    assert METRICS.value("http_connections_total") - c0 == 1
    assert METRICS.value("http_client_stamp_dropped_total") == d0
    assert made == [] and srv.httpd._fronts == {}


def test_the_stamp_is_one_header_of_three_fields():
    c = DgraphClient("http://127.0.0.1:1")
    t0 = time.time_ns()
    a, b = c._stamp().split(), c._stamp().split()
    assert a[0] == b[0] and len(a[0]) == 16
    assert (int(a[1]), int(b[1])) == (1, 2)
    assert t0 <= int(a[2]) <= int(b[2]) <= time.time_ns()
    assert DgraphClient("http://127.0.0.1:1")._stamp().split()[0] != a[0]


def test_the_client_imports_neither_jax_nor_the_tracer():
    code = ("import sys, dgraph_tpu.client; "
            "print(sorted(m for m in ('jax', 'dgraph_tpu.utils.observe') "
            "if m in sys.modules))")
    got = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True)
    assert got.stdout.strip() == "[]"


def test_backlog_is_the_accept_queue():
    lsock = socket.socket()
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(16)
    conns = []
    try:
        if http_server._backlog(lsock) is None:
            pytest.skip("no TCP_INFO on this platform")
        assert http_server._backlog(lsock) == 0
        conns = [socket.create_connection(lsock.getsockname())
                 for _ in range(3)]
        deadline = time.monotonic() + 5
        while http_server._backlog(lsock) != 3:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        lsock.accept()[0].close()
        assert http_server._backlog(lsock) == 2
    finally:
        for c in conns:
            c.close()
        lsock.close()


class _NoInfo:
    def __init__(self, got):
        self.got = got

    def getsockopt(self, *a):
        if isinstance(self.got, Exception):
            raise self.got
        return self.got


@pytest.mark.parametrize("got", [bytes(32), OSError("no TCP_INFO")],
                         ids=["zeros", "refused"])
def test_backlog_is_absent_where_the_platform_fills_nothing(got):
    assert http_server._backlog(_NoInfo(got)) is None


def test_client_replies_keep_the_newest_clients(monkeypatch):
    monkeypatch.setattr(http_server, "_CLIENTS_KEPT", 2)
    replies = http_server._ClientReplies()
    a1, a2, a4, b1, c1 = object(), object(), object(), object(), object()
    assert replies.swap(("a", 1), a1) is None
    assert replies.swap(("a", 2), a2) is a1
    assert replies.swap(("a", 4), a4) is None  # not the one before
    assert replies.swap(("b", 1), b1) is None
    assert replies.swap(("c", 1), c1) is None  # "a" goes out
    assert replies.swap(("a", 5), object()) is None
    assert replies.swap(("c", 2), object()) is c1


# -- a write's tree ------------------------------------------------------------

WRITERS, WRITES = 4, 6
WRITE_SPANS = ("http.request", "http.read", "mutate", "mutate.parse",
               "mutate.apply", "commit.wait", "http.reply")
WRITE_COUNTERS = ("mutate_nquads_total", "commit_batches_total",
                  "num_commits")
WRITE_READERS = ("write_cpu_ms_per_write", "commit_wait_ms_per_write",
                 "commit_batch_mean")


@pytest.fixture(scope="module")
def write_records(served, tmp_path_factory):
    """WRITERS clients committing WRITES writes of three N-Quads each at
    once, under a profiler session: their `/mutate` records, the
    counters that moved, and each record's spans."""
    import jax

    url, _ = served
    before = {c: METRICS.value(c) for c in WRITE_COUNTERS}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t0 = time.time()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("writes")),
                             profiler_options=opts)
    try:
        def loop(i):
            c = DgraphClient(url)
            for j in range(WRITES):
                c.txn().mutate(set_rdf="\n".join(
                    f'_:{b} <name> "w{i}.{j}.{b}" .' for b in "abc"),
                    commit_now=True)

        threads = [threading.Thread(target=loop, args=(i,))
                   for i in range(WRITERS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        jax.profiler.stop_trace()
    moved = {c: METRICS.value(c) - before[c] for c in WRITE_COUNTERS}
    deadline = time.monotonic() + 20
    while True:
        recs = [r for r in TRACER.request_records(1024, profiled=True)
                if r["start"] >= t0 and r["root_attrs"].get("path") == "/mutate"]
        if len(recs) == WRITERS * WRITES or time.monotonic() > deadline:
            break
        time.sleep(0.01)
    spans = {r["trace_id"]: TRACER.trace_spans(int(r["trace_id"], 16))
             for r in recs}
    return recs, moved, spans


def test_a_write_is_one_tree(write_records):
    """http.request > {http.read, mutate > {mutate.parse, mutate.apply,
    commit.wait}, http.reply}; a batch's `commit` sits under the
    `commit.wait` of the thread that ran it."""
    recs, _, spans = write_records
    assert len(recs) == WRITERS * WRITES
    for rec in recs:
        assert {n: rec["counts"].get(n) for n in WRITE_SPANS} == {
            n: 1 for n in WRITE_SPANS}
        assert rec["counts"].get("commit", 0) <= 1
        by_id = {sp["span_id"]: sp for sp in spans[rec["trace_id"]]}
        parent = {sp["name"]: by_id[sp["parent_id"]]["name"]
                  for sp in by_id.values() if sp["parent_id"] in by_id}
        assert {n: parent[n] for n in WRITE_SPANS[1:]} == {
            "http.read": "http.request", "mutate": "http.request",
            "mutate.parse": "mutate", "mutate.apply": "mutate",
            "commit.wait": "mutate", "http.reply": "http.request"}
        if "commit" in rec["counts"]:
            assert parent["commit"] == "commit.wait"
            commit = next(sp for sp in by_id.values()
                          if sp["name"] == "commit")
            assert {"batch", "oracle_ms", "propose_ms", "apply_ms"} <= set(
                commit["attrs"])


def test_the_write_counters_are_its_spans(write_records):
    """`mutate_nquads_total` is the N-Quads the `mutate.parse` spans
    parsed, `commit_batches_total` the `commit` spans (one a batch, in
    its leader's tree), `num_commits` the transactions their `batch`
    attrs carried."""
    recs, moved, _ = write_records
    summed = {k: sum(r["attrs"].get(k, 0) for r in recs) for k in (
        "mutate.parse.nquads", "mutate.apply.edges", "commit.batch")}
    assert moved["mutate_nquads_total"] == summed["mutate.parse.nquads"] \
        == summed["mutate.apply.edges"] == 3 * WRITERS * WRITES
    assert moved["commit_batches_total"] == sum(
        r["counts"].get("commit", 0) for r in recs) >= 1
    assert moved["num_commits"] == summed["commit.batch"] == WRITERS * WRITES


@pytest.mark.parametrize("name", WRITE_READERS)
def test_a_write_reader_reads_the_records_or_none(write_records, name):
    recs, moved, _ = write_records
    got = _reader(name).read({"mutate_records": recs})
    assert isinstance(got, float) and got >= 0
    if name == "commit_batch_mean":
        assert got == WRITERS * WRITES / moved["commit_batches_total"]
    if name == "write_cpu_ms_per_write":  # the CPU clock, profiled trees
        assert all(r["profiled"] for r in recs)
    assert _reader(name).read({"mutate_records": None}) is None
