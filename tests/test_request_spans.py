"""One request, one span tree, one clock (utils/observe.py Tracer and the
layer boundaries of the two served paths): the tree a `/query` yields
over a real HTTPServer, its request record, the dispatch counters, the
spans on the profiler's timeline, and what TRACE=0 leaves of it."""

import glob
import json
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.api.http_server import HTTPServer
from dgraph_tpu.api.server import Server
from dgraph_tpu.client import DgraphClient
from dgraph_tpu.query import dispatch
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS, TRACER, Tracer

N = 240
FANOUT = 16
SETOP_SPANS = {"setop.pad", "setop.upload", "setop.launch", "setop.wait",
               "setop.split"}
VEC_SPANS = {"vec.plan", "vec.launch", "vec.wait", "vec.post"}
TRAVERSAL = ('{ q(func: uid(0x1)) { knows { knows '
             '@filter(eq(name, "p3")) { uid name } } } }')


def _vector_query(seed: int) -> str:
    q = np.random.default_rng(seed).normal(size=8).round(3).tolist()
    return ('{ q(func: similar_to(emb, 3, "%s")) { uid } }' % json.dumps(q))


@pytest.fixture(scope="module")
def served():
    """A small graph with vectors behind a real HTTPServer."""
    s = Server()
    s.alter('name: string @index(exact) .\nknows: [uid] @reverse .\n'
            'emb: float32vector @index(hnsw(metric: "euclidean")) .')
    rng = np.random.default_rng(7)
    rdf = []
    for i in range(1, N + 1):
        rdf.append(f'<0x{i:x}> <name> "p{i % 7}" .')
        for j in rng.choice(np.arange(1, N + 1), FANOUT, replace=False):
            rdf.append(f'<0x{i:x}> <knows> <0x{int(j):x}> .')
        vec = rng.normal(size=8).round(3).tolist()
        rdf.append(f'<0x{i:x}> <emb> "{json.dumps(vec)}" .')
    s.new_txn().mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
    srv = HTTPServer(s, host="127.0.0.1", port=0).start()
    try:
        yield s, DgraphClient(f"http://127.0.0.1:{srv.port}"), srv.port
    finally:
        srv.stop()


@pytest.fixture(autouse=True)
def every_tree_whole(monkeypatch):
    """Every request tree takes its fine spans, however fast the
    requests follow each other (the default: one tree in 50 ms)."""
    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 0.0)


@pytest.fixture
def profiling(tmp_path):
    """A jax profiler session, as chipbench/run.traced opens it: the one
    place where cpu=True spans read the thread CPU clock."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield tmp_path
    finally:
        if observe._profiler_active():
            jax.profiler.stop_trace()


@pytest.fixture
def device_path(monkeypatch):
    """The accelerator's threshold, lowered, on the CPU backend: the
    second level of TRAVERSAL (~250 ids) goes through the jitted set
    ops."""
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 64)


def _spans_of(tid: str) -> list:
    """The trace's spans once its root has finished: the client has the
    answer before the handler closes `http.request`."""
    deadline = time.monotonic() + 10
    while True:
        spans = TRACER.trace_spans(int(tid, 16))
        if any(sp["parent_id"] is None for sp in spans):
            return spans
        assert time.monotonic() < deadline, "the root span never finished"
        time.sleep(0.005)


def _record_of(tid: str) -> dict:
    _spans_of(tid)
    return next(r for r in TRACER.request_records(64)
                if r["trace_id"] == tid)


def _tree_of(resp: dict):
    """(spans of the response's trace, {span_id: span}, root)."""
    spans = _spans_of(resp["extensions"]["trace_id"])
    by_id = {sp["span_id"]: sp for sp in spans}
    roots = [sp for sp in spans if sp["parent_id"] is None]
    assert len(roots) == 1, [sp["name"] for sp in roots]
    return spans, by_id, roots[0]


def _children(spans, parent) -> set:
    return {sp["name"] for sp in spans
            if sp["parent_id"] == parent["span_id"]}


def _ancestors(by_id, sp) -> list:
    out = []
    while sp["parent_id"] in by_id:
        sp = by_id[sp["parent_id"]]
        out.append(sp["name"])
    return out


def _one(spans, name):
    got = [sp for sp in spans if sp["name"] == name]
    assert len(got) == 1, (name, len(got))
    return got[0]


def test_one_query_one_tree_set_ops(served, device_path):
    _, client, _ = served
    spans, by_id, root = _tree_of(client.query(TRAVERSAL))
    assert {sp["trace_id"] for sp in spans} == {root["trace_id"]}
    assert root["name"] == "http.request"
    assert root["attrs"]["path"] == "/query"
    assert root["attrs"]["bytes_in"] > 0 and root["attrs"]["bytes_out"] > 0
    assert _children(spans, root) == {"http.read", "query", "http.reply"}
    query = _one(spans, "query")
    assert _children(spans, query) == {"parse", "admit", "process", "encode"}
    process = _one(spans, "process")
    assert "level_task" in _children(spans, process)
    for name in SETOP_SPANS:
        sp = _one(spans, name)
        assert _ancestors(by_id, sp)[-3:] == ["process", "query",
                                              "http.request"]
    assert _one(spans, "setop.pad")["attrs"]["family"] == "intersect#shared"
    assert _one(spans, "setop.launch")["attrs"]["family"] == \
        "intersect#shared"
    up = _one(spans, "setop.upload")["attrs"]
    assert up["bytes"] > 0 and up["cache_misses"] >= 1
    assert _one(spans, "setop.wait")["attrs"]["bytes"] > 0
    levels = [sp for sp in spans if sp["name"] == "level_task"]
    # knows from the root, knows one step out, name two steps out
    assert sorted(sp["attrs"]["level"] for sp in levels) == [1, 2, 3]
    reads = [sp for sp in levels if sp["attrs"]["attr"] == "knows"]
    assert len(reads) == 2
    assert all(sp["attrs"]["decoded_bytes"] > 0 for sp in reads)
    assert _one(spans, "encode")["attrs"]["bytes"] > 0
    assert _one(spans, "parse")["attrs"]["plan_cache_hit"] in (True, False)


def test_one_query_one_tree_vector(served):
    _, client, _ = served
    spans, by_id, root = _tree_of(client.query(_vector_query(1)))
    assert {sp["trace_id"] for sp in spans} == {root["trace_id"]}
    assert root["name"] == "http.request"
    assert _children(spans, _one(spans, "query")) == {
        "parse", "admit", "process", "encode"}
    for name in VEC_SPANS:
        sp = _one(spans, name)
        assert "process" in _ancestors(by_id, sp)
        assert sp["attrs"]["tier"] == "brute" and sp["attrs"]["nq"] == 1
    assert not SETOP_SPANS & {sp["name"] for sp in spans}


@pytest.mark.parametrize("query", [TRAVERSAL, _vector_query(2)],
                         ids=["set_ops", "vector"])
def test_record_self_times_sum_to_the_root(served, device_path, profiling,
                                           query):
    _, client, _ = served
    tid = client.query(query)["extensions"]["trace_id"]
    rec = _record_of(tid)
    assert rec["name"] == "http.request"
    assert rec["root_attrs"]["path"] == "/query"
    assert rec["threads"] == 1
    assert rec["detail"] is True and rec["profiled"] is True
    assert sum(rec["self_wall_ms"].values()) == pytest.approx(
        rec["wall_ms"], rel=1e-6, abs=1e-3)
    # CPU time: never over the wall time, span by span and in the sum
    timed = {sp["name"] for sp in _spans_of(tid) if sp["cpu_ms"] is not None}
    assert {"http.request", "parse", "process", "encode"} <= timed
    for sp in _spans_of(tid):
        if sp["cpu_ms"] is not None:
            assert sp["cpu_ms"] <= sp["duration_ms"] + 0.05, sp["name"]
    assert sum(rec["self_cpu_ms"].values()) <= rec["wall_ms"] + 0.05
    assert all(v >= -1e-6 for v in rec["self_cpu_ms"].values())
    assert rec["counts"]["http.request"] == 1 and rec["counts"]["admit"] == 2


def test_dispatch_counter_rises_by_the_launch_spans(served, device_path):
    _, client, _ = served
    before = METRICS.snapshot("device_dispatch_total")
    tids = [client.query(q)["extensions"]["trace_id"]
            for q in (TRAVERSAL, _vector_query(3), TRAVERSAL)]
    after = METRICS.snapshot("device_dispatch_total")
    launches = {}
    for tid in tids:
        for sp in _spans_of(tid):
            if sp["name"] == "setop.launch":
                fam = sp["attrs"]["family"]
            elif sp["name"] == "vec.launch":
                fam = "vec." + sp["attrs"]["tier"]
            else:
                continue
            launches[fam] = launches.get(fam, 0) + 1
    assert launches == {"intersect#shared": 2, "vec.brute": 1}
    for fam, n in launches.items():
        key = f'device_dispatch_total{{family="{fam}"}}'
        assert after[key] - before.get(key, 0) == n
    assert (after["device_dispatch_total"]
            - before.get("device_dispatch_total", 0)) == 3


def test_host_kept_op_opens_no_span(monkeypatch):
    monkeypatch.setattr(dispatch, "_DEVICE_MIN_TOTAL", 1 << 20)
    d = dispatch.SetOpDispatcher()
    a = np.arange(1, 40, dtype=np.uint64)
    b = np.arange(20, 60, dtype=np.uint64)
    kept0 = METRICS.value("device_host_kept_total")
    sent0 = METRICS.value("device_dispatch_total")
    with TRACER.span("process") as root:
        assert d.run_pairs("intersect", [(a, b)])[0].tolist() == list(
            range(20, 40))
        d.run_rows_vs_one("difference", [a, a[:5]], b)
        d.run_chain("union", [a, b, a])
        d.run_rows_vs_one_ragged(
            "intersect", a, np.array([0, 10, len(a)]), b)
    assert METRICS.value("device_host_kept_total") - kept0 == 4
    assert METRICS.value("device_dispatch_total") == sent0
    assert [sp["name"] for sp in TRACER.trace_spans(root.trace_id)] == [
        "process"]


@pytest.mark.parametrize("variant", ["chain", "pairs", "rows"])
def test_every_device_variant_has_the_five_spans(device_path, variant):
    d = dispatch.SetOpDispatcher()
    rng = np.random.default_rng(11)
    sets = [np.unique(rng.integers(1, 4000, 300)).astype(np.uint64)
            for _ in range(4)]
    with TRACER.span("process") as root:
        if variant == "chain":
            got = d.run_chain("union", sets[:3])
            assert np.array_equal(got, np.unique(np.concatenate(sets[:3])))
            family = "union#chain"
        elif variant == "pairs":
            got = d.run_pairs("difference", [(sets[0], sets[1])])[0]
            assert np.array_equal(got, np.setdiff1d(sets[0], sets[1]))
            family = "difference"
        else:
            got = d.run_rows_vs_one("intersect", sets[:3], sets[3])
            for row, res in zip(sets, got):
                assert np.array_equal(res, np.intersect1d(row, sets[3]))
            family = "intersect#shared"
    spans = TRACER.trace_spans(root.trace_id)
    assert SETOP_SPANS <= {sp["name"] for sp in spans}
    launch = [sp for sp in spans if sp["name"] == "setop.launch"]
    assert [sp["attrs"]["family"] for sp in launch] == [family]
    assert all(sp["parent_id"] == root.span_id for sp in spans
               if sp["name"] != "process")


def test_device_cache_inserts_ride_in_the_upload_span(device_path,
                                                      monkeypatch):
    """An insert is host work on the launching thread, so it is neither
    after the wait (PR 26 measured -5.2% qps with it there) nor
    outside a span (its time would read as the executor's): both
    inserts happen under `setop.upload`, before the launch, and a
    second call with the same tokens uploads nothing. The span counts
    arrays, the DeviceCache's counters entries, and in the flat form an
    entry is one array (the level's ids; the shared operand): the
    lengths are scalars, never uploaded as arrays nor cached, so
    `device_cache_misses_total` rises by the arrays the span reports
    as misses."""
    d = dispatch.SetOpDispatcher()
    rng = np.random.default_rng(5)
    rows = [np.unique(rng.integers(1, 4000, 300)).astype(np.uint64)
            for _ in range(3)]
    b = np.unique(rng.integers(1, 4000, 900)).astype(np.uint64)
    seen = []
    put = dispatch.DeviceCache.put

    def spy(self, token, keys, arrays, nbytes):
        launched = [sp.name for sp in TRACER.finished
                    if sp.trace_id == root.trace_id
                    and sp.name == "setop.launch"]
        seen.append((token[0], observe._CURRENT.get().name, launched))
        inserted.append(len(arrays))
        return put(self, token, keys, arrays, nbytes)

    inserted = []
    monkeypatch.setattr(dispatch.DeviceCache, "put", spy)
    toks = [(b"k%d" % i, 7) for i in range(3)]
    counted, names = [], ("misses", "hits", "evictions")
    with TRACER.span("process") as root:
        for _ in range(2):
            c0 = [METRICS.value(f"device_cache_{n}_total") for n in names]
            got = d.run_rows_vs_one(
                "intersect", rows, b, row_tokens=toks, b_token=(b"kb", 7))
            counted.append((got, [
                METRICS.value(f"device_cache_{n}_total") - was
                for n, was in zip(names, c0)]))
    (first, cold), (again, warm) = counted
    assert seen == [("b", "setop.upload", []),
                    ("stack", "setop.upload", [])]
    for got, want in zip(first, again):
        assert np.array_equal(got, want)
    uploads = [sp["attrs"] for sp in TRACER.trace_spans(root.trace_id)
               if sp["name"] == "setop.upload"]
    assert [u["cache_misses"] for u in uploads] == [2, 0]
    assert uploads[1]["cache_hits"] == 2 and uploads[1]["bytes"] == 0
    # the flat array of the rows' ids, padded to a power of four of their
    # total, and b: 4 bytes an element, no stack of rows x widest row
    total = sum(len(r) for r in rows)
    assert uploads[0]["bytes"] == 4 * (
        dispatch._pow4(total) + dispatch._pow2(len(b)))
    assert inserted == [1, 1]
    assert sum(inserted) == uploads[0]["cache_misses"]
    assert cold == [len(inserted), 0, 0] and warm == [0, len(inserted), 0]
    assert d.device_cache.stats()["misses"] == len(inserted) == 2


def test_spans_are_events_on_the_profilers_host_plane(served, device_path,
                                                      profiling):
    """host_tracer_level=1 is what chipbench/run.traced asks for."""
    import jax
    from jax.profiler import ProfileData

    _, client, _ = served
    tid = client.query(TRAVERSAL)["extensions"]["trace_id"]
    _spans_of(tid)
    jax.profiler.stop_trace()
    path = glob.glob(str(profiling / "**" / "*.xplane.pb"), recursive=True)[0]
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "trace_id" in stats:
                    # the converter reads an all-digit hex id as a number
                    seen[ev.name] = str(stats["trace_id"]).zfill(32)
    assert {"http.request", "query", "parse", "process", "level_task",
            "encode", "http.reply"} | SETOP_SPANS <= set(seen)
    assert set(seen.values()) == {tid}


@pytest.mark.parametrize("query", [TRAVERSAL, _vector_query(4)],
                         ids=["set_ops", "vector"])
def test_trace_off_allocates_no_span_and_same_bytes(served, device_path,
                                                    monkeypatch, query):
    """TRACE=0: none of the sites allocates a Span, nothing reaches the
    ring, and the response's `data` bytes are those of a traced run
    (only extensions.trace_id may differ)."""
    import urllib.request

    _, _, port = served

    def post() -> dict:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/query", data=query.encode(),
            headers={"Content-Type": "application/dql"})
        with urllib.request.urlopen(req, timeout=60) as r:
            raw = r.read()
        start = raw.index(b'"data":')
        end = raw.index(b',"extensions":')
        return {"data": raw[start:end], "ext": json.loads(raw)["extensions"]}

    on = post()
    _spans_of(on["ext"]["trace_id"])
    made = []
    init = observe.Span.__init__

    def counting(self, *a, **kw):
        made.append(a[0])
        init(self, *a, **kw)

    monkeypatch.setattr(observe.Span, "__init__", counting)
    monkeypatch.setenv("DGRAPH_TPU_TRACE", "0")
    ring = len(TRACER.finished)
    newest = TRACER.finished[-1]
    off = post()
    assert made == []
    assert len(TRACER.finished) == ring and TRACER.finished[-1] is newest
    assert "trace_id" not in off["ext"]
    assert off["data"] == on["data"] and len(on["data"]) > 20


def test_trace_and_sample_are_read_once_per_root(monkeypatch):
    reads = {"trace": 0, "sample": 0}
    enabled, sample = observe._trace_enabled, observe._sample_root

    def count_trace():
        reads["trace"] += 1
        return enabled()

    def count_sample():
        reads["sample"] += 1
        return sample()

    monkeypatch.setattr(observe, "_trace_enabled", count_trace)
    monkeypatch.setattr(observe, "_sample_root", count_sample)
    tr = Tracer()
    with tr.span("root"):
        for _ in range(5):
            with tr.span("kid", cpu=True):
                with tr.span("grandkid"):
                    pass
    assert reads == {"trace": 1, "sample": 1}
    monkeypatch.setenv("DGRAPH_TPU_TRACE", "0")
    with tr.span("root") as root:
        assert tr.current_context() is None
        for _ in range(5):
            with tr.span("kid") as kid:
                assert kid is observe.NULL_SPAN
                kid.attrs["dropped"] = 1
    assert root.trace_id == 0 and not observe.NULL_SPAN.attrs
    assert reads == {"trace": 2, "sample": 1}
    assert tr.current_context() is None


def test_untraced_root_still_propagates_a_remote_parent(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_TRACE", "0")
    tr = Tracer()
    ctx = observe.SpanContext(0xABC, 0xDEF, True)
    token = tr.attach(ctx)
    try:
        with tr.span("rpc"):
            assert tr.current_context() == ctx
            with tr.span("inner"):
                assert tr.current_context() == ctx
        assert tr.current_context() == ctx
    finally:
        tr.detach(token)
    assert tr.recent() == []


def test_fine_spans_ride_in_one_tree_per_interval(monkeypatch, tmp_path):
    """Coarse spans in every tree; fine ones in the first tree of each
    interval, in every tree under a profiler session, and nowhere
    outside a live tree."""
    import jax

    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 3600.0)
    tr = Tracer()

    def request():
        with tr.span("http.request") as root:
            with tr.span("query"):
                with tr.span("parse", fine=True) as fine:
                    fine.attrs["plan_cache_hit"] = True
                    with tr.span("level_task"):  # below a dropped span
                        pass
        return sorted(sp["name"] for sp in tr.trace_spans(root.trace_id))

    whole = ["http.request", "level_task", "parse", "query"]
    coarse = ["http.request", "level_task", "query"]
    assert [request() for _ in range(3)] == [whole, coarse, coarse]
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert [request() for _ in range(2)] == [whole, whole]
    finally:
        jax.profiler.stop_trace()
    assert request() == coarse
    with tr.span("setop.pad", fine=True) as alone:  # no tree to ride in
        pass
    assert alone is observe.NULL_SPAN
    assert [r["detail"] for r in tr.request_records(8)] == [
        False, True, True, False, False, True]
    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 0.0)
    assert request() == whole


def test_a_root_without_fine_sites_cannot_take_a_requests_detail(
        monkeypatch):
    """The turn is drawn when a tree's first fine site asks: `commit`,
    `rpc_server` and `raft_recv` roots, which have no fine children,
    follow each other faster than the interval in a cluster and would
    otherwise starve every `http.request` tree of its fine spans."""
    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 3600.0)
    tr = Tracer()

    def others():
        for name in ("commit", "rpc_server", "raft_recv"):
            with tr.span(name):
                with tr.span("level_task"):
                    pass
        with tr.span("rpc_server", parent=observe.SpanContext(7, 9, True)):
            pass

    def request():
        with tr.span("http.request"):
            with tr.span("query"):
                with tr.span("parse", fine=True) as fine:
                    pass
        return fine

    others()
    first = request()
    others()
    second = request()
    assert type(first) is observe.Span and second is observe.NULL_SPAN
    recs = tr.request_records(32)
    mine = [r for r in recs if r["name"] == "http.request"]
    assert [r["detail"] for r in mine] == [False, True]
    assert [r["counts"].get("parse", 0) for r in mine] == [0, 1]
    # a tree no fine site asked in lacks nothing
    assert all(r["detail"] for r in recs if r["name"] != "http.request")


def test_cpu_clock_is_read_only_under_a_profiler_session(tmp_path):
    """One rule: `cpu=True` takes the thread CPU clock only in trees
    whose root began while a profiler session was collecting (a read
    costs 6 us on the chip's host); those trees are kept apart for
    `request_records(profiled=True)`."""
    import jax

    tr = Tracer()
    with tr.span("http.request", cpu=True, which="before") as root:
        with tr.span("parse", cpu=True) as kid:
            pass
    assert root.cpu_ms is None and kid.cpu_ms is None
    assert tr.request_records(8, profiled=True) == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("http.request", cpu=True, which="during") as root:
            with tr.span("parse", cpu=True) as kid:
                with tr.span("untimed") as bare:
                    pass
    finally:
        jax.profiler.stop_trace()
    assert root.cpu_ms is not None and kid.cpu_ms is not None
    assert bare.cpu_ms is None
    with tr.span("http.request", cpu=True, which="after") as root:
        pass
    assert root.cpu_ms is None
    got = tr.request_records(8, profiled=True)
    assert [r["root_attrs"]["which"] for r in got] == ["during"]
    assert set(got[0]["self_cpu_ms"]) == {"http.request", "parse"}
    got = tr.request_records(8)
    assert [r["root_attrs"]["which"] for r in got] == [
        "after", "during", "before"]
    assert [r["profiled"] for r in got] == [False, True, False]
    assert got[0]["self_cpu_ms"] == {} and got[2]["self_cpu_ms"] == {}


def test_span_histograms_take_a_tree_at_its_roots_finish():
    """One acquisition of the metrics lock per request tree: a child's
    `span_<name>_seconds` observation waits for its local root; a span
    that outlives its root goes in alone, and none is counted twice."""
    import contextvars

    def count(name):
        return METRICS.hist_stats(f"span_{name}_seconds")[1]

    tr = Tracer()
    gate, done = threading.Event(), threading.Event()

    def straggler():
        with tr.span("hist_straggler"):
            done.set()
            assert gate.wait(timeout=30)

    base = {n: count(n) for n in ("hist_root", "hist_kid", "hist_straggler")}
    with tr.span("hist_root"):
        for _ in range(3):
            with tr.span("hist_kid"):
                pass
        assert count("hist_kid") == base["hist_kid"]  # not yet
        t = threading.Thread(
            target=contextvars.copy_context().run, args=(straggler,))
        t.start()
        assert done.wait(timeout=30)
    assert count("hist_kid") == base["hist_kid"] + 3
    assert count("hist_root") == base["hist_root"] + 1
    assert count("hist_straggler") == base["hist_straggler"]  # still open
    gate.set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert count("hist_straggler") == base["hist_straggler"] + 1
    with tr.span("hist_root"):  # a later tree of the trace buffer
        pass
    assert count("hist_kid") == base["hist_kid"] + 3
    assert count("hist_straggler") == base["hist_straggler"] + 1


def test_inc_many_adds_each_counter_once():
    before = METRICS.snapshot("inc_many_")
    METRICS.inc_many({"inc_many_a": 2, "inc_many_b": 0.5})
    METRICS.inc_many({"inc_many_a": 1})
    after = METRICS.snapshot("inc_many_")
    assert after["inc_many_a"] - before.get("inc_many_a", 0) == 3
    assert after["inc_many_b"] - before.get("inc_many_b", 0) == 0.5


def test_child_on_another_thread_keeps_its_own_cpu(profiling):
    """A pool thread's child burns its own CPU time: it does not come
    off its parent's, while a same-thread child's does."""
    import contextvars

    tr = Tracer()

    def burn(ms: float):
        import time

        end = time.thread_time() + ms / 1e3
        while time.thread_time() < end:
            pass

    def pooled():
        with tr.span("level_task", cpu=True):
            burn(30)

    with tr.span("http.request", cpu=True) as root:
        with tr.span("process", cpu=True):
            burn(10)
            with tr.span("encode", cpu=True):
                burn(20)
            ctx = contextvars.copy_context()
            t = threading.Thread(target=ctx.run, args=(pooled,))
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
    rec = tr.request_records(1)[0]
    assert rec["trace_id"] == f"{root.trace_id:032x}"
    assert rec["threads"] == 2
    cpu = rec["self_cpu_ms"]
    assert cpu["encode"] >= 20 and cpu["level_task"] >= 30
    assert 10 <= cpu["process"] < 20, cpu  # encode's 20 came off, not 30
    assert cpu["http.request"] < 5


def test_self_wall_time_is_less_what_children_cover():
    """Hand-set clocks: overlapping children count once; a child's own
    children come off the child, not the grandparent."""
    tr = Tracer()
    with tr.span("root") as root:
        with tr.span("a") as a:
            with tr.span("a1") as a1:
                pass
        with tr.span("b") as b:
            pass
    root.start, root.end = 0.0, 1.0
    a.start, a.end = 0.1, 0.5
    a1.start, a1.end = 0.2, 0.3
    b.start, b.end = 0.4, 0.7  # overlaps a by 0.1
    rec = tr.request_records(1)[0]
    assert rec["self_wall_ms"] == pytest.approx(
        {"root": 400.0, "a": 300.0, "a1": 100.0, "b": 300.0})
    assert rec["wall_ms"] == pytest.approx(1000.0)
    assert rec["self_cpu_ms"] == {}


def test_buffer_keeps_1024_requests_and_every_root(monkeypatch):
    assert observe._TRACE_BUF_TRACES == 1024
    monkeypatch.setattr(observe, "_TRACE_BUF_SPANS", 8)
    tr = Tracer()
    for i in range(1030):
        with tr.span("http.request", i=i):
            if i == 1029:
                for _ in range(20):  # over the per-trace cap
                    with tr.span("level_task"):
                        pass
    recs = tr.request_records(2000)
    assert len(recs) == 1024
    assert [r["root_attrs"]["i"] for r in recs[:3]] == [1029, 1028, 1027]
    assert recs[0]["counts"] == {"http.request": 1, "level_task": 8}
    assert len(tr.request_records(5)) == 5


def test_debug_traces_serves_request_records(served):
    import urllib.request

    _, client, port = served
    tid = client.query(TRAVERSAL)["extensions"]["trace_id"]
    _spans_of(tid)
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces?requests=4",
            timeout=30) as r:
        out = json.loads(r.read())
    assert out["spans"]
    assert 1 <= len(out["requests"]) <= 4
    rec = out["requests"][0]
    assert rec["trace_id"] == tid and rec["name"] == "http.request"
    assert {"self_wall_ms", "self_cpu_ms", "counts", "attrs"} <= set(rec)
    assert rec["detail"] is True and rec["profiled"] is False
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces", timeout=30) as r:
        assert "requests" not in json.loads(r.read())


def test_named_scopes_reach_the_lowered_programs():
    from dgraph_tpu.models import vector

    d = dispatch.SetOpDispatcher()
    A = np.zeros((2, 8), np.uint32)
    L = np.zeros((2,), np.int32)
    B = np.zeros((16,), np.uint32)
    # flat: the level's ids as one array, no batch axis
    shared = d._get_jitted_shared("intersect", 8, 16).lower(
        A[0], np.int32(5), B, np.int32(3)).as_text(debug_info=True)
    assert "setop.intersect.shared" in shared
    assert "module @jit_membership" in shared  # the program's name stays
    stacked = d._get_jitted_shared("union", 8, 16).lower(
        A, L, B, np.int32(3)).as_text(debug_info=True)
    assert "setop.union.shared" in stacked
    assert "module @jit_union" in stacked
    pairs = d._get_jitted("union", 8, 8).lower(A, L, A, L).as_text(
        debug_info=True)
    assert "setop.union.pairs" in pairs
    chain = d._get_jitted_chain("union", 2, 8).lower(A, L).as_text(
        debug_info=True)
    assert "setop.union.chain" in chain
    f32 = np.float32
    ivf = vector._jit_ivf("euclidean", 2, 8).lower(
        np.zeros((4, 8), f32), np.zeros((4,), f32), np.zeros((6,), np.int32),
        np.zeros((6, 128, 8), f32), np.zeros((6, 128), f32),
        np.zeros((6, 128), np.int32), np.zeros((8,), f32),
    ).as_text(debug_info=True)
    assert "vec.ivf" in ivf and "module @jit_run" in ivf
    brute = vector._jit_brute("euclidean", 4).lower(
        np.zeros((16, 8), f32), np.zeros((16,), f32), np.ones((16,), bool),
        np.zeros((8,), f32)).as_text(debug_info=True)
    assert "vec.brute" in brute
