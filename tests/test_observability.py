"""Metrics histograms, spans, heartbeat pruning, size-based rebalance
(ref x/metrics.go, conn/pool.go:233,
zero/tablet.go:53) + the distributed-observability primitives: random
span ids, traceparent context, exposition escaping/merge exactness,
OTLP shutdown flush, slow-query force-sampling.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import Metrics, Tracer


def test_histogram_buckets_and_render():
    m = Metrics(prefix="t")
    m.inc("ops")
    m.inc("ops", 2)
    m.set_gauge("live", 3)
    for v in (0.0002, 0.002, 0.02, 0.2, 2.0, 20.0):
        m.observe("lat_seconds", v)
    text = m.render()
    assert "t_ops 3" in text
    assert "t_live 3" in text
    assert 't_lat_seconds_bucket{le="+Inf"} 6' in text
    assert "t_lat_seconds_count 6" in text
    # cumulative counts are monotone
    counts = [
        int(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith("t_lat_seconds_bucket")
    ]
    assert counts == sorted(counts)


def test_timer_contextmanager():
    m = Metrics()
    with m.timer("op_seconds"):
        time.sleep(0.005)
    assert m._hists["op_seconds"].total == 1
    assert m._hists["op_seconds"].sum >= 0.005


def test_spans_nest_and_record(tmp_path):
    tr = Tracer(sink_path=str(tmp_path / "spans.jsonl"))
    with tr.span("outer", op="query"):
        with tr.span("inner"):
            pass
    spans = tr.recent()
    assert [s["name"] for s in spans] == ["inner", "outer"]
    inner, outer = spans
    assert inner["trace_id"] == outer["trace_id"]
    assert inner["parent_id"] == outer["span_id"]
    assert outer["attrs"] == {"op": "query"}
    assert (tmp_path / "spans.jsonl").read_text().count("\n") == 2


def test_engine_emits_metrics_and_spans():
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.utils.observe import METRICS, TRACER

    s = Server()
    s.alter("name: string @index(exact) .")
    s.new_txn().mutate_rdf(set_rdf='_:a <name> "m" .', commit_now=True)
    s.query('{ q(func: eq(name, "m")) { name } }')
    text = METRICS.render()
    assert "dgraph_tpu_num_queries" in text
    assert "dgraph_tpu_query_latency_seconds_bucket" in text
    assert "dgraph_tpu_commit_latency_seconds_count" in text
    names = {sp["name"] for sp in TRACER.recent()}
    assert {"query", "commit"} <= names


def test_membership_prune_and_size_rebalance():
    from dgraph_tpu.worker.groups import DistributedCluster

    c = DistributedCluster(n_groups=2, replicas=3)
    try:
        # all six members heartbeat via the pump loop
        time.sleep(0.3)
        assert len(c.zero.members) == 6
        c.kill_node(1)
        deadline = time.time() + 15
        while time.time() < deadline and 1 in c.zero.members:
            time.sleep(0.2)
        assert 1 not in c.zero.members  # pruned after missing heartbeats
        c.revive_node(1)

        # size-based rebalance: pile data onto one group's tablets
        c.alter("heavy: string .\nlight: string .")
        gid = c.zero.should_serve("heavy")
        # force both tablets onto the same group for the test
        c.zero.tablets["light"] = gid
        t = c.new_txn()
        rdf = [f'<0x{i:x}> <heavy> "{"x" * 200}" .' for i in range(1, 60)]
        rdf += [f'<0x{i:x}> <light> "s" .' for i in range(1, 10)]
        t.mutate_rdf(set_rdf="\n".join(rdf), commit_now=True)
        moved = c.rebalance_by_size(min_move_bytes=100)
        # moving `heavy` off the shared group narrows the byte gap
        assert moved == "heavy"
        assert c.zero.belongs_to(moved) != gid
        # data still readable after the move
        out = c.query("{ q(func: uid(0x1)) { heavy } }")
        assert out["data"]["q"][0]["heavy"].startswith("x")
    finally:
        c.close()


def test_traceparent_roundtrip_and_attach():
    from dgraph_tpu.utils.observe import (
        SpanContext,
        format_traceparent,
        parse_traceparent,
    )

    ctx = SpanContext(0xDEADBEEF0123456789ABCDEF01234567, 0x1234ABCD, True)
    assert parse_traceparent(format_traceparent(ctx)) == ctx
    un = SpanContext(5, 7, False)
    assert parse_traceparent(format_traceparent(un)) == un
    for bad in ("", "garbage", "00-zz-yy-01", "01-0-0-00", None):
        assert parse_traceparent(bad) is None
    tr = Tracer()
    token = tr.attach(ctx)
    try:
        assert tr.current_traceparent() == format_traceparent(ctx)
        with tr.span("child") as sp:
            assert sp.trace_id == ctx.trace_id
            assert sp.parent_id == ctx.span_id
            assert sp.sampled
    finally:
        tr.detach(token)
    assert tr.current_context() is None


def test_span_ids_never_collide_across_processes():
    """Two separate interpreter processes must emit disjoint random
    span/trace ids (the old sequential per-process counter collided and
    corrupted merged traces)."""
    prog = (
        "from dgraph_tpu.utils.observe import Tracer\n"
        "import json\n"
        "tr = Tracer()\n"
        "ids = []\n"
        "for _ in range(100):\n"
        "    with tr.span('s') as sp:\n"
        "        ids.append([sp.trace_id, sp.span_id])\n"
        "print(json.dumps(ids))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    runs = []
    for _ in range(2):
        got = subprocess.run(
            [sys.executable, "-c", prog],
            capture_output=True, text=True, env=env, timeout=120,
        )
        assert got.returncode == 0, got.stderr
        runs.append(json.loads(got.stdout))
    a_spans = {s for _, s in runs[0]}
    b_spans = {s for _, s in runs[1]}
    a_traces = {t for t, _ in runs[0]}
    b_traces = {t for t, _ in runs[1]}
    assert not a_spans & b_spans
    assert not a_traces & b_traces
    assert all(0 < s < 1 << 64 for s in a_spans | b_spans)
    assert all(0 < t < 1 << 128 for t in a_traces | b_traces)


def test_exposition_label_escaping_roundtrip():
    m = Metrics()
    m.inc("ops", 3)
    weird = 'inst"a\\b\nc'
    merged = observe.merge_expositions({weird: m.render()})
    parsed = observe.parse_exposition(merged)
    assert parsed["counter"]["dgraph_tpu_ops"] == 3
    labeled = [
        k for k in parsed["counter"] if k.startswith("dgraph_tpu_ops{")
    ]
    assert len(labeled) == 1
    assert parsed["counter"][labeled[0]] == 3
    inner = labeled[0][len("dgraph_tpu_ops{"):-1]
    assert observe._parse_labels(inner)["instance"] == weird


def test_parse_exposition_skips_malformed_lines():
    """A corrupt/foreign scrape (truncated line, OpenMetrics flavor,
    bare-word labels) must not crash the merge — malformed lines are
    skipped, well-formed ones still parse."""
    text = (
        "# TYPE x counter\n"
        "x{oops} 3\n"          # no '=' in labels
        "x{a=b} 3\n"           # unquoted label value
        'x{a="unterminated 3\n'
        "x notanumber\n"
        "x 2\n"
        'x{inst="ok"} 4\n'
    )
    p = observe.parse_exposition(text)
    assert p["counter"]["x"] == 2
    assert p["counter"]['x{inst="ok"}'] == 4
    # and a merge over a corrupt instance still succeeds
    merged = observe.merge_expositions({"a": text, "b": "x 1\n"})
    assert observe.parse_exposition(merged)["counter"]["x"] == 3


def test_merge_is_exact_for_counters_and_histograms():
    m1, m2 = Metrics(), Metrics()
    m1.inc("shared", 2)
    m2.inc("shared", 5)
    m1.inc("only_a", 1)
    m2.set_gauge("g", 4)
    for v in (0.0002, 0.03, 7.0):
        m1.observe("lat_seconds", v)
    for v in (0.0002, 0.2):
        m2.observe("lat_seconds", v)
    merged = observe.merge_expositions({"a": m1.render(), "b": m2.render()})
    p = observe.parse_exposition(merged)
    assert p["counter"]["dgraph_tpu_shared"] == 7
    assert p["counter"]['dgraph_tpu_shared{instance="a"}'] == 2
    assert p["counter"]['dgraph_tpu_shared{instance="b"}'] == 5
    assert p["counter"]["dgraph_tpu_only_a"] == 1
    assert p["gauge"]["dgraph_tpu_g"] == 4
    h = p["histogram"]["dgraph_tpu_lat_seconds"]
    assert h["count"] == 5
    assert h["sum"] == pytest.approx(7.2304)
    # exact bucket-merge on the shared cumulative grid
    assert h["buckets"]["0.0001"] == 0
    assert h["buckets"]["0.00025"] == 2  # one 0.0002 from each side
    assert h["buckets"]["0.05"] == 3     # + m1's 0.03
    assert h["buckets"]["0.25"] == 4     # + m2's 0.2
    assert h["buckets"]["10.0"] == 5     # + m1's 7.0
    assert h["buckets"]["+Inf"] == h["count"]
    # cumulative counts stay monotone in le order
    les = sorted(h["buckets"], key=observe._le_sortkey)
    cums = [h["buckets"][le] for le in les]
    assert cums == sorted(cums)


def test_slow_query_log_force_samples(tmp_path, monkeypatch):
    log = tmp_path / "slow.jsonl"
    monkeypatch.setenv("DGRAPH_TPU_SLOW_QUERY_MS", "0")
    monkeypatch.setenv("DGRAPH_TPU_SLOW_QUERY_LOG", str(log))
    monkeypatch.setenv("DGRAPH_TPU_SLOW_QUERY_LOG_MAX", "5")
    monkeypatch.setenv("DGRAPH_TPU_TRACE_SAMPLE", "0")  # unsampled trace
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter("name: string @index(exact) .")
    s.new_txn().mutate_rdf(set_rdf='_:a <name> "sl" .', commit_now=True)
    out = s.query('{ q(func: eq(name, "sl")) { name } }')
    recs = [json.loads(line) for line in open(log)]
    assert recs, "slow query not logged"
    rec = recs[-1]
    assert rec["kind"] == "query" and rec["took_ms"] > 0
    # the full local span tree rides along, force-sampled even though
    # the trace itself was unsampled
    assert rec["trace_id"] == out["extensions"]["trace_id"]
    names = {sp["name"] for sp in rec["spans"]}
    assert "query" in names and "level_task" in names
    roots = [sp for sp in rec["spans"] if sp["parent_id"] is None]
    assert len(roots) == 1
    # bounded: the log rewrites itself down to SLOW_QUERY_LOG_MAX
    for _ in range(12):
        s.query('{ q(func: eq(name, "sl")) { name } }')
    assert sum(1 for _ in open(log)) <= 5


def test_unsampled_spans_skip_export_but_feed_ring(tmp_path, monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_TRACE_SAMPLE", "0")
    sink = tmp_path / "sink.jsonl"
    tr = Tracer(sink_path=str(sink))
    with tr.span("root") as root:
        with tr.span("kid"):
            pass
    assert sink.read_text() == ""  # nothing exported
    assert {s["name"] for s in tr.recent()} == {"root", "kid"}
    # force-sampling retro-exports the buffered trace
    assert tr.force_sample(root.trace_id) == 2
    names = {json.loads(line)["name"] for line in open(sink)}
    assert names == {"root", "kid"}
    assert tr.force_sample(root.trace_id) == 0  # idempotent


def test_trace_disabled_is_a_noop(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_TRACE", "0")
    tr = Tracer()
    with tr.span("off") as sp:
        assert sp.trace_id == 0
    assert tr.recent() == []


def test_otlp_flush_exports_spans_the_drainer_dequeued():
    """Shutdown path: spans the background drainer already moved into
    its working batch (but not yet posted — batch/interval not due)
    must still reach the collector via otlp_flush()."""
    import http.server
    import threading

    got = []

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            got.append(json.loads(self.rfile.read(n)))
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        tr = Tracer()
        # huge batch + long interval: the drainer dequeues but never
        # posts on its own within the test window
        tr.enable_otlp(
            f"http://127.0.0.1:{srv.server_port}",
            batch=10_000, flush_interval_s=600.0,
        )
        with tr.span("a"):
            pass
        with tr.span("b"):
            pass
        deadline = time.time() + 5
        while time.time() < deadline:
            with tr._otlp["lock"]:
                moved = len(tr._otlp["pending"])
            if moved == 2 and tr._otlp["q"].empty():
                break
            time.sleep(0.02)
        assert moved == 2, "drainer never dequeued the spans"
        assert not got, "spans posted prematurely (batching defeated)"
        tr.otlp_flush()
        names = {
            s["name"]
            for b in got
            for s in b["resourceSpans"][0]["scopeSpans"][0]["spans"]
        }
        assert names == {"a", "b"}
    finally:
        srv.shutdown()


def test_otlp_exporter_posts_spans():
    """OTLP/HTTP trace export (the utils/observe.py seam)."""
    import http.server
    import json as _json
    import threading

    from dgraph_tpu.utils.observe import Tracer

    got = []

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            got.append((self.path, _json.loads(self.rfile.read(n))))
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        tr = Tracer()
        tr.enable_otlp(
            f"http://127.0.0.1:{srv.server_port}", batch=2,
            service_name="svc-x",
        )
        with tr.span("outer", q="abc"):
            with tr.span("inner"):
                pass
        tr.otlp_flush()  # exporting is async; force anything queued out
        import time as _time

        deadline = _time.time() + 5
        while not got and _time.time() < deadline:
            _time.sleep(0.02)  # drainer may hold the batch briefly
        assert got, "no OTLP batch received"
        while (
            sum(len(b["resourceSpans"][0]["scopeSpans"][0]["spans"]) for _, b in got) < 2
            and _time.time() < deadline
        ):
            _time.sleep(0.02)
        path, body = got[0]
        assert path == "/v1/traces"
        rs = body["resourceSpans"][0]
        attrs = {
            a["key"]: a["value"]["stringValue"]
            for a in rs["resource"]["attributes"]
        }
        assert attrs["service.name"] == "svc-x"
        # spans may arrive across one or two batches
        spans = [
            s
            for _, b in got
            for s in b["resourceSpans"][0]["scopeSpans"][0]["spans"]
        ]
        names = {s["name"] for s in spans}
        assert names == {"outer", "inner"}
        inner = next(s for s in spans if s["name"] == "inner")
        outer = next(s for s in spans if s["name"] == "outer")
        assert inner["parentSpanId"] == outer["spanId"]
        assert inner["traceId"] == outer["traceId"]
        assert int(outer["endTimeUnixNano"]) >= int(
            outer["startTimeUnixNano"]
        )
    finally:
        srv.shutdown()
