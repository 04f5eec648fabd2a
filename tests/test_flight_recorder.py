"""The flight recorder: query digest store (shape-keyed aggregates,
LRU eviction into `other`, cluster merge), the metrics history ring
(in-memory + on-disk AppendLog with torn-tail truncation), per-tenant
SLO slices, the wall-clock sampling profiler (on-demand + sustained-
burn auto-trigger), the debug HTTP surfaces, and the one-command
debug bundle.
"""

import json
import os
import tarfile
import threading
import time
import urllib.error
import urllib.request

import pytest

from dgraph_tpu.serving.digest import (
    DIGESTS,
    OTHER_SHAPE,
    DigestStore,
    merge_rows,
)
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS, HistoryLog, MetricsHistory


# ---------------------------------------------------------------------------
# digest store
# ---------------------------------------------------------------------------


def test_digest_record_snapshot_and_totals():
    d = DigestStore(capacity=8)
    d.record("0", "{ q ( func : has ( ? ) ) { ? } }", 0.010,
             rows=3, nbytes=120, plan_hit=True)
    d.record("0", "{ q ( func : has ( ? ) ) { ? } }", 0.030,
             rows=3, nbytes=120, result_hit=True)
    d.record("0", None, 0.001, error=True)  # unlexable -> `other`
    rows = {(r["ns"], r["shape"]): r for r in d.snapshot()}
    agg = rows[("0", "{ q ( func : has ( ? ) ) { ? } }")]
    assert agg["calls"] == 2 and agg["errors"] == 0
    assert agg["rows"] == 6 and agg["bytes"] == 240
    assert agg["plan_hits"] == 1 and agg["result_hits"] == 1
    assert abs(agg["lat_sum"] - 0.040) < 1e-9
    assert sum(agg["lat_counts"]) == 2
    other = rows[("0", OTHER_SHAPE)]
    assert other["calls"] == 1 and other["errors"] == 1
    t = d.totals()
    assert t["calls"] == 3 and t["errors"] == 1
    assert 0.0 < t["top_shape_lat_share"] <= 1.0


def test_digest_lru_eviction_folds_into_other_conserving_calls():
    d = DigestStore(capacity=2)
    before = METRICS.value("digest_evicted_total")
    for i in range(5):
        d.record("0", f"{{ shape {i} }}", 0.001 * (i + 1))
    rows = d.snapshot()
    assert len(rows) <= 2
    # eviction folded, never dropped: total calls conserved
    assert sum(r["calls"] for r in rows) == 5
    other = [r for r in rows if r["shape"] == OTHER_SHAPE]
    assert other and other[0]["calls"] >= 3
    assert METRICS.value("digest_evicted_total") > before


def test_digest_other_sink_never_evicts_itself():
    d = DigestStore(capacity=2)
    d.record("0", None, 0.001)  # `other` becomes the coldest row
    for i in range(6):
        d.record("0", f"{{ s {i} }}", 0.001)
    rows = d.snapshot()
    assert sum(r["calls"] for r in rows) == 7
    assert any(r["shape"] == OTHER_SHAPE for r in rows)


def test_digest_knob_off_disables_recording(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_DIGEST", "0")
    d = DigestStore(capacity=8)
    d.record("0", "{ q }", 0.001)
    assert d.snapshot() == []


def test_digest_merge_rows_sums_per_key_and_bucketwise():
    d = DigestStore(capacity=8)
    d.record("0", "{ a }", 0.010, rows=1)
    d.record("0", "{ a }", 0.020, rows=2)
    d.record("1", "{ a }", 0.005)
    snap = d.snapshot()
    merged = merge_rows([snap, snap])
    by_key = {(r["ns"], r["shape"]): r for r in merged}
    # the cluster-merge contract: merged counts == sum of scrapes
    assert by_key[("0", "{ a }")]["calls"] == 4
    assert by_key[("1", "{ a }")]["calls"] == 2
    one = next(r for r in snap if r["ns"] == "0")
    two = by_key[("0", "{ a }")]
    assert two["lat_counts"] == [c * 2 for c in one["lat_counts"]]
    assert abs(two["lat_sum"] - 2 * one["lat_sum"]) < 1e-9


def test_server_queries_feed_digest_with_normalized_shape():
    from dgraph_tpu.api.server import Server

    DIGESTS.reset()
    s = Server()
    s.alter("fname: string @index(exact) .")
    s.new_txn().mutate_rdf(
        set_rdf='<0x1> <fname> "A" .\n<0x2> <fname> "B" .',
        commit_now=True,
    )
    # two literals, one shape: digest keys on the normalized form
    s.query('{ q(func: eq(fname, "A")) { fname } }')
    s.query('{ q(func: eq(fname, "B")) { fname } }')
    rows = [r for r in DIGESTS.snapshot() if r["shape"] != OTHER_SHAPE]
    assert len(rows) == 1, rows
    r = rows[0]
    assert r["calls"] == 2 and r["errors"] == 0
    assert "?" in r["shape"] and '"A"' not in r["shape"]
    assert r["rows"] == 2 and r["bytes"] > 0
    # a failing query still accrues (as an error) — never silently lost
    with pytest.raises(Exception):
        s.query("{ q(func: eq(nosuchpred")
    total = DIGESTS.totals()
    assert total["errors"] >= 1


def test_slow_query_log_records_digest_shape(tmp_path, monkeypatch):
    from dgraph_tpu.api.server import Server

    log = tmp_path / "slow.jsonl"
    monkeypatch.setenv("DGRAPH_TPU_SLOW_QUERY_LOG", str(log))
    monkeypatch.setenv("DGRAPH_TPU_SLOW_QUERY_MS", "0.0")
    s = Server()
    s.alter("sqname: string .")
    s.new_txn().mutate_rdf(
        set_rdf='<0x1> <sqname> "A" .', commit_now=True
    )
    s.query('{ q(func: has(sqname)) { sqname } }')
    rec = json.loads(log.read_text().splitlines()[-1])
    assert "shape" in rec and "sqname" in rec["shape"], rec
    assert rec.get("ns") is not None


HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ref_golden")
SMOKE_CASES = json.load(open(os.path.join(HERE, "cases.json")))[::9]


@pytest.fixture(scope="module")
def golden_server():
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(open(os.path.join(HERE, "schema.txt")).read())
    for rdf in ("triples.rdf", "triples_facets.rdf"):
        s.new_txn().mutate_rdf(
            set_rdf=open(os.path.join(HERE, rdf)).read(), commit_now=True
        )
    return s


@pytest.mark.parametrize(
    "case", SMOKE_CASES, ids=[c["id"] for c in SMOKE_CASES]
)
def test_recorder_on_off_byte_identity(golden_server, case, monkeypatch):
    """The recorder never changes response bytes (or the error a query
    fails with), and the digest store records on the on arm only."""

    def run():
        try:
            d = golden_server.query(case["query"], want="raw")["data"]
        except Exception as exc:
            return f"{type(exc).__name__}: {exc}"
        raw = getattr(d, "raw", None)
        return bytes(raw) if raw is not None else json.dumps(
            d, sort_keys=True
        ).encode()

    calls = DIGESTS.totals()["calls"]
    monkeypatch.setenv("DGRAPH_TPU_DIGEST", "0")
    monkeypatch.setenv("DGRAPH_TPU_HISTORY", "0")
    off = run()
    assert DIGESTS.totals()["calls"] == calls
    monkeypatch.setenv("DGRAPH_TPU_DIGEST", "1")
    monkeypatch.setenv("DGRAPH_TPU_HISTORY", "1")
    assert run() == off
    assert DIGESTS.totals()["calls"] == calls + 1


# ---------------------------------------------------------------------------
# per-tenant SLO slices
# ---------------------------------------------------------------------------


def test_tenant_slices_report_and_healthz(monkeypatch):
    monkeypatch.setattr(observe, "_TENANT_SLO", {})
    observe.note_tenant("query", 7, 0.001)
    observe.note_tenant("query", 7, 0.002)
    observe.note_tenant("commit", 0, 0.001)
    rep = observe.tenant_slo_report()
    assert rep["query"]["7"]["windows"]["60s"]["total"] == 2
    assert rep["commit"]["0"]["windows"]["60s"]["total"] == 1
    h = observe.healthz()
    assert h["tenants"]["slo"]["query"]["7"]["windows"]["60s"]["total"] == 2
    assert "traffic" in h["tenants"]


def test_tenant_slices_bounded(monkeypatch):
    monkeypatch.setattr(observe, "_TENANT_SLO", {})
    for i in range(observe._TENANT_CAP + 16):
        observe.note_tenant("query", i, 0.001)
    assert len(observe._TENANT_SLO) <= observe._TENANT_CAP


# ---------------------------------------------------------------------------
# metrics history ring
# ---------------------------------------------------------------------------


def test_history_report_windowed_deltas():
    h = MetricsHistory(retention=16)
    h.record_now()
    METRICS.inc("num_queries", 3)
    h.record_now()
    rep = h.report(window_s=3600.0)
    assert rep["samples"] >= 2 and rep["retained"] >= 2
    assert rep["deltas"].get("num_queries") == 3.0
    assert rep["to_ts"] >= rep["from_ts"]
    # zero-delta metrics are dropped from the payload
    assert all(v for v in rep["deltas"].values())


def test_history_retention_bounds_ring():
    h = MetricsHistory(retention=4)
    for _ in range(9):
        h.record_now()
    assert len(h.snapshots()) == 4
    h.reset()
    assert h.snapshots() == []


def test_history_disk_roundtrip_survives_restart(tmp_path, monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_HISTORY_DIR", str(tmp_path))
    h = MetricsHistory(retention=8)
    h.set_label("t-restart")
    for _ in range(3):
        h.record_now()
    # a fresh process: empty ring, replayed from the same on-disk file
    h2 = MetricsHistory(retention=8)
    h2.set_label("t-restart")
    assert h2.load_disk() == 3
    assert len(h2.snapshots()) == 3
    # load_disk never clobbers a live ring
    assert h2.load_disk() == 0


def test_history_disk_rotation_keeps_newest_half(tmp_path, monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_HISTORY_DISK_MAX_BYTES", "4096")
    log = HistoryLog(str(tmp_path / "ring.log"))
    pad = "x" * 256
    rotations = 0
    for i in range(64):
        rotations += log.append({"i": i, "pad": pad})
    assert rotations >= 1
    snaps = log.scan()
    assert snaps, "rotation emptied the ring"
    # newest records survive; the oldest were dropped
    assert snaps[-1]["i"] == 63
    assert snaps[0]["i"] > 0
    assert os.path.getsize(log.path) <= 2 * 4096
    log.close()


def test_history_log_torn_tail_every_byte_boundary(tmp_path):
    """A crash mid-append leaves a torn tail: reopening folds to the
    last COMPLETE snapshot and physically truncates the garbage (the
    AppendLog WAL-crash contract, exercised at every byte boundary)."""
    from dgraph_tpu.worker.tabletmove import AppendLog

    seed = tmp_path / "seed.log"
    log = HistoryLog(str(seed))
    for i in range(3):
        log.append({"i": i, "values": {"m": float(i)}})
    log.close()
    blob = seed.read_bytes()
    offsets, pos = [], 0
    while pos < len(blob):
        _, plen = AppendLog._HDR.unpack_from(blob, pos)
        offsets.append(pos)
        pos += AppendLog._HDR.size + plen
    assert pos == len(blob) and len(offsets) == 3
    last = offsets[-1]
    for cut in range(last, len(blob)):
        p = tmp_path / f"cut_{cut}.log"
        p.write_bytes(blob[:cut])
        lr = HistoryLog(str(p))
        snaps = lr.scan()
        assert [s["i"] for s in snaps] == [0, 1], cut
        assert os.path.getsize(p) == last, cut  # tail truncated
        # appends after repair land on a clean boundary
        lr.append({"i": 99})
        lr.close()
        lr2 = HistoryLog(str(p))
        assert [s["i"] for s in lr2.scan()] == [0, 1, 99], cut
        lr2.close()


def test_history_sampler_thread_ticks(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_HISTORY_INTERVAL_S", "0.05")
    h = MetricsHistory(retention=64)
    h.start()
    try:
        deadline = time.monotonic() + 5.0
        while len(h.snapshots()) < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(h.snapshots()) >= 2
    finally:
        h.stop()


# ---------------------------------------------------------------------------
# sampling profiler
# ---------------------------------------------------------------------------


def _burn(stop):
    while not stop.is_set():
        sum(i * i for i in range(500))


def test_profiler_folds_busy_thread_stacks():
    from dgraph_tpu.utils.profiler import PROFILER

    before = METRICS.value("profiler_samples_total")
    stop = threading.Event()
    t = threading.Thread(target=_burn, args=(stop,), daemon=True)
    t.start()
    try:
        folded = PROFILER.profile(0.3, hz=200)
    finally:
        stop.set()
        t.join()
    assert folded.strip(), "no stacks sampled"
    assert "_burn" in folded
    # folded format: `frame;frame;... count`, counts descending
    counts = [int(line.rsplit(" ", 1)[1])
              for line in folded.strip().splitlines()]
    assert counts == sorted(counts, reverse=True)
    assert METRICS.value("profiler_samples_total") > before
    assert METRICS.value("profiler_active") == 0.0


def test_auto_profiler_triggers_on_burn_with_cooldown(monkeypatch):
    from dgraph_tpu.utils import profiler as profmod

    monkeypatch.setenv("DGRAPH_TPU_PROFILE_AUTO_S", "0.1")
    monkeypatch.setenv("DGRAPH_TPU_PROFILE_BURN", "2.0")
    auto = profmod.AutoProfiler()
    monkeypatch.setattr(
        auto, "_query_burn_300s", staticmethod(lambda: 9.0)
    )
    before = METRICS.value("profiler_auto_triggers_total")
    stop = threading.Event()
    t = threading.Thread(target=_burn, args=(stop,), daemon=True)
    t.start()
    try:
        assert auto.check() is True
        # cooldown: a second sustained-burn tick does NOT re-trigger
        assert auto.check() is False
        deadline = time.monotonic() + 5.0
        while auto.last_info() is None and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        stop.set()
        t.join()
    info = auto.last_info()
    assert info and info["burn"] == 9.0
    assert auto.last(), "auto-capture retained no folded stacks"
    assert METRICS.value("profiler_auto_triggers_total") == before + 1


def test_auto_profiler_quiet_below_burn(monkeypatch):
    from dgraph_tpu.utils import profiler as profmod

    monkeypatch.setenv("DGRAPH_TPU_PROFILE_BURN", "2.0")
    auto = profmod.AutoProfiler()
    monkeypatch.setattr(
        auto, "_query_burn_300s", staticmethod(lambda: 1.0)
    )
    assert auto.check() is False
    monkeypatch.setenv("DGRAPH_TPU_PROFILE_AUTO", "0")
    monkeypatch.setattr(
        auto, "_query_burn_300s", staticmethod(lambda: 99.0)
    )
    assert auto.check() is False


# ---------------------------------------------------------------------------
# debug HTTP surfaces + CLI
# ---------------------------------------------------------------------------


@pytest.fixture()
def http_server():
    from dgraph_tpu.api.http_server import HTTPServer
    from dgraph_tpu.api.server import Server

    engine = Server()
    engine.alter("hname: string @index(exact) .")
    engine.new_txn().mutate_rdf(
        set_rdf='<0x1> <hname> "A" .', commit_now=True
    )
    srv = HTTPServer(engine, port=0).start()
    yield engine, srv
    srv.stop()


def _get(srv, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}{path}", timeout=10
    ) as r:
        return r.read()


def test_debug_http_flight_recorder_routes(http_server):
    engine, srv = http_server
    DIGESTS.reset()
    engine.query("{ q(func: has(hname)) { hname } }")
    body = json.loads(_get(srv, "/debug/digests"))
    assert body["digests"] and body["digests"][0]["calls"] >= 1
    hist = json.loads(_get(srv, "/debug/history?window=60"))
    assert "samples" in hist and "retained" in hist
    cfg = json.loads(_get(srv, "/debug/config"))
    assert cfg["DIGEST"]["env"] == "DGRAPH_TPU_DIGEST"
    assert "value" in cfg["HISTORY_INTERVAL_S"]
    stop = threading.Event()
    t = threading.Thread(target=_burn, args=(stop,), daemon=True)
    t.start()
    try:
        folded = _get(srv, "/debug/profile?seconds=0.1")
    finally:
        stop.set()
        t.join()
    assert b"_burn" in folded
    # no auto-capture yet -> 404 on ?last=1
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(srv, "/debug/profile?last=1")
    assert ei.value.code == 404
    assert _get(srv, "/debug/slowlog") is not None


def test_cli_top_renders_digest_rows(http_server, capsys):
    from dgraph_tpu import cli

    engine, srv = http_server
    DIGESTS.reset()
    engine.query('{ q(func: eq(hname, "A")) { hname } }')
    rc = cli.main([
        "top", "--addr", f"http://127.0.0.1:{srv.port}", "-n", "5",
    ])
    assert rc in (0, None)
    out = capsys.readouterr().out
    assert "CALLS" in out and "SHAPE" in out
    assert "hname" in out
    rc = cli.main([
        "top", "--addr", f"http://127.0.0.1:{srv.port}", "--json",
    ])
    assert rc in (0, None)
    body = json.loads(capsys.readouterr().out)
    assert body["digests"]


def test_cli_debug_bundle_against_live_server(http_server, tmp_path,
                                              capsys):
    from dgraph_tpu import cli

    engine, srv = http_server
    engine.query("{ q(func: has(hname)) { hname } }")
    out = tmp_path / "bundle.tar.gz"
    rc = cli.main([
        "debug-bundle",
        "--addr", f"http://127.0.0.1:{srv.port}",
        "-o", str(out),
    ])
    assert rc in (0, None)
    assert "wrote" in capsys.readouterr().out
    with tarfile.open(out) as tar:
        names = {m.name for m in tar.getmembers()}
        for want in (
            "debug-bundle/MANIFEST.json",
            "debug-bundle/metrics.prom",
            "debug-bundle/digests.json",
            "debug-bundle/history.json",
            "debug-bundle/health.json",
            "debug-bundle/config.json",
            "debug-bundle/lockgraph.json",
        ):
            assert want in names, want
        manifest = json.load(
            tar.extractfile("debug-bundle/MANIFEST.json")
        )
        assert all(
            f.get("ok") for f in manifest["files"].values()
        ), manifest["files"]
        digests = json.load(
            tar.extractfile("debug-bundle/digests.json")
        )
        assert digests["digests"]
        lg = json.load(tar.extractfile("debug-bundle/lockgraph.json"))
        assert lg["edges"] and {"outer", "inner", "path"} <= set(
            lg["edges"][0]
        )


def test_cli_debug_bundle_partial_when_endpoint_dead(tmp_path, capsys):
    """Every endpoint down (no server at all) still yields a readable
    bundle: locally-computed sections present, failures in MANIFEST."""
    import socket

    from dgraph_tpu import cli

    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        dead_port = sk.getsockname()[1]
    out = tmp_path / "partial.tar.gz"
    rc = cli.main([
        "debug-bundle",
        "--addr", f"http://127.0.0.1:{dead_port}",
        "-o", str(out), "--timeout", "0.5",
    ])
    assert rc in (0, None)
    assert "PARTIAL" in capsys.readouterr().out
    with tarfile.open(out) as tar:
        names = {m.name for m in tar.getmembers()}
        assert "debug-bundle/MANIFEST.json" in names
        assert "debug-bundle/lockgraph.json" in names
        assert "debug-bundle/config.json" in names  # local fallback
        manifest = json.load(
            tar.extractfile("debug-bundle/MANIFEST.json")
        )
        assert not manifest["files"]["metrics.prom"]["ok"]
        assert manifest["files"]["config.json"].get("local")
