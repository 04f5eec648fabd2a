"""Serving front: cross-query micro-batching, plan cache, admission.

Byte-equality is the batcher's contract (the same one the worker pool
holds in test_parallel_exec.py): `DGRAPH_TPU_BATCH_WINDOW_US` is a pure
performance knob — the DQL golden smoke subset must serialize
identically at window 0 (the true off switch: the executor never sees
a batcher) and window 200, solo and under real cross-query
concurrency. Plan caching must keep correctness under concurrent
mutation (commit-epoch invalidation: no stale result ever), and
admission must shed with a retryable too_many_requests past the
in-flight budget and degrade — bounded, marked, partial — under a
seeded fault plan instead of queueing without bound.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.utils.observe import METRICS

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ref_golden")
CASES = json.load(open(os.path.join(HERE, "cases.json")))
SMOKE_CASES = CASES[::9]


@pytest.fixture(scope="module")
def golden_server():
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(open(os.path.join(HERE, "schema.txt")).read())
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf=open(os.path.join(HERE, "triples.rdf")).read(),
        commit_now=True,
    )
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf=open(os.path.join(HERE, "triples_facets.rdf")).read(),
        commit_now=True,
    )
    return s


def _query_windows(server, q, windows=("0", "200")):
    """Run q at each batch window; return the byte-exact payloads (or
    identical error reprs)."""
    out = []
    for w in windows:
        os.environ["DGRAPH_TPU_BATCH_WINDOW_US"] = w
        try:
            got = json.dumps(server.query(q)["data"], sort_keys=False)
        except Exception as exc:
            got = f"{type(exc).__name__}: {exc}"
        out.append(got)
    os.environ.pop("DGRAPH_TPU_BATCH_WINDOW_US", None)
    return out


# ---------------------------------------------------------------------------
# Micro-batcher: byte-equality, off switch, coalescing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case", SMOKE_CASES, ids=[c["id"] for c in SMOKE_CASES]
)
def test_batch_window_smoke(golden_server, case):
    off, on = _query_windows(golden_server, case["query"])
    assert off == on


def test_window_zero_is_a_true_off_switch(golden_server, monkeypatch):
    """At window 0 the executor must take today's exact path — the
    batcher object is never consulted at all."""
    from dgraph_tpu.serving.microbatch import MicroBatcher

    def boom(*a, **kw):
        raise AssertionError("batcher engaged at BATCH_WINDOW_US=0")

    monkeypatch.setattr(MicroBatcher, "read_uids", boom)
    monkeypatch.setattr(MicroBatcher, "read_values", boom)
    monkeypatch.delenv("DGRAPH_TPU_BATCH_WINDOW_US", raising=False)
    q = SMOKE_CASES[0]["query"]
    golden_server.query(q)  # must not touch the batcher


@pytest.mark.parametrize("admission", [0, 1])
def test_concurrent_queries_coalesce_and_stay_byte_identical(
    golden_server, monkeypatch, admission
):
    """Four closed-loop clients behind the batch window, with and
    without the admission gate (a shed query is retried, as a client
    of the front does): every answer arrives and is the solo answer."""
    from dgraph_tpu.conn.retry import Deadline, RetryPolicy, retrying_call
    from dgraph_tpu.serving import TooManyRequestsError

    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", str(admission))
    monkeypatch.setenv("DGRAPH_TPU_MAX_INFLIGHT", "2")
    shed = METRICS.value("admission_shed_total")
    q = """{ me(func: eq(name, "Michonne")) {
        name
        friend { name friend { name } }
        school { name }
    } }"""
    base = json.dumps(golden_server.query(q)["data"], sort_keys=False)
    # slow the level reads so same-shape arrivals reliably pile up
    # behind the in-flight dispatch (the coalescing trigger)
    real_read_many = golden_server.mem.read_many

    def slow_read_many(kv, keys_list, read_ts):
        time.sleep(0.002)
        return real_read_many(kv, keys_list, read_ts)

    monkeypatch.setattr(golden_server.mem, "read_many", slow_read_many)
    monkeypatch.setenv("DGRAPH_TPU_BATCH_WINDOW_US", "20000")
    before = METRICS.value("batch_coalesced_total")
    results = []
    lock = threading.Lock()
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        for _ in range(20):
            res = retrying_call(
                lambda: golden_server.query(q),
                policy=RetryPolicy(base=0.002, cap=0.05),
                deadline=Deadline.after(60.0),
                retryable=(TooManyRequestsError,),
            )
            with lock:
                results.append(json.dumps(res["data"], sort_keys=False))

    ths = [threading.Thread(target=worker) for _ in range(4)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    assert len(results) == 80 and all(r == base for r in results)
    shed = METRICS.value("admission_shed_total") - shed
    assert (shed > 0) == bool(admission), shed
    # a budget of two leaves nothing in flight to coalesce with
    assert admission or METRICS.value("batch_coalesced_total") > before, (
        "no cross-query coalescing happened under 4-way concurrency"
    )


def test_batcher_demux_slices_match_solo_reads():
    """Direct contract check: members arriving during an in-flight
    same-key dispatch form the next batch; its combined-read slices are
    byte-identical to each member's solo read, incl. duplicate keys."""
    from dgraph_tpu.serving.microbatch import MicroBatcher

    first_started = threading.Event()
    release_first = threading.Event()

    class StubCache:
        kv = object()
        mem = object()
        read_ts = 3
        calls = 0

        def uids_many(self, keys_list):
            StubCache.calls += 1
            if StubCache.calls == 1:
                first_started.set()
                release_first.wait(5)
            rows = [
                np.arange(int(k), dtype=np.uint64) for k in keys_list
            ]
            offs = np.zeros(len(rows) + 1, dtype=np.int64)
            offs[1:] = np.cumsum([len(r) for r in rows])
            flat = (
                np.concatenate(rows)
                if rows
                else np.zeros(0, np.uint64)
            )
            return flat, offs, [("tok", int(k)) for k in keys_list]

    cache = StubCache()
    b = MicroBatcher(inflight_fn=lambda: 4)
    os.environ["DGRAPH_TPU_BATCH_WINDOW_US"] = "1000000"
    before = METRICS.value("batch_coalesced_total")
    try:
        out = {}

        def member(name, keys):
            out[name] = b.read_uids("p", cache, keys)

        t0 = threading.Thread(target=member, args=("z", [1]))
        t1 = threading.Thread(target=member, args=("a", [3, 1]))
        t2 = threading.Thread(target=member, args=("b", [2, 3]))
        t0.start()  # dispatches immediately, blocks inside the read
        first_started.wait(5)
        t1.start()  # opens the next batch behind the runner
        time.sleep(0.05)
        t2.start()  # joins that batch
        time.sleep(0.05)
        release_first.set()
        for th in (t0, t1, t2):
            th.join(10)
    finally:
        os.environ.pop("DGRAPH_TPU_BATCH_WINDOW_US", None)
        release_first.set()
    assert METRICS.value("batch_coalesced_total") == before + 2
    for name, keys in (("z", [1]), ("a", [3, 1]), ("b", [2, 3])):
        flat, offs, toks = out[name]
        solo_flat, solo_offs, solo_toks = cache.uids_many(keys)
        assert np.array_equal(flat, solo_flat)
        assert np.array_equal(offs, solo_offs)
        assert list(toks) == list(solo_toks)


def test_batcher_snapshot_token_respects_commits(golden_server):
    """Two queries separated by a commit must never share a coalescing
    group key: the watermark moves with the commit."""
    b = golden_server.serving.batcher
    from dgraph_tpu.posting.lists import LocalCache

    c1 = LocalCache(
        golden_server.kv, golden_server.zero.read_ts(),
        mem=golden_server.mem,
    )
    t1 = b._snapshot_token(c1)
    tx = golden_server.new_txn()
    tx.mutate_rdf(
        set_rdf='<0x9999> <name> "snapshot-probe" .', commit_now=True
    )
    c2 = LocalCache(
        golden_server.kv, golden_server.zero.read_ts(),
        mem=golden_server.mem,
    )
    t2 = b._snapshot_token(c2)
    assert t1 != t2
    # and a pre-commit read_ts can never join the post-commit group
    assert b._snapshot_token(c1) != t2


# ---------------------------------------------------------------------------
# Plan cache
# ---------------------------------------------------------------------------


def test_normalize_strips_values_and_whitespace():
    from dgraph_tpu.serving.plancache import normalize

    a = normalize('{ q(func: eq(name, "Alice"), first: 5) { name } }')
    b = normalize(
        '{  q(func: eq(name,   "Bob"), first: 17) {\n name }\n }'
    )
    c = normalize('{ q(func: eq(age, 3)) { name } }')
    assert a is not None and b is not None and c is not None
    assert a[0] == b[0]  # same shape, different literals
    assert a[1] != b[1]
    assert a[0] != c[0]  # different shape
    assert normalize("{ q(func: \x01") is None or True  # lex errors -> None


def test_plan_cache_hit_and_variant_semantics(golden_server):
    pc = golden_server.serving.plan_cache
    q1 = '{ q(func: eq(name, "Michonne")) { name } }'
    q2 = '{ q(func: eq(name, "Rick Grimes")) { name } }'
    h0 = METRICS.value("plan_cache_hit_total")
    r1a = json.dumps(golden_server.query(q1)["data"])
    r1b = json.dumps(golden_server.query(q1)["data"])
    assert r1a == r1b
    assert METRICS.value("plan_cache_hit_total") > h0
    # same shape, different literal: correct (different) results
    r2 = json.dumps(golden_server.query(q2)["data"])
    assert "Rick" in r2 and r2 != r1a
    st = pc.stats()
    assert st["shapes"] >= 1 and st["hits"] >= 1


def test_plan_cache_reuse_is_execution_safe(golden_server):
    """The executor must not mutate cached parse trees: repeated
    cache-hit executions (incl. expand/recurse, which build child
    GraphQuerys at run time) stay byte-identical."""
    queries = [
        '{ q(func: eq(name, "Michonne")) { expand(_all_) } }',
        '{ q(func: eq(name, "Michonne")) @recurse(depth: 3) '
        "{ name friend } }",
        '{ q(func: eq(name, "Michonne")) { name friend @facets '
        "(first: 2) { name } } }",
    ]
    for q in queries:
        first = json.dumps(golden_server.query(q)["data"])
        for _ in range(3):
            assert json.dumps(golden_server.query(q)["data"]) == first


def test_plan_cache_epoch_invalidation_no_stale_plans():
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter("pname: string @index(exact) .")
    t = s.new_txn()
    t.mutate_rdf(set_rdf='<0x1> <pname> "v0" .', commit_now=True)
    q = '{ q(func: has(pname)) { pname } }'
    assert s.query(q)["data"]["q"][0]["pname"] == "v0"
    e0 = s.serving.plan_cache.epoch
    t = s.new_txn()
    t.mutate_rdf(set_rdf='<0x1> <pname> "v1" .', commit_now=True)
    assert s.serving.plan_cache.epoch > e0  # commit bumped the epoch
    assert s.query(q)["data"]["q"][0]["pname"] == "v1"  # never stale


def test_plan_cache_correct_under_concurrent_mutation():
    """Queries racing a mutator must always see a committed value —
    a cached plan may be reused, a stale RESULT may not exist."""
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter("cname: string @index(exact) .")
    t = s.new_txn()
    t.mutate_rdf(set_rdf='<0x1> <cname> "w0" .', commit_now=True)
    stop = threading.Event()
    versions = ["w0"]
    errs = []

    def mutator():
        for i in range(1, 25):
            # the value becomes legal BEFORE the commit lands (a reader
            # may observe it the instant the commit applies)
            versions.append(f"w{i}")
            tx = s.new_txn()
            tx.mutate_rdf(
                set_rdf=f'<0x1> <cname> "w{i}" .', commit_now=True
            )
            time.sleep(0.001)
        stop.set()

    def reader():
        while not stop.is_set():
            try:
                got = s.query('{ q(func: has(cname)) { cname } }')
                val = got["data"]["q"][0]["cname"]
                if val not in versions:
                    errs.append(val)
            except Exception as exc:  # pragma: no cover
                errs.append(repr(exc))

    ths = [threading.Thread(target=mutator)] + [
        threading.Thread(target=reader) for _ in range(2)
    ]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    assert not errs, errs
    # the final read must see the final committed value
    assert (
        s.query('{ q(func: has(cname)) { cname } }')["data"]["q"][0][
            "cname"
        ]
        == "w24"
    )


def test_plan_cache_lru_bound(monkeypatch):
    from dgraph_tpu.serving.plancache import PlanCache

    pc = PlanCache(size=4)
    for i in range(10):
        pc.put(f"shape{i}", ("x",), [i])
    assert pc.stats()["shapes"] <= 4
    assert pc.get("shape9", ("x",)) == [9]
    assert pc.get("shape0", ("x",)) is None  # evicted


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def test_admission_sheds_over_budget_and_is_retryable(monkeypatch):
    from dgraph_tpu.serving import TooManyRequestsError
    from dgraph_tpu.serving.front import ServingFront

    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", "1")
    monkeypatch.setenv("DGRAPH_TPU_MAX_INFLIGHT", "2")
    front = ServingFront()
    t1 = front.admit(None)
    t2 = front.admit(None)
    shed0 = METRICS.value("admission_shed_total")
    with pytest.raises(TooManyRequestsError) as exc:
        front.admit(None)
    assert exc.value.retryable and exc.value.code == "too_many_requests"
    assert METRICS.value("admission_shed_total") == shed0 + 1
    front.finish(t1, None, 1.0)
    t3 = front.admit(None)  # slot freed -> admitted again
    front.finish(t2, None, 1.0)
    front.finish(t3, None, 1.0)
    assert front.admission.inflight == 0


def test_admission_idle_server_always_admits_one(monkeypatch):
    """A single expensive query must be admitted on an idle server even
    when its estimated cost exceeds the whole budget."""
    from dgraph_tpu.serving.front import ServingFront

    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", "1")
    monkeypatch.setenv("DGRAPH_TPU_MAX_INFLIGHT", "1")
    front = ServingFront()
    front.plan_cache.observe_cost("big", 10000.0)  # ~1000 tokens
    t = front.admit("big")
    assert t.cost > 1.0
    front.finish(t, "big", 5.0)


def test_admission_degrades_when_slow_query_signal_fires(monkeypatch):
    from dgraph_tpu.serving.front import ServingFront

    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", "1")
    monkeypatch.setenv("DGRAPH_TPU_MAX_INFLIGHT", "64")
    front = ServingFront()
    d0 = METRICS.value("admission_degraded_total")
    for _ in range(6):  # cross the saturation threshold
        front.admission.note_slow()
    t = front.admit(None)
    assert t.degrade
    assert METRICS.value("admission_degraded_total") == d0 + 1
    front.finish(t, None, 1.0)


def test_http_429_with_retryable_code(monkeypatch):
    import urllib.error
    import urllib.request

    from dgraph_tpu.api.http_server import HTTPServer
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.conn.retry import RetryPolicy, retrying_call

    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", "1")
    monkeypatch.setenv("DGRAPH_TPU_MAX_INFLIGHT", "1")
    s = Server()
    s.alter("hname: string @index(exact) .")
    srv = HTTPServer(s, port=0).start()
    try:
        # hold the whole budget so the HTTP query sheds
        held = s.serving.admit(None)
        url = f"http://127.0.0.1:{srv.port}/query"

        def post():
            req = urllib.request.Request(
                url,
                data=b'{ q(func: has(hname)) { hname } }',
                method="POST",
            )
            return urllib.request.urlopen(req, timeout=10)

        with pytest.raises(urllib.error.HTTPError) as err:
            post()
        assert err.value.code == 429
        body = json.loads(err.value.read())
        ext = body["errors"][0]["extensions"]
        assert ext["code"] == "too_many_requests" and ext["retryable"]

        # retrying_call: release the budget from a timer; the retry
        # loop must then get through
        timer = threading.Timer(
            0.2, lambda: s.serving.finish(held, None, 1.0)
        )
        timer.start()

        def attempt():
            try:
                return post().read()
            except urllib.error.HTTPError as e:
                if e.code == 429:
                    e.retryable = True  # transport-level mapping
                raise

        got = retrying_call(
            attempt,
            policy=RetryPolicy(base=0.05, cap=0.2, max_attempts=50),
        )
        assert b'"data"' in got
        timer.join()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Exec-pool backpressure (bounded submit + gauge)
# ---------------------------------------------------------------------------


def test_pool_bounded_submit_and_gauge(monkeypatch):
    from dgraph_tpu.query import subgraph

    # a full backlog refuses the submit (caller expands inline)
    monkeypatch.setattr(subgraph, "_POOL_QUEUED", 8)
    pool = subgraph._expand_pool(2)
    assert subgraph._submit_bounded(pool, 2, lambda: None) is None
    monkeypatch.setattr(subgraph, "_POOL_QUEUED", 0)
    fut = subgraph._submit_bounded(pool, 2, lambda: 41)
    assert fut is not None and fut.result() == 41
    queued, workers = subgraph.pool_backpressure()
    assert queued == 0


def test_pool_queue_depth_surfaces_in_profile(golden_server, monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_EXEC_WORKERS", "4")
    out = golden_server.query(
        """{ me(func: eq(name, "Michonne")) {
            friend { name } school { name } pet { name }
        } }"""
    )
    prof = out["extensions"]["profile"]
    assert "exec_pool" in prof
    assert prof["exec_pool"]["max_queue_depth"] >= 0


# ---------------------------------------------------------------------------
# Admission under a seeded fault plan (cluster, chaos marker)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_admission_shed_degrade_under_seeded_faults(monkeypatch):
    """Fixed-seed delay faults slow the cluster's RPC plane; a client
    flood against a tiny in-flight budget must shed fast (retryable),
    keep every accepted query bounded, and mark degraded-admission
    responses — never queue without bound."""
    from dgraph_tpu.conn import faults
    from dgraph_tpu.conn.faults import FaultPlan
    from dgraph_tpu.serving import TooManyRequestsError
    from dgraph_tpu.worker.harness import ProcCluster

    monkeypatch.setenv("DGRAPH_TPU_ADMISSION", "1")
    monkeypatch.setenv("DGRAPH_TPU_MAX_INFLIGHT", "2")
    monkeypatch.setenv("DGRAPH_TPU_SLOW_QUERY_MS", "25")
    c = ProcCluster(n_groups=1, replicas=3)
    try:
        c.alter("aname: string @index(exact) .")
        c.new_txn().mutate_rdf(
            set_rdf="\n".join(
                f'<0x{i:x}> <aname> "acct{i}" .' for i in range(1, 30)
            ),
            commit_now=True,
        )
        faults.install(
            FaultPlan(
                seed=1234,
                rules=[
                    dict(
                        point="send", action="delay", p=0.5,
                        delay_ms=30,
                    ),
                ],
            )
        )
        stats = {"ok": 0, "shed": 0, "degraded": 0, "slowest": 0.0}
        lock = threading.Lock()

        def client(i):
            for _ in range(6):
                t0 = time.monotonic()
                try:
                    out = c.query(
                        '{ q(func: eq(aname, "acct%d")) { aname } }'
                        % (i + 1),
                        timeout_s=10.0,
                    )
                    with lock:
                        stats["ok"] += 1
                        if out["extensions"].get("degraded_admission"):
                            stats["degraded"] += 1
                except TooManyRequestsError:
                    with lock:
                        stats["shed"] += 1
                finally:
                    took = time.monotonic() - t0
                    with lock:
                        stats["slowest"] = max(stats["slowest"], took)

        ths = [
            threading.Thread(target=client, args=(i,)) for i in range(8)
        ]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        assert stats["shed"] > 0, stats  # over-limit traffic shed
        assert stats["ok"] > 0, stats  # in-budget traffic served
        # bounded: nothing queued past its deadline + fault delays
        assert stats["slowest"] < 15.0, stats
        assert METRICS.value("admission_shed_total") > 0
    finally:
        faults.reset()
        c.close()
