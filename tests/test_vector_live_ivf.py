"""A vector index's device snapshot takes writes in place.

Inserts, re-embeds (a set on a uid that has a vector) and deletes queue
host rows; the next search applies them to the device snapshot through
update programs that donate the arrays they change: an appended row
lands in a free row of each of its top-2 cells' last slab, or of a spare
slab given to that cell, and a tombstone turns the row's slab entries
into the probe's -1 padding. The plain reference is exact numpy top-k
over the live rows. Runs on the jitted engine (`VEC_QUANT=0`) with a
small `ivf_threshold`, so the CPU takes the slab path the chip takes."""

from __future__ import annotations

import sys
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.models import vector
from dgraph_tpu.models.vector import VectorIndex
from dgraph_tpu.utils.observe import METRICS

REBUILDS = "vector_ivf_rebuilds_total"
K = 10
NPROBE = 3  # a narrow probe, so that recall reads under 1


@pytest.fixture(autouse=True)
def jitted_engine(monkeypatch):
    monkeypatch.setenv("DGRAPH_TPU_VEC_QUANT", "0")


def mixture(seed: int, n: int, d: int, clusters: int = 32):
    rng = np.random.default_rng(seed)
    centers = (rng.standard_normal((clusters, d)) * 4.0).astype(np.float32)
    V = (centers[rng.integers(0, clusters, n)]
         + rng.standard_normal((n, d))).astype(np.float32)
    return rng, centers, V


def built(V: np.ndarray, first_uid: int = 1) -> VectorIndex:
    idx = VectorIndex("emb", ivf_threshold=1024, nprobe=NPROBE)
    idx.bulk_load(np.arange(first_uid, first_uid + len(V), dtype=np.uint64),
                  V)
    idx.search(V[0], K)  # the first search builds the snapshot
    assert idx._ivf is not None and idx._device["live"]
    return idx


def exact(live: dict, q: np.ndarray, k: int = K) -> list:
    uids = np.fromiter(live, np.int64, len(live))
    V = np.stack([live[u] for u in uids.tolist()]).astype(np.float64)
    d = ((V - q.astype(np.float64)) ** 2).sum(axis=1)
    return uids[np.lexsort((uids, d))[:k]].tolist()


def recall(idx: VectorIndex, live: dict, queries) -> float:
    hits = sum(len(set(map(int, idx.search(q, K))) & set(exact(live, q)))
               for q in queries)
    return hits / (K * len(queries))


def cells_of(idx: VectorIndex) -> dict:
    """{cell: uids laid in its slabs} as the device holds them, the
    pending writes applied."""
    dev = idx._sync_device()
    ivf = dev["ivf"]["dev"]
    slab_cell = np.asarray(ivf["slab_cell"])
    rows = np.asarray(ivf["flat_rows"])
    out = {}
    for s in np.flatnonzero(slab_cell >= 0):
        r = rows[s][rows[s] >= 0]
        out.setdefault(int(slab_cell[s]), set()).update(
            int(u) for u in dev["uids"][r])
    return {c: u for c, u in out.items() if u}


def laid_afresh(idx: VectorIndex, live: dict) -> dict:
    """The same rows laid out from scratch under the index's centroids:
    each live row in its exact top-2 cells."""
    uids = list(live)
    top2 = vector._assign_top2_exact(
        np.stack([live[u] for u in uids]), idx._ivf["centroids"])
    out = {}
    for u, pair in zip(uids, top2.tolist()):
        for c in pair:
            out.setdefault(c, set()).add(u)
    return out


def rebuilds() -> float:
    return METRICS.value(REBUILDS)


@pytest.mark.parametrize("seed,n,d,shares", [
    (11, 4096, 32, (6, 3, 1)),
    (12, 8192, 64, (2, 2, 6)),
    (13, 16384, 32, (6, 3, 1)),
    (14, 8192, 32, (1, 8, 1)),
])
def test_interleaved_writes_and_searches_match_the_reference(
        seed, n, d, shares):
    rng, centers, V = mixture(seed, n, d)
    idx = built(V)
    live = {u: V[u - 1] for u in range(1, n + 1)}
    fresh_uid = 10 * n
    r0, builds = rebuilds(), idx.build_count
    p = np.asarray(shares, np.float64) / sum(shares)
    for step in range(300):
        op = rng.choice(3, p=p)
        if op == 0:  # insert a new uid
            fresh_uid += 1
            u = fresh_uid
            vec = (centers[rng.integers(len(centers))]
                   + rng.standard_normal(d)).astype(np.float32)
        elif op == 1:  # re-embed a live uid
            u = int(rng.choice(list(live)))
            vec = (centers[rng.integers(len(centers))]
                   + rng.standard_normal(d)).astype(np.float32)
        else:  # delete a live uid
            u = int(rng.choice(list(live)))
            vec = live.pop(u)
            idx.remove(u)
        if op != 2:
            live[u] = vec
            idx.insert(u, vec)
        if step % 3:
            continue
        # the anchor: the row just written is the nearest by far, and a
        # deleted one is named by no answer
        q = (vec + 0.05 * rng.standard_normal(d)).astype(np.float32)
        got = [int(x) for x in idx.search(q, K)]
        assert len(got) == K and len(set(got)) == K
        assert all(g in live for g in got), "an answer names a dead uid"
        if op == 2:
            assert u not in got
        else:
            assert got[0] == u
        # each at its current value: closest first by the live vectors
        dist = [float(((live[g] - q) ** 2).sum()) for g in got]
        assert dist == sorted(dist) or np.allclose(dist, sorted(dist),
                                                   rtol=1e-5)
    assert idx.build_count == builds and rebuilds() == r0
    assert cells_of(idx) == laid_afresh(idx, live)
    # between two centres, where a narrow probe misses rows
    queries = [(centers[rng.integers(len(centers), size=2)].mean(axis=0)
                + rng.standard_normal(d)).astype(np.float32)
               for _ in range(40)]
    uids = np.fromiter(live, np.uint64, len(live))
    again = VectorIndex("emb", ivf_threshold=1024, nprobe=NPROBE)
    again.bulk_load(uids, np.stack([live[int(u)] for u in uids]))
    assert recall(idx, live, queries) >= recall(again, live, queries)


def test_a_snapshot_under_the_ivf_line_takes_writes_in_place():
    """Below `ivf_threshold` the exact brute tier serves: the corpus
    arrays take the writes in place, and every answer is the exact
    top-k of the live rows."""
    rng, centers, V = mixture(61, 4096, 32)
    idx = VectorIndex("emb", ivf_threshold=1 << 30)
    idx.bulk_load(np.arange(1, 4097, dtype=np.uint64), V)
    idx.search(V[0], K)
    assert idx._ivf is None and idx._device["live"]
    live = {u: V[u - 1] for u in range(1, 4097)}
    r0 = rebuilds()
    for step in range(120):
        u = int(rng.choice(list(live)))
        if step % 4 == 0:
            live.pop(u)
            idx.remove(u)
        else:
            u = u if step % 4 == 1 else 50_000 + step
            live[u] = (centers[rng.integers(len(centers))]
                       + rng.standard_normal(32)).astype(np.float32)
            idx.insert(u, live[u])
        q = (centers[rng.integers(len(centers))]
             + rng.standard_normal(32)).astype(np.float32)
        assert [int(x) for x in idx.search(q, K)] == exact(live, q)
    assert rebuilds() == r0


@pytest.mark.parametrize("rows,cap", [(5000, 8192), (8000, 9216),
                                      (8192, 9216), (65000, 67072)])
def test_the_corpus_room_is_its_padding_or_a_32nd(rows, cap):
    """A snapshot's spare corpus rows are what its pow2 padding leaves,
    or 1,024 or 1/32 more rows, whichever is more, rounded up to
    `_PAD_ROWS`, where that padding leaves fewer: a corpus just under a
    power of two does not double. A few writes rebuild nothing; more
    appended rows than the room holds rebuild once, counted
    `why="spare"`, into a snapshot with room for them."""
    rng, centers, V = mixture(23, rows, 16)
    idx = built(V)
    assert idx._device["cap"] == cap
    by_why = 'vector_ivf_rebuilds_total{why="spare"}'
    r0, spare_r0 = rebuilds(), METRICS.value(by_why)
    for i in range(8):
        idx.insert(100_000 + i, V[i] + 0.01)
    assert int(idx.search(V[3] + 0.01, 1)[0]) == 100_003
    assert rebuilds() == r0
    room = cap - rows - 8
    for i in range(8, room + 9):
        idx.insert(100_000 + i, V[i % rows] + 0.01)
    assert int(idx.search(V[room] + 0.01, 1)[0]) == 100_000 + room
    assert rebuilds() - r0 == 1 and METRICS.value(by_why) - spare_r0 == 1
    assert idx._device["cap"] > rows + room + 8


def test_the_spare_room_runs_out_once_and_is_counted():
    rng, centers, V = mixture(21, 5000, 32)
    idx = built(V)
    spare0 = len(idx._ivf["spare"])
    by_why = 'vector_ivf_rebuilds_total{why="spare"}'
    r0, spare_r0 = rebuilds(), METRICS.value(by_why)
    written = []
    for round_ in range(40):
        for _ in range(64):  # all near one centre: two cells fill up
            written.append((centers[0] + 0.3 * rng.standard_normal(32))
                           .astype(np.float32))
            idx.insert(100_000 + len(written), written[-1])
        spare = len(idx._ivf["spare"]) if idx._ivf is not None else None
        idx.search(written[-1], K)
        if rebuilds() > r0:
            break
        assert idx.build_count == 1
        assert len(idx._ivf["spare"]) < spare0 or round_ == 0
    assert rebuilds() - r0 == 1 and METRICS.value(by_why) - spare_r0 == 1
    assert idx.build_count == 2 and spare == 0
    for i in rng.choice(len(written), 20, replace=False):
        assert int(idx.search(written[i], 1)[0]) == 100_001 + i


def test_tombstones_past_a_quarter_of_the_rows_rebuild_once():
    _, _, V = mixture(71, 4096, 32)
    idx = built(V)
    by_why = 'vector_ivf_rebuilds_total{why="dead"}'
    r0, dead0 = rebuilds(), METRICS.value(by_why)
    for u in range(1, 801):  # a fifth: taken in place
        idx.remove(u)
    assert int(idx.search(V[0], 1)[0]) != 1
    assert rebuilds() == r0 and idx._device["dead"] == 800
    for u in range(801, 1001):  # past a quarter of the live rows
        idx.remove(u)
    assert not set(map(int, idx.search(V[900], K))) & set(range(1, 1001))
    assert rebuilds() - r0 == 1 and METRICS.value(by_why) - dead0 == 1
    assert idx.build_count == 2 and idx._device["dead"] == 0


def test_committed_writes_reach_the_device_snapshot_in_place():
    """Through the server: a set, a re-embed, a delete by value and a
    star delete of a stored value, each committed, each seen by the
    next `similar_to` with no rebuild."""
    from dgraph_tpu.api.server import Server

    _, _, V = mixture(31, 4096, 16)
    s = Server()
    s.alter('emb: float32vector @index(hnsw(metric:"euclidean")) .')
    idx = s.vector_indexes["emb"]
    idx.ivf_threshold = 1024
    idx.bulk_load(np.arange(1, 4097, dtype=np.uint64), V)

    def similar(q):
        """The top 3, as a set: a root function's uids come back in
        uid order."""
        vec = "[" + ", ".join(repr(float(x)) for x in q) + "]"
        out = s.query('{ r(func: similar_to(emb, 3, "%s")) { uid } }' % vec)
        return {int(r["uid"], 16) for r in out["data"]["r"]}

    def commit(set_rdf="", del_rdf=""):
        s.new_txn().mutate_rdf(set_rdf=set_rdf, del_rdf=del_rdf,
                               commit_now=True)

    def literal(u, vec):
        return '<0x%x> <emb> "[%s]" .' % (u, ", ".join(map(repr, vec.tolist())))

    similar(V[0])
    r0 = rebuilds()
    new = (V[7] + 3.0).astype(np.float32)
    commit(set_rdf=literal(0x9000, new))
    assert 0x9000 in similar(new)
    moved = (V[9] + 3.0).astype(np.float32)
    commit(set_rdf=literal(10, moved))  # uid 10 held V[9]
    assert 10 in similar(moved) and 10 not in similar(V[9])
    commit(del_rdf=literal(12, V[11]))  # a loaded row: not in the store
    assert 12 not in similar(V[11])
    commit(del_rdf="<0x9000> <emb> * .")  # a stored value
    assert 0x9000 not in similar(new)
    assert rebuilds() == r0 and idx.build_count == 1


def test_a_search_sent_after_a_write_finds_it_from_any_thread(monkeypatch):
    """Eight threads each write a row and search for it at once, while
    the others' updates are in flight (each held 2 ms before it
    launches, as a device under load would hold it): a search that
    comes while another thread applies its write waits for the
    update, and none launches on an array an update donated."""
    rng, centers, V = mixture(41, 8192, 32)
    idx = built(V)
    errors, done = [], []
    launch = VectorIndex._launch_updates

    def slow_launch(self, *a):
        time.sleep(0.002)
        return launch(self, *a)

    monkeypatch.setattr(VectorIndex, "_launch_updates", slow_launch)

    def own_writes(t: int):
        r = np.random.default_rng([41, t])
        try:
            for i in range(100):
                u = 1_000_000 * (t + 1) + i
                vec = (centers[r.integers(len(centers))]
                       + r.standard_normal(32)).astype(np.float32)
                idx.insert(u, vec)
                if int(idx.search(vec, K)[0]) != u:
                    errors.append((t, i))
            done.append(t)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    written = threading.Event()
    seen = []

    def writer():
        vec = (V[3] + 2.5).astype(np.float32)
        idx.insert(777_777, vec)
        seen.append(vec)
        written.set()

    def reader():
        assert written.wait(30)
        seen.append(int(idx.search(seen[0], 1)[0]))

    threads = [threading.Thread(target=own_writes, args=(t,))
               for t in range(8)]
    threads += [threading.Thread(target=reader),
                threading.Thread(target=writer)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == [] and sorted(done) == list(range(8))
    assert seen[1] == 777_777
    assert idx.build_count == 1


def test_searches_racing_updates_never_launch_on_a_donated_buffer():
    rng, centers, V = mixture(51, 8192, 32)
    idx = built(V)
    errors = []
    stop = threading.Event()

    def searcher(t: int):
        r = np.random.default_rng([51, t])
        try:
            while not stop.is_set():
                q = V[r.integers(len(V))]
                if t % 2:
                    got = idx.search_batch(np.stack([q, q + 1.0]), K)
                    assert got.shape == (2, K)
                else:
                    assert len(idx.search(q, K)) == K
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    def writer(t: int):
        r = np.random.default_rng([52, t])
        try:
            for i in range(80):
                u = 2_000_000 * (t + 1) + i
                idx.insert(u, (V[r.integers(len(V))] + 0.5).astype(
                    np.float32))
                if i % 4 == 3:
                    idx.remove(u - 2)
                idx.search(V[r.integers(len(V))], K)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=searcher, args=(t,))
                   for t in range(4)]
        writers = [threading.Thread(target=writer, args=(t,))
                   for t in range(4)]
        for th in readers + writers:
            th.start()
        for th in writers:
            th.join(180)
        stop.set()
        for th in readers:
            th.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in readers + writers)
    assert errors == []
    assert idx.build_count == 1
