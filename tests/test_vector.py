"""Vector index tests: brute-force exactness, IVF recall, similar_to e2e.

Mirrors /root/reference/tok/hnsw/persistent_hnsw_test.go and
ef_recall_test.go intent: correctness + recall against exact scan.
"""

import numpy as np
import pytest

from dgraph_tpu.models.vector import VectorIndex


def _exact_topk(V, uids, q, k, metric="euclidean"):
    if metric == "euclidean":
        d = ((V - q[None, :]) ** 2).sum(axis=1)
    elif metric == "cosine":
        d = 1 - (V @ q) / (
            np.linalg.norm(V, axis=1) * np.linalg.norm(q) + 1e-12
        )
    else:
        d = -(V @ q)
    idx = np.argsort(d, kind="stable")[:k]
    return [int(uids[i]) for i in idx]


@pytest.mark.parametrize("metric", ["euclidean", "cosine", "dotproduct"])
def test_brute_force_exact(metric):
    rng = np.random.default_rng(0)
    n, d = 500, 32
    V = rng.standard_normal((n, d)).astype(np.float32)
    uids = np.arange(1, n + 1)
    idx = VectorIndex("emb", metric=metric)
    for u, v in zip(uids, V):
        idx.insert(int(u), v)
    q = rng.standard_normal(d).astype(np.float32)
    got = list(idx.search(q, 10))
    want = _exact_topk(V, uids, q, 10, metric)
    assert got == want


def test_insert_update_remove():
    idx = VectorIndex("emb")
    idx.insert(1, [0.0, 0.0])
    idx.insert(2, [1.0, 1.0])
    idx.insert(3, [5.0, 5.0])
    assert list(idx.search([0.1, 0.1], 2)) == [1, 2]
    idx.insert(1, [10.0, 10.0])  # update moves uid 1 away
    assert list(idx.search([0.1, 0.1], 2)) == [2, 3]
    idx.remove(2)
    assert list(idx.search([0.1, 0.1], 3)) == [3, 1]
    assert len(idx) == 2


def test_filtered_search_and_threshold():
    idx = VectorIndex("emb")
    for u in range(1, 11):
        idx.insert(u, [float(u), 0.0])
    got = list(idx.search([0.0, 0.0], 3, allowed=np.array([4, 5, 6], np.uint64)))
    assert got == [4, 5, 6]
    got = list(idx.search([0.0, 0.0], 10, distance_threshold=9.1))
    assert got == [1, 2, 3]  # squared euclidean <= 9.1


def test_search_with_uid():
    idx = VectorIndex("emb")
    for u in range(1, 6):
        idx.insert(u, [float(u), 0.0])
    assert list(idx.search_with_uid(3, 2)) == [2, 4]


def test_ivf_recall():
    rng = np.random.default_rng(1)
    n, d, k = 4000, 16, 10
    V = rng.standard_normal((n, d)).astype(np.float32)
    uids = np.arange(1, n + 1)
    idx = VectorIndex("emb", ivf_threshold=1000, nprobe=16)
    for u, v in zip(uids, V):
        idx.insert(int(u), v)
    idx._sync_device()
    assert idx._ivf is not None
    hits = total = 0
    for _ in range(20):
        q = rng.standard_normal(d).astype(np.float32)
        got = set(int(u) for u in idx.search(q, k))
        want = set(_exact_topk(V, uids, q, k))
        hits += len(got & want)
        total += k
    recall = hits / total
    assert recall >= 0.90, recall


def test_similar_to_e2e():
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(
        "embedding: float32vector @index(hnsw(metric:\"euclidean\")) .\n"
        "name: string @index(exact) ."
    )
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf="\n".join(
            [
                '<0x1> <name> "a" .',
                '<0x1> <embedding> "[1.0, 0.0]"^^<float32vector> .',
                '<0x2> <name> "b" .',
                '<0x2> <embedding> "[0.9, 0.1]"^^<float32vector> .',
                '<0x3> <name> "c" .',
                '<0x3> <embedding> "[-1.0, 0.5]"^^<float32vector> .',
            ]
        ),
        commit_now=True,
    )
    res = s.query(
        '{ v(func: similar_to(embedding, 2, "[1.0, 0.05]")) { name } }'
    )["data"]
    assert [o["name"] for o in res["v"]] == ["a", "b"]

    # by-uid form (result order is uid-ascending, ref worker/task.go:407)
    res = s.query('{ v(func: similar_to(embedding, 2, 0x3)) { name } }')[
        "data"
    ]
    assert {o["name"] for o in res["v"]} == {"b", "c"}

    # vector roundtrip in output
    res = s.query('{ v(func: uid(0x1)) { embedding } }')["data"]
    assert res["v"][0]["embedding"] == [1.0, 0.0]

    # update vector then delete entity removes from index
    t = s.new_txn()
    t.mutate_rdf(del_rdf="<0x1> <embedding> * .", commit_now=True)
    res = s.query(
        '{ v(func: similar_to(embedding, 3, "[1.0, 0.05]")) { name } }'
    )["data"]
    assert [o["name"] for o in res["v"]] == ["b", "c"]


def test_mesh_sharded_engine_search(monkeypatch):
    """DGRAPH_TPU_SHARD_VECTORS=1 routes engine vector search through the
    row-sharded mesh top-k (runs on the virtual 8-device CPU mesh —
    the distributed data plane for 1M×768-class corpora)."""
    import numpy as np

    import jax

    if len(jax.devices()) < 2:
        import pytest as _pytest

        _pytest.skip("needs multi-device mesh")
    monkeypatch.setenv("DGRAPH_TPU_SHARD_VECTORS", "1")
    from dgraph_tpu.models.vector import VectorIndex

    rng = np.random.default_rng(4)
    n, d = 3000, 32
    V = rng.standard_normal((n, d)).astype(np.float32)
    idx = VectorIndex("m", ivf_threshold=1 << 62)
    for i in range(n):
        idx.insert(i + 1, V[i])
    q = V[17] + 0.001 * rng.standard_normal(d).astype(np.float32)
    got = idx.search(q, 5)
    assert idx._device["mesh"] is not None  # actually sharded
    # exact result parity with the single-device brute force
    monkeypatch.delenv("DGRAPH_TPU_SHARD_VECTORS")
    idx2 = VectorIndex("m2", ivf_threshold=1 << 62)
    for i in range(n):
        idx2.insert(i + 1, V[i])
    want = idx2.search(q, 5)
    np.testing.assert_array_equal(got, want)
    assert got[0] == 18  # uid of the perturbed row

    # engine-level similar_to through the sharded path
    monkeypatch.setenv("DGRAPH_TPU_SHARD_VECTORS", "1")
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(
        'emb: float32vector @index(hnsw(metric:"euclidean")) .\n'
        "name: string @index(exact) ."
    )
    t = s.new_txn()
    objs = [
        {"uid": f"0x{i+1:x}", "name": f"v{i+1}", "emb": V[i].tolist()}
        for i in range(50)
    ]
    t.mutate_json(set_obj=objs, commit_now=True)
    vec_str = "[" + ", ".join(f"{x:.6f}" for x in V[7]) + "]"
    out = s.query(
        '{ q(func: similar_to(emb, 3, "%s")) { name } }' % vec_str
    )
    assert out["data"]["q"][0]["name"] == "v8"


def _small_jit_index(ivf_threshold):
    """300 rows: below the quantized engine's floor, so searches take the
    jitted device path on the CPU backend too."""
    import numpy as np

    from dgraph_tpu.models.vector import VectorIndex

    rng = np.random.default_rng(5)
    V = rng.standard_normal((300, 16)).astype(np.float32)
    idx = VectorIndex("r", ivf_threshold=ivf_threshold)
    idx.bulk_load(np.arange(1, 301, dtype=np.uint64), V)
    return idx, V


def test_device_rebuild_releases_old_snapshot_first_and_retries(monkeypatch):
    """At 1M x 768 the old and the new device copy do not fit in HBM
    together: the rebuild after an insert lets go of the old snapshot
    before it uploads, and a rebuild that dies leaves nothing half-built
    to serve — the next search builds again."""
    import numpy as np
    import pytest

    from dgraph_tpu.models.vector import VectorIndex

    idx, V = _small_jit_index(ivf_threshold=100)
    assert idx.search(V[7], 1)[0] == 8
    assert idx._device is not None and idx._ivf is not None

    import gc
    import weakref

    new = (V[7] + 5.0).astype(np.float32)
    idx.insert(999, new)
    old_corpus = weakref.ref(idx._device["vecs"])
    old_slabs = weakref.ref(idx._ivf["dev"]["flat_vecs"])
    seen_during_build = []

    def dies(self, mat):
        gc.collect()
        # not only unlinked from the index: no frame on the way here may
        # still name the old arrays, or their HBM stays allocated
        seen_during_build.append(
            (self._device, old_corpus() is None, old_slabs() is None)
        )
        raise MemoryError("RESOURCE_EXHAUSTED")

    with monkeypatch.context() as m:
        m.setattr(VectorIndex, "_train_ivf", dies)
        with pytest.raises(MemoryError):
            idx.search(new, 1)
    assert seen_during_build == [(None, True, True)]
    assert idx._device is None
    assert idx.search(new, 1)[0] == 999  # rebuilt, and the insert is in it


def test_concurrent_searches_share_one_device_rebuild():
    import time
    from concurrent.futures import ThreadPoolExecutor

    idx, V = _small_jit_index(ivf_threshold=1 << 62)
    rebuilds = []
    orig = idx._rebuild_device

    def slow_rebuild():
        rebuilds.append(1)
        time.sleep(0.05)
        orig()

    idx._rebuild_device = slow_rebuild
    with ThreadPoolExecutor(8) as pool:
        got = list(pool.map(lambda i: idx.search(V[i], 1)[0], range(8)))
    assert got == list(range(1, 9))
    assert len(rebuilds) == 1
