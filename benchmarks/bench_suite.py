"""Extended benchmark suite: the BASELINE.md north-star configs.

Measures (sized by --scale to fit the machine):
  1. 2-hop friends-of-friends traversal through the full engine
     (BASELINE.md: systest/1million 2-hop, metric = edges/sec)
  2. vector top-k QPS (BASELINE.md: 1M x 768 f32 top-10; scaled variant
     on small machines), brute-force exact + IVF@recall
  3. batched intersect throughput (algo/benchmarks shapes)

Usage: python benchmarks/bench_suite.py [--scale small|full] [--json out]
Prints one JSON object with all results (bench.py stays the single-line
driver contract; this is the detailed harness).
"""

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:  # `python benchmarks/x.py` puts only benchmarks/ there
    _sys.path.insert(0, _REPO)

import dgraph_tpu  # noqa: E402,F401 — places the compile cache before jax loads

import argparse
import json
import sys
import time

import numpy as np


def bench_2hop(scale: str) -> dict:
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.loaders.bulk import BulkLoader
    from dgraph_tpu.loaders.rdf import NQuad

    n_users = 20_000 if scale == "small" else 200_000
    deg = 20
    rng = np.random.default_rng(0)

    s = Server()
    s.alter("name: string @index(exact) .\nfriend: [uid] @reverse @count .")
    loader = BulkLoader(s)
    t0 = time.time()
    for u in range(1, n_users + 1):
        loader.add_nquad(NQuad(subject=hex(u), predicate="name",
                               object_value=_val(f"user{u}")))
        for v in rng.integers(1, n_users + 1, deg):
            if int(v) != u:
                loader.add_nquad(
                    NQuad(subject=hex(u), predicate="friend",
                          object_id=hex(int(v)))
                )
    loader.finish()
    load_s = time.time() - t0

    # 2-hop expansion from a batch of roots; count traversed edges
    roots = rng.integers(1, n_users + 1, 64)
    t0 = time.time()
    edges = 0
    for r in roots:
        res = s.query(
            "{ q(func: uid(%s)) { friend { friend { uid } } } }" % hex(int(r))
        )["data"]
        for f1 in res["q"][0].get("friend", []):
            edges += 1 + len(f1.get("friend", []))
    dt = time.time() - t0
    return {
        "n_users": n_users,
        "avg_degree": deg,
        "load_seconds": round(load_s, 2),
        "queries": len(roots),
        "edges_traversed": edges,
        "edges_per_sec": round(edges / dt, 1),
        "latency_ms_per_query": round(dt / len(roots) * 1e3, 2),
    }


def bench_vector(scale: str) -> dict:
    """Vector QPS, measured the way ANN benches are: a query batch per
    dispatch (search_batch — one device round trip per 64 queries) plus
    an honest single-query latency. recall@10 for IVF is computed against
    the brute tier's exact results over ALL timed queries."""
    import gc

    import jax

    from dgraph_tpu.models.vector import VectorIndex

    n, d = (100_000, 256) if scale == "small" else (1_000_000, 768)
    k = 10
    qb, nq = 64, 256
    rng = np.random.default_rng(1)
    # mixture-of-gaussians corpus: real embedding sets cluster; pure
    # isotropic gaussian is IVF's pathological worst case (distance
    # concentration) and misrepresents production recall
    n_clusters = 256
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32) * 4.0
    assign = rng.integers(0, n_clusters, n)
    V = centers[assign] + rng.standard_normal((n, d)).astype(np.float32)
    Qs = (
        centers[rng.integers(0, n_clusters, nq)]
        + rng.standard_normal((nq, d))
    ).astype(np.float32)

    uids = np.arange(1, n + 1, dtype=np.uint64)

    idx = VectorIndex("emb", ivf_threshold=1 << 62)  # brute force tier
    idx.bulk_load(uids, V)

    idx.search_batch(Qs[:qb], k)  # compile + upload
    t0 = time.time()
    exact = [idx.search_batch(Qs[i : i + qb], k) for i in range(0, nq, qb)]
    brute_qps = nq / (time.time() - t0)
    exact = np.concatenate(exact, axis=0)

    idx.search(Qs[0], k)  # warm the single-query jit before timing
    t0 = time.time()
    for q in Qs[:10]:
        idx.search(q, k)
    brute_ms_single = (time.time() - t0) / 10 * 1e3

    # free the brute tier's device arrays before the IVF build: at
    # 1Mx768 both tiers together would not fit a 16GB chip
    idx._device = None
    del idx
    gc.collect()

    idx2 = VectorIndex("emb2", ivf_threshold=1)  # auto nprobe
    idx2.bulk_load(uids, V)
    t0 = time.time()
    if idx2._use_quant():
        idx2._quant_view()  # quantize + centroid train + cell assignment
    else:
        idx2._sync_device()  # corpus device upload + slab IVF train
    ivf_sync_build_s = time.time() - t0

    idx2.search_batch(Qs[:qb], k)  # compile
    t0 = time.time()
    got = [idx2.search_batch(Qs[i : i + qb], k) for i in range(0, nq, qb)]
    ivf_qps = nq / (time.time() - t0)
    got = np.concatenate(got, axis=0)

    idx2.search(Qs[0], k)  # warm the single-query jit before timing
    t0 = time.time()
    for q in Qs[:10]:
        idx2.search(q, k)
    ivf_ms_single = (time.time() - t0) / 10 * 1e3

    hits = sum(
        len(set(map(int, got[i])) & set(map(int, exact[i])))
        for i in range(nq)
    )
    return {
        "n_vectors": n,
        "dim": d,
        "query_batch": qb,
        "brute_force_qps": round(brute_qps, 1),
        "brute_latency_ms_single": round(brute_ms_single, 2),
        "ivf_qps": round(ivf_qps, 1),
        "ivf_latency_ms_single": round(ivf_ms_single, 2),
        "ivf_sync_build_seconds": round(ivf_sync_build_s, 1),
        "ivf_recall_at_10": round(hits / (nq * k), 3),
        "device": str(jax.devices()[0]),
    }


def bench_intersect() -> dict:
    import jax
    import jax.numpy as jnp

    from dgraph_tpu.ops import setops

    rng = np.random.default_rng(0)
    big = np.unique(rng.integers(0, 1 << 31, 1_200_000, dtype=np.uint64)).astype(
        np.uint32
    )[: 1 << 20]
    out = {}
    for batch, small_n in ((256, 10), (64, 1000)):
        A = np.full((batch, max(16, 1 << (small_n - 1).bit_length())), 0xFFFFFFFF, np.uint32)
        LA = np.zeros((batch,), np.int32)
        for i in range(batch):
            a = np.sort(rng.choice(big, small_n, replace=False))
            A[i, : len(a)] = a
            LA[i] = len(a)
        fn = jax.jit(jax.vmap(setops.intersect, in_axes=(0, 0, None, None)))
        # device arrays made ONCE: re-converting per call uploads the
        # operands every iteration and measures transfer, not the kernel
        Ad, LAd = jnp.asarray(A), jnp.asarray(LA)
        Bd, LBd = jnp.asarray(big), np.int32(big.size)
        r = fn(Ad, LAd, Bd, LBd)
        jax.block_until_ready(r)
        t0 = time.time()
        for _ in range(5):
            r = fn(Ad, LAd, Bd, LBd)
            jax.block_until_ready(r)
        dt = (time.time() - t0) / 5
        out[f"batch{batch}_{small_n}v1M_ns_per_op"] = round(dt / batch * 1e9, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", choices=["small", "full"], default="small")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    results = {}
    for name, fn in (
        ("two_hop", lambda: bench_2hop(args.scale)),
        ("vector", lambda: bench_vector(args.scale)),
        ("intersect", bench_intersect),
    ):
        print(f"running {name}...", file=sys.stderr)
        t0 = time.time()
        results[name] = fn()
        print(f"  {name} done in {time.time()-t0:.1f}s", file=sys.stderr)

    blob = json.dumps(results, indent=2)
    print(blob)
    if args.json:
        with open(args.json, "w") as f:
            f.write(blob)


def _val(s):
    from dgraph_tpu.types.types import TypeID, Val

    return Val(TypeID.STRING, s)


if __name__ == "__main__":
    main()
