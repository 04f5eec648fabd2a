"""The vector corpus: a mixture of 256 gaussians (cluster centres at 4
sigma, unit noise: real embedding sets cluster and there is no network
to fetch one), with its plain reference, exact nearest neighbours in
float64. It is the benchmark's own generator; chip_smoke.py builds a
corpus of the same kind for its smoke run and shares no code with it.

The rows are drawn in chunks of 65,536, each from a generator of its own
keyed by (seed, chunk), in float32 and in threads: the same seed gives
the same corpus whatever the thread count. Nothing of the program is
imported outside `install` and `describe`.
"""

from __future__ import annotations

import concurrent.futures
import os
import shutil
import time

import numpy as np

PRED = "emb"
UID_BASE = 0x1000000
CHUNK = 65536


def centers_of(config: dict, seed: int) -> np.ndarray:
    s = config["sizes"]
    rng = np.random.default_rng([seed, 21])
    return (rng.standard_normal((config["assumed"]["clusters"], s["dim"]))
            .astype(np.float32) * np.float32(config["assumed"]["center_sigma"]))


def catalog(config: dict, seed: int) -> dict:
    """What a client needs to write requests: the mixture's centres."""
    return {"centers": centers_of(config, seed)}


def corpus(config: dict, seed: int, labels_out=None) -> np.ndarray:
    """The rows; where `labels_out` (n,) is given, each row's cluster is
    written into it."""
    n, d = config["sizes"]["vectors"], config["sizes"]["dim"]
    centers = centers_of(config, seed)
    V = np.empty((n, d), np.float32)

    def fill(chunk: int) -> None:
        off = chunk * CHUNK
        rows = min(CHUNK, n - off)
        rng = np.random.default_rng([seed, 22, chunk])
        labels = rng.integers(0, len(centers), rows)
        block = rng.standard_normal((rows, d), dtype=np.float32)
        block += centers[labels]
        V[off:off + rows] = block
        if labels_out is not None:
            labels_out[off:off + rows] = labels

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(-(-n // CHUNK))))
    return V


class Model:
    """Plain reference: exact nearest neighbours by squared euclidean
    distance (chip_smoke.ExactTopK). float32 BLAS picks 8*k candidates
    per query, float64 direct differences rank them, so no float32
    rounding of the first pass reaches the answer. Row uids are
    contiguous from UID_BASE."""

    def __init__(self, V: np.ndarray, labels=None):
        self.V = V
        self.labels = labels  # each row's cluster, for the planted faults
        self.sq = np.einsum("ij,ij->i", V, V)

    def d64(self, rows, q) -> np.ndarray:
        diff = self.V[rows].astype(np.float64) - q.astype(np.float64)
        return np.einsum("ij,ij->i", diff, diff)

    def topk(self, Q: np.ndarray, k: int):
        """[(uids closest-first, float64 distances)] per query."""
        out = []
        pool = min(8 * k, len(self.V))
        for off in range(0, len(Q), 64):  # (64, n) float32 at a time
            q = Q[off:off + 64]
            d32 = self.sq[None, :] - 2.0 * (q @ self.V.T)
            cand = np.argpartition(d32, pool - 1, axis=1)[:, :pool]
            for qi, rows in zip(q, cand):
                d = self.d64(rows, qi)
                order = np.lexsort((rows, d))[:k]
                out.append((rows[order].astype(np.uint64) + UID_BASE,
                            d[order]))
        return out

    def dists(self, uids, q) -> np.ndarray:
        """float64 distances of `uids`, in their order, to q; a uid
        outside the corpus is infinitely far."""
        rows = np.asarray(uids, np.int64) - UID_BASE
        ok = (rows >= 0) & (rows < len(self.V))
        d = np.full(len(rows), np.inf)
        d[ok] = self.d64(rows[ok], q)
        return d


class ProbeTap:
    """Reads what the timed compiled programs produce. The served
    response carries uids only, so the probe's own float32 distances are
    tapped where the index fetches its jitted search (a watcher of
    `alpha.JitFetches`, the one wrapper of those getters): every
    `every`-th call keeps references to the query batch, the distances
    and the rows it returned. Nothing is copied or waited for inside the
    window."""

    def __init__(self, fetches, every: int = 8):
        self.every, self.calls, self.kept = every, 0, []
        self.probe_slabs = set()  # the slab counts the IVF probes ran at
        fetches.watchers.append(self.watch)

    def watch(self, label: str, key, fn):
        if not label.startswith("vector:"):
            return fn
        if label.startswith("vector:ivf"):
            self.probe_slabs.add(int(key[1]))

        def run(*args):
            out = fn(*args)
            self.calls += 1
            if self.calls % self.every == 0:
                self.kept.append((args[-1], out))
            return out

        return run

    def reset(self) -> None:
        self.kept = []
        self.probe_slabs = set()

    def captured(self) -> list:
        """[(query float32 (d,), corpus rows, float32 distances)] of the
        kept calls, one entry per query of a batch."""
        out = []
        for Q, (dd, rows) in self.kept:
            Q, dd, rows = (np.asarray(x) for x in (Q, dd, rows))
            if Q.ndim == 1:
                Q, dd, rows = Q[None], dd[None], rows[None]
            out += list(zip(Q, rows, dd))
        return out


def make(config: dict, seed: int) -> Model:
    labels = np.empty(config["sizes"]["vectors"], np.int32)
    return Model(corpus(config, seed, labels), labels)


def install(config: dict, seed: int, alpha, store_dir: str):
    """A fresh LSM store, the schema, the corpus through
    `VectorIndex.bulk_load` (there is no binary vector ingest; 1M rows
    as RDF text is ~8 GB), and the first `similar_to`, which builds the
    IVF on the host, uploads it and compiles the probe."""
    t0 = time.perf_counter()
    model = make(config, seed)
    made_s = time.perf_counter() - t0
    model.tap = ProbeTap(alpha.fetches)
    shutil.rmtree(store_dir, ignore_errors=True)  # no index is kept
    engine = alpha.open(os.path.join(store_dir, "p"))
    engine.alter(f'{PRED}: float32vector @index(hnsw(metric:"euclidean")) .')
    n = len(model.V)
    engine.vector_indexes[PRED].bulk_load(
        np.arange(UID_BASE, UID_BASE + n, dtype=np.uint64), model.V)
    q = centers_of(config, seed)[0]
    t0 = time.perf_counter()
    out = engine.query('{ res(func: similar_to(%s, %d, "%s")) { uid } }' % (
        PRED, config["sizes"]["k"], [float(x) for x in q]))
    build_s = time.perf_counter() - t0
    if len(out["data"]["res"]) != config["sizes"]["k"]:
        raise RuntimeError(f"first similar_to answered {out}")
    return model, {"corpus_s": made_s, "index_build_s": build_s}


def window_opens(model) -> None:
    model.tap.reset()


def captured(model) -> list:
    return model.tap.captured()


def describe(alpha, model) -> dict:
    """The static shape of the index's probe, for the roofline reader:
    the slabs a probe gathers are read off the jitted probe's own key
    (one width, or the reader has nothing sound to read)."""
    from dgraph_tpu.models import vector

    idx = alpha.engine.vector_indexes[PRED]
    ivf = idx._ivf
    if ivf is None or len(model.tap.probe_slabs) != 1:
        return {}
    return {"probe_rows": min(model.tap.probe_slabs) * vector._SLAB,
            "n_slabs": int(ivf["n_slabs"]), "dim": int(idx.dim),
            "nlist": int(len(ivf["centroids"]))}
