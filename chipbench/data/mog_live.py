"""Data maker `mog_live`: `mog`'s corpus and install (the same mixture,
the same rows from the same seed, the same bulk load and first
`similar_to`), with a plain model that GROWS under committed vector
writes (`chipbench/queries/vec_insert.py`, `vec_reembed.py`,
`vec_delete.py`), judged in commit order by `chipbench/history.py`.

The model keeps the loaded corpus (`mog.Model`) read-only and shared by
every copy the judge and the control take (`__deepcopy__` copies only
what the writes changed: each uid's written vector, and `dead`, the
uids deleted). Its exact top-k at a state is incremental: each read
is scored once against the loaded corpus (its `POOL` nearest loaded
rows, kept in `pools`, shared), and at a state only the rows written so
far are scored, the loaded rows the writes changed or deleted dropped
from the pool.

`install` first puts ONE question to the program: does its vector index
take writes in place (`vector_ivf_appended_rows_total`, METRICS.md)? A
program from before that rebuilds the whole index, ~80 s at 1M x 768,
on the first search after every write, so under this cell's ~23 writes
a second no search would finish inside the window. Such a program is
refused at once, exit code 1, the reason on stderr, no result line,
nothing built, as `snb_reads`, `snb_feed` and `snb_mixed` refuse theirs.
Then, after `mog`'s install, it commits one write of a loaded row's own
value and searches, so that the index's update programs compile in the
set-up (the model does not change).

No `captured`: a writing mix takes numbers per answer, not per tapped
program (`chipbench/run.py`, `chipbench/history.py`). So the probe's own
float32 distances are logged per query (`ProbeLog`, on the model as
`probes`), and the read kind (`chipbench/queries/similar_to_live.py`)
finds the probe that served each answer by its query vector and judges
those distances at the state the answer is judged at: the probe's
arithmetic (float32 at `highest`), and the norms and slab rows the
update programs wrote for the appended rows. `window_opens` and
`describe` report the
program's counters that moved over the window on stderr and to the
per-layer readers (`ctx["describe"]`), with the device time of the
update programs where the window was traced on a device.

No program import at module level (the load generator imports this
file for `catalog`)."""

from __future__ import annotations

import copy
import glob
import os
import sys
import tempfile
import time

import numpy as np

from chipbench.data import mog

NEEDS = "vector_ivf_appended_rows_total"
NEW_BASE = mog.UID_BASE + (1 << 24)  # past every loaded uid
HEAD = 1024  # the rows of each chunk a delete may take
POOL = 64  # loaded rows kept per read; the exact top-k is among them


def heads(config: dict, seed: int):
    """(rows, vectors) of the first HEAD rows of every chunk of the
    corpus, drawn as `mog.corpus` draws them (a chunk's generator gives
    its labels, then its rows in order, so a chunk's first rows need
    only its first draws)."""
    n, d = config["sizes"]["vectors"], config["sizes"]["dim"]
    centers = mog.centers_of(config, seed)
    rows, vecs = [], []
    for chunk in range(-(-n // mog.CHUNK)):
        off = chunk * mog.CHUNK
        size = min(mog.CHUNK, n - off)
        rng = np.random.default_rng([seed, 22, chunk])
        labels = rng.integers(0, len(centers), size)
        take = min(HEAD, size)
        block = rng.standard_normal((take, d), dtype=np.float32)
        block += centers[labels[:take]]
        rows.append(np.arange(off, off + take))
        vecs.append(block)
    return np.concatenate(rows), np.concatenate(vecs)


def catalog(config: dict, seed: int) -> dict:
    """What a client needs to write requests: the mixture's centres, the
    corpus size, and the rows a delete may take with their values."""
    rows, vecs = heads(config, seed)
    return {"centers": mog.centers_of(config, seed),
            "vectors": config["sizes"]["vectors"],
            "head_rows": rows, "head_vecs": vecs}


class ProbeLog:
    """Every search program the vector index fetched (the update
    programs are not fetched through `alpha.JitFetches`): the query or
    query batch, the float32 distances and rows it returned, and the
    snapshot's row -> uid map at the launch. Inside the window only
    references are kept: nothing is copied or waited for. A snapshot's
    rows are never reused and its map only grows, so the map read at
    the launch names the rows that launch returned."""

    def __init__(self, fetches, index):
        self.index, self.kept, self.by_query = index, [], None
        fetches.watchers.append(self.watch)

    def watch(self, label: str, key, fn):
        if not label.startswith("vector:"):
            return fn

        def run(*args):
            dev = self.index._device
            out = fn(*args)
            self.kept.append((args[-1], out,
                              None if dev is None else dev["uids"]))
            return out

        return run

    def of(self, q: np.ndarray):
        """(uids int64, float32 distances) of the rows the probe of query
        `q` returned, or None where no probe of it was logged."""
        if self.by_query is None:
            self.by_query = self._table()
        return self.by_query.get(np.asarray(q, np.float32).tobytes())

    def _table(self) -> dict:
        import jax

        kept, self.kept = self.kept, []
        host = jax.device_get([(Q, out[0], out[1]) for Q, out, _ in kept])
        table = {}
        for (Q, dd, rows), (_, _, uids) in zip(host, kept):
            if uids is None:  # a launch while the snapshot was rebuilt
                continue
            for q, d, r in zip(*(np.atleast_2d(x) for x in (Q, dd, rows))):
                ok = (r >= 0) & (r < len(uids)) & np.isfinite(d)
                table[q.astype(np.float32).tobytes()] = (
                    uids[r[ok]].astype(np.int64), d[ok])
        return table


class Model:
    """The plain reference under writes (module docstring). A uid is
    live where a write gave it a vector, or where it is a loaded one no
    write changed or deleted; a live uid's value is its written vector,
    else its loaded row. `probes`, the program's `ProbeLog`, is shared
    by every copy, as the loaded corpus is."""

    def __init__(self, base: mog.Model, probes=None):
        self.base = base
        self.probes = probes
        self.pools: dict = {}  # query bytes -> (uids, float64 distances)
        d = base.V.shape[1]
        self._vec = np.empty((64, d), np.float32)  # written vectors
        self._uid = np.empty((64,), np.int64)
        self._ok = np.empty((64,), bool)  # not since rewritten or deleted
        self._n = 0
        self._at: dict = {}  # uid -> its row in _vec
        self.dead: set = set()

    def __deepcopy__(self, memo):
        new = copy.copy(self)  # the loaded corpus and the pools: shared
        for name in ("_vec", "_uid", "_ok"):
            setattr(new, name, getattr(self, name).copy())
        new._at = dict(self._at)
        new.dead = set(self.dead)
        return new

    def put(self, uid: int, vec: np.ndarray) -> None:
        """A committed set of `uid`'s vector (an insert or a re-embed)."""
        old = self._at.get(uid)
        if old is not None:
            self._ok[old] = False
        if self._n == len(self._uid):
            for name in ("_vec", "_uid", "_ok"):
                arr = getattr(self, name)
                setattr(self, name, np.concatenate([arr, np.empty_like(arr)]))
        i = self._n
        self._vec[i], self._uid[i], self._ok[i] = vec, uid, True
        self._at[uid] = i
        self._n += 1
        self.dead.discard(uid)

    def kill(self, uid: int) -> None:
        """A committed delete of `uid`'s vector."""
        old = self._at.pop(uid, None)
        if old is not None:
            self._ok[old] = False
        self.dead.add(uid)

    def live(self, uid: int) -> bool:
        return uid in self._at or (
            uid not in self.dead
            and 0 <= uid - mog.UID_BASE < len(self.base.V))

    def values(self, uids):
        """(float64 (n, d) values of `uids` here, which of them are
        live); a uid that is not live reads as zeros."""
        uids = np.asarray(uids, np.int64)
        out = np.zeros((len(uids), self.base.V.shape[1]))
        ok = np.zeros(len(uids), bool)
        for j, u in enumerate(uids.tolist()):
            if u in self._at:
                out[j], ok[j] = self._vec[self._at[u]], True
            elif self.live(u):
                out[j], ok[j] = self.base.V[u - mog.UID_BASE], True
        return out, ok

    def dists(self, uids, q: np.ndarray) -> np.ndarray:
        """float64 distances of `uids` at their values here; a uid that
        is not live is infinitely far."""
        V, ok = self.values(uids)
        diff = V - q.astype(np.float64)
        return np.where(ok, np.einsum("ij,ij->i", diff, diff), np.inf)

    def _pools(self, Q: list) -> list:
        """Each query's POOL nearest loaded rows (uids, float64
        distances, nearest first), scored once a query and kept."""
        keys = [q.tobytes() for q in Q]
        todo = [i for i, key in enumerate(keys) if key not in self.pools]
        V, sq = self.base.V, self.base.sq
        pre = min(8 * POOL, len(V))
        for off in range(0, len(todo), 64):  # (64, n) float32 at a time
            part = todo[off:off + 64]
            q = np.stack([Q[i] for i in part])
            d32 = sq[None, :] - 2.0 * (q @ V.T)
            cand = np.argpartition(d32, pre - 1, axis=1)[:, :pre]
            for i, rows in zip(part, cand):
                d = self.base.d64(rows, Q[i])
                order = np.lexsort((rows, d))[:POOL]
                self.pools[keys[i]] = (rows[order] + mog.UID_BASE, d[order])
        return [self.pools[key] for key in keys]

    def topk(self, Q: list, k: int) -> list:
        """[(uids, float64 distances)] of the exact k nearest live rows
        to each query, here, nearest first (ties by uid)."""
        out = []
        W = self._vec[: self._n][self._ok[: self._n]].astype(np.float64)
        U = self._uid[: self._n][self._ok[: self._n]]
        changed = np.fromiter(self.dead | self._at.keys(), np.int64)
        for q, (pool_u, pool_d) in zip(Q, self._pools(Q)):
            keep = ~np.isin(pool_u, changed)
            if keep.sum() < k:  # the writes ate the pool: all loaded rows
                pool_u, pool_d = self._all_loaded(q, changed, k)
                keep = np.ones(len(pool_u), bool)
            diff = W - q.astype(np.float64)
            u = np.concatenate([pool_u[keep][:k], U])
            d = np.concatenate([pool_d[keep][:k],
                                np.einsum("ij,ij->i", diff, diff)])
            order = np.lexsort((u, d))[:k]
            out.append((u[order], d[order]))
        return out

    def _all_loaded(self, q, changed, k):
        """The k nearest loaded rows that no write changed, over the
        whole corpus (float32 picks 8k, float64 ranks them)."""
        d32 = self.base.sq - 2.0 * (self.base.V @ q)
        rows = changed - mog.UID_BASE
        d32[rows[(rows >= 0) & (rows < len(d32))]] = np.inf
        cand = np.argpartition(d32, 8 * k - 1)[: 8 * k]
        d = self.base.d64(cand, q)
        order = np.lexsort((cand, d))[:k]
        return cand[order] + mog.UID_BASE, d[order]


def make(config: dict, seed: int) -> Model:
    return Model(mog.make(config, seed))


def install(config: dict, seed: int, alpha, store_dir: str):
    """`mog.install` on `store_dir` (its probe tap, which keeps every
    8th call, replaced by a `ProbeLog`, which keeps every one), one
    write of a loaded row's own value and a search (the update programs
    compile), the model growing from there. A program whose vector
    index cannot take a write in place is refused first (`NEEDS`)."""
    from dgraph_tpu.utils import observe

    if not observe.registered_metric(NEEDS):
        raise SystemExit(
            f"chipbench: {config['name']} needs a program whose vector "
            f"index takes writes in place (it declares no metric {NEEDS}: "
            "METRICS.md); this one rebuilds the whole index, ~80 s at "
            "1M x 768, on the first search after every write, so under "
            "the cell's writes no search would finish inside the window")
    base, info = mog.install(config, seed, alpha, store_dir)
    alpha.fetches.watchers.remove(base.tap.watch)
    del base.tap
    t0 = time.perf_counter()
    engine = alpha.engine
    probes = ProbeLog(alpha.fetches, engine.vector_indexes[mog.PRED])
    engine.new_txn().mutate_rdf(
        set_rdf=literal(mog.UID_BASE, base.V[0]), commit_now=True)
    q = mog.centers_of(config, seed)[1]
    out = engine.query('{ res(func: similar_to(%s, %d, "%s")) { uid } }' % (
        mog.PRED, config["sizes"]["k"], [float(x) for x in q]))
    if len(out["data"]["res"]) != config["sizes"]["k"]:
        raise RuntimeError(f"similar_to after the first write answered {out}")
    return Model(base, probes), dict(info,
                                     first_write_s=time.perf_counter() - t0)


def literal(uid: int, vec: np.ndarray) -> str:
    """The n-quad that gives `uid` the vector `vec` (float32, every
    digit it needs to come back the same)."""
    return '<0x%x> <%s> "%s" .' % (uid, mog.PRED, [float(x) for x in vec])


def _counters() -> dict:
    from dgraph_tpu.utils.observe import METRICS

    return METRICS.snapshot()


def window_opens(model: Model) -> None:
    model.counters_at_window = _counters()
    model.window_opened = time.time()


SHOWN = ("vector_", "device_dispatch_total", "num_commits", "commit_batches")


def describe(alpha, model: Model) -> dict:
    """The program's counters and gauges that moved over the window
    (those of the vector index and the write path on stderr too), the
    index's shape, and the device time of the update programs in the
    window's trace (`apply_device`)."""
    before = getattr(model, "counters_at_window", {})
    moved = {k: v - before.get(k, 0.0) for k, v in _counters().items()
             if v != before.get(k, 0.0)}
    shown = {k: v for k, v in sorted(moved.items()) if k.startswith(SHOWN)}
    print(f"counters in the window: {shown}", file=sys.stderr, flush=True)
    out = {"counters_in_window": moved}
    ivf = alpha.engine.vector_indexes[mog.PRED]._ivf
    if ivf is not None:
        out["nlist"], out["dim"] = (int(x) for x in ivf["centroids"].shape)
    traced = apply_device(getattr(model, "window_opened", None))
    print(f"update programs in the trace: {traced}", file=sys.stderr,
          flush=True)
    out.update(traced)
    return out


def apply_device(since):
    """{"apply_device_s", "apply_programs_traced"}: the summed device
    time of the programs the `ivf.apply.launch` spans enqueued, and how
    many, in the trace `chipbench/run.py` took of this window (the newest
    `chipbench_trace_*` directory, written after `since`); programs are
    matched to launches by order, as `span_reduce.order_offset` matches
    them. Else {"apply_unmatched": why}: no such trace, no device plane,
    or a match that cannot separate them."""
    from chipbench import span_reduce, trace_reduce

    files = [f for f in glob.glob(os.path.join(
        tempfile.gettempdir(), "chipbench_trace_*", "**", "*.xplane.pb"),
        recursive=True) if since is not None and os.path.getmtime(f) >= since]
    if not files:
        return {"apply_unmatched": "no trace of this window"}
    planes = span_reduce.read_planes(max(files, key=os.path.getmtime))
    names = span_reduce.span_names(planes)
    launches = sorted(
        (ev[1], ev[0]) for pname, lines in planes
        if not trace_reduce.is_device_plane(pname)
        for _, events in lines for ev in events
        if ev[0] in names and ev[0].endswith(".launch"))
    out = {"apply_unmatched": "no device plane"}
    for pname, lines in planes:
        if not trace_reduce.is_device_plane(pname):
            continue
        modules = sorted((ev[1], ev[2]) for ev in
                         dict(lines).get(trace_reduce.MODULES_LINE, []))
        shift, _ = span_reduce.order_offset([s for s, _ in launches],
                                            [s for s, _ in modules])
        if shift is None:
            return {"apply_unmatched": f"no order: {len(launches)} launch "
                    f"spans, {len(modules)} programs"}
        mine = [modules[i + shift][1] for i, (_, name) in enumerate(launches)
                if name == "ivf.apply.launch"
                and 0 <= i + shift < len(modules)]
        out = {"apply_device_s": sum(mine) / 1e9,
               "apply_programs_traced": len(mine), "apply_shift": shift}
    return out
