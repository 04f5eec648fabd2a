#!/usr/bin/env python3
"""chip_smoke.py: the served query path, end to end, on one TPU chip.

One process owns the chip and makes the calls `dgraph-tpu bulk` and
`dgraph-tpu alpha` make (dgraph_tpu/cli.py): a `Server` on the durable
configuration (`--storage backend=lsm`, a data dir outside the
checkout), `ParallelBulkLoader`, `HTTPServer(engine).start()`, and then
drives it over the socket with the bundled `DgraphClient`. Default
knobs; all data from `--seed`.

Legs (each answer is checked against a plain model, never only for "no
errors"):

  graph    LDBC-SNB-shaped corpus (benchmarks/ldbc_corpus.py), >= 100k
           persons: friends-of-friends from 1, 64 and 1,024 roots, a
           3-hop with a string filter, an OR filter — against the
           corpus model.
  vector   BASELINE.json config 4: 1,000,000 x 768 float32, top-10, the
           mixture-of-gaussians corpus below. Served DQL
           `similar_to`, single and 64 concurrent; exact float32/64
           numpy on the same rows is the reference.
  write    after the first device query: a committed mutation (knows
           edges; later knows edges + one vector) read back over HTTP,
           then visible to FoF and `similar_to`. The first commit forks
           the apply-shard workers from the process that holds the chip.
  kernels  every jitted set-op family called through `SetOpDispatcher`
           above the device threshold — each against numpy.

It fails (exit code != 0, no "ok" line) when jax finds no accelerator,
when any check fails, or when no graph query or not every vector query
was seen to dispatch to the device. `--rehearsal` runs the same code at
a tiny size on whatever platform jax has (the CPU tests use it); only a
run without it counts.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

T0 = time.perf_counter()
FULL_PERSONS, FULL_VECTORS, DIM, TOPK = 100_000, 1_000_000, 768, 10


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


class Checks:
    """Named pass/fail results; the run is ok only if all passed."""

    def __init__(self):
        self.results = collections.OrderedDict()

    def add(self, name: str, ok: bool, detail: str = "") -> None:
        self.results[name] = {"ok": bool(ok), "detail": detail}
        say(f"{'PASS' if ok else 'FAIL'} {name} {detail}")

    def failed(self):
        return [k for k, v in self.results.items() if not v["ok"]]


class CompileClock:
    """XLA compilations and persistent-cache traffic, from jax's own
    monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif event in (
            "/jax/core/compile/jaxpr_trace_duration",
            "/jax/core/compile/jaxpr_to_mlir_module_duration",
        ):
            self.trace_lower_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snap(self) -> dict:
        return {
            "compiles": self.compiles,
            "compile_s": self.compile_s + self.trace_lower_s,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {
            k: round(b[k] - a[k], 2) if k.endswith("_s") else b[k] - a[k]
            for k in a
        }


class JitFetches:
    """Per-query device evidence: every fetch of a jitted function from
    the dispatcher's or the vector index's jit cache is followed by one
    execution on jax's default device, so the fetches between two marks
    are the device dispatches of the query in between."""

    def __init__(self):
        from dgraph_tpu.models import vector
        from dgraph_tpu.query.dispatch import DISPATCHER

        self.log: list = []
        for name, suffix in (
            ("_get_jitted", ""),
            ("_get_jitted_shared", "#shared"),
            ("_get_jitted_chain", "#chain"),
        ):
            setattr(
                DISPATCHER, name,
                self._wrap(getattr(DISPATCHER, name), "setop:%s" + suffix),
            )
        for name in ("_jit_brute", "_jit_brute_batch", "_jit_ivf",
                     "_jit_ivf_batch"):
            setattr(
                vector, name,
                self._wrap(getattr(vector, name), "vector:" + name[5:]),
            )

    def _wrap(self, orig, label: str):
        def fetch(*a):
            self.log.append(label % a[0] if "%s" in label else label)
            return orig(*a)

        return fetch

    def mark(self) -> int:
        return len(self.log)

    def since(self, mark: int) -> dict:
        return dict(collections.Counter(self.log[mark:]))


def versions() -> dict:
    import jax
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "numpy": np.__version__,
           "python": sys.version.split()[0]}
    try:
        import libtpu

        out["libtpu"] = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        out["libtpu"] = None
    return out


def hbm(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


# ---------------------------------------------------------------------------
# graph leg
# ---------------------------------------------------------------------------

_FIRST6 = ["Karl", "Jose", "Rudolf", "Wei", "Maria", "Ivan"]


class Graph:
    """Queries over the LDBC-shaped corpus, each with its model answer."""

    def __init__(self, corpus, seed: int):
        self.c = corpus
        rng = np.random.default_rng(seed + 1)
        persons = list(corpus.persons)
        pick = rng.choice(len(persons), min(1024, len(persons)),
                          replace=False)
        self.roots = [persons[int(i)] for i in pick]

    def sid(self, uid: int) -> int:
        return self.c.persons[uid].sid

    def _fqids(self, uids) -> str:
        return ", ".join(f'"person_{self.sid(u)}"' for u in uids)

    def friends(self, uid: int) -> set:
        return {f for f, _ in self.c.knows_of(uid)}

    def fof(self, roots):
        """(query, model ids) for friends-of-friends of `roots` in ONE
        block: fof = (union of friends' knows) - roots - friends."""
        q = (
            f"{{ me as var(func: eq(fqid, [{self._fqids(roots)}])) "
            "{ f as knows } "
            "q(func: uid(f)) { fof as knows "
            "@filter(NOT uid(me) AND NOT uid(f)) } "
            "res(func: uid(fof)) { id } }"
        )
        direct = set().union(*(self.friends(r) for r in roots))
        want = set().union(*(self.friends(f) for f in direct))
        want -= set(roots) | direct
        return q, sorted(self.sid(u) for u in want)

    def three_hop(self, roots, first: str):
        q = (
            f"{{ var(func: eq(fqid, [{self._fqids(roots)}])) "
            "{ knows { knows { h3 as knows "
            f'@filter(eq(firstName, "{first}")) }} }} }} '
            "res(func: uid(h3)) { id } }"
        )
        hop = set(roots)
        for _ in range(3):
            hop = set().union(*(self.friends(u) for u in hop))
        want = [u for u in hop if self.c.persons[u].first == first]
        return q, sorted(self.sid(u) for u in want)

    def or_filter(self):
        terms = " OR ".join(f'eq(firstName, "{n}")' for n in _FIRST6)
        q = f"{{ res(func: type(person)) @filter({terms}) {{ id }} }}"
        want = [p.sid for p in self.c.persons.values() if p.first in _FIRST6]
        return q, sorted(want)


def run_graph_query(name, client, q, want, fetches, clock, checks, report):
    mark, c0, t0 = fetches.mark(), clock.snap(), time.perf_counter()
    out = client.query(q)
    wall = time.perf_counter() - t0
    got = sorted(r["id"] for r in out.get("data", {}).get("res", []))
    disp = fetches.since(mark)
    comp = CompileClock.delta(c0, clock.snap())
    report["queries"][name] = {
        "results": len(got),
        "seconds": round(wall, 3),
        "compile_s": comp["compile_s"],
        "compiles": comp["compiles"],
        "device_dispatches": disp,
        "ran_on": "device" if disp else "host",
    }
    checks.add(
        f"graph:{name}",
        "errors" not in out and got == want,
        f"{len(got)} ids (model {len(want)}), {wall:.2f}s, "
        f"{'device ' + json.dumps(disp) if disp else 'host kernels'}",
    )
    return bool(disp)


# ---------------------------------------------------------------------------
# vector leg
# ---------------------------------------------------------------------------


def mixture_of_gaussians(n, d, nq, seed, n_clusters=256):
    """Cluster centers at 4 sigma, unit noise (real embedding sets
    cluster). The noise is drawn in row chunks: the same stream as one
    (n, d) draw without its 6 GB float64 copy."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_clusters, d)).astype(np.float32) * 4.0
    V = centers[rng.integers(0, n_clusters, n)]
    for off in range(0, n, 65536):
        rows = min(65536, n - off)
        V[off:off + rows] += rng.standard_normal((rows, d)).astype(np.float32)
    Q = (centers[rng.integers(0, n_clusters, nq)]
         + rng.standard_normal((nq, d))).astype(np.float32)
    return V, Q


class ExactTopK:
    """Plain reference: exact nearest neighbours by squared euclidean
    distance. float32 BLAS picks 8*k candidates per query, float64
    direct differences rank them, so no float32 rounding of the first
    pass reaches the answer. Row uids are contiguous from uids[0]."""

    def __init__(self, V: np.ndarray, uids: np.ndarray):
        self.V, self.uids = V, uids
        self.sq = np.einsum("ij,ij->i", V, V)

    def _d64(self, rows, q):
        diff = self.V[rows].astype(np.float64) - q.astype(np.float64)
        return np.einsum("ij,ij->i", diff, diff)

    def topk(self, Q: np.ndarray, k: int, extra=None):
        """[(uids closest-first, float64 dists)] per query; `extra` is
        an optional (uid, vec) appended to the corpus."""
        d32 = self.sq[None, :] - 2.0 * (Q @ self.V.T)
        pool = min(8 * k, len(self.uids))
        cand = np.argpartition(d32, pool - 1, axis=1)[:, :pool]
        out = []
        for q, rows in zip(Q, cand):
            d = self._d64(rows, q)
            ids = self.uids[rows]
            if extra is not None:
                ev = extra[1].astype(np.float64) - q.astype(np.float64)
                d = np.append(d, ev @ ev)
                ids = np.append(ids, np.uint64(extra[0]))
            order = np.lexsort((ids, d))[:k]
            out.append((ids[order], d[order]))
        return out

    def dists(self, uids, q, extra=None):
        """float64 distances of `uids` (any order) to q, ascending; a
        uid outside the corpus is infinitely far."""
        base, n = int(self.uids[0]), len(self.uids)
        q64 = q.astype(np.float64)
        d = []
        for u in map(int, uids):
            if extra is not None and u == int(extra[0]):
                v = extra[1]
            elif base <= u < base + n:
                v = self.V[u - base]
            else:
                d.append(np.inf)
                continue
            diff = v.astype(np.float64) - q64
            d.append(float(diff @ diff))
        return np.sort(np.asarray(d))


def dist_tol(ref: "ExactTopK", q: np.ndarray) -> float:
    """Largest float32 rounding error of |v|^2 - 2 v.q + |q|^2 at this
    scale (a few ulps of its biggest term). Neighbour gaps in this
    corpus are about a hundred times wider and one bf16 matmul pass errs
    about a thousand times more, so an answer within it is an
    exact-float32 answer."""
    scale = float(ref.sq.max()) + float(q @ q)
    return 16 * float(np.finfo(np.float32).eps) * scale


def score_answer(ref, got_uids, q, want_uids, want_d, extra=None):
    """(recall@k, exact) of one answer against the reference. `exact`:
    same uid set, or — where float32 rounding can order a near-tie
    either way — the same distances within dist_tol."""
    k = len(want_uids)
    recall = len(set(map(int, got_uids)) & set(map(int, want_uids))) / k
    if set(map(int, got_uids)) == set(map(int, want_uids)):
        return recall, True
    if len(got_uids) != k:
        return recall, False
    got_d = ref.dists(got_uids, q, extra)
    return recall, bool(np.all(np.abs(got_d - want_d) <= dist_tol(ref, q)))


def similar_query(pred: str, k: int, q: np.ndarray) -> str:
    vec = json.dumps([float(x) for x in q])
    return '{ res(func: similar_to(%s, %d, "%s")) { uid } }' % (pred, k, vec)


def array_platforms(idx) -> list:
    """Platforms holding the index's big device arrays. A function of
    its own so that no reference to them outlives the call: the index
    releases its arrays before a rebuild, and a caller that still held
    them would keep ~10 GB of HBM pinned."""
    dev = idx._device or {}
    slabs = (dev.get("ivf") or {}).get("dev") or {}
    arrays = [a for a in (dev.get("vecs"), slabs.get("flat_vecs"))
              if a is not None]
    return sorted({d.platform for a in arrays for d in a.devices()})


def vector_tier(disp: dict) -> str:
    tiers = sorted(k.split(":", 1)[1] for k in disp if k.startswith("vector:"))
    return "+".join(tiers) if tiers else "none"


# ---------------------------------------------------------------------------
# kernel families
# ---------------------------------------------------------------------------


def kernel_families(checks, fetches, clock, report, seed, on_tpu):
    """Each jitted set-op family through SetOpDispatcher with operands
    just above the accelerator threshold (1<<15), against numpy."""
    from dgraph_tpu.query.dispatch import DISPATCHER as d

    rng = np.random.default_rng(seed + 3)

    def sset(n):
        return np.unique(rng.integers(1, 1 << 31, n + n // 4,
                                      dtype=np.uint64))[:n]

    ref = {"intersect": np.intersect1d, "difference": np.setdiff1d,
           "union": np.union1d}
    fams = report["kernel_families"] = {}

    def family(name, want_key, fn):
        mark, c0, t0 = fetches.mark(), clock.snap(), time.perf_counter()
        ok = fn()
        disp = fetches.since(mark)
        comp = CompileClock.delta(c0, clock.snap())
        fams[name] = {"seconds": round(time.perf_counter() - t0, 2),
                      "compile_s": comp["compile_s"],
                      "device_dispatches": disp}
        on_dev = any(k == "setop:" + want_key for k in disp)
        checks.add(f"kernel:{name}", ok and (on_dev or not on_tpu),
                   f"matches numpy={ok}, dispatches={json.dumps(disp)}")

    for op in ("intersect", "difference", "union"):
        def pairs(op=op):
            ps = []
            for _ in range(2):
                a, b = sset(9000), sset(9000)
                ps.append((a, np.unique(np.concatenate([b, a[::3]]))))
            got = d.run_pairs(op, ps)
            return all(np.array_equal(g, ref[op](a, b))
                       for g, (a, b) in zip(got, ps))

        family(op, op, pairs)

    big = sset(40_000)
    for op in ("intersect", "difference"):
        def shared(op=op):
            rows = [np.unique(np.concatenate([rng.choice(big, 60), sset(40)]))
                    for _ in range(256)]
            got = d.run_rows_vs_one(op, rows, big)
            return all(np.array_equal(g, ref[op](r, big))
                       for g, r in zip(got, rows))

        family(op + "#shared", op + "#shared", shared)

    def chain_intersect():
        base = sset(20_000)
        parts = [np.unique(np.concatenate([base[::2], sset(4000)]))
                 for _ in range(3)]
        want = parts[0]
        for p in parts[1:]:
            want = np.intersect1d(want, p)
        return np.array_equal(d.run_chain("intersect", parts), want)

    family("intersect#chain", "intersect#chain", chain_intersect)

    def chain_union():
        parts = [sset(12_000) for _ in range(3)]
        return np.array_equal(d.run_chain("union", parts),
                              np.unique(np.concatenate(parts)))

    family("union#chain", "union#chain", chain_union)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--persons", type=int, default=FULL_PERSONS)
    ap.add_argument("--vectors", type=int, default=FULL_VECTORS)
    ap.add_argument("--dim", type=int, default=DIM)
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="run on whatever platform jax has (tiny sizes on the CPU); "
        "never a chip result",
    )
    args = ap.parse_args(argv)

    # the device first, before any data is built. dgraph_tpu before jax:
    # the package places the persistent compile cache
    import dgraph_tpu  # noqa: F401
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearsal:
        print(f"chip_smoke: no TPU — jax reports {device}", file=sys.stderr)
        return 2

    from dgraph_tpu import native
    from dgraph_tpu.x import config

    native.require()
    pinned = [k for k in ("FORCE_DEVICE", "DEVICE_MIN_TOTAL")
              if config.is_set(k)]
    if pinned and not args.rehearsal:
        print(f"chip_smoke: runs at default knobs, but {pinned} are set",
              file=sys.stderr)
        return 2

    def cache_entries() -> int:
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_dir = jax.config.jax_compilation_cache_dir or ""
    reduced = [] if args.rehearsal else [
        f"{what}: {got} < {full} (command line)"
        for what, got, full in (("persons", args.persons, FULL_PERSONS),
                                ("vectors", args.vectors, FULL_VECTORS),
                                ("dim", args.dim, DIM))
        if got < full
    ]
    report = {
        "device": device,
        "versions": versions(),
        "native": native.NATIVE_AVAILABLE,
        "rehearsal": args.rehearsal,
        "seed": args.seed,
        "knobs_pinned": pinned,
        "compile_cache": {"dir": cache_dir, "entries_before": cache_entries()},
        "reduced": reduced,
        "legs": {},
        "queries": {},
    }
    say(f"device {device}  versions {report['versions']}  native=True")
    say(f"compile cache {cache_dir} "
        f"({report['compile_cache']['entries_before']} entries)")

    checks = Checks()
    clock = CompileClock()
    with contextlib.ExitStack() as stack:
        serve(args, stack, checks, clock, JitFetches(), report, on_tpu)

    report["hbm"] = hbm(devs[0])
    report["compile_cache"]["entries_after"] = cache_entries()
    report["compile_total"] = {k: round(v, 2)
                               for k, v in clock.snap().items()}
    report["seconds_total"] = round(time.perf_counter() - T0, 1)
    report["checks"] = checks.results
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke_report.jsonl"), "a") as f:
        f.write(json.dumps(report) + "\n")
    print(json.dumps(report, indent=1))
    failed = checks.failed()
    if failed:
        print(json.dumps({"ok": False, "failed": failed}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


def serve(args, stack, checks, clock, fetches, report, on_tpu) -> None:
    """Load, serve and drive. Everything started here is registered on
    `stack`, which stops it whether or not a leg raises."""
    import jax

    from benchmarks.ldbc_corpus import SCHEMA, generate
    from dgraph_tpu import cli
    from dgraph_tpu.api.http_server import HTTPServer
    from dgraph_tpu.client import DgraphClient
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader
    from dgraph_tpu.query.dispatch import DISPATCHER
    from dgraph_tpu.utils.observe import METRICS
    from dgraph_tpu.worker import applyshard
    from dgraph_tpu.x import device as xdevice

    dev0 = jax.devices()[0]
    legs = report["legs"]
    data_dir = tempfile.mkdtemp(prefix="chip_smoke_")  # outside the checkout
    stack.callback(shutil.rmtree, data_dir, ignore_errors=True)

    # -- bulk: what `dgraph-tpu bulk --storage backend=lsm` does -------------
    t0 = time.perf_counter()
    corpus, rdf = generate(
        n_persons=args.persons, n_posts=args.persons // 4,
        n_comments=args.persons // 4, seed=args.seed,
    )
    rdf_path = os.path.join(data_dir, "ldbc.rdf")
    with open(rdf_path, "w") as f:
        f.write("\n".join(rdf))
    n_rdf = len(rdf)
    del rdf
    gen_s = time.perf_counter() - t0
    engine = cli._server(argparse.Namespace(
        p=os.path.join(data_dir, "p"), storage="backend=lsm",
        encryption_key_file=None,
    ))
    stack.callback(engine.kv.close)
    stack.callback(applyshard.shutdown)  # the commit path's worker processes
    engine.alter(SCHEMA)
    t0 = time.perf_counter()
    loader = ParallelBulkLoader(engine)
    loader.load_files([rdf_path])
    engine.kv.sync()
    load_s = time.perf_counter() - t0
    legs["bulk"] = {
        "persons": args.persons, "knows_edges": 2 * len(corpus.knows),
        "nquads": loader.nquads, "storage": type(engine.kv).__name__,
        "generate_s": round(gen_s, 1), "load_s": round(load_s, 1),
    }
    checks.add("bulk:loaded", loader.nquads == n_rdf == corpus.n_edges,
               f"{loader.nquads} nquads in {load_s:.1f}s "
               f"({type(engine.kv).__name__})")

    # -- alpha: what `dgraph-tpu alpha` does ---------------------------------
    say(f"alpha {xdevice.describe()}")
    srv = HTTPServer(engine, host="127.0.0.1", port=0).start()
    stack.callback(srv.stop)
    url = f"http://127.0.0.1:{srv.port}"
    client = DgraphClient(url, timeout=900.0)
    health = {k: client.health()[0].get(k)
              for k in ("platform", "device_kind", "device_count")}
    checks.add(
        "alpha:health_names_device",
        health == {"platform": report["device"]["platform"],
                   "device_kind": report["device"]["kind"],
                   "device_count": report["device"]["count"]},
        json.dumps(health),
    )

    # -- graph leg -------------------------------------------------------------
    g = Graph(corpus, args.seed)
    c0, t0 = clock.snap(), time.perf_counter()
    plan = [
        ("fof_1_root",) + g.fof(g.roots[:1]),
        ("fof_64_roots",) + g.fof(g.roots[:64]),
        ("fof_1024_roots",) + g.fof(g.roots),
        ("three_hop_string_filter_64_roots",)
        + g.three_hop(g.roots[:64], "Karl"),
        ("or_filter_union",) + g.or_filter(),
    ]
    graph_on_device = [
        run_graph_query(name, client, q, want, fetches, clock, checks, report)
        for name, q, want in plan
    ]
    legs["graph"] = dict(
        CompileClock.delta(c0, clock.snap()),
        seconds=round(time.perf_counter() - t0, 1),
        queries_on_device=sum(graph_on_device),
        hbm=hbm(dev0),
    )
    checks.add("graph:device_evidence", any(graph_on_device) or not on_tpu,
               f"{sum(graph_on_device)} of {len(plan)} queries dispatched "
               "to the device")

    # -- write then read -------------------------------------------------------
    root = g.roots[0]
    strangers = (u for u in g.roots[1:]
                 if u not in g.friends(root)
                 and g.friends(u) - g.friends(root) - {root})

    def add_friend(tag, vector=None):
        """Commit knows edges root <-> a stranger (and optionally one
        (pred, uid, vec) vector) over HTTP, read them back, and re-run
        FoF(root) against the updated model. Returns (ack time, commit
        seconds, apply-shard workers alive)."""
        stranger = next(strangers)
        before = g.fof([root])[1]
        rdf = [f"<0x{root:x}> <knows> <0x{stranger:x}> .",
               f"<0x{stranger:x}> <knows> <0x{root:x}> ."]
        read = (f"e(func: uid(0x{root:x})) {{ knows "
                f"@filter(uid(0x{stranger:x})) {{ id }} }}")
        if vector is not None:
            vpred, vuid, vec = vector
            lit = json.dumps([float(x) for x in vec])
            rdf.append(f'<0x{vuid:x}> <{vpred}> "{lit}"^^<float32vector> .')
            read += f" v(func: uid(0x{vuid:x})) {{ {vpred} }}"
        t0 = time.perf_counter()
        client.txn().mutate(set_rdf="\n".join(rdf), commit_now=True)
        t_ack = time.perf_counter()
        workers = 0 if applyshard._POOL is None else applyshard._POOL.nprocs
        out = client.query("{ %s }" % read)["data"]
        edge = [k["id"] for e in out["e"] for k in e.get("knows", [])]
        ok = edge == [g.sid(stranger)]
        detail = f"edge -> {edge}"
        if vector is not None:
            back = (out["v"] or [{}])[0].get(vpred)
            same = back is not None and np.array_equal(
                np.asarray(back, np.float32), vec)
            ok, detail = ok and same, detail + f", vector equal={same}"
        checks.add(
            f"{tag}:read_back", ok,
            f"{detail}; commit {t_ack - t0:.2f}s, {workers} apply-shard "
            "worker processes alive (forked after device init)")
        corpus.knows[(min(root, stranger), max(root, stranger))] = 0
        object.__setattr__(corpus, "_adj", None)  # the model's cache
        q, after = g.fof([root])
        run_graph_query(f"fof_1_root_after_{tag}", client, q, after, fetches,
                        clock, checks, report)
        checks.add(f"{tag}:fof_answer_changed", after != before,
                   f"{len(before)} -> {len(after)} ids in the model")
        return t_ack, t_ack - t0, workers

    # before any vector index exists the commit takes the columnar path,
    # whose apply-shard workers fork from this process, TPU runtime live
    c0 = clock.snap()
    _, commit_s, workers = add_friend("write")
    legs["write"] = dict(CompileClock.delta(c0, clock.snap()),
                         commit_s=round(commit_s, 2),
                         apply_shard_workers=workers)

    # -- vector leg ------------------------------------------------------------
    pred = "emb"
    c0, t0 = clock.snap(), time.perf_counter()
    V, Q = mixture_of_gaussians(args.vectors, args.dim, 68, args.seed)
    vbase = 0x1000000
    vuids = np.arange(vbase, vbase + args.vectors, dtype=np.uint64)
    client.alter(f'{pred}: float32vector @index(hnsw(metric:"euclidean")) .')
    idx = engine.vector_indexes[pred]
    idx.bulk_load(vuids, V)
    ref = ExactTopK(V, vuids)
    want = ref.topk(Q, TOPK)
    setup_s = time.perf_counter() - t0
    say(f"vector corpus {V.shape} bulk_load'ed, reference top-{TOPK} ready "
        f"({setup_s:.1f}s)")

    def similar(q, wanted, extra=None):
        """One served similar_to from a client of its own -> (uids,
        (recall, exact))."""
        out = DgraphClient(url, timeout=900.0).query(
            similar_query(pred, TOPK, q))
        got = [int(r["uid"], 16) for r in out["data"]["res"]]
        return got, score_answer(ref, got, q, wanted[0], wanted[1], extra)

    served = report["vector_queries"] = []
    vec_on_device = []
    for qi in range(4):
        mark, tq = fetches.mark(), time.perf_counter()
        _, score = similar(Q[qi], want[qi])
        tier = vector_tier(fetches.since(mark))
        served.append({"query": f"single_{qi}", "tier": tier,
                       "recall": score[0], "exact": score[1],
                       "seconds": round(time.perf_counter() - tq, 3)})
        vec_on_device.append(tier != "none")
        if qi == 0:
            say(f"first similar_to (index build + upload + compile) "
                f"{served[0]['seconds']}s; hbm {hbm(dev0)}")
    mark, tq = fetches.mark(), time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(64) as pool:
        futures = [pool.submit(similar, Q[qi], want[qi])
                   for qi in range(4, 68)]
        burst = [f.result() for f in futures]
    burst_s = time.perf_counter() - tq
    burst_disp = fetches.since(mark)
    for qi, (_, score) in zip(range(4, 68), burst):
        served.append({"query": f"burst_{qi}",
                       "tier": vector_tier(burst_disp),
                       "recall": score[0], "exact": score[1]})
    # concurrent queries cannot be told apart in the fetch log: 64
    # queries must have fetched at least 64 jitted searches between them
    vec_on_device.append(
        sum(n for k, n in burst_disp.items() if k.startswith("vector:")) >= 64)
    recalls = [r["recall"] for r in served]
    checks.add("vector:recall_at_10", float(np.mean(recalls)) >= 0.95,
               f"mean {np.mean(recalls):.4f}, min {min(recalls):.2f} over "
               f"{len(recalls)} served queries; tiers "
               f"{sorted({r['tier'] for r in served})}")

    # at this size the IVF probe wins every solo query, so the brute
    # tier is driven by one 64-wide search_batch
    mark, tq = fetches.mark(), time.perf_counter()
    got_b = idx.search_batch(Q[:64], TOPK)
    brute_s = time.perf_counter() - tq
    tier = vector_tier(fetches.since(mark))
    brute = [score_answer(ref, got_b[i], Q[i], want[i][0], want[i][1])
             for i in range(64)]
    identical = sum(np.array_equal(got_b[i], want[i][0]) for i in range(64))
    checks.add(
        "vector:brute_tier_exact",
        all(exact for _, exact in brute)
        and (tier == "brute_batch" or not on_tpu),
        f"tier {tier}: {sum(exact for _, exact in brute)}/64 exact "
        f"({identical}/64 in identical order), {brute_s:.2f}s first call",
    )
    on = array_platforms(idx)
    checks.add(
        "vector:device_evidence",
        (all(vec_on_device) and on == [report["device"]["platform"]])
        or not on_tpu,
        f"{sum(vec_on_device)}/{len(vec_on_device)} query groups fetched a "
        f"jitted search; corpus arrays on {on}",
    )
    legs["vector"] = dict(
        CompileClock.delta(c0, clock.snap()),
        rows=args.vectors, dim=args.dim, k=TOPK,
        ingest="VectorIndex.bulk_load after alter (there is no binary "
               "vector ingest; 1M rows as RDF text is ~8 GB)",
        setup_s=round(setup_s, 1),
        seconds=round(time.perf_counter() - t0, 1),
        first_query_s=served[0]["seconds"],
        burst_64_s=round(burst_s, 2),
        burst_dispatches=burst_disp,
        ivf=None if idx._ivf is None else {
            "m_slabs": idx._ivf["m_slabs"], "n_slabs": idx._ivf["n_slabs"]},
        hbm=hbm(dev0),
    )

    # -- write then read, with the vector ----------------------------------------
    c0 = clock.snap()
    new_uid = vbase + args.vectors + 1
    new_vec = (V[0] + np.float32(0.25)).astype(np.float32)
    t_ack, commit_s, workers = add_friend("write_with_vector",
                                          (pred, new_uid, new_vec))
    extra = (new_uid, new_vec)
    mark = fetches.mark()
    got, score = similar(
        new_vec, ref.topk(new_vec[None, :], TOPK, extra=extra)[0], extra)
    visible_s = time.perf_counter() - t_ack
    checks.add(
        "write_with_vector:similar_to_sees_it",
        new_uid in got and score[0] >= 0.95,
        f"new uid in top-{TOPK}: {new_uid in got}, recall {score[0]:.2f}, "
        f"tier {vector_tier(fetches.since(mark))}; visible {visible_s:.1f}s "
        f"after the commit ack ({idx.build_count} index builds so far)",
    )
    legs["write_with_vector"] = dict(
        CompileClock.delta(c0, clock.snap()),
        commit_s=round(commit_s, 2),
        vector_visible_s=round(visible_s, 1),
        index_builds=idx.build_count,
        apply_shard_workers=workers,
        hbm=hbm(dev0),
    )
    del V, ref

    # -- kernel families ---------------------------------------------------------
    c0, t0 = clock.snap(), time.perf_counter()
    kernel_families(checks, fetches, clock, report, args.seed, on_tpu)
    legs["kernels"] = dict(CompileClock.delta(c0, clock.snap()),
                           seconds=round(time.perf_counter() - t0, 1))

    report["dispatcher"] = {
        "jit_cache_keys": sorted(
            f"{k[0]}[{k[1]},{k[2]}]" for k in DISPATCHER._jit_cache),
        "device_cache": DISPATCHER.device_cache.stats(),
        "setop_pairs_total": METRICS.value("setop_pairs_total"),
        "setop_packed_total": METRICS.value("setop_packed_total"),
    }


if __name__ == "__main__":
    sys.exit(main())
