"""North-star metric: LDBC SNB 2-hop friends-of-friends edges/sec.

BASELINE.json's headline config — "systest/ldbc SNB interactive short
reads" / "friends-of-friends 2-hop traversal (batched UID intersect)",
target >=5x CPU on TPU. The real SNB dataset is CI-fetched and not
available here; benchmarks/ldbc_corpus.py generates the same shape at a
configurable scale.

Measures, through the FULL engine (parse -> plan -> dispatch -> merge):
  - 2-hop FoF queries from a batch of person roots (var block + uid()
    expansion + NOT-filters, the IS-style traversal),
  - edges traversed per second (knows edges touched at both hops),
  - per-query latency.

Usage: python benchmarks/ldbc_bench.py [--persons 20000] [--roots 64]
                                       [--json out]
"""

import os as _os
import sys as _sys

_REPO = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
if _REPO not in _sys.path:  # `python benchmarks/x.py` puts only benchmarks/ there
    _sys.path.insert(0, _REPO)

import dgraph_tpu  # noqa: E402,F401 — places the compile cache before jax loads

import argparse
import json
import time

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--persons", type=int, default=20_000)
    ap.add_argument("--roots", type=int, default=64)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    import jax

    from benchmarks.ldbc_corpus import generate, SCHEMA
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.loaders.bulk2 import ParallelBulkLoader

    rng = np.random.default_rng(11)
    t0 = time.time()
    corpus, rdf = generate(
        n_persons=args.persons,
        n_posts=args.persons // 4,
        n_comments=args.persons // 4,
    )
    gen_s = time.time() - t0

    s = Server()
    s.alter(SCHEMA)
    t0 = time.time()
    ParallelBulkLoader(s).load_text("\n".join(rdf))
    load_s = time.time() - t0

    person_uids = list(corpus.persons)
    roots = [
        person_uids[int(rng.integers(len(person_uids)))]
        for _ in range(args.roots)
    ]

    def fof_query(pu):
        sid = corpus.persons[pu].sid
        return (
            f'{{ me as var(func: eq(fqid, "person_{sid}")) {{ f as knows }} '
            "q(func: uid(f)) { fof as knows @filter(NOT uid(me) AND NOT uid(f)) } "
            "res(func: uid(fof)) { count(uid) } }"
        )

    # warm (compiles)
    s.query(fof_query(roots[0]))

    # edge accounting OUTSIDE the timed loop (round 3 timed this O(E)
    # model scan per root and recorded it as engine latency)
    corpus.adjacency()
    per_root_edges = {}
    for pu in roots:
        direct = {f for f, _ in corpus.knows_of(pu)}
        per_root_edges[pu] = len(direct) + sum(
            len(corpus.knows_of(f)) for f in direct
        )
    edges = sum(per_root_edges[pu] for pu in roots)

    queries = [fof_query(pu) for pu in roots]
    t0 = time.time()
    for q in queries:
        out = s.query(q)
        assert "errors" not in out, out
    wall = time.time() - t0

    # batched-roots variant: every root in ONE uid() block — the
    # "batched UID intersect" shape the north star describes. One parse
    # + one level-batched dispatch per hop for all roots together.
    # Edge accounting matches the batched semantics: roots dedupe in
    # eq(fqid, [...]), and each unique friend's knows list is traversed
    # once for the whole batch (NOT once per root as in the loop above).
    uroots = sorted(set(roots))
    union_friends = {
        f for r in uroots for f, _ in corpus.knows_of(r)
    }
    batched_edges = sum(len(corpus.knows_of(r)) for r in uroots) + sum(
        len(corpus.knows_of(f)) for f in union_friends
    )
    # model golden for the global exclusion semantics:
    # fof = (union of friends' knows) - me - f
    want_fof = {
        g for f in union_friends for g, _ in corpus.knows_of(f)
    } - set(uroots) - union_friends
    all_sids = ", ".join(f'"person_{corpus.persons[pu].sid}"' for pu in uroots)
    batched_q = (
        f"{{ me as var(func: eq(fqid, [{all_sids}])) {{ f as knows }} "
        "q(func: uid(f)) { fof as knows @filter(NOT uid(me) AND NOT uid(f)) } "
        "res(func: uid(fof)) { count(uid) } }"
    )
    out = s.query(batched_q)  # warm + validate against the model
    assert "errors" not in out, out
    got_count = out["data"]["res"][0]["count"]
    batched_ok = got_count == len(want_fof)
    reps = 5
    t0 = time.time()
    for _ in range(reps):
        out = s.query(batched_q)
        assert "errors" not in out, out
    batched_wall = (time.time() - t0) / reps

    # correctness spot-check vs the model
    pu = roots[0]
    out = s.query(fof_query(pu).replace("count(uid)", "id"))
    got = sorted(r["id"] for r in out["data"]["res"])
    want = sorted(corpus.persons[u].sid for u in corpus.friends_of_friends(pu))
    ok = got == want

    result = {
        "persons": args.persons,
        "knows_edges": 2 * len(corpus.knows),
        "gen_seconds": round(gen_s, 1),
        "load_seconds": round(load_s, 1),
        "load_edges_per_sec": round(corpus.n_edges / load_s),
        "roots": args.roots,
        "fof_edges_per_sec": round(edges / wall),
        "latency_ms_per_query": round(wall / args.roots * 1e3, 2),
        "batched_fof_edges_per_sec": round(batched_edges / batched_wall),
        "batched_latency_ms": round(batched_wall * 1e3, 2),
        "batched_conformant": batched_ok,
        "conformant": ok,
        "device": str(jax.devices()[0]),
    }
    text = json.dumps(result, indent=1)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main()
