"""Triage driver: run every extracted golden case, bucket failures.

Usage: python tests/ref_golden/run_triage.py [substr-filter]
"""

import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def canon(x):
    """JSONEq semantics: exact structure incl. array order; Go unmarshals all
    numbers to float64, so normalize ints to floats."""
    if isinstance(x, dict):
        return {k: canon(v) for k, v in x.items()}
    if isinstance(x, list):
        return [canon(v) for v in x]
    if isinstance(x, bool):
        return x
    if isinstance(x, (int, float)):
        return float(x)
    return x


def canon_unordered(x):
    if isinstance(x, dict):
        return {k: canon_unordered(v) for k, v in x.items()}
    if isinstance(x, list):
        return sorted(
            (canon_unordered(v) for v in x),
            key=lambda v: json.dumps(v, sort_keys=True, default=str),
        )
    if isinstance(x, bool):
        return x
    if isinstance(x, (int, float)):
        return float(x)
    return x


def build_server(facets=False):
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(open(os.path.join(HERE, "schema.txt")).read())
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf=open(os.path.join(HERE, "triples.rdf")).read(), commit_now=True
    )
    if facets:
        # query_facets_test.go cases run with populateClusterWithFacets
        # applied on top of the base fixture
        t = s.new_txn()
        t.mutate_rdf(
            set_rdf=open(os.path.join(HERE, "triples_facets.rdf")).read(),
            commit_now=True,
        )
    return s


def main():
    filt = sys.argv[1] if len(sys.argv) > 1 else ""
    cases = json.load(open(os.path.join(HERE, "cases.json")))
    if filt:
        cases = [c for c in cases if filt in c["id"]]
    s = build_server()
    sf = build_server(facets=True)
    ok = okuo = 0
    errors, wrong = [], []
    for c in cases:
        eng = sf if c["file"] == "query_facets_test.go" else s
        try:
            got = {"data": eng.query(c["query"])["data"]}
        except Exception as e:
            errors.append((c["id"], f"{type(e).__name__}: {e}"))
            continue
        try:
            want = json.loads(c["expected"])
        except Exception:
            errors.append((c["id"], "unparseable expected"))
            continue
        if canon(got) == canon(want):
            ok += 1
        elif canon_unordered(got) == canon_unordered(want):
            okuo += 1
            wrong.append((c["id"], "ORDER-ONLY", None, None))
        else:
            wrong.append(
                (
                    c["id"],
                    "VALUE",
                    json.dumps(want, default=str)[:200],
                    json.dumps(got, default=str)[:200],
                )
            )
    print(f"\n=== {ok} exact, {okuo} order-only, "
          f"{len(wrong)-okuo} wrong, {len(errors)} errors / {len(cases)}")
    with open("/tmp/golden_triage.json", "w") as f:
        json.dump({"errors": errors, "wrong": wrong}, f, indent=1, default=str)
    from collections import Counter

    print("\n-- error types --")
    for msg, cnt in Counter(e[1].split(":")[0] for e in errors).most_common():
        print(f"  {cnt:4d}  {msg}")
    print("\n-- first errors --")
    for eid, msg in errors[:15]:
        print(f"  {eid}: {msg[:140]}")
    print("\n-- first wrong --")
    for w in wrong[:10]:
        print(f"  {w[0]} [{w[1]}]")
        if w[2]:
            print(f"    want: {w[2]}")
            print(f"    got : {w[3]}")


if __name__ == "__main__":
    main()
