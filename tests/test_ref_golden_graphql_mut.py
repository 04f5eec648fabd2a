"""GraphQL *mutation* conformance against the reference's rewriter
oracles.

Cases: tests/ref_golden_graphql/mutation_cases.json, extracted from
/root/reference/graphql/resolve/{add,update,delete,validate}_mutation_test.yaml
(driven there by mutation_test.go TestMutationRewriting).

Execution-equivalence (see mutation_support.py): both sides run against
OUR engine on identical seeded worlds — our GraphQL layer on store A,
the reference-blessed plan (dgquery/dgquerysec + setjson/deletejson/
@if conds via Txn.upsert_json) on store B — and the resulting graphs
must match modulo uid renaming. Error cases must error on side A too.

Failures are tracked in known_fails_mut.json (strict xfail — a fixed
case must be removed); shrinking it is the metric.
"""

import json
import os
import sys

import pytest

# the reference YAMLs freeze $now (@default) at this instant
os.environ.setdefault("DGRAPH_TPU_FAKE_NOW", "2000-01-01T00:00:00.00Z")

HERE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ref_golden_graphql"
)
sys.path.insert(0, HERE)

CASES = json.load(open(os.path.join(HERE, "mutation_cases.json")))
SCHEMA = open(os.path.join(HERE, "resolve_schema.graphql")).read()


def _load(name):
    p = os.path.join(HERE, name)
    return set(json.load(open(p))) if os.path.exists(p) else set()


KNOWN = _load("known_fails_mut.json")


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(
            c,
            marks=(
                [pytest.mark.xfail(strict=True, reason="tracked gap")]
                if c["id"] in KNOWN
                else []
            ),
        )
        for c in CASES
    ],
    ids=[c["id"] for c in CASES],
)
def test_graphql_mutation_equiv(case):
    import mutation_support as ms

    types = __import__(
        "dgraph_tpu.graphql.sdl", fromlist=["parse_sdl"]
    ).parse_sdl(SCHEMA)
    seeds, max_uid = ms.seed_objects(case, types)

    # --- side A: our GraphQL layer -------------------------------------
    sa, gql = ms.make_server(SCHEMA, max_uid)
    ms.apply_seed(sa, seeds)
    res = gql.execute(
        case["gqlmutation"], variables=case.get("gqlvariables")
    )
    errored = bool(res.get("errors"))

    wants_error = any(
        k in case for k in ("error", "error2", "validationerror")
    )
    if wants_error:
        assert errored, (
            f"reference rejects this mutation "
            f"({case.get('error') or case.get('error2') or case.get('validationerror')!r}) "
            f"but ours succeeded: {res}"
        )
        return
    assert not errored, res["errors"]

    # --- side B: reference plan through our engine ---------------------
    sb, _ = ms.make_server(SCHEMA, max_uid)
    ms.apply_seed(sb, seeds)
    query = case.get("dgquerysec") or ""
    if case["kind"] == "delete":
        query = case.get("dgquery") or query
    txn = sb.new_txn()
    txn.upsert_json(query, case.get("dgmutations", []), commit_now=True)
    if case.get("dgmutationssec"):
        txn2 = sb.new_txn()
        txn2.upsert_json(
            query, case["dgmutationssec"], commit_now=True
        )

    got = ms.canonicalize(ms.dump_triples(sa))
    want = ms.canonicalize(ms.dump_triples(sb))
    assert got == want, _diff(got, want)


def _diff(got, want):
    gs, ws = set(map(repr, got)), set(map(repr, want))
    extra = sorted(gs - ws)[:12]
    missing = sorted(ws - gs)[:12]
    return (
        f"state mismatch\n  ours-only ({len(gs - ws)}): {extra}\n"
        f"  ref-only ({len(ws - gs)}): {missing}"
    )
