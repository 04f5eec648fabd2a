"""GraphQL @auth conformance against the reference's rewriter oracles.

Cases: tests/ref_golden_graphql/auth_cases.json, extracted from
/root/reference/graphql/resolve/auth_*_test.yaml (driven there by
auth_test.go over graphql/e2e/auth/schema.graphql — copied here as
auth_schema.graphql).

Execution equivalence on a discriminating seeded world (two nodes per
type: one matching the case's auth-rule values, one not — see
mutation_support.auth_seed_objects):
  query  — our GraphQL layer with JWT claims vs the reference dgquery
           through our DQL engine; responses must agree (Tier-B
           normalization).
  delete — both sides mutate sibling stores; final graphs must match
           modulo uid renaming.
  add/update — error cases must error; success cases must succeed.

Failures tracked in known_fails_auth.json (strict xfail)."""

import json
import os
import re
import sys

import pytest

HERE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ref_golden_graphql"
)
sys.path.insert(0, HERE)

CASES = json.load(open(os.path.join(HERE, "auth_cases.json")))
SCHEMA = open(os.path.join(HERE, "auth_schema.graphql")).read()


def _load(name):
    p = os.path.join(HERE, name)
    return set(json.load(open(p))) if os.path.exists(p) else set()


KNOWN = _load("known_fails_auth.json")

_EMPTY_DGQ = re.compile(r"^\s*query\s*\{\s*(\w+)\(\)\s*\}\s*$")


def _types():
    from dgraph_tpu.graphql.sdl import parse_sdl

    return parse_sdl(SCHEMA)


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
def test_graphql_auth_equiv(case):
    import mutation_support as ms
    from test_ref_golden_graphql import (
        _canon,
        _normalize_pair,
        _sorted_lists,
    )

    types = _types()
    seeds, max_uid = ms.auth_seed_objects(case, types)
    claims = dict(case.get("jwtvar") or {})

    sa, gql = ms.make_server(SCHEMA, max_uid)
    if case.get("closed"):
        gql.closed_by_default = True
    ms.apply_seed(sa, seeds)
    res = gql.execute(
        case["gqlquery"],
        variables=case.get("variables"),
        claims=claims or None,
    )
    errored = bool(res.get("errors"))

    if case["kind"] in ("add", "update") or (
        case.get("closed") and case.get("error")
    ):
        if case.get("error"):
            assert errored, (
                f"reference rejects ({case['error']!r}) but ours "
                f"succeeded: {res}"
            )
        else:
            assert not errored, res["errors"]
        return

    assert not errored, res["errors"]

    if case["kind"] == "delete":
        sb, _ = ms.make_server(SCHEMA, max_uid)
        ms.apply_seed(sb, seeds)
        txn = sb.new_txn()
        txn.upsert_json(
            case.get("dgquery") or "",
            case.get("dgmutations", []),
            commit_now=True,
        )
        got = ms.canonicalize(ms.dump_triples(sa))
        want = ms.canonicalize(ms.dump_triples(sb))
        assert got == want, _mdiff(got, want)
        return

    # query equivalence
    dgq = case.get("dgquery") or ""
    m = _EMPTY_DGQ.match(dgq)
    if m:
        # rewriter denied outright: our response must be empty
        for v in (res.get("data") or {}).values():
            assert v in (None, [], {}), res
        return
    ref = sa.query(dgq, variables=case.get("dgvars"))["data"]
    got, want = _normalize_pair(res["data"], ref)
    assert _canon(_sorted_lists(got)) == _canon(_sorted_lists(want))


def _mdiff(got, want):
    gs, ws = set(map(repr, got)), set(map(repr, want))
    return (
        f"state mismatch\n  ours-only ({len(gs - ws)}): "
        f"{sorted(gs - ws)[:10]}\n  ref-only ({len(ws - gs)}): "
        f"{sorted(ws - gs)[:10]}"
    )
