"""Reference golden conformance: the Dgraph query suites as oracle.

Runs every case extracted from /root/reference/query/query{0..4}_test.go,
query_facets_test.go and math_test.go (tests/ref_golden/cases.json, built by
extract_goldens.py) against the ported common_test.go fixture
(tests/ref_golden/{schema.txt,triples.rdf,triples_facets.rdf}, built by
extract_fixture.py), comparing with testify-JSONEq semantics (exact
structure; Go numbers are float64).

This replaces self-derived goldens with the reference's own answers.
Cases the engine doesn't match yet are tracked in
known_fails.json and xfail — shrinking that file is the conformance metric
(currently 444/535 exact).
"""

import json
import os

import pytest

HERE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ref_golden")

CASES = json.load(open(os.path.join(HERE, "cases.json")))
KNOWN_FAILS = set(json.load(open(os.path.join(HERE, "known_fails.json"))))


def _canon(x):
    if isinstance(x, dict):
        return {k: _canon(v) for k, v in x.items()}
    if isinstance(x, list):
        return [_canon(v) for v in x]
    if isinstance(x, bool):
        return x
    if isinstance(x, (int, float)):
        return float(x)
    return x


def _build(facets: bool):
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter(open(os.path.join(HERE, "schema.txt")).read())
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf=open(os.path.join(HERE, "triples.rdf")).read(),
        commit_now=True,
    )
    if facets:
        t = s.new_txn()
        t.mutate_rdf(
            set_rdf=open(os.path.join(HERE, "triples_facets.rdf")).read(),
            commit_now=True,
        )
    return s


@pytest.fixture(scope="module")
def base_server():
    return _build(facets=False)


@pytest.fixture(scope="module")
def facets_server():
    return _build(facets=True)


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(
            c,
            marks=(
                # strict: a tracked case that starts passing XPASSes and
                # fails the suite — known_fails.json cannot go stale
                [pytest.mark.xfail(strict=True, reason="tracked gap")]
                if c["id"] in KNOWN_FAILS
                else []
            ),
        )
        for c in CASES
    ],
    ids=[c["id"] for c in CASES],
)
def test_ref_golden(case, base_server, facets_server):
    s = (
        facets_server
        if case["file"] == "query_facets_test.go"
        else base_server
    )
    got = {"data": s.query(case["query"])["data"]}
    want = json.loads(case["expected"])
    assert _canon(got) == _canon(want)
