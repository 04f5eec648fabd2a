"""GraphQL conformance against the reference's own oracles.

Two tiers, mirroring how tests/test_ref_golden.py gave DQL its oracle:

Tier A — e2e response goldens: cases extracted from
/root/reference/graphql/e2e/common/query.go (extract_goldens.py) run
over the normal-suite fixture (e2e_schema.graphql + e2e_data.json,
copied from /root/reference/graphql/e2e/normal/) and compared with
testify-JSONEq / testutil-CompareJSON semantics.

Tier B — translation-equivalence goldens: the 167 cases of
/root/reference/graphql/resolve/query_test.yaml each pair a GraphQL
query with the DQL the reference rewrites it to. Both run against the
SAME store here: the GraphQL query through our graphql layer, the
reference-blessed dgquery through our DQL engine (itself 535/535
conformant to the reference query suites) — results must agree after
alias normalization. This checks our GraphQL semantics against the
reference's rewriter without requiring byte-identical internal DQL.

Failures are tracked in known_fails_{e2e,resolve}.json (strict xfail —
a fixed case must be removed); shrinking them is the metric.
"""

import json
import os

import pytest

HERE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "ref_golden_graphql"
)

E2E_CASES = json.load(open(os.path.join(HERE, "cases.json")))
RESOLVE_CASES = json.load(open(os.path.join(HERE, "resolve_cases.json")))


def _load(name):
    p = os.path.join(HERE, name)
    return set(json.load(open(p))) if os.path.exists(p) else set()


KNOWN_E2E = _load("known_fails_e2e.json")
KNOWN_RESOLVE = _load("known_fails_resolve.json")


# ---------------------------------------------------------------------------
# Fixtures
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def e2e():
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.graphql import GraphQLServer

    s = Server()
    gql = GraphQLServer(
        s, open(os.path.join(HERE, "e2e_schema.graphql")).read()
    )
    data = json.load(open(os.path.join(HERE, "e2e_data.json")))
    t = s.new_txn()
    t.mutate_json(set_obj=data)
    t.commit()
    return gql


@pytest.fixture(scope="module")
def e2e_directives():
    """The SAME e2e cases over the directives fixture (ref
    graphql/e2e/directives: @dgraph(type:/pred:) storage mappings +
    reverse-edge preds) — the reference's RunAll exercises both
    clusters; so do we."""
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.graphql import GraphQLServer

    s = Server()
    gql = GraphQLServer(
        s, open(os.path.join(HERE, "e2e_directives_schema.graphql")).read()
    )
    data = json.load(
        open(os.path.join(HERE, "e2e_directives_data.json"))
    )
    t = s.new_txn()
    t.mutate_json(set_obj=data)
    t.commit()
    return gql


@pytest.fixture(scope="module")
def resolve_world():
    from dgraph_tpu.api.server import Server
    from dgraph_tpu.graphql import GraphQLServer

    s = Server()
    gql = GraphQLServer(
        s, open(os.path.join(HERE, "resolve_schema.graphql")).read()
    )

    def mut(q, variables=None):
        res = gql.execute(q, variables=variables)
        assert "errors" not in res or not res["errors"], res
        return res

    # a small world covering the resolve schema's main types, seeded
    # through our own GraphQL mutations so every query has data to hit
    mut(
        """
        mutation {
          addCountry(input: [
            {name: "Ruritania", states: [
              {code: "RU-N", name: "North", capital: "Nordberg"},
              {code: "RU-S", name: "South"}]},
            {name: "Elbonia", states: [{code: "EL-1", name: "Mud"}]}
          ]) { numUids }
        }
        """
    )
    mut(
        """
        mutation {
          addAuthor(input: [
            {name: "A. N. Author", dob: "2000-01-01", reputation: 6.6,
             posts: [
               {title: "GraphQL doco", text: "types and queries",
                tags: ["graphql", "docs"], numLikes: 100,
                isPublished: true, postType: [Fact]},
               {title: "Random post", text: "this is random",
                tags: ["random"], numLikes: 2, isPublished: false,
                postType: [Opinion]}
             ]},
            {name: "Other Author", dob: "1988-01-01", reputation: 8.9,
             posts: [{title: "Another post", text: "words",
                      tags: ["docs"], numLikes: 10, isPublished: true,
                      postType: [Question]}]}
          ]) { numUids }
        }
        """
    )
    mut(
        """
        mutation {
          addEditor(input: [{code: "ed1", name: "E. Ditor"}]) { numUids }
        }
        """
    )
    mut(
        """
        mutation {
          addHuman(input: [
            {name: "Bob", ename: "bob-emp", dob: "2000-01-01",
             female: false}
          ]) { numUids }
        }
        """
    )
    mut(
        """
        mutation {
          addUser(input: [{name: "user1", pwd: "Password"}]) { numUids }
        }
        """
    )
    mut(
        """
        mutation {
          addAstronaut(input: [
            {id: "0x1", missions: [{id: "m1", designation: "Apollo"}]},
            {id: "0x2", missions: [{id: "m2", designation: "Artemis"}]}
          ]) { numUids }
          addSpaceShip(input: [
            {id: "0x1", missions: [{id: "m3", designation: "Falcon"}]}
          ]) { numUids }
        }
        """
    )
    mut(
        """
        mutation {
          addVerification(input: [
            {name: "v1", status: [ACTIVE], prevStatus: INACTIVE},
            {name: "v2", status: [INACTIVE, DEACTIVATED],
             prevStatus: ACTIVE}
          ]) { numUids }
        }
        """
    )
    return gql, s


# ---------------------------------------------------------------------------
# Comparison helpers
# ---------------------------------------------------------------------------


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


def _sorted_lists(x):
    """testutil.CompareJSON semantics: arrays compare order-insensitively
    at every depth."""
    if isinstance(x, dict):
        return {k: _sorted_lists(v) for k, v in x.items()}
    if isinstance(x, list):
        return sorted(
            (_sorted_lists(v) for v in x),
            key=lambda v: json.dumps(v, sort_keys=True),
        )
    return x


_CHILD_AGG_RE = None


def _strip_ref(x):
    """Normalize a dgquery response: 'Type.field' aliases -> 'field',
    drop dgraph.uid / dgraph.type (the rewriter injects both), and fold
    the rewriter's flat child-aggregate aliases
    ('AggRes.cnt_Country.ag': N -> {'ag': {'cnt': N}})."""
    global _CHILD_AGG_RE
    import re

    if _CHILD_AGG_RE is None:
        _CHILD_AGG_RE = re.compile(r"^(\w+)_[A-Z]\w*\.(\w+)$")
    if isinstance(x, dict):
        out = {}
        folded = {}
        for k, v in x.items():
            if k in ("dgraph.uid", "dgraph.type"):
                continue
            # 'AggRes.cnt_Country.ag' -> strip the alias-type prefix,
            # leaving 'cnt_Country.ag' for the fold below
            k = k.split(".", 1)[1] if "." in k else k
            m = _CHILD_AGG_RE.match(k)
            if m:
                folded.setdefault(m.group(2), {})[m.group(1)] = _strip_ref(v)
            else:
                out[k] = _strip_ref(v)
        out.update(folded)
        return out
    if isinstance(x, list):
        return [_strip_ref(v) for v in x]
    return x


def _strip_ours(x):
    """Normalize our GraphQL response for DQL comparison: drop
    requested-but-missing fields (GraphQL nulls / empty lists — DQL
    omits them) and __typename (no DQL counterpart)."""
    if isinstance(x, dict):
        out = {}
        for k, v in x.items():
            if v is None or v == [] or k == "__typename":
                continue
            sv = _strip_ours(v)
            if sv == {}:
                # an all-null child aggregate strips to {}; DQL omits
                # the block entirely
                continue
            out[k] = sv
        return out
    if isinstance(x, list):
        return [_strip_ours(v) for v in x]
    return x


# ---------------------------------------------------------------------------
# Tier A: e2e response goldens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(
            c,
            marks=(
                [pytest.mark.xfail(strict=True, reason="tracked gap")]
                if c["id"] in KNOWN_E2E
                else []
            ),
        )
        for c in E2E_CASES
    ],
    ids=[c["id"] for c in E2E_CASES],
)
def test_graphql_e2e_golden(case, e2e):
    res = e2e.execute(case["query"], variables=case.get("variables"))
    assert "errors" not in res or not res["errors"], res
    got = _canon(res["data"])
    want = _canon(json.loads(case["expected"]))
    if case.get("unordered"):
        got, want = _sorted_lists(got), _sorted_lists(want)
    assert got == want


KNOWN_DIRECTIVES = _load("known_fails_directives.json")


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(
            c,
            marks=(
                [pytest.mark.xfail(strict=True, reason="tracked gap")]
                if c["id"] in KNOWN_DIRECTIVES
                else []
            ),
        )
        for c in E2E_CASES
    ],
    ids=[f"dir-{c['id']}" for c in E2E_CASES],
)
def test_graphql_e2e_golden_directives(case, e2e_directives):
    """Same goldens over @dgraph-mapped storage (type renames, custom
    predicate names, reverse-edge mappings)."""
    res = e2e_directives.execute(
        case["query"], variables=case.get("variables")
    )
    assert "errors" not in res or not res["errors"], res
    got = _canon(res["data"])
    want = _canon(json.loads(case["expected"]))
    if case.get("unordered"):
        got, want = _sorted_lists(got), _sorted_lists(want)
    assert got == want


def _normalize_pair(ours_data, ref_data):
    """(got, want) ready to compare: our entities stripped of GraphQL
    nulls/empties (DQL omits them), ref aliases de-qualified, getX
    object results wrapped to lists, and root keys aligned (our
    response honors root aliases; the dgquery block keeps the
    generated operation name)."""
    got = {}
    for k, v in ours_data.items():
        if not isinstance(v, list):
            v = [] if v is None else [v]
        got[k] = _strip_ours(v)
    want = _strip_ref(ref_data)
    # the reference rewriter injects val(distance) as vector_distance
    # even when the GraphQL query never selected it; drop it from the
    # dgquery side unless our response carries it too
    def _has_vd(x):
        if isinstance(x, dict):
            return "vector_distance" in x or any(
                _has_vd(v) for v in x.values()
            )
        if isinstance(x, list):
            return any(_has_vd(v) for v in x)
        return False

    def _drop_vd(x):
        if isinstance(x, dict):
            return {
                k: _drop_vd(v)
                for k, v in x.items()
                if k != "vector_distance"
            }
        if isinstance(x, list):
            return [_drop_vd(v) for v in x]
        return x

    if not _has_vd(got):
        want = _drop_vd(want)
    # rewriter helper blocks appear in the dgquery response but have no
    # GraphQL counterpart — an EXPLICIT allowlist only:
    # a blanket subset-drop would also hide root fields our resolver
    # silently failed to return)
    _HELPER_KEYS = ("checkPwd",)
    for hk in _HELPER_KEYS:
        if hk in want and hk not in got:
            want = {k: v for k, v in want.items() if k != hk}
    # DQL encodes a root aggregate as one single-key object per
    # aggregate child; GraphQL completion merges them and turns a
    # missing count into 0 (ref completeAggregateValues). Apply the
    # same completion to the dgquery side before comparing.
    for k, v in list(want.items()):
        g = got.get(k)
        if (
            isinstance(v, list)
            and len(v) > 1
            and all(isinstance(e, dict) and len(e) <= 1 for e in v)
            and isinstance(g, list)
            and len(g) == 1
        ):
            merged = {}
            for e in v:
                merged.update(e)
            merged = {
                mk: (0 if mv is None and mk in g[0] and g[0][mk] == 0 else mv)
                for mk, mv in merged.items()
            }
            merged = {mk: mv for mk, mv in merged.items() if mv is not None}
            want[k] = [merged]
    if set(got) != set(want) and len(got) == len(want):
        # root alias: compare positionally (both sides preserve
        # selection order)
        got = {i: v for i, v in enumerate(got.values())}
        want = {i: v for i, v in enumerate(want.values())}
    return got, want



# ---------------------------------------------------------------------------
# Tier B: translation equivalence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case",
    [
        pytest.param(
            c,
            marks=(
                [pytest.mark.xfail(strict=True, reason="tracked gap")]
                if c["id"] in KNOWN_RESOLVE
                else []
            ),
        )
        for c in RESOLVE_CASES
    ],
    ids=[c["id"] for c in RESOLVE_CASES],
)
def test_graphql_resolve_equiv(case, resolve_world):
    gql, s = resolve_world
    ours = gql.execute(case["gqlquery"], variables=case.get("gqlvariables"))
    assert "errors" not in ours or not ours["errors"], ours
    ref = s.query(case["dgquery"], variables=case.get("dgvars"))["data"]
    got, want = _normalize_pair(ours["data"], ref)
    assert _canon(_sorted_lists(got)) == _canon(_sorted_lists(want))
