"""GraphQL @auth + introspection (ref
graphql/schema/auth.go, resolve/query_rewriter auth injection,
schema/introspection.go).
"""

import json

import pytest

from dgraph_tpu.acl import jwt as jwtlib
from dgraph_tpu.api.server import Server
from dgraph_tpu.graphql.resolve import GraphQLServer

SDL = r'''
type Todo @auth(
  query: { or: [
    { rule: "{$ROLE: { eq: \"ADMIN\" } }" },
    { rule: """query($USER: String!) { queryTodo(filter: { owner: { eq: $USER } }) { __typename } }""" }
  ]},
  add: { or: [
    { rule: "{$ROLE: { eq: \"ADMIN\" } }" },
    { rule: """query($USER: String!) { queryTodo(filter: { owner: { eq: $USER } }) { __typename } }""" }
  ]},
  delete: { rule: "{$ROLE: { eq: \"ADMIN\" } }" }
) {
  id: ID!
  owner: String @search(by: [exact])
  text: String @search(by: [term])
}

type Public {
  id: ID!
  name: String @search(by: [exact])
}

# Dgraph.Authorization {"VerificationKey":"secret-key","Header":"X-App-Auth","Namespace":"","Algo":"HS256"}
'''


def _token(claims):
    return jwtlib.encode(claims, b"secret-key")


@pytest.fixture()
def gql():
    engine = Server()
    g = GraphQLServer(engine, SDL)
    g.execute(
        'mutation { addTodo(input: [{owner: "alice", text: "a1"}, '
        '{owner: "bob", text: "b1"}]) { numUids } }',
        claims={"USER": "system", "ROLE": "ADMIN"},
    )
    return g


def test_auth_config_parsed(gql):
    assert gql.auth_config is not None
    assert gql.auth_config.header == "X-App-Auth"


def test_query_rule_filters_by_owner(gql):
    out = gql.execute(
        "{ queryTodo { owner text } }", jwt_token=_token({"USER": "alice"})
    )
    todos = out["data"]["queryTodo"]
    assert [t["owner"] for t in todos] == ["alice"]


def test_rbac_admin_sees_all(gql):
    out = gql.execute(
        "{ queryTodo { owner } }",
        jwt_token=_token({"USER": "nobody", "ROLE": "ADMIN"}),
    )
    assert len(out["data"]["queryTodo"]) == 2


def test_no_token_denied_but_unprotected_type_open(gql):
    out = gql.execute("{ queryTodo { owner } }")
    # no claims: the or-rule needs $USER -> error surfaces in envelope
    assert out.get("errors") or out["data"]["queryTodo"] == []
    out = gql.execute("{ queryPublic { name } }")
    assert out["data"]["queryPublic"] == []  # open type, just empty


def test_add_rule_enforced(gql):
    # bob may only add todos he owns
    out = gql.execute(
        'mutation { addTodo(input: [{owner: "bob", text: "ok"}]) { numUids } }',
        jwt_token=_token({"USER": "bob"}),
    )
    assert out["data"]["addTodo"]["numUids"] == 1
    out = gql.execute(
        'mutation { addTodo(input: [{owner: "eve", text: "nope"}]) { numUids } }',
        jwt_token=_token({"USER": "bob"}),
    )
    # ref resolver wording: post-insert auth check failed
    assert out["data"] is None and "authorization failed" in (
        out["errors"][0]["message"]
    )


def test_delete_rbac(gql):
    out = gql.execute(
        'mutation { deleteTodo(filter: {owner: {eq: "alice"}}) { numUids } }',
        jwt_token=_token({"USER": "alice"}),  # not ADMIN
    )
    # a denied delete matches nothing — empty payload, NOT an error
    # (ref auth_delete_test "top level RBAC false": `x as deleteLog()`)
    assert not out.get("errors"), out
    assert out["data"]["deleteTodo"]["numUids"] == 0
    out = gql.execute(
        'mutation { deleteTodo(filter: {owner: {eq: "alice"}}) { numUids } }',
        jwt_token=_token({"ROLE": "ADMIN"}),
    )
    assert out["data"]["deleteTodo"]["numUids"] == 1


def test_bad_signature_rejected(gql):
    bad = jwtlib.encode({"USER": "alice"}, b"wrong-key")
    out = gql.execute("{ queryTodo { owner } }", jwt_token=bad)
    assert out.get("errors")


def test_typename_injection(gql):
    out = gql.execute(
        "{ queryTodo { __typename owner } }",
        jwt_token=_token({"ROLE": "ADMIN", "USER": "x"}),
    )
    assert all(t["__typename"] == "Todo" for t in out["data"]["queryTodo"])


def test_introspection_schema(gql):
    out = gql.execute(
        """{ __schema {
             queryType { name }
             mutationType { name }
             types { name kind }
           } }"""
    )
    sch = out["data"]["__schema"]
    assert sch["queryType"]["name"] == "Query"
    names = {t["name"] for t in sch["types"]}
    assert {"Todo", "Public", "Query", "Mutation", "String"} <= names


def test_introspection_type_fields(gql):
    out = gql.execute(
        '{ __type(name: "Todo") { name kind fields { name type { kind name ofType { name } } } } }'
    )
    t = out["data"]["__type"]
    assert t["name"] == "Todo" and t["kind"] == "OBJECT"
    fields = {f["name"] for f in t["fields"]}
    assert {"id", "owner", "text"} <= fields


def test_introspection_query_fields(gql):
    out = gql.execute(
        '{ __type(name: "Query") { fields { name } } }'
    )
    names = {f["name"] for f in out["data"]["__type"]["fields"]}
    assert {"getTodo", "queryTodo", "aggregateTodo", "queryPublic"} <= names
