"""gRPC api.Dgraph wire-protocol smoke tests.

Drives the server exactly the way stock pydgraph/dgo do: raw gRPC calls
on the /api.Dgraph/* method paths with the public proto messages —
login-txn-query-mutate-commit, upsert blocks, JSON mutations, aborts.
(pydgraph itself isn't installable in this image; these stubs use the
identical method paths + serialized messages, which IS the protocol.)
"""

import json

import grpc
import pytest

from dgraph_tpu.api.grpc_server import pb, serve
from dgraph_tpu.api.server import Server


class MiniDgraphClient:
    """The exact call surface pydgraph's DgraphClientStub builds."""

    def __init__(self, addr):
        self.channel = grpc.insecure_channel(addr)
        u = self.channel.unary_unary
        self.login = u(
            "/api.Dgraph/Login",
            request_serializer=pb.LoginRequest.SerializeToString,
            response_deserializer=pb.Response.FromString,
        )
        self.query = u(
            "/api.Dgraph/Query",
            request_serializer=pb.Request.SerializeToString,
            response_deserializer=pb.Response.FromString,
        )
        self.alter = u(
            "/api.Dgraph/Alter",
            request_serializer=pb.Operation.SerializeToString,
            response_deserializer=pb.Payload.FromString,
        )
        self.commit_or_abort = u(
            "/api.Dgraph/CommitOrAbort",
            request_serializer=pb.TxnContext.SerializeToString,
            response_deserializer=pb.TxnContext.FromString,
        )
        self.check_version = u(
            "/api.Dgraph/CheckVersion",
            request_serializer=pb.Check.SerializeToString,
            response_deserializer=pb.Version.FromString,
        )


@pytest.fixture(scope="module")
def client():
    engine = Server()
    server, port = serve(engine)
    c = MiniDgraphClient(f"127.0.0.1:{port}")
    yield c
    server.stop(0)


def test_check_version(client):
    v = client.check_version(pb.Check())
    assert v.tag == "dgraph-tpu"


def test_alter_and_mutate_commit_now(client):
    client.alter(pb.Operation(schema="name: string @index(exact) ."))
    req = pb.Request(commit_now=True)
    m = req.mutations.add()
    m.set_nquads = b'_:a <name> "grpc-alice" .'
    resp = client.query(req)
    assert resp.txn.commit_ts > 0
    assert "a" in dict(resp.uids)

    q = pb.Request(
        query='{ q(func: eq(name, "grpc-alice")) { name } }', read_only=True
    )
    out = json.loads(client.query(q).json)
    assert out["q"][0]["name"] == "grpc-alice"


def test_txn_query_mutate_commit(client):
    # open a txn with the first query (start_ts=0 -> server assigns)
    r1 = client.query(pb.Request(query="{ q(func: has(name)) { uid } }"))
    ts = r1.txn.start_ts
    assert ts > 0
    # mutate inside the txn
    req = pb.Request(start_ts=ts)
    m = req.mutations.add()
    m.set_nquads = b'_:b <name> "grpc-bob" .'
    r2 = client.query(req)
    assert r2.txn.commit_ts == 0  # not committed yet
    # uncommitted write visible inside the txn
    r3 = client.query(
        pb.Request(
            start_ts=ts, query='{ q(func: eq(name, "grpc-bob")) { name } }'
        )
    )
    assert json.loads(r3.json)["q"][0]["name"] == "grpc-bob"
    # not visible outside
    r4 = client.query(
        pb.Request(
            read_only=True, query='{ q(func: eq(name, "grpc-bob")) { name } }'
        )
    )
    assert json.loads(r4.json)["q"] == []
    # commit, then visible
    ctx = client.commit_or_abort(pb.TxnContext(start_ts=ts))
    assert ctx.commit_ts > 0
    r5 = client.query(
        pb.Request(
            read_only=True, query='{ q(func: eq(name, "grpc-bob")) { name } }'
        )
    )
    assert json.loads(r5.json)["q"][0]["name"] == "grpc-bob"


def test_txn_abort_discards(client):
    r1 = client.query(pb.Request(query="{ q(func: has(name)) { uid } }"))
    ts = r1.txn.start_ts
    req = pb.Request(start_ts=ts)
    m = req.mutations.add()
    m.set_nquads = b'_:c <name> "grpc-ghost" .'
    client.query(req)
    ctx = client.commit_or_abort(pb.TxnContext(start_ts=ts, aborted=True))
    assert ctx.aborted
    r = client.query(
        pb.Request(
            read_only=True, query='{ q(func: eq(name, "grpc-ghost")) { uid } }'
        )
    )
    assert json.loads(r.json)["q"] == []


def test_json_mutation(client):
    req = pb.Request(commit_now=True)
    m = req.mutations.add()
    m.set_json = json.dumps(
        {"uid": "_:x", "name": "grpc-json", "age": 7}
    ).encode()
    client.query(req)
    r = client.query(
        pb.Request(
            read_only=True,
            query='{ q(func: eq(name, "grpc-json")) { name age } }',
        )
    )
    got = json.loads(r.json)["q"][0]
    assert got["name"] == "grpc-json" and got["age"] == 7


def test_upsert_block(client):
    req = pb.Request(
        commit_now=True,
        query='{ u as var(func: eq(name, "grpc-alice")) }',
    )
    m = req.mutations.add()
    m.set_nquads = b'uid(u) <name> "grpc-alice-renamed" .'
    client.query(req)
    r = client.query(
        pb.Request(
            read_only=True,
            query='{ q(func: eq(name, "grpc-alice-renamed")) { name } }',
        )
    )
    assert len(json.loads(r.json)["q"]) == 1


def test_conflict_aborts_with_grpc_status(client):
    client.alter(pb.Operation(schema="counter: int @upsert ."))
    client.query(_commit_now_nquads(b'<0x500> <counter> "1"^^<xs:int> .'))
    r1 = client.query(pb.Request(query="{ q(func: uid(0x500)) { counter } }"))
    r2 = client.query(pb.Request(query="{ q(func: uid(0x500)) { counter } }"))
    for ts, val in ((r1.txn.start_ts, b"2"), (r2.txn.start_ts, b"3")):
        req = pb.Request(start_ts=ts)
        m = req.mutations.add()
        m.set_nquads = b'<0x500> <counter> "%s"^^<xs:int> .' % val
        client.query(req)
    assert client.commit_or_abort(
        pb.TxnContext(start_ts=r1.txn.start_ts)
    ).commit_ts > 0
    with pytest.raises(grpc.RpcError) as ei:
        client.commit_or_abort(pb.TxnContext(start_ts=r2.txn.start_ts))
    assert ei.value.code() == grpc.StatusCode.ABORTED


def _commit_now_nquads(nq: bytes) -> "pb.Request":
    req = pb.Request(commit_now=True)
    m = req.mutations.add()
    m.set_nquads = nq
    return req


def test_grpc_login_with_acl():
    """Login over gRPC against an ACL-enabled engine returns working
    JWTs (ref edgraph/access_ee login flow)."""
    import json as _json

    engine = Server()
    engine.enable_acl(groot_password="secret123")
    server, port = serve(engine)
    try:
        c = MiniDgraphClient(f"127.0.0.1:{port}")
        resp = c.login(pb.LoginRequest(userid="groot", password="secret123"))
        jwt = _json.loads(resp.json)
        assert jwt["accessJwt"]
        # wrong password -> UNAUTHENTICATED
        import grpc as _grpc

        with pytest.raises(_grpc.RpcError) as ei:
            c.login(pb.LoginRequest(userid="groot", password="nope"))
        assert ei.value.code() == _grpc.StatusCode.UNAUTHENTICATED
    finally:
        server.stop(0)
