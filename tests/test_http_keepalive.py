"""Persistent connections on both sides of the wire (api/http_server.py
`_Handler` / `_Listener`, dgraph_tpu/client.py `DgraphClient`): a client
keeps one HTTP/1.1 connection a thread, the alpha serves its requests in
turn on one thread, and every request after which the stream could be
out of step, or that asks for it, closes the connection instead."""

import base64
import json
import os
import socket
import threading
import time

import pytest

from dgraph_tpu.api import http_server
from dgraph_tpu.api.http_server import HTTPServer
from dgraph_tpu.api.server import Server
from dgraph_tpu.client import DgraphClient, DgraphClientError
from dgraph_tpu.utils.observe import METRICS

QUERY = '{ q(func: eq(name, "p1")) { uid name } }'


@pytest.fixture
def served():
    s = Server()
    s.alter("name: string @index(exact) .")
    s.new_txn().mutate_rdf(
        set_rdf="\n".join(f'<0x{i:x}> <name> "p{i % 5}" .'
                          for i in range(1, 60)),
        commit_now=True)
    srv = HTTPServer(s, host="127.0.0.1", port=0).start()
    try:
        yield s, srv
    finally:
        srv.stop()


def _conns() -> float:
    return METRICS.value("http_connections_total")


def _raw(port, data: bytes) -> socket.socket:
    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    sock.sendall(data)
    return sock


def _read_reply(f):
    """(status, headers, body) of one reply from a socket's file."""
    status = int(f.readline().split()[1])
    headers = {}
    while True:
        line = f.readline().decode().strip()
        if not line:
            break
        k, v = line.split(":", 1)
        headers[k.strip().lower()] = v.strip()
    body = f.read(int(headers.get("content-length", 0)))
    return status, headers, body


def _post(path, body: bytes, version="HTTP/1.1", extra="",
          length=None) -> bytes:
    n = len(body) if length is None else length
    return (f"POST {path} {version}\r\nHost: x\r\n"
            f"Content-Type: application/dql\r\nContent-Length: {n}\r\n"
            f"{extra}\r\n").encode() + body


def _closed(sock) -> bool:
    """Whether the server closed the connection (EOF within 10 s)."""
    sock.settimeout(10)
    try:
        return sock.recv(1) == b""
    except ConnectionResetError:
        return True


@pytest.mark.parametrize("n", [2, 10])
def test_n_queries_on_one_client_cost_one_connection(served, n):
    _, srv = served
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    c0 = _conns()
    for _ in range(n):
        assert len(c.query(QUERY)["data"]["q"]) == 12
    assert _conns() - c0 == 1


def test_a_connection_a_thread(served):
    """Threads that share a client each keep a connection of their own."""
    _, srv = served
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    c0, out = _conns(), []

    def loop():
        out.extend(len(c.query(QUERY)["data"]["q"]) for _ in range(3))

    threads = [threading.Thread(target=loop) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert out == [12] * 9
    assert _conns() - c0 == 3


def test_close_then_the_next_request_opens_another(served):
    _, srv = served
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    c0 = _conns()
    c.query(QUERY)
    c.close()
    c.close()  # a second close does nothing
    c.query(QUERY)
    assert _conns() - c0 == 2


def test_a_kept_connection_answers_requests_in_turn(served):
    _, srv = served
    body = QUERY.encode()
    sock = _raw(srv.port, _post("/query", body) * 3)
    f = sock.makefile("rb")
    try:
        for _ in range(3):
            status, headers, out = _read_reply(f)
            assert status == 200 and "connection" not in headers
            assert len(json.loads(out)["data"]["q"]) == 12
    finally:
        f.close()
        sock.close()


@pytest.mark.parametrize("version,extra", [
    ("HTTP/1.0", ""),
    ("HTTP/1.1", "Connection: close\r\n"),
], ids=["http10", "connection_close"])
def test_a_request_that_asks_for_a_close_is_answered_and_closed(
        served, version, extra):
    _, srv = served
    sock = _raw(srv.port, _post("/query", QUERY.encode(), version, extra))
    f = sock.makefile("rb")
    try:
        status, _, out = _read_reply(f)
        assert status == 200
        assert len(json.loads(out)["data"]["q"]) == 12
        assert _closed(sock)
    finally:
        f.close()
        sock.close()


def test_the_graphql_websocket_upgrade_still_closes(served):
    _, srv = served
    key = base64.b64encode(os.urandom(16)).decode()
    sock = _raw(srv.port, (
        "GET /graphql HTTP/1.1\r\nHost: x\r\nUpgrade: websocket\r\n"
        f"Connection: Upgrade\r\nSec-WebSocket-Key: {key}\r\n"
        "Sec-WebSocket-Version: 13\r\n"
        "Sec-WebSocket-Protocol: graphql-transport-ws\r\n\r\n").encode())
    f = sock.makefile("rb")
    try:
        assert f.readline().split()[1] == b"101"
        while f.readline().strip():
            pass
        sock.sendall(bytes([0x88, 0x80]) + os.urandom(4))  # masked close
        assert _closed(sock)
    finally:
        f.close()
        sock.close()


def test_a_write_then_a_read_on_one_kept_connection(served):
    _, srv = served
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    c0 = _conns()
    txn = c.txn()
    txn.mutate(set_rdf='<0x1000> <name> "kept" .', commit_now=True)
    out = c.query('{ q(func: eq(name, "kept")) { uid name } }')
    assert out["data"]["q"] == [{"uid": "0x1000", "name": "kept"}]
    assert _conns() - c0 == 1


def test_after_an_idle_close_the_next_requests_go_on_a_new_connection(
        served, monkeypatch):
    """The alpha closes a connection idle for `_IDLE_S`; the client
    sees the close before it sends, and a query and a mutation each go
    once on a new connection."""
    s, srv = served
    monkeypatch.setattr(http_server, "_IDLE_S", 0.2)
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    c0 = _conns()
    c.query(QUERY)
    time.sleep(1.0)
    assert len(c.query(QUERY)["data"]["q"]) == 12
    assert _conns() - c0 == 2
    time.sleep(1.0)
    m0 = srv.httpd.RequestHandlerClass.metrics.get("num_mutations", 0)
    c.txn().mutate(set_rdf='<0x1001> <name> "idle" .', commit_now=True)
    assert srv.httpd.RequestHandlerClass.metrics["num_mutations"] - m0 == 1
    assert _conns() - c0 == 3
    assert len(s.query('{ q(func: eq(name, "idle")) { uid } }')
               ["data"]["q"]) == 1


def test_a_commit_after_an_idle_close_goes_on_a_new_connection(
        served, monkeypatch):
    """A commit is one write with no body: where it went out on the
    closed connection it would fail after the send, unanswered, and could
    not go again; the client finds the close before it sends."""
    s, srv = served
    monkeypatch.setattr(http_server, "_IDLE_S", 0.2)
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    txn = c.txn()
    txn.mutate(set_rdf='<0x1003> <name> "later" .')
    time.sleep(1.0)
    c0 = _conns()
    assert txn.commit()["code"] == "Success"
    assert _conns() - c0 == 1
    assert len(s.query('{ q(func: eq(name, "later")) { uid } }')
               ["data"]["q"]) == 1


class _ClosingServer:
    """A server that answers a connection's first request and closes it
    on reading its second, as a close racing a kept connection's next
    request does; it keeps every request line it read."""

    def __init__(self):
        self.lsock = socket.socket()
        self.lsock.bind(("127.0.0.1", 0))
        self.lsock.listen(8)
        self.port = self.lsock.getsockname()[1]
        self.lines = []
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while True:
            try:
                conn, _ = self.lsock.accept()
            except OSError:
                return
            with conn, conn.makefile("rb") as f:
                for n in range(2):
                    line = f.readline()
                    if not line:
                        break
                    self.lines.append(line.split()[1].decode())
                    length = 0
                    while (h := f.readline().strip()):
                        k, v = h.decode().split(":", 1)
                        if k.lower() == "content-length":
                            length = int(v)
                    f.read(length)
                    if n == 1:
                        break  # closed without a reply
                    body = b'{"data": {"ok": true}}'
                    conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Length: "
                                 + str(len(body)).encode() + b"\r\n\r\n"
                                 + body)

    def close(self):
        self.lsock.close()


@pytest.fixture
def closing():
    srv = _ClosingServer()
    try:
        yield srv
    finally:
        srv.close()


def test_a_read_cut_off_by_the_close_goes_again_once(closing):
    c = DgraphClient(f"http://127.0.0.1:{closing.port}")
    assert c.query(QUERY) == {"data": {"ok": True}}
    assert c.query(QUERY) == {"data": {"ok": True}}
    assert closing.lines == ["/query"] * 3


def test_a_write_cut_off_by_the_close_is_not_sent_twice(closing):
    c = DgraphClient(f"http://127.0.0.1:{closing.port}")
    assert c.query(QUERY) == {"data": {"ok": True}}
    with pytest.raises(DgraphClientError, match="connection failed"):
        c.txn().mutate(set_rdf='<0x1> <name> "x" .', commit_now=True)
    assert closing.lines == ["/query", "/mutate?commitNow=true"]
    # the next request opens a new connection
    assert c.query(QUERY) == {"data": {"ok": True}}


def test_a_short_body_is_refused_and_closes(served):
    """A body that ends before its Content-Length is not applied: the
    reply says so and the connection closes, rather than read the next
    request from the middle of this one."""
    s, srv = served
    rdf = b'{ set { <0x1002> <name> "short" . } }'
    sock = _raw(srv.port, _post("/mutate?commitNow=true", rdf,
                                length=len(rdf) + 40))
    sock.shutdown(socket.SHUT_WR)
    f = sock.makefile("rb")
    try:
        status, headers, out = _read_reply(f)
        assert status == 400 and headers["connection"] == "close"
        assert "body" in json.loads(out)["errors"][0]["message"]
        assert _closed(sock)
    finally:
        f.close()
        sock.close()
    assert s.query('{ q(func: eq(name, "short")) { uid } }')["data"]["q"] \
        == []


def test_a_reply_before_the_body_was_read_closes(served):
    """A route that answers without reading the body leaves the body in
    the stream: the reply says so and the connection closes."""
    _, srv = served
    sock = _raw(srv.port, _post("/no-such-route", b"x" * 64)
                + _post("/query", QUERY.encode()))
    f = sock.makefile("rb")
    try:
        status, headers, _ = _read_reply(f)
        assert status == 404 and headers["connection"] == "close"
        assert _closed(sock)
    finally:
        f.close()
        sock.close()


def test_an_error_after_the_reply_began_writes_no_second_reply(
        served, monkeypatch):
    _, srv = served
    handler = srv.httpd.RequestHandlerClass

    def reply_then_fail(self, qs, token=None):
        self._body()
        self._reply({"data": {"code": "Success"}})
        raise RuntimeError("after the reply")

    monkeypatch.setattr(handler, "_handle_mutate", reply_then_fail)
    sock = _raw(srv.port, _post("/mutate", b"{}")
                + _post("/query", QUERY.encode()))
    f = sock.makefile("rb")
    try:
        status, _, out = _read_reply(f)
        assert status == 200 and json.loads(out)["data"]["code"] == "Success"
        assert _closed(sock)  # neither a 500 nor the query's answer
    finally:
        f.close()
        sock.close()


def test_stop_closes_the_kept_connections_that_wait(served):
    _, srv = served
    c = DgraphClient(f"http://127.0.0.1:{srv.port}")
    c.query(QUERY)
    t0 = time.monotonic()
    srv.stop()
    assert time.monotonic() - t0 < 5
    with pytest.raises(DgraphClientError, match="connection failed"):
        c.query(QUERY)
