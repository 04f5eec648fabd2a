"""HTTP front-end: the Alpha endpoint surface.

Mirrors /root/reference/dgraph/cmd/alpha (setupServer run.go:458, http.go,
admin.go): /query, /mutate, /commit, /alter, /health, /state,
/admin/schema, /admin/export, /admin/backup, /debug/prometheus_metrics.
JSON bodies and response envelope follow the reference's
{"data": ..., "extensions": {"server_latency": ...}} shape.
"""

from __future__ import annotations

import json
import socket
import struct
import threading
import time
import traceback
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional
from urllib.parse import parse_qs, urlparse

from dgraph_tpu.acl.acl import AclError
from dgraph_tpu.acl.jwt import JwtError
from dgraph_tpu.dql.parser import ParseError
from dgraph_tpu.query import streamjson
from dgraph_tpu.query.functions import QueryError
from dgraph_tpu.api.server import Server, TxnHandle
from dgraph_tpu.client import STAMP_HEADER
from dgraph_tpu.serving import TooManyRequestsError
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS, TRACER
from dgraph_tpu.worker.remote import RetryBudgetExhausted
from dgraph_tpu.worker.tabletmove import TabletFencedError
from dgraph_tpu.zero.zero import TxnConflictError

# A kept connection with no request for this long is closed, so that
# clients that went away do not pin handler threads (the reason Go's
# net/http server, which upstream's alpha serves through, has one).
_IDLE_S = 120.0


class _Handler(BaseHTTPRequestHandler):
    server_version = "dgraph-tpu/0.1"
    # persistent connections: one thread serves a connection's requests
    # in turn (`handle`) until the client speaks HTTP/1.0, asks for a
    # close, goes quiet for `_IDLE_S` (`_Listener.service_actions`), or
    # a reply leaves the stream out of step (`send_response`). The
    # socket stays blocking: one with a timeout polls before every read
    # and write, and gives the interpreter's lock away twice for each.
    protocol_version = "HTTP/1.1"
    # a route that writes a reply's head and body apart would otherwise
    # wait for the client's delayed ACK of the head (Nagle)
    disable_nagle_algorithm = True
    engine: Server = None  # type: ignore[assignment]
    txns: Dict[int, TxnHandle] = {}
    txn_owner: Dict[int, str] = {}
    metrics: Dict[str, float] = {}
    # body and reply sizes of the request in hand (http.request's attrs)
    _bytes_in = 0
    _bytes_out = 0
    # of the request in hand: body bytes read, whether a reply began
    _body_read = 0
    _replied = False

    def __init__(self, request, client_address, server, front=None):
        # the request's front door (`_Front`), None with TRACE off: the
        # listener's for a connection's first, `_arrived`'s for the rest
        self._front = front
        super().__init__(request, client_address, server)

    def log_message(self, *a):  # quiet
        pass

    # -- the connection ------------------------------------------------------

    def handle(self):
        # BaseHTTPRequestHandler.handle's loop, each request awaited by
        # `_arrived`; on a kept connection `http.tail` ends where the
        # thread turns to the next request, on the last in `finish`
        while self._arrived():
            self._body_read, self._replied = 0, False
            self.handle_one_request()
            if self.close_connection:
                return
            if self._front is not None:
                self._front.closed()
                self._front = None

    def _arrived(self) -> bool:
        """Wait for the next request's first bytes. False where the
        client closed or reset, or the listener closed the connection
        (idle for `_IDLE_S`, or the server closing). With TRACE on, a
        kept connection's request gets its front door here: its head,
        and the instant that stands in for the accept, begin where the
        read returns."""
        server = self.server
        with server.idle_lock:
            if server.closing:
                return False
            server.idle[self.connection] = time.monotonic()
        try:
            arrived = bool(self.rfile.peek(1))
        except OSError:  # reset
            return False
        finally:
            with server.idle_lock:
                server.idle.pop(self.connection, None)
        if arrived and self._front is None and observe.trace_enabled():
            head = observe.Stretch("http.head")
            self._front = _Front(server.clients, head.start, None, None)
            self._front.head = head
        return arrived

    def finish(self):
        try:
            super().finish()
        finally:
            if self._front is not None:
                self._front.closed()

    def send_response(self, code, message=None):
        # a reply after which the client's stream may be out of step
        # says so, and the connection closes after it
        in_step = self.close_connection or self._in_step()
        self._replied = True
        super().send_response(code, message)
        if not in_step:
            self.send_header("Connection", "close")

    def _in_step(self) -> bool:
        """Whether the next request begins where this one's body ended:
        no reply begun yet, and the body read whole, not chunked."""
        headers = getattr(self, "headers", None)
        if self._replied or headers is None or "Transfer-Encoding" in headers:
            return False
        try:
            return self._body_read >= int(headers.get("Content-Length") or 0)
        except ValueError:
            return False

    # -- helpers -------------------------------------------------------------

    def _body(self) -> bytes:
        n = int(self.headers.get("Content-Length", 0))
        self._bytes_in = n
        data = self.rfile.read(n) if n else b""
        self._body_read = len(data)
        if len(data) < n:  # the client closed part way
            raise ValueError(f"request body ends at byte {len(data)} of {n}")
        return data

    def _reply(self, obj, code=200):
        # `http.reply`: response assembly and the socket write; a fine
        # span, so it is recorded only below `http.request` (the /query
        # and /mutate routes) and is a no-op on every other route
        with TRACER.span("http.reply", cpu=True, fine=True) as sp:
            sp.attrs["bytes"] = self._bytes_out = self._write(obj, code)

    def _write(self, obj, code) -> int:
        if self._replied:
            # an error after the reply began: a second reply would be
            # read as the answer to the client's next request
            self.close_connection = True
            return 0
        # responses whose `data` carries pre-encoded wire bytes (the
        # streaming arena encoder, query/streamjson.py) are SPLICED —
        # the result tree never runs through json.dumps a second time
        raw = (
            streamjson.response_bytes(obj)
            if isinstance(obj, dict)
            else None
        )
        data = raw if raw is not None else json.dumps(obj).encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        # end_headers with the body: one send, so one turn at the
        # interpreter's lock where two sends would wait twice
        self._headers_buffer.extend((b"\r\n", data))
        self.flush_headers()
        return len(data)

    def _error(self, msg, code=400):
        self._reply(
            {"errors": [{"message": str(msg), "extensions": {"code": "Error"}}]},
            code,
        )

    def _count(self, name):
        self.metrics[name] = self.metrics.get(name, 0) + 1

    # -- routes ---------------------------------------------------------------

    def do_GET(self):
        if self._front is not None:
            self._front.head.close()
        parsed_url = urlparse(self.path)
        path = parsed_url.path
        get_qs = parse_qs(parsed_url.query)
        if path == "/graphql":
            from dgraph_tpu.api import ws

            if ws.is_upgrade(self.headers):
                # GraphQL subscriptions over websocket (ref
                # graphql/subscription/poller.go transport)
                if ws.handshake(self):
                    ws.serve_graphql_ws(self, self.engine)
                self.close_connection = True
                return
        if path == "/health":
            from dgraph_tpu.x import device

            # platform / device_kind / device_count: a client can refuse
            # an answer that did not come from a chip
            self._reply(
                [
                    {
                        "instance": "alpha",
                        "status": "healthy",
                        "version": "0.1.0",
                        "uptime": int(time.time() - _START),
                        **device.info(),
                    }
                ]
            )
        elif path == "/state":
            self._reply(
                {
                    "counter": self.engine.zero.max_assigned,
                    "maxUID": self.engine.zero._max_uid,
                    "groups": {"1": {"tablets": {
                        p: {"predicate": p}
                        for p in self.engine.schema.predicates()
                    }}},
                }
            )
        elif path == "/admin/schema":
            from dgraph_tpu.admin.export import _schema_line

            lines = [
                _schema_line(self.engine.schema.get(p))
                for p in self.engine.schema.predicates()
            ]
            self._reply({"data": {"schema": "\n".join(lines)}})
        elif path == "/debug/traces":
            from dgraph_tpu.utils.observe import TRACER

            # a cluster engine (ProcCluster) merges every process's
            # spans; single-process engines serve the local ring
            merged_traces = getattr(self.engine, "merged_traces", None)
            if merged_traces is not None:
                out = {"spans": merged_traces(200)}
            else:
                out = {"spans": TRACER.recent(200)}
            if get_qs.get("requests"):
                # ?requests=<n>: this process's newest n request records
                # (per-span-name self wall and CPU time, counts, bytes;
                # `detail` false marks a tree without its fine spans)
                out["requests"] = TRACER.request_records(
                    int(get_qs["requests"][0])
                )
            self._reply(out)
        elif path == "/debug/tablets":
            from dgraph_tpu.utils.observe import TABLETS

            # cluster engines merge every alpha's traffic rows (plus
            # unreachable_instances); single-process engines serve the
            # local accumulator
            merged_tablets = getattr(self.engine, "merged_tablets", None)
            if merged_tablets is not None:
                self._reply(merged_tablets())
            else:
                TABLETS.publish()
                self._reply({"tablets": TABLETS.snapshot()})
        elif path == "/debug/healthz":
            from dgraph_tpu.utils import observe

            health = getattr(self.engine, "health", None)
            self._reply(health() if health is not None else observe.healthz())
        elif path == "/debug/digests":
            # cluster engines merge every process's digest store
            # (rows summed by (ns, shape)); single-process engines
            # serve the local store
            merged_digests = getattr(self.engine, "merged_digests", None)
            if merged_digests is not None:
                self._reply(merged_digests())
            else:
                from dgraph_tpu.serving.digest import DIGESTS

                self._reply({"digests": DIGESTS.snapshot()})
        elif path == "/debug/history":
            from dgraph_tpu.utils.observe import HISTORY

            try:
                window = float(get_qs.get("window", ["600"])[0])
            except ValueError:
                window = 600.0
            merged_history = getattr(self.engine, "merged_history", None)
            if merged_history is not None:
                self._reply(merged_history(window))
            else:
                self._reply(HISTORY.report(window))
        elif path == "/debug/profile":
            from dgraph_tpu.utils.profiler import AUTO, PROFILER

            if get_qs.get("last"):
                folded = AUTO.last() or ""
                data = folded.encode()
                self.send_response(200 if folded else 404)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            else:
                try:
                    seconds = float(get_qs.get("seconds", ["5"])[0])
                except ValueError:
                    seconds = 5.0
                data = PROFILER.profile(
                    min(max(seconds, 0.05), 60.0)
                ).encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
        elif path == "/debug/slowlog":
            from dgraph_tpu.utils.observe import slow_query_log

            body = b""
            log = slow_query_log()
            if log is not None:
                try:
                    with open(log.path, "rb") as f:
                        body = f.read()
                except OSError:
                    body = b""
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif path == "/debug/config":
            from dgraph_tpu.x import config as _cfg

            self._reply(_cfg.resolved())
        elif path == "/debug/bundle":
            bundle = getattr(self.engine, "debug_bundle", None)
            if bundle is None:
                return self._error("no cluster engine behind this facade", 404)
            try:
                window = float(get_qs.get("window", ["600"])[0])
            except ValueError:
                window = 600.0
            self._reply(bundle(window))
        elif path == "/debug/openmetrics":
            from dgraph_tpu.utils.observe import METRICS

            data = METRICS.render_openmetrics().encode()
            self.send_response(200)
            self.send_header(
                "Content-Type",
                "application/openmetrics-text; version=1.0.0; "
                "charset=utf-8",
            )
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        elif path == "/debug/prometheus_metrics":
            from dgraph_tpu.utils.observe import METRICS

            out = []
            for k, v in sorted(self.metrics.items()):
                out.append(f"# TYPE dgraph_tpu_http_{k} counter")
                out.append(f"dgraph_tpu_http_{k} {v}")
            # cluster engines scrape every alpha/zero process and merge
            # (counters summed, histogram buckets merged, per-instance
            # labels); single-process engines render the local registry
            merged = getattr(self.engine, "merged_metrics", None)
            out.append(merged() if merged is not None else METRICS.render())
            data = ("\n".join(out) + "\n").encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/plain")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        else:
            self._error(f"no route {path}", 404)

    def do_POST(self):
        t0 = time.time()
        front = self._front
        parsed = urlparse(self.path)
        path = parsed.path
        if path not in ("/query", "/mutate"):
            if front is not None:
                front.head.close()
            return self._post(t0, parsed, path)
        # `http.request`: the root of a served request's span tree, from
        # the handler's first line to after the reply has been written
        # (the request line and headers were parsed before do_POST:
        # `http.head`; after it the thread turns to the connection's
        # next request, or closes it: `http.tail`)
        self._bytes_in = self._bytes_out = 0
        root = (TRACER.span("http.request", cpu=True, path=path)
                if front is None
                else front.open_root(path, self.headers.get(STAMP_HEADER)))
        with root:
            self._post(t0, parsed, path)
            root.attrs["bytes_in"] = self._bytes_in
            root.attrs["bytes_out"] = self._bytes_out
        if front is not None:
            front.replied(root)

    def _post(self, t0, parsed, path):
        qs = parse_qs(parsed.query)
        token = self.headers.get("X-Dgraph-AccessToken")
        # admin/DDL routes are guardian-only once ACL is enabled
        # (ref edgraph alter/admin guardian checks)
        _GUARDED = (
            "/alter", "/admin", "/admin/export", "/admin/backup",
            "/admin/restore", "/admin/cdc",
            "/admin/schema/graphql", "/admin/draining", "/admin/shutdown",
            "/admin/task",
            # GraphQL resolvers run inside the engine without per-predicate
            # enforcement this round; guardian-only when ACL is on (the
            # reference gates GraphQL with its own @auth system instead)
            "/graphql",
        )
        try:
            if self.engine.acl is not None and path in _GUARDED:
                if not self.engine.acl.is_guardian(token):
                    return self._error(
                        "only guardians can access this endpoint", 403
                    )
            if self.engine.acl is not None and path == "/commit":
                # commits/aborts are bound to the txn owner's identity
                ts_q = int(qs.get("startTs", ["0"])[0])
                owner = self.txn_owner.get(ts_q)
                try:
                    caller = self.engine.acl.claims(token)["userid"] if token else None
                except Exception:
                    caller = None
                if caller is None or (owner is not None and owner != caller):
                    return self._error(
                        "access token required to commit this transaction", 401
                    )
            if path == "/login":
                if self.engine.acl is None:
                    return self._error("ACL not enabled", 400)
                body = json.loads(self._body().decode("utf-8"))
                if body.get("refreshToken"):
                    toks = self.engine.acl.refresh(body["refreshToken"])
                    self.engine._audit("login-refresh")
                else:
                    toks = self.engine.login(
                        body.get("userid", ""),
                        body.get("password", ""),
                        int(body.get("namespace", 0)),
                    )
                self._reply({"data": toks})
            elif path == "/query":
                self._count("num_queries")
                if qs.get("respFormat", [""])[0] == "rdf":
                    raw = self._body().decode("utf-8")
                    rdf = self.engine.query_rdf(raw)
                    data = rdf.encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/n-quads")
                    self.send_header("Content-Length", str(len(data)))
                    self.end_headers()
                    self.wfile.write(data)
                    self._bytes_out = len(data)
                    return
                variables = None
                # EXPLAIN/ANALYZE: ?debug=true (the reference's debug
                # query param) or a "debug": true JSON body field turns
                # on plan capture; data bytes are unchanged by it
                debug = qs.get("debug", ["false"])[0] == "true"
                # `http.read`: the body off the socket and the JSON
                # decode of its envelope
                with TRACER.span("http.read", cpu=True, fine=True):
                    raw = self._body().decode("utf-8")
                    if "json" in self.headers.get("Content-Type", ""):
                        body = json.loads(raw)
                        if not isinstance(body, dict):
                            raise ValueError(
                                "JSON query body must be an object"
                            )
                        raw = body.get("query", "")
                        variables = body.get("variables")
                        if variables is not None and not isinstance(
                            variables, dict
                        ):
                            raise ValueError('"variables" must be an object')
                        # accept only explicit truthy spellings: a client
                        # sending the STRING "false" must not enable debug
                        debug = body.get("debug", debug) in (
                            True, "true", "1",
                        )
                timeout_ms = None
                if qs.get("timeout"):
                    t = qs["timeout"][0]  # "5s" / "500ms" (ref ?timeout=)
                    timeout_ms = (
                        float(t[:-2]) if t.endswith("ms")
                        else float(t.rstrip("s")) * 1e3
                    )
                res = self.engine.query(
                    raw,
                    access_jwt=token,
                    variables=variables,
                    timeout_ms=timeout_ms,
                    # serving surface: data stays wire bytes end-to-end
                    # (no dict parse-back; _reply splices the arena)
                    want="raw",
                    debug=debug,
                )
                # keep the engine's server_latency/profile/trace_id and
                # stamp the HTTP-layer total on top (reference envelope)
                ext = res.setdefault("extensions", {})
                lat = ext.setdefault("server_latency", {})
                lat["total_ns"] = int((time.time() - t0) * 1e9)
                self._reply(res)
            elif path == "/mutate":
                if getattr(self.engine, "draining", False):
                    return self._error(
                        "the server is in draining mode", 503
                    )
                self._count("num_mutations")
                self._handle_mutate(qs, token)
            elif path == "/commit":
                ts = int(qs.get("startTs", ["0"])[0])
                txn = self.txns.pop(ts, None)
                self.txn_owner.pop(ts, None)
                if txn is None:
                    return self._error(f"no pending txn with startTs {ts}")
                if qs.get("abort", ["false"])[0] == "true":
                    txn.discard()
                    return self._reply({"data": {"code": "Success", "message": "Done"}})
                commit_ts = txn.commit()
                self._reply({"data": {"code": "Success", "commitTs": commit_ts}})
            elif path == "/alter":
                if getattr(self.engine, "draining", False):
                    return self._error("the server is in draining mode", 503)
                body = self._body().decode("utf-8")
                try:
                    op = json.loads(body)
                except json.JSONDecodeError:
                    op = {"schema": body}
                if op.get("drop_all"):
                    self.engine.alter(drop_all=True)
                elif op.get("drop_attr"):
                    self.engine.alter(drop_attr=op["drop_attr"])
                else:
                    self.engine.alter(op.get("schema", ""))
                self._reply({"data": {"code": "Success", "message": "Done"}})
            elif path == "/graphql":
                body = json.loads(self._body().decode("utf-8"))
                gql = getattr(self.engine, "graphql", None)
                if gql is None:
                    return self._error("no GraphQL schema configured", 400)
                # @auth JWT: read the header named by Dgraph.Authorization
                token = None
                if gql.auth_config is not None:
                    token = self.headers.get(gql.auth_config.header)
                self._reply(
                    gql.execute(
                        body.get("query", ""),
                        body.get("variables"),
                        jwt_token=token,
                    )
                )
            elif path == "/admin":
                # the admin GraphQL schema (ref graphql/admin/admin.go)
                from dgraph_tpu.graphql.admin import AdminGraphQL

                body = json.loads(self._body().decode("utf-8"))
                self._reply(
                    AdminGraphQL(self.engine).execute(
                        body.get("query", ""), body.get("variables")
                    )
                )
            elif path == "/admin/schema/graphql":
                # upload an SDL schema (ref graphql/admin updateGQLSchema)
                from dgraph_tpu.graphql import GraphQLServer

                sdl = self._body().decode("utf-8")
                self.engine.graphql = GraphQLServer(self.engine, sdl)
                self._reply({"data": {"code": "Success", "message": "Done"}})
            elif path == "/admin/export":
                import tempfile

                from dgraph_tpu.admin import tasks

                out_dir = qs.get(
                    "destination", [tempfile.mkdtemp(prefix="dgraph_export_")]
                )[0]
                tid = tasks.enqueue_export(self.engine, out_dir)
                st = tasks._queue_of(self.engine).wait(tid)
                ok = st.get("status") == "Success"
                self._reply(
                    {"data": {"code": st.get("status", "Unknown"), **st}},
                    200 if ok else 500,
                )
            elif path == "/admin/backup":
                from dgraph_tpu.admin import tasks

                dest = qs.get("destination", ["/tmp/dgraph_tpu_backup"])[0]
                full = qs.get("full", ["false"])[0] == "true"
                tid = tasks.enqueue_backup(
                    self.engine, dest, incremental=not full
                )
                if qs.get("wait", ["true"])[0] == "true":
                    # distributed online backups can legitimately run
                    # long (move drains alone cost up to the fence
                    # deadline per tablet) — the queue default of 30s
                    # would 500 a backup that later succeeds
                    st = tasks._queue_of(self.engine).wait(
                        tid, timeout=300
                    )
                    ok = st.get("status") == "Success"
                    self._reply(
                        {"data": {"code": st.get("status", "Unknown"), **st}},
                        200 if ok else 500,
                    )
                else:
                    self._reply(
                        {"data": {"code": "Success", "taskId": f"{tid:#x}"}}
                    )
            elif path == "/admin/restore":
                from dgraph_tpu.admin import tasks

                src = qs.get("source", [""])[0]
                if not src:
                    return self._error("restore needs ?source=<dir>")
                tid = tasks.enqueue_restore(self.engine, src)
                if qs.get("wait", ["true"])[0] == "true":
                    st = tasks._queue_of(self.engine).wait(tid, timeout=300)
                    ok = st.get("status") == "Success"
                    self._reply(
                        {"data": {"code": st.get("status", "Unknown"), **st}},
                        200 if ok else 500,
                    )
                else:
                    self._reply(
                        {"data": {"code": "Success", "taskId": f"{tid:#x}"}}
                    )
            elif path == "/admin/cdc":
                from dgraph_tpu.admin.cdc import cdc_for_uri

                sink = qs.get("sink", [""])[0]
                cdc = getattr(self.engine, "_cdc", None)
                if qs.get("disable", [""])[0] == "true":
                    if cdc is not None:
                        cdc.close()
                    self._reply({"data": {"code": "Success",
                                          "enabled": False}})
                elif sink:
                    if cdc is not None:
                        cdc.close()
                    cdc = cdc_for_uri(self.engine, sink)
                    self._reply(
                        {
                            "data": {
                                "code": "Success",
                                "enabled": True,
                                "sink": sink,
                                "checkpoint": cdc.checkpoint,
                            }
                        }
                    )
                else:
                    # status probe; `dead` means the emitter thread is
                    # gone and events defer to replay — re-enable with
                    # ?sink= to recover the stream
                    self._reply(
                        {
                            "data": {
                                "enabled": cdc is not None,
                                "sink": getattr(cdc, "sink_uri", None),
                                "checkpoint": (
                                    cdc.checkpoint if cdc else 0
                                ),
                                "dead": bool(
                                    cdc is not None and cdc.dead
                                ),
                            }
                        }
                    )
            elif path == "/admin/task":
                tid = int(qs.get("id", ["0"])[0], 16)
                from dgraph_tpu.admin import tasks

                st = tasks._queue_of(self.engine).status(tid)
                if st is None:
                    return self._error(f"no task {tid:#x}", 404)
                self._reply({"data": st})
            elif path == "/admin/draining":
                enable = qs.get("enable", ["true"])[0] == "true"
                self.engine.draining = enable
                self._reply(
                    {"data": {"code": "Success",
                              "message": f"draining mode set to {enable}"}}
                )
            elif path == "/admin/shutdown":
                self._reply({"data": {"code": "Success", "message": "Done"}})
                threading.Thread(
                    target=self.server.shutdown, daemon=True
                ).start()
            else:
                self._error(f"no route {path}", 404)
        except TooManyRequestsError as e:
            # admission shed: retryable — clients back off and resend
            self._reply(
                {
                    "errors": [
                        {
                            "message": str(e),
                            "extensions": {
                                "code": TooManyRequestsError.code,
                                "retryable": True,
                            },
                        }
                    ]
                },
                429,
            )
        except TabletFencedError as e:
            # tablet move fence: the window is bounded (or awaiting
            # recovery) — retryable, never wrong data
            self._reply(
                {
                    "errors": [
                        {
                            "message": str(e),
                            "extensions": {
                                "code": TabletFencedError.code,
                                "retryable": True,
                            },
                        }
                    ]
                },
                503,
            )
        except RetryBudgetExhausted as e:
            # the query's retry/hedge budget ran dry (brownout): shed
            # retryable instead of letting clients amplify the storm
            self._reply(
                {
                    "errors": [
                        {
                            "message": str(e),
                            "extensions": {
                                "code": RetryBudgetExhausted.code,
                                "retryable": True,
                            },
                        }
                    ]
                },
                503,
            )
        except TxnConflictError as e:
            self._error(f"Transaction has been aborted. Please retry. {e}", 409)
        except (AclError, JwtError) as e:
            self._error(e, 401)
        except (json.JSONDecodeError, ValueError, ParseError, QueryError) as e:
            self._error(e, 400)  # malformed client input/query
        except Exception as e:
            traceback.print_exc()
            self._error(e, 500)

    def _handle_mutate(self, qs, token=None):
        ctype = self.headers.get("Content-Type", "application/rdf")
        with TRACER.span("http.read", cpu=True, fine=True):
            body = self._body().decode("utf-8")
            if "json" in ctype:
                obj = json.loads(body) if body.strip() else {}
        commit_now = qs.get("commitNow", ["false"])[0] == "true"
        start_ts = int(qs.get("startTs", ["0"])[0])

        # `mutate`: the transaction, its N-Quads' parse and apply
        # (`mutate.parse`, `mutate.apply`) and, with commitNow, its
        # commit (`commit.wait`, server.py)
        with TRACER.span("mutate", cpu=True):
            if start_ts and start_ts in self.txns:
                txn = self.txns[start_ts]
            else:
                txn = self.engine.new_txn()

            if "json" in ctype:
                uids = txn.mutate_json(
                    set_obj=obj.get("set"),
                    del_obj=obj.get("delete"),
                    access_jwt=token,
                )
            else:
                # RDF body: {set { ... } delete { ... }} or bare nquads
                set_rdf, del_rdf = _split_rdf_blocks(body)
                uids = txn.mutate_rdf(
                    set_rdf=set_rdf, del_rdf=del_rdf, access_jwt=token
                )

            if commit_now:
                self.txns.pop(txn.start_ts, None)  # finished: no linger
                commit_ts = txn.commit()
        if commit_now:
            self._reply(
                {
                    "data": {
                        "code": "Success",
                        "uids": uids,
                        "commitTs": commit_ts,
                    }
                }
            )
        else:
            self.txns[txn.start_ts] = txn
            if self.engine.acl is not None and token:
                try:
                    self.txn_owner[txn.start_ts] = self.engine.acl.claims(
                        token
                    )["userid"]
                except Exception:
                    pass
            self._reply(
                {"data": {"code": "Success", "uids": uids, "startTs": txn.start_ts}}
            )


_START = time.time()


def _scan_block(body: str, keyword: str) -> str:
    """Extract the `keyword { ... }` block with quote-aware brace scanning
    ('}' inside RDF string literals, e.g. GeoJSON values, must not
    terminate the block; ref chunker mutation lexing)."""
    import re

    m = re.search(rf"\b{keyword}\s*\{{", body)
    if not m:
        return ""
    i = m.end()
    in_quote = False
    n = len(body)
    start = i
    while i < n:
        c = body[i]
        if in_quote:
            if c == "\\":
                i += 2
                continue
            if c == '"':
                in_quote = False
        elif c == '"':
            in_quote = True
        elif c == "}":
            return body[start:i]
        i += 1
    return body[start:]


def _split_rdf_blocks(body: str):
    """Parse `{ set { ... } delete { ... } }` mutation envelopes
    (ref chunker mutation parsing); bare N-Quads treated as set."""
    set_block = _scan_block(body, "set")
    del_block = _scan_block(body, "delete")
    if set_block or del_block:
        return set_block, del_block
    return body, ""


_STAMP_MAX_S = 60.0  # a client stamp further from the accept is implausible
_CLIENTS_KEPT = 4096  # clients whose last reply `_Listener` remembers


def _backlog(listening: socket.socket) -> Optional[int]:
    """Connections waiting in a listening socket's accept queue: Linux's
    TCP_INFO, whose `tcpi_unacked` holds `sk_ack_backlog` on a listening
    socket and `tcpi_sacked` its limit, `sk_max_ack_backlog`. None where
    the platform has no such option, or fills neither (a limit of 0)."""
    try:
        info = listening.getsockopt(socket.IPPROTO_TCP, socket.TCP_INFO, 32)
    except (AttributeError, OSError):
        return None
    waiting, limit = struct.unpack_from("II", info, 24)
    return waiting if limit else None


class _Front:
    """One request's front door, with TRACE on: the instant it was
    taken up (`accepted`) and the two stretches of the handler thread
    outside `http.request` (`observe.Stretch`). On a connection's first
    request `accepted` is the listener's accept, and `http.head` runs
    from the thread's first line; on a kept connection's later request
    both are the moment the thread's read of the request line returned
    (`_Handler._arrived`), so the idle time between two requests is in
    neither. `http.head` ends at do_POST's first lines, where
    `http.request` opens (the request line, the headers, the client's
    stamp); `http.tail` runs from the written reply to the thread's
    turn to the connection's next request, or to the end of its
    handler. Their numbers reach the request's record as attrs of
    `http.request`: `handoff_ms` (`accepted` to the head: on a first
    request the thread's start and its wait for the interpreter's lock,
    on a later one 0), `head_ms`, `tail_ms`, on a first request
    `backlog` and `accept_gap_ms` (since the listener's previous
    accept), and under a profiler session `head_cpu_ms` and
    `tail_cpu_ms` (attrs, not self CPU: the spans' readers read what
    they read before). A client's stamp (`dgraph_tpu/client.py`) adds
    `connect_ms` (the stamp to `accepted`) and `client_gap_ms`.
    `tail_ms`, `tail_cpu_ms` and `client_gap_ms` are set by `closed`,
    after the root has finished."""

    __slots__ = ("clients", "accepted", "accept_gap_ms", "backlog", "head",
                 "root", "tail", "stamp", "prev", "replied_at")

    def __init__(self, clients, accepted, accept_gap_ms, backlog):
        self.clients = clients
        self.accepted = accepted
        self.accept_gap_ms = accept_gap_ms
        self.backlog = backlog
        self.head = self.root = self.tail = self.stamp = self.prev = None
        self.replied_at: Optional[float] = None

    def open_root(self, path: str, stamp: Optional[str]):
        """The client's stamp read, the head closed and the request's
        `http.request` opened at once, in the head's trace, with the
        front door's attrs. `head_ms` runs to the root's start, so that
        a client's pieces tile its loop from one stamp to the next."""
        client = self._read_stamp(stamp) if stamp is not None else None
        head = self.head
        head.close()
        root = TRACER.span("http.request", cpu=True, path=path,
                           trace_id=head.trace_id)
        if not isinstance(root, observe.Span):
            return root
        attrs = {"handoff_ms": (head.start - self.accepted) * 1e3,
                 "head_ms": (root.start - head.start) * 1e3}
        if self.backlog is not None:
            attrs["backlog"] = self.backlog
        if self.accept_gap_ms is not None:
            attrs["accept_gap_ms"] = self.accept_gap_ms
        if head.cpu_ms is not None:
            attrs["head_cpu_ms"] = head.cpu_ms
        if client is not None:
            attrs["connect_ms"] = (self.accepted - self.stamp) * 1e3
            self.prev = self.clients.swap(client, self)
        root.attrs.update(attrs)
        return root

    def _read_stamp(self, stamp: str):
        """(client id, sequence number) of a plausible stamp, its instant
        in `self.stamp`; None, counted, for any other."""
        try:
            client, seq, ns = stamp.split(" ")
            seq, at = int(seq), int(ns) / 1e9
        except ValueError:
            at = None
        if at is None or not 0.0 <= self.accepted - at <= _STAMP_MAX_S:
            METRICS.inc("http_client_stamp_dropped_total")
            return None
        self.stamp = at
        return client, seq

    def replied(self, root) -> None:
        if isinstance(root, observe.Span):
            self.root, self.replied_at = root, root.end
            self.tail = observe.Stretch("http.tail", root.trace_id)

    def closed(self) -> None:
        """The request is done with: the tail's and the client's attrs."""
        self.head.close()
        root = self.root
        if root is None:
            return
        tail = self.tail
        tail.close()
        attrs = {"tail_ms": (tail.end - tail.start) * 1e3}
        if tail.cpu_ms is not None:
            attrs["tail_cpu_ms"] = tail.cpu_ms
        prev = self.prev
        if prev is not None and prev.replied_at is not None:
            # negative where the client had the reply and stamped its
            # next request before that handler thread closed its root:
            # it is the root's end that makes the pieces tile
            gap = self.stamp - prev.replied_at
            if abs(gap) <= _STAMP_MAX_S:
                attrs["client_gap_ms"] = gap * 1e3
        root.attrs.update(attrs)
        # kept by `_ClientReplies` until the client's next request: no
        # tree, and no chain of its predecessors, with it
        self.root = self.prev = None


class _ClientReplies:
    """The newest traced request (`_Front`) of each stamped client, the
    oldest client out past `_CLIENTS_KEPT`: where its next request finds
    the moment its previous reply was written (`client_gap_ms`)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._last: "OrderedDict[str, tuple]" = OrderedDict()

    def swap(self, client, front):
        """Keep `front` as client[0]'s newest request, numbered
        client[1]; return the request numbered one before, if that was
        the one kept."""
        cid, seq = client
        with self._lock:
            prev = self._last.pop(cid, None)
            self._last[cid] = (seq, front)
            if len(self._last) > _CLIENTS_KEPT:
                self._last.popitem(last=False)
        return prev[1] if prev is not None and prev[0] == seq - 1 else None


class _Listener(ThreadingHTTPServer):
    # socketserver's default listen backlog is 5: a burst of 64 clients
    # connecting at once overflowed it and saw connection resets
    request_queue_size = 128

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._fronts: Dict[socket.socket, _Front] = {}
        self._last_accept: Optional[float] = None
        self._tcp_info = True  # until the platform shows it fills none
        self.clients = _ClientReplies()
        # connections whose thread waits for their next request
        # (`_Handler._arrived`), with the moment it began to
        self.idle: Dict[socket.socket, float] = {}
        self.idle_lock = threading.Lock()
        self.closing = False

    def get_request(self):
        # One serial thread, once a connection: with TRACE on it keeps
        # the accept's instant and the queue behind it for the
        # connection's first request, and nothing more.
        sock, addr = self.socket.accept()
        if observe.trace_enabled():
            now, last = time.time(), self._last_accept
            backlog = _backlog(self.socket) if self._tcp_info else None
            self._tcp_info = backlog is not None
            self._fronts[sock] = _Front(
                self.clients, now,
                None if last is None else (now - last) * 1e3, backlog)
            self._last_accept = now
        return sock, addr

    def process_request_thread(self, request, client_address):
        # ThreadingMixIn's, with the connection counted and its first
        # request's front door handed to its handler
        front = self._fronts.pop(request, None)
        if front is not None:
            front.head = observe.Stretch("http.head")
        METRICS.inc("http_connections_total")
        try:
            self.RequestHandlerClass(request, client_address, self, front)
        except Exception:
            self.handle_error(request, client_address)
        finally:
            self.shutdown_request(request)

    def service_actions(self):
        # serve_forever's turn after each accept or half second: a kept
        # connection idle for `_IDLE_S` is closed, and its thread ends
        now = time.monotonic()
        with self.idle_lock:
            stale = [s for s, t in self.idle.items() if now - t > _IDLE_S]
            for sock in stale:
                del self.idle[sock]
                _hang_up(sock)

    def server_close(self):
        # as Go's Server.Shutdown: the kept connections that wait for a
        # request are closed, a request in hand is answered first; then
        # ThreadingMixIn joins the handler threads
        with self.idle_lock:
            self.closing = True
            for sock in self.idle:
                _hang_up(sock)
        super().server_close()


def _hang_up(sock: socket.socket) -> None:
    """End a connection whose thread waits in a read: the read returns
    empty, and the thread closes it."""
    try:
        sock.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass


class HTTPServer:
    """Embeddable HTTP server (the Alpha's 8080 surface)."""

    def __init__(self, engine: Server, host: str = "127.0.0.1", port: int = 8080):
        handler = type(
            "BoundHandler",
            (_Handler,),
            {"engine": engine, "txns": {}, "txn_owner": {}, "metrics": {}},
        )
        self.httpd = _Listener((host, port), handler)
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()
