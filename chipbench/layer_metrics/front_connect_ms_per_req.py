"""Median over the traced `/query` requests of `connect_ms`, an attr of
`http.request` (api/http_server.py `_Front`): from the client's stamp,
taken just before it sent the request, or opened the connection where
it opens one (dgraph_tpu/client.py), to the accept, or on a kept
connection to the read of the request line. The connect, the kernel's
accept queue, the listener's turn; on a kept connection the request's
wait in its socket for its thread and the thread's for the interpreter's
lock. Over the records that carry it; None where none does (a program
without the front door's attrs, a client that sends no stamp).
Layer: wire (front door). Moves: latency_p50_ms."""

import statistics

from chipbench import spans


def median_of(ctx, attr: str):
    """The median of one `http.request` attr over the traced requests
    that carry it; None where none does."""
    key = "http.request." + attr
    got = [r["attrs"][key] for r in spans.records(ctx) or ()
           if key in r["attrs"]]
    return float(statistics.median(got)) if got else None


def read(ctx):
    return median_of(ctx, "connect_ms")
