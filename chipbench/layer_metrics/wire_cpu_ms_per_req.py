"""Mean CPU time a `/query` request spends in the HTTP handler itself:
self CPU of the spans `http.request`, `http.read` (body off the socket,
JSON envelope) and `http.reply` (response assembly, socket write). The
request line and headers are parsed before `do_POST` and are outside.
Layer: wire. Moves: qps."""

from chipbench import spans


def read(ctx):
    return spans.mean_self_cpu(
        ctx, ("http.request", "http.read", "http.reply"))
