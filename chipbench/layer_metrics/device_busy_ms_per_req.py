"""Device busy time (union of op intervals in the traced window) over
the requests completed in that window. Layer: kernels. Moves: qps."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0 or not ctx["traced_requests"]:
        return None
    return 1e3 * t["busy_s"] / ctx["traced_requests"]
