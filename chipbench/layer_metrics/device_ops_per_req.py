"""Jitted set-op and vector-search fetches (JitFetches, patched from
outside) in the window over the requests it answered. 0 is a reading: a
cell that never leaves the host kernels. Layer: device boundary. Moves:
qps."""


def read(ctx):
    if not ctx["requests"]:
        return None
    return sum(ctx["fetches"].values()) / ctx["requests"]
