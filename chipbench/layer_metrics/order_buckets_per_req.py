"""Mean over the traced requests of the index buckets their ordered
blocks and rows listed or read: the `process` span's `order_buckets`
(query/subgraph.py: the single-key walk and the multi-key window walk).
A request that ordered nothing counts 0; None where no traced request
carries the attr (a program from before it). Layer: executor. Moves:
qps."""

from chipbench import spans


def mean_attr(ctx, attr: str):
    """Mean of one summed span attr over the traced requests, 0 for a
    request without it; None where none carries it."""
    recs = spans.records(ctx)
    if not recs or not any(attr in r["attrs"] for r in recs):
        return None
    return spans.mean(ctx, lambda r: float(r["attrs"].get(attr, 0)))


def read(ctx):
    return mean_attr(ctx, "process.order_buckets")
