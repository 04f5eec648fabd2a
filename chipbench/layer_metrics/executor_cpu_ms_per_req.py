"""Mean CPU time a request spends in the executor outside its level
reads, its device calls and its encoding: self CPU of the spans `query`
(result cache probe, profile, digests, slow-query log) and `process`
(query/subgraph.py, query/functions.py: filters, sorts, pagination).
Layer: executor. Moves: qps."""

from chipbench import spans


def read(ctx):
    return spans.mean_self_cpu(ctx, ("query", "process"))
