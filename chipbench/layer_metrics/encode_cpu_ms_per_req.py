"""Mean CPU time a request spends producing its response's `data`
bytes (query/streamjson.py): self CPU of the span `encode`. Layer:
encode. Moves: qps."""

from chipbench import spans


def read(ctx):
    return spans.mean_self_cpu(ctx, ("encode",))
