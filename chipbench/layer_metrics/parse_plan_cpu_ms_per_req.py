"""Mean CPU time a request spends parsing (or finding its plan in the
plan cache) and being admitted (admission gate, ACL, audit, read ts):
self CPU of the spans `parse` and `admit`. Layer: parse/plan. Moves:
qps."""

from chipbench import spans


def read(ctx):
    return spans.mean_self_cpu(ctx, ("parse", "admit"))
