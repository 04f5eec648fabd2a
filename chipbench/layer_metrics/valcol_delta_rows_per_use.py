"""Delta rows a use of a value column laid over its base, over the
window: the rise of `value_column_delta_rows_read_total` (the `rows`
of the `valcol.delta` spans, summed) over that of the column's
dispatches, `device_dispatch_total{family="column#filter"}` and
`{family="column#narrow"}` (query/valcol.py). Counted over the whole
window because complex read 9, the cell's one use, is 0.136% of its
requests. None where the program has no such counter or no use fell in
the window. Layer: device boundary. Moves: qps."""

from chipbench import write_spans

USES = ('device_dispatch_total{family="column#filter"}',
        'device_dispatch_total{family="column#narrow"}')


def read(ctx):
    moved = (ctx.get("describe") or {}).get("counters_in_window")
    rows = "value_column_delta_rows_read_total"
    if moved is None or not _declared(rows):
        return None
    uses = sum(write_spans.in_window(ctx, n) for n in USES)
    return write_spans.in_window(ctx, rows) / uses if uses else None


def _declared(name: str) -> bool:
    from dgraph_tpu.utils import observe

    return observe.registered_metric(name)
