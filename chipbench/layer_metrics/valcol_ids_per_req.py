"""Mean over the traced requests of the ids they gave a resident value
column's programs, filters and orders together: the `process` span's
`column_cands` (query/valcol.py). None where no traced request carries
the attr (a program without value columns, or requests under the device
line). Layer: device boundary. Moves: qps."""

from chipbench.layer_metrics.order_buckets_per_req import mean_attr


def read(ctx):
    return mean_attr(ctx, "process.column_cands")
