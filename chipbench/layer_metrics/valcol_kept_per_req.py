"""Mean over the traced requests of the ids a value column's narrowing
left for the value comparator: the `process` span's `column_kept`
(query/subgraph.py `_narrow_by_column`); with `first: 20`, twenty and
the ties of the twentieth date. None where no traced request carries
the attr. Layer: executor. Moves: qps."""

from chipbench.layer_metrics.order_buckets_per_req import mean_attr


def read(ctx):
    return mean_attr(ctx, "process.column_kept")
