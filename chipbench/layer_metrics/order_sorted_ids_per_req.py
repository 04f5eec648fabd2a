"""Mean over the traced requests of the ids their ordered blocks and
rows handed to the value comparator (one stored value read per id and
key): the `process` span's `order_kept`. A request that ordered nothing
counts 0; None where no traced request carries the attr. Layer:
executor. Moves: qps."""

from chipbench.layer_metrics.order_buckets_per_req import mean_attr


def read(ctx):
    return mean_attr(ctx, "process.order_kept")
