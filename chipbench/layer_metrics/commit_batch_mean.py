"""Mean transactions a commit batch carried, over the `commit` spans of
the traced `/mutate` requests (one in the tree of the thread that ran
each batch; its `batch` attr, 1 for a serial commit): the group
commit's realized width under this traffic. None where no traced
write holds a `commit` span. Layer: commit. Moves: qps."""

from chipbench import write_spans


def read(ctx):
    recs = write_spans.records(ctx) or ()
    batches = sum(r["counts"].get("commit", 0) for r in recs)
    if not batches:
        return None
    return float(sum(r["attrs"].get("commit.batch", 0)
                     for r in recs)) / batches
