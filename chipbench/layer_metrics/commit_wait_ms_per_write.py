"""Median over the traced `/mutate` requests of the wall time a write
spends committing: the self wall time of `commit.wait` (asking to
commit, parked while another thread's batch carries it, to its apply
barrier), plus `commit` and `valcol.patch` where the request's own
thread ran the batch (api/server.py, worker/groupcommit.py). None
where no traced write has a `commit.wait` span. Layer: commit. Moves:
latency_p50_ms."""

from chipbench import write_spans


def read(ctx):
    recs = write_spans.records(ctx)
    if not recs or not any("commit.wait" in r["counts"] for r in recs):
        return None
    return write_spans.median(ctx, lambda r: sum(
        r["self_wall_ms"].get(n, 0.0)
        for n in ("commit.wait", "commit", "valcol.patch")))
