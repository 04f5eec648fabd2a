"""Rebuilds of the vector index's device snapshot inside the window: the
rise of the program's `vector_ivf_rebuilds_total` between the window's
first request and its last answer, as the maker reports it
(`chipbench/data/mog_live.py` `describe`). An index whose snapshot takes
the writes in place rebuilds none (expected 0); one that cannot stalls
every search behind a rebuild of the whole corpus. None where the
program has no such counter. Layer: vector index. Moves:
latency_p50_ms."""

from chipbench import write_spans

NAME = "vector_ivf_rebuilds_total"


def read(ctx):
    from dgraph_tpu.utils import observe

    if not observe.registered_metric(NAME):
        return None
    return write_spans.in_window(ctx, NAME)
