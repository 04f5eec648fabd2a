"""IVF slab rows an update program of the vector index changed, over
the window: the rise of `vector_ivf_appended_rows_total` plus that of
`vector_ivf_tombstoned_rows_total` over that of
`vector_ivf_apply_programs_total` (models/vector.py: two slab rows for
each row appended or deleted; a search that finds writes pending
launches one cell assignment, where rows were appended, and one update
a bucket). How well pending writes batch into a program. None where the
program has no such counters or launched no update program in the
window. Layer: vector index. Moves: qps."""

from chipbench import write_spans

ROWS = ("vector_ivf_appended_rows_total", "vector_ivf_tombstoned_rows_total")
PROGRAMS = "vector_ivf_apply_programs_total"


def read(ctx):
    from dgraph_tpu.utils import observe

    if not observe.registered_metric(PROGRAMS):
        return None
    programs = write_spans.in_window(ctx, PROGRAMS)
    if not programs:
        return None
    return sum(write_spans.in_window(ctx, n) for n in ROWS) / programs
