"""Fault `setops`: every row the dispatcher's shared-operand kernel
returns loses its lower half (the ids below the row's median). It breaks
a mix whose requests intersect or subtract a level's rows on the chip."""

from __future__ import annotations


def plant() -> None:
    import numpy as np

    from dgraph_tpu.query.dispatch import DISPATCHER

    orig = DISPATCHER.run_rows_vs_one

    def broken(op, rows, b, *a, **kw):
        return [np.asarray(r)[len(r) // 2:]
                for r in orig(op, rows, b, *a, **kw)]

    DISPATCHER.run_rows_vs_one = broken
