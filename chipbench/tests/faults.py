"""Drive a rehearsal run of one cell with the timed path broken
underneath: an answer altered where it is produced. `correct` has to
come out false (test_chipbench.py, beside this file, runs it in a
process of its own)."""

from __future__ import annotations

import sys


def break_setops() -> None:
    """Every row the dispatcher's shared-operand kernel returns loses
    its lower half (the ids below the row's median)."""
    import numpy as np

    from dgraph_tpu.query.dispatch import DISPATCHER

    orig = DISPATCHER.run_rows_vs_one

    def broken(op, rows, b, *a, **kw):
        return [np.asarray(r)[len(r) // 2:]
                for r in orig(op, rows, b, *a, **kw)]

    DISPATCHER.run_rows_vs_one = broken


def break_vector_search() -> None:
    """Every third search names a wrong neighbour."""
    from dgraph_tpu.models import vector

    orig = vector.VectorIndex.search_one
    calls = [0]

    def broken(self, q, k):
        uids = orig(self, q, k).copy()
        calls[0] += 1
        if calls[0] % 3 == 0 and len(uids):
            uids[0] ^= 1  # the row next door: some other cluster's
        return uids

    vector.VectorIndex.search_one = broken


FAULTS = {"setops": break_setops, "vector_search": break_vector_search}


def main(argv) -> int:
    fault, rest = argv[0], argv[1:]
    import dgraph_tpu  # noqa: F401  (places the compile cache before jax)

    FAULTS[fault]()
    from chipbench import run

    return run.main(rest + ["--rehearsal"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
