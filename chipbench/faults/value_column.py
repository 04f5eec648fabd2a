"""Fault `value_column`: the program that narrows an order's candidates
to the window (`column#narrow`) is asked for half the window, so every
`order .. first: 20` over a resident value column comes back with the
ten newest and their ties. It breaks a mix whose requests order tens of
thousands of messages by date (LDBC's complex read 9); filters, and
orders the index walks serve, are left alone."""

from __future__ import annotations


def plant() -> None:
    from dgraph_tpu.query.dispatch import DISPATCHER

    orig = DISPATCHER.run_column

    def broken(use, ids, column, *scalars):
        if use == "narrow":
            scalars = (scalars[0] // 2, *scalars[1:])
        return orig(use, ids, column, *scalars)

    DISPATCHER.run_column = broken
