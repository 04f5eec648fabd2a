"""Fault `order_single`: every block or row ordered by ONE key loses its
first id, the newest under `orderdesc: creationDate`. It breaks a mix
whose requests order a person's messages or a message's replies (LDBC's
short reads 2 and 7); an order of two keys or more (complex read 1) is
left alone."""

from __future__ import annotations


def plant() -> None:
    from dgraph_tpu.query.subgraph import Executor

    orig = Executor._order_uids

    def broken(self, gq, uids, full=False):
        out = orig(self, gq, uids, full)
        return out[1:] if len(gq.order) == 1 else out

    Executor._order_uids = broken
