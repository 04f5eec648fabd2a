"""Fault `vector_search`: every third search names a wrong neighbour.
It breaks a mix whose requests are `similar_to` lookups."""

from __future__ import annotations


def plant() -> None:
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
