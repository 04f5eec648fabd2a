"""Fault `vector_write_behind`: the vector index applies every write one
write late: an insert or a delete is held until the next one arrives.
It breaks a mix whose reads follow their own client's vector writes
(`vec.insert16`'s session rule): the read right after a write misses
the row it wrote, or finds the row it deleted."""

from __future__ import annotations

import threading


def plant() -> None:
    from dgraph_tpu.models import vector

    insert, remove = vector.VectorIndex.insert, vector.VectorIndex.remove
    held: dict = {}
    lock = threading.Lock()

    def later(self, write) -> None:
        with lock:
            before = held.get(id(self))
            held[id(self)] = write
        if before is not None:
            before()

    def held_insert(self, uid, vec):
        later(self, lambda: insert(self, uid, vec))

    def held_remove(self, uid):
        later(self, lambda: remove(self, uid))

    vector.VectorIndex.insert = held_insert
    vector.VectorIndex.remove = held_remove
