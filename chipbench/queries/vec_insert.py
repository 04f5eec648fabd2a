"""Query kind `vec_insert`, which WRITES: a new uid with a vector drawn
from the mixture, one transaction committed at once. The client's next
read queries it (`chipbench/queries/vec_writes.py`)."""

from __future__ import annotations

from chipbench.data import mog_live
from chipbench.queries import vec_writes as w
from chipbench.queries.vec_writes import check, control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (uid, vector)."""
    uid = w.new_uid(params, client, seq)
    vec = w.drawn(catalog, rng)
    w.pend(catalog, rng, uid, vec, deleted=False)
    return (uid, vec), {"set": mog_live.literal(uid, vec)}


apply = w.written
