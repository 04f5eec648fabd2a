"""Query kind `vec_reembed`, which WRITES: a loaded uid drawn uniformly
from the rows no delete may take (not among `mog_live.heads`, which
`vec_delete` deletes by their loaded values) gets a new vector from a
drawn centre (its content changed, its embedding with it), one
transaction committed at once. The client's next read queries the new
vector (`chipbench/queries/vec_writes.py`)."""

from __future__ import annotations

from chipbench.data import mog, mog_live
from chipbench.queries import vec_writes as w
from chipbench.queries.vec_writes import check, control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (uid, vector)."""
    row = int(rng.integers(0, catalog["vectors"]))
    while row % mog.CHUNK < mog_live.HEAD:  # a row a delete may take
        row = int(rng.integers(0, catalog["vectors"]))
    uid = mog.UID_BASE + row
    vec = w.drawn(catalog, rng)
    w.pend(catalog, rng, uid, vec, deleted=False)
    return (uid, vec), {"set": mog_live.literal(uid, vec)}


apply = w.written
