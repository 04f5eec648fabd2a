"""Query kind `vec_delete`, which WRITES: the delete of a loaded,
still-live uid's vector, one transaction committed at once. A client
deletes only its own share of the rows `chipbench/data/mog_live.py`
`heads` gives (the first rows of each chunk of the corpus, whose values
the catalog holds, and which no re-embed takes), each once; the delete
names the value, for the loaded rows are in the index and not in the
store (the configuration's `assumed`). The client's next read queries
the deleted vector (`chipbench/queries/vec_writes.py`)."""

from __future__ import annotations

from chipbench.data import mog, mog_live
from chipbench.queries import vec_writes as w
from chipbench.queries.vec_writes import check, control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (uid,)."""
    mine = catalog.setdefault("deletes.left", {}).get(client)
    if mine is None:
        rows = catalog["head_rows"]
        mine = catalog["deletes.left"][client] = list(
            range(client, len(rows), params["clients"]))
    i = mine.pop(int(rng.integers(0, len(mine))))
    uid = mog.UID_BASE + int(catalog["head_rows"][i])
    vec = catalog["head_vecs"][i]
    w.pend(catalog, rng, uid, vec, deleted=True)
    return (uid,), {"set": "", "delete": mog_live.literal(uid, vec)}


def apply(model, params: dict, key, answer) -> None:
    model.kill(key[0])
