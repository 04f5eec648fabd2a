"""What the vector writes (kinds `vec_insert`, `vec_reembed`,
`vec_delete`, which WRITE) and the read that follows a write
(`similar_to_live`) share. The configuration's `assumed` states each
choice.

A write is ONE transaction committed at once (`chipbench/client.py`).
An inserted uid is `NEW_BASE + seq * clients + client` (`clients`: the
mix's, in each kind's params; `seq`: the draw's), so it is unique in a
run and a function of the seed. A new vector is drawn from the mixture
as a read's query is: a drawn centre plus unit noise.

The session rule: after a write, the same client's next read queries
the vector it wrote, or for a delete the vector it deleted, plus noise
of `ANCHOR_NOISE` a coordinate; a later write replaces what is pending.
The state is kept on the catalog, the load generator's one object that
every draw is handed, under the client's draw stream (`rng`: one per
client and phase, drawn from in the order the client sends), as
`chipbench/queries/snb_writes.py` keeps its own.

Nothing of the program is imported here."""

from __future__ import annotations

import numpy as np

from chipbench.data import mog_live

ANCHOR_NOISE = 0.05


def pend(catalog: dict, rng, uid: int, vec: np.ndarray,
         deleted: bool) -> None:
    """What this stream's next read queries: (the anchor's uid, its
    vector, whether the write deleted it). The stream is held, so its
    id names no other stream while anything is pending for it."""
    catalog.setdefault("writes.pending", {})[id(rng)] = (
        rng, (uid, vec, deleted))


def take(catalog: dict, rng):
    got = catalog.get("writes.pending", {}).pop(id(rng), None)
    return got[1] if got is not None and got[0] is rng else None


def new_uid(params: dict, client: int, seq: int) -> int:
    return mog_live.NEW_BASE + seq * params["clients"] + client


def drawn(catalog: dict, rng) -> np.ndarray:
    """A new vector: a drawn centre plus unit noise."""
    centers = catalog["centers"]
    c = int(rng.integers(0, len(centers)))
    return (centers[c] + rng.standard_normal(centers.shape[1])).astype(
        np.float32)


def written(model, params: dict, key, answer) -> None:
    """`apply` of a set: the uid takes the vector."""
    uid, vec = key
    model.put(uid, vec)


def parse(data: dict) -> dict:
    """The server's uids of the write's blank nodes (none here)."""
    return data["uids"]


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    """A write is judged by the reads that see it."""
    return {}


def control(model, params: dict, keys: list):
    """A write has no stale form: the reads after it are held one
    acknowledged write behind (`chipbench/history.py`)."""
    return [None] * len(keys), None
