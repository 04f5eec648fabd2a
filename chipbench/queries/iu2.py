"""Query kind `iu2`, which WRITES: LDBC SNB Interactive update 2, add a
like to a post: a loaded person likes a loaded post (`likes`, with its
creationDate as a facet)."""

from __future__ import annotations

from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (person, post's index, date)."""
    info = w.loaded(catalog)
    p = int(rng.integers(info["persons"]))
    j = int(rng.integers(info["posts"]))
    at = w.date(catalog, params, client, seq)
    w.pend(catalog, rng)
    return (p, j, at), {"set": f"{w.person(p)} <likes> "
                                f"{w.message(catalog, j)} "
                                f"{w.facet('creationDate', at)} ."}


def apply(model, params: dict, key, answer: dict) -> None:
    model.messages().likes.append(key)


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, None)
