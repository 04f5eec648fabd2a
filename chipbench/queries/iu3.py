"""Query kind `iu3`, which WRITES: LDBC SNB Interactive update 3, add a
like to a comment: a loaded person likes a loaded comment (`likes`,
with its creationDate as a facet)."""

from __future__ import annotations

from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (person, comment's index, date)."""
    info = w.loaded(catalog)
    p = int(rng.integers(info["persons"]))
    i = info["posts"] + int(rng.integers(info["messages"] - info["posts"]))
    at = w.date(catalog, params, client, seq)
    w.pend(catalog, rng)
    return (p, i, at), {"set": f"{w.person(p)} <likes> "
                                f"{w.message(catalog, i)} "
                                f"{w.facet('creationDate', at)} ."}


def apply(model, params: dict, key, answer: dict) -> None:
    model.messages().likes.append(key)


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, None)
