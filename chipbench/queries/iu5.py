"""Query kind `iu5`, which WRITES: LDBC SNB Interactive update 5, add a
forum membership: a loaded forum takes a loaded person as a member
(`hasMember`, with the joinDate as a facet; the maker declares the
predicate)."""

from __future__ import annotations

from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (forum's index, person, date)."""
    info = w.loaded(catalog)
    f = int(rng.integers(info["forums"]))
    p = int(rng.integers(info["persons"]))
    at = w.date(catalog, params, client, seq)
    w.pend(catalog, rng)
    return (f, p, at), {"set": f"{w.forum(catalog, f)} <hasMember> "
                                f"{w.person(p)} {w.facet('joinDate', at)} ."}


def apply(model, params: dict, key, answer: dict) -> None:
    model.messages().members.append(key)


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, None)
