"""Query kind `iu8`, which WRITES: LDBC SNB Interactive update 8, add a
friendship: two loaded persons who are not loaded friends `knows` each
other, both ways, with the friendship's creationDate as a facet. The
client's next IS3 reads one of them (`chipbench/queries/snb_writes.py`)."""

from __future__ import annotations

from chipbench.queries import snb_reads as reads
from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (a, b, date), a < b."""
    info = w.loaded(catalog)
    n = info["persons"]
    while True:
        a, b = int(rng.integers(n)), int(rng.integers(n))
        if a != b and min(a, b) * n + max(a, b) not in info["pairs"]:
            break
    at = w.date(catalog, params, client, seq)
    fd = w.facet("creationDate", at)
    w.pend(catalog, rng, is3=a)
    return (min(a, b), max(a, b), at), {"set": (
        f"{w.person(a)} <knows> {w.person(b)} {fd} .\n"
        f"{w.person(b)} <knows> {w.person(a)} {fd} .")}


def apply(model, params: dict, key, answer: dict) -> None:
    model.add_knows(*key)
    reads.friendships.cache_clear()  # `is3`'s view of the pairs


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, None)
