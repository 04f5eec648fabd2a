"""Query kind `iu4`, which WRITES: LDBC SNB Interactive update 4, add a
forum, with its title, creationDate and moderator (a loaded person;
LDBC's tags are left out: the data has none)."""

from __future__ import annotations

from chipbench.data import snb
from chipbench.queries import snb_writes as w
from chipbench.queries.snb_writes import control, parse  # noqa: F401

WRITES = True


def request(catalog: dict, params: dict, rng, client: int, seq: int):
    """(key, write): the key is (the forum's index, its moderator, its
    title, date)."""
    info = w.loaded(catalog)
    f = info["forums"] + w.slot(params, client, seq)
    mod = int(rng.integers(info["persons"]))
    title = f"Group {f} of person_{snb.person_sid(mod)}"
    at = w.date(catalog, params, client, seq)
    lines = [f'_:f <fqid> "forum_{snb.forum_sid(f)}" .',
             f'_:f <id> "{snb.forum_sid(f)}"^^<xs:int> .',
             f'_:f <title> "{title}" .',
             f"_:f <creationDate> {w.datetime(at)} .",
             '_:f <dgraph.type> "forum" .',
             f"_:f <hasModerator> {w.person(mod)} ."]
    w.pend(catalog, rng)
    return (f, mod, title, at), {"set": "\n".join(lines)}


def apply(model, params: dict, key, answer: dict) -> None:
    f, mod, title, at = key
    model.messages().new_forums[f] = {
        "moderator": mod, "title": title, "ms": at,
        "uid": int(answer["f"], 16)}


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    return w.named(answers, "f")
